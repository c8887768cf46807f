"""Share of the traced window in which no operation ran on the device,
averaged over the chips: the training driver and pipeline's gaps."""


def read(ctx):
    return 100.0 * ctx["reduced"].idle_share()
