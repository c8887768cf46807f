"""The prefetch pull kernel's share of its roofline: the rows it pulled,
each read from the table and written into its slot once, at the HBM
peak, over the kernel's device time."""

from bench import work

KERNEL = "staged_gather"


def read(ctx):
    red = ctx["reduced"]
    spent = red.kernel_seconds(KERNEL)
    if not spent or not ctx["rows_pulled"]:
        return None
    need = work.staged_gather(ctx["rows_pulled"], ctx["cfg"]["embedding_dim"])
    least, _ = work.least_seconds(need, ctx["peaks"])
    return 100.0 * least / spent
