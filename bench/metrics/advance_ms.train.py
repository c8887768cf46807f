"""Device milliseconds per training step of the advance stage: the
sample exchange (an all-to-all on several chips) and the cache-state
update."""

MODULES = ("jit_advance",)
STEP = "jit_train_jit"


def read(ctx):
    red = ctx["reduced"]
    steps = red.module_calls.get(STEP, 0)
    if not steps or not any(m in red.module_s for m in MODULES):
        return None
    return 1e3 * red.seconds(MODULES) / steps
