"""Device milliseconds per training step of the decide stage: Alg. 1's
cost matrix and the hybrid assignment, with the staging plane priced in."""

MODULES = ("jit_decide", "jit_with_staged")
STEP = "jit_train_jit"


def read(ctx):
    red = ctx["reduced"]
    steps = red.module_calls.get(STEP, 0)
    if not steps or not any(m in red.module_s for m in MODULES):
        return None
    return 1e3 * red.seconds(MODULES) / steps
