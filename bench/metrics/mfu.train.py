"""The whole training step's share of the chip's peak: the least time
its required work can take (MLP and interaction FLOPs at the bf16 peak,
or the touched rows and dense parameters read and written at the HBM
peak, whichever bounds it) over the traced time per step."""

from bench import work

STEP = "jit_train_jit"


def read(ctx):
    red = ctx["reduced"]
    steps = red.module_calls.get(STEP, 0)
    if not steps or not ctx["distinct_per_step"]:
        return None
    need = work.train_step(ctx["cfg"], ctx["rows_per_step"],
                           ctx["distinct_per_step"])
    least, _ = work.least_seconds(need, ctx["peaks"])
    return 100.0 * steps * least / red.window_s
