"""Device milliseconds per training step of the optimizer inside the train
step (row-wise Adagrad over the table and the MLPs): the exclusive device
time of the operations under the ``optim.update`` scope."""

SCOPE = "optim.update"
STEP = "jit_train_jit"


def read(ctx):
    steps = ctx["reduced"].module_calls.get(STEP, 0)
    seconds = ctx["scope_s"].get(SCOPE)
    if not steps or seconds is None:
        return None
    return 1e3 * seconds / steps
