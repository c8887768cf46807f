"""Device milliseconds per training step of the embedding bags: the
exclusive device time of the operations under the ``dlrm.bag_pool``
scope, the table gather and the per-field sum, forward and backward."""

SCOPE = "dlrm.bag_pool"
STEP = "jit_train_jit"


def read(ctx):
    steps = ctx["reduced"].module_calls.get(STEP, 0)
    seconds = ctx["scope_s"].get(SCOPE)
    if not steps or seconds is None:
        return None
    return 1e3 * seconds / steps
