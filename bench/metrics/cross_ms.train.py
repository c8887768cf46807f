"""Device milliseconds per training step of the cross network: the
exclusive device time of the operations under the ``dlrm.cross`` scope,
forward and backward, the weight-gradient products among them (XLA
fuses them with the optimizer's squares and keeps the product's name).
XLA prefetches the cross's weights into on-chip memory with
asynchronous copies that carry no scope and overlap other operations;
their time is not in this number."""

SCOPE = "dlrm.cross"
STEP = "jit_train_jit"


def read(ctx):
    steps = ctx["reduced"].module_calls.get(STEP, 0)
    seconds = ctx["scope_s"].get(SCOPE)
    if not steps or seconds is None:
        return None
    return 1e3 * seconds / steps
