"""Device milliseconds per training step of the cache-state update inside
the advance stage: the exclusive device time of the operations under the
``esd.cache_update`` scope."""

SCOPE = "esd.cache_update"
STEP = "jit_train_jit"


def read(ctx):
    steps = ctx["reduced"].module_calls.get(STEP, 0)
    seconds = ctx["scope_s"].get(SCOPE)
    if not steps or seconds is None:
        return None
    return 1e3 * seconds / steps
