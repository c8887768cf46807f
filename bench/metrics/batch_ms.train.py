"""Host milliseconds per training step that the pipeline's main thread
spends taking the next batch from its source (the ``batch.next`` span,
read from the profiler's trace of the main thread)."""

SPAN = "batch.next"
STEP = "jit_train_jit"


def read(ctx):
    steps = ctx["reduced"].module_calls.get(STEP, 0)
    seconds = ctx["host_phase_s"].get(SPAN)
    if not steps or seconds is None:
        return None
    return 1e3 * seconds / steps
