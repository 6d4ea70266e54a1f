"""Device time of the jitted train step (module ``jit_train_step``) per
step in the traced window, from the profiler trace."""
from bench import tracing


def read(run):
    if run.trace is None:
        return None
    count, seconds = tracing.module_time(run.trace, "jit_train_step")
    return seconds / count * 1e3 if count else None
