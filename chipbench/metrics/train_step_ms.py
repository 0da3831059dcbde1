"""trainer (training/train_step.py): device time of the train program per
train step in the traced window."""
UNIT = "ms"
MODULE = "jit_train_step"


def read(ctx):
    if ctx.trace is None:
        return None
    runs, seconds = ctx.trace.module_runs(lambda n: n.startswith(MODULE))
    if runs == 0:
        return None
    return 1000.0 * seconds / runs
