"""runners (rollout/runners.py): device time of the decode program and its
sampler (the ``rollout_decode`` and ``rollout_sample`` modules) per run of
the decode program, so per decode dispatch. Set beside ``rollout_step_ms``
it says how much of a dispatch's host time the device works."""
UNIT = "ms"
DECODE = "jit_rollout_decode"
SAMPLE = "jit_rollout_sample"


def _program(name):
    # a module event is named "<program>(<id>)"
    return name.split("(", 1)[0]


def read(ctx):
    if ctx.trace is None:
        return None
    runs, decode_s = ctx.trace.module_runs(lambda n: _program(n) == DECODE)
    if runs == 0:
        return None
    _, sample_s = ctx.trace.module_runs(lambda n: _program(n) == SAMPLE)
    return 1000.0 * (decode_s + sample_s) / runs
