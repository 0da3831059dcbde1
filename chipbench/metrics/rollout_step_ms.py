"""runners (rollout/runners.py): host wall time of the engines' step calls
(``timers["decode"]``: admission, prefill and one decode dispatch, ending
in a host read of the sampled tokens) per decode dispatch."""
UNIT = "ms"


def read(ctx):
    steps = ctx.delta("decode_steps")
    if steps <= 0:
        return None
    return 1000.0 * ctx.delta("timer_decode") / steps
