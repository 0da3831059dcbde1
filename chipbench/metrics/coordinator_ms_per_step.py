"""coordinator (core/coordinator.py): host time of the coordinator's
snapshot -> command decisions (``timers["coordinator"]``) per RL step."""
UNIT = "ms"


def read(ctx):
    if ctx.steps <= 0:
        return None
    return 1000.0 * ctx.delta("timer_coordinator") / ctx.steps
