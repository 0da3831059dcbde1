"""rollout engine (rollout/engine.py): decoded tokens per decode dispatch
over the slots an instance holds, summed over instances."""
UNIT = "%"


def read(ctx):
    steps = ctx.delta("decode_steps")
    if steps <= 0:
        return None
    per_inst = ctx.c1["slots"] / max(1, ctx.mix.n_instances)
    return 100.0 * ctx.delta("decode_tokens") / (steps * per_inst)
