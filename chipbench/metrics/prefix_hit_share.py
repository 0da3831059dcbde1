"""prefix cache (rollout/prefix_cache.py): prompt tokens not prefilled
again, thanks to a shared prefix, over all prompt tokens admitted."""
UNIT = "%"


def read(ctx):
    saved = ctx.delta("prefill_tokens_saved")
    total = ctx.delta("prefill_tokens") + saved
    if total <= 0:
        return None
    return 100.0 * saved / total
