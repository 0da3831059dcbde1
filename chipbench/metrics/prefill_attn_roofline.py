"""kernels (kernels/flash_attention.py; paged_prefill_attention in
kernels/paged_attention.py): the least time the traced steps' prefill
attention needs for its valid tokens (``work.prefill_attention``, per
layer) over the summed device time of the prefill attention kernels."""
from chipbench import work
from chipbench.trace import op_kind

UNIT = "%"
KERNELS = ("flash_attention", "paged_prefill")


def read(ctx):
    if ctx.trace is None or not (ctx.work.prefill or ctx.work.suffix):
        return None
    seconds = ctx.trace.op_seconds(
        lambda n: any(k in op_kind(n) for k in KERNELS))
    if seconds <= 0:
        return None
    s = work.Shape.from_config(ctx.conf)
    peak = work.peak(ctx.device_kind)
    least = 0.0
    for call in ctx.work.prefill:
        least += s.n_layers * peak.least_s(*work.prefill_attention(
            s, [(n, n) for n in call]))
    for call in ctx.work.suffix:
        least += s.n_layers * peak.least_s(*work.prefill_attention(s, call))
    return 100.0 * least / seconds
