"""kernels (kernels/paged_attention.py): the least time the paged decode
attention needs for the traced steps' live K/V rows (``work.paged_decode``,
per layer) over the summed device time of the kernel's calls."""
from chipbench import work
from chipbench.trace import op_kind

UNIT = "%"
# the kernel's operation in the device trace
KERNEL = "paged_decode"


def read(ctx):
    if ctx.trace is None or not ctx.work.decode:
        return None
    seconds = ctx.trace.op_seconds(lambda n: KERNEL in op_kind(n))
    if seconds <= 0:
        return None
    s = work.Shape.from_config(ctx.conf)
    peak = work.peak(ctx.device_kind)
    least = 0.0
    for lengths in ctx.work.decode:
        f, b = work.paged_decode(s, lengths)
        least += s.n_layers * peak.least_s(f, b)
    return 100.0 * least / seconds
