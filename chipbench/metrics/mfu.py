"""whole step: the model operations the job needs in the window (each
group's prompt once, each decoded token once, the trainer's forward and
backward over the consumed tokens; see ``work.job_flops``) over the
window times the chip's bf16 peak. The program computes in float32 at the
chip's default matmul precision, which runs as bf16 passes, so the bf16
peak is the bound."""
from chipbench import work

UNIT = "%"


def read(ctx):
    if not ctx.groups or ctx.window_s <= 0:
        return None
    s = work.Shape.from_config(ctx.conf)
    flops = sum(work.job_flops(s, p, rs) for p, rs in ctx.groups)
    peak = work.peak(ctx.device_kind)
    return 100.0 * flops / (ctx.window_s * peak.flops_per_s)
