"""The routed-expert LUT kernel's share of its roofline: the least time
the chip needs for the held experts' work in a decode step (the
``"routed_experts"`` part of the configuration's
``bench/work/<work>.py`` count: their weights once at 4 bits, their
FLOPs for the tokens routed to them) times the decode program's runs,
over the device time of the kernel's operations."""
from metrics.expert_decode_ms import PROGRAM, kernel_seconds

LAYER = "expert kernels"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(ctx):
    parts = getattr(ctx.decode_step_work, "parts", None) or {}
    work = parts.get("routed_experts")
    n = ctx.trace.program_runs(PROGRAM)
    seconds = kernel_seconds(ctx.trace)
    if work is None or n == 0 or seconds <= 0:
        return None
    least, _ = work.least_s(ctx.peaks)
    return 100.0 * n * least / seconds
