"""The whole decode step's share of the chip's peak bf16 FLOP/s: model
FLOPs of the active rows over the decode program's device time."""
LAYER = "model step"
UNIT = "%"
MOVES = "itl_p95_ms"
PROGRAM = "jit__decode_impl"


def read(ctx):
    n = ctx.trace.program_runs(PROGRAM)
    seconds = ctx.trace.program_seconds(PROGRAM)
    if n == 0 or seconds <= 0 or ctx.decode_step_work is None:
        return None
    return (100.0 * n * ctx.decode_step_work.flops
            / (seconds * ctx.peaks.flops_bf16))
