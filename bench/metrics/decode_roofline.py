"""The decode program's share of its roofline: the least time the chip
needs for the work of the active rows (counted from the configuration's
shapes by the ``bench/work/<work>.py`` it names; frozen projections at
4 bits) over the decode program's device time.  ``ctx.decode_bound``
says whether memory or compute bounds it."""
LAYER = "decode kernels"
UNIT = "%"
MOVES = "itl_p95_ms"
PROGRAM = "jit__decode_impl"


def read(ctx):
    n = ctx.trace.program_runs(PROGRAM)
    seconds = ctx.trace.program_seconds(PROGRAM)
    if n == 0 or seconds <= 0 or ctx.decode_step_work is None:
        return None
    least, _ = ctx.decode_step_work.least_s(ctx.peaks)
    return 100.0 * n * least / seconds
