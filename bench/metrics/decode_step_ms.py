"""Mean device time of one execution of the decode program."""
LAYER = "model step"
UNIT = "ms"
MOVES = "itl_p95_ms"
PROGRAM = "jit__decode_impl"


def read(ctx):
    n = ctx.trace.program_runs(PROGRAM)
    if n == 0:
        return None
    return ctx.trace.program_seconds(PROGRAM) / n * 1e3
