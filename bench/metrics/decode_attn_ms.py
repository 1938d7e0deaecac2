"""Device time of the paged decode attention kernel
(``paged_decode_attention``, one call per attention application of a
decode step) per execution of the decode program."""
LAYER = "decode kernels"
UNIT = "ms"
MOVES = "itl_p95_ms"
PROGRAM = "jit__decode_impl"
KERNEL = "paged_decode_attention"


def kernel_seconds(trace) -> float:
    """Device seconds of the kernel's operations inside the decode
    program."""
    total = 0.0
    for key, seconds in trace.ops.items():
        prog, _, op = key.partition("/")
        if prog.startswith(PROGRAM) and KERNEL in op:
            total += seconds
    return total


def read(ctx):
    n = ctx.trace.program_runs(PROGRAM)
    seconds = kernel_seconds(ctx.trace)
    if n == 0 or seconds <= 0:
        return None
    return seconds / n * 1e3
