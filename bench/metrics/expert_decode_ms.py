"""Device time of the routed-expert LUT kernel (``lut_gemm_dc_res_experts``,
one call per frozen expert matrix per MoE layer) per execution of the
decode program."""
LAYER = "expert kernels"
UNIT = "ms"
MOVES = "itl_p95_ms"
PROGRAM = "jit__decode_impl"
KERNEL = "lut_gemm_dc_res_experts"


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
