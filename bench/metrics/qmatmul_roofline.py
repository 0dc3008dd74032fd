"""Roofline share of the fused DFXP quantized matmul kernels
(``repro.kernels.qmatmul``): the work of every weighted sum of the
traced steps (forward, data and weight gradient; operations against the
bf16 peak, 10-bit operands at 2 bytes and f32 results against HBM
bandwidth), the larger of the two times, over the kernels' device time.
The work is counted from the model, so the share reads the same
whatever implements it."""
from bench.readers import share, span

KERNEL = r"qmm_2d"


def read(ctx, trace):
    ops, byts = ctx["qmatmul_step"]
    p = ctx["peaks"]
    a, b = span(ctx)
    steps = sum(1 for t in ctx["step_ends"] if a < t <= b)
    least = steps * max(ops / p["bf16_flops"], byts / p["hbm_bytes_per_s"])
    return share(least, trace.op_seconds(KERNEL))
