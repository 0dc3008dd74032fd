"""Roofline share of the fused flash-decode kernel
(``repro.kernels.attn``): the int8 K/V of the positions present in the
live rows (every layer, K and V, all kv heads) plus each row's f32 query
and output, over HBM bandwidth, against the kernel's device time.  The
kernel is bound by bytes: 4 operations per int8 K/V byte read is far
below the chip's ratio of peak operations to bandwidth."""
from bench import flops
from bench.readers import in_window, share

KERNEL = r"flash_decode_call"


def read(ctx, trace):
    toks = in_window(ctx, ctx["decode_tokens"])
    if not toks:
        return None
    byts = flops.flash_decode_bytes(ctx["config"]["model"], ctx["kv_bits"],
                                    len(toks), sum(n for _, _, n in toks))
    return share(byts / ctx["peaks"]["hbm_bytes_per_s"],
                 trace.op_seconds(KERNEL))
