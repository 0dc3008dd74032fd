"""Model FLOP/s utilisation of the decode step: 2 x active parameters
per live row plus the attention over its cached positions, for every
token decoded in the window, over the decode program's device time and
the chip's bf16 peak."""
from bench import flops
from bench.readers import in_window, share

PROGRAM = r"jit__decode_impl"


def read(ctx, trace):
    toks = in_window(ctx, ctx["decode_tokens"])
    if not toks:
        return None
    work = flops.moe_decode_flops(ctx["config"]["model"], len(toks),
                                  sum(n for _, _, n in toks))
    return share(work / ctx["peaks"]["bf16_flops"],
                 trace.program_seconds(PROGRAM))
