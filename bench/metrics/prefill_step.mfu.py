"""Model FLOP/s utilisation of whole-prompt prefill: 2 x active
parameters per prompt position plus causal attention over the prompt,
for every request prefilled in the window, over the prefill program's
device time and the chip's bf16 peak."""
from bench import flops
from bench.readers import in_window, share

PROGRAM = r"jit__prefill_impl"


def read(ctx, trace):
    pre = in_window(ctx, ctx["prefills"])
    if not pre:
        return None
    work = sum(flops.moe_prefill_flops(ctx["config"]["model"], L)
               for _, L, _ in pre)
    return share(work / ctx["peaks"]["bf16_flops"],
                 trace.program_seconds(PROGRAM))
