"""Model FLOP/s utilisation of the training step: model FLOPs per token
(forward and backward, no recomputation) times the tokens of the steps
that ended inside the traced window, over that window and the chip's
bf16 peak."""
from bench.readers import span


def read(ctx, trace):
    a, b = span(ctx)
    steps = sum(1 for t in ctx["step_ends"] if a < t <= b)
    if not steps:
        return None
    return 100.0 * ctx["flops_per_token"] * steps * ctx["tokens_per_step"] \
        / (b - a) / ctx["peaks"]["bf16_flops"]
