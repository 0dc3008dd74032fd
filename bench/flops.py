"""Operations and bytes of the benchmarked work, computed from the model
shapes in a configuration file (``bench/configs/<name>.json``).

Counts are what the algorithm needs, not what an implementation happens
to execute: model FLOPs count each weighted sum once per pass (forward,
and for training the two backward products), never recomputation,
padding or the empty expert slots of a dropless dispatch.  A kernel's
roofline share divides these counts by the kernel's device time, so a
share reads the same whatever implements the work.
"""
from __future__ import annotations


def _ssm_dims(m: dict):
    d_inner = m["expand"] * m["d_model"]
    heads = d_inner // m["headdim"]
    return d_inner, heads


def mamba2_dot_shapes(m: dict):
    """(K, N, count) of each weighted sum one token passes through:
    ``in_proj`` and ``out_proj`` per layer, then the tied vocabulary head."""
    d, n = m["d_model"], m["d_state"]
    d_inner, heads = _ssm_dims(m)
    in_dim = 2 * d_inner + 2 * m["ngroups"] * n + heads
    return [(d, in_dim, m["n_layer"]), (d_inner, d, m["n_layer"]),
            (d, m["vocab_size"], 1)]


def mamba2_ssd_flops_per_token(m: dict) -> float:
    """Forward SSD operations per token per layer, within-chunk causal
    average: C·Bᵀ (Q/2·N multiply-adds), the masked mix with X
    (Q/2·H·P), the chunk state and its read-out (H·P·N each)."""
    _, heads = _ssm_dims(m)
    q, n, p = m["chunk_size"], m["d_state"], m["headdim"]
    return 2.0 * (q / 2 * n + q / 2 * heads * p + 2 * heads * p * n)


def mamba2_train_flops_per_token(m: dict) -> float:
    """Forward + backward model FLOPs per trained token (3 × forward)."""
    dots = sum(2.0 * k * n * c for k, n, c in mamba2_dot_shapes(m))
    return 3.0 * (dots + m["n_layer"] * mamba2_ssd_flops_per_token(m))


def qmatmul_step_work(m: dict, tokens: int, operand_bytes: int = 2,
                      out_bytes: int = 4):
    """(ops, bytes) of every weighted sum of one training step run as a
    quantized matmul: forward, data gradient and weight gradient, each
    2·M·N·K, reading its two operands at ``operand_bytes`` (10-bit DFXP
    values held in 2 bytes) and writing an f32 result."""
    ops = byts = 0.0
    M = tokens
    for K, N, count in mamba2_dot_shapes(m):
        ops += count * 3 * 2.0 * M * N * K
        fwd = (M * K + K * N) * operand_bytes + M * N * out_bytes
        dgrad = (M * N + K * N) * operand_bytes + M * K * out_bytes
        wgrad = (M * K + M * N) * operand_bytes + K * N * out_bytes
        byts += count * (fwd + dgrad + wgrad)
    return ops, byts


def moe_active_params(m: dict) -> float:
    """Parameters one token multiplies through in granite-style MoE
    decoding: attention projections, the router, ``top_k`` of the
    SwiGLU experts per layer, and the tied head."""
    d, hd = m["hidden_size"], m["head_dim"]
    q = m["num_attention_heads"] * hd
    kv = m["num_key_value_heads"] * hd
    attn = d * q + 2 * d * kv + q * d
    experts = m["num_experts_per_tok"] * 3 * d * m["intermediate_size"]
    router = d * m["num_local_experts"]
    return m["num_hidden_layers"] * (attn + experts + router) \
        + m["vocab_size"] * d


def moe_attention_flops(m: dict, q_tokens: int, kv_positions: float) -> float:
    """Scores and value mix of ``q_tokens`` queries against
    ``kv_positions`` keys each (summed over the queries), all layers."""
    h, hd = m["num_attention_heads"], m["head_dim"]
    return 4.0 * h * hd * kv_positions * m["num_hidden_layers"] \
        if q_tokens else 0.0


def moe_decode_flops(m: dict, rows: int, context_sum: float) -> float:
    """One decode step over ``rows`` live sequences whose cached
    positions sum to ``context_sum``."""
    return 2.0 * moe_active_params(m) * rows \
        + moe_attention_flops(m, rows, context_sum)


def moe_prefill_flops(m: dict, prompt_len: int) -> float:
    """Whole-prompt prefill of one request: every position through the
    active parameters (the head only at the last position, which is all
    prefill computes), causal attention over the prompt."""
    body = moe_active_params(m) - m["vocab_size"] * m["hidden_size"]
    causal = prompt_len * (prompt_len + 1) / 2.0
    return 2.0 * body * prompt_len \
        + 2.0 * m["vocab_size"] * m["hidden_size"] \
        + moe_attention_flops(m, prompt_len, causal)


def kv_bytes_per_token(m: dict, kv_bits: int) -> int:
    """Bytes one cached position holds: K and V of every kv head of
    every layer at ``kv_bits``."""
    return (m["num_hidden_layers"] * 2 * m["num_key_value_heads"]
            * m["head_dim"] * kv_bits // 8)


def flash_decode_bytes(m: dict, kv_bits: int, rows: int,
                       context_sum: float) -> float:
    """What the decode attention must move in one step: the K/V of the
    positions present in the live rows, plus each row's query read and
    output written in f32, every layer."""
    qo = rows * m["num_hidden_layers"] * 2 * m["num_attention_heads"] \
        * m["head_dim"] * 4
    return kv_bytes_per_token(m, kv_bits) * context_sum + qo
