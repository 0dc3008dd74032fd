"""Mixture-of-Experts FFN with expert-parallel shard_map dispatch.

Routing is capacity-based (Switch/GShard style): each token's top-k experts
get it unless the expert's local capacity ``C = ceil(T·k/E · cf)`` is
exhausted. Dispatch/combine are scatter/gather (cheap) rather than one-hot
einsums (dense FLOPs).

Under a mesh, the block is a ``shard_map`` island inside the jit program:
tokens stay sharded over the data axes, experts are sharded over ``ep_axis``
(the model axis), and two ``all_to_all``s move token slots to expert owners
and back — the standard EP pattern, visible as such in the dry-run HLO.
Expert weights are additionally FSDP-sharded over ``fsdp_axis`` and
``all_gather``-ed per layer (needed to fit 400B-class models).

DFXP: dispatched activations, expert hidden, and expert outputs are
quantization sites; router logits/softmax stay wide (documented deviation —
routing decisions are precision-sensitive and the paper predates MoE).
"""
from __future__ import annotations

import dataclasses
import math
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.policy import MATMUL_PRECISION
from repro.core.tape import QTape
from repro.dist.context import DistCtx

from .layers import init_dense, init_swiglu, swiglu

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class MoESpec:
    d_model: int
    d_ff: int                      # per-expert hidden dim
    num_experts: int
    top_k: int
    capacity_factor: float = 1.25
    shared_expert_d_ff: int = 0    # 0 = no shared expert (llama4 uses one)
    renormalize: bool = True


def init_moe(key, spec: MoESpec) -> dict:
    ks = jax.random.split(key, 5)
    E, D, F = spec.num_experts, spec.d_model, spec.d_ff
    p = {
        "router": init_dense(ks[0], D, E, scale=0.02),
        "w_gate": jax.random.normal(ks[1], (E, D, F)) / math.sqrt(D),
        "w_up": jax.random.normal(ks[2], (E, D, F)) / math.sqrt(D),
        "w_down": jax.random.normal(ks[3], (E, F, D)) / math.sqrt(F),
    }
    if spec.shared_expert_d_ff:
        p["shared"] = init_swiglu(ks[4], D, spec.shared_expert_d_ff)
    return p


def _capacity(t_local: int, spec: MoESpec, dropless: bool = False) -> int:
    if dropless:
        # decode batches are tiny: full capacity costs nothing and keeps
        # decode bit-exact w.r.t. the full forward (no token dropping)
        return t_local
    return max(1, math.ceil(t_local * spec.top_k / spec.num_experts
                            * spec.capacity_factor))


def _moe_local(x, router_w, w_gate, w_up, w_down, scales, sinks,
               *, spec: MoESpec, policy, dist: DistCtx, prefix: str,
               t_local: int, dropless: bool = False):
    """Per-device MoE math. ``x``: [T_local, D] local tokens."""
    tape = QTape(policy, scales, sinks)
    E, k = spec.num_experts, spec.top_k
    C = _capacity(t_local, spec, dropless)
    T = x.shape[0]

    # --- routing (wide precision: documented deviation) -------------------
    logits = jnp.einsum("td,de->te", x, router_w,
                        precision=MATMUL_PRECISION,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)                    # [T, k]
    if spec.renormalize:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    eid = ids.reshape(-1)                                   # [T*k]
    gate = gates.reshape(-1)
    tok = jnp.repeat(jnp.arange(T), k)

    onehot = jax.nn.one_hot(eid, E, dtype=jnp.int32)        # [T*k, E]
    pos = jnp.take_along_axis(jnp.cumsum(onehot, 0) - 1,
                              eid[:, None], axis=1)[:, 0]   # rank within expert
    keep = pos < C
    pos_c = jnp.minimum(pos, C - 1)

    # --- dispatch: scatter token slots to [E, C, D] ------------------------
    contrib = jnp.where(keep[:, None], x[tok], 0.0)
    xe = jnp.zeros((E, C, x.shape[1]), x.dtype).at[eid, pos_c].add(contrib)
    xe = tape.act(f"{prefix}/dispatch", xe)

    a2a_bits = getattr(policy, "a2a_compress_bits", 0)
    if dist.ep_axis:
        if a2a_bits:
            from repro.dist.compress import compressed_all_to_all
            e_disp = tape._exp(f"a:{prefix}/dispatch")
            xe = compressed_all_to_all(xe, e_disp, a2a_bits, dist.ep_axis,
                                       split_axis=0, concat_axis=1)
        else:
            xe = jax.lax.all_to_all(xe, dist.ep_axis, split_axis=0,
                                    concat_axis=1, tiled=True)  # [E/ep, C*ep, D]

    # --- expert compute ------------------------------------------------------
    stationary = dist.moe_stationary and dist.fsdp_axis and dropless
    if dist.fsdp_axis and not stationary:
        # training: gather FSDP-sharded weights per layer (tokens are huge,
        # weights amortize). [E/ep, D/fsdp, F] → [E/ep, D, F]; w_down is
        # [E/ep, F, D/fsdp].
        w_gate = jax.lax.all_gather(w_gate, dist.fsdp_axis, axis=1, tiled=True)
        w_up = jax.lax.all_gather(w_up, dist.fsdp_axis, axis=1, tiled=True)
        w_down = jax.lax.all_gather(w_down, dist.fsdp_axis, axis=2, tiled=True)
    w_gate = tape.weight(f"{prefix}/w_gate", w_gate)
    w_up = tape.weight(f"{prefix}/w_up", w_up)
    w_down = tape.weight(f"{prefix}/w_down", w_down)

    if stationary:
        # decode: weights stay put, activations move (the classic inference
        # trick — a 400B expert bank must not cross ICI per token). Each
        # fsdp rank holds a D-slice: partial matmuls + psum(h), then the
        # D-sharded down-proj output is all-gathered (activation-sized).
        didx = jax.lax.axis_index(dist.fsdp_axis)
        Dl = w_gate.shape[1]
        xe_l = jax.lax.dynamic_slice_in_dim(xe, didx * Dl, Dl, axis=2)
        g = jnp.einsum("ecd,edf->ecf", xe_l, w_gate,
                       precision=MATMUL_PRECISION,
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("ecd,edf->ecf", xe_l, w_up,
                       precision=MATMUL_PRECISION,
                       preferred_element_type=jnp.float32)
        g = jax.lax.psum(g, dist.fsdp_axis)
        u = jax.lax.psum(u, dist.fsdp_axis)
        h = tape.act(f"{prefix}/pre",
                     (jax.nn.silu(g) * u).astype(x.dtype))
        ye = jnp.einsum("ecf,efd->ecd", h, w_down,
                        precision=MATMUL_PRECISION,
                        preferred_element_type=jnp.float32).astype(x.dtype)
        ye = jax.lax.all_gather(ye, dist.fsdp_axis, axis=2, tiled=True)
    else:
        g = jnp.einsum("ecd,edf->ecf", xe, w_gate,
                       precision=MATMUL_PRECISION,
                       preferred_element_type=jnp.float32).astype(x.dtype)
        u = jnp.einsum("ecd,edf->ecf", xe, w_up,
                       precision=MATMUL_PRECISION,
                       preferred_element_type=jnp.float32).astype(x.dtype)
        h = tape.act(f"{prefix}/pre", jax.nn.silu(g) * u)
        ye = jnp.einsum("ecf,efd->ecd", h, w_down,
                        precision=MATMUL_PRECISION,
                        preferred_element_type=jnp.float32).astype(x.dtype)

    if dist.ep_axis:
        if a2a_bits:
            from repro.dist.compress import compressed_all_to_all
            e_out = tape._exp(f"a:{prefix}/expert_out")
            ye = compressed_all_to_all(ye, e_out, a2a_bits, dist.ep_axis,
                                       split_axis=1, concat_axis=0)
        else:
            ye = jax.lax.all_to_all(ye, dist.ep_axis, split_axis=1,
                                    concat_axis=0, tiled=True)  # [E, C, D]
    ye = tape.act(f"{prefix}/expert_out", ye)

    # --- combine -----------------------------------------------------------
    picked = ye[eid, pos_c] * (gate * keep)[:, None].astype(ye.dtype)
    y = jnp.zeros_like(x).at[tok].add(picked)

    stats = tape.stats
    if dist.active:
        stats = {n: jax.lax.psum(s, dist.all_axes) for n, s in stats.items()}
    return y, stats


def moe_ffn(params, spec: MoESpec, x: Array, tape: QTape, prefix: str,
            dist: DistCtx = DistCtx(), dropless: bool = False) -> Array:
    """MoE block. ``x``: [B, S, D]. Merges local stats into ``tape``."""
    B, S, D = x.shape
    x_flat = x.reshape(B * S, D)
    n_tok_shards = 1
    scales, sinks = tape.scales, tape.sinks

    if dist.active:
        import numpy as np
        mesh = jax.sharding.get_abstract_mesh()
        n_tok_shards = int(np.prod([mesh.shape[a] for a in dist.token_axes]))
        t_local = (B * S) // n_tok_shards
        fn = jax.shard_map(
            lambda xf, rw, wg, wu, wd, sc, sk: _moe_local(
                xf, rw, wg, wu, wd, sc, sk, spec=spec, policy=tape.policy,
                dist=dist, prefix=prefix, t_local=t_local,
                dropless=dropless),
            in_specs=(P(dist.token_axes, None), P(), P(dist.ep_axis, dist.fsdp_axis, None),
                      P(dist.ep_axis, dist.fsdp_axis, None),
                      P(dist.ep_axis, None, dist.fsdp_axis), P(), P()),
            out_specs=(P(dist.token_axes, None), P()),
            check_vma=False,
        )
        y, stats = fn(x_flat, params["router"], params["w_gate"],
                      params["w_up"], params["w_down"], scales, sinks)
    else:
        y, stats = _moe_local(
            x_flat, params["router"], params["w_gate"], params["w_up"],
            params["w_down"], scales, sinks, spec=spec, policy=tape.policy,
            dist=dist, prefix=prefix, t_local=B * S, dropless=dropless)

    for n, s in stats.items():
        tape._record(n, s)

    y = y.reshape(B, S, D)
    if spec.shared_expert_d_ff:
        y = y + swiglu(params["shared"], x, tape, f"{prefix}/shared")
    return tape.act(f"{prefix}/out", y)
