"""Distributed execution: mesh context, sharding rules, low-bit collectives.

The subsystem has four pieces, mirroring the distributed hot paths the
paper's low-precision formats must flow through:

  * :mod:`repro.dist.context`   — ``DistCtx``, the mesh-axis contract every
    model/launch function threads (which axes hold tokens, experts, FSDP
    shards, the context-parallel KV window);
  * :mod:`repro.dist.sharding`  — ``ShardingRules``, logical-name →
    ``PartitionSpec`` resolution for params, optimizer state, batches and
    decode caches;
  * :mod:`repro.dist.compress`  — DFXP gradient/activation compression with
    error feedback for the all-reduce and MoE all-to-all wires;
  * :mod:`repro.dist.cp_attention` — context-parallel GQA decode attention
    (KV window sharded, softmax statistics combined exactly).
"""
from .context import (  # noqa: F401
    DistCtx,
    MeshConfigError,
    multi_pod_ctx,
    serve_pod_ctx,
    single_pod_ctx,
)
from .sharding import ShardingRules  # noqa: F401
