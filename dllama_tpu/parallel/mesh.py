"""Device mesh construction for tensor/data parallel inference.

TPU-native replacement for the reference's cluster topology: where the
reference bootstraps a full TCP socket mesh of 2^n root+worker processes
(NnNetwork::connect/serve, src/nn/nn-network.cpp:295-379) and ships op
graphs to workers, here every chip runs the same SPMD program under one
controller and the "topology" is a `jax.sharding.Mesh` whose collectives
ride ICI (multi-host: DCN via `jax.distributed.initialize`, see
`initialize_multihost`).

Axes:
    dp — data parallel over the batch axis (the reference has no DP;
         surfaced here because it is free under SPMD)
    tp — tensor parallel: matmul row/col splits, kv-head-split attention,
         mirroring the reference's slicing (src/nn/nn-core.cpp:211-285)
"""

from __future__ import annotations

import os

import jax
from jax.sharding import Mesh

from ..formats.model_file import LlmHeader


# Where compiled programs are kept when JAX_COMPILATION_CACHE_DIR is unset:
# one fixed path inside the checkout (the path is part of the cache key, so
# a directory that moves never hits).
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compilation_cache() -> str:
    """Persistent XLA compilation cache: decode/prefill programs survive
    process restarts (the reference has no compilation to cache, but its
    'workers receive prebuilt graphs' startup is the analogous
    amortization). Where JAX_COMPILATION_CACHE_DIR is set JAX picks it up
    itself and nothing is set here; otherwise the cache lives at
    DEFAULT_COMPILATION_CACHE_DIR. A directory that cannot be created
    raises. Returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = DEFAULT_COMPILATION_CACHE_DIR
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def validate_tp(h: LlmHeader, tp: int) -> None:
    """Mirror the reference's shardability constraints (src/app.cpp:236-240
    requires nNodes ≤ nKvHeads and 2^n nodes; the dimension divisibility
    asserts live in its slicers, src/nn/nn-core.cpp:211-243)."""
    if tp < 1 or (tp & (tp - 1)) != 0:
        raise ValueError(f"tp must be a power of two, got {tp}")
    if tp > h.n_kv_heads:
        raise ValueError(
            f"tp={tp} exceeds nKvHeads={h.n_kv_heads} (the KV cache shards "
            "by kv head, like the reference's sliceKvCache)"
        )
    for name, dim in [
        ("dim", h.dim),
        ("qDim", h.q_dim),
        ("kvDim", h.kv_dim),
        ("hiddenDim", h.ff_dim),
        ("vocabSize", h.vocab_size),
    ]:
        if dim % tp != 0:
            raise ValueError(f"{name}={dim} not divisible by tp={tp}")


def auto_tp(model_path: str, n_devices: int | None = None) -> int:
    """Largest power-of-two tp that both the device count and the model's
    shardability constraints allow (mirrors the reference's
    nNodes <= nKvHeads rule, src/app.cpp:236-238). Shared by the CLI and
    the API server."""
    from ..formats.model_file import read_llm_header

    if n_devices is None:
        n_devices = len(jax.devices())
    h = read_llm_header(model_path)
    tp = 1
    while tp * 2 <= n_devices:
        try:
            validate_tp(h, tp * 2)
        except ValueError:
            break
        tp *= 2
    return tp


def make_mesh(
    tp: int = 1, dp: int = 1, sp: int = 1, pp: int = 1, devices=None
) -> Mesh:
    """Build a (pp, dp, sp, tp) mesh over the available devices.

    `sp` is the sequence/context-parallel axis (ring attention); `pp` the
    pipeline-stage axis (layer ranges per stage, parallel/pipeline.py —
    the axis that lifts the reference's nNodes <= nKvHeads ceiling on
    cluster size). Each axis only appears in the mesh when > 1 so
    existing PartitionSpecs stay valid. Uses `jax.experimental.mesh_utils`
    device ordering so the tp axis maps to physically adjacent chips
    (fastest ICI hops) on real TPU slices; pp is outermost — stage
    hand-offs are the rarest, smallest transfers.
    """
    if devices is None:
        devices = jax.devices()
    n_needed = tp * dp * sp * pp
    if n_needed > len(devices):
        raise ValueError(
            f"need {n_needed} devices (pp={pp} x tp={tp} x dp={dp} x "
            f"sp={sp}), have {len(devices)}"
        )
    shape = (dp, sp, tp) if sp > 1 else (dp, tp)
    names = ("dp", "sp", "tp") if sp > 1 else ("dp", "tp")
    if pp > 1:
        shape = (pp,) + shape
        names = ("pp",) + names
    try:
        from jax.experimental import mesh_utils

        device_array = mesh_utils.create_device_mesh(
            shape, devices=devices[:n_needed]
        )
    except Exception:
        import numpy as np

        device_array = np.asarray(devices[:n_needed]).reshape(shape)
    return Mesh(device_array, axis_names=names)


def initialize_multihost(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Multi-host (DCN) bootstrap — the SPMD analogue of the reference's
    root/worker handshake (src/nn/nn-network.cpp:295-379). On a TPU pod
    slice all arguments are auto-detected from the TPU metadata; elsewhere
    pass them explicitly."""
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
