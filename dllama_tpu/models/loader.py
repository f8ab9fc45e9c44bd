"""Load `.m` weights into the transformer's params pytree.

TPU-native counterpart of the reference's weight loading + distribution
(loadLlmNetWeight, src/llm.cpp:614-669): the reference root slices every
matmul weight per node and ships slices over TCP; here each tensor is read
(streamed via memmap), transposed to the [in, out] matmul layout, stacked
across layers (one [L, ...] array per weight: the layer scan slices the
dense ones, and the Pallas kernels read the quantized ones in place by
layer number), and `jax.device_put` with a NamedSharding does the slicing
— XLA/ICI plays the role of the socket loader.

Llama q/k row permutation note: the converter pre-permutes q/k rows to the
interleaved-rope layout (converter/convert-hf.py:13-16), so like the
reference we consume the file as-is and use interleaved RoPE for llama.
"""

from __future__ import annotations

import os
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..formats.model_file import LlmArch, LlmHeader, ModelReader, layer_table
from ..formats.quants import FloatType, pack_q40_device
from ..ops.jnp_ops import rope_cache
from ..ops.quant_matmul import (
    NIBBLES,
    PACKED_GROUP,
    FusedQuantWeight,
    PackedQuantWeight,
    QuantWeight,
    packed_kernels_take,
    planar_to_device_layout,
)
from ..utils import native
from .transformer import Params

# Placement hook: receives (name, np array) and returns the device array.
# The TP engine passes a function that applies the right NamedSharding;
# default is plain device_put semantics via jnp.asarray.
PutFn = Callable[[str, np.ndarray], jnp.ndarray]


def _default_put(name: str, arr: np.ndarray) -> jnp.ndarray:
    return jnp.asarray(arr)


def _pack_q40(raw: np.ndarray, out_dim: int, in_dim: int):
    """The wire's bytes of a [out, in] tensor -> the packed device planes
    (words int32 [in // 8, out], scales f32 [in // 32, out]): native C++
    when built (one multithreaded pass), numpy otherwise."""
    return native.q40_pack_transposed(raw, out_dim, in_dim) or pack_q40_device(
        raw, out_dim, in_dim
    )


def _interleave_concat(arrs: list[np.ndarray], tp: int) -> np.ndarray:
    """Concatenate matmul weights along the out (last) axis in SHARD-MAJOR
    order: [a_0 | b_0 | ... | a_1 | b_1 | ...] where x_i is tensor x's i-th
    of `tp` out-dim slices. Under the row-split PartitionSpec (.., "tp")
    each tp shard then holds its own slice of EVERY constituent, so one
    fused kernel launch computes what separate launches did, and the
    outputs un-interleave with local reshapes (transformer._split_fused) —
    no cross-shard data movement."""
    for a in arrs:
        if a.shape[-1] % tp != 0:
            raise ValueError(
                f"fused out dim {a.shape[-1]} not divisible by tp={tp}"
            )
    if tp == 1:
        return np.concatenate(arrs, axis=-1)
    chunks = []
    for s in range(tp):
        for a in arrs:
            o = a.shape[-1] // tp
            chunks.append(a[..., s * o : (s + 1) * o])
    return np.concatenate(chunks, axis=-1)


def _lead_indices(lead_sls, lead_shape):
    """Cartesian product of the lead-axis slice ranges (layer / expert)."""
    import itertools

    ranges = [
        range(*sl.indices(n)) for sl, n in zip(lead_sls, lead_shape)
    ]
    return list(itertools.product(*ranges)) if ranges else [()]


def _stream_quant_stack(
    reader: ModelReader,
    put: PutFn,
    tag: str,
    name_fns: list,
    lead_shape: tuple[int, ...],
    fuse: int = 1,
    packed: bool = False,
):
    """Stacked QuantWeight built WITHOUT materializing the host stack.

    Iterates the sharding's device->index map and answers each shard
    with ranged reads off the memmap (native C++ unpack;
    ModelReader.planar_q40_range as the pure-numpy fallback), one unpack
    per DISTINCT shard index (replicas reuse it), so the host high-water
    mark is one shard plus one row-range — not the full [L(, E), in, out] stack
    (at Llama-70B the w13 stack alone is ~37 GB of host RAM; the
    reference streams node slices over sockets for the same reason,
    src/llm.cpp:614-669).

    `name_fns`: one per-(lead idx) tensor-name fn, or several for a
    FUSED weight — constituents interleave shard-major in `fuse` chunks
    (the _interleave_concat layout restated as index math, so a fused
    shard never touches the other shards' bytes).

    `packed` lays each shard out in the nibble device format
    (weight_format="q40i4": an int32 word = eight int4 values) straight
    from the wire's bytes — neither host nor device ever sees the
    1 B/value layout, and the fused qkv/w13 interleave metadata is
    unchanged because packing acts on the in axis while the interleave
    permutes the out axis.

    Returns (QuantWeight | PackedQuantWeight, out_dims) with out_dims the
    constituents' global out dims (FusedQuantWeight metadata)."""
    from ..formats.quants import Q40_BLOCK_BYTES

    sh = getattr(put, "sharding")(tag)
    zero = tuple(0 for _ in lead_shape)
    specs0 = [reader.by_name[fn(*zero)] for fn in name_fns]
    inner = specs0[0].shape[1]
    douts = [s.shape[0] for s in specs0]
    for s in specs0:
        if s.shape[1] != inner:
            raise ValueError(f"{tag}: fused constituents disagree on in dim")
    total_out = sum(douts)
    nb = inner // 32
    widths = [d // fuse for d in douts]
    for d in douts:
        if d % fuse:
            raise ValueError(f"fused out dim {d} not divisible by tp={fuse}")
    cw = sum(widths)
    offs = [0]
    for w_ in widths[:-1]:
        offs.append(offs[-1] + w_)

    def fused_parts(g0: int, g1: int):
        """(constituent j, file rows [c0, c1)) pieces covering the fused
        out range [g0, g1), in fused order."""
        g = g0
        while g < g1:
            s, r = divmod(g, cw)
            j = 0
            while r >= offs[j] + widths[j]:
                j += 1
            take = min(g1 - g, offs[j] + widths[j] - r)
            c0 = s * widths[j] + (r - offs[j])
            yield j, c0, c0 + take
            g += take

    def ranged_both(lead_idx, g0, g1, b0, b1):
        """Device-layout (values [i, o] int8, or packed words [i//8, o]
        int32; scales [i//32, o] f32) for one lead index; ONE pass feeds
        both leaves (native C++ when built). Full-width rows slice the
        memmap zero-copy; block sub-ranges copy exactly the shard's bytes
        first."""
        qs, ds = [], []
        for j, c0, c1 in fused_parts(g0, g1):
            name = name_fns[j](*lead_idx)
            sub_inner = (b1 - b0) * 32
            if b0 == 0 and b1 == nb:
                rowb = nb * Q40_BLOCK_BYTES
                raw = reader.raw(name)[c0 * rowb : c1 * rowb]
            else:
                full = reader.raw(name).reshape(-1, nb, Q40_BLOCK_BYTES)
                raw = np.ascontiguousarray(full[c0:c1, b0:b1]).reshape(-1)
            if packed:
                unpacked = _pack_q40(raw, c1 - c0, sub_inner)
            else:
                unpacked = native.q40_unpack_transposed(raw, c1 - c0, sub_inner)
            if unpacked is None:
                q, d = reader.planar_q40_range(name, c0, c1, b0, b1)
                unpacked = planar_to_device_layout(q, d)
            qs.append(unpacked[0])
            ds.append(unpacked[1])
        if len(qs) == 1:
            return qs[0], ds[0]
        return np.concatenate(qs, axis=1), np.concatenate(ds, axis=1)

    q_shape = (*lead_shape, inner, total_out)
    d_shape = (*lead_shape, nb, total_out)
    q_map = sh.addressable_devices_indices_map(q_shape)
    d_map = sh.addressable_devices_indices_map(d_shape)
    # group devices by DISTINCT shard index (dp replicas share one
    # unpack), then build -> device_put -> FREE one shard at a time: the
    # host never holds more than one shard's numpy buffers (holding all
    # of them was a ~2x-largest-tensor transient, enough to OOM the
    # 125 GB rehearsal host at 70B scale)
    by_key: dict = {}
    for dev, q_idx in q_map.items():
        key = tuple((sl.start, sl.stop, sl.step) for sl in q_idx)
        by_key.setdefault(key, (q_idx, []))[1].append(dev)
    q_parts: dict = {}
    d_parts: dict = {}
    for key, (q_idx, devs) in by_key.items():
        *lead_sls, i_sl, o_sl = q_idx
        i0, i1, _ = i_sl.indices(inner)
        o0, o1, _ = o_sl.indices(total_out)
        if i0 % 32 or i1 % 32:
            raise ValueError(f"{tag}: shard slice [{i0},{i1}) not 32-aligned")
        b0, b1 = i0 // 32, i1 // 32
        db_sl = d_map[devs[0]][len(lead_sls)]
        if db_sl.indices(nb)[:2] != (b0, b1):  # leaves must shard alike
            raise ValueError(f"{tag}: value/scale shard maps disagree")
        leads = _lead_indices(lead_sls, lead_shape)
        lead_lens = [
            len(range(*sl.indices(n))) for sl, n in zip(lead_sls, lead_shape)
        ]
        # preallocate at the final shard shape and write each lead index's
        # planes in place: a pairs list + np.stack would hold TWO copies of
        # the shard at once — several GB of transient for a 70B w13 tp shard
        sub_inner = (b1 - b0) * 32
        if packed and sub_inner != inner and not packed_kernels_take(sub_inner):
            raise ValueError(
                f"{tag}: a packed shard's in slice [{i0},{i1}) is not whole "
                f"groups of {PACKED_GROUP} rows"
            )
        q_rows, q_dtype = (
            (sub_inner // NIBBLES, np.int32) if packed else (sub_inner, np.int8)
        )
        q_np = np.empty((len(leads), q_rows, o1 - o0), q_dtype)
        d_np = np.empty((len(leads), b1 - b0, o1 - o0), np.float32)
        for i, li in enumerate(leads):
            q_np[i], d_np[i] = ranged_both(li, o0, o1, b0, b1)
        q_np = q_np.reshape(*lead_lens, *q_np.shape[1:])
        d_np = d_np.reshape(*lead_lens, *d_np.shape[1:])
        for dev in devs:
            q_parts[dev] = jax.device_put(q_np, dev)
            d_parts[dev] = jax.device_put(d_np, dev)
        jax.block_until_ready(  # transfers done before freeing the source
            [q_parts[d] for d in devs] + [d_parts[d] for d in devs]
        )
        del q_np, d_np
    out_q_shape = (
        (*lead_shape, inner // NIBBLES, total_out) if packed else q_shape
    )
    q_arr = jax.make_array_from_single_device_arrays(
        out_q_shape, sh, [q_parts[d] for d in q_map]
    )
    d_arr = jax.make_array_from_single_device_arrays(
        d_shape, getattr(put, "sharding")(tag), [d_parts[d] for d in q_map]
    )
    cls = PackedQuantWeight if packed else QuantWeight
    return cls(q_arr, d_arr), tuple(douts)


def _q40_in_dims(specs, routed: bool) -> list[int]:
    """The in axes of a file's Q40 matrices: the routed experts', or every
    other's but `wkv_b`'s, which is dequantised whatever its width."""
    return [
        spec.shape[1] for spec in specs
        if spec.float_type == FloatType.Q40 and len(spec.shape) == 2
        and (".experts." in spec.name) == routed
        and not spec.name.endswith(".wkv_b")
    ]


def packs_dense(specs, tp: int = 1) -> bool:
    """Whether the dense matmuls of a file are held packed on `tp` shards:
    all or none, so every one's in axis has to be whole groups of 256 rows
    a shard (what "auto" asks on a TPU; else int8 values)."""
    return all(packed_kernels_take(k, tp) for k in _q40_in_dims(specs, False))


def packs_experts(specs, devices: int = 1) -> bool:
    """Whether the routed experts are held packed with the dense matmuls:
    one device holds every sparse layer whole, so `moe_held_experts_q40` is
    the kernel that reads them and unpacks a tile in VMEM, and every
    expert's in axis (D of w1 and w3, F of w2) is whole groups of 256 rows.
    On a mesh the older expert kernels' shards slice F and read int8
    values: there the experts stay int8."""
    experts = _q40_in_dims(specs, True)
    return devices == 1 and bool(experts) and all(map(packed_kernels_take, experts))


def weight_forms(specs, weight_format: str, devices: int = 1) -> tuple[str, str]:
    """The forms `load_params` holds a file's (dense matmuls, routed
    experts) in under a resolved `weight_format`, in the names of
    obs/cost.weight_bytes_by_form: `float`, `int8` or `packed`."""
    if weight_format == "q40i4":
        return "packed", "packed" if packs_experts(specs, devices) else "int8"
    form = "int8" if weight_format == "q40" else "float"
    return form, form


def load_params(
    reader: ModelReader,
    dtype=jnp.float32,
    put: PutFn = _default_put,
    weight_format: str = "dense",
    fuse: int = 0,
) -> Params:
    """Materialize the params pytree from a `.m` file.

    `dtype` is the activation/matmul dtype for the dense (dequantized)
    path — f32 for exactness tests, bf16 for TPU speed. Norm weights and
    the rope cache stay f32.

    `weight_format="q40"` keeps the matmul weights block-quantized on
    device as `QuantWeight` (int8 values + f32 scales, the Pallas kernel's
    layout) instead of dequantizing — ~3.6x less HBM traffic per decode
    step. Requires a Q40 file. MoE expert weights are kept quantized too
    (the ragged kernel dequantizes selected blocks in VMEM), so a Q40 MoE
    file's device footprint stays ~1.125 B/weight instead of blowing up to
    bf16 density.

    `weight_format="q40i4"` instead lays the matmul weights out in the
    nibble device format (`PackedQuantWeight`: eight int4 values per int32
    word + f32 scales, 0.625 B/weight) straight from the wire's bytes; the
    Pallas kernel unpacks in VMEM after the HBM copy. The routed expert
    stacks [L, E, in, out] are laid out the same way where `packs_experts`
    says so, over the devices of `put`'s mesh (one, for a put that names no
    sharding); else they stay int8 `QuantWeight`, which the mesh's expert
    kernels consume.

    `fuse` (quantized path only): the tp shard count; > 0 emits fused
    "wqkv" (q|k|v) and, for dense-FFN archs, "w13" (w1|w3) weights in
    shard-major interleaved layout instead of the separate tensors —
    decode drops from 7 to 4 Pallas launches per layer and reads the
    activations once per pair (the round-3 silicon probe measured ~41 us
    fixed cost per kernel launch). Must equal the
    mesh's tp axis size.
    """
    h = reader.header
    quantize = weight_format in ("q40", "q40i4")
    sharding = getattr(put, "sharding", None)
    dense_form, expert_form = weight_forms(
        reader.specs, weight_format,
        devices=sharding("w1").mesh.devices.size if sharding else 1,
    )
    packed, pack_experts = dense_form == "packed", expert_form == "packed"
    if quantize and h.weight_type != FloatType.Q40:
        raise ValueError(
            f"weight_format={weight_format!r} needs a Q40 model file, got "
            f"{h.weight_type.name}"
        )
    # Streamed shard-by-shard placement whenever the put hook exposes its
    # shardings (shard_params_put does); DLLAMA_STREAM_LOAD=0 forces the
    # host-stack path (kept for single-device puts and as the oracle the
    # streamed path is tested against).
    streaming = (
        quantize
        and sharding is not None
        and os.environ.get("DLLAMA_STREAM_LOAD", "1") != "0"
    )

    def w(name: str, transpose: bool = True) -> np.ndarray:
        spec = reader.by_name[name]
        if (
            transpose
            and spec.float_type == FloatType.Q40
            and len(spec.shape) == 2
        ):
            # multithreaded C++ dequant straight into the transposed layout
            out_dim, in_dim = spec.shape
            a = native.q40_dequant_transposed(reader.raw(name), out_dim, in_dim)
            if a is not None:
                return a
        a = reader.dense_f32(name)
        if transpose:
            if a.ndim == 2 and a.size >= 1 << 20:
                t = native.f32_transpose(a)
                if t is not None:
                    return t
            a = np.ascontiguousarray(a.T)  # file is (out, in) -> we want (in, out)
        return a

    # leaves of the attention block are stacked over every layer; those of
    # an FFN over the layers of its kind (`layer_table`), since a model may
    # lead with dense layers and go on with experts
    every = list(range(h.n_layers))
    table = layer_table(h)
    dense_layers = [l for l in every if not table[l].experts]
    expert_layers = [l for l in every if table[l].experts]
    # and where some layers are gated short convolutions, each operator's
    # leaves over the layers of its own kind
    attn_layers = [l for l in every if not table[l].keeps_state]
    conv_layers = [l for l in every if table[l].conv]
    ssm_layers = [l for l in every if table[l].ssm]

    def stack(fn: Callable[[int], np.ndarray], layers=every) -> np.ndarray:
        return np.stack([fn(l) for l in layers])

    def unpack_q40(name: str, pack: bool = False) -> tuple[np.ndarray, np.ndarray]:
        """Q40 tensor -> (q int8 [in, out], d f32 [in//32, out]) device
        layout, or with `pack` its words int32 [in//8, out] in q's
        place; native C++ when built (one multithreaded pass), numpy
        fallback otherwise."""
        out_dim, in_dim = reader.by_name[name].shape
        if pack:
            return _pack_q40(reader.raw(name), out_dim, in_dim)
        unpacked = native.q40_unpack_transposed(reader.raw(name), out_dim, in_dim)
        if unpacked is None:
            unpacked = planar_to_device_layout(*reader.planar_q40(name))
        return unpacked

    def qw(tag: str, fn: Callable[[int], str], layers=every):
        """Stacked QuantWeight (or PackedQuantWeight when packed) for a
        per-layer matmul tensor."""
        if streaming:
            w_, _ = _stream_quant_stack(
                reader, put, tag, [lambda i: fn(layers[i])], (len(layers),),
                packed=packed,
            )
            return w_
        qs, ds = [], []
        for l in layers:
            q_arr, d_arr = unpack_q40(fn(l), packed)
            qs.append(q_arr)
            ds.append(d_arr)
        cls = PackedQuantWeight if packed else QuantWeight
        return cls(put(tag, np.stack(qs)), put(tag, np.stack(ds)))

    layers: dict[str, jnp.ndarray] = {}
    layers["att_norm"] = put(
        "att_norm", stack(lambda l: w(f"layers.{l}.att_norm", False))
    )
    layers["ffn_norm"] = put(
        "ffn_norm", stack(lambda l: w(f"layers.{l}.ffn_norm", False))
    )
    def qw_fused(
        tag: str, names: list[Callable[[int], str]], layers=every, fuse: int = fuse
    ) -> FusedQuantWeight:
        """Stacked FusedQuantWeight fusing several row-split matmul tensors
        along the out axis, shard-major for `fuse` tp shards; the fuse
        factor and constituent out dims ride as static pytree metadata."""
        if streaming:
            w_, dims = _stream_quant_stack(
                reader, put, tag,
                [lambda i, fn=fn: fn(layers[i]) for fn in names],
                (len(layers),), fuse=fuse, packed=packed,
            )
            return FusedQuantWeight(w_, fuse, dims)
        qs, ds = [], []
        dims: tuple[int, ...] = ()
        for l in layers:
            parts = [unpack_q40(fn(l), packed) for fn in names]
            dims = tuple(p[0].shape[-1] for p in parts)
            # interleave permutes the out axis, packing folds the in
            # axis — they commute, so the fuse/dims metadata is the same
            # for both device formats
            qs.append(_interleave_concat([p[0] for p in parts], fuse))
            ds.append(_interleave_concat([p[1] for p in parts], fuse))
        cls = PackedQuantWeight if packed else QuantWeight
        return FusedQuantWeight(
            cls(put(tag, np.stack(qs)), put(tag, np.stack(ds))),
            fuse,
            dims,
        )

    if conv_layers:
        for n in ("conv_in", "conv_out"):
            if quantize:
                layers[n] = qw(n, lambda l, n=n: f"layers.{l}.{n}", conv_layers)
            else:
                layers[n] = put(n, stack(
                    lambda l, n=n: w(f"layers.{l}.{n}"), conv_layers).astype(dtype))
        # the taps as [K, D] rows, f32 as the norms are
        layers["conv_w"] = put(
            "conv_w", stack(lambda l: w(f"layers.{l}.conv_w"), conv_layers))
    if ssm_layers:
        # a Mamba-2 mixer: `in_proj`'s three tensors as one matmul `[z | xBC |
        # dt]` (one device holds it: no interleave), the projection back, and
        # the small leaves f32 as the norms are, the taps as [K, C] rows
        parts = ("ssm_in_z", "ssm_in_xbc", "ssm_in_dt")
        if quantize:
            layers["ssm_in"] = qw_fused(
                "ssm_in", [lambda l, n=n: f"layers.{l}.{n}" for n in parts], ssm_layers,
                fuse=1,
            ).weight
            layers["ssm_out"] = qw("ssm_out", lambda l: f"layers.{l}.ssm_out", ssm_layers)
        else:
            layers["ssm_in"] = put("ssm_in", stack(
                lambda l: np.concatenate([w(f"layers.{l}.{n}") for n in parts], axis=1),
                ssm_layers).astype(dtype))
            layers["ssm_out"] = put("ssm_out", stack(
                lambda l: w(f"layers.{l}.ssm_out"), ssm_layers).astype(dtype))
        layers["ssm_conv_w"] = put(
            "ssm_conv_w", stack(lambda l: w(f"layers.{l}.ssm_conv_w"), ssm_layers))
        for n in ("ssm_conv_b", "ssm_dt_bias", "ssm_a_log", "ssm_d", "ssm_norm"):
            layers[n] = put(n, stack(lambda l, n=n: w(f"layers.{l}.{n}", False), ssm_layers))
    has_gate = "layers.0.att_gate" in reader.by_name
    qkv = ["q", "k", "v"] + (["att_gate"] if has_gate else [])
    if h.latent:
        # latent attention: the projections as they are in the file, but
        # `wkv_b`. The absorbed path contracts it over its OUTPUT side (a
        # query's nope columns against a head's `U_h`), which a Q40 block
        # along the input side cannot serve, so it is dequantised once
        # into two per-head stacks in the activation dtype: `wkv_b_k`
        # [L, H, nope, kv_lora] and `wkv_b_v` [L, H, kv_lora, v].
        # an index over the cache: its two projections as the others, its
        # head weights f32 as the router's, the key's LayerNorm as a norm
        index = ("idx_wq_b", "idx_wk") if h.indexed else ()
        for n in ("wq_a", "wq_b", "wkv_a", "wo", *index):
            if quantize:
                layers[n] = qw(n, lambda l, n=n: f"layers.{l}.{n}")
            else:
                layers[n] = put(
                    n, stack(lambda l, n=n: w(f"layers.{l}.{n}")).astype(dtype)
                )
        for n in ("q_a_norm", "kv_a_norm") + (("idx_k_norm", "idx_k_bias") if index else ()):
            layers[n] = put(n, stack(lambda l, n=n: w(f"layers.{l}.{n}", False)))
        if index:
            layers["idx_w"] = put("idx_w", stack(lambda l: w(f"layers.{l}.idx_w")))
        nope = h.qk_nope_head_dim

        def per_head(l: int) -> np.ndarray:  # [H, nope + v, kv_lora]
            return w(f"layers.{l}.wkv_b", False).reshape(
                h.n_heads, nope + h.v_head_dim, h.kv_lora_rank
            )

        layers["wkv_b_k"] = put(
            "wkv_b_k", stack(lambda l: per_head(l)[:, :nope]).astype(dtype)
        )
        layers["wkv_b_v"] = put("wkv_b_v", stack(
            lambda l: per_head(l)[:, nope:].transpose(0, 2, 1)).astype(dtype))
    elif quantize and fuse:
        # the gate on the attention output reads the same input: it rides
        # the fused launch as a fourth constituent
        layers["wqkv"] = qw_fused(
            "wqkv", [lambda l, n=n: f"layers.{l}.{n}" for n in qkv], attn_layers
        )
        layers["wo"] = qw("wo", lambda l: f"layers.{l}.wo", attn_layers)
    elif quantize:
        for tag, n in zip(("wq", "wk", "wv", "wg"), qkv):
            layers[tag] = qw(tag, lambda l, n=n: f"layers.{l}.{n}", attn_layers)
        layers["wo"] = qw("wo", lambda l: f"layers.{l}.wo", attn_layers)
    else:
        for tag, n in zip(("wq", "wk", "wv", "wg"), qkv):
            layers[tag] = put(
                tag, stack(lambda l, n=n: w(f"layers.{l}.{n}"), attn_layers).astype(dtype)
            )
        layers["wo"] = put(
            "wo", stack(lambda l: w(f"layers.{l}.wo"), attn_layers).astype(dtype))

    def swiglu(prefix: str, name: Callable[[int, str], str], ls: list[int]) -> None:
        """A SwiGLU's three matrices over the layers `ls`, as
        `<prefix>w1`..`w3`, or fused `<prefix>w13` and `<prefix>w2`."""
        if quantize and fuse:
            layers[prefix + "w13"] = qw_fused(
                prefix + "w13",
                [lambda l: name(l, "w1"), lambda l: name(l, "w3")], ls,
            )
            layers[prefix + "w2"] = qw(prefix + "w2", lambda l: name(l, "w2"), ls)
            return
        for n in ("w1", "w2", "w3"):
            if quantize:
                layers[prefix + n] = qw(prefix + n, lambda l, n=n: name(l, n), ls)
            else:
                layers[prefix + n] = put(
                    prefix + n,
                    stack(lambda l, n=n: w(name(l, n)), ls).astype(dtype),
                )

    if expert_layers:
        layers["moe_gate"] = put(
            "moe_gate", stack(lambda l: w(f"layers.{l}.moe_gate"), expert_layers)
        )
        if f"layers.{expert_layers[0]}.expert_bias" in reader.by_name:
            layers["expert_bias"] = put("expert_bias", stack(
                lambda l: w(f"layers.{l}.expert_bias", False), expert_layers))
        if h.n_shared_experts:
            swiglu("shared_", lambda l, n: f"layers.{l}.shared.{n}", expert_layers)

        if quantize:
            # Experts stay block-quantized on device (the reference stores
            # and ships experts Q40 too: src/llm.cpp:425-499,
            # src/nn/nn-network.cpp:856-888); the ragged MoE kernel
            # dequantizes selected blocks in VMEM. Layout per expert is the
            # same [in, out] device layout as the dense matmuls, stacked
            # [L, E, ...]: int8 values, or where `packs_experts` the packed
            # words, written straight from the wire an expert at a time.

            def qexperts(tag: str, which: str):
                if streaming:
                    w_, _ = _stream_quant_stack(
                        reader, put, tag,
                        [lambda i, e, wh=which:
                            f"layers.{expert_layers[i]}.experts.{e}.{wh}"],
                        (len(expert_layers), h.n_experts),
                        packed=pack_experts,
                    )
                    return w_
                lqs, lds = [], []
                for l in expert_layers:
                    unpacked = [
                        unpack_q40(f"layers.{l}.experts.{e}.{which}", pack_experts)
                        for e in range(h.n_experts)
                    ]
                    lqs.append(np.stack([u[0] for u in unpacked]))
                    lds.append(np.stack([u[1] for u in unpacked]))
                cls = PackedQuantWeight if pack_experts else QuantWeight
                return cls(put(tag, np.stack(lqs)), put(tag, np.stack(lds)))

            layers["w1"] = qexperts("w1", "w1")
            layers["w2"] = qexperts("w2", "w2")
            layers["w3"] = qexperts("w3", "w3")
        else:

            def experts(l: int, which: str) -> np.ndarray:
                return np.stack(
                    [w(f"layers.{l}.experts.{e}.{which}") for e in range(h.n_experts)]
                )

            for n in ("w1", "w2", "w3"):
                layers[n] = put(
                    n, stack(lambda l, n=n: experts(l, n), expert_layers).astype(dtype)
                )
    if dense_layers:
        # beside experts the dense layers' FFN is a stack of its own
        swiglu(
            "dense_" if expert_layers else "",
            lambda l, n: f"layers.{l}.{n}", dense_layers,
        )

    for n in ("q_norm", "k_norm"):  # of the attention layers
        if f"layers.{attn_layers[0]}.{n}" in reader.by_name:
            layers[n] = put(
                n, stack(lambda l, n=n: w(f"layers.{l}.{n}", False), attn_layers))
    for n in ("post_att_norm", "post_ffn_norm"):
        if f"layers.0.{n}" in reader.by_name:
            layers[n] = put(n, stack(lambda l, n=n: w(f"layers.{l}.{n}", False)))

    cos, sin = rope_cache(h)
    if quantize and streaming:
        wcls, _ = _stream_quant_stack(
            reader, put, "wcls", [lambda: "wcls"], (), packed=packed
        )
    elif quantize:
        q_arr, d_arr = unpack_q40("wcls", packed)
        cls = PackedQuantWeight if packed else QuantWeight
        wcls = cls(put("wcls", q_arr), put("wcls", d_arr))
    else:
        wcls = put("wcls", w("wcls").astype(dtype))
    params: Params = {
        "embed": put("embed", reader.dense_f32("embed").astype(dtype)),
        "wcls": wcls,
        "final_norm": put("final_norm", w("final_norm", False)),
        "rope_cos": put("rope_cos", np.asarray(cos)),
        "rope_sin": put("rope_sin", np.asarray(sin)),
        "layers": layers,
    }
    return params
