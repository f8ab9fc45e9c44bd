"""Synthetic model construction: headers + random params without a `.m` file.

Used by chip_smoke.py, __graft_entry__.py and tests to exercise the full model
path at arbitrary scale without multi-GB downloads. Shapes and pytree layout
are identical to models/loader.load_params output.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..formats.model_file import HiddenAct, LlmArch, LlmHeader, RopeType, tensor_plan
from ..formats.quants import FloatType
from ..ops.jnp_ops import rope_cache
from .loader import weight_forms
from .transformer import Params

# Real-model shape presets (from the reference's supported model zoo,
# launch.py:17-73 / BASELINE.json configs).
PRESETS = {
    "llama-1b": dict(
        dim=2048, hidden_dim=8192, n_layers=16, n_heads=32, n_kv_heads=8,
        head_dim=64, vocab_size=128256, seq_len=131072, rope_theta=500000.0,
    ),
    "llama-8b": dict(
        dim=4096, hidden_dim=14336, n_layers=32, n_heads=32, n_kv_heads=8,
        head_dim=128, vocab_size=128256, seq_len=131072, rope_theta=500000.0,
    ),
    "llama-70b": dict(
        dim=8192, hidden_dim=28672, n_layers=80, n_heads=64, n_kv_heads=8,
        head_dim=128, vocab_size=128256, seq_len=131072, rope_theta=500000.0,
    ),
    "qwen3-14b": dict(
        dim=5120, hidden_dim=17408, n_layers=40, n_heads=40, n_kv_heads=8,
        head_dim=128, vocab_size=151936, seq_len=40960, rope_theta=1000000.0,
        arch=LlmArch.QWEN3,
    ),
    "qwen3-30b-a3b": dict(
        dim=2048, hidden_dim=6144, moe_hidden_dim=768, n_layers=48,
        n_heads=32, n_kv_heads=4, head_dim=128, vocab_size=151936,
        seq_len=40960, rope_theta=1000000.0, arch=LlmArch.QWEN3_MOE,
        n_experts=128, n_active_experts=8,
    ),
    "tiny": dict(
        dim=64, hidden_dim=160, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=16, vocab_size=256, seq_len=64,
    ),
}


def make_header(preset: str | dict, max_seq_len: int = 0) -> LlmHeader:
    cfg = dict(PRESETS[preset]) if isinstance(preset, str) else dict(preset)
    h = LlmHeader()
    h.arch = cfg.pop("arch", LlmArch.LLAMA)
    h.n_experts = cfg.pop("n_experts", 0)
    h.n_active_experts = cfg.pop("n_active_experts", 0)
    h.moe_hidden_dim = cfg.pop("moe_hidden_dim", 0)
    h.rope_theta = cfg.pop("rope_theta", 10000.0)
    for k, v in cfg.items():
        setattr(h, k, v)
    h.orig_seq_len = h.seq_len
    if max_seq_len and h.seq_len > max_seq_len:
        h.seq_len = max_seq_len
    if h.head_dim == 0:
        h.head_dim = h.dim // h.n_heads
    h.hidden_act = HiddenAct.SILU
    h.weight_type = FloatType.Q40
    h.rope_type = (
        RopeType.FALCON
        if h.arch in (LlmArch.QWEN3, LlmArch.QWEN3_MOE)
        else RopeType.LLAMA
    )
    h.norm_epsilon = 1e-5
    return h


# Embedding rows are N(0, 3), not N(0, 0.02): thirty-two random layers add
# about 2 rms of drift to the residual stream, and under a small embedding
# greedy decode sits on one token whatever the prompt. At 3 the token fed
# keeps its identity and the layers still decide the argmax.
EMBED_STD = 3.0


def write_synth_model(
    path,
    preset: str | dict = "llama-70b",
    seed: int = 0,
    max_seq_len: int = 4096,
    n_layers: int | None = None,
    tile_bytes: int = 8 << 20,
):
    """Stream a synthetic random Q40 `.m` of ARBITRARY size to disk with
    O(tile) host memory, every row different: Q40 tensors are written as
    wire blocks directly — 16 random nibble bytes under a random f16
    scale of either sign in [0.5, 1] x 0.02/8, so weights are uniform
    with std ~0.009 and mean zero (nibbles alone average -0.5; with
    one-signed scales that mean is an eigen-direction of every matrix
    with gain |mean| x dim = 3.9 at dim 4096, and four layers are enough
    for it to fix the argmax whatever the input) — a tile at a time from
    one seeded stream; the embedding is
    N(0, EMBED_STD), other f32 tensors N(0, 0.02) and norms 1.0. Logits
    of such a model are not degenerate (greedy decode wanders over the
    vocab), so a comparison against a reference on this file can fail;
    an 8B file takes under a minute. Real checkpoints stay the parity
    oracle for the converter.
    Returns the LlmHeader describing the file."""
    from ..formats.quants import Q40_BLOCK_BYTES, Q40_BLOCK_SIZE
    from ..formats.writer import write_header

    cfg = dict(PRESETS[preset]) if isinstance(preset, str) else dict(preset)
    if n_layers is not None:
        cfg["n_layers"] = n_layers
    h = make_header(cfg, max_seq_len=max_seq_len)
    params = {
        "version": 0,
        "arch_type": int(h.arch),
        "dim": h.dim,
        "hidden_dim": h.hidden_dim,
        "n_layers": h.n_layers,
        "n_heads": h.n_heads,
        "n_kv_heads": h.n_kv_heads,
        "n_experts": h.n_experts,
        "n_active_experts": h.n_active_experts,
        "vocab_size": h.vocab_size,
        "max_seq_len": h.seq_len,
        "hidden_act": int(h.hidden_act),
        "rope_theta": int(h.rope_theta),
        "weights_float_type": int(FloatType.Q40),
        "head_dim": h.head_dim,
        "norm_epsilon": 5,  # header quirk: eps rides as an enum (5 = 1e-5)
    }
    if h.arch == LlmArch.QWEN3_MOE:
        params["moe_hidden_dim"] = h.moe_hidden_dim
    rng = np.random.default_rng(seed)
    scale = 0.02

    def f32_tile(n: int, std: float) -> bytes:
        return (rng.standard_normal(n, dtype=np.float32) * std).tobytes()

    def q40_tile(n_blocks: int) -> bytes:
        n_bytes = n_blocks * Q40_BLOCK_BYTES
        words = rng.integers(0, 1 << 64, -(-n_bytes // 8), dtype=np.uint64)
        blocks = words.view(np.uint8)[:n_bytes].reshape(
            n_blocks, Q40_BLOCK_BYTES
        )
        d = rng.uniform(0.5 * scale / 8, scale / 8, n_blocks)
        d *= rng.choice((-1.0, 1.0), n_blocks)
        blocks[:, :2] = d.astype(np.float16).view(np.uint8).reshape(-1, 2)
        return blocks.tobytes()

    with open(path, "wb") as f:
        write_header(f, params)
        for spec in tensor_plan(h):
            n = int(np.prod(spec.shape, dtype=np.int64))
            if spec.float_type == FloatType.F32:
                if "norm" in spec.name:
                    f.write(np.ones(spec.shape, np.float32).tobytes())
                    continue
                std = EMBED_STD if spec.name == "embed" else scale
                unit_bytes, tile = 4, functools.partial(f32_tile, std=std)
            elif spec.float_type == FloatType.Q40:
                n //= Q40_BLOCK_SIZE
                unit_bytes, tile = Q40_BLOCK_BYTES, q40_tile
            else:  # pragma: no cover - synth files are Q40+F32 only
                raise ValueError(f"unsupported synth type {spec.float_type}")
            per_tile = max(1, tile_bytes // unit_bytes)
            for start in range(0, n, per_tile):
                f.write(tile(min(per_tile, n - start)))
    return h


def write_synth_tokenizer(
    path, vocab_size: int, chat_template: str = "<|start_header_id|>"
):
    """Byte-level `.t` to go with a synthetic model: 256 single-byte
    tokens, `<padN>` fillers up to the model's vocab (decode indexes
    vocab[token] for any sampled id), then `<s>`, `</s>`, `<|eot|>` at
    the top. The default template marker selects the llama3 chat format —
    the server refuses a tokenizer without one. Returns the data."""
    from ..formats.tokenizer_file import TokenizerData, write_tokenizer

    specials = [b"<s>", b"</s>", b"<|eot|>"]
    vocab = [bytes([i]) for i in range(256)]
    if vocab_size < len(vocab) + len(specials):
        raise ValueError(f"vocab_size {vocab_size} < 259")
    vocab += [
        f"<pad{i}>".encode() for i in range(256, vocab_size - len(specials))
    ]
    bos_id = len(vocab)
    vocab += specials
    data = TokenizerData(
        vocab=vocab,
        scores=[0.0] * len(vocab),
        bos_id=bos_id,
        add_bos=True,
        eos_token_ids=[bos_id + 1, bos_id + 2],
        chat_template=chat_template,
        max_token_length=max(len(v) for v in vocab),
    )
    write_tokenizer(path, data)
    return data


def random_params(
    h: LlmHeader,
    dtype=jnp.bfloat16,
    seed: int = 0,
    mesh=None,
    put=None,  # kept for API symmetry with load_params; unused when mesh given
    weight_format: str = "dense",
    fuse: int = 0,
) -> Params:
    """Random params pytree with the loader's exact layout, generated
    directly ON DEVICE (jit + out_shardings): no multi-GB host->device
    transfer.

    Pass `mesh` to get TP-sharded parameters (same rules as
    parallel.sharding.param_spec_tree)."""
    from jax.sharding import NamedSharding, PartitionSpec

    specs = None
    if mesh is not None:
        from ..parallel.sharding import param_spec_tree

        specs = param_spec_tree(h)

    root_key = jax.random.PRNGKey(seed)
    scale = 0.02

    def sharding_for(name):
        if specs is None:
            return None
        spec = specs.get(name)
        if spec is None:
            spec = specs["layers"].get(name, PartitionSpec())
        return NamedSharding(mesh, spec)

    def mk(name, *shape, norm=False):
        sh = sharding_for(name)
        if norm:
            f = jax.jit(
                lambda: jnp.ones(shape, jnp.float32), out_shardings=sh
            )
            return f()
        import zlib

        key = jax.random.fold_in(root_key, zlib.crc32(name.encode()))
        f = jax.jit(
            lambda k: jax.random.normal(k, shape, dtype) * jnp.asarray(scale, dtype),
            out_shardings=sh,
        )
        return f(key)

    def mk_quant(name, *shape, packed=False):
        """Random QuantWeight [..., in, out] on device: int8 values in
        [-8, 7] + f32 per-block scales (the loader's q40 layout). With
        `packed` the q40i4 device layout instead: int32 words of eight
        nibbles [..., in//8, out] — any word is eight valid nibbles, so
        the packed tensor is generated directly at its final shape."""
        import zlib

        from ..ops.quant_matmul import NIBBLES, PackedQuantWeight, QuantWeight

        sh = sharding_for(name)
        *lead, inner, out = shape
        key = jax.random.fold_in(root_key, zlib.crc32(name.encode()))
        kq, kd = jax.random.split(key)
        q_shape = (*lead, inner // NIBBLES, out) if packed else shape
        q = jax.jit(
            lambda k: (
                jax.lax.bitcast_convert_type(
                    jax.random.bits(k, q_shape, jnp.uint32), jnp.int32)
                if packed
                else jax.random.randint(k, q_shape, -8, 8, dtype=jnp.int8)
            ),
            out_shardings=sh,
        )(kq)
        d_shape = (*lead, inner // 32, out)
        d = jax.jit(
            lambda k: jax.random.uniform(
                k, d_shape, jnp.float32, minval=0.5 * scale / 8, maxval=scale / 8
            ),
            out_shardings=sh,
        )(kd)
        cls = PackedQuantWeight if packed else QuantWeight
        return cls(q, d)

    def dev(name, arr):
        sh = sharding_for(name)
        arr = jnp.asarray(arr)
        return jax.device_put(arr, sh) if sh is not None else arr

    L, D, HD = h.n_layers, h.dim, h.head_dim
    QD, KD, FF, V = h.q_dim, h.kv_dim, h.ff_dim, h.vocab_size
    moe = h.arch == LlmArch.QWEN3_MOE
    E = h.n_experts

    quant = weight_format in ("q40", "q40i4")
    # the forms the loader would hold a Q40 file of this header in
    dense_form, expert_form = weight_forms(
        tensor_plan(dataclasses.replace(h, weight_type=FloatType.Q40)),
        weight_format, devices=1 if mesh is None else mesh.devices.size,
    )
    packed, pack_experts = dense_form == "packed", expert_form == "packed"
    if quant:
        def mm(name, *shape, expert=False):
            return mk_quant(name, *shape, packed=pack_experts if expert else packed)
    else:
        def mm(name, *shape, expert=False):
            return mk(name, *shape)
    layers = {
        "att_norm": mk("att_norm", L, D, norm=True),
        "ffn_norm": mk("ffn_norm", L, D, norm=True),
        "wo": mm("wo", L, QD, D),
        # MoE experts follow the loader's policy: quantized on device for
        # q40 (the ragged/grouped kernels dequantize selected blocks in
        # VMEM), dense otherwise
        "w1": mm("w1", L, E, D, FF, expert=True) if moe else mm("w1", L, D, FF),
        "w2": mm("w2", L, E, FF, D, expert=True) if moe else mm("w2", L, FF, D),
        "w3": mm("w3", L, E, D, FF, expert=True) if moe else mm("w3", L, D, FF),
    }
    if quant and fuse:
        # fused-launch layout (loader `fuse`): the content is random either
        # way, so generate the fused tensors directly in their shapes
        from ..ops.quant_matmul import FusedQuantWeight

        layers["wqkv"] = FusedQuantWeight(
            mm("wqkv", L, D, QD + 2 * KD), fuse, (QD, KD, KD)
        )
        if not moe:
            del layers["w1"], layers["w3"]
            layers["w13"] = FusedQuantWeight(
                mm("w13", L, D, 2 * FF), fuse, (FF, FF)
            )
    else:
        layers["wq"] = mm("wq", L, D, QD)
        layers["wk"] = mm("wk", L, D, KD)
        layers["wv"] = mm("wv", L, D, KD)
    if moe:
        gate_key = jax.random.fold_in(root_key, 12345)
        layers["moe_gate"] = jax.jit(
            lambda k: jax.random.normal(k, (L, D, E), jnp.float32) * scale,
            out_shardings=sharding_for("moe_gate"),
        )(gate_key)
    if h.arch in (LlmArch.QWEN3, LlmArch.QWEN3_MOE):
        layers["q_norm"] = mk("q_norm", L, HD, norm=True)
        layers["k_norm"] = mk("k_norm", L, HD, norm=True)

    cos, sin = rope_cache(h)
    params = {
        "embed": mk("embed", V, D),
        "wcls": mm("wcls", D, V),
        "final_norm": mk("final_norm", D, norm=True),
        "rope_cos": dev("rope_cos", cos),
        "rope_sin": dev("rope_sin", sin),
        "layers": layers,
    }
    return params
