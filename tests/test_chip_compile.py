"""The serving path's kernels, compiled for the real chip at real widths.

No chip is attached here: the TPU's compiler is installed and compiles for a
*described* v5e 2x2 topology (on-chip-measurement guide, section 2). That
shows what interpret mode cannot — tiling the chip refuses, VMEM a kernel may
not use, a kernel that cannot be partitioned — at no chip time. Nothing runs,
so these tests say nothing about results or speed.

Widths: Llama-3.1-8B (dim 4096, ffn 14336, vocab 128256, 32/8 heads of 128)
and Qwen3-30B-A3B experts (D 2048, F 768, E 128, top-8).

The topology is described inside a module-scoped fixture (never at import:
only one process may load libtpu, and every xdist worker imports this file),
and every such test lives in this one file so one worker owns the library.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

D, FF, V, KH, H, HD = 4096, 14336, 128256, 8, 32, 128
SHAPES = [(D, FF), (FF, D), (D, V), (D, 6144)]  # w1/w3, w2, wcls, fused qkv
TP_VOCAB = [V // 4, V // 8]  # 32064, 16032: no multiple of 128 divides them


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology compile can be written to the persistent cache
    # but never read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp4(topo):
    return Mesh(np.asarray(topo.devices[:4]).reshape(1, 4), ("dp", "tp"))


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def compiled_text(fn, *args) -> str:
    # conftest.py asks for "highest" matmul precision (f32 oracles on the
    # CPU); the serving path runs at the default, and Mosaic refuses an f32
    # contraction over the kernels' bf16 operands
    with jax.default_matmul_precision("default"):
        text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def q40_args(m, k, n, s):
    return (
        sds((m, k), jnp.bfloat16, s),
        sds((k, n), jnp.int8, s),
        sds((k // 32, n), jnp.float32, s),
    )


@pytest.mark.parametrize("m", [1, 128])
@pytest.mark.parametrize("k,n", SHAPES)
def test_qmatmul_q40(one_chip, m, k, n):
    from dllama_tpu.ops.quant_matmul import qmatmul_2d

    compiled_text(qmatmul_2d, *q40_args(m, k, n, one_chip))


@pytest.mark.parametrize("m", [1, 128])
@pytest.mark.parametrize("n", TP_VOCAB)
def test_lm_head_per_shard_vocab(one_chip, m, n):
    """The tp=4 / tp=8 lm head: a per-shard vocab no 128-multiple divides
    runs on a ragged grid instead of one 126 MB whole-axis block."""
    from dllama_tpu.ops.quant_matmul import qmatmul_2d

    compiled_text(qmatmul_2d, *q40_args(m, D, n, one_chip))


@pytest.mark.parametrize("k,n", [(D, 2 * FF), (FF, D)])
def test_qmatmul_prefill_rows(one_chip, k, n):
    """A 512-token chunk over four lanes is 2048 rows: tiled by BLOCK_M, not
    one activation block the size of VMEM."""
    from dllama_tpu.ops.quant_matmul import qmatmul_2d

    compiled_text(qmatmul_2d, *q40_args(2048, k, n, one_chip))


def q40_stack(n_layers, k, n, s, packed=False):
    from dllama_tpu.ops.quant_matmul import PackedQuantWeight, QuantWeight

    scales = sds((n_layers, k // 32, n), jnp.float32, s)
    if packed:
        return PackedQuantWeight(sds((n_layers, k // 8, n), jnp.int32, s), scales)
    return QuantWeight(sds((n_layers, k, n), jnp.int8, s), scales)


def weight_copies(text: str) -> list[str]:
    """HLO instructions that would materialise quantized weights: a slice or
    a copy whose result is s8, or s32 of a weight's size (packed words: a
    layer of the smallest stack here is 512 Ki; a program's other int32
    arrays are positions, tokens and a router's 512 rows x 128 experts, 64
    Ki at most). (A `bitcast`, a merge of leading axes, moves nothing.)"""
    import math
    import re

    found = []
    for line in text.splitlines():
        if not any(op in line for op in ("dynamic-slice(", " copy(", " slice(")):
            continue
        result = re.match(r"\s*(s8|s32)\[([\d,]*)\]", line.split("=", 1)[-1])
        if result and (
            result[1] == "s8"
            or math.prod(int(d) for d in result[2].split(",") if d) >= 1 << 18
        ):
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("packed", [False, True], ids=["int8", "packed"])
def test_layer_scan_reads_weight_stacks_in_place(one_chip, monkeypatch, packed):
    """A scan over the layer number that closes over Mistral-7B's FFN stacks
    (`s8[32,4096,28672]`, `s8[32,14336,4096]`, or packed `s32[32,512,28672]`,
    `s32[32,1792,4096]`): the kernels take the stacks whole, so the compiled
    loop holds no slice or copy of a quantized weight. As the scan's `xs`
    each layer was copied out (a `dynamic-slice` with an `s8[1,4096,28672]`
    result) before the kernel read it again."""
    from jax import lax

    from dllama_tpu.ops import quant_matmul as qm

    monkeypatch.setattr(qm, "_use_pallas", lambda: True)  # no chip here
    n_layers = 32

    def f(x, w13, w2):
        def step(x, l):
            up = qm.qmatmul(x, w13, l).astype(x.dtype)
            y = qm.qmatmul(up[..., :FF] * up[..., FF:], w2, l)
            return x + y.astype(x.dtype), None

        return lax.scan(step, x, jnp.arange(n_layers, dtype=jnp.int32))[0]

    text = compiled_text(
        jax.jit(f),
        sds((5, 1, D), jnp.bfloat16, one_chip),
        q40_stack(n_layers, D, 2 * FF, one_chip, packed),
        q40_stack(n_layers, FF, D, one_chip, packed),
    )
    assert text.count("tpu_custom_call") == 2
    assert not weight_copies(text), weight_copies(text)


@pytest.mark.parametrize("kernel", ["active", "grouped"])
def test_moe_q40_expert_stacks_in_place(one_chip, kernel):
    """Qwen3-30B-A3B's expert stacks of 12 layers, `s8[12,128,2048,768]`,
    with a layer number: viewed as [L*E, ...] (a bitcast) and indexed by
    offset ids, never a copy of a layer's 128 experts."""
    from jax import lax

    from dllama_tpu.ops import moe_kernel as mk

    n_layers, d, f, e, k = 12, 2048, 768, 128, 8
    n = 16 if kernel == "active" else 8192
    fn = (
        mk.moe_active_experts_q40
        if kernel == "active"
        else mk.moe_grouped_experts_q40
    )
    w13 = (sds((n_layers, e, d, f), jnp.int8, one_chip),
           sds((n_layers, e, d // 32, f), jnp.float32, one_chip))
    w2 = (sds((n_layers, e, f, d), jnp.int8, one_chip),
          sds((n_layers, e, f // 32, d), jnp.float32, one_chip))

    def run(x, w1q, w1d, w2q, w2d, w3q, w3d, ii, ww):
        def step(x, l):
            y = fn(x, w1q, w1d, w2q, w2d, w3q, w3d, ii, ww, l)
            return x + y.astype(x.dtype), None

        return lax.scan(step, x, jnp.arange(n_layers, dtype=jnp.int32))[0]

    text = compiled_text(
        jax.jit(run),
        sds((n, d), jnp.bfloat16, one_chip),
        *w13, *w2, *w13,
        sds((n, k), jnp.int32, one_chip),
        sds((n, k), jnp.float32, one_chip),
    )
    assert not weight_copies(text), weight_copies(text)


# the benchmark's two cache shapes: [L, lanes, KH, 4096 + padding rows, hd]
MISTRAL_CACHE = (32, 5, 8, 4608, 128)
QWEN_CACHE = (12, 16, 4, 4608, 128)


def cache_copies(text: str, cache: tuple[int, ...], dtype: str = "bf16"):
    """HLO instructions that materialise one layer's whole lane cache (a
    `dynamic-slice`, `copy`, `dynamic-update-slice` or fusion whose result
    has a layer's shape) or copy the whole stack. Parameters, tuples and
    bitcasts move nothing; a `dynamic-update-slice` of the STACK is the
    in-place row write."""
    n_layers, *layer = cache
    one = ",".join(map(str, layer))
    results = (f"{dtype}[{one}]", f"{dtype}[1,{one}]")
    stack = f"{dtype}[{n_layers},{one}]"
    moves_nothing = (
        " parameter(", " get-tuple-element(", " bitcast(", " tuple(",
    )
    found = []
    for line in text.splitlines():
        name, _, rhs = line.strip().partition(" = ")
        if not rhs or any(op in line for op in moves_nothing):
            continue
        if rhs.startswith(results) or (
            rhs.startswith(stack) and (" copy(" in rhs or "copy" in name)
        ):
            found.append(line.strip()[:160])
    return found


def mistral_layers(n_layers, s, row=None, col=None, packed=False):
    """Mistral-7B's per-layer leaves as shapes: Q40 stacks and two norms."""
    row, col = row or s, col or s
    f32 = sds((n_layers, D), jnp.float32, s)
    return dict(
        att_norm=f32, ffn_norm=f32,
        wq=q40_stack(n_layers, D, H * HD, row, packed),
        wk=q40_stack(n_layers, D, KH * HD, row, packed),
        wv=q40_stack(n_layers, D, KH * HD, row, packed),
        wo=q40_stack(n_layers, H * HD, D, col, packed),
        w1=q40_stack(n_layers, D, FF, row, packed),
        w3=q40_stack(n_layers, D, FF, row, packed),
        w2=q40_stack(n_layers, FF, D, col, packed),
    )


def qwen_moe_layers(n_layers, s):
    """Qwen3-30B-A3B's: 32/4 heads of 128 with q/k norm, 128 experts of 768."""
    from dllama_tpu.ops.quant_matmul import QuantWeight

    d, f, e = 2048, 768, 128

    def experts(k, n):
        return QuantWeight(
            sds((n_layers, e, k, n), jnp.int8, s),
            sds((n_layers, e, k // 32, n), jnp.float32, s),
        )

    return dict(
        att_norm=sds((n_layers, d), jnp.float32, s),
        ffn_norm=sds((n_layers, d), jnp.float32, s),
        q_norm=sds((n_layers, HD), jnp.float32, s),
        k_norm=sds((n_layers, HD), jnp.float32, s),
        wq=q40_stack(n_layers, d, 32 * HD, s),
        wk=q40_stack(n_layers, d, 4 * HD, s),
        wv=q40_stack(n_layers, d, 4 * HD, s),
        wo=q40_stack(n_layers, 32 * HD, d, s),
        moe_gate=sds((n_layers, d, e), jnp.float32, s),
        w1=experts(d, f), w3=experts(d, f), w2=experts(f, d),
    )


def layer_scan_text(header, layers, cache, t, window, s, cache_s=None, mesh=None):
    """`run_layers` over `t` rows a lane at per-lane positions, the caches
    donated as the engine's programs donate them, compiled for the chip. A
    chunk (`t` > 1) is the engine's `lane_prefill`: one admitted lane."""
    from dllama_tpu.models import transformer as tf

    n_lanes, hd = cache[1], cache[4]
    kv = sds(cache, jnp.bfloat16, cache_s or s)

    def step(x, layers, k, v, pos, cos, sin):
        return tf.run_layers(
            x, layers, k, v, header, pos, pos, cos, sin,
            mesh=mesh, attn_window=window, live_lanes_alone=t > 1,
        )

    return compiled_text(
        jax.jit(step, donate_argnums=(2, 3)),
        sds((n_lanes, t, header.dim), jnp.bfloat16, s),
        layers, kv, kv,
        sds((n_lanes,), jnp.int32, s),
        sds((n_lanes, t, hd // 2), jnp.float32, s),
        sds((n_lanes, t, hd // 2), jnp.float32, s),
    )


def mistral_header():
    from dllama_tpu.formats.model_file import LlmArch, LlmHeader, RopeType

    return LlmHeader(
        arch=LlmArch.LLAMA, dim=D, hidden_dim=FF, n_layers=32, n_heads=H,
        n_kv_heads=KH, vocab_size=32768, seq_len=4096, head_dim=HD,
        rope_type=RopeType.LLAMA,
    )


def qwen_moe_header():
    from dllama_tpu.formats.model_file import LlmArch, LlmHeader, RopeType

    return LlmHeader(
        arch=LlmArch.QWEN3_MOE, dim=2048, hidden_dim=6144,
        moe_hidden_dim=768, n_layers=12, n_heads=32, n_kv_heads=4,
        n_experts=128, n_active_experts=8, vocab_size=151936,
        seq_len=4096, head_dim=HD, rope_type=RopeType.FALCON,
        norm_epsilon=1e-6,
    )


_SCAN_TEXTS: dict = {}  # a lane program is compiled once for the tests that read it


def scan_text_of(model, rows, window, s, monkeypatch) -> str:
    # the program asks the backend which branches to take; no chip here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if (model, rows, window) not in _SCAN_TEXTS:
        header, cache, layers = (
            (mistral_header(), MISTRAL_CACHE,
             mistral_layers(32, s, packed=model == "mistral-packed"))
            if model.startswith("mistral")
            else (qwen_moe_header(), QWEN_CACHE, qwen_moe_layers(12, s))
        )
        _SCAN_TEXTS[model, rows, window] = layer_scan_text(
            header, layers, cache, rows, window, s)
    return _SCAN_TEXTS[model, rows, window]


LANE_PROGRAMS = pytest.mark.parametrize(
    "rows,window", [(1, 1024), (512, 2048)], ids=["decode", "prefill"])


@LANE_PROGRAMS
def test_one_chip_reads_each_distinct_expert_once(one_chip, monkeypatch, rows, window):
    """Qwen3-30B-A3B's decode step and 512-row chunk over 16 lanes on one
    chip: every expert layer is `moe_held_experts_q40`, whose grid stops
    behind the distinct experts the live rows touched; the kernel that reads
    an expert a (token, choice) pair and the one over a static grid of every
    row's pairs are for a mesh of more than one device
    (`test_moe_q40_expert_stacks_tp4`)."""
    text = scan_text_of("qwen3moe", rows, window, one_chip, monkeypatch)
    # the custom calls by the jitted function that made them (the text also
    # holds a table of every Python frame its traces passed through)
    kernels = {
        line.split(" = ")[0].strip().lstrip("%").rsplit(".", 1)[0]
        for line in text.splitlines() if "tpu_custom_call" in line and " = " in line
    }
    assert "moe_held_experts_q40" in kernels, kernels
    assert not {"moe_active_experts_q40", "moe_grouped_experts_q40"} & kernels, kernels


def test_chunk_program_sorts_one_lanes_pairs(one_chip, monkeypatch):
    """Qwen3-30B-A3B's `lane_prefill` program, 16 lanes x 512 rows with one
    lane admitted: the expert block takes that lane's rows, so the held
    kernel's sorted operand (`x_sorted`) and its output are `bucket x k` =
    4096 rows of 2048, and nothing in the program is shaped by the 65536
    pairs of every lane's rows: no sort, gather, `where` or scatter-add over
    them. The decode block and the tp=4 programs are as they were
    (`test_one_chip_reads_each_distinct_expert_once[decode]`, the `tp4`
    tests)."""
    import re

    lanes, bucket, k, d = QWEN_CACHE[1], 512, 8, 2048
    text = scan_text_of("qwen3moe", bucket, 2048, one_chip, monkeypatch)
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "moe_held_experts_q40" in line.split(" = ")[0]]
    assert calls
    for line in calls:
        assert f"bf16[{bucket * k},{d}]" in line, line[:300]  # x_sorted
        assert line.split(" = ")[1].startswith(f"f32[{bucket * k},{d}]"), line[:300]
    padded = lanes * bucket * k
    shaped = re.findall(rf"\w+\[{padded}(?:,\d+)*\]", text)
    assert not shaped, sorted(set(shaped))


@LANE_PROGRAMS
def test_packed_lane_programs_read_weight_stacks_in_place(
    one_chip, monkeypatch, rows, window
):
    """`mistral-7b-v0.3` as `--weight-format auto` serves it: the decode
    step of five lanes and the 512-row chunk program over packed stacks
    (`s32[32,512,4096]` ...): seven kernel calls a layer, the cache rows
    written in place as over int8 stacks, and no slice or copy of a packed
    weight anywhere in the compiled loop."""
    text = scan_text_of("mistral-packed", rows, window, one_chip, monkeypatch)
    assert "s32[32,512,4096]" in text and " s8[" not in text
    assert not weight_copies(text), weight_copies(text)
    assert not cache_copies(text, MISTRAL_CACHE), cache_copies(text, MISTRAL_CACHE)
    kernels = [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and "qmatmul_i4_2d" in line.split(" = ")[0]
    ]
    assert len(kernels) == 7, len(kernels)


@LANE_PROGRAMS
@pytest.mark.parametrize("model", ["mistral", "qwen3moe"])
def test_layer_scan_writes_cache_rows_in_place(
    one_chip, monkeypatch, model, rows, window
):
    """A decode step and a 512-row prefill chunk of the benchmark's two
    configurations over their lane caches (`bf16[32,5,8,4608,128]`,
    `bf16[12,16,4,4608,128]`): the scan carries the stacks, `kv_write` is a
    `dynamic-update-slice` of the stack by the chunk's rows, decode
    attention slices its window inside the dot's fusion and the flash kernel
    takes the stack and a layer number. So nothing in the compiled loop has
    one layer's whole cache as its result, and the stack is never copied."""
    cache = MISTRAL_CACHE if model == "mistral" else QWEN_CACHE
    text = scan_text_of(model, rows, window, one_chip, monkeypatch)
    assert text.count("dynamic-update-slice(") >= 2  # K and V rows
    assert not cache_copies(text, cache), cache_copies(text, cache)


# Trinity-Large's cut: 2 full layers at 16384 + 512 rows, 7 window layers as a
# ring of 4096 + 512 between 512 spare rows on either side, 8 lanes
TRINITY_FULL = (2, 8, 8, 16896, 128)
TRINITY_RING = (7, 8, 8, 5632, 128)


def trinity_layers(s):
    """Trinity-Large-Preview's per-layer leaves as the loader stacks them:
    attention over all 9 layers (q, k, v and the gate fused), the leading
    dense layer's FFN apart, and 8 expert layers of a shared expert and the
    32 experts held of 256."""
    from dllama_tpu.ops.quant_matmul import FusedQuantWeight, QuantWeight

    d, f, fd, e, n, ns = 3072, 3072, 12288, 32, 9, 8

    def f32(*shape):
        return sds(shape, jnp.float32, s)

    def fused(layers, k, dims):
        return FusedQuantWeight(q40_stack(layers, k, sum(dims), s), 1, tuple(dims))

    def experts(k, width):
        return QuantWeight(sds((ns, e, k, width), jnp.int8, s),
                           sds((ns, e, k // 32, width), jnp.float32, s))

    return dict(
        att_norm=f32(n, d), ffn_norm=f32(n, d), post_att_norm=f32(n, d),
        post_ffn_norm=f32(n, d), q_norm=f32(n, HD), k_norm=f32(n, HD),
        wqkv=fused(n, d, (48 * HD, 8 * HD, 8 * HD, 48 * HD)),
        wo=q40_stack(n, 48 * HD, d, s),
        dense_w13=fused(1, d, (fd, fd)), dense_w2=q40_stack(1, fd, d, s),
        moe_gate=f32(ns, d, 256), expert_bias=f32(ns, 256),
        shared_w13=fused(ns, d, (f, f)), shared_w2=q40_stack(ns, f, d, s),
        w1=experts(d, f), w3=experts(d, f), w2=experts(f, d),
    )


@pytest.mark.parametrize("rows,window", [(1, 8192), (512, 8192)],
                         ids=["decode", "prefill"])
def test_layer_scan_writes_both_cache_stacks_in_place(one_chip, monkeypatch, rows, window):
    """Trinity's cut at its cell's size: window and full layers in one scan
    over two cache stacks. Each stack is written by `dynamic-update-slice`
    of the chunk's rows (the kind that a layer is not of, at its spare
    rows), attention reads under a conditional that hands no stack through,
    a chunk that wraps the ring is two such writes: so no instruction has a
    layer of either stack as its result, and neither stack is copied or
    kept in another layout. (A conditional around the writes copied the
    other kind's stack whole; a scatter of the ring's rows, and a rolled
    read-modify-write, made the compiler transpose the ring stack twice a
    layer.) And the held experts' kernel, whose grid is as long as the
    pairs that landed here, compiles."""
    from dllama_tpu.formats.model_file import LlmArch, LlmHeader, RopeType
    from dllama_tpu.models import transformer as tf

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    s = one_chip
    h = LlmHeader(
        arch=LlmArch.AFMOE, dim=3072, hidden_dim=12288, moe_hidden_dim=3072,
        n_layers=9, n_heads=48, n_kv_heads=8, n_experts=32, n_active_experts=4,
        vocab_size=25024, seq_len=16384, head_dim=HD, rope_type=RopeType.FALCON,
        sliding_window=4096, full_attn_period=4, full_attn_no_rope=True,
        n_dense_layers=1, n_shared_experts=1, score_sigmoid=True,
        route_scale=2.448, n_routed_experts=256, embed_scale=True,
    )
    lanes = TRINITY_FULL[1]

    def step(x, layers, k, v, kw, vw, pos, cos, sin):
        counts = []
        out = tf.run_layers(
            x, layers, k, v, h, pos, jnp.where(pos >= 16384, -16896, pos), cos, sin,
            attn_window=window, kw_cache=kw, vw_cache=vw, kv_ring=4608,
            route_stats=counts, live_lanes_alone=rows > 1,
        )
        return out, counts

    text = compiled_text(
        jax.jit(step, donate_argnums=(2, 3, 4, 5)),
        sds((lanes, rows, 3072), jnp.bfloat16, s), trinity_layers(s),
        sds(TRINITY_FULL, jnp.bfloat16, s), sds(TRINITY_FULL, jnp.bfloat16, s),
        sds(TRINITY_RING, jnp.bfloat16, s), sds(TRINITY_RING, jnp.bfloat16, s),
        sds((lanes,), jnp.int32, s),
        sds((lanes, rows, HD // 2), jnp.float32, s),
        sds((lanes, rows, HD // 2), jnp.float32, s),
    )
    assert text.count("dynamic-update-slice(") >= 4  # K and V rows of two stacks
    for stack in (TRINITY_FULL, TRINITY_RING):
        assert not cache_copies(text, stack), cache_copies(text, stack)
        shape = ",".join(map(str, stack))
        assert f"bf16[{shape}]{{3,4" not in text  # nor kept in a transposed layout
    assert "moe_held_experts_q40" in text and " conditional(" in text


# openPangu-Ultra-MoE's cut: 5 latent layers at 16384 + 512 rows of 576, 4 lanes
# (the context ISSUE 34's first set of sizes asked for; its cell runs 8192)
PANGU_LATENT = (5, 4, 1, 16896, 576)


def pangu_layers(s):
    """openPangu-Ultra-MoE's per-layer leaves as the loader stacks them:
    the latent projections over all 5 layers (`wkv_b` as two per-head bf16
    stacks), the leading dense layer's FFN apart, and 4 expert layers of a
    shared expert and the 32 experts held of 256."""
    from dllama_tpu.ops.quant_matmul import FusedQuantWeight, QuantWeight

    d, f, fd, e, n, ns, heads = 7680, 2048, 18432, 32, 5, 4, 128

    def f32(*shape):
        return sds(shape, jnp.float32, s)

    def fused(layers, k, dims):
        return FusedQuantWeight(q40_stack(layers, k, sum(dims), s), 1, tuple(dims))

    def experts(k, width):
        return QuantWeight(sds((ns, e, k, width), jnp.int8, s),
                           sds((ns, e, k // 32, width), jnp.float32, s))

    return dict(
        att_norm=f32(n, d), ffn_norm=f32(n, d), post_att_norm=f32(n, d),
        post_ffn_norm=f32(n, d), q_a_norm=f32(n, 1536), kv_a_norm=f32(n, 512),
        wq_a=q40_stack(n, d, 1536, s), wq_b=q40_stack(n, 1536, heads * 192, s),
        wkv_a=q40_stack(n, d, 576, s), wo=q40_stack(n, heads * 128, d, s),
        wkv_b_k=sds((n, heads, 128, 512), jnp.bfloat16, s),
        wkv_b_v=sds((n, heads, 512, 128), jnp.bfloat16, s),
        dense_w13=fused(1, d, (fd, fd)), dense_w2=q40_stack(1, fd, d, s),
        moe_gate=f32(ns, d, 256),
        shared_w13=fused(ns, d, (f, f)), shared_w2=q40_stack(ns, f, d, s),
        w1=experts(d, f), w3=experts(d, f), w2=experts(f, d),
    )


@pytest.mark.parametrize("rows,window", [(1, 16384), (512, 4096), (512, 16384)],
                         ids=["decode", "prefill-4096", "prefill-16384"])
def test_layer_scan_writes_latent_rows_in_place(one_chip, monkeypatch, rows, window):
    """openPangu-Ultra-MoE's cut at twice its cell's context: latent attention
    over one cache stack of 576-wide rows. A layer's row is one
    `dynamic-update-slice` a lane into the carried stack, a chunk's
    attention is the latent kernel over the stack where it lies and a decode
    step's a slice of the window's rows: no instruction has a layer of the
    stack as its result and the stack is never copied; no per-head key or
    value of the context's length exists."""
    from dllama_tpu.formats.model_file import LlmArch, LlmHeader, RopeType
    from dllama_tpu.models import transformer as tf

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    s = one_chip
    h = LlmHeader(
        arch=LlmArch.PANGU_MOE, dim=7680, hidden_dim=18432, moe_hidden_dim=2048,
        n_layers=5, n_heads=128, n_kv_heads=128, n_experts=32, n_active_experts=8,
        vocab_size=19200, seq_len=16384, head_dim=192, rope_type=RopeType.FALCON,
        n_dense_layers=1, n_shared_experts=1, score_sigmoid=True, route_scale=2.5,
        n_routed_experts=256, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    )
    lanes = PANGU_LATENT[1]

    def step(x, layers, c, pos, cos, sin):
        counts = []
        out = tf.run_layers(
            x, layers, None, None, h, pos, jnp.where(pos >= 16384, -16896, pos),
            cos, sin, attn_window=window, c_cache=c, route_stats=counts,
            live_lanes_alone=rows > 1,
        )
        return out, counts

    text = compiled_text(
        jax.jit(step, donate_argnums=(2,)),
        sds((lanes, rows, 7680), jnp.bfloat16, s), pangu_layers(s),
        sds(PANGU_LATENT, jnp.bfloat16, s), sds((lanes,), jnp.int32, s),
        sds((lanes, rows, 32), jnp.float32, s), sds((lanes, rows, 32), jnp.float32, s),
    )
    assert text.count("dynamic-update-slice(") >= 1
    assert not cache_copies(text, PANGU_LATENT), cache_copies(text, PANGU_LATENT)
    assert "moe_held_experts_q40" in text
    assert ("latent_flash_attention" in text) == (rows > 1)
    # nothing of a context's length per head: 128 heads x rows x 192 or 128
    assert f"bf16[{lanes},128,{window}," not in text and f",{window},128,1" not in text


# DeepSeek-V3.2's cut: 5 latent layers at 8192 + 512 rows, 4 lanes, and the
# index keys' stack beside the latent rows
DSV32_LATENT = (5, 4, 1, 8704, 576)
DSV32_INDEX = (5, 4, 1, 8704, 128)


def dsv32_layers(s):
    """DeepSeek-V3.2's per-layer leaves as the loader stacks them: the
    latent projections and the index's over all 5 layers, the leading dense
    layer's FFN apart, and 4 expert layers of a router with its selection
    bias, a shared expert and the 32 experts held of 256. No post-norms."""
    from dllama_tpu.ops.quant_matmul import FusedQuantWeight, QuantWeight

    d, f, fd, e, n, ns, heads = 7168, 2048, 18432, 32, 5, 4, 128

    def f32(*shape):
        return sds(shape, jnp.float32, s)

    def fused(layers, k, dims):
        return FusedQuantWeight(q40_stack(layers, k, sum(dims), s), 1, tuple(dims))

    def experts(k, width):
        return QuantWeight(sds((ns, e, k, width), jnp.int8, s),
                           sds((ns, e, k // 32, width), jnp.float32, s))

    return dict(
        att_norm=f32(n, d), ffn_norm=f32(n, d), q_a_norm=f32(n, 1536),
        kv_a_norm=f32(n, 512),
        wq_a=q40_stack(n, d, 1536, s), wq_b=q40_stack(n, 1536, heads * 192, s),
        wkv_a=q40_stack(n, d, 576, s), wo=q40_stack(n, heads * 128, d, s),
        wkv_b_k=sds((n, heads, 128, 512), jnp.bfloat16, s),
        wkv_b_v=sds((n, heads, 512, 128), jnp.bfloat16, s),
        idx_wq_b=q40_stack(n, 1536, 64 * 128, s), idx_wk=q40_stack(n, d, 128, s),
        idx_k_norm=f32(n, 128), idx_k_bias=f32(n, 128), idx_w=f32(n, d, 64),
        dense_w13=fused(1, d, (fd, fd)), dense_w2=q40_stack(1, fd, d, s),
        moe_gate=f32(ns, d, 256), expert_bias=f32(ns, 256),
        shared_w13=fused(ns, d, (f, f)), shared_w2=q40_stack(ns, f, d, s),
        w1=experts(d, f), w3=experts(d, f), w2=experts(f, d),
    )


@pytest.mark.parametrize("rows,window", [(1, 8192), (512, 4096), (512, 8192)],
                         ids=["decode", "prefill-4096", "prefill-8192"])
def test_layer_scan_writes_latent_rows_and_index_keys_in_place(
        one_chip, monkeypatch, rows, window):
    """DeepSeek-V3.2's cut at its cell's context: latent attention over the
    rows an index picks, two cache stacks under one position. A layer's
    latent row and its index key are each one `dynamic-update-slice` a lane
    into the carried stacks; the index reads a lane's keys by one slice of
    the window's rows, the selection is a mask and gathers nothing, a
    chunk's attention is the latent kernel over the stack where it lies
    with the mask beside it: neither stack is ever copied whole, and no
    instruction has a layer of one as its result."""
    from dllama_tpu.formats.model_file import LlmArch, LlmHeader, RopeType
    from dllama_tpu.models import transformer as tf

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    s = one_chip
    h = LlmHeader(
        arch=LlmArch.DEEPSEEK_V32, dim=7168, hidden_dim=18432, moe_hidden_dim=2048,
        n_layers=5, n_heads=128, n_kv_heads=128, n_experts=32, n_active_experts=8,
        vocab_size=16160, seq_len=8192, head_dim=192, rope_type=RopeType.YARN,
        rope_scaling_factor=40.0, rope_scaling_orig_max_seq_len=4096,
        rope_mscale=1.0, rope_mscale_all_dim=1.0, norm_epsilon=1e-6,
        n_dense_layers=1, n_shared_experts=1, score_sigmoid=True, route_scale=2.5,
        n_routed_experts=256, n_group=8, topk_group=4, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        index_n_heads=64, index_head_dim=128, index_topk=2048,
    )
    lanes = DSV32_LATENT[1]

    def step(x, layers, c, i, pos, cos, sin):
        counts = []
        out = tf.run_layers(
            x, layers, None, None, h, pos, jnp.where(pos >= 8192, -8704, pos),
            cos, sin, attn_window=window, c_cache=c, i_cache=i, route_stats=counts,
            live_lanes_alone=rows > 1,
        )
        return out, counts

    text = compiled_text(
        jax.jit(step, donate_argnums=(2, 3)),
        sds((lanes, rows, 7168), jnp.bfloat16, s), dsv32_layers(s),
        sds(DSV32_LATENT, jnp.bfloat16, s), sds(DSV32_INDEX, jnp.bfloat16, s),
        sds((lanes,), jnp.int32, s),
        sds((lanes, rows, 32), jnp.float32, s), sds((lanes, rows, 32), jnp.float32, s),
    )
    assert text.count("dynamic-update-slice(") >= 2
    for stack in (DSV32_LATENT, DSV32_INDEX):
        assert not cache_copies(text, stack), cache_copies(text, stack)
    assert "moe_held_experts_q40" in text
    assert ("latent_flash_attention" in text) == (rows > 1)
    # the selection counts and compares; the router's top-k is the one sort
    assert "index_select" in text and "index_score" in text
    assert not [ln for ln in text.splitlines() if " sort(" in ln and "/index_" in ln]
    assert f"bf16[{lanes},128,{window}," not in text and f",{window},128,1" not in text
    if rows == 1:
        # the held eighth of the vocabulary, 16160 rows: no multiple of 128 divides it
        from dllama_tpu.ops.quant_matmul import qmatmul_2d

        compiled_text(qmatmul_2d, *q40_args(lanes, 7168, 16160, s))


# LFM2-24B-A2B's cut: 10 attention layers at 4096 + 512 rows, 30 convolution
# layers' states of 2 rows of 2048, 16 lanes
LFM2_CACHE = (10, 16, 4, 4608, 128)  # two heads of 64 to a row
LFM2_STATE = (30, 16, 2, 2048)
LFM2_TYPES = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9 + [
    "full_attention", "conv"]


def expert_block_forms(h, rows: int, packed: bool) -> int:
    """The forms a program's expert block compiles of `moe_held_experts_q40`
    over one lane's `rows`: 2 (under one conditional) where the landed form
    saves rows enough at that count of pairs and held share, else 1."""
    from dllama_tpu.ops import moe_kernel as mk

    pairs = rows * h.n_active_experts
    cap = mk._landed_cap(pairs, mk._held_rows(pairs, packed), h.n_experts, h.n_routed_experts)
    return 2 if cap < pairs else 1


def only_the_expert_block_branches(text: str, forms: int) -> bool:
    """Every conditional of a compiled program holds one landed form of the
    held kernel (none where the expert block compiles one form)."""
    landed = [line for line in text.splitlines() if "tpu_custom_call" in line
              and "moe_held_experts_q40_landed" in line.split(" = ")[0]]
    return text.count(" conditional(") == len(landed) and bool(landed) == (forms == 2)


def lfm2_header(published: bool):
    """LFM2-24B-A2B as one of four chips, or the tests' tiny widths with the
    same pattern over 12 layers."""
    from dllama_tpu.formats.model_file import LlmArch, LlmHeader, RopeType

    types = LFM2_TYPES if published else LFM2_TYPES[:10] + LFM2_TYPES[-2:]
    sizes = dict(
        dim=2048, hidden_dim=11776, moe_hidden_dim=1536, n_heads=32, n_kv_heads=8,
        n_experts=16, n_routed_experts=64, n_active_experts=4, vocab_size=16384,
    ) if published else dict(
        dim=256, hidden_dim=512, moe_hidden_dim=256, n_heads=4, n_kv_heads=2,
        n_experts=4, n_routed_experts=8, n_active_experts=2, vocab_size=512,
    )
    return LlmHeader(
        arch=LlmArch.LFM2_MOE, n_layers=len(types), seq_len=4096, head_dim=64,
        rope_type=RopeType.FALCON, n_dense_layers=2, score_sigmoid=True,
        conv_l_cache=3,
        attn_layers=sum(1 << l for l, t in enumerate(types) if t == "full_attention"),
        **sizes,
    )


def lfm2_layers(h, s):
    """The leaves as the loader stacks them: each operator's over the layers
    of its kind, the dense layers' FFN apart, the held experts' stacks."""
    from dllama_tpu.formats.model_file import layer_table
    from dllama_tpu.ops.quant_matmul import FusedQuantWeight, QuantWeight

    table = layer_table(h)
    n, nc = h.n_layers, sum(k.conv for k in table)
    na, ns = n - nc, n - h.n_dense_layers
    d, f, fd, e = h.dim, h.moe_hidden_dim, h.hidden_dim, h.n_experts

    def f32(*shape):
        return sds(shape, jnp.float32, s)

    def fused(layers, k, dims):
        return FusedQuantWeight(q40_stack(layers, k, sum(dims), s), 1, tuple(dims))

    def experts(k, width):
        return QuantWeight(sds((ns, e, k, width), jnp.int8, s),
                           sds((ns, e, k // 32, width), jnp.float32, s))

    return dict(
        att_norm=f32(n, d), ffn_norm=f32(n, d), q_norm=f32(na, 64), k_norm=f32(na, 64),
        wqkv=fused(na, d, (h.q_dim, h.kv_dim, h.kv_dim)), wo=q40_stack(na, h.q_dim, d, s),
        conv_in=q40_stack(nc, d, 3 * d, s), conv_out=q40_stack(nc, d, d, s),
        conv_w=f32(nc, 3, d),
        dense_w13=fused(2, d, (fd, fd)), dense_w2=q40_stack(2, fd, d, s),
        moe_gate=f32(ns, d, h.n_routed_experts), expert_bias=f32(ns, h.n_routed_experts),
        w1=experts(d, f), w3=experts(d, f), w2=experts(f, d),
    )


@pytest.mark.parametrize(
    "rows,window", [(1, 1024), (512, 2048), (256, 1024), (128, 4096)],
    ids=["decode", "prefill", "prefill256", "prefill128"])  # the ladder's middle rungs: PR 49
@pytest.mark.parametrize("published", [True, False], ids=["published", "tiny"])
def test_layer_scan_carries_lane_state_beside_a_cache_of_ten_layers(
        one_chip, monkeypatch, published, rows, window):
    """LFM2-24B-A2B's decode step and 512-row chunk at its cell's size, and
    at the tests' tiny widths: the scan carries the cache stack of the 10
    attention layers and the state stack of the 30 convolution layers, each
    written in place by `dynamic-update-slice` (a chunk's rows; a lane's two
    rows), no layer of either is copied out, no branch is taken on the
    device over the layer pattern (a chunk's sparse layers branch over their
    expert block's two forms, and nothing else does), and the chunk
    program's convolution layers compute the admitted lane's 512 rows, not
    the 8192 of all."""
    from dllama_tpu.formats.model_file import layer_table
    from dllama_tpu.models import transformer as tf

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    s = one_chip
    h = lfm2_header(published)
    table = layer_table(h)
    nc = sum(k.conv for k in table)
    lanes = 16
    cache = (h.n_layers - nc, lanes, h.n_kv_heads // h.kv_pack, 4608, 64 * h.kv_pack)
    state = (nc, lanes, 2, h.dim)
    assert cache == LFM2_CACHE or not published

    def step(x, layers, k, v, st, pos, cos, sin, aux):
        counts = []
        live = pos < 4096
        out = tf.run_layers(
            x, layers, k, v, h, pos, jnp.where(live, pos, -4608), cos, sin,
            attn_window=window, route_stats=counts, live_lanes_alone=rows > 1,
            s_cache=st, state_rows=jnp.where(live, aux[0], 0),
            write_floor=aux[1] if rows > 1 else None,  # a chunk program's alone
            state_fresh=jnp.logical_and(live, aux[2] > 0),
        )
        return out, counts

    text = compiled_text(
        jax.jit(step, donate_argnums=(2, 3, 4)),
        sds((lanes, rows, h.dim), jnp.bfloat16, s), lfm2_layers(h, s),
        sds(cache, jnp.bfloat16, s), sds(cache, jnp.bfloat16, s),
        sds(state, jnp.bfloat16, s), sds((lanes,), jnp.int32, s),
        sds((lanes, rows, 32), jnp.float32, s), sds((lanes, rows, 32), jnp.float32, s),
        sds((3,), jnp.int32, s),
    )
    assert "moe_held_experts_q40" in text
    assert only_the_expert_block_branches(text, expert_block_forms(h, rows, packed=False))
    if not published:
        return  # the tiny widths lower: a shape the chip rejects fails here
    assert text.count("dynamic-update-slice(") >= 3  # K rows, V rows, a state
    # the cache stack is written in place and never copied; the state stack
    # (3.9 MB) the compiler may lay out its own way for a block: no matter
    assert not cache_copies(text, cache), cache_copies(text, cache)
    # nor one lane's rows of every layer: the masked write below a floor read
    # a slice a lane once, and the chip rewrote all sixteen a layer (2.1 ms)
    assert f"bf16[{cache[0]},1,{cache[2]},{cache[3]},{cache[4]}]" not in text
    if rows > 1:
        # `in_proj` of a convolution layer over one lane's bucket
        assert f"bf16[{rows},{3 * h.dim}]" in text or f"f32[{rows},{3 * h.dim}]" in text
        assert f"[{lanes * rows},{3 * h.dim}]" not in text


def outside_fusions(text: str) -> str:
    """The HLO text without the computations that fusions call: what is left
    are the instructions whose results are buffers (a line inside a fused
    computation names a value that is never stored)."""
    import re

    fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
    out, skip = [], False
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            skip = line.split(" ")[0] in fused
        if not skip:
            out.append(line)
    return "\n".join(out)


# granite-4.0-h-small's cut: 2 attention layers at 4096 + 512 rows, 18 Mamba-2
# layers' float32 states of 128 x 64 x 128 and 3 rows of 8448, 32 lanes
GRANITE_TYPES = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 2


def granite_header(published: bool):
    """granite-4.0-h-small as one of four chips, or the tests' tiny widths
    with the same pattern."""
    from dllama_tpu.formats.model_file import LlmArch, LlmHeader, RopeType

    sizes = dict(
        dim=4096, hidden_dim=768, n_heads=32, n_kv_heads=8, head_dim=128,
        n_experts=18, n_routed_experts=72, n_active_experts=10, vocab_size=25088,
        ssm_n_heads=128, ssm_head_dim=64, ssm_state_dim=128,
    ) if published else dict(
        dim=256, hidden_dim=256, n_heads=4, n_kv_heads=2, head_dim=128,
        n_experts=4, n_routed_experts=8, n_active_experts=3, vocab_size=512,
        ssm_n_heads=8, ssm_head_dim=64, ssm_state_dim=128,
    )
    return LlmHeader(
        arch=LlmArch.GRANITE_MOE_HYBRID, n_layers=len(GRANITE_TYPES), seq_len=4096,
        rope_type=RopeType.FALCON, full_attn_no_rope=True, n_shared_experts=2,
        ssm_conv_taps=4, embed_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=1 / 128, logits_scaling=16.0,
        attn_layers=sum(1 << l for l, t in enumerate(GRANITE_TYPES) if t == "attention"),
        **sizes,
    )


def granite_layers(h, s):
    """The leaves as the loader stacks them on one chip: each operator's over
    the layers of its kind, packed words, the held experts' stacks packed."""
    from dllama_tpu.formats.model_file import layer_table
    from dllama_tpu.ops.quant_matmul import FusedQuantWeight, PackedQuantWeight

    n, nm = h.n_layers, sum(k.ssm for k in layer_table(h))
    na, d, f, e = n - nm, h.dim, h.ff_dim, h.n_experts
    inner, conv = h.ssm_inner, h.ssm_conv_dim

    def f32(*shape):
        return sds(shape, jnp.float32, s)

    def packed(layers, k, width):
        return q40_stack(layers, k, width, s, packed=True)

    def experts(k, width):
        return PackedQuantWeight(sds((n, e, k // 8, width), jnp.int32, s),
                                 sds((n, e, k // 32, width), jnp.float32, s))

    return dict(
        att_norm=f32(n, d), ffn_norm=f32(n, d),
        wqkv=FusedQuantWeight(packed(na, d, h.q_dim + 2 * h.kv_dim), 1,
                              (h.q_dim, h.kv_dim, h.kv_dim)),
        wo=packed(na, h.q_dim, d),
        ssm_in=packed(nm, d, inner + conv + h.ssm_n_heads), ssm_out=packed(nm, inner, d),
        ssm_conv_w=f32(nm, 4, conv), ssm_conv_b=f32(nm, conv), ssm_norm=f32(nm, inner),
        ssm_dt_bias=f32(nm, h.ssm_n_heads), ssm_a_log=f32(nm, h.ssm_n_heads),
        ssm_d=f32(nm, h.ssm_n_heads),
        moe_gate=f32(n, d, h.n_routed_experts),
        shared_w13=FusedQuantWeight(packed(n, d, 4 * f), 1, (2 * f, 2 * f)),
        shared_w2=packed(n, 2 * f, d),
        w1=experts(d, f), w3=experts(d, f), w2=experts(f, d),
    )


@pytest.mark.parametrize(
    "rows,window", [(1, 1024), (512, 2048), (256, 1024), (64, 512), (128, 4096)],
    ids=["decode", "prefill512", "prefill256", "prefill64", "prefill128"])
@pytest.mark.parametrize("published", [True, False], ids=["published", "tiny"])
def test_layer_scan_carries_a_recurrent_state_beside_a_cache_of_two_layers(
        one_chip, monkeypatch, published, rows, window):
    """granite-4.0-h-small's decode step and its chunk programs (two blocks of
    the recurrence's 256 rows, one, a quarter of one) at its cell's size, and
    at the tests' tiny widths: the scan carries the cache stack of the 2
    attention layers, the 18 Mamba-2 layers' convolution rows and their 2.4 GB
    of float32 recurrent states, each written in place by
    `dynamic-update-slice`; no copy of the recurrent stack, or of one layer's
    lanes of it, is made; no branch is taken on the device over the layer
    pattern (a chunk's sparse layers branch over their expert block's two
    forms, and nothing else does); and a chunk program's mixers compute the
    admitted lane's rows, not those of all 32."""
    from dllama_tpu.formats.model_file import layer_table
    from dllama_tpu.models import transformer as tf

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    s = one_chip
    h = granite_header(published)
    nm = sum(k.ssm for k in layer_table(h))
    lanes = 32
    cache = (h.n_layers - nm, lanes, h.n_kv_heads, 4608, 128)
    conv = (nm, lanes, 3, h.ssm_conv_dim)
    state = (nm, lanes, 128, h.ssm_inner)

    def step(x, layers, k, v, st, rec, pos, cos, sin, aux):
        counts = []
        live = pos < 4096
        out = tf.run_layers(
            x, layers, k, v, h, pos, jnp.where(live, pos, -4608), cos, sin,
            attn_window=window, route_stats=counts, live_lanes_alone=rows > 1,
            s_cache=st, r_cache=rec, state_rows=jnp.where(live, aux[0], 0),
            write_floor=aux[1] if rows > 1 else None,
            state_fresh=jnp.logical_and(live, aux[2] > 0),
        )
        return out, counts

    text = compiled_text(
        jax.jit(step, donate_argnums=(2, 3, 4, 5)),
        sds((lanes, rows, h.dim), jnp.bfloat16, s), granite_layers(h, s),
        sds(cache, jnp.bfloat16, s), sds(cache, jnp.bfloat16, s),
        sds(conv, jnp.bfloat16, s), sds(state, jnp.float32, s),
        sds((lanes,), jnp.int32, s),
        sds((lanes, rows, 64), jnp.float32, s), sds((lanes, rows, 64), jnp.float32, s),
        sds((3,), jnp.int32, s),
    )
    assert "moe_held_experts_q40" in text
    assert only_the_expert_block_branches(text, expert_block_forms(h, rows, packed=True))
    if not published:
        return  # the tiny widths lower: a shape the chip rejects fails here
    assert text.count("dynamic-update-slice(") >= 4  # K rows, V rows, both states
    assert not cache_copies(text, cache), cache_copies(text, cache)
    # the recurrent stack (2.4 GB) is the scan's carry, updated where it lies:
    # neither the stack nor one layer's 32 lanes of it (134 MB) is copied
    moved = cache_copies(outside_fusions(text), state, "f32")
    assert not moved, moved
    if rows > 1:
        width = h.ssm_inner + h.ssm_conv_dim + h.ssm_n_heads
        assert f"[{rows},{width}]" in text and f"[{lanes * rows},{width}]" not in text


def test_layer_scan_writes_cache_rows_in_place_tp4(tp4, monkeypatch):
    """The same prefill chunk at tp=4: KH is the stack's sharded axis
    (`P(None, "dp", "tp", None, None)` into the flash kernel's `shard_map`,
    the layer number replicated), so a shard's stack is `[32,5,2,4608,128]`
    and no instruction has one layer of it as its result."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rep = NamedSharding(tp4, P())
    text = layer_scan_text(
        mistral_header(),
        mistral_layers(
            32, rep,
            row=NamedSharding(tp4, P(None, None, "tp")),
            col=NamedSharding(tp4, P(None, "tp", None)),
        ),
        MISTRAL_CACHE, 512, 2048, rep,
        cache_s=NamedSharding(tp4, P(None, "dp", "tp", None, None)),
        mesh=tp4,
    )
    shard = (32, 5, KH // 4, 4608, 128)
    assert not cache_copies(text, shard), cache_copies(text, shard)


def hlo_computations(text: str) -> dict[str, list[str]]:
    """An HLO module's computations by name, each with its instructions."""
    comps, lines = {}, None
    for line in text.splitlines():
        if line.endswith("{") and "(" in line and " = " not in line.split("(")[0]:
            lines = comps.setdefault(
                line.split("(")[0].split()[-1].lstrip("%"), [])
        elif lines is not None:
            lines.append(line.strip())
    return comps


def hlo_reach(comps, roots, skip=" conditional(") -> set[str]:
    """Computations reached from `roots` through `calls=`, `to_apply=`,
    `body=`, `condition=` and `branch_computations=`, never through an
    instruction that holds `skip`."""
    import re

    seen, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            if skip and skip in line:
                continue
            for group in re.findall(
                r"(?:calls|to_apply|body|condition|branch_computations)="
                r"\{?([%\w.\-, ]+)\}?", line,
            ):
                todo += [g.strip().lstrip("%") for g in group.split(",")]
    return seen


def test_lane_block_keeps_the_sampler_conditional(one_chip):
    """The engine's own `lane_block` loop at the sparse cell's size (16
    lanes, vocabulary 151936, 8 steps), its forward pass stood in for by
    an embedding row and the logits head: the chip's compiler keeps the
    sampler's `cond` a `conditional` (it has not made it a select that
    runs both sides), the vocabulary sort lies in one of its branches,
    and the step loop's body reaches no sort any other way."""
    import re
    import types

    from dllama_tpu.runtime.engine import InferenceEngine

    lanes, vocab, dim = 16, 151936, 2048

    def fwd(params, tok, cur, cache, **_):
        x = params["emb"][tok[:, 0]]
        cache = cache.at[:, 0].set(x[:, 0] + cur.astype(x.dtype))
        return (x @ params["wcls"]).astype(jnp.float32)[:, None, :], cache

    stand_in = types.SimpleNamespace(
        _precision=None, _fwd=fwd, _park=4096, _counts_routing=False,
        _token_sharding=one_chip,
        header=types.SimpleNamespace(seq_len=4096),
        _build=lambda key, make, specs, origin: make(),
    )
    block = InferenceEngine._lane_decode_fn(stand_in, 8, 1024)
    vec = lambda dtype: sds((lanes,), dtype, one_chip)  # noqa: E731
    with jax.default_matmul_precision("default"):
        text = block.lower(
            dict(emb=sds((vocab, dim), jnp.bfloat16, one_chip),
                 wcls=sds((dim, vocab), jnp.bfloat16, one_chip)),
            sds((lanes, 1), jnp.int32, one_chip),
            sds((lanes, 8), jnp.bfloat16, one_chip),
            vec(jnp.int32), vec(jnp.bool_), vec(jnp.int32),
            vec(jnp.float32), vec(jnp.float32),
            sds((lanes, 1), jnp.int32, one_chip),  # the last block's last tokens
        ).compile().as_text()
    comps = hlo_computations(text)
    conds = [l for lines in comps.values() for l in lines if " conditional(" in l]
    assert len(conds) == 1 and "sample/cond" in conds[0], conds
    branches = re.search(r"branch_computations=\{([^}]*)\}", conds[0]).group(1)
    inside = hlo_reach(comps, [b.strip().lstrip("%") for b in branches.split(",")], skip="")
    sorts = {name for name, lines in comps.items() if any(" sort(" in l for l in lines)}
    assert sorts and sorts <= inside, (sorts, inside)
    (loop,) = [l for lines in comps.values() for l in lines if " while(" in l]
    body = re.search(r"body=%?([\w.\-]+)", loop).group(1)
    outside = hlo_reach(comps, [body])  # the body itself among them
    assert any(conds[0] in comps[c] for c in outside)
    assert not sorts & outside, sorts & outside


@pytest.mark.parametrize("preset", ["mistral-7b-v0.3", "openpangu-ultra-l5-e32"])
def test_rehearsal_schedules_the_cells_programs_and_no_other(preset):
    """`mistral-7b-v0.3` as its cells serve it (five lanes of 4096 positions,
    the ladder of `--nbatches 32`, blocks of 8 steps) and a preset with
    experts, `openpangu-ultra-l5-e32` as `pangu-docqa` serves it (four lanes
    of 8192 latent rows, the default ladder): `rehearse_admission` schedules
    one chunk program a rung at the rung's base window and the decode block,
    under the keys it always had. A chunk program that fills several
    admitting lanes' rows (its expert block a live lane after another, a
    traced count of them) is one of these, or the same rung at a deeper
    window, built as one lane's is when a lane gets there."""
    import types

    from dllama_tpu.runtime.engine import InferenceEngine, prefill_ladder

    dense = preset == "mistral-7b-v0.3"
    scheduled = []
    stand_in = types.SimpleNamespace(
        _require_lanes=lambda: None, _aot_blocks=True, kv_native=False, kv_pool=None,
        _draft_params=None, prefill_buckets=prefill_ladder(32 if dense else 256), sp=1,
        _latent=not dense,
        header=types.SimpleNamespace(seq_len=4096 if dense else 8192, sliding_window=0),
        _prefetch=lambda key, build: scheduled.append(key),
    )
    stand_in._attn_window = lambda limit: InferenceEngine._attn_window(stand_in, limit)
    InferenceEngine.rehearse_admission(stand_in, 8)
    rungs, window = ((1, 32, 128, 256, 512), 512) if dense else ((1, 256, 512), 4096)
    assert scheduled == [("lane_prefill", rung, window) for rung in rungs] + [
        ("lane_block", 8, window)]


def test_cache_copies_flags_the_caches_as_scan_xs(one_chip):
    """The design before PR 29: the layer scan takes the caches as `xs` and
    gives them back as `ys`. Each step then slices the layer's whole cache
    out of the stack and writes it back whole, which is what
    `cache_copies` is there to find."""
    from jax import lax

    n_layers, n_lanes, kh, s, hd = MISTRAL_CACHE

    def old(x, k, v, pos):
        def step(x, layer):
            k_l, v_l = layer
            new = x[:, None, None, :hd]  # a row a lane, [B, 1, 1, hd]
            new = jnp.broadcast_to(new, (n_lanes, kh, 1, hd)).astype(k_l.dtype)
            k_l = lax.dynamic_update_slice_in_dim(k_l, new, pos, axis=2)
            v_l = lax.dynamic_update_slice_in_dim(v_l, new, pos, axis=2)
            att = jnp.einsum("bd,bksd->bd", x[:, :hd], k_l[:, :, :1024])
            att = att + jnp.einsum("bd,bksd->bd", x[:, :hd], v_l[:, :, :1024])
            return x.at[:, :hd].add(att.astype(x.dtype)), (k_l, v_l)

        return lax.scan(step, x, (k, v))

    with jax.default_matmul_precision("default"):
        text = jax.jit(old, donate_argnums=(1, 2)).lower(
            sds((n_lanes, D), jnp.bfloat16, one_chip),
            sds(MISTRAL_CACHE, jnp.bfloat16, one_chip),
            sds(MISTRAL_CACHE, jnp.bfloat16, one_chip),
            sds((), jnp.int32, one_chip),
        ).compile().as_text()
    assert cache_copies(text, MISTRAL_CACHE)


@pytest.mark.parametrize("role", ["row", "col"])
def test_qmatmul_tp4(tp4, monkeypatch, role):
    """The FFN splits at tp=4 under shard_map: the kernel is there per
    shard, the col split pays exactly one all-reduce, the row split none."""
    from dllama_tpu.ops import quant_matmul as qm

    monkeypatch.setattr(qm, "_use_pallas", lambda: True)  # no chip here
    k, n = (D, FF) if role == "row" else (FF, D)
    w_spec = P(None, "tp") if role == "row" else P("tp", None)
    x_spec = P("dp", None, None) if role == "row" else P("dp", None, "tp")

    def f(x, q, d):
        return qm.qmatmul_tp(x, qm.QuantWeight(q, d), role, tp4)

    text = compiled_text(
        jax.jit(f),
        sds((1, 1, k), jnp.bfloat16, NamedSharding(tp4, x_spec)),
        sds((k, n), jnp.int8, NamedSharding(tp4, w_spec)),
        sds((k // 32, n), jnp.float32, NamedSharding(tp4, w_spec)),
    )
    n_reduce = text.count(" all-reduce(") + text.count(" all-reduce-start(")
    assert n_reduce == (1 if role == "col" else 0), text

    # a layer's weights: the [L, k, n] stack and a replicated layer number
    def g(x, q, d, l):
        return qm.qmatmul_tp(x, qm.QuantWeight(q, d), role, tp4, layer=l)

    stack = NamedSharding(tp4, P(None, *w_spec))
    text = compiled_text(
        jax.jit(g),
        sds((1, 1, k), jnp.bfloat16, NamedSharding(tp4, x_spec)),
        sds((4, k, n), jnp.int8, stack),
        sds((4, k // 32, n), jnp.float32, stack),
        sds((), jnp.int32, NamedSharding(tp4, P())),
    )
    n_reduce = text.count(" all-reduce(") + text.count(" all-reduce-start(")
    assert n_reduce == (1 if role == "col" else 0), text
    assert not weight_copies(text), weight_copies(text)


@pytest.mark.parametrize("layout", ["device", "row_major"])
@pytest.mark.parametrize("helper", ["pallas", "grouped"])
def test_moe_q40_expert_stacks_tp4(tp4, helper, layout):
    """Qwen3-30B-A3B's expert stacks split four ways on F under the MoE
    helpers' shard_map, `s8[12,128,2048,768]` with a leading `None` in the
    spec and a replicated layer number: each shard's kernel is there and
    the partial outputs pay one all-reduce. A shard's 192 columns are
    stored column-major by the device (`{2,3,1,0}`; so does the chip:
    PERF.md section 6, PR 25), and the program turns the w1 and w3 stacks
    row-major for the kernel, whole and once a program, where the scan's
    `xs` turned one layer a step. Placed row-major nothing is copied."""
    from jax.experimental.layout import Format, Layout

    from dllama_tpu.models import transformer as tf
    from dllama_tpu.ops.quant_matmul import QuantWeight

    n_layers, d, f, e, k = 12, 2048, 768, 128, 8
    rows = 16 if helper == "pallas" else 512
    fn = tf._moe_ffn_pallas if helper == "pallas" else tf._moe_ffn_grouped
    row = NamedSharding(tp4, P(None, None, None, "tp"))
    col = NamedSharding(tp4, P(None, None, "tp", None))
    rep = NamedSharding(tp4, P())
    if layout == "row_major":
        row_major = Layout(major_to_minor=(0, 1, 2, 3))
        row, col = Format(row_major, row), Format(row_major, col)

    def w(k_in, n_out, s):
        return QuantWeight(
            sds((n_layers, e, k_in, n_out), jnp.int8, s),
            sds((n_layers, e, k_in // 32, n_out), jnp.float32, s),
        )

    def run(x, gate, w1, w2, w3, l):
        return fn(x, gate, w1, w2, w3, tf.Routing(k), tp4, layer=l)

    text = compiled_text(
        jax.jit(run),
        sds((1, rows, d), jnp.bfloat16, rep),
        sds((d, e), jnp.bfloat16, rep),
        w(d, f, row), w(f, d, col), w(d, f, row),
        sds((), jnp.int32, rep),
    )
    n_reduce = text.count(" all-reduce(") + text.count(" all-reduce-start(")
    assert n_reduce == 1, n_reduce
    turned = weight_copies(text)
    assert len(turned) == (2 if layout == "device" else 0), turned


def test_lm_head_tp4(tp4, monkeypatch):
    """Llama-3 logits at --tp 4 through qmatmul_tp: the regression test for
    the per-shard vocab that used to die in the compiler after two minutes."""
    from dllama_tpu.ops import quant_matmul as qm

    monkeypatch.setattr(qm, "_use_pallas", lambda: True)
    row = NamedSharding(tp4, P(None, "tp"))

    def f(x, q, d):
        return qm.qmatmul_tp(x, qm.QuantWeight(q, d), "row", tp4)

    compiled_text(
        jax.jit(f),
        sds((4, 1, D), jnp.bfloat16, NamedSharding(tp4, P("dp", None, None))),
        sds((D, V), jnp.int8, row),
        sds((D // 32, V), jnp.float32, row),
    )


def test_no_legal_block_is_our_error():
    """A contraction axis no 128-multiple tiles raises from our code with
    the shape in it, instead of a whole-axis block the compiler chokes on."""
    from dllama_tpu.ops.quant_matmul import _pick_block

    with pytest.raises(ValueError, match="32064"):
        _pick_block(32064, 4096)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_flash_attention_prefill(one_chip, kv):
    from dllama_tpu.ops.flash_attention import flash_attention
    from dllama_tpu.ops.kv_cache import QuantKV

    b, t, s = 4, 128, 2048
    if kv == "int8":
        cache = QuantKV(
            sds((b, KH, s, HD), jnp.int8, one_chip),
            sds((b, KH, s, 1), jnp.float32, one_chip),
        )
    else:
        cache = sds((b, KH, s, HD), jnp.bfloat16, one_chip)
    compiled_text(
        jax.jit(flash_attention),
        sds((b, t, H, HD), jnp.bfloat16, one_chip),
        cache,
        cache,
        sds((b,), jnp.int32, one_chip),
    )


def test_flash_decode(one_chip):
    from dllama_tpu.ops.flash_attention import flash_decode

    b, s = 4, 2048
    cache = sds((b, KH, s, HD), jnp.bfloat16, one_chip)
    compiled_text(
        jax.jit(flash_decode),
        sds((b, 1, H, HD), jnp.bfloat16, one_chip),
        cache,
        cache,
        sds((b,), jnp.int32, one_chip),
    )


def test_moe_grouped_experts_q40(one_chip):
    from dllama_tpu.ops.moe_kernel import moe_grouped_experts_q40

    n, d, f, e, k = 512, 2048, 768, 128, 8
    w13 = (sds((e, d, f), jnp.int8, one_chip),
           sds((e, d // 32, f), jnp.float32, one_chip))
    w2 = (sds((e, f, d), jnp.int8, one_chip),
          sds((e, f // 32, d), jnp.float32, one_chip))
    compiled_text(
        jax.jit(moe_grouped_experts_q40),
        sds((n, d), jnp.bfloat16, one_chip),
        *w13, *w2, *w13,
        sds((n, k), jnp.int32, one_chip),
        sds((n, k), jnp.float32, one_chip),
    )


def packed_args(m, k, n, s):
    return (
        sds((m, k), jnp.bfloat16, s),
        sds((k // 8, n), jnp.int32, s),
        sds((k // 32, n), jnp.float32, s),
    )


@pytest.mark.parametrize("m", [1, 128])
@pytest.mark.parametrize("k,n", SHAPES + [(D, V // 4)])
def test_qmatmul_q40i4(one_chip, m, k, n):
    """--weight-format q40i4 at Llama-3.1-8B's widths: int32 words of eight
    nibbles, 512-wide tiles at one row and 256-wide at 128."""
    from dllama_tpu.ops.quant_matmul import qmatmul_i4_2d

    compiled_text(qmatmul_i4_2d, *packed_args(m, k, n, one_chip))


# every (k, n) the seven cells' programs hand `qmatmul` (fused q|k|v(|gate)
# and w1|w3 as one tp shard fuses them), with the rows of the cell's decode
# block and of its chunk program (lanes x 512; a convolution layer and its
# FFN: the admitted lane's 512), and since PR 49 of the chunk programs at
# the ladder's middle rungs where the row blocks are new: five lanes' 640
# and 1280 rows (`_pick_row_block`: 2 x 320, 3 x 432 with a ragged tail),
# one lane's 128 and 256; sixteen and thirty-two lanes' are whole blocks of
# 512 like their 8192
CELL_MATMULS = {
    "mistral-7b-v0.3": ([5, 640, 1280, 2560], [
        (4096, 6144), (4096, 4096), (4096, 28672), (14336, 4096), (4096, 32768)]),
    "qwen3-30b-a3b-l12": ([16, 8192], [(2048, 5120), (4096, 2048), (2048, 151936)]),
    "trinity-large-l9-e32": ([8, 4096], [
        (3072, 14336), (6144, 3072), (3072, 24576), (12288, 3072), (3072, 6144),
        (3072, 3072), (3072, 25024)]),
    "openpangu-ultra-l5-e32": ([4, 2048], [
        (7680, 1536), (1536, 24576), (7680, 576), (16384, 7680), (7680, 36864),
        (18432, 7680), (7680, 4096), (2048, 7680), (7680, 19200)]),
    "deepseek-v3.2-l5-e32": ([4, 2048], [
        (7168, 1536), (1536, 24576), (7168, 576), (16384, 7168), (7168, 36864),
        (18432, 7168), (7168, 4096), (2048, 7168), (1536, 8192), (7168, 128),
        (7168, 16160)]),
    "lfm2-24b-a2b-e16": ([16, 128, 256, 512, 8192], [
        (2048, 3072), (2048, 2048), (2048, 6144), (2048, 23552), (11776, 2048),
        (2048, 16384)]),
    # `in_proj`'s 16768 = 131 x 128 columns: tiles of 128, the one multiple
    # of 128 that divides them
    "granite-4.0-h-small-l20-e18": ([32, 128, 256, 512, 16384], [
        (4096, 16768), (8192, 4096), (4096, 6144), (4096, 4096), (4096, 3072),
        (1536, 4096), (4096, 25088)]),
}


@pytest.mark.parametrize(
    "m,k,n",
    [
        pytest.param(m, k, n, id=f"{config}-{m}x{k}x{n}")
        for config, (rows, shapes) in CELL_MATMULS.items()
        for m in rows for k, n in shapes
    ],
)
def test_qmatmul_packed_at_the_cells_shapes(one_chip, m, k, n):
    """What `--weight-format auto` serves in every cell: the packed kernel
    at each configuration's widths (k = 11776 in k blocks of 512, the
    latent and index projections, per-shard vocabularies no 128-multiple
    divides) and both its row counts, 512-wide tiles under the decode rows
    and 256-wide under a chunk's row blocks."""
    from dllama_tpu.ops.quant_matmul import qmatmul_i4_2d

    compiled_text(qmatmul_i4_2d, *packed_args(m, k, n, one_chip))


# (D, F, experts held, experts a token, decode lanes) of the six sparse
# cells: Qwen3-30B-A3B, Trinity-Large, openPangu-Ultra, DeepSeek-V3.2, LFM2,
# granite-4.0-h-small
HELD_SHAPES = [
    (2048, 768, 128, 8, 16), (3072, 3072, 32, 4, 8), (7680, 2048, 32, 8, 4),
    (7168, 2048, 32, 8, 4), (2048, 1536, 16, 4, 16), (4096, 768, 18, 10, 32),
]


@pytest.mark.parametrize("rows,d,f,e,k,lanes", [
    pytest.param(rows, *shape, id=f"{shape[0]}x{shape[1]}-{rows}")
    for rows in ("decode", "pairs128", "chunk") for shape in HELD_SHAPES
    if rows != "pairs128" or 128 % shape[3] == 0  # ten a token: no whole rows of 128 pairs
])
def test_held_experts_packed_at_the_cells_shapes(one_chip, d, f, e, k, lanes, rows):
    """`moe_held_experts_q40` over packed expert stacks (int32 words of eight
    nibbles, `[L, E, in // 8, out]`) at the five served expert shapes: a
    decode step's pairs, 128 pairs and a 512-row chunk's, each under its own
    row tile and F block (`_held_rows`, `_held_f_block`), compile inside the
    kernel's VMEM limit; called by layer number out of the stacks in a scan,
    no layer's words are sliced or copied out, and the program still names
    the jitted function the trace's `moe_held_experts_q40.N` is read by."""
    from jax import lax

    from dllama_tpu.ops import moe_kernel as mk

    n_layers = 4
    n = {"decode": lanes, "pairs128": 128 // k, "chunk": 512}[rows]
    assert rows != "pairs128" or n * k == 128
    bf, r = mk._held_f_block(f, d, True), mk._held_rows(n * k, True)
    assert f % bf == 0 and bf % 256 == 0 and r in (16, 32, 64)
    w13 = (sds((n_layers, e, d // 8, f), jnp.int32, one_chip),
           sds((n_layers, e, d // 32, f), jnp.float32, one_chip))
    w2 = (sds((n_layers, e, f // 8, d), jnp.int32, one_chip),
          sds((n_layers, e, f // 32, d), jnp.float32, one_chip))

    def run(x, w1q, w1d, w2q, w2d, w3q, w3d, ii, ww):
        def step(x, l):
            y = mk.moe_held_experts_q40(x, w1q, w1d, w2q, w2d, w3q, w3d, ii, ww, l)
            return x + y.astype(x.dtype), None

        return lax.scan(step, x, jnp.arange(n_layers, dtype=jnp.int32))[0]

    text = compiled_text(
        jax.jit(run),
        sds((n, d), jnp.bfloat16, one_chip),
        *w13, *w2, *w13,
        sds((n, k), jnp.int32, one_chip),
        sds((n, k), jnp.float32, one_chip),
    )
    assert "moe_held_experts_q40" in text
    assert not weight_copies(text), weight_copies(text)


@pytest.mark.parametrize("d,f,e,n_routed,k,cap", [
    (7680, 2048, 32, 256, 8, 768), (4096, 768, 18, 72, 10, 1920),
    (2048, 1536, 16, 64, 4, 768),
], ids=["pangu", "granite", "lfm2"])
def test_held_share_chunk_compiles_both_forms(one_chip, d, f, e, n_routed, k, cap):
    """A 512-row chunk's expert block where a share of the routed experts is
    held (packed stacks, by layer number in a scan): what surrounds the
    kernel is compiled twice under one conditional, over the sorted pairs'
    first `cap` (the landed form) and over all of them, each with its own
    kernel call under the call's row tile and F block; the experts' words
    reach both branches where they lie, no layer's copied."""
    from jax import lax

    from dllama_tpu.ops import moe_kernel as mk

    n_layers, n = 4, 512
    assert mk._landed_cap(n * k, mk._held_rows(n * k, True), e, n_routed) == cap
    w13 = (sds((n_layers, e, d // 8, f), jnp.int32, one_chip),
           sds((n_layers, e, d // 32, f), jnp.float32, one_chip))
    w2 = (sds((n_layers, e, f // 8, d), jnp.int32, one_chip),
          sds((n_layers, e, f // 32, d), jnp.float32, one_chip))

    def run(x, w1q, w1d, w2q, w2d, w3q, w3d, ii, ww):
        def step(x, l):
            y = mk.moe_held_experts_q40(
                x, w1q, w1d, w2q, w2d, w3q, w3d, ii, ww, l, n_routed=n_routed)
            return x + y.astype(x.dtype), None

        return lax.scan(step, x, jnp.arange(n_layers, dtype=jnp.int32))[0]

    text = compiled_text(
        jax.jit(run),
        sds((n, d), jnp.bfloat16, one_chip),
        *w13, *w2, *w13,
        sds((n, k), jnp.int32, one_chip),
        sds((n, k), jnp.float32, one_chip),
    )
    assert text.count(" conditional(") == 1
    # a profile's names for the two: the jitted functions that hold them
    kernels = {form: [line for line in text.splitlines() if "tpu_custom_call" in line
                      and f"moe_held_experts_q40_{form}" in line.split(" = ")[0]]
               for form in ("landed", "whole")}
    assert [len(v) for v in kernels.values()] == [1, 1], kernels
    for form, rows in (("landed", cap), ("whole", n * k)):
        (line,) = kernels[form]
        assert f"bf16[{rows},{d}]" in line, line[:300]  # x_sorted
        assert line.split(" = ")[1].startswith(f"f32[{rows},{d}]"), line[:300]
    assert not weight_copies(text), weight_copies(text)
