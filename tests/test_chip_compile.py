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


@pytest.mark.parametrize("m", [1, 128])
@pytest.mark.parametrize("k,n", SHAPES + [(D, V // 4)])
def test_qmatmul_q40i4(one_chip, m, k, n):
    """--weight-format q40i4: the f16 scale plane enters the kernel as its
    raw int16 bits (the chip has no f16 vector load)."""
    from dllama_tpu.ops.quant_matmul import qmatmul_i4_2d

    compiled_text(
        qmatmul_i4_2d,
        sds((m, k), jnp.bfloat16, one_chip),
        sds((k // 2, n), jnp.int8, one_chip),
        sds((k // 32, n), jnp.float16, one_chip),
    )


@pytest.mark.parametrize("m", [1, 128])
@pytest.mark.parametrize("k,n", SHAPES + [(D, V // 4), (FF // 4, D)])
def test_i8matmul_q40i8(one_chip, m, k, n):
    """--weight-format q40i8, the w2 down-projection (k=14336, 28 groups of
    512, 7 per k block) and its tp=4 shard (k=3584) included."""
    from dllama_tpu.ops.int8_matmul import i8matmul_2d

    g = 512
    compiled_text(
        i8matmul_2d,
        sds((m, k), jnp.int8, one_chip),
        sds((m, k // g), jnp.float32, one_chip),
        sds((k, n), jnp.int8, one_chip),
        sds((k // g, n), jnp.float32, one_chip),
    )
