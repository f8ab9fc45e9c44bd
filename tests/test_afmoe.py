"""The `afmoe` decoder (Trinity) through the program's normal path, at a
small size on the CPU, against the benchmark's plain reference: logits, not
ids. Layers that differ (window and full attention over two cache stacks,
a leading dense layer and then experts), a ring cache that wraps, lanes at
unequal positions with one parked, a share of the experts and of the
vocabulary, and the prefix pool over two kinds of cache."""

from __future__ import annotations

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "benchmark")) if p not in sys.path]

from benchmark.harness import weights  # noqa: E402
from benchmark.references import afmoe  # noqa: E402
from helpers import AFMOE_WINDOW as WINDOW, tiny_afmoe_config as tiny  # noqa: E402
from dllama_tpu.formats.model_file import ModelReader, layer_table  # noqa: E402
from dllama_tpu.models import transformer as tf  # noqa: E402
from dllama_tpu.models.loader import load_params  # noqa: E402
from dllama_tpu.models.transformer import forward, init_kv_cache  # noqa: E402

CHUNK, SEQ = 16, 256
RING = WINDOW + CHUNK
TOL = 2e-4  # f32 on both sides; the largest logit error read is 2e-5 of a std near 1


def build(tmp_path, cfg: dict, seed: int = 3):
    path = str(tmp_path / f"{cfg['name']}-{seed}.m")
    weights.write_model(path, cfg, seed)
    reader = ModelReader(path, max_seq_len=SEQ)
    return path, reader.header, load_params(reader, dtype=jnp.float32)


def token_ids(n: int, seed: int = 0) -> list[int]:
    return [int(t) for t in np.random.default_rng(seed).integers(0, 500, n)]


def served_logits(h, params, ids, n_prefill: int, chunk: int = CHUNK):
    """Logits of every position: chunks of `chunk` rows up to `n_prefill`,
    then a decode step a token, through both caches."""
    cache = init_kv_cache(h, 1, jnp.float32, seq_len=SEQ + chunk, ring=RING, ring_pad=chunk)
    step = jax.jit(lambda toks, pos, cache: forward(params, h, toks, pos, cache, kv_ring=RING))
    out, p = [], 0
    while p < len(ids):
        width = chunk if p + chunk <= n_prefill else 1
        logits, cache = step(jnp.asarray([ids[p:p + width]]), jnp.int32(p), cache)
        out.append(np.asarray(logits[0]))
        p += width
    return np.concatenate(out)


def reference_logits(path, cfg, ids):
    return np.asarray(afmoe.last_logits(path, cfg, [ids], [len(ids)])[0])


@pytest.mark.parametrize("n,n_prefill", [
    (24, 16),  # below the window
    (60, 32),  # across it, decoding over the edge
    (150, 96),  # the ring (48 rows) wraps three times, in chunks and in decode steps
    (70, 64),  # the chunk at 48-63 runs over the ring's end, its queries straddle the window's edge
], ids=["below-window", "across-window", "ring-wraps", "chunk-straddles"])
def test_prefill_then_decode_through_both_caches_equals_the_reference(tmp_path, n, n_prefill):
    cfg = tiny()
    path, h, params = build(tmp_path, cfg)
    assert [k.window for k in layer_table(h)] == [True, True, True, False, True]
    ids = token_ids(n)
    want = reference_logits(path, cfg, ids)
    got = served_logits(h, params, ids, n_prefill)
    assert np.abs(got - want).max() < TOL * want.std()


@pytest.mark.parametrize("over", [
    {"layer_types": ["full_attention"] * 3},
    {"layer_types": ["sliding_attention"] * 3},
    {"num_dense_layers": 0},
    {"num_dense_layers": 5},
    {"num_shared_experts": 0},
], ids=["full-only", "window-only", "experts-only", "dense-only", "no-shared-expert"])
def test_each_kind_of_layer_alone_equals_the_reference(tmp_path, over):
    cfg = tiny(**over)
    path, h, params = build(tmp_path, cfg)
    ids = token_ids(100, seed=1)
    got = served_logits(h, params, ids, 64)
    want = reference_logits(path, cfg, ids)
    assert np.abs(got - want).max() < TOL * want.std()


def test_eight_lanes_at_unequal_positions_one_parked(tmp_path):
    """Eight lanes, each its own sequence at its own length, decode one step
    together; lane 5 is parked (its position is the park row). Every live
    lane's logits are the reference's for its sequence, and the parked
    lane's rows of both caches, ring and context, are as they were."""
    cfg = tiny()
    path, h, params = build(tmp_path, cfg)
    lanes, park = 8, SEQ
    lengths = [5, 31, 33, 47, 49, 20, 95, 130]
    seqs = [token_ids(n + 1, seed=10 + i) for i, n in enumerate(lengths)]
    cache = init_kv_cache(h, lanes, jnp.float32, seq_len=SEQ + CHUNK, ring=RING, ring_pad=CHUNK)
    step = jax.jit(lambda toks, pos, cache: forward(
        params, h, toks, pos, cache, kv_ring=RING, attn_park_threshold=park))
    # fill lane by lane, as the engine's lane prefill does: the others parked
    for lane, ids in enumerate(seqs):
        p = 0
        while p < lengths[lane]:
            width = CHUNK if p + CHUNK <= lengths[lane] else 1
            toks = np.zeros((lanes, width), np.int32)
            toks[lane] = ids[p:p + width]
            pos = np.full(lanes, park, np.int32)
            pos[lane] = p
            _, cache = step(jnp.asarray(toks), jnp.asarray(pos), cache)
            p += width
    before = jax.tree.map(np.asarray, cache)
    pos = np.asarray(lengths, np.int32)
    pos[5] = park
    toks = np.asarray([[ids[-1]] for ids in seqs], np.int32)
    logits, cache = step(jnp.asarray(toks), jnp.asarray(pos), cache)
    for lane, ids in enumerate(seqs):
        if lane == 5:
            continue
        want = reference_logits(path, cfg, ids)[-1]
        assert np.abs(np.asarray(logits[lane, 0]) - want).max() < TOL * want.std(), lane
    after = jax.tree.map(np.asarray, cache)
    assert np.array_equal(after["k"][:, 5, :, :SEQ], before["k"][:, 5, :, :SEQ])
    ring = slice(CHUNK, CHUNK + RING)
    assert np.array_equal(after["kw"][:, 5, :, ring], before["kw"][:, 5, :, ring])
    assert not np.array_equal(after["kw"][:, 6, :, ring], before["kw"][:, 6, :, ring])


@pytest.mark.parametrize("family", ["afmoe", "pangu_ultra_moe", "deepseek_v32"])
def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer(tmp_path, family):
    """The share tied to the model: one expert layer of a model that holds
    all 8 experts the router scores, in the reference; and the same layer
    as 8 chips would compute it, each holding one expert, in the program's
    own routing and expert code. The routed parts of the 8 shares plus the
    shared expert, counted once, are the uncut layer's output. Some token
    has no expert on some chip, and gets nothing from it. The families
    that serve a held share: `afmoe` (a selection bias, scale 2.448),
    `pangu_ultra_moe` (none, scale 2.5) and `deepseek_v32` (a bias, scale
    2.5, and a group limit: 2 of 4 groups of 2, so a token's two experts lie
    on at most two of the four pairs of chips that hold a group)."""
    groups = (1, 1)
    if family == "afmoe":
        ref, cfg, scale = afmoe, tiny(num_experts=8), 2.448
    elif family == "deepseek_v32":
        from benchmark.references import deepseek_v32 as ref
        from helpers import tiny_dsv32_config

        cfg, scale, groups = tiny_dsv32_config(n_routed_experts=8), 2.5, (4, 2)
    else:
        from benchmark.references import pangu_ultra_moe as ref
        from helpers import tiny_pangu_config

        cfg, scale = tiny_pangu_config(n_routed_experts=8), 2.5
    path, h, params = build(tmp_path, cfg)
    layer = 2  # an expert layer; its row among the expert layers' stacks is 1
    lp = {k: v[1] for k, v in params["layers"].items()
          if k in ("moe_gate", "expert_bias", "w1", "w2", "w3", "shared_w1", "shared_w2", "shared_w3")}
    assert ("expert_bias" in lp) == (family != "pangu_ultra_moe")
    y = jnp.asarray(np.random.default_rng(2).standard_normal((1, 40, 64)), jnp.float32)
    f = ref.Q40File(path)
    w = ref.layer_weights(f, layer, cfg)
    want = np.asarray(ref.routed_experts(y[0], w, cfg) + ref.dense_ffn(
        y[0], w["shared_w1"], w["shared_w2"], w["shared_w3"]))
    parts, empty = [], 0
    for first in range(8):
        route = tf.Routing(2, True, True, scale, first, 1, 8, *groups)
        top_i, wts = tf._moe_route(y, lp["moe_gate"], route, lp.get("expert_bias"))
        held = route.held(top_i)
        part = tf._moe_ffn(
            y, lp["moe_gate"], *(lp[n][first:first + 1] for n in ("w1", "w2", "w3")),
            route, tf.silu, routed=(held, wts))
        rows_without = np.asarray((held == 1).all(axis=-1))[0]
        assert not np.asarray(part)[0][rows_without].any()
        empty += int(rows_without.sum())
        parts.append(np.asarray(part[0]))
    assert empty > 0
    shared = np.asarray(afmoe.dense_ffn(
        y[0], *(jnp.asarray(lp["shared_" + n]).T for n in ("w1", "w2", "w3"))))
    assert np.abs(sum(parts) + shared - want).max() < 1e-4 * np.abs(want).max()


def test_the_bias_moves_the_selection_and_never_the_weights(tmp_path):
    cfg = tiny(num_experts=8)
    _, h, params = build(tmp_path, cfg)
    gate, bias = params["layers"]["moe_gate"][0], params["layers"]["expert_bias"][0]
    y = jnp.asarray(np.random.default_rng(4).standard_normal((400, 64)), jnp.float32)
    route = tf.routing_of(h)
    with_i, with_w = tf._moe_route(y, gate, route, bias)
    plain_i, _ = tf._moe_route(y, gate, route, None)
    moved = np.asarray((jnp.sort(with_i) != jnp.sort(plain_i)).any(axis=-1)).mean()
    assert 0.05 < moved < 0.95  # the selection of a share of the tokens, not of all
    scores = np.asarray(jax.nn.sigmoid(y @ gate))
    chosen = np.take_along_axis(scores, np.asarray(with_i), axis=1)
    want = 2.448 * chosen / chosen.sum(axis=1, keepdims=True)
    assert np.abs(np.asarray(with_w) - want).max() < 1e-5


def test_a_sliced_head_gives_the_references_rows(tmp_path):
    """The file holds a slice of the vocabulary: the program's head over it
    gives the rows of the reference's head over the whole."""
    cfg = tiny()
    path, h, params = build(tmp_path, cfg)
    x = jnp.asarray(np.random.default_rng(5).standard_normal((1, 3, 64)), jnp.float32)
    f = afmoe.Q40File(path)
    whole = np.asarray(afmoe.head(x[0], f.f32("final_norm"), f.f32("wcls"), 1e-5))
    sliced = dict(params, wcls=params["wcls"][:, :64])
    got = np.asarray(tf.logits_head(x, sliced, h, None, "all"))[0]
    assert np.abs(got - whole[:, :64]).max() < 1e-4


def held_kernel_case(n, k, p_held, seed, e=4, live=None, extremes=False):
    """Pairs of `n` rows over `e` held experts, each held with probability
    `p_held` and the sentinel `e` otherwise; rows outside `live` (a slice)
    carry the sentinel alone, as a parked lane's do. D and F are one packed
    group (256 rows), the least the packed form takes. `extremes`: both
    ends of a nibble in every block, and negative, zero and f16-subnormal
    scales among the block scales."""
    rng = np.random.default_rng(seed)
    n_layers, d, f = 2, 256, 256

    def stack(i, o):
        q = rng.integers(-8, 8, (n_layers, e, i, o)).astype(np.int8)
        s = ((rng.random((n_layers, e, i // 32, o)) + 0.5) * 0.02).astype(np.float32)
        if extremes:
            q[..., 0::32, :], q[..., 1::32, :] = -8, 7
            s[..., 0, :] *= -1
            s[..., 1, :] = 0.0
            s[..., 2, :] = np.float32(np.float16(3e-6))  # an f16 subnormal
            assert 0 < s[0, 0, 2, 0] < 6.1e-5
        return q, s, (q.astype(np.float32).reshape(n_layers, e, i // 32, 32, o)
                      * s[..., None, :]).reshape(q.shape)

    w1, w3, w2 = stack(d, f), stack(d, f), stack(f, d)
    x = rng.standard_normal((n, d)).astype(np.float32)
    held = rng.random((n, k)) < p_held
    if live is not None:
        parked = np.ones(n, bool)
        parked[live] = False
        held[parked] = False
    ids = np.where(held, rng.integers(0, e, (n, k)), e).astype(np.int32)
    wts = np.where(held, rng.random((n, k)), 0).astype(np.float32)
    return x, ids, wts, w1, w2, w3


@pytest.mark.parametrize("form", ["int8", "packed"])
@pytest.mark.parametrize("n,k,p_held,e,live", [
    (5, 2, 0.3, 4, None), (300, 4, 0.125, 4, None), (3, 2, 0.0, 4, None),
    (40, 4, 1.0, 4, None),
    # what one device that holds a whole layer makes hot: a decode block's
    # lanes over every expert, ids repeating across rows; an admission chunk
    # of four lanes of which one is live
    (16, 8, 1.0, 128, None), (256, 4, 1.0, 16, slice(64, 128)),
], ids=["decode", "prefill", "none-held", "all-held", "all-held-decode-lanes",
        "chunk-one-lane-live"])
def test_held_experts_kernel_computes_the_pairs_that_landed_here(
        n, k, p_held, e, live, form):
    """`moe_held_experts_q40` in interpret mode (its grid is as long as the
    steps that hold a real pair) against the sum written out, and against
    the dense `_moe_ffn` over the same pairs. Over packed words, its own
    row tiles and all, it gives the int8 kernel's output bit for bit: the
    unpacked tile is `_dequant_block`'s, and a pair's row meets the same
    dots in the same order whatever the tile's height."""
    from dllama_tpu.ops.jnp_ops import silu
    from dllama_tpu.ops.moe_kernel import _held_rows, moe_held_experts_q40
    from dllama_tpu.ops.quant_matmul import QuantWeight, pack_nibbles

    x, ids, wts, w1, w2, w3 = held_kernel_case(
        n, k, p_held, seed=n, e=e, live=live, extremes=n == 40)
    if e > 4:
        assert len(np.unique(ids[ids < e])) < (ids < e).sum()  # experts shared by rows
    if live is not None:
        parked = np.ones(n, bool)
        parked[live] = False
        assert (ids[parked] == e).all() and (ids[~parked] < e).all()
    layer = 1
    stacks = [jnp.asarray(a) for w in (w1, w2, w3) for a in w[:2]]
    call = lambda *ws: np.asarray(moe_held_experts_q40(
        jnp.asarray(x), *ws, jnp.asarray(ids), jnp.asarray(wts), jnp.int32(layer),
        interpret=True))
    got = call(*stacks)
    if form == "packed":
        assert _held_rows(n * k, True) < _held_rows(n * k, False)
        packed = [a for q, d in zip(stacks[::2], stacks[1::2])
                  for a in pack_nibbles(QuantWeight(q, d))]
        assert packed[0].dtype == jnp.int32 and packed[0].shape[-2] == 256 // 8
        np.testing.assert_array_equal(call(*packed), got)
    xb = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    want = np.zeros_like(x)
    for t, j in zip(*np.nonzero(ids < e)):
        h1, h3 = xb[t] @ w1[2][layer, ids[t, j]], xb[t] @ w3[2][layer, ids[t, j]]
        want[t] += wts[t, j] * ((h1 / (1 + np.exp(-h1)) * h3) @ w2[2][layer, ids[t, j]])
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= 0.02 * max(np.abs(want).max(), 1e-6)
    dense = np.asarray(tf._moe_ffn(
        jnp.asarray(xb)[None], None, *(jnp.asarray(w[2][layer]) for w in (w1, w2, w3)),
        tf.Routing(k), silu, routed=(jnp.asarray(ids)[None], jnp.asarray(wts)[None])))[0]
    assert np.abs(got - dense).max() <= 0.02 * max(np.abs(dense).max(), 1e-6)


LANDED_N, LANDED_K, LANDED_CAP, LANDED_TILE = 64, 4, 64, 16


def landed_pairs(case: str, rng, e: int):
    """[64, 4] held ids (the sentinel `e` elsewhere) and weights of one
    case of `LANDED_CASES`, and the rows of x the case wants alike."""
    n, k, cap = LANDED_N, LANDED_K, LANDED_CAP
    held = np.zeros(n * k, bool)
    ids = rng.integers(0, e, n * k)
    alike = None
    if case == "parked":  # a quarter of the rows live, the rest the sentinel alone
        held.reshape(n, k)[16:32] = rng.random((16, k)) < 0.6
    elif case == "one-expert-tiles":
        # padding rows: one token many times over, all its pairs on one held
        # expert, so whole row tiles are one expert's, beside a few others'
        alike = slice(20, 60)
        held.reshape(n, k)[alike, 1] = True
        ids.reshape(n, k)[alike, 1] = 2
        held.reshape(n, k)[:6, 0] = True
    else:
        count = {"none": 0, "one": 1, "cap-1": cap - 1, "cap": cap, "cap+1": cap + 1,
                 "all": n * k}[case]
        held[rng.permutation(n * k)[:count]] = True
    ids = np.where(held, ids, e).astype(np.int32).reshape(n, k)
    wts = np.where(ids < e, rng.random((n, k)) + 0.1, 0).astype(np.float32)
    return ids, wts, alike


LANDED_CASES = ["none", "one", "cap-1", "cap", "cap+1", "all", "parked", "one-expert-tiles"]


@pytest.mark.parametrize("form", ["int8", "packed"])
@pytest.mark.parametrize("case", LANDED_CASES)
def test_the_landed_form_equals_the_whole_form_bit_for_bit(case, form):
    """`moe_held_experts_q40` with its surroundings sized by the sorted
    pairs' first 64 of 256 (`cap`: the schedule, the gather, the kernel's
    output, and a sum by gathers where the whole form masks and
    scatter-adds), under `lax.cond` beside the whole form, against the whole
    form alone: one output bit for bit, whichever side of the `cond` the
    pairs that landed take (at most 64: the landed form; 65 and more: the
    whole one)."""
    from dllama_tpu.ops.moe_kernel import moe_held_experts_q40
    from dllama_tpu.ops.quant_matmul import QuantWeight, pack_nibbles

    e = 4
    x, _, _, w1, w2, w3 = held_kernel_case(LANDED_N, LANDED_K, 0.0, seed=11, e=e)
    ids, wts, alike = landed_pairs(case, np.random.default_rng(12), e)
    if alike is not None:
        x[alike] = x[alike.start]
    landed = int((ids < e).sum())
    assert {"none": 0, "one": 1, "cap-1": 63, "cap": 64, "cap+1": 65, "all": 256}.get(
        case, landed) == landed
    assert case != "one-expert-tiles" or (
        np.sort(ids[ids < e])[16:32] == 2).all()  # a whole row tile of one expert
    stacks = [jnp.asarray(a) for w in (w1, w2, w3) for a in w[:2]]
    if form == "packed":
        stacks = [a for q, d in zip(stacks[::2], stacks[1::2])
                  for a in pack_nibbles(QuantWeight(q, d))]
    call = lambda cap: np.asarray(moe_held_experts_q40(
        jnp.asarray(x), *stacks, jnp.asarray(ids), jnp.asarray(wts), jnp.int32(1),
        interpret=True, row_tile=LANDED_TILE, cap=cap))
    whole = call(LANDED_N * LANDED_K)
    np.testing.assert_array_equal(call(LANDED_CAP), whole)
    assert np.isfinite(whole).all() and (landed == 0) == (not whole.any())
    if case == "parked":
        assert not whole[:16].any() and not whole[32:].any() and whole[16:32].any()


def held_text(n, k, e, n_routed, **kw):
    from dllama_tpu.ops.moe_kernel import moe_held_experts_q40

    _, ids, wts, w1, w2, w3 = held_kernel_case(n, k, 0.5, seed=1, e=e)
    stacks = [jnp.asarray(a) for w in (w1, w2, w3) for a in w[:2]]
    return str(jax.make_jaxpr(
        functools.partial(moe_held_experts_q40, n_routed=n_routed, interpret=True, **kw)
    )(jnp.zeros((n, 256), jnp.float32), *stacks, jnp.asarray(ids), jnp.asarray(wts),
      jnp.int32(1)))


@pytest.mark.parametrize("n,k,e,n_routed,forms", [
    (512, 8, 4, 4, 1),  # every expert held: a chunk of the sparse cell
    (4, 8, 4, 32, 1),  # an eighth held, a decode step's pairs: too few rows to save
    (512, 8, 4, 32, 2),  # an eighth held, a chunk's pairs: cap 768 of 4096
], ids=["all-held-chunk", "share-decode", "share-chunk"])
def test_the_form_follows_from_the_shape_and_the_held_share(n, k, e, n_routed, forms):
    """Where every expert is held, or too few rows would be saved, the
    function traces to one form: the program it traces to when it is told
    nothing of a share (the parent's call), one kernel call in it. A chunk
    of a held share traces to a `cond` over both."""
    from dllama_tpu.ops import moe_kernel as mk

    pairs = n * k
    cap = mk._landed_cap(pairs, mk._held_rows(pairs, False), e, n_routed)
    text = held_text(n, k, e, n_routed)
    plain = held_text(n, k, e, None)
    assert text.count("pallas_call[") == forms and plain.count("pallas_call[") == 1
    if forms == 1:
        assert cap == -(-pairs // 128) * 128 and text == plain
    else:
        assert cap == 768 and text != plain
        assert text == held_text(n, k, e, None, cap=768)


def test_ring_flash_kernel_equals_the_dense_path():
    """The flash kernel over a ring that has wrapped (interpret mode), with
    spare rows before it, against `attention_dense` over the same ring."""
    from dllama_tpu.ops.flash_attention import flash_attention
    from dllama_tpu.ops.jnp_ops import attention_dense

    rng = np.random.default_rng(0)
    b, t, heads, kh, hd, window, ring, pad = 2, 16, 4, 2, 32, 64, 128, 32
    k = rng.standard_normal((1, b, kh, pad + ring + pad, hd)).astype(np.float32)
    v = rng.standard_normal(k.shape).astype(np.float32)
    q = rng.standard_normal((b, t, heads, hd)).astype(np.float32)
    for pos in ([10, 40], [250, 371], [250, -1000]):
        pos = jnp.asarray(pos)
        want = attention_dense(jnp.asarray(q), jnp.asarray(k[0, :, :, pad:pad + ring]),
                               jnp.asarray(v[0, :, :, pad:pad + ring]), pos,
                               ring=ring, window=window)
        got = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), pos,
                              block_t=8, block_s=32, interpret=True, layer=0, rows=ring,
                              ring=ring, window=window, row0=pad)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    assert not np.asarray(got)[1].any()  # a parked lane attends to nothing


# -- through the engine: lanes, the prefix pool over two kinds of cache ------


@pytest.fixture(scope="module")
def lanes(tmp_path_factory):
    from helpers import make_tiny_afmoe

    from dllama_tpu.runtime.engine import InferenceEngine

    path = str(tmp_path_factory.mktemp("afmoe") / "m.m")
    cfg = make_tiny_afmoe(path)
    e = InferenceEngine(path, tp=1, dtype=jnp.float32, temperature=0.0, batch_size=4,
                        prefill_buckets=(1, CHUNK), max_seq_len=SEQ)
    return e, cfg, path


def lane_logits(e, lane: int, token: int, pos: int):
    """The engine's own forward pass for one lane's next step, the others
    parked; the cache it returns is dropped, so nothing is written."""
    toks = np.zeros((e.batch_size, 1), np.int32)
    toks[lane] = token
    posv = np.full(e.batch_size, e._park, np.int32)
    posv[lane] = pos
    logits, _ = e._fwd(e.params, jnp.asarray(toks), jnp.asarray(posv), e.cache,
                       attn_window=e._attn_window(pos + 1),
                       attn_park_threshold=e._park, logits_mode="last")
    return np.asarray(logits[lane, 0])


def test_the_window_layers_cache_is_a_ring_of_window_and_chunk_not_the_context(lanes):
    e, _, _ = lanes
    assert e.kv_ring == WINDOW + CHUNK
    assert e.cache["k"].shape[3] == SEQ + CHUNK and e.cache["k"].shape[0] == 1
    assert e.cache["kw"].shape[3] == e.kv_ring + 2 * CHUNK and e.cache["kw"].shape[0] == 4
    assert e.kv_cache_bytes["window"] == 2 * e.cache["kw"].size * 4
    assert e.kv_cache_bytes["full"] == 2 * e.cache["k"].size * 4


def test_an_adopted_prefix_gives_the_logits_of_the_request_served_without_it(lanes):
    from dllama_tpu.kv.manager import PagedKVManager

    e, cfg, path = lanes
    kv = PagedKVManager(e, page_size=4, n_pages=40)
    first = token_ids(40, seed=21)
    e.prefill_lane(0, first)  # rows [0, 39) of lane 0
    assert kv.publish(0, first[:39]) == 9  # whole pages of both kinds of cache
    second = first[:30] + token_ids(12, seed=22)
    m, pages = kv.match(1, second)
    assert m == 30 and len(pages) == 8  # the last page in part: its tail is written over
    kv.adopt(1, pages)
    e.prefill_lane(1, second[m:], pos0=m)
    e.prefill_lane(2, second)  # the same request with nothing adopted
    adopted = lane_logits(e, 1, second[-1], len(second) - 1)
    plain = lane_logits(e, 2, second[-1], len(second) - 1)
    assert np.abs(adopted - plain).max() < 1e-5
    want = reference_logits(path, cfg, second)[-1]
    assert np.abs(adopted - want).max() < TOL * want.std()
    kv.release_lane(1)


def test_a_prefix_whose_window_rows_are_gone_is_a_miss(lanes):
    from dllama_tpu.kv.manager import PagedKVManager

    e, _, _ = lanes
    kv = PagedKVManager(e, page_size=4, n_pages=40)
    long = token_ids(100, seed=23)  # past the ring's 48 rows: it has wrapped
    wraps = e._m_ring_wraps.value
    e.prefill_lane(3, long)
    assert e._m_ring_wraps.value == wraps + 2
    assert e.kv_publishable(99) == 0 and e.kv_publishable(e.kv_ring) == e.kv_ring
    assert kv.publish(3, long[:99]) == 0
    assert kv.match(0, long[:60] + [1, 2, 3]) == (0, [])


def test_a_decode_block_counts_the_pairs_that_landed_here(lanes):
    """`decode_lanes` returns tokens only, its counts ride in the same
    array: the recorder gets the rows in context by cache kind before the
    dispatch and the routed pairs after it."""
    e, _, _ = lanes
    e.prefill_lane(0, token_ids(60, seed=24))
    e.prefill_lane(1, token_ids(20, seed=25))
    n0 = len(e.recorder.events("moe_route"))
    out = e.decode_lanes([5, 6, 0, 0], [59, 19, 0, 0], 4, active=[True, True, False, False])
    assert np.asarray(out).shape == (4, 4)
    (event,) = e.recorder.events("moe_route")[n0:]
    n_expert_layers = 4
    assert event["pairs_routed"] == 4 * 2 * 2 * n_expert_layers  # steps x lanes x k x layers
    assert 0 < event["pairs_held"] < event["pairs_routed"]
    assert 0 < event["held_touched"] <= min(event["pairs_held"], 4 * 4 * n_expert_layers)
    dispatch = [d for d in e.recorder.events("step_dispatch") if d["step"] == "decode_lanes"][-1]
    assert dispatch["rows_full"] == sum(p + i + 1 for p in (59, 19) for i in range(4))
    assert dispatch["rows_window"] == sum(
        min(p + i + 1, WINDOW) for p in (59, 19) for i in range(4))


@pytest.mark.parametrize("kwargs,named", [
    ({"tp": 2}, "--tp 2"), ({"sp": 2}, "--sp 2"), ({"pp": 2}, "--pp 2"),
    ({"kv_dtype": "int8"}, "--kv-dtype int8"),
])
def test_what_the_architecture_does_not_run_under_fails_at_start_up(lanes, kwargs, named):
    from dllama_tpu.runtime.engine import InferenceEngine

    _, _, path = lanes
    with pytest.raises(ValueError, match=named):
        InferenceEngine(path, **{"tp": 1, "dtype": jnp.float32, "batch_size": 2,
                                 "max_seq_len": SEQ, **kwargs})


def test_pool_native_pages_and_speculation_are_refused_by_name(lanes):
    e, _, _ = lanes
    with pytest.raises(ValueError, match="--kv-native"):
        e.init_kv_pool(4, 40, native=True)
    with pytest.raises(ValueError, match="--speculation"):
        e.rehearse_admission(4, spec_k=4)


@pytest.mark.parametrize("n_prompt", [100, 200])
def test_a_prompt_through_a_middle_rung_leaves_what_the_largest_rung_leaves(lanes, n_prompt):
    """The served ladder's rungs at 128 and 256 rows (PR 49) against the 512
    this family's chunk program always ran at: cache rows, lane states and
    the next token's logits of one prompt through either."""
    from helpers import assert_a_middle_rung_equals_the_largest

    assert_a_middle_rung_equals_the_largest(lanes[2], n_prompt)
