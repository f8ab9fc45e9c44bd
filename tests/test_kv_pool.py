"""Paged KV pool with cross-lane radix prefix sharing (ISSUE 6).

Unit layers bottom-up: PagePool refcount/free-list invariants, RadixTree
match/insert/split/LRU-eviction, the paged gather/scatter/view helpers
(QuantKV included), the paged flash decode kernel (interpret mode) — then
the device seam: engine publish -> adopt round trips are byte-identical
to fresh prefill (full pages, partial-tail + chunked suffix resume, int8
KV pool), and the PagedKVManager's dedup/COW/eviction accounting on top.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.kv import MatchResult, PagePool, RadixTree
from dllama_tpu.kv.pool import SCRATCH_PAGE
from dllama_tpu.ops.kv_cache import (
    QuantKV,
    dequant_kv,
    gather_pages,
    paged_view,
    quantize_kv_rows,
    scatter_pages,
)

from helpers import make_tiny_model

PS = 4  # page size used across the host-side tests


# -- PagePool -----------------------------------------------------------------


@pytest.mark.fast
def test_page_pool_invariants():
    events = []
    pool = PagePool(8, PS, on_event=lambda k, p: events.append((k, p)))
    st = pool.stats()
    assert st.total == 7 and st.free == 7 and st.used == 0  # scratch excluded

    a = pool.alloc(3)
    assert len(a) == 3 and SCRATCH_PAGE not in a
    assert all(pool.refcount(p) == 1 for p in a)
    pool.check()

    # retain -> shared; release -> back to tree-only; refcounts exact
    pool.retain(a)
    assert pool.stats().shared == 3
    assert all(pool.refcount(p) == 2 for p in a)
    assert pool.release(a) == 0  # still referenced once
    assert pool.stats().shared == 0 and pool.stats().used == 3

    # fork: a COW alloc, counted
    f = pool.fork(a[0])
    assert f not in a and pool.refcount(f) == 1
    assert pool.stats().cow_forks == 1
    assert any(k == "kv_cow_fork" for k, _ in events)

    # exhaustion raises without corrupting state
    rest = pool.alloc(pool.free_pages)
    with pytest.raises(MemoryError):
        pool.alloc(1)
    pool.check()

    # full release drains back to an all-free pool
    freed = pool.release(a + [f] + rest)
    assert freed == 7 and pool.free_pages == 7
    pool.check()

    # LIFO free list: the last freed page is reused first
    x = pool.alloc(1)[0]
    pool.release([x])
    assert pool.alloc(1)[0] == x

    # invalid ops surface loudly
    with pytest.raises(KeyError):
        pool.release([SCRATCH_PAGE])
    with pytest.raises(KeyError):
        pool.retain([999])
    with pytest.raises(KeyError):
        pool.fork(SCRATCH_PAGE)  # padded page-id vectors must not leak in

    pool.reset()
    assert pool.free_pages == 7 and pool.stats().used == 0
    assert pool.stats().cow_forks == 1  # cumulative telemetry survives reset
    assert any(k == "kv_page_alloc" for k, _ in events)
    assert any(k == "kv_page_free" for k, _ in events)


# -- RadixTree ----------------------------------------------------------------


def _seq(*chunks):
    out = []
    for c in chunks:
        out.extend(c)
    return out


@pytest.mark.fast
def test_radix_match_insert_split():
    pool = PagePool(32, PS)
    tree = RadixTree(PS)
    assert tree.match([1, 2, 3]) == MatchResult(0, [])

    # store A = 3 pages
    A = _seq([1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12])
    pa = pool.alloc(3)
    tree.insert(A, pa, first_slot=0)
    assert tree.n_pages == 3 and tree.token_count() == 12

    # exact + partial-final-page matches collect pages in slot order
    m = tree.match(A)
    assert m.n_tokens == 12 and m.pages == pa
    m = tree.match(A[:6] + [99])  # diverges mid page 1
    assert m.n_tokens == 6 and m.pages == pa  # stale-tail pages included
    m = tree.match(A + [13, 14])  # query longer than stored
    assert m.n_tokens == 12 and m.pages == pa

    # store B sharing pages 0-1, new final page: edge splits, the shared
    # pages move to the split head, dedup'd insert attaches only slot 2
    B = A[:8] + [20, 21, 22, 23]
    mb = tree.match(B)
    assert mb.n_tokens == 8 and mb.pages == pa
    pb = pool.alloc(1)
    tree.insert(B, pb, first_slot=2)
    assert tree.n_pages == 4
    assert tree.match(A).pages == pa
    assert tree.match(B).pages == pa[:2] + pb
    # mid-page divergence against BOTH: shares only slot 0's span + 2 toks
    C = A[:6] + [50, 51]
    mc = tree.match(C)
    assert mc.n_tokens == 6 and mc.pages[0] == pa[0]
    pool.check()


@pytest.mark.fast
def test_radix_lru_eviction_respects_refcounts():
    pool = PagePool(16, PS)
    tree = RadixTree(PS)
    seqs = {}
    for i in range(3):
        s = [100 * i + j for j in range(8)]  # 2 pages each, disjoint
        seqs[i] = (s, pool.alloc(2))
        tree.insert(s, seqs[i][1], first_slot=0)
    assert tree.n_pages == 6

    # touch 0 and 2: sequence 1 is LRU
    tree.match(seqs[0][0])
    tree.match(seqs[2][0])
    freed = tree.evict(1, pool)
    assert freed == 2  # leaf granularity: the whole LRU leaf goes
    assert tree.match(seqs[1][0]).n_tokens == 0
    assert tree.match(seqs[0][0]).n_tokens == 8

    # a lane-retained (refcount 2) leaf is NOT evictable; the next LRU is
    pool.retain(seqs[0][1])
    tree.match(seqs[0][0])  # 0 is now MRU anyway; make 2 LRU explicit
    freed = tree.evict(4, pool)
    assert freed == 2  # only sequence 2's leaf could go
    assert tree.match(seqs[0][0]).n_tokens == 8
    assert tree.n_pages == 2
    pool.release(seqs[0][1])
    # clear releases the tree's remaining pages back to the pool
    tree.clear(pool)
    assert pool.free_pages == 15
    pool.check()


@pytest.mark.fast
def test_radix_insert_rejects_gapped_path():
    """insert(first_slot=k) whose dedup'd lower slots are NOT stored
    (e.g. the matched leaf was evicted after the caller's match) must
    raise before mutating anything, never build a token path with no
    pages behind its early positions."""
    pool = PagePool(8, PS)
    tree = RadixTree(PS)
    A = [1, 2, 3, 4, 5, 6, 7, 8]
    pa = pool.alloc(1)
    with pytest.raises(ValueError):
        tree.insert(A, pa, first_slot=1)  # slot 0 was never stored
    assert tree.node_count() == 0 and tree.n_pages == 0

    # ...and with a stored-but-too-short prefix it still refuses
    tree.insert(A[:4], pa, first_slot=0)
    pb = pool.alloc(1)
    B = A + [9, 10, 11, 12]
    with pytest.raises(ValueError):
        tree.insert(B, pb, first_slot=2)  # slot 1 missing from the path
    assert tree.n_pages == 1
    pool.check()


@pytest.mark.fast
def test_radix_evict_collapses_dead_ancestors():
    """Evicting a leaf must also remove now-childless, pageless
    ancestors: left behind they are match()-able token spans with no
    pages, inflating node/token counts until the next pressure event."""
    pool = PagePool(16, PS)
    tree = RadixTree(PS)
    A = [1, 2, 3, 4, 5, 6, 7, 8]
    B = [1, 2, 30, 40, 50, 60, 70, 80]  # splits A's first edge at offset 2
    tree.insert(A, pool.alloc(2), first_slot=0)
    tree.insert(B, pool.alloc(2), first_slot=0)
    # the split head [1, 2] holds no pages (no slot ends inside it)
    assert tree.evict(4, pool) == 4
    assert tree.node_count() == 0 and tree.token_count() == 0
    assert tree.n_pages == 0
    assert tree.match(A) == MatchResult(0, [])
    pool.check()


# -- PagedKVManager host accounting (no device) -------------------------------


class _StubEngine:
    """Host-accounting-only stand-in: the manager's match/publish
    bookkeeping races need no device to reproduce."""

    kv_pool_epoch = 0

    def init_kv_pool(self, page_size, n_pages, native=False):
        return n_pages

    def kv_adopt(self, lane, pages):
        pass

    def kv_publish(self, lane, pages, start_page):
        pass

    def reset_kv_pool(self):
        pass

    def kv_publishable(self, n_tokens):
        return n_tokens


@pytest.mark.fast
def test_publish_pressure_pins_matched_prefix():
    """Regression: a publish extending a stored prefix under pool
    pressure must not LRU-evict that prefix's own refcount-1 leaf out
    from under its MatchResult — previously the stale ``mr`` made
    insert rebuild a gapped token path and later matches returned
    suffix pages as if they covered slot 0 (cross-request KV
    corruption)."""
    from dllama_tpu.kv.manager import PagedKVManager

    kv = PagedKVManager(_StubEngine(), page_size=PS, n_pages=6)  # 5 usable
    A = [10 + i for i in range(8)]  # 2 pages, tree-only (refcount 1)
    assert kv.publish(0, A) == 2
    pa = kv.tree.match(A).pages

    # B extends A by 4 pages: 3 free, 1 short — and the ONLY refcount-1
    # leaf is A's own, which this publish just matched. It must be
    # pinned: eviction frees nothing and the publish is skipped whole.
    B = A + [60 + i for i in range(16)]
    assert kv.publish(1, B) == 0
    m = kv.tree.match(A)
    assert m.n_tokens == 8 and m.pages == pa  # prefix intact, same pages
    assert kv.tree.match(B).n_tokens == 8  # only the old prefix stored
    kv.check()

    # the pin was transient: a fitting publish still works afterwards
    C = [200 + i for i in range(8)]
    assert kv.publish(0, C) == 2
    kv.check()


@pytest.mark.fast
def test_match_retains_pages_until_release():
    """Regression: match() must pin the returned pages immediately —
    the scheduler runs the adopt copy a full tick after the match, and
    another lane's publish->evict in that gap previously freed and
    reallocated the refcount-1 pages, copying an unrelated sequence's
    KV into the new lane's prefix rows."""
    from dllama_tpu.kv.manager import PagedKVManager

    kv = PagedKVManager(_StubEngine(), page_size=PS, n_pages=6)  # 5 usable
    A = [10 + i for i in range(8)]  # 2 pages, tree-only
    assert kv.publish(0, A) == 2
    m, pages = kv.match(1, A + [9])
    assert m == 8 and pages == kv.tree.match(A).pages
    assert all(kv.pool.refcount(p) == 2 for p in pages)  # pinned NOW

    # another lane publishes in the match->adopt gap, filling the pool
    # and then forcing an eviction: the pinned pages are untouchable,
    # the pressure lands on the other leaf instead
    D = [90 + i for i in range(12)]
    assert kv.publish(0, D) == 3  # pool now full
    E = [300 + i for i in range(4)]
    assert kv.publish(0, E) == 1  # evicts D's leaf, never A's
    assert kv.tree.match(D).n_tokens == 0
    assert kv.tree.match(A).pages == pages
    assert all(kv.pool.refcount(p) == 2 for p in pages)

    kv.adopt(1, pages)  # device copy only: no double retain
    assert all(kv.pool.refcount(p) == 2 for p in pages)
    kv.release_lane(1)  # the single release path drops the match pin
    assert all(kv.pool.refcount(p) == 1 for p in pages)
    kv.check()


# -- paged gather/scatter/view helpers ---------------------------------------


@pytest.mark.fast
def test_gather_scatter_paged_view_roundtrip():
    rng = np.random.default_rng(0)
    P, KH, ps, hd = 6, 2, 4, 8
    pool_l = jnp.asarray(rng.normal(size=(P, KH, ps, hd)), jnp.float32)
    ids = jnp.asarray([3, 1, 4], jnp.int32)

    rows = gather_pages(pool_l, ids)
    assert rows.shape == (KH, 3 * ps, hd)
    # row (slot s, offset o) is page ids[s] row o
    np.testing.assert_array_equal(
        np.asarray(rows[:, ps: 2 * ps]), np.asarray(pool_l[1])
    )
    back = scatter_pages(jnp.zeros_like(pool_l), ids, rows)
    np.testing.assert_array_equal(
        np.asarray(back[np.asarray(ids)]), np.asarray(pool_l[np.asarray(ids)])
    )

    # QuantKV pools round-trip bytes and dequantize through paged_view
    dense = jnp.asarray(rng.normal(size=(KH, 3 * ps, hd)), jnp.float32)
    qv, qs = quantize_kv_rows(dense)
    qpool = QuantKV(
        jnp.zeros((P, KH, ps, hd), jnp.int8),
        jnp.ones((P, KH, ps, 1), jnp.float32),
    )
    qpool = scatter_pages(qpool, ids, QuantKV(qv, qs))
    got = gather_pages(qpool, ids)
    np.testing.assert_array_equal(np.asarray(got.q), np.asarray(qv))
    view = paged_view(qpool, ids, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(view), np.asarray(dequant_kv(QuantKV(qv, qs), jnp.float32)),
        rtol=0, atol=0,
    )


# -- paged flash decode kernel (interpret mode) -------------------------------


def _ref_attention(q, k, v, pos):
    """[B,1,H,hd] x per-lane [KH, S, hd] causal reference."""
    b, _, h, hd = q.shape
    kh = k[0].shape[0]
    g = h // kh
    out = np.zeros_like(np.asarray(q))
    for lane in range(b):
        for head in range(h):
            qh = np.asarray(q[lane, 0, head], np.float32)
            kk = np.asarray(k[lane][head // g], np.float32)[: pos[lane] + 1]
            vv = np.asarray(v[lane][head // g], np.float32)[: pos[lane] + 1]
            s = kk @ qh / np.sqrt(hd)
            w = np.exp(s - s.max())
            w /= w.sum()
            out[lane, 0, head] = w @ vv
    return out


@pytest.mark.fast
@pytest.mark.parametrize("quant", [False, True])
def test_paged_flash_decode_matches_dense(quant):
    from dllama_tpu.ops.flash_attention import paged_flash_decode

    rng = np.random.default_rng(1)
    B, H, KH, hd, ps, P = 2, 4, 2, 16, 4, 10
    n_blocks = 4  # 16 positions of logical window per lane
    q = jnp.asarray(rng.normal(size=(B, 1, H, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, KH, ps, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, KH, ps, hd)), jnp.float32)
    # lane 0 and lane 1 SHARE physical pages 3,4 for their first two
    # blocks — the cross-lane sharing read path; padding slots point at
    # the scratch page and sit beyond each lane's causal frontier
    pt = jnp.asarray([[3, 4, 5, 0], [3, 4, 7, 8]], jnp.int32)
    pos = jnp.asarray([9, 14], jnp.int32)

    if quant:
        kq = QuantKV(*quantize_kv_rows(kp.reshape(P * KH * ps, hd))[:2])
        kq = QuantKV(kq.q.reshape(P, KH, ps, hd), kq.s.reshape(P, KH, ps, 1))
        vq = QuantKV(*quantize_kv_rows(vp.reshape(P * KH * ps, hd))[:2])
        vq = QuantKV(vq.q.reshape(P, KH, ps, hd), vq.s.reshape(P, KH, ps, 1))
        out = paged_flash_decode(q, kq, vq, pt, pos, interpret=True)
        kd = dequant_kv(kq, jnp.float32)
        vd = dequant_kv(vq, jnp.float32)
    else:
        out = paged_flash_decode(q, kp, vp, pt, pos, interpret=True)
        kd, vd = kp, vp

    k_lanes = [gather_pages(kd, pt[lane]) for lane in range(B)]
    v_lanes = [gather_pages(vd, pt[lane]) for lane in range(B)]
    ref = _ref_attention(q, k_lanes, v_lanes, np.asarray(pos))
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


# -- engine seam: publish -> adopt byte parity --------------------------------


CFG = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=4, n_kv_heads=2,
           head_dim=16, vocab_size=256, seq_len=64)


@pytest.fixture(scope="module")
def tiny_model(tmp_path_factory):
    d = tmp_path_factory.mktemp("kvpool")
    mp = str(d / "m.m")
    make_tiny_model(mp, cfg=CFG)
    return mp


def _stream(e, lane, token, pos, steps, seed):
    """Seeded single-lane decode stream (other lane parked): per-lane
    (seed, position) keys make it depend on nothing else."""
    toks, t, p = [], token, pos
    active = [i == lane for i in range(e.batch_size)]
    while len(toks) < steps:
        n = min(4, steps - len(toks))
        rows = e.decode_lanes(
            [t if i == lane else 0 for i in range(e.batch_size)],
            [p if i == lane else 0 for i in range(e.batch_size)],
            n, active,
            [0.8] * e.batch_size, [0.9] * e.batch_size,
            seeds=[seed if i == lane else None for i in range(e.batch_size)],
        )
        toks.extend(r[lane] for r in rows)
        t, p = toks[-1], p + n
    return toks


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_engine_publish_adopt_parity(tiny_model, kv_dtype):
    """KV published from one lane and adopted into ANOTHER produces the
    byte-identical seeded stream a fresh prefill would: full-page
    adoption, and partial-tail adoption resumed by chunked suffix
    prefill (the scheduler's mid-page path). int8 pools round-trip the
    quantized bytes + scales through the same programs."""
    from dllama_tpu.runtime.engine import InferenceEngine

    kw = {"kv_dtype": kv_dtype} if kv_dtype else {}
    e = InferenceEngine(
        tiny_model, tp=1, dtype=jnp.float32, temperature=0.8, batch_size=2,
        **kw,
    )
    ps = 4
    e.init_kv_pool(ps, n_pages=16)
    prompt = [2 + (i * 7) % 250 for i in range(23)]  # 22 fills: 5.5 pages

    # fresh reference on lane 1
    e.prefill_lane(1, prompt, pos0=0)
    expected = _stream(e, 1, prompt[-1], len(prompt) - 1, 10, seed=42)

    # lane 0 prefills the same prompt and publishes its 5 full pages
    e.prefill_lane(0, prompt, pos0=0)
    pages = [1, 2, 3, 4, 5]
    e.kv_publish(0, pages, start_page=0)

    # a later "admission" on lane 1: adopt rows [0, 20), chunk-prefill the
    # unmatched suffix fills [20, 22), decode — byte parity required
    e.reset()
    e.kv_adopt(1, pages)
    fills, cur = prompt[:-1], 20
    while cur < len(fills):
        cur += e.prefill_lane_chunk(1, fills[cur:], cur, budget=8)
    got = _stream(e, 1, prompt[-1], len(prompt) - 1, 10, seed=42)
    assert got == expected

    # whole-prefix adoption parity too (no suffix prefill at all): a
    # 21-token prompt has exactly 5 pages of fills
    p21 = prompt[:21]
    e.reset()
    e.prefill_lane(0, p21, pos0=0)
    exp21 = _stream(e, 0, p21[-1], 20, 8, seed=7)
    e.reset()
    e.kv_adopt(0, pages)  # pages hold fills[0:20] == p21[:-1]'s rows
    got21 = _stream(e, 0, p21[-1], 20, 8, seed=7)
    assert got21 == exp21

    # pool survives engine cache resets/epochs: adopt still works after
    # the cache buffer was rebuilt (pool is never donated by decode)
    e.reset()
    e.kv_adopt(1, pages)
    assert _stream(e, 1, p21[-1], 20, 8, seed=7) == exp21


def test_manager_dedup_cow_and_eviction(tiny_model):
    """PagedKVManager accounting over a live engine: repeat publishes
    dedup to zero new pages (the stored-once guarantee), a mid-page
    divergence COW-forks exactly one page, lane retains block eviction
    until released, and pool pressure LRU-evicts tree leaves."""
    from dllama_tpu.kv.manager import PagedKVManager
    from dllama_tpu.runtime.engine import InferenceEngine

    e = InferenceEngine(
        tiny_model, tp=1, dtype=jnp.float32, temperature=0.0, batch_size=2,
    )
    kv = PagedKVManager(e, page_size=4, n_pages=10)  # 9 usable pages
    ps = kv.page_size

    A = [10 + i for i in range(16)]  # 4 pages
    e.prefill_lane(0, A + [9], pos0=0)  # fills == A
    assert kv.publish(0, A) == 4
    used = kv.pool.stats().used
    assert used == 4 and kv.tree.n_pages == 4

    # stored once: the same tokens publish zero new pages from any lane
    e.prefill_lane(1, A + [9], pos0=0)
    assert kv.publish(1, A) == 0
    assert kv.pool.stats().used == used

    # match pins shared pages for the lane on the spot; adopt is only
    # the device copy; gauges see refcount >= 2
    m, pages = kv.match(0, A + [9])
    assert m == 16 and pages == kv.tree.match(A).pages
    assert kv.pool.stats().shared == 4
    kv.adopt(0, pages)
    assert kv.pool.stats().shared == 4

    # mid-page divergence: B shares 6 tokens (1.5 pages) -> k_shared=1,
    # the divergent page COW-forks, the rest alloc fresh
    B = A[:6] + [200, 201] + [210 + i for i in range(4)]  # 12 toks, 3 pages
    e.prefill_lane(1, B + [9], pos0=0)
    cow0 = kv.pool.stats().cow_forks
    assert kv.publish(1, B) == 2
    assert kv.pool.stats().cow_forks == cow0 + 1
    mb = kv.tree.match(B)
    assert mb.n_tokens == 12
    assert mb.pages[0] == kv.tree.match(A).pages[0]  # slot 0 shared
    assert mb.pages[1] != kv.tree.match(A).pages[1]  # slot 1 forked

    # pool pressure: 4 + 2 used, 3 free of 9. A 4-page publish must evict
    # the LRU unreferenced leaf — but A's pages are lane-retained, so B's
    # tail goes instead
    C = [300 + i for i in range(16)]
    e.prefill_lane(1, C + [9], pos0=0)
    b_ev = kv.c_evictions.value
    assert kv.publish(1, C) == 4
    assert kv.c_evictions.value > b_ev
    assert kv.tree.match(A).n_tokens == 16  # retained: survived
    assert kv.tree.match(B).n_tokens < 12  # evicted (shared head remains)
    kv.check()

    # release the lane; a full reset leaves a clean pool
    kv.release_lane(0)
    assert kv.pool.stats().shared == 0
    dbg = kv.debug()
    assert dbg["pool"]["free"] + dbg["pool"]["used"] == dbg["pool"]["total"]
    assert dbg["radix"]["pages"] == dbg["pool"]["used"]
    kv.reset()
    assert kv.pool.stats().used == 0 and kv.tree.n_pages == 0
    kv.check()


def test_manager_publish_failure_narrows_to_culprit(tiny_model, monkeypatch):
    """A TRANSIENT publish-dispatch failure (pool epoch unchanged: the
    donated buffer was never touched) must release only that publish's
    freshly-allocated pages — survivors' stored prefixes stay intact
    and matchable. Only a POISONING failure (the engine guard rebuilt
    the pool, epoch moved) drops the whole host accounting."""
    from dllama_tpu.kv.manager import PagedKVManager
    from dllama_tpu.runtime.engine import InferenceEngine

    e = InferenceEngine(
        tiny_model, tp=1, dtype=jnp.float32, temperature=0.0, batch_size=2,
    )
    kv = PagedKVManager(e, page_size=4, n_pages=8)
    A = [10 + i for i in range(8)]
    e.prefill_lane(0, A + [9], pos0=0)
    assert kv.publish(0, A) == 2
    pa = kv.tree.match(A).pages
    used0 = kv.pool.stats().used

    def boom(*a, **k):
        raise RuntimeError("injected publish failure")

    monkeypatch.setattr(e, "kv_publish", boom)
    B = [50 + i for i in range(8)]
    assert kv.publish(0, B) == 0  # swallowed, not raised
    # survivor intact: A's leaf and pages untouched, B's fresh pages freed
    assert kv.tree.match(A).n_tokens == 8 and kv.tree.match(A).pages == pa
    assert kv.pool.stats().used == used0
    assert kv.tree.match(B).n_tokens == 0
    kv.check()

    # poisoning failure: the dispatch guard rebuilt the pool buffer and
    # bumped the epoch — every page's device contents are gone, so the
    # host accounting (A included) must drop with them
    def boom_poison(*a, **k):
        e.kv_pool_epoch += 1
        raise RuntimeError("injected poisoning failure")

    monkeypatch.setattr(e, "kv_publish", boom_poison)
    C = [90 + i for i in range(8)]
    assert kv.publish(0, C) == 0
    assert kv.tree.n_pages == 0 and kv.pool.stats().used == 0  # full reset
    kv.check()
