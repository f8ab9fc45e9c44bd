"""Pool-native paged decode (PR 16): byte-parity + oversubscription.

The slab path (tests/test_kv_pool.py) moves published prefixes between
the lane slab and the pool with copy programs; pool-native mode makes
the pool the *only* KV storage — lanes decode through a per-lane page
table — so adoption is refcount bookkeeping and the only device copy
left is the COW fork of a mid-page boundary. These tests pin the two
invariants that make that safe to ship:

* **byte parity** — seeded streams decoded through the page table are
  token-identical to the slab engine (f32 and int8 pools, fresh and
  adopted prefixes, spec-on and spec-off);
* **zero-copy adoption** — a full-page adopt moves no bytes
  (`dllama_kv_copy_bytes_total` unchanged), a mid-page adopt forks
  exactly one page.

The server-level test drives oversubscription (`--max-streams` 2x the
lane count) and checks park -> resume returns byte-identical output.
"""

import json
import re
import threading
import urllib.request

import jax.numpy as jnp
import pytest

from dllama_tpu.formats import FloatType
from dllama_tpu.kv.manager import PagedKVManager
from dllama_tpu.runtime.api_server import serve
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.tokenizer import Tokenizer

from helpers import make_tiny_model, make_tiny_tokenizer

CFG = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=4, n_kv_heads=2,
           head_dim=16, vocab_size=256, seq_len=64)


@pytest.fixture(scope="module")
def tiny_model(tmp_path_factory):
    d = tmp_path_factory.mktemp("kvnative")
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


# -- engine level ------------------------------------------------------------


@pytest.mark.parametrize("kv_dtype", [
    pytest.param(None, marks=pytest.mark.fast),
    "int8",
])
def test_native_decode_parity(tiny_model, kv_dtype):
    """Decoding through the page table (lane_block_paged) is
    token-identical to the slab engine, f32 and QuantKV int8 pools."""
    kw = {"kv_dtype": kv_dtype} if kv_dtype else {}
    prompt = [2 + (i * 7) % 250 for i in range(23)]

    e = InferenceEngine(
        tiny_model, tp=1, dtype=jnp.float32, temperature=0.8,
        batch_size=2, **kw,
    )
    e.prefill_lane(1, prompt, pos0=0)
    expected = _stream(e, 1, prompt[-1], len(prompt) - 1, 10, seed=42)

    e2 = InferenceEngine(
        tiny_model, tp=1, dtype=jnp.float32, temperature=0.8,
        batch_size=2, **kw,
    )
    e2.init_kv_pool(4, native=True)
    nb = e2._kv_n_blocks
    e2.adopt_pages(1, list(range(1, nb + 1)))  # page 0 is the null page
    e2.prefill_lane(1, prompt, pos0=0)
    got = _stream(e2, 1, prompt[-1], len(prompt) - 1, 10, seed=42)
    assert got == expected


@pytest.mark.fast
def test_manager_native_zero_copy_and_cow(tiny_model):
    """Manager-level native flow: a full-page adopt moves ZERO device
    bytes (page-table writes + refcounts only), shared pages serve two
    lanes byte-identically, and a mid-page adopt forks exactly the
    boundary page (COW) before diverging."""
    prompt = [2 + (i * 7) % 250 for i in range(23)]

    e_ref = InferenceEngine(
        tiny_model, tp=1, dtype=jnp.float32, temperature=0.8, batch_size=2,
    )
    e_ref.prefill_lane(1, prompt, pos0=0)
    expected = _stream(e_ref, 1, prompt[-1], len(prompt) - 1, 10, seed=42)

    e = InferenceEngine(
        tiny_model, tp=1, dtype=jnp.float32, temperature=0.8, batch_size=2,
    )
    kv = PagedKVManager(e, page_size=4, native=True)
    m, pages = kv.match(0, prompt)
    assert (m, pages) == (0, [])
    kv.adopt(0, pages)  # native: allocates the lane's private page list
    e.prefill_lane(0, prompt, pos0=0)
    first = _stream(e, 0, prompt[-1], len(prompt) - 1, 10, seed=42)
    assert first == expected
    history = prompt + first
    assert kv.publish(0, history[:20]) == 5  # 5 full pages, page-aligned
    kv.release_lane(0)
    kv.check()

    # full-page adopt into the OTHER lane: zero copy bytes
    bytes0 = e._m_kv_copy_bytes.value
    m, pages = kv.match(1, prompt)
    assert m == 20
    kv.adopt(1, pages)
    assert e._m_kv_copy_bytes.value == bytes0, (
        "full-page adopt must copy zero bytes"
    )
    fills, cur = prompt[:-1], m
    while cur < len(fills):
        cur += e.prefill_lane_chunk(1, fills[cur:], cur, budget=8)
    got = _stream(e, 1, prompt[-1], len(prompt) - 1, 10, seed=42)
    assert got == expected
    # lane 1 publishes one more page over the 5 shared slots (dedup)
    h1 = prompt + got
    assert kv.publish(1, h1[:24]) == 1
    kv.release_lane(1)
    kv.check()

    # mid-page boundary: share 22 of the stored 24 tokens, then diverge
    p2 = prompt[:22] + [199, 198, 197]
    e_ref.reset()
    e_ref.prefill_lane(0, p2, pos0=0)
    exp2 = _stream(e_ref, 0, p2[-1], len(p2) - 1, 8, seed=9)

    m, pages = kv.match(0, p2)
    assert m == 22 and m % 4 != 0  # boundary falls mid-page
    kv.adopt(0, pages)
    assert e._m_kv_copy_bytes.value > bytes0, (
        "mid-page adopt must fork the boundary page"
    )
    fills, cur = p2[:-1], m
    while cur < len(fills):
        cur += e.prefill_lane_chunk(0, fills[cur:], cur, budget=8)
    got2 = _stream(e, 0, p2[-1], len(p2) - 1, 8, seed=9)
    assert got2 == exp2
    kv.release_lane(0)
    kv.check()


# -- server level: oversubscription ------------------------------------------

SRV_CFG = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=288, seq_len=384)
PROMPTS = [f"hello number {i} tell me a story" for i in range(4)]


@pytest.fixture(scope="module")
def native_server(tmp_path_factory):
    """2-lane pool-native server admitting up to 4 streams, with n-gram
    speculation on (greedy lanes verify drafts through the paged verify
    programs; a park resume rebuilds the lane's drafter)."""
    d = tmp_path_factory.mktemp("oversub")
    mp, tp_ = str(d / "m.m"), str(d / "t.t")
    make_tiny_model(mp, weight_type=FloatType.Q40, cfg=SRV_CFG)
    make_tiny_tokenizer(tp_, chat_template="<|start_header_id|>")
    tok = Tokenizer(tp_)
    engine = InferenceEngine(
        mp, tokenizer=tok, tp=1, dtype=jnp.float32, temperature=0.0, seed=3,
        batch_size=2,
    )
    srv = serve(
        engine, tok, host="127.0.0.1", port=0,
        kv_page_size=4, kv_native=True, max_streams=4, speculation="ngram",
    )
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield f"http://127.0.0.1:{srv.server_address[1]}", srv, (mp, tp_)
    srv.shutdown()


def _chat(url, content, max_tokens=40):
    payload = {
        "model": "m", "stream": False, "max_tokens": max_tokens,
        "temperature": 0,
        "messages": [{"role": "user", "content": content}],
    }
    req = urllib.request.Request(
        url + "/v1/chat/completions",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=600) as r:
        data = json.loads(r.read())
    choice = data["choices"][0]
    assert choice["finish_reason"] in ("stop", "length")
    return choice["message"]["content"]


def _metric(url, name):
    with urllib.request.urlopen(url + "/metrics") as r:
        metrics = r.read().decode()
    m = re.search(rf"^{name}(?:\{{[^}}]*\}})? ([0-9.e+-]+)$", metrics, re.M)
    return float(m.group(1)) if m else None


def test_oversubscription_park_resume_parity(native_server):
    """4 concurrent greedy streams on 2 lanes: every stream completes,
    at least one got parked and resumed, and each stream's bytes match
    its uncontended (solo) run exactly."""
    url, srv, _ = native_server
    # the metrics registry is the process's, and under xdist another file's
    # server may have parked streams in this worker already: count from here
    resumes0 = _metric(url, "dllama_stream_resumes_total")
    solo = [_chat(url, p) for p in PROMPTS]  # one at a time: no parking
    assert _metric(url, "dllama_stream_resumes_total") == resumes0

    results = [None] * len(PROMPTS)

    def run(i):
        results[i] = _chat(url, PROMPTS[i])

    threads = [threading.Thread(target=run, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)

    assert results == solo, "park -> resume changed stream bytes"
    assert _metric(url, "dllama_stream_resumes_total") > resumes0, (
        "oversubscribed run never parked a stream"
    )
    assert _metric(url, "dllama_streams_parked") == 0  # all drained
    sched = srv.state.scheduler
    assert sched._n_parked == 0 and not sched.pending
    srv.state.kv_manager.check()


def test_native_spec_off_parity(native_server, tmp_path_factory):
    """Speculative decoding through the paged verify programs is
    lossless: a spec-off pool-native server emits the identical
    bytes."""
    url, _, (mp, tp_) = native_server
    spec_on = _chat(url, "speculation parity probe", max_tokens=24)

    tok = Tokenizer(tp_)
    engine = InferenceEngine(
        mp, tokenizer=tok, tp=1, dtype=jnp.float32, temperature=0.0, seed=3,
        batch_size=2,
    )
    srv2 = serve(
        engine, tok, host="127.0.0.1", port=0,
        kv_page_size=4, kv_native=True, max_streams=4, speculation="off",
    )
    threading.Thread(target=srv2.serve_forever, daemon=True).start()
    url2 = f"http://127.0.0.1:{srv2.server_address[1]}"
    try:
        spec_off = _chat(url2, "speculation parity probe", max_tokens=24)
    finally:
        srv2.shutdown()
    assert spec_on == spec_off
