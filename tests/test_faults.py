"""Chaos-plane tests (PR 12): fault-spec parsing, deterministic draws,
and the seeded multi-lane soak.

The soak is the acceptance bar for the self-healing serving path: under
every armed schedule, each request either completes byte-identical to
the fault-free run or fails with a structured retryable error; the
scheduler thread never dies; and the PagePool invariant check passes
after every recovery. Load shedding and graceful drain ride the same
fixtures.
"""

import http.client
import json
import re
import threading
import time
import urllib.error
import urllib.request

import jax.numpy as jnp
import pytest

from dllama_tpu.formats import FloatType
from dllama_tpu.runtime.api_server import serve
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.runtime.faults import (
    FaultPlane,
    FaultSpecError,
    parse_fault_spec,
    set_fault_plane,
)
from dllama_tpu.tokenizer import Tokenizer

from helpers import make_tiny_model, make_tiny_tokenizer


@pytest.fixture(autouse=True)
def _disarm_fault_plane():
    """Every test leaves the process-wide plane unarmed, pass or fail."""
    yield
    set_fault_plane("")


# -- spec parsing -------------------------------------------------------------


def test_parse_valid_specs():
    scheds = parse_fault_spec(
        "dispatch:p=0.05:seed=7,kv_alloc:nth=12,"
        "dispatch:every=40:kind=poison:n=2:op=decode_lanes"
    )
    assert [s.site for s in scheds] == ["dispatch", "kv_alloc", "dispatch"]
    a, b, c = scheds
    assert a.p == 0.05 and a.seed == 7 and a.kind == "transient"
    assert b.nth == 12
    assert c.every == 40 and c.kind == "poison" and c.n == 2
    assert c.op == "decode_lanes"


def test_parse_empty_and_blank_segments():
    assert parse_fault_spec("") == []
    assert [s.site for s in parse_fault_spec("dispatch:nth=1, ,")] == [
        "dispatch"
    ]


@pytest.mark.parametrize(
    "spec",
    [
        "warp_core:p=0.5",            # unknown site
        "dispatch:p=0.5:mean=3",      # unknown key
        "dispatch:p=0.5:kind=flaky",  # unknown kind
        "dispatch",                   # no trigger
        "dispatch:kind=poison",       # no trigger either
        "dispatch:p=0.5:nth=3",       # two triggers
        "dispatch:p=abc",             # bad value
        "dispatch:p=1.5",             # p outside [0, 1]
        "dispatch:nth=0",             # nth must be >= 1
        "dispatch:every=0",           # every must be >= 1
        "dispatch:p",                 # not key=value
    ],
)
def test_parse_rejects(spec):
    with pytest.raises(FaultSpecError):
        parse_fault_spec(spec)


# -- deterministic draws ------------------------------------------------------


def test_unarmed_plane_is_free():
    plane = FaultPlane("")
    assert not plane.armed
    assert plane.draw("dispatch", op="decode_lanes") is None
    assert plane.counts() == {}


def test_nth_fires_exactly_once():
    plane = FaultPlane("dispatch:nth=3")
    fired = [plane.draw("dispatch") is not None for _ in range(10)]
    assert fired == [False, False, True] + [False] * 7
    assert plane.counts() == {"dispatch": 1}


def test_every_is_periodic_and_n_caps():
    plane = FaultPlane("dispatch:every=3:n=2")
    fired = [plane.draw("dispatch") is not None for _ in range(12)]
    # draws 3 and 6 fire; the n=2 cap silences draws 9 and 12
    assert fired == [
        False, False, True, False, False, True,
        False, False, False, False, False, False,
    ]
    assert plane.counts() == {"dispatch": 2}


def test_p_schedule_is_seed_reproducible():
    a = FaultPlane("dispatch:p=0.3:seed=11")
    b = FaultPlane("dispatch:p=0.3:seed=11")
    pa = [a.draw("dispatch") is not None for _ in range(200)]
    pb = [b.draw("dispatch") is not None for _ in range(200)]
    assert pa == pb
    assert any(pa) and not all(pa)


def test_op_filter_restricts_dispatch_schedule():
    plane = FaultPlane("dispatch:op=decode_lanes:nth=1")
    # non-matching ops do not even advance the draw counter
    assert plane.draw("dispatch", op="prefill_lane_chunk") is None
    assert plane.draw("kv_alloc") is None
    fault = plane.draw("dispatch", op="decode_lanes")
    assert fault is not None
    assert fault.site == "dispatch" and fault.op == "decode_lanes"
    assert fault.kind == "transient" and not fault.poison
    assert fault.seq == 1
    assert "decode_lanes" in str(fault)


def test_poison_fault_attributes():
    plane = FaultPlane("kv_alloc:nth=1:kind=poison")
    fault = plane.draw("kv_alloc", op="publish")
    assert fault is not None and fault.poison
    assert "poison" in str(fault)


# -- the chaos server ---------------------------------------------------------


@pytest.fixture(scope="module")
def chaos_server(tmp_path_factory):
    """4-lane CPU server the soak, shed, and recovery tests share."""
    d = tmp_path_factory.mktemp("api_chaos")
    mp, tp_ = str(d / "m.m"), str(d / "t.t")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=288, seq_len=384)
    make_tiny_model(mp, weight_type=FloatType.Q40, cfg=cfg)
    make_tiny_tokenizer(tp_, chat_template="<|start_header_id|>")
    tok = Tokenizer(tp_)
    engine = InferenceEngine(
        mp, tokenizer=tok, tp=1, dtype=jnp.float32, temperature=0.0, seed=3,
        batch_size=4,
    )
    srv = serve(engine, tok, host="127.0.0.1", port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield srv
    srv.shutdown()


def _url(srv):
    return f"http://127.0.0.1:{srv.server_address[1]}"


def _ask(srv, prompt, max_tokens=8, priority=None, timeout=300, extra=None):
    """One non-stream completion. Returns ("ok", content) or
    ("error", status, error_dict, retry_after_header)."""
    payload = {
        "messages": [{"role": "user", "content": prompt}],
        "max_tokens": max_tokens,
        "temperature": 0,
    }
    if priority is not None:
        payload["priority"] = priority
    if extra:
        payload.update(extra)
    req = urllib.request.Request(
        _url(srv) + "/v1/chat/completions",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            body = json.loads(r.read())
        return ("ok", body["choices"][0]["message"]["content"])
    except urllib.error.HTTPError as e:
        err = json.loads(e.read()).get("error", {})
        return ("error", e.code, err, e.headers.get("Retry-After"))


def _ask_many(srv, prompts, max_tokens=8):
    results = [None] * len(prompts)

    def worker(i):
        results[i] = _ask(srv, prompts[i], max_tokens=max_tokens)

    threads = [
        threading.Thread(target=worker, args=(i,))
        for i in range(len(prompts))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert all(r is not None for r in results), "a soak worker hung"
    return results


def _get_json(srv, path):
    with urllib.request.urlopen(_url(srv) + path, timeout=30) as r:
        return json.loads(r.read())


SOAK_PROMPTS = [
    "alpha", "beta particle", "gamma ray burst",
    "delta wing", "epsilon small", "zeta function",
]

# (spec, exact number of requests allowed to fail, or None = any)
SOAK_SCHEDULES = [
    # transient sprinkles: retry/backoff absorbs every one (ISSUE CI bar:
    # completion rate 1.0 for retryable schedules)
    ("dispatch:p=0.05:seed=7", 0),
    ("dispatch:every=7:seed=1", 0),
    # one decode poison: a batched step has no culprit, every lane
    # recovers and every stream stays byte-identical
    ("dispatch:op=decode_lanes:nth=2:kind=poison", 0),
    # admission poison: exactly the culprit lane fails (the lead of the
    # chunk program, whose riders start over), survivors resume. The first
    # program: a round's admissions may all ride in one
    ("dispatch:op=prefill_lane_chunk:nth=1:kind=poison", 1),
    # unfiltered poison sprinkle: outcome depends on which dispatch it
    # lands on — hold only the either-or invariant
    ("dispatch:p=0.08:seed=3:kind=poison:n=2", None),
]


def test_chaos_soak(chaos_server):
    """The seeded soak: >= 5 schedules against the 4-lane server."""
    state = chaos_server.state
    sched = state.scheduler
    baseline = {}
    for status, content in _ask_many(chaos_server, SOAK_PROMPTS):
        assert status == "ok"
    # second fault-free round IS the baseline: by now every prompt's
    # prefix is published, so faulted rounds see the same adopt-vs-
    # prefill split the baseline did
    for prompt, (status, content) in zip(
        SOAK_PROMPTS, _ask_many(chaos_server, SOAK_PROMPTS)
    ):
        assert status == "ok"
        baseline[prompt] = content

    for spec, n_fail_expected in SOAK_SCHEDULES:
        plane = set_fault_plane(spec)
        b_recovered = state.m_lanes_recovered.value
        try:
            results = _ask_many(chaos_server, SOAK_PROMPTS)
        finally:
            counts = plane.counts()
            set_fault_plane("")
        n_failed = 0
        for prompt, res in zip(SOAK_PROMPTS, results):
            if res[0] == "ok":
                assert res[1] == baseline[prompt], (
                    f"{spec}: surviving stream diverged for {prompt!r}"
                )
            else:
                n_failed += 1
                _, code, err, retry_after = res
                assert code == 503, (spec, res)
                assert err.get("retryable") is True, (spec, err)
                assert retry_after is not None, (spec, res)
        if n_fail_expected is not None:
            assert n_failed == n_fail_expected, (spec, results)
        if ":nth=" in spec:  # deterministic schedules must have fired
            assert sum(counts.values()) >= 1, (spec, counts)
        if spec.startswith("dispatch:op=decode_lanes"):
            # the poisoned decode had live lanes: they resumed
            assert state.m_lanes_recovered.value > b_recovered
        # the invariants the whole PR hangs on
        assert sched.thread.is_alive(), f"scheduler died under {spec}"
        sched.kv.check()
        assert not sched.admitting and not sched.pending

    # disarmed follow-up round: the server is fully healthy again
    for prompt, res in zip(
        SOAK_PROMPTS, _ask_many(chaos_server, SOAK_PROMPTS)
    ):
        assert res == ("ok", baseline[prompt])


def test_kv_alloc_fault_is_absorbed(chaos_server):
    """A publish-time pool-allocation failure costs future reuse, never
    the response: the stream already served when publish runs. Needs
    prompts the radix tree has NOT seen — a fully dedup'd publish
    returns before it ever allocates (or draws)."""
    state = chaos_server.state
    sched = state.scheduler
    prompts = [f"unseen kv alloc prompt number {i} " * 4 for i in range(6)]
    plane = set_fault_plane("kv_alloc:nth=1")
    try:
        first = _ask_many(chaos_server, prompts)
    finally:
        counts = plane.counts()
        set_fault_plane("")
    assert all(r[0] == "ok" for r in first), first
    assert counts == {"kv_alloc": 1}
    sched.kv.check()
    assert sched.thread.is_alive()
    # the un-published conversation re-prefills to the same bytes
    for (status, content), res in zip(first, _ask_many(chaos_server, prompts)):
        assert res == ("ok", content)


@pytest.mark.parametrize("kind", ["poison", "transient"])
def test_poison_recovery_resumes_stream_byte_identical(chaos_server, kind):
    """Arm a decode poison MID-STREAM: the lane re-prefills its history
    and the client's stream continues byte-identically — the blast-radius
    acceptance check, without soak timing in the way. A transient fault
    there is retried on the cache as it stands. Either way the dispatch
    that drew the fault was one ahead of a block in flight."""
    state = chaos_server.state
    # the loop runs one block ahead while requests wait for a lane: when
    # the first delta has arrived and two more requests have queued, blocks
    # have gone by, so leave enough of them to poison
    mt = 16 * state.scheduler.block_size + 4
    status, want = _ask(chaos_server, "resume me byte for byte", max_tokens=mt)
    assert status == "ok"
    b_recovered = state.m_lanes_recovered.value
    b_retries = state.m_dispatch_retries.value
    after_fault = state.m_decode_blocks.labels(order="drained_first", reason="fault")
    b_fault = after_fault.value
    # three longer streams take the other lanes and two more requests
    # queue for one: with a queue the loop runs ahead, and a batched fault
    # hits every stream
    beside = [f"a stream beside the faulted one, number {i}" for i in range(5)]
    want_beside = [_ask(chaos_server, p, max_tokens=2 * mt) for p in beside]
    got_beside = [None] * 5
    fillers = [threading.Thread(target=lambda i=i: got_beside.__setitem__(
        i, _ask(chaos_server, beside[i], max_tokens=2 * mt))) for i in range(5)]
    for t in fillers[:3]:
        t.start()
    deadline = time.time() + 60
    while time.time() < deadline and sum(ls is not None for ls in state.scheduler.lanes) < 3:
        time.sleep(0.01)

    req = urllib.request.Request(
        _url(chaos_server) + "/v1/chat/completions",
        data=json.dumps({
            "messages": [
                {"role": "user", "content": "resume me byte for byte"}
            ],
            "max_tokens": mt, "temperature": 0, "stream": True,
        }).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    deltas, armed = [], False
    with urllib.request.urlopen(req, timeout=300) as r:
        for raw in r:
            line = raw.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            ev = json.loads(line[len("data: "):])
            delta = ev["choices"][0].get("delta", {}).get("content")
            if delta:
                deltas.append(delta)
            if deltas and not armed:
                for t in fillers[3:]:
                    t.start()
                deadline = time.time() + 60
                while time.time() < deadline and not state.scheduler.pending:
                    time.sleep(0.002)
                # the queue has formed: the next block is the last one
                # dispatched after its predecessor's collect, those behind
                # it go ahead, and the third draws the fault
                set_fault_plane(
                    f"dispatch:op=decode_lanes:nth=3:kind={kind}"
                )
                armed = True
    plane = set_fault_plane("")
    assert armed
    assert "".join(deltas) == want, "recovered stream diverged"
    for t in fillers:
        t.join(timeout=300)
    assert got_beside == want_beside and all(r[0] == "ok" for r in got_beside)
    # the faulted dispatch was one ahead of a block in flight: that block
    # was collected (its tokens are in the stream) before the recovery or
    # the retry, and the first block after it says why it did not run ahead
    assert after_fault.value == b_fault + 1
    kinds = {e["kind"]
             for e in _get_json(chaos_server, "/v1/debug/recorder")["events"]}
    if kind == "poison":
        assert state.m_lanes_recovered.value > b_recovered
        assert {"fault_injected", "lane_recovery", "lane_recovered"} <= kinds
    else:
        assert state.m_lanes_recovered.value == b_recovered
        assert state.m_dispatch_retries.value == b_retries + 1
        assert {"fault_injected", "dispatch_retry"} <= kinds
    state.scheduler.kv.check()


def test_sse_flush_fault_cancels_only_that_stream(chaos_server):
    """An injected flush failure looks like the client hanging up: the
    stream dies, the lane is reclaimed, the server keeps serving."""
    state = chaos_server.state
    plane = set_fault_plane("sse_flush:nth=1")
    req = urllib.request.Request(
        _url(chaos_server) + "/v1/chat/completions",
        data=json.dumps({
            "messages": [{"role": "user", "content": "doomed stream"}],
            "max_tokens": 8, "temperature": 0, "stream": True,
        }).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=300) as r:
            raw = r.read().decode()
        assert "data: [DONE]" not in raw
    except (urllib.error.HTTPError, http.client.HTTPException, OSError):
        pass  # a torn/short-read connection is an acceptable client view
    assert plane.counts() == {"sse_flush": 1}
    set_fault_plane("")
    assert state.scheduler.thread.is_alive()
    status, _ = _ask(chaos_server, "after the torn stream")
    assert status == "ok"


def test_failed_admission_releases_pages_and_fails_job(chaos_server):
    """Satellite-1 regression: a job that dies MID-ADMISSION (no active
    stream yet) is failed with a structured retryable error — not leaked
    in self.admitting — and its adopted-page retains are released."""
    state = chaos_server.state
    sched = state.scheduler
    prompt = "leak check conversation " * 8  # long enough to span pages
    status, _ = _ask(chaos_server, prompt)  # publish a reusable prefix
    assert status == "ok"
    # same prefix + a fresh suffix: the admission adopts the published
    # pages (retains them) and must still prefill the unseen tail — a
    # fully-matched prompt would skip prefill and never hit the fault
    prompt2 = prompt + " plus an unpublished suffix to prefill"
    engine = state.engine
    real = engine.prefill_lane_chunk

    def boom(*a, **k):
        raise RuntimeError("injected admission failure")

    engine.prefill_lane_chunk = boom
    try:
        res = _ask(chaos_server, prompt2)
    finally:
        engine.prefill_lane_chunk = real
    assert res[0] == "error"
    _, code, err, retry_after = res
    assert code == 503 and err["retryable"] is True
    assert retry_after is not None
    # nothing leaked: no admitting entry, no lane retains, pool invariant
    assert not sched.admitting
    assert sched.kv.debug()["lanes"] == {}
    sched.kv.check()
    status, _ = _ask(chaos_server, prompt2)
    assert status == "ok"


# -- load shedding ------------------------------------------------------------


def test_queue_full_shed_ladder(chaos_server):
    """Admission refuses by priority class once the queue is at depth:
    low sheds at half the threshold, normal at it, high rides out double.
    Sentinels are parked in the pending queue WITHOUT a cv notify, so the
    idle scheduler never observes them — the gate reads only len()."""
    state = chaos_server.state
    sched = state.scheduler
    b_shed = dict(state.m_shed.child_values())
    state.max_queue_depth = 2
    sentinels = [object(), object()]
    with sched.cv:
        sched.pending.extend(sentinels)
    try:
        for priority in ("normal", "low"):
            res = _ask(chaos_server, "shed me", priority=priority)
            assert res[0] == "error"
            _, code, err, retry_after = res
            assert code == 429
            assert "queue_full" in err["message"]
            assert err["retryable"] is True
            assert retry_after == str(err["retry_after_s"])
        # high priority rides out double the threshold (checked via the
        # gate directly: actually admitting a request would pop the
        # sentinels into the scheduler)
        assert state.admission_decision("high") is None
    finally:
        with sched.cv:
            for s in sentinels:
                sched.pending.remove(s)
        state.max_queue_depth = 0
    shed = state.m_shed.child_values()
    assert shed[("queue_full",)] == b_shed.get(("queue_full",), 0) + 2
    # with the queue drained the same request is admitted again
    assert _ask(chaos_server, "shed me no more")[0] == "ok"


def test_degraded_sheds_low_priority_only(chaos_server):
    """While the engine is degraded (watchdog/anomaly), spare capacity
    heals it: priority=low requests shed, normal traffic still lands."""
    state = chaos_server.state
    state.degraded_reasons = lambda: ["watchdog:test_forced"]
    try:
        res = _ask(chaos_server, "background job", priority="low")
        assert res[0] == "error"
        _, code, err, _ = res
        assert code == 429 and "degraded" in err["message"]
        assert _ask(chaos_server, "interactive user")[0] == "ok"
    finally:
        del state.degraded_reasons


def test_bad_priority_rejected(chaos_server):
    res = _ask(chaos_server, "hi", priority="vip")
    assert res[0] == "error" and res[1] == 400


def test_chaos_overload_predictive_admission(chaos_server):
    """Fault plane + overload + predictive admission (ISSUE 20): 3x the
    lane count of mixed-priority, mixed-deadline requests under a
    transient fault sprinkle. The scheduler never dies, every response
    is either a completed stream or a structured retryable error, and
    hopeless budgets are shed as infeasible up front instead of queuing
    to fail slowly."""
    state = chaos_server.state
    sched = state.scheduler
    prompts = [f"overload wave request {i}" for i in range(12)]
    extras = []
    for i in range(12):
        e = {"priority": ("high", "normal", "low")[i % 3]}
        if i % 4 == 0:
            e["deadline_ms"] = 300_000.0  # generous: feasible
        elif i % 4 == 2:
            e["ttft_budget_ms"] = 0.0001  # hopeless: must shed
        extras.append(e)
    hopeless = [i for i in range(12) if i % 4 == 2]
    b_rejected = dict(state.m_admission_rejected.child_values())

    state.admission_predict = True
    plane = set_fault_plane("dispatch:p=0.05:seed=13")
    results = [None] * 12
    try:

        def worker(i):
            results[i] = _ask(chaos_server, prompts[i], extra=extras[i])

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(12)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        set_fault_plane("")
        state.admission_predict = False
    assert all(r is not None for r in results), "an overload worker hung"

    for i, res in enumerate(results):
        if res[0] == "ok":
            continue
        _, code, err, retry_after = res
        assert code in (429, 503), (i, res)
        assert err.get("retryable") is True, (i, err)
        assert retry_after is not None and int(retry_after) >= 1, (i, res)
    # every hopeless budget was refused (never served); the rest
    # completed — the transient sprinkle is absorbed by retry/backoff
    for i in hopeless:
        assert results[i][0] == "error", (i, results[i])
    for i in range(12):
        if i not in hopeless:
            assert results[i][0] == "ok", (i, results[i])
    rejected = state.m_admission_rejected.child_values()
    assert rejected.get(("infeasible",), 0) >= (
        b_rejected.get(("infeasible",), 0) + 2
    )

    # the invariants the chaos plane holds everywhere
    assert sched.thread.is_alive(), "scheduler died under overload"
    t_end = time.time() + 180
    while time.time() < t_end and (sched.admitting or sched.pending):
        time.sleep(0.02)
    assert not sched.admitting and not sched.pending
    sched.kv.check()
    assert _ask(chaos_server, "after the overload wave")[0] == "ok"


# -- graceful drain -----------------------------------------------------------


@pytest.fixture
def drain_server(tmp_path_factory):
    """Function-scoped: draining is sticky, so the drained server must
    not be shared with other tests."""
    d = tmp_path_factory.mktemp("api_drain")
    mp, tp_ = str(d / "m.m"), str(d / "t.t")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=288, seq_len=384)
    make_tiny_model(mp, weight_type=FloatType.Q40, cfg=cfg)
    make_tiny_tokenizer(tp_, chat_template="<|start_header_id|>")
    tok = Tokenizer(tp_)
    engine = InferenceEngine(
        mp, tokenizer=tok, tp=1, dtype=jnp.float32, temperature=0.0, seed=3,
        batch_size=2,
    )
    srv = serve(engine, tok, host="127.0.0.1", port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield srv
    srv.shutdown()


def test_graceful_drain(drain_server):
    """POST /v1/drain: admission stops (503 + Retry-After, reason
    draining), the in-flight stream runs to completion, health flips to
    "draining", the gauge holds 1, and ``drained`` fires once idle."""
    state = drain_server.state
    first_delta = threading.Event()
    stream_result = {}

    def streamer():
        req = urllib.request.Request(
            _url(drain_server) + "/v1/chat/completions",
            data=json.dumps({
                "messages": [{"role": "user", "content": "drain survivor"}],
                "max_tokens": 64, "temperature": 0, "stream": True,
            }).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        chunks = []
        with urllib.request.urlopen(req, timeout=300) as r:
            for raw in r:
                chunks.append(raw.decode())
                if "data: " in chunks[-1]:
                    first_delta.set()
        stream_result["raw"] = "".join(chunks)

    t = threading.Thread(target=streamer)
    t.start()
    assert first_delta.wait(timeout=120), "stream never started"

    req = urllib.request.Request(
        _url(drain_server) + "/v1/drain", data=b"", method="POST"
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        body = json.loads(r.read())
    assert body["status"] == "draining"
    assert body["in_flight"] >= 1

    health = _get_json(drain_server, "/v1/health")
    assert health["status"] == "draining"
    assert health["draining_since_unix"] is not None

    res = _ask(drain_server, "too late")
    assert res[0] == "error"
    _, code, err, retry_after = res
    assert code == 503 and "draining" in err["message"]
    assert err["retryable"] is True and retry_after is not None

    with urllib.request.urlopen(_url(drain_server) + "/metrics",
                                timeout=30) as r:
        text = r.read().decode()
    m = re.search(r"^dllama_draining (\d+)", text, re.M)
    assert m and m.group(1) == "1"

    t.join(timeout=300)
    raw = stream_result.get("raw", "")
    assert raw.rstrip().endswith("data: [DONE]"), "in-flight stream cut off"
    assert '"error"' not in raw

    assert state.drained.wait(timeout=60), "drain never completed"
    kinds = [e["kind"]
             for e in _get_json(drain_server, "/v1/debug/recorder")["events"]]
    assert "drain_begin" in kinds and "drain_complete" in kinds
    # idempotent: a second drain reports, never re-arms
    with urllib.request.urlopen(req, timeout=30) as r:
        assert json.loads(r.read())["status"] == "draining"
    assert kinds.count("drain_begin") == 1
