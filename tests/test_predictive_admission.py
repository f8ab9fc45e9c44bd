"""Predictive SLO-aware admission control (ISSUE 20).

The LoadPredictor units are fake-clock / fake-engine pure tests (the
fast lockwatch subset); the scheduler-level tests drive a real tiny
engine through the ApiState directly (EDF ordering, infeasible-reject,
byte-identity predictive on vs off); the server-level test forces a
deterministic preemption and asserts the parked victim resumes
byte-identically through the PR 16 park/resume contract.
"""

import json
import math
import threading
import time
import urllib.error
import urllib.request

import jax.numpy as jnp
import pytest

from dllama_tpu.runtime.admission import (
    _CORR_MAX,
    LoadPredictor,
    OccupancySnapshot,
    Prediction,
    effective_deadline_ms,
)
from dllama_tpu.runtime.api_server import (
    ApiState,
    ChatMessage,
    InferenceParams,
    serve,
)
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.tokenizer import Tokenizer

from helpers import assert_one_spelling, make_tiny_model, make_tiny_tokenizer

CFG = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
           head_dim=16, vocab_size=288, seq_len=384)


# -- LoadPredictor units (no engine: cold-floor physics) ----------------------


@pytest.mark.fast
def test_predict_occupancy_sensitivity():
    """More load => higher forecast, on every axis the snapshot carries:
    queue depth raises TTFT (each queued request adds drain time), busy
    lanes raise TTFT (decode interleave per chunk), parked streams
    stretch TPOT by the oversubscription factor."""
    pred = LoadPredictor(object(), clock=lambda: 0.0)

    by_queue = [
        pred.predict(100, OccupancySnapshot(4, 4, queue_depth=q))
        for q in (0, 2, 6)
    ]
    assert by_queue[0].ttft_ms < by_queue[1].ttft_ms < by_queue[2].ttft_ms
    assert (
        by_queue[0].queue_wait_ms
        < by_queue[1].queue_wait_ms
        < by_queue[2].queue_wait_ms
    )

    idle = pred.predict(100, OccupancySnapshot(4, 0))
    busy = pred.predict(100, OccupancySnapshot(4, 4))
    assert busy.ttft_ms > idle.ttft_ms

    over = pred.predict(100, OccupancySnapshot(4, 4, parked=4))
    assert over.tpot_ms == pytest.approx(2.0 * idle.tpot_ms)  # 8 streams / 4 lanes

    for p in (*by_queue, idle, busy, over):
        assert math.isfinite(p.ttft_ms) and math.isfinite(p.tpot_ms)
        assert p.ttft_ms > 0 and p.tpot_ms > 0


@pytest.mark.fast
def test_predict_prefix_match_sensitivity():
    """A radix-tree match is prefill the engine skips: matched tokens
    shrink the chunk count and the TTFT, floored at one chunk (admission
    always replays the last matched token for the first logits)."""
    pred = LoadPredictor(object())
    occ = OccupancySnapshot(4, 2, admission_chunk=32)
    full = pred.predict(256, occ)
    half = pred.predict(256, occ, matched_tokens=128)
    whole = pred.predict(256, occ, matched_tokens=256)
    assert full.prefill_chunks == 8
    assert half.prefill_chunks == 4
    assert whole.prefill_chunks == 1
    assert full.ttft_ms > half.ttft_ms > whole.ttft_ms


@pytest.mark.fast
def test_queue_drain_and_retry_after_monotonic_in_queue_depth():
    """The satellite contract: every shed Retry-After is derived from the
    predicted queue-drain time, monotonic in queue depth and capped at
    the max-wait knob."""
    pred = LoadPredictor(object())
    drains = [
        pred.queue_drain_seconds(OccupancySnapshot(2, 2, queue_depth=q))
        for q in range(6)
    ]
    assert all(b > a for a, b in zip(drains, drains[1:])), drains

    ras = [
        pred.retry_after_s(
            OccupancySnapshot(2, 2, queue_depth=q), max_wait_ms=30_000
        )
        for q in (0, 10, 50)
    ]
    assert ras[0] >= 1
    assert ras[0] < ras[1] < ras[2], ras
    # the cap: an absurd backlog still advertises at most max_wait
    assert pred.retry_after_s(
        OccupancySnapshot(2, 2, queue_depth=10_000), max_wait_ms=4_000
    ) == 4


@pytest.mark.fast
def test_ewma_self_calibration_converges():
    """Closed loop: predictions fold their own observed error back in,
    so a consistently-slow reality converges the forecast onto itself;
    a single wild observation is clamped, never a 10x swing."""
    pred = LoadPredictor(object())
    occ = OccupancySnapshot(2, 1)
    true_ms = 300.0
    # reality is consistently 2x the uncorrected tpot forecast: the
    # closed loop must converge the correction onto that fixed truth
    true_tpot_ms = 2.0 * pred.predict(64, occ).tpot_ms
    for _ in range(40):
        p = pred.predict(64, occ)
        pred.observe_ttft(p.ttft_ms, true_ms)
        pred.observe_tpot(p.tpot_ms, true_tpot_ms)
    final = pred.predict(64, occ)
    assert final.ttft_ms == pytest.approx(true_ms, rel=0.10)
    assert final.tpot_ms == pytest.approx(true_tpot_ms, rel=0.10)
    snap = pred.snapshot()
    assert snap["n_observations"] == 40
    assert snap["tpot_correction"] == pytest.approx(2.0, rel=0.10)

    # clamp: absurd ratios saturate at the correction ceiling
    wild = LoadPredictor(object(), alpha=0.9)
    for _ in range(50):
        wild.observe_ttft(1.0, 1e9)
    assert wild.snapshot()["ttft_correction"] <= _CORR_MAX
    # degenerate observations are ignored entirely
    n0 = wild.snapshot()["n_observations"]
    wild.observe_ttft(0.0, 100.0)
    wild.observe_ttft(100.0, -1.0)
    assert wild.snapshot()["n_observations"] == n0


@pytest.mark.fast
def test_step_seconds_prefers_measured_over_floor():
    """Cost resolution order: measured step p50 (once enough samples
    exist) > analytic cost model > cold floor."""

    class _Child:
        def __init__(self, count, p50):
            self.count, self._p50 = count, p50

        def percentile(self, q):
            return self._p50

    class _Hist:
        def __init__(self, children):
            self._children = children

        def labels(self, kind):
            return self._children[kind]

    class _Engine:
        def __init__(self, count):
            self._m_step = _Hist({
                "prefill_lane_chunk": _Child(count, 0.007),
                "decode_lanes": _Child(count, 0.003),
            })

    warm = LoadPredictor(_Engine(count=50))
    assert warm.step_seconds("prefill_lane_chunk", 0.05) == 0.007
    assert warm.step_seconds("decode_lanes", 0.02) == 0.003

    # below MIN_STEP_SAMPLES (and no cost_report): the cold floor
    cold = LoadPredictor(_Engine(count=2))
    assert cold.step_seconds("prefill_lane_chunk", 0.05) == 0.05
    assert cold.step_seconds("decode_lanes", 0.02) == 0.02


@pytest.mark.fast
def test_effective_deadline_edf_key():
    """Deterministic EDF keys: hints win (tightest hint), the unhinted
    priority ladder becomes deadline offsets preserving strict
    high < normal < low ordering — the PR 12 contract."""
    now = 1_000_000.0
    assert effective_deadline_ms(now, deadline_ms=5000.0) == now + 5000.0
    assert effective_deadline_ms(
        now, deadline_ms=5000.0, ttft_budget_ms=800.0
    ) == now + 800.0

    hi = effective_deadline_ms(now, "high")
    no = effective_deadline_ms(now, "normal")
    lo = effective_deadline_ms(now, "low")
    assert hi < no < lo
    assert no == now + 600_000.0
    assert no - hi == 60_000.0 and lo - no == 60_000.0
    # unknown priority degrades to normal
    assert effective_deadline_ms(now, "vip") == no
    # a hinted low-priority request still beats an unhinted high one:
    # explicit budgets always dominate the synthetic ladder
    assert effective_deadline_ms(now, "low", deadline_ms=1000.0) < hi
    # determinism: same inputs, same key
    assert effective_deadline_ms(now, "low", deadline_ms=1000.0) == (
        effective_deadline_ms(now, "low", deadline_ms=1000.0)
    )


# -- knobs: env + CLI ---------------------------------------------------------


KNOB_TWINS = (
    "DLLAMA_ADMISSION_PREDICT", "DLLAMA_ADMISSION_MAX_WAIT_MS",
    "DLLAMA_DEADLINE_DEFAULT_MS", "DLLAMA_DEADLINE_PRIORITY_STEP_MS",
)


@pytest.mark.parametrize("name", KNOB_TWINS)
def test_admission_knob_resolution(unflagged, flagged, name):
    """The flag's default with the former variable set (`resolve_admission_
    knobs` / `resolve_deadline_knobs` read it until PR 45), and an explicit
    flag on the state the admission controller reads."""
    assert_one_spelling(name, unflagged, flagged)


@pytest.mark.fast
def test_admission_cli_flags():
    import argparse

    from dllama_tpu.cli import add_engine_args

    parser = argparse.ArgumentParser()
    add_engine_args(parser)
    args = parser.parse_args([
        "--admission-predict",
        "--admission-max-wait-ms", "5000",
        "--deadline-default-ms", "100000",
        "--deadline-priority-step-ms", "1000",
    ])
    assert args.admission_predict is True
    assert args.admission_max_wait_ms == 5000
    assert args.deadline_default_ms == 100_000
    assert args.deadline_priority_step_ms == 1000
    # absent flags hold their defaults: nothing else resolves them
    blank = parser.parse_args([])
    assert blank.admission_predict is False
    assert blank.admission_max_wait_ms == 30_000


# -- router: Retry-After propagation + shed backoff ---------------------------


@pytest.mark.fast
def test_router_retry_after_parse():
    from dllama_tpu.fleet.router import _retry_after_s

    assert _retry_after_s("3") == 3
    assert _retry_after_s(5) == 5
    assert _retry_after_s("2.7") == 2
    assert _retry_after_s(None) == 2
    assert _retry_after_s("abc") == 2
    assert _retry_after_s("0") == 2
    assert _retry_after_s(None, default=7) == 7


@pytest.mark.fast
def test_router_shed_backoff_ordering(tmp_path):
    """A replica that shed with Retry-After is demoted to the spill
    tail (soonest-free first) until its self-predicted busy window
    expires; nothing is ever dropped, and the all-shed 503 quotes the
    smallest non-expired wait."""
    from dllama_tpu.fleet.replicas import ReplicaRegistry
    from dllama_tpu.fleet.router import RouterState

    tp_ = str(tmp_path / "t.t")
    make_tiny_tokenizer(tp_, chat_template="<|start_header_id|>")
    reg = ReplicaRegistry(
        {"a": "http://a", "b": "http://b", "c": "http://c"},
        fetch=lambda url: {"status": "ok"},
    )
    state = RouterState(reg, Tokenizer(tp_))

    assert state.min_shed_wait_s() is None
    assert state.order_by_backoff(["a", "b", "c"]) == ["a", "b", "c"]

    state.note_shed("a", "30")
    state.note_shed("b", 10)
    # free replica keeps affinity order; busy ones spill soonest-free
    assert state.order_by_backoff(["a", "b", "c"]) == ["c", "b", "a"]
    assert state.shed_wait_s("c") == 0.0
    assert 0.0 < state.shed_wait_s("b") <= 10.0
    assert state.shed_wait_s("b") < state.shed_wait_s("a")
    # the honest all-shed Retry-After: ceil of the smallest live wait
    assert 1 <= state.min_shed_wait_s() <= 10


# -- scheduler level: EDF, infeasible-reject, byte-identity -------------------


@pytest.fixture(scope="module")
def tiny_paths(tmp_path_factory):
    d = tmp_path_factory.mktemp("predadm")
    mp, tp_ = str(d / "m.m"), str(d / "t.t")
    make_tiny_model(mp, cfg=CFG)
    make_tiny_tokenizer(tp_, chat_template="<|start_header_id|>")
    return mp, tp_


@pytest.fixture(scope="module")
def pred_state(tiny_paths):
    """A predictive-mode scheduler ApiState driven directly (no HTTP)."""
    mp, tp_ = tiny_paths
    tok = Tokenizer(tp_)
    engine = InferenceEngine(
        mp, tokenizer=tok, tp=1, dtype=jnp.float32, temperature=0.0, seed=3,
        batch_size=2,
    )
    state = ApiState(
        engine, tok, lane_block_size=4, admission_chunk=6,
        admission_predict=True,
    )
    assert state.scheduler is not None and state.predictor is not None
    return state


def _params(content, max_tokens=3, **kw):
    return InferenceParams(
        messages=[ChatMessage("user", content)], max_tokens=max_tokens,
        temperature=0.0, **kw,
    )


def _drain(job, timeout=300):
    deltas = []
    deadline = time.time() + timeout
    while True:
        kind, payload = job.events.get(
            timeout=max(0.1, deadline - time.time())
        )
        if kind == "delta":
            deltas.append(payload)
        elif kind == "done":
            return "".join(deltas), payload
        else:
            raise AssertionError(f"job errored: {payload}")


def _wait_lanes(state, n, timeout=300):
    sched = state.scheduler
    deadline = time.time() + timeout
    while time.time() < deadline:
        with sched.cv:
            active = sum(1 for ls in sched.lanes if ls is not None)
        if active >= n:
            return
        time.sleep(0.02)
    raise AssertionError(f"{n} lanes never became active")


def test_edf_ordering_deterministic(pred_state):
    """Three requests queued while every lane is busy admit in EDF
    order — tightest deadline first, unhinted synthetic deadlines last —
    regardless of submit order."""
    state = pred_state
    sched, rec = state.scheduler, state.recorder

    blockers = [
        sched.submit(_params(f"edf blocker {i}", max_tokens=220))
        for i in range(2)
    ]
    _wait_lanes(state, 2)
    base = rec.total_recorded

    # submit in REVERSE deadline order; distinct prompt lengths map the
    # admit events back to jobs (the admit record carries n_prompt)
    late = sched.submit(_params("e " * 30, priority="high"))  # unhinted
    mid = sched.submit(_params("dd " * 18, deadline_ms=150_000.0))
    tight = sched.submit(_params("c " * 6, deadline_ms=50_000.0))
    assert tight.edf_deadline_ms < mid.edf_deadline_ms < late.edf_deadline_ms

    for b in blockers:
        b.cancelled = True
        _drain(b)
    order = []
    for job in (tight, mid, late):
        _drain(job)
    n_by_job = {
        tight.n_prompt_tokens: "tight",
        mid.n_prompt_tokens: "mid",
        late.n_prompt_tokens: "late",
    }
    assert len(n_by_job) == 3, "prompts must tokenize to distinct lengths"
    for ev in rec.events():
        if ev["seq"] > base and ev["kind"] == "admit":
            if ev["n_prompt"] in n_by_job:
                order.append(n_by_job[ev["n_prompt"]])
    assert order == ["tight", "mid", "late"], order


def test_infeasible_rejected_before_admission(pred_state):
    """A hinted request whose budget cannot be met is refused by the
    pre-queue gate: structured reason, derived Retry-After, rejection
    counter bumped, and the scheduler queue never sees it."""
    state = pred_state
    sched = state.scheduler
    before = dict(state.m_admission_rejected.child_values())
    q_before = len(sched.pending)

    decision = state.admission_decision(
        "normal", _params("budget doom", ttft_budget_ms=0.0001)
    )
    assert decision is not None
    reason, retry_after = decision
    assert reason == "infeasible"
    assert isinstance(retry_after, int) and retry_after >= 1
    after = state.m_admission_rejected.child_values()
    assert after[("infeasible",)] == before.get(("infeasible",), 0) + 1
    assert len(sched.pending) == q_before  # never queued

    # unhinted requests are NEVER infeasible-rejected (PR 12 ladder)
    assert state.admission_decision("normal", _params("no hints")) is None
    # predictive off: the gate is exactly the reactive ladder
    state.admission_predict = False
    try:
        assert state.admission_decision(
            "normal", _params("budget doom", ttft_budget_ms=0.0001)
        ) is None
    finally:
        state.admission_predict = True


def test_state_retry_after_monotonic_in_queue_depth(pred_state):
    """predicted_retry_after() derives from live occupancy: parking
    opaque sentinels in the pending queue (no cv notify — the idle
    scheduler never observes them) must never DECREASE the advertised
    wait."""
    state = pred_state
    sched = state.scheduler
    ras = []
    sentinels = []
    try:
        for extra in (0, 200, 2000):
            with sched.cv:
                while len(sentinels) < extra:
                    s = object()
                    sentinels.append(s)
                    sched.pending.append(s)
            ras.append(state.predicted_retry_after())
    finally:
        with sched.cv:
            for s in sentinels:
                sched.pending.remove(s)
    assert all(r >= 1 for r in ras)
    assert ras == sorted(ras), ras
    assert ras[-1] <= max(1, state.admission_max_wait_ms // 1000)


def test_greedy_bytes_identical_predictive_on_off(pred_state):
    """The acceptance invariant: prediction only gates and orders work.
    The same greedy request produces byte-identical output with the
    controller on, off, and with deadline hints attached."""
    state = pred_state
    sched = state.scheduler

    text_on, reason = _drain(
        sched.submit(_params("determinism probe", max_tokens=16))
    )
    assert reason in ("stop", "length")
    state.admission_predict = False
    try:
        text_off, _ = _drain(
            sched.submit(_params("determinism probe", max_tokens=16))
        )
    finally:
        state.admission_predict = True
    text_hinted, _ = _drain(
        sched.submit(_params(
            "determinism probe", max_tokens=16, deadline_ms=90_000.0,
        ))
    )
    assert text_on == text_off == text_hinted


def test_prediction_error_is_tracked(pred_state):
    """Admission records a forecast; finish scores it: the error ring
    feeds /v1/debug/admission and the predict-error histogram has
    samples with finite values."""
    state = pred_state
    _drain(state.scheduler.submit(_params("score me", max_tokens=8)))
    stats = state.predict_error_stats()
    assert stats["n"] >= 1
    assert stats["p50_ms"] is not None and math.isfinite(stats["p50_ms"])
    assert stats["p95_ms"] is not None and math.isfinite(stats["p95_ms"])
    snap = state.predictor.snapshot()
    assert snap["n_observations"] >= 1
    ttft_child = state.m_predict_error.labels(signal="ttft")
    assert ttft_child.count >= 1


# -- server level: deterministic preemption + park/resume byte parity ---------

LOW_PROMPTS = [
    "tell me a long winding story about lane zero",
    "tell me a long winding story about lane one",
]
HIGH_PROMPT = "urgent deadline question"


@pytest.fixture(scope="module")
def preempt_server(tiny_paths):
    """2-lane pool-native predictive server; max_streams == lanes keeps
    PR 16 oversubscription parking OUT of the picture, so the only park
    path left is deadline preemption."""
    mp, tp_ = tiny_paths
    tok = Tokenizer(tp_)
    engine = InferenceEngine(
        mp, tokenizer=tok, tp=1, dtype=jnp.float32, temperature=0.0, seed=3,
        batch_size=2,
    )
    srv = serve(
        engine, tok, host="127.0.0.1", port=0,
        lane_block_size=4, kv_page_size=4, kv_native=True, max_streams=2,
        admission_predict=True,
    )
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield srv
    srv.shutdown()


def _url(srv):
    return f"http://127.0.0.1:{srv.server_address[1]}"


def _chat(srv, content, max_tokens=40, priority=None, deadline_ms=None,
          ttft_budget_ms=None, headers=None):
    payload = {
        "model": "m", "stream": False, "max_tokens": max_tokens,
        "temperature": 0,
        "messages": [{"role": "user", "content": content}],
    }
    if priority is not None:
        payload["priority"] = priority
    if deadline_ms is not None:
        payload["deadline_ms"] = deadline_ms
    if ttft_budget_ms is not None:
        payload["ttft_budget_ms"] = ttft_budget_ms
    hdrs = {"Content-Type": "application/json"}
    if headers:
        hdrs.update(headers)
    req = urllib.request.Request(
        _url(srv) + "/v1/chat/completions",
        data=json.dumps(payload).encode(), headers=hdrs, method="POST",
    )
    with urllib.request.urlopen(req, timeout=600) as r:
        data = json.loads(r.read())
    choice = data["choices"][0]
    assert choice["finish_reason"] in ("stop", "length")
    return choice["message"]["content"]


def _get_json(srv, path):
    with urllib.request.urlopen(_url(srv) + path, timeout=30) as r:
        return json.loads(r.read())


def test_debug_admission_endpoint(preempt_server):
    snap = _get_json(preempt_server, "/v1/debug/admission")
    assert snap["predictive"] is True
    assert snap["max_wait_ms"] >= 1
    assert snap["retry_after_s"] >= 1
    assert set(snap["occupancy"]) >= {
        "lanes_total", "active_lanes", "queue_depth", "oversubscription",
    }
    assert set(snap["predictor"]) >= {
        "ttft_correction", "tpot_correction", "prefill_chunk_s",
    }
    assert snap["prediction_error"]["n"] >= 0


def test_deadline_header_infeasible_reject(preempt_server):
    """The fleet router forwards x-dllama-deadline-ms; a relayed budget
    that cannot be met is shed as infeasible with a derived
    Retry-After — no body hint needed."""
    state = preempt_server.state
    before = dict(state.m_admission_rejected.child_values())
    with pytest.raises(urllib.error.HTTPError) as exc:
        _chat(
            preempt_server, "relayed doomed budget",
            headers={"x-dllama-deadline-ms": "0.0001"},
        )
    e = exc.value
    assert e.code == 429
    err = json.loads(e.read())["error"]
    assert "infeasible" in err["message"]
    assert err["retryable"] is True
    assert int(e.headers["Retry-After"]) >= 1
    after = state.m_admission_rejected.child_values()
    assert after[("infeasible",)] == before.get(("infeasible",), 0) + 1


def test_preemption_parks_victim_byte_identical(preempt_server, monkeypatch):
    """The seeded preemption test: two low-priority greedy streams hold
    both lanes past the no-thrash progress floor; a deadline-hinted
    high-priority request arrives; the forecast (made deterministic)
    says it blows its budget waiting but meets it on a freed lane — so
    the scheduler parks one low stream through the PR 16 contract. All
    three streams complete byte-identical to their uncontended solo
    runs: the victim was paused, never restarted."""
    srv = preempt_server
    state = srv.state
    sched = state.scheduler

    solo_low = [_chat(srv, p, max_tokens=48) for p in LOW_PROMPTS]
    solo_high = _chat(srv, HIGH_PROMPT, max_tokens=8)
    base_resumes = state.m_stream_resumes.value
    base_events = state.recorder.total_recorded

    def fake_predict(n_tok, occ, matched_tokens=0):
        # deterministic forecast: infeasible while both lanes are busy
        # and the request waits in queue, trivially feasible otherwise
        # (the freed-lane forecast zeroes queue_depth and drops a lane)
        busy = occ.active_lanes >= 2 and occ.queue_depth > 0
        return Prediction(
            ttft_ms=1e9 if busy else 1.0, tpot_ms=1.0,
            queue_wait_ms=0.0, prefill_chunks=1,
        )

    monkeypatch.setattr(state.predictor, "predict", fake_predict)

    results = [None, None]

    def run_low(i):
        results[i] = _chat(srv, LOW_PROMPTS[i], max_tokens=48, priority="low")

    threads = [
        threading.Thread(target=run_low, args=(i,)) for i in range(2)
    ]
    for t in threads:
        t.start()
    # wait until both lanes are decoding with more than one block of
    # progress (the preemption victim floor)
    deadline = time.time() + 300
    while time.time() < deadline:
        with sched.cv:
            active = [
                i for i, ls in enumerate(sched.lanes) if ls is not None
            ]
            ready = (
                len(active) == 2
                and all(
                    sched._progress[i] > sched.block_size for i in active
                )
            )
        if ready:
            break
        time.sleep(0.01)
    else:
        raise AssertionError("low streams never filled both lanes")

    high = _chat(
        srv, HIGH_PROMPT, max_tokens=8, priority="high",
        deadline_ms=600_000.0,
    )
    for t in threads:
        t.join(timeout=600)

    assert high == solo_high
    assert results == solo_low, "preempted stream diverged after resume"

    pre = {
        k: v for k, v in state.m_preemptions.child_values().items()
    }
    assert sum(pre.values()) >= 1, "no preemption fired"
    assert pre.get(("priority",), 0) >= 1
    assert state.m_stream_resumes.value > base_resumes
    kinds = [
        e["kind"] for e in state.recorder.events()
        if e["seq"] > base_events
    ]
    assert "stream_preempt" in kinds
    assert "stream_park" in kinds and "stream_resume" in kinds

    # fully drained: no parked streams, no queue, pool invariant holds
    deadline = time.time() + 60
    while time.time() < deadline and (
        any(sched.lanes) or sched.admitting or sched.pending
    ):
        time.sleep(0.02)
    assert sched._n_parked == 0 and not sched.pending
    state.kv_manager.check()
