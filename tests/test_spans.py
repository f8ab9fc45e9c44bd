"""obs/spans.py + obs/slo.py + obs/watchdog.py unit tests.

Pure-Python (no engine) against injected fake clocks, local registries
and local flight recorders, so they ride the fast CI lane and are
deterministic: the span math, the window math and every watchdog stall
rule are driven by hand-advanced time, never by sleeps.
"""

import json
import os

import pytest

from dllama_tpu.obs.metrics import MetricsRegistry
from dllama_tpu.obs.recorder import FlightRecorder
from dllama_tpu.obs.slo import SloTracker
from dllama_tpu.obs.spans import SpanTracker
from dllama_tpu.obs.watchdog import EngineWatchdog

from helpers import assert_one_spelling

pytestmark = pytest.mark.fast


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


# -- SpanTracker -------------------------------------------------------------


def test_span_lifecycle_and_attrs():
    clk = FakeClock()
    st = SpanTracker(capacity=16, enabled=True, clock=clk)
    h = st.begin("queue", component="scheduler", request_id="r1", lane=2,
                 n_prompt=7)
    clk.t = 0.25
    st.end(h, reused=3)
    (s,) = st.completed()
    assert s["name"] == "queue"
    assert s["component"] == "scheduler"
    assert s["request_id"] == "r1"
    assert s["lane"] == 2
    assert s["t0"] == 0.0
    assert s["dur_s"] == 0.25
    assert s["attrs"] == {"n_prompt": 7, "reused": 3}
    # idempotent end: the error path racing the normal one records once
    st.end(h)
    assert len(st.completed()) == 1
    assert st.completed(request_id="nope") == []


def test_span_context_manager_records_on_raise():
    clk = FakeClock()
    st = SpanTracker(capacity=4, enabled=True, clock=clk)
    with pytest.raises(RuntimeError):
        with st.span("chunk", request_id="r1"):
            clk.t = 1.5
            raise RuntimeError("engine died")
    (s,) = st.completed()
    assert s["dur_s"] == 1.5  # the error still took the time


def test_span_disabled_is_noop():
    st = SpanTracker(capacity=4, enabled=False)
    assert st.begin("x") is None
    st.end(None)  # call sites never branch on enablement
    with st.span("y") as h:
        assert h is None
    assert st.completed() == []
    assert st.total_recorded == 0


def test_span_ring_overflow_records_event():
    rec = FlightRecorder(capacity=64)
    st = SpanTracker(capacity=2, enabled=True, recorder=rec)
    for _ in range(3):
        st.end(st.begin("s"))
    assert st.total_recorded == 3
    assert st.dropped == 1
    evs = rec.events("obs_overflow")
    assert len(evs) == 1  # first drop fires...
    assert evs[0]["what"] == "span_ring"
    for _ in range(2):
        st.end(st.begin("s"))
    assert st.dropped == 3  # ...then every `capacity` further drops
    assert len(rec.events("obs_overflow")) == 2


def test_chrome_trace_shape_and_roundtrip(tmp_path):
    clk = FakeClock()
    st = SpanTracker(capacity=16, enabled=True, clock=clk)
    h = st.begin("queue", component="scheduler", request_id="r1", lane=0)
    clk.t = 0.001
    st.end(h)
    h = st.begin("decode_lanes", component="engine")
    clk.t = 0.003
    st.end(h)
    trace = st.chrome_trace()
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    ms = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    assert len(xs) == 2
    # pid = component, tid = lane (-1 = no lane), ts/dur in microseconds
    q = next(e for e in xs if e["name"] == "queue")
    assert q["ts"] == 0.0 and q["dur"] == 1000.0 and q["tid"] == 0
    d = next(e for e in xs if e["name"] == "decode_lanes")
    assert d["tid"] == -1 and d["pid"] != q["pid"]
    names = {(e["name"], e["args"]["name"]) for e in ms}
    assert ("process_name", "scheduler") in names
    assert ("process_name", "engine") in names
    # the export is plain JSON a viewer can load back
    path = os.path.join(tmp_path, "tl.json")
    assert st.export_file(path) == 2
    with open(path) as f:
        loaded = json.load(f)
    assert loaded["dllama"]["n_spans"] == 2


def test_streamed_timeline_outlives_the_ring_and_loads_as_a_chrome_trace(tmp_path):
    """--timeline-out appends every completed span once: a run with more
    spans than the ring holds leaves all of them in the file, which is a
    Chrome trace in the JSON array form, closing bracket optional."""
    from dllama_tpu.obs.spans import read_timeline

    clk = FakeClock(100.0)
    st = SpanTracker(capacity=8, enabled=True, clock=clk, wall_clock=lambda: 5e9)
    path = os.path.join(tmp_path, "timeline.json")
    st.set_sink(path)
    for i in range(50):
        h = st.begin("sched_tick", component="scheduler", lane=i % 3, i=i)
        clk.t += 0.002
        st.end(h, done=True)
    assert len(st.completed()) == 8 and st.dropped == 42
    # the file of a server that still runs may end inside a line
    assert len(read_timeline(path)[1]) < 50
    st.set_sink(None)
    with open(path) as f:
        text = f.read()
    assert text.startswith("[") and text.endswith(",\n")
    assert all(json.loads(ln.lstrip("[").rstrip(",")) for ln in text.splitlines())
    events = json.loads(text.rstrip(",\n") + "]")
    assert events[0]["name"] == "timeline_epoch"
    assert events[0]["args"] == {"epoch_unix": 5e9, "epoch_monotonic": 100.0}
    xs = [e for e in events if e["ph"] == "X"]
    assert [e["args"]["i"] for e in xs] == list(range(50))
    assert xs[7]["ts"] == pytest.approx(7 * 2000.0) and xs[7]["dur"] == pytest.approx(2000.0)
    assert all(e["args"]["done"] is True for e in xs)
    # each pid and (pid, tid) is named once, before its first span
    named = [(e["pid"], e["tid"]) for e in events if e["name"] == "thread_name"]
    assert sorted(named) == sorted({(e["pid"], e["tid"]) for e in xs})
    meta, spans = read_timeline(path)
    assert meta["epoch_monotonic"] == 100.0 and spans == xs
    # closing the sink ended the stream; the ring goes on
    st.end(st.begin("later"))
    assert len(read_timeline(path)[1]) == 50


def test_a_broken_sink_is_recorded_and_the_ring_lives_on(tmp_path):
    rec = FlightRecorder(capacity=16)
    st = SpanTracker(capacity=4, enabled=True, recorder=rec)
    st.set_sink(os.path.join(tmp_path, "timeline.json"))
    st._sink.close()  # the disk went away under the server
    st.end(st.begin("s"))
    st.end(st.begin("s"))
    assert len(st.completed()) == 2
    (ev,) = rec.events("obs_sink_error")
    assert ev["what"] == "timeline"


class _FakeAnnotation:
    built = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs, self.open = name, kwargs, None
        _FakeAnnotation.built.append(self)

    def __enter__(self):
        self.open = True

    def __exit__(self, *exc):
        self.open = False


@pytest.fixture
def annotations(monkeypatch):
    from dllama_tpu.obs import spans

    monkeypatch.setattr(spans, "TraceAnnotation", _FakeAnnotation)
    _FakeAnnotation.built = []
    return _FakeAnnotation.built


def test_a_span_opens_an_annotation_with_its_int_and_str_attributes(annotations):
    st = SpanTracker(capacity=4, enabled=True)
    h = st.begin("decode_lanes", component="engine", request_id="r1", lane=2,
                 n_steps=8, window=512, ratio=0.5, pages=[1, 2], kind="lane")
    (a,) = annotations
    assert a.name == "dllama.engine.decode_lanes" and a.open is True
    assert a.kwargs == {"request_id": "r1", "lane": 2, "n_steps": 8,
                        "window": 512, "kind": "lane"}
    st.end(h)
    st.end(h)
    assert a.open is False
    with pytest.raises(ValueError):
        with st.span("emit", component="scheduler"):
            raise ValueError("the body raised")
    assert [x.open for x in annotations] == [False, False]
    # a span handed to another thread says nothing of what this one does
    st.end(st.begin("queue", component="scheduler", annotate=False))
    assert len(annotations) == 2 and len(st.completed()) == 3


def test_a_disabled_tracker_builds_no_annotation(annotations):
    st = SpanTracker(capacity=4, enabled=False)
    st.end(st.begin("decode_lanes", n_steps=8))
    with st.span("emit"):
        pass
    assert annotations == [] and st.completed() == []


def test_a_caller_may_read_the_clock_for_the_tracker():
    clk = FakeClock(50.0)
    st = SpanTracker(capacity=4, enabled=True, clock=clk)
    st.end(st.begin("decode_lanes", at=50.5), at=50.75)
    (s,) = st.completed()
    assert s["t0"] == 0.5 and s["dur_s"] == 0.25


# -- which thread a span ran on, and which span it ran under -----------------


def _nested(st):
    with st.span("sched_tick", component="scheduler"):
        with st.span("emit", component="scheduler"):
            with st.span("finish", component="scheduler"):
                pass
    return {"sched_tick": None, "emit": "sched_tick", "finish": "emit"}


def _siblings(st):
    with st.span("sched_tick", component="scheduler"):
        st.end(st.begin("step_prep", component="scheduler"))
        st.end(st.begin("dispatch_prep"))
    st.end(st.begin("sched_wait", component="scheduler"))
    return {"sched_tick": None, "step_prep": "sched_tick",
            "dispatch_prep": "sched_tick", "sched_wait": None}


def _unstacked(st):
    """A span begun with `annotate=False` is nobody's parent and has none,
    whatever is open around it or begun while it is."""
    with st.span("sched_tick", component="scheduler"):
        decode = st.begin("decode", component="scheduler", annotate=False)
        with st.span("emit", component="scheduler"):
            st.end(st.begin("device_drained", annotate=False))
        st.end(decode)
    return {"sched_tick": None, "decode": None, "emit": "sched_tick",
            "device_drained": None}


def _raised(st):
    """`span` ends in a `finally`; a bare `begin` whose `end` an exception
    skipped leaves the stack with the span under it."""
    with st.span("sched_tick", component="scheduler"):
        with pytest.raises(RuntimeError):
            with st.span("emit", component="scheduler"):
                st.begin("finish", component="scheduler")  # never ended
                raise RuntimeError("the body raised")
        st.end(st.begin("step_prep", component="scheduler"))
    st.end(st.begin("sched_wait", component="scheduler"))
    return {"sched_tick": None, "emit": "sched_tick",
            "step_prep": "sched_tick", "sched_wait": None}


def _ended_late(st):
    """An `end` that comes after the span's parent ended pops nothing."""
    tick = st.begin("sched_tick", component="scheduler")
    emit = st.begin("emit", component="scheduler")
    st.end(tick)
    st.end(emit)
    st.end(st.begin("sched_wait", component="scheduler"))
    return {"sched_tick": None, "emit": "sched_tick", "sched_wait": None}


NESTING_CASES = {f.__name__.lstrip("_"): f for f in (
    _nested, _siblings, _unstacked, _raised, _ended_late)}


@pytest.mark.parametrize("case", list(NESTING_CASES))
def test_a_span_says_its_thread_and_its_parent(case):
    import threading

    from dllama_tpu.obs import spans

    st = SpanTracker(capacity=16, enabled=True)
    want = NESTING_CASES[case](st)
    done = {s["name"]: s for s in st.completed()}
    assert set(done) == set(want)
    ids = {s["id"]: s["name"] for s in done.values()}
    assert len(ids) == len(done)
    assert {n: ids.get(s.get("parent")) for n, s in done.items()} == want
    # what never entered the stack says so by carrying no parent at all
    assert {n for n, s in done.items() if "parent" not in s} == {
        n for n in done if n in ("decode", "device_drained")}
    assert {s["thread"] for s in done.values()} == {threading.get_ident()}
    assert spans._thread_ctx.stack == []


def test_a_span_of_another_thread_has_that_threads_parent():
    import threading

    st = SpanTracker(capacity=16, enabled=True)

    def handler():
        with st.span("sse_flush", component="http"):
            pass
        st.end(queue)  # begun there, ended here: its thread is the beginner's

    with st.span("sched_tick", component="scheduler"):
        queue = st.begin("queue", component="scheduler", annotate=False)
        t = threading.Thread(target=handler)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    done = {s["name"]: s for s in st.completed()}
    assert done["sse_flush"]["parent"] is None  # the tick is not its parent
    assert done["sse_flush"]["thread"] == t.ident != threading.get_ident()
    assert done["queue"]["thread"] == done["sched_tick"]["thread"] == threading.get_ident()


def test_a_disabled_tracker_keeps_no_stack():
    from dllama_tpu.obs import spans

    def other_thread():
        st = SpanTracker(capacity=4, enabled=False)
        with st.span("sched_tick"):
            assert st.begin("emit") is None
        seen.append(hasattr(spans._thread_ctx, "stack"))

    import threading

    seen = []
    t = threading.Thread(target=other_thread)
    t.start()
    t.join(timeout=10)
    assert seen == [False]


def test_the_sink_and_the_export_carry_thread_and_parent(tmp_path):
    import threading

    from dllama_tpu.obs.spans import read_timeline

    st = SpanTracker(capacity=16, enabled=True)
    path = os.path.join(tmp_path, "timeline.json")
    st.set_sink(path)
    with st.span("sched_tick", component="scheduler", mono_ns=5, profiled=0):
        st.end(st.begin("device_drained", annotate=False, before="decode_lanes"))
        with st.span("emit", component="scheduler"):
            pass
    st.set_sink(None)
    _, streamed = read_timeline(path)
    exported = [ev for ev in st.chrome_trace()["traceEvents"] if ev["ph"] == "X"]
    assert streamed == exported
    by_name = {ev["name"]: ev["args"] for ev in streamed}
    assert {a["thread"] for a in by_name.values()} == {threading.get_ident()}
    assert by_name["emit"]["parent"] == by_name["sched_tick"]["id"]
    assert by_name["sched_tick"]["parent"] is None
    assert "parent" not in by_name["device_drained"]
    assert by_name["sched_tick"]["profiled"] == 0
    # pid and tid stay the component and the lane
    assert {ev["name"]: (ev["pid"], ev["tid"]) for ev in streamed} == {
        "sched_tick": (1, -1), "emit": (1, -1), "device_drained": (2, -1)}


def test_request_summary_coverage_and_phases():
    clk = FakeClock()
    st = SpanTracker(capacity=16, enabled=True, clock=clk)

    def record(name, t0, t1, rid="r1"):
        clk.t = t0
        h = st.begin(name, request_id=rid)
        clk.t = t1
        st.end(h)

    record("queue", 0.0, 1.0)
    record("decode", 1.0, 3.0)
    record("sample", 1.5, 2.5)  # nested: must not double-count coverage
    record("other", 0.0, 9.0, rid="r2")  # another request: excluded
    s = st.request_summary("r1")
    assert s["n_spans"] == 3
    assert s["wall_ms"] == 3000.0
    assert s["covered_ms"] == 3000.0
    assert s["coverage"] == 1.0
    assert s["phases"]["queue"]["total_ms"] == 1000.0
    assert s["phases"]["queue"]["share"] == round(1 / 3, 4)
    assert s["phases"]["decode"]["total_ms"] == 2000.0
    # a gap between spans is uncovered wall time
    record("a", 10.0, 11.0, rid="r3")
    record("b", 12.0, 13.0, rid="r3")
    s3 = st.request_summary("r3")
    assert s3["wall_ms"] == 3000.0
    assert s3["covered_ms"] == 2000.0
    assert s3["coverage"] == round(2 / 3, 4)
    assert st.request_summary("missing")["coverage"] is None


# -- SloTracker --------------------------------------------------------------


def test_slo_windows_attainment_and_goodput():
    clk = FakeClock()
    reg = MetricsRegistry()
    slo = SloTracker(ttft_target_ms=100.0, registry=reg, clock=clk)
    clk.t = 1.0
    assert slo.observe_request(ttft_s=0.05, tpot_s=None, n_tokens=20)
    slo.note_tokens(20)
    clk.t = 5.0
    assert not slo.observe_request(ttft_s=0.2, tpot_s=None, n_tokens=30)
    slo.note_tokens(30)
    clk.t = 9.0
    snap = slo.snapshot()
    w10 = snap["windows"]["10s"]
    assert w10["n_requests"] == 2 and w10["n_met"] == 1
    assert w10["ttft_attainment"] == 0.5
    assert w10["attainment"] == 0.5
    # goodput counts ONLY the SLO-met request's tokens; throughput all
    assert w10["goodput_tokens_per_s"] == round(20 / 10.0, 3)
    assert w10["throughput_tokens_per_s"] == round(50 / 10.0, 3)
    # both requests age out of 10s/1m but stay inside 5m
    clk.t = 100.0
    snap = slo.snapshot()
    assert snap["windows"]["10s"]["n_requests"] == 0
    assert snap["windows"]["10s"]["attainment"] == 1.0  # vacuous, finite
    assert snap["windows"]["10s"]["goodput_tokens_per_s"] == 0.0
    assert snap["windows"]["1m"]["n_requests"] == 0
    assert snap["windows"]["5m"]["n_requests"] == 2
    text = reg.render()
    assert 'dllama_slo_ttft_attainment{window="10s"} 1' in text
    assert 'dllama_slo_window_requests{window="5m"} 2' in text


def test_slo_tpot_target_and_unset_targets():
    clk = FakeClock()
    slo = SloTracker(tpot_target_ms=50.0, registry=MetricsRegistry(),
                     clock=clk)
    assert slo.observe_request(ttft_s=99.0, tpot_s=0.01)  # no TTFT target
    assert not slo.observe_request(ttft_s=0.01, tpot_s=0.2)
    none_set = SloTracker(registry=MetricsRegistry(), clock=clk)
    assert none_set.observe_request(ttft_s=None, tpot_s=None)  # vacuous


def test_slo_observe_span():
    class Span:
        finish_reason = "stop"
        n_completion = 11
        ttft_s = 0.05
        total_s = 1.05
        queue_wait_s = 0.01

    clk = FakeClock(t=1.0)
    slo = SloTracker(ttft_target_ms=100.0, tpot_target_ms=200.0,
                     registry=MetricsRegistry(), clock=clk)
    # tpot = (1.05 - 0.05) / 10 = 0.1s <= 200ms
    assert slo.observe_span(Span()) is True
    cancelled = Span()
    cancelled.finish_reason = "cancelled"
    assert slo.observe_span(cancelled) is None  # says nothing about SLOs
    assert slo.snapshot()["windows"]["10s"]["n_requests"] == 1


KNOB_TWINS = ("DLLAMA_SLO_TTFT_MS", "DLLAMA_SLO_TPOT_MS")


@pytest.mark.parametrize("name", KNOB_TWINS)
def test_slo_knob_resolution(unflagged, flagged, name):
    """No target unless `--slo-ttft-ms` / `--slo-tpot-ms` names one, with
    the former variable set (`resolve_slo_knobs` read it until PR 45), and
    an explicit flag is the tracker's target."""
    assert_one_spelling(name, unflagged, flagged)


# -- EngineWatchdog ----------------------------------------------------------


def _watchdog(tmp_path, clk, **kw):
    reg = MetricsRegistry()
    rec = FlightRecorder(capacity=64, postmortem_dir=str(tmp_path))
    wd = EngineWatchdog(clock=clk, registry=reg, recorder=rec, **kw)
    return wd, reg, rec


def test_watchdog_dispatch_hung_postmortem_and_recovery(tmp_path):
    clk = FakeClock()
    wd, reg, rec = _watchdog(tmp_path, clk, dispatch_timeout_s=30.0)
    # n_active=0 keeps the decode-gap rule disarmed so only the in-flight
    # dispatch's age can trip the watchdog here
    wd.beat(n_active=0)
    wd.dispatch_begin("decode_lanes")
    clk.t = 10.0
    assert wd.check_once() is None
    clk.t = 31.0
    assert wd.check_once() == "dispatch-hung"
    assert wd.degraded
    assert wd.status()["reason"] == "dispatch-hung"
    assert "decode_lanes" in wd.status()["detail"]
    text = reg.render()
    assert "dllama_watchdog_degraded 1" in text
    assert 'dllama_watchdog_stalls_total{reason="dispatch-hung"} 1' in text
    # the hang wrote the black box while the process is still alive
    pms = [p for p in os.listdir(tmp_path) if p.startswith("postmortem-")]
    assert len(pms) == 1
    payload = json.loads((tmp_path / pms[0]).read_text())
    assert payload["reason"] == "watchdog"
    assert "dispatch-hung" in payload["error"]
    # edge-triggered: re-checks while stalled pay nothing further
    clk.t = 32.0
    assert wd.check_once() == "dispatch-hung"
    assert len(rec.events("watchdog_stall")) == 1
    assert len(
        [p for p in os.listdir(tmp_path) if p.startswith("postmortem-")]
    ) == 1
    # recovery clears degraded and records the transition
    wd.dispatch_end()
    wd.beat(n_active=0)
    assert wd.check_once() is None
    assert not wd.degraded
    assert rec.events("watchdog_recovered")[0]["reason"] == "dispatch-hung"
    assert "dllama_watchdog_degraded 0" in reg.render()


def test_watchdog_scheduler_stalled(tmp_path):
    clk = FakeClock()
    wd, _, rec = _watchdog(tmp_path, clk, dispatch_timeout_s=30.0)
    wd.beat(n_active=2)
    clk.t = 31.0
    assert wd.check_once() == "scheduler-stalled"
    # an idle scheduler (no busy lanes) is quiet, not stalled
    wd2, _, _ = _watchdog(tmp_path, clk, dispatch_timeout_s=30.0)
    wd2.beat(n_active=0, n_admitting=0)
    clk.t = 100.0
    assert wd2.check_once() is None


def test_watchdog_decode_stalled_scales_with_p99(tmp_path):
    clk = FakeClock()
    wd, _, _ = _watchdog(
        tmp_path, clk, min_stall_s=5.0, stall_factor=20.0,
        block_p99=lambda: 1.0,
    )
    wd.beat(n_active=1)  # arms the decode-gap rule from t=0
    clk.t = 6.0
    wd.beat(n_active=1)
    # gap 6s > min_stall but < 20 x p99(1s): a slow model, not a stall
    assert wd.check_once() is None
    clk.t = 21.0
    wd.beat(n_active=1)
    assert wd.check_once() == "decode-stalled"


def test_watchdog_decode_stalled_min_floor_without_p99(tmp_path):
    clk = FakeClock()
    wd, _, _ = _watchdog(tmp_path, clk, min_stall_s=5.0)
    wd.beat(n_active=1)
    clk.t = 6.0
    wd.beat(n_active=1)  # fresh beat; decode gap is the stale signal
    assert wd.check_once() == "decode-stalled"


def test_watchdog_admission_stalled_and_progress_resets(tmp_path):
    clk = FakeClock()
    wd, _, _ = _watchdog(tmp_path, clk, dispatch_timeout_s=30.0)
    wd.beat(n_admitting=1)
    clk.t = 20.0
    wd.beat(n_admitting=1)
    # a chunk completed: progress timestamp moves, no stall at t=31
    wd.dispatch_begin("prefill_lane_chunk")
    wd.dispatch_end()
    clk.t = 31.0
    wd.beat(n_admitting=1)
    assert wd.check_once() is None
    clk.t = 51.0
    wd.beat(n_admitting=1)
    assert wd.check_once() == "admission-stalled"
