"""`drained` charges a hand-written timeline's `device_drained` intervals to
the scheduler thread's innermost span, keeps `sched_wait` apart, leaves the
profiler's session out, and lays the traced slice's intervals over a
hand-written trace; the four readers reduce a fixture run directory to
hand-computed numbers."""

import json
import os

import pytest

import run as bench
from benchmark.harness import drained, hostspans, xplane

SCHED, HTTP = 7, 9  # thread ids
EPOCH, T0, T1 = 90.0, 100.0, 151.0
OFFSET_NS = 5e9  # the profiler's clock less the host's
SEP = hostspans.SEP
TICK, EMIT, FINISH = "scheduler.sched_tick", "scheduler.emit", "scheduler.finish"
STEP_PREP, WAIT = "scheduler.step_prep", "scheduler.sched_wait"
PREP_BLOCK, PREP_CHUNK = "engine.dispatch_prep(decode_lanes)", "engine.dispatch_prep(prefill_lane_chunk)"


def timeline(with_threads=True):
    """Seconds on the host's clock. Tick A 101.0-101.1 ends in an `emit`
    whose `finish` publishes; tick B 101.105-101.3 admits a chunk and runs a
    block; a wait for work 101.31-101.56; tick C. The profiler's session
    disturbs ticks P and Q (120.0-125.0); R, S and T are bare ticks."""
    events, ids = [], {}

    def span(name, t0, t1, pid=1, parent=None, thread=SCHED, stacked=True, **args):
        ids[name, t0] = len(ids) + 1
        a = {"id": ids[name, t0], "thread": thread, **args}
        if stacked:
            a["parent"] = parent and ids[parent]
        if not with_threads:
            a = dict(args)
        events.append({"ph": "X", "pid": pid, "tid": -1, "name": name,
                       "ts": (t0 - EPOCH) * 1e6, "dur": (t1 - t0) * 1e6, "args": a})

    def drain(t0, t1, before):
        span("device_drained", t0, t1, pid=2, stacked=False, before=before)

    def tick(t0, t1, profiled=0):
        span("sched_tick", t0, t1, mono_ns=int(t0 * 1e9), profiled=profiled)
        return "sched_tick", t0

    drain(99.5, 99.6, "decode_lanes")  # before the window
    a = tick(101.0, 101.1)
    span("step_prep", 101.0, 101.01, parent=a)
    span("dispatch_prep", 101.01, 101.02, pid=2, parent=a, step="decode_lanes")
    span("decode_lanes", 101.02, 101.08, pid=2, parent=a)
    span("decode_lanes.device", 101.025, 101.08, pid=2, parent=("decode_lanes", 101.02))
    span("emit", 101.08, 101.1, parent=a)
    span("finish", 101.085, 101.095, parent=("emit", 101.08))
    drain(101.08, 101.13, "prefill_lane_chunk")  # through the pool copy
    span("kv_publish", 101.09, 101.092, pid=2, parent=("finish", 101.085))
    b = tick(101.105, 101.3)
    span("dispatch_prep", 101.11, 101.13, pid=2, parent=b, step="prefill_lane_chunk")
    span("prefill_lane_chunk", 101.13, 101.135, pid=2, parent=b)
    span("step_prep", 101.135, 101.14, parent=b)
    span("dispatch_prep", 101.14, 101.15, pid=2, parent=b, step="decode_lanes")
    span("decode_lanes", 101.15, 101.3, pid=2, parent=b)
    drain(101.3, 101.6, "decode_lanes")
    span("sched_wait", 101.31, 101.56)
    c = tick(101.56, 101.7)
    span("step_prep", 101.56, 101.59, parent=c)
    span("dispatch_prep", 101.59, 101.6, pid=2, parent=c, step="decode_lanes")
    span("decode_lanes", 101.6, 101.7, pid=2, parent=c)
    # what says nothing of the scheduler thread's work, or is not its own
    span("decode", 101.0, 101.7, stacked=False)
    span("queue", 101.05, 101.4, thread=HTTP, stacked=False)
    span("sse_flush", 101.3, 101.5, pid=4, thread=HTTP)
    tick(120.0, 120.5, profiled=1)
    drain(120.1, 120.2, "decode_lanes")
    tick(124.6, 125.0, profiled=1)
    tick(129.8, 130.1)  # `stop_trace` still exports
    drain(129.9, 130.05, "decode_lanes")
    tick(140.0, 140.2)
    drain(140.0, 140.1, "decode_lanes")
    tick(150.9, 151.2)
    drain(150.95, 151.05, "decode_lanes")  # cut at the window's end
    return events


def trace_lines(plane="/device:TPU:0"):
    """On the profiler's clock: blocks run 120.0-120.1 and 120.21-120.4, a
    small launch 120.19-120.191 inside the drained interval 120.1-120.2, a
    chunk 120.45-120.5 behind a gap nobody recorded."""
    ns = lambda t: t * 1e9 + OFFSET_NS
    mods = [("jit_block(1)", ns(120.0), 0.1e9),
            ("jit_convert_element_type(2)", ns(120.19), 0.001e9),
            ("jit_block(1)", ns(120.21), 0.19e9), ("jit_step(3)", ns(120.45), 0.05e9)]
    return [(plane, xplane.MODULES, mods), (plane, xplane.OPS, mods)]


def reduced(events, lo_s=T0, hi_s=T1):
    stacked, intervals, profiled = drained.scheduler_thread(events)
    lo, hi = ((t - EPOCH) * 1e6 for t in (lo_s, hi_s))
    segments = hostspans.innermost(stacked)
    return drained.attribute(segments, intervals, lo, hi), drained.by_second(
        segments, intervals, lo, hi), profiled


def test_drained_seconds_go_to_the_innermost_span_of_the_scheduler_thread():
    table, _, profiled = reduced(timeline(), hi_s=119.0)
    assert [(a / 1e6 + EPOCH, b / 1e6 + EPOCH) for a, b in profiled] == [
        pytest.approx((120.0, 120.5)), pytest.approx((124.6, 125.0))]
    assert table["used_s"] == pytest.approx(19.0)
    by = {k.split(SEP)[-1]: v for k, v in table["by_span"].items()}
    assert by == {
        EMIT: pytest.approx(0.010), FINISH: pytest.approx(0.008),
        "engine.kv_publish": pytest.approx(0.002),
        hostspans.UNATTRIBUTED: pytest.approx(0.015),  # between ticks
        TICK: pytest.approx(0.005), PREP_CHUNK: pytest.approx(0.020),
        WAIT: pytest.approx(0.25), STEP_PREP: pytest.approx(0.030),
        PREP_BLOCK: pytest.approx(0.010)}
    assert SEP.join([TICK, EMIT, FINISH]) in table["by_span"]
    assert list(table["by_span"])[0] == WAIT  # ranked
    assert table["drained_s"] == pytest.approx(0.35)
    assert table["waiting_s"] == pytest.approx(0.25)
    assert table["exposed_s"] == pytest.approx(0.1)
    assert table["by_before"] == {
        "decode_lanes": pytest.approx(0.3), "prefill_lane_chunk": pytest.approx(0.05)}


def test_every_second_of_the_window_shows_the_sessions_reach():
    whole, seconds, _ = reduced(timeline())
    assert whole["exposed_s"] == pytest.approx(0.1 + 0.1 + 0.15 + 0.1 + 0.05)
    assert len(seconds) == 51
    assert {i: s for i, s in enumerate(seconds) if s} == {
        1: pytest.approx(0.1), 20: pytest.approx(0.1), 29: pytest.approx(0.1),
        30: pytest.approx(0.05), 40: pytest.approx(0.1), 50: pytest.approx(0.05)}


def test_the_traced_slice_against_the_devices_idle_gaps():
    _, intervals, _ = drained.scheduler_thread(timeline())
    on_profiler = [((EPOCH + a / 1e6) * 1e9 + OFFSET_NS, (EPOCH + b / 1e6) * 1e9 + OFFSET_NS)
                   for a, b, _ in intervals]
    got = drained.against_trace(on_profiler, trace_lines())
    # gaps 120.1-120.19, 120.191-120.21 and 120.4-120.45; drained 120.1-120.2
    assert got == {"idle_s": pytest.approx(0.159), "covered_s": pytest.approx(0.099),
                   "drained_while_busy_s": pytest.approx(0.001)}
    assert got["idle_s"] == pytest.approx(sum(
        s for _, s in xplane.digest(trace_lines(), 0.5)["idle_gaps"]))
    two = drained.against_trace(on_profiler, trace_lines() + [
        ("/device:TPU:1", xplane.MODULES, [("jit_block(1)", 120.0e9 + OFFSET_NS, 0.5e9)])])
    assert two["idle_s"] == pytest.approx(0.159 / 2)
    assert two["drained_while_busy_s"] == pytest.approx((0.001 + 0.1) / 2)
    assert drained.against_trace(on_profiler, [("/device:TPU:0", xplane.OPS, [])]) is None


@pytest.fixture
def run_dir(tmp_path, monkeypatch):
    d = str(tmp_path)
    dump = lambda name, obj: json.dump(obj, open(os.path.join(d, name), "w"))
    dump("window.json", {"t0": T0, "t1": T1, "trace_t0": 120.0, "trace_t1": 125.0})
    dump(hostspans.TABLE, {"idle_s": 0.159, "window_s": 5.0, "by_span": {},
                           "clock": {"ticks": 2, "offset_ns": OFFSET_NS, "drift_ns": 0.0}})
    dump(hostspans.SCOPES, None)
    events = [{"ph": "M", "pid": 0, "tid": 0, "name": "timeline_epoch",
               "args": {"epoch_unix": 1.0, "epoch_monotonic": EPOCH}}] + timeline()
    with open(os.path.join(d, "timeline.json"), "w") as f:
        f.write("[" + "".join(json.dumps(ev) + ",\n" for ev in events))
    monkeypatch.setattr(xplane, "load", lambda profile: trace_lines())
    return d


def test_the_four_readers_on_a_fixture(run_dir):
    read = lambda metric: bench.layer_reader(metric).read(run_dir)
    # the window up to a second before the first profiled tick: 100-119 s
    assert read("host_exposed_pct") == pytest.approx(100 * 0.1 / 19.0)
    assert read("host_exposed_in_emit_pct") == pytest.approx(100 * 0.020 / 19.0)  # emit, its finish, the copy
    assert read("host_exposed_in_dispatch_prep_pct") == pytest.approx(100 * 0.030 / 19.0)
    assert read("drained_covers_idle_pct") == pytest.approx(100 * 0.099 / 0.159)
    with open(os.path.join(run_dir, drained.TABLE)) as f:
        kept = json.load(f)
    assert kept["window_s"] == 51.0 and kept["used_s"] == pytest.approx(19.0)
    assert kept["cycles"] == 3 and len(kept["exposed_by_second"]) == 51
    # what is left of the window five seconds behind the last profiled tick
    assert kept["after_session"] == {"used_s": pytest.approx(21.0), "cycles": 0,
                                     "exposed_s": pytest.approx(0.05 + 0.1 + 0.05)}
    assert kept["traced"]["drained_while_busy_s"] == pytest.approx(0.001)
    assert read("host_exposed_in_emit_pct") + read("host_exposed_in_dispatch_prep_pct") <= read(
        "host_exposed_pct")


def test_an_untraced_run_with_a_timeline_is_used_whole(run_dir):
    os.remove(os.path.join(run_dir, hostspans.TABLE))
    json.dump({"t0": T0, "t1": T1, "trace_t0": None, "trace_t1": None},
              open(os.path.join(run_dir, "window.json"), "w"))
    events = [{"ph": "M", "pid": 0, "tid": 0, "name": "timeline_epoch",
               "args": {"epoch_unix": 1.0, "epoch_monotonic": EPOCH}}] + [
        e for e in timeline() if not e["args"].get("profiled")]
    with open(os.path.join(run_dir, "timeline.json"), "w") as f:
        f.write("[" + "".join(json.dumps(ev) + ",\n" for ev in events))
    read = lambda metric: bench.layer_reader(metric).read(run_dir)
    assert read("host_exposed_pct") == pytest.approx(100 * 0.5 / 51.0)
    assert read("drained_covers_idle_pct") is None
    assert "after_session" not in drained.table(run_dir)


@pytest.mark.parametrize("metric,entry", [
    ("host_exposed_pct", ("device", "%", "lower", "program_span")),
    ("host_exposed_in_emit_pct", ("scheduler", "%", "lower", "program_span")),
    ("host_exposed_in_dispatch_prep_pct", ("engine step", "%", "lower", "program_span")),
    ("drained_covers_idle_pct", ("device", "%", "higher", "device_trace")),
])
def test_a_reader_states_the_entry_it_will_have(metric, entry):
    """What `test_contract.py` will hold a reader to against `BENCHMARK.json`
    once a `benchmark` PR lists it: layers named as the file's other metrics
    name them."""
    reader = bench.layer_reader(metric)
    assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.SOURCE) == entry
    assert reader.MOVES == "tpot_p95_ms"
    assert metric in drained.METRICS
    assert reader.read(os.path.join(bench.HERE, "no-such-run")) is None


@pytest.mark.parametrize("metric", drained.METRICS)
def test_readers_of_a_run_of_a_program_without_the_spans(run_dir, metric):
    """The parent's traced run: its timeline's spans say neither their
    thread nor their parent, and it records no `device_drained`."""
    events = [{"ph": "M", "pid": 0, "tid": 0, "name": "timeline_epoch",
               "args": {"epoch_unix": 1.0, "epoch_monotonic": EPOCH}}] + [
        e for e in timeline(with_threads=False) if e["name"] != "device_drained"]
    with open(os.path.join(run_dir, "timeline.json"), "w") as f:
        f.write("[" + "".join(json.dumps(ev) + ",\n" for ev in events))
    assert bench.layer_reader(metric).read(run_dir) is None
    assert not os.path.exists(os.path.join(run_dir, drained.TABLE))
