"""`devicedone` reduces a hand-written recorder's `device_done` events to a
program's device time by step over the part of the window `drained.py` uses,
says whether every dispatch of the lane path was stamped, and lays the traced
slice's stamps over a hand-written trace; the readers state the entries
`test_contract.py` will hold them to, and read nothing of no run."""

import json
import os

import pytest

import run as bench
from benchmark.harness import devicedone, drained, hostspans, xplane

EPOCH, T0, T1 = 90.0, 100.0, 151.0
OFFSET_NS = 5e9  # the profiler's clock less the host's
SCHED = 7


def recorder():
    """Blocks of 50 ms back to back from 100.0 (each queued behind the one
    before: the loop runs ahead), a chunk of 250 ms at 110, a block the
    device waited 20 ms for, one program in error, and blocks inside the
    profiler's session and past the window's end."""
    events, n = [], [0]

    def dispatch(step, t, **f):
        events.append({"kind": "step_dispatch", "t": t, "step": step, "dry": 0, **f})

    def done(step, at, device_ms, queued_ms=0.0, dry_ms=0.0, **f):
        n[0] += 1
        events.append({"kind": "device_done", "t": at + 0.06, "program": n[0], "step": step,
                       "at": at, "device_ms": device_ms, "queued_ms": queued_ms,
                       "dry_ms": dry_ms, **f})

    dispatch("decode_lanes", 99.0)
    done("decode_lanes", 99.9, 48.0)  # before the window
    for i in range(4):
        dispatch("decode_lanes", 100.0 + 0.05 * i, ahead=1)
        done("decode_lanes", 100.05 + 0.05 * i, 50.0 + i, queued_ms=45.0, late_ms=0.2 * i)
    dispatch("kv_publish", 109.9)  # a pool copy: no program of the stamps
    dispatch("prefill_lane_chunk", 110.0)
    done("prefill_lane_chunk", 110.25, 250.0)
    dispatch("prefill_lane_chunk", 110.3)
    done("prefill_lane_chunk", 110.6, 270.0)
    dispatch("decode_lanes", 110.62)
    done("decode_lanes", 110.7, 60.0, dry_ms=20.0)
    dispatch("decode_lanes", 111.0)
    done("decode_lanes", 111.01, 0.0, error="XlaRuntimeError")
    # the traced slice, 120.0-125.0: two blocks and a chunk the trace holds
    dispatch("decode_lanes", 120.1)
    done("decode_lanes", 120.1905, 90.4)
    dispatch("decode_lanes", 120.2)
    done("decode_lanes", 120.4002, 190.2)
    dispatch("prefill_lane_chunk", 120.4)
    done("prefill_lane_chunk", 120.951, 500.7)
    dispatch("decode_lanes", 124.8)
    done("decode_lanes", 124.95, 100.0)  # the trace lost its execution
    dispatch("decode_lanes", 150.9)
    done("decode_lanes", 151.2, 55.0)  # past the window
    dispatch("decode_lanes", 151.3)  # not accounted for yet
    return events


def trace_lines():
    """Device 0 on the profiler's clock: blocks 120.1-120.19 and 120.21-120.4,
    a chunk 120.45-120.95; a second plane is left out of the check."""
    t = lambda s: s * 1e9 + OFFSET_NS
    return [
        ("/device:TPU:0", xplane.MODULES, [
            ("jit_block(1)", t(120.1), 0.09e9), ("jit_block(1)", t(120.21), 0.19e9),
            ("jit_step(2)", t(120.45), 0.5e9)]),
        ("/device:TPU:0", xplane.OPS, [("%fusion.1 = f32[] fusion()", t(120.1), 0.09e9)]),
        ("/device:TPU:1", xplane.MODULES, [("jit_block(1)", t(120.0), 0.5e9)]),
    ]


def timeline(profiled=True):
    tick = lambda t0, t1, p: {
        "ph": "X", "pid": 1, "tid": -1, "name": "sched_tick", "ts": (t0 - EPOCH) * 1e6,
        "dur": (t1 - t0) * 1e6,
        "args": {"id": int(t0 * 10), "thread": SCHED, "parent": None, "profiled": p}}
    return [tick(101.0, 101.1, 0), tick(120.0, 120.5, int(profiled)),
            tick(124.6, 125.0, int(profiled)), tick(140.0, 140.1, 0)]


@pytest.fixture
def run_dir(tmp_path, monkeypatch):
    d = str(tmp_path)
    dump = lambda name, obj: json.dump(obj, open(os.path.join(d, name), "w"))
    dump("window.json", {"t0": T0, "t1": T1, "trace_t0": 120.0, "trace_t1": 125.0})
    dump("recorder.json", {"events": recorder()})
    dump(hostspans.TABLE, {"idle_s": 0.0, "window_s": 5.0, "by_span": {},
                           "clock": {"ticks": 2, "offset_ns": OFFSET_NS, "drift_ns": 0.0}})
    dump(hostspans.SCOPES, None)
    events = [{"ph": "M", "pid": 0, "tid": 0, "name": "timeline_epoch",
               "args": {"epoch_unix": 1.0, "epoch_monotonic": EPOCH}}] + timeline()
    with open(os.path.join(d, "timeline.json"), "w") as f:
        f.write("[" + "".join(json.dumps(ev) + ",\n" for ev in events))
    monkeypatch.setattr(xplane, "load", lambda profile: trace_lines())
    return d


def test_the_stamps_of_a_window_by_step_in_the_order_they_were_taken():
    by = devicedone.reduce(recorder(), T0, 119.0)
    assert {s: [e["device_ms"] for e in evs] for s, evs in by.items()} == {
        "decode_lanes": [50.0, 51.0, 52.0, 53.0, 60.0],  # no error, nothing outside
        "prefill_lane_chunk": [250.0, 270.0]}
    shuffled = sorted(recorder(), key=lambda e: -e["t"])
    assert devicedone.reduce(shuffled, T0, 119.0) == by


def test_every_dispatch_of_the_lane_path_is_accounted_for_in_order():
    got = devicedone.accounted(recorder())
    assert (got["dispatched"], got["done"], got["in_order"], got["errors"]) == (15, 14, True, 1)
    assert got["late"] == {"n": 4, "median": pytest.approx(0.3), "p95": pytest.approx(0.6),
                           "max": pytest.approx(0.6)}
    events = recorder()
    chunk = next(e for e in events if e["kind"] == "device_done" and e["step"] != "decode_lanes")
    chunk["step"] = "decode_lanes"
    assert not devicedone.accounted(events)["in_order"]
    assert devicedone.accounted([e for e in events if e["kind"] != "device_done"]) == {
        "dispatched": 15, "done": 0, "in_order": True, "errors": 0, "late": None}


def test_the_traced_slices_stamps_against_the_modules_they_name():
    done = devicedone.reduce(recorder(), 120.0, 125.0)
    got = devicedone.against_trace(done, OFFSET_NS, trace_lines())
    block, chunk = got["decode_lanes"], got["prefill_lane_chunk"]
    assert (block["module"], block["programs"], block["matched"]) == ("jit_block", 3, 2)
    assert block["trace_ms"] == pytest.approx(140.0)  # the median of 90 and 190
    assert block["diff_ms"]["median"] == pytest.approx(0.3)  # +0.4, +0.2
    assert block["diff_ms"]["max"] == pytest.approx(0.4)
    assert block["late_ms"]["median"] == pytest.approx(0.35)  # 0.5 and 0.2 ms behind the ends
    assert (chunk["module"], chunk["matched"]) == ("jit_step", 1)
    assert chunk["diff_ms"]["median"] == pytest.approx(0.7)
    assert chunk["late_ms"]["max"] == pytest.approx(1.0)
    assert devicedone.against_trace(done, OFFSET_NS, [("/device:TPU:0", xplane.OPS, [])]) == {}


def test_what_the_drained_intervals_miss_of_the_idle_gaps_by_length_and_neighbours():
    """Gaps of the first plane: 120.19-120.21 (20 ms, block > block) and
    120.4-120.45 (50 ms, block > chunk). An interval 120.195-120.205 leaves a
    head and a tail of 5 ms; nothing touches the second gap."""
    t = lambda s: s * 1e9 + OFFSET_NS
    got = devicedone.missed_gaps([(t(120.195), t(120.205))], trace_lines())
    row = lambda **kw: {k: pytest.approx(v) for k, v in kw.items()}
    assert got["by_neighbours"] == {
        "jit_block > jit_block": row(gaps=1, idle_s=0.02, covered_s=0.01, head_s=0.005,
                                     tail_s=0.005, bare_s=0.0),
        "jit_block > jit_step": row(gaps=1, idle_s=0.05, covered_s=0.0, head_s=0.0,
                                    tail_s=0.0, bare_s=0.05)}
    assert got["by_length_ms"] == {"20.0 and over": row(
        gaps=2, idle_s=0.07, covered_s=0.01, head_s=0.005, tail_s=0.005, bare_s=0.05)}
    assert devicedone.missed_gaps([], [("/device:TPU:0", xplane.OPS, [])]) is None


def test_the_readers_on_a_fixture(run_dir):
    read = lambda metric: bench.layer_reader(metric).read(run_dir)
    # the window up to a second before the first profiled tick: 100-119 s
    assert devicedone.used(run_dir) == (T0, pytest.approx(119.0))
    assert read("decode_block_device_ms") == read("decode_block_device_ms.longprompt") == 52.0
    assert read("prefill_chunk_device_ms") == pytest.approx(260.0)
    got = devicedone.check(run_dir)
    assert got["accounted"]["in_order"] and got["used_s"] == pytest.approx(19.0)
    assert got["by_step"]["decode_lanes"]["programs"] == 5
    assert got["by_step"]["decode_lanes"]["dry_s"] == pytest.approx(0.020)
    assert got["by_step"]["prefill_lane_chunk"]["busy_s"] == pytest.approx(0.520)
    assert got["traced"]["decode_lanes"]["matched"] == 2


def test_an_untraced_run_is_used_whole(run_dir):
    os.remove(os.path.join(run_dir, hostspans.TABLE))
    os.remove(os.path.join(run_dir, "timeline.json"))  # `--trace 0` streams none
    json.dump({"t0": T0, "t1": T1, "trace_t0": None, "trace_t1": None},
              open(os.path.join(run_dir, "window.json"), "w"))
    assert devicedone.used(run_dir) == (T0, T1)
    # 50, 51, 52, 53, 60, 90.4, 190.2 and 100
    assert bench.layer_reader("decode_block_device_ms").read(run_dir) == pytest.approx(56.5)
    assert bench.layer_reader("prefill_chunk_device_ms").read(run_dir) == pytest.approx(
        (250.0 + 270.0 + 500.7) / 3)
    assert "traced" not in devicedone.check(run_dir)


ENTRIES = {
    "decode_block_device_ms": ("engine step", "ms", "lower", "program_span", "tpot_p95_ms"),
    "decode_block_device_ms.longprompt": (
        "engine step", "ms", "lower", "program_span", "ttft_mean_ms"),
    "prefill_chunk_device_ms": ("engine step", "ms", "lower", "program_span", "ttft_mean_ms"),
    "host_exposed_pct.longprompt": ("device", "%", "lower", "program_span", "ttft_mean_ms"),
}


@pytest.mark.parametrize("metric", list(ENTRIES))
def test_a_reader_states_the_entry_it_will_have(metric):
    """What `test_contract.py` will hold a reader to against `BENCHMARK.json`
    once a `benchmark` PR lists it: layers named as the file's other metrics
    name them, and a twin's entry its cell's own end-to-end metric."""
    reader = bench.layer_reader(metric)
    assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.SOURCE, reader.MOVES) == ENTRIES[metric]
    with open(os.path.join(os.path.dirname(bench.HERE), "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert reader.LAYER in {m["layer"] for m in spec["per_layer"]}
    assert reader.MOVES in {m["name"] for m in spec["end_to_end"]}
    assert reader.read(os.path.join(bench.HERE, "no-such-run")) is None


@pytest.mark.parametrize("metric", list(ENTRIES))
def test_readers_of_a_run_of_a_program_without_the_stamps(run_dir, metric):
    """The parent's run: its recorder holds no `device_done`, and its
    `device_drained` came from the read-backs, which `host_exposed_pct` reads
    as it always did."""
    json.dump({"events": [e for e in recorder() if e["kind"] != "device_done"]},
              open(os.path.join(run_dir, "recorder.json"), "w"))
    value = bench.layer_reader(metric).read(run_dir)
    if metric.startswith("host_exposed_pct"):
        assert value == 0.0 and os.path.exists(os.path.join(run_dir, drained.TABLE))
    else:
        assert value is None
