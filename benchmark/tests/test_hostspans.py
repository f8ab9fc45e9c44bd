"""`hostspans` charges a small hand-recorded trace's idle gaps to the
innermost scheduler span, and the five readers reduce a fixture run
directory to hand-computed numbers."""

import json
import os

import pytest

import run as bench
from benchmark.harness import hostspans, xplane

MS = 1e6  # nanoseconds
TICK, EMIT, PREP = "scheduler.sched_tick", "scheduler.emit", "engine.dispatch_prep"
NEW = ["idle_attributed_pct", "idle_in_emit_pct", "idle_in_dispatch_prep_pct",
       "tick_host_ms", "layer_scan_copy_pct"]


def host_spans():
    """One tick of 20 ms: emit 2-9 ms with a finish 4-6 ms inside it, then
    dispatch_prep 9-11 ms and the dispatch with its read-back wait; a second
    tick begins at 30 ms; a wait for work from 25 ms to 30 ms between them."""
    return [
        (TICK, 0 * MS, 20 * MS, 1_000 * MS),
        (EMIT, 2 * MS, 7 * MS, None),
        ("scheduler.finish", 4 * MS, 2 * MS, None),
        (PREP, 9 * MS, 2 * MS, None),
        ("engine.decode_lanes", 11 * MS, 9 * MS, None),
        ("engine.decode_lanes.device", 12 * MS, 8 * MS, None),
        ("scheduler.sched_wait", 25 * MS, 5 * MS, None),
        (TICK, 30 * MS, 10 * MS, 1_030.002 * MS),
    ]


def device_lines(plane="/device:TPU:0", shift=0.0):
    """Programs run 0-1, 10.5-19, 23-24 and 31-40 ms: idle gaps of 9.5, 4
    and 7 ms."""
    mods = [("jit_block(1)", 0 * MS, 1 * MS), ("jit_convert_element_type(2)", 10.5 * MS, 0.5 * MS),
            ("jit_block(1)", 11 * MS, 8 * MS), ("jit_step(3)", 23 * MS + shift, 1 * MS),
            ("jit_block(1)", 31 * MS, 9 * MS)]
    return [(plane, xplane.MODULES, mods), (plane, xplane.OPS, [(n, s, d) for n, s, d in mods])]


def test_innermost_flattens_nested_spans():
    segments = hostspans.innermost(host_spans())
    assert [(a / MS, b / MS, p.split(hostspans.SEP)[-1]) for a, b, p in segments] == [
        (0, 2, TICK), (2, 4, EMIT), (4, 6, "scheduler.finish"), (6, 9, EMIT),
        (9, 11, PREP), (11, 12, "engine.decode_lanes"),
        (12, 20, "engine.decode_lanes.device"), (25, 30, "scheduler.sched_wait"),
        (30, 40, TICK)]
    assert segments[2][2] == hostspans.SEP.join([TICK, EMIT, "scheduler.finish"])


def test_gaps_go_to_the_innermost_span_and_the_rest_is_unattributed():
    table = hostspans.attribute(host_spans(), device_lines(), window_s=0.040)
    by = {k.split(hostspans.SEP)[-1]: v for k, v in table["by_span"].items()}
    # 1-10.5 ms: tick 1, emit 2+3, finish 2, prep 1.5; 19-23 ms: the
    # read-back wait 1, no span 3; 24-31 ms: no span 1, wait 5, tick 1
    assert by == {
        TICK: pytest.approx(0.002), EMIT: pytest.approx(0.005),
        "scheduler.finish": pytest.approx(0.002), PREP: pytest.approx(0.0015),
        "engine.decode_lanes.device": pytest.approx(0.001),
        "scheduler.sched_wait": pytest.approx(0.005),
        hostspans.UNATTRIBUTED: pytest.approx(0.004)}
    digest = xplane.digest(device_lines(), window_s=0.040)
    assert table["idle_s"] == pytest.approx(sum(s for _, s in digest["idle_gaps"]))
    assert table["idle_s"] == pytest.approx(0.0205)
    assert table["clock"] == {"ticks": 2, "offset_ns": pytest.approx(-1_000.001 * MS),
                              "drift_ns": pytest.approx(0.002 * MS)}


def test_two_device_planes_average():
    lines = device_lines() + device_lines("/device:TPU:1", shift=2 * MS)
    table = hostspans.attribute(host_spans(), lines, window_s=0.040)
    by = {k.split(hostspans.SEP)[-1]: v for k, v in table["by_span"].items()}
    # on the second chip the 19-23 ms gap is 19-25 ms and the next 26-31 ms
    assert table["devices"] == 2
    assert by[hostspans.UNATTRIBUTED] == pytest.approx((0.004 + 0.005) / 2)
    assert by["scheduler.sched_wait"] == pytest.approx((0.005 + 0.004) / 2)
    assert by[EMIT] == pytest.approx(0.005)
    digest = xplane.digest(lines, window_s=0.040)
    assert table["idle_s"] == pytest.approx(sum(s for _, s in digest["idle_gaps"]))


def test_a_trace_without_scheduler_spans_gives_no_table():
    assert hostspans.attribute([], device_lines(), 0.040) is None


@pytest.mark.parametrize("op_name, scope", [
    ("jit(block)/while/body/closed_call/layers/while/body/closed_call/attn/dot_general:", "layers/attn"),
    ("jit(block)/while/body/layers/while/body/kv_write/transpose;attn", "layers/kv_write"),
    ("jit(step)/layers/while/body/closed_call/moe/jit(sort)/sort:", "layers/moe"),
    ("jit(block)/while/body/closed_call/layers/while:", "layers"),
    ("jit(block)/while/body/closed_call/layers/while/body/dynamic_slice:", "layers"),
    ("jit(block)/while/body/closed_call/logits_head/btd,dv->btv:", "logits_head"),
    ("jit(block)/while/body/closed_call/sample/vmap()/argmax:", "sample"),
    ("jit(convert_element_type)/convert_element_type:", "other"),
])
def test_scope_of_an_op_name(op_name, scope):
    assert hostspans.scope_of(op_name) == scope


def test_device_seconds_by_scope():
    ops = [("/device:TPU:0", "%fusion.1 = ...", 4 * MS, "jit(block)/layers/while/body/attn/dot:"),
           ("/device:TPU:0", "%copy.2 = ...", 3 * MS, "jit(block)/layers/while:"),
           ("/device:TPU:0", "%while.3 = ...", 7 * MS, "jit(block)/layers/while:"),  # a container
           ("/device:TPU:1", "%fusion.1 = ...", 2 * MS, "jit(block)/logits_head/dot:")]
    assert hostspans.by_scope(ops, 2) == {
        "layers/attn": pytest.approx(0.002), "layers": pytest.approx(0.0015),
        "logits_head": pytest.approx(0.001)}
    assert hostspans.by_scope(ops[3:], 2) is None  # a program without the scopes
    assert hostspans.by_scope([], 1) is None


def varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def field(num: int, value) -> bytes:
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    return varint(num << 3 | 2) + varint(len(value)) + value


def test_tf_ops_reads_the_op_name_from_the_wire_format():
    """An `XSpace` of two planes, encoded by hand: the device plane names
    stat 7 `tf_op`; one operation carries it as a string, one as a reference
    to a stat's name, one not at all; a fixed64 field is skipped."""
    stat_names = (field(5, field(1, 7) + field(2, field(1, 7) + field(2, b"tf_op")))
                  + field(5, field(1, 9) + field(2, field(1, 9) + field(2, b"jit(f)/layers/while:")))
                  + field(5, field(1, 3) + field(2, field(1, 3) + field(2, b"flops"))))
    def event(i, name, stats):
        return field(4, field(1, i) + field(2, field(1, i) + field(2, name) + stats))
    plane = (field(1, 0) + field(2, b"/device:TPU:0") + field(3, b"\x08\x01") + stat_names
             + event(1, b"%fusion.1 = f32[8]", field(5, field(1, 3) + field(3, 99))
                     + field(5, field(1, 7) + field(5, b"jit(f)/layers/attn/dot:")))
             + event(2, b"%copy.2 = f32[8]", field(5, field(1, 7) + field(7, 9)))
             + event(3, b"%add.3 = f32[8]", field(5, varint(2 << 3 | 1) + b"\0" * 8)))
    host = field(2, b"/host:CPU") + stat_names + event(1, b"x", field(5, field(1, 7) + field(5, b"a/b")))
    assert hostspans.tf_ops(field(1, plane) + field(1, host)) == {"/device:TPU:0": {
        "%fusion.1 = f32[8]": "jit(f)/layers/attn/dot:", "%copy.2 = f32[8]": "jit(f)/layers/while:"}}


@pytest.fixture
def run_dir(tmp_path):
    """A traced run's directory as the readers find it: the window is
    100-151 s on the host's clock, the slice 0.040 s, the timeline's epoch
    90 s."""
    d = str(tmp_path)
    dump = lambda name, obj: json.dump(obj, open(os.path.join(d, name), "w"))
    dump("window.json", {"t0": 100.0, "t1": 151.0, "trace_t0": 112.0, "trace_t1": 112.040})
    dump("trace_digest.json", {"busy_s": 0.0195, "window_s": 0.040})
    dump(hostspans.TABLE, hostspans.attribute(host_spans(), device_lines(), 0.040))
    dump(hostspans.SCOPES, {"layers/attn": 0.010, "layers": 0.0039, "logits_head": 0.002})
    span = lambda name, t0_s, dur_ms: {"ph": "X", "pid": 1, "tid": -1, "name": name,
                                       "ts": (t0_s - 90.0) * 1e6, "dur": dur_ms * 1e3, "args": {}}
    events = [{"ph": "M", "pid": 0, "tid": 0, "name": "timeline_epoch",
               "args": {"epoch_unix": 1.0, "epoch_monotonic": 90.0}},
              span("sched_tick", 99.0, 500.0),  # before the window
              span("decode_lanes.device", 101.010, 300.0), span("sched_tick", 101.0, 340.0),
              span("sched_tick", 102.0, 5.0),  # an admission tick: no read-back
              span("decode_lanes.device", 103.020, 400.0), span("verify_lanes.device", 103.5, 20.0),
              span("sched_tick", 103.0, 480.0),
              span("sched_tick", 151.5, 900.0)]  # after it
    with open(os.path.join(d, "timeline.json"), "w") as f:
        f.write("[" + "".join(json.dumps(ev) + ",\n" for ev in events))
    return d


def test_the_five_readers_on_a_fixture(run_dir):
    read = lambda metric: bench.layer_reader(metric).read(run_dir)
    assert read("idle_attributed_pct") == pytest.approx(100 * (1 - 0.006 / 0.0205))
    assert read("idle_in_emit_pct") == pytest.approx(100 * 0.007 / 0.040)  # emit and its finish
    assert read("idle_in_dispatch_prep_pct") == pytest.approx(100 * 0.0015 / 0.040)
    assert read("tick_host_ms") == pytest.approx(40.0)  # of 40, 5 and 60
    assert read("layer_scan_copy_pct") == pytest.approx(100 * 0.0039 / 0.0195)
    idle = bench.layer_reader("device_idle_pct").read(run_dir)
    assert read("idle_in_emit_pct") + read("idle_in_dispatch_prep_pct") <= idle


@pytest.mark.parametrize("metric,entry", [
    ("idle_attributed_pct", ("device", "%", "higher", "device_trace")),
    ("idle_in_emit_pct", ("scheduler", "%", "lower", "device_trace")),
    ("idle_in_dispatch_prep_pct", ("engine step", "%", "lower", "device_trace")),
    ("tick_host_ms", ("scheduler", "ms", "lower", "program_span")),
    ("layer_scan_copy_pct", ("kernels", "%", "lower", "device_trace")),
])
def test_a_reader_states_the_entry_it_will_have(metric, entry):
    """What `test_contract.py` holds a reader to against `BENCHMARK.json`:
    layers named as the file's other metrics name them."""
    reader = bench.layer_reader(metric)
    assert (reader.LAYER, reader.UNIT, reader.BETTER, reader.SOURCE) == entry
    assert reader.MOVES == "tpot_p95_ms"


def test_report_prints_the_five_as_a_result_line_would(run_dir, tmp_path_factory):
    """`python3 -m benchmark.harness.hostspans <run_dir>` reads a run
    directory that is already there."""
    assert tuple(NEW) == hostspans.METRICS
    got = hostspans.report(run_dir)
    assert list(got) == list(NEW)
    assert {m: v["unit"] for m, v in got.items()} == {
        m: "ms" if m == "tick_host_ms" else "%" for m in NEW}
    assert got["tick_host_ms"]["value"] == pytest.approx(40.0)
    assert hostspans.report(str(tmp_path_factory.mktemp("empty"))) == {}


@pytest.mark.parametrize("metric", NEW)
def test_a_reader_finds_nothing_in_an_empty_run_directory(metric, tmp_path):
    assert bench.layer_reader(metric).read(str(tmp_path)) is None


def test_readers_of_a_run_of_a_program_without_the_spans(run_dir):
    """The parent's traced run: its trace holds no `dllama.*` event and no
    scope, and its timeline is one JSON object."""
    for name in (hostspans.TABLE, hostspans.SCOPES):
        json.dump(None, open(os.path.join(run_dir, name), "w"))
    json.dump({"traceEvents": [], "dllama": {"epoch_unix": 1.0}},
              open(os.path.join(run_dir, "timeline.json"), "w"))
    for metric in NEW:
        assert bench.layer_reader(metric).read(run_dir) is None
