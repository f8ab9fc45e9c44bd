"""obs/ unit tests: metrics registry rendering, lifecycle spans, JSONL
tracing, and the telemetry Counter migration. Pure-Python (no engine), so
they ride the fast CI lane."""

import json
import threading

import pytest

from dllama_tpu.obs.metrics import (
    DEFAULT_LATENCY_BUCKETS_S,
    MetricsRegistry,
)
from dllama_tpu.obs.trace import NULL_SPAN, Tracer, read_jsonl

pytestmark = pytest.mark.fast


# -- metrics registry --------------------------------------------------------


def test_counter_gauge_basic():
    reg = MetricsRegistry()
    c = reg.counter("t_requests_total", "requests")
    c.inc()
    c.inc(2)
    assert c.value == 3
    g = reg.gauge("t_depth", "queue depth")
    g.set(5)
    g.inc()
    g.dec(2)
    assert g.value == 4


def test_registration_idempotent_and_type_checked():
    reg = MetricsRegistry()
    a = reg.counter("t_x_total")
    b = reg.counter("t_x_total")
    assert a is b
    with pytest.raises(ValueError):
        reg.gauge("t_x_total")


def test_histogram_buckets_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("t_lat_seconds", "latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 2.0, 100.0):  # le is inclusive: 0.1 -> first
        h.observe(v)
    text = reg.render()
    assert 't_lat_seconds_bucket{le="0.1"} 2' in text
    assert 't_lat_seconds_bucket{le="1"} 3' in text
    assert 't_lat_seconds_bucket{le="10"} 4' in text
    assert 't_lat_seconds_bucket{le="+Inf"} 5' in text
    assert "t_lat_seconds_count 5" in text
    assert f"t_lat_seconds_sum {0.05 + 0.1 + 0.5 + 2.0 + 100.0}" in text


def test_labeled_families_and_escaping():
    reg = MetricsRegistry()
    c = reg.counter("t_by_path_total", 'paths with "quotes"', labelnames=("path",))
    c.labels(path="/v1/chat").inc()
    c.labels(path='we"ird\npath').inc(2)
    with pytest.raises(ValueError):
        c.labels(wrong="x")
    with pytest.raises(ValueError):
        c.inc()  # labeled family has no default child
    text = reg.render()
    assert '# HELP t_by_path_total paths with "quotes"' in text
    assert 't_by_path_total{path="/v1/chat"} 1' in text
    assert 't_by_path_total{path="we\\"ird\\npath"} 2' in text


def test_render_prometheus_text_format_shape():
    """Every family renders a HELP+TYPE header and every sample line is
    `name{labels} value` — the subset of the 0.0.4 exposition format a
    stock Prometheus scraper requires."""
    reg = MetricsRegistry()
    reg.counter("t_a_total", "a").inc()
    reg.gauge("t_b", "b").set(1.5)
    reg.histogram("t_c_seconds", "c").observe(0.2)
    lines = reg.render().splitlines()
    assert lines, "empty render"
    names = set()
    for line in lines:
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            names.add(line.split()[2])
            continue
        name, value = line.rsplit(" ", 1)
        float(value)  # parses as a number
        base = name.split("{")[0]
        base = base.removesuffix("_bucket").removesuffix("_sum")
        base = base.removesuffix("_count")
        assert base in names, line  # samples follow their family header
    assert len(DEFAULT_LATENCY_BUCKETS_S) + 1 == sum(
        1 for line in lines if line.startswith("t_c_seconds_bucket")
    )


def test_disabled_registry_is_noop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("t_n_total")
    h = reg.histogram("t_n_seconds")
    c.inc()
    h.observe(1.0)
    assert c.value == 0 and h.count == 0
    reg.enable()
    c.inc()
    assert c.value == 1


def test_registry_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("t_mt_total")
    h = reg.histogram("t_mt_seconds", buckets=(1.0,))

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(0.5)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    assert h.count == 8000


# -- spans + tracer ----------------------------------------------------------


def test_span_lifecycle_and_record():
    tr = Tracer(capacity=4)
    span = tr.span(path="lanes")
    qw = span.mark_admitted(lane=2, reused_prefix_tokens=7)
    assert qw >= 0 and span.lane == 2
    span.set_prefill_seconds(0.25)
    ttft = span.mark_first_token()
    assert ttft is not None and ttft >= qw
    assert span.mark_first_token() is None  # single-shot
    rec = span.finish("stop", n_prompt=11, n_completion=3)
    assert span.finish("length") is None  # idempotent: first reason wins
    assert rec["finish_reason"] == "stop" and rec["cancelled"] is False
    assert rec["reused_prefix_tokens"] == 7
    assert rec["n_prompt_tokens"] == 11 and rec["n_completion"] == 3
    assert rec["prefill_s"] == 0.25
    assert rec["queue_wait_s"] is not None and rec["ttft_s"] is not None
    assert tr.records() == [rec]
    assert span.ttft_ms == pytest.approx(rec["ttft_s"] * 1000)


def test_span_cancelled_flag():
    tr = Tracer()
    span = tr.span()
    span.mark_admitted()
    rec = span.finish("cancelled", n_completion=2)
    assert rec["cancelled"] is True
    # TTFT never happened: recorded honestly as null
    assert rec["ttft_s"] is None


def test_null_span_is_inert():
    tr_len_before = NULL_SPAN.finish("stop")
    assert tr_len_before is None
    assert NULL_SPAN.mark_admitted(lane=1) == 0.0
    assert NULL_SPAN.mark_first_token() is None


def test_tracer_ring_bound_and_jsonl(tmp_path):
    sink = str(tmp_path / "trace.jsonl")
    tr = Tracer(capacity=3, sink_path=sink)
    for i in range(5):
        span = tr.span(request_id=f"r{i}")
        span.mark_admitted()
        span.finish("stop", n_prompt=1, n_completion=i)
    assert [r["request_id"] for r in tr.records()] == ["r2", "r3", "r4"]
    tr.close()
    # the sink kept ALL records (the ring only bounds memory)
    recs = read_jsonl(sink)
    assert [r["request_id"] for r in recs] == [f"r{i}" for i in range(5)]
    assert all(json.dumps(r) for r in recs)  # each line round-trips
    # export dumps the current ring
    out = str(tmp_path / "export.jsonl")
    assert tr.export(out) == 3
    assert len(read_jsonl(out)) == 3


def test_telemetry_counter_on_registry():
    from dllama_tpu.obs.metrics import get_registry
    from dllama_tpu.utils.telemetry import Counter

    c = Counter("t_decode")
    c.add(10.0, n=2)
    c.add(5.0)
    assert c.n == 3 and c.total_ms == 15.0
    assert c.rate == pytest.approx(3 * 1000.0 / 15.0)
    reg = get_registry()
    assert reg.counter("dllama_t_decode_events_total").value == 3
    assert reg.counter("dllama_t_decode_ms_total").value == 15.0
    # anonymous counters keep the old purely-local behavior
    anon = Counter()
    anon.add(1.0)
    assert anon.n == 1


# -- flight recorder ---------------------------------------------------------


def test_recorder_ring_overflow_keeps_newest():
    from dllama_tpu.obs.recorder import FlightRecorder

    rec = FlightRecorder(capacity=4)
    for i in range(10):
        rec.record("step_dispatch", i=i)
    evs = rec.events()
    assert len(evs) == 4
    assert [e["i"] for e in evs] == [6, 7, 8, 9]  # oldest fell off
    assert [e["seq"] for e in evs] == [7, 8, 9, 10]  # lifetime index survives
    assert rec.total_recorded == 10
    d = rec.dump()
    assert d["n_events"] == 4 and d["total_recorded"] == 10
    assert d["dropped"] == 6 and d["capacity"] == 4
    assert json.loads(rec.dump_json())["n_events"] == 4
    assert rec.events(kind="nope") == []
    rec.clear()
    assert rec.events() == []
    assert rec.total_recorded == 10  # clear drops events, not the ledger


def test_recorder_thread_safety():
    from dllama_tpu.obs.recorder import FlightRecorder

    rec = FlightRecorder(capacity=128)

    def work(tid):
        for i in range(500):
            rec.record("e", tid=tid, i=i)

    threads = [threading.Thread(target=work, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert rec.total_recorded == 4000
    evs = rec.events()
    assert len(evs) == 128
    seqs = [e["seq"] for e in evs]
    assert len(set(seqs)) == 128 and max(seqs) == 4000
    assert seqs == sorted(seqs)  # ring preserves recording order


def test_recorder_disabled_is_noop():
    from dllama_tpu.obs.recorder import FlightRecorder

    rec = FlightRecorder(capacity=4, enabled=False)
    rec.record("e")
    assert rec.events() == [] and rec.total_recorded == 0
    rec.enable()
    rec.record("e")
    assert rec.total_recorded == 1
    rec.disable()
    rec.record("e")
    assert rec.total_recorded == 1


def test_recorder_postmortem_dump(tmp_path):
    from dllama_tpu.obs.recorder import FlightRecorder

    rec = FlightRecorder(capacity=16, postmortem_dir=str(tmp_path / "pm"))
    rec.record("step_dispatch", step="decode_block", pos=7)
    path = rec.postmortem("engine-step", RuntimeError("kaboom"))
    assert path is not None
    with open(path) as f:
        payload = json.load(f)
    assert payload["reason"] == "engine-step"
    assert payload["error"] == "kaboom"
    assert payload["error_type"] == "RuntimeError"
    kinds = [e["kind"] for e in payload["events"]]
    assert kinds == ["step_dispatch", "postmortem"]  # ring + the marker
    # a second postmortem gets a distinct file
    path2 = rec.postmortem("scheduler-loop", "plain string error")
    assert path2 is not None and path2 != path
    with open(path2) as f:
        p2 = json.load(f)
    assert p2["error"] == "plain string error" and p2["error_type"] is None


def test_recorder_postmortem_never_raises(tmp_path):
    from dllama_tpu.obs.recorder import FlightRecorder

    # no dir configured -> None, events still recorded
    rec = FlightRecorder(capacity=4)
    assert rec.postmortem("x", RuntimeError("e")) is None
    assert rec.events(kind="postmortem")
    # dir path blocked by a plain file -> swallowed, None returned
    blocker = tmp_path / "blocked"
    blocker.write_text("not a directory")
    rec.postmortem_dir = str(blocker)
    assert rec.postmortem("x", RuntimeError("e")) is None


def test_get_recorder_is_process_singleton():
    from dllama_tpu.obs.recorder import get_recorder

    assert get_recorder() is get_recorder()


# -- cost analysis + roofline ------------------------------------------------


class _FakeCompiled:
    def __init__(self, ca):
        self._ca = ca

    def cost_analysis(self):
        if isinstance(self._ca, Exception):
            raise self._ca
        return self._ca


def test_extract_cost_shapes():
    from dllama_tpu.obs.cost import extract_cost

    assert extract_cost(object()) is None  # lazily jitted fn: no surface
    assert extract_cost(_FakeCompiled(RuntimeError("no"))) is None
    assert extract_cost(_FakeCompiled(None)) is None
    assert extract_cost(_FakeCompiled({})) is None
    got = extract_cost(
        _FakeCompiled({"flops": 10.0, "bytes accessed": 20.0})
    )
    assert got == {"flops": 10.0, "bytes_accessed": 20.0}
    got = extract_cost(_FakeCompiled({"flops": 3.0}))
    assert got == {"flops": 3.0, "bytes_accessed": 0.0}


def test_extract_cost_real_aot_executable():
    """The integration the /v1/debug/compile endpoint rides on: a real
    AOT-compiled executable reports non-empty cost analysis on CPU."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.obs.cost import extract_cost

    x = jnp.ones((8, 8), jnp.float32)
    compiled = jax.jit(lambda a, b: a @ b).lower(x, x).compile()
    cost = extract_cost(compiled)
    assert cost is not None and cost["flops"] > 0


def test_roofline_fraction():
    from dllama_tpu.obs.cost import roofline_fraction

    assert roofline_fraction(1e9, 0.002, 819e9) == pytest.approx(
        (1e9 / 0.002) / 819e9
    )
    assert roofline_fraction(1e9, 0.002, None) is None
    assert roofline_fraction(1e9, 0.0, 819e9) is None
    assert roofline_fraction(0.0, 0.002, 819e9) is None


def test_weight_bytes_per_token_formats():
    from types import SimpleNamespace

    from dllama_tpu.obs.cost import weight_bytes_per_token

    h = SimpleNamespace(dim=4, q_dim=4, kv_dim=2, ff_dim=8, n_layers=1,
                        vocab_size=10, n_experts=0, n_active_experts=0)
    att = 4 * 4 + 2 * 4 * 2 + 4 * 4     # 48
    ffn = 3 * 4 * 8                      # 96
    base = att + ffn + 4 * 10            # + embed/cls read
    assert weight_bytes_per_token(h, ("int8", "int8")) == int(base * 1.125)
    assert weight_bytes_per_token(h, ("float", "float")) == base * 2
    assert weight_bytes_per_token(h, ("packed", "packed")) == int(base * 0.625)


@pytest.mark.parametrize(
    "weight_format,devices,dense_bpw,expert_bpw",
    [("q40", 1, 1.125, 1.125), ("q40i4", 4, 0.625, 1.125),
     ("q40i4", 1, 0.625, 0.625), ("dense", 1, 2.0, 2.0)],
)
def test_weight_bytes_per_token_charges_each_leaf_by_its_form(
        weight_format, devices, dense_bpw, expert_bpw):
    """Under `q40i4` the routed experts are charged by the form the loader
    holds them in (`models/loader.weight_forms`): packed where one device
    holds the layer, int8 on a mesh: there a sparse model's step is
    charged 0.625 B a weight for attention and the head and 1.125 for the
    active experts, not 0.5625 throughout (ROADMAP D8)."""
    from types import SimpleNamespace

    from dllama_tpu.formats.quants import FloatType
    from dllama_tpu.models.loader import weight_forms
    from dllama_tpu.obs.cost import roofline_report, weight_bytes_per_token

    h = SimpleNamespace(dim=64, q_dim=64, kv_dim=32, ff_dim=32, n_layers=2,
                        vocab_size=96, n_experts=8, n_active_experts=2)
    # the bytes are the header's; the forms are those of a file whose
    # experts' in axes the packed kernel takes
    specs = [SimpleNamespace(name=f"layers.0.experts.0.{n}", shape=(256, 256),
                             float_type=FloatType.Q40) for n in ("w1", "w2")]
    forms = weight_forms(specs, weight_format, devices)
    att = 64 * 64 * 2 + 2 * 64 * 32
    experts = 3 * 64 * 32 * 2
    want = (2 * (att * dense_bpw + experts * expert_bpw)
            + 64 * 96 * dense_bpw + 2 * 64 * 8 * 4)
    assert weight_bytes_per_token(h, forms) == int(want)
    rep = roofline_report(h, forms)
    assert rep["weight_bytes_per_token_per_chip"] == int(want)


def test_roofline_report_degrades_without_tpu():
    """On the CPU test backend the HBM peak is unknown: every derived
    figure is an explicit None, never a made-up fraction."""
    from types import SimpleNamespace

    from dllama_tpu.obs.cost import (
        hbm_peak_bytes_per_s,
        print_roofline_report,
        roofline_report,
    )

    assert hbm_peak_bytes_per_s() is None
    h = SimpleNamespace(dim=64, q_dim=64, kv_dim=32, ff_dim=160, n_layers=2,
                        vocab_size=288, n_experts=0, n_active_experts=0)
    q40 = ("int8", "int8")
    rep = roofline_report(h, q40, tp=2)
    assert rep["weight_bytes_per_token_per_chip"] > 0
    assert rep["hbm_peak_bytes_per_s"] is None
    assert rep["min_ms_per_token"] is None
    assert rep["max_tok_s_per_chip"] is None
    # tp*pp shards the weight reads
    assert rep["weight_bytes_per_token_per_chip"] == pytest.approx(
        roofline_report(h, q40)["weight_bytes_per_token_per_chip"] // 2,
        abs=1,
    )
    assert print_roofline_report(h, q40, tp=2) == rep  # prints, returns same


# -- device memory telemetry -------------------------------------------------


def test_device_memory_stats_shape():
    import jax

    from dllama_tpu.obs.device import device_memory_stats

    stats = device_memory_stats()
    assert len(stats) == len(jax.devices())
    for s in stats:
        assert set(s) >= {"device", "platform", "available"}
        if s["available"]:
            assert s["bytes_in_use"] >= 0 and s["bytes_limit"] >= 0
        else:
            assert "bytes_in_use" not in s  # no fabricated zeros


def test_sample_device_memory_registers_gauges():
    from dllama_tpu.obs.device import sample_device_memory

    reg = MetricsRegistry()
    stats = sample_device_memory(reg)
    text = reg.render()
    for fam in ("dllama_device_bytes_in_use",
                "dllama_device_peak_bytes_in_use",
                "dllama_device_bytes_limit"):
        assert f"# TYPE {fam} gauge" in text
    for s in stats:
        if s["available"]:  # TPU run: the gauge really carries the sample
            assert f'dllama_device_bytes_in_use{{device="{s["device"]}"}}' \
                in text


def test_compare_with_analytic_divergence(caplog):
    import logging

    from dllama_tpu.obs.device import compare_with_analytic

    ok = [{"device": "d0", "platform": "tpu", "available": True,
           "bytes_in_use": 105, "peak_bytes_in_use": 110, "bytes_limit": 200}]
    with caplog.at_level(logging.WARNING, logger="dllama_tpu.obs.device"):
        cmp_ok = compare_with_analytic(100, stats=ok)
    assert cmp_ok["available"] is True
    assert cmp_ok["max_divergence_fraction"] == pytest.approx(0.05)
    assert not caplog.records  # within tolerance: silent

    bad = [dict(ok[0], bytes_in_use=130)]
    with caplog.at_level(logging.WARNING, logger="dllama_tpu.obs.device"):
        cmp_bad = compare_with_analytic(100, stats=bad)
    assert cmp_bad["max_divergence_fraction"] == pytest.approx(0.30)
    assert any("diverges" in r.message for r in caplog.records)


def test_compare_with_analytic_unavailable():
    from dllama_tpu.obs.device import compare_with_analytic

    none_avail = [{"device": "cpu:0", "platform": "cpu", "available": False}]
    cmp_ = compare_with_analytic(100, stats=none_avail)
    assert cmp_["available"] is False
    assert cmp_["max_divergence_fraction"] is None and cmp_["per_chip"] == []
    assert compare_with_analytic(0, stats=[])["available"] is False


# -- telemetry hardening + consistency ---------------------------------------


def test_profile_survives_start_trace_failure(monkeypatch, caplog):
    import logging

    import jax

    from dllama_tpu.utils import telemetry

    def bad_start(d):
        raise RuntimeError("profiler already active")

    monkeypatch.setattr(jax.profiler, "start_trace", bad_start)
    ran = False
    with caplog.at_level(logging.WARNING, logger="dllama_tpu.utils.telemetry"):
        with telemetry.profile("/tmp/nowhere"):
            ran = True  # the profiled body still runs
    assert ran
    assert any("start_trace" in r.message for r in caplog.records)


def test_profile_survives_stop_trace_failure(monkeypatch, caplog):
    import logging

    import jax

    from dllama_tpu.utils import telemetry

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)

    def bad_stop():
        raise RuntimeError("trace collection died")

    monkeypatch.setattr(jax.profiler, "stop_trace", bad_stop)
    with caplog.at_level(logging.WARNING, logger="dllama_tpu.utils.telemetry"):
        with telemetry.profile("/tmp/nowhere"):
            pass
    assert any("stop_trace" in r.message for r in caplog.records)


def test_profile_noop_without_log_dir(monkeypatch):
    import jax

    from dllama_tpu.utils import telemetry

    def explode(*a):
        raise AssertionError("profiler must not be touched")

    monkeypatch.setattr(jax.profiler, "start_trace", explode)
    with telemetry.profile(None):
        pass
    with telemetry.profile(""):
        pass


def test_replicated_keys_match_param_spec_tree():
    """Pin telemetry's replication list to the sharding layout it models:
    the keys memory_report treats as whole-on-every-chip must be exactly
    the P() leaves of parallel/sharding.param_spec_tree across all
    arches. A sharding change that replicates or splits a new leaf must
    touch both files (this test is the tripwire)."""
    from types import SimpleNamespace

    from jax.sharding import PartitionSpec as P

    from dllama_tpu.formats.model_file import LlmArch
    from dllama_tpu.parallel.sharding import param_spec_tree
    from dllama_tpu.utils.telemetry import _REPLICATED_KEYS

    replicated = set()
    for arch in (LlmArch.LLAMA, LlmArch.QWEN3, LlmArch.QWEN3_MOE):
        spec = param_spec_tree(SimpleNamespace(arch=arch))
        layers = spec.pop("layers")
        for scope in (spec, layers):
            for key, leaf_spec in scope.items():
                if leaf_spec == P():
                    replicated.add(key)
    assert replicated == _REPLICATED_KEYS


# -- histogram percentile (watchdog stall thresholds ride on this) -----------


def test_histogram_percentile_interpolation():
    reg = MetricsRegistry()
    h = reg.histogram("t_pct_seconds", "pct", buckets=(1.0, 2.0, 4.0))
    assert h.percentile(0.5) is None  # no observations yet
    for v in (0.5, 0.5, 0.5, 0.5, 1.5, 1.5, 1.5, 1.5, 3.0, 3.0):
        h.observe(v)
    # 10 samples: 4 in (0,1], 4 in (1,2], 2 in (2,4]; linear interpolation
    # within the landing bucket, first bucket's lower edge is 0.0
    assert h.percentile(0.0) == 0.0
    assert h.percentile(0.4) == 1.0  # exactly exhausts the first bucket
    assert h.percentile(0.5) == pytest.approx(1.25)
    assert h.percentile(0.8) == pytest.approx(2.0)
    assert h.percentile(0.9) == pytest.approx(3.0)
    assert h.percentile(1.0) == 4.0
    with pytest.raises(ValueError):
        h.percentile(1.5)
    with pytest.raises(ValueError):
        h.percentile(-0.1)


def test_histogram_percentile_edge_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("t_pct_edge_seconds", "pct", buckets=(1.0, 2.0, 4.0))
    h.observe(1.5)
    h.observe(1.5)
    # target 0 lands in the empty first bucket -> its upper edge
    assert h.percentile(0.0) == 1.0
    assert h.percentile(1.0) == 2.0
    # overflow-only data clamps to the largest finite edge (the +Inf
    # bucket has no finite upper bound to interpolate toward)
    h2 = reg.histogram("t_pct_inf_seconds", "pct", buckets=(1.0, 2.0, 4.0))
    h2.observe(100.0)
    assert h2.percentile(0.5) == 4.0


def test_histogram_percentile_labeled_child():
    reg = MetricsRegistry()
    fam = reg.histogram(
        "t_pct_lbl_seconds", "pct", labelnames=("kind",), buckets=(1.0, 2.0)
    )
    fam.labels(kind="decode").observe(0.5)
    # one sample in (0,1]: p100 interpolates to the bucket's upper edge
    assert fam.labels(kind="decode").percentile(1.0) == 1.0
    assert fam.labels(kind="decode").percentile(0.5) == pytest.approx(0.5)
    assert fam.labels(kind="other").percentile(0.5) is None


# -- tracer serialization fallback + sink-error event ------------------------


def test_tracer_sink_survives_nonserializable_attrs(tmp_path):
    sink = str(tmp_path / "trace.jsonl")
    tr = Tracer(capacity=4, sink_path=sink)
    tr.record({"request_id": "r1", "err": ValueError("boom")})
    tr.close()
    (rec,) = read_jsonl(sink)
    assert rec["request_id"] == "r1"
    assert rec["err"] == repr(ValueError("boom"))  # degraded, not dropped


def test_tracer_export_survives_nonserializable_attrs(tmp_path):
    tr = Tracer(capacity=4)
    tr.record({"request_id": "r1", "obj": object()})
    out = str(tmp_path / "export.jsonl")
    assert tr.export(out) == 1
    (rec,) = read_jsonl(out)
    assert rec["obj"].startswith("<object object")


def test_dumps_safe_circular_structure():
    from dllama_tpu.obs.trace import _dumps_safe

    d = {"request_id": "r1"}
    d["self"] = d  # json.dumps raises ValueError even with default=repr
    rec = json.loads(_dumps_safe(d))
    assert "_unserializable" in rec


def test_tracer_sink_write_error_records_event(tmp_path):
    from dllama_tpu.obs.recorder import get_recorder

    sink = str(tmp_path / "trace.jsonl")
    tr = Tracer(capacity=4, sink_path=sink)
    tr._sink.close()  # simulate the fd dying under the tracer
    before = len(get_recorder().events("obs_sink_error"))
    tr.record({"request_id": "r1"})
    evs = get_recorder().events("obs_sink_error")
    assert len(evs) == before + 1
    assert evs[-1]["what"] == "trace_jsonl"
    assert evs[-1]["path"] == sink
    assert evs[-1]["error_type"] == "ValueError"
    # the sink is dropped, the ring keeps serving, no second event
    assert tr._sink is None
    tr.record({"request_id": "r2"})
    assert len(get_recorder().events("obs_sink_error")) == before + 1
    assert [r["request_id"] for r in tr.records()] == ["r1", "r2"]
