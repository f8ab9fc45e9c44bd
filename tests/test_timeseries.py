"""Fake-clock units for the in-process time-series store, the sampler,
the registry refresh-hook path, and the anomaly monitor (ISSUE 9).

Everything here runs against PRIVATE MetricsRegistry / FlightRecorder
instances and injected clocks — no real time, no shared global state —
so every ring/downsampling/anomaly assertion is deterministic.
"""

import threading
import time

import pytest

from dllama_tpu.obs.anomaly import (
    AnomalyMonitor,
    AnomalyRule,
    EwmaBaseline,
    _level,
    _per_event_rate,
    _slope,
    build_default_rules,
)
from dllama_tpu.obs.metrics import MetricsRegistry
from dllama_tpu.obs.recorder import FlightRecorder
from dllama_tpu.obs.timeseries import (
    DOWNSAMPLE_EVERY,
    MetricsSampler,
    SeriesStore,
    resolve_series_knobs,
)

from helpers import assert_one_spelling

pytestmark = pytest.mark.fast


def _store(**kw):
    """SeriesStore bound to private registry+recorder (no global state)."""
    reg = kw.pop("registry", MetricsRegistry())
    rec = kw.pop("recorder", FlightRecorder())
    kw.setdefault("interval_s", 1.0)
    return SeriesStore(registry=reg, recorder=rec, **kw), reg, rec


# -- knob resolution --------------------------------------------------------


KNOB_TWINS = ("DLLAMA_SERIES_RETENTION_S",)


def test_series_knob_defaults(monkeypatch):
    """An hour's retention is `--series-retention`'s default; the sampling
    interval has no flag and stays `DLLAMA_SERIES_INTERVAL_S`'s, 1 s unset."""
    from dllama_tpu.runtime.api_server import build_arg_parser

    assert build_arg_parser().parse_args([]).series_retention == 3600.0
    monkeypatch.delenv("DLLAMA_SERIES_INTERVAL_S", raising=False)
    assert resolve_series_knobs() == 1.0
    monkeypatch.setenv("DLLAMA_SERIES_INTERVAL_S", "0.5")
    assert resolve_series_knobs() == 0.5


@pytest.mark.parametrize("name", KNOB_TWINS)
def test_series_knob_env_and_explicit(unflagged, flagged, name):
    """The store keeps an hour with the former variable set
    (`resolve_series_knobs` read it until PR 45), and `--series-retention
    120` is the store's retention."""
    assert_one_spelling(name, unflagged, flagged)


# -- registry: flat_values + refresh hooks ----------------------------------


def test_flat_values_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.counter("dllama_t_total", "c").inc(3)
    reg.gauge("dllama_t_g", "g", labelnames=("k",)).labels(k="a").set(7.0)
    h = reg.histogram("dllama_t_h", "h")
    for v in (0.1, 0.2, 0.3, 0.4):
        h.observe(v)
    flat = reg.flat_values()
    assert flat["dllama_t_total"] == ("counter", 3.0)
    assert flat['dllama_t_g{k="a"}'] == ("gauge", 7.0)
    # histograms flatten to rate-able cumulative sum/count plus quantile
    # estimate gauges
    assert flat["dllama_t_h_count"] == ("counter", 4.0)
    kind, total = flat["dllama_t_h_sum"]
    assert kind == "counter" and total == pytest.approx(1.0)
    kind, p50 = flat["dllama_t_h_p50"]
    assert kind == "gauge" and 0.1 <= p50 <= 0.4
    assert "dllama_t_h_p99" in flat


def test_refresh_hooks_keyed_replacement():
    """Registering under an existing name REPLACES the hook — ApiState
    churn against the process-global registry must not stack dead
    closures (the stale-gauge regression this PR fixes structurally)."""
    reg = MetricsRegistry()
    g = reg.gauge("dllama_t_hook", "g")
    calls = []
    reg.add_refresh_hook("h", lambda: calls.append("old"))
    reg.add_refresh_hook("h", lambda: (calls.append("new"), g.set(42.0)))
    reg.run_refresh_hooks()
    assert calls == ["new"]
    assert reg.flat_values()["dllama_t_hook"] == ("gauge", 42.0)
    reg.remove_refresh_hook("h")
    reg.run_refresh_hooks()
    assert calls == ["new"]


def test_refresh_hook_failure_is_contained():
    """One broken refresher logs and is skipped; later hooks still run."""
    reg = MetricsRegistry()
    ran = []
    reg.add_refresh_hook("bad", lambda: 1 / 0)
    reg.add_refresh_hook("good", lambda: ran.append(True))
    reg.run_refresh_hooks()  # must not raise
    assert ran == [True]


def test_refresh_hooks_disabled_registry():
    reg = MetricsRegistry(enabled=False)
    ran = []
    reg.add_refresh_hook("h", lambda: ran.append(True))
    reg.run_refresh_hooks()
    assert ran == []


# -- SeriesStore ------------------------------------------------------------


def test_two_tier_downsampling():
    """Counter series downsample by LAST value, gauge series by MEAN."""
    store, _, _ = _store(tier1_retention_s=10.0, retention_s=100.0)
    for i in range(DOWNSAMPLE_EVERY):
        store.record(
            float(i),
            {
                "c_total": ("counter", float(i + 1)),
                "g": ("gauge", float(i)),
            },
        )
    with store._lock:
        c, g = store._series["c_total"], store._series["g"]
        assert len(c.tier1) == 10 and len(c.tier2) == 1
        # cumulative counter at the bucket edge: exact last value
        assert c.tier2[0] == (9.0, 10.0)
        # gauge mean over 0..9
        assert g.tier2[0] == (9.0, pytest.approx(4.5))


def test_tier_capacities_bound_memory():
    store, _, _ = _store(tier1_retention_s=5.0, retention_s=100.0)
    for i in range(300):
        store.record(float(i), {"g": ("gauge", float(i))})
    with store._lock:
        s = store._series["g"]
        assert len(s.tier1) == 5  # tier1_retention_s / interval_s
        assert len(s.tier2) == 10  # retention_s / (interval * 10)


def test_query_tier_selection_and_cutoff():
    store, _, _ = _store(tier1_retention_s=10.0, retention_s=200.0)
    for i in range(100):
        store.record(float(i), {"g": ("gauge", float(i))})
    # short window -> full-resolution tier, now defaults to newest sample
    q1 = store.query("g", window_s=5.0)
    assert q1["tier"] == "1s" and q1["interval_s"] == 1.0
    assert q1["now"] == 99.0
    # cutoff is inclusive: window 5 back from t=99 keeps t>=94
    assert [t for t, _ in q1["points"]] == [
        94.0, 95.0, 96.0, 97.0, 98.0, 99.0,
    ]
    # long window -> downsampled tier
    q2 = store.query("g", window_s=100.0)
    assert q2["tier"] == "10s" and q2["interval_s"] == 10.0
    assert len(q2["points"]) >= 9
    assert store.query("missing", window_s=10.0) is None


def test_max_series_cap_drops_new_names_once():
    store, reg, rec = _store(max_series=2)
    store.record(0.0, {"a": ("gauge", 1.0), "b": ("gauge", 2.0)})
    store.record(
        1.0,
        {"a": ("gauge", 1.0), "b": ("gauge", 2.0), "c": ("gauge", 3.0)},
    )
    store.record(2.0, {"c": ("gauge", 3.0), "d": ("gauge", 4.0)})
    assert store.names() == ["a", "b"]
    assert store.m_dropped.value == 3
    assert store.g_tracked.value == 2
    # existing series kept sampling through the overflow
    assert store.latest("a") == 1.0
    # the overflow announced itself exactly once
    assert len(rec.events("obs_overflow")) == 1


def test_latest():
    store, _, _ = _store()
    assert store.latest("g") is None
    store.record(0.0, {"g": ("gauge", 5.0)})
    store.record(1.0, {"g": ("gauge", 6.0)})
    assert store.latest("g") == 6.0


# -- MetricsSampler ---------------------------------------------------------


def test_sample_once_runs_hooks_and_callbacks():
    reg = MetricsRegistry()
    g = reg.gauge("dllama_t_live", "g")
    ticks = {"n": 0}

    def refresher():
        ticks["n"] += 1
        g.set(float(ticks["n"]))

    reg.add_refresh_hook("live", refresher)
    store, _, _ = _store(registry=reg)
    fake = {"t": 100.0}
    sampler = MetricsSampler(store, registry=reg, clock=lambda: fake["t"])
    seen = []
    sampler.on_sample.append(seen.append)
    sampler.on_sample.append(lambda now: 1 / 0)  # must be contained

    now = sampler.sample_once()
    assert now == 100.0
    # the hook ran BEFORE the snapshot: the sampled value is current,
    # independent of any /metrics scrape
    assert store.latest("dllama_t_live") == 1.0
    assert seen == [100.0]
    fake["t"] = 101.0
    sampler.sample_once()
    assert store.latest("dllama_t_live") == 2.0
    assert store.m_samples.value == 2


def test_sampler_thread_starts_and_joins():
    """The sampler thread is named, daemonic, and stop() joins it — the
    fast lane runs this under DLLAMA_LOCKWATCH=1 in CI."""
    reg = MetricsRegistry()
    reg.gauge("dllama_t_g", "g").set(1.0)
    store, _, _ = _store(registry=reg, interval_s=0.005)
    sampler = MetricsSampler(store, registry=reg)
    sampler.start()
    t = sampler._thread
    assert t is not None and t.daemon and t.name == "dllama-series-sampler"
    deadline = time.monotonic() + 5.0
    while store.m_samples.value < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert store.m_samples.value >= 2, "sampler thread never ticked"
    sampler.stop()
    assert sampler._thread is None
    assert not t.is_alive()
    sampler.stop()  # idempotent


# -- EWMA / rules -----------------------------------------------------------


def test_ewma_baseline_tracks_mean_and_var():
    b = EwmaBaseline(alpha=0.2)
    for _ in range(200):
        b.update(10.0)
    assert b.mean == pytest.approx(10.0)
    assert b.std == pytest.approx(0.0, abs=1e-9)
    for v in (9.0, 11.0, 9.0, 11.0, 9.0, 11.0):
        b.update(v)
    assert 9.0 < b.mean < 11.0
    assert b.std > 0.0


def test_rule_warmup_and_guards():
    rule = AnomalyRule(
        "t", lambda: None, z_threshold=4.0, min_samples=10,
        min_abs=0.5, rel_frac=1.0,
    )
    b = EwmaBaseline()
    for _ in range(5):
        b.update(1.0)
    # warmup: even a huge spike cannot fire before min_samples
    assert rule.abnormal(b, 100.0) is None
    for _ in range(10):
        b.update(1.0)
    # min_abs/rel_frac floors: a tiny deviation off a near-constant
    # baseline has a huge z but must not alarm
    assert rule.abnormal(b, 1.3) is None
    z = rule.abnormal(b, 100.0)
    assert z is not None and z >= 4.0


def test_rule_low_direction_min_mean():
    rule = AnomalyRule(
        "t", lambda: None, direction="low", min_samples=3, min_mean=1.0,
        min_abs=0.5,
    )
    b = EwmaBaseline()
    for _ in range(10):
        b.update(0.0)
    # an idle signal sitting at zero can never "drop"
    assert rule.abnormal(b, -5.0) is None
    b2 = EwmaBaseline()
    for _ in range(10):
        b2.update(10.0)
    assert rule.abnormal(b2, 0.0) is not None


def test_rule_rejects_bad_direction():
    with pytest.raises(ValueError):
        AnomalyRule("t", lambda: None, direction="sideways")


# -- AnomalyMonitor ---------------------------------------------------------


def _monitor(rule, **kw):
    reg = kw.pop("registry", MetricsRegistry())
    rec = kw.pop("recorder", FlightRecorder())
    fake = {"t": 0.0}
    mon = AnomalyMonitor(
        [rule], registry=reg, recorder=rec, clock=lambda: fake["t"]
    )
    return mon, reg, rec, fake


def test_anomaly_fires_and_recovers_deterministically():
    """The ISSUE 9 acceptance unit: a rule fires on an injected spike
    (incrementing dllama_anomaly_total and the degraded gauge), its
    baseline FREEZES while active, and `recover_ticks` calm ticks later
    it recovers — all under a fake clock."""
    sig = {"v": 1.0}
    rule = AnomalyRule(
        "stall", lambda: sig["v"], z_threshold=4.0, min_samples=20,
        min_abs=0.1, rel_frac=0.5, recover_ticks=3,
    )
    mon, reg, rec, fake = _monitor(rule)
    for i in range(30):
        fake["t"] = float(i)
        assert mon.evaluate() == []
    assert not mon.degraded

    sig["v"] = 50.0
    fake["t"] = 30.0
    assert mon.evaluate() == ["stall"]
    assert mon.degraded and mon.active_signals() == ["stall"]
    assert mon.m_anomalies.labels(signal="stall").value == 1
    assert mon.g_degraded.value == 1.0
    (ev,) = rec.events("anomaly")
    assert ev["signal"] == "stall" and ev["z"] >= 4.0
    frozen_mean = mon._state["stall"].baseline.mean
    st = mon.status()
    assert st["degraded"] and "stall" in st["active"]
    assert st["active"]["stall"]["active_s"] == 0.0

    # still anomalous: stays active, fires NOTHING new (edge-triggered),
    # and the anomaly never teaches the baseline
    fake["t"] = 31.0
    assert mon.evaluate() == []
    assert mon.m_anomalies.labels(signal="stall").value == 1
    assert mon._state["stall"].baseline.mean == frozen_mean

    # recovery hysteresis: recover_ticks consecutive calm ticks clear it
    sig["v"] = 1.0
    for i in range(3):
        fake["t"] = 32.0 + i
        assert mon.evaluate() == []
    assert not mon.degraded
    assert mon.g_degraded.value == 0.0
    assert [e["signal"] for e in rec.events("anomaly_recovered")] == ["stall"]


def test_anomaly_missing_values_count_as_calm():
    """A quiet engine (value_fn -> None: no traffic) must recover."""
    sig = {"v": 1.0}
    rule = AnomalyRule(
        "r", lambda: sig["v"], min_samples=5, min_abs=0.1, recover_ticks=2,
    )
    mon, _, _, fake = _monitor(rule)
    for i in range(10):
        fake["t"] = float(i)
        mon.evaluate()
    sig["v"] = 99.0
    fake["t"] = 10.0
    assert mon.evaluate() == ["r"]
    sig["v"] = None
    for i in range(2):
        fake["t"] = 11.0 + i
        mon.evaluate()
    assert not mon.degraded


def test_anomaly_value_fn_errors_are_contained():
    rule = AnomalyRule("boom", lambda: 1 / 0, min_samples=1)
    mon, _, _, _ = _monitor(rule)
    assert mon.evaluate() == []  # logs, skips, keeps serving
    assert not mon.degraded


# -- signal helpers / default rule set --------------------------------------


def test_per_event_rate_reads_histogram_deltas():
    store, _, _ = _store()
    fn = _per_event_rate(store, "h_sum", "h_count")
    assert fn() is None  # series absent
    store.record(0.0, {"h_sum": ("counter", 1.0), "h_count": ("counter", 2.0)})
    assert fn() is None  # first observation: no previous tick
    store.record(1.0, {"h_sum": ("counter", 4.0), "h_count": ("counter", 4.0)})
    assert fn() == pytest.approx(1.5)  # (4-1)/(4-2)
    store.record(2.0, {"h_sum": ("counter", 4.0), "h_count": ("counter", 4.0)})
    assert fn() is None  # no new observations this tick


def test_slope_and_level():
    store, _, _ = _store()
    slope, level = _slope(store, "g"), _level(store, "g")
    assert slope() is None and level() is None
    store.record(0.0, {"g": ("gauge", 100.0)})
    assert slope() is None and level() == 100.0
    store.record(1.0, {"g": ("gauge", 90.0)})
    assert slope() == pytest.approx(-10.0) and level() == 90.0


def test_default_rules_cover_the_production_signals():
    store, _, _ = _store()
    rules = build_default_rules(store)
    assert [r.signal for r in rules] == [
        "decode_stall", "ttft", "tpot", "kv_free_slope", "goodput",
        "predict_error",
    ]
    # every rule's value_fn is callable against an empty store (returns
    # None, which neither fires nor learns)
    assert all(r.value_fn() is None for r in rules)


def test_kv_free_slope_fires_on_sustained_drain():
    """End-to-end over the real store + default rules: steady KV
    free-page churn teaches the baseline, then a persistent fast drain
    fires kv_free_slope (the leak early-warning)."""
    store, _, _ = _store()
    rules = {r.signal: r for r in build_default_rules(store)}
    rule = rules["kv_free_slope"]
    mon = AnomalyMonitor(
        [rule], registry=MetricsRegistry(), recorder=FlightRecorder(),
        clock=lambda: 0.0,
    )
    free = 10_000.0
    t = 0.0
    for i in range(40):  # slope -1 page/tick: normal churn
        free -= 1.0
        store.record(t, {"dllama_kv_pages_free": ("gauge", free)})
        t += 1.0
        assert mon.evaluate(now=t) == []
    fired = []
    for i in range(5):  # drain 400 pages/tick
        free -= 400.0
        store.record(t, {"dllama_kv_pages_free": ("gauge", free)})
        t += 1.0
        fired += mon.evaluate(now=t)
    assert fired == ["kv_free_slope"]
