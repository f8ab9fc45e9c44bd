"""Generators give the same schedule for the same schedule seed, another
order of the same sizes for another, and report how late they sent."""

import time

import numpy as np

import run as bench
from benchmark.generators import closed, open_poisson
from benchmark.harness import traffic as tr


def fake_send(log):
    def send(i, n_prompt, n_out, due):
        now = time.monotonic()
        log.append((i, n_prompt, n_out, due))
        return {"id": i, "due": due, "sent": now, "error": None}
    return send


def drive_open(seed):
    traffic = bench.load_json("traffic", "chat-short.json")
    log = []
    t = time.monotonic()
    out = open_poisson.drive(traffic, {"rate": 40.0}, np.random.default_rng(seed),
                             0.5, fake_send(log), lanes=8, clock={})
    return out, log, time.monotonic() - t


def test_open_loop_repeats_for_a_seed_and_reports_lateness():
    a, log_a, took = drive_open(7)
    b, log_b, _ = drive_open(7)
    c, log_c, _ = drive_open(8)
    rel = lambda out, log: [(i, p, o, round(due - out["t0"], 9)) for i, p, o, due in log]
    assert rel(a, log_a) == rel(b, log_b)
    assert rel(a, log_a) != rel(c, log_c)
    assert sorted(p for _, p, _, _ in log_a) == sorted(p for _, p, _, _ in log_c)
    assert len(log_a) == 20 and 0.4 < took < 1.5
    late = [r["sent"] - r["due"] for r in a["records"]]
    assert all(0 <= x < 0.2 for x in late)


def test_arrivals_fill_the_window_at_the_rate():
    due = tr.poisson_arrivals(2.0, 40.0, np.random.default_rng(1))
    assert len(due) == 80 and 0 <= due[0] and due[-1] < 40.0
    gaps = np.diff(due)
    assert abs(gaps.mean() - 0.5) < 0.02 and gaps.std() > 0.3  # not evenly spaced


def test_closed_loop_keeps_its_clients_busy_and_stops_at_the_window():
    traffic = bench.load_json("traffic", "decode-sat.json")
    log = []

    def send(i, n_prompt, n_out, due):
        time.sleep(0.02)
        return fake_send(log)(i, n_prompt, n_out, due)

    clock = {}
    traffic["ramp_s"] = 0.2
    out = closed.drive(traffic, {}, np.random.default_rng(3), 0.3, send, lanes=2, clock=clock)
    assert len(out["records"]) >= 4 * 10  # 4 clients, about 25 rounds with the ramp
    assert all(r["due"] < out["t1"] for r in out["records"])
    assert clock["t0"] == out["t0"] and min(r["due"] for r in out["records"]) < out["t0"]
    lo, hi = traffic["output_tokens"]["min"], traffic["output_tokens"]["max"]
    assert all(lo <= o <= hi for _, _, o, _ in log)


def test_quantiles_follow_the_distribution():
    q = tr.quantiles({"dist": "lognormal", "median": 250, "sigma": 0.7,
                      "min": 100, "max": 900}, 101)
    assert q[50] == 250 and q.min() == 100 and q.max() == 900
    u = tr.quantiles({"dist": "uniform", "min": 16, "max": 64}, 49)
    assert u[0] == 16 and u[-1] == 64 and u[24] == 40
