"""95th percentile of the client's time to first token over the window's
requests. A window holds about twenty, so this is nearly their maximum and
swings with which request meets which: a record of the queue's tail, not a
judged metric (PERF.md section 2)."""
import numpy as np

from benchmark.harness import rundir

LAYER, UNIT, BETTER, SOURCE, MOVES = "scheduler", "ms", "lower", "host_clock", "ttft_mean_ms"


def read(run_dir):
    ttft = [(r["first"] - r["due"]) * 1e3 for r in rundir.requests(run_dir)
            if r["first"] and not r["failed"]]
    return float(np.percentile(ttft, 95)) if ttft else None
