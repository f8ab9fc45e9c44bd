"""Median time a request waited for a lane (`--trace-out` queue wait)."""
from benchmark.harness import rundir

LAYER, UNIT, BETTER, SOURCE, MOVES = "scheduler", "ms", "lower", "program_span", "ttft_mean_ms"


def read(run_dir):
    return rundir.median(
        r["queue_wait_s"] * 1e3 for r in rundir.server_records(run_dir)
        if r["queue_wait_s"] is not None
    )
