"""Median wall time of one decode dispatch (a block of steps over all lanes,
enqueue to read-back), from the flight recorder."""
from benchmark.harness import rundir

LAYER, UNIT, BETTER, SOURCE, MOVES = "engine step", "ms", "lower", "program_span", "tpot_p95_ms"


def read(run_dir):
    return rundir.median(
        e["ms"] for e in rundir.events(run_dir, "step_complete", "decode_lanes"))
