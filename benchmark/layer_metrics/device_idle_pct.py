"""Share of the traced slice in which no operation ran on the device,
averaged over the chips used."""
from benchmark.harness import rundir

LAYER, UNIT, BETTER, SOURCE, MOVES = "device", "%", "lower", "device_trace", "tpot_p95_ms"


def read(run_dir):
    d = rundir.digest(run_dir)
    return d and 100.0 * (1.0 - d["busy_s"] / d["window_s"])
