"""Mean device time of one prefill chunk program in the traced slice. The
recorder's wall time is not used: the dispatch returns at enqueue."""
from benchmark.harness import rundir

LAYER, UNIT, BETTER, SOURCE, MOVES = "engine step", "ms", "lower", "device_trace", "ttft_mean_ms"


def read(run_dir):
    m = rundir.module_seconds(run_dir, "lane_prefill")
    return m and 1e3 * m[0] / m[1]
