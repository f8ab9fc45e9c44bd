"""95th percentile over the window's requests of (last - first token time) /
(tokens - 1), where it is a record and not judged: with three callers and
long prompts it depends on which decode block waits behind which chunk
(spread 4% over six runs, PERF.md section 2)."""
import numpy as np

from benchmark.harness import rundir

LAYER, UNIT, BETTER, SOURCE, MOVES = "scheduler", "ms", "lower", "host_clock", "ttft_mean_ms"


def read(run_dir):
    tpot = [(r["last"] - r["first"]) * 1e3 / (len(r["ids"]) - 1)
            for r in rundir.requests(run_dir) if not r["failed"] and len(r["ids"]) > 1]
    return float(np.percentile(tpot, 95)) if tpot else None
