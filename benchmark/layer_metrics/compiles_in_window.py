"""Programs built inside the window; anything but 0 makes the run incorrect."""
from benchmark.harness import rundir

LAYER, UNIT, BETTER, SOURCE, MOVES = "engine step", "count", "lower", "program_counter", "tpot_p95_ms"


def read(run_dir):
    if not rundir.window(run_dir):
        return None
    return float(sum(len(rundir.events(run_dir, k))
                     for k in ("compile_start", "compile")))
