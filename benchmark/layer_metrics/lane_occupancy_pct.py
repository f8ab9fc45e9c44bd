"""Live lanes per decode dispatch over lanes, mean over the window."""
from benchmark.harness import rundir

LAYER, UNIT, BETTER, SOURCE, MOVES = "scheduler", "%", "higher", "program_counter", "out_tokens_per_s"


def read(run_dir):
    live = [e["n_live"] for e in rundir.events(run_dir, "step_dispatch", "decode_lanes")]
    if not live:
        return None
    return 100.0 * sum(live) / len(live) / rundir.window(run_dir)["lanes"]
