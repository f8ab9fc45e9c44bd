"""Share of the window in which the device stood drained and waited for the
host: 100 x the `device_drained` seconds outside `sched_wait` over the window's
seconds used (the window less the profiler's session), on the program's own
clock, from the streamed `--timeline-out`: the untraced twin of `device_idle_pct`."""
from benchmark.harness import drained

LAYER, UNIT, BETTER, SOURCE, MOVES = "device", "%", "lower", "program_span", "tpot_p95_ms"


def read(run_dir):
    return drained.exposed_share_in(run_dir)
