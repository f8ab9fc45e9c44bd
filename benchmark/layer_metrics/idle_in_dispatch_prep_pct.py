"""Share of the traced slice in which the device was idle and the scheduler
thread was in the engine's `dispatch_prep`: window choice, prefetch, seed vector
and the small host-to-device conversions before a compiled program is called."""
from benchmark.harness import hostspans

LAYER, UNIT, BETTER, SOURCE, MOVES = "engine step", "%", "lower", "device_trace", "tpot_p95_ms"


def read(run_dir):
    return hostspans.idle_share_in(run_dir, "engine.dispatch_prep")
