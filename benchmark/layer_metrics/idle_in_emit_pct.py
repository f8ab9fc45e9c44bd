"""Share of the traced slice in which the device was idle and the scheduler
thread was in `emit`: the token loop after a decode block or a verify dispatch
(detokenise, stop checks, the streams' queues, a finished request's publish)."""
from benchmark.harness import hostspans

LAYER, UNIT, BETTER, SOURCE, MOVES = "scheduler", "%", "lower", "device_trace", "tpot_p95_ms"


def read(run_dir):
    return hostspans.idle_share_in(run_dir, "scheduler.emit")
