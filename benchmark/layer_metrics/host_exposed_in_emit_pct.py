"""The part of `host_exposed_pct` with the scheduler thread in `emit` or a span
under it (detokenise, stop checks, the streams' queues, a finished request's
publish): the untraced twin of `idle_in_emit_pct`."""
from benchmark.harness import drained

LAYER, UNIT, BETTER, SOURCE, MOVES = "scheduler", "%", "lower", "program_span", "tpot_p95_ms"


def read(run_dir):
    return drained.exposed_share_in(run_dir, drained.EMIT)
