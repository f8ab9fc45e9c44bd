"""The part of `host_exposed_pct` with the scheduler thread in the engine's
`dispatch_prep` (window choice, prefetch, seed vector, the small host-to-device
conversions before a program call): the untraced twin of
`idle_in_dispatch_prep_pct`."""
from benchmark.harness import drained

LAYER, UNIT, BETTER, SOURCE, MOVES = "engine step", "%", "lower", "program_span", "tpot_p95_ms"


def read(run_dir):
    return drained.exposed_share_in(run_dir, drained.PREP)
