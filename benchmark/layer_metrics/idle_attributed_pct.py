"""Share of the traced slice's idle seconds (gaps between program executions)
that lie inside a leaf span of the scheduler thread: not in a bare `sched_tick`,
not `unattributed`. What is left over is idle time nobody can name."""
from benchmark.harness import hostspans

LAYER, UNIT, BETTER, SOURCE, MOVES = "device", "%", "higher", "device_trace", "tpot_p95_ms"


def read(run_dir):
    table, _ = hostspans.tables(run_dir)
    if not table or not table["idle_s"]:
        return None
    bare = sum(table["by_span"].get(k, 0.0) for k in (hostspans.TICK, hostspans.UNATTRIBUTED))
    return 100.0 * (1.0 - bare / table["idle_s"])
