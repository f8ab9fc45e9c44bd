"""Share of the traced slice's idle seconds (gaps between program executions)
that lie inside an interval the program itself recorded as `device_drained`,
the two laid on one clock through `sched_tick`'s `mono_ns`. At most 100 by
construction; near 100 says the program's accounting and the device agree."""
from benchmark.harness import drained

LAYER, UNIT, BETTER, SOURCE, MOVES = "device", "%", "higher", "device_trace", "tpot_p95_ms"


def read(run_dir):
    t = drained.table(run_dir)
    traced = t and t.get("traced")
    if not traced or not traced["idle_s"]:
        return None
    return 100.0 * traced["covered_s"] / traced["idle_s"]
