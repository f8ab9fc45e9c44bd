"""Share of the traced slice's device busy seconds in operations whose `op_name`
is under `layers` but under none of `attn`, `kv_write`, `ffn`, `moe`, `norm`: the
layer scan's own slicing and copying of the stacked weights and caches."""
from benchmark.harness import hostspans, rundir

LAYER, UNIT, BETTER, SOURCE, MOVES = "kernels", "%", "lower", "device_trace", "tpot_p95_ms"


def read(run_dir):
    _, scopes = hostspans.tables(run_dir)
    d = rundir.digest(run_dir)
    if not scopes or not d or not d["busy_s"]:
        return None
    return 100.0 * scopes.get(hostspans.LAYERS, 0.0) / d["busy_s"]
