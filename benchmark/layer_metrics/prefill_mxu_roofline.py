"""Prefill programs' share of the bf16 MXU peak in the traced slice: 2 x
weights x rows each chunk program computes (every lane's rows, padding
included, `costs.prefill_flops`) over the chips' peak, divided by the
programs' device time."""
from benchmark.harness import costs, rundir

LAYER, UNIT, BETTER, SOURCE, MOVES = "kernels", "%", "higher", "device_trace", "ttft_mean_ms"


def read(run_dir):
    m = rundir.module_seconds(run_dir, "lane_prefill")
    chunks = rundir.events(run_dir, "step_dispatch", "prefill_lane_chunk", span="trace")
    if not m or not chunks:
        return None
    w, cfg = rundir.window(run_dir), rundir.config(run_dir)
    if costs.family_costs(cfg) is None:  # no floor for this family: no share of one
        return None
    need = sum(costs.prefill_flops(cfg, w["lanes"] * e["bucket"]) for e in chunks)
    peak = costs.peaks(w["device_kind"])["bf16_flops_per_s"] * w["chips"]
    return 100.0 * need / peak / (m[0] / m[1] * len(chunks))
