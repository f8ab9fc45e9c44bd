"""Decode programs' share of the HBM roofline in the traced slice: the
bytes their steps had to read (`costs.decode_step_bytes`: Q40 weights, only
the experts some live lane routed to, keys and values really in context)
over the chips' peak bandwidth, divided by the programs' device time."""
from benchmark.harness import costs, rundir

LAYER, UNIT, BETTER, SOURCE, MOVES = "kernels", "%", "higher", "device_trace", "tpot_p95_ms"


def read(run_dir):
    m = rundir.module_seconds(run_dir, "lane_block")
    steps = rundir.events(run_dir, "step_dispatch", "decode_lanes", span="trace")
    if not m or not steps:
        return None
    w, cfg = rundir.window(run_dir), rundir.config(run_dir)
    if costs.family_costs(cfg) is None:  # no floor for this family: no share of one
        return None
    need = sum(
        e["n_steps"] * costs.decode_step_bytes(cfg, e["n_live"], w["mean_context"])
        for e in steps)
    peak = costs.peaks(w["device_kind"])["hbm_bytes_per_s"] * w["chips"]
    # device seconds of the dispatches counted: the slice's mean per call
    return 100.0 * need / peak / (m[0] / m[1] * len(steps))
