"""The decode expert layer's share of the HBM roofline in the traced slice:
the held experts that the traced decode steps' tokens really touched (the
program's count, `held_touched` of its `moe_route` events, summed over the
expert layers), the shared expert and the f32 router of every expert layer
and step, at the Q40 file's bytes, over the chips' peak bandwidth, divided
by the device time under the decode expert scope (`moe/decode`)."""
from benchmark.harness import costs, rundir, scopes

LAYER, UNIT, BETTER, SOURCE, MOVES = "kernels", "%", "higher", "device_trace", "tpot_p95_ms"


def read(run_dir):
    m = rundir.module_seconds(run_dir, "lane_block")
    blocks = rundir.events(run_dir, "moe_route", "decode_lanes", span="trace")
    busy = scopes.seconds_under(run_dir, "moe/decode")
    if not m or not blocks or not busy:
        return None
    w, cfg = rundir.window(run_dir), rundir.config(run_dir)
    family = costs.family_costs(cfg)
    if family is None or not hasattr(family, "shared_weights"):
        return None
    sparse = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    expert = family.swiglu_weights(cfg, cfg["moe_intermediate_size"])
    each_step = sparse * (
        family.shared_weights(cfg) * costs.Q40_BYTES_PER_WEIGHT + family.router_bytes(cfg))
    need = sum(e["held_touched"] * expert * costs.Q40_BYTES_PER_WEIGHT
               + e["n_steps"] * each_step for e in blocks)
    peak = costs.peaks(w["device_kind"])["hbm_bytes_per_s"] * w["chips"]
    return 100.0 * need / peak / (busy / m[1] * len(blocks))
