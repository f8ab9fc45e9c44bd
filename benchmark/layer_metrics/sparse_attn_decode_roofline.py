"""The restricted decode attention's share of its roofline in the traced
slice: the latent rows the traced decode steps' queries attended to
(`rows_selected` of their `step_dispatch` events: at most `index_topk` a
query) times the layers, as bytes over the chips' HBM peak and as the
absorbed form's FLOPs over their bf16 peak (`costs/<family>.py`:
`sparse_decode_cost`), the larger of the two, divided by the device time
under the decode attention scope (`attn/latent_decode`)."""
from benchmark.harness import costs, rundir, scopes

LAYER, UNIT, BETTER, SOURCE, MOVES = "kernels", "%", "higher", "device_trace", "tpot_p95_ms"


def read(run_dir):
    m = rundir.module_seconds(run_dir, "lane_block")
    steps = [e for e in rundir.events(run_dir, "step_dispatch", "decode_lanes", span="trace")
             if "rows_selected" in e]
    busy = scopes.seconds_under(run_dir, "attn/latent_decode")
    if not m or not steps or not busy:
        return None
    w, cfg = rundir.window(run_dir), rundir.config(run_dir)
    family = costs.family_costs(cfg)
    if family is None or not hasattr(family, "sparse_decode_cost"):
        return None
    nbytes, flops = family.sparse_decode_cost(
        cfg, cfg["num_hidden_layers"] * sum(e["rows_selected"] for e in steps))
    peaks = costs.peaks(w["device_kind"])
    floor = max(nbytes / peaks["hbm_bytes_per_s"], flops / peaks["bf16_flops_per_s"])
    # device seconds of the dispatches counted: the slice's mean per call
    return 100.0 * floor / w["chips"] / (busy / m[1] * len(steps))
