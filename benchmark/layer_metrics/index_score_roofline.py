"""The index's share of its roofline in the traced slice: every traced
dispatch's pairs of a live query and a cached row it sees (`rows_latent` of
the `step_dispatch` events of chunks and decode blocks: what the index
scores) times the layers, as index heads x width x 2 FLOPs a pair over the
chips' bf16 peak, or for a decode step, which reads a key a pair, as that
key's bytes over their HBM peak, the larger (`costs/<family>.py`:
`index_score_cost`), divided by the device time under the index's two
scopes, scores and selection (`attn/index_score`, `attn/index_select`), in
both programs."""
from benchmark.harness import costs, rundir, scopes

LAYER, UNIT, BETTER, SOURCE, MOVES = "kernels", "%", "higher", "device_trace", "ttft_mean_ms"


def read(run_dir):
    found = {step: [e for e in rundir.events(run_dir, "step_dispatch", step, span="trace")
                    if "rows_selected" in e]
             for step in ("prefill_lane_chunk", "decode_lanes")}
    busy = scopes.seconds_under(run_dir, "attn/index_score", "attn/index_select")
    if not any(found.values()) or not busy:
        return None
    w, cfg = rundir.window(run_dir), rundir.config(run_dir)
    family = costs.family_costs(cfg)
    if family is None or not hasattr(family, "index_score_cost"):
        return None
    peaks, layers = costs.peaks(w["device_kind"]), cfg["num_hidden_layers"]
    floor = 0.0
    for step, events in found.items():
        for e in events:
            nbytes, flops = family.index_score_cost(cfg, layers * e["rows_latent"])
            seconds = flops / peaks["bf16_flops_per_s"]
            if step == "decode_lanes":
                seconds = max(seconds, nbytes / peaks["hbm_bytes_per_s"])
            floor += seconds
    return 100.0 * floor / w["chips"] / busy
