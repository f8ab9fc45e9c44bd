"""The restricted prefill attention's share of the bf16 peak in the traced
slice: the rows the traced chunks' queries attended to (`rows_selected` of
their `step_dispatch` events: the sum over a live lane's queries of
min(position + 1, `index_topk`)) times the heads and the multiply-adds a
pair of the cheaper form for that chunk (`costs/<family>.py`:
`sparse_prefill_flops`), times the layers, over the chips' peak, divided by
the device time under the prefill attention scope (`attn/latent_prefill`).
What the kernel computes for pairs it then masks counts nothing."""
from benchmark.harness import costs, rundir, scopes

LAYER, UNIT, BETTER, SOURCE, MOVES = "kernels", "%", "higher", "device_trace", "ttft_mean_ms"


def read(run_dir):
    m = rundir.module_seconds(run_dir, "lane_prefill")
    chunks = [e for e in rundir.events(run_dir, "step_dispatch", "prefill_lane_chunk",
                                       span="trace") if "rows_selected" in e]
    busy = scopes.seconds_under(run_dir, "attn/latent_prefill")
    if not m or not chunks or not busy:
        return None
    w, cfg = rundir.window(run_dir), rundir.config(run_dir)
    family = costs.family_costs(cfg)
    if family is None or not hasattr(family, "sparse_prefill_flops"):
        return None
    need = cfg["num_hidden_layers"] * sum(
        family.sparse_prefill_flops(cfg, e["rows_selected"], e["pos"] + e["n_tokens"])
        for e in chunks)
    peak = costs.peaks(w["device_kind"])["bf16_flops_per_s"] * w["chips"]
    return 100.0 * need / peak / (busy / m[1] * len(chunks))
