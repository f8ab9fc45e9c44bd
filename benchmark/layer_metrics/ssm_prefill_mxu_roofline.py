"""The prefill Mamba-2 mixer's share of the bf16 MXU peak in the traced slice:
2 x both projections' weights x the rows that were asked for (the admitted
lane's `n_tokens` of the traced chunks' `step_dispatch` events, not `lanes x
bucket`: the floor is the same whatever computes it) and the recurrence's
block form over those rows, times the Mamba-2 layers (`costs/<family>.py`:
`ssm_prefill_flops`), over the chips' peak, divided by the device time under
the prefill mixer's scope (`ssm/prefill`). The recurrence runs in float32 at
the highest precision, several passes of the MXU an operation counted once:
the share says what that costs."""
from benchmark.harness import costs, rundir, scopes

LAYER, UNIT, BETTER, SOURCE, MOVES = "kernels", "%", "higher", "device_trace", "ttft_mean_ms"


def read(run_dir):
    m = rundir.module_seconds(run_dir, "lane_prefill")
    chunks = [e for e in rundir.events(run_dir, "step_dispatch", "prefill_lane_chunk",
                                       span="trace") if "state_lanes" in e]
    busy = scopes.seconds_under(run_dir, "ssm/prefill")
    if not m or not chunks or not busy:
        return None
    w, cfg = rundir.window(run_dir), rundir.config(run_dir)
    family = costs.family_costs(cfg)
    if family is None or not hasattr(family, "ssm_prefill_flops"):
        return None
    n_mamba = family.layer_counts(cfg)[0]
    need = n_mamba * sum(family.ssm_prefill_flops(cfg, e["n_tokens"]) for e in chunks)
    peak = costs.peaks(w["device_kind"])["bf16_flops_per_s"] * w["chips"]
    return 100.0 * need / peak / (busy / m[1] * len(chunks))
