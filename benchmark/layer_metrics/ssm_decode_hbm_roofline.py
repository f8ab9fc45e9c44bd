"""The decode Mamba-2 mixer's share of the HBM roofline in the traced slice:
what the Mamba-2 layers of the traced decode steps had to move
(`costs/<family>.py`: `ssm_decode_bytes`: both projections at the Q40 file's
bytes, the f32 leaves, and the float32 recurrent state and the convolution's
rows, in and out, of the lanes whose states the program advanced:
`state_lanes` of their `step_dispatch` events), times the Mamba-2 layers and
the steps, over the chips' peak bandwidth, divided by the device time under
the decode mixer's scope (`ssm/decode`)."""
from benchmark.harness import costs, rundir, scopes

LAYER, UNIT, BETTER, SOURCE, MOVES = "kernels", "%", "higher", "device_trace", "tpot_p95_ms"


def read(run_dir):
    m = rundir.module_seconds(run_dir, "lane_block")
    steps = [e for e in rundir.events(run_dir, "step_dispatch", "decode_lanes", span="trace")
             if "state_lanes" in e]
    busy = scopes.seconds_under(run_dir, "ssm/decode")
    if not m or not steps or not busy:
        return None
    w, cfg = rundir.window(run_dir), rundir.config(run_dir)
    family = costs.family_costs(cfg)
    if family is None or not hasattr(family, "ssm_decode_bytes"):
        return None
    n_mamba = family.layer_counts(cfg)[0]
    need = n_mamba * sum(
        e["n_steps"] * family.ssm_decode_bytes(cfg, e["state_lanes"]) for e in steps)
    peak = costs.peaks(w["device_kind"])["hbm_bytes_per_s"] * w["chips"]
    # device seconds of the dispatches counted: the slice's mean per call
    return 100.0 * need / peak / (busy / m[1] * len(steps))
