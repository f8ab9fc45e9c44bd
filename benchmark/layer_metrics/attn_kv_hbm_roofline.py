"""Decode attention's share of the HBM roofline in the traced slice, over
both kinds of cache: the key and value rows the traced decode steps had to
read (`rows_full` and `rows_window` of their `step_dispatch` events: every
live lane's rows in context at every step, a window layer's capped at the
window), times the layers of each kind and a row's bytes, over the chips'
peak bandwidth, divided by the device time under the decode attention
scopes (`attn/full_decode`, `attn/window_decode`)."""
from benchmark.costs.dense_gqa import kv_row_bytes
from benchmark.harness import costs, rundir, scopes

LAYER, UNIT, BETTER, SOURCE, MOVES = "kernels", "%", "higher", "device_trace", "tpot_p95_ms"


def read(run_dir):
    m = rundir.module_seconds(run_dir, "lane_block")
    steps = [e for e in rundir.events(run_dir, "step_dispatch", "decode_lanes", span="trace")
             if "rows_full" in e]
    busy = scopes.seconds_under(run_dir, "attn/full_decode", "attn/window_decode")
    if not m or not steps or not busy:
        return None
    w, cfg = rundir.window(run_dir), rundir.config(run_dir)
    window = sum(t == "sliding_attention" for t in cfg["layer_types"])
    full = len(cfg["layer_types"]) - window
    need = kv_row_bytes(cfg) * sum(
        full * e["rows_full"] + window * e["rows_window"] for e in steps)
    peak = costs.peaks(w["device_kind"])["hbm_bytes_per_s"] * w["chips"]
    # device seconds of the dispatches counted: the slice's mean per call
    return 100.0 * need / peak / (busy / m[1] * len(steps))
