"""`host_exposed_pct` in a cell that judges no TPOT: a second the device stands
drained between a chunk and the next program is a second later for the first
token of every caller that waits."""
from benchmark.layer_metrics.host_exposed_pct import BETTER, LAYER, SOURCE, UNIT, read  # noqa: F401

MOVES = "ttft_mean_ms"
