"""`device_idle_pct` in a cell that judges no TPOT (its TPOT swings with which decode
block waits behind which chunk): there it moves the time to first token, a
decode block running between every two chunks of a long prompt."""
from benchmark.layer_metrics.device_idle_pct import BETTER, LAYER, SOURCE, UNIT, read  # noqa: F401

MOVES = "ttft_mean_ms"
