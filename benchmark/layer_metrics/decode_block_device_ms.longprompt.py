"""`decode_block_device_ms` in a cell that judges no TPOT: there it moves the
time to first token, a decode block running between every two chunks of a
long prompt (as `decode_block_ms.longprompt`)."""
from benchmark.layer_metrics.decode_block_device_ms import BETTER, LAYER, SOURCE, UNIT, read  # noqa: F401

MOVES = "ttft_mean_ms"
