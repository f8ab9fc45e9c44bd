"""Mean time of one prefill chunk program on the device, from the engine's
completion stamps (`device_done`'s `device_ms`) over the window: the untraced
twin of `prefill_chunk_ms`, which reads the profiler's five seconds."""
from benchmark.harness import devicedone

LAYER, UNIT, BETTER, SOURCE, MOVES = "engine step", "ms", "lower", "program_span", "ttft_mean_ms"


def read(run_dir):
    ms = devicedone.device_ms(run_dir, "prefill_lane_chunk")
    return sum(ms) / len(ms) if ms else None
