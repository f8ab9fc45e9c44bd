"""Median time of one decode block program on the device, from the engine's
completion stamps (`device_done`'s `device_ms`: a program's end less the later
of the end before it and its enqueue): no profiler, and nothing of the
program queued in front, which `decode_block_ms` (collect to collect) holds."""
from benchmark.harness import devicedone, rundir

LAYER, UNIT, BETTER, SOURCE, MOVES = "engine step", "ms", "lower", "program_span", "tpot_p95_ms"


def read(run_dir):
    return rundir.median(devicedone.device_ms(run_dir, "decode_lanes"))
