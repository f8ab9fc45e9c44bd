"""Host milliseconds a scheduler tick spends not waiting for the device: median
over the window's `sched_tick` spans of the tick's wall time less the `*.device`
spans (read-back waits) inside it, from the streamed `--timeline-out`."""
import bisect

from benchmark.harness import hostspans, rundir

LAYER, UNIT, BETTER, SOURCE, MOVES = "scheduler", "ms", "lower", "program_span", "tpot_p95_ms"


def read(run_dir):
    meta, spans = hostspans.timeline(run_dir)
    w = rundir.window(run_dir)
    if not spans or w.get("t0") is None:
        return None
    lo, hi = ((t - meta["epoch_monotonic"]) * 1e6 for t in (w["t0"], w["t1"]))
    waits = sorted((s["ts"], s["dur"]) for s in spans if s["name"].endswith(".device"))
    starts = [ts for ts, _ in waits]
    host = []
    for tick in spans:
        if tick["name"] != "sched_tick" or not lo <= tick["ts"] < hi:
            continue
        end = tick["ts"] + tick["dur"]
        i = bisect.bisect_left(starts, tick["ts"])
        waited = 0.0
        while i < len(waits) and waits[i][0] + waits[i][1] <= end:
            waited += waits[i][1]
            i += 1
        host.append((tick["dur"] - waited) / 1e3)
    return rundir.median(host)
