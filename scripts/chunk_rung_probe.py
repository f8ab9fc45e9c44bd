"""Time a prefill chunk program by the rung of the ladder it runs, on the chip.

    chiprun -- python3 scripts/chunk_rung_probe.py [--configs a,b] [--rungs 32,128,256,512]

For each of the benchmark's configurations named (`benchmark/configs/`; by
default those that pass no `--nbatches` and so serve the default ladder) the
seeded weights are
written and the configuration's own server is started as the benchmark
starts it (`benchmark/harness/server.Served`: its lanes, context, weight
format and flags), with `--nbatches` set to the smallest rung asked for so
that `engine.prefill_ladder` holds every rung to be timed. No request is
sent. Per rung one lane is prefilled at position 0 with a chunk that fills
the rung (`prefill_lane_chunk`, the scheduler's own call; every other lane
parked, as in an admission), `--reps` times behind two that warm it, each
timed on the host's clock from the call to `block_until_ready` of the cache
the program returns: a chunk's device time and a dispatch of about a
millisecond. Prints a JSON line a rung (median, fastest and slowest ms, the
window the program was built at) and writes chiprun_out/chunk_rung_probe.json.
Off a TPU it refuses to time anything (`--rehearse`: the control flow at
the tiny widths on the CPU, no number kept).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import statistics
import sys
import time

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)


def default_ladder_configs() -> list[str]:
    """The benchmark's configurations that name no `--nbatches` of their own."""
    names = []
    for path in sorted(glob.glob(os.path.join(ROOT, "benchmark", "configs", "*.json"))):
        with open(path) as f:
            if "--nbatches" not in json.load(f)["serving"]["args"]:
                names.append(os.path.basename(path)[: -len(".json")])
    return names


def time_rungs(name: str, rungs: list[int], seed: int, reps: int, rehearse: bool) -> list[dict]:
    import jax

    from benchmark import run as bench
    from benchmark.harness import weights
    from benchmark.harness.server import Served

    cfg = bench.load_config(name, rehearse)
    cfg = {**cfg, "serving": {**cfg["serving"], "args": [
        *cfg["serving"]["args"], "--nbatches", str(min(rungs))]}}
    model_dir = os.path.join(bench.WORK, f"probe-{name}-{seed}")
    run_dir = os.path.join(bench.WORK, f"probe-run-{name}")
    os.makedirs(run_dir, exist_ok=True)
    lines, served = [], None
    try:
        model, tok = weights.write_pair(model_dir, cfg, seed)
        served = Served(cfg, model, tok, run_dir)
        e = served.engine
        assert set(rungs) <= set(e.prefill_buckets), (rungs, e.prefill_buckets)
        served.compile_admission_path()
        for rung in rungs:
            tokens = [1 + i % 200 for i in range(rung)]
            ms = []
            for rep in range(reps + 2):
                jax.block_until_ready(e.cache)
                t = time.perf_counter()
                assert e.prefill_lane_chunk(0, tokens, 0) == rung
                jax.block_until_ready(e.cache)
                if rep >= 2:
                    ms.append(1e3 * (time.perf_counter() - t))
            lines.append({
                "config": name, "lanes": e.batch_size, "rung": rung,
                "window": e._attn_window(rung), "ms_median": statistics.median(ms),
                "ms_min": min(ms), "ms_max": max(ms), "reps": reps,
                "device": jax.devices()[0].device_kind,
            })
            print(json.dumps(lines[-1]), flush=True)
    finally:
        if served is not None:  # the next configuration needs the chip's memory
            served.stop()
            served.free()
        shutil.rmtree(model_dir, ignore_errors=True)
    return lines


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--configs", default=",".join(default_ladder_configs()))
    ap.add_argument("--rungs", default="32,128,256,512")
    ap.add_argument("--seed", type=int, default=4900001)
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("chunk_rung_probe: needs a TPU; no number", file=sys.stderr)
        return 2
    rungs = sorted(int(r) for r in args.rungs.split(","))
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    lines = []
    for name in args.configs.split(","):
        lines += time_rungs(name, rungs, args.seed, args.reps, args.rehearse)
        if not args.rehearse:  # after every configuration: a later one may fail
            with open(os.path.join(out, "chunk_rung_probe.json"), "w") as f:
                json.dump(lines, f, indent=1)
    if args.rehearse:
        print("rehearsal: no number is kept off the chip", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
