#!/usr/bin/env python3
"""The correctness ladder: served tokens against the plain reference at prompt
lengths that straddle every chunk and window boundary, one request at a time
and then all at once, on several seeds. Run on the chip before anything is
timed; its table is the reason for `gap_tol` in the configuration files.

    python3 benchmark/ladder.py --config mistral-7b-v0.3 --seeds 11,12,13 \\
        --lengths 100,500,520,1000,1030,2040,2060,3900 [--rehearse]

Prints one line per (seed, length, alone|concurrent) with the largest and
rms gap in units of the reference's logit std. With `--power`, the first
seed's served ids are also checked against each deliberately wrong reference
that the family's module names in `FAULTS` (for `dense_gqa`: rotary base 1e4;
the keys of positions 512-1023 zeroed): what the rule reads when the model
code is wrong.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import random
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
os.environ.setdefault("DLLAMA_RECORDER_CAPACITY", "262144")
N_OUT = 32


def log(msg: str) -> None:
    print(msg, flush=True)


POWER_SAMPLES = 4  # requests checked against each wrong reference


def power(cfg: dict, model: str, samples: list[dict], compare) -> None:
    """One line per request and fault of the family's `FAULTS`: a dict is
    laid over the configuration, anything else is entered around the check."""
    for name, fault in getattr(compare.reference_for(cfg), "FAULTS", {}).items():
        over = isinstance(fault, dict)
        can_show = [s for s in samples
                    if len(s["prompt_ids"]) > getattr(fault, "min_prompt", 0)]
        with contextlib.nullcontext() if over else fault:
            reports = compare.check({**cfg, **fault} if over else cfg, model,
                                    can_show[:POWER_SAMPLES])
        for rep in reports:
            log(f"power {name} {rep['id']}: max_gap {rep['max_gap_std']:.3f} "
                f"top1 {rep['top1_share']:.3f}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="11,12,13")
    ap.add_argument("--lengths", default="100,500,520,1000,1030,2040,2060,3900")
    ap.add_argument("--power", action="store_true")
    ap.add_argument("--profile", action="store_true",
                    help="trace the first seed's concurrent phase and print its digest")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--n-out", type=int, default=N_OUT,
                    help="output tokens per request (long ones measure the gap's tail)")
    ap.add_argument("--skip-alone", action="store_true")
    ap.add_argument("--lanes", type=int, default=None,
                    help="override the configuration's lanes (to find what fits)")
    args = ap.parse_args()

    import jax

    import run as bench
    from benchmark.harness import client, compare, weights, xplane
    from benchmark.harness.server import Served
    from dllama_tpu.parallel.mesh import enable_compilation_cache

    cfg = bench.load_config(args.config, args.rehearse)
    if args.lanes:
        cfg["serving"]["lanes"] = args.lanes
    devs = bench.devices_or_exit(cfg["serving"]["tp"], args.rehearse)
    enable_compilation_cache()
    lengths = [int(x) for x in args.lengths.split(",")]
    rows = []
    for n_seed, seed in enumerate(int(s) for s in args.seeds.split(",")):
        work = os.path.join(bench.WORK, f"ladder-{cfg['name']}-{seed}")
        run_dir = os.path.join(bench.WORK, f"ladder-run-{cfg['name']}")
        shutil.rmtree(run_dir, ignore_errors=True)
        os.makedirs(run_dir)
        t = time.monotonic()
        model, tok = weights.write_pair(work, cfg, seed)
        log(f"[seed {seed}] wrote {os.path.getsize(model) / 1e9:.2f} GB in "
            f"{time.monotonic() - t:.1f} s")
        t = time.monotonic()
        served = Served(cfg, model, tok, run_dir)
        log(f"[seed {seed}] loaded in {time.monotonic() - t:.1f} s: lanes "
            f"{served.engine.batch_size}, {served.engine.weight_format}, peak "
            f"{bench.peak_bytes(devs)}")
        t = time.monotonic()
        served.build_programs(max(lengths), max(lengths) + args.n_out)
        log(f"[seed {seed}] programs built in {time.monotonic() - t:.1f} s")
        overhead = len(served.prompt_ids(""))
        texts, records = {}, {}

        def send(mode: str, n: int) -> None:
            rid = f"{mode}-{seed}-{n}"
            texts[rid] = client.prompt_text(n - overhead, random.Random(rid))
            records[rid] = client.stream_chat(
                served.port, rid, texts[rid], args.n_out, time.monotonic())

        n_compiled = len(served.engine.recorder.events("compile_end"))
        t = time.monotonic()
        for n in [] if args.skip_alone else lengths:
            send("alone", n)
        log(f"[seed {seed}] {len(lengths)} requests alone in {time.monotonic() - t:.1f} s")
        tracing = args.profile and n_seed == 0
        if tracing:
            jax.profiler.start_trace(os.path.join(run_dir, "profile"))
            t_trace = time.monotonic()
        t = time.monotonic()
        threads = [threading.Thread(target=send, args=("concurrent", n)) for n in lengths]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        log(f"[seed {seed}] the same concurrently in {time.monotonic() - t:.1f} s")
        if tracing:
            jax.profiler.stop_trace()
            dig = xplane.digest(xplane.load(os.path.join(run_dir, "profile")),
                                time.monotonic() - t_trace)
            log("[trace digest] " + json.dumps(dig))
        late = served.engine.recorder.events("compile_end")[n_compiled:]
        log(f"[seed {seed}] programs built at dispatch: {[e['key'] for e in late]}")
        log(f"[seed {seed}] peak_bytes_in_use {bench.peak_bytes(devs)}")
        served.stop()
        samples = []
        for rid, rec in records.items():
            if rec["error"] or rec["finish"] != "length":
                log(f"[seed {seed}] {rid}: error {rec['error']} finish {rec['finish']} "
                    f"({len(rec['ids'])} ids)")
            samples.append({"id": rid, "prompt_ids": served.prompt_ids(texts[rid]),
                            "served": rec["ids"]})
        served.free()
        t = time.monotonic()
        reports = compare.check(cfg, model, samples)
        log(f"[seed {seed}] reference over {len(samples)} requests in "
            f"{time.monotonic() - t:.1f} s")
        for rep in reports:
            mode, _, n = rep["id"].split("-")
            rows.append({"seed": seed, "length": int(n), "mode": mode, **rep})
            log(f"ladder {cfg['name']} seed {seed} length {n:>5} {mode:<10} "
                f"prompt {rep['n_prompt']:>5} served {rep['n_served']:>3} "
                f"max_gap {rep['max_gap_std']:.4f} rms {rep['rms_gap_std']:.4f} "
                f"top1 {rep['top1_share']:.3f} worst_at {rep['worst_at']}")
        if args.power and n_seed == 0:
            power(cfg, model, samples, compare)
        shutil.rmtree(work, ignore_errors=True)
    worst = max(r["max_gap_std"] for r in rows)
    gaps = sorted(g for r in rows for g in r["gaps"])
    tail = {q: round(gaps[min(len(gaps) - 1, int(q * len(gaps)))], 4)
            for q in (0.5, 0.9, 0.99, 0.999, 0.9999)}
    log(f"ladder {cfg['name']}: {len(rows)} requests, {len(gaps)} tokens, largest gap "
        f"{worst:.4f} std; gap quantiles {tail}; top-1 share "
        f"{sum(g == 0 for g in gaps) / len(gaps):.3f}")
    os.makedirs(os.path.join(os.path.dirname(HERE), "chiprun_out"), exist_ok=True)
    with open(os.path.join(os.path.dirname(HERE), "chiprun_out",
                           f"ladder-{cfg['name']}-out{args.n_out}.json"), "w") as f:
        json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
