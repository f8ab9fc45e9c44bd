#!/usr/bin/env python3
"""One run of one benchmark cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is data found by name: `workloads/<name>.json`
names a `configs/` file and a `traffic/` file; the configuration names its
`references/<family>.py`, the traffic its `generators/<kind>.py`, and every
per-layer metric is read by `layer_metrics/<metric>.py`. See README.md.

The last line of standard output is the result, one JSON object. Without a
TPU, or with fewer chips than the cell asks for, the run exits non-zero and
prints none. `--rehearse` runs the same control flow at a tiny size on the
CPU, prints counts only and exits 3.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
# the flight recorder's ring must hold a whole window's events
os.environ.setdefault("DLLAMA_RECORDER_CAPACITY", "262144")

from benchmark.harness import client  # noqa: E402  (no JAX: after sys.path)

WORK = os.path.join(HERE, "work")
DRAIN_S = 120.0  # a request still unanswered this long after the window fails
SAMPLE = 4  # finished requests compared with the reference in every run
TRACE_SLICE_S = 5.0
TINY = {  # --rehearse: the program's `tiny` preset widths, on the CPU
    "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 2,
    "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 16,
    "vocab_size": 512, "max_position_embeddings": 4096,
}
TINY_MOE = {"num_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 128}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def rehearsal_of(cfg: dict) -> dict:
    """A configuration cut to the tiny widths, where no tolerance has been
    measured and nothing is judged. Sizes that must shrink with them and that
    TINY does not know are the configuration's own `rehearse`: keys laid over
    the file's, those of its `file` group one by one."""
    over = cfg.get("rehearse", {})
    return {
        **cfg, **TINY, **(TINY_MOE if cfg.get("num_experts") else {}),
        "assumed": {**cfg.get("assumed", {}), "head_dim": TINY["head_dim"]},
        **{k: v for k, v in over.items() if k != "file"},
        "file": {**cfg["file"], **over.get("file", {})},
        "gap_tol": None,
    }


def load_config(name: str, rehearse: bool) -> dict:
    """A configuration file; for a rehearsal, cut to the tiny widths."""
    cfg = load_json("configs", f"{name}.json")
    return rehearsal_of(cfg) if rehearse else cfg


def load_cell(name: str, rehearse: bool) -> tuple[dict, dict, dict]:
    cell = load_json("workloads", f"{name}.json")
    traffic = load_json("traffic", f"{cell['traffic']}.json")
    return cell, load_config(cell["config"], rehearse), traffic


def peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest of the devices."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)


def layer_reader(metric: str):
    path = os.path.join(HERE, "layer_metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"layer_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def devices_or_exit(chips: int, rehearse: bool):
    import jax

    devs = jax.devices()
    if rehearse:
        if len(devs) < chips:
            log(f"rehearsal needs {chips} devices: set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={chips}")
            sys.exit(2)
        return devs
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"benchmark: needs {chips} TPU chip(s), found {len(devs)} x "
            f"{devs[0].platform!r}; no result")
        sys.exit(2)
    return devs


def warm_up(served, traffic: dict, send) -> None:
    """Build every program the cell's lengths can reach, then answer one
    shortest and one longest prompt (two decode blocks each) so that the
    HTTP path, the pool copies and the host's caches have run once."""
    p, o = traffic["prompt_tokens"], traffic["output_tokens"]
    served.build_programs(p["max"], p["max"] + o["max"])
    recorder = served.engine.recorder
    before = len(recorder.events("compile_end"))
    n_out = 2 * served.state.scheduler.block_size
    for i, n_prompt in enumerate([p["min"], p["max"]]):
        rec = send(f"warm-{i}", n_prompt, n_out, time.monotonic())
        if rec["error"]:
            raise RuntimeError(f"warm-up request failed: {rec['error']}")
    built = recorder.events("compile_end")[before:]
    if built:
        log(f"warm-up: {len(built)} programs were built at dispatch: "
            f"{[b['key'] for b in built]}")


def percentile(values: list[float], q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, dtype=float), q))


def end_to_end(records: list[dict], t0: float, t1: float) -> dict:
    """Client-side metrics over the requests due inside the window."""
    ok = [r for r in records if r["in_window"] and not r["failed"]]
    ttft = [(r["first"] - r["due"]) * 1e3 for r in ok if r["first"]]
    tpot = [(r["last"] - r["first"]) * 1e3 / (len(r["ids"]) - 1)
            for r in ok if len(r["ids"]) > 1]
    streamed = sum(n for r in records for t, n in r["deltas"] if t0 <= t < t1)
    out = {"out_tokens_per_s": streamed / (t1 - t0)}
    for name, values in (("ttft", ttft), ("tpot", tpot)):
        if values:
            out[f"{name}_mean_ms"] = sum(values) / len(values)
            out[f"{name}_p50_ms"] = percentile(values, 50)
            out[f"{name}_p95_ms"] = percentile(values, 95)
    return out


def judge_counts(records: list[dict], server: dict) -> list[str]:
    """Faults of count: every finished response ran to the token count asked
    for (the weights give the end-of-sequence ids a logit of 0)."""
    faults = []
    for r in records:
        if r["failed"]:
            continue
        s = server.get(r["id"])
        if s is None:
            faults.append(f"{r['id']}: no server trace record")
        elif s["n_completion"] != r["max_tokens"] or s["finish_reason"] != "length":
            faults.append(f"{r['id']}: asked {r['max_tokens']}, got "
                          f"{s['n_completion']} ({s['finish_reason']})")
        elif len(r["ids"]) > s["n_completion"]:
            faults.append(f"{r['id']}: streamed {len(r['ids'])} ids of {s['n_completion']}")
    return faults


def pick_sample(records: list[dict], seed: int) -> list[dict]:
    """The longest finished request and three drawn by the seed."""
    done = [r for r in records if r["in_window"] and not r["failed"] and r["ids"]]
    if not done:
        return []
    longest = max(done, key=lambda r: r["n_prompt"] + len(r["ids"]))
    rest = [r for r in done if r is not longest]
    rnd = random.Random(seed)
    return [longest] + rnd.sample(rest, min(SAMPLE - 1, len(rest)))


def trace_slice(run_dir: str, seconds: float, out: dict) -> None:
    """Profile TRACE_SLICE_S seconds from a quarter into the window, whose
    start the generator puts into `out["t0"]`."""
    import jax

    while "t0" not in out:
        time.sleep(0.01)
    time.sleep(max(0.0, out["t0"] + seconds / 4 - time.monotonic()))
    span = min(TRACE_SLICE_S, seconds / 2)
    jax.profiler.start_trace(os.path.join(run_dir, "profile"))
    out["trace_t0"] = time.monotonic()
    time.sleep(span)
    out["trace_t1"] = time.monotonic()
    jax.profiler.stop_trace()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell, cfg, traffic = load_cell(args.workload, args.rehearse)
    from benchmark.harness import weights  # no JAX yet

    try:  # an arch or a header key the program lacks: before the chip is touched
        weights.header_for(cfg)
    except ValueError as e:
        log(f"benchmark: {e}; no result")
        return 2

    real_stdout = sys.stdout
    with contextlib.redirect_stdout(sys.stderr):
        code, result = run(args, cell, cfg, traffic)
    if result is not None:
        print(json.dumps(result), file=real_stdout, flush=True)
    return code


class Session:
    """Weights on disk, the server up and warm: everything before a window."""

    def __init__(self, cell: dict, cfg: dict, traffic: dict, seed: int,
                 rehearse: bool, timeline: bool):
        self.cell, self.cfg, self.traffic, self.seed = cell, cfg, traffic, seed
        self.devs = devices_or_exit(cell["chips"], rehearse)
        self.run_dir = os.path.join(WORK, f"run-{cell['name']}")
        self.model_dir = os.path.join(WORK, f"{cfg['name']}-{seed}")
        self.served = None
        self.texts: dict[str, str] = {}
        try:
            self._start(timeline)
        except BaseException:
            self.cleanup()
            raise

    def _start(self, timeline: bool) -> None:
        from benchmark.harness import weights
        from benchmark.harness.server import Served
        from dllama_tpu.parallel.mesh import enable_compilation_cache

        log(f"compile cache: {enable_compilation_cache()}")
        shutil.rmtree(self.run_dir, ignore_errors=True)
        os.makedirs(self.run_dir)
        t = time.monotonic()
        self.model, tok = weights.write_pair(self.model_dir, self.cfg, self.seed)
        log(f"[setup] wrote {os.path.getsize(self.model) / 1e9:.2f} GB in "
            f"{time.monotonic() - t:.1f} s")
        t = time.monotonic()
        self.served = Served(self.cfg, self.model, tok, self.run_dir, timeline=timeline)
        e = self.served.engine
        log(f"[setup] loaded in {time.monotonic() - t:.1f} s; lanes {e.batch_size}, "
            f"weight format {e.weight_format}, tp {e.tp}")
        self.overhead = len(self.served.prompt_ids(""))
        t = time.monotonic()
        warm_up(self.served, self.traffic, self.send)
        log(f"[setup] warm-up {time.monotonic() - t:.1f} s")

    def send(self, rid, n_prompt: int, n_out: int, due: float) -> dict:
        """One request of `n_prompt` prompt tokens in all (the chat template's
        included); its text depends on the seed and its id only."""
        rid = rid if isinstance(rid, str) else f"r{self.seed}-{rid}"
        text = client.prompt_text(max(1, n_prompt - self.overhead),
                                  random.Random(f"{self.seed}/{rid}"))
        self.texts[rid] = text
        rec = client.stream_chat(self.served.port, rid, text, n_out, due)
        rec["n_prompt"] = n_prompt
        return rec

    def window(self, seconds: float, trace: bool) -> dict:
        """Drive the cell's traffic for `seconds`; returns t0, t1, the
        client's records and the traced slice's clock."""
        import numpy as np

        generator = importlib.import_module(
            f"benchmark.generators.{self.traffic['generator']}")
        clock = {"trace_t0": None, "trace_t1": None}
        tracer = None
        if trace:
            tracer = threading.Thread(
                target=trace_slice, name="bench-trace",
                args=(self.run_dir, seconds, clock))
            tracer.start()
        drove = generator.drive(
            self.traffic, self.cell, np.random.default_rng(self.traffic["schedule_seed"]),
            seconds, self.send, self.served.engine.batch_size, clock)
        if tracer:
            tracer.join()
        t0, t1 = drove["t0"], drove["t1"]
        for r in drove["records"]:
            r["in_window"] = t0 <= r["due"] < t1
            r["failed"] = bool(r["error"]) or r["finish"] is None or \
                r["done"] - t1 > DRAIN_S
        return {**drove, **clock}

    def close(self) -> None:
        if self.served is not None:
            self.served.stop()
            self.served = None

    def cleanup(self) -> None:
        """Stop what still runs and take the weights off the disk."""
        try:
            self.close()
        finally:
            shutil.rmtree(self.model_dir, ignore_errors=True)


def run(args, cell: dict, cfg: dict, traffic: dict) -> tuple[int, dict | None]:
    session = Session(cell, cfg, traffic, args.seed, args.rehearse, bool(args.trace))
    try:
        return measure(session, args)
    finally:
        session.cleanup()


def measure(session: Session, args) -> tuple[int, dict | None]:
    import numpy as np

    from benchmark.harness import compare, xplane
    from dllama_tpu.obs.trace import read_jsonl

    cell, cfg, served, run_dir = session.cell, session.cfg, session.served, session.run_dir
    devs = session.devs
    win = session.window(args.seconds, bool(args.trace))
    t0, t1, records = win["t0"], win["t1"], win["records"]
    setup_s = t0 - T_START
    peak = peak_bytes(devs[: cell["chips"]])
    recorder = served.engine.recorder.dump()
    session.close()

    server = {s["request_id"]: s for s in read_jsonl(served.trace_path)}
    contexts = [s["n_prompt_tokens"] + s["n_completion"] / 2 for rid, s in server.items()
                if not rid.startswith("warm") and s["n_prompt_tokens"]]
    window = {
        "t0": t0, "t1": t1, "seconds": args.seconds,
        "trace_t0": win["trace_t0"], "trace_t1": win["trace_t1"],
        "lanes": served.engine.batch_size, "chips": cell["chips"],
        "block_size": served.state.scheduler.block_size,
        "device_kind": devs[0].device_kind, "config": cfg,
        "mean_context": float(np.mean(contexts)) if contexts else 0.0,
    }
    for name, obj in (("window.json", window), ("recorder.json", recorder)):
        with open(os.path.join(run_dir, name), "w") as f:
            json.dump(obj, f)
    with open(os.path.join(run_dir, "requests.jsonl"), "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")

    late = [(r["sent"] - r["due"]) * 1e3 for r in records]
    log(f"[window] {len(records)} requests, generator lateness ms: median "
        f"{percentile(late, 50):.2f}, max {max(late):.2f}")
    compiles = [e for e in recorder["events"]
                if e["kind"] in ("compile_start", "compile") and t0 <= e["t"] < t1]
    faults = judge_counts(records, server)
    if compiles:
        faults.append(f"{len(compiles)} programs compiled inside the window: "
                      f"{[e.get('key') for e in compiles]}")

    served.free()
    t = time.monotonic()
    sample = []
    for r in pick_sample(records, args.seed):
        ids = served.prompt_ids(session.texts[r["id"]])
        if len(ids) != server[r["id"]]["n_prompt_tokens"]:
            faults.append(f"{r['id']}: {len(ids)} prompt ids, the server counted "
                          f"{server[r['id']]['n_prompt_tokens']}")
        sample.append({"id": r["id"], "prompt_ids": ids, "served": r["ids"]})
    reports = compare.check(cfg, session.model, sample)
    for rep in reports:
        log("[compare] " + json.dumps({k: v for k, v in rep.items() if k != "gaps"}))
    log(f"[compare] {len(reports)} requests against references/{cfg['family']}.py "
        f"in {time.monotonic() - t:.1f} s; gap_tol {cfg['gap_tol']}")
    if not reports:
        faults.append("no finished request to compare")
    faults += [f"{rep['id']}: gap {rep['max_gap_std']:.4f} std at token "
               f"{rep['worst_at']} over gap_tol {cfg['gap_tol']}"
               for rep in reports if rep["passed"] is False or not rep["finite"]]
    for fault in faults:
        log(f"[incorrect] {fault}")

    attempted = sum(1 for r in records if r["in_window"])
    failed = sum(1 for r in records if r["in_window"] and r["failed"])
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    e2e = {**end_to_end(records, t0, t1), "setup_s": setup_s}
    if args.rehearse:
        log("rehearsal: counts only, no result is printed off the chip")
        return 3, {"rehearsal": True, "device": device, "counts": {
            "attempted": attempted, "failed": failed,
            "streamed_tokens": sum(len(r["ids"]) for r in records),
            "compared": len(reports), "faults": faults,
            "top1_share": [rep["top1_share"] for rep in reports],
            "max_gap_std": [rep["max_gap_std"] for rep in reports]}}

    result = {"correct": not faults, "attempted": attempted, "failed": failed,
              "device": device}
    if not args.trace:
        result["metrics"] = {m: {"value": e2e[m], "unit": unit}
                             for m, unit in cell["end_to_end"].items() if m in e2e}
        return 0, result
    dig = xplane.digest(xplane.load(os.path.join(run_dir, "profile")),
                        win["trace_t1"] - win["trace_t0"])
    with open(os.path.join(run_dir, "trace_digest.json"), "w") as f:
        json.dump(dig, f)
    device.update(busy_s=dig["busy_s"], window_s=dig["window_s"])
    result["breakdown"] = {"device_ops": dig["device_ops"], "idle_gaps": dig["idle_gaps"]}
    result["metrics"] = {}
    for metric in cell["per_layer"]:
        reader = layer_reader(metric)
        value = reader.read(run_dir)
        if value is not None:
            result["metrics"][metric] = {"value": float(value), "unit": reader.UNIT}
    log("[end_to_end in the traced run] " + json.dumps(e2e))
    return 0, result


if __name__ == "__main__":
    sys.exit(main())
