"""What a run leaves in its directory, for the per-layer readers.

`window.json` (clocks and sizes), `requests.jsonl` (the client's records),
`server_trace.jsonl` (the server's `--trace-out`), `recorder.json` (the
flight recorder's events) and, in a traced run, `trace_digest.json`
(`xplane.digest`). A reader that finds nothing to read returns None.
"""

from __future__ import annotations

import json
import os
import statistics

# the jitted functions behind the engine's program keys, as the profiler
# names their modules
MODULE_OF = {"lane_block": "jit_block", "lane_prefill": "jit_step"}


def _json(run_dir: str, name: str):
    path = os.path.join(run_dir, name)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def _jsonl(run_dir: str, name: str) -> list[dict]:
    path = os.path.join(run_dir, name)
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def window(run_dir: str) -> dict:
    return _json(run_dir, "window.json") or {}


def config(run_dir: str) -> dict:
    return window(run_dir)["config"]


def digest(run_dir: str) -> dict | None:
    return _json(run_dir, "trace_digest.json")


def requests(run_dir: str) -> list[dict]:
    """Client records of requests due inside the window."""
    return [r for r in _jsonl(run_dir, "requests.jsonl") if r.get("in_window")]


def server_records(run_dir: str) -> list[dict]:
    """`--trace-out` records of the window's requests."""
    ids = {r["id"] for r in requests(run_dir)}
    return [r for r in _jsonl(run_dir, "server_trace.jsonl") if r["request_id"] in ids]


def events(run_dir: str, kind: str, step: str | None = None,
           span: str = "window") -> list[dict]:
    """Recorder events of one kind inside the window (`span="trace"`: inside
    the traced slice), on the recorder's monotonic clock."""
    w = window(run_dir)
    lo, hi = (w.get("trace_t0"), w.get("trace_t1")) if span == "trace" else (
        w.get("t0"), w.get("t1"))
    if lo is None:
        return []
    rec = _json(run_dir, "recorder.json") or {"events": []}
    return [e for e in rec["events"]
            if e["kind"] == kind and lo <= e["t"] < hi
            and (step is None or e.get("step") == step)]


def median(values) -> float | None:
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None


def module_seconds(run_dir: str, program: str) -> tuple[float, float] | None:
    """(device seconds, calls) of a program inside the traced slice."""
    d = digest(run_dir)
    m = d and d["modules"].get(MODULE_OF[program])
    return (m["seconds"], m["calls"]) if m and m["calls"] else None
