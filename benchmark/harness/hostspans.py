"""Charge the device's idle gaps to what the scheduler thread was doing.

The program opens a `jax.profiler.TraceAnnotation` for every span it times
(`dllama.<component>.<name>`, `dllama_tpu/obs/spans.py`), so a traced run's
`.xplane.pb` holds the scheduler thread's spans beside the device's
operations, on the profiler's clock. From it:

- `idle_by_span.json`: the idle gaps are `xplane.digest`'s (between
  consecutive executions on a device plane's "XLA Modules" line, averaged
  over planes). Each instant of a gap goes to the innermost `dllama.*` span
  of the scheduler thread that covers it, keyed by that span's path from
  the top (`scheduler.sched_tick > scheduler.emit > scheduler.finish`), or
  to `unattributed`. Also the offset between the host's `time.monotonic`
  and the profiler's clock, read from `mono_ns` of every `sched_tick`.
- `device_by_scope.json`: device seconds by the `jax.named_scope` of each
  operation's `op_name` (`layers/attn`, `layers/ffn`, ..., and `layers` for
  what the layer scan runs outside its layer's scopes), where the trace
  carries an operation's `op_name`.

`load` adapts `jax.profiler.ProfileData`, which shows an event's own stats
and not those of its metadata, where the profiler files an operation's
`op_name` (as `tf_op`): `tf_ops` reads that one stat from the file's
protobuf wire format. `attribute` and `by_scope` work on plain tuples, as
`xplane.digest` does, so they are tested on a small hand-recorded trace
without a device. A trace of a program without the
annotations (the parent of the PR that added them) gives no table, and the
readers return None.

The five readers built on this file (`METRICS`) are per-layer metrics of
`mistral7b-chat` and `qwen3moe-decode-sat`, so a traced run's result line
carries them. For a run directory that is already there,

    python3 -m benchmark.harness.hostspans benchmark/work/run-<cell>

writes the two tables there and prints the five numbers as one JSON object.
"""

from __future__ import annotations

import bisect
import glob
import importlib.util
import json
import os
import statistics
import sys

from benchmark.harness import xplane

PREFIX = "dllama."
TICK = "scheduler.sched_tick"
UNATTRIBUTED = "unattributed"
SEP = " > "
LAYERS = "layers"
LAYER_SCOPES = ("attn", "kv_write", "ffn", "moe", "norm")
TABLE, SCOPES = "idle_by_span.json", "device_by_scope.json"
METRICS = ("idle_attributed_pct", "idle_in_emit_pct", "idle_in_dispatch_prep_pct",
           "tick_host_ms", "layer_scan_copy_pct")


def load(trace_dir: str):
    """(host, lines, ops) of the newest trace under `trace_dir`: `host` is
    [(span, start_ns, duration_ns, mono_ns or None)] of the thread that
    holds the scheduler's ticks, names without the prefix; `lines` is what
    `xplane.load` gives; `ops` is [(plane, event, duration_ns, op_name)] of
    the operations whose `op_name` the trace carries."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(paths[-1], "rb") as f:
        raw = f.read()
    data = jax.profiler.ProfileData.from_serialized_xspace(raw)
    try:
        op_names = tf_ops(raw)
    except (ValueError, IndexError, KeyError):  # not the layout `tf_ops` knows: no scopes
        op_names = {}
    host, lines, ops = [], [], []
    for plane in data.planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            named = op_names.get(plane.name, {})
            for line in plane.lines:
                if line.name not in (xplane.OPS, xplane.MODULES):
                    continue
                events = [(e.name, float(e.start_ns), float(e.duration_ns))
                          for e in line.events]
                lines.append((plane.name, line.name, events))
                if line.name == xplane.OPS:
                    ops += [(plane.name, name, dur, named[name])
                            for name, _, dur in events if name in named]
        elif plane.name.startswith("/host:") and not host:
            for line in plane.lines:
                spans = [(e.name[len(PREFIX):], float(e.start_ns), float(e.duration_ns),
                          dict(e.stats).get("mono_ns") if e.name == PREFIX + TICK else None)
                         for e in line.events if e.name.startswith(PREFIX)]
                if any(name == TICK for name, *_ in spans):
                    host = spans
                    break
    return host, lines, ops


def _fields(buf: bytes):
    """(field number, value) of one protobuf message: an int for a varint,
    the bytes of a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                break
        wire = key & 7
        if wire == 0 or wire == 2:
            value = shift = 0
            while True:
                b = buf[i]
                i += 1
                value |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            if wire == 2:
                value, i = buf[i:i + value], i + value
            yield key >> 3, value
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in a trace")


def tf_ops(xspace: bytes) -> dict[str, dict[str, str]]:
    """{plane name: {event name: op_name}} from a serialized `XSpace`: of
    each plane (field 1) its name (2), its stat names (5: a map to
    `XStatMetadata` id 1, name 2) and, for each event's metadata (4: a map
    to `XEventMetadata` name 2, stats 5), the `tf_op` stat (`XStat`
    metadata_id 1, str_value 5 or ref_value 7, which names a stat
    metadata). The lines (3), most of the bytes, are skipped whole."""
    out = {}
    for num, plane in _fields(xspace):
        if num != 1:
            continue
        name, stat_names, metadata = "", {}, []
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode()
            elif f == 5:
                entry = dict(_fields(dict(_fields(v))[2]))
                stat_names[entry.get(1, 0)] = entry.get(2, b"").decode()
            elif f == 4:
                metadata.append(dict(_fields(v))[2])
        wanted = {i for i, n in stat_names.items() if n == "tf_op"}
        if not wanted or not xplane.DEVICE_PLANE.match(name):
            continue
        ops = out.setdefault(name, {})
        for em in metadata:
            event, op_name = "", None
            for f, v in _fields(em):
                if f == 2:
                    event = v.decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in wanted:
                        op_name = (stat[5].decode() if 5 in stat
                                   else stat_names.get(stat.get(7), ""))
            if op_name:
                ops[event] = op_name
    return out


def innermost(spans) -> list[tuple[float, float, str]]:
    """Flatten one thread's nested spans into disjoint (start, end, path)
    segments in time order, each under the path of the innermost span that
    covers it. A child is cut to its parent's end."""
    out, stack = [], []  # stack of (end, path)
    cursor = 0.0

    def close(until: float) -> None:
        nonlocal cursor
        while stack and stack[-1][0] <= until:
            end, path = stack.pop()
            if end > cursor:
                out.append((cursor, end, path))
                cursor = end

    for name, start, dur, *_ in sorted(spans, key=lambda s: (s[1], -s[2])):
        close(start)
        if stack and start > cursor:
            out.append((cursor, start, stack[-1][1]))
        cursor = max(cursor, start) if stack else start
        end = min(start + dur, stack[-1][0]) if stack else start + dur
        stack.append((end, stack[-1][1] + SEP + name if stack else name))
    close(float("inf"))
    return out


def attribute(host, lines, window_s: float) -> dict | None:
    """The table of `idle_by_span.json`; None where the trace holds no
    scheduler spans."""
    if not host:
        return None
    segments = innermost(host)
    starts = [s for s, _, _ in segments]
    planes = sorted({p for p, _, _ in lines})
    by_span: dict[str, float] = {}
    for p in planes:
        mods = sorted((ev for q, ln, evs in lines if q == p and ln == xplane.MODULES
                       for ev in evs), key=lambda e: e[1])
        end = None
        for _, start, dur in mods:
            if end is not None and start > end:
                left = start - end
                i = max(0, bisect.bisect_right(starts, end) - 1)
                while i < len(segments) and segments[i][0] < start:
                    s0, s1, path = segments[i]
                    over = min(s1, start) - max(s0, end)
                    if over > 0:
                        by_span[path] = by_span.get(path, 0.0) + over / 1e9 / len(planes)
                        left -= over
                    i += 1
                by_span[UNATTRIBUTED] = by_span.get(UNATTRIBUTED, 0.0) + left / 1e9 / len(planes)
            end = max(end or 0.0, start + dur)
    offsets = [start - mono for name, start, _, mono in host if mono is not None]
    return {
        "devices": len(planes),
        "window_s": window_s,
        "idle_s": sum(by_span.values()),
        "by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
        "clock": {
            "ticks": len(offsets),
            "offset_ns": statistics.median(offsets) if offsets else None,
            "drift_ns": max(offsets) - min(offsets) if offsets else None,
        },
    }


def scope_of(op_name: str) -> str:
    """`layers/attn` for an operation inside a layer's scope, `layers` for
    what the scan runs outside them, else the first scope below the jitted
    functions (`logits_head`, `sample`) or `other`."""
    parts = [p for p in op_name.split(";")[0].split("/")
             if not (p.startswith("jit(") or p in ("while", "body", "cond", "closed_call"))]
    if LAYERS in parts:
        inner = [p for p in parts[parts.index(LAYERS) + 1:] if p in LAYER_SCOPES]
        return f"{LAYERS}/{inner[0]}" if inner else LAYERS
    return parts[0] if len(parts) > 1 else "other"


def by_scope(ops, n_planes: int) -> dict | None:
    """Device seconds by scope, averaged over planes; None where no
    operation carries an `op_name`, or none lies under `layers` (a program
    without the scopes)."""
    seconds: dict[str, float] = {}
    for _, event, dur, op_name in ops:
        if not xplane.is_container(event):
            key = scope_of(op_name)
            seconds[key] = seconds.get(key, 0.0) + dur / 1e9 / n_planes
    if not any(k.split("/")[0] == LAYERS for k in seconds):
        return None
    return dict(sorted(seconds.items(), key=lambda kv: -kv[1]))


def _load_json(path: str):
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def tables(run_dir: str) -> tuple[dict | None, dict | None]:
    """(idle by span, device seconds by scope) of a traced run, reduced
    once from `run_dir/profile` and kept beside it."""
    marker = os.path.join(run_dir, TABLE)
    if not os.path.exists(marker):
        window = _load_json(os.path.join(run_dir, "window.json")) or {}
        if window.get("trace_t0") is None or not os.path.isdir(os.path.join(run_dir, "profile")):
            return None, None
        host, lines, ops = load(os.path.join(run_dir, "profile"))
        table = attribute(host, lines, window["trace_t1"] - window["trace_t0"])
        scopes = by_scope(ops, len({p for p, _, _ in lines}) or 1)
        for name, obj in ((TABLE, table), (SCOPES, scopes)):
            with open(os.path.join(run_dir, name), "w") as f:
                json.dump(obj, f, indent=1)
    return _load_json(marker), _load_json(os.path.join(run_dir, SCOPES))


def idle_share_in(run_dir: str, span: str) -> float | None:
    """Percent of the traced slice in which the device was idle and the
    scheduler thread was inside `span` (or a span nested in it)."""
    table, _ = tables(run_dir)
    if not table:
        return None
    inside = sum(s for path, s in table["by_span"].items() if span in path.split(SEP))
    return 100.0 * inside / table["window_s"]


def timeline(run_dir: str) -> tuple[dict, list[dict]]:
    """The streamed `--timeline-out` of a run, through the program's own
    reader: the metadata event's `args` and the span events. ({}, []) where
    the program streams none (its parent rewrote one JSON object, which
    held only the ring's last spans)."""
    path = os.path.join(run_dir, "timeline.json")
    try:
        from dllama_tpu.obs.spans import read_timeline
    except ImportError:
        return {}, []
    meta, spans = read_timeline(path) if os.path.exists(path) else ({}, [])
    return (meta, spans) if "epoch_monotonic" in meta else ({}, [])


def report(run_dir: str) -> dict:
    """{metric: {"value", "unit"}} of `METRICS`, each through its reader in
    `layer_metrics/`, as `run.py` would print them; a reader that finds
    nothing leaves its metric out."""
    out = {}
    for metric in METRICS:
        path = os.path.join(os.path.dirname(__file__), os.pardir, "layer_metrics", f"{metric}.py")
        spec = importlib.util.spec_from_file_location(f"layer_metric_{metric}", path)
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        value = reader.read(run_dir)
        if value is not None:
            out[metric] = {"value": float(value), "unit": reader.UNIT}
    return out


if __name__ == "__main__":
    for run in sys.argv[1:]:
        print(json.dumps({"run_dir": run, "metrics": report(run)}))
