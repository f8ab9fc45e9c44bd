"""Reduce a JAX profiler trace (`*.xplane.pb`) to what the metrics read.

A trace holds planes (one per device, others for the host), each with lines
of events (name, start, duration in nanoseconds). On a TPU plane the line
"XLA Ops" holds every operation that ran on the device and "XLA Modules"
one event per executed program (`jit_<function>(<fingerprint>)`). From them:

- busy seconds of a device: the union of its "XLA Ops" intervals ("XLA
  Modules" where a plane has no ops line), so overlapping events count once;
- seconds and calls per program, by the module's name without fingerprint;
- the operations that took most time, and the longest idle gaps, each named
  by the program that ran next (what the device was waiting for).

`load` adapts `jax.profiler.ProfileData`; `digest` works on plain tuples, so
the reduction is tested on a small hand-recorded trace without a device.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS, MODULES = "XLA Ops", "XLA Modules"
TOP = 10


def load(trace_dir: str) -> list[tuple[str, str, list[tuple[str, float, float]]]]:
    """[(plane, line, [(event, start_ns, duration_ns)])] of the device
    planes of the newest trace under `trace_dir`."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name in (OPS, MODULES):
                events = [(e.name, float(e.start_ns), float(e.duration_ns))
                          for e in line.events]
                out.append((plane.name, line.name, events))
    return out


def union_ns(events) -> float:
    """Total length covered by [start, start + duration) intervals."""
    total, end = 0.0, float("-inf")
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def module_name(event: str) -> str:
    return re.sub(r"\(\d+\)$", "", event)


def op_name(event: str) -> str:
    """`%fusion.12 = bf16[...] fusion(...)` -> `fusion.12`: the trace names
    an operation by its whole HLO line."""
    return event.split(" = ", 1)[0].lstrip("%")[:64]


def is_container(event: str) -> bool:
    """A while, conditional or call spans the operations it runs, which are
    on the line themselves: it counts as busy time, not as an operation."""
    return op_name(event).split(".")[0] in ("while", "conditional", "call")


def digest(lines, window_s: float) -> dict:
    """See the module docstring. `window_s` is the traced wall time."""
    planes = sorted({p for p, _, _ in lines})
    if not planes:
        raise ValueError("the trace has no device plane with XLA lines")
    by = {(p, ln): ev for p, ln, ev in lines}
    busy, modules, ops, gaps = [], {}, {}, {}
    for p in planes:
        op_events = by.get((p, OPS)) or by.get((p, MODULES)) or []
        busy.append(union_ns(op_events) / 1e9)
        for name, _, dur in by.get((p, OPS), []):
            if not is_container(name):
                key = op_name(name)
                ops[key] = ops.get(key, 0.0) + dur / 1e9 / len(planes)
        mods = sorted(by.get((p, MODULES), []), key=lambda e: e[1])
        end = None
        for name, start, dur in mods:
            m = modules.setdefault(module_name(name), {"seconds": 0.0, "calls": 0})
            m["seconds"] += dur / 1e9 / len(planes)
            m["calls"] += 1 / len(planes)
            if end is not None and start > end:
                key = "before " + module_name(name)
                gaps[key] = gaps.get(key, 0.0) + (start - end) / 1e9 / len(planes)
            end = max(end or 0.0, start + dur)
    top = lambda d: sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])[:TOP]
    return {
        "devices": len(planes),
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "busy_s_per_device": busy,
        "modules": modules,
        "device_ops": top(ops),
        "idle_gaps": top(gaps),
    }
