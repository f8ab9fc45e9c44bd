"""Charge the seconds the device stood drained to what the scheduler thread
was doing, from the program's own clock: no profiler.

The engine knows when the device has nothing to do: from the end of a
read-back (the only host syncs of the lane path) until the next dispatch
begins nothing is enqueued. It records that interval as a span
`device_drained` (`before` = the step dispatched next; a pool copy, which
nobody reads back and which runs a millisecond, is host work inside it), and
every span says the `thread` it ran on and, where it tells what that thread
was doing, its `parent` (`dllama_tpu/obs/spans.py`). From the
streamed timeline (`hostspans.timeline`) of a run:

- `drained_by_span.json`: the spans of the thread that holds `sched_tick`
  that carry a `parent` are flattened with `hostspans.innermost`; every
  instant of every drained interval goes to the innermost span's path, the
  paths of `idle_by_span.json` with `dispatch_prep` split by its `step`.
  Seconds under `sched_wait` are kept apart: idle for want of work is not
  the host's doing. A traced run's window is used up to `MARGIN_BEFORE_S`
  before the first tick that says `profiled` and no further: the profiler's
  Python tracer slows the session's ticks, `stop_trace` then exports for
  seconds on the interpreter's lock, and the host stays slower for the rest
  of the process's life (`PERF.md`, PR 37). `after_session` keeps that
  rest's numbers, from `MARGIN_AFTER_S` behind the last such tick, so the
  residue shows in every table.
- For the traced slice, on the profiler's clock through the `mono_ns` offset
  of `idle_by_span.json`: `covered_s`, the seconds of the profiler's idle
  gaps (between consecutive executions on a device's "XLA Modules" line,
  averaged over planes) that lie inside a drained interval, and
  `drained_while_busy_s`, drained seconds during which a module ran: the
  accounting's error.

`attribute`, `by_second` and `against_trace` work on plain tuples, so they are tested on
a hand-written timeline and trace. A timeline whose spans carry no `thread`
(the parent of the PR that added it) gives no table, and the four readers in
`layer_metrics/` return None. For a run directory that is already there,

    python3 -m benchmark.harness.drained benchmark/work/run-<cell>

writes the table there and prints the four numbers as one JSON object.
"""

from __future__ import annotations

import bisect
import json
import os
import sys

from benchmark.harness import hostspans, rundir, xplane

TABLE = "drained_by_span.json"
DRAINED = "device_drained"
WAIT = "scheduler.sched_wait"
EMIT, PREP = "scheduler.emit", "engine.dispatch_prep"
BLOCK = "engine.decode_lanes"
# the tracker's stable pid of each component (`obs/spans.py`)
COMPONENTS = {1: "scheduler", 2: "engine", 3: "kv", 4: "http", 5: "cli"}
# around the ticks that say `profiled`: the session's start-up, its export
MARGIN_BEFORE_S, MARGIN_AFTER_S = 1.0, 5.0
METRICS = ("host_exposed_pct", "host_exposed_in_emit_pct",
           "host_exposed_in_dispatch_prep_pct", "drained_covers_idle_pct")


def label(event: dict) -> str:
    """`scheduler.emit`; `engine.dispatch_prep(decode_lanes)`."""
    name = f"{COMPONENTS.get(event['pid'], event['pid'])}.{event['name']}"
    if name == PREP:
        name += f"({event['args'].get('step')})"
    return name


def scheduler_thread(events: list[dict]):
    """(stacked, drained, profiled) of the thread that holds the ticks, in
    microseconds on the timeline's clock: the spans that carry a `parent`
    as (label, start, duration), the drained intervals as (start, end,
    before), and the (start, end) of the ticks a profiler session disturbed.
    None where the spans carry no `thread`."""
    threads = [e["args"].get("thread") for e in events if e["name"] == "sched_tick"]
    if not threads or None in threads:
        return None
    thread = max(set(threads), key=threads.count)
    mine = [e for e in events if e["args"].get("thread") == thread]
    stacked = [(label(e), e["ts"], e["dur"]) for e in mine if "parent" in e["args"]]
    drained = sorted((e["ts"], e["ts"] + e["dur"], e["args"].get("before"))
                     for e in mine if e["name"] == DRAINED)
    profiled = [(e["ts"], e["ts"] + e["dur"]) for e in mine
                if e["name"] == "sched_tick" and e["args"].get("profiled")]
    return stacked, drained, profiled


def _overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def pieces(segments, drained, lo: float, hi: float):
    """(start, end, path, before) of every part of a drained interval inside
    [lo, hi) that one flattened segment covers; what no segment covers comes
    under `unattributed`."""
    starts = [s for s, _, _ in segments]
    for d0, d1, before in drained:
        d0, d1 = max(d0, lo), min(d1, hi)
        if d1 <= d0:
            continue
        at = d0
        i = max(0, bisect.bisect_right(starts, d0) - 1)
        while i < len(segments) and segments[i][0] < d1:
            s0, s1, path = segments[i]
            a, b = max(s0, at), min(s1, d1)
            if b > a:
                if a > at:
                    yield at, a, hostspans.UNATTRIBUTED, before
                yield a, b, path, before
                at = b
            i += 1
        if d1 > at:
            yield at, d1, hostspans.UNATTRIBUTED, before


def waits(path: str) -> bool:
    return WAIT in path.split(hostspans.SEP)


def attribute(segments, drained, lo: float, hi: float) -> dict:
    """Seconds of the drained intervals inside [lo, hi) by the path of the
    innermost of the flattened `segments`, and by the step dispatched next."""
    by_span: dict[str, float] = {}
    by_before: dict[str, float] = {}
    for a, b, path, before in pieces(segments, drained, lo, hi):
        for table, key in ((by_span, path), (by_before, str(before))):
            table[key] = table.get(key, 0.0) + (b - a) / 1e6
    waiting = sum(s for path, s in by_span.items() if waits(path))
    ranked = lambda d: dict(sorted(d.items(), key=lambda kv: -kv[1]))
    return {
        "used_s": max(0.0, hi - lo) / 1e6,
        "drained_s": sum(by_span.values()),
        "waiting_s": waiting,
        "exposed_s": sum(by_span.values()) - waiting,
        "by_span": ranked(by_span),
        "by_before": ranked(by_before),
    }


def by_second(segments, drained, lo: float, hi: float) -> list[float]:
    """Exposed seconds (drained, outside `sched_wait`) of each second of
    [lo, hi): where a profiler's session disturbed the host, and for how
    long, shows here."""
    out = [0.0] * max(0, -int(-(hi - lo) // 1e6))
    for a, b, path, _ in pieces(segments, drained, lo, hi):
        if not waits(path):
            for sec in range(int((a - lo) // 1e6), min(len(out), int((b - lo) // 1e6) + 1)):
                out[sec] += _overlap(a, b, lo + sec * 1e6, lo + (sec + 1) * 1e6) / 1e6
    return [round(s, 4) for s in out]


def against_trace(drained_ns, lines) -> dict | None:
    """The traced slice's half: `drained_ns` are the drained intervals on the
    profiler's clock, in time order, `lines` what `xplane.load` gives.
    Seconds, averaged over planes; None where the trace has no "XLA Modules"
    line."""
    planes = sorted({p for p, ln, _ in lines if ln == xplane.MODULES})
    if not planes:
        return None
    starts = [d0 for d0, _ in drained_ns]

    def inside(a: float, b: float) -> float:
        """Nanoseconds of [a, b) inside a drained interval (they are disjoint)."""
        total = 0.0
        i = max(0, bisect.bisect_right(starts, a) - 1)
        while i < len(drained_ns) and drained_ns[i][0] < b:
            total += _overlap(a, b, *drained_ns[i])
            i += 1
        return total

    idle = covered = while_busy = 0.0
    for p in planes:
        mods = sorted((ev for q, ln, evs in lines if q == p and ln == xplane.MODULES
                       for ev in evs), key=lambda e: e[1])
        end = None
        for _, start, dur in mods:
            if end is None:
                end = start
            if start > end:
                idle += start - end
                covered += inside(end, start)
            if start + dur > end:  # what of it no earlier execution covers
                while_busy += inside(max(start, end), start + dur)
                end = start + dur
    n = len(planes) * 1e9
    return {"idle_s": idle / n, "covered_s": covered / n, "drained_while_busy_s": while_busy / n}


def table(run_dir: str) -> dict | None:
    """`drained_by_span.json` of a run, reduced once and kept beside it."""
    path = os.path.join(run_dir, TABLE)
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    meta, events = hostspans.timeline(run_dir)
    w = rundir.window(run_dir)
    found = scheduler_thread(events) if events and w.get("t0") is not None else None
    if found is None:
        return None
    stacked, drained, profiled = found
    segments = hostspans.innermost(stacked)
    epoch = meta["epoch_monotonic"]
    lo, hi = ((t - epoch) * 1e6 for t in (w["t0"], w["t1"]))
    end = min([hi] + [s - MARGIN_BEFORE_S * 1e6 for s, _ in profiled])
    blocks = [start for name, start, _ in stacked if name == BLOCK]
    out = {"window_s": w["t1"] - w["t0"], **attribute(segments, drained, lo, end),
           "cycles": sum(lo <= b < end for b in blocks),
           "exposed_by_second": by_second(segments, drained, lo, hi)}
    if profiled:
        resume = max(e for _, e in profiled) + MARGIN_AFTER_S * 1e6
        after = attribute(segments, drained, resume, hi)
        out["after_session"] = {"used_s": after["used_s"], "exposed_s": after["exposed_s"],
                                "cycles": sum(resume <= b < hi for b in blocks)}
    idle, _ = hostspans.tables(run_dir)
    offset = idle and idle["clock"]["offset_ns"]
    if offset is not None:
        lines = xplane.load(os.path.join(run_dir, "profile"))
        out["traced"] = against_trace(
            [((epoch + d0 / 1e6) * 1e9 + offset, (epoch + d1 / 1e6) * 1e9 + offset)
             for d0, d1, _ in drained], lines)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    return out


def exposed_share_in(run_dir: str, span: str | None = None) -> float | None:
    """Percent of the window's seconds used in which the device stood
    drained outside `sched_wait`; with `span` (`scheduler.emit`,
    `engine.dispatch_prep`), the part of it with the scheduler thread inside
    that span or one nested in it."""
    t = table(run_dir)
    if not t or not t["used_s"]:
        return None
    if span is None:
        return 100.0 * t["exposed_s"] / t["used_s"]
    inside = sum(s for path, s in t["by_span"].items()
                 if any(part.split("(")[0] == span for part in path.split(hostspans.SEP)))
    return 100.0 * inside / t["used_s"]


if __name__ == "__main__":
    from benchmark.run import layer_reader

    for run in sys.argv[1:]:
        found = {m: layer_reader(m).read(run) for m in METRICS}
        print(json.dumps({"run_dir": run, "metrics": {
            m: {"value": float(v), "unit": "%"} for m, v in found.items() if v is not None}}))
