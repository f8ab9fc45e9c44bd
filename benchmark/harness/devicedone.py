"""A program's time on the device from the engine's completion stamps: no
profiler.

The engine stamps when each program of the lane path left the device
(`dllama_tpu/runtime/done.py`) and records, a program, `device_done`:
`step`, `at` (the stamp, on the recorder's clock), `device_ms` (the stamp
less the later of the stamp before it and the program's enqueue), `queued_ms`,
`dry_ms` and, where its handle raised, `error`. From `recorder.json` of a run:

- `device_ms(run_dir, step)`: the `device_ms` of the window's programs of one
  step, by their stamps. A traced run's window is used as far as `drained.py`
  uses it (up to `MARGIN_BEFORE_S` before the first tick that says
  `profiled`): the profiler's session slows the host, not the device, but one
  cut for the untraced readers keeps their numbers of one stretch of time.
- `accounted(run_dir)`: whether the recorder holds one `device_done` for
  every `step_dispatch` of the lane path, in dispatch order, none in error.
- `against_trace`: for the check of the stamps only, the traced slice's
  programs, each against the profiler's execution of the same module call
  (the one whose end lies nearest the stamp, both on the profiler's clock
  through the `mono_ns` offset of `idle_by_span.json`): how far `device_ms`
  is from the module's duration, and how long after the module's end the
  stamp was taken; beside them `trace_digest.json`'s mean a call.
- `missed_gaps`: the traced slice's idle gaps against the `device_drained`
  intervals, by the gap's length and its neighbours: what
  `drained_covers_idle_pct` misses, and at which end of a gap.

`reduce`, `against_trace` and `missed_gaps` work on plain lists, so they are tested on a
hand-written recorder and trace. A recorder without the events (the parent of
the PR that added them) gives nothing, and the readers in `layer_metrics/`
return None. For a run directory that is already there,

    python3 -m benchmark.harness.devicedone benchmark/work/run-<cell>

prints the account, the numbers by step and, of a traced run, the check.
"""

from __future__ import annotations

import bisect
import json
import os
import statistics
import sys

from benchmark.harness import drained, hostspans, rundir, xplane

EVENT = "device_done"
# the step of a `device_done` -> the engine's program key (`rundir.MODULE_OF`)
PROGRAM_OF = {"decode_lanes": "lane_block", "prefill_lane_chunk": "lane_prefill"}
LANE_STEPS = ("decode_lanes", "prefill_lane_chunk", "verify_lanes", "draft_step")


def used(run_dir: str) -> tuple[float, float] | None:
    """(begin, end) of the window's part the untraced readers use, on the
    recorder's clock: `drained.table`'s cut."""
    w = rundir.window(run_dir)
    if w.get("t0") is None:
        return None
    end = w["t1"]
    meta, events = hostspans.timeline(run_dir)
    found = drained.scheduler_thread(events) if events else None
    if found:
        epoch = meta["epoch_monotonic"]
        end = min([end] + [epoch + s / 1e6 - drained.MARGIN_BEFORE_S for s, _ in found[2]])
    return w["t0"], end


def recorded(run_dir: str) -> list[dict]:
    rec = rundir._json(run_dir, "recorder.json") or {"events": []}
    return rec["events"]


def reduce(events: list[dict], lo: float, hi: float) -> dict[str, list[dict]]:
    """The `device_done` events stamped inside [lo, hi) that are no error,
    by step, in the order of their stamps."""
    out: dict[str, list[dict]] = {}
    for e in sorted((e for e in events if e["kind"] == EVENT and "error" not in e
                     and lo <= e["at"] < hi), key=lambda e: e["at"]):
        out.setdefault(e["step"], []).append(e)
    return out


def device_ms(run_dir: str, step: str) -> list[float]:
    span = used(run_dir)
    if span is None:
        return []
    return [e["device_ms"] for e in reduce(recorded(run_dir), *span).get(step, [])]


def accounted(events: list[dict]) -> dict:
    """Dispatches of the lane path against the programs stamped: the stamped
    ones have to be the dispatched ones, in order, less the last few that
    the engine had not accounted for when the recorder was read."""
    began = [e["step"] for e in events
             if e["kind"] == "step_dispatch" and e["step"] in LANE_STEPS]
    done = [e for e in events if e["kind"] == EVENT]
    steps = [e["step"] for e in done]
    return {"dispatched": len(began), "done": len(done),
            "in_order": steps == began[: len(steps)],
            "errors": sum("error" in e for e in done),
            "late": _spread([e["late_ms"] for e in done if "late_ms" in e])}


def _spread(values: list[float]) -> dict | None:
    if not values:
        return None
    values = sorted(values)
    return {"n": len(values), "median": statistics.median(values),
            "p95": values[min(len(values) - 1, int(0.95 * len(values)))],
            "max": values[-1]}


def against_trace(done: dict[str, list[dict]], offset_ns: float, lines) -> dict:
    """By step: each stamped program against the execution of its module
    whose end lies nearest its stamp, on the first device plane. `diff_ms` is
    the stamps' `device_ms` less the module's duration, `late_ms` the stamp
    less the module's end; a stamp farther from every end than half that
    module's duration is matched to none."""
    planes = sorted({p for p, ln, _ in lines if ln == xplane.MODULES})
    out = {}
    for step, events in done.items():
        module = rundir.MODULE_OF.get(PROGRAM_OF.get(step, ""))
        mods = sorted((start + dur, dur) for p, ln, evs in lines
                      if planes and p == planes[0] and ln == xplane.MODULES
                      for name, start, dur in evs if xplane.module_name(name) == module)
        if not mods:
            continue
        ends = [end for end, _ in mods]
        diff, late, trace_ms, stamped = [], [], [], []
        for e in events:
            at = e["at"] * 1e9 + offset_ns
            i = bisect.bisect_left(ends, at)
            j = min((k for k in (i - 1, i) if 0 <= k < len(mods)),
                    key=lambda k: abs(ends[k] - at))
            if abs(at - ends[j]) > mods[j][1] / 2:
                continue  # at the slice's edge: its execution is not in the trace
            diff.append(e["device_ms"] - mods[j][1] / 1e6)
            late.append((at - ends[j]) / 1e6)
            trace_ms.append(mods[j][1] / 1e6)
            stamped.append(e["device_ms"])
        if not diff:
            continue
        out[step] = {
            "programs": len(events), "matched": len(diff), "module": module,
            "device_ms": statistics.median(stamped),
            "trace_ms": statistics.median(trace_ms),
            "diff_ms": {"median": statistics.median(diff), **{
                k: v for k, v in _spread([abs(d) for d in diff]).items() if k != "median"}},
            "late_ms": _spread(late),
        }
    return out


GAP_EDGES_MS = (0.1, 1.0, 5.0, 20.0)


def missed_gaps(drained_ns, lines) -> dict | None:
    """What `drained_covers_idle_pct` misses, for the check of the stamps:
    the first device plane's idle gaps (between consecutive executions on its
    "XLA Modules" line) against the `device_drained` intervals `drained_ns`
    (on the profiler's clock, in time order), by the gap's length and by the
    modules on either side of it. Of a gap an interval covers in part, `head_s`
    is what lies before the interval (the stamp was taken after the program's
    end) and `tail_s` what lies behind it (from the dispatch's begin to the
    program's start on the device); `bare_s` is the gaps no interval touches
    (the dispatch found the device busy, or the gap is a launch between two
    queued programs). Seconds; None where the trace has no modules."""
    planes = sorted({p for p, ln, _ in lines if ln == xplane.MODULES})
    if not planes:
        return None
    mods = sorted((ev for p, ln, evs in lines if p == planes[0] and ln == xplane.MODULES
                   for ev in evs), key=lambda e: e[1])
    starts = [d0 for d0, _ in drained_ns]
    rows: dict[str, dict[str, dict]] = {"by_length_ms": {}, "by_neighbours": {}}
    end, before = None, None
    for name, start, dur in mods:
        if end is not None and start > end:
            covered, first, last = 0.0, None, None
            i = max(0, bisect.bisect_right(starts, end) - 1)
            while i < len(drained_ns) and drained_ns[i][0] < start:
                a, b = max(drained_ns[i][0], end), min(drained_ns[i][1], start)
                if b > a:
                    covered += b - a
                    first, last = (a if first is None else first), b
                i += 1
            gap = start - end
            edge = next((f"under {e}" for e in GAP_EDGES_MS if gap < e * 1e6),
                        f"{GAP_EDGES_MS[-1]} and over")
            pair = f"{xplane.module_name(before)} > {xplane.module_name(name)}"
            for table, key in ((rows["by_length_ms"], edge), (rows["by_neighbours"], pair)):
                row = table.setdefault(key, {"gaps": 0, "idle_s": 0.0, "covered_s": 0.0,
                                             "head_s": 0.0, "tail_s": 0.0, "bare_s": 0.0})
                row["gaps"] += 1
                row["idle_s"] += gap / 1e9
                row["covered_s"] += covered / 1e9
                if first is None:
                    row["bare_s"] += gap / 1e9
                else:
                    row["head_s"] += (first - end) / 1e9
                    row["tail_s"] += (start - last) / 1e9
        if end is None or start + dur > end:
            end, before = start + dur, name
    return rows


def check(run_dir: str) -> dict:
    """Everything the command prints of one run."""
    events = recorded(run_dir)
    span = used(run_dir)
    out = {"run_dir": run_dir, "accounted": accounted(events)}
    if span is None:
        return out
    out["used_s"] = span[1] - span[0]
    out["by_step"] = {
        step: {"programs": len(evs),
               "device_ms": _spread([e["device_ms"] for e in evs]),
               "queued_ms": _spread([e["queued_ms"] for e in evs]),
               "busy_s": sum(e["device_ms"] for e in evs) / 1e3,
               "dry_s": sum(e["dry_ms"] for e in evs) / 1e3}
        for step, evs in reduce(events, *span).items()}
    w = rundir.window(run_dir)
    idle, _ = hostspans.tables(run_dir)
    offset = idle and idle["clock"]["offset_ns"]
    if offset is not None:
        lines = xplane.load(os.path.join(run_dir, "profile"))
        out["traced"] = against_trace(
            reduce(events, w["trace_t0"], w["trace_t1"]), offset, lines)
        for step, row in out["traced"].items():  # the slice's mean a call, as the traced readers take it
            m = rundir.module_seconds(run_dir, PROGRAM_OF[step])
            row["digest_ms"] = m and 1e3 * m[0] / m[1]
        meta, spans = hostspans.timeline(run_dir)
        found = drained.scheduler_thread(spans) if spans else None
        if found:
            on_profiler = lambda us: (meta["epoch_monotonic"] + us / 1e6) * 1e9 + offset
            out["missed_gaps"] = missed_gaps(
                [(on_profiler(d0), on_profiler(d1)) for d0, d1, _ in found[1]], lines)
    return out


if __name__ == "__main__":
    for run in sys.argv[1:]:
        print(json.dumps(check(run)))
