"""Span timeline tracing: where inside the engine each millisecond went.

PR 2's request tracer answers "how did this request do" with ONE flat
record; this layer answers "where did its time GO" with a timeline of
sub-request spans — queue wait, admission chunks, ``kv_adopt``/
``kv_publish`` copies, decode-block dispatch vs. device completion, SSE
flushes — each tagged with the owning request and lane so the full
serving path of one request reconstructs from a single export.

Design constraints mirror the metrics registry:

* **Low overhead.** A span is two clock reads and one dict append under
  a short lock; with the tracker disabled ``begin`` returns ``None``
  after one attribute read and ``end(None)`` is a no-op, so the bench's
  obs on/off comparison toggles this layer together with the registry
  and the recorder.
* **One host clock, and the profiler's.** Spans stamp ``time.monotonic``,
  the flight recorder's clock: ``epoch_monotonic + t0`` of a span and
  ``t`` of a recorder event are one axis. Every span whose begin and end
  share a thread also opens a ``jax.profiler.TraceAnnotation``
  (``dllama.<component>.<name>``), so a profiler session holds it in the
  same ``.xplane.pb`` as the device's operations, on the profiler's
  clock; with no session active that is one atomic read.
* **Bounded memory.** Completed spans land in a ring; old spans fall
  off. Drops are themselves observable: the first drop (and then every
  ``capacity`` further drops) records an ``obs_overflow`` flight-recorder
  event.
* **Three exports.** :meth:`SpanTracker.chrome_trace` renders the ring (or
  one request's spans) as Chrome-trace / Perfetto JSON — ``pid`` is the
  component (scheduler / engine / kv / http), ``tid`` is the lane — and
  :meth:`SpanTracker.request_summary` folds one request's spans into a
  millisecond accounting ("TTFT = 480ms: 210 queue + 190 prefill-chunks
  + 45 adopt + 35 first block") plus a wall-time coverage fraction.
  ``GET /v1/debug/timeline`` serves both. ``--timeline-out`` streams:
  :meth:`SpanTracker.set_sink` appends each completed span to a file as
  one Chrome-trace event per line, whatever the ring has dropped since.

Threading: ``begin``/``end`` may run on different threads (the queue
span begins on the HTTP handler thread and ends on the scheduler
thread); a handle is mutated only by its ender and ``end`` is idempotent
(the first ender wins), so cross-thread handoff needs no lock beyond the
ring append. Such a span is begun with ``annotate=False``: an annotation
says what its thread was doing, and has to end where it began.

Nesting: every record carries an ``id``, the ``thread`` that began it
(``threading.get_ident()``) and ``parent``, the id of the span that was
open under it on that thread (``None`` at the top). The open spans of a
thread are a thread-local stack: ``begin`` pushes, ``end`` pops, and with
them what was begun above and never ended (an exception between a
``begin`` and its ``end``). A span begun with ``annotate=False`` says
nothing of what its thread is doing, so it never enters the stack and its
record has no ``parent`` key at all. A span's self time is then its
duration less its children's, from the timeline alone.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Callable, Iterator

from .recorder import get_recorder

try:
    from jax.profiler import TraceAnnotation
except ImportError:  # obs/ stays importable without JAX: spans, no annotations
    TraceAnnotation = None

DEFAULT_CAPACITY = 4096
# the streamed timeline's first event: its args anchor every span's `ts`
TIMELINE_EPOCH = "timeline_epoch"

# stable Chrome-trace pid per component (new components get the next id)
_COMPONENT_PIDS = {"scheduler": 1, "engine": 2, "kv": 3, "http": 4, "cli": 5}

# Replica attribution (ISSUE 19): the in-process fleet shares ONE global
# tracker across N replicas, so span records carry the replica that
# produced them. The tag is registered per-thread (scheduler loop + HTTP
# handler threads are replica-owned; engine compile/prefetch helpers stay
# untagged) and stamped at ``begin`` — a span that begins on a replica
# thread and ends elsewhere keeps its origin.
_thread_ctx = threading.local()


def set_thread_replica(tag: str | None) -> None:
    """Tag every span subsequently begun on THIS thread with a replica
    name (``None`` clears). Single-replica servers never call this and
    their span records are unchanged."""
    _thread_ctx.replica = tag


def get_thread_replica() -> str | None:
    return getattr(_thread_ctx, "replica", None)


def profiler_collecting() -> int:
    """1 while a profiler session collects annotations (and, with the
    profiler's defaults, every Python call of every thread): what a tick
    stamps on itself so that a reader can leave it out. One atomic read."""
    return int(TraceAnnotation is not None and TraceAnnotation.is_enabled())


_span_ids = itertools.count(1)


class _SpanHandle:
    """In-flight span state between ``begin`` and ``end``."""

    __slots__ = ("name", "component", "request_id", "lane", "t0", "attrs",
                 "replica", "annotation", "done", "id", "thread", "parent",
                 "stack")

    def __init__(self, name, component, request_id, lane, t0, attrs,
                 replica=None, annotation=None, stack=None):
        self.name = name
        self.component = component
        self.request_id = request_id
        self.lane = lane
        self.t0 = t0
        self.attrs = attrs
        self.replica = replica
        self.annotation = annotation
        self.done = False
        self.id = next(_span_ids)
        self.thread = threading.get_ident()
        # the beginning thread's open spans, this one on top; None for a
        # span that says nothing of what its thread is doing
        self.stack = stack
        self.parent = None
        if stack is not None:
            if stack:
                self.parent = stack[-1].id
            stack.append(self)


def _annotate(name, component, request_id, lane, attrs):
    """An entered ``TraceAnnotation`` carrying the span's integer and
    string attributes (what the profiler's stats can hold)."""
    args = {k: v for k, v in attrs.items() if isinstance(v, (int, str))}
    if request_id is not None:
        args["request_id"] = request_id
    if lane is not None:
        args["lane"] = lane
    annotation = TraceAnnotation(f"dllama.{component}.{name}", **args)
    annotation.__enter__()
    return annotation


def _write_events(sink, events: list[dict]) -> None:
    sink.write(
        "".join(json.dumps(ev, default=repr) + ",\n" for ev in events)
    )


class _ChromeEvents:
    """Span records -> Chrome-trace events: one complete ("X") event per
    span, pid = component, tid = lane (-1 = no lane), ts/dur in
    microseconds since the tracker epoch, and the process/thread name
    ("M") events the first time a pid or a (pid, tid) appears. `args`
    holds the span's attributes and its `id`, `thread` and `parent`."""

    def __init__(self, pid_prefix: str | None = None, pid_base: int = 0):
        self._pid_prefix = pid_prefix
        self._pid_base = pid_base
        self._seen_pids: set[int] = set()
        self._seen_tids: set[tuple[int, int]] = set()

    def events(self, s: dict) -> list[dict]:
        comp = s["component"]
        pid = _COMPONENT_PIDS.get(comp)
        if pid is None:
            pid = _COMPONENT_PIDS.setdefault(
                comp, max(_COMPONENT_PIDS.values()) + 1
            )
        pid += self._pid_base
        tid = s["lane"] if s["lane"] is not None else -1
        out = []
        if pid not in self._seen_pids:
            self._seen_pids.add(pid)
            out.append({
                "ph": "M", "pid": pid, "tid": 0,
                "name": "process_name",
                "args": {
                    "name": f"{self._pid_prefix}/{comp}"
                    if self._pid_prefix else comp
                },
            })
        if (pid, tid) not in self._seen_tids:
            self._seen_tids.add((pid, tid))
            out.append({
                "ph": "M", "pid": pid, "tid": tid,
                "name": "thread_name",
                "args": {"name": f"lane {tid}" if tid >= 0 else "no lane"},
            })
        args = {"request_id": s["request_id"], **(s.get("attrs") or {}),
                "id": s["id"], "thread": s["thread"]}
        if "parent" in s:
            args["parent"] = s["parent"]
        if s.get("replica") is not None:
            args["replica"] = s["replica"]
        out.append({
            "ph": "X",
            "pid": pid,
            "tid": tid,
            "ts": round(s["t0"] * 1e6, 3),
            "dur": round(s["dur_s"] * 1e6, 3),
            "name": s["name"],
            "args": args,
        })
        return out


class SpanTracker:
    """Thread-safe bounded ring of completed spans; see module docstring."""

    def __init__(
        self,
        capacity: int = DEFAULT_CAPACITY,
        enabled: bool | None = None,
        clock: Callable[[], float] = time.monotonic,
        wall_clock: Callable[[], float] = time.time,
        recorder: object | None = None,
    ) -> None:
        self.enabled = (
            enabled
            if enabled is not None
            else os.environ.get("DLLAMA_OBS", "1") != "0"
        )
        self.capacity = capacity
        self._clock = clock
        # all span t0s are seconds since this anchor, on the recorder's
        # clock: epoch_monotonic + t0 is a recorder event's `t`
        self.epoch_monotonic = self._epoch = clock()
        self.epoch_unix = wall_clock()
        self._ring: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._recorder = recorder
        self._total = 0
        self._dropped = 0
        # optional append-only file sink (--timeline-out on the server)
        self._sink_path: str | None = None
        self._sink = None
        self._sink_events: _ChromeEvents | None = None

    @property
    def recorder(self):
        if self._recorder is None:
            self._recorder = get_recorder()
        return self._recorder

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    # -- span lifecycle ----------------------------------------------------

    def begin(self, name: str, component: str = "engine",
              request_id: str | None = None, lane: int | None = None,
              annotate: bool = True, at: float | None = None,
              **attrs) -> _SpanHandle | None:
        """Open a span; returns an opaque handle (or None when disabled —
        ``end(None)`` no-ops, so call sites never branch).
        ``annotate=False`` is for a span that ends on another thread, or
        outlives the spans begun after it on its own: it opens no
        annotation and is nobody's parent. ``at`` is the
        caller's own reading of the tracker's clock (the engine's
        dispatch helper reads it once for all it feeds)."""
        if not self.enabled:
            return None
        annotation = stack = None
        if annotate:
            stack = getattr(_thread_ctx, "stack", None)
            if stack is None:
                stack = _thread_ctx.stack = []
            if TraceAnnotation is not None:
                annotation = _annotate(
                    name, component, request_id, lane, attrs
                )
        return _SpanHandle(
            name, component, request_id, lane,
            self._clock() if at is None else at, attrs or None,
            replica=get_thread_replica(), annotation=annotation, stack=stack,
        )

    def end(self, handle: _SpanHandle | None, at: float | None = None,
            **attrs) -> None:
        """Close a span and commit it to the ring; idempotent (a second
        end — e.g. an error path racing the normal one — no-ops)."""
        if handle is None or handle.done:
            return
        handle.done = True
        t1 = self._clock() if at is None else at
        if handle.annotation is not None:
            handle.annotation.__exit__(None, None, None)
        stack = handle.stack
        if stack:
            if stack[-1] is handle:
                stack.pop()
            elif handle in stack:  # with what was begun above it and never ended
                del stack[stack.index(handle):]
        if attrs:
            handle.attrs = {**(handle.attrs or {}), **attrs}
        rec = {
            "name": handle.name,
            "component": handle.component,
            "request_id": handle.request_id,
            "lane": handle.lane,
            "t0": handle.t0 - self._epoch,
            "dur_s": max(t1 - handle.t0, 0.0),
            "id": handle.id,
            "thread": handle.thread,
        }
        if stack is not None:
            rec["parent"] = handle.parent
        if handle.replica is not None:
            rec["replica"] = handle.replica
        if handle.attrs:
            rec["attrs"] = handle.attrs
        overflowed = False
        with self._lock:
            self._total += 1
            if len(self._ring) == self.capacity:
                self._dropped += 1
                # rate-limit the meta-event: first drop, then every
                # `capacity` further drops (a busy server overflows on
                # every span once the ring is full)
                overflowed = self._dropped % self.capacity == 1
            self._ring.append(rec)
            dropped = self._dropped
            if self._sink is not None:
                try:
                    _write_events(self._sink, self._sink_events.events(rec))
                except (ValueError, OSError) as e:
                    self._sink_failed(e)
        if overflowed:
            self.recorder.record(
                "obs_overflow", what="span_ring", capacity=self.capacity,
                dropped=dropped,
            )

    @contextmanager
    def span(self, name: str, component: str = "engine",
             request_id: str | None = None, lane: int | None = None,
             **attrs) -> Iterator[_SpanHandle]:
        """``with tracker.span("admission_chunk", ...):`` — the body is
        timed even when it raises (the error still took the time)."""
        handle = self.begin(name, component, request_id, lane, **attrs)
        try:
            yield handle
        finally:
            self.end(handle)

    # -- views -------------------------------------------------------------

    def completed(self, request_id: str | None = None,
                  replica: str | None = None) -> list[dict]:
        with self._lock:
            spans = list(self._ring)
        if request_id is not None:
            spans = [s for s in spans if s["request_id"] == request_id]
        if replica is not None:
            spans = [s for s in spans if s.get("replica") == replica]
        return spans

    @property
    def total_recorded(self) -> int:
        with self._lock:
            return self._total

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    # -- Chrome-trace / Perfetto export ------------------------------------

    def chrome_trace(self, request_id: str | None = None,
                     replica: str | None = None,
                     pid_prefix: str | None = None,
                     pid_base: int = 0) -> dict:
        """Chrome-trace JSON-object format (loadable by Perfetto and
        chrome://tracing): one complete ("X") event per span, pid =
        component, tid = lane (-1 = no lane), ts/dur in microseconds
        since the tracker epoch. Extra top-level keys (the per-request
        summary under "dllama") are legal metadata both viewers ignore.

        ``replica`` keeps only spans stamped with that replica tag (the
        in-process fleet shares one tracker). ``pid_prefix`` prefixes
        every process name and ``pid_base`` offsets every pid, so a fleet
        stitcher can merge N fragments without two replicas' identical
        component names/pids colliding in the viewer (ISSUE 19)."""
        spans = self.completed(request_id, replica)
        convert = _ChromeEvents(pid_prefix, pid_base)
        events = [ev for s in spans for ev in convert.events(s)]
        out = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "dllama": {
                "epoch_unix": self.epoch_unix,
                "epoch_monotonic": self.epoch_monotonic,
                "n_spans": len(spans),
                "dropped": self.dropped,
            },
        }
        if replica is not None:
            out["dllama"]["replica"] = replica
        if request_id is not None:
            out["dllama"]["request_id"] = request_id
            out["dllama"]["summary"] = self.request_summary(request_id)
        return out

    def export_file(self, path: str, request_id: str | None = None) -> int:
        """Write the ring's Chrome-trace JSON to ``path`` (the CLI's
        ``--timeline-out``, at the end of its one run); returns the span
        count. Serialization failures fall back to ``repr`` per value
        (same policy as the tracer sink)."""
        trace = self.chrome_trace(request_id)
        with open(path, "w") as f:
            f.write(json.dumps(trace, default=repr))
        return trace["dllama"]["n_spans"]

    def set_sink(self, path: str | None) -> None:
        """Stream to ``path`` (the server's ``--timeline-out``): every
        span completed from now on is appended once, as Chrome-trace
        events one per line in the JSON array form — ``[`` and the
        metadata event first, every line ending in a comma, no closing
        bracket (Perfetto and chrome://tracing load that as it stands;
        :func:`read_timeline` reads it back). Buffered like any file, not
        by line: a write is a system call that hands the interpreter lock
        to another thread, and the scheduler thread ends a hundred spans
        a second. ``None`` closes the sink, and writes out what the
        buffer still holds."""
        with self._lock:
            if self._sink is not None:
                try:
                    self._sink.close()
                except OSError as e:  # the last buffer did not reach the disk
                    self._sink_failed(e)
            self._sink, self._sink_path = None, path
            if path is None:
                return
            self._sink = open(path, "w")
            self._sink_events = _ChromeEvents()
            self._sink.write("[")
            _write_events(self._sink, [{
                "ph": "M", "pid": 0, "tid": 0, "name": TIMELINE_EPOCH,
                "args": {
                    "epoch_unix": self.epoch_unix,
                    "epoch_monotonic": self.epoch_monotonic,
                },
            }])

    def flush(self) -> None:
        """Write out what the sink's buffer holds (a drained server is
        safe to kill)."""
        with self._lock:
            if self._sink is not None:
                try:
                    self._sink.flush()
                except (ValueError, OSError) as e:
                    self._sink_failed(e)

    def _sink_failed(self, error: Exception) -> None:
        """(Lock held.) Drop a closed or broken sink: the ring lives on,
        and the failure is itself observable."""
        path, self._sink = self._sink_path, None  # dlint: disable=guarded-attrs — every caller holds self._lock
        self.recorder.record(
            "obs_sink_error", what="timeline", path=path, error=str(error),
        )

    # -- per-request millisecond accounting --------------------------------

    def request_summary(self, request_id: str) -> dict:
        """Fold one request's spans into per-phase totals and shares plus
        a wall-time coverage fraction (union of span intervals / first
        span start -> last span end). The ≥95%-coverage acceptance bar
        lives on this number: every serving phase is spanned, so the only
        uncovered time is scheduler-tick bookkeeping between spans."""
        spans = self.completed(request_id)
        if not spans:
            return {"request_id": request_id, "n_spans": 0, "phases": {},
                    "wall_ms": 0.0, "coverage": None}
        intervals = sorted(
            (s["t0"], s["t0"] + s["dur_s"]) for s in spans
        )
        wall_t0 = intervals[0][0]
        wall_t1 = max(t1 for _, t1 in intervals)
        wall = max(wall_t1 - wall_t0, 0.0)
        covered = 0.0
        cur0, cur1 = intervals[0]
        for t0, t1 in intervals[1:]:
            if t0 > cur1:
                covered += cur1 - cur0
                cur0, cur1 = t0, t1
            else:
                cur1 = max(cur1, t1)
        covered += cur1 - cur0
        phases: dict[str, dict] = {}
        for s in spans:
            ph = phases.setdefault(
                s["name"], {"n": 0, "total_ms": 0.0, "share": 0.0}
            )
            ph["n"] += 1
            ph["total_ms"] += s["dur_s"] * 1000.0
        for ph in phases.values():
            ph["total_ms"] = round(ph["total_ms"], 3)
            ph["share"] = (
                round(ph["total_ms"] / (wall * 1000.0), 4) if wall else None
            )
        return {
            "request_id": request_id,
            "n_spans": len(spans),
            "wall_ms": round(wall * 1000.0, 3),
            "covered_ms": round(covered * 1000.0, 3),
            "coverage": round(covered / wall, 4) if wall else None,
            "phases": dict(sorted(phases.items())),
        }


_DEFAULT = SpanTracker(
    capacity=int(os.environ.get("DLLAMA_SPAN_CAPACITY",
                                str(DEFAULT_CAPACITY))),
)


def get_span_tracker() -> SpanTracker:
    """The process-wide default span tracker (shared by the engine, the
    lane scheduler, the KV manager and ``/v1/debug/timeline``)."""
    return _DEFAULT


def read_timeline(path: str) -> tuple[dict, list[dict]]:
    """Load a streamed ``--timeline-out`` file back: the metadata event's
    ``args`` (``epoch_unix``, ``epoch_monotonic``) and the span ("X")
    events, ``ts``/``dur`` in microseconds since that epoch. The file of
    a server that still runs may end inside a line, which is left out."""
    meta, spans = {}, []
    with open(path) as f:
        for line in f:
            if not line.endswith(",\n"):
                break
            ev = json.loads(line.lstrip("[")[:-2])
            if ev["ph"] == "X":
                spans.append(ev)
            elif ev["name"] == TIMELINE_EPOCH:
                meta = ev["args"]
    return meta, spans
