"""OpenAI-compatible HTTP API server.

Capability port of the reference's `dllama-api` (src/dllama-api.cpp):

* ``POST /v1/chat/completions`` — chat completion with ``stream`` (SSE),
  ``temperature``, ``seed``, ``max_tokens``, ``stop`` parameters
  (src/dllama-api.cpp:491-520);
* ``GET /v1/models`` — single-model listing (src/dllama-api.cpp:538-547);
* ``GET /metrics`` — Prometheus text exposition of the serving/engine
  metrics (obs/metrics.py; see docs/serving_metrics.md);
* ``GET /v1/health`` — model name, lane occupancy, queue depth, uptime;
* **NaiveCache** — on the serialized (batch_size == 1) path, KV positions
  are reused when a new request's messages are a strict superset of the
  previous conversation (src/dllama-api.cpp:298-343);
* ``GET /v1/debug/kv`` — paged-KV pool / radix-tree introspection
  (lane-scheduler path);
* ``GET /v1/debug/timeline`` — Chrome-trace/Perfetto span timeline
  (``?request_id=`` narrows to one request and adds its millisecond
  accounting; obs/spans.py);
* ``GET /v1/debug/slo`` — windowed SLO attainment / goodput snapshot
  (obs/slo.py);
* ``GET /v1/debug/series`` — in-process metrics time-series
  (obs/timeseries.py; ``?name=&window=`` for points, bare for the index);
* ``GET /v1/debug/xlalint`` — compiled-program lint over the live
  compile cache (analysis/xlalint.py; docs/static_analysis.md);
* ``GET /dashboard`` — zero-dependency live dashboard, a single
  self-contained HTML page of canvas sparklines (obs/dashboard.py);
* ``POST /v1/debug/profile`` — on-demand ``jax.profiler`` capture
  ({"seconds": 2.0}; hardened, CPU-safe; 409 while one runs).

``/v1/health`` reports ``status: degraded`` while the engine watchdog
(obs/watchdog.py) detects a stall OR the anomaly monitor
(obs/anomaly.py) has an active signal; ``degraded_reasons`` lists every
contributing source.

The reference hand-rolls an HTTP/1.1 server over raw sockets; here Python's
stdlib ThreadingHTTPServer carries the protocol. With a batch_size == 1
engine a lock serializes model access (the reference's single-threaded
accept loop, same effective policy); with batch_size > 1 a LaneScheduler
serves requests CONCURRENTLY over the engine's batch lanes — per-lane
parked prefill admits new requests while other conversations stream, a
capability the reference does not have. On the lane path, prompt-prefix
reuse is CROSS-LANE: a PagedKVManager (kv/manager.py) matches every
admission against a shared radix tree of previously served prefixes and
adopts the covering pool pages into the lane, so a system prompt fanned
out over N streams is prefilled and stored once.
"""

from __future__ import annotations

import json
import queue
import threading
import time
import uuid
from dataclasses import dataclass, field
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs

from ..analysis.lockwatch import make_condition, make_lock
from ..obs.anomaly import AnomalyMonitor, build_default_rules
from ..obs.dashboard import DASHBOARD_CONTENT_TYPE, render_dashboard
from ..obs.device import compare_with_analytic, sample_device_memory
from ..obs.metrics import DEFAULT_TOKEN_BUCKETS_S, get_registry
from ..obs.recorder import get_recorder
from ..obs.slo import SloTracker
from ..obs.spans import (
    get_span_tracker,
    profiler_collecting,
    set_thread_replica,
)
from ..obs.timeseries import (
    MetricsSampler,
    SeriesStore,
    resolve_series_knobs,
)
from ..obs.trace import NULL_SPAN, Tracer
from ..obs.watchdog import EngineWatchdog, resolve_watchdog_knobs
from ..tokenizer import (
    CHAT_TEMPLATE_NAMES,
    ChatItem,
    ChatTemplateGenerator,
    ChatTemplateType,
    EosDetector,
    EosResult,
    Tokenizer,
)
from .admission import (
    LoadPredictor,
    OccupancySnapshot,
    effective_deadline_ms,
)
from .engine import InferenceEngine
from .faults import get_fault_plane, set_fault_plane
from .spec import (
    DEFAULT_SPEC_K,
    SOURCE_DRAFT,
    SPEC_MODES,
    NgramDrafter,
    SharedNgramStore,
    bucket_for,
    spec_buckets,
)


@dataclass
class ChatMessage:
    role: str
    content: str


@dataclass
class NaiveCacheItem:
    end_pos: int
    message: ChatMessage


class NaiveCache:
    """Prompt-prefix KV reuse (reference: src/dllama-api.cpp:298-343)."""

    def __init__(self):
        self.items: list[NaiveCacheItem] = []

    def push(self, item: NaiveCacheItem) -> None:
        self.items.append(item)

    def clear(self) -> None:
        self.items = []

    def _matches_all(self, messages: list[ChatMessage]) -> bool:
        """True when `messages` strictly extends the cached conversation
        (single source of the match rule for probe and resolve)."""
        n = len(self.items)
        if n == 0 or len(messages) <= n:
            return False
        return all(
            self.items[i].message.role == messages[i].role
            and self.items[i].message.content == messages[i].content
            for i in range(n)
        )

    def probe(self, messages: list[ChatMessage]) -> int:
        """Start position a resolve would reuse, WITHOUT mutating — the
        lane scheduler peeks at every free lane's cache to route a
        continuing conversation back to its lane."""
        if self._matches_all(messages):
            return self.items[-1].end_pos
        return 0

    def resolve_delta_prompt(
        self, messages: list[ChatMessage]
    ) -> tuple[list[ChatMessage], int]:
        """If `messages` extends the cached conversation, return only the new
        suffix plus the cache's end position; else reset."""
        if not self.items:
            return messages, 0
        if self._matches_all(messages):
            n = len(self.items)
            start_pos = self.items[-1].end_pos
            print(f"🐤 Found naive cache for {n} messages, pos={start_pos}")
            return messages[n:], start_pos
        self.clear()
        return messages, 0


@dataclass
class InferenceParams:
    messages: list[ChatMessage] = field(default_factory=list)
    temperature: float = 0.8
    top_p: float = 0.9
    seed: int | None = None
    stream: bool = False
    max_tokens: int = -1
    stop: list[str] = field(default_factory=list)
    # admission priority class for load shedding (docs/resilience.md):
    # under queue pressure or a degraded engine, "low" sheds first,
    # "high" last — the reason-tagged 429/503 + Retry-After path
    priority: str = "normal"
    # fleet failover (docs/fleet.md): a raw token history replaces the
    # chat template + tokenizer entirely — the router re-issues a dead
    # replica's stream with prompt + already-emitted tokens, and the
    # recovery-admission path (radix re-match + chunked re-prefill)
    # continues it byte-identically (greedy). Lane scheduler only.
    resume_tokens: list[int] | None = None
    # attribute each SSE delta with the exact generated token ids and
    # their raw decoded piece text (dllama_tokens / dllama_piece chunk
    # fields) so a router can reconstruct the token history mid-stream
    include_tokens: bool = False
    # fleet trace propagation (ISSUE 19): the router mints a trace id +
    # request id per client request and forwards them as x-dllama-trace /
    # x-dllama-request on every relay INCLUDING failover re-issues;
    # admission adopts them so replica spans, recorder events and trace
    # JSONL carry fleet-level identity. None outside a fleet.
    trace_id: str | None = None
    request_id: str | None = None
    # predictive admission (ISSUE 20): optional per-request latency
    # budgets. deadline_ms bounds the WHOLE completion, ttft_budget_ms
    # just the first token; either makes the request "hinted" — the
    # predictive controller may infeasible-reject it up front and EDF
    # orders it by its effective deadline. The fleet router forwards
    # x-dllama-deadline-ms so a budget survives relays and failovers.
    deadline_ms: float | None = None
    ttft_budget_ms: float | None = None

    @property
    def deadline_hinted(self) -> bool:
        return self.deadline_ms is not None or self.ttft_budget_ms is not None


class LaneJob:
    """One admitted request: the scheduler thread produces events, the
    HTTP handler thread consumes them. Events: ("delta", str),
    ("done", finish_reason), ("error", message). The handler sets
    `cancelled` when the client disconnects; the scheduler then frees the
    lane instead of decoding to max_pos for nobody."""

    def __init__(self, params: InferenceParams):
        self.params = params
        self.events: queue.Queue = queue.Queue()
        self.n_prompt_tokens = 0
        self.n_completion = 0
        self.buffer = ""
        self.cancelled = False
        # lifecycle span (obs/trace.py): submit() swaps in a live one; the
        # scheduler marks admit/first-token/finish, the handler reads the
        # derived metadata for the response
        self.span = NULL_SPAN
        # timeline queue span (obs/spans.py): begun at submit on the
        # handler thread, ended by the scheduler when admission starts
        self.queue_span = None
        # predictive admission (ISSUE 20): the EDF sort key (set at
        # submit from the deadline hints / priority offsets) and the
        # forecast recorded at admission start for error tracking —
        # _finish compares it against the observed TTFT/TPOT and folds
        # the ratio back into the LoadPredictor's EWMA correction
        self.edf_deadline_ms: float | None = None
        self.submit_t: float | None = None
        self.predicted_ttft_ms: float | None = None
        self.predicted_tpot_ms: float | None = None


@dataclass
class _LaneState:
    job: LaneJob
    pos: int
    token: int
    max_pos: int
    detector: EosDetector
    decoder: object  # tokenizer StreamDecoder
    temperature: float
    top_p: float
    seed: int | None = None  # per-lane sampled-stream reproducibility
    # every token FED to the engine so far (prompt + generated, in feed
    # order). KV rows [0, pos) hold exactly history[:pos]; the final entry
    # is the pending token whose row is written by the next decode step.
    # _finish publishes history[:pos] into the shared page pool.
    history: list = field(default_factory=list)
    # include_tokens attribution: (token id, raw piece text) consumed
    # since the last flushed delta. The EOS detector's holdback means a
    # flushed delta's TEXT can lag the consumed tokens; the tape carries
    # the exact ids + piece text so each delta event reports both, and a
    # fleet router can rebuild the full token history at any flush point
    tape: list = field(default_factory=list)
    # timeline span covering the lane's whole decode stretch (admission
    # done -> finish); the request-attributed backbone of the timeline
    decode_span: object = None
    # warm-start carry (runtime/spec.py): a park/recovery stashes the
    # lane's NgramDrafter here so the resume reinstalls it — learned
    # AIMD k, private n-gram index, and shared-store publish cursor all
    # survive instead of paying a cold-start acceptance dip
    drafter: object = None


@dataclass
class _AdmittingLane:
    """A request mid-admission: its prompt prefills one bounded chunk per
    scheduler tick (interleaved with decode blocks for the active lanes)
    instead of one monolithic prefill_lane call that freezes every other
    stream for the whole prompt. Everything the old _admit computed before
    touching the engine lives here, held across loop iterations until the
    last fill token lands and the lane flips to a _LaneState."""

    job: LaneJob
    tokens: list[int]  # full conversation prompt, pending token included
    pos0: int
    cursor: int  # fill tokens already in the lane's cache (adopted rows
    # count: the chunked prefill starts at the radix-match point, or for a
    # model with lane state `engine.state_replay_rows` before it)
    prompt_end: int
    max_pos: int
    public_prompt: str
    start_pos: int  # reused (adopted) prefix length, 0 = fresh prefill
    adopt_pages: list = field(default_factory=list)  # pool pages to copy in
    adopted: bool = False  # the adopt dispatch ran (it is its own tick)
    n_chunks: int = 0
    prefill_s: float = 0.0  # chunk dispatch time only, decode excluded
    # crash recovery (PR 12): when set, this admission is a poisoned
    # lane's resume — `tokens` is the lane's full fed history and
    # _finish_admission reinstalls this preserved _LaneState (decoder,
    # detector, counts) instead of building a fresh one, so the client's
    # stream continues byte-identically after the re-prefill
    resume_state: "_LaneState | None" = None
    # oversubscription (PR 16): this admission resumes a PARKED stream —
    # same reinstall contract as a crash-recovery resume, but the park
    # was voluntary (scheduler made room for a queued request), so it
    # gets its own recorder event + metrics instead of "lane_recovered"
    from_park: bool = False


@dataclass
class _BlockInFlight:
    """A decode block the scheduler dispatched and has not collected."""

    block: object  # the engine's handle (`LaneBlock`)
    # the _LaneState each live lane ran for, None elsewhere: a lane whose
    # stream ended or changed since has its rows dropped at the collect
    states: list


class LaneScheduler:
    """Continuous-batching loop over the engine's batch lanes.

    A central thread owns ALL engine calls: it admits pending requests
    into free lanes (per-lane parked prefill keeps the other lanes'
    caches intact) and steps every active lane together in shared decode
    blocks, each lane at its own position with its own sampling settings.
    This is the concurrency surface the reference's single-threaded
    accept loop (src/dllama-api.cpp:563-574) lacks entirely: N clients
    stream simultaneously at roughly the single-stream decode rate.

    Prompt-prefix reuse is CROSS-LANE and shared (PR6, replacing the
    per-lane NaiveCaches): every admission retokenizes the full
    conversation and matches it against the PagedKVManager's radix tree
    of previously served token prefixes. Matched pool pages are adopted
    (device-copied) into the lane and only the unmatched suffix runs
    through the chunked prefill; on finish, the lane's fed history is
    published back into the pool, deduplicated against the tree so a
    prefix N streams share is physically stored once. Any free lane can
    serve any conversation — affinity routing is gone because the prefix
    store is no longer trapped in lane-local KV.
    """

    def __init__(
        self,
        state: "ApiState",
        block_size: int = 8,
        admission_chunk: int = 0,
        speculation: str = "off",
        spec_k: int = DEFAULT_SPEC_K,
        max_streams: int = 0,
    ):
        self.state = state
        self.engine = state.engine
        self.block_size = max(1, int(block_size))
        # oversubscription (PR 16): admit up to max_streams concurrent
        # streams over batch_size lanes by parking/resuming (0 = off).
        # Parking needs the shared pool to hold the parked KV, so the
        # knob is inert when kv sharing is disabled.
        self.max_streams = max(0, int(max_streams))
        # tokens generated since the lane was last (re)admitted: a park
        # victim must have decoded at least one full block since, so a
        # pathological queue can't thrash park/resume without progress
        self._progress: list[int] = [0] * state.engine.batch_size
        self._n_parked = 0
        # speculation mode ladder (runtime/spec.py): greedy lanes draft
        # from their own context — plus, cumulatively, every sibling's
        # published continuation ("shared") and a resident draft model
        # ("draft") — and verify k tokens per dispatch; "off" is a pure
        # bypass (no drafters, no store, no verify/draft programs)
        self.spec_mode = speculation
        self.spec_on = speculation != "off"
        # verify rows are 1 + k wide and parked lanes write them into
        # the padding rows, so k is capped by the lane padding
        self.spec_k = max(1, min(int(spec_k), self.engine._lane_pad - 1))
        self.spec_buckets = spec_buckets(self.spec_k)
        self.drafters: dict[int, NgramDrafter] = {}
        # cross-lane shared n-gram store, keyed by radix anchors: only
        # meaningful with the KV manager on (no manager -> no anchors ->
        # drafters degrade to private-ngram behavior, store stays empty)
        self.spec_store = (
            SharedNgramStore()
            if speculation in ("shared", "draft")
            else None
        )
        # resident-draft-model catch-up cursors: rows [0, _draft_pos[l])
        # of the draft cache hold lane l's verified history prefix; the
        # epoch snapshot detects a rebuilt draft cache (cursors reset)
        self._draft_pos: dict[int, int] = {}
        self._draft_epoch = getattr(state.engine, "draft_cache_epoch", 0)
        # lane -> (position, k) of this tick's draft-model propose, so
        # the verify outcome can advance the catch-up cursor past the
        # accepted rows instead of re-feeding them
        self._draft_fed: dict[int, tuple[int, int]] = {}
        # admission chunk budget: at most this many prompt tokens prefill
        # per scheduler tick (0 = the largest prefill bucket), so the
        # worst-case inter-token gap an active stream sees is one chunk +
        # one decode block, never one full prefill
        self.admission_chunk = (
            int(admission_chunk)
            if admission_chunk
            else max(self.engine.prefill_buckets)
        )
        self.lanes: list[_LaneState | None] = [None] * self.engine.batch_size
        # shared paged-KV pool + radix prefix tree (None = sharing off)
        self.kv = state.kv_manager
        # admission counter per lane: fresh admissions prefer the
        # least-recently-used free lane (keeps a rough spread for the
        # flight recorder; no KV state rides on the choice anymore)
        self.lane_used: list[int] = [0] * self.engine.batch_size
        self._admission_count = 0
        # lanes mid-admission (resumable chunked prefill state machine)
        self.admitting: dict[int, _AdmittingLane] = {}
        self._rr = -1  # round-robin cursor over concurrently admitting lanes: a tick's lead
        # decode blocks owed before the next chunk program: one a rider of
        # the last, where a rider added rows to it (`_admission_tick`)
        self._chunk_debt = 0
        # injectable clock for the stall/prefill accounting (fake-clock
        # scheduler tests replace it; production uses the monotonic timer)
        self._clock = time.perf_counter
        self._last_decode_end: float | None = None
        # the decode loop runs one block ahead: the block dispatched and
        # not yet collected, and why the last one was collected before
        # its successor was dispatched (the next block's `reason`)
        self._flight: _BlockInFlight | None = None
        self._drained_why: str | None = None
        self._n_pending = 0  # requests left waiting after the tick's admissions
        # transient-dispatch retry policy (the state's knobs):
        # attempts after the first failure, exponential backoff base.
        # _sleep is injectable so chaos tests don't pay real backoff.
        self.retry_max = int(getattr(state, "retry_max", 3))
        self.retry_backoff_s = (
            int(getattr(state, "retry_backoff_ms", 5)) / 1000.0
        )
        self._sleep = time.sleep
        self.pending: list[LaneJob] = []
        self.cv = make_condition("sched.cv")
        self._stop = False
        # build the admission-path programs (every prefill bucket + the
        # decode block + the speculative verify buckets) off-thread NOW,
        # so the first admission under load doesn't pay a synchronous
        # compile stall
        self.engine.rehearse_admission(
            self.block_size, spec_k=self.spec_k if self.spec_on else 0
        )
        self.thread = threading.Thread(
            target=self._loop, daemon=True, name="dllama-scheduler"
        )
        self.thread.start()

    def stop(self, timeout: float = 5.0) -> None:
        """Stop the scheduler thread (idempotent; used by server close and
        by tests that churn many servers in one process)."""
        with self.cv:
            self._stop = True
            self.cv.notify_all()
        if self.thread.is_alive():
            self.thread.join(timeout=timeout)

    def submit(self, params: InferenceParams) -> LaneJob:
        job = LaneJob(params)
        # EDF sort key (ISSUE 20): hints win; otherwise the priority
        # ladder becomes deadline offsets, so with no hints the pick
        # order is (priority class, arrival) — the PR 12 contract
        job.submit_t = self._clock()
        job.edf_deadline_ms = effective_deadline_ms(
            job.submit_t * 1000.0,
            priority=params.priority,
            deadline_ms=params.deadline_ms,
            ttft_budget_ms=params.ttft_budget_ms,
            default_ms=self.state.deadline_default_ms,
            priority_step_ms=self.state.deadline_priority_step_ms,
        )
        # adopt router-propagated identity when present: the span's
        # request id (and thus every timeline span keyed on it) is the
        # FLEET request id, so a failover's two half-timelines share it
        job.span = self.state.tracer.span(
            request_id=params.request_id, path="lanes",
            trace_id=params.trace_id,
        )
        # queue span: begins here on the handler thread, ends on the
        # scheduler thread once admission work (tokenize + radix match)
        # is done — so timeline "queue" covers wait AND admission setup
        job.queue_span = self.state.spans.begin(
            "queue", component="scheduler", request_id=job.span.request_id,
            annotate=False,  # ends on the scheduler thread
        )
        with self.cv:
            self.pending.append(job)
            self.state.m_queue_depth.set(len(self.pending))
            self.cv.notify()
        return job

    def _set_lane_gauge(self) -> None:
        self.state.m_lanes_active.set(
            sum(1 for ls in self.lanes if ls is not None)
        )

    def occupancy(self) -> OccupancySnapshot:
        """Dynamic load snapshot for the LoadPredictor: the engine's
        occupancy() contributes the static shape, this overlays active
        lanes / admitting chunks / parked streams / queue depth. Takes
        the scheduler cv briefly so the queue-depth read is consistent
        with the lane fields; callable from any thread (the scheduler
        itself only calls it outside its cv block)."""
        chunk = max(1, self.admission_chunk)
        with self.cv:
            active = sum(1 for ls in self.lanes if ls is not None)
            admitting = list(self.admitting.values())
            queue_depth = len(self.pending)
            parked = self._n_parked
        chunks_left = 0
        for adm in admitting:
            todo = max(0, adm.prompt_end - adm.cursor)
            chunks_left += max(1, -(-todo // chunk))
        return OccupancySnapshot(
            lanes_total=len(self.lanes),
            active_lanes=active,
            parked=parked,
            admitting=len(admitting),
            admitting_chunks=chunks_left,
            queue_depth=queue_depth,
            block_size=self.block_size,
            admission_chunk=chunk,
        )

    # -- failure classification + recovery (PR 12) -------------------------

    def _retry_dispatch(self, what: str, fn, failed: Exception | None = None):
        """Bounded exponential-backoff retry for engine dispatches whose
        failure left the donated buffers intact: the cache epoch did not
        move, so the guard never fired, lane KV is exactly as it was
        before the call, and re-issuing the dispatch is safe and
        idempotent. A failure that DID move the epoch re-raises
        immediately — retrying against the rebuilt (zeroed) cache would
        decode garbage; the caller's recovery path owns that class.
        ``failed`` is a first attempt the caller made itself (a block
        dispatched ahead) that raised on an intact cache."""
        attempt = 0
        while True:
            epoch = self.engine.cache_epoch
            try:
                if failed is not None:
                    e, failed = failed, None
                    raise e
                return fn()
            except Exception as e:
                if (
                    self.engine.cache_epoch != epoch
                    or attempt >= self.retry_max
                ):
                    raise
                attempt += 1
                self.state.m_dispatch_retries.inc()
                self.state.recorder.record(
                    "dispatch_retry", step=what, attempt=attempt,
                    error=str(e), error_type=type(e).__name__,
                )
                self._sleep(self.retry_backoff_s * (2 ** (attempt - 1)))

    def _fail_active(self, lane: int, err: dict) -> None:
        """Error out one ACTIVE lane's request with a structured payload
        and free the lane (no publish: its slab KV is not trustworthy on
        any path that reaches here)."""
        ls = self.lanes[lane]
        self.state.spans.end(ls.decode_span, error=err["message"])
        ls.job.events.put(("error", err))
        if ls.job.span.finish(
            "error", n_completion=ls.job.n_completion
        ) is not None:
            self.state.m_finished.labels(reason="error").inc()
        self.lanes[lane] = None
        self.drafters.pop(lane, None)
        self._draft_pos.pop(lane, None)
        if self.kv is not None:
            self.kv.release_lane(lane)

    def _fail_admitting(self, lane: int, err: dict) -> None:
        """Error out one MID-ADMISSION request with a structured payload,
        releasing its adopted-page retains (satellite-audited leak path:
        every drop route must pop self.admitting AND release the lane)."""
        adm = self.admitting.pop(lane, None)
        if adm is None:
            return
        if adm.from_park:
            # a parked stream that failed to resume is parked no more
            self._n_parked -= 1
            self.state.m_streams_parked.set(self._n_parked)
        if adm.resume_state is not None:
            # a recovery resume that failed again: the original stream's
            # decode span is still open — close it with the error
            self.state.spans.end(
                adm.resume_state.decode_span, error=err["message"]
            )
        adm.job.events.put(("error", err))
        if adm.job.span.finish(
            "error", n_completion=adm.job.n_completion
        ) is not None:
            self.state.m_finished.labels(reason="error").inc()
        self.drafters.pop(lane, None)
        self._draft_pos.pop(lane, None)
        if self.kv is not None:
            self.kv.release_lane(lane)

    def _drop_all(self, e: Exception) -> None:
        """Retries exhausted on an intact cache (or recovery itself is
        impossible): fail every in-flight request with a structured
        RETRYABLE error and keep the scheduler thread alive — the
        pre-PR 12 behavior, now with clients told to come back."""
        err = {"message": str(e), "retryable": True}
        self._discard_flight()
        for lane in range(len(self.lanes)):
            if self.lanes[lane] is not None:
                self._fail_active(lane, err)
        # iterate the dict, not range(len(lanes)): an admitting lane is
        # exactly the kind of entry a lanes-indexed loop can miss
        for lane in list(self.admitting):
            self._fail_admitting(lane, err)
        if self.kv is not None:
            # belt and suspenders after the per-lane releases: no retain
            # may survive a drop-all (pool pages themselves are NOT
            # donated by decode/prefill, so stored prefixes stay valid)
            self.kv.release_all_lanes()
        self.drafters.clear()
        self._draft_pos.clear()
        self._set_lane_gauge()

    def _recover(self, e: Exception, culprit: int | None) -> None:
        """A poisoning failure rebuilt the donated cache: every lane's
        slab KV is zeroed, but the shared page pool is NOT (dispatches
        never donate it), so each surviving lane's state is recoverable
        from host-side truth. Active lanes flip back to _AdmittingLane
        resumes: radix re-match their fed history against the pool
        (published prefixes adopt back in; only the unpublished suffix
        re-prefills, chunked as usual) and the preserved _LaneState is
        reinstalled on completion — the client's stream continues
        byte-identically, never seeing the fault. Mid-admission lanes
        rewind their chunk cursor to the adopted prefix (their page
        retains survived). Only ``culprit`` — the lane whose own
        admission dispatch poisoned the cache — gets a structured
        retryable error."""
        err = {"message": str(e), "retryable": True}
        # a block still in flight decoded from the cache that went, for
        # lane states that resume from their histories: dropped un-read
        self._discard_flight()
        native = self.kv is not None and getattr(self.kv, "native", False)
        if native:
            # pool-native lanes decode straight out of the pool; the
            # guard that moved the epoch rebuilt the POOL buffer too, so
            # every page id (lane retains, radix entries, mid-admission
            # adopt lists) points into dead memory — reset the host
            # accounting to match (reset_device=False: the dispatch
            # guard already rebuilt the buffer)
            self.kv.reset(reset_device=False)
        n_resumed = 0
        for lane in list(self.admitting):
            adm = self.admitting[lane]
            if lane == culprit:
                self._fail_admitting(lane, err)
                continue
            # the partial prefill died with the cache; the adopt copy
            # must re-run too (it targeted the old buffer)
            adm.cursor = self._prefill_start(adm.start_pos)
            adm.adopted = False
            if native:
                # the adopted prefix's pages died with the pool: this
                # admission restarts from position 0 with a fresh page
                # allocation on its re-run adopt tick
                adm.cursor = 0
                adm.start_pos = 0
                adm.adopt_pages = []
        for lane in range(len(self.lanes)):
            ls = self.lanes[lane]
            if ls is None:
                continue
            if lane == culprit:
                self._fail_active(lane, err)
                continue
            if ls.job.cancelled:
                # no client to resume for; _finish("cancelled") publishes
                # nothing (the slab KV backing the history is garbage)
                self._finish(lane, "cancelled")
                continue
            self.lanes[lane] = None
            # warm-start: the drafter rides the preserved state through
            # the recovery re-admission (its index/AIMD k are host-side
            # truth the crash never touched); _finish_admission rebinds
            # it to the re-matched radix anchor and reinstalls it
            ls.drafter = self.drafters.pop(lane, None)
            self._draft_pos.pop(lane, None)
            start_pos, pages = 0, []
            if self.kv is not None:
                start_pos, pages = self._match_prefix(lane, ls.history)
            self.admitting[lane] = _AdmittingLane(
                job=ls.job,
                tokens=list(ls.history),
                pos0=0,
                cursor=self._prefill_start(start_pos),
                prompt_end=len(ls.history) - 1,
                max_pos=ls.max_pos,
                public_prompt="",
                start_pos=start_pos,
                adopt_pages=pages,
                resume_state=ls,
            )
            n_resumed += 1
        self.state.recorder.record(
            "lane_recovery", error=str(e), error_type=type(e).__name__,
            culprit=culprit, n_resumed=n_resumed,
            n_admitting=len(self.admitting),
        )
        self._set_lane_gauge()
        with self.cv:
            self.cv.notify_all()

    # -- scheduler thread --------------------------------------------------

    def _loop(self) -> None:
        if self.state.replica_id is not None:
            # the scheduler thread is replica-owned for its lifetime:
            # every span it begins (admission, decode, publish, park)
            # carries the replica tag (obs/spans.py, ISSUE 19)
            set_thread_replica(self.state.replica_id)
        while True:
            with self.cv:
                while (
                    not self._stop
                    and not self.pending
                    and not any(self.lanes)
                    and not self.admitting
                    and self._flight is None
                ):
                    # going idle is not a stall: without this the last
                    # beat still says "lanes active" and an idle server
                    # turns scheduler-stalled after the timeout
                    if self.state.watchdog is not None:
                        self.state.watchdog.beat()
                    with self.state.spans.span(
                        "sched_wait", component="scheduler"
                    ):
                        self.cv.wait()
                if self._stop:
                    return
                admissions = []
                free = [
                    i
                    for i in range(len(self.lanes))
                    if self.lanes[i] is None and i not in self.admitting
                ]
                while self.pending and free:
                    # EDF pick (ISSUE 20, predictive mode): earliest
                    # effective deadline first, queue order breaking
                    # ties — priorityless no-hint traffic degenerates to
                    # FIFO. Predictive off keeps the PR 12 pop(0).
                    # Objects without an edf key (tests inject opaque
                    # queue fillers) sort last instead of crashing.
                    idx = 0
                    if self.state.admission_predict:
                        idx = min(
                            range(len(self.pending)),
                            key=lambda i: (
                                getattr(
                                    self.pending[i], "edf_deadline_ms",
                                    None,
                                )
                                if getattr(
                                    self.pending[i], "edf_deadline_ms",
                                    None,
                                ) is not None
                                else float("inf"),
                                i,
                            ),
                        )
                    job = self.pending.pop(idx)
                    # any lane serves any conversation (the prefix store is
                    # the shared pool, not lane KV): take the
                    # least-recently-used free lane
                    lane = min(free, key=lambda i: self.lane_used[i])
                    free.remove(lane)
                    self._admission_count += 1
                    self.lane_used[lane] = self._admission_count
                    admissions.append((lane, job))
                n_pending = len(self.pending)
                self.state.m_queue_depth.set(n_pending)
            self._n_pending = n_pending  # this thread's own, read in the tick
            # liveness heartbeat: the watchdog's scheduler-stalled rule
            # audits the gap between these
            wd = self.state.watchdog
            if wd is not None:
                wd.beat(
                    n_active=sum(1 for ls in self.lanes if ls is not None),
                    n_admitting=len(self.admitting),
                )
            # mono_ns: this thread's host clock at the tick's begin. In a
            # profiler trace the annotation's own start less mono_ns is
            # the offset between the two clocks, read once per tick, so a
            # reader places recorder events and --trace-out records on
            # the device's axis and can check the offset for drift.
            # profiled: a profiler session was collecting at the begin,
            # so its Python tracer slows this tick and a reader of host
            # time leaves it out
            spans = self.state.spans
            tick_sp = spans.begin(
                "sched_tick", component="scheduler",
                n_pending=n_pending, n_admitting=len(self.admitting),
                mono_ns=time.monotonic_ns(), profiled=profiler_collecting(),
            )
            for lane, job in admissions:
                with spans.span(
                    "begin_admission", component="scheduler",
                    request_id=job.span.request_id, lane=lane,
                ):
                    self._begin_admission(lane, job)
            # oversubscription (PR 16): requests queued while every lane
            # is busy and --max-streams allows more concurrency — park
            # the most-progressed lane (publish + drop page list); it
            # frees this tick and the queued request admits next tick.
            # A park publishes a live lane's history, so a block in
            # flight is collected first: every token it holds is read
            if self._flight is not None and self._may_evict(n_pending):
                self._guarded_step(lambda: self._drain("park"))
            t_evict = time.monotonic()
            parked = self._maybe_park(n_pending)
            # deadline preemption (ISSUE 20): park an over-budget /
            # deadline-blown lower-priority stream when that flips a
            # feasible hinted request from "blows its budget waiting"
            # to "meets SLO" — reuses the PR 16 park/resume contract,
            # so the victim's stream stays byte-identical on resume
            if self._maybe_preempt(n_pending) or parked:
                # one span for both, and only when a stream was parked
                # (whose `park` span is inside): begun after the fact,
                # so a tick that evicts nothing pays two clock reads
                spans.end(spans.begin(
                    "park_preempt", component="scheduler",
                    annotate=False, at=t_evict,
                ))
            # stall-free admission: at most ONE bounded chunk program per
            # tick, then a decode block for every active lane — the worst
            # case inter-token gap is one chunk program + one block. A
            # program carries every admitting lane's next chunk; where a
            # rider adds rows to it (experts), its riders are paid for in
            # blocks before the next: a tick each with a block and no
            # program, so a decoding lane gets a block a lane's chunk
            self._admission_tick()
            if any(self.lanes) or self._flight is not None:
                self._guarded_step(self._step_block)
            self.state.spans.end(tick_sp)
            if not any(self.lanes):
                # decode went idle: the next dispatch starts a new stall
                # window, don't charge it for the quiet period
                self._last_decode_end = None

    def _guarded_step(self, step) -> None:
        """Run one decode-side step of the tick (`_step_block`, or the
        collect before a park). The scheduler thread must survive any
        engine error (the reference's crash-retry loop plays this role
        for its single stream, dllama-api.cpp:616-628). _retry_dispatch
        already absorbed transient failures; what reaches here is
        classified by the cache epoch: moved => the dispatch guard
        rebuilt the donated cache (every lane's slab KV is gone) and the
        lanes RESUME from the shared page pool; unchanged => retries
        exhausted on an intact cache — fail the in-flight requests with
        a structured retryable error and keep serving."""
        epoch0 = self.engine.cache_epoch
        try:
            step()
        except Exception as e:
            import logging

            poisoned = self.engine.cache_epoch != epoch0
            logging.getLogger(__name__).exception(
                "lane scheduler step failed (%s); %s",
                "cache poisoned" if poisoned else "cache intact",
                "recovering lanes" if poisoned
                else "dropping in-flight lanes",
            )
            self.state.m_sched_errors.inc()
            self.state.recorder.record(
                "scheduler_error",
                error=str(e),
                error_type=type(e).__name__,
                poisoned=poisoned,
                n_lanes=sum(
                    1 for ls in self.lanes if ls is not None
                ),
            )
            # black-box dump: the ring holds the dispatches that
            # led here (written only when a postmortem dir is set)
            self.state.recorder.postmortem("scheduler-loop", e)
            if poisoned:
                # batched dispatch: no single lane is culpable, so
                # every lane resumes (none of them caused it)
                self._recover(e, culprit=None)
            else:
                self._drop_all(e)
            with self.cv:
                self.cv.notify_all()

    # -- oversubscription: park / resume (PR 16) ---------------------------

    def _may_evict(self, n_pending: int) -> bool:
        """What a park and a preemption both need: one of the two is
        switched on, requests wait, the shared pool can hold a parked
        stream, no admission is under way and no lane is free."""
        if not (
            self.max_streams > len(self.lanes) or self.state.admission_predict
        ):
            return False
        if n_pending <= 0 or self.kv is None or self.admitting:
            return False
        return all(ls is not None for ls in self.lanes)

    def _maybe_park(self, n_pending: int) -> bool:
        """Park ONE active lane when requests wait, no lane is free, and
        the stream cap (--max-streams > lanes) says the queue pressure
        is oversubscription, not overload. The victim is the lane that
        decoded the most tokens since its last (re)admission, and it
        must have at least one full block of progress — so a deep queue
        rotates lanes round-robin instead of thrashing park/resume.
        ``n_pending`` is the tick's queue-depth snapshot (taken under
        the cv in _loop). Returns whether a stream was parked."""
        if self.max_streams <= len(self.lanes) or not self._may_evict(n_pending):
            return False
        victim, best = -1, self.block_size - 1
        for lane, ls in enumerate(self.lanes):
            if ls is None or ls.job.cancelled:
                continue
            if self._progress[lane] > best:
                victim, best = lane, self._progress[lane]
        if victim >= 0:
            self._park_stream(victim)
        return victim >= 0

    def _maybe_preempt(self, n_pending: int) -> bool:
        """Deadline preemption (ISSUE 20, predictive mode only): when
        the EDF head is a HINTED request that blows its budget if it
        waits for natural lane turnover, but would meet it on a lane
        freed right now, park ONE active lower-priority (or already
        deadline-blown) stream through the PR 16 contract. The victim
        requeues with its later effective deadline, so EDF resumes it
        after the deadline traffic — paused, never restarted, its
        token stream byte-identical. Preemption never fires when the
        head is infeasible either way: burning a victim cannot save
        it. Returns whether a stream was preempted."""
        st = self.state
        if (
            not st.admission_predict
            or st.predictor is None
            or not self._may_evict(n_pending)
        ):
            return False
        head, head_key = None, None
        with self.cv:
            pending = list(self.pending)
        for j in pending:
            key = getattr(j, "edf_deadline_ms", None)
            if key is None:
                continue
            if head_key is None or key < head_key:
                head, head_key = j, key
        if head is None or not head.params.deadline_hinted:
            return False
        now_ms = self._clock() * 1000.0
        remaining_ms = head_key - now_ms
        if remaining_ms <= 0:
            return False
        n_tok = head.n_prompt_tokens or st.estimate_prompt_tokens(
            head.params
        )
        occ = self.occupancy()
        wait_pred = st.predictor.predict(n_tok, occ)
        if wait_pred.ttft_ms <= remaining_ms:
            return False  # feasible by waiting — no victim needed
        # forecast against a freed lane: zero queue wait, admission
        # starts next tick
        occ_freed = self.occupancy()
        occ_freed.queue_depth = 0
        occ_freed.active_lanes = max(0, occ_freed.active_lanes - 1)
        now_pred = st.predictor.predict(n_tok, occ_freed)
        if now_pred.ttft_ms > remaining_ms:
            return False  # infeasible either way
        prio_rank = {"low": 0, "normal": 1, "high": 2}
        head_rank = prio_rank.get(head.params.priority, 1)
        victim, v_score, v_blown = -1, None, False
        for lane, ls in enumerate(self.lanes):
            if ls is None or ls.job.cancelled:
                continue
            # same no-thrash floor as _maybe_park: at least one full
            # block of progress since (re)admission
            if self._progress[lane] <= self.block_size - 1:
                continue
            r = prio_rank.get(ls.job.params.priority, 1)
            vkey = getattr(ls.job, "edf_deadline_ms", None)
            vkey = vkey if vkey is not None else float("inf")
            blown = vkey < now_ms
            if r >= head_rank and not blown:
                continue  # only lower-priority or deadline-blown streams
            score = (r, -vkey)
            if v_score is None or score < v_score:
                victim, v_score, v_blown = lane, score, blown
        if victim < 0:
            return False
        reason = "deadline_blown" if v_blown else "priority"
        rid = self.lanes[victim].job.span.request_id
        self._park_stream(victim)
        st.m_preemptions.labels(reason=reason).inc()
        st.recorder.record(
            "stream_preempt", lane=victim, reason=reason,
            victim_request=rid,
            head_request=head.span.request_id,
            head_remaining_ms=round(remaining_ms, 3),
            predicted_ttft_ms=round(now_pred.ttft_ms, 3),
        )
        return True

    def _park_stream(self, lane: int) -> None:
        """Evict an active stream from its lane to make room for a
        queued request: publish the fed history's whole pages into the
        shared pool (so the resume re-matches nearly everything), drop
        the lane's page list (radix entry kept), and requeue the job
        carrying its preserved _LaneState — exactly the
        recovery-admission contract (_AdmittingLane resume_state=),
        minus the crash. The decode span stays open: the client's
        stream pauses but never observably restarts."""
        ls = self.lanes[lane]
        st = self.state
        rid = ls.job.span.request_id
        with st.spans.span(
            "park", component="scheduler", request_id=rid, lane=lane,
            pos=ls.pos,
        ):
            # publish failures self-narrow inside the manager (the
            # culprit pages release, survivors stay); a 0-token store
            # just means the resume re-prefills more
            self.kv.publish(lane, ls.history[: ls.pos])
            self.kv.release_lane(lane)
        self.lanes[lane] = None
        # warm-start (spec satellite): the drafter parks WITH the stream
        # instead of being discarded — the resume rebinds + reinstalls it
        ls.drafter = self.drafters.pop(lane, None)
        self._draft_pos.pop(lane, None)
        self._progress[lane] = 0
        ls.job._park_resume = ls
        # parked = queue-visible again: a fresh queue span covers the
        # parked wait so the timeline shows where the stream's time went
        ls.job.queue_span = st.spans.begin(
            "queue", component="scheduler", request_id=rid, parked=True,
            annotate=False,  # outlives this tick
        )
        self._n_parked += 1
        st.m_streams_parked.set(self._n_parked)
        self._set_lane_gauge()
        with self.cv:
            self.pending.append(ls.job)
            n_pending = len(self.pending)
            st.m_queue_depth.set(n_pending)
            self.cv.notify()
        st.recorder.record(
            "stream_park", lane=lane, pos=ls.pos,
            n_pending=n_pending, n_parked=self._n_parked,
        )

    def _prefill_start(self, start_pos: int) -> int:
        """Where the chunked prefill begins behind an adopted prefix of
        `start_pos` positions: at its end, or, for a model whose lanes keep
        state (`engine.state_replay_rows`), that many positions before it:
        those rows run again, their cache writes masked, to rebuild the
        lane's states (`engine.prefill_lane_chunk`'s `write_floor`)."""
        return start_pos - self.engine.state_replay_rows if start_pos else start_pos

    def _match_prefix(self, lane: int, tokens: list[int]) -> tuple[int, list]:
        """The pool's longest stored prefix of `tokens` and its pages,
        retained for `lane`. A model whose lanes keep state declines a
        prefix no longer than what it would run again to rebuild them, and
        every prefix where the state reaches back to position 0
        (`engine.state_unbounded`): the lane then runs its prompt whole."""
        start_pos, pages = self.kv.match(lane, tokens)
        why = "unbounded" if self.engine.state_unbounded else (
            "short" if self._prefill_start(start_pos) <= 0 else None)
        if start_pos and why:
            self.kv.release_lane(lane)
            self.engine.decline_adoption(why)
            return 0, []
        return start_pos, pages

    def _begin_admission(self, lane: int, job: LaneJob) -> None:
        """Resolve the prompt and park it as an _AdmittingLane — the front
        half of the old monolithic _admit, with NO engine work: the adopt
        copy and the prefill chunks run one per tick in _admission_tick.
        Validation failures here precede any engine call.

        The FULL conversation is retokenized every time and matched
        against the shared radix tree: a continuing conversation reuses
        its stored prefix from ANY lane (the template renders
        prefix-stable transcripts, so turn N's rendering begins with turn
        N-1's), and so does an unrelated request that shares a system
        prompt. The match is token-granular; the chunked prefill then
        covers only positions [start_pos, prompt_end)."""
        state, tok = self.state, self.state.tokenizer
        p = job.params
        ls0 = getattr(job, "_park_resume", None)
        if ls0 is not None:
            # parked-stream resume: no retokenize (the preserved state's
            # history IS the fed token stream) — radix re-match, chunked
            # re-prefill of whatever wasn't published, then
            # _finish_admission reinstalls the state untouched
            self._resume_parked(lane, job, ls0)
            return
        try:
            if p.resume_tokens is not None:
                # fleet mid-stream failover (docs/fleet.md): the router
                # replays a dead sibling's fed history (prompt +
                # already-emitted tokens) as raw ids — no template, no
                # tokenizer. The radix match + chunked prefill below
                # treat it like any other prompt, so a shared prefix
                # adopts from the pool and the stream continues
                # byte-identically (greedy) from tokens[-1].
                if len(p.resume_tokens) < 2:
                    raise ValueError(
                        "resume_tokens needs at least 2 token ids"
                    )
                tokens = [int(t) for t in p.resume_tokens]
                public_prompt = ""
            else:
                items = [ChatItem(m.role, m.content) for m in p.messages]
                prompt = state.template.generate(
                    items, append_generation_prompt=True
                )
                tokens = tok.encode(
                    prompt.content, is_start=True, add_special_tokens=True
                )
                public_prompt = prompt.public_prompt or ""
            start_pos, adopt_pages = 0, []
            if self.kv is not None:
                # match retains the pages for this lane immediately —
                # the adopt copy runs a tick later and unpinned pages
                # could be evicted/reallocated in between
                start_pos, adopt_pages = self._match_prefix(lane, tokens)
            if start_pos > 0:
                state.m_prefix_hits.inc()
                state.m_reused_tokens.inc(start_pos)
                self.kv.note_hit(start_pos)
            else:
                state.m_prefix_misses.inc()
            qw = job.span.mark_admitted(
                lane=lane, reused_prefix_tokens=start_pos
            )
            # the queue span absorbs tokenize+match above, so per-request
            # timeline coverage only misses inter-tick bookkeeping
            state.spans.end(
                job.queue_span, lane=lane, n_prompt=len(tokens),
                reused_prefix_tokens=start_pos,
            )
            state.m_queue_wait.observe(qw)
            state.m_admissions.inc()
            # admission-time forecast (ISSUE 20): the queue wait is now
            # known exactly and the radix match says how much prefill
            # is skipped — record the prediction _finish scores against
            # the observed TTFT/TPOT to self-calibrate the predictor
            if state.predictor is not None and qw is not None:
                fc = state.predictor.predict(
                    len(tokens), self.occupancy(),
                    matched_tokens=start_pos,
                )
                job.predicted_ttft_ms = (
                    qw * 1000.0 + fc.ttft_ms - fc.queue_wait_ms
                )
                job.predicted_tpot_ms = fc.tpot_ms
                state.m_predicted_ttft.observe(job.predicted_ttft_ms)
            seq_len = self.engine.header.seq_len
            prompt_end = len(tokens) - 1
            if prompt_end >= seq_len:
                raise ValueError(
                    f"prompt of {len(tokens)} tokens exceeds "
                    f"seqLen {seq_len}"
                )
            max_pos = (
                min(prompt_end + p.max_tokens, seq_len)
                if p.max_tokens > 0
                else seq_len
            )
            job.n_prompt_tokens = len(tokens)
            self.admitting[lane] = _AdmittingLane(
                job=job,
                tokens=tokens,
                pos0=0,
                cursor=self._prefill_start(start_pos),
                prompt_end=prompt_end,
                max_pos=max_pos,
                public_prompt=public_prompt,
                start_pos=start_pos,
                adopt_pages=adopt_pages,
            )
        except Exception as e:
            state.spans.end(job.queue_span, error=str(e))
            # validation failures (bad template, prompt too long) are the
            # client's to fix, not to retry — retryable stays False
            job.events.put(
                ("error", {"message": str(e), "retryable": False})
            )
            if job.span.finish("error") is not None:
                state.m_finished.labels(reason="error").inc()
            if self.kv is not None:
                # a validation failure after the match (e.g. prompt too
                # long) must drop the pages match() just retained
                self.kv.release_lane(lane)

    def _resume_parked(
        self, lane: int, job: LaneJob, ls: "_LaneState"
    ) -> None:
        """Front half of a parked stream's re-admission: the park
        published the history's whole pages, so the radix match adopts
        them back (zero device copies in pool-native mode) and only the
        page-tail + generated suffix re-prefills."""
        state = self.state
        job._park_resume = None
        try:
            # park requires the shared pool, so self.kv is non-None here
            start_pos, adopt_pages = self._match_prefix(lane, ls.history)
            if start_pos > 0:
                state.m_prefix_hits.inc()
                state.m_reused_tokens.inc(start_pos)
                self.kv.note_hit(start_pos)
            state.spans.end(
                job.queue_span, lane=lane, resumed_from_park=True,
                reused_prefix_tokens=start_pos,
            )
            self.admitting[lane] = _AdmittingLane(
                job=job,
                tokens=list(ls.history),
                pos0=0,
                cursor=self._prefill_start(start_pos),
                prompt_end=len(ls.history) - 1,
                max_pos=ls.max_pos,
                public_prompt="",
                start_pos=start_pos,
                adopt_pages=adopt_pages,
                resume_state=ls,
                from_park=True,
            )
        except Exception as e:
            state.spans.end(job.queue_span, error=str(e))
            state.spans.end(ls.decode_span, error=str(e))
            job.events.put(
                ("error", {"message": str(e), "retryable": True})
            )
            if job.span.finish(
                "error", n_completion=job.n_completion
            ) is not None:
                state.m_finished.labels(reason="error").inc()
            self._n_parked -= 1
            state.m_streams_parked.set(self._n_parked)
            self.kv.release_lane(lane)

    def _adopt_due(self, adm: _AdmittingLane) -> bool:
        """Whether `adm`'s next tick is its adopt. Pool-native mode runs the
        adopt tick even on a zero-token match: kv.adopt() is where the
        lane's private pages allocate and its page table installs — without
        it there is no KV home for the prefill to write into."""
        return self.kv is not None and not adm.adopted and (
            bool(adm.adopt_pages) or getattr(self.kv, "native", False)
        )

    def _chunk_riders(self, lead: int) -> list[int]:
        """The admitting lanes that ride in `lead`'s chunk program, in
        round-robin order behind it: those whose next action is a chunk too
        (not cancelled, adopted or needing no adopt, fill tokens left), as
        many as the engine's chunk program fills besides the lead: none
        where it takes one lane's rows (`engine.chunk_lanes`)."""
        order = sorted(self.admitting)
        behind = [i for i in order if i > lead] + [i for i in order if i < lead]
        riders = [lane for lane in behind if self._chunk_due(self.admitting[lane])]
        return riders[: self.engine.chunk_lanes - 1]

    def _chunk_due(self, adm: _AdmittingLane) -> bool:
        """Whether `adm`'s next action is a chunk."""
        return (not adm.job.cancelled and not self._adopt_due(adm)
                and adm.cursor < len(adm.tokens) - 1)

    def _chunk_dispatch(self, carried: list[int]) -> int:
        """ONE chunk program for the next chunk of every lane of `carried`
        (the tick's lead first), each at its own position. Cursors move
        once the dispatch has returned, each by what its lane consumed: a
        lane the engine left out (its rows would pass the context's end in
        the common bucket) consumed nothing and waits for a tick that it
        leads. Returns the lanes the program filled."""
        adms = [self.admitting[lane] for lane in carried]
        wd = self.state.watchdog
        spans = [
            self.state.spans.begin(
                "admission_chunk", component="scheduler",
                request_id=adm.job.span.request_id, lane=lane,
                pos=adm.pos0 + adm.cursor,
            )
            for lane, adm in zip(carried, adms)
        ]
        chunks = [
            (lane, adm.tokens[adm.cursor:-1], adm.pos0 + adm.cursor)
            for lane, adm in zip(carried, adms)
        ]
        if len(chunks) == 1:
            (lane, tokens, pos), adm = chunks[0], adms[0]

            def dispatch():
                return [self.engine.prefill_lane_chunk(
                    lane, tokens, pos, budget=self.admission_chunk,
                    # lane state: the adopted rows stay as they are
                    **({"write_floor": adm.pos0 + adm.start_pos}
                       if adm.cursor < adm.start_pos else {}),
                )]
        else:
            def dispatch():
                return self.engine.prefill_lanes_chunk(
                    chunks, budget=self.admission_chunk)
        if wd is not None:
            wd.dispatch_begin("prefill_lane_chunk")
        t0 = self._clock()
        try:
            widths = self._retry_dispatch("prefill_lane_chunk", dispatch)
        finally:
            if wd is not None:
                wd.dispatch_end()
            for sp in reversed(spans):
                self.state.spans.end(sp)
        took = self._clock() - t0
        for lane, adm, width in zip(carried, adms, widths):
            if not width:
                continue
            adm.prefill_s += took
            adm.cursor += width
            adm.n_chunks += 1
            self.state.m_admission_chunks.inc()
            self.state.recorder.record(
                "admission_chunk", lane=lane, chunk=adm.n_chunks,
                pos=adm.pos0 + adm.cursor - width, n_tokens=width,
                done=adm.cursor >= len(adm.tokens) - 1,
            )
        return sum(1 for width in widths if width)

    def _admission_tick(self) -> None:
        """Run at most ONE bounded engine dispatch of admission per
        scheduler tick and flip a lane into decode once its last fill token
        lands. The tick's lead is picked round-robin across concurrent
        admissions; its adopt is its own tick, and its chunk program carries
        the next chunk of every other admitting lane that can ride in it
        (`_chunk_riders`).

        Where a rider adds rows to the program (`engine.
        chunk_rider_adds_rows`) the program owes the decoding lanes a block
        a rider: while it owes and a lane decodes, a tick whose lead's turn
        is a chunk dispatches none and takes one off the debt (the tick's
        block follows as ever), so a lane's chunk is still followed by a
        block of its own. With no lane decoding the debt is dropped."""
        if not self.admitting:
            self._chunk_debt = 0
            return
        order = sorted(self.admitting)
        lane = min((i for i in order if i > self._rr), default=order[0])
        adm = self.admitting[lane]
        if self._chunk_debt and self._chunk_due(adm):
            if any(self.lanes) or self._flight is not None:
                self._chunk_debt -= 1
                self.state.m_admission_yielded.inc()
                self.state.recorder.record("admission_yield", lane=lane, owed=self._chunk_debt)
                return
            self._chunk_debt = 0
        self._rr = lane
        if adm.job.cancelled:
            self._abort_admission(lane, "cancelled")
            return
        wd = self.state.watchdog
        epoch0 = self.engine.cache_epoch
        carried = [lane]
        try:
            if self._adopt_due(adm):
                # the adopt copy is this lane's first tick action and is
                # its own tick (one bounded engine dispatch per tick, same
                # budget discipline as a prefill chunk)
                sp = self.state.spans.begin(
                    "adopt", component="scheduler",
                    request_id=adm.job.span.request_id,
                    lane=lane, n_pages=len(adm.adopt_pages),
                )
                if wd is not None:
                    wd.dispatch_begin("kv_adopt")
                t0 = self._clock()
                try:
                    self._retry_dispatch(
                        "kv_adopt",
                        lambda: self.kv.adopt(lane, adm.adopt_pages),
                    )
                finally:
                    if wd is not None:
                        wd.dispatch_end()
                    self.state.spans.end(sp)
                adm.prefill_s += self._clock() - t0
                adm.adopted = True
            elif adm.cursor < len(adm.tokens) - 1:
                carried += self._chunk_riders(lane)
                filled = self._chunk_dispatch(carried)
                if self.engine.chunk_rider_adds_rows:
                    self._chunk_debt = filled - 1
        except Exception as e:
            self._admission_fault(e, epoch0, carried)
            return
        for lane in carried:
            # a lane that a finish's fault failed or rewound is not done
            adm = self.admitting.get(lane)
            if adm is None or adm.cursor < len(adm.tokens) - 1 or self._adopt_due(adm):
                continue
            try:
                with self.state.spans.span(
                    "finish_admission", component="scheduler",
                    request_id=adm.job.span.request_id, lane=lane,
                ):
                    self._finish_admission(lane, adm)
            except Exception as e:
                self._admission_fault(e, epoch0, [lane])

    def _admission_fault(self, e: Exception, epoch0: int, lanes: list[int]) -> None:
        """An admission dispatch for `lanes` (a tick's lead first), or one
        lane's finish, raised."""
        self.state.recorder.record(
            "admission_error", lane=lanes[0], error=str(e),
            error_type=type(e).__name__,
            poisoned=self.engine.cache_epoch != epoch0,
        )
        if self.engine.cache_epoch != epoch0:
            # the failed adopt/chunk ran inside the engine's donated-
            # buffer guard: the WHOLE cache was rebuilt, so every
            # other lane's slab KV died with this admission — recover
            # them all, failing only the lead's request (before
            # PR 12 this path silently left active lanes decoding
            # against a zeroed cache)
            self._recover(e, culprit=lanes[0])
        else:
            # cache intact (retries exhausted on a transient fault):
            # only this dispatch's admissions are affected — error the
            # jobs and drop their page retains (a lane's partial KV is
            # overwritten by the next admission anyway)
            for lane in lanes:
                self._fail_admitting(
                    lane, {"message": str(e), "retryable": True}
                )

    def _finish_admission(self, lane: int, adm: _AdmittingLane) -> None:
        """Last fill token landed: install the decode-side _LaneState.
        `seed` is honored PER LANE (r5): decode_lanes derives each lane's
        sampling keys from (its seed, its absolute positions), so a seeded
        request reproduces regardless of which other lanes are active,
        how blocks split — or how its admission was chunked."""
        state, tok = self.state, self.state.tokenizer
        job, p = adm.job, adm.job.params
        if adm.resume_state is not None:
            # crash-recovery OR park resume: the re-prefill just
            # restored KV rows [0, pos) of the preserved lane state's
            # history — reinstall that state untouched (stream decoder,
            # EOS detector, token counts all intact) and the client's
            # stream continues exactly where it paused. No prompt delta,
            # no fresh spans, no second "admit": the request never
            # observably restarted.
            self.lanes[lane] = adm.resume_state
            del self.admitting[lane]
            self._progress[lane] = 0
            self._set_lane_gauge()
            # warm-start (spec satellite): reinstall the drafter the
            # park/recovery stashed on the preserved state — learned
            # AIMD k and n-gram index intact, rebound to the re-matched
            # radix anchor. A resume without one (e.g. speculation
            # turned on between park and resume) builds fresh.
            if self.spec_on and adm.resume_state.temperature <= 0.0:
                dr = adm.resume_state.drafter
                adm.resume_state.drafter = None
                if not isinstance(dr, NgramDrafter):
                    dr = self._make_drafter(lane, adm.job.span.request_id)
                else:
                    dr.rebind(*self._lane_anchor(lane))
                self.drafters[lane] = dr
            if adm.from_park:
                self._n_parked -= 1
                state.m_streams_parked.set(self._n_parked)
                state.m_stream_resumes.inc()
                state.recorder.record(
                    "stream_resume", lane=lane, pos=adm.resume_state.pos,
                    reused_prefix_tokens=adm.start_pos,
                    n_chunks=adm.n_chunks,
                )
            else:
                state.m_lanes_recovered.inc()
                state.recorder.record(
                    "lane_recovered", lane=lane, pos=adm.resume_state.pos,
                    reused_prefix_tokens=adm.start_pos,
                    n_chunks=adm.n_chunks,
                )
            return
        job.span.set_prefill_seconds(adm.prefill_s)
        job.span.set_tokens(n_prompt=len(adm.tokens))
        state.m_prefill.observe(adm.prefill_s)
        if adm.public_prompt:
            job.buffer += adm.public_prompt
            job.events.put(("delta", adm.public_prompt))
        detector = EosDetector(
            tok.eos_token_ids,
            state.stops if not p.stop else p.stop,
            padding_left=state.max_stop_len,
            padding_right=state.max_stop_len,
        )
        self.lanes[lane] = _LaneState(
            job=job,
            pos=adm.prompt_end,
            token=adm.tokens[-1],
            max_pos=adm.max_pos,
            detector=detector,
            decoder=tok.stream_decoder(),
            temperature=p.temperature,
            top_p=p.top_p,
            seed=p.seed,
            history=list(adm.tokens),
            decode_span=state.spans.begin(
                "decode", component="scheduler",
                request_id=job.span.request_id, lane=lane,
                n_prompt=len(adm.tokens),
                annotate=False,  # a stream's life, not what a thread does
            ),
        )
        del self.admitting[lane]
        self._progress[lane] = 0
        if self.spec_on and p.temperature <= 0.0:
            # greedy lanes only: a sampled lane's next token is not the
            # argmax the verify pass returns, so it stays on the decode
            # block (the fallback is per-lane, not per-server)
            self.drafters[lane] = self._make_drafter(
                lane, job.span.request_id
            )
        self._set_lane_gauge()
        state.recorder.record(
            "admit", lane=lane, reused_prefix_tokens=adm.start_pos,
            n_prompt=len(adm.tokens), n_chunks=adm.n_chunks,
        )

    def _abort_admission(self, lane: int, reason: str) -> None:
        """Client went away mid-admission: stop prefilling for nobody."""
        adm = self.admitting.pop(lane)
        job = adm.job
        if adm.from_park:
            self._n_parked -= 1
            self.state.m_streams_parked.set(self._n_parked)
        if adm.resume_state is not None:
            # recovery resume cancelled mid-re-prefill: the original
            # stream's decode span is still open — close it here
            self.state.spans.end(adm.resume_state.decode_span, reason=reason)
        if job.span.finish(
            reason, n_prompt=len(adm.tokens), n_completion=job.n_completion
        ) is not None:
            self.state.m_finished.labels(reason=reason).inc()
            if reason == "cancelled":
                self.state.m_cancellations.inc()
        job.events.put(("done", reason))
        self.drafters.pop(lane, None)
        self._draft_pos.pop(lane, None)
        if self.kv is not None:
            # nothing publishable mid-admission; just drop page retains
            self.kv.release_lane(lane)
        self.state.recorder.record(
            "finish", lane=lane, reason=reason, pos=adm.pos0 + adm.cursor,
            n_completion=job.n_completion,
        )

    def _finish(self, lane: int, reason: str) -> None:
        ls = self.lanes[lane]
        rid = ls.job.span.request_id
        with self.state.spans.span(
            "finish", component="scheduler", request_id=rid, lane=lane,
            reason=reason,
        ):
            self._finish_stream(lane, ls, rid, reason)

    def _finish_stream(
        self, lane: int, ls: "_LaneState", rid: str, reason: str
    ) -> None:
        self.state.spans.end(
            ls.decode_span, reason=reason,
            n_completion=ls.job.n_completion,
        )
        if self.kv is not None:
            if reason in ("stop", "length"):
                # publish the fed history's whole pages into the shared
                # pool BEFORE signalling done, so a client's immediate
                # follow-up request (any lane) matches this conversation.
                # Dedup inside publish keeps shared prefixes stored once.
                with self.state.spans.span(
                    "publish", component="scheduler", request_id=rid,
                    lane=lane, n_tokens=ls.pos,
                ):
                    self.kv.publish(lane, ls.history[: ls.pos])
            # cancelled/errored streams publish nothing; either way the
            # lane's adopted-page retains are released now
            self.kv.release_lane(lane)
        if ls.job.span.finish(
            reason,
            n_prompt=ls.job.n_prompt_tokens,
            n_completion=ls.job.n_completion,
        ) is not None:
            self.state.m_finished.labels(reason=reason).inc()
            if reason == "cancelled":
                self.state.m_cancellations.inc()
        self.state.slo.observe_span(
            ls.job.span, deadline_ms=ls.job.params.deadline_ms
        )
        self._score_prediction(ls.job, reason)
        ls.job.events.put(("done", reason))
        self.state.recorder.record(
            "finish", lane=lane, reason=reason, pos=ls.pos,
            n_completion=ls.job.n_completion,
        )
        self.lanes[lane] = None
        self.drafters.pop(lane, None)
        self._draft_pos.pop(lane, None)
        self._set_lane_gauge()
        with self.cv:
            self.cv.notify()

    def _score_prediction(self, job: LaneJob, reason: str) -> None:
        """Estimated-vs-observed TTFT/TPOT for one finished request
        (ISSUE 20): the absolute error feeds the first-class error
        histogram and the EWMA correction folds the observed/predicted
        ratio back into the LoadPredictor. Only clean finishes score —
        a cancelled stream's latency says nothing about the model."""
        st = self.state
        pred = st.predictor
        if (
            pred is None
            or job.predicted_ttft_ms is None
            or reason not in ("stop", "length")
        ):
            return
        span = job.span
        ttft_s = getattr(span, "ttft_s", None)
        if ttft_s is not None and ttft_s > 0:
            obs_ms = ttft_s * 1000.0
            err_ms = abs(obs_ms - job.predicted_ttft_ms)
            st.m_predict_error.labels(signal="ttft").observe(err_ms)
            st.note_predict_error(err_ms)
            pred.observe_ttft(job.predicted_ttft_ms, obs_ms)
        total_s = getattr(span, "total_s", None)
        n = job.n_completion
        if (
            job.predicted_tpot_ms is not None
            and total_s is not None
            and ttft_s is not None
            and n > 1
        ):
            obs_tpot_ms = (total_s - ttft_s) / (n - 1) * 1000.0
            st.m_predict_error.labels(signal="tpot").observe(
                abs(obs_tpot_ms - job.predicted_tpot_ms)
            )
            pred.observe_tpot(job.predicted_tpot_ms, obs_tpot_ms)

    def _consume_token(self, lane: int, t: int) -> bool:
        """Advance one lane by one generated token — lane state, history,
        SSE delta, EOS/length detection. Returns False once the lane
        finished (callers stop feeding it; any remaining burst tokens'
        KV rows sit beyond the lane's final position and are never
        published). Shared by the decode-block row loop and the
        speculative verify path, so an accepted draft run flushes
        through EXACTLY the same per-token machinery as plain decode —
        that is what makes spec-on streams byte-identical."""
        ls = self.lanes[lane]
        if ls is None:
            return False
        self._progress[lane] += 1
        ls.pos += 1
        ls.token = t
        ls.history.append(t)
        ls.job.n_completion += 1
        if ls.job.n_completion == 1:
            ttft = ls.job.span.mark_first_token()
            if ttft is not None:
                self.state.m_ttft.observe(ttft)
        piece = ls.decoder.decode(t)
        if ls.job.params.include_tokens:
            ls.tape.append((t, piece or ""))
        eos_type = ls.detector.append(t, piece)
        if eos_type in (EosResult.NOT_EOS, EosResult.EOS):
            delta = ls.detector.get_delta()
            if delta:
                ls.job.buffer += delta
                if ls.job.params.include_tokens:
                    # attribute the flush with the exact consumed tokens:
                    # cumulative `tokens` across deltas == the generated
                    # history, cumulative `piece` == its exact text (the
                    # delta text lags by the detector's holdback)
                    ls.job.events.put(
                        (
                            "delta",
                            {
                                "text": delta,
                                "tokens": [tid for tid, _ in ls.tape],
                                "piece": "".join(px for _, px in ls.tape),
                            },
                        )
                    )
                    ls.tape = []
                else:
                    ls.job.events.put(("delta", delta))
            ls.detector.reset()
        if eos_type == EosResult.EOS:
            self._finish(lane, "stop")
            return False
        if ls.pos >= ls.max_pos:
            self._finish(lane, "length")
            return False
        return True

    def _lane_anchor(self, lane: int) -> tuple[int | None, int]:
        """The lane's current radix anchor (node_id, matched tokens) —
        the shared-store grouping key captured by the admission match —
        or (None, 0) when sharing is off / nothing matched."""
        if self.spec_store is not None and self.kv is not None:
            a = self.kv.anchor_for(lane)
            if a is not None:
                return a
        return (None, 0)

    def _make_drafter(self, lane: int, stream_id: str) -> NgramDrafter:
        anchor, aoff = self._lane_anchor(lane)
        return NgramDrafter(
            k_max=self.spec_k,
            shared_store=self.spec_store,
            stream_id=stream_id,
            anchor=anchor,
            anchor_offset=aoff,
            use_draft_model=(
                self.spec_mode == "draft" and self.engine.has_draft_model
            ),
        )

    def _spec_drafts(self) -> dict[int, list[int]]:
        """Collect this tick's draft proposals: greedy lanes whose
        drafter proposes >=1 token within the lane's remaining budget
        (both max_tokens and seq_len cap the accepted run). The source
        ladder runs per lane — private n-gram vs the shared store's
        sibling continuations, longest suffix match winning, then
        (mode draft) one batched draft-model propose over every lane
        both n-gram sources left dry."""
        out: dict[int, list[int]] = {}
        st = self.state
        seq_len = self.engine.header.seq_len
        self._draft_fed.clear()
        model_lanes: dict[int, int] = {}  # lane -> model-draft budget
        for lane, dr in self.drafters.items():
            ls = self.lanes[lane]
            if ls is None:
                continue
            dr.update(ls.history)
            # an accepted run emits up to len(draft)+1 tokens from pos
            room = min(ls.max_pos, seq_len) - ls.pos - 1
            if room < 1:
                continue
            budget = min(self.spec_k, room)
            d = dr.draft(budget=budget)
            if d:
                out[lane] = d
                if st.m_spec_source is not None and dr.last_source:
                    st.m_spec_source.labels(source=dr.last_source).inc(
                        len(d)
                    )
                continue
            mb = dr.model_budget(budget)
            if mb > 0:
                model_lanes[lane] = mb
        if model_lanes:
            for lane, d in self._draft_with_model(model_lanes).items():
                dr = self.drafters.get(lane)
                if dr is not None:
                    dr.last_source = SOURCE_DRAFT
                out[lane] = d
                if st.m_spec_source is not None:
                    st.m_spec_source.labels(source=SOURCE_DRAFT).inc(
                        len(d)
                    )
        if self.spec_store is not None and st.g_spec_store_groups is not None:
            stats = self.spec_store.stats()
            st.g_spec_store_groups.set(stats["groups"])
            st.g_spec_store_streams.set(stats["streams"])
            st.g_spec_store_tokens.set(stats["tokens"])
            st.g_spec_store_hits.set(stats["hits"])
            st.g_spec_store_misses.set(stats["misses"])
        return out

    def _draft_with_model(
        self, budgets: dict[int, int]
    ) -> dict[int, list[int]]:
        """Resident-draft-model proposals for lanes whose n-gram sources
        ran dry: per lane, catch the draft KV cache up on the verified
        history it has not seen (bucketed draft_prefill chunks), then
        ONE batched draft_step dispatch autoregresses k greedy tokens
        for every such lane. Purely advisory — any failure here skips
        model drafting for the tick (the lanes fall back to the decode
        block) and never touches the target cache."""
        eng = self.engine
        b = len(self.lanes)
        dseq = eng.draft_seq_len
        if eng.draft_cache_epoch != self._draft_epoch:
            # the draft cache was rebuilt (draft-side dispatch failure):
            # every lane's draft context is gone; cursors restart at 0
            # and the catch-up below re-derives them from host history
            self._draft_pos.clear()
            self._draft_epoch = eng.draft_cache_epoch
        k = 0
        lanes: list[int] = []
        try:
            for lane in budgets:
                ls = self.lanes[lane]
                if ls is None or ls.pos + budgets[lane] > dseq:
                    continue
                dpos = self._draft_pos.get(lane, 0)
                if dpos < ls.pos:
                    # feed history[dpos:pos] at dpos: rows past a verify
                    # rewind are overwritten here before any draft query
                    # can attend to them (same causal-mask argument as
                    # the target's rewind)
                    eng.draft_prefill(lane, ls.history[dpos:ls.pos], dpos)
                    self._draft_pos[lane] = ls.pos
                lanes.append(lane)
                k = max(k, budgets[lane])
            if not lanes or k < 1:
                return {}
            k = bucket_for(k, self.spec_buckets)
            tokens = [0] * b
            pos = [0] * b
            act = [False] * b
            for lane in lanes:
                ls = self.lanes[lane]
                tokens[lane] = ls.token
                pos[lane] = ls.pos
                act[lane] = True
            props = eng.draft_propose(tokens, pos, act, k)
        except Exception as e:
            self.state.recorder.record(
                "draft_model_error", error=str(e),
                error_type=type(e).__name__, n_lanes=len(budgets),
            )
            return {}
        if not props:
            return {}
        out: dict[int, list[int]] = {}
        for lane in lanes:
            d = props[lane][: budgets[lane]]
            if d:
                out[lane] = d
                self._draft_fed[lane] = (pos[lane], len(d))
        return out

    def _spec_verify(self, drafts: dict[int, list[int]]) -> None:
        """One batched verify dispatch for every drafting lane: build the
        shared-width rows [pending, draft..., pads], accept each lane's
        longest matching prefix + 1 correction token, and flush the run
        through the normal per-token path. Lanes too close to seq_len
        for the shared bucket width drop out and decode normally."""
        st = self.state
        b = len(self.lanes)
        seq_len = self.engine.header.seq_len
        t = 1 + bucket_for(
            max(len(d) for d in drafts.values()), self.spec_buckets
        )
        for lane in list(drafts):
            ls = self.lanes[lane]
            if ls is None or ls.pos + t > seq_len:
                del drafts[lane]
        if not drafts:
            return
        rows = [[0] * t for _ in range(b)]
        pos = [0] * b
        act = [False] * b
        for lane, d in drafts.items():
            ls = self.lanes[lane]
            rows[lane] = [ls.token, *d] + [0] * (t - 1 - len(d))
            pos[lane] = ls.pos
            act[lane] = True
        # a verify dispatch IS token progress: it participates in the
        # same stall window accounting as the decode block
        now = self._clock()
        if self._last_decode_end is not None:
            st.m_decode_stall.observe(now - self._last_decode_end)
        t0 = time.perf_counter()
        wd = st.watchdog
        sp = st.spans.begin(
            "spec_verify", component="scheduler",
            n_lanes=len(drafts), t=t,
        )
        if wd is not None:
            wd.dispatch_begin("verify_lanes")
        try:
            grid = self._retry_dispatch(
                "verify_lanes",
                lambda: self.engine.verify_lanes(rows, pos, act),
            )
        finally:
            if wd is not None:
                wd.dispatch_end()
            st.spans.end(sp)
        self._last_decode_end = self._clock()
        dt = time.perf_counter() - t0
        n_emitted = n_finished = 0
        emit_sp = st.spans.begin("emit", component="scheduler")
        for lane, d in drafts.items():
            out = grid[lane]
            # out[0] is the greedy token after the pending one (what a
            # decode step at this position would emit); out[j] is the
            # greedy token after draft j-1 — accept while they agree,
            # then emit out[a] as the correction/continuation token
            a = 0
            while a < len(d) and out[a] == d[a]:
                a += 1
            emitted = d[:a] + [out[a]]
            dr = self.drafters.get(lane)
            if dr is not None:
                dr.feedback(len(d), a)
            fed = self._draft_fed.pop(lane, None)
            if fed is not None:
                # draft-cache rows p+j hold history[p+j] for j <= a (row
                # p is the pending token, row p+i is draft i-1, valid
                # iff i-1 accepted drafts agree); rows past the rewind
                # point are stale and re-fed by catch-up before use
                self._draft_pos[lane] = fed[0] + min(a + 1, fed[1])
            st.m_spec_drafted.inc(len(d))
            st.m_spec_accepted.inc(a)
            st.m_spec_accept_len.observe(float(a))
            st.recorder.record(
                "spec_verify", lane=lane, k=len(d), accepted=a,
                pos=pos[lane],
            )
            n_emitted += len(emitted)
            # the accepted run flushes as a burst, but per-token latency
            # accounting stays honest: this lane got len(emitted) tokens
            # for one dispatch's wall time
            st.m_tpot.observe(dt / len(emitted))
            for tok in emitted:
                if not self._consume_token(lane, tok):
                    n_finished += 1
                    break
        st.spans.end(emit_sp, n_tokens=n_emitted, n_finished=n_finished)
        st.slo.note_tokens(n_emitted)
        if st.m_spec_drafted.value > 0:
            st.g_spec_rate.set(
                st.m_spec_accepted.value / st.m_spec_drafted.value
            )
        if (
            st.g_spec_tokens_per_pass is not None
            and st.m_spec_accept_len.count > 0
        ):
            # each verify dispatch is one weight pass emitting 1+a tokens
            st.g_spec_tokens_per_pass.set(
                1.0 + st.m_spec_accept_len.sum / st.m_spec_accept_len.count
            )

    def _step_block(self) -> None:
        """The decode side of one tick. Where nothing holds it back
        (`_why_not_ahead`) the loop runs one block ahead: with block n
        in flight, block n + 1 is built from what the host knows without
        n's tokens (positions advance by n's steps, a lane whose length
        ends inside n drops out, a continuing lane's input token stays
        on the device) and dispatched BEFORE n is collected, so the
        device has it queued while the host emits n's tokens, finishes
        and publishes streams and runs the next admission tick. Where
        something does (nobody waits for a lane, above all), the tick is
        dispatch, then collect, as it always was, and a block still in
        flight is collected first."""
        b = len(self.lanes)
        spans = self.state.spans
        # step_prep: the cancel sweep, speculative drafts and list
        # building — the scheduler's own host work before a dispatch
        prep_sp = spans.begin("step_prep", component="scheduler")
        try:
            # free lanes whose client went away before paying for more
            # decode (a block in flight drops such a lane's rows)
            for lane in range(b):
                ls = self.lanes[lane]
                if ls is not None and ls.job.cancelled:
                    self._finish(lane, "cancelled")
            held = self._why_not_ahead()
            if self._flight is not None and held is not None:
                # the next tick dispatches after its admission work
                spans.end(prep_sp)
                self._drain(held)
                return
            # speculative verify first: greedy lanes whose drafter
            # proposes a continuation take ONE batched verify dispatch;
            # everyone else — temperature>0 lanes, greedy lanes with
            # nothing to propose — shares the normal decode block in the
            # same tick, so mixed batches fall back transparently per
            # lane, not per server
            verified: set[int] = set()
            if self.spec_on and self.drafters:
                drafts = self._spec_drafts()
                if drafts:
                    spans.end(prep_sp)
                    self._spec_verify(drafts)
                    verified = set(drafts)
                    prep_sp = spans.begin("step_prep", component="scheduler")
            args = self._block_args(verified)
            if args is None and self._flight is not None:
                spans.end(prep_sp)
                self._drain("no_live_lane")
        finally:
            spans.end(prep_sp)
        if args is None:
            return
        failed = None
        if self._flight is not None:
            epoch = self.engine.cache_epoch
            try:
                ahead = self._dispatch_block(args)
            except Exception as e:
                # what is in flight was decoded before the fault: its
                # tokens are good, and are emitted before the recovery
                # (epoch moved) or the retry (cache intact) that follows
                self._drain("fault")
                if self.engine.cache_epoch != epoch:
                    raise
                failed, args = e, self._block_args(verified)
                if args is None:
                    return
                held = self._why_not_ahead()
            else:
                flight, self._flight = self._flight, ahead
                self._collect_block(flight, since=ahead.block.t1)
                return
        self._flight = self._retry_dispatch(
            "decode_lanes", lambda: self._dispatch_block(args), failed
        )
        if self._flight is None:
            # every decode-side lane is out of sequence space (verified
            # lanes already advanced this tick and are not touched)
            for lane, ls in enumerate(args[0]):
                if ls is not None and self.lanes[lane] is ls:
                    self._finish(lane, "length")
        elif held is not None:
            self._drain(held, since=self._flight.block.t1)

    def _why_not_ahead(self) -> str | None:
        """What holds the decode loop to dispatch, then collect, in one
        tick; None where a block may stay in flight into the next tick
        and the next block be dispatched ahead of its collect. `paged`:
        the pool's block takes no device-side input token. `verify`: a
        lane with a drafter needs its newest tokens for its draft.
        `no_queue`: nobody waits for a lane, so a request that arrives
        takes one at once, and its first chunk would stand behind the
        block queued ahead: its first token a block later than under
        dispatch, then collect, whenever it finds the device busy. That
        is the one token running ahead delivers later, so the loop runs
        ahead only where a queue pays for it: there a first token waits
        for a lane, not for a block, and the lanes come free sooner.
        `park`: a park or a preemption publishes a live lane's
        history."""
        if self.engine.kv_native:
            return "paged"
        if self.spec_on and self.drafters:
            return "verify"
        if self._n_pending == 0:
            return "no_queue"
        if self._may_evict(self._n_pending):
            return "park"
        return None

    def _block_args(self, verified: set[int]):
        """The next decode block, None where no lane would run live:
        the `_LaneState` a lane, None where it is not live, and
        `dispatch_lanes`' arguments. A lane that runs in the block in flight
        continues from it: its token stays on the device (None here) and
        its position is past that block's steps; where its length ends
        inside that block it has no step to come. Any other lane starts
        from its host token."""
        flight = self._flight
        n = len(self.lanes)
        states, tokens, pos = [None] * n, [0] * n, [0] * n
        for lane, ls in enumerate(self.lanes):
            if ls is None or lane in verified:
                continue
            token, at = ls.token, ls.pos
            if flight is not None and flight.states[lane] is ls:
                token, at = None, ls.pos + flight.block.n_steps
                if at >= ls.max_pos:
                    continue
            states[lane], tokens[lane], pos[lane] = ls, token, at
        if not any(states):
            return None
        return states, (
            tokens, pos, self.block_size,
            [ls is not None for ls in states],
            [ls.temperature if ls else 0.0 for ls in states],
            [ls.top_p if ls else 1.0 for ls in states],
            [ls.seed if ls else None for ls in states],
        )

    def _dispatch_block(self, args) -> "_BlockInFlight | None":
        """Dispatch one decode block (the engine returns at the enqueue)
        and count it by its order: `ahead` of the collect of the block in
        flight, or `drained_first` with the reason that block was
        collected before this dispatch (`first_block`: there was none)."""
        states, call = args
        st = self.state
        # decode stall: the gap since the previous decode-block
        # collect finished, while >=1 lane was active the whole time
        # — whatever sat in between (admission chunks, host work) is
        # latency a streaming client ate. Chunked admission bounds it
        # by one chunk + one block.
        if self._last_decode_end is not None:
            st.m_decode_stall.observe(self._clock() - self._last_decode_end)
        wd = st.watchdog
        if wd is not None:
            wd.dispatch_begin("decode_lanes")
        try:
            block = self.engine.dispatch_lanes(*call)
        finally:
            if wd is not None:
                wd.dispatch_end()
        if block is None:
            return None
        if self._flight is not None:
            st.m_decode_blocks.labels(order="ahead", reason="").inc()
        else:
            st.m_decode_blocks.labels(
                order="drained_first",
                reason=self._drained_why or "first_block",
            ).inc()
            self._drained_why = None
        return _BlockInFlight(block=block, states=states)

    def _drain(self, reason: str, since: float | None = None) -> None:
        """Collect the block in flight now, before the next is
        dispatched, and keep `reason` for that block's count."""
        flight, self._flight = self._flight, None
        if flight is not None:
            self._drained_why = reason
            self._collect_block(flight, since)

    def _discard_flight(self) -> None:
        """Abandon the block in flight un-read: its streams were dropped,
        or resume from their histories on a fresh cache."""
        flight, self._flight = self._flight, None
        if flight is not None:
            self.engine.discard_lanes(flight.block)

    def _collect_block(
        self, flight: _BlockInFlight, since: float | None = None
    ) -> None:
        """Wait for a dispatched block and emit its rows: one span for
        the block's whole row-by-lane token loop (detokenise, stop
        checks, events.put, perhaps _finish). A lane whose stream ended
        on an earlier block's tokens (EOS, a stop string, a client that
        went away) ran this block for nobody: its rows are dropped, and
        what was published is its history up to that end. `since`: where
        the span before this collect ended on the tick's thread (the
        call of the block dispatched just now), so that the tick's
        spans leave no stretch between them to nobody."""
        st, spans = self.state, self.state.spans
        wd = st.watchdog
        # collect: the wait for the block (the engine's `.device` span
        # inside it) and the engine's accounting of what it read back
        collect_sp = spans.begin("collect", component="scheduler", at=since)
        if wd is not None:
            wd.dispatch_begin("decode_lanes")
        try:
            rows = self.engine.collect_lanes(flight.block)
        finally:
            if wd is not None:
                wd.dispatch_end()
            collected = time.monotonic()
            spans.end(collect_sp, at=collected)
        n_tokens = n_finished = 0
        emit_sp = spans.begin("emit", component="scheduler", at=collected)
        try:
            self._last_decode_end = self._clock()
            live = [
                lane for lane, ls in enumerate(flight.states)
                if ls is not None and self.lanes[lane] is ls
            ]
            # every active stream advanced len(rows) tokens in this block
            st.m_tpot.observe(flight.block.seconds / len(rows))
            st.slo.note_tokens(len(rows) * len(live))
            for row in rows:
                for lane in list(live):
                    n_tokens += 1
                    if not self._consume_token(lane, row[lane]):
                        live.remove(lane)
                        n_finished += 1
        finally:
            spans.end(emit_sp, n_tokens=n_tokens, n_finished=n_finished)


class ApiState:
    """Engine + tokenizer + conversation cache shared across requests."""

    def __init__(
        self,
        engine: InferenceEngine,
        tokenizer: Tokenizer,
        model_name: str = "dllama-tpu",
        chat_template_type: ChatTemplateType = ChatTemplateType.UNKNOWN,
        tracer: Tracer | None = None,
        lane_block_size: int = 8,
        admission_chunk: int = 0,
        kv_page_size: int = 0,
        kv_pool_pages: int = 0,
        kv_native: bool = False,
        max_streams: int = 0,
        slo_ttft_ms: float | None = None,
        slo_tpot_ms: float | None = None,
        series_retention: float = 3600.0,
        speculation: str = "off",
        spec_k: int = DEFAULT_SPEC_K,
        retry_max: int = 3,
        retry_backoff_ms: int = 5,
        max_queue_depth: int = 0,
        replica_id: str | None = None,
        admission_predict: bool = False,
        admission_max_wait_ms: int = 30_000,
        deadline_default_ms: int = 600_000,
        deadline_priority_step_ms: int = 60_000,
    ):
        if speculation not in SPEC_MODES:
            raise ValueError(
                f"speculation must be one of {'/'.join(SPEC_MODES)}, got "
                f"{speculation!r}"
            )
        self.engine = engine
        self.tokenizer = tokenizer
        self.model_name = model_name
        # fleet identity (docs/fleet.md): names this replica in
        # /v1/health and scopes chaos injection (sse_flush op filter)
        self.replica_id = replica_id
        self.start_unix = time.time()
        # resilience knobs: the scheduler reads the retry policy off this
        # state; admission_decision() reads the shed threshold (0 =
        # unbounded queue, shedding off)
        self.retry_max = int(retry_max)
        self.retry_backoff_ms = int(retry_backoff_ms)
        self.max_queue_depth = int(max_queue_depth)
        # predictive admission (ISSUE 20): predict gates the whole
        # controller (infeasible-reject, EDF ordering, deadline
        # preemption); the deadline knobs shape the synthetic effective
        # deadlines that keep PR 12 priority semantics when no hints are
        # given. The
        # LoadPredictor itself also backs the derived Retry-After on
        # every shed path, predictive mode on or off.
        self.admission_predict = bool(admission_predict)
        self.admission_max_wait_ms = int(admission_max_wait_ms)
        self.deadline_default_ms = int(deadline_default_ms)
        self.deadline_priority_step_ms = int(deadline_priority_step_ms)
        # bounded ring of recent |predicted - observed| TTFT errors in
        # ms: /v1/debug/admission reports p50/p95 off it (the bench's
        # prediction-error readout); appends on the scheduler thread
        from collections import deque

        self.predict_errors: deque = deque(maxlen=512)
        # graceful drain (POST /v1/drain, SIGTERM): admission stops, the
        # in-flight streams finish, sinks flush, /v1/health says so
        self.draining = False
        self.draining_since: float | None = None
        self.drained = threading.Event()
        # serving observability (obs/): the registry families behind
        # GET /metrics and the tracer behind --trace-out. Handles are
        # created up front (before the scheduler thread starts using them)
        # so the hot path never pays a registry lookup.
        self.obs = get_registry()
        self.recorder = get_recorder()
        self.tracer = tracer if tracer is not None else Tracer()
        # span timeline (GET /v1/debug/timeline, --timeline-out) and
        # windowed SLO attainment/goodput (GET /v1/debug/slo)
        self.spans = get_span_tracker()
        self.slo = SloTracker(
            ttft_target_ms=slo_ttft_ms, tpot_target_ms=slo_tpot_ms
        )
        # one refresh path for every on-demand gauge: the /metrics scrape
        # and the series sampler both call run_refresh_hooks(), so the SLO
        # windows / device memory / step cost are never scrape-only stale.
        # Keyed registration: test churn rebuilds ApiState against the
        # process-global registry, and each rebuild REPLACES the hooks.
        self.obs.add_refresh_hook(
            "device_memory", lambda: sample_device_memory(self.obs)
        )
        self.obs.add_refresh_hook("slo", self.slo.snapshot)
        # in-process time-series store + sampler thread + anomaly monitor
        # (obs/timeseries.py, obs/anomaly.py): /v1/debug/series and the
        # /dashboard sparklines read the store; the anomaly monitor rides
        # the sampler tick and feeds /v1/health's degraded status
        self.series = SeriesStore(
            interval_s=resolve_series_knobs(), retention_s=series_retention
        )
        self.sampler = MetricsSampler(self.series)
        self.anomaly = AnomalyMonitor(build_default_rules(self.series))
        self.sampler.on_sample.append(self.anomaly.evaluate)
        # POST /v1/debug/profile concurrency guard (one capture at a time)
        self.profile_lock = make_lock("api.profile")
        # analytic per-chip accounting, computed once: /v1/debug/memory
        # compares it against the live device.memory_stats() snapshot
        from ..utils.telemetry import memory_report

        self.mem_report = memory_report(
            engine.params,
            engine.cache,
            n_devices=engine.mesh.devices.size,
            tp=engine.tp,
        )
        self.m_http = self.obs.counter(
            "dllama_http_requests_total",
            "HTTP requests by path (unknown paths fold into 'other').",
            labelnames=("path",),
        )
        self.m_queue_depth = self.obs.gauge(
            "dllama_queue_depth",
            "Requests waiting for a free lane (lane-scheduler path).",
        )
        self.m_lanes_total = self.obs.gauge(
            "dllama_lanes_total", "Serving lanes this engine exposes."
        )
        self.m_lanes_active = self.obs.gauge(
            "dllama_lanes_active", "Lanes currently decoding a request."
        )
        self.m_queue_wait = self.obs.histogram(
            "dllama_queue_wait_seconds",
            "Submit -> admission wait (lane assignment or engine lock).",
        )
        self.m_prefill = self.obs.histogram(
            "dllama_prefill_seconds",
            "Prompt prefill wall time at admission.",
        )
        self.m_ttft = self.obs.histogram(
            "dllama_ttft_seconds",
            "Submit -> first generated token (time to first token).",
        )
        self.m_tpot = self.obs.histogram(
            "dllama_tpot_seconds",
            "Per-token decode latency a streaming client observes "
            "(block wall time / tokens per lane in the block).",
            buckets=DEFAULT_TOKEN_BUCKETS_S,
        )
        self.m_admissions = self.obs.counter(
            "dllama_admissions_total", "Requests admitted into a lane."
        )
        self.m_prefix_hits = self.obs.counter(
            "dllama_prefix_cache_hits_total",
            "Admissions that reused a stored prompt prefix (radix-tree "
            "match on the lane path, NaiveCache on the serialized path).",
        )
        self.m_prefix_misses = self.obs.counter(
            "dllama_prefix_cache_misses_total",
            "Admissions that prefilled from position 0.",
        )
        self.m_reused_tokens = self.obs.counter(
            "dllama_reused_prefix_tokens_total",
            "KV positions skipped thanks to prompt-prefix reuse.",
        )
        self.m_evictions = self.obs.counter(
            "dllama_cache_evictions_total",
            "Stored prompt prefixes dropped to make room: radix-tree LRU "
            "page evictions on the lane path (see also "
            "dllama_radix_evictions_total).",
        )
        self.m_cancellations = self.obs.counter(
            "dllama_sse_cancellations_total",
            "Streaming requests whose client disconnected mid-response.",
        )
        self.m_finished = self.obs.counter(
            "dllama_requests_finished_total",
            "Completed requests by finish reason "
            "(stop/length/cancelled/error).",
            labelnames=("reason",),
        )
        self.m_sched_errors = self.obs.counter(
            "dllama_scheduler_errors_total",
            "Engine errors swallowed by the lane-scheduler loop (each one "
            "dropped every in-flight lane; see the traceback log).",
        )
        # resilience (PR 12): retry/recovery/shed/drain observability
        self.m_dispatch_retries = self.obs.counter(
            "dllama_dispatch_retries_total",
            "Transient engine-dispatch failures re-issued by the "
            "scheduler's bounded-backoff retry (the cache epoch did not "
            "move, so lane KV survived the failure).",
        )
        self.m_lanes_recovered = self.obs.counter(
            "dllama_lanes_recovered_total",
            "Lanes resumed after a poisoning dispatch failure: the donated "
            "cache was rebuilt, the lane radix re-matched its published "
            "prefix and re-prefilled the unpublished suffix, and its "
            "stream continued byte-identically.",
        )
        self.m_shed = self.obs.counter(
            "dllama_requests_shed_total",
            "Requests refused at admission with 429/503 + Retry-After, by "
            "reason (draining / queue_full / degraded).",
            labelnames=("reason",),
        )
        self.g_draining = self.obs.gauge(
            "dllama_draining",
            "1 while the server drains (admission stopped, in-flight "
            "streams finishing), else 0.",
        )
        self.m_admission_chunks = self.obs.counter(
            "dllama_admission_chunks_total",
            "Bounded prefill chunks of admitting lanes (one a lane that a "
            "scheduler tick's chunk program carried).",
        )
        self.m_admission_yielded = self.obs.counter(
            "dllama_admission_ticks_yielded_total",
            "Scheduler ticks on which an admitting lane's chunk was due and "
            "no chunk program was dispatched: the last one carried riders "
            "that added rows to it (experts), and owed the decoding lanes a "
            "block a rider.",
        )
        self.m_decode_blocks = self.obs.counter(
            "dllama_sched_decode_blocks_total",
            "Decode blocks by the order of their dispatch: ahead = enqueued "
            "before the block in flight was read back (the device keeps a "
            "program queued), drained_first = after it, with the reason: "
            "first_block (none was in flight), no_queue, verify, park, "
            "fault, paged, no_live_lane.",
            labelnames=("order", "reason"),
        )
        self.m_decode_stall = self.obs.histogram(
            "dllama_decode_stall_seconds",
            "Gap between consecutive decode-block dispatches while >=1 "
            "lane is active — the inter-token stall streaming clients "
            "see; bounded by one admission chunk + one block.",
        )
        # model-free speculation (runtime/spec.py): draft/accept volume,
        # the per-dispatch acceptance-length distribution, and the
        # cumulative acceptance ratio the /dashboard sparkline tracks
        self.m_spec_drafted = self.obs.counter(
            "dllama_spec_draft_tokens_total",
            "Draft tokens proposed by the n-gram speculator across "
            "verify dispatches.",
        )
        self.m_spec_accepted = self.obs.counter(
            "dllama_spec_accepted_tokens_total",
            "Draft tokens accepted by batched verification (the greedy "
            "argmax agreed with the draft at that position).",
        )
        self.m_spec_accept_len = self.obs.histogram(
            "dllama_spec_accept_length",
            "Accepted draft-prefix length per lane per verify dispatch "
            "(0 = the first draft token already diverged; each dispatch "
            "still emits one correction token).",
            buckets=(0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0),
        )
        self.g_spec_rate = self.obs.gauge(
            "dllama_spec_acceptance_rate",
            "Cumulative accepted/drafted token ratio of the n-gram "
            "speculator (0 until the first verify dispatch).",
        )
        # second-generation speculation (PR 18): per-source draft volume,
        # shared-store occupancy, and the roofline-facing tokens-per-
        # weight-pass gauge. Registered only when speculation is on so
        # `--speculation off` stays a pure bypass (no new series).
        self.m_spec_source = None
        self.g_spec_tokens_per_pass = None
        self.g_spec_store_groups = None
        self.g_spec_store_streams = None
        self.g_spec_store_tokens = None
        self.g_spec_store_hits = None
        self.g_spec_store_misses = None
        if speculation != "off":
            self.m_spec_source = self.obs.counter(
                "dllama_spec_source_total",
                "Draft tokens proposed, by source: the lane's private "
                "n-gram index, a sibling continuation from the shared "
                "store, or the resident draft model.",
                labelnames=("source",),
            )
            self.g_spec_tokens_per_pass = self.obs.gauge(
                "dllama_spec_tokens_per_weight_pass",
                "Mean tokens emitted per verify dispatch (1 + mean "
                "accepted prefix length) — compare against the roofline "
                "ceiling printed at startup.",
            )
        if speculation in ("shared", "draft"):
            self.g_spec_store_groups = self.obs.gauge(
                "dllama_spec_shared_store_groups",
                "Anchor groups (radix node identities) currently held "
                "by the cross-lane shared n-gram store.",
            )
            self.g_spec_store_streams = self.obs.gauge(
                "dllama_spec_shared_store_streams",
                "Published stream continuations across all anchor "
                "groups in the shared n-gram store.",
            )
            self.g_spec_store_tokens = self.obs.gauge(
                "dllama_spec_shared_store_tokens",
                "Accepted tokens retained across all shared-store "
                "stream continuations.",
            )
            self.g_spec_store_hits = self.obs.gauge(
                "dllama_spec_shared_store_hits",
                "Cumulative shared-store lookups that returned a "
                "sibling continuation.",
            )
            self.g_spec_store_misses = self.obs.gauge(
                "dllama_spec_shared_store_misses",
                "Cumulative shared-store lookups that found no usable "
                "sibling continuation.",
            )
        # oversubscription (PR 16): streams beyond the lane count park
        # (publish + drop page list, radix entry kept) and resume via
        # the recovery-admission path
        self.m_streams_parked = self.obs.gauge(
            "dllama_streams_parked",
            "Admitted streams currently parked out of their lane "
            "(--max-streams oversubscription): KV published to the "
            "shared pool, page list dropped, waiting to resume.",
        )
        self.m_stream_resumes = self.obs.counter(
            "dllama_stream_resumes_total",
            "Parked streams resumed into a lane via radix re-match "
            "through the recovery-admission path (near-zero re-prefill "
            "when the parked history published page-aligned).",
        )
        # predictive admission (ISSUE 20): forecast + error tracking.
        # Millisecond-scale buckets: TTFT forecasts span ~1ms (warm
        # prefix, idle engine) to tens of seconds (deep queue).
        _ms_buckets = (
            1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0, 500.0,
            1000.0, 2000.0, 5000.0, 10000.0, 30000.0, 60000.0,
        )
        self.m_predicted_ttft = self.obs.histogram(
            "dllama_admission_predicted_ttft_ms",
            "LoadPredictor TTFT forecast recorded at admission (known "
            "queue wait + cost-model/percentile prefill forecast over "
            "the radix-matched suffix), in milliseconds.",
            buckets=_ms_buckets,
        )
        self.m_predict_error = self.obs.histogram(
            "dllama_admission_predict_error_ms",
            "Absolute estimated-vs-observed error of the admission "
            "forecast on clean finishes, by signal (ttft / tpot), in "
            "milliseconds; the EWMA correction factor feeds on the "
            "same pairs.",
            labelnames=("signal",),
            buckets=_ms_buckets,
        )
        self.m_admission_rejected = self.obs.counter(
            "dllama_admission_rejected_total",
            "Requests rejected by the PREDICTIVE controller before "
            "touching the queue, by reason (infeasible = the forecast "
            "says the deadline/TTFT budget cannot be met even if "
            "admitted now).",
            labelnames=("reason",),
        )
        self.m_preemptions = self.obs.counter(
            "dllama_preemptions_total",
            "Active streams parked by deadline preemption so a feasible "
            "hinted request could meet its SLO, by reason (priority = "
            "lower-priority victim; deadline_blown = the victim's own "
            "effective deadline had already passed).",
            labelnames=("reason",),
        )
        # request defaults captured once: per-request sampler mutations must
        # not leak into later requests' defaults
        self.default_temperature = engine.temperature
        self.default_top_p = engine.sampler.topp
        stops = [
            tokenizer.vocab[t].decode("utf-8", "replace")
            for t in tokenizer.eos_token_ids
        ]
        eos_piece = stops[0] if stops else ""
        self.stops = stops
        self.max_stop_len = max((len(s) for s in stops), default=0)
        self.template = ChatTemplateGenerator(
            chat_template_type, tokenizer.chat_template, eos_piece
        )
        self.naive_cache = NaiveCache()
        self.lock = make_lock("api.state")
        # batch_size > 1 engines serve requests CONCURRENTLY over the
        # engine's batch lanes (the reference's accept loop — and the
        # batch_size == 1 path here — serves one request at a time)
        lanes_on = engine.batch_size > 1 and engine.sp == 1
        # shared paged-KV pool + radix prefix tree for the lane path
        # (kv_page_size < 0 = sharing off, the bench baseline)
        self.kv_manager = None
        if lanes_on and kv_page_size >= 0:
            from ..kv.manager import PagedKVManager

            self.kv_manager = PagedKVManager(
                engine,
                page_size=kv_page_size,
                n_pages=kv_pool_pages,
                evict_counter=self.m_evictions,
                native=kv_native,
            )
        # LoadPredictor (ISSUE 20): always built on the lane path — the
        # derived Retry-After reads it even with predictive mode off;
        # the predictive gates (infeasible-reject, EDF, preemption)
        # additionally consult it when admission_predict is on. Must
        # exist BEFORE the scheduler thread starts (admission records
        # forecasts through it).
        self.predictor = LoadPredictor(engine) if lanes_on else None
        # engine watchdog audits the scheduler loop; it must exist BEFORE
        # the scheduler thread starts (the loop beats it every tick). The
        # decode-stalled threshold scales off the engine's own p99 block
        # time so slow models don't false-alarm.
        self.watchdog = None
        if lanes_on:
            self.watchdog = EngineWatchdog(
                block_p99=lambda: engine._m_step.labels(
                    kind="decode_lanes"
                ).percentile(0.99),
                recorder=self.recorder,
                **resolve_watchdog_knobs(),
            )
            self.watchdog.start()
        self.scheduler = (
            LaneScheduler(
                self,
                block_size=lane_block_size,
                admission_chunk=admission_chunk,
                speculation=speculation,
                spec_k=spec_k,
                max_streams=max_streams,
            )
            if lanes_on
            else None
        )
        self.m_lanes_total.set(
            engine.batch_size if self.scheduler is not None else 1
        )
        # postmortem context (satellite, PR 12): every ring dump embeds a
        # /v1/health snapshot plus the trailing 60 s of the anomaly-rule
        # series, so a dump is diagnosable without the live server
        self.recorder.add_context_provider("health", self.health_snapshot)
        self.recorder.add_context_provider("series_60s", self._series_context)
        # sampler last: every gauge/hook it snapshots now exists
        self.sampler.start()

    # -- health / drain / shed (PR 12) -----------------------------------

    def degraded_reasons(self) -> list[str]:
        """Composed degradation: the watchdog (hard stall) and the anomaly
        monitor (soft baseline deviation) each contribute reasons — never
        last-writer-wins. Shared by /v1/health and admission_decision."""
        reasons: list[str] = []
        wd = self.watchdog
        if wd is not None and wd.degraded:
            reasons.append(f"watchdog:{wd.status().get('reason')}")
        if self.anomaly.degraded:
            reasons.extend(
                f"anomaly:{s}" for s in self.anomaly.active_signals()
            )
        return reasons

    def health_snapshot(self) -> dict:
        """The /v1/health payload — also embedded into postmortem dumps
        via the recorder's context providers, so it must never take the
        scheduler cv (a postmortem can fire on the scheduler thread):
        the lane/pending reads are GIL-atomic snapshots."""
        sched = self.scheduler
        total = self.engine.batch_size if sched is not None else 1
        if sched is not None:
            active = sum(1 for ls in sched.lanes if ls is not None)
            queued = len(sched.pending)
        else:
            active = 1 if self.lock.locked() else 0
            queued = 0
        if sched is not None:
            admitting = len(sched.admitting)
            parked = sched._n_parked
            max_streams = max(sched.max_streams, total)
        else:
            admitting = 0
            parked = 0
            max_streams = 1
        payload = {
            "status": "ok",
            "model": self.model_name,
            "uptime_s": round(time.time() - self.start_unix, 3),
            "lanes": {
                "total": total,
                "active": active,
                "free": total - active,
            },
            "queue_depth": queued,
            "cache_epoch": self.engine.cache_epoch,
            # router-facing capacity (docs/fleet.md): what a front door
            # needs for admission-aware spill decisions — the stream
            # ceiling, everything currently holding a slot toward it,
            # and whether the pool is native (parks/resumes are cheap)
            "capacity": {
                "lanes": total,
                "max_streams": max_streams,
                "in_flight": active + admitting + parked + queued,
                "parked": parked,
                "kv_native": bool(
                    self.kv_manager is not None
                    and getattr(self.kv_manager, "native", False)
                ),
            },
        }
        if self.replica_id is not None:
            payload["replica"] = self.replica_id
        reasons = self.degraded_reasons()
        wd = self.watchdog
        if wd is not None and wd.degraded:
            payload["watchdog"] = wd.status()
        if self.anomaly.degraded:
            payload["anomaly"] = self.anomaly.status()
        if reasons:
            # a degraded engine is still accepting connections — health
            # says so, so a probe/router can act on it
            payload["status"] = "degraded"
            payload["degraded_reasons"] = reasons
        if self.draining:
            # draining wins: routers must stop sending traffic regardless
            # of how healthy the engine itself looks
            payload["status"] = "draining"
            payload["draining_since_unix"] = self.draining_since
        return payload

    def _series_context(self) -> dict:
        from ..obs.anomaly import DEFAULT_SIGNAL_SERIES

        out = {}
        for name in DEFAULT_SIGNAL_SERIES:
            q = self.series.query(name, 60.0)
            if q is not None:
                out[name] = q
        return out

    def estimate_prompt_tokens(self, params: InferenceParams) -> int:
        """Coarse pre-tokenize prompt-length estimate for the PRE-QUEUE
        feasibility gate (~4 chars/token plus template overhead per
        message). Deliberately conservative — it assumes zero radix
        match; the accurate forecast (real token count, real match
        length) is recorded at admission and the EWMA correction
        absorbs the residual bias."""
        if params.resume_tokens is not None:
            return len(params.resume_tokens)
        n_chars = sum(len(m.content) for m in params.messages)
        return max(2, n_chars // 4 + 8 * max(1, len(params.messages)))

    def note_predict_error(self, err_ms: float) -> None:
        self.predict_errors.append(float(err_ms))

    def predict_error_stats(self) -> dict:
        """p50/p95 of the recent TTFT prediction errors (ms) — the
        bench's prediction-error readout via /v1/debug/admission."""
        errs = sorted(self.predict_errors)
        n = len(errs)
        if not n:
            return {"n": 0, "p50_ms": None, "p95_ms": None}
        return {
            "n": n,
            "p50_ms": round(errs[n // 2], 3),
            "p95_ms": round(errs[min(n - 1, int(n * 0.95))], 3),
        }

    def predicted_retry_after(self, floor: int = 1) -> int:
        """Retry-After derived from the predicted queue-drain time
        (ISSUE 20) — monotonic in queue depth — replacing the PR 12
        constants everywhere the structured retryable error is built.
        Falls back to ``floor`` on the serialized path (no scheduler,
        no queue to predict)."""
        sched, pred = self.scheduler, self.predictor
        if sched is None or pred is None:
            return floor
        return max(
            floor,
            pred.retry_after_s(
                sched.occupancy(), self.admission_max_wait_ms
            ),
        )

    def admission_snapshot(self) -> dict:
        """GET /v1/debug/admission: the predictor's calibration state,
        the live occupancy it forecasts against, and recent prediction
        error percentiles."""
        out: dict = {
            "predictive": self.admission_predict,
            "max_wait_ms": self.admission_max_wait_ms,
            "deadline_default_ms": self.deadline_default_ms,
            "deadline_priority_step_ms": self.deadline_priority_step_ms,
            "prediction_error": self.predict_error_stats(),
        }
        sched, pred = self.scheduler, self.predictor
        if sched is not None and pred is not None:
            occ = sched.occupancy()
            out["occupancy"] = occ.as_dict()
            out["predictor"] = pred.snapshot()
            out["retry_after_s"] = pred.retry_after_s(
                occ, self.admission_max_wait_ms
            )
        return out

    def admission_decision(
        self, priority: str, params: InferenceParams | None = None
    ) -> tuple[str, int] | None:
        """Load-shedding gate, consulted by the handler BEFORE a request
        touches the scheduler queue. None admits; otherwise returns
        (reason, retry_after_s) and the handler refuses with 429/503 +
        Retry-After. The priority ladder sheds lowest first: a "low"
        request is refused at half the queue threshold and whenever the
        engine is degraded; "high" rides out twice the threshold.

        Every Retry-After is DERIVED from the predicted queue-drain
        time (ISSUE 20) instead of the old constants, with the PR 12
        constants kept as floors. With predictive mode on, a HINTED
        request whose forecast cannot meet its budget even if admitted
        now is additionally rejected as ``infeasible`` — unhinted
        requests never are, so with no hints this gate is exactly the
        PR 12 ladder."""
        if self.draining:
            return ("draining", self.predicted_retry_after(floor=5))
        sched = self.scheduler
        if sched is not None and self.max_queue_depth > 0:
            factor = {"low": 0.5, "high": 2.0}.get(priority, 1.0)
            if len(sched.pending) >= self.max_queue_depth * factor:
                return ("queue_full", self.predicted_retry_after())
        if priority == "low" and self.degraded_reasons():
            return ("degraded", self.predicted_retry_after(floor=2))
        if (
            self.admission_predict
            and params is not None
            and params.deadline_hinted
            and sched is not None
            and self.predictor is not None
        ):
            budget = min(
                h for h in (params.deadline_ms, params.ttft_budget_ms)
                if h is not None
            )
            pred = self.predictor.predict(
                self.estimate_prompt_tokens(params), sched.occupancy()
            )
            if pred.ttft_ms > budget:
                self.m_admission_rejected.labels(
                    reason="infeasible"
                ).inc()
                return ("infeasible", self.predicted_retry_after())
        return None

    def begin_drain(self) -> dict:
        """Start a graceful drain (POST /v1/drain, SIGTERM): admission
        flips to shedding, in-flight streams run to completion, then the
        span/trace sinks flush and ``drained`` is set. Idempotent."""
        sched = self.scheduler
        if sched is not None:
            in_flight = (
                sum(1 for ls in sched.lanes if ls is not None)
                + len(sched.admitting)
                + len(sched.pending)
            )
        else:
            in_flight = 1 if self.lock.locked() else 0
        if not self.draining:
            self.draining = True
            self.draining_since = time.time()
            self.g_draining.set(1)
            self.recorder.record("drain_begin", in_flight=in_flight)
            t = threading.Thread(  # dlint: disable=thread-hygiene — the drained event is the join surface; the process exits after it fires
                target=self._drain_watch, daemon=True, name="dllama-drain"
            )
            t.start()
        return {
            "status": "draining",
            # the streams still running RIGHT NOW plus whether the drain
            # already finished — a rolling restart polls this endpoint
            # until drained flips true (docs/fleet.md runbook)
            "in_flight": in_flight,
            "drained": self.drained.is_set(),
            "since_unix": self.draining_since,
        }

    def _drain_watch(self) -> None:
        """Poll until every in-flight request finished, then flush the
        observability sinks and signal ``drained`` (the SIGTERM handler
        waits on it before shutting the HTTP server down)."""
        sched = self.scheduler
        while True:
            if sched is not None:
                idle = (
                    not any(sched.lanes)
                    and not sched.admitting
                    and not sched.pending
                )
            else:
                idle = not self.lock.locked()
            if idle:
                break
            time.sleep(0.05)
        self.spans.flush()
        self.recorder.record("drain_complete")
        # the rolling-restart poll target: in-flight hit zero, sinks are
        # flushed, the process is safe to replace (drain_s from the
        # POST /v1/drain that started the drain)
        since = self.draining_since
        self.recorder.record(
            "drained",
            in_flight=0,
            drain_s=(
                round(time.time() - since, 3) if since is not None else 0.0
            ),
        )
        self.drained.set()

    # -- completion ------------------------------------------------------

    def complete(self, params: InferenceParams, emit, span=None) -> dict:
        """Run one chat completion; `emit(delta)` is called per text delta
        (streaming). Returns the non-stream response dict.
        (reference: ApiServer::complete, src/dllama-api.cpp:367-487)

        Crash consistency (single-stream analogue of the lane
        scheduler's error path, and of the reference's 3 s whole-app
        retry loop, src/dllama-api.cpp:616-628): a dispatch failure has
        already dropped the engine's donated KV cache
        (engine._cache_guard), so the positions recorded in the
        NaiveCache no longer exist. The cache EPOCH is the exact
        signal — any exception class can be raised inside a guarded
        dispatch (even ValueError, at trace time), so "which exception"
        does not tell us whether KV state survived; the epoch does.
        Client-caused errors raised before any dispatch leave the
        epoch, and therefore the prompt cache, untouched."""
        if span is None:
            span = NULL_SPAN
        epoch = self.engine.cache_epoch
        try:
            return self._complete(params, emit, span)
        except BaseException as e:
            if self.engine.cache_epoch != epoch:
                self.naive_cache.clear()
            # an OSError here came from emit -> the client's socket: the
            # request was cancelled, not broken
            reason = "cancelled" if isinstance(e, OSError) else "error"
            if span.finish(reason) is not None:
                self.m_finished.labels(reason=reason).inc()
                if reason == "cancelled":
                    self.m_cancellations.inc()
            raise

    def _complete(self, params: InferenceParams, emit, span=NULL_SPAN) -> dict:
        engine, tok = self.engine, self.tokenizer
        engine.temperature = params.temperature
        engine.sampler.set_temp(params.temperature)
        engine.sampler.set_topp(params.top_p)
        if params.seed is not None:
            engine.set_seed(params.seed)

        delta_prompt, start_pos = self.naive_cache.resolve_delta_prompt(
            params.messages
        )
        if start_pos > 0:
            self.m_prefix_hits.inc()
            self.m_reused_tokens.inc(start_pos)
        else:
            self.m_prefix_misses.inc()
        span.set_reused_prefix(start_pos)
        if start_pos == 0:
            engine.reset()

        items = [ChatItem(m.role, m.content) for m in delta_prompt]
        prompt = self.template.generate(items, append_generation_prompt=True)
        tokens = tok.encode(
            prompt.content, is_start=start_pos == 0, add_special_tokens=True
        )
        n_prompt_tokens = len(tokens)
        seq_len = engine.header.seq_len
        prompt_end_pos = min(start_pos + n_prompt_tokens - 1, seq_len)
        max_pred_pos = (
            min(prompt_end_pos + params.max_tokens, seq_len)
            if params.max_tokens > 0
            else seq_len
        )

        buffer = ""
        if prompt.public_prompt:
            emit(prompt.public_prompt)
            buffer += prompt.public_prompt

        tok.reset_decoder()
        detector = EosDetector(
            tok.eos_token_ids,
            self.stops if not params.stop else params.stop,
            padding_left=self.max_stop_len,
            padding_right=self.max_stop_len,
        )

        # On-device block decode via the engine's shared loop (one host
        # dispatch per ~8 tokens). EOS is detected per consumed token; the
        # KV rows a block wrote past the stop are masked garbage until the
        # next prefill overwrites them. NB: sampled (temperature>0) decode
        # uses the engine's on-device JAX PRNG — seeded-reproducible, but a
        # different RNG than the reference's xorshift host sampler (which
        # remains available via engine.decode_step / Sampler).
        state = {"hit_eos": False, "buffer": buffer}
        t_gen = time.perf_counter()

        def on_token(t: int):
            self.slo.note_tokens(1)
            ttft = span.mark_first_token()
            if ttft is not None:
                self.m_ttft.observe(ttft)
                # prefill span on this path: generate() start -> first
                # token readback (prefill + the first decode dispatch)
                pf = time.perf_counter() - t_gen
                span.set_prefill_seconds(pf)
                self.m_prefill.observe(pf)
            piece = tok.decode(t)
            eos_type = detector.append(t, piece)
            if eos_type in (EosResult.NOT_EOS, EosResult.EOS):
                delta = detector.get_delta()
                if delta:
                    emit(delta)
                    state["buffer"] += delta
                detector.reset()
            if eos_type == EosResult.EOS:
                state["hit_eos"] = True
                return False
            return True

        out_tokens, _, _ = engine.generate(
            tokens,
            max_steps=max_pred_pos - start_pos,
            on_token=on_token,
            start_pos=start_pos,
        )
        pos = prompt_end_pos + len(out_tokens)
        token = out_tokens[-1] if out_tokens else tokens[-1]
        hit_eos = state["hit_eos"]
        buffer = state["buffer"]

        n_completion = pos - prompt_end_pos
        if not hit_eos and pos < seq_len:
            # (block decode already wrote this KV row if the block ran past
            # max_pred_pos, but re-writing the same row is idempotent)
            # max_tokens truncation: the last sampled token's text is in
            # `buffer` but its KV entry was never written; run one KV-only
            # step so a cached continuation resumes from a complete context
            # (the reference skips this and silently degrades, dllama-api.cpp:470-475).
            engine.decode_step(token, pos)
            pos += 1

        message = ChatMessage("assistant", buffer)
        if pos >= seq_len:
            self.naive_cache.clear()
            engine.reset()
        else:
            # Record the conversation only now that its KV entries really
            # exist (pushing before prefill would let a failed request
            # poison the cache with positions that were never written).
            for m in delta_prompt:
                self.naive_cache.push(NaiveCacheItem(prompt_end_pos, m))
            self.naive_cache.push(NaiveCacheItem(pos, message))

        reason = "stop" if hit_eos else "length"
        if span.finish(
            reason, n_prompt=n_prompt_tokens, n_completion=n_completion
        ) is not None:
            self.m_finished.labels(reason=reason).inc()
            self.slo.observe_span(span)
        return _completion_response(
            self,
            buffer,
            reason,
            n_prompt_tokens,
            n_completion,
            span=span,
        )


def _span_metadata(span) -> dict | None:
    """Serving metadata exposed to clients (`dllama` field of the
    non-stream response and the final SSE chunk): request id, TTFT,
    queue wait, lane, reused prefix."""
    if span is None or span is NULL_SPAN:
        return None
    return {
        "request_id": span.request_id,
        "lane": span.lane,
        "ttft_ms": None if span.ttft_ms is None else round(span.ttft_ms, 3),
        "queue_ms": (
            None if span.queue_wait_ms is None
            else round(span.queue_wait_ms, 3)
        ),
        "reused_prefix_tokens": span.reused_prefix_tokens,
    }


def _completion_response(
    state: "ApiState",
    content: str,
    finish_reason: str,
    n_prompt: int,
    n_completion: int,
    span=None,
) -> dict:
    """The chat.completion response body, shared by the serialized and
    lane-scheduled serving paths."""
    resp = {
        "id": f"chatcmpl-{uuid.uuid4().hex[:12]}",
        "object": "chat.completion",
        "created": int(time.time()),
        "model": state.model_name,
        "choices": [
            {
                "index": 0,
                "message": {"role": "assistant", "content": content},
                "finish_reason": finish_reason,
            }
        ],
        "usage": {
            "prompt_tokens": n_prompt,
            "completion_tokens": n_completion,
            "total_tokens": n_prompt + n_completion,
        },
    }
    meta = _span_metadata(span)
    if meta is not None:
        resp["dllama"] = meta
    return resp


def _sse_write(wfile, data: str) -> None:
    """One HTTP-chunked SSE frame (shared by both streaming paths)."""
    raw = data.encode("utf-8")
    wfile.write(f"{len(raw):x}\r\n".encode() + raw + b"\r\n")


def _chunk_payload(
    state: ApiState,
    delta: str | None,
    stop: bool,
    reason: str = "stop",
    span=None,
) -> dict:
    choice: dict = {"index": 0, "finish_reason": reason if stop else None}
    if not stop:
        choice["delta"] = {"role": "assistant", "content": delta}
    payload = {
        "id": "cmpl-1",
        "object": "chat.completion.chunk",
        "created": int(time.time()),
        "model": state.model_name,
        "choices": [choice],
    }
    if stop:
        meta = _span_metadata(span)
        if meta is not None:
            payload["dllama"] = meta
    return payload


_KNOWN_PATHS = frozenset(
    {
        "/v1/chat/completions",
        "/v1/models",
        "/v1/health",
        "/v1/debug/recorder",
        "/v1/debug/memory",
        "/v1/debug/compile",
        "/v1/debug/xlalint",
        "/v1/debug/kv",
        "/v1/debug/timeline",
        "/v1/debug/slo",
        "/v1/debug/admission",
        "/v1/debug/series",
        "/v1/debug/profile",
        "/v1/drain",
        "/dashboard",
        "/metrics",
        "/health",
        "/healthz",
    }
)


def make_handler(state: ApiState):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def _count_request(self) -> None:
            # unknown paths fold into one label so a scanner can't blow up
            # the metric's cardinality; query strings don't split series
            path = self.path.partition("?")[0]
            if path not in _KNOWN_PATHS:
                path = "other"
            state.m_http.labels(path=path).inc()

        def log_message(self, fmt, *args):  # quiet access log
            pass

        def _json(
            self, payload: dict, status: int = 200,
            retry_after: int | None = None,
        ) -> None:
            body = json.dumps(payload).encode("utf-8")
            self.send_response(status)
            self.send_header("Access-Control-Allow-Origin", "*")
            self.send_header("Content-Type", "application/json; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            if retry_after is not None:
                # shed/drain refusals tell the client when to come back
                self.send_header("Retry-After", str(retry_after))
            self.end_headers()
            self.wfile.write(body)

        def do_OPTIONS(self):  # CORS preflight (reference: writeCors)
            self.send_response(204)
            self.send_header("Access-Control-Allow-Origin", "*")
            self.send_header(
                "Access-Control-Allow-Methods", "GET, POST, PUT, DELETE"
            )
            self.send_header(
                "Access-Control-Allow-Headers", "Content-Type, Authorization"
            )
            self.end_headers()

        def do_GET(self):
            self._count_request()
            if state.replica_id is not None:
                set_thread_replica(state.replica_id)
            # /v1/debug/timeline takes ?request_id=...; parse by hand so
            # the other exact-match branches tolerate stray queries too
            path, _, query = self.path.partition("?")
            params = parse_qs(query)
            if path == "/v1/models":
                self._json(
                    {
                        "object": "list",
                        "data": [
                            {
                                "id": state.model_name,
                                "object": "model",
                                "created": 0,
                                "owned_by": "user",
                            }
                        ],
                    }
                )
            elif path == "/metrics":
                # the shared refresh path (device memory, SLO windows,
                # step cost) — the series sampler runs the SAME hooks, so
                # scrape and sampler always agree
                state.obs.run_refresh_hooks()
                body = state.obs.render().encode("utf-8")
                self.send_response(200)
                self.send_header("Content-Type", state.obs.CONTENT_TYPE)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            elif path == "/v1/health":
                # composed status (ok/degraded/draining) — the same
                # snapshot postmortem dumps embed (ApiState.health_snapshot)
                self._json(state.health_snapshot())
            elif path == "/v1/debug/recorder":
                # the engine flight recorder's ring: the last N
                # dispatches/compiles/epochs/scheduler decisions
                self._json(state.recorder.dump())
            elif path == "/v1/debug/memory":
                stats = sample_device_memory(state.obs)
                mr = state.mem_report
                self._json(
                    {
                        "devices": stats,
                        "analytic": {
                            "params_bytes": mr.params_bytes,
                            "cache_bytes": mr.cache_bytes,
                            "total_bytes": mr.total_bytes,
                            "per_device_bytes": mr.per_device_bytes,
                        },
                        "comparison": compare_with_analytic(
                            mr.per_device_bytes, stats
                        ),
                    }
                )
            elif path == "/v1/debug/kv":
                # paged-KV pool + radix tree accounting (lane path);
                # {"enabled": false} when sharing is off or single-lane
                if state.kv_manager is None:
                    self._json({"enabled": False})
                else:
                    payload = state.kv_manager.debug()
                    payload["enabled"] = True
                    self._json(payload)
            elif path == "/v1/debug/compile":
                self._json(
                    {
                        "programs": state.engine.compile_cache_report(),
                        "cost": state.engine.cost_report(),
                    }
                )
            elif path == "/v1/debug/xlalint":
                # compiled-program lint over the live compile cache:
                # donation/collective/dtype/host/cost-budget findings
                # split new-vs-baselined (docs/static_analysis.md)
                self._json(state.engine.xlalint_report())
            elif path == "/v1/debug/timeline":
                # Chrome-trace / Perfetto JSON of the span ring; with
                # ?request_id= it narrows to one request and adds its
                # millisecond-accounting summary under "dllama". The
                # fleet stitcher adds ?replica= (keep only that replica's
                # spans — the in-process fleet shares one tracker),
                # ?pid_prefix= and ?pid_base= so merged fragments don't
                # collide (obs/spans.py, ISSUE 19)
                rid = (params.get("request_id") or [None])[0]
                rep = (params.get("replica") or [None])[0]
                prefix = (params.get("pid_prefix") or [None])[0]
                try:
                    base = int((params.get("pid_base") or ["0"])[0])
                except ValueError:
                    base = 0
                self._json(state.spans.chrome_trace(
                    request_id=rid, replica=rep, pid_prefix=prefix,
                    pid_base=base,
                ))
            elif path == "/v1/debug/slo":
                self._json(state.slo.snapshot())
            elif path == "/v1/debug/admission":
                # predictive-admission introspection (ISSUE 20): the
                # predictor's calibration, live occupancy, and recent
                # prediction-error percentiles
                self._json(state.admission_snapshot())
            elif path == "/v1/debug/series":
                # in-process time-series: no ?name= lists the tracked
                # series (plus the anomaly monitor's status); with
                # ?name=&window= it returns the trailing points
                name = (params.get("name") or [None])[0]
                if name is None:
                    self._json(
                        {
                            "names": state.series.names(),
                            "interval_s": state.series.interval_s,
                            "retention_s": state.series.retention_s,
                            "anomaly": state.anomaly.status(),
                        }
                    )
                    return
                try:
                    window = float(
                        (params.get("window") or ["300"])[0]
                    )
                except ValueError:
                    self._json(
                        {"error": {"message": "bad window"}}, 400
                    )
                    return
                result = state.series.query(name, window)
                if result is None:
                    self._json(
                        {"error": {"message": f"no series {name!r}"}}, 404
                    )
                    return
                self._json(result)
            elif path == "/dashboard":
                # single-file live dashboard (obs/dashboard.py): inline
                # HTML/JS sparklines over /v1/debug/series, no external
                # assets
                body = render_dashboard()
                self.send_response(200)
                self.send_header("Content-Type", DASHBOARD_CONTENT_TYPE)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            elif path in ("/health", "/healthz"):
                self._json({"status": "ok"})
            else:
                self.send_error(404, "Not Found")

        def do_POST(self):
            self._count_request()
            if state.replica_id is not None:
                # replica-attributed spans (ISSUE 19): handler threads are
                # per-request, so tag each one; the in-process fleet's
                # shared tracker then knows which replica each span is
                set_thread_replica(state.replica_id)
            path = self.path.partition("?")[0]
            if path == "/v1/debug/profile":
                self._profile()
                return
            if path == "/v1/drain":
                # graceful drain: stop admission, finish in-flight
                # streams, flush sinks, flip /v1/health to "draining"
                self._json(state.begin_drain())
                return
            if path != "/v1/chat/completions":
                self.send_error(404, "Not Found")
                return
            try:
                length = int(self.headers.get("Content-Length", "0"))
                body = json.loads(self.rfile.read(length) or b"{}")
                params = self._parse_params(body)
            except (ValueError, KeyError, TypeError) as e:
                self._json({"error": {"message": f"bad request: {e}"}}, 400)
                return

            if params.trace_id is not None:
                # fleet identity adopted: leave a recorder trail BEFORE
                # the shed gate so even refused relays are attributable
                state.recorder.record(
                    "trace_adopt", trace_id=params.trace_id,
                    request_id=params.request_id,
                    replica=state.replica_id,
                    resumed=params.resume_tokens is not None,
                )

            # load shedding BEFORE the request touches the queue or the
            # engine lock: a refused request costs the server nothing
            shed = state.admission_decision(params.priority, params)
            if shed is not None:
                reason, retry_after = shed
                state.m_shed.labels(reason=reason).inc()
                state.recorder.record(
                    "request_shed", reason=reason,
                    priority=params.priority, retry_after_s=retry_after,
                )
                self._json(
                    {
                        "error": {
                            "message": f"request shed: {reason}",
                            "retryable": True,
                            "retry_after_s": retry_after,
                        }
                    },
                    503 if reason == "draining" else 429,
                    retry_after=retry_after,
                )
                return

            if state.scheduler is not None:
                self._complete_lanes(params)
                return
            if params.resume_tokens is not None:
                # the serialized (batch_size == 1) path has no
                # recovery-admission machinery; a resume there would
                # silently retokenize — refuse instead
                self._json(
                    {
                        "error": {
                            "message": "resume_tokens requires the lane "
                            "scheduler (batch_size > 1)",
                        }
                    },
                    400,
                )
                return
            span = state.tracer.span(
                request_id=params.request_id, path="single",
                trace_id=params.trace_id,
            )
            with state.lock:
                # queue wait on this path is the engine-lock wait
                state.m_queue_wait.observe(span.mark_admitted())
                state.m_admissions.inc()
                state.m_lanes_active.set(1)
                try:
                    if params.stream:
                        self._stream(params, span)
                    else:
                        try:
                            response = state.complete(
                                params, emit=lambda d: None, span=span
                            )
                        except ValueError as e:  # client-caused (e.g. prompt too long)
                            self._json({"error": {"message": str(e)}}, 400)
                            return
                        except Exception as e:  # surface model errors as JSON
                            self._json({"error": {"message": str(e)}}, 500)
                            return
                        self._json(response)
                finally:
                    state.m_lanes_active.set(0)

        def _profile(self) -> None:
            """POST /v1/debug/profile — on-demand jax.profiler capture.

            Body: {"seconds": 2.0, "out_dir": "..."} (both optional).
            One capture at a time (409 while another runs); the hardened
            telemetry.profile() context logs-and-continues on backends
            where tracing is unavailable, so this is CPU-safe."""
            import os
            import tempfile

            from ..utils import telemetry

            try:
                length = int(self.headers.get("Content-Length", "0"))
                body = json.loads(self.rfile.read(length) or b"{}")
                seconds = float(body.get("seconds", 2.0))
                out_dir = body.get("out_dir")
            except (ValueError, TypeError) as e:
                self._json({"error": {"message": f"bad request: {e}"}}, 400)
                return
            if not (0.0 < seconds <= 60.0):
                self._json(
                    {"error": {"message": "seconds must be in (0, 60]"}},
                    400,
                )
                return
            if not out_dir:
                out_dir = os.path.join(
                    tempfile.gettempdir(),
                    f"dllama-profile-{uuid.uuid4().hex[:8]}",
                )
            if not state.profile_lock.acquire(blocking=False):
                self._json(
                    {"error": {"message": "a capture is already running"}},
                    409,
                )
                return
            try:
                with telemetry.profile(out_dir):
                    time.sleep(seconds)
            finally:
                state.profile_lock.release()
            n_files = 0
            for _, _, files in os.walk(out_dir):
                n_files += len(files)
            state.recorder.record(
                "profile_capture", log_dir=out_dir, seconds=seconds,
                n_files=n_files,
            )
            self._json(
                {"log_dir": out_dir, "seconds": seconds, "n_files": n_files}
            )

        def _complete_lanes(self, params: InferenceParams) -> None:
            """Concurrent path: submit to the lane scheduler and relay its
            event stream; many handler threads can sit here at once."""
            # `seed` is honored per lane (r5): the scheduler threads it
            # to decode_lanes, whose per-lane (seed, position) keys make
            # the stream reproducible independent of other lanes
            job = state.scheduler.submit(params)
            if params.stream:
                self._sse_headers()
                finish_reason = "stop"
                errored = False
                try:
                    while True:
                        kind, payload = job.events.get()
                        if kind == "delta":
                            # include_tokens deltas arrive as dicts with
                            # exact token/piece attribution; plain deltas
                            # (and the public-prompt echo) stay strings
                            if isinstance(payload, dict):
                                chunk = _chunk_payload(
                                    state, payload["text"], stop=False
                                )
                                chunk["dllama_tokens"] = payload["tokens"]
                                chunk["dllama_piece"] = payload["piece"]
                            else:
                                chunk = _chunk_payload(
                                    state, payload, stop=False
                                )
                                if params.include_tokens:
                                    # prompt-echo text: no generated
                                    # tokens back it (they are already in
                                    # the prompt), but the piece field
                                    # keeps exact-text accounting whole
                                    chunk["dllama_tokens"] = []
                                    chunk["dllama_piece"] = payload
                            # one span per SSE frame: a slow client's
                            # socket backpressure shows up on the http
                            # track of the timeline, not as engine time
                            with state.spans.span(
                                "sse_flush", component="http",
                                request_id=job.span.request_id,
                                lane=job.span.lane,
                            ):
                                # chaos site: a mid-stream client death is
                                # indistinguishable from a flush failure,
                                # so inject it AS one (exercises the
                                # cancel path below)
                                # `op` scopes the injection to one
                                # replica (sse_flush:op=r1:...) so fleet
                                # chaos can kill a single replica's
                                # streams while its siblings stay clean
                                fault = get_fault_plane().draw(
                                    "sse_flush", op=state.replica_id
                                )
                                if fault is not None:
                                    raise OSError(str(fault))
                                _sse_write(
                                    self.wfile,
                                    f"data: {json.dumps(chunk)}\r\n\r\n",
                                )
                        elif kind == "error":
                            err = (
                                payload
                                if isinstance(payload, dict)
                                else {"message": str(payload)}
                            )
                            _sse_write(
                                self.wfile,
                                "data: "
                                + json.dumps({"error": err})
                                + "\r\n\r\n",
                            )
                            errored = True
                            break
                        else:  # done
                            finish_reason = payload
                            break
                    if not errored:
                        final = _chunk_payload(
                            state, None, True, finish_reason, span=job.span
                        )
                        _sse_write(
                            self.wfile,
                            "data: " + json.dumps(final) + "\r\n\r\n",
                        )
                    _sse_write(self.wfile, "data: [DONE]\r\n\r\n")
                    self.wfile.write(b"0\r\n\r\n")
                except OSError:
                    # client went away: tell the scheduler to stop paying
                    # for this lane (the serialized path aborts via the
                    # emit exception; this is the lane-mode equivalent).
                    # The chunked body is unterminated, so this keep-alive
                    # connection can never carry another request — close
                    # it, which is also what lets a fleet router observe
                    # the death as EOF instead of a stalled read
                    job.cancelled = True
                    self.close_connection = True
                return
            finish_reason = "stop"
            while True:
                kind, payload = job.events.get()
                if kind == "error":
                    err = (
                        payload
                        if isinstance(payload, dict)
                        else {"message": str(payload)}
                    )
                    # a retryable failure (engine fault, not the client's
                    # request) answers 503 + Retry-After; validation
                    # errors keep their 500
                    self._json(
                        {"error": err},
                        503 if err.get("retryable") else 500,
                        # derived Retry-After (ISSUE 20): quote the
                        # predicted queue-drain, not a constant
                        retry_after=(
                            state.predicted_retry_after()
                            if err.get("retryable")
                            else None
                        ),
                    )
                    return
                if kind == "done":
                    finish_reason = payload
                    break
            response = _completion_response(
                state,
                job.buffer,
                finish_reason,
                job.n_prompt_tokens,
                job.n_completion,
                span=job.span,
            )
            self._json(response)

        def _sse_headers(self) -> None:
            self.send_response(200)
            self.send_header("Access-Control-Allow-Origin", "*")
            self.send_header("Content-Type", "text/event-stream; charset=utf-8")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()

        def _stream(self, params: InferenceParams, span=None) -> None:
            self._sse_headers()

            def write_chunk(data: str) -> None:
                _sse_write(self.wfile, data)

            def emit(delta: str) -> None:
                payload = _chunk_payload(state, delta, stop=False)
                write_chunk(f"data: {json.dumps(payload)}\r\n\r\n")

            finish_reason = "stop"
            try:
                result = state.complete(params, emit=emit, span=span)
                finish_reason = result["choices"][0]["finish_reason"]
            except OSError:
                # the client disconnected mid-stream (emit hit its dead
                # socket); complete() already recorded the cancellation —
                # nothing left to write to
                return
            except Exception as e:
                # headers are already sent; deliver the error in-stream so
                # the client still gets a well-formed SSE termination
                write_chunk(
                    f"data: {json.dumps({'error': {'message': str(e)}})}\r\n\r\n"
                )
            write_chunk(
                "data: "
                + json.dumps(
                    _chunk_payload(state, None, True, finish_reason, span=span)
                )
                + "\r\n\r\n"
            )
            write_chunk("data: [DONE]\r\n\r\n")
            self.wfile.write(b"0\r\n\r\n")

        def _parse_params(self, body: dict) -> InferenceParams:
            """(reference: parseRequest, src/dllama-api.cpp:491-520)"""
            params = InferenceParams(
                temperature=state.default_temperature,
                top_p=state.default_top_p,
                stop=[],
            )
            if body.get("resume_tokens") is not None:
                # fleet failover resume: a raw fed-token history stands in
                # for the chat messages (lane path only; see do_POST)
                params.resume_tokens = [
                    int(t) for t in body["resume_tokens"]
                ]
                params.messages = [
                    ChatMessage(m["role"], m["content"])
                    for m in body.get("messages", [])
                ]
            else:
                params.messages = [
                    ChatMessage(m["role"], m["content"])
                    for m in body["messages"]
                ]
            if "include_tokens" in body:
                params.include_tokens = bool(body["include_tokens"])
            if "stream" in body:
                params.stream = bool(body["stream"])
            if "temperature" in body:
                params.temperature = float(body["temperature"])
            if "top_p" in body:
                params.top_p = float(body["top_p"])
            if "seed" in body:
                params.seed = int(body["seed"])
            if "max_tokens" in body:
                params.max_tokens = int(body["max_tokens"])
            if "stop" in body:
                stop = body["stop"]
                # OpenAI allows a bare string or a list of strings
                params.stop = [stop] if isinstance(stop, str) else [str(x) for x in stop]
            if "priority" in body:
                priority = str(body["priority"])
                if priority not in ("low", "normal", "high"):
                    raise ValueError(f"unknown priority {priority!r}")
                params.priority = priority
            # predictive admission (ISSUE 20): optional latency budgets.
            # Body fields win; the x-dllama-deadline-ms relay header
            # (fleet router) backstops deadline_ms so budgets survive
            # relays and failover re-issues
            if body.get("deadline_ms") is not None:
                params.deadline_ms = float(body["deadline_ms"])
                if params.deadline_ms <= 0:
                    raise ValueError("deadline_ms must be > 0")
            if body.get("ttft_budget_ms") is not None:
                params.ttft_budget_ms = float(body["ttft_budget_ms"])
                if params.ttft_budget_ms <= 0:
                    raise ValueError("ttft_budget_ms must be > 0")
            hdr_deadline = self.headers.get("x-dllama-deadline-ms")
            if hdr_deadline and params.deadline_ms is None:
                try:
                    params.deadline_ms = float(hdr_deadline)
                except ValueError:
                    pass  # a malformed relay header never fails the request
                else:
                    if params.deadline_ms <= 0:
                        params.deadline_ms = None
            # fleet trace propagation (ISSUE 19): adopt the router-minted
            # identity headers; absent outside a fleet
            trace_id = self.headers.get("x-dllama-trace")
            request_id = self.headers.get("x-dllama-request")
            if trace_id:
                params.trace_id = str(trace_id)
            if request_id:
                params.request_id = str(request_id)
            return params

    return Handler


def serve(
    engine: InferenceEngine,
    tokenizer: Tokenizer,
    host: str = "0.0.0.0",
    port: int = 9990,
    model_name: str = "dllama-tpu",
    chat_template_type: ChatTemplateType = ChatTemplateType.UNKNOWN,
    trace_out: str | None = None,
    postmortem_dir: str | None = None,
    lane_block_size: int = 8,
    admission_chunk: int = 0,
    kv_page_size: int = 0,
    kv_pool_pages: int = 0,
    kv_native: bool = False,
    max_streams: int = 0,
    timeline_out: str | None = None,
    slo_ttft_ms: float | None = None,
    slo_tpot_ms: float | None = None,
    series_retention: float = 3600.0,
    speculation: str = "off",
    spec_k: int = DEFAULT_SPEC_K,
    draft_model: str | None = None,
    retry_max: int = 3,
    retry_backoff_ms: int = 5,
    max_queue_depth: int = 0,
    faults: str | None = None,
    replica_id: str | None = None,
    admission_predict: bool = False,
    admission_max_wait_ms: int = 30_000,
    deadline_default_ms: int = 600_000,
    deadline_priority_step_ms: int = 60_000,
):
    """The HTTP server over `engine`. The knobs are the flags of
    `cli.add_engine_args` under the same names and defaults, each with one
    spelling: `ApiState` and its `LaneScheduler` take them as given."""
    if speculation == "draft":
        if draft_model is None:
            raise ValueError(
                "--speculation draft needs a draft checkpoint: pass "
                "--draft-model"
            )
        # load BEFORE ApiState: the scheduler's admission rehearsal
        # prefetches draft_prefill/draft_step only if the model is there
        engine.init_draft_model(draft_model)
    if faults is not None:
        # arm the process-wide chaos plane for this server's lifetime
        # (--faults; the env spec DLLAMA_FAULTS armed it at import)
        set_fault_plane(faults)
    state = ApiState(
        engine,
        tokenizer,
        model_name,
        chat_template_type,
        tracer=Tracer(sink_path=trace_out) if trace_out else None,
        lane_block_size=lane_block_size,
        admission_chunk=admission_chunk,
        kv_page_size=kv_page_size,
        kv_pool_pages=kv_pool_pages,
        kv_native=kv_native,
        max_streams=max_streams,
        slo_ttft_ms=slo_ttft_ms,
        slo_tpot_ms=slo_tpot_ms,
        series_retention=series_retention,
        speculation=speculation,
        spec_k=spec_k,
        retry_max=retry_max,
        retry_backoff_ms=retry_backoff_ms,
        max_queue_depth=max_queue_depth,
        replica_id=replica_id,
        admission_predict=admission_predict,
        admission_max_wait_ms=admission_max_wait_ms,
        deadline_default_ms=deadline_default_ms,
        deadline_priority_step_ms=deadline_priority_step_ms,
    )
    if postmortem_dir:
        # a crashed scheduler loop / engine step dumps the event ring here
        state.recorder.postmortem_dir = postmortem_dir
    if timeline_out:
        # every completed span is appended once, as it completes
        state.spans.set_sink(timeline_out)
    server = ThreadingHTTPServer((host, port), make_handler(state))
    server.state = state  # tests and callers reach the tracer/registry here
    inner_close = server.server_close

    def _close_and_flush():
        inner_close()
        if state.scheduler is not None:
            state.scheduler.stop()
        # the last completion stamps reach the timeline before its sink closes
        state.engine.close()
        if state.watchdog is not None:
            state.watchdog.stop()
        # join the sampler so a closed server (and test churn) never
        # leaks a thread mutating the shared registry
        state.sampler.stop()
        if timeline_out:
            state.spans.set_sink(None)

    server.server_close = _close_and_flush
    if host in ("0.0.0.0", "127.0.0.1"):
        print(f"Server URL: http://localhost:{port}/v1/")
    return server  # caller runs serve_forever() (tests drive it in a thread)


def _install_drain_handler(server) -> None:
    """SIGTERM = graceful drain (the rolling-restart primitive a replica
    router relies on): stop admission, let in-flight streams finish (60 s
    cap), flush sinks, then shut the HTTP server down. Signal handlers
    only install from the main thread; anywhere else (tests driving
    main() in a worker) this is a no-op."""
    import signal

    def _on_term(signum, frame):
        server.state.begin_drain()

        def _wait_and_stop():
            server.state.drained.wait(timeout=60.0)
            server.shutdown()

        threading.Thread(  # dlint: disable=thread-hygiene — process is exiting; server.shutdown() is the terminal act
            target=_wait_and_stop, daemon=True, name="dllama-drain-stop"
        ).start()

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except ValueError:
        pass


def build_arg_parser():
    import argparse

    from ..cli import add_engine_args

    parser = argparse.ArgumentParser(prog="dllama-tpu-api")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=9990)
    add_engine_args(parser)  # includes --trace-out (the JSONL sink)
    return parser


def serve_from_args(args):
    """Engine + HTTP server from parsed CLI args: everything `main` does
    short of `serve_forever()`, so whoever needs the server a deployment
    would start (chip_smoke.py, tests) builds it the same way. The engine
    is `server.state.engine`."""
    import os

    from ..cli import load_engine

    engine, tok = load_engine(args)
    ttype = (
        CHAT_TEMPLATE_NAMES[args.chat_template]
        if args.chat_template
        else ChatTemplateType.UNKNOWN
    )
    return serve(
        engine,
        tok,
        host=args.host,
        port=args.port,
        model_name=os.path.basename(args.model),
        chat_template_type=ttype,
        trace_out=args.trace_out,
        postmortem_dir=args.postmortem_dir,
        lane_block_size=args.lane_block_size,
        admission_chunk=args.admission_chunk,
        kv_page_size=args.kv_page_size,
        kv_pool_pages=args.kv_pool_pages,
        kv_native=args.kv_native,
        max_streams=args.max_streams,
        timeline_out=args.timeline_out,
        slo_ttft_ms=args.slo_ttft_ms,
        slo_tpot_ms=args.slo_tpot_ms,
        series_retention=args.series_retention,
        speculation=args.speculation,
        spec_k=args.spec_k,
        draft_model=args.draft_model,
        retry_max=args.retry_max,
        retry_backoff_ms=args.retry_backoff_ms,
        max_queue_depth=args.max_queue_depth,
        faults=args.faults,
        replica_id=args.replica_id,
        admission_predict=args.admission_predict,
        admission_max_wait_ms=args.admission_max_wait_ms,
        deadline_default_ms=args.deadline_default_ms,
        deadline_priority_step_ms=args.deadline_priority_step_ms,
    )


# Start-up attempts, 3 s apart (the reference's dllama-api retries app init
# every 3 s forever, dllama-api.cpp:616-628). Only what a restart can cure
# is retried: an OS-level error such as a port still held by the previous
# process. A kernel the compiler refuses, a chip another process holds, a
# missing file or a bad setting raise straight out and the process exits
# non-zero — never a server that neither listens nor dies.
START_ATTEMPTS = 3


def main(argv=None) -> None:
    import gc

    from ..parallel.mesh import enable_compilation_cache

    args = build_arg_parser().parse_args(argv)
    enable_compilation_cache()
    for attempt in range(1, START_ATTEMPTS + 1):
        try:
            server = serve_from_args(args)
            break
        except FileNotFoundError:
            raise
        except OSError as e:
            if attempt == START_ATTEMPTS:
                raise
            print(f"⚠️  {e}; retry {attempt}/{START_ATTEMPTS - 1} in 3s...")
        gc.collect()  # the failed attempt's engine must not pin device memory
        time.sleep(3)
    _install_drain_handler(server)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
