"""Predictive SLO-aware admission control (ISSUE 20).

PR 12's admission gate is reactive: it sheds on queue depth after the
queue has already built up, with a constant ``Retry-After``. This module
closes the loop from measurement to control. :class:`LoadPredictor`
forecasts a candidate request's TTFT and steady-state TPOT *before* the
scheduler commits a lane to it, from three inputs the engine already
tracks:

* the per-program cost model (``obs/cost.py`` analytic bytes over the
  chip's HBM peak — the cold-start floor before any step has run) and
  the observed ``dllama_engine_step_seconds`` percentiles for the
  relevant prefill-chunk / decode-block kinds once they exist;
* current occupancy — active lanes, parked streams, queued admission
  chunks, and the pending queue ahead of the candidate
  (:class:`OccupancySnapshot`, assembled by the scheduler under its
  lock);
* the radix-tree match length: a matched prefix is prefill the engine
  will skip, so a warm-prefix request is predicted (and admitted)
  cheaper than a cold one of the same length.

Requests carry optional deadline hints (``deadline_ms`` /
``ttft_budget_ms`` body fields; ``x-dllama-deadline-ms`` forwarded by
the fleet router). The scheduler turns the forecast into three control
actions:

* **infeasible-reject** — a hinted request whose predicted TTFT cannot
  meet its budget even if admitted now is rejected up front with a
  structured retryable error whose ``Retry-After`` is the predicted
  queue-drain time (monotonic in queue depth), not a constant. Unhinted
  requests are NEVER infeasible-rejected: with no hints the controller
  degrades exactly to the PR 12 ladder.
* **EDF lane picking** — the pending queue is ordered by earliest
  effective deadline (:func:`effective_deadline_ms`). The PR 12
  priority ladder becomes deadline *offsets* (high before normal before
  low, FIFO within a class), so ordering is unchanged when no hints are
  given.
* **deadline preemption** — an over-budget or deadline-blown
  low-priority stream is parked through the PR 16 ``_park_stream`` /
  resume contract when that flips a feasible hinted request from
  "reject" to "meet SLO". Parking never alters tokens, so preempted
  streams stay byte-identical on resume.

Prediction error (estimated vs observed TTFT/TPOT) is a first-class
metric; an EWMA multiplicative correction factor folds the observed
ratio back into the predictor so it self-calibrates on real hardware.
Prediction only gates and orders work — it never touches
``decode_lanes`` inputs — so greedy output under predictive admission
is byte-identical to predictive-off runs by construction.
"""

from __future__ import annotations

import math
import threading
import time

from typing import Callable

# step-histogram kinds the predictor reads (engine._m_step labels)
PREFILL_KIND = "prefill_lane_chunk"
DECODE_KIND = "decode_lanes"

# priority -> effective-deadline offset multiplier (offset = mult * step)
PRIORITY_OFFSET_MULT = {"high": -1.0, "normal": 0.0, "low": 1.0}

# EWMA correction clamp: a single wild observation (compile stall, GC
# pause) must not swing the predictor by more than this factor per side
_CORR_MIN, _CORR_MAX = 0.1, 10.0


def effective_deadline_ms(
    arrival_ms: float,
    priority: str = "normal",
    deadline_ms: float | None = None,
    ttft_budget_ms: float | None = None,
    default_ms: int = 600_000,
    priority_step_ms: int = 60_000,
) -> float:
    """The EDF sort key for one request, in the caller's clock domain.

    A hinted request's effective deadline is its arrival plus the
    tighter of its hints. An unhinted request gets a synthetic deadline
    ``arrival + default + offset(priority)`` — the priority ladder as
    deadline offsets, so with no hints EDF ordering is (priority class,
    arrival), exactly the PR 12 contract.
    """
    hint = None
    for h in (deadline_ms, ttft_budget_ms):
        if h is not None and (hint is None or h < hint):
            hint = h
    if hint is not None:
        return arrival_ms + float(hint)
    mult = PRIORITY_OFFSET_MULT.get(priority, 0.0)
    return arrival_ms + float(default_ms) + mult * float(priority_step_ms)


class OccupancySnapshot:
    """One consistent view of scheduler load, taken under the scheduler
    condition variable (see ``LaneScheduler.occupancy``). The engine
    contributes the static shape (lane count, chunk/block sizes); the
    scheduler contributes the dynamic load."""

    __slots__ = (
        "lanes_total", "active_lanes", "parked", "admitting",
        "admitting_chunks", "queue_depth", "block_size", "admission_chunk",
    )

    def __init__(
        self,
        lanes_total: int,
        active_lanes: int,
        parked: int = 0,
        admitting: int = 0,
        admitting_chunks: int = 0,
        queue_depth: int = 0,
        block_size: int = 16,
        admission_chunk: int = 128,
    ) -> None:
        self.lanes_total = lanes_total
        self.active_lanes = active_lanes
        self.parked = parked
        self.admitting = admitting
        self.admitting_chunks = admitting_chunks
        self.queue_depth = queue_depth
        self.block_size = block_size
        self.admission_chunk = admission_chunk

    @property
    def free_lanes(self) -> int:
        return max(
            0, self.lanes_total - self.active_lanes - self.admitting
        )

    @property
    def oversubscription(self) -> float:
        """Streams per lane (>= 1.0): parked streams time-share lanes
        through the PR 16 park/resume rotation, stretching every
        stream's effective TPOT by roughly this factor."""
        if self.lanes_total <= 0:
            return 1.0
        streams = self.active_lanes + self.admitting + self.parked
        return max(1.0, streams / self.lanes_total)

    def as_dict(self) -> dict:
        return {
            "lanes_total": self.lanes_total,
            "active_lanes": self.active_lanes,
            "free_lanes": self.free_lanes,
            "parked": self.parked,
            "admitting": self.admitting,
            "admitting_chunks": self.admitting_chunks,
            "queue_depth": self.queue_depth,
            "oversubscription": round(self.oversubscription, 3),
        }


class Prediction:
    """One forecast: predicted TTFT / steady-state TPOT for a candidate
    plus the queue-drain estimate behind its ``Retry-After``."""

    __slots__ = ("ttft_ms", "tpot_ms", "queue_wait_ms", "prefill_chunks")

    def __init__(
        self,
        ttft_ms: float,
        tpot_ms: float,
        queue_wait_ms: float,
        prefill_chunks: int,
    ) -> None:
        self.ttft_ms = ttft_ms
        self.tpot_ms = tpot_ms
        self.queue_wait_ms = queue_wait_ms
        self.prefill_chunks = prefill_chunks

    def as_dict(self) -> dict:
        return {
            "ttft_ms": round(self.ttft_ms, 3),
            "tpot_ms": round(self.tpot_ms, 3),
            "queue_wait_ms": round(self.queue_wait_ms, 3),
            "prefill_chunks": self.prefill_chunks,
        }


class LoadPredictor:
    """TTFT/TPOT forecaster over the engine's own physics.

    Step costs come from the measured ``dllama_engine_step_seconds``
    p50 per kind once at least ``min_step_samples`` dispatches exist;
    before that, from the XLA cost model (bytes accessed over the HBM
    peak) via :func:`~dllama_tpu.obs.cost.analytic_step_seconds`; and
    as a last resort from conservative floor constants, so the
    predictor always returns a finite forecast. An EWMA correction
    factor (observed/predicted ratio per signal) self-calibrates the
    model against what the serving path actually delivers.

    Thread-safety: predictions run on HTTP handler threads while
    observations land from the scheduler thread; the correction state
    takes one short lock.
    """

    # floors used before any measurement or cost model exists; generous
    # on purpose — an optimistic cold predictor would admit infeasible
    # work, a pessimistic one merely queues the first request
    COLD_PREFILL_CHUNK_S = 0.050
    COLD_DECODE_STEP_S = 0.020
    MIN_STEP_SAMPLES = 5

    def __init__(
        self,
        engine: object,
        clock: Callable[[], float] = time.monotonic,
        alpha: float = 0.2,
    ) -> None:
        self.engine = engine
        self._clock = clock
        self.alpha = float(alpha)
        self._lock = threading.Lock()
        # multiplicative EWMA corrections, observed/predicted
        self._ttft_corr = 1.0
        self._tpot_corr = 1.0
        self._n_obs = 0
        # analytic per-kind step seconds, resolved lazily once (the
        # compile cache walk is not free; invalidated never — the cost
        # model only tightens as more programs compile, and measured
        # percentiles take over after MIN_STEP_SAMPLES anyway)
        self._analytic: dict[str, float | None] = {}

    # -- step costs --------------------------------------------------------

    def _measured_step_s(self, kind: str) -> float | None:
        hist = getattr(self.engine, "_m_step", None)
        if hist is None:
            return None
        try:
            child = hist.labels(kind=kind)
        except Exception:  # dlint: disable=silent-except — best-effort cost probe; the predictor's cold floor is the documented fallback
            return None
        if getattr(child, "count", 0) < self.MIN_STEP_SAMPLES:
            return None
        return child.percentile(0.5)

    def _analytic_step_s(self, kind: str) -> float | None:
        if kind in self._analytic:
            return self._analytic[kind]
        est = None
        try:
            from ..obs.cost import analytic_step_seconds, hbm_peak_bytes_per_s

            peak = hbm_peak_bytes_per_s()
            report = self.engine.cost_report()
            info = report.get("kinds", {}).get(kind)
            if info is not None:
                est = analytic_step_seconds(
                    info.get("bytes_accessed"), peak
                )
        except Exception:  # dlint: disable=silent-except — cost model is advisory; a failed walk degrades to the cold floor, never blocks admission
            est = None
        self._analytic[kind] = est
        return est

    def step_seconds(self, kind: str, cold_default: float) -> float:
        """Best available estimate of one dispatch of ``kind``:
        measured p50 > analytic cost model > cold floor."""
        s = self._measured_step_s(kind)
        if s is not None and s > 0:
            return s
        s = self._analytic_step_s(kind)
        if s is not None and s > 0:
            return s
        return cold_default

    # -- forecasting -------------------------------------------------------

    def predict(
        self,
        n_prompt_tokens: int,
        occ: OccupancySnapshot,
        matched_tokens: int = 0,
    ) -> Prediction:
        """Forecast TTFT and steady-state TPOT for a candidate with
        ``n_prompt_tokens`` of prompt, of which ``matched_tokens`` are
        already resident in the radix tree (prefill the engine skips)."""
        chunk = max(1, occ.admission_chunk)
        prefill_s = self.step_seconds(PREFILL_KIND, self.COLD_PREFILL_CHUNK_S)
        decode_s = self.step_seconds(DECODE_KIND, self.COLD_DECODE_STEP_S)
        todo = max(0, int(n_prompt_tokens) - int(matched_tokens))
        # at least one chunk always runs: admission replays the last
        # matched token to produce the first logits
        n_chunks = max(1, math.ceil(todo / chunk))
        queue_wait_s = self.queue_drain_seconds(occ)
        # the admission loop interleaves one prefill chunk per tick with
        # the active lanes' decode block, so each chunk's wall time is
        # the chunk itself plus one decode dispatch when lanes are busy
        interleave_s = decode_s if occ.active_lanes > 0 else 0.0
        ttft_s = queue_wait_s + n_chunks * (prefill_s + interleave_s)
        # steady-state: one decode dispatch per token, stretched by the
        # park/resume rotation when streams oversubscribe lanes
        tpot_s = decode_s * occ.oversubscription
        with self._lock:
            ttft_corr, tpot_corr = self._ttft_corr, self._tpot_corr
        return Prediction(
            ttft_ms=ttft_s * 1000.0 * ttft_corr,
            tpot_ms=tpot_s * 1000.0 * tpot_corr,
            queue_wait_ms=queue_wait_s * 1000.0 * ttft_corr,
            prefill_chunks=n_chunks,
        )

    def queue_drain_seconds(self, occ: OccupancySnapshot) -> float:
        """Predicted time until the CURRENT backlog is admitted — what a
        shed response should quote as ``Retry-After``. Monotonic in
        queue depth by construction: every queued request adds its
        expected admission cost on top of the in-flight chunk backlog.
        """
        chunk_s = self.step_seconds(PREFILL_KIND, self.COLD_PREFILL_CHUNK_S)
        decode_s = self.step_seconds(DECODE_KIND, self.COLD_DECODE_STEP_S)
        # chunks still owed by streams mid-admission
        backlog_s = occ.admitting_chunks * chunk_s
        # each queued request: assume one admission-chunk prefill, plus
        # a share of a lane becoming free when none is (half a block of
        # decode per wave of lane turnover — a deliberately coarse but
        # monotonic stand-in for remaining stream length, which the
        # server cannot know)
        per_req_s = chunk_s
        if occ.free_lanes <= 0:
            per_req_s += max(1, occ.block_size) * decode_s * 0.5
        with self._lock:
            corr = self._ttft_corr
        return (backlog_s + occ.queue_depth * per_req_s) * corr

    def retry_after_s(
        self, occ: OccupancySnapshot, max_wait_ms: int = 30_000
    ) -> int:
        """``Retry-After`` seconds derived from the predicted drain:
        at least 1 (HTTP Retry-After is integral seconds and "now" is
        what the client just tried), capped by the max-wait knob."""
        drain_s = self.queue_drain_seconds(occ)
        cap_s = max(1.0, max_wait_ms / 1000.0)
        return int(min(cap_s, max(1.0, math.ceil(drain_s))))

    # -- feasibility -------------------------------------------------------

    def infeasible(
        self,
        pred: Prediction,
        ttft_budget_ms: float | None = None,
        deadline_ms: float | None = None,
        slack_factor: float = 1.0,
    ) -> bool:
        """Whether a hinted candidate cannot meet its budget even if
        admitted against the current occupancy. Callers must only apply
        this to requests that actually carry hints."""
        budget = None
        for h in (ttft_budget_ms, deadline_ms):
            if h is not None and (budget is None or h < budget):
                budget = h
        if budget is None:
            return False
        return pred.ttft_ms > budget * slack_factor

    # -- self-calibration --------------------------------------------------

    def observe_ttft(
        self, predicted_ms: float, observed_ms: float
    ) -> None:
        """Fold one (predicted, observed) TTFT pair into the EWMA
        correction. The ratio is clamped so one compile stall cannot
        poison the model."""
        if predicted_ms <= 0 or observed_ms <= 0:
            return
        ratio = min(_CORR_MAX, max(_CORR_MIN, observed_ms / predicted_ms))
        with self._lock:
            self._ttft_corr += self.alpha * (
                ratio * self._ttft_corr - self._ttft_corr
            )
            self._ttft_corr = min(
                _CORR_MAX, max(_CORR_MIN, self._ttft_corr)
            )
            self._n_obs += 1

    def observe_tpot(
        self, predicted_ms: float, observed_ms: float
    ) -> None:
        if predicted_ms <= 0 or observed_ms <= 0:
            return
        ratio = min(_CORR_MAX, max(_CORR_MIN, observed_ms / predicted_ms))
        with self._lock:
            self._tpot_corr += self.alpha * (
                ratio * self._tpot_corr - self._tpot_corr
            )
            self._tpot_corr = min(
                _CORR_MAX, max(_CORR_MIN, self._tpot_corr)
            )

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "ttft_correction": round(self._ttft_corr, 4),
                "tpot_correction": round(self._tpot_corr, 4),
                "n_observations": self._n_obs,
                "prefill_chunk_s": round(
                    self.step_seconds(
                        PREFILL_KIND, self.COLD_PREFILL_CHUNK_S
                    ), 6,
                ),
                "decode_step_s": round(
                    self.step_seconds(
                        DECODE_KIND, self.COLD_DECODE_STEP_S
                    ), 6,
                ),
            }
