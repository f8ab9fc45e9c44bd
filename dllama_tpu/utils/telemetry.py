"""Observability: memory reports, ICI traffic estimates, profiler hooks.

The reference's observability surface (SURVEY.md §5) is: required-memory
printout at startup (nn-core.cpp:175-189), per-token Eval/Sync ms +
Sent/Recv kB (dllama.cpp:59-66), and compile-time debug dumps. The TPU
equivalents here:

  * `memory_report` — exact per-leaf accounting of params + KV cache bytes,
    total and per-chip (what the reference's `printRequiredMemory` did);
  * `ici_traffic_per_token` — analytic bytes/token of tensor-parallel
    collectives (the Sent/Recv column: ICI traffic isn't countable from the
    host the way the reference counts socket bytes, but it is exactly
    determined by the sharding layout);
  * `profile` — context manager around jax.profiler for kernel-level traces
    (the deep-dive tool the reference lacked entirely).
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass

import jax
import numpy as np

from ..formats.model_file import LlmHeader


def _fmt_bytes(n: int) -> str:
    for unit, div in (("GB", 1024**3), ("MB", 1024**2), ("kB", 1024)):
        if n >= div:
            return f"{n / div:.2f} {unit}"
    return f"{n} B"


def _leaf_bytes(tree) -> int:
    return sum(
        x.size * x.dtype.itemsize
        for x in jax.tree.leaves(tree)
        if hasattr(x, "dtype")
    )


@dataclass
class MemoryReport:
    params_bytes: int
    cache_bytes: int
    n_devices: int
    replicated_bytes: int = 0
    tp_sharded_bytes: int = 0  # embed: split over tp, replicated elsewhere
    tp: int = 1

    @property
    def total_bytes(self) -> int:
        return self.params_bytes + self.cache_bytes

    @property
    def per_device_bytes(self) -> int:
        # replicated leaves (norms, rope) live whole on every chip; the
        # embed table splits over tp ONLY (P("tp", None)) and is
        # replicated across the remaining mesh axes; everything else
        # divides by the full mesh size
        n = max(self.n_devices, 1)
        tp = max(self.tp, 1)
        sharded = self.total_bytes - self.replicated_bytes - self.tp_sharded_bytes
        return (
            self.replicated_bytes
            + self.tp_sharded_bytes // tp
            + sharded // n
        )

    def print(self) -> None:
        print(f"💾 Params: {_fmt_bytes(self.params_bytes)}")
        print(f"💾 KV cache: {_fmt_bytes(self.cache_bytes)}")
        print(
            f"💾 Total: {_fmt_bytes(self.total_bytes)} "
            f"(~{_fmt_bytes(self.per_device_bytes)}/chip over "
            f"{self.n_devices} chip(s))"
        )


_REPLICATED_KEYS = {
    # embed left this set in r5: vocab-sharded over tp (param_spec_tree)
    "final_norm", "rope_cos", "rope_sin",
    "att_norm", "ffn_norm", "q_norm", "k_norm", "moe_gate",
    "post_att_norm", "post_ffn_norm", "expert_bias",
    # a gated short convolution's projections and taps: one device holds them
    "conv_in", "conv_out", "conv_w",
    # and a Mamba-2 mixer's
    "ssm_in", "ssm_out", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias", "ssm_a_log",
    "ssm_d", "ssm_norm",
}


def memory_report(params, cache, n_devices: int = 1, tp: int = 1) -> MemoryReport:
    """Accounting of the loaded model (reference: printRequiredMemory).
    Replication follows parallel/sharding.param_spec_tree: norms, gates
    and rope tables are whole on every chip; the embed table splits over
    `tp` (vocab-sharded, r5) and is replicated across the other axes."""
    replicated = 0
    for key in _REPLICATED_KEYS:
        for scope in (params, params.get("layers", {})):
            leaf = scope.get(key) if hasattr(scope, "get") else None
            if leaf is not None:
                replicated += _leaf_bytes(leaf)
    return MemoryReport(
        params_bytes=_leaf_bytes(params),
        cache_bytes=_leaf_bytes(cache),
        n_devices=n_devices,
        replicated_bytes=replicated,
        tp_sharded_bytes=_leaf_bytes(params.get("embed")),
        tp=tp,
    )


def ici_traffic_per_token(
    h: LlmHeader, tp: int, activation_bytes: float = 2.0,
    include_logits: bool = True, pp: int = 1,
    pp_activation_bytes: float | None = None,
) -> int:
    """Analytic per-decoded-token ICI bytes per chip for the TP/PP layout.

    TP: two all-reduces of a [dim] activation per layer (after attention's
    col-split wo and the FFN's col-split w2 — where the reference ran
    SYNC_NODE_SLICES + MERGE_ADD, llm.cpp:403,554) plus the logits
    all-gather (vocab/tp per chip receives the rest). Ring all-reduce moves
    2*(tp-1)/tp of the payload per chip. `activation_bytes`: 4 for the
    f32 psum payload, 1.125 for Q80-compressed sync
    (buffer_float_type="q80", parallel/collectives.psum_q80 — the
    reference's README.md:89 ~26% figure), 2 for bf16 GSPMD all-reduces.

    PP: one [dim] activation ppermute per pipeline tick (pp ticks per
    decode token, parallel/pipeline.forward_pp) plus the exit-register
    all-reduce — tiny next to the tp terms, listed for honesty. These
    hand-offs carry UNCOMPRESSED activations (the stage register's model
    dtype), so they get their own `pp_activation_bytes` (defaults to
    `activation_bytes`) — Q80 sync compression applies only to the tp
    partial-sum psums, never to the pipeline hops.
    """
    total = 0.0
    if tp > 1:
        ring = 2 * (tp - 1) / tp
        total += h.n_layers * 2 * h.dim * activation_bytes * ring
        # vocab-sharded embedding (r5): one [dim] psum assembling the
        # looked-up row — same payload class as a layer psum
        total += h.dim * activation_bytes * ring
        if include_logits:
            total += h.vocab_size * 4 * (tp - 1) / tp
    if pp > 1:
        ppb = activation_bytes if pp_activation_bytes is None else pp_activation_bytes
        total += pp * h.dim * ppb  # tick hand-offs
        total += 2 * (pp - 1) / pp * h.dim * ppb  # exit psum
    return int(total)


_COLLECTIVE_MARKERS = (
    "all-reduce", "allreduce", "all-gather", "allgather", "reduce-scatter",
    "reducescatter", "collective-permute", "collectivepermute", "all-to-all",
    "alltoall",
)


def measure_sync_ms(run_fn, steps: int = 3) -> float | None:
    """MEASURED per-call collective (sync) wall time — the counterpart of
    the reference's per-step sync clock (src/nn/nn-executor.cpp:158-163,
    printed per token by dllama.cpp:59-66). The reference wraps its
    socket waits in a timer; under XLA the collectives are fused into the
    compiled program, so the measurement comes from the profiler instead:
    run `run_fn()` `steps` times under `jax.profiler.trace`, parse the
    perfetto trace, and sum the durations of collective HLO events
    (all-reduce / all-gather / reduce-scatter / collective-permute /
    all-to-all) across device lanes, averaged over devices and calls.

    Returns ms per call per device, or None when the profile contains no
    trace (profiler unavailable). `run_fn` must block until the step
    really finished (readback), and must be IDEMPOTENT on engine state —
    callers re-run the upcoming step at a fixed position (rewriting the
    same KV rows), so the measurement does not perturb the stream."""
    import glob
    import gzip
    import json
    import os
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        try:
            with jax.profiler.trace(d):
                for _ in range(steps):
                    run_fn()
        except Exception:
            return None
        files = glob.glob(
            os.path.join(d, "**", "*.trace.json.gz"), recursive=True
        )
        total_us = 0.0
        pids = set()
        found = False
        for f in files:
            try:
                with gzip.open(f, "rt") as fh:
                    trace = json.load(fh)
            except Exception:
                continue
            for ev in trace.get("traceEvents", []):
                if ev.get("ph") != "X":
                    continue
                found = True
                name = str(ev.get("name", "")).lower()
                if any(m in name for m in _COLLECTIVE_MARKERS):
                    total_us += float(ev.get("dur", 0.0))
                    pids.add(ev.get("pid", 0))
        if not found:
            return None
        n_lanes = max(len(pids), 1)
        return total_us / 1000.0 / steps / n_lanes


@contextlib.contextmanager
def profile(log_dir: str | None):
    """jax.profiler trace scope; no-op when log_dir is falsy.

    Profiler failures degrade to a logged warning instead of killing the
    run: start_trace raises on a double-start (another profiler session
    alive in the process) and some backends lack the profiler service
    entirely — neither should take down the generation being profiled."""
    if not log_dir:
        yield
        return
    import logging

    log = logging.getLogger(__name__)
    started = False
    try:
        jax.profiler.start_trace(log_dir)
        started = True
    except Exception:
        log.warning(
            "jax.profiler.start_trace(%r) failed (already tracing, or "
            "profiler unavailable on this backend); continuing unprofiled",
            log_dir,
            exc_info=True,
        )
    try:
        yield
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
                print(f"🔬 Profile trace written to {log_dir}")
            except Exception:
                log.warning(
                    "jax.profiler.stop_trace() failed; the trace under %r "
                    "may be incomplete",
                    log_dir,
                    exc_info=True,
                )


class Counter:
    """Tiny run-length metric accumulator for the serving surface.

    Migrated onto the obs registry (obs/metrics.py): each named Counter
    doubles its (n, total_ms) into `dllama_<name>_events_total` /
    `dllama_<name>_ms_total` so CLI-side token accounting shows up on a
    server's ``GET /metrics`` scrape. The local ``n``/``total_ms``/``rate``
    surface is unchanged (and is what the printers read) — the registry
    copies are the exported view."""

    def __init__(self, name: str = ""):
        self.n = 0
        self.total_ms = 0.0
        self._m_events = self._m_ms = None
        if name:
            from ..obs.metrics import get_registry

            reg = get_registry()
            self._m_events = reg.counter(
                f"dllama_{name}_events_total",
                f"Events accumulated by the {name!r} telemetry counter.",
            )
            self._m_ms = reg.counter(
                f"dllama_{name}_ms_total",
                f"Milliseconds accumulated by the {name!r} telemetry "
                "counter.",
            )

    def add(self, ms: float, n: int = 1) -> None:
        self.n += n
        self.total_ms += ms
        if self._m_events is not None:
            self._m_events.inc(n)
            self._m_ms.inc(ms)

    @property
    def rate(self) -> float:
        return self.n * 1000.0 / self.total_ms if self.total_ms > 0 else 0.0
