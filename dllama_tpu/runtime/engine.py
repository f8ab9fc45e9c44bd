"""Inference engine: jitted prefill/decode over a (dp, tp) mesh.

TPU-native counterpart of the reference's runtime stack (NnExecutor +
RootLlmInference/WorkerLlmInference, src/nn/nn-executor.cpp +
src/app.cpp:170-230): the pthread step-list interpreter and the per-forward
control-packet broadcast collapse into two jit-compiled XLA programs
(prefill at a few bucketed chunk lengths, decode at T=1) with a donated KV
cache. Sampling for the greedy path is fused on-device so the decode loop
ships one int32 per token instead of a [vocab] logits row; the
temperature/top-p path uses the reference-parity host sampler.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
import weakref
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..formats.model_file import LlmHeader, ModelReader
from ..formats.quants import FloatType
from ..models import forward, init_kv_cache, load_params
from ..models.loader import packs_dense
from ..models.transformer import lanes_on_one_device
from ..ops.quant_matmul import PACKED_GROUP
from ..parallel import cache_specs, make_mesh, shard_params_put, validate_tp
from ..tokenizer import Tokenizer
from .done import DoneWatcher, Program
from .faults import get_fault_plane
from .sampler import Sampler

# the rungs a ladder takes between the smallest rung asked for and the
# largest: below about 128 rows a lane a chunk stops getting cheaper with
# its rows (the weights' intake sets its floor), and every rung is one more
# program to build at every attention window
MIDDLE_RUNGS = (128, 256)


def prefill_ladder(smallest: int, largest: int = 512) -> tuple[int, ...]:
    """The prefill chunk buckets, one compiled program a bucket and window
    (the reference's --nBatches plays the same role: its graphs are
    compiled-in for nBatches rows and prefill walks the prompt in
    nBatches-sized chunks): 1, the `smallest` rung a caller asks for
    (`--nbatches`), `largest`, and the middle rungs that lie strictly
    between the two. A chunk runs the smallest rung that covers it
    (`_bucket_for`), so a rung saves the padding rows above it."""
    middle = {b for b in MIDDLE_RUNGS if smallest < b < largest}
    return tuple(sorted({1, smallest, largest} | middle))


DEFAULT_PREFILL_BUCKETS = prefill_ladder(8)
# the smallest attention window of a cache of latent rows (`_attn_window`)
LATENT_MIN_WINDOW = 4096


def _chunk_mark(cache) -> jax.Array:
    """One int32 of a chunk program's output cache, whatever it reads: the
    small output beside the donated cache that says when the program has
    left the device (`InferenceEngine._launched`)."""
    leaf = jax.tree.leaves(cache)[0]
    return leaf[(0,) * leaf.ndim].astype(jnp.int32)  # a slice: no reshape of a sharded stack


def _sds(x):
    """ShapeDtypeStruct (with sharding) of one live array — the lowering
    spec the AOT pre-compiles consume."""
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=getattr(x, "sharding", None)
    )


def _topp_mask(probs, topp):
    """Top-p nucleus mask on device, [B, V] probs -> masked probs; `topp`
    is a scalar or a per-lane [B] vector.

    Same selection rule as the host sampler (apply the cutoff pre-filter
    (1 - topp) / (V - 1), then keep the smallest prefix of descending
    probs whose cumulative mass exceeds topp, including the crossing
    token — reference: sample_topp, tokenizer.cpp:426-467); topp outside
    (0, 1) keeps the full distribution, matching the host sampler's
    sample_mult fallthrough, and a cumsum that never crosses (f32
    rounding at topp near 1) keeps the cutoff-filtered set, matching the
    host's empty-`over` branch (which also samples from the filtered
    set). Split out so its support set can be equivalence-tested against
    the host rule (tests/test_engine.py).
    Known divergence: exact prob TIES at the nucleus boundary keep all
    tied tokens here (threshold rule) where the host keeps only those
    before its sort's crossing point — the host's own tie order is
    sort-dependent, so the boundary choice is arbitrary in both.
    """
    b, v = probs.shape
    topp_col = jnp.broadcast_to(
        jnp.atleast_1d(jnp.asarray(topp, jnp.float32)), (b,)
    )[:, None]
    topp_valid = jnp.logical_and(topp_col > 0.0, topp_col < 1.0)
    # host sampler pre-filter: rows below (1-topp)/(V-1) can never be part
    # of a nucleus that still needs them; the host drops them before its
    # sort and KEEPS ONLY the filtered set in the never-crosses fallback
    cutoff = (1.0 - topp_col) / jnp.float32(v - 1)
    pf = jnp.where(jnp.logical_and(topp_valid, probs < cutoff), 0.0, probs)
    sorted_probs = jnp.sort(pf, axis=-1)[..., ::-1]
    csum = jnp.cumsum(sorted_probs, axis=-1)
    crossed = csum > topp_col
    cross = jnp.where(
        jnp.any(crossed, axis=-1),
        jnp.argmax(crossed, axis=-1),
        v - 1,
    )
    thresh = jnp.take_along_axis(sorted_probs, cross[..., None], axis=-1)
    # never-crosses fallback: thresh is the smallest filtered value (> 0
    # rows kept), so the support is exactly the cutoff-filtered set
    thresh = jnp.maximum(thresh, cutoff)
    masked = jnp.where(pf >= thresh, pf, 0.0)
    return jnp.where(topp_valid, masked, probs)


def _sample(logits, temperature, topp, counts, draw):
    """The one sampling rule, [B, V] f32 -> [B] int32. Lanes at
    temperature 0 take the greedy argmax, the others a `draw` (from
    [B, V] log-probabilities to [B] ids) under the host sampler's
    selection rule (see _topp_mask). Softmax, vocabulary sort, running
    sum and draw sit in a branch the device enters only when a lane that
    `counts` (a [B] mask: live, inside its window) samples; a batch with
    none costs one argmax. The branch that samples is the unconditional
    formula whole, so its ids do not depend on which lanes are greedy."""
    b = logits.shape[0]
    temp_col = jnp.broadcast_to(
        jnp.atleast_1d(jnp.asarray(temperature, jnp.float32)), (b,)
    )[:, None]

    def greedy_side():
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled_side():
        probs = _topp_mask(
            jax.nn.softmax(logits / jnp.maximum(temp_col, 1e-6), axis=-1), topp
        )
        sampled = draw(jnp.log(probs + 1e-30)).astype(jnp.int32)
        return jnp.where(temp_col[:, 0] <= 0.0, greedy_side(), sampled)

    engaged = jnp.any(jnp.logical_and(counts, temp_col[:, 0] > 0.0))
    return lax.cond(engaged, sampled_side, greedy_side)


@jax.named_scope("sample")
def _sample_on_device(logits, temperature, topp, key):
    """Temperature + top-p sampling on device, [B, V] f32 -> [B] int32;
    `temperature`/`topp` may be per-lane [B] vectors, and lanes with
    temperature == 0 take the greedy argmax — so one compiled program
    serves any mix of sampling settings across lanes.

    Host-sampler selection rule (see _topp_mask) driven by the JAX PRNG
    instead of xorshift: on-device sampling keeps the decode loop free of
    per-token host round trips. Seeded runs are reproducible, just under a
    different (documented) RNG than the reference.
    """
    return _sample(
        logits, temperature, topp, jnp.ones(logits.shape[:1], jnp.bool_),
        lambda logp: jax.random.categorical(key, logp, axis=-1),
    )


@jax.named_scope("sample")
def _sample_per_lane(logits, temperature, topp, seeds, positions, counts):
    """Per-LANE seeded sampling: lane l's key derives from (seeds[l],
    positions[l]) only, so a seeded request's draws are reproducible
    regardless of which other lanes are active and of how the block
    decode is split (the key depends on the absolute position, not the
    block offset). Greedy lanes (temperature 0) ignore the key, and a
    lane outside `counts` (parked) engages nothing whatever its
    temperature."""

    def draw(logp):
        keys = jax.vmap(
            lambda s, p: jax.random.fold_in(jax.random.PRNGKey(s), p)
        )(seeds, positions)
        return jax.vmap(lambda k, row: jax.random.categorical(k, row))(
            keys, logp
        )

    return _sample(logits, temperature, topp, counts, draw)


@dataclasses.dataclass
class StepStats:
    """Per-forward timing surface (reference: dllama.cpp:59-66,88-95)."""

    time_ms: float
    n_tokens: int


@dataclasses.dataclass
class LaneBlock:
    """A decode block that `dispatch_lanes` enqueued and nobody has read
    back: what `collect_lanes` needs to wait for it and account for it."""

    out: object  # the program's un-read output, [n_steps, lanes (+ counts)]
    n_steps: int  # after the clamp at the context's end
    live: frozenset  # the lanes the program ran live
    native: bool
    program: Program  # its completion stamp in the making (`_launched`)
    t0: float  # its dispatch's begin, on the spans' clock
    t1: float  # its call's end: the program is enqueued
    fields: dict  # what `step_complete` repeats of `step_dispatch`
    seconds: float | None = None  # `step_complete`'s `ms`, once collected


# What a model keeps besides one stack of keys and values a position, and why
# each feature that is off by default does not run over it: `_refuse` raises
# from this table alone, so a new family writes one row. The four mesh flags
# (--tp, --sp, --pp, --dp) share the first reason.
_KEPT = (
    ("_latent", "a cache of latent rows", {
        "mesh": "latent attention layers over a cache of latent rows run on one device",
        "--kv-dtype": "a cache of latent rows is not quantized",
        "--speculation": "the verify programs are untested over a cache of latent rows",
        "--kv-native": "the pool-native programs read keys and values, not latent rows",
    }),
    ("_stateful", "a layer's state a lane", {
        "mesh": "layers that keep a state a lane run on one device",
        "--kv-dtype": "a layer's state a lane is not quantized",
        "--speculation": "a rejected draft would have moved a lane's states (a "
                         "convolution's rows, a recurrence), which cannot step back",
        "--kv-native": "the pool-native programs keep no lane state",
    }),
    ("_two_cache_kinds", "a ring cache under its window layers", {
        "mesh": "window attention layers over a ring cache run on one device",
        "--kv-dtype": "the window layers' ring cache is not quantized",
        "--speculation": "the verify programs are untested over the window "
                         "layers' ring cache",
        "--kv-native": "the pool-native programs read one kind of cache",
    }),
)


def chunk_takes_one_lane(h: LlmHeader) -> bool:
    """Whether some block of the model's chunk program takes ONE lane
    (`run_layers` under `live_lanes_alone`): a layer that keeps a state a
    lane takes one lane's state, a latent index builds one lane's mask. Such
    a program is wrong for two live lanes; every other computes each live
    lane's rows at that lane's position (the expert block a live lane after
    another), as the verify programs do, and one call can fill several
    admitting lanes' rows (`InferenceEngine.chunk_lanes`)."""
    return bool(h.stateful or h.indexed)


class InferenceEngine:
    """See module docstring. `batch_size` > 1 turns the batch axis into
    independent decoding lanes (`generate_batch`) — the data-parallel
    throughput surface the reference lacks (SURVEY.md §2 marks DP absent
    there)."""

    def __init__(
        self,
        model_path: str,
        tokenizer: Tokenizer | None = None,
        tp: int = 1,
        dp: int = 1,
        sp: int = 1,
        pp: int = 1,
        dtype=jnp.bfloat16,
        kv_dtype=None,
        max_seq_len: int = 0,
        batch_size: int = 1,
        temperature: float = 0.0,
        topp: float = 0.9,
        seed: int = 12345,
        prefill_buckets: tuple[int, ...] = DEFAULT_PREFILL_BUCKETS,
        matmul_precision: str | None = None,
        weight_format: str = "auto",
        buffer_float_type: str = "f32",
        moe_decode_dedup: bool | str = "auto",
    ):
        # observability hooks (obs/metrics.py): every handle below is a
        # no-op when the registry is disabled, so the decode path carries
        # one attribute read of overhead in that state. Created before the
        # first _fresh_cache() call (which bumps the epoch counter).
        from ..obs.metrics import (
            DEFAULT_TOKEN_BUCKETS_S,
            get_registry,
        )
        from ..obs.recorder import get_recorder

        self.obs = get_registry()
        # flight recorder (obs/recorder.py): structured engine events —
        # dispatches, compiles, cache epochs, errors — in a bounded ring;
        # /v1/debug/recorder dumps it, crashes postmortem it
        self.recorder = get_recorder()
        # span timelines (obs/spans.py): every dispatch below brackets a
        # component="engine" span, with a nested ".device" span splitting
        # host dispatch from device completion on the block-decode paths
        from ..obs.spans import get_span_tracker

        self._spans = get_span_tracker()
        self._m_step = self.obs.histogram(
            "dllama_engine_step_seconds",
            "Wall time of one engine dispatch (compiled program call + "
            "host readback), by step kind.",
            labelnames=("kind",),
        )
        self._m_compiles = self.obs.counter(
            "dllama_engine_compiles_total",
            "Compiled-program builds by origin: dispatch = synchronous "
            "compile on the serving path, prefetch = background window "
            "pre-compile, prefetch-failed = a broken prefetch (boundary "
            "will stall on a synchronous compile).",
            labelnames=("origin",),
        )
        self._m_xlalint = self.obs.counter(
            "dllama_xlalint_findings_total",
            "New (non-baselined) xlalint findings on freshly compiled "
            "programs; any increment means a compiled executable broke "
            "a donation/collective/dtype/host/cost-budget invariant.",
        )
        self._m_window_crossings = self.obs.counter(
            "dllama_engine_window_crossings_total",
            "Attention-window boundary crossings (a larger compiled "
            "window took over mid-generation).",
        )
        self._m_epochs = self.obs.counter(
            "dllama_engine_cache_epochs_total",
            "KV-cache rebuilds (engine init, reset, or crash-consistency "
            "recovery after a failed donated dispatch).",
        )
        self._m_tpot = self.obs.histogram(
            "dllama_engine_block_token_seconds",
            "Per-token share of a block decode dispatch (dispatch wall "
            "time / tokens in the block).",
            buckets=DEFAULT_TOKEN_BUCKETS_S,
        )
        self._m_sampler = self.obs.counter(
            "dllama_engine_decode_lanes_blocks_total",
            "decode_lanes blocks by what the sampler ran on the device: "
            "greedy = the argmax alone (no live lane had temperature > 0), "
            "full = softmax, top-p sort and draw on every step.",
            labelnames=("sampler",),
        )
        self._m_kv_copy_bytes = self.obs.counter(
            "dllama_kv_copy_bytes_total",
            "Device bytes moved by KV copy programs: slab adopt/publish "
            "page copies, plus the pool-native path's COW mid-page tail "
            "forks (its only remaining device copy — a full-page prefix "
            "adoption moves zero bytes).",
        )
        self._obs_last_window = None

        self.reader = ModelReader(model_path, max_seq_len=max_seq_len)
        self.header: LlmHeader = self.reader.header
        self.tokenizer = tokenizer
        # a model with window layers keeps them in a second cache stack,
        # written as a ring (models/transformer.init_kv_cache): one device
        # serves it, and what has not been made to work over two stacks
        # says so here and not in a wrong token
        from ..formats.model_file import layer_table

        self._two_cache_kinds = any(k.window for k in layer_table(self.header))
        # latent attention keeps one stack of `[c | k_rope]` rows, one head
        # for all, which heads-over-chips cannot divide
        self._latent = self.header.latent
        # layers that keep a state a lane and no cache row a position (gated
        # short convolutions, Mamba-2 mixers): the state stacks ride in
        # `self.cache` beside the attention layers' keys and values, on one
        # device
        self._stateful = self.header.stateful
        for flag, n in (("--tp", tp), ("--sp", sp), ("--pp", pp), ("--dp", dp)):
            if n > 1:
                self._refuse(f"{flag} {n}")
        if kv_dtype in ("int8", jnp.int8):
            self._refuse("--kv-dtype int8")
        if self._stateful and batch_size < 2:
            raise ValueError(
                f"--batch-size {batch_size}: a model with lane state is served by "
                f"the lane engine, which needs two lanes or more "
                f"({self.header.arch.name})"
            )
        validate_tp(self.header, tp)
        # sequence parallelism: the KV cache's sequence axis shards over sp
        # chips (the long-context axis; models/transformer._attention_sp).
        # Shard boundaries must tile the cache.
        if sp < 1 or (sp & (sp - 1)) != 0:
            raise ValueError(f"sp must be a power of two >= 1, got {sp}")
        if sp > 1 and self.header.seq_len % sp != 0:
            raise ValueError(
                f"seqLen {self.header.seq_len} not divisible by sp={sp}"
            )
        # pipeline stages: layer ranges per stage (parallel/pipeline.py) —
        # the capacity axis past the reference's nNodes <= nKvHeads bound.
        # Composes with tp (stages of tp groups), dp (lanes sharded inside
        # stages) and sp (stage-local sequence shards, manual merged-stats
        # attention).
        from ..parallel.pipeline import validate_pp

        validate_pp(self.header, pp)
        if dp > 1 and batch_size % dp != 0:
            raise ValueError(
                f"batch_size {batch_size} must divide over dp={dp} lanes"
            )
        self.mesh = make_mesh(tp=tp, dp=dp, sp=sp, pp=pp)
        self.tp, self.dp, self.sp, self.pp = tp, dp, sp, pp
        self.batch_size = batch_size
        self.dtype = dtype
        # kv_dtype "int8" (or jnp.int8) turns on the quantized KV cache
        # (models/transformer.QuantKV): per-row int8 values + f32 scales,
        # ~2x KV capacity vs bf16 — the long-context fit lever on top of
        # windowed reads
        if isinstance(kv_dtype, str):
            named = {
                "f32": jnp.float32,
                "f16": jnp.float16,
                "bf16": jnp.bfloat16,
                "int8": jnp.int8,
            }
            if kv_dtype not in named:
                raise ValueError(
                    f"kv_dtype must be one of {sorted(named)}, got "
                    f"{kv_dtype!r}"
                )
            kv_dtype = named[kv_dtype]
        self.kv_dtype = kv_dtype or dtype
        self.sampler = Sampler(self.header.vocab_size, temperature, topp, seed)
        self.temperature = temperature
        self._precision = matmul_precision
        # sp > 1: prefill chunks > 1 token shard their query axis over sp,
        # so buckets must divide evenly (width-1 chunks go through the
        # merged-stats decode branch instead)
        self.prefill_buckets = tuple(
            b
            for b in sorted(prefill_buckets)
            if b <= self.header.seq_len
            and (sp == 1 or b == 1 or b % sp == 0)
            # pp x sp: stage-local sp writes are windowed per shard, so a
            # chunk must fit one shard's local rows (run_layers sp_axis)
            and (pp == 1 or sp == 1 or b <= self.header.seq_len // sp)
        ) or ((1,) if sp == 1 else (sp,))

        # "auto": keep Q40 weights quantized on device when the Pallas path
        # is available (TPU), packed two nibbles a byte wherever the kernel
        # takes every dense matmul's in axis (models/loader.packs_dense;
        # else int8 values); dense bf16/f32 elsewhere (the CPU fallback
        # dequantizes per call, fine for tests, slow for serving).
        if weight_format == "auto":
            weight_format = "dense"
            if (
                self.header.weight_type == FloatType.Q40
                and jax.default_backend() == "tpu"
            ):
                weight_format = "q40i4" if packs_dense(self.reader.specs, tp) else "q40"
        if weight_format not in ("dense", "q40", "q40i4"):
            raise ValueError(
                f"weight_format must be 'auto', 'dense', 'q40' or 'q40i4', "
                f"got {weight_format!r}"
            )
        if weight_format == "q40i4" and tp > 1 and not packs_dense(self.reader.specs, tp):
            raise ValueError(
                f"q40i4 weight format with tp={tp} needs every dense matmul's "
                f"in dim divisible by {PACKED_GROUP * tp}"
            )
        self.weight_format = weight_format
        quantized = weight_format in ("q40", "q40i4")
        # Q80-compressed partial-sum all-reduces (the reference's
        # --buffer-float-type q80, src/llm.cpp:195): worthwhile on
        # DCN-connected multi-host pods where sync bytes are the
        # bottleneck; over single-host ICI the exact f32 psum is the
        # right default (ICI bandwidth dwarfs the [dim] payload).
        if buffer_float_type not in ("f32", "q80"):
            raise ValueError(
                f"buffer_float_type must be 'f32' or 'q80', got "
                f"{buffer_float_type!r}"
            )
        self._sync_quant = buffer_float_type == "q80"
        if quantized and tp > 1:
            # col-split quant weights shard the scale tensor's block axis
            # (in//32): every contraction dim must divide by 32*tp
            for dim_name, dim in [
                ("dim", self.header.dim),
                ("qDim", self.header.q_dim),
                ("hiddenDim", self.header.ff_dim),
            ]:
                if dim % (32 * tp) != 0:
                    raise ValueError(
                        f"q40 weight format with tp={tp} needs {dim_name} "
                        f"divisible by {32 * tp}, got {dim}"
                    )
        self.params = load_params(
            self.reader,
            dtype=dtype,
            put=shard_params_put(self.mesh, self.header),
            # q40i4 packs host-side inside the loader itself
            weight_format=weight_format,
            # quantized path: fuse q|k|v (and w1|w3 for dense-FFN archs)
            # into single shard-major-interleaved kernel launches — 7 -> 4
            # Pallas calls per decode layer (~41 us fixed cost each on
            # the round-3 chip run)
            fuse=tp if quantized else 0,
        )
        # what was loaded, by the form each leaf is held in, and how much of
        # a decode step's quantized bytes is packed (1.0 dense, and sparse
        # where the experts are packed too; a mesh's routed experts stay
        # int8): obs/cost.weight_bytes_by_form
        from ..obs.cost import weight_bytes_by_form

        self.weight_bytes = weight_bytes_by_form(self.params, self.header)
        g_weights = self.obs.gauge(
            "dllama_weight_bytes",
            "Resident bytes of the loaded parameters over the whole mesh, by "
            "the form a leaf is held in: packed = nibble words and their "
            "block scales, int8 = int8 values and their block scales, float "
            "= every other leaf.",
            labelnames=("form",),
        )
        for form in ("packed", "int8", "float"):
            g_weights.labels(form=form).set(self.weight_bytes[form])
        self.obs.gauge(
            "dllama_decode_step_packed_share",
            "Share of the quantized weight bytes a one-token decode step "
            "reads (dense stacks whole, routed expert stacks at the active "
            "share of the experts held) that is packed nibbles.",
        ).set(self.weight_bytes["decode_packed_share"])
        self.recorder.record("weights", format=weight_format, **self.weight_bytes)
        # Per-lane serving: lanes park their cache writes in padding rows
        # beyond seqLen while other lanes prefill/idle, so independent
        # requests can occupy the batch lanes at different positions.
        # Padding must cover the widest chunk a parked lane "writes";
        # under sp it is rounded up so the padded sequence axis still
        # tiles across the sp shards. Pipeline stages reuse the same
        # scratch rows for INVALID-tick writes (parallel/pipeline.py
        # park_pos): without padding every tick select-merges the whole
        # stage cache, which costs as much HBM as the stage weight read.
        pad = (
            max(self.prefill_buckets)
            if (batch_size > 1 or pp > 1 or self._two_cache_kinds) else 0
        )
        if pad and sp > 1:
            pad += (-pad) % sp
        self._lane_pad = pad
        self._park = self.header.seq_len  # first padding row
        # the window layers' ring: a window and the largest chunk, so that
        # a chunk, written before it is read, overwrites no row its first
        # query still sees; never more than the context. Its stack has the
        # same padding rows behind it for parked lanes' writes.
        self.kv_ring = (
            min(self.header.seq_len,
                self.header.sliding_window + max(self.prefill_buckets))
            if self._two_cache_kinds else 0
        )
        self._cache_sharding = {
            k: NamedSharding(self.mesh, spec)
            for k, spec in cache_specs(
                self.header, sp=sp > 1, pp=pp > 1
            ).items()
        }
        if self._two_cache_kinds:
            self._cache_sharding.update(
                kw=self._cache_sharding["k"], vw=self._cache_sharding["v"]
            )
        if self._latent:
            # the latent rows and, where an index picks among them, its keys
            names = ("c", "i") if self.header.indexed else ("c",)
            self._cache_sharding = {n: self._cache_sharding["k"] for n in names}
        # positions before an adopted prefix's end that a lane runs again to
        # rebuild its states (0: the model keeps none): a convolution layer's
        # state reaches back `conv_state_rows` positions of its own input, so
        # that of the k-th from the bottom k times as many of the tokens, and
        # an attention layer between them reads older positions from the cache
        # alone. Rounded up to a multiple of 8 rows. A recurrent state reaches
        # back to position 0 and no replay rebuilds it: `state_unbounded`, under
        # which no prefix is adopted and none stored.
        n_conv = sum(k.conv for k in layer_table(self.header))
        self.state_replay_rows = -(-n_conv * self.header.conv_state_rows // 8) * 8
        self.state_unbounded = self.header.state_unbounded
        if self._stateful:
            self._cache_sharding["s"] = NamedSharding(self.mesh, P())
            if self.state_unbounded:
                self._cache_sharding["r"] = NamedSharding(self.mesh, P())
            # where each lane's states stand: the position behind the last row
            # that moved them, None where nothing was installed
            self._state_pos: list[int | None] = [None] * batch_size
        self._m_state_installs = self.obs.counter(
            "dllama_lane_state_installs_total",
            "Lane states installed at an admission's first chunk: zero = from "
            "position 0, replay = rebuilt behind an adopted prefix by running "
            "the positions before its end again.",
            labelnames=("how",),
        )
        self._m_replay_tokens = self.obs.counter(
            "dllama_lane_state_replay_tokens_total",
            "Token rows of adopted prefixes that chunk programs ran again to "
            "rebuild a lane's states (cache writes masked).",
        )
        self._m_adoptions_declined = self.obs.counter(
            "dllama_prefix_adoptions_declined_total",
            "Stored prefixes a lane matched and did not adopt because its "
            "states could not be rebuilt behind them: unbounded = a recurrent "
            "state reaches back to position 0, short = the prefix is no longer "
            "than the positions a replay runs again.",
            labelnames=("why",),
        )
        self._m_ring_wraps = self.obs.counter(
            "dllama_kv_ring_wraps_total",
            "Times a lane's position passed the end of the window layers' "
            "ring cache and its writes began again at the ring's first row.",
        )
        self._m_index_rows = self.obs.counter(
            "dllama_attn_index_rows_total",
            "Cached rows a layer that live queries' index scored, and those "
            "of them the queries then attended to (at most index_topk a "
            "query), in decode blocks and prefill chunks.",
            labelnames=("kind",),
        )
        self._m_moe_pairs = self.obs.counter(
            "dllama_moe_pairs_total",
            "Token-expert pairs the router chose in decode blocks, where "
            "the experts compute the pairs that landed here (a held share, "
            "or a whole layer on one device): routed = all of live lanes, "
            "held = those that landed on an expert held here.",
            labelnames=("landed",),
        )
        self._m_moe_touched = self.obs.counter(
            "dllama_moe_held_experts_touched_total",
            "Held experts that some live lane's token was routed to, summed "
            "over the expert layers of every decode step: what the expert "
            "kernel had to read.",
        )
        self._m_moe_forms = self.obs.counter(
            "dllama_moe_block_forms_total",
            "Expert layers of prefill chunk programs, where the experts "
            "held here are a share of those the router scores, by the form "
            "their kernel's surroundings took: landed = sized by the pairs "
            "that landed here, whole = by every pair the router chose (more "
            "pairs landed than the landed form holds). Counted when the "
            "next decode block is collected.",
            labelnames=("program", "form"),
        )
        self._m_moe_chunk_rows = self.obs.counter(
            "dllama_moe_chunk_rows_total",
            "Token rows of prefill chunk programs by what the expert block "
            "did with them: computed = rows it routed and ran (the bucket, a "
            "carried lane), parked_skipped = parked lanes' rows it left out.",
            labelnames=("rows",),
        )
        self._m_prefill_chunks = self.obs.counter(
            "dllama_prefill_chunks_total",
            "Prefill chunk programs dispatched (prefill_lane_chunk), by the "
            "bucket they ran: the smallest rung of the ladder that covers "
            "the widest chunk they carried.",
            labelnames=("bucket",),
        )
        self._m_prefill_lanes = self.obs.counter(
            "dllama_prefill_lanes_total",
            "Lanes those chunk programs filled, summed over the programs: "
            "over dllama_prefill_chunks_total, the lanes a program carries "
            "(1 where the model's chunk program takes one lane: its state, "
            "its index's mask).",
        )
        self._m_prefill_rows = self.obs.counter(
            "dllama_prefill_rows_total",
            "Rows of those chunk programs: real = the tokens the carried "
            "lanes' chunks were asked for, bucket = the rows a carried lane "
            "(1 - real / bucket is the padded share), computed = the rows of "
            "every lane, parked ones too (real / computed is the share of a "
            "program's rows that are tokens).",
            labelnames=("kind",),
        )
        self._m_drained = self.obs.counter(
            "dllama_engine_device_drained_seconds_total",
            "Seconds the device stood drained and waited for the host: from "
            "the moment a program left the device to the begin of the next "
            "dispatch that is no pool copy, where that came later, by the "
            "step dispatched.",
            labelnames=("before",),
        )
        self._m_busy = self.obs.counter(
            "dllama_engine_device_busy_seconds_total",
            "Seconds the lane path's programs ran on the device, from their "
            "completion stamps, by step: its rate is the device's duty cycle.",
            labelnames=("step",),
        )
        self._m_dispatches = self.obs.counter(
            "dllama_engine_dispatches_total",
            "Dispatches that are no pool copy, by step and by whether the "
            "program enqueued before had left the device at the dispatch's "
            "begin (dry) or not (busy).",
            labelnames=("step", "device"),
        )
        # programs enqueued so far that a completion stamp is kept for (the
        # lane path's, counted where the call returned: one that raised
        # enqueued nothing); a chunk's forms and a block's handle keep the
        # count at their own
        self._enqueued = 0
        # the completion stamps (`runtime/done.py`): the programs enqueued
        # and not yet accounted for, in dispatch order (`_settle`), the
        # newest of all, and when the one before the first of them left
        # the device. The watcher reads the clock the engine reads (this
        # module's `time`, looked up at each reading)
        self._watcher = DoneWatcher(lambda: time.monotonic())
        weakref.finalize(self, self._watcher.close, 0)
        self._unsettled: collections.deque[Program] = collections.deque(maxlen=256)
        self._newest: Program | None = None
        self._last_done: float | None = None
        # the newest lane block while it is dispatched and not collected,
        # and the end of the newest collect (`collect_lanes`' `ms` starts
        # no earlier)
        self._uncollected: LaneBlock | None = None
        self._collected_at = 0.0
        self.cache = self._fresh_cache()
        g_bytes = self.obs.gauge(
            "dllama_kv_cache_bytes",
            "Device bytes of the lane KV cache by kind of layer: full = "
            "rows for the whole context, window = a ring of the window "
            "and one chunk, latent = one stack of [c | k_rope] rows for the "
            "whole context, index = the index keys beside them, conv = the "
            "state layers' convolution rows (rows a lane, not a position), "
            "recurrent = the Mamba-2 layers' float32 states a lane.",
            labelnames=("kind",),
        )
        self.kv_cache_bytes = {
            kind: sum(
                leaf.nbytes for name in names if name in self.cache
                for leaf in jax.tree.leaves(self.cache[name])
            )
            for kind, names in (
                ("full", ("k", "v")), ("window", ("kw", "vw")), ("latent", ("c",)),
                ("index", ("i",)), ("conv", ("s",)), ("recurrent", ("r",)))
            # a kind of its own where there is one
            if kind not in ("index", "conv", "recurrent") or names[0] in self.cache
        }
        for kind, n in self.kv_cache_bytes.items():
            g_bytes.labels(kind=kind).set(n)
        self.recorder.record(
            "kv_cache", ring=self.kv_ring, **{f"{k}_bytes": v for k, v in self.kv_cache_bytes.items()}
        )
        self._token_sharding = NamedSharding(self.mesh, P("dp", None))
        # each lane's last token of the newest decode block, on the device:
        # the next block's `last` (`_lane_decode_fn`)
        self._lane_last = jax.device_put(
            np.zeros((self.batch_size, 1), np.int32), self._token_sharding
        )
        # AOT lowering specs are SNAPSHOTTED once here (r5 advisor item):
        # params never change after init and every fresh cache has the
        # same shapes/dtypes/shardings, so the prefetch thread lowers
        # against this frozen tree instead of reading `self.cache` live —
        # the live tree's buffers may be donated (deleted) mid-read by a
        # concurrent dispatch on the serving thread.
        self._param_specs = jax.tree.map(_sds, self.params)
        self._cache_specs = jax.tree.map(_sds, self.cache)
        # resident draft model (second-generation speculation): loaded on
        # demand by init_draft_model; None means mode "draft" is off and
        # no draft program ever compiles
        self._draft_params = None
        self._draft_header: LlmHeader | None = None
        self.draft_cache = None
        self.draft_cache_epoch = 0
        self._m_spec_draft_ms = None
        # shared KV page pool (cross-lane prefix sharing): allocated on
        # demand by init_kv_pool; None means the paged path is off
        self.kv_pool = None
        self._kv_page_size = 0
        self._kv_pool_pages = 0
        self._kv_pool_specs = None
        # pool-native mode (ISSUE 16): the pool IS the lane KV home —
        # decode/verify/prefill read and write through a per-lane page
        # table instead of the slab, kv_adopt becomes a page-table write
        # and kv_publish an ownership transfer. kv_pool_epoch moves every
        # time the pool buffer is reallocated so the manager/scheduler can
        # tell "this dispatch poisoned the pool" from a transient failure.
        self.kv_native = False
        self.kv_pool_epoch = 0
        self._kv_n_blocks = 0
        self._page_table = None  # host np.int32 mirror [batch, n_blocks]
        self._compiled = {}
        self._base_key = jax.random.PRNGKey(seed)
        self._lane_seed_base = seed
        self._rng_calls = 0
        # window pre-compile: decode blocks are AOT-
        # compiled so a background thread can build the NEXT window's
        # program before a lane crosses the boundary — the crossing then
        # performs no synchronous compile. _compile_origin records who
        # built each program (the boundary-stall test pins "prefetch").
        import os as _os
        import threading as _threading

        from ..analysis.lockwatch import make_lock

        self._aot_blocks = (
            _os.environ.get("DLLAMA_WINDOW_PRECOMPILE", "1") != "0"
        )
        self._compile_lock = make_lock("engine.compile")
        self._inflight: dict = {}  # key -> threading.Event
        self._compile_origin: dict = {}
        self._compile_seconds: dict = {}  # key -> AOT build wall seconds
        # XLA cost analysis memoized per compiled key: the series sampler
        # refreshes the cost gauges at ~1 Hz via the registry's
        # "engine.cost" hook, and cost_analysis() on every program every
        # tick would dwarf the tick itself. "unavailable" (None) results
        # are NOT cached — a lazily jitted program exposes its executable
        # only after its first call.
        self._cost_cache: dict = {}
        self.obs.add_refresh_hook("engine.cost", self.cost_report)
        # compiled-program lint (xlalint, docs/static_analysis.md): every
        # AOT build is checked right after it lands in the cache —
        # donation honored, collective census, dtype/host policy, cost
        # budget. "0"/"off" disables, "strict" raises XlalintError on a
        # new finding (dispatch-path compiles propagate it; prefetch
        # threads log it and mark the key prefetch-failed), anything
        # else warns through the engine logger.
        self._xlalint_mode = (
            _os.environ.get("DLLAMA_XLALINT", "warn").strip().lower()
        )
        self._xlalint_baseline: set | None = None

        if moe_decode_dedup == "auto":
            # decision boundary from the routing-correlation study
            # (scripts/moe_routing_sim.py, docs/moe_decode_dedup.md): at
            # >= 8 decode lanes the small grid hits ~always under even
            # moderate inter-lane correlation (rho 0.5) or mild expert-
            # popularity skew, and a miss just takes the ragged branch;
            # under 8 lanes hits need strong correlation, so the second
            # compiled program isn't worth carrying
            moe_decode_dedup = bool(self.header.n_experts and batch_size >= 8)
        self.moe_decode_dedup = bool(moe_decode_dedup)
        moe_decode_dedup = self.moe_decode_dedup

        # unified forward dispatch: every compiled step goes through this,
        # so the pipeline schedule slots under the SAME bucketed prefill /
        # block decode / lane machinery as the flat mesh
        h = self.header
        mesh = self.mesh
        sync_quant = self._sync_quant
        if pp > 1:
            from ..parallel.pipeline import forward_pp

            park = self._park if self._lane_pad else 0

            def fwd(params, tokens, pos, cache, *, attn_window=0,
                    logits_mode="all", attn_park_threshold=0, n_micro=1,
                    live_lanes_alone=False):
                return forward_pp(
                    params, h, tokens, pos, cache, mesh,
                    attn_window=attn_window, logits_mode=logits_mode,
                    attn_park_threshold=attn_park_threshold,
                    n_micro=n_micro, sync_quant=sync_quant,
                    park_pos=park, moe_decode_dedup=moe_decode_dedup,
                    live_lanes_alone=live_lanes_alone,
                )

        else:

            kv_ring = self.kv_ring

            def fwd(params, tokens, pos, cache, *, attn_window=0,
                    logits_mode="all", attn_park_threshold=0, n_micro=1,
                    route_stats=None, expert_forms=None, live_lanes_alone=False,
                    **lane_state):
                del n_micro  # sequence-wave microbatching is pp-only
                return forward(
                    params, h, tokens, pos, cache, mesh=mesh,
                    attn_window=attn_window, logits_mode=logits_mode,
                    attn_park_threshold=attn_park_threshold,
                    sync_quant=sync_quant,
                    moe_decode_dedup=moe_decode_dedup,
                    kv_ring=kv_ring, route_stats=route_stats,
                    expert_forms=expert_forms,
                    live_lanes_alone=live_lanes_alone, **lane_state,
                )

        self._fwd = fwd
        # the lane decode block counts routed pairs where its experts take
        # the path that computes the pairs that landed here: some of the
        # experts the router scores lie on other chips, or one device holds
        # the whole layer (`models/transformer.py:moe_block`)
        self._counts_routing = pp == 1 and h.n_experts > 0 and (
            h.n_experts < h.n_routed_experts
            or mesh is None or mesh.devices.size == 1
        )
        # a chunk program of a held share of the experts returns, un-read,
        # the forms its expert layers took (`ops/moe_kernel.held_forms`):
        # (blocks enqueued before it, the array), counted at a later collect
        self._counts_forms = self._counts_routing and h.n_experts < h.n_routed_experts
        self._chunk_forms: list[tuple[int, jax.Array]] = []

    def _pp_micro(self, t: int) -> int:
        """Sequence-wave microbatch count for a T-wide pp prefill chunk:
        prefer ~4 chunks in flight per stage (utilization
        n_micro/(pp+n_micro-1)) while keeping >= 8 rows per wave (flash-
        kernel-friendly; tiny waves would be launch-overhead-bound)."""
        if self.pp == 1 or t < 2 * self.pp:
            return 1
        for k in (4 * self.pp, 2 * self.pp, self.pp):
            if t % k == 0 and t // k >= 8:
                return k
        return 1

    # -- cache ---------------------------------------------------------------

    def _fresh_cache(self):
        # epoch lets callers detect that cached KV state was dropped
        # (api_server clears its prompt cache iff this moved — a
        # ValueError raised inside a guarded dispatch also rebuilds)
        self.cache_epoch = getattr(self, "cache_epoch", -1) + 1
        self._m_epochs.inc()
        self.recorder.record("cache_epoch", epoch=self.cache_epoch)
        # a block in flight wrote the cache that went: nothing continues it
        self._uncollected = None
        if self._stateful:
            self._state_pos = [None] * self.batch_size
        cache = init_kv_cache(
            self.header,
            self.batch_size,
            dtype=self.kv_dtype,
            seq_len=self.header.seq_len + self._lane_pad,
            ring=self.kv_ring or None, ring_pad=self._lane_pad,
        )
        return {
            k: jax.device_put(v, self._cache_sharding[k]) for k, v in cache.items()
        }

    def reset(self) -> None:
        """Drop KV state (new conversation)."""
        self.cache = self._fresh_cache()

    @contextlib.contextmanager
    def _cache_guard(self):
        """Crash consistency for the donated KV cache: every compiled
        step donates `self.cache` (donate_argnums), so a dispatch that
        raises leaves the engine holding buffers in an unknown —
        possibly already-donated — state, and the next call would fail
        on them. Replace with a fresh cache before re-raising, so one
        failed request costs its context but never wedges the engine
        (the reference's analogue re-initializes the whole app every
        3 s on executor errors, src/dllama-api.cpp:616-628; here params
        are never donated, so only the cache needs rebuilding)."""
        try:
            yield
        except BaseException as e:
            self.recorder.record(
                "error", error=str(e), error_type=type(e).__name__
            )
            self.recorder.postmortem("engine-step", e)
            try:
                self.cache = self._fresh_cache()
            except Exception as rebuild_err:  # pragma: no cover
                raise rebuild_err from e
            raise

    # programs nobody reads back, and short (a millisecond of pages
    # copied): to the drained interval one is host work like any other
    _POOL_COPIES = frozenset(("kv_adopt", "kv_publish", "kv_page_copy"))

    def _begin_dispatch(
        self, step: str, prep=None, head=None, host_args=0, **fields
    ) -> dict:
        """The begin of one dispatch of a compiled program: ends ``prep``,
        the caller's open ``dispatch_prep`` span, reads the clock once
        (the dispatch's ``t0``) and records ``step_dispatch``. Returns what
        ``_launched`` wants of it: ``step``, ``t0`` and ``dry``.

        ``step_dispatch`` says what the host did before the call:
        ``prep_ms`` from ``prep``'s begin (a method with no such span:
        ``head``, its first clock reading) to ``t0``, and ``host_args``,
        the host arrays ``_host_args`` handed over for the call; and, of a
        step that is no pool copy, ``dry``: whether the newest program
        enqueued had left the device at ``t0`` (asked of its handle
        without a wait, so exact where it says 0), counted as
        ``dllama_engine_dispatches_total{step, device}``. The programs
        stamped since the last dispatch are accounted for here
        (``_settle``)."""
        t0 = time.monotonic()
        self._spans.end(prep, at=t0)
        begun = {"step": step, "t0": t0}
        host = {"host_args": host_args}
        since = prep.t0 if prep is not None else head
        if since is not None:
            host["prep_ms"] = round((t0 - since) * 1000, 3)
        if step not in self._POOL_COPIES:
            dry = self._newest is None or self._newest.left_the_device()
            begun["dry"] = dry
            host["dry"] = int(dry)
            self._m_dispatches.labels(
                step=step, device="dry" if dry else "busy").inc()
        self._settle()
        self.recorder.record("step_dispatch", step=step, **fields, **host)
        return begun

    def _launched(self, begun: dict, handle) -> Program:
        """A program of the lane path has been enqueued: `begun` is its
        ``_begin_dispatch``'s, `handle` a small output of it that no later
        program is given to consume. The watcher stamps when it leaves the
        device, and a later dispatch accounts for it (`_settle`)."""
        self._enqueued += 1
        program = Program(
            self._enqueued, begun["step"], begun["t0"], time.monotonic(),
            begun["dry"], handle,
        )
        self._newest = program
        if len(self._unsettled) == self._unsettled.maxlen:
            # nobody stamps any more (a watcher that hangs on a dead device):
            # what is known of the programs before this one is nothing
            self._unsettled.clear()
            self._last_done = None
        self._unsettled.append(program)
        self._watcher.watch(program)
        return program

    def _settle(self) -> None:
        """Account for every program, in the order they were enqueued, that
        has left the device since the last call, from its stamp `done[k]`,
        the one before it and its dispatch's `t0[k]` and `t1[k]`:

        - recorder ``device_done``: ``program`` = its number among the
          programs enqueued, ``device_ms`` = ``done[k]`` less the
          later of ``done[k-1]`` and ``t1[k]``, ``queued_ms`` = what of
          ``done[k-1]`` lies past ``t1[k]``, ``dry_ms`` (below), ``at`` =
          ``done[k]``, ``error`` where its handle raised, ``late_ms`` where
          a read-back's reading came before the watcher's, by how much;
          ``dllama_engine_device_busy_seconds_total{step}``;
        - where ``done[k-1]`` lies before ``t0[k]``, the device had nothing
          to do between them and the host is why: the span
          ``device_drained`` (``before`` = the step; on the thread that
          dispatched it), ``dllama_engine_device_drained_seconds_total``
          and ``dry_ms``, all three from that one pair of readings.

        Late by a program or two where the loop runs ahead, which a ring,
        a streamed timeline and a counter do not mind."""
        while self._unsettled and self._unsettled[0].done is not None:
            k = self._unsettled.popleft()
            done, last = k.done, self._last_done
            self._last_done = done
            event = {"program": k.seq, "step": k.step, "at": done, "dry_ms": 0.0}
            if k.error is not None:
                event["error"] = k.error
            if k.read is not None and k.watched is not None and k.watched > k.read:
                event["late_ms"] = round((k.watched - k.read) * 1000, 3)
            began = k.t1 if last is None else max(last, k.t1)
            device_s = max(0.0, done - began)
            event["device_ms"] = round(device_s * 1000, 3)
            event["queued_ms"] = round(max(0.0, began - k.t1) * 1000, 3)
            if k.dry and last is not None and last < k.t0:
                sp = self._spans.begin(
                    "device_drained", component="engine", annotate=False,
                    at=last, before=k.step,
                )
                if sp is not None:
                    sp.thread = k.thread  # whoever comes by to settle it
                self._spans.end(sp, at=k.t0)
                self._m_drained.labels(before=k.step).inc(k.t0 - last)
                event["dry_ms"] = round((k.t0 - last) * 1000, 3)
            self._m_busy.labels(step=k.step).inc(device_s)
            self.recorder.record("device_done", **event)

    def close(self) -> None:
        """A server that stops: account for what has been stamped, end the
        watcher's thread and drop the programs it has not stamped. The
        engine serves on if asked: nothing is then known of the device, and
        the next program starts a watcher of its own."""
        self._settle()
        self._unsettled.clear()
        self._newest = self._last_done = None
        self._watcher.close()

    def _complete_dispatch(self, step: str, t0: float, t1: float, **fields) -> float:
        """The end of one dispatch: the step histogram and the recorder's
        ``step_complete`` (``ms``) from the same two clock readings;
        returns their distance in seconds."""
        self._m_step.labels(kind=step).observe(t1 - t0)
        self.recorder.record(
            "step_complete", step=step, **fields,
            ms=round((t1 - t0) * 1000, 3),
        )
        return t1 - t0

    @contextlib.contextmanager
    def _dispatch(self, step: str, prep=None, head=None, host_args=0, **fields):
        """Time one dispatch of a compiled program that is read back, or
        left un-read, inside the ``with``, once, for everything that
        wants it: the recorder's ``step_dispatch``/``step_complete``
        pair (``ms`` on the latter), the ``engine`` span named ``step``
        and the step histogram all get the same two clock readings
        (``_begin_dispatch``, ``_complete_dispatch``), and the yielded
        dict, ``_begin_dispatch``'s, gets ``seconds``. The lane path hands
        it to ``_launched`` behind its program's call. The read-back wait
        inside is ``_read_back``'s. A dispatch that raises ends its span
        and completes nothing."""
        timed = self._begin_dispatch(step, prep, head, host_args, **fields)
        t0 = timed["t0"]
        sp = self._spans.begin(step, component="engine", at=t0, **fields)
        try:
            yield timed
        except BaseException:
            self._spans.end(sp, error=True)
            raise
        t1 = time.monotonic()
        self._spans.end(sp, at=t1)
        timed["seconds"] = self._complete_dispatch(step, t0, t1, **fields)

    def _rows_in_context(self, starts: list[int], n: int) -> dict:
        """`step_dispatch` fields of a model with two kinds of cache: the
        key and value rows, a layer, that the queries of the dispatch's live
        lanes see, summed over its `n` steps or rows: in the full layers all
        of a lane's context, in the window layers at most the window. The
        ring's wraps that the dispatch brings are counted here too. A latent
        cache: `rows_latent`, the latent rows a layer those queries see (and
        an index scores), and under an index `rows_selected`, those of them
        they attend to: at most `index_topk` a query; counted too.
        Nothing for a model of keys and values of one kind."""
        if self._latent:
            # every live query sees its whole context, one latent row a layer
            seen = [p + i + 1 for p in starts for i in range(n)]
            if not self.header.indexed:
                return {"rows_latent": sum(seen)}
            rows = {"rows_latent": sum(seen),
                    "rows_selected": sum(min(r, self.header.index_topk) for r in seen)}
            self._m_index_rows.labels(kind="scored").inc(rows["rows_latent"])
            self._m_index_rows.labels(kind="selected").inc(rows["rows_selected"])
            return rows
        if self._stateful:
            # the attention layers, a subset of the model's, see the context
            return {"rows_full": sum(p + i + 1 for p in starts for i in range(n))}
        if not self._two_cache_kinds:
            return {}
        wraps = sum((p + n) // self.kv_ring - p // self.kv_ring for p in starts)
        if wraps:
            self._m_ring_wraps.inc(wraps)
        w = self.header.sliding_window
        # what the dispatch's queries see: position p's sees p + 1 rows
        seen = [p + i + 1 for p in starts for i in range(n)]
        return {"rows_full": sum(seen), "rows_window": sum(min(s, w) for s in seen)}

    def _chunk_rows_in_context(self, filled) -> dict:
        """`_rows_in_context` of a chunk program: each `(lane, tokens, pos0)`
        of `filled` its tokens' rows from its `pos0`, summed."""
        rows: dict = {}
        for _, tokens, pos0 in filled:
            for k, v in self._rows_in_context([pos0], len(tokens)).items():
                rows[k] = rows.get(k, 0) + v
        return rows

    def _chunk_expert_rows(self, bucket: int, n_lanes: int) -> dict:
        """`step_dispatch` field of a sparse model's chunk that fills
        `n_lanes` lanes: the token rows its expert block computes, `bucket` a
        carried lane where the program takes the live lanes' rows alone
        (`run_layers`' `live_lanes_alone`) and every lane's where the lanes
        are split over devices. Counted too, with the parked lanes' rows
        left out. Nothing for a dense model."""
        if not self.header.n_experts:
            return {}
        every = self.batch_size * bucket
        rows = n_lanes * bucket if self.chunk_rider_adds_rows else every
        self._m_moe_chunk_rows.labels(rows="computed").inc(rows)
        self._m_moe_chunk_rows.labels(rows="parked_skipped").inc(every - rows)
        return {"expert_rows": rows}

    def _dispatch_prep(self, step: str):
        """Open the span of the host work before a lane dispatch: window
        choice, prefetch, seed vector and the host arrays of the call.
        ``_dispatch(prep=)`` ends it."""
        return self._spans.begin("dispatch_prep", component="engine", step=step)

    def _host_args(self, *arrays, tokens=None) -> tuple:
        """The one way a dispatch's host values reach its program: numpy
        arrays of the dtypes and shapes the program's arg specs state,
        handed over as the call's own arguments, which moves them. No
        ``jnp.asarray`` or ``jnp.int32``: each is a little program of its
        own, launched while the device stands drained. ``tokens`` comes
        first, as in every program's signature; its spec alone carries a
        sharding, and on a mesh of more than one device it is placed by
        it (a transfer, no program), as a lazily jitted program expects."""
        if tokens is None:
            return arrays
        if self.mesh.size > 1:
            tokens = jax.device_put(tokens, self._token_sharding)
        return (tokens, *arrays)

    def _page_table_arg(self) -> tuple:
        """The paged programs' page table argument, which follows the
        pool in their signatures (a copy: the host goes on writing its
        mirror); nothing for the slab's programs."""
        return (self._page_table.copy(),) if self.kv_native else ()

    def _lane_state_arg(self, lane: int, pos0: int, width: int, floor: int) -> tuple:
        """A chunk program's `aux` for a model with lane state, and the
        host's account of where the lane's states then stand; nothing for a
        model without. The chunk starts the states from zero unless it
        continues where they stand (position 0 is zero by itself)."""
        if not self._stateful:
            return ()
        if floor > pos0 and pos0 + self.state_replay_rows < floor:
            raise ValueError(
                f"a write floor of {floor} over a chunk at {pos0}: a replay "
                f"starts {self.state_replay_rows} positions before the floor"
            )
        fresh = self._state_pos[lane] != pos0
        if fresh:
            if pos0 and floor <= pos0:
                raise ValueError(
                    f"lane {lane}: a chunk at position {pos0} neither continues "
                    f"the lane's states (at {self._state_pos[lane]}) nor replays "
                    "behind an adopted prefix (write_floor)"
                )
            self._m_state_installs.labels(how="replay" if pos0 else "zero").inc()
        self._state_pos[lane] = pos0 + width
        return (np.asarray([width, floor, fresh], np.int32),)

    def _chunk_state_fields(self, pos0: int, width: int, floor: int) -> dict:
        """`step_dispatch` fields of a chunk of a model with lane state:
        `state_lanes`, the lanes whose states the program moves (the
        admitted one), and `replay_tokens`, the chunk's rows below the write
        floor, run again for the states alone; counted too."""
        if not self._stateful:
            return {}
        replay = max(0, min(floor, pos0 + width) - pos0)
        if replay:
            self._m_replay_tokens.inc(replay)
        return {"state_lanes": 1, "replay_tokens": replay}

    def _refuse(self, flag: str) -> None:
        """Raise where the model keeps what `flag`'s feature does not run
        over (`_KEPT`), naming the flag, the reason and the architecture."""
        for attr, kept, why in _KEPT:
            if getattr(self, attr):
                raise ValueError(
                    f"{flag}: {why.get(flag.split()[0], why['mesh'])} "
                    f"({self.header.arch.name} keeps {kept})"
                )

    def decline_adoption(self, why: str) -> None:
        """Count a stored prefix that a lane matched and did not adopt
        (`dllama_prefix_adoptions_declined_total`); on the recorder too."""
        self._m_adoptions_declined.labels(why=why).inc()
        self.recorder.record("prefix_adoption_declined", why=why)

    def _require_stateless(self, what: str) -> None:
        if self._stateful:
            raise ValueError(
                f"{what}: a model with lane state runs through the lane programs "
                f"alone (prefill_lane_chunk, decode_lanes) ({self.header.arch.name})"
            )

    def _lane_chunks(self, chunks, bucket: int, park: int):
        """A chunk program's token rows and positions: each ``(lane, tokens,
        pos0)`` of ``chunks`` has its tokens in its lane's row at its
        ``pos0``, every other lane zeros at ``park``."""
        rows = np.zeros((self.batch_size, bucket), np.int32)
        posv = np.full(self.batch_size, park, np.int32)
        for lane, tokens, pos0 in chunks:
            rows[lane, : len(tokens)] = tokens
            posv[lane] = pos0
        return rows, posv

    def _read_back(self, step: str, out, program: Program) -> np.ndarray:
        """The device-complete wait: the program call returned as soon as
        it was enqueued, and the read-back waits for the device. Its own
        ``<step>.device`` span, so a timeline splits dispatch overhead
        from device time. The device runs programs in the order they
        were enqueued, so the clock reading that ends the span says that
        ``program``, whose output ``out`` is, and every program before it
        have left the device by then (``Program.read``)."""
        sp = self._spans.begin(f"{step}.device", component="engine")
        try:
            host = np.asarray(out)
        except BaseException:
            self._spans.end(sp, error=True)
            raise
        t1 = time.monotonic()
        self._spans.end(sp, at=t1)
        for k in self._unsettled:
            if k.seq <= program.seq and k.read is None:
                k.read = t1
        return host

    def _fault(self, op: str):
        """Chaos hook (runtime/faults.py): the armed fault for this
        dispatch, if any. Callers raise a TRANSIENT fault BEFORE their
        donated-buffer guard (buffers intact, the epoch does not move,
        the scheduler retries) and a POISON fault INSIDE it (the guard
        rebuilds the buffer and the epoch moves — the recovery path)."""
        return get_fault_plane().draw("dispatch", op=op)

    def set_seed(self, seed: int) -> None:
        """Reseed BOTH sampling paths (host xorshift sampler and the
        on-device PRNG used by blocked decode)."""
        self.sampler.set_seed(seed)
        self._base_key = jax.random.PRNGKey(seed)
        self._lane_seed_base = seed
        self._rng_calls = 0

    # -- compiled steps ------------------------------------------------------

    def _attn_window(self, limit: int) -> int:
        """Smallest power-of-2 window >= limit (min 512, and no less than a
        model's sliding window) covering the live
        cache prefix; full seq_len when nothing smaller fits. One
        compiled program per window keeps decode reads proportional to
        the context actually used instead of the allocated seq_len —
        O(pos) decode reads live HERE, not in a kernel: round-3 silicon
        showed Mosaic does not elide repeated-index DMAs, and windowed
        XLA dense attention beats the Pallas decode kernel.

        Under sp the cache uses the CYCLIC sequence layout (global row g
        on shard g % sp at local row g // sp — models/transformer), so a
        window that is an sp x 512 tile is exactly the 512-row local
        prefix of every shard: the live context spreads evenly and
        windowed O(pos) reads survive on the long-context axis (r3
        returned 0 here, re-reading the whole per-shard cache)."""
        s = self.header.seq_len
        if self.sp > 1:
            w = 512 * self.sp
            while w < limit:
                w *= 2
            return min(w, s)
        # a model with window layers starts at its window: below it every
        # layer would read alike, and each smaller window is three more
        # programs to build (at a context of 16384 six windows took 179 s
        # of a cold start on the chip, three 100 s: PERF.md, PR 32), for
        # contexts that one of 4096 rows serves at up to 3584 rows more a
        # layer and lane in a decode step
        # a latent cache starts at 4096 rows: a 4096-row read of latents
        # (4.7 MB a layer and lane at 576 wide) costs what 1152 rows of a
        # GQA cache of 8 heads of 128 cost, and each smaller window is
        # three more programs in a cold run
        w = 512
        floor = LATENT_MIN_WINDOW if self._latent else self.header.sliding_window
        while w < max(limit, floor):
            w *= 2
        # NB: crossing a window boundary mid-generation compiles a fresh
        # program for the next window (one synchronous stall per crossing,
        # log2(seq_len/512) of them worst case, amortized by the on-disk
        # compilation cache across runs).
        return min(w, s)

    def _note_window(self, window: int) -> None:
        """Count attention-window growth (each crossing compiles — or
        prefetched — a fresh program; the counter makes the p99 stall
        source visible on `/metrics`)."""
        if (
            self._obs_last_window is not None
            and window > self._obs_last_window
        ):
            self._m_window_crossings.inc()
        self._obs_last_window = window

    def _build(self, key, make, arg_specs=None, origin: str = "dispatch"):
        """The one way a program enters `_compiled`. A cached `key` is
        returned as it is; a dispatch that finds a prefetch thread
        building `key` waits for it and reuses its program. Otherwise
        `make()` gives the jitted function. With `arg_specs` (a callable,
        so a cache hit builds no specs) and `_aot_blocks` it is lowered
        against them and compiled here and now, which is what lets
        `_prefetch` build a program off the serving thread; the recorder
        gets `compile_start` / `compile_end` (`s`: the build's seconds)
        and xlalint reads the executable. Without `arg_specs` the program
        is lazily jitted: XLA compiles at its first call, so there is no
        build time to record, and one deferred `compile` marker takes
        the pair's place."""
        with self._compile_lock:
            if key in self._compiled:
                return self._compiled[key]
            ev = self._inflight.get(key) if origin == "dispatch" else None
        if ev is not None:
            ev.wait()
            with self._compile_lock:
                if key in self._compiled:
                    return self._compiled[key]
        eager = arg_specs is not None and self._aot_blocks
        if arg_specs is not None:
            self.recorder.record("compile_start", key=str(key), origin=origin)
        t0 = time.perf_counter()
        fn = make()
        if eager:
            fn = fn.lower(*arg_specs()).compile()
        dt = time.perf_counter() - t0
        with self._compile_lock:
            self._compiled[key] = fn
            self._compile_origin[key] = origin
            if eager:
                self._compile_seconds[key] = dt
        self._m_compiles.labels(origin=origin).inc()
        if arg_specs is None:
            self.recorder.record(
                "compile", key=str(key), origin=origin, deferred=True
            )
        else:
            self.recorder.record(
                "compile_end", key=str(key), origin=origin, s=round(dt, 4)
            )
            self._xlalint_after_compile(key)
        return fn

    def _step_fn(self, t: int, greedy: bool, window: int = 0):
        """Build/jit the forward step for chunk length `t`."""
        self._require_stateless("prefill / decode_step")

        def make():
            precision = self._precision
            fwd = self._fwd

            @partial(jax.jit, donate_argnums=(2,))
            def step(params, tokens, cache, pos):
                ctx = (
                    jax.default_matmul_precision(precision)
                    if precision
                    else contextlib.nullcontext()
                )
                with ctx:
                    logits, cache = fwd(
                        params, tokens, pos, cache,
                        attn_window=window, logits_mode="last",
                        n_micro=self._pp_micro(t),
                    )
                last = logits[:, -1, :]
                if greedy:
                    # On-device sampling (reference samples on host from the
                    # logits pipe; fusing argmax here avoids the [vocab] device
                    # -> host transfer per decoded token).
                    return jnp.argmax(last, axis=-1).astype(jnp.int32), cache
                return last, cache

            return step

        return self._build((t, greedy, window), make)

    def _block_arg_specs(self, n_steps: int):
        """ShapeDtypeStructs (with shardings) matching a decode_block
        dispatch exactly — what the AOT pre-compile lowers against. Uses
        the init-time snapshot (`_param_specs`/`_cache_specs`): reading
        `self.cache` here would race the serving thread's donated
        dispatches (a donated buffer deletes mid-read)."""
        tok = jax.ShapeDtypeStruct(
            (self.batch_size, 1), jnp.int32, sharding=self._token_sharding
        )
        # scalars/rng stay UNSHARDED specs: the dispatch passes fresh
        # uncommitted arrays, and pinning a single device here conflicts
        # with multi-device meshes at lowering time
        scalar_i = jax.ShapeDtypeStruct((), jnp.int32)
        scalar_f = jax.ShapeDtypeStruct((), jnp.float32)
        key = jax.random.fold_in(self._base_key, 0)
        rng = jax.ShapeDtypeStruct(key.shape, key.dtype)
        return (
            self._param_specs,
            tok,
            self._cache_specs,
            scalar_i,
            rng,
            scalar_f,
            scalar_f,
        )

    def _decode_block_fn(
        self, n_steps: int, greedy: bool, window: int = 0, origin: str = "dispatch"
    ):
        """Jitted on-device decode of `n_steps` tokens: the sample ->
        feed-back loop runs under `lax.fori_loop`, so the host pays one
        dispatch per block instead of one per token (the lax.fori_loop
        multi-step plan from SURVEY.md §7 hard parts).
        Sampling (temperature/top-p) runs on device too; temp/topp are
        traced so changing them does not recompile.

        With `_aot_blocks` the program is compiled EAGERLY (AOT lower +
        compile against the live arg specs) and the cache stores the
        executable — which is what lets `_prefetch_block` build the next
        attention window's program off-thread before a lane crosses the
        boundary (no synchronous compile at the crossing)."""
        self._require_stateless("decode_block")

        def make():
            precision = self._precision
            fwd = self._fwd

            @partial(jax.jit, donate_argnums=(2,))
            def block(params, token, cache, pos, rng, temperature, topp):
                def body(i, carry):
                    tok, cache, out = carry
                    ctx = (
                        jax.default_matmul_precision(precision)
                        if precision
                        else contextlib.nullcontext()
                    )
                    with ctx:
                        logits, cache = fwd(
                            params, tok, pos + i, cache,
                            attn_window=window, logits_mode="last",
                        )
                    last = logits[:, -1, :]
                    if greedy:
                        nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
                    else:
                        nxt = _sample_on_device(
                            last, temperature, topp, jax.random.fold_in(rng, i)
                        )
                    nxt = nxt.reshape(-1, 1)
                    out = lax.dynamic_update_index_in_dim(out, nxt[:, 0], i, axis=0)
                    return nxt, cache, out

                out0 = jnp.zeros((n_steps, token.shape[0]), jnp.int32)
                tok, cache, out = lax.fori_loop(
                    0, n_steps, body, (token, cache, out0)
                )
                return out, cache

            return block

        return self._build(
            ("block", n_steps, greedy, window), make,
            lambda: self._block_arg_specs(n_steps), origin,
        )

    def _prefetch(self, key, builder) -> None:
        """Compile the NEXT attention window's program in a daemon thread: called when a lane passes ~75% of the current
        window, so the boundary crossing finds the program in `_compiled`
        instead of stalling a serving-path dispatch on a synchronous XLA
        compile. `builder` must call the matching *_fn with
        origin='prefetch'."""
        import threading

        with self._compile_lock:
            if key in self._compiled or key in self._inflight:
                return
            ev = threading.Event()
            self._inflight[key] = ev

        def work():
            try:
                fault = get_fault_plane().draw("prefetch")
                if fault is not None:
                    raise fault
                builder()
            except Exception:
                # a daemon thread dies silently by default: the boundary
                # crossing would then fall back to a synchronous compile
                # every window with nothing in the logs explaining the p99
                # stalls. Log it and mark the key so telemetry/tests can
                # see the prefetch path is broken.
                import logging

                logging.getLogger(__name__).exception(
                    "AOT prefetch failed for %r; the window boundary will "
                    "compile synchronously",
                    key,
                )
                with self._compile_lock:
                    self._compile_origin[key] = "prefetch-failed"
                self._m_compiles.labels(origin="prefetch-failed").inc()
            finally:
                with self._compile_lock:
                    self._inflight.pop(key, None)
                ev.set()

        # joined via the per-key `ev` Event in _build (the dispatch path
        # waits on it), not via the Thread handle
        threading.Thread(  # dlint: disable=thread-hygiene — lifetime bounded by the _inflight[key] Event; waiters join through ev.wait()
            target=work, daemon=True, name=f"dllama-prefetch-{key[1]}"
        ).start()

    def _prefetch_block(self, n_steps: int, greedy: bool, window: int) -> None:
        self._prefetch(
            ("block", n_steps, greedy, window),
            lambda: self._decode_block_fn(
                n_steps, greedy, window, origin="prefetch"
            ),
        )

    def decode_block(
        self, token: int | list[int], pos: int, n_steps: int
    ) -> list[int] | list[list[int]]:
        """Decode up to `n_steps` tokens in one device dispatch (greedy when
        temperature == 0, on-device temperature/top-p sampling otherwise).

        `token` may be a per-lane list (one independent sequence per batch
        lane, the dp axis); the return is then [n_steps][lanes]."""
        head = time.monotonic()
        per_lane = isinstance(token, (list, tuple))
        n_steps = self._block_width(pos, n_steps)
        if n_steps <= 0:
            return []
        if per_lane and len(token) != self.batch_size:
            raise ValueError(
                f"{len(token)} lane tokens for batch_size {self.batch_size}"
            )
        greedy = self.temperature == 0.0
        window = self._attn_window(pos + n_steps)
        self._note_window(window)
        block = self._decode_block_fn(n_steps, greedy, window)
        if (
            self._aot_blocks
            and window < self.header.seq_len
            and pos + n_steps >= (3 * window) // 4
        ):
            # past 75% of this window: build the next window's program in
            # the background so the crossing performs no synchronous
            # compile (the window-boundary p99 stall)
            self._prefetch_block(n_steps, greedy, self._attn_window(window + 1))
        # fold in a call counter so successive generations differ (the
        # reference's xorshift state advances across calls the same way)
        self._rng_calls += 1
        rng = jax.random.fold_in(
            jax.random.fold_in(self._base_key, pos), self._rng_calls
        )
        b = self.batch_size
        arr, pos_arg, temperature, topp = self._host_args(
            np.int32(pos),
            np.float32(max(self.temperature, 1e-6)),
            np.float32(self.sampler.topp),
            tokens=np.asarray(
                token if per_lane else [token] * b, np.int32
            ).reshape(b, 1),
        )
        with self._dispatch(
            "decode_block", head=head, host_args=4,
            pos=pos, n_steps=n_steps, window=window,
        ) as timed, self._cache_guard():
            out, self.cache = block(
                self.params, arr, self.cache, pos_arg, rng, temperature, topp
            )
            out = self._read_back(  # [n_steps, lanes]
                "decode_block", out, self._launched(timed, out))
        self._m_tpot.observe(timed["seconds"] / n_steps)
        if per_lane:
            return [[int(t) for t in row] for row in out]
        return [int(t) for t in out[:, 0]]

    def _score_fn(self, t: int, window: int = 0):
        """Build/jit the teacher-forced scoring step for chunk length `t`:
        returns the summed next-token NLL of the chunk's unmasked rows as
        ONE scalar (no [T, vocab] logits transfer — the reference ships the
        full logits pipe to host per batch, src/dllama.cpp:132-172)."""
        self._require_stateless("perplexity")

        def make():
            precision = self._precision
            fwd = self._fwd

            @partial(jax.jit, donate_argnums=(4,))
            def score(params, tokens, targets, mask, cache, pos):
                ctx = (
                    jax.default_matmul_precision(precision)
                    if precision
                    else contextlib.nullcontext()
                )
                with ctx:
                    logits, cache = fwd(
                        params, tokens, pos, cache, attn_window=window,
                        n_micro=self._pp_micro(t),
                    )
                lg = logits.astype(jnp.float32)  # [B, T, V]
                lse = jax.nn.logsumexp(lg, axis=-1)  # [B, T]
                tgt = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
                nll = (lse - tgt) * mask
                return jnp.sum(nll[0]), cache

            return score

        return self._build(("score", t, window), make)

    def perplexity(self, tokens: list[int]) -> tuple[float, float, int]:
        """Teacher-forced (nll, perplexity, n_scored) over `tokens`,
        scored chunk-by-chunk through the bucketed prefill programs — the
        result is chunk-size invariant and compiles only bucket-shaped
        programs (the reference walks the prompt in nBatches chunks the
        same way, src/dllama.cpp:132-172)."""
        t = len(tokens)
        if t < 2:
            raise ValueError("need at least 2 tokens for perplexity")
        if t > self.header.seq_len:
            raise ValueError(
                f"{t} tokens exceed seqLen {self.header.seq_len}"
            )
        bad = max(tokens)
        if bad >= self.header.vocab_size:
            # a tokenizer/model vocab mismatch would otherwise score
            # out-of-range rows as NaN (gather clamps silently on device)
            raise ValueError(
                f"token id {bad} out of range for model vocab "
                f"{self.header.vocab_size} (tokenizer/model mismatch?)"
            )
        self.reset()
        nll_sum = 0.0
        p = 0
        remaining = list(tokens)
        while remaining:
            bucket = self._bucket_for(len(remaining), p)
            width = min(bucket, len(remaining))
            chunk = remaining[:width] + [0] * (bucket - width)
            remaining = remaining[width:]
            # row j (global index p+j) is scored against token p+j+1; the
            # final token and padding rows are masked out
            targets = [
                tokens[p + j + 1] if (p + j + 1 < t and j < width) else 0
                for j in range(bucket)
            ]
            mask = [
                1.0 if (p + j + 1 < t and j < width) else 0.0
                for j in range(bucket)
            ]
            arr, tgt, msk = (
                jax.device_put(
                    np.tile(np.asarray(row, dtype), (self.batch_size, 1)),
                    self._token_sharding,
                )
                for row, dtype in (
                    (chunk, np.int32), (targets, np.int32), (mask, np.float32)
                )
            )
            score = self._score_fn(
                bucket, window=self._attn_window(p + bucket)
            )
            with self._cache_guard():
                part, self.cache = score(
                    self.params, arr, tgt, msk, self.cache, np.int32(p)
                )
                nll_sum += float(np.asarray(part))
            p += width
        n_scored = t - 1
        nll = nll_sum / n_scored
        return nll, float(np.exp(nll)), n_scored

    # -- per-lane serving (continuous-batching surface) ----------------------

    def _require_lanes(self) -> None:
        if self._lane_pad == 0:
            raise ValueError(
                "per-lane serving needs batch_size > 1 "
                "(lanes park their writes in cache padding rows)"
            )

    def _lane_prefill_arg_specs(self, t: int):
        """Arg specs for a lane-prefill chunk dispatch (the AOT lowering
        input): token rows are (lanes, bucket) with the lane sharding, the
        position vector is per-lane, and the params/cache trees come from
        the init-time snapshot (same no-donated-reads rule as
        _lane_arg_specs — rehearsal threads must never read live trees a
        serving dispatch is donating)."""
        b = self.batch_size
        tok = jax.ShapeDtypeStruct(
            (b, t), jnp.int32, sharding=self._token_sharding
        )
        return (
            self._param_specs,
            tok,
            self._cache_specs,
            jax.ShapeDtypeStruct((b,), jnp.int32),
            # lane state: (the chunk's real rows, the write floor, fresh)
            *((jax.ShapeDtypeStruct((3,), jnp.int32),) if self._stateful else ()),
        )

    def _lane_prefill_fn(
        self, t: int, window: int = 0, origin: str = "dispatch"
    ):
        """Vector-position prefill step: each lane writes its chunk at its
        own position; parked lanes write into the padding rows.
        AOT-compiled like the decode blocks — this is the lane scheduler's
        ADMISSION path, so a synchronous XLA compile here is exactly the
        first-admission stall rehearse_admission() exists to remove.

        A model with lane state takes one more argument, `aux` = (the
        chunk's real rows, the write floor, fresh): a chunk is padded to its
        bucket and every lane writes one, so the admitted lane's states move
        by its real rows alone and every other lane's stay; cache rows at
        positions below the floor keep what they hold; and `fresh` starts
        the lane's states from zero (`prefill_lane_chunk`).

        The program returns (cache, a small output for its completion
        stamp): one integer (`_chunk_mark`), or of a held share of the
        experts (`_counts_forms`) int32 [3]: of its expert layers, those
        that took the landed form, those that took the whole one, and the
        pairs that landed."""

        def make():
            precision = self._precision
            fwd = self._fwd
            park = self._park
            stateful = self._stateful
            forms = [] if self._counts_forms else None

            @partial(jax.jit, donate_argnums=(2,))
            def step(params, tokens, cache, pos_vec, *aux):
                ctx = (
                    jax.default_matmul_precision(precision)
                    if precision
                    else contextlib.nullcontext()
                )
                lane_state = {}
                if stateful:
                    (aux,) = aux
                    live = pos_vec < park
                    lane_state = dict(
                        state_rows=jnp.where(live, aux[0], 0),
                        write_floor=aux[1],
                        state_fresh=jnp.logical_and(live, aux[2] > 0),
                    )
                with ctx:
                    _, cache = fwd(
                        params, tokens, pos_vec, cache,
                        attn_window=window, attn_park_threshold=park,
                        logits_mode="last", n_micro=self._pp_micro(t),
                        live_lanes_alone=True, **lane_state,
                        **({} if forms is None else {"expert_forms": forms}),
                    )
                return cache, (_chunk_mark(cache) if forms is None else forms.pop())

            return step

        return self._build(
            ("lane_prefill", t, window), make,
            lambda: self._lane_prefill_arg_specs(t), origin,
        )

    def rehearse_admission(
        self,
        block_size: int | None = None,
        spec_k: int = 0,
        wait: bool = False,
    ) -> None:
        """Pre-compile the admission-path programs in the background: one
        lane-prefill chunk program per configured bucket (at the bucket's
        base attention window) plus the lane decode block — and, when
        speculation is on (spec_k > 0), one verify program per draft
        bucket — so the FIRST admission under load finds them in the
        cache instead of paying a synchronous compile stall on the
        serving path. No-op without AOT blocks
        (DLLAMA_WINDOW_PRECOMPILE=0): the lazily jitted programs then
        compile at first dispatch as before.

        ``wait=True`` blocks until every scheduled compile has finished
        (successfully or not) — what the xlalint CLI and the clean-engine
        smoke test use to lint a deterministic program set."""
        self._require_lanes()
        if not self._aot_blocks:
            return
        native = self.kv_native
        for bucket in self.prefill_buckets:
            window = self._attn_window(bucket)
            if native:
                self._prefetch(
                    ("lane_prefill_paged", bucket, window),
                    lambda b=bucket, w=window: self._lane_prefill_paged_fn(
                        b, window=w, origin="prefetch"
                    ),
                )
            else:
                self._prefetch(
                    ("lane_prefill", bucket, window),
                    lambda b=bucket, w=window: self._lane_prefill_fn(
                        b, window=w, origin="prefetch"
                    ),
                )
        if block_size:
            window = self._attn_window(block_size)
            if native:
                self._prefetch(
                    ("lane_block_paged", block_size, window),
                    lambda n=block_size, w=window: self._lane_decode_paged_fn(
                        n, w, origin="prefetch"
                    ),
                )
            else:
                self._prefetch(
                    ("lane_block", block_size, window),
                    lambda n=block_size, w=window: self._lane_decode_fn(
                        n, w, origin="prefetch"
                    ),
                )
        if spec_k > 0:
            self._refuse("--speculation")
            # one verify program per draft bucket (width 1 + bucket for
            # the pending token) at the base window; deeper windows ride
            # the same 75% prefetch as the decode block
            from .spec import spec_buckets

            for kb in spec_buckets(min(spec_k, self._lane_pad - 1)):
                t = kb + 1
                window = self._attn_window(t)
                if native:
                    self._prefetch(
                        ("lane_verify_paged", t, window),
                        lambda tt=t, w=window: self._lane_verify_paged_fn(
                            tt, w, origin="prefetch"
                        ),
                    )
                else:
                    self._prefetch(
                        ("lane_verify", t, window),
                        lambda tt=t, w=window: self._lane_verify_fn(
                            tt, w, origin="prefetch"
                        ),
                    )
        if spec_k > 0 and self._draft_params is not None:
            # resident draft model: its catch-up prefill buckets and
            # k-step propose blocks sit on the serving path exactly like
            # the verify programs — pre-build them all (they are tiny)
            from .spec import spec_buckets as _sb

            dseq = self._draft_header.seq_len
            for bucket in self.prefill_buckets:
                if bucket > dseq:
                    continue
                self._prefetch(
                    ("draft_prefill", bucket),
                    lambda b=bucket: self._draft_prefill_fn(
                        b, origin="prefetch"
                    ),
                )
            for kb in _sb(min(spec_k, self._lane_pad - 1)):
                self._prefetch(
                    ("draft_step", kb),
                    lambda n=kb: self._draft_step_fn(n, origin="prefetch"),
                )
        if self.kv_pool is not None and native:
            # the only device copy left on the native path: the COW fork
            # of a mid-page adoption boundary (one page at a time)
            self._prefetch(
                ("kv_page_copy", 1),
                lambda: self._kv_page_copy_fn(1, origin="prefetch"),
            )
        elif self.kv_pool is not None:
            # page-copy programs sit on the admission (adopt) and finish
            # (publish) paths; pre-build every power-of-two bucket up to a
            # full sequence's page count
            b = 1
            while b <= self._kv_max_pages:
                for kind in ("adopt", "publish"):
                    self._prefetch(
                        ("kv_" + kind, b),
                        lambda k=kind, n=b: self._kv_copy_fn(
                            k, n, origin="prefetch"
                        ),
                    )
                b *= 2
        if wait:
            # drain the prefetch threads: snapshot under the lock, wait
            # outside it (builders need the lock to finish), repeat until
            # nothing is in flight
            while True:
                with self._compile_lock:
                    pending = list(self._inflight.values())
                if not pending:
                    return
                for ev in pending:
                    ev.wait()

    @property
    def chunk_lanes(self) -> int:
        """The lanes one chunk program can fill (`prefill_lanes_chunk`):
        every lane, or 1 where the program takes one lane somewhere
        (`chunk_takes_one_lane`) or reads the pool's pages (`--kv-native
        1`, whose paged program has not been run with two live lanes)."""
        one = chunk_takes_one_lane(self.header) or self.kv_native
        return 1 if one else self.batch_size

    @property
    def chunk_rider_adds_rows(self) -> bool:
        """Whether a lane more in a chunk program is rows more to compute:
        true where the expert block runs over the live lanes' rows alone, a
        lane after another; false for a dense program, and where the lanes
        are split over devices, which compute every lane's rows whatever
        they hold. What the scheduler pays a program's riders by."""
        return bool(self.header.n_experts) and lanes_on_one_device(self.mesh)

    def prefill_lane_chunk(
        self,
        lane: int,
        tokens: list[int],
        pos0: int,
        budget: int | None = None,
        write_floor: int = 0,
    ) -> int:
        """Write ONE bucket-shaped chunk of `tokens` (fill rows — the
        caller already dropped the prompt's final token) into `lane`'s
        cache at `pos0`; returns how many tokens were consumed. This is
        the resumable half of prefill_lane: the lane scheduler dispatches
        one chunk per loop tick so a long prompt's admission interleaves
        with decode blocks instead of freezing every active lane for the
        whole prefill. `budget` caps the chunk width (--admission-chunk).
        Chunks reuse the same _lane_prefill_fn bucket programs as the
        monolithic path — no new compiled shapes — and write the same KV
        rows, so chunked admission is token-exact vs monolithic. The
        one-element case of `prefill_lanes_chunk`.

        A model with lane state (`header.stateful`): the lane's states move
        by the chunk's `width` real rows. A chunk at `pos0` that does not
        continue where the lane's states stand starts them from zero: at
        position 0 a cold admission, elsewhere a REPLAY behind a prefix the
        lane adopted (`kv_adopt`): the caller passes the prefix's length as
        `write_floor` and starts `state_replay_rows` positions before it;
        the cache rows below the floor stay the adopted ones, and at the
        floor every layer's state is what a cold run would hold there."""
        return self.prefill_lanes_chunk(
            [(lane, tokens, pos0)], budget=budget, write_floor=write_floor)[0]

    def prefill_lanes_chunk(
        self,
        chunks: list[tuple[int, list[int], int]],
        budget: int | None = None,
        write_floor: int = 0,
    ) -> list[int]:
        """ONE chunk program for the next chunk of every `(lane, tokens,
        pos0)` of `chunks`, at most `chunk_lanes` of them, the first the
        lead: the program computes every lane's rows whatever they hold (its
        expert block the live lanes' alone, a lane after another), so each
        admitting lane's row holds its own tokens at its own position and a
        parked lane's alone is zeros. Returns the width each consumed,
        in `chunks`' order.

        The common bucket is the largest that a carried lane's chunk asks
        of `_bucket_for`; the common window, the largest that one of them
        reaches in it. A lane whose rows would pass the context's end in
        that bucket, or whose own bucket an earlier lane's rows would, is
        left out and consumed 0: its own tick carries it. The program and
        its key are `_lane_prefill_fn`'s, one lane or several. A lane's
        cache rows are what a chunk of its own in that bucket and window
        writes. `write_floor`: of a model with lane state, one lane."""
        self._require_lanes()
        if not 1 <= len(chunks) <= self.chunk_lanes:
            raise ValueError(
                f"a chunk program of {self.header.arch.name} fills 1 to "
                f"{self.chunk_lanes} lanes, not {len(chunks)}"
            )
        lanes = [lane for lane, _, _ in chunks]
        if len(set(lanes)) < len(lanes):
            raise ValueError(f"a lane twice in one chunk program: {lanes}")
        for lane, tokens, pos0 in chunks:
            if not 0 <= lane < self.batch_size:
                raise ValueError(f"lane {lane} out of range")
            n = len(tokens)
            if n < 1:
                raise ValueError("empty chunk")
            if pos0 + n > self.header.seq_len:
                raise ValueError(
                    f"{n} fill tokens at pos {pos0} exceed "
                    f"seqLen {self.header.seq_len}"
                )
        prep = self._dispatch_prep("prefill_lane_chunk")
        fault = self._fault("prefill_lane_chunk")
        if fault is not None and not fault.poison:
            raise fault
        wants = [min(len(tokens), budget) if budget and budget > 0 else len(tokens)
                 for _, tokens, _ in chunks]
        bucket, carried = 0, []
        for i, ((_, _, pos0), want) in enumerate(zip(chunks, wants)):
            wider = max(bucket, self._bucket_for(want, pos0))
            # the lead rides whatever `_bucket_for` gave it
            if not i or all(
                self._chunk_space(chunks[j][2]) >= wider for j in (*carried, i)
            ):
                bucket = wider
                carried.append(i)
        widths, filled = [0] * len(chunks), []
        for i in carried:
            lane, tokens, pos0 = chunks[i]
            widths[i] = min(bucket, wants[i])
            filled.append((lane, tokens[: widths[i]], pos0))
        window = max(self._attn_window(pos0 + bucket) for _, _, pos0 in filled)
        native = self.kv_native
        step = (
            self._lane_prefill_paged_fn(bucket, window=window)
            if native
            else self._lane_prefill_fn(bucket, window=window)
        )
        # the paged view parks at `window` (its tail rows); the slab
        # parks at seq_len (its padding rows)
        rows, posv = self._lane_chunks(filled, bucket, window if native else self._park)
        lane, _, pos0 = chunks[0]
        try:
            state_arg = self._lane_state_arg(lane, pos0, widths[0], write_floor)
        except ValueError:
            self._spans.end(prep)  # a refusal is no dispatch: nothing stays open
            raise
        self._m_prefill_chunks.labels(bucket=str(bucket)).inc()
        self._m_prefill_lanes.inc(len(filled))
        self._m_prefill_rows.labels(kind="real").inc(sum(widths))
        self._m_prefill_rows.labels(kind="bucket").inc(bucket * len(filled))
        self._m_prefill_rows.labels(kind="computed").inc(bucket * self.batch_size)
        arr, *rest = self._host_args(
            *self._page_table_arg(), posv, *state_arg, tokens=rows
        )
        with self._dispatch(
            "prefill_lane_chunk", prep, host_args=1 + len(rest),
            lane=lane, pos=pos0, lanes=[c[0] for c in filled],
            n_tokens=sum(widths), bucket=bucket, window=window,
            **self._chunk_rows_in_context(filled),
            **self._chunk_expert_rows(bucket, len(filled)),
            **self._chunk_state_fields(pos0, widths[0], write_floor),
        ) as timed:
            # `mark`: the forms its expert layers took, or one integer, for
            # the completion stamp's sake alone
            if native:
                with self._kv_pool_guard():
                    if fault is not None:
                        raise fault
                    self.kv_pool, mark = step(self.params, arr, self.kv_pool, *rest)
            else:
                with self._cache_guard():
                    if fault is not None:
                        raise fault
                    self.cache, mark = step(self.params, arr, self.cache, *rest)
            program = self._launched(timed, mark)
            if self._counts_forms and not native:
                # never waited for; with no collect to count them
                # (prompts prefilled and nothing decoded) the oldest go
                self._chunk_forms = self._chunk_forms[-63:] + [(program.seq, mark)]
        return widths

    def prefill_lane(
        self, lane: int, tokens: list[int], pos0: int = 0, write_floor: int = 0
    ) -> None:
        """Prefill one lane's prompt (all but the last token) while every
        other lane's cache rows stay untouched — their writes land in the
        padding rows beyond seqLen, and causal masking hides those rows
        from every real query. This is what lets the API server admit a
        new request into a free lane while other lanes hold live
        conversations (the reference's single-stream loop has no
        equivalent). Runs the chunks back-to-back; the lane scheduler
        instead calls prefill_lane_chunk directly to interleave them with
        decode blocks."""
        self._require_lanes()
        if not 0 <= lane < self.batch_size:
            raise ValueError(f"lane {lane} out of range")
        n = len(tokens)
        if n < 1:
            raise ValueError("empty prompt")
        if pos0 + n - 1 > self.header.seq_len:
            raise ValueError(
                f"prompt of {n} tokens at pos {pos0} exceeds "
                f"seqLen {self.header.seq_len}"
            )
        fills = tokens[:-1]
        p = pos0
        self.recorder.record(
            "step_dispatch", step="prefill_lane", lane=lane, pos=pos0,
            n_tokens=len(fills),
        )
        t0 = time.perf_counter()
        while fills:
            # lane state: a replay's chunks, which start below the floor, keep it
            width = self.prefill_lane_chunk(
                lane, fills, p, **({"write_floor": write_floor} if p < write_floor else {})
            )
            fills = fills[width:]
            p += width
        if p > pos0:
            dt = time.perf_counter() - t0
            self._m_step.labels(kind="prefill_lane").observe(dt)
            self.recorder.record(
                "step_complete", step="prefill_lane", lane=lane, pos=pos0,
                n_tokens=p - pos0, ms=round(dt * 1000, 3),
            )

    # -- paged KV pool (cross-lane prefix sharing) ---------------------------

    def _require_kv_pool(self) -> None:
        if self.kv_pool is None:
            raise ValueError("KV page pool not initialized (init_kv_pool)")

    def _kv_pool_sharding(self) -> NamedSharding:
        # mirror the cache's lead (pp stage) and tp (kv-head) axes; the
        # page axis replaces the dp batch axis and stays replicated —
        # pages are lane-free, that is the whole point
        lead = "pp" if self.pp > 1 else None
        return NamedSharding(self.mesh, P(lead, None, "tp", None, None))

    def _alloc_kv_pool(self):
        from ..ops.kv_cache import QuantKV

        h = self.header
        sharding = self._kv_pool_sharding()
        shape = (
            h.n_layers, self._kv_pool_pages, h.n_kv_heads,
            self._kv_page_size, h.head_dim,
        )
        if self._two_cache_kinds or self._latent or self._stateful:
            # a page holds its positions' rows of every layer, so the pool
            # is the cache's stacks (two kinds of layer: two pairs; latent
            # rows: one stack, one head, `latent_row` wide; lane state: the
            # attention layers' keys and values, and nothing of the states,
            # which belong to no position) under one page number
            return {
                name: jax.device_put(
                    jnp.zeros(
                        (leaf.shape[0], shape[1], leaf.shape[2], shape[3], leaf.shape[4]),
                        self.kv_dtype,
                    ), sharding
                )
                for name, leaf in self._cache_specs.items() if name not in ("s", "r")
            }
        if self.kv_dtype == jnp.int8:
            def leaf():
                return QuantKV(
                    jax.device_put(jnp.zeros(shape, jnp.int8), sharding),
                    jax.device_put(
                        jnp.ones(shape[:-1] + (1,), jnp.float32), sharding
                    ),
                )

            return {"k": leaf(), "v": leaf()}
        return {
            k: jax.device_put(jnp.zeros(shape, self.kv_dtype), sharding)
            for k in ("k", "v")
        }

    def init_kv_pool(
        self, page_size: int, n_pages: int = 0, native: bool = False
    ) -> int:
        """Allocate the shared KV page pool: ``[L, n_pages, KH, page_size,
        hd]`` per k/v leaf (QuantKV pairs under int8 KV), replicated over
        the page axis and sharded like the cache elsewhere. Page 0 is the
        scratch page bucketed copy programs pad with. ``n_pages`` <= 0
        picks a budget of two full-length sequences' worth of pages (in
        native mode: one sequence per lane plus two shareable sequences,
        since the pool is then the only KV home). ``native=True`` switches
        decode/verify/prefill to the pool-native paged programs; each lane
        reads K/V through its page-table row instead of its slab rows.
        Returns the page count actually allocated."""
        self._require_lanes()
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if page_size > self._lane_pad:
            # the bucketed copy loop guarantees (start + bucket) * ps never
            # exceeds seq_len + lane_pad only when one page fits in the
            # padding (dynamic_slice would clamp silently and misalign)
            raise ValueError(
                f"page_size {page_size} exceeds lane padding {self._lane_pad}"
            )
        if native:
            self._refuse("--kv-native 1")
        if native and (self.pp > 1 or self.sp > 1):
            # the pp fwd closure parks at the slab's seq_len and sp shards
            # the sequence axis; both assume slab geometry — the native
            # paged view parks at `window` and is unsharded on its row axis
            raise ValueError("kv_native requires pp == 1 and sp == 1")
        n_blocks = -(-self.header.seq_len // page_size)
        if n_pages <= 0:
            if native:
                n_pages = (self.batch_size + 2) * n_blocks + 1
            else:
                n_pages = 2 * (self.header.seq_len // page_size) + 1
        self._kv_page_size = page_size
        self._kv_pool_pages = n_pages
        self._kv_n_blocks = n_blocks
        self.kv_native = bool(native)
        self._page_table = np.zeros((self.batch_size, n_blocks), np.int32)
        self.kv_pool = self._alloc_kv_pool()
        self._kv_pool_specs = jax.tree.map(_sds, self.kv_pool)
        self.kv_pool_epoch += 1
        return n_pages

    def kv_publishable(self, n_tokens: int) -> int:
        """How many of a lane's first `n_tokens` positions still have their
        rows in every layer's cache, so that pages of them may be stored:
        all, unless the window layers' ring has wrapped. Then the lane's
        first positions are gone from those layers, no page from position 0
        can be stored, and a later request with that prefix misses. (A
        prefix that was stored is whole: every row a window layer's next
        query needs came with it.) None of a model whose lane state reaches
        back to position 0."""
        if self._two_cache_kinds and n_tokens > self.kv_ring:
            return 0
        if self.state_unbounded:
            # no lane could adopt the rows: its recurrent states would not
            # come with them, and no replay rebuilds those
            return 0
        return n_tokens

    @property
    def _kv_max_pages(self) -> int:
        """The most pages one adopt or publish can move."""
        rows = self.kv_ring if self._two_cache_kinds else self.header.seq_len
        return max(1, rows // self._kv_page_size)

    def reset_kv_pool(self) -> None:
        """Reallocate the pool buffer (all page contents dropped). The
        caller owns resetting its host-side page/radix accounting to
        match."""
        self._require_kv_pool()
        self.kv_pool = self._alloc_kv_pool()
        self.kv_pool_epoch += 1
        if self._page_table is not None:
            self._page_table[:] = 0

    def adopt_pages(self, lane: int, page_ids: list[int]) -> None:
        """Pool-native kv_adopt: point ``lane``'s page-table row at
        ``page_ids`` (slot i backs rows [i*ps, (i+1)*ps)). No device work
        — this is the whole point. Unfilled slots fall back to the scratch
        page 0, which only ever receives parked/out-of-range writes."""
        self._require_kv_pool()
        if not self.kv_native:
            raise ValueError("adopt_pages requires kv_native mode")
        if not 0 <= lane < self.batch_size:
            raise ValueError(f"lane {lane} out of range")
        if len(page_ids) > self._kv_n_blocks:
            raise ValueError(
                f"{len(page_ids)} pages exceed {self._kv_n_blocks} blocks"
            )
        row = self._page_table[lane]
        row[:] = 0
        if page_ids:
            row[: len(page_ids)] = np.asarray(page_ids, np.int32)
        self.recorder.record(
            "step_complete", step="kv_adopt", lane=lane,
            n_pages=len(page_ids), ms=0.0, native=True,
        )

    def clear_lane_pages(self, lane: int) -> None:
        """Drop ``lane``'s page-table row (back to the scratch page)."""
        if self._page_table is not None:
            self._page_table[lane] = 0

    def clear_all_lane_pages(self) -> None:
        if self._page_table is not None:
            self._page_table[:] = 0

    def _kv_page_bytes(self) -> int:
        """Device bytes per pool page, summed over k/v (and QuantKV
        scale) leaves and all layers — the unit dllama_kv_copy_bytes_total
        counts in."""
        total = 0
        for leaf in jax.tree.leaves(self._kv_pool_specs):
            n = 1
            for i, d in enumerate(leaf.shape):
                if i != 1:  # every axis but the page axis
                    n *= d
            total += n * jnp.dtype(leaf.dtype).itemsize
        return total

    @contextlib.contextmanager
    def _kv_pool_guard(self):
        """Crash consistency for the donated pool buffer (the publish
        program's analogue of _cache_guard): a failed dispatch may leave
        the pool half-donated, so rebuild it before re-raising. Host-side
        accounting is the manager's to reset. kv_pool_epoch moves so the
        manager can tell pool-poisoning failures from transient ones; in
        native mode cache_epoch moves too — the pool IS the lane KV, so
        the scheduler's existing poisoned/transient classification keeps
        working unchanged."""
        try:
            yield
        except BaseException as e:
            self.recorder.record(
                "error", error=str(e), error_type=type(e).__name__
            )
            try:
                self.kv_pool = self._alloc_kv_pool()
            except Exception as rebuild_err:  # pragma: no cover
                raise rebuild_err from e
            self.kv_pool_epoch += 1
            if self.kv_native:
                self._uncollected = None
                self.cache_epoch += 1
                self._m_epochs.inc()
                self.recorder.record(
                    "cache_epoch", epoch=self.cache_epoch, native=True
                )
            raise

    def _kv_copy_arg_specs(self, bucket: int):
        return (
            self._cache_specs,
            self._kv_pool_specs,
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((bucket,), jnp.int32),
        )

    def _kv_copy_fn(self, kind: str, bucket: int, origin: str = "dispatch"):
        """Jitted page-copy program: ``adopt`` gathers ``bucket`` pool
        pages into a lane's slab rows ``[start*ps, (start+bucket)*ps)``
        (donates the cache), ``publish`` scatters those slab rows into
        pool pages (donates the pool). One program per (kind, bucket) —
        bucketed like prefill so the compile-cache footprint stays
        O(log max_pages). QuantKV caches work unchanged: jax.tree.map
        descends into the (values, scales) pair and every op below is
        shape-generic in the trailing dim."""
        if kind not in ("adopt", "publish"):
            raise ValueError(f"unknown kv copy kind {kind!r}")

        def make():
            ps = self._kv_page_size
            # a lane's position 0 in each stack: the window layers' ring
            # (which has not wrapped where pages are copied,
            # `kv_publishable`) lies behind its spare rows
            row0 = {
                name: self._lane_pad if name in ("kw", "vw") else 0
                for name in self._cache_specs
            }

            def by_stack(leaf, cache, pool):
                # the lane states are no stack of the pool: an adopted cache
                # hands them through, a published pool never sees them
                out = {
                    name: jax.tree.map(
                        partial(leaf, first=row0[name]),
                        cache[name], pool[name],
                    )
                    for name in pool
                }
                if kind == "adopt":
                    out.update({n: c for n, c in cache.items() if n not in pool})
                return out

            if kind == "adopt":

                @partial(jax.jit, donate_argnums=(0,))
                def fn(cache, pool, lane, start_page, ids):
                    def leaf(c, p, first):
                        pages = p[:, ids]  # [L, bucket, KH, ps, last]
                        l_, _, kh, _, last = pages.shape
                        rows = pages.transpose(0, 2, 1, 3, 4).reshape(
                            l_, 1, kh, bucket * ps, last
                        )
                        return lax.dynamic_update_slice(
                            c, rows, (0, lane, 0, first + start_page * ps, 0)
                        )

                    return by_stack(leaf, cache, pool)

            else:

                @partial(jax.jit, donate_argnums=(1,))
                def fn(cache, pool, lane, start_page, ids):
                    def leaf(c, p, first):
                        l_, _, kh, _, last = c.shape
                        rows = lax.dynamic_slice(
                            c, (0, lane, 0, first + start_page * ps, 0),
                            (l_, 1, kh, bucket * ps, last),
                        )
                        pages = rows[:, 0].reshape(
                            l_, kh, bucket, ps, last
                        ).transpose(0, 2, 1, 3, 4)
                        return p.at[:, ids].set(pages)

                    return by_stack(leaf, cache, pool)

            return fn

        return self._build(
            ("kv_" + kind, bucket), make,
            lambda: self._kv_copy_arg_specs(bucket), origin,
        )

    def _kv_copy_chunks(self, n: int):
        """Decompose an n-page copy into decreasing power-of-two buckets.
        Running largest-first keeps start+bucket <= n at every step, so
        with page_size <= lane_pad no dynamic_slice can reach past the
        slab (where it would clamp silently and misalign rows)."""
        out, start = [], 0
        while start < n:
            b = 1
            while b * 2 <= n - start:
                b *= 2
            out.append((start, b))
            start += b
        return out

    def kv_adopt(self, lane: int, page_ids: list[int]) -> None:
        """Copy pool pages into ``lane``'s slab rows ``[0, n*ps)`` — the
        admission half of prefix sharing: the lane starts its life with
        the shared prefix's KV already in place and only the unmatched
        suffix is prefilled. Rows of a partial final page beyond the
        matched token count hold the donor's stale tail; they are
        overwritten by suffix prefill before any query position can
        attend to them (the parked-row garbage argument)."""
        head = time.monotonic()
        self._require_kv_pool()
        if not 0 <= lane < self.batch_size:
            raise ValueError(f"lane {lane} out of range")
        n = len(page_ids)
        if n < 1:
            raise ValueError("empty page list")
        if n * self._kv_page_size > self.header.seq_len:
            raise ValueError(f"{n} pages exceed seqLen {self.header.seq_len}")
        fault = self._fault("kv_adopt")
        if fault is not None and not fault.poison:
            raise fault
        chunks, ids = self._kv_copy_chunks(n), np.asarray(page_ids, np.int32)
        if self._stateful:
            # the rows are another lane's work: this lane's states stand nowhere
            self._state_pos[lane] = None
        with self._dispatch(
            "kv_adopt", head=head, host_args=3 * len(chunks), lane=lane, n_pages=n
        ):
            for start, bucket in chunks:
                fn = self._kv_copy_fn("adopt", bucket)
                args = self._host_args(
                    np.int32(lane), np.int32(start), ids[start : start + bucket]
                )
                with self._cache_guard():
                    if fault is not None:
                        raise fault
                    self.cache = fn(self.cache, self.kv_pool, *args)
        self._m_kv_copy_bytes.inc(n * self._kv_page_bytes())

    def kv_publish(
        self, lane: int, page_ids: list[int], start_page: int
    ) -> None:
        """Scatter ``lane``'s slab rows ``[start_page*ps, ...)`` into pool
        pages — the finish half of prefix sharing: a completed stream's
        full-page KV becomes adoptable by every later admission. The
        caller dedups against the radix tree first, so only slots the
        tree does not already hold are written."""
        head = time.monotonic()
        self._require_kv_pool()
        if not 0 <= lane < self.batch_size:
            raise ValueError(f"lane {lane} out of range")
        n = len(page_ids)
        if n < 1:
            raise ValueError("empty page list")
        if (start_page + n) * self._kv_page_size > self.header.seq_len:
            raise ValueError(
                f"pages [{start_page}, {start_page + n}) exceed "
                f"seqLen {self.header.seq_len}"
            )
        fault = self._fault("kv_publish")
        if fault is not None and not fault.poison:
            raise fault
        chunks, ids = self._kv_copy_chunks(n), np.asarray(page_ids, np.int32)
        with self._dispatch(
            "kv_publish", head=head, host_args=3 * len(chunks),
            lane=lane, n_pages=n, start_page=start_page,
        ):
            for off, bucket in chunks:
                fn = self._kv_copy_fn("publish", bucket)
                args = self._host_args(
                    np.int32(lane), np.int32(start_page + off),
                    ids[off : off + bucket],
                )
                with self._kv_pool_guard():
                    if fault is not None:
                        raise fault
                    self.kv_pool = fn(self.cache, self.kv_pool, *args)
        self._m_kv_copy_bytes.inc(n * self._kv_page_bytes())

    # -- pool-native paged programs (ISSUE 16) -------------------------------
    #
    # In kv_native mode the pool is the only KV home: each compiled lane
    # program GATHERS the window's pages through the per-lane page table
    # into a contiguous [L, B, KH, window + T, hd] view, runs the exact
    # slab loop body on that view (so live lanes see bit-identical K/V
    # rows and produce bit-identical logits), and SCATTERS the rows it
    # wrote back to the lanes' private pages. Rows at-or-beyond `window`
    # are the view's parking tail (the slab parks at seq_len; the view
    # parks at `window`) and are never scattered — parked/out-of-range
    # garbage stays in the discarded view copy.

    def _kv_page_copy_arg_specs(self, bucket: int):
        return (
            self._kv_pool_specs,
            jax.ShapeDtypeStruct((bucket,), jnp.int32),
            jax.ShapeDtypeStruct((bucket,), jnp.int32),
        )

    def _kv_page_copy_fn(self, bucket: int = 1, origin: str = "dispatch"):
        """Pool-internal page copy (src pages -> dst pages), donating the
        pool: the COW fork of a mid-page adoption tail — the ONLY device
        copy left on the native admission path."""

        def make():

            @partial(jax.jit, donate_argnums=(0,))
            def fn(pool, src, dst):
                def leaf(p):
                    return p.at[:, dst].set(p[:, src])

                return jax.tree.map(leaf, pool)

            return fn

        return self._build(
            ("kv_page_copy", bucket), make,
            lambda: self._kv_page_copy_arg_specs(bucket), origin,
        )

    def kv_page_copy(self, src_ids: list[int], dst_ids: list[int]) -> None:
        """Copy pool pages ``src_ids[i]`` -> ``dst_ids[i]`` on device."""
        head = time.monotonic()
        self._require_kv_pool()
        n = len(src_ids)
        if n < 1 or len(dst_ids) != n:
            raise ValueError("src/dst page lists must match and be non-empty")
        fault = self._fault("kv_page_copy")
        if fault is not None and not fault.poison:
            raise fault
        chunks = self._kv_copy_chunks(n)
        src, dst = np.asarray(src_ids, np.int32), np.asarray(dst_ids, np.int32)
        with self._dispatch(
            "kv_page_copy", head=head, host_args=2 * len(chunks), n_pages=n
        ):
            for start, bucket in chunks:
                fn = self._kv_page_copy_fn(bucket)
                args = self._host_args(
                    src[start : start + bucket], dst[start : start + bucket]
                )
                with self._kv_pool_guard():
                    if fault is not None:
                        raise fault
                    self.kv_pool = fn(self.kv_pool, *args)
        self._m_kv_copy_bytes.inc(n * self._kv_page_bytes())

    def _paged_gather(self, pool, pt, window: int, tail: int):
        """Contiguous per-lane KV view of the first `window` rows plus a
        `tail`-row parking pad, gathered through the page table."""
        ps = self._kv_page_size
        wb = -(-window // ps)

        def leaf(p):
            pages = p[:, pt[:, :wb]]  # [L, B, wb, KH, ps, last]
            l_, b, _, kh, _, last = pages.shape
            rows = pages.transpose(0, 1, 3, 2, 4, 5).reshape(
                l_, b, kh, wb * ps, last
            )
            rows = rows[:, :, :, :window, :]
            pad = jnp.zeros((l_, b, kh, tail, last), p.dtype)
            return jnp.concatenate([rows, pad], axis=3)

        return jax.tree.map(leaf, pool)

    def _paged_scatter(self, pool, view, pt, rows, safe):
        """Write view rows back to the pool: view row `rows[b, t]` of lane
        b lands in that lane's page-table page for slot rows//ps at page
        row rows%ps. Unsafe entries (parked lanes, rows at-or-beyond the
        window) collapse onto the scratch page 0 — a don't-care row no
        live read ever resolves to. Safe rows always map to lane-PRIVATE
        pages (the manager COW-forks a shared mid-page boundary before
        admission), so cross-lane scatter collisions cannot happen."""
        ps = self._kv_page_size
        nb = pt.shape[1]
        slot = jnp.clip(rows // ps, 0, nb - 1)
        page = jnp.where(safe, jnp.take_along_axis(pt, slot, axis=1), 0)
        prow = jnp.where(safe, rows % ps, 0)
        srow = jnp.where(safe, rows, 0)

        def leaf(p, v):
            vals = jnp.take_along_axis(
                v, srow[None, :, None, :, None], axis=3
            )  # [L, B, KH, T, last]
            return p.at[:, page, :, prow, :].set(
                vals.transpose(1, 3, 0, 2, 4)
            )

        return jax.tree.map(leaf, pool, view)

    def _lane_paged_specs(self, t: int):
        b = self.batch_size
        tok = jax.ShapeDtypeStruct(
            (b, t), jnp.int32, sharding=self._token_sharding
        )
        return (
            self._param_specs,
            tok,
            self._kv_pool_specs,
            jax.ShapeDtypeStruct((b, self._kv_n_blocks), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
        )

    def _lane_decode_paged_arg_specs(self, n_steps: int):
        b = self.batch_size
        return self._lane_paged_specs(1) + (
            jax.ShapeDtypeStruct((b,), jnp.bool_),
            jax.ShapeDtypeStruct((b,), jnp.int32),  # per-lane seeds
            jax.ShapeDtypeStruct((b,), jnp.float32),
            jax.ShapeDtypeStruct((b,), jnp.float32),
        )

    def _lane_decode_paged_fn(
        self, n_steps: int, window: int, origin: str = "dispatch"
    ):
        """Pool-native decode block: _lane_decode_fn's loop body run on
        the gathered page view (donating the POOL, not the slab). Live
        lanes read/write the exact rows the slab program would, so the
        emitted tokens are bit-identical; the slab cache is untouched."""

        def make():
            precision = self._precision
            fwd = self._fwd
            seq_len = self.header.seq_len

            @partial(jax.jit, donate_argnums=(2,))
            def block(
                params, token, pool, pt, pos_vec, active, seeds,
                temperature, topp,
            ):
                view = self._paged_gather(pool, pt, window, n_steps)

                def body(i, carry):
                    tok, view, out = carry
                    ok = jnp.logical_and(active, pos_vec + i < seq_len)
                    cur = jnp.where(ok, pos_vec + i, window)
                    ctx = (
                        jax.default_matmul_precision(precision)
                        if precision
                        else contextlib.nullcontext()
                    )
                    with ctx:
                        logits, view = fwd(
                            params, tok, cur, view,
                            attn_window=window,
                            attn_park_threshold=window, logits_mode="last",
                        )
                    last = logits[:, -1, :]
                    nxt = _sample_per_lane(
                        last, temperature, topp, seeds, cur, ok
                    )
                    nxt = jnp.where(ok, nxt, 0).reshape(-1, 1)
                    out = lax.dynamic_update_index_in_dim(
                        out, nxt[:, 0], i, axis=0
                    )
                    return nxt, view, out

                out0 = jnp.zeros((n_steps, token.shape[0]), jnp.int32)
                _, view, out = lax.fori_loop(
                    0, n_steps, body, (token, view, out0)
                )
                rows = pos_vec[:, None] + jnp.arange(n_steps)[None, :]
                safe = jnp.logical_and(active[:, None], rows < window)
                pool = self._paged_scatter(pool, view, pt, rows, safe)
                return out, pool

            return block

        return self._build(
            ("lane_block_paged", n_steps, window), make,
            lambda: self._lane_decode_paged_arg_specs(n_steps), origin,
        )

    def _lane_verify_paged_arg_specs(self, t: int):
        b = self.batch_size
        return self._lane_paged_specs(t) + (
            jax.ShapeDtypeStruct((b,), jnp.bool_),
        )

    def _lane_verify_paged_fn(
        self, t: int, window: int, origin: str = "dispatch"
    ):
        """Pool-native speculative verify: _lane_verify_fn on the page
        view (one fwd over t tokens, greedy argmax grid back)."""

        def make():
            precision = self._precision
            fwd = self._fwd
            seq_len = self.header.seq_len

            @partial(jax.jit, donate_argnums=(2,))
            def vstep(params, tokens, pool, pt, pos_vec, active):
                view = self._paged_gather(pool, pt, window, t)
                cur = jnp.where(active, pos_vec, window)
                ctx = (
                    jax.default_matmul_precision(precision)
                    if precision
                    else contextlib.nullcontext()
                )
                with ctx:
                    logits, view = fwd(
                        params, tokens, cur, view,
                        attn_window=window, attn_park_threshold=window,
                        logits_mode="all", n_micro=self._pp_micro(t),
                    )
                out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                out = jnp.where(active[:, None], out, 0)
                rows = cur[:, None] + jnp.arange(t)[None, :]
                safe = jnp.logical_and(
                    jnp.logical_and(active[:, None], rows < window),
                    rows < seq_len,
                )
                pool = self._paged_scatter(pool, view, pt, rows, safe)
                return out, pool

            return vstep

        return self._build(
            ("lane_verify_paged", t, window), make,
            lambda: self._lane_verify_paged_arg_specs(t), origin,
        )

    def _lane_prefill_paged_fn(
        self, t: int, window: int, origin: str = "dispatch"
    ):
        """Pool-native lane-prefill chunk: _lane_prefill_fn on the page
        view. Parked lanes are fed pos = `window` (the view's parking
        tail), so their writes never scatter back."""

        def make():
            precision = self._precision
            fwd = self._fwd

            @partial(jax.jit, donate_argnums=(2,))
            def step(params, tokens, pool, pt, pos_vec):
                view = self._paged_gather(pool, pt, window, t)
                ctx = (
                    jax.default_matmul_precision(precision)
                    if precision
                    else contextlib.nullcontext()
                )
                with ctx:
                    _, view = fwd(
                        params, tokens, pos_vec, view,
                        attn_window=window, attn_park_threshold=window,
                        logits_mode="last", n_micro=self._pp_micro(t),
                        live_lanes_alone=True,
                    )
                rows = pos_vec[:, None] + jnp.arange(t)[None, :]
                safe = rows < window
                pool = self._paged_scatter(pool, view, pt, rows, safe)
                return pool, _chunk_mark(pool)

            return step

        return self._build(
            ("lane_prefill_paged", t, window), make,
            lambda: self._lane_paged_specs(t), origin,
        )

    def _lane_arg_specs(self, n_steps: int):
        """Arg specs for a decode_lanes dispatch (the AOT pre-compile's
        lowering input); per-lane vectors stay unsharded like the
        scalars in _block_arg_specs, and the params/cache trees come from
        the init-time snapshot for the same no-donated-reads reason."""
        b = self.batch_size
        tok = jax.ShapeDtypeStruct(
            (b, 1), jnp.int32, sharding=self._token_sharding
        )
        return (
            self._param_specs,
            tok,
            self._cache_specs,
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.bool_),
            jax.ShapeDtypeStruct((b,), jnp.int32),  # per-lane seeds
            jax.ShapeDtypeStruct((b,), jnp.float32),
            jax.ShapeDtypeStruct((b,), jnp.float32),
            tok,  # the newest block's last tokens, on the device
        )

    def _lane_decode_fn(
        self, n_steps: int, window: int = 0, origin: str = "dispatch"
    ):
        """Per-lane block decode: every lane advances from its own
        position; inactive lanes are parked (fed token 0, writing only
        padding rows). Sampling settings are per-lane vectors (temperature
        0 = greedy argmax inside _sample; a block none of whose live
        lanes samples runs that argmax and nothing else), so ONE compiled
        program serves any mix of requests. One host dispatch per block,
        like decode_block. `window` bounds attention reads by the deepest
        live lane (parked writes land beyond seq_len and are causally
        masked, so the window only limits reads). AOT-compiled like
        _decode_block_fn so the API server's window crossings can be
        prefetched too (this IS the serving path).

        `last` is the third output of the block dispatched before this
        one, each lane's last sampled token, still on the device: a lane
        whose host `token` is negative starts from it, so the host can
        dispatch this block before it has read the last one back."""

        def make():
            precision = self._precision
            fwd = self._fwd
            park = self._park

            seq_len = self.header.seq_len
            # a model whose experts compute the pairs that landed here
            # counts, a step, the pairs its router chose, those, the held experts
            # touched and the tokens that landed: four more columns of the
            # block's one output, so no read-back is added
            counting = self._counts_routing
            token_sharding = self._token_sharding

            @partial(jax.jit, donate_argnums=(2,))
            def block(params, token, cache, pos_vec, active, seeds, temperature,
                      topp, last):
                token = jnp.where(token < 0, last, token)

                def body(i, carry):
                    tok, cache, out = carry
                    counts = [] if counting else None
                    # per-lane in-block stop: a lane whose window fills mid-
                    # block parks itself (writes land in padding, token 0
                    # emitted) instead of shrinking the whole batch's block to
                    # its remaining space — one near-full lane no longer
                    # degrades every concurrent stream to 1-token dispatches
                    #; callers already deactivate a lane the
                    # moment its position cap is reached.
                    ok = jnp.logical_and(active, pos_vec + i < seq_len)
                    cur = jnp.where(ok, pos_vec + i, park)
                    ctx = (
                        jax.default_matmul_precision(precision)
                        if precision
                        else contextlib.nullcontext()
                    )
                    with ctx:
                        logits, cache = fwd(
                            params, tok, cur, cache,
                            attn_window=window,
                            attn_park_threshold=park, logits_mode="last",
                            **({"route_stats": counts} if counting else {}),
                        )
                    last = logits[:, -1, :]
                    # per-lane (seed, position)-derived keys: a seeded lane's
                    # stream is reproducible independent of the other lanes
                    # and of block splits (weak r4 #7 closed for lane mode)
                    nxt = _sample_per_lane(
                        last, temperature, topp, seeds, cur, ok
                    )
                    nxt = jnp.where(ok, nxt, 0).reshape(-1, 1)
                    row = nxt[:, 0]
                    if counting:
                        row = jnp.concatenate([row, counts[0]])
                    out = lax.dynamic_update_index_in_dim(out, row, i, axis=0)
                    return nxt, cache, out

                out0 = jnp.zeros(
                    (n_steps, token.shape[0] + (4 if counting else 0)), jnp.int32
                )
                last, cache, out = lax.fori_loop(
                    0, n_steps, body, (token, cache, out0)
                )
                # placed as the next block's arg spec states it
                return out, cache, lax.with_sharding_constraint(last, token_sharding)

            return block

        return self._build(
            ("lane_block", n_steps, window), make,
            lambda: self._lane_arg_specs(n_steps), origin,
        )

    def dispatch_lanes(
        self,
        tokens: list[int | None],
        pos: list[int],
        n_steps: int,
        active: list[bool] | None = None,
        temperature: list[float] | None = None,
        topp: list[float] | None = None,
        seeds: list[int | None] | None = None,
    ) -> LaneBlock | None:
        """The dispatch half of `decode_lanes`: everything up to and
        including the program call, which returns at the enqueue. Gives
        the block's handle for `collect_lanes`, or None where no lane is
        live or no step fits.

        A lane whose `tokens[l]` is None continues from the last token
        the block dispatched before this one sampled for it, which is
        still on the device (the program's `last`): the caller passes
        `pos[l]` = that block's `pos[l]` + its `n_steps` and needs no
        read-back to dispatch. Such a lane has to have run live in that
        block, and the slab's program alone takes it (not `kv_native`)."""
        self._require_lanes()
        if len(tokens) != self.batch_size or len(pos) != self.batch_size:
            raise ValueError("tokens/pos must have one entry per lane")
        if active is None:
            active = [True] * self.batch_size
        live = [i for i, a in enumerate(active) if a]
        if not live:
            return None
        n_steps = min(
            n_steps, max(self.header.seq_len - pos[i] for i in live)
        )
        if n_steps <= 0:
            return None
        native = self.kv_native
        carried = [i for i in live if tokens[i] is None]
        before = self._uncollected
        if carried and (
            native or before is None or not before.live.issuperset(carried)
        ):
            raise ValueError(
                f"lanes {carried} continue from the device's last tokens, "
                "which the block before this one did not sample for them"
            )
        prep = self._dispatch_prep("decode_lanes")
        if temperature is None:
            temperature = [self.temperature] * self.batch_size
        if topp is None:
            topp = [self.sampler.topp] * self.batch_size
        # the program's own predicate (_sample) engages on the same lanes,
        # less one that fills its window inside the block
        n_sampling = sum(1 for i in live if temperature[i] > 0.0)
        deepest = max(pos[i] for i in live)
        window = self._attn_window(deepest + n_steps)
        self._note_window(window)
        block = (
            self._lane_decode_paged_fn(n_steps, window)
            if native
            else self._lane_decode_fn(n_steps, window)
        )
        if (
            self._aot_blocks
            and window < self.header.seq_len
            and deepest + n_steps >= (3 * window) // 4
        ):
            if native:
                self._prefetch(
                    ("lane_block_paged", n_steps, self._attn_window(window + 1)),
                    lambda nw=self._attn_window(window + 1):
                        self._lane_decode_paged_fn(
                            n_steps, nw, origin="prefetch"
                        ),
                )
            else:
                self._prefetch(
                    ("lane_block", n_steps, self._attn_window(window + 1)),
                    lambda nw=self._attn_window(window + 1):
                        self._lane_decode_fn(
                            n_steps, nw, origin="prefetch"
                        ),
                )
        self._rng_calls += 1
        # unseeded lanes draw from an engine-lifetime stream (varies per
        # call); a seeded lane's stream depends ONLY on (its seed, its
        # absolute positions) — reproducible across block splits and
        # independent of other lanes' activity
        seed_vec = [
            (s if s is not None
             else (self._lane_seed_base + 1_000_003 * self._rng_calls + i)
             ) & 0x7FFFFFFF
            for i, s in enumerate(seeds or [None] * self.batch_size)
        ]
        fault = self._fault("decode_lanes")
        if fault is not None and not fault.poison:
            raise fault
        arr, *rest = self._host_args(
            *self._page_table_arg(),
            np.asarray(pos, np.int32),
            np.asarray(active, np.bool_),
            np.asarray(seed_vec, np.int32),
            np.asarray(temperature, np.float32),
            np.asarray(topp, np.float32),
            tokens=np.asarray(
                [-1 if t is None else t for t in tokens], np.int32
            ).reshape(self.batch_size, 1),
        )
        fields = dict(
            pos=deepest, n_steps=n_steps,
            window=window, n_live=len(live), n_sampling=n_sampling,
        )
        if self._stateful:
            # a live lane's states move a row a step; they have to stand
            # where the lane decodes (position 0 is zero by itself)
            astray = [i for i in live if pos[i] and self._state_pos[i] != pos[i]]
            if astray:
                self._spans.end(prep)
                raise ValueError(
                    f"lanes {astray} decode at {[pos[i] for i in astray]} and their "
                    f"states stand at {[self._state_pos[i] for i in astray]}"
                )
            for i in live:
                self._state_pos[i] = pos[i] + n_steps
            fields["state_lanes"] = len(live)
        # ahead: a block is enqueued and not collected, so the device has
        # this one queued when that one ends
        begun = self._begin_dispatch(
            "decode_lanes", prep, host_args=1 + len(rest), **fields,
            ahead=int(self._uncollected is not None),
            **self._rows_in_context([pos[i] for i in live], n_steps),
        )
        # the call: the enqueue and the transfer of its host arrays
        t0 = begun["t0"]
        sp = self._spans.begin("decode_lanes", component="engine", at=t0, **fields)
        guard = self._kv_pool_guard if native else self._cache_guard
        try:
            with guard():
                if fault is not None:
                    raise fault
                if native:
                    out, self.kv_pool = block(self.params, arr, self.kv_pool, *rest)
                else:
                    out, self.cache, self._lane_last = block(
                        self.params, arr, self.cache, *rest, self._lane_last
                    )
        except BaseException:
            self._spans.end(sp, error=True)
            raise
        program = self._launched(begun, out)
        self._uncollected = LaneBlock(
            out=out, n_steps=n_steps, live=frozenset(live), native=native,
            program=program, t0=t0, t1=program.t1, fields=fields,
        )
        self._spans.end(sp, at=program.t1)
        return self._uncollected

    def discard_lanes(self, block: LaneBlock) -> None:
        """Abandon a dispatched block un-read (the scheduler dropped or
        resumes its streams): no lane continues from it, and the next
        block is not ahead of a collect that never comes."""
        if self._uncollected is block:
            self._uncollected = None

    def collect_lanes(self, block: LaneBlock) -> list[list[int]]:
        """The collect half of `decode_lanes`: wait for the block, read
        its rows back and count what it counted. Blocks are collected in
        the order they were dispatched. `step_complete`'s `ms` runs from
        the later of the block's dispatch and the end of the collect
        before it (with a block dispatched ahead, about where the device
        began it) to this collect's end."""
        if self._uncollected is block:
            self._uncollected = None
        guard = self._kv_pool_guard if block.native else self._cache_guard
        with guard():
            out_np = self._read_back("decode_lanes", block.out, block.program)
        t1 = time.monotonic()
        block.seconds = self._complete_dispatch(
            "decode_lanes", max(block.t0, self._collected_at), t1,
            **block.fields,
        )
        self._collected_at = t1
        if self._counts_routing and not block.native:
            routed, held, touched, landed = (
                int(n) for n in out_np[:, self.batch_size:].sum(axis=0)
            )
            out_np = out_np[:, : self.batch_size]
            self._m_moe_pairs.labels(landed="routed").inc(routed)
            self._m_moe_pairs.labels(landed="held").inc(held)
            self._m_moe_touched.inc(touched)
            self.recorder.record(
                "moe_route", step="decode_lanes", n_steps=block.n_steps,
                pairs_routed=routed, pairs_held=held, held_touched=touched,
                tokens_landed=landed,
            )
        self._count_chunk_forms(block.program.seq)
        # each active stream advances one token per block row
        self._m_tpot.observe(block.seconds / block.n_steps)
        self._m_sampler.labels(
            sampler="full" if block.fields["n_sampling"] else "greedy"
        ).inc()
        return [[int(t) for t in row] for row in out_np]

    def _count_chunk_forms(self, block: int) -> None:
        """Count the forms of the chunk programs enqueued before decode block
        number `block`, which has just been read back: that block took the
        cache they returned, so the device has passed them and their few
        integers are read without a wait."""
        passed = [f for at, f in self._chunk_forms if at < block]
        if not passed:
            return
        self._chunk_forms = [(at, f) for at, f in self._chunk_forms if at >= block]
        landed, whole, pairs = (
            int(n) for n in np.sum([np.asarray(f) for f in passed], axis=0))
        self._m_moe_forms.labels(program="chunk", form="landed").inc(landed)
        self._m_moe_forms.labels(program="chunk", form="whole").inc(whole)
        self.recorder.record(
            "moe_block_forms", program="chunk", chunks=len(passed),
            landed=landed, whole=whole, pairs_landed=pairs,
        )

    def decode_lanes(
        self,
        tokens: list[int],
        pos: list[int],
        n_steps: int,
        active: list[bool] | None = None,
        temperature: list[float] | None = None,
        topp: list[float] | None = None,
        seeds: list[int | None] | None = None,
    ) -> list[list[int]]:
        """Decode `n_steps` tokens on every ACTIVE lane in one device
        dispatch, each lane at its own position (and its own sampling
        settings — temperature 0 decodes that lane greedily; a per-lane
        `seeds[l]` makes that lane's sampled stream reproducible
        regardless of the other lanes — r4's 'seed ignored in lane mode'
        gap). Returns
        [n_steps][lanes] (parked lanes report token 0). A lane that fills
        its window MID-BLOCK parks itself on device and reports 0 for the
        remaining rows — callers must stop consuming a lane's rows once
        its position cap is reached (both the API scheduler and
        generate_batch already do); the block length is clamped only by
        the DEEPEST live lane, so one near-full lane doesn't reduce the
        whole batch to tiny dispatches.

        `dispatch_lanes` and `collect_lanes` back to back: the order of a
        caller that does not run a block ahead."""
        block = self.dispatch_lanes(
            tokens, pos, n_steps, active, temperature, topp, seeds
        )
        return [] if block is None else self.collect_lanes(block)

    def _lane_verify_arg_specs(self, t: int):
        """Arg specs for a speculative verify dispatch (the AOT
        lowering input): token rows are (lanes, 1 + draft bucket) with
        the lane sharding, plus the per-lane position vector and active
        mask; params/cache trees come from the init-time snapshot (same
        no-donated-reads rule as _lane_arg_specs)."""
        b = self.batch_size
        tok = jax.ShapeDtypeStruct(
            (b, t), jnp.int32, sharding=self._token_sharding
        )
        return (
            self._param_specs,
            tok,
            self._cache_specs,
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.bool_),
        )

    def _lane_verify_fn(
        self, t: int, window: int = 0, origin: str = "dispatch"
    ):
        """Batched draft verification for n-gram speculation
        (runtime/spec.py): a close cousin of _lane_decode_fn that feeds
        each ACTIVE lane a row of [pending token, draft_0..draft_{k-1},
        pads] at vector positions pos..pos+t-1 in ONE forward pass and
        returns the greedy argmax at EVERY position, so the host can
        accept the longest draft prefix the model agrees with plus one
        correction token. Unlike the decode block this is a single fwd
        over t tokens, not t sequential fwds — one weight pass amortized
        over up to t emitted tokens, which is the whole point on an
        HBM-bound decode. Greedy only: sampled lanes take the normal
        decode block in the same scheduler tick. AOT-compiled and
        bucketed by draft length (spec_buckets) so no new shape compiles
        mid-serve; rehearse_admission pre-builds every bucket."""

        def make():
            precision = self._precision
            fwd = self._fwd
            park = self._park

            @partial(jax.jit, donate_argnums=(2,))
            def vstep(params, tokens, cache, pos_vec, active):
                cur = jnp.where(active, pos_vec, park)
                ctx = (
                    jax.default_matmul_precision(precision)
                    if precision
                    else contextlib.nullcontext()
                )
                with ctx:
                    logits, cache = fwd(
                        params, tokens, cur, cache,
                        attn_window=window, attn_park_threshold=park,
                        logits_mode="all", n_micro=self._pp_micro(t),
                    )
                out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                out = jnp.where(active[:, None], out, 0)
                return out, cache

            return vstep

        return self._build(
            ("lane_verify", t, window), make,
            lambda: self._lane_verify_arg_specs(t), origin,
        )

    def verify_lanes(
        self,
        rows: list[list[int]],
        pos: list[int],
        active: list[bool],
    ) -> list[list[int]]:
        """Verify each active lane's draft row in ONE compiled dispatch.

        `rows[l]` is [pending token, draft_0..draft_{k-1}, zero pads] of
        the shared width t (a 1 + spec bucket); it is fed at positions
        pos[l]..pos[l]+t-1 and the per-position greedy argmax grid
        [lanes][t] comes back (inactive lanes report 0). The caller
        accepts the longest draft prefix matching the argmax of the
        PREVIOUS position plus one correction token and rewinds the
        rest: rejected rows hold garbage KV, but they sit at-or-beyond
        the lane's rewound position, so they are causally masked and
        overwritten before any query can attend to them — the same
        argument that covers block-decode rows past a stop, and why
        publish-on-finish (which covers only history[:pos]) composes
        with rewinds without touching the paged pool's accounting."""
        self._require_lanes()
        if len(rows) != self.batch_size or len(pos) != self.batch_size:
            raise ValueError("rows/pos must have one entry per lane")
        t = len(rows[0])
        if t < 2:
            raise ValueError("verify rows need a pending token + >=1 draft")
        if any(len(r) != t for r in rows):
            raise ValueError("verify rows must share one bucketed width")
        if t > self._lane_pad:
            raise ValueError(
                f"verify width {t} exceeds lane padding {self._lane_pad} "
                "(parked rows would clamp into live cache)"
            )
        live = [i for i, a in enumerate(active) if a]
        if not live:
            return []
        for i in live:
            if pos[i] + t > self.header.seq_len:
                raise ValueError(
                    f"lane {i}: verify row at pos {pos[i]} width {t} "
                    f"exceeds seqLen {self.header.seq_len}"
                )
        prep = self._dispatch_prep("verify_lanes")
        deepest = max(pos[i] for i in live)
        window = self._attn_window(deepest + t)
        self._note_window(window)
        native = self.kv_native
        vstep = (
            self._lane_verify_paged_fn(t, window)
            if native
            else self._lane_verify_fn(t, window)
        )
        if (
            self._aot_blocks
            and window < self.header.seq_len
            and deepest + t >= (3 * window) // 4
        ):
            if native:
                self._prefetch(
                    ("lane_verify_paged", t, self._attn_window(window + 1)),
                    lambda nw=self._attn_window(window + 1):
                        self._lane_verify_paged_fn(
                            t, nw, origin="prefetch"
                        ),
                )
            else:
                self._prefetch(
                    ("lane_verify", t, self._attn_window(window + 1)),
                    lambda nw=self._attn_window(window + 1):
                        self._lane_verify_fn(
                            t, nw, origin="prefetch"
                        ),
                )
        fault = self._fault("verify_lanes")
        if fault is not None and not fault.poison:
            raise fault
        arr, *rest = self._host_args(
            *self._page_table_arg(),
            np.asarray(pos, np.int32),
            np.asarray(active, np.bool_),
            tokens=np.asarray(rows, np.int32),
        )
        guard = self._kv_pool_guard if native else self._cache_guard
        with self._dispatch(
            "verify_lanes", prep, host_args=1 + len(rest),
            pos=deepest, t=t, window=window, n_live=len(live),
        ) as timed, guard():
            if fault is not None:
                raise fault
            if native:
                out, self.kv_pool = vstep(self.params, arr, self.kv_pool, *rest)
            else:
                out, self.cache = vstep(self.params, arr, self.cache, *rest)
            out_np = self._read_back(
                "verify_lanes", out, self._launched(timed, out))
        return [[int(x) for x in row] for row in out_np]

    # -- resident draft model (second-generation speculation) ----------------

    @property
    def has_draft_model(self) -> bool:
        return self._draft_params is not None

    @property
    def draft_seq_len(self) -> int:
        """The draft checkpoint's own context length — the scheduler must
        not request model drafts for a lane past this position (the tiny
        checkpoint may carry a shorter seqLen than the target)."""
        return self._draft_header.seq_len if self._draft_header else 0

    def init_draft_model(self, model_path: str) -> None:
        """Load a tiny Llama-family DRAFT checkpoint into the same engine
        (``--speculation draft``, runtime/spec.py): its params live
        beside the target's on the same mesh, its KV cache mirrors the
        lane layout (own seqLen + the same padding rows), and its
        programs (``draft_prefill`` chunk buckets, ``draft_step`` greedy
        k-step blocks) go through the SAME _compile_lock/_inflight/
        rehearse machinery as every serving program — AOT-compiled,
        xlalint-checked, cost-budgeted. The draft must share the
        target's tokenizer, which structurally means its vocab: drafts
        are proposed as target token ids and verified by the target, so
        a vocab mismatch is a config error, not a quality problem."""
        self._require_lanes()
        self._refuse("--speculation")
        if self.pp > 1 or self.sp > 1:
            raise ValueError(
                "draft model requires pp == 1 and sp == 1 (the draft "
                "forward runs on the flat mesh path)"
            )
        reader = ModelReader(model_path, max_seq_len=self.header.seq_len)
        dh = reader.header
        if dh.vocab_size != self.header.vocab_size:
            raise ValueError(
                f"draft model vocab {dh.vocab_size} != target vocab "
                f"{self.header.vocab_size}; the draft must share the "
                "target's tokenizer"
            )
        validate_tp(dh, self.tp)
        # dense weights: the draft is tiny, so the q40 device formats'
        # divisibility constraints and kernel launches buy nothing here
        self._draft_params = load_params(
            reader,
            dtype=self.dtype,
            put=shard_params_put(self.mesh, dh),
            weight_format="dense",
            fuse=0,
        )
        self._draft_header = dh
        self._draft_cache_sharding = {
            k: NamedSharding(self.mesh, spec)
            for k, spec in cache_specs(dh, sp=False, pp=False).items()
        }
        mesh = self.mesh

        def dfwd(params, tokens, pos, cache, *, attn_park_threshold=0,
                 logits_mode="all", live_lanes_alone=False):
            return forward(
                params, dh, tokens, pos, cache, mesh=mesh,
                attn_window=0, logits_mode=logits_mode,
                attn_park_threshold=attn_park_threshold,
                live_lanes_alone=live_lanes_alone,
            )

        self._draft_fwd = dfwd
        self.draft_cache = self._fresh_draft_cache()
        self._draft_param_specs = jax.tree.map(_sds, self._draft_params)
        self._draft_cache_specs = jax.tree.map(_sds, self.draft_cache)
        self._m_spec_draft_ms = self.obs.histogram(
            "dllama_spec_draft_model_step_ms",
            "Wall milliseconds of one draft-model dispatch (catch-up "
            "prefill chunk or k-step propose block).",
            labelnames=("kind",),
            buckets=(0.5, 1, 2, 5, 10, 20, 50, 100, 200, 500, 1000),
        )
        self.recorder.record(
            "draft_model_loaded", path=model_path, seq_len=dh.seq_len,
            vocab=dh.vocab_size,
        )

    def _require_draft_model(self) -> None:
        if self._draft_params is None:
            raise ValueError("draft model not loaded (init_draft_model)")

    def _fresh_draft_cache(self):
        """Rebuild the draft KV cache; bumps draft_cache_epoch so the
        scheduler knows every lane's draft context is gone (its
        _draft_pos map resets and catch-up prefill re-derives it —
        advisory state only: drafts are always verified by the target,
        so a dropped draft cache costs acceptance, never bytes)."""
        self.draft_cache_epoch += 1
        self.recorder.record(
            "draft_cache_epoch", epoch=self.draft_cache_epoch
        )
        dh = self._draft_header
        cache = init_kv_cache(
            dh,
            self.batch_size,
            dtype=self.dtype,
            seq_len=dh.seq_len + self._lane_pad,
        )
        return {
            k: jax.device_put(v, self._draft_cache_sharding[k])
            for k, v in cache.items()
        }

    @contextlib.contextmanager
    def _draft_cache_guard(self):
        """_cache_guard's draft twin: draft programs donate
        ``self.draft_cache``, so a failed dispatch rebuilds it before
        re-raising. The target cache is untouched — a draft-side crash
        never costs a live conversation its context."""
        try:
            yield
        except BaseException as e:
            self.recorder.record(
                "error", error=str(e), error_type=type(e).__name__,
                draft=True,
            )
            try:
                self.draft_cache = self._fresh_draft_cache()
            except Exception as rebuild_err:  # pragma: no cover
                raise rebuild_err from e
            raise

    def _draft_park(self) -> int:
        return self._draft_header.seq_len  # first draft padding row

    def _draft_bucket_for(self, n: int, pos: int) -> int:
        """_bucket_for against the DRAFT sequence length (the draft
        checkpoint may be shorter than the target)."""
        space = self._draft_header.seq_len - pos
        fitting = [b for b in self.prefill_buckets if b <= space]
        if not fitting:
            return max(min(space, n), 1)
        for b in fitting:
            if n <= b:
                return b
        return fitting[-1]

    def _draft_prefill_arg_specs(self, t: int):
        b = self.batch_size
        tok = jax.ShapeDtypeStruct(
            (b, t), jnp.int32, sharding=self._token_sharding
        )
        return (
            self._draft_param_specs,
            tok,
            self._draft_cache_specs,
            jax.ShapeDtypeStruct((b,), jnp.int32),
        )

    def _draft_prefill_fn(self, t: int, origin: str = "dispatch"):
        """Draft-cache catch-up prefill: one lane writes a chunk at its
        own position, every other lane parks in the draft padding rows —
        _lane_prefill_fn against the draft params/cache. Full attention
        reads (window 0): the draft is small enough that windowing buys
        nothing over its whole seqLen."""
        self._require_draft_model()

        def make():
            dfwd = self._draft_fwd
            park = self._draft_park()

            @partial(jax.jit, donate_argnums=(2,))
            def step(params, tokens, cache, pos_vec):
                _, cache = dfwd(
                    params, tokens, pos_vec, cache,
                    attn_park_threshold=park, logits_mode="last",
                    live_lanes_alone=True,
                )
                return cache

            return step

        return self._build(
            ("draft_prefill", t), make,
            lambda: self._draft_prefill_arg_specs(t), origin,
        )

    def _draft_step_arg_specs(self, n_steps: int):
        b = self.batch_size
        tok = jax.ShapeDtypeStruct(
            (b, 1), jnp.int32, sharding=self._token_sharding
        )
        return (
            self._draft_param_specs,
            tok,
            self._draft_cache_specs,
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.bool_),
        )

    def _draft_step_fn(self, n_steps: int, origin: str = "dispatch"):
        """Greedy k-step draft-model block: _lane_decode_fn's shape
        (per-lane positions, parked inactive lanes, fori_loop feed-back)
        minus sampling — drafts only ever seed a greedy verify, so plain
        argmax is the whole sampler. One host dispatch proposes k tokens
        for every drafting lane at once."""
        self._require_draft_model()

        def make():
            dfwd = self._draft_fwd
            park = self._draft_park()
            dseq = self._draft_header.seq_len

            @partial(jax.jit, donate_argnums=(2,))
            def block(params, token, cache, pos_vec, active):
                def body(i, carry):
                    tok, cache, out = carry
                    ok = jnp.logical_and(active, pos_vec + i < dseq)
                    cur = jnp.where(ok, pos_vec + i, park)
                    logits, cache = dfwd(
                        params, tok, cur, cache,
                        attn_park_threshold=park, logits_mode="last",
                    )
                    nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
                    nxt = jnp.where(ok, nxt, 0).reshape(-1, 1)
                    out = lax.dynamic_update_index_in_dim(
                        out, nxt[:, 0], i, axis=0
                    )
                    return nxt, cache, out

                out0 = jnp.zeros((n_steps, token.shape[0]), jnp.int32)
                _, cache, out = lax.fori_loop(
                    0, n_steps, body, (token, cache, out0)
                )
                return out, cache

            return block

        return self._build(
            ("draft_step", n_steps), make,
            lambda: self._draft_step_arg_specs(n_steps), origin,
        )

    def draft_prefill(self, lane: int, tokens: list[int], pos0: int) -> None:
        """Catch the draft cache up on `lane`: write `tokens` (context
        rows the draft has not seen — typically the tail the target
        accepted since the last model draft) at pos0.., chunked through
        the bucketed draft_prefill programs. Rows past a later rewind
        are overwritten by the next catch-up before any draft query can
        attend to them — the same causal-mask argument that makes the
        target's verify rewind safe."""
        self._require_draft_model()
        if not 0 <= lane < self.batch_size:
            raise ValueError(f"lane {lane} out of range")
        n = len(tokens)
        if n < 1:
            return
        dseq = self._draft_header.seq_len
        if pos0 + n > dseq:
            raise ValueError(
                f"{n} draft fill tokens at pos {pos0} exceed draft "
                f"seqLen {dseq}"
            )
        park = self._draft_park()
        fills = list(tokens)
        p = pos0
        t0 = time.perf_counter()
        while fills:
            bucket = self._draft_bucket_for(len(fills), p)
            width = min(bucket, len(fills))
            step = self._draft_prefill_fn(bucket)
            rows, posv = self._lane_chunks([(lane, fills[:width], p)], bucket, park)
            arr, posv = self._host_args(posv, tokens=rows)
            with self._draft_cache_guard():
                self.draft_cache = step(
                    self._draft_params, arr, self.draft_cache, posv
                )
            fills = fills[width:]
            p += width
        dt = time.perf_counter() - t0
        self._m_step.labels(kind="draft_prefill").observe(dt)
        if self._m_spec_draft_ms is not None:
            self._m_spec_draft_ms.labels(kind="prefill").observe(dt * 1000)
        self.recorder.record(
            "step_complete", step="draft_prefill", lane=lane, pos=pos0,
            n_tokens=n, ms=round(dt * 1000, 3),
        )

    def draft_propose(
        self,
        tokens: list[int],
        pos: list[int],
        active: list[bool],
        k: int,
    ) -> list[list[int]]:
        """Propose up to `k` greedy draft-model tokens per ACTIVE lane in
        one dispatch: lane l feeds tokens[l] at pos[l] and autoregresses
        k steps through the draft. Returns [lanes][k] (inactive or
        past-draft-capacity rows report 0). Purely advisory — every
        returned token goes through the target's verify pass, so this
        can be wrong, stale, or truncated without any correctness
        cost."""
        head = time.monotonic()
        self._require_draft_model()
        if len(tokens) != self.batch_size or len(pos) != self.batch_size:
            raise ValueError("tokens/pos must have one entry per lane")
        live = [i for i, a in enumerate(active) if a]
        if not live or k < 1:
            return []
        dseq = self._draft_header.seq_len
        k = min(k, max(dseq - pos[i] for i in live))
        if k <= 0:
            return []
        block = self._draft_step_fn(k)
        arr, *rest = self._host_args(
            np.asarray(pos, np.int32),
            np.asarray(active, np.bool_),
            tokens=np.asarray(tokens, np.int32).reshape(self.batch_size, 1),
        )
        with self._dispatch(
            "draft_step", head=head, host_args=1 + len(rest),
            n_steps=k, n_live=len(live),
        ) as timed, self._draft_cache_guard():
            out, self.draft_cache = block(
                self._draft_params, arr, self.draft_cache, *rest
            )
            out_np = self._read_back(
                "draft_step", out, self._launched(timed, out))
        if self._m_spec_draft_ms is not None:
            self._m_spec_draft_ms.labels(kind="propose").observe(
                timed["seconds"] * 1000
            )
        # transpose [k][lanes] -> [lanes][k]
        return [
            [int(out_np[i][lane]) for i in range(k)]
            for lane in range(self.batch_size)
        ]

    def _chunk_space(self, pos: int) -> int:
        """The rows a chunk at `pos` may be padded to (dynamic_update_slice
        clamps silently if pos+bucket > seqLen, which would corrupt earlier
        cache rows)."""
        space = self.header.seq_len - pos
        if self.pp > 1 and self.sp > 1:
            # stage-local sp writes are windowed per shard (run_layers
            # sp_axis): no chunk may exceed one shard's local rows. The
            # bucket filter enforces this for configured buckets; cap the
            # fallback widths below the same way.
            space = min(space, self.header.seq_len // self.sp)
        return space

    def _bucket_for(self, n: int, pos: int) -> int:
        """Smallest bucket covering n tokens whose PADDED extent still fits
        in the cache (`_chunk_space`)."""
        space = self._chunk_space(pos)
        fitting = [b for b in self.prefill_buckets if b <= space]
        if not fitting:
            # guarded by the prefill bounds check: space >= 1 and bucket 1
            # may not be configured; fall back to exact width. Under sp a
            # chunk wider than 1 shards its query axis over sp chips, so
            # round down to a shardable width (width-1 chunks go through
            # the merged-stats branch instead and are always valid).
            if self.sp > 1 and space % self.sp:
                space -= space % self.sp
                if space == 0:
                    return 1
            return max(space, 1)
        for b in fitting:
            if n <= b:
                return b
        return fitting[-1]

    # -- public API ----------------------------------------------------------

    def prefill(self, tokens: list[int], pos: int = 0) -> StepStats:
        """Run all but the last prompt token through the cache (the last
        token is the decode loop's first input, reference: dllama.cpp:38-68)."""
        return self._prefill_rows([tokens] * self.batch_size, pos)

    def _prefill_rows(self, rows: list[list[int]], pos: int = 0) -> StepStats:
        """Chunked, bucketed prefill of per-lane token rows (all the same
        length); everything but the last token of each row enters the cache."""
        n = len(rows[0])
        if n < 1:
            raise ValueError("empty prompt")
        if pos + n - 1 > self.header.seq_len:
            # dynamic_update_slice clamps silently; fail loudly instead
            # (the reference bounds pos by seqLen the same way,
            # dllama.cpp:27-28,76).
            raise ValueError(
                f"prompt of {n} tokens at pos {pos} exceeds "
                f"seqLen {self.header.seq_len}"
            )
        fills = [row[:-1] for row in rows]
        total_ms = 0.0
        p = pos
        while fills[0]:
            bucket = self._bucket_for(len(fills[0]), p)
            width = min(bucket, len(fills[0]))
            head = time.monotonic()
            padded = np.zeros((len(fills), bucket), np.int32)
            padded[:, :width] = [fill[:width] for fill in fills]
            fills = [fill[width:] for fill in fills]
            arr, pos_arg = self._host_args(np.int32(p), tokens=padded)
            window = self._attn_window(p + bucket)
            step = self._step_fn(bucket, greedy=False, window=window)
            # Padding tokens write garbage into cache slots [p+width,
            # p+bucket) — harmless: the causal mask hides them until real
            # tokens overwrite those positions.
            with self._dispatch(
                "prefill", head=head, host_args=2,
                pos=p, bucket=bucket, window=window,
            ) as timed, self._cache_guard():
                _, self.cache = step(self.params, arr, self.cache, pos_arg)
                jax.block_until_ready(self.cache)
            total_ms += timed["seconds"] * 1000
            p += width
        return StepStats(time_ms=total_ms, n_tokens=max(n - 1, 0))

    def _block_width(self, pos: int, block: int) -> int:
        """Block size to run at `pos`: the full compiled width whenever it
        fits the cache, else the exact remaining space."""
        if pos + block <= self.header.seq_len:
            return block
        return self.header.seq_len - pos

    def decode_step(self, token: int, pos: int) -> tuple[int, StepStats]:
        """One decode step: feed `token` at `pos`, return the sampled next
        token (reference: dllama.cpp:74-99)."""
        if pos >= self.header.seq_len:
            raise ValueError(
                f"decode position {pos} out of range (seqLen "
                f"{self.header.seq_len}); the KV cache would clamp silently"
            )
        head = time.monotonic()
        arr, pos_arg = self._host_args(
            np.int32(pos), tokens=np.full((self.batch_size, 1), token, np.int32)
        )
        greedy = self.temperature == 0.0
        window = self._attn_window(pos + 1)
        step = self._step_fn(1, greedy=greedy, window=window)
        with self._dispatch(
            "decode_step", head=head, host_args=2, pos=pos, window=window
        ) as timed, self._cache_guard():
            out, self.cache = step(self.params, arr, self.cache, pos_arg)
            out = jax.block_until_ready(out)
        ms = timed["seconds"] * 1000
        if greedy:
            next_token = int(np.asarray(out)[0])
        else:
            # the one host-side sampling site left (block decode samples
            # on-device inside the compiled program)
            with self._spans.span("sample", component="engine", pos=pos):
                next_token = self.sampler.sample(np.asarray(out)[0])
        return next_token, StepStats(time_ms=ms, n_tokens=1)

    def generate(
        self,
        prompt_tokens: list[int],
        max_steps: int,
        on_token=None,
        stop_condition=None,
        block_size: int = 8,
        start_pos: int = 0,
    ):
        """Prefill + decode loop. Yields nothing; returns (tokens, eval_stats,
        pred_stats). `on_token(token)` fires per generated token and may
        return False to stop (EOS handling lives with the caller, which owns
        the tokenizer/EosDetector).

        Greedy decoding runs in on-device blocks of `block_size` tokens
        (one host dispatch per block); a stop mid-block leaves the already-
        written KV rows beyond the stop as garbage, which is safe — they
        are causally masked and overwritten by the next prefill at those
        positions."""
        # max_steps counts positions from start_pos (for start_pos == 0 this
        # is the reference's absolute --steps semantics, dllama.cpp:76)
        max_pos = min(self.header.seq_len, start_pos + max_steps)
        eval_stats = self.prefill(prompt_tokens, pos=start_pos)
        pos = start_pos + len(prompt_tokens) - 1
        token = prompt_tokens[-1]
        out_tokens: list[int] = []
        pred_ms = 0.0
        block = max(1, block_size)
        stopped = False
        while pos < max_pos and not stopped:
            if block > 1:
                # run the full block size whenever it fits in the cache
                # (compiling a one-off program per tail length costs seconds
                # on this platform); surplus tokens are simply not consumed
                n = self._block_width(pos, block)
                want = min(n, max_pos - pos)
                t0 = time.perf_counter()
                toks = self.decode_block(token, pos, n)[:want]
                pred_ms += (time.perf_counter() - t0) * 1000
                if not toks:
                    break
                for tk in toks:
                    pos += 1
                    out_tokens.append(tk)
                    if on_token is not None and on_token(tk) is False:
                        stopped = True
                        break
                    if stop_condition is not None and stop_condition(tk):
                        stopped = True
                        break
                token = out_tokens[-1]
            else:
                token, stats = self.decode_step(token, pos)
                pred_ms += stats.time_ms
                pos += 1
                out_tokens.append(token)
                if on_token is not None and on_token(token) is False:
                    break
                if stop_condition is not None and stop_condition(token):
                    break
        return out_tokens, eval_stats, StepStats(pred_ms, len(out_tokens))

    def generate_batch(
        self,
        prompts: list[list[int]],
        max_steps: int,
        block_size: int = 8,
    ) -> list[list[int]]:
        """Decode independent sequences, one per batch lane (requires
        batch_size == len(prompts)). Prompts may have DIFFERENT lengths:
        each lane prefills separately (parked writes keep the others
        intact) and decodes from its own position; `max_steps` is the
        per-lane absolute position cap, matching `generate`. Greedy/
        sampled per the engine temperature; returns per-lane token
        lists."""
        if len(prompts) != self.batch_size:
            raise ValueError(
                f"{len(prompts)} prompts for batch_size {self.batch_size}"
            )
        n = len(prompts[0])
        max_pos = min(self.header.seq_len, max_steps)
        if all(len(p) == n for p in prompts):
            # synchronized fast path: one batched prefill, shared positions
            self._prefill_rows(prompts, 0)
            pos = n - 1
            tokens = [p[-1] for p in prompts]
            outs: list[list[int]] = [[] for _ in prompts]
            while pos < max_pos:
                nb = self._block_width(pos, block_size)
                want = min(nb, max_pos - pos)
                rows = self.decode_block(tokens, pos, nb)[:want]
                if not rows:
                    break
                for row in rows:
                    for lane, t in enumerate(row):
                        outs[lane].append(t)
                tokens = rows[-1]
                pos += len(rows)
            return outs

        self._require_lanes()
        for lane, p in enumerate(prompts):
            if not p:
                raise ValueError(f"lane {lane}: empty prompt")
            self.prefill_lane(lane, p)
        pos = [len(p) - 1 for p in prompts]
        tokens = [p[-1] for p in prompts]
        active = [pos[i] < max_pos for i in range(self.batch_size)]
        outs = [[] for _ in prompts]
        while any(active):
            rows = self.decode_lanes(tokens, pos, block_size, active)
            if not rows:
                break
            for row in rows:
                for lane, t in enumerate(row):
                    if active[lane]:
                        outs[lane].append(t)
                        pos[lane] += 1
                        tokens[lane] = t
                        if pos[lane] >= max_pos:
                            active[lane] = False
        return outs

    # -- introspection (obs) -------------------------------------------------

    @staticmethod
    def _key_kind(key) -> str:
        """Step kind of a compile-cache key, matching the
        `dllama_engine_step_seconds{kind=}` label values where one exists."""
        if isinstance(key, tuple) and key and isinstance(key[0], str):
            return {
                "block": "decode_block",
                "lane_block": "decode_lanes",
                "lane_prefill": "prefill_lane",
                "lane_verify": "verify_lanes",
                # pool-native paged variants observe into the same step
                # kinds as their slab twins — serving dashboards don't
                # care which KV home a block decoded from
                "lane_block_paged": "decode_lanes",
                "lane_prefill_paged": "prefill_lane",
                "lane_verify_paged": "verify_lanes",
                "kv_page_copy": "kv_page_copy",
                "score": "score",
            }.get(key[0], key[0])
        return "prefill"  # plain (t, greedy, window) keys

    def compile_cache_report(self) -> list[dict]:
        """Per-key view of the compile cache (what `/v1/debug/compile`
        serves): the cache key, its step kind, who built it
        (dispatch/prefetch), the AOT build wall seconds where measured,
        and XLA's cost analysis — or the explicit ``"unavailable"``
        marker for lazily jitted programs, which expose no executable
        until their first call."""
        from ..obs.cost import extract_cost

        with self._compile_lock:
            items = list(self._compiled.items())
            origins = dict(self._compile_origin)
            seconds = dict(self._compile_seconds)
        out = []
        for key, fn in items:
            cost = self._cost_cache.get(key)
            if cost is None:
                cost = extract_cost(fn)
                if cost is not None:
                    self._cost_cache[key] = cost
            out.append(
                {
                    "key": list(key),
                    "kind": self._key_kind(key),
                    "origin": origins.get(key, "dispatch"),
                    "compile_seconds": seconds.get(key),
                    "cost": cost if cost is not None else "unavailable",
                }
            )
        return out

    def cost_report(self) -> dict:
        """Fold the compile cache into per-kind cost gauges and an
        achieved-vs-roofline fraction from the measured step histograms.

        The representative program per kind is the one accessing the most
        bytes (the widest attention window — what bounds steady-state
        decode); its roofline fraction divides achieved bytes/s
        (cost-analysis bytes / mean measured step seconds) by the chip's
        HBM peak. Fractions are absent when the backend's peak is unknown
        (CPU) or the kind has no measured steps yet."""
        from ..obs.cost import hbm_peak_bytes_per_s, roofline_fraction

        g_flops = self.obs.gauge(
            "dllama_compiled_step_flops",
            "XLA cost-analysis flops of the representative (most "
            "bytes-accessed) compiled program, per step kind.",
            labelnames=("kind",),
        )
        g_bytes = self.obs.gauge(
            "dllama_compiled_step_bytes_accessed",
            "XLA cost-analysis bytes accessed of the representative "
            "compiled program, per step kind.",
            labelnames=("kind",),
        )
        g_roof = self.obs.gauge(
            "dllama_step_roofline_fraction",
            "Achieved HBM bandwidth (cost-analysis bytes / mean measured "
            "step seconds) over the chip's peak, per step kind; only set "
            "when both a cost and a known peak exist.",
            labelnames=("kind",),
        )
        peak = hbm_peak_bytes_per_s()
        per_kind: dict[str, dict] = {}
        for e in self.compile_cache_report():
            cost = e["cost"]
            if not isinstance(cost, dict):
                continue
            cur = per_kind.get(e["kind"])
            if cur is None or cost["bytes_accessed"] > cur["bytes_accessed"]:
                per_kind[e["kind"]] = {
                    "key": e["key"],
                    "flops": cost["flops"],
                    "bytes_accessed": cost["bytes_accessed"],
                }
        for kind, info in per_kind.items():
            g_flops.labels(kind=kind).set(info["flops"])
            g_bytes.labels(kind=kind).set(info["bytes_accessed"])
            hist = self._m_step.labels(kind=kind)
            mean_s = (hist.sum / hist.count) if hist.count else 0.0
            info["mean_step_s"] = mean_s if mean_s > 0 else None
            frac = roofline_fraction(info["bytes_accessed"], mean_s, peak)
            info["roofline_fraction"] = frac
            if frac is not None:
                g_roof.labels(kind=kind).set(frac)
        return {"hbm_peak_bytes_per_s": peak, "kinds": per_kind}

    def occupancy(self) -> dict:
        """The engine's static contribution to an admission-control
        occupancy snapshot (runtime/admission.py): lane capacity and the
        measured per-kind step-time p50s the LoadPredictor forecasts
        from. The scheduler overlays the dynamic half (active lanes,
        parked streams, queue depth) under its own lock."""
        step_p50_s: dict[str, float] = {}
        for kind in ("decode_lanes", "prefill_lane_chunk", "verify_lanes"):
            try:
                p50 = self._m_step.labels(kind=kind).percentile(0.5)
            except Exception:
                p50 = None
            if p50 is not None:
                step_p50_s[kind] = p50
        return {
            "lanes_total": self.batch_size,
            "prefill_buckets": list(self.prefill_buckets),
            "step_p50_s": step_p50_s,
        }

    def _xlalint_baseline_set(self) -> set:
        if self._xlalint_baseline is None:
            from ..analysis.core import load_baseline
            from ..analysis.xlalint import default_baseline_path

            self._xlalint_baseline = load_baseline(default_baseline_path())
        return self._xlalint_baseline

    def _xlalint_after_compile(self, key) -> None:
        """Lint ONE just-compiled program (called by `_build`, so
        dispatch compiles, window prefetches, and rehearse_admission all
        pass through). Warn-by-default;
        DLLAMA_XLALINT=strict raises XlalintError, =0/off disables.
        Lint bugs themselves must never take down a serving compile, so
        non-strict mode swallows analysis errors after logging them."""
        if self._xlalint_mode in ("0", "off", "false"):
            return
        if not self._aot_blocks:
            return  # lazily jitted: no executable to read yet
        import logging

        from ..analysis.xlalint import XlalintError, lint_engine_key

        try:
            new = lint_engine_key(self, key, self._xlalint_baseline_set())
        except Exception:
            logging.getLogger(__name__).exception(
                "xlalint failed analyzing %r (program NOT checked)", key
            )
            return
        if not new:
            return
        rendered = "; ".join(f.render() for f in new)
        self._m_xlalint.inc(len(new))
        if self._xlalint_mode == "strict":
            raise XlalintError(
                f"xlalint: {len(new)} new finding(s) in compiled program "
                f"{key!r}: {rendered}"
            )
        logging.getLogger(__name__).warning(
            "xlalint: %d new finding(s) in compiled program %r: %s",
            len(new), key, rendered,
        )

    def xlalint_report(self) -> dict:
        """Compiled-program lint over the WHOLE compile cache (what
        `GET /v1/debug/xlalint` serves): per-program census, findings
        split new-vs-baselined against xlalint-baseline.json, and the
        keys skipped for exposing no executable. See
        docs/static_analysis.md."""
        from ..analysis.xlalint import lint_engine_report

        rep = lint_engine_report(self, self._xlalint_baseline_set())
        rep["mode"] = self._xlalint_mode
        return rep

