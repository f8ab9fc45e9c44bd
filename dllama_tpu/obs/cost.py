"""Compiled-step cost analysis + HBM roofline accounting.

Decode on this hardware is HBM-bandwidth-bound: a decode step's floor is
(bytes it must read) / (HBM peak). XLA already knows the first number for
every compiled program — ``compiled.cost_analysis()`` reports flops and
bytes accessed — so this module harvests it from the engine's compile
cache, pairs it with the measured step-time histograms, and turns "is
decode as fast as the hardware allows?" into a single
achieved-vs-roofline fraction instead of a guess.

An analytic weight-read model (``weight_bytes_per_token``) lives here
so the CLI can print a startup
roofline report next to the memory/ICI reports: bytes per decoded token
per chip, the HBM floor in ms/token, and the implied tok/s ceiling.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

import jax

if TYPE_CHECKING:
    from ..formats.model_file import LlmHeader

# Per-chip HBM peak bandwidth, bytes/s, keyed by the exact
# ``jax.devices()[0].device_kind`` string. Source: Google Cloud TPU
# documentation, "TPU v5e" system architecture: 16 GB HBM2e at 819 GB/s
# per chip; the v5e reports itself as "TPU v5 lite". Only kinds whose
# reported string has been checked are listed: a TPU that is not here is
# an error, never a default. The CPU test backend reports None and every
# roofline figure downstream reads "unavailable".
HBM_PEAK_BYTES_PER_S = {
    "TPU v5 lite": 819e9,
}


def hbm_peak_bytes_per_s() -> float | None:
    """Per-chip HBM peak for the current backend; None off the TPU. An
    unlisted TPU kind raises: a roofline share against a guessed peak is
    worse than none."""
    if jax.default_backend() != "tpu":
        return None
    kind = jax.devices()[0].device_kind
    if kind not in HBM_PEAK_BYTES_PER_S:
        raise ValueError(
            f"no HBM peak for device_kind {kind!r}; add it (with its "
            f"source) to obs.cost.HBM_PEAK_BYTES_PER_S "
            f"(known: {sorted(HBM_PEAK_BYTES_PER_S)})"
        )
    return HBM_PEAK_BYTES_PER_S[kind]


def extract_cost(compiled: object) -> dict | None:
    """{flops, bytes_accessed} from an executable's ``cost_analysis()``
    (a dict on jax 0.9), or None when the object is not an AOT-compiled
    executable (lazily jitted step fns), the backend returns nothing, or
    the surface raises."""
    fn = getattr(compiled, "cost_analysis", None)
    if fn is None:
        return None
    try:
        ca = fn()
    except Exception:
        return None
    if not isinstance(ca, dict):
        return None
    flops = ca.get("flops")
    bytes_accessed = ca.get("bytes accessed")
    if flops is None and bytes_accessed is None:
        return None
    return {
        "flops": float(flops or 0.0),
        "bytes_accessed": float(bytes_accessed or 0.0),
    }


def roofline_fraction(
    bytes_accessed: float, step_seconds: float, peak_bytes_per_s: float | None
) -> float | None:
    """Fraction of the HBM roofline a measured step achieved: achieved
    bytes/s over peak. None when any input is missing/degenerate."""
    if (
        peak_bytes_per_s is None
        or peak_bytes_per_s <= 0
        or step_seconds <= 0
        or bytes_accessed <= 0
    ):
        return None
    return (bytes_accessed / step_seconds) / peak_bytes_per_s


def analytic_step_seconds(
    bytes_accessed: float | None, peak_bytes_per_s: float | None
) -> float | None:
    """Bandwidth-bound lower bound on one dispatch's wall time: the
    program's cost-analysis bytes pushed through the chip's HBM peak.
    The LoadPredictor's cold-start floor (runtime/admission.py) before
    any measured step percentiles exist; None when the cost or the peak
    is unknown (CPU backend, lazily jitted program)."""
    if (
        bytes_accessed is None
        or bytes_accessed <= 0
        or peak_bytes_per_s is None
        or peak_bytes_per_s <= 0
    ):
        return None
    return float(bytes_accessed) / float(peak_bytes_per_s)


# bytes a weight, scales included, by the form a leaf is held in
# (ops/quant_matmul: eight nibbles an int32 word, or int8 values; an f32
# scale per 32 block either way; a float weight as bf16)
BYTES_PER_WEIGHT = {"packed": 0.5 + 4.0 / 32.0, "int8": 1.0 + 4.0 / 32.0, "float": 2.0}


def weight_bytes_per_token(h: "LlmHeader", forms: tuple[str, str]) -> int:
    """HBM bytes of weights a single decode step must read: every matmul
    weight once (MoE: attention weights + the active experts' share), each
    charged by the form it is held in. `forms` is what
    models/loader.weight_forms returns: the form of the dense matmuls and
    that of the routed experts, each `packed` (nibbles, 0.625 B/weight),
    `int8` (1.125) or `float` (bf16, 2)."""
    dense_bpw, expert_bpw = (BYTES_PER_WEIGHT[form] for form in forms)
    att = h.dim * h.q_dim + 2 * h.dim * h.kv_dim + h.q_dim * h.dim
    ffn = 3 * h.dim * h.ff_dim
    ffn_bpw = dense_bpw
    if h.n_experts:
        ffn *= h.n_active_experts  # ragged kernel reads active experts only
        ffn_bpw = expert_bpw
    total = (
        h.n_layers * (att * dense_bpw + ffn * ffn_bpw)
        + h.dim * h.vocab_size * dense_bpw
    )
    if h.n_experts:
        total += h.n_layers * h.dim * h.n_experts * 4  # f32 gate
    return int(total)


def weight_bytes_by_form(params, h: "LlmHeader") -> dict[str, float]:
    """What the engine holds, from the leaves it loaded: resident bytes by
    form (`packed`: nibble words + their scales; `int8`: int8 values + their
    scales; `float`: every other leaf: embedding, norms, routers, dense
    matmuls; the three add up to the leaves' bytes), and
    `decode_packed_share`: of the quantized bytes a one-token decode step
    reads (every dense stack whole, a routed expert stack [L, E, ...] at
    the active share of the experts held), the part that is packed. 1.0
    says every byte of a step is half what int8 holds; a sparse model
    whose experts stay int8 (a mesh) reads well under a half."""
    import jax

    from ..ops.quant_matmul import FusedQuantWeight, PackedQuantWeight, QuantWeight

    out = {"packed": 0, "int8": 0, "float": 0}
    step = {"packed": 0.0, "int8": 0.0}
    is_leaf = lambda x: isinstance(x, (QuantWeight, PackedQuantWeight, FusedQuantWeight))
    for leaf in jax.tree.leaves(params, is_leaf=is_leaf):
        if isinstance(leaf, FusedQuantWeight):
            leaf = leaf.weight
        if not isinstance(leaf, (QuantWeight, PackedQuantWeight)):
            out["float"] += leaf.nbytes
            continue
        form = "packed" if isinstance(leaf, PackedQuantWeight) else "int8"
        n = sum(a.nbytes for a in leaf)
        out[form] += n
        routed = leaf[0].ndim == 4 and h.n_experts  # [L, E, in, out]
        step[form] += n * h.n_active_experts / h.n_experts if routed else n
    read = step["packed"] + step["int8"]
    return {**out, "decode_packed_share": step["packed"] / read if read else 0.0}


def program_cost_ceilings(
    family: str,
    *,
    steps: int = 1,
    tokens: int = 1,
    param_bytes: float = 0.0,
    cache_bytes: float = 0.0,
    pool_bytes: float = 0.0,
    param_elems: float = 0.0,
    cache_elems: float = 0.0,
    slack: float = 8.0,
    paged: bool = False,
) -> dict:
    """Per-program {bytes_accessed, flops} ceilings for the xlalint cost
    budget gate, derived from the same roofline model as
    ``weight_bytes_per_token``: a forward step fundamentally reads the
    weights once plus the touched KV window (bytes floor) and does
    ~2 flops per weight per token plus the attention reads (flops
    floor). The ``slack`` multiple (default 8x) makes these CLIFF
    guards, not tight bounds — a program only trips one when it does
    work a whole multiple of its analytic floor (the classic regather /
    accidental-replication failure mode), so backend fusion differences
    never flap the gate. Copy programs (``kv_adopt``/``kv_publish``/
    ``kv_page_copy``) move pages between KV buffers: their bytes
    ceiling is a slack multiple of the buffers involved and their flops
    are ~0 (a flat allowance covers index arithmetic). ``paged=True``
    marks a pool-native lane program (PR 16): its forward reads K/V
    through a page-table gather out of the pool and scatters the new
    rows back, so its ceiling grows by ~two extra pool traversals per
    step — page indirection that costs MORE than that is exactly the
    regression this gate exists to catch. Draft-model programs
    (``draft_prefill``/``draft_step``) are plain forwards over the DRAFT
    checkpoint's params/cache: callers pass the draft spec trees and the
    same forward math applies (``draft_step`` autoregresses, so its
    ``steps`` is the draft length k).
    """
    if family in ("kv_adopt", "kv_publish", "kv_page_copy"):
        return {
            "bytes_accessed": slack * (cache_bytes + pool_bytes),
            "flops": slack * cache_elems + 1e6,
        }
    steps = max(1, steps)
    tokens = max(1, tokens)
    # the cache term scales with the token count: a t-wide prefill's
    # attention reads/writes the KV window per token, and on small
    # models that activation traffic dwarfs the one-time weight read
    base_bytes = param_bytes + (1.0 + tokens) * cache_bytes + pool_bytes
    if paged:
        # page-table gather (view materialization) + row scatter-back
        base_bytes += 2.0 * pool_bytes
    return {
        "bytes_accessed": slack * steps * base_bytes,
        "flops": (
            slack * steps * (2.0 * param_elems * tokens
                             + 4.0 * cache_elems * tokens)
            + 1e6
        ),
    }


def roofline_report(
    h: "LlmHeader", forms: tuple[str, str], tp: int = 1, pp: int = 1,
    spec_k: int = 0
) -> dict:
    """Analytic decode roofline for this model/forms/layout: weight-read
    bytes per token per chip (weights shard over tp x pp; dp/sp replicate
    them, each replica reading its own copy) and, when the backend's HBM
    peak is known, the ms/token floor + tok/s ceiling. With speculation
    on (``spec_k`` > 0) one verify dispatch — one weight pass — emits up
    to ``spec_k + 1`` tokens, so the weight-bound ceiling scales by the
    achieved tokens-per-weight-pass, which live decoding reports as the
    ``dllama_spec_tokens_per_weight_pass`` gauge (floor 1.0 = nothing
    accepted, ceiling ``spec_k + 1`` = every draft accepted)."""
    shards = max(tp, 1) * max(pp, 1)
    per_chip = weight_bytes_per_token(h, forms) // shards
    peak = hbm_peak_bytes_per_s()
    rep: dict = {
        "weight_bytes_per_token_per_chip": per_chip,
        "hbm_peak_bytes_per_s": peak,
        "min_ms_per_token": None,
        "max_tok_s_per_chip": None,
        "spec_tokens_per_pass_floor": None,
        "spec_tokens_per_pass_ceiling": None,
    }
    if peak:
        rep["min_ms_per_token"] = per_chip / peak * 1000.0
        rep["max_tok_s_per_chip"] = peak / per_chip if per_chip else None
    if spec_k > 0:
        rep["spec_tokens_per_pass_floor"] = 1.0
        rep["spec_tokens_per_pass_ceiling"] = float(spec_k + 1)
    return rep


def print_roofline_report(
    h: "LlmHeader", forms: tuple[str, str], tp: int = 1, pp: int = 1,
    spec_k: int = 0
) -> dict:
    """Startup roofline printout (rides next to the memory/ICI reports in
    cli.load_engine); returns the report dict it printed."""
    rep = roofline_report(h, forms, tp=tp, pp=pp, spec_k=spec_k)
    gb = rep["weight_bytes_per_token_per_chip"] / 1e9
    if rep["hbm_peak_bytes_per_s"]:
        print(
            f"📐 Roofline: {gb:.3f} GB weight reads/token/chip @ "
            f"{rep['hbm_peak_bytes_per_s'] / 1e9:.0f} GB/s HBM peak -> "
            f">= {rep['min_ms_per_token']:.2f} ms/token "
            f"(<= {rep['max_tok_s_per_chip']:.1f} tok/s/chip)"
        )
    else:
        print(
            f"📐 Roofline: {gb:.3f} GB weight reads/token/chip "
            f"(HBM peak unknown on the {jax.default_backend()!r} backend; "
            "no tok/s ceiling)"
        )
    if rep["spec_tokens_per_pass_ceiling"] is not None:
        print(
            f"📐 Speculation: 1 weight pass emits 1.0..."
            f"{rep['spec_tokens_per_pass_ceiling']:.1f} tokens (k="
            f"{spec_k}; live: dllama_spec_tokens_per_weight_pass)"
        )
    return rep
