"""The comparison that decides `correct`.

The served path yields token ids and no logits, so the rule is teacher-
forced: the configuration's plain reference (`references/<family>.py`)
computes logits for `prompt + served ids`, run whole from position 0, and
the served token at each position passes when, in the reference's own
logits, it lies no more than `gap_tol` below the reference's largest:

    gap = (top1_logit - served_logit) / std(logits at that position)

No token has to equal the reference's top-1: with seeded random weights
one position in ten (dense) to one in four (sparse) has its two largest
logits closer than the server's honest bf16 rounding, and there the server
may pick either. A fault in the model code (a wrong mask, a missed chunk, a
wrong rotary base, ids off by one) gives near-random tokens, whose gap is
two to four std; the tolerance, in the configuration file, is set from the
largest gap the correctness ladder read on the chip over thousands of
served tokens (PERF.md section 6): 0.4 std for the dense model, 1.3 for the
sparse one, whose honest tail is heavier because rounding can route a
token to another expert. The weights (`weights.py`) are sized so that the
output depends on the whole context; with weights under which the next
token follows from the last one alone, no comparison could see such faults.
"""

from __future__ import annotations

import importlib
import math

import jax.numpy as jnp
import numpy as np

KEEP_STEP = 64  # logits are taken for a multiple of this many positions


def reference_for(cfg: dict):
    return importlib.import_module(f"benchmark.references.{cfg['family']}")


def token_gaps(logits, served) -> tuple[np.ndarray, np.ndarray]:
    """(gap in std units, served == top-1) per served token; `logits`
    [n, vocab] are the reference's for the positions that produced them."""
    served = jnp.asarray(served)
    got = jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0]
    gap = (jnp.max(logits, axis=1) - got) / jnp.std(logits, axis=1)
    return np.asarray(gap), np.asarray(jnp.argmax(logits, axis=1) == served)


def check(cfg: dict, model_path: str, samples: list[dict]) -> list[dict]:
    """One report per sample `{"prompt_ids", "served", ...}`: the largest
    and rms gap, the share of tokens equal to the reference's top-1, and
    whether every token passed `cfg["gap_tol"]` (None: nothing is judged)."""
    samples = [s for s in samples if s["served"]]
    seqs = [s["prompt_ids"] + s["served"][:-1] for s in samples]
    keep = [
        min(len(q), -(-len(s["served"]) // KEEP_STEP) * KEEP_STEP)
        for q, s in zip(seqs, samples)
    ]
    all_logits = reference_for(cfg).last_logits(model_path, cfg, seqs, keep)
    tol = cfg.get("gap_tol")
    out = []
    for s, logits in zip(samples, all_logits):
        n = len(s["served"])
        gap, top1 = token_gaps(logits[-n:], s["served"])
        worst = int(gap.argmax())
        out.append({
            "id": s.get("id"),
            "n_prompt": len(s["prompt_ids"]),
            "n_served": n,
            "max_gap_std": float(gap.max()),
            "rms_gap_std": float(math.sqrt(float((gap**2).mean()))),
            "p99_gap_std": float(np.percentile(gap, 99)),
            "top1_share": float(top1.mean()),
            "worst_at": worst,
            "gaps": [round(float(g), 4) for g in gap],
            "finite": bool(np.isfinite(gap).all()),
            "passed": None if tol is None else bool(
                np.isfinite(gap).all() and gap.max() <= tol
            ),
        })
    return out
