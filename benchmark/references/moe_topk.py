"""Plain reference: the `dense_gqa` block with a sparse mixture of experts.

Follows the published Qwen3-MoE modelling code: router logits = y @ gate^T,
softmax over all experts in float32, the `num_experts_per_tok` largest kept,
renormalised to sum to 1 when `norm_topk_prob`, and the output is the
weighted sum of those experts' SwiGLU. float32, `Precision.HIGHEST`, no
kernels, nothing imported from `dllama_tpu.models` or `dllama_tpu.ops`.

Departures from the published code (besides those of `dense_gqa.py`):
- Every expert is evaluated on every token and multiplied by its routing
  weight, which is zero for the experts not chosen. That is 16 times the
  published arithmetic at 8 of 128 and the same result; it needs no sorting
  or gathering, which is what the program's kernels do and what the
  reference must not share.
- A token whose 8th and 9th router probabilities lie closer than the
  server's bf16 rounding can be routed differently there. The comparison
  rule's tolerance is measured with that included (PERF.md, the ladder).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import dense_gqa
from .dense_gqa import HI, attention_block, rms_norm, swiglu
from .q40file import Q40File

FAULTS = dense_gqa.FAULTS  # this family's attention is `dense_gqa.attention`


def routing_weights(y, gate, top_k, renormalise):
    """[T, E] weights, zero outside each token's top_k experts."""
    probs = jax.nn.softmax(jnp.matmul(y, gate.T, precision=HI), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    if renormalise:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    rows = jnp.arange(y.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, top_i].set(top_p)


@functools.partial(jax.jit, static_argnames=("shape",))
def layer(x, w, shape):
    kw = dict(shape)
    top_k, renormalise = kw.pop("top_k"), kw.pop("renormalise")
    x = attention_block(x, w, **kw)
    y = rms_norm(x, w["ffn_norm"], kw["eps"])
    weights = routing_weights(y, w["moe_gate"], top_k, renormalise)

    def add_expert(acc, e):
        w1, w2, w3, share = e
        return acc + share[:, None] * swiglu(y, w1, w2, w3), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x), (w["w1"], w["w2"], w["w3"], weights.T)
    )
    return x + out


def layer_weights(f: Q40File, i: int, cfg: dict) -> dict:
    w = dense_gqa.attention_weights(f, i, cfg)
    w["moe_gate"] = f.f32(f"layers.{i}.moe_gate")
    for n in ("w1", "w2", "w3"):
        w[n] = f.f32_stack(
            [f"layers.{i}.experts.{e}.{n}" for e in range(cfg["num_experts"])]
        )
    return w


def last_logits(path: str, cfg: dict, seqs, keep):
    return dense_gqa.last_logits(
        path, cfg, seqs, keep,
        layer_fn=lambda x, w, shape: layer(
            x, w,
            shape + (("top_k", cfg["num_experts_per_tok"]),
                     ("renormalise", bool(cfg["norm_topk_prob"]))),
        ),
        weights_fn=layer_weights,
    )
