"""Plain reference: a pre-norm decoder with grouped-query attention and SwiGLU.

Follows the published Mistral / Qwen3 modelling code (RMSNorm, q/k/v
projections, optional per-head RMSNorm of q and k, rotary embedding, causal
softmax attention with each KV head shared by a group of query heads, output
projection, residual; RMSNorm, SwiGLU, residual; final RMSNorm, untied head).
float32 throughout, every product at `Precision.HIGHEST`, no cache, no
kernels, nothing imported from `dllama_tpu.models` or `dllama_tpu.ops`.

Departures from the published code, all forced by what is compared:
- Weights come from the Q40 `.m` file the server loaded (`q40file.py`), one
  layer at a time, so the reference and the server see the same numbers.
- Rotary pairing is the `.m` file's, named by the configuration's
  `file.rope_pairing`: "interleaved" rotates elements (2j, 2j+1), which is
  what a converted Llama/Mistral checkpoint holds (the converter permutes the
  q/k rows), "half" rotates (j, j + head_dim/2) as the published code does.
  With seeded random weights the two are different models, not two views of
  one, so the file's convention is the model's.
- Sequences are padded at the end to a multiple of PAD rows so that few
  shapes compile; causal masking keeps every real position independent of
  the padding. Attention runs over query blocks of QB rows against all keys
  up to the block's end, which changes memory, not arithmetic.

`FAULTS` names the ways this reference can be made wrong on purpose, so that
`ladder.py --power` and the tests can show what the comparison reads when the
model code is wrong: a dict of configuration overrides, or a context manager
that breaks the module and mends it on exit. A fault that shows only past
some position says so in `min_prompt`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .q40file import Q40File

PAD = 256
QB = 512
HI = jax.lax.Precision.HIGHEST


def rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def rope(x, theta, pairing):
    """x [T, heads, head_dim], positions 0..T-1."""
    t, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if pairing == "interleaved":
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)
    if pairing != "half":
        raise ValueError(f"unknown rope pairing {pairing!r}")
    a, b = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def attention(q, k, v):
    """Causal attention, q [T, H, hd] against k, v [T, KH, hd]."""
    t, n_heads, hd = q.shape
    kh = k.shape[1]
    q = q.reshape(t, kh, n_heads // kh, hd)
    outs = []
    for s in range(0, t, QB):
        e = min(s + QB, t)
        scores = jnp.einsum("bkgd,tkd->kgbt", q[s:e], k[:e], precision=HI)
        scores = scores / math.sqrt(hd)
        seen = jnp.arange(s, e)[:, None] >= jnp.arange(e)[None, :]
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("kgbt,tkd->bkgd", p, v[:e], precision=HI))
    return jnp.concatenate(outs).reshape(t, n_heads * hd)


class ZeroedKeys:
    """While entered, `attention` drops the keys of positions [lo, hi): a
    chunk of the context that never reached the cache."""

    def __init__(self, lo: int, hi: int):
        self.lo, self.hi, self.min_prompt = lo, hi, hi

    def __enter__(self):
        global attention
        self._real, lo, hi = attention, self.lo, self.hi
        attention = lambda q, k, v: self._real(q, k.at[lo:hi].set(0.0), v)  # noqa: E731
        jax.clear_caches()  # `layer` is jitted with the real one inside

    def __exit__(self, *exc):
        global attention
        attention = self._real
        jax.clear_caches()


FAULTS = {"rope_theta=1e4": {"rope_theta": 1e4}, "keys[512:1024]=0": ZeroedKeys(512, 1024)}


def attention_block(x, w, *, n_heads, n_kv_heads, head_dim, eps, theta, pairing):
    t = x.shape[0]
    y = rms_norm(x, w["att_norm"], eps)
    q = jnp.matmul(y, w["q"].T, precision=HI).reshape(t, n_heads, head_dim)
    k = jnp.matmul(y, w["k"].T, precision=HI).reshape(t, n_kv_heads, head_dim)
    v = jnp.matmul(y, w["v"].T, precision=HI).reshape(t, n_kv_heads, head_dim)
    if "q_norm" in w:
        q = rms_norm(q, w["q_norm"], eps)
        k = rms_norm(k, w["k_norm"], eps)
    q, k = rope(q, theta, pairing), rope(k, theta, pairing)
    return x + jnp.matmul(attention(q, k, v), w["wo"].T, precision=HI)


def swiglu(y, w1, w2, w3):
    gate = jax.nn.silu(jnp.matmul(y, w1.T, precision=HI))
    return jnp.matmul(gate * jnp.matmul(y, w3.T, precision=HI), w2.T, precision=HI)


@functools.partial(jax.jit, static_argnames=("shape",))
def layer(x, w, shape):
    kw = dict(shape)
    x = attention_block(x, w, **kw)
    y = rms_norm(x, w["ffn_norm"], kw["eps"])
    return x + swiglu(y, w["w1"], w["w2"], w["w3"])


@functools.partial(jax.jit, static_argnames=("eps",))
def head(x, final_norm, wcls, eps):
    return jnp.matmul(rms_norm(x, final_norm, eps), wcls.T, precision=HI)


def attention_shape(cfg: dict) -> tuple:
    """The static sizes of the attention block, hashable for `jit`."""
    return tuple(
        {
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg.get("head_dim") or cfg["assumed"]["head_dim"],
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "pairing": cfg["file"]["rope_pairing"],
        }.items()
    )


def attention_weights(f: Q40File, i: int, cfg: dict) -> dict:
    names = ["q", "k", "v", "wo", "att_norm", "ffn_norm"]
    if cfg["file"]["qk_norm"]:
        names += ["q_norm", "k_norm"]
    return {n: f.f32(f"layers.{i}.{n}") for n in names}


def layer_weights(f: Q40File, i: int, cfg: dict) -> dict:
    w = attention_weights(f, i, cfg)
    w.update({n: f.f32(f"layers.{i}.{n}") for n in ("w1", "w2", "w3")})
    return w


def last_logits(path: str, cfg: dict, seqs, keep, layer_fn=layer,
                weights_fn=layer_weights):
    """Logits [keep[i], vocab] at the last keep[i] positions of each
    sequence of token ids, every sequence run whole from position 0."""
    f = Q40File(path)
    shape = attention_shape(cfg)
    xs = []
    for ids in seqs:
        x = f.rows_f32("embed", ids)
        xs.append(jnp.pad(x, ((0, -len(ids) % PAD), (0, 0))))
    for i in range(cfg["num_hidden_layers"]):
        w = weights_fn(f, i, cfg)
        xs = [layer_fn(x, w, shape) for x in xs]
        del w
    final_norm, wcls = f.f32("final_norm"), f.f32("wcls")
    eps = float(cfg["rms_norm_eps"])
    return [
        head(x[len(ids) - n : len(ids)], final_norm, wcls, eps)
        for x, ids, n in zip(xs, seqs, keep)
    ]
