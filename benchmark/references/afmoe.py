"""Plain reference: the `afmoe` decoder (Trinity), as one of the chips that
share its layers.

Follows the published configuration and, where that is silent, the public
`transformers` implementation `modeling_afmoe.py` (the configuration's
`assumed` names the three points). RMSNorm with the configuration's eps
throughout; `x0 = embed[token] * sqrt(hidden)` (`mup_enabled`). Layer l:

    h = norm_in(x);  q, k, v = h Wq, h Wk, h Wv;  g = h Wg
    q = rmsnorm_q(q), k = rmsnorm_k(k)  per head
    sliding layers: q, k = rope(q, k), rotate-half; full layers: no rope
    a = softmax(q k^T / sqrt(hd) + mask) v;  mask: j <= i, and i - j < window
        on sliding layers
    x = x + norm_post_attn((a * sigmoid(g)) Wo)
    h = norm_pre_mlp(x)
    l <  num_dense_layers: m = SwiGLU(h), `intermediate_size` wide
    l >= num_dense_layers: s = sigmoid(h Wr);  I = top-k(s + expert_bias)
        w_e = s_e (e in I);  w = w / (sum w + 1e-20) (`route_norm`);
        w = route_scale * w;  m = shared(h) + sum_{e in I, held} w_e expert_e(h)
    x = x + norm_post_mlp(m)

then the final RMSNorm and the untied head. float32, every product at
`Precision.HIGHEST`, no cache, no kernels, nothing imported from
`dllama_tpu.models` or `dllama_tpu.ops`.

The share: the file holds `num_experts` of the `num_routed_experts` the
router scores, from `first_expert`, and a slice of the vocabulary. What the
absent experts would have added is left out here as in the program, and
that partial sum goes on to the next layer; the shared expert, attention
and the dense layers are whole.

Departures from the published code, forced by what is compared:
- Weights come from the Q40 `.m` file the server loaded, one layer at a time.
- All sequences of a call are padded to one length, and attention runs as a
  scan over query blocks of QB rows, each over all keys under its mask. The
  window and the rotary flag are traced values, so window and full layers
  are one program; padding rows are left out of the experts' routing. The
  programs are compiled ahead, in threads (`compile_programs`).
- Experts: the router and the shared expert run over every token; then the
  held experts one after the other (`experts_sum`), each over the token rows
  routed to it, gathered by `jnp.nonzero` to a common capacity (GROUP rows,
  doubled until the most any expert has fit), and added back with their weights.
  Nothing is sorted, and no schedule is shared with the program.
- Q40 blocks go to the device as the file's bytes and are widened there
  (`widen`), a layer's experts in one transfer.

`FAULTS`: each makes this reference wrong in one stated way (a key laid
over the configuration); `ladder.py --power` shows what the comparison reads
against it. The window's fault shows only past `sliding_window` positions.
"""

from __future__ import annotations

import concurrent.futures
import functools
import math

import jax
import jax.numpy as jnp

from .dense_gqa import HI, head, rms_norm, swiglu
from .q40file import Q40File

QB = 512  # query rows a block
PAD = 1024  # sequences are padded to a multiple of this, all to one length
GROUP = 512  # an expert's token rows are padded to this, doubled until they fit


class Fault(dict):
    """Configuration keys that make the reference wrong; `min_prompt`: the
    shortest prompt on which that shows."""

    def __init__(self, min_prompt: int = 0, **keys):
        super().__init__(keys)
        self.min_prompt = min_prompt


FAULTS = {
    "rope on full layers too": Fault(fault_rope_full=True),
    "window ignored": Fault(min_prompt=4096 + 256, sliding_window=1 << 30),
    "no attention gate": Fault(fault_no_gate=True),
    "bias in the weights": Fault(fault_bias_in_weights=True),
    "route_scale=1": Fault(route_scale=1.0),
    "no route_norm": Fault(route_norm=False),
    "no shared expert": Fault(num_shared_experts=0),
    "absent experts computed": Fault(fault_wrap_absent=True),
    "no post-norms": Fault(fault_no_post_norms=True),
    "no embedding scale": Fault(mup_enabled=False),
    # not a fault of the model code: the control that bounds `gap_tol` from
    # above, this reference with its activations one precision below the
    # program's bfloat16
    "activations in float8": Fault(fault_act_dtype="float8_e4m3fn"),
}


def lossy(x, kw: dict):
    """x after a round trip through the type that `fault_act_dtype` names
    (as it is without one): what a block hands to the next one."""
    return x if kw["act"] is None else x.astype(kw["act"]).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("shape",))
def widen(raw, shape):
    """Q40 wire blocks (the file's bytes, uint8 [n * 18]: an f16 scale, 16
    bytes of nibbles a block) as float32 of `shape`, on the device:
    `q40file._widen`'s arithmetic with the split of scale and nibbles moved
    there too (on the host it was 2 s of strided copies a stack of 32
    experts, 50 s a run). The bytes cross as one flat run: an [n, 18]
    array would be laid out on the device with each row padded to a tile."""
    raw = raw.reshape(-1, 18)
    scales = jax.lax.bitcast_convert_type(raw[:, :2], jnp.float16).astype(jnp.float32)
    nibbles = raw[:, 2:]
    lo = (nibbles & 0xF).astype(jnp.float32) - 8.0
    hi = (nibbles >> 4).astype(jnp.float32) - 8.0
    return (jnp.concatenate([lo, hi], axis=1) * scales[:, None]).reshape(shape)


def matrix(f: Q40File, name: str):
    """A Q40 matrix of the file as float32 (out, in); an f32 tensor as it is."""
    s = f.specs[name]
    if s.float_type.name != "Q40":
        return f.f32(name)
    return widen(jnp.asarray(f._raw(name)), s.shape)


def experts(f: Q40File, layer: int, n_experts: int) -> dict:
    """w1, w2, w3 of a layer's held experts, each [E, out, in]: the file
    holds them expert by expert, one run of bytes, moved in one transfer."""
    first = f.specs[f"layers.{layer}.experts.0.w1"]
    last = f.specs[f"layers.{layer}.experts.{n_experts - 1}.w3"]
    each = first.nbytes
    if (last.offset + last.nbytes - first.offset) != 3 * n_experts * each:
        raise ValueError("the experts' matrices are not one run of equal parts")
    raw = jnp.asarray(f._mm[first.offset : last.offset + last.nbytes])
    raw = raw.reshape(n_experts, 3, each)
    shapes = [f.specs[f"layers.{layer}.experts.0.{n}"].shape for n in ("w1", "w2", "w3")]
    return {
        n: widen(raw[:, j].reshape(-1), (n_experts, *shape))
        for j, (n, shape) in enumerate(zip(("w1", "w2", "w3"), shapes))
    }


def rope_half(x, positions, theta):
    """x [T, heads, hd] rotated by `positions` [T], pairing (j, j + hd/2)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def attention(q, k, v, window):
    """Causal attention of q [T, H, hd] over k, v [T, KH, hd], a query
    seeing the last `window` positions (traced: a full layer's is past
    every position); T a multiple of QB."""
    t, n_heads, hd = q.shape
    kh = k.shape[1]
    qb = q.reshape(t // QB, QB, kh, n_heads // kh, hd)
    kpos = jnp.arange(t)[None, :]

    def block(_, args):
        i, qi = args
        scores = jnp.einsum("bkgd,tkd->kgbt", qi, k, precision=HI) / math.sqrt(hd)
        qpos = i * QB + jnp.arange(QB)[:, None]
        seen = jnp.logical_and(kpos <= qpos, qpos - kpos < window)
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return None, jnp.einsum("kgbt,tkd->bkgd", p, v, precision=HI)

    _, out = jax.lax.scan(block, None, (jnp.arange(t // QB), qb))
    return out.reshape(t, n_heads * hd)


def attention_block(x, w, window, rope, kw: dict):
    """(x after the attention block, its pre-FFN norm). `window` and `rope`
    are traced, so window and full layers share a program."""
    t, n_heads, n_kv, hd, eps = x.shape[0], kw["n_heads"], kw["n_kv_heads"], kw["head_dim"], kw["eps"]
    y = lossy(rms_norm(x, w["att_norm"], eps), kw)
    q = jnp.matmul(y, w["q"].T, precision=HI).reshape(t, n_heads, hd)
    k = jnp.matmul(y, w["k"].T, precision=HI).reshape(t, n_kv, hd)
    v = lossy(jnp.matmul(y, w["v"].T, precision=HI).reshape(t, n_kv, hd), kw)
    q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
    positions = jnp.arange(t)
    q = lossy(jnp.where(rope, rope_half(q, positions, kw["theta"]), q), kw)
    k = lossy(jnp.where(rope, rope_half(k, positions, kw["theta"]), k), kw)
    a = lossy(attention(q, k, v, window), kw)
    if kw["gate"]:
        a = lossy(a * jax.nn.sigmoid(jnp.matmul(y, w["att_gate"].T, precision=HI)), kw)
    o = jnp.matmul(a, w["wo"].T, precision=HI)
    if kw["post_norms"]:
        o = rms_norm(o, w["post_att_norm"], eps)
    x = lossy(x + o, kw)
    return x, lossy(rms_norm(x, w["ffn_norm"], eps), kw)


def add_ffn(x, m, post_norm_w, kw: dict):
    return lossy(x + (rms_norm(m, post_norm_w, kw["eps"]) if kw["post_norms"] else m), kw)


def dense_ffn(y, w1, w2, w3):
    return swiglu(y, w1, w2, w3)


def route(y, gate, bias, kw: dict):
    """(ids [T, k] among all routed experts, weights [T, k])."""
    scores = jax.nn.sigmoid(jnp.matmul(y, gate.T, precision=HI))
    _, ids = jax.lax.top_k(scores + bias, kw["top_k"])
    w = jnp.take_along_axis(scores + bias if kw["bias_in_weights"] else scores, ids, axis=1)
    if kw["route_norm"]:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return ids, w * kw["route_scale"]


def held_rows(ids, n_rows, kw: dict):
    """Each choice's row among the held experts, -1 where the expert is held
    elsewhere or the token row is padding (>= `n_rows`); and the most token
    rows that any held expert got."""
    n_held = kw["n_held"]
    local = ids - kw["first"]
    if kw["wrap_absent"]:
        local = local % n_held
    held = jnp.logical_and(local >= 0, local < n_held)
    held = jnp.logical_and(held, (jnp.arange(ids.shape[0]) < n_rows)[:, None])
    local = jnp.where(held, local, -1)
    counts = jnp.sum(jax.nn.one_hot(local, n_held, dtype=jnp.int32), axis=(0, 1))
    return local, jnp.max(counts)


def experts_sum(y, local, weights, w1, w2, w3, capacity):
    """sum over the held experts e of weight x expert_e(y) on the token
    rows routed to e: `local` [T, k] holds each choice's row among the held
    experts (anything else: held elsewhere). One expert after the other,
    each over its own rows gathered to `capacity` (at least the most any
    expert has; a row of T is padding and adds nothing)."""
    t = y.shape[0]

    def add(m, expert):
        e, a, b, c = expert
        mine = local == e
        (rows,) = jnp.nonzero(jnp.any(mine, axis=1), size=capacity, fill_value=t)
        w = jnp.sum(jnp.where(mine, weights, 0.0), axis=1)
        x = jnp.take(y, rows, axis=0, mode="fill", fill_value=0.0)
        part = swiglu(x, a, b, c) * jnp.take(w, rows, mode="fill", fill_value=0.0)[:, None]
        return m.at[rows].add(part, mode="drop"), None

    m, _ = jax.lax.scan(add, jnp.zeros_like(y), (jnp.arange(w1.shape[0]), w1, w2, w3))
    return m


def capacity_for(most: int) -> int:
    capacity = GROUP
    while capacity < most:  # a power of two: few programs over a run's layers
        capacity *= 2
    return capacity


def routed_experts(y, w, cfg: dict, n_rows=None):
    """The held experts' part of the routed sum, [T, D]."""
    kw = dict(statics(cfg))
    ids, wts = route(y, w["moe_gate"], w["expert_bias"], kw)
    local, most = held_rows(ids, y.shape[0] if n_rows is None else n_rows, kw)
    return experts_sum(y, local, wts, w["w1"], w["w2"], w["w3"], capacity_for(int(most)))


# The chip's compiler takes seconds over every float32 product at
# `Precision.HIGHEST` (described v5e: a [2048, 3072] x [3072, 3072] product
# 8.6 s, at default precision 0.9 s; the four programs below 22.7, 21.2, 5.8
# and 8.5 s), so a layer is few programs: one for a dense layer, two for a
# sparse one (the experts' capacity is known only after routing), window and
# full layers share them, and `compile_programs` builds them side by side.


@functools.partial(jax.jit, static_argnames=("static",))
def dense_layer(x, w, window, rope, static):
    kw = dict(static)
    x, y = attention_block(x, w, window, rope, kw)
    return add_ffn(x, dense_ffn(y, w["w1"], w["w2"], w["w3"]), w["post_ffn_norm"], kw)


@functools.partial(jax.jit, static_argnames=("static",))
def sparse_front(x, w, window, rope, n_rows, static):
    """Attention, the router and the shared expert of a sparse layer:
    (x, pre-FFN norm, held rows, weights, the shared expert's output, the
    most rows a held expert got)."""
    kw = dict(static)
    x, y = attention_block(x, w, window, rope, kw)
    ids, wts = route(y, w["moe_gate"], w["expert_bias"], kw)
    local, most = held_rows(ids, n_rows, kw)
    shared = (
        dense_ffn(y, w["shared_w1"], w["shared_w2"], w["shared_w3"])
        if kw["shared"] else jnp.zeros_like(y)
    )
    return x, y, local, wts, shared, most


@functools.partial(jax.jit, static_argnames=("capacity", "static"))
def sparse_back(x, y, local, wts, shared, w, capacity, static):
    m = shared + experts_sum(y, local, wts, w["w1"], w["w2"], w["w3"], capacity)
    return add_ffn(x, m, w["post_ffn_norm"], dict(static))


ATTENTION = ("q", "k", "v", "wo", "att_gate", "q_norm", "k_norm", "att_norm",
             "post_att_norm", "ffn_norm")  # what `attention_block` reads


def layer_tensors(i: int, cfg: dict) -> tuple[dict, bool]:
    """({key in a layer's weights: the file's tensor}, whether the held
    experts' w1, w2, w3 come besides)."""
    names = {n: f"layers.{i}.{n}" for n in (*ATTENTION, "post_ffn_norm")}
    if i < cfg["num_dense_layers"]:
        names.update({n: f"layers.{i}.{n}" for n in ("w1", "w2", "w3")})
        return names, False
    names.update({n: f"layers.{i}.{n}" for n in ("moe_gate", "expert_bias")})
    if cfg["num_shared_experts"]:
        names.update({"shared_" + n: f"layers.{i}.shared.{n}" for n in ("w1", "w2", "w3")})
    return names, True


def layer_weights(f: Q40File, i: int, cfg: dict) -> dict:
    names, sparse = layer_tensors(i, cfg)
    w = {key: matrix(f, name) for key, name in names.items()}
    if sparse:
        w.update(experts(f, i, cfg["num_experts"]))
    return w


def layer_shapes(f: Q40File, i: int, cfg: dict) -> dict:
    """`layer_weights` as shapes, to compile against."""
    names, sparse = layer_tensors(i, cfg)
    w = {key: jax.ShapeDtypeStruct(f.specs[name].shape, jnp.float32)
         for key, name in names.items()}
    for n in ("w1", "w2", "w3") if sparse else ():
        one = f.specs[f"layers.{i}.experts.0.{n}"].shape
        w[n] = jax.ShapeDtypeStruct((cfg["num_experts"], *one), jnp.float32)
    return w


def statics(cfg: dict) -> tuple:
    """What of the configuration (and of a fault laid over it) is static in
    the layers' programs, hashable for `jit`."""
    return tuple({
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
        "gate": not cfg.get("fault_no_gate"),
        "post_norms": not cfg.get("fault_no_post_norms"),
        "top_k": cfg["num_experts_per_tok"],
        "route_norm": bool(cfg["route_norm"]),
        "route_scale": float(cfg["route_scale"]),
        "bias_in_weights": bool(cfg.get("fault_bias_in_weights")),
        "n_held": cfg["num_experts"],
        "first": cfg.get("first_expert", 0),
        "wrap_absent": bool(cfg.get("fault_wrap_absent")),
        "shared": bool(cfg["num_shared_experts"]),
        "act": cfg.get("fault_act_dtype"),
    }.items())


HEAVY = ("w1", "w2", "w3", "post_ffn_norm")  # what `sparse_back` reads


def compile_programs(f: Q40File, cfg: dict, t_pad: int, n_head: int) -> dict:
    """The run's programs, lowered against their shapes and compiled side by
    side in threads: the chip's compiler takes 6 to 23 s over each (float32
    products at `Precision.HIGHEST`), 58 s one after the other. Futures of
    callables that take a program's traced arguments."""
    static, n_dense = statics(cfg), cfg["num_dense_layers"]
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    x, i32, flag = f32((t_pad, cfg["hidden_size"])), jax.ShapeDtypeStruct((), jnp.int32), \
        jax.ShapeDtypeStruct((), jnp.bool_)
    k = cfg["num_experts_per_tok"]
    jobs = {"head": lambda: head.lower(
        f32((n_head, cfg["hidden_size"])), f32(f.specs["final_norm"].shape),
        f32(f.specs["wcls"].shape), eps=float(cfg["rms_norm_eps"])).compile()}
    if n_dense:
        jobs["dense"] = lambda: dense_layer.lower(
            x, layer_shapes(f, 0, cfg), i32, flag, static=static).compile()
    if n_dense < cfg["num_hidden_layers"]:
        w = layer_shapes(f, n_dense, cfg)
        jobs["front"] = lambda: sparse_front.lower(
            x, {n: v for n, v in w.items() if n not in HEAVY[:3]}, i32, flag, i32,
            static=static).compile()
        jobs["back"] = lambda: sparse_back.lower(
            x, x, jax.ShapeDtypeStruct((t_pad, k), jnp.int32), f32((t_pad, k)), x,
            {n: w[n] for n in HEAVY}, capacity=GROUP, static=static).compile()
    pool = concurrent.futures.ThreadPoolExecutor(len(jobs))
    futures = {name: pool.submit(job) for name, job in jobs.items()}
    pool.shutdown(wait=False)
    return futures


def layer(x, n_rows: int, w, cfg: dict, i: int, programs: dict):
    """Layer i over one padded sequence of `n_rows` tokens."""
    sliding = cfg["layer_types"][i] == "sliding_attention"
    window = jnp.int32(min(int(cfg["sliding_window"]), 1 << 30) if sliding else 1 << 30)
    rope = jnp.asarray(sliding or bool(cfg.get("fault_rope_full")))
    if i < cfg["num_dense_layers"]:
        return programs["dense"].result()(x, w, window, rope)
    x, y, local, wts, shared, most = programs["front"].result()(
        x, {n: v for n, v in w.items() if n not in HEAVY[:3]}, window, rope, jnp.int32(n_rows))
    heavy, capacity = {n: w[n] for n in HEAVY}, capacity_for(int(most))
    if capacity == GROUP:
        return programs["back"].result()(x, y, local, wts, shared, heavy)
    return sparse_back(x, y, local, wts, shared, heavy, capacity, statics(cfg))


def last_logits(path: str, cfg: dict, seqs, keep):
    """Logits [keep[i], vocab] at the last keep[i] positions of each
    sequence of token ids, every sequence run whole from position 0."""
    if not seqs:
        return []
    f = Q40File(path)
    t_pad = -(-max(len(ids) for ids in seqs) // PAD) * PAD
    n_head = min(t_pad, max(keep))  # one head program: the most rows any asks for
    programs = compile_programs(f, cfg, t_pad, n_head)
    scale = math.sqrt(cfg["hidden_size"]) if cfg["mup_enabled"] else 1.0
    xs = []
    for ids in seqs:
        x = lossy(f.rows_f32("embed", ids) * scale, {"act": cfg.get("fault_act_dtype")})
        xs.append(jnp.pad(x, ((0, t_pad - len(ids)), (0, 0))))
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(f, i, cfg)
        xs = [layer(x, len(ids), w, cfg, i, programs) for x, ids in zip(xs, seqs)]
        del w
    final_norm, wcls = f.f32("final_norm"), matrix(f, "wcls")
    out = []
    for x, ids, n in zip(xs, seqs, keep):
        start = max(0, min(len(ids) - n, t_pad - n_head))
        rows = programs["head"].result()(
            jax.lax.dynamic_slice_in_dim(x, start, n_head), final_norm, wcls)
        out.append(rows[len(ids) - n - start : len(ids) - start])
    return out
