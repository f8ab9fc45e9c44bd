"""Plain reference: the `pangu_ultra_moe` decoder (openPangu-Ultra-MoE), as
one of the chips that share its layers, with latent attention in the
EXPANDED form: keys and values of every head are rebuilt from the latent
through `wkv_b`, and nothing is absorbed.

Follows the published configuration; where that is silent, the family's
convention (the configuration's `assumed` names each point). RMSNorm with
the configuration's eps throughout; `x0 = embed[token]`. Layer l:

    h = norm_in(x)
    cq = rmsnorm_qa(h Wqa);  q = cq Wqb -> heads of [q_nope | q_rope];  q_rope = rope(q_rope)
    [ckv | kr] = h Wkva;  c = rmsnorm_kva(ckv);  kr = rope(kr), one head shared by all
    [k_nope_h | v_h](j) = c(j) Wkvb_h
    s_h(i, j) = (q_nope_h(i) . k_nope_h(j) + q_rope_h(i) . kr(j)) / sqrt(nope + rope), j <= i
    a_h = softmax_j(s_h) v_h;  x = x + norm_post_attn(concat_h(a_h) Wo)
    h = norm_pre_mlp(x)
    l <  first_k_dense_replace: m = SwiGLU(h), `intermediate_size` wide
    l >= first_k_dense_replace: s = sigmoid(h Wr);  I = top-k(s);  w_e = s_e / (sum_I s + 1e-20)
        (`norm_topk_prob`);  w = routed_scaling_factor * w
        m = shared(h) + sum_{e in I, held} w_e expert_e(h)
    x = x + norm_post_mlp(m)

then the final RMSNorm and the untied head. The `nextn` module is no term
of these logits and is not in the file. float32, every product at
`Precision.HIGHEST`, no cache, no kernels, nothing imported from
`dllama_tpu.models` or `dllama_tpu.ops`.

The share is `afmoe.py`'s: the file holds `n_routed_experts` of the
`num_routed_experts` the router scores, from `first_expert`, and a slice of
the vocabulary; what the absent experts would have added is left out.

Departures from the published code, forced by what is compared (and shared
with `afmoe.py`, whose helpers this module imports):
- Weights come from the Q40 `.m` file the server loaded, one layer at a time,
  the bytes sent flat and widened on the device.
- All sequences of a call are padded to one length; attention runs as a scan
  over query blocks of QB rows, each over all keys under its mask.
- Experts: the held experts one after the other, each over the token rows
  routed to it, gathered to a common capacity. An expert's three matrices are
  widened from their Q40 bytes inside that loop, so that a layer's 32 experts
  of 47M weights are 0.85 GB on the device and not 6 GB of float32.
- A layer is five programs (projections; attention and the output
  projection; the dense FFN, or router and shared expert, then the held
  experts), compiled ahead, in threads (`compile_programs`).

`FAULTS`: each makes this reference wrong in one stated way; `ladder.py
--power` shows what the comparison reads against it.
"""

from __future__ import annotations

import concurrent.futures
import functools
import math

import jax
import jax.numpy as jnp

from .afmoe import (
    GROUP, PAD, Fault, add_ffn, capacity_for, dense_ffn, held_rows, lossy, matrix,
    rope_half, widen)
from .dense_gqa import HI, head, rms_norm, swiglu
from .q40file import Q40File

QB = 256  # query rows a block: 128 heads x 256 x 13k keys of f32 scores are 1.7 GB

FAULTS = {
    "scale 1/sqrt(nope)": Fault(fault_scale_nope=True),
    "no rope on k_rope": Fault(fault_no_rope_kr=True),
    "rope on the nope columns": Fault(fault_rope_nope=True),
    "no kv_a_norm": Fault(fault_no_kv_a_norm=True),
    "no q_a_norm": Fault(fault_no_q_a_norm=True),
    "values from the key half of wkv_b": Fault(fault_values_from_keys=True),
    "routed_scaling_factor=1": Fault(routed_scaling_factor=1.0),
    "no shared expert": Fault(n_shared_experts=0),
    "absent experts computed": Fault(fault_wrap_absent=True),
    "no post-norms": Fault(fault_no_post_norms=True),
    # not a fault of the model code: the control that bounds `gap_tol` from
    # above, this reference with its activations one precision below the
    # program's bfloat16
    "activations in float8": Fault(fault_act_dtype="float8_e4m3fn"),
}


def attention(q, k, v, scale):
    """Causal attention of q [T, H, dk] over k [T, H, dk], v [T, H, dv], every
    head its own keys and values; T a multiple of QB."""
    t, n_heads, _ = q.shape
    qb = q.reshape(t // QB, QB, n_heads, q.shape[-1])
    kpos = jnp.arange(t)[None, :]

    def block(_, args):
        i, qi = args
        scores = jnp.einsum("bhd,thd->hbt", qi, k, precision=HI) * scale
        qpos = i * QB + jnp.arange(QB)[:, None]
        p = jax.nn.softmax(jnp.where(kpos <= qpos, scores, -jnp.inf), axis=-1)
        return None, jnp.einsum("hbt,thd->bhd", p, v, precision=HI)

    _, out = jax.lax.scan(block, None, (jnp.arange(t // QB), qb))
    return out.reshape(t, n_heads * v.shape[-1])


def latent_qkv(x, w, kw: dict):
    """The five projections' first four and both latent norms: (q, k, v) of
    every head, [T, H, nope + rope], the same, and [T, H, v]: the expanded
    form, keys and values rebuilt from the latent through `wkv_b`."""
    t, n_heads, eps = x.shape[0], kw["n_heads"], kw["eps"]
    nope, rope, vd, kvl = kw["nope"], kw["rope"], kw["v_dim"], kw["kv_rank"]
    y = lossy(rms_norm(x, w["att_norm"], eps), kw)
    cq = jnp.matmul(y, w["wq_a"].T, precision=HI)
    if kw["q_a_norm"]:
        cq = rms_norm(cq, w["q_a_norm"], eps)
    q = jnp.matmul(lossy(cq, kw), w["wq_b"].T, precision=HI).reshape(t, n_heads, nope + rope)
    ckv = jnp.matmul(y, w["wkv_a"].T, precision=HI)
    c, kr = ckv[:, :kvl], ckv[:, None, kvl:]  # the rope key is one head
    if kw["kv_a_norm"]:
        c = rms_norm(c, w["kv_a_norm"], eps)
    c = lossy(c, kw)
    kv = jnp.matmul(c, w["wkv_b"].T, precision=HI).reshape(t, n_heads, nope + vd)
    k_nope = kv[..., :nope]
    v = kv[..., :vd] if kw["values_from_keys"] else kv[..., nope:]
    positions = jnp.arange(t)
    q_nope, q_rope = q[..., :nope], rope_half(q[..., nope:], positions, kw["theta"])
    if kw["rope_kr"]:
        kr = rope_half(kr, positions, kw["theta"])
    if kw["rope_nope"]:
        q_nope = rope_half(q_nope, positions, kw["theta"])
        k_nope = rope_half(k_nope, positions, kw["theta"])
    q = lossy(jnp.concatenate([q_nope, q_rope], -1), kw)
    k = lossy(jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr, (t, n_heads, rope))], -1), kw)
    return q, k, lossy(v, kw)


def attend(x, q, k, v, w, kw: dict):
    """(x after the attention block, its pre-FFN norm) from a layer's q, k, v."""
    nope, rope, eps = kw["nope"], kw["rope"], kw["eps"]
    scale = 1.0 / math.sqrt(nope if kw["scale_nope"] else nope + rope)
    a = lossy(attention(q, k, v, scale), kw)
    o = jnp.matmul(a, w["wo"].T, precision=HI)
    if kw["post_norms"]:
        o = rms_norm(o, w["post_att_norm"], eps)
    x = lossy(x + o, kw)
    return x, lossy(rms_norm(x, w["ffn_norm"], eps), kw)


def route(y, gate, kw: dict):
    """(ids [T, k] among all routed experts, weights [T, k])."""
    scores = jax.nn.sigmoid(jnp.matmul(y, gate.T, precision=HI))
    w, ids = jax.lax.top_k(scores, kw["top_k"])
    if kw["route_norm"]:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return ids, w * kw["route_scale"]


def experts_raw(f: Q40File, layer: int, n_experts: int):
    """A layer's held experts as the file's Q40 bytes, uint8 [E, 3, each]
    (w1, w2, w3 of an expert lie one after the other, of equal size), in one
    transfer; and the three matrices' shapes (out, in)."""
    first = f.specs[f"layers.{layer}.experts.0.w1"]
    last = f.specs[f"layers.{layer}.experts.{n_experts - 1}.w3"]
    each = first.nbytes
    if (last.offset + last.nbytes - first.offset) != 3 * n_experts * each:
        raise ValueError("the experts' matrices are not one run of equal parts")
    raw = jnp.asarray(f._mm[first.offset : last.offset + last.nbytes])
    shapes = tuple(f.specs[f"layers.{layer}.experts.0.{n}"].shape for n in ("w1", "w2", "w3"))
    return raw.reshape(n_experts, 3, each), shapes


def experts_sum(y, local, weights, raw, shapes, capacity):
    """sum over the held experts e of weight x expert_e(y) on the token rows
    routed to e (`afmoe.experts_sum`), an expert's matrices widened from
    `raw` [E, 3, bytes] as its turn comes."""
    t = y.shape[0]

    def add(m, expert):
        e, bytes_e = expert
        a, b, c = (widen(bytes_e[j], shape) for j, shape in enumerate(shapes))
        mine = local == e
        (rows,) = jnp.nonzero(jnp.any(mine, axis=1), size=capacity, fill_value=t)
        w = jnp.sum(jnp.where(mine, weights, 0.0), axis=1)
        x = jnp.take(y, rows, axis=0, mode="fill", fill_value=0.0)
        part = swiglu(x, a, b, c) * jnp.take(w, rows, mode="fill", fill_value=0.0)[:, None]
        return m.at[rows].add(part, mode="drop"), None

    m, _ = jax.lax.scan(add, jnp.zeros_like(y), (jnp.arange(raw.shape[0]), raw))
    return m


def routed_experts(y, w, cfg: dict, n_rows=None):
    """The held experts' part of the routed sum, [T, D]."""
    kw = dict(statics(cfg))
    ids, wts = route(y, w["moe_gate"], kw)
    local, most = held_rows(ids, y.shape[0] if n_rows is None else n_rows, kw)
    return experts_sum(y, local, wts, w["experts"], kw["expert_shapes"],
                       capacity_for(int(most)))


# A layer is five small programs, compiled side by side (`compile_programs`):
# the chip's compiler takes seconds over every float32 product at
# `Precision.HIGHEST` (`afmoe.py`), and q, k and v leave the device's memory
# between the projections and the next layer.


@functools.partial(jax.jit, static_argnames=("static",))
def qkv_program(x, w, static):
    return latent_qkv(x, w, dict(static))


@functools.partial(jax.jit, static_argnames=("static",))
def attend_program(x, q, k, v, w, static):
    return attend(x, q, k, v, w, dict(static))


@functools.partial(jax.jit, static_argnames=("static",))
def dense_ffn_program(x, y, w, static):
    return add_ffn(x, dense_ffn(y, w["w1"], w["w2"], w["w3"]), w["post_ffn_norm"], dict(static))


@functools.partial(jax.jit, static_argnames=("static",))
def sparse_route(y, w, n_rows, static):
    """The router and the shared expert of a sparse layer: (held rows,
    weights, the shared expert's output, the most rows a held expert got)."""
    kw = dict(static)
    ids, wts = route(y, w["moe_gate"], kw)
    local, most = held_rows(ids, n_rows, kw)
    shared = (
        dense_ffn(y, w["shared_w1"], w["shared_w2"], w["shared_w3"])
        if kw["shared"] else jnp.zeros_like(y)
    )
    return local, wts, shared, most


@functools.partial(jax.jit, static_argnames=("capacity", "static"))
def sparse_back(x, y, local, wts, shared, experts, post_ffn_norm, capacity, static):
    kw = dict(static)
    m = shared + experts_sum(y, local, wts, experts, kw["expert_shapes"], capacity)
    return add_ffn(x, m, post_ffn_norm, kw)


QKV = ("att_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b")
ATTEND = ("wo", "post_att_norm", "ffn_norm")
DENSE = ("w1", "w2", "w3", "post_ffn_norm")
ROUTE = ("moe_gate", "shared_w1", "shared_w2", "shared_w3")


def pick(w: dict, names) -> dict:
    return {n: w[n] for n in names if n in w}


def layer_tensors(i: int, cfg: dict) -> tuple[dict, bool]:
    """({key in a layer's weights: the file's tensor}, whether the held
    experts' bytes come besides)."""
    names = {n: f"layers.{i}.{n}" for n in (*QKV, *ATTEND, "post_ffn_norm")}
    if i < cfg["first_k_dense_replace"]:
        names.update({n: f"layers.{i}.{n}" for n in ("w1", "w2", "w3")})
        return names, False
    names["moe_gate"] = f"layers.{i}.moe_gate"
    if cfg["n_shared_experts"]:
        names.update({"shared_" + n: f"layers.{i}.shared.{n}" for n in ("w1", "w2", "w3")})
    return names, True


def layer_weights(f: Q40File, i: int, cfg: dict) -> dict:
    names, sparse = layer_tensors(i, cfg)
    w = {key: matrix(f, name) for key, name in names.items()}
    if sparse:
        w["experts"], _ = experts_raw(f, i, cfg["n_routed_experts"])
    return w


def layer_shapes(f: Q40File, i: int, cfg: dict) -> dict:
    """`layer_weights` as shapes, to compile against."""
    names, sparse = layer_tensors(i, cfg)
    w = {key: jax.ShapeDtypeStruct(f.specs[name].shape, jnp.float32)
         for key, name in names.items()}
    if sparse:
        each = f.specs[f"layers.{i}.experts.0.w1"].nbytes
        w["experts"] = jax.ShapeDtypeStruct((cfg["n_routed_experts"], 3, each), jnp.uint8)
    return w


def statics(cfg: dict) -> tuple:
    """What of the configuration (and of a fault laid over it) is static in
    the layers' programs, hashable for `jit`."""
    d, width = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return tuple({
        "n_heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"],
        "v_dim": cfg["v_head_dim"],
        "kv_rank": cfg["kv_lora_rank"],
        "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
        "scale_nope": bool(cfg.get("fault_scale_nope")),
        "rope_kr": not cfg.get("fault_no_rope_kr"),
        "rope_nope": bool(cfg.get("fault_rope_nope")),
        "kv_a_norm": not cfg.get("fault_no_kv_a_norm"),
        "q_a_norm": not cfg.get("fault_no_q_a_norm"),
        "values_from_keys": bool(cfg.get("fault_values_from_keys")),
        "post_norms": not cfg.get("fault_no_post_norms"),
        "top_k": cfg["num_experts_per_tok"],
        "route_norm": bool(cfg["norm_topk_prob"]),
        "route_scale": float(cfg["routed_scaling_factor"]),
        "n_held": cfg["n_routed_experts"],
        "first": cfg.get("first_expert", 0),
        "wrap_absent": bool(cfg.get("fault_wrap_absent")),
        "shared": bool(cfg["n_shared_experts"]),
        "expert_shapes": ((width, d), (d, width), (width, d)),
        "act": cfg.get("fault_act_dtype"),
    }.items())


def compile_programs(f: Q40File, cfg: dict, t_pad: int, n_head: int) -> dict:
    """The run's programs, lowered against their shapes and compiled side by
    side in threads (`afmoe.compile_programs` says why): futures of callables
    that take a program's traced arguments."""
    static, n_dense = statics(cfg), cfg["first_k_dense_replace"]
    kw = dict(static)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    x, i32 = f32((t_pad, cfg["hidden_size"])), jax.ShapeDtypeStruct((), jnp.int32)
    heads, k = kw["n_heads"], cfg["num_experts_per_tok"]
    qk = f32((t_pad, heads, kw["nope"] + kw["rope"]))
    w = layer_shapes(f, 0, cfg)
    jobs = {
        "head": lambda: head.lower(
            f32((n_head, cfg["hidden_size"])), f32(f.specs["final_norm"].shape),
            f32(f.specs["wcls"].shape), eps=float(cfg["rms_norm_eps"])).compile(),
        "qkv": lambda: qkv_program.lower(x, pick(w, QKV), static=static).compile(),
        "attend": lambda: attend_program.lower(
            x, qk, qk, f32((t_pad, heads, kw["v_dim"])), pick(w, ATTEND),
            static=static).compile(),
    }
    if n_dense:
        jobs["dense"] = lambda: dense_ffn_program.lower(
            x, x, pick(w, DENSE), static=static).compile()
    if n_dense < cfg["num_hidden_layers"]:
        ws = layer_shapes(f, n_dense, cfg)
        jobs["route"] = lambda: sparse_route.lower(
            x, pick(ws, ROUTE), i32, static=static).compile()
        jobs["back"] = lambda: sparse_back.lower(
            x, x, jax.ShapeDtypeStruct((t_pad, k), jnp.int32), f32((t_pad, k)), x,
            ws["experts"], ws["post_ffn_norm"], capacity=GROUP, static=static).compile()
    pool = concurrent.futures.ThreadPoolExecutor(len(jobs))
    futures = {name: pool.submit(job) for name, job in jobs.items()}
    pool.shutdown(wait=False)
    return futures


def layer(x, n_rows: int, w, cfg: dict, i: int, programs: dict):
    """Layer i over one padded sequence of `n_rows` tokens."""
    q, k, v = programs["qkv"].result()(x, pick(w, QKV))
    x, y = programs["attend"].result()(x, q, k, v, pick(w, ATTEND))
    del q, k, v
    if i < cfg["first_k_dense_replace"]:
        return programs["dense"].result()(x, y, pick(w, DENSE))
    local, wts, shared, most = programs["route"].result()(
        y, pick(w, ROUTE), jnp.int32(n_rows))
    capacity = capacity_for(int(most))
    if capacity == GROUP:
        return programs["back"].result()(
            x, y, local, wts, shared, w["experts"], w["post_ffn_norm"])
    return sparse_back(x, y, local, wts, shared, w["experts"], w["post_ffn_norm"],
                       capacity, statics(cfg))


def last_logits(path: str, cfg: dict, seqs, keep):
    """Logits [keep[i], vocab] at the last keep[i] positions of each
    sequence of token ids, every sequence run whole from position 0."""
    if not seqs:
        return []
    f = Q40File(path)
    t_pad = -(-max(len(ids) for ids in seqs) // PAD) * PAD
    n_head = min(t_pad, max(keep))  # one head program: the most rows any asks for
    programs = compile_programs(f, cfg, t_pad, n_head)
    xs = []
    for ids in seqs:
        x = lossy(f.rows_f32("embed", ids), {"act": cfg.get("fault_act_dtype")})
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
