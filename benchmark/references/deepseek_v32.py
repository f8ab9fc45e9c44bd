"""Plain reference: the `deepseek_v32` decoder (DeepSeek-V3.2), as one of the
chips that share its layers: latent attention in the EXPANDED form (keys and
values of every head rebuilt from the latent through `wkv_b`, nothing
absorbed), restricted to the rows a learned index picks, and a router that
takes its experts from `topk_group` of `n_group` groups.

RMSNorm with the configuration's eps, one before each block and none after
(no sandwich); `x0 = embed[token]`. Layer l, position t, h = norm_in(x_t):

    cq = rmsnorm_qa(h Wqa);  q_i = cq Wqb_i = [q_nope | q_rope];  q_rope = rope_t(q_rope)
    [ckv | kr] = h Wkva;  c_t = rmsnorm_kva(ckv);  kr_t = rope_t(kr), one head shared by all
    [k_nope_i | v_i](s) = c_s Wkvb_i
    index: qI_j = cq Wiq_j (j over `index_n_heads`);  kI_s = LayerNorm(h_s Wik) (weight, bias);
        rope on the first `qk_rope_head_dim` columns of both, rotate-half pairing;
        w_j = (h_t Wiw)_j / sqrt(heads) / sqrt(index_head_dim)
        I(t, s) = sum_j w_j ReLU(qI_j . kI_s);  S_t = the min(index_topk, t + 1) rows s <= t
        of largest I(t, s), ties to the lower row
    a_i = sum over s in S_t of softmax_{S_t}((q_nope_i . k_nope_i(s) + q_rope_i . kr_s) a) v_i(s)
        a = (nope + rope)^-1/2 m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
    x = x + concat_i(a_i) Wo;  g = norm_pre_mlp(x)
    l <  first_k_dense_replace: x = x + SwiGLU(g), `intermediate_size` wide
    l >= first_k_dense_replace: s = sigmoid(g Wr) in f32;  s' = s + bias;
        G_k = the two largest s' of group k summed; the `topk_group` groups of largest G_k
        stay, the others' s' become 0; I = the `num_experts_per_tok` largest of what is left;
        w_e = s_e / (sum_I s + 1e-20) x routed_scaling_factor
        x = x + shared(g) + sum_{e in I, held} w_e expert_e(g)

then the final RMSNorm and the untied head. Both ropes turn by one table of
`qk_rope_head_dim / 2` frequencies f_d = theta^(-2d/dim) scaled by band
(`rope_scaling.type: yarn`): f'_d = (1 - r_d) f_d + r_d f_d / factor, r_d the
ramp between the pairs that turn `beta_fast` and `beta_slow` times over the
original length; the latent rope pairs (2d, 2d + 1). The `nextn` module is no
term of these logits and is not in the file. The index's queries and keys
stay in float32 here: the published inference code holds them in FP8 after a
Hadamard rotation, which is orthogonal and leaves every qI . kI as it is (the
configuration's `assumed`). float32, every product at `Precision.HIGHEST`,
no cache, no kernels, nothing imported from `dllama_tpu.models` or
`dllama_tpu.ops`.

The share is `afmoe.py`'s: the file holds `n_routed_experts` of the
`num_routed_experts` the router scores, from `first_expert`, and a slice of
the vocabulary; what the absent experts would have added is left out.

Departures forced by what is compared (shared with `pangu_ultra_moe.py`,
whose expert helpers this module imports): weights come from the Q40 `.m`
file the server loaded, a layer at a time; all sequences of a call are
padded to one length; the index and the attention run as scans over query
blocks of QB rows, each over all keys, the attention under the block's part
of the selection's mask (dense scores, the complement of S_t masked); the
held experts one after the other over the rows routed to each; a layer is a
few programs compiled ahead, in threads.

`FAULTS`: each makes this reference wrong in one stated way; `ladder.py
--power` shows what the comparison reads against it. A positive factor on
every w_j leaves S_t as it is and is not listed.
"""

from __future__ import annotations

import concurrent.futures
import functools
import math

import jax
import jax.numpy as jnp

from .afmoe import GROUP, PAD, Fault, capacity_for, dense_ffn, held_rows, lossy, matrix
from .dense_gqa import HI, head, rms_norm
from .pangu_ultra_moe import experts_raw, experts_sum, pick
from .q40file import Q40File

QB = 256  # query rows a block: 128 heads x 256 x 7.7k keys of f32 scores are 1 GB

FAULTS = {
    "selection ignored (dense attention)": Fault(min_prompt=2048 + 256, fault_dense=True),
    "index_topk 1024": Fault(min_prompt=1024 + 256, index_topk=1024),
    "no rope on the index": Fault(min_prompt=2048 + 256, fault_index_no_rope=True),
    "no LayerNorm on the index key": Fault(min_prompt=2048 + 256, fault_index_no_k_norm=True),
    "no ReLU in the index": Fault(min_prompt=2048 + 256, fault_index_no_relu=True),
    "index queries from the un-normalised latent": Fault(
        min_prompt=2048 + 256, fault_index_raw_latent=True),
    "group limit ignored": Fault(n_group=1, topk_group=1),
    "bias left out of the selection": Fault(fault_no_expert_bias=True),
    "m^2 left out of the softmax scale": Fault(fault_no_mscale=True),
    "rotary table unscaled": Fault(fault_rope_unscaled=True),
    "no shared expert": Fault(n_shared_experts=0),
    "absent experts computed": Fault(fault_wrap_absent=True),
    "routed_scaling_factor=1": Fault(routed_scaling_factor=1.0),
    # not a fault of the model code: the control that bounds `gap_tol` from
    # above, this reference with its activations one precision below the
    # program's bfloat16
    "activations in float8": Fault(fault_act_dtype="float8_e4m3fn"),
}


def mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def rope_frequencies(kw: dict):
    """The `rope / 2` frequencies of both ropes, scaled by band."""
    dim, theta, factor, orig = kw["rope"], kw["theta"], kw["rope_factor"], kw["rope_orig"]
    d = jnp.arange(dim // 2, dtype=jnp.float32)
    freq = theta ** (-2.0 * d / dim)
    if factor == 1.0:
        return freq

    def band(rotations: float) -> float:
        return dim * math.log(orig / (rotations * 2.0 * math.pi)) / (2.0 * math.log(theta))

    low = max(math.floor(band(kw["beta_fast"])), 0)
    high = min(math.ceil(band(kw["beta_slow"])), dim // 2 - 1)
    ramp = jnp.clip((d - low) / max(high - low, 0.001), 0.0, 1.0)
    return (1.0 - ramp) * freq + ramp * freq / factor


def rope(x, kw: dict, pairing: str):
    """x [T, heads, rope] at positions 0..T-1, turned by the scaled table
    times its own magnitude factor; pairs (2d, 2d + 1) or (d, d + rope/2)."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * rope_frequencies(kw)[None, :]
    cos, sin = (f(ang)[:, None, :] * kw["table_mscale"] for f in (jnp.cos, jnp.sin))
    if pairing == "interleaved":
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def layer_norm(x, weight, bias, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight + bias


def selection(y, cq_raw, cq, w, kw: dict):
    """bool [T, T]: query t attends to row s. The index scores every row,
    a block of QB queries at a time, and `lax.top_k` (ties to the lower row)
    takes the `index_topk` best of those a query sees."""
    t = y.shape[0]
    n_idx, di, rd, topk = kw["index_heads"], kw["index_dim"], kw["rope"], kw["index_topk"]
    pos = jnp.arange(t)
    causal = pos[None, :] <= pos[:, None]
    if kw["dense"] or topk >= t:
        return causal

    def turned(z):  # [T, heads, dI]: rope on the first columns, rotate-half
        if kw["index_no_rope"]:
            return z
        return jnp.concatenate([rope(z[..., :rd], kw, "half"), z[..., rd:]], -1)

    # `fault_index_act_dtype` rounds the index's activations alone: what the
    # selection's flips cost, apart from every other rounding (PERF.md)
    kw = {**kw, "act": kw["index_act"] or kw["act"]}
    src, y = cq_raw if kw["index_raw_latent"] else cq, lossy(y, kw)
    qi = jnp.matmul(lossy(src, kw), w["idx_wq_b"].T, precision=HI).reshape(t, n_idx, di)
    ki = jnp.matmul(y, w["idx_wk"].T, precision=HI)
    if not kw["index_no_k_norm"]:
        ki = layer_norm(ki, w["idx_k_norm"], w["idx_k_bias"], kw["eps"])
    qi, ki = lossy(turned(qi), kw), lossy(turned(ki[:, None, :])[:, 0], kw)
    wj = jnp.matmul(y, w["idx_w"].T, precision=HI) * (n_idx ** -0.5 * di ** -0.5)

    def block(_, args):
        i, q, ww = args
        dots = jnp.einsum("bjd,sd->bjs", q, ki, precision=HI)
        if not kw["index_no_relu"]:
            dots = jnp.maximum(dots, 0.0)
        scores = jnp.einsum("bjs,bj->bs", dots, ww, precision=HI)
        qpos = i * QB + jnp.arange(QB)[:, None]
        seen = pos[None, :] <= qpos
        _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), topk)
        taken = jnp.zeros((QB, t), bool).at[jnp.arange(QB)[:, None], idx].set(True)
        return None, jnp.logical_and(taken, seen)

    _, keep = jax.lax.scan(block, None, (
        jnp.arange(t // QB), qi.reshape(t // QB, QB, n_idx, di), wj.reshape(t // QB, QB, n_idx)))
    return keep.reshape(t, t)


def attention(q, k, v, keep, scale):
    """Attention of q [T, H, dk] over k [T, H, dk], v [T, H, dv], every head
    its own keys and values, each query over the rows `keep` [T, T] names
    (dense scores, the rest masked); T a multiple of QB."""
    t, n_heads, _ = q.shape
    qb = q.reshape(t // QB, QB, n_heads, q.shape[-1])

    def block(_, args):
        qi, mask = args
        scores = jnp.einsum("bhd,thd->hbt", qi, k, precision=HI) * scale
        p = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return None, jnp.einsum("hbt,thd->bhd", p, v, precision=HI)

    _, out = jax.lax.scan(block, None, (qb, keep.reshape(t // QB, QB, t)))
    return out.reshape(t, n_heads * v.shape[-1])


def latent_qkv(x, w, kw: dict):
    """The projections, both latent norms and the index: (q, k, v) of every
    head in the expanded form, [T, H, nope + rope], the same, and [T, H, v],
    and the selection's mask [T, T]."""
    t, n_heads, eps = x.shape[0], kw["n_heads"], kw["eps"]
    nope, rd, vd, kvl = kw["nope"], kw["rope"], kw["v_dim"], kw["kv_rank"]
    y = lossy(rms_norm(x, w["att_norm"], eps), kw)
    cq_raw = jnp.matmul(y, w["wq_a"].T, precision=HI)
    cq = rms_norm(cq_raw, w["q_a_norm"], eps)
    q = jnp.matmul(lossy(cq, kw), w["wq_b"].T, precision=HI).reshape(t, n_heads, nope + rd)
    ckv = jnp.matmul(y, w["wkv_a"].T, precision=HI)
    c, kr = lossy(rms_norm(ckv[:, :kvl], w["kv_a_norm"], eps), kw), ckv[:, None, kvl:]
    kv = jnp.matmul(c, w["wkv_b"].T, precision=HI).reshape(t, n_heads, nope + vd)
    q = lossy(jnp.concatenate([q[..., :nope], rope(q[..., nope:], kw, "interleaved")], -1), kw)
    kr = jnp.broadcast_to(rope(kr, kw, "interleaved"), (t, n_heads, rd))
    k = lossy(jnp.concatenate([kv[..., :nope], kr], -1), kw)
    return q, k, lossy(kv[..., nope:], kw), selection(y, cq_raw, cq, w, kw)


def attend(x, q, k, v, keep, w, kw: dict):
    """(x after the attention block, its pre-FFN norm)."""
    scale = (kw["nope"] + kw["rope"]) ** -0.5 * kw["softmax_mscale"] ** 2
    a = lossy(attention(q, k, v, keep, scale), kw)
    x = lossy(x + jnp.matmul(a, w["wo"].T, precision=HI), kw)
    return x, lossy(rms_norm(x, w["ffn_norm"], kw["eps"]), kw)


def route(y, gate, bias, kw: dict):
    """(ids [T, k] among all routed experts, weights [T, k])."""
    scores = jax.nn.sigmoid(jnp.matmul(y, gate.T, precision=HI))
    chosen_by = scores if kw["no_bias"] else scores + bias
    n_group = kw["n_group"]
    if n_group > 1:
        grouped = chosen_by.reshape(-1, n_group, chosen_by.shape[1] // n_group)
        best = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # [T, groups]
        _, stay = jax.lax.top_k(best, kw["topk_group"])
        mask = jnp.zeros_like(best, bool).at[jnp.arange(best.shape[0])[:, None], stay].set(True)
        chosen_by = jnp.where(mask[:, :, None], grouped, 0.0).reshape(chosen_by.shape)
    _, ids = jax.lax.top_k(chosen_by, kw["top_k"])
    w = jnp.take_along_axis(scores, ids, axis=1)
    if kw["route_norm"]:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    return ids, w * kw["route_scale"]


def routed_experts(y, w, cfg: dict, n_rows=None):
    """The held experts' part of the routed sum, [T, D]."""
    kw = dict(statics(cfg))
    ids, wts = route(y, w["moe_gate"], w["expert_bias"], kw)
    local, most = held_rows(ids, y.shape[0] if n_rows is None else n_rows, kw)
    return experts_sum(y, local, wts, w["experts"], kw["expert_shapes"],
                       capacity_for(int(most)))


@functools.partial(jax.jit, static_argnames=("static",))
def qkv_program(x, w, static):
    return latent_qkv(x, w, dict(static))


@functools.partial(jax.jit, static_argnames=("static",))
def attend_program(x, q, k, v, keep, w, static):
    return attend(x, q, k, v, keep, w, dict(static))


@functools.partial(jax.jit, static_argnames=("static",))
def dense_ffn_program(x, y, w, static):
    return lossy(x + dense_ffn(y, w["w1"], w["w2"], w["w3"]), dict(static))


@functools.partial(jax.jit, static_argnames=("static",))
def sparse_route(y, w, n_rows, static):
    """The router and the shared expert of a sparse layer: (held rows,
    weights, the shared expert's output, the most rows a held expert got)."""
    kw = dict(static)
    ids, wts = route(y, w["moe_gate"], w["expert_bias"], kw)
    local, most = held_rows(ids, n_rows, kw)
    shared = (
        dense_ffn(y, w["shared_w1"], w["shared_w2"], w["shared_w3"])
        if kw["shared"] else jnp.zeros_like(y)
    )
    return local, wts, shared, most


@functools.partial(jax.jit, static_argnames=("capacity", "static"))
def sparse_back(x, y, local, wts, shared, experts, capacity, static):
    kw = dict(static)
    return lossy(
        x + shared + experts_sum(y, local, wts, experts, kw["expert_shapes"], capacity), kw)


QKV = ("att_norm", "wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b",
       "idx_wq_b", "idx_wk", "idx_k_norm", "idx_k_bias", "idx_w")
ATTEND = ("wo", "ffn_norm")
DENSE = ("w1", "w2", "w3")
ROUTE = ("moe_gate", "expert_bias", "shared_w1", "shared_w2", "shared_w3")


def layer_tensors(i: int, cfg: dict) -> tuple[dict, bool]:
    """({key in a layer's weights: the file's tensor}, whether the held
    experts' bytes come besides)."""
    names = {n: f"layers.{i}.{n}" for n in (*QKV, *ATTEND)}
    if i < cfg["first_k_dense_replace"]:
        names.update({n: f"layers.{i}.{n}" for n in DENSE})
        return names, False
    names.update({n: f"layers.{i}.{n}" for n in ("moe_gate", "expert_bias")})
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
    scaling = cfg["rope_scaling"]
    factor = 1.0 if cfg.get("fault_rope_unscaled") else float(scaling["factor"])
    return tuple({
        "n_heads": cfg["num_attention_heads"],
        "nope": cfg["qk_nope_head_dim"],
        "rope": cfg["qk_rope_head_dim"],
        "v_dim": cfg["v_head_dim"],
        "kv_rank": cfg["kv_lora_rank"],
        "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
        "rope_factor": factor,
        "rope_orig": float(scaling["original_max_position_embeddings"]),
        "beta_fast": float(scaling["beta_fast"]),
        "beta_slow": float(scaling["beta_slow"]),
        "table_mscale": mscale(factor, scaling["mscale"]) / mscale(
            factor, scaling["mscale_all_dim"]),
        "softmax_mscale": 1.0 if cfg.get("fault_no_mscale") else mscale(
            float(scaling["factor"]), scaling["mscale_all_dim"]),
        "index_heads": cfg["index_n_heads"],
        "index_dim": cfg["index_head_dim"],
        "index_topk": cfg["index_topk"],
        "dense": bool(cfg.get("fault_dense")),
        "index_no_rope": bool(cfg.get("fault_index_no_rope")),
        "index_no_k_norm": bool(cfg.get("fault_index_no_k_norm")),
        "index_no_relu": bool(cfg.get("fault_index_no_relu")),
        "index_raw_latent": bool(cfg.get("fault_index_raw_latent")),
        "top_k": cfg["num_experts_per_tok"],
        "n_group": cfg["n_group"],
        "topk_group": cfg["topk_group"],
        "no_bias": bool(cfg.get("fault_no_expert_bias")),
        "route_norm": bool(cfg["norm_topk_prob"]),
        "route_scale": float(cfg["routed_scaling_factor"]),
        "n_held": cfg["n_routed_experts"],
        "first": cfg.get("first_expert", 0),
        "wrap_absent": bool(cfg.get("fault_wrap_absent")),
        "shared": bool(cfg["n_shared_experts"]),
        "expert_shapes": ((width, d), (d, width), (width, d)),
        "act": cfg.get("fault_act_dtype"),
        "index_act": cfg.get("fault_index_act_dtype"),
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
            x, qk, qk, f32((t_pad, heads, kw["v_dim"])),
            jax.ShapeDtypeStruct((t_pad, t_pad), jnp.bool_), pick(w, ATTEND),
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
            ws["experts"], capacity=GROUP, static=static).compile()
    pool = concurrent.futures.ThreadPoolExecutor(len(jobs))
    futures = {name: pool.submit(job) for name, job in jobs.items()}
    pool.shutdown(wait=False)
    return futures


def layer(x, n_rows: int, w, cfg: dict, i: int, programs: dict):
    """Layer i over one padded sequence of `n_rows` tokens."""
    q, k, v, keep = programs["qkv"].result()(x, pick(w, QKV))
    x, y = programs["attend"].result()(x, q, k, v, keep, pick(w, ATTEND))
    del q, k, v, keep
    if i < cfg["first_k_dense_replace"]:
        return programs["dense"].result()(x, y, pick(w, DENSE))
    local, wts, shared, most = programs["route"].result()(
        y, pick(w, ROUTE), jnp.int32(n_rows))
    capacity = capacity_for(int(most))
    if capacity == GROUP:
        return programs["back"].result()(x, y, local, wts, shared, w["experts"])
    return sparse_back(x, y, local, wts, shared, w["experts"], capacity, statics(cfg))


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
