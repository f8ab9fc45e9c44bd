"""Plain reference: the `lfm2_moe` decoder (LFM2-24B-A2B), as one of the chips
that share its layers.

Follows the published configuration and, where that is silent, the public
`transformers` implementation `modeling_lfm2_moe.py` as remembered (the
configuration's `assumed` names each point). RMSNorm with the configuration's
eps throughout. Layer l, `u = rmsnorm_op(x)`:

    layer_types[l] == "conv":
        [B | C | g] = u W_in  (three thirds of 3 x hidden, in this order)
        z = B * g
        c_t = w[:, 0] z_{t-2} + w[:, 1] z_{t-1} + w[:, 2] z_t
            (depthwise, causal, zeros before position 0: `Conv1d` with
            groups = hidden, padding = L - 1, cut to the sequence; L =
            `conv_L_cache` taps, the last meets the current row)
        x = x + (C * c) W_out                    no activation, no bias
    layer_types[l] == "full_attention":
        q, k, v = u Wq, u Wk, u Wv;  q, k = rmsnorm_q(q), rmsnorm_k(k) per head
        q, k = rope(q, k), rotate-half on whole heads
        x = x + softmax(q k^T / sqrt(hd) + causal) v Wo
    h = rmsnorm_ffn(x)
    l <  num_dense_layers: m = SwiGLU(h), `intermediate_size` wide
    l >= num_dense_layers: s = sigmoid(h Wr);  I = top-k(s + expert_bias)
        w_e = s_e (e in I);  w = w / (sum w + 1e-6) (`norm_topk_prob`);
        w = routed_scaling_factor * w;  m = sum_{e in I, held} w_e expert_e(h)
    x = x + m

then the final RMSNorm and the head. float32, every product at
`Precision.HIGHEST`, the whole sequence from position 0: no cache, no state,
no kernels, nothing imported from `dllama_tpu.models` or `dllama_tpu.ops`.

The share: the file holds `num_experts` of the `num_routed_experts` the
router scores, from `first_expert`, and a slice of the vocabulary. What the
absent experts would have added is left out here as in the program, and that
partial sum goes on to the next layer; the operators, the norms and the dense
layers are whole.

Departures forced by what is compared are `afmoe.py`'s, whose pieces this
file uses: weights from the Q40 `.m` file the server loaded, widened on the
device; sequences padded to one length, attention over query blocks; the
held experts one after the other over the token rows routed to each;
programs compiled ahead, side by side.

`FAULTS`: each makes this reference wrong in one stated way; `ladder.py
--power` shows what the comparison reads against it. "zero state at a chunk
boundary" is what a program would compute that carried no state from one
512-row chunk into the next; it shows only past 512 positions.
"""

from __future__ import annotations

import concurrent.futures
import functools

import jax
import jax.numpy as jnp

from .afmoe import (
    GROUP, PAD, Fault, attention, capacity_for, experts, experts_sum, held_rows, lossy,
    matrix, rope_half)
from .dense_gqa import HI, head, rms_norm, swiglu
from .q40file import Q40File

CHUNK = 512  # the program's largest prefill bucket: where a lost state would show

FAULTS = {
    "zero state at a chunk boundary": Fault(min_prompt=CHUNK + 64, fault_zero_state_every=CHUNK),
    "taps reversed": Fault(fault_taps_reversed=True),
    "B left out": Fault(fault_no_b=True),
    "C left out": Fault(fault_no_c=True),
    "no q/k norm": Fault(fault_no_qk_norm=True),
    "rope_theta=1e4": Fault(rope_theta=1e4),
    "selection without the bias": Fault(fault_no_bias=True),
    "no renormalisation": Fault(norm_topk_prob=False),
    # a sparse layer run as a dense one: every token through the first held
    # expert with weight 1, no router
    "a sparse layer as a dense one": Fault(fault_sparse_as_dense=True),
    # not a fault of the model code: the control that bounds `gap_tol` from
    # above, this reference with its activations one precision below the
    # program's bfloat16
    "activations in float8": Fault(fault_act_dtype="float8_e4m3fn"),
}


def short_conv(u, w, kw: dict):
    """The gated short convolution over a whole sequence `u` [T, D]."""
    t, d = u.shape
    bcx = jnp.matmul(u, w["conv_in"].T, precision=HI)
    b, c, g = bcx[:, :d], bcx[:, d : 2 * d], bcx[:, 2 * d :]
    z = lossy(g if kw["no_b"] else b * g, kw)
    taps = w["conv_w"][:, ::-1] if kw["taps_reversed"] else w["conv_w"]  # [D, L]
    n_taps = taps.shape[1]
    zp = jnp.pad(z, ((n_taps - 1, 0), (0, 0)))
    at = jnp.arange(t)
    acc = jnp.zeros_like(z)
    for j in range(n_taps):
        back = n_taps - 1 - j  # the tap meets the row `back` positions before
        term = taps[:, j][None, :] * zp[j : j + t]
        if kw["zero_state_every"] and back:
            # a chunk that starts from a zero state sees nothing before its row 0
            term = jnp.where((at % kw["zero_state_every"] >= back)[:, None], term, 0.0)
        acc = acc + term
    y = lossy(acc if kw["no_c"] else c * acc, kw)
    return jnp.matmul(y, w["conv_out"].T, precision=HI)


def full_attention(u, w, kw: dict):
    t, n_heads, n_kv, hd, eps = u.shape[0], kw["n_heads"], kw["n_kv_heads"], kw["head_dim"], kw["eps"]
    q = jnp.matmul(u, w["q"].T, precision=HI).reshape(t, n_heads, hd)
    k = jnp.matmul(u, w["k"].T, precision=HI).reshape(t, n_kv, hd)
    v = lossy(jnp.matmul(u, w["v"].T, precision=HI).reshape(t, n_kv, hd), kw)
    if not kw["no_qk_norm"]:
        q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
    positions = jnp.arange(t)
    q = lossy(rope_half(q, positions, kw["theta"]), kw)
    k = lossy(rope_half(k, positions, kw["theta"]), kw)
    a = lossy(attention(q, k, v, jnp.int32(1 << 30)), kw)
    return jnp.matmul(a, w["wo"].T, precision=HI)


def operator_block(x, w, conv: bool, kw: dict):
    """(x after the layer's operator, its pre-FFN norm)."""
    u = lossy(rms_norm(x, w["att_norm"], kw["eps"]), kw)
    x = lossy(x + (short_conv(u, w, kw) if conv else full_attention(u, w, kw)), kw)
    return x, lossy(rms_norm(x, w["ffn_norm"], kw["eps"]), kw)


def route(y, gate, bias, kw: dict):
    """(ids [T, k] among all routed experts, weights [T, k])."""
    scores = jax.nn.sigmoid(jnp.matmul(y, gate.T, precision=HI))
    _, ids = jax.lax.top_k(scores if kw["no_bias"] else scores + bias, kw["top_k"])
    w = jnp.take_along_axis(scores, ids, axis=1)
    if kw["route_norm"]:
        w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-6)
    return ids, w * kw["route_scale"]


@functools.partial(jax.jit, static_argnames=("conv", "static"))
def dense_layer(x, w, conv, static):
    kw = dict(static)
    x, y = operator_block(x, w, conv, kw)
    return lossy(x + swiglu(y, w["w1"], w["w2"], w["w3"]), kw)


@functools.partial(jax.jit, static_argnames=("conv", "static"))
def sparse_front(x, w, n_rows, conv, static):
    """The operator and the router of a sparse layer: (x, pre-FFN norm, held
    rows, weights, the most rows a held expert got)."""
    kw = dict(static)
    x, y = operator_block(x, w, conv, kw)
    ids, wts = route(y, w["moe_gate"], w["expert_bias"], kw)
    if kw["sparse_as_dense"]:
        ids = jnp.full_like(ids[:, :1], kw["first"])
        wts = jnp.ones_like(wts[:, :1])
    local, most = held_rows(ids, n_rows, kw)
    return x, y, local, wts, most


@functools.partial(jax.jit, static_argnames=("capacity", "static"))
def sparse_back(x, y, local, wts, w, capacity, static):
    m = experts_sum(y, local, wts, w["w1"], w["w2"], w["w3"], capacity)
    return lossy(x + m, dict(static))


def routed_experts(y, w, cfg: dict, n_rows=None):
    """The held experts' part of the routed sum, [T, D]."""
    kw = dict(statics(cfg))
    ids, wts = route(y, w["moe_gate"], w["expert_bias"], kw)
    local, most = held_rows(ids, y.shape[0] if n_rows is None else n_rows, kw)
    return experts_sum(y, local, wts, w["w1"], w["w2"], w["w3"], capacity_for(int(most)))


CONV = ("conv_in", "conv_w", "conv_out", "att_norm", "ffn_norm")
ATTN = ("q", "k", "v", "wo", "q_norm", "k_norm", "att_norm", "ffn_norm")
HEAVY = ("w1", "w2", "w3")  # what `sparse_back` reads


def is_conv(cfg: dict, i: int) -> bool:
    return cfg["layer_types"][i] == "conv"


def layer_tensors(i: int, cfg: dict) -> tuple[dict, bool]:
    """({key in a layer's weights: the file's tensor}, whether the held
    experts' w1, w2, w3 come besides)."""
    names = {n: f"layers.{i}.{n}" for n in (CONV if is_conv(cfg, i) else ATTN)}
    if i < cfg["num_dense_layers"]:
        names.update({n: f"layers.{i}.{n}" for n in HEAVY})
        return names, False
    names.update({n: f"layers.{i}.{n}" for n in ("moe_gate", "expert_bias")})
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
    for n in HEAVY if sparse else ():
        one = f.specs[f"layers.{i}.experts.0.{n}"].shape
        w[n] = jax.ShapeDtypeStruct((cfg["num_experts"], *one), jnp.float32)
    return w


def statics(cfg: dict) -> tuple:
    """What of the configuration (and of a fault laid over it) is static in
    the layers' programs, hashable for `jit`."""
    return tuple({
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg.get("head_dim") or cfg["assumed"]["head_dim"],
        "eps": float(cfg.get("norm_eps", cfg.get("rms_norm_eps"))),
        "theta": float(cfg["rope_theta"]),
        "top_k": cfg["num_experts_per_tok"],
        "route_norm": bool(cfg["norm_topk_prob"]),
        "route_scale": float(cfg["routed_scaling_factor"]),
        "n_held": cfg["num_experts"],
        "first": cfg.get("first_expert", 0),
        "wrap_absent": False,
        "no_bias": bool(cfg.get("fault_no_bias")),
        "no_b": bool(cfg.get("fault_no_b")),
        "no_c": bool(cfg.get("fault_no_c")),
        "no_qk_norm": bool(cfg.get("fault_no_qk_norm")),
        "taps_reversed": bool(cfg.get("fault_taps_reversed")),
        "zero_state_every": int(cfg.get("fault_zero_state_every", 0)),
        "sparse_as_dense": bool(cfg.get("fault_sparse_as_dense")),
        "act": cfg.get("fault_act_dtype"),
    }.items())


def program_key(cfg: dict, i: int) -> tuple[str, bool]:
    return ("dense" if i < cfg["num_dense_layers"] else "front", is_conv(cfg, i))


def compile_programs(f: Q40File, cfg: dict, t_pad: int, n_head: int) -> dict:
    """The run's programs, lowered against their shapes and compiled side by
    side in threads (float32 products at `Precision.HIGHEST` take the chip's
    compiler seconds each). Futures of callables that take a program's traced
    arguments: a dense and a sparse layer of each operator the configuration
    has, the experts' half of a sparse layer, the head."""
    static = statics(cfg)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    x, i32 = f32((t_pad, cfg["hidden_size"])), jax.ShapeDtypeStruct((), jnp.int32)
    k = cfg["num_experts_per_tok"]
    eps = dict(static)["eps"]
    jobs = {"head": lambda: head.lower(
        f32((n_head, cfg["hidden_size"])), f32(f.specs["final_norm"].shape),
        f32(f.specs["wcls"].shape), eps=eps).compile()}
    first_of: dict = {}
    for i in range(cfg["num_hidden_layers"]):
        first_of.setdefault(program_key(cfg, i), i)
    for (what, conv), i in first_of.items():
        w = layer_shapes(f, i, cfg)
        if what == "dense":
            jobs[what, conv] = lambda w=w, conv=conv: dense_layer.lower(
                x, w, conv=conv, static=static).compile()
            continue
        light = {n: v for n, v in w.items() if n not in HEAVY}
        jobs[what, conv] = lambda light=light, conv=conv: sparse_front.lower(
            x, light, i32, conv=conv, static=static).compile()
        jobs["back"] = lambda w=w: sparse_back.lower(
            x, x, jax.ShapeDtypeStruct((t_pad, 1 if dict(static)["sparse_as_dense"] else k),
                                       jnp.int32),
            f32((t_pad, 1 if dict(static)["sparse_as_dense"] else k)),
            {n: w[n] for n in HEAVY}, capacity=GROUP, static=static).compile()
    pool = concurrent.futures.ThreadPoolExecutor(len(jobs))
    futures = {name: pool.submit(job) for name, job in jobs.items()}
    pool.shutdown(wait=False)
    return futures


def layer(x, n_rows: int, w, cfg: dict, i: int, programs: dict):
    """Layer i over one padded sequence of `n_rows` tokens."""
    what, conv = program_key(cfg, i)
    if what == "dense":
        return programs[what, conv].result()(x, w)
    x, y, local, wts, most = programs[what, conv].result()(
        x, {n: v for n, v in w.items() if n not in HEAVY}, jnp.int32(n_rows))
    heavy, capacity = {n: w[n] for n in HEAVY}, capacity_for(int(most))
    if capacity == GROUP:
        return programs["back"].result()(x, y, local, wts, heavy)
    return sparse_back(x, y, local, wts, heavy, capacity, statics(cfg))


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
