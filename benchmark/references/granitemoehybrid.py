"""Plain reference: the `granitemoehybrid` decoder (granite-4.0-h-small), as one
of the chips that share its layers.

Follows the published configuration and, where that is silent, the public
`transformers` implementation `modeling_granitemoehybrid.py` (whose mixer is
`modeling_bamba.py`'s, after `mamba_ssm`'s `Mamba2`) as remembered: the
configuration's `assumed` names each point. RMSNorm with `rms_norm_eps`
throughout. `x_0 = embedding_multiplier * embed(id)`. Layer l, `u =
rmsnorm_in(x)`:

    layer_types[l] == "mamba" (Mamba-2, one group):
        [z | xBC | dt] = u W_in            8192 + 8448 + 128 columns, no bias
        xBC_t = silu(b + sum_j w[:, j] xBC_{t-3+j})
            (depthwise over the 8448 channels, causal, zeros before position
            0: `Conv1d` with groups = channels, padding = taps - 1, cut to the
            sequence; the last of the `mamba_d_conv` taps meets the current row)
        [x | B | C] = xBC                  x: heads x `mamba_d_head`; B, C: `mamba_d_state`
        per head k:  d_t = softplus(dt_t[k] + dt_bias[k])
                     a_t = exp(-exp(A_log[k]) d_t)
                     H_t = a_t H_{t-1} + d_t x_t[k] (outer) B_t      H_{-1} = 0
                     y_t[k] = H_t C_t + D[k] x_t[k]
        x = x + residual_multiplier * rmsnorm_mixer(y * silu(z)) W_out
    layer_types[l] == "attention":
        q, k, v = u Wq, u Wk, u Wv         no bias, NO rope
        x = x + residual_multiplier * softmax(attention_multiplier q k^T + causal) v Wo
    h = rmsnorm_post(x)
    s = h Wr (over all the routed experts);  I = the `num_experts_per_tok` largest
    w = softmax(s_I)  (= softmax over all, renormalised over the chosen)
    m = sum_{e in I, held} w_e expert_e(h) + shared(h)       SwiGLUs
    x = x + residual_multiplier * m

then the final RMSNorm, the head, and the logits divided by `logits_scaling`.
The recurrence is a plain `lax.scan` over the positions, one `H` carried;
float32, every product at `Precision.HIGHEST`, the whole sequence from
position 0: no cache, no chunks, no kernels, nothing imported from
`dllama_tpu.models` or `dllama_tpu.ops`.

The share: the file holds `num_experts` of the `num_routed_experts` the router
scores, from `first_expert`, and a slice of the vocabulary. What the absent
experts would have added is left out here as in the program, and that partial
sum goes on to the next layer; the mixers, attention, the shared expert and
the norms are whole.

Departures forced by what is compared are `afmoe.py`'s, whose pieces this file
uses: weights from the Q40 `.m` file the server loaded, widened on the device;
sequences padded to one length, attention over query blocks; the held experts
one after the other over the token rows routed to each; programs compiled
ahead, side by side.

`FAULTS`: each makes this reference wrong in one stated way; `ladder.py
--power` shows what the comparison reads against it. "zero state at a chunk
boundary" is what a program would compute that carried neither the recurrent
state nor the convolution's rows from one 512-row chunk into the next; it
shows only past 512 positions. `logits_scaling` has no fault: the comparison
counts gaps in the logits' own std, which a common divisor leaves alone.
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
    "decay left out": Fault(fault_no_decay=True),
    "dt_bias left out": Fault(fault_no_dt_bias=True),
    "D left out": Fault(fault_no_d=True),
    "the gate left out": Fault(fault_no_gate=True),
    "gate after the norm": Fault(fault_gate_after_norm=True),
    "conv bias left out": Fault(fault_no_conv_bias=True),
    "taps reversed": Fault(fault_taps_reversed=True),
    "residual_multiplier=1": Fault(residual_multiplier=1.0),
    "attention scaled head_dim^-1/2": Fault(fault_attn_sqrt_scale=True),
    "rope applied": Fault(fault_rope=True),
    "the shared expert left out": Fault(fault_no_shared=True),
    "softmax over all, not renormalised": Fault(fault_no_renorm=True),
    # not a fault of the model code: the control that bounds `gap_tol` from
    # above, this reference with its activations one precision below the
    # program's bfloat16
    "activations in float8": Fault(fault_act_dtype="float8_e4m3fn"),
}


def mamba2(u, w, kw: dict):
    """The Mamba-2 mixer over a whole sequence `u` [T, D], position by position."""
    t = u.shape[0]
    n_heads, p, n = kw["ssm_heads"], kw["ssm_head_dim"], kw["ssm_state"]
    z = jnp.matmul(u, w["ssm_in_z"].T, precision=HI)
    xbc = lossy(jnp.matmul(u, w["ssm_in_xbc"].T, precision=HI), kw)
    dt = jnp.matmul(u, w["ssm_in_dt"].T, precision=HI)
    taps = w["ssm_conv_w"][:, ::-1] if kw["taps_reversed"] else w["ssm_conv_w"]  # [C, K]
    n_taps = taps.shape[1]
    padded = jnp.pad(xbc, ((n_taps - 1, 0), (0, 0)))
    at = jnp.arange(t)
    every = kw["zero_state_every"]
    acc = jnp.zeros_like(xbc) if kw["no_conv_bias"] else jnp.broadcast_to(
        w["ssm_conv_b"][None, :], xbc.shape)
    for j in range(n_taps):
        back = n_taps - 1 - j  # the tap meets the row `back` positions before
        term = taps[:, j][None, :] * padded[j : j + t]
        if every and back:
            # a chunk that starts from a zero state sees nothing before its row 0
            term = jnp.where((at % every >= back)[:, None], term, 0.0)
        acc = acc + term
    act = jax.nn.silu(acc)
    x = act[:, : n_heads * p].reshape(t, n_heads, p)
    b, c = act[:, n_heads * p : n_heads * p + n], act[:, n_heads * p + n :]
    d = jax.nn.softplus(dt if kw["no_dt_bias"] else dt + w["ssm_dt_bias"][None, :])
    a = jnp.ones_like(d) if kw["no_decay"] else jnp.exp(-jnp.exp(w["ssm_a_log"])[None, :] * d)
    fresh = (at % every == 0) if every else jnp.zeros((t,), bool)

    def step(state, row):
        x_t, b_t, c_t, d_t, a_t, fresh_t = row
        state = jnp.where(fresh_t, 0.0, state)
        state = a_t[:, None, None] * state + (d_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return state, jnp.sum(state * c_t[None, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((n_heads, p, n), jnp.float32), (x, b, c, d, a, fresh))
    if not kw["no_d"]:
        y = y + w["ssm_d"][None, :, None] * x
    y = y.reshape(t, n_heads * p)
    gate = jax.nn.silu(z)
    if kw["gate_after_norm"]:
        y = rms_norm(y, w["ssm_norm"], kw["eps"]) * gate
    else:
        y = rms_norm(y if kw["no_gate"] else y * gate, w["ssm_norm"], kw["eps"])
    return jnp.matmul(lossy(y, kw), w["ssm_out"].T, precision=HI)


def nope_attention(u, w, kw: dict):
    t, n_heads, n_kv, hd = u.shape[0], kw["n_heads"], kw["n_kv_heads"], kw["head_dim"]
    q = jnp.matmul(u, w["q"].T, precision=HI).reshape(t, n_heads, hd)
    k = jnp.matmul(u, w["k"].T, precision=HI).reshape(t, n_kv, hd)
    v = lossy(jnp.matmul(u, w["v"].T, precision=HI).reshape(t, n_kv, hd), kw)
    if kw["rope"]:
        q, k = rope_half(q, jnp.arange(t), kw["theta"]), rope_half(k, jnp.arange(t), kw["theta"])
    if not kw["attn_sqrt_scale"]:
        # `attention` divides by sqrt(hd): the queries carry the rest
        q = q * (kw["attn_scale"] * hd ** 0.5)
    a = lossy(attention(lossy(q, kw), lossy(k, kw), v, jnp.int32(1 << 30)), kw)
    return jnp.matmul(a, w["wo"].T, precision=HI)


def route(y, gate, kw: dict):
    """(ids [T, k] among all routed experts, weights [T, k])."""
    logits = jnp.matmul(y, gate.T, precision=HI)
    top, ids = jax.lax.top_k(logits, kw["top_k"])
    if kw["no_renorm"]:
        return ids, jnp.take_along_axis(jax.nn.softmax(logits, axis=1), ids, axis=1)
    return ids, jax.nn.softmax(top, axis=1)


@functools.partial(jax.jit, static_argnames=("mamba", "static"))
def front(x, w, n_rows, mamba, static):
    """The mixer or attention, the router and the shared expert of a layer:
    (x, post norm, held rows, weights, the shared expert's output, the most
    rows a held expert got)."""
    kw = dict(static)
    u = lossy(rms_norm(x, w["att_norm"], kw["eps"]), kw)
    mixed = mamba2(u, w, kw) if mamba else nope_attention(u, w, kw)
    x = lossy(x + kw["residual"] * mixed, kw)
    y = lossy(rms_norm(x, w["ffn_norm"], kw["eps"]), kw)
    ids, wts = route(y, w["moe_gate"], kw)
    local, most = held_rows(ids, n_rows, kw)
    shared = jnp.zeros_like(y) if kw["no_shared"] else swiglu(
        y, w["shared_w1"], w["shared_w2"], w["shared_w3"])
    return x, y, local, wts, shared, most


@functools.partial(jax.jit, static_argnames=("capacity", "static"))
def back(x, y, local, wts, shared, w, capacity, static):
    kw = dict(static)
    m = shared + experts_sum(y, local, wts, w["w1"], w["w2"], w["w3"], capacity)
    return lossy(x + kw["residual"] * m, kw)


def routed_experts(y, w, cfg: dict, n_rows=None):
    """The held experts' part of the routed sum, [T, D]."""
    kw = dict(statics(cfg))
    ids, wts = route(y, w["moe_gate"], kw)
    local, most = held_rows(ids, y.shape[0] if n_rows is None else n_rows, kw)
    return experts_sum(y, local, wts, w["w1"], w["w2"], w["w3"], capacity_for(int(most)))


MAMBA = ("ssm_in_z", "ssm_in_xbc", "ssm_in_dt", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias",
         "ssm_a_log", "ssm_d", "ssm_norm", "ssm_out")
ATTN = ("q", "k", "v", "wo")
HEAVY = ("w1", "w2", "w3")  # what `back` reads


def is_mamba(cfg: dict, i: int) -> bool:
    return cfg["layer_types"][i] == "mamba"


def layer_tensors(i: int, cfg: dict) -> dict:
    """{key in a layer's weights: the file's tensor}; the held experts' w1,
    w2, w3 come besides."""
    names = {n: f"layers.{i}.{n}" for n in (MAMBA if is_mamba(cfg, i) else ATTN)}
    names.update({n: f"layers.{i}.{n}" for n in ("att_norm", "ffn_norm", "moe_gate")})
    names.update({f"shared_{n}": f"layers.{i}.shared.{n}" for n in HEAVY})
    return names


def layer_weights(f: Q40File, i: int, cfg: dict) -> dict:
    w = {key: matrix(f, name) for key, name in layer_tensors(i, cfg).items()}
    w.update(experts(f, i, cfg["num_experts"]))
    return w


def layer_shapes(f: Q40File, i: int, cfg: dict) -> dict:
    """`layer_weights` as shapes, to compile against."""
    w = {key: jax.ShapeDtypeStruct(f.specs[name].shape, jnp.float32)
         for key, name in layer_tensors(i, cfg).items()}
    for n in HEAVY:
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
        "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
        "residual": float(cfg["residual_multiplier"]),
        "attn_scale": float(cfg["attention_multiplier"]),
        "ssm_heads": cfg["mamba_n_heads"],
        "ssm_head_dim": cfg["mamba_d_head"],
        "ssm_state": cfg["mamba_d_state"],
        "top_k": cfg["num_experts_per_tok"],
        "n_held": cfg["num_experts"],
        "first": cfg.get("first_expert", 0),
        "wrap_absent": False,
        "zero_state_every": int(cfg.get("fault_zero_state_every", 0)),
        "no_decay": bool(cfg.get("fault_no_decay")),
        "no_dt_bias": bool(cfg.get("fault_no_dt_bias")),
        "no_d": bool(cfg.get("fault_no_d")),
        "no_gate": bool(cfg.get("fault_no_gate")),
        "gate_after_norm": bool(cfg.get("fault_gate_after_norm")),
        "no_conv_bias": bool(cfg.get("fault_no_conv_bias")),
        "taps_reversed": bool(cfg.get("fault_taps_reversed")),
        "attn_sqrt_scale": bool(cfg.get("fault_attn_sqrt_scale")),
        "rope": bool(cfg.get("fault_rope")),
        "no_shared": bool(cfg.get("fault_no_shared")),
        "no_renorm": bool(cfg.get("fault_no_renorm")),
        "act": cfg.get("fault_act_dtype"),
    }.items())


def compile_programs(f: Q40File, cfg: dict, t_pad: int, n_head: int) -> dict:
    """The run's programs, lowered against their shapes and compiled side by
    side in threads (float32 products at `Precision.HIGHEST` take the chip's
    compiler seconds each). Futures of callables that take a program's traced
    arguments: the front half of a layer of each kind the configuration has,
    the experts' half, the head."""
    static = statics(cfg)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    x, i32 = f32((t_pad, cfg["hidden_size"])), jax.ShapeDtypeStruct((), jnp.int32)
    k = cfg["num_experts_per_tok"]
    jobs = {"head": lambda: head.lower(
        f32((n_head, cfg["hidden_size"])), f32(f.specs["final_norm"].shape),
        f32(f.specs["wcls"].shape), eps=dict(static)["eps"]).compile()}
    first_of: dict = {}
    for i in range(cfg["num_hidden_layers"]):
        first_of.setdefault(is_mamba(cfg, i), i)
    for mamba, i in first_of.items():
        w = layer_shapes(f, i, cfg)
        light = {n: v for n, v in w.items() if n not in HEAVY}
        jobs["front", mamba] = lambda light=light, mamba=mamba: front.lower(
            x, light, i32, mamba=mamba, static=static).compile()
        jobs["back"] = lambda w=w: back.lower(
            x, x, jax.ShapeDtypeStruct((t_pad, k), jnp.int32), f32((t_pad, k)), x,
            {n: w[n] for n in HEAVY}, capacity=GROUP, static=static).compile()
    pool = concurrent.futures.ThreadPoolExecutor(len(jobs))
    futures = {name: pool.submit(job) for name, job in jobs.items()}
    pool.shutdown(wait=False)
    return futures


def layer(x, n_rows: int, w, cfg: dict, i: int, programs: dict):
    """Layer i over one padded sequence of `n_rows` tokens."""
    x, y, local, wts, shared, most = programs["front", is_mamba(cfg, i)].result()(
        x, {n: v for n, v in w.items() if n not in HEAVY}, jnp.int32(n_rows))
    heavy, capacity = {n: w[n] for n in HEAVY}, capacity_for(int(most))
    if capacity == GROUP:
        return programs["back"].result()(x, y, local, wts, shared, heavy)
    return back(x, y, local, wts, shared, heavy, capacity, statics(cfg))


def last_logits(path: str, cfg: dict, seqs, keep):
    """Logits [keep[i], vocab] at the last keep[i] positions of each
    sequence of token ids, every sequence run whole from position 0."""
    if not seqs:
        return []
    f = Q40File(path)
    t_pad = -(-max(len(ids) for ids in seqs) // PAD) * PAD
    n_head = min(t_pad, max(keep))  # one head program: the most rows any asks for
    programs = compile_programs(f, cfg, t_pad, n_head)
    scale = float(cfg["embedding_multiplier"])
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
        out.append(rows[len(ids) - n - start : len(ids) - start] / float(cfg["logits_scaling"]))
    return out
