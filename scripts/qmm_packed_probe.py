"""Time the dense Q40 matmul kernels alone on the chip: int8 against packed.

    chiprun -- python3 scripts/qmm_packed_probe.py [--variants] [--compile-only]

For Mistral-7B's four layer stacks and `wcls`, at decode rows (5, 16) and a
dense chunk's (2560), each kernel is called by layer number out of a 32-layer
stack (`wcls`: 8) inside one `fori_loop`, as the block program calls it, and
timed to `block_until_ready`. Prints, per shape and rows, microseconds a call and GB/s
of the bytes the form holds; writes the table to chiprun_out/qmm_packed_probe.json.

`packed` is the module's kernel (int32 words of eight two's-complement
nibbles, f32 scales). `--variants` also times the encodings PR 43 tried and
dropped (kept so the choice can be measured again on another
chip or jaxlib): `wire_f16` (PR 1's: the wire's byte pairing, offset nibbles,
f16 scale bits decoded in the kernel), `wire_f32` (the same bytes, f32
scales), `words_f16` (the words under f16 scale bits), `int4` (a native
`jnp.int4` stack with an in-kernel `astype`). `--block-n` sweeps the packed
tile's width. `--compile-only` compiles every case for a described v5e and
runs nothing (no chip needed).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp

from dllama_tpu.ops import quant_matmul as qm

L = 32
STACKS = {  # name: (k, n, layers)
    "wqkv": (4096, 6144, L),
    "wo": (4096, 4096, L),
    "w13": (4096, 28672, L),
    "w2": (14336, 4096, L),
    # a stack of one as served; eight here, so that no two calls of the timed
    # loop are the same call (XLA hoists a loop-invariant one out of it)
    "wcls": (4096, 32768, 8),
}
ROWS = (5, 16, 2560)


# ---- the variants tried (ISSUE 43, step 2) --------------------------------

def _f16_bits_to_f32(bits: jnp.ndarray) -> jnp.ndarray:
    """Exact f16 -> f32 from the raw 16 bits, in integer ops (the chip's
    vector unit loads no f16): PR 1's packed kernel decoded its scale plane
    so; the module keeps f32 scales since PR 43."""
    b = bits.astype(jnp.int32)
    exp = (b >> 10) & 0x1F
    mant = b & 0x3FF
    normal = jax.lax.bitcast_convert_type(
        ((exp + 112) << 23) | (mant << 13), jnp.float32
    )
    mag = jnp.where(exp == 0, mant.astype(jnp.float32) * 2.0**-24, normal)
    return jnp.where((b & 0x8000) != 0, -mag, mag)


def _words_f16_kernel(l_ref, x_ref, qw_ref, d_ref, o_ref, acc_ref, *, n_k):
    """The module's words under an f16 scale plane (0.5625 B a weight)."""
    words = qw_ref[:]
    rows, bn = words.shape
    g = rows // 32
    w3 = words.reshape(g, 32, bn)
    d3 = _f16_bits_to_f32(d_ref[:]).reshape(g, 8, bn)
    pieces = []
    for j in range(8):
        v = w3 >> 28 if j == 7 else (w3 << (28 - 4 * j)) >> 28
        pieces.append(
            (v.astype(jnp.float32) * d3[:, j : j + 1, :]).astype(jnp.bfloat16)
        )
    w = jnp.concatenate(pieces, axis=1).reshape(rows * 8, bn)
    qm._mxu_accumulate(x_ref, w, o_ref, acc_ref, n_k)


def _int4_kernel(l_ref, x_ref, q_ref, d_ref, o_ref, acc_ref, *, n_k):
    q = q_ref[:]
    bk, bn = q.shape
    w = (
        (q.astype(jnp.float32).reshape(bk // 32, 32, bn) * d_ref[:][:, None, :])
        .reshape(bk, bn).astype(jnp.bfloat16)
    )
    qm._mxu_accumulate(x_ref, w, o_ref, acc_ref, n_k)


def pack_words(q: jnp.ndarray, d: jnp.ndarray) -> jnp.ndarray:
    return qm.pack_nibbles(qm.QuantWeight(q, d)).qp


def variant_call(name: str, block_n: int, block_k: int = 4096):
    """(fn(x, values, scales, layer), pack(q, d) -> (values, scales), bytes a weight)"""
    def call(kernel, pack, cast=None):
        @functools.partial(jax.jit)
        def fn(x, values, scales, layer):
            if cast is not None:
                scales = cast(scales)
            return qm._qmm_call(
                kernel, x, values, scales, layer, pack, block_n, block_k, False
            )
        return fn

    f16_bits = lambda d: jax.lax.bitcast_convert_type(d, jnp.int16)
    if name == "int8":
        return (call(qm._qmm_kernel, 1), lambda q, d: (q, d), 1.125)
    if name == "wire_f16":  # PR 1's kernel as it stood
        return (
            call(functools.partial(_wire_kernel, f16=True), 2, f16_bits),
            lambda q, d: (_pr1_pack(q), d.astype(jnp.float16)), 0.5625,
        )
    if name == "wire_f32":
        return (
            call(functools.partial(_wire_kernel, f16=False), 2),
            lambda q, d: (_pr1_pack(q), d), 0.625,
        )
    if name == "packed":  # the module's kernel
        return (
            call(qm._qmm_i4_kernel, 8), lambda q, d: (pack_words(q, d), d), 0.625)
    if name == "words_f16":
        return (
            call(_words_f16_kernel, 8, f16_bits),
            lambda q, d: (pack_words(q, d), d.astype(jnp.float16)), 0.5625,
        )
    if name == "int4":
        return (
            call(_int4_kernel, 1),
            lambda q, d: (q.astype(jnp.int4), d), 0.625,
        )
    raise ValueError(name)


def _pr1_pack(q):
    *lead, k, n = q.shape
    blk = q.astype(jnp.int32).reshape(*lead, k // 32, 32, n)
    b = (blk[..., :16, :] + 8) | ((blk[..., 16:, :] + 8) << 4)
    return jnp.where(b >= 128, b - 256, b).astype(jnp.int8).reshape(*lead, k // 2, n)


def _wire_kernel(l_ref, x_ref, qp_ref, d_ref, o_ref, acc_ref, *, n_k, f16):
    """PR 1's byte layout (the wire's pairing, offset nibbles); with `f16`
    its scale plane too, decoded from the raw bits, else f32 scales."""
    qp = qp_ref[:]
    d = _f16_bits_to_f32(d_ref[:]) if f16 else d_ref[:]
    half, bn = qp.shape
    bk = half * 2
    u = qp.astype(jnp.int32) & 0xFF
    blk = u.reshape(bk // 32, 16, bn)
    lo = (blk & 0xF) - 8
    hi = (blk >> 4) - 8
    w = (
        (jnp.concatenate([lo, hi], axis=1).astype(jnp.float32) * d[:, None, :])
        .reshape(bk, bn).astype(jnp.bfloat16)
    )
    qm._mxu_accumulate(x_ref, w, o_ref, acc_ref, n_k)


# ---- timing ----------------------------------------------------------------

def loop_over_layers(fn, layers: int):
    """One program that calls `fn` once per layer of the stack."""
    @jax.jit
    def run(x, values, scales):
        def body(l, acc):
            return acc + fn(x, values, scales, l)[0, 0]
        return jax.lax.fori_loop(0, layers, body, jnp.float32(0))
    return run


def time_call(run, args, calls: int, reps: int = 5) -> float:
    jax.block_until_ready(run(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        best = min(best, time.perf_counter() - t0)
    return best / calls * 1e6  # us a call


def make_stack(key, k, n, layers):
    """Four drawn layers, repeated to `layers` (a draw of the whole stack
    at once would hold its 32-bit random words: 15 GB for w13)."""
    kq, kd = jax.random.split(key)
    drawn = min(layers, 4)
    q = jax.random.randint(kq, (drawn, k, n), -8, 8, dtype=jnp.int8)
    d = jax.random.uniform(kd, (drawn, k // 32, n), jnp.float32, 0.002, 0.004)
    d = d.astype(jnp.float16).astype(jnp.float32)
    return jnp.tile(q, (layers // drawn, 1, 1)), jnp.tile(d, (layers // drawn, 1, 1))


def by_layer(pack):
    """`pack` a layer at a time: a stack's int32 temporaries do not fit."""
    def packed(q, d):
        return jax.lax.map(lambda qd: pack(*qd), (q, d))
    return packed


def describe_compile(cases):
    """Compile every case for a described v5e; nothing runs."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    s = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=s)
    for name, bn, m, k, n in cases:
        fn, pack, _ = variant_call(name, bn)
        vals, scs = jax.eval_shape(
            pack, jax.ShapeDtypeStruct((k, n), jnp.int8),
            jax.ShapeDtypeStruct((k // 32, n), jnp.float32),
        )
        t0 = time.perf_counter()
        try:
            fn.lower(
                sds((m, k), jnp.bfloat16), sds(vals.shape, vals.dtype),
                sds(scs.shape, scs.dtype), None,
            ).compile()
            print(f"compiled {name} bn={bn} m={m} k={k} n={n} "
                  f"{time.perf_counter() - t0:.1f}s", flush=True)
        except Exception as e:  # what the chip's compiler would refuse
            print(f"REFUSED  {name} bn={bn} m={m} k={k} n={n}: "
                  f"{str(e).splitlines()[0][:300]}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--variants", action="store_true")
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--stacks", default=",".join(STACKS))
    ap.add_argument("--rows", default=",".join(map(str, ROWS)))
    ap.add_argument("--block-n", default="256,512")
    a = ap.parse_args()
    names = ["int8", "packed"]
    if a.variants:
        names += ["wire_f16", "wire_f32", "words_f16", "int4"]
    bns = [int(b) for b in a.block_n.split(",")]
    rows = [int(r) for r in a.rows.split(",")]
    stacks = a.stacks.split(",")
    if a.compile_only:
        describe_compile([
            (nm, bn, m, *STACKS[s][:2])
            for nm in names for bn in bns for s in stacks for m in rows
        ])
        return 0
    if jax.default_backend() != "tpu":
        print("no chip: a kernel time comes only from a chip run", file=sys.stderr)
        return 2
    out = []
    for si, s in enumerate(stacks):
        k, n, layers = STACKS[s]
        q, d = make_stack(jax.random.PRNGKey(si), k, n, layers)
        for nm in names:
            for bn in bns if nm != "int8" else bns[:1]:
                try:
                    fn, pack, bpw = variant_call(nm, bn)
                    vals, scs = jax.jit(by_layer(pack))(q, d)
                    jax.block_until_ready(vals)
                    run = loop_over_layers(fn, layers)
                    for m in rows:
                        x = jax.random.normal(
                            jax.random.PRNGKey(100 + m), (m, k), jnp.bfloat16)
                        us = time_call(run, (x, vals, scs), layers)
                        # same tile, same dot: the outputs are equal bit for bit
                        ref = qm.qmatmul_2d(x, q, d, 0)
                        got = fn(x, vals, scs, 0)
                        same = bool(jnp.array_equal(ref, got))
                        gbs = bpw * k * n / us / 1e3
                        rec = dict(stack=s, k=k, n=n, rows=m, kernel=nm, block_n=bn,
                                   us_per_call=us, gb_per_s_held=gbs, bit_equal=same)
                        out.append(rec)
                        print(json.dumps(rec), flush=True)
                    del vals, scs
                except Exception as e:
                    print(f"FAILED {s} {nm} bn={bn}: {str(e).splitlines()[0][:300]}",
                          flush=True)
        del q, d
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/qmm_packed_probe.json", "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "results": out}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
