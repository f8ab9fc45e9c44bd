#!/usr/bin/env python
"""Q40 matmul kernel bandwidth sweep — run on real TPU silicon.

The round-1 kernel measured ~190 GB/s while XLA's in-model dense matvec
reaches ~460 GB/s on the same chip (ROADMAP.md); this script separates the
hypotheses so the fix is driven by data, not guesses:

  A. xla-dense-bf16     : XLA jit matvec — the bandwidth target
  B. pallas-dense-bf16  : dense bf16 pallas matvec — isolates Pallas
                          pipeline overhead from dequant cost
  C. pallas-int8-raw    : int8 weights, no scales, cast+matmul — isolates
                          the int8->bf16 conversion cost
  D. qmm-current        : the shipping kernel (ops/quant_matmul.qmatmul_2d)
                          across (block_k, block_n) and grid-order variants
  E. qmm-vreg           : VPU-reduction variant (elementwise multiply +
                          sublane-sum instead of an MXU [1,k]x[k,n] dot —
                          matvecs underuse the MXU's 128x128 tile)
  F. qmm-flat           : 1D grid over n only (whole k per step) — fewer
                          grid steps, bigger DMAs

Usage:  python scripts/kernel_sweep.py            # full sweep
        SWEEP_QUICK=1 python scripts/kernel_sweep.py
Prints one line per variant: name, ms/call, effective GB/s (weight+scale
bytes moved per call / time).
"""

from __future__ import annotations

import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from dllama_tpu.parallel.mesh import enable_compilation_cache

enable_compilation_cache()

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Q_BLOCK = 32


def timeit(f, n_iter=100):
    jax.block_until_ready(f())
    t0 = time.perf_counter()
    for _ in range(n_iter):
        o = f()
    jax.block_until_ready(o)
    return (time.perf_counter() - t0) / n_iter * 1000


def report(name: str, ms: float, nbytes: int):
    gbs = nbytes / (ms / 1000) / 1e9
    print(f"{name:42s} {ms:8.3f} ms   {gbs:7.1f} GB/s", flush=True)
    return gbs


def main():
    quick = bool(os.environ.get("SWEEP_QUICK"))
    k, n = (4096, 4096) if quick else (4096, 14336)
    m = 1
    rng = np.random.default_rng(0)
    print(f"devices: {jax.devices()}  shapes: m={m} k={k} n={n}", flush=True)

    wq = rng.integers(-8, 8, size=(k, n), dtype=np.int8)
    wd = (rng.standard_normal((k // Q_BLOCK, n)).astype(np.float32) * 0.01)
    wq_j = jnp.asarray(wq)
    wd_j = jnp.asarray(wd)
    w_bf16 = jnp.asarray(
        (wq.astype(np.float32) * np.repeat(wd, Q_BLOCK, axis=0)), jnp.bfloat16
    )
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32), jnp.bfloat16)

    q_bytes = k * n + (k // Q_BLOCK) * n * 4  # int8 + f32 scales
    dense_bytes = k * n * 2

    # A. XLA dense bf16 matvec (the target)
    f_xla = jax.jit(lambda xx, ww: xx @ ww)
    report("A xla-dense-bf16", timeit(lambda: f_xla(x, w_bf16)), dense_bytes)

    # B. dense bf16 pallas matvec, several block_n
    def dense_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k):
        pk = pl.program_id(1)
        p = jax.lax.dot_general(
            x_ref[:], w_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(pk == 0)
        def _():
            acc_ref[:] = p

        @pl.when(pk > 0)
        def _():
            acc_ref[:] += p

        @pl.when(pk == n_k - 1)
        def _():
            o_ref[:] = acc_ref[:]

    def pallas_dense(bn, bk, dims=None):
        n_k = k // bk
        grid = (n // bn, n_k)
        kw = {}
        if dims is not None:
            kw["compiler_params"] = pltpu.CompilerParams(
                dimension_semantics=dims
            )
        return pl.pallas_call(
            functools.partial(dense_kernel, n_k=n_k),
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
            grid=grid,
            in_specs=[
                pl.BlockSpec((m, bk), lambda i, j: (0, j)),
                pl.BlockSpec((bk, bn), lambda i, j: (j, i)),
            ],
            out_specs=pl.BlockSpec((m, bn), lambda i, j: (0, i)),
            scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
        )(x, w_bf16)

    for bn, bk in [(512, 2048), (512, 4096), (1024, 4096), (2048, 4096)]:
        if n % bn or k % bk:
            continue
        try:
            f = jax.jit(functools.partial(pallas_dense, bn, bk))
            report(f"B pallas-dense-bf16 bn={bn} bk={bk}", timeit(f), dense_bytes)
        except Exception as e:
            print(f"B pallas-dense-bf16 bn={bn} bk={bk}: {type(e).__name__}: {str(e)[:120]}")
    try:
        f = jax.jit(
            functools.partial(pallas_dense, 512, 4096, ("parallel", "arbitrary"))
        )
        report("B pallas-dense-bf16 512/4096 par-hint", timeit(f), dense_bytes)
    except Exception as e:  # compiler_params API drift
        print(f"  (par-hint variant unavailable: {type(e).__name__})")

    # C. int8 raw (no scales): conversion cost probe
    def int8_kernel(x_ref, q_ref, o_ref, acc_ref, *, n_k):
        pk = pl.program_id(1)
        w = q_ref[:].astype(jnp.bfloat16)
        p = jax.lax.dot_general(
            x_ref[:], w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(pk == 0)
        def _():
            acc_ref[:] = p

        @pl.when(pk > 0)
        def _():
            acc_ref[:] += p

        @pl.when(pk == n_k - 1)
        def _():
            o_ref[:] = acc_ref[:]

    def pallas_int8(bn, bk):
        n_k = k // bk
        return pl.pallas_call(
            functools.partial(int8_kernel, n_k=n_k),
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
            grid=(n // bn, n_k),
            in_specs=[
                pl.BlockSpec((m, bk), lambda i, j: (0, j)),
                pl.BlockSpec((bk, bn), lambda i, j: (j, i)),
            ],
            out_specs=pl.BlockSpec((m, bn), lambda i, j: (0, i)),
            scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
        )(x, wq_j)

    for bn, bk in [(512, 4096), (1024, 4096), (2048, 4096)]:
        if n % bn or k % bk:
            continue
        try:
            f = jax.jit(functools.partial(pallas_int8, bn, bk))
            report(f"C pallas-int8-raw bn={bn} bk={bk}", timeit(f), k * n)
        except Exception as e:
            print(f"C pallas-int8-raw bn={bn} bk={bk}: {type(e).__name__}: {str(e)[:120]}")

    # D. current shipping kernel across block configs
    from dllama_tpu.ops.quant_matmul import qmatmul_2d

    for bn, bk in [(512, 2048), (512, 4096), (1024, 2048), (1024, 4096),
                   (2048, 2048), (2048, 4096), (256, 4096)]:
        if n % bn or k % bk:
            continue
        try:
            f = jax.jit(
                lambda bn=bn, bk=bk: qmatmul_2d(x, wq_j, wd_j, block_n=bn, block_k=bk)
            )
            report(f"D qmm-current bn={bn} bk={bk}", timeit(f), q_bytes)
        except Exception as e:
            print(f"D qmm-current bn={bn} bk={bk}: {type(e).__name__}: {str(e)[:120]}")

    # E. VPU-reduction variant: no MXU — broadcast-multiply + k-axis sum.
    #    x arrives pre-scaled per k-row is impossible (scales vary per n),
    #    so dequant stays, but the reduction avoids the [1,k]x[k,n] MXU dot.
    def vreg_kernel(x_ref, q_ref, d_ref, o_ref, acc_ref, *, n_k):
        pk = pl.program_id(1)
        q = q_ref[:]  # [bk, bn] int8
        d = d_ref[:]  # [bk//32, bn] f32
        bk, bn = q.shape
        xv = x_ref[:]  # [1, bk] bf16
        # w[i, o] * x[i] summed over i: fold x into the dequant multiply
        xq = (q.astype(jnp.float32) * xv.reshape(bk, 1).astype(jnp.float32))
        part = jnp.sum(
            xq.reshape(bk // Q_BLOCK, Q_BLOCK, bn), axis=1
        )  # [bk//32, bn]
        p = jnp.sum(part * d, axis=0, keepdims=True)  # [1, bn]

        @pl.when(pk == 0)
        def _():
            acc_ref[:] = p

        @pl.when(pk > 0)
        def _():
            acc_ref[:] += p

        @pl.when(pk == n_k - 1)
        def _():
            o_ref[:] = acc_ref[:]

    def pallas_vreg(bn, bk):
        n_k = k // bk
        return pl.pallas_call(
            functools.partial(vreg_kernel, n_k=n_k),
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
            grid=(n // bn, n_k),
            in_specs=[
                pl.BlockSpec((m, bk), lambda i, j: (0, j)),
                pl.BlockSpec((bk, bn), lambda i, j: (j, i)),
                pl.BlockSpec((bk // Q_BLOCK, bn), lambda i, j: (j, i)),
            ],
            out_specs=pl.BlockSpec((m, bn), lambda i, j: (0, i)),
            scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
        )(x, wq_j, wd_j)

    for bn, bk in [(512, 2048), (1024, 2048), (2048, 1024)]:
        if n % bn or k % bk:
            continue
        try:
            f = jax.jit(functools.partial(pallas_vreg, bn, bk))
            report(f"E qmm-vreg bn={bn} bk={bk}", timeit(f), q_bytes)
        except Exception as e:
            print(f"E qmm-vreg bn={bn} bk={bk}: {type(e).__name__}: {str(e)[:120]}")

    # F. 1D grid: whole k per step (one tall DMA per n block)
    def flat_kernel(x_ref, q_ref, d_ref, o_ref):
        q = q_ref[:]
        d = d_ref[:]
        bk, bn = q.shape
        w = (
            (q.astype(jnp.float32).reshape(bk // Q_BLOCK, Q_BLOCK, bn)
             * d[:, None, :])
            .reshape(bk, bn)
            .astype(jnp.bfloat16)
        )
        o_ref[:] = jax.lax.dot_general(
            x_ref[:], w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def pallas_flat(bn):
        return pl.pallas_call(
            flat_kernel,
            out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
            grid=(n // bn,),
            in_specs=[
                pl.BlockSpec((m, k), lambda i: (0, 0)),
                pl.BlockSpec((k, bn), lambda i: (0, i)),
                pl.BlockSpec((k // Q_BLOCK, bn), lambda i: (0, i)),
            ],
            out_specs=pl.BlockSpec((m, bn), lambda i: (0, i)),
        )(x, wq_j, wd_j)

    for bn in [256, 512, 1024]:
        if n % bn:
            continue
        try:
            f = jax.jit(functools.partial(pallas_flat, bn))
            report(f"F qmm-flat bn={bn}", timeit(f), q_bytes)
        except Exception as e:
            print(f"F qmm-flat bn={bn}: {type(e).__name__}: {str(e)[:120]}")

    # G. kernel-launch overhead probe: decode runs 7 quantized matmuls per
    # layer; if N small calls cost meaningfully more than one call over
    # the same bytes, qkv/w1w3 fusion (ROADMAP #3) is worth the layout
    # complexity.
    n_split = 4
    n_small = n // n_split
    if n % n_split == 0 and n_small % 128 == 0:
        f_one = jax.jit(lambda: qmatmul_2d(x, wq_j, wd_j, block_n=512))
        qs = [jnp.asarray(wq[:, i * n_small:(i + 1) * n_small]) for i in range(n_split)]
        ds = [jnp.asarray(wd[:, i * n_small:(i + 1) * n_small]) for i in range(n_split)]

        def f_many():
            outs = [
                qmatmul_2d(x, qs[i], ds[i], block_n=min(512, n_small))
                for i in range(n_split)
            ]
            return outs[-1]

        f_many_j = jax.jit(f_many)
        t_one = timeit(f_one)
        t_many = timeit(f_many_j)
        report("G one fused call", t_one, q_bytes)
        report(f"G {n_split} split calls (same bytes)", t_many, q_bytes)
        print(f"  -> per-call overhead ~{(t_many - t_one) / (n_split - 1):.3f} ms")

    # correctness spot check for the variants that could ship
    from dllama_tpu.ops.quant_matmul import QuantWeight, qmatmul_ref

    ref = np.asarray(qmatmul_ref(x.astype(jnp.float32), QuantWeight(wq_j, wd_j)))
    cur = np.asarray(jax.jit(lambda: qmatmul_2d(x, wq_j, wd_j))())
    print("current kernel max err vs ref:", np.abs(cur - ref).max())


if __name__ == "__main__":
    main()
