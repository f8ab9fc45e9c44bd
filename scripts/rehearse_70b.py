"""Llama-3.3-70B fit-and-plan rehearsal on the 8-virtual-device CPU mesh.

Nothing in the repo had ever run at 70B shapes. This script
does, end to end, with no silicon:

  1. streams a REAL-SIZE synthetic Q40 `.m` to disk (80 layers, 8192 dim,
     28672 ffn, 64/8 heads, 128k vocab — ~43 GB, the exact tensor plan a
     converted Llama-3.3-70B-Instruct-Q40 has; reference runs this model
     per /root/reference/README.md:22);
  2. STREAM-loads it onto a pp4 x tp2 mesh through models/loader's
     shard-by-shard path (the host high-water mark is the headline: the
     pre-r5 loader stacked whole [80, ...] tensors on host — ~37 GB for
     w13 alone);
  3. prints the per-device HBM plan (weights + int8 KV at the file's
     seq_len, plus the analytic 131k-context budget vs v5e 16 GB);
  4. runs ONE pp4xtp2 prefill chunk (T=8) and ONE decode step at full
     70B shapes and checks the logits are finite.

Run:  python scripts/rehearse_70b.py [--layers 80] [--path .scratch/synth70b.m]
Results land in docs/70b_plan.md (hand-recorded).
"""

import argparse
import json
import os
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    flags += " --xla_force_host_platform_device_count=8"
# NOTE: 8 virtual devices time-slice ONE core here, so a shard can take
# minutes to reach a collective, and XLA CPU's rendezvous hard-terminates
# at 40 s. No flag governs that rendezvous
# (--xla_cpu_collective_timeout_seconds parses but both 80-layer runs
# still aborted at the first DECODE all-reduce with "of 40 seconds
# exceeded") — on a 1-core host the decode step is unreachable; prefill
# completes (docs/70b_plan.md).
os.environ["XLA_FLAGS"] = flags.strip()

import jax

jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from dllama_tpu.formats.model_file import ModelReader
from dllama_tpu.models import init_kv_cache, load_params
from dllama_tpu.models.synthetic import write_synth_model
from dllama_tpu.parallel import cache_specs, make_mesh, shard_params_put
from dllama_tpu.parallel.pipeline import forward_pp
from dllama_tpu.utils.telemetry import memory_report

V5E_HBM = 16e9


def hwm_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def per_device_bytes(tree) -> dict[str, int]:
    out: dict[str, int] = {}
    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            key = str(sh.device)
            out[key] = out.get(key, 0) + sh.data.nbytes
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=80)
    ap.add_argument("--path", default=".scratch/synth70b.m")
    ap.add_argument("--pp", type=int, default=4)
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=4096)
    args = ap.parse_args()
    rec: dict = {"layers": args.layers, "pp": args.pp, "tp": args.tp}

    if not os.path.exists(args.path):
        t0 = time.perf_counter()
        write_synth_model(
            args.path, "llama-70b", n_layers=args.layers,
            max_seq_len=args.seq_len,
        )
        rec["write_s"] = round(time.perf_counter() - t0, 1)
    rec["file_gb"] = round(os.path.getsize(args.path) / 1e9, 2)
    print(f"file: {rec['file_gb']} GB ({args.layers} layers)", flush=True)

    r = ModelReader(args.path)
    h = r.header
    if h.n_layers != args.layers or h.seq_len != args.seq_len:
        raise SystemExit(
            f"existing {args.path} has {h.n_layers} layers / seq "
            f"{h.seq_len}, but --layers {args.layers} --seq-len "
            f"{args.seq_len} was requested; delete the file or match the args"
        )
    mesh = make_mesh(tp=args.tp, pp=args.pp)
    base_hwm = hwm_gb()
    t0 = time.perf_counter()
    params = load_params(
        r, weight_format="q40", dtype=jnp.bfloat16,
        put=shard_params_put(mesh, h), fuse=args.tp,
    )
    jax.block_until_ready(jax.tree.leaves(params))
    rec["load_s"] = round(time.perf_counter() - t0, 1)
    rec["host_hwm_gb"] = round(hwm_gb(), 2)
    rec["host_hwm_baseline_gb"] = round(base_hwm, 2)

    cache = init_kv_cache(h, 1, dtype=jnp.int8)
    cs = cache_specs(h, pp=args.pp > 1)
    cache = {
        k: jax.device_put(v, NamedSharding(mesh, cs[k])) for k, v in cache.items()
    }
    dev_w = per_device_bytes(params)
    dev_c = per_device_bytes(cache)
    rec["per_device_weights_gb"] = {
        k: round(v / 1e9, 3) for k, v in sorted(dev_w.items())
    }
    rec["per_device_cache_gb_seq4096_int8"] = round(
        max(dev_c.values()) / 1e9, 3
    )
    rep = memory_report(params, cache, n_devices=8, tp=args.tp)
    rec["params_gb_total"] = round(rep.params_bytes / 1e9, 2)

    # analytic long-context budget: int8 KV at the true 131072 context
    kv131k = 2 * h.n_layers * h.n_kv_heads * 131072 * (h.head_dim + 4)
    rec["kv131k_int8_gb_per_chip"] = round(kv131k / 8 / 1e9, 2)
    worst = max(dev_w.values()) / 1e9
    rec["worst_chip_gb_at_131k"] = round(
        worst + kv131k / 8 / 1e9 + 0.5, 2  # +0.5 activations/workspace
    )
    rec["fits_v5e_16gb"] = rec["worst_chip_gb_at_131k"] < V5E_HBM / 1e9
    print(json.dumps(rec, indent=1), flush=True)

    # one pp4xtp2 prefill chunk + one decode step at full 70B shapes
    # (cache donated: the engine's steps donate too, and the rehearsal
    # host has no headroom for two live caches + logits)
    step = jax.jit(
        lambda p, t, c, pos: forward_pp(
            p, h, t, pos, c, mesh, logits_mode="last", sync_quant=False
        ),
        donate_argnums=(2,),
    )
    tok8 = jnp.ones((1, 8), jnp.int32)
    t0 = time.perf_counter()
    logits, cache = step(params, tok8, cache, jnp.int32(0))
    ok = bool(np.isfinite(np.asarray(logits)).all())
    rec["prefill8_s"] = round(time.perf_counter() - t0, 1)
    rec["prefill_finite"] = ok
    print(f"prefill8: {rec['prefill8_s']}s finite={ok}", flush=True)
    tok1 = jnp.ones((1, 1), jnp.int32)
    t0 = time.perf_counter()
    logits, cache = step(params, tok1, cache, jnp.int32(8))
    ok = bool(np.isfinite(np.asarray(logits)).all())
    rec["decode_s"] = round(time.perf_counter() - t0, 1)
    rec["decode_finite"] = ok
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
