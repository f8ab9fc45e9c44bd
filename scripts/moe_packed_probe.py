"""Time the held experts' kernel alone on the chip: int8 stacks against packed.

    chiprun -- python3 scripts/moe_packed_probe.py [--compile-only] [--cases ...]
                                                   [--row-tiles 128,32]

`moe_held_experts_q40` is called by layer number out of a `[L, E, ...]` stack
inside one `fori_loop`, as the block and chunk programs call it, at the
served expert shapes (`CASES`): the sparse cell's decode step (16 lanes x 8
= 128 pairs over 128 experts of 2048 x 768, the distinct experts touched
swept over 32 / 52 / 80) and its chunk (512 rows x 8), and the decode steps
of `lfm2` (2048 x 1536, 16 held), `pangu` (7680 x 2048, 32 held) and
`trinity` (3072 x 3072, 32 held). Per case it times each form AS SERVED (the
int8 values under 128-row tiles and `_pick_f_block`, the packed words under
`_held_rows` and `_held_f_block`; the packed output's largest gap to the
int8 one is printed: another F block sums F in another order), then both
forms at EQUAL tiles over `--row-tiles` x the case's F blocks, where the
packed output has to equal the int8 kernel's bit for bit. Prints
microseconds a call and a grid step and GB/s of the bytes the form holds;
writes chiprun_out/moe_packed_probe.json. `--compile-only` compiles every
case for a described v5e and runs nothing (no chip needed).

`SHARE_CASES`: a held share of the experts the router scores (`pangu` 32 of
256, `granite` 18 of 72, `lfm2` 16 of 64), packed as served, every row's k
experts drawn among all of them, at a decode step's rows and a chunk's
buckets. Per case the whole form alone (the surroundings over every pair:
`cap` = the call's pairs) against the two forms under `lax.cond` at
`_landed_cap`'s count whatever rows it saves (the draw lands fewer pairs, so
the landed form runs): microseconds a call of both, and whether the outputs
are equal bit for bit. `_LANDED_MIN_SAVED` is read off these lines.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from dllama_tpu.ops import moe_kernel as mk
from dllama_tpu.ops import quant_matmul as qm

# name: (D, F, experts held, layers, rows, k, pairs held, distinct experts
# touched, F blocks); `lfm2` routes 4 of 64 and holds 16, `pangu` 8 of 256
# and holds 32: a step's pairs mostly land elsewhere (the sentinel)
CASES = {
    "qwen-decode-32": (2048, 768, 128, 12, 16, 8, 128, 32, (256, 768)),
    "qwen-decode-52": (2048, 768, 128, 12, 16, 8, 128, 52, (256, 768)),
    "qwen-decode-80": (2048, 768, 128, 12, 16, 8, 128, 80, (256, 768)),
    "qwen-chunk": (2048, 768, 128, 12, 512, 8, 4096, 128, (256, 768)),
    "lfm2-decode": (2048, 1536, 16, 12, 16, 4, 16, 10, (256, 512, 768)),
    "pangu-decode": (7680, 2048, 32, 2, 4, 8, 4, 3, (256, 512)),
    "trinity-decode": (3072, 3072, 32, 2, 8, 4, 4, 3, (256, 512)),
}
FORMS = {"int8": 1.125, "packed": 0.625}  # bytes a weight, scales included
# name: (D, F, experts held, experts routed, layers, rows, k)
SHARE_CASES = {
    f"{model}-share-{rows}": (d, f, held, routed, layers, rows, k)
    for model, (d, f, held, routed, layers, k), buckets in (
        ("pangu", (7680, 2048, 32, 256, 2, 8), (4, 128, 512)),
        ("granite", (4096, 768, 18, 72, 4, 10), (32, 64, 128, 256, 512)),
        ("lfm2", (2048, 1536, 16, 64, 4, 4), (16, 128, 512)),
    )
    for rows in buckets
}


def make_stack(key, layers, e, i, o):
    """[L, E, i, o] int8 values and f16-exact f32 scales: four experts
    drawn, tiled (a whole stack's random words would not fit)."""
    kq, kd = jax.random.split(key)
    q = jax.random.randint(kq, (1, 4, i, o), -8, 8, dtype=jnp.int8)
    d = jax.random.uniform(kd, (1, 4, i // 32, o), jnp.float32, 0.002, 0.004)
    d = d.astype(jnp.float16).astype(jnp.float32)
    return jnp.tile(q, (layers, e // 4, 1, 1)), jnp.tile(d, (layers, e // 4, 1, 1))


def pack_stack(q, d):
    """A layer at a time: a stack's int32 temporaries do not fit."""
    return jax.lax.map(lambda qd: qm.pack_nibbles(qm.QuantWeight(*qd)).qp, (q, d))


def held_pairs(rows, k, e, pairs, touched, seed=0):
    """[rows, k] ids with `pairs` held pairs over exactly `touched` distinct
    experts (spread over the stack, so no two tiles are neighbours in HBM),
    the rest the sentinel `e`; weights in (0, 1)."""
    rng = np.random.default_rng(seed)
    if pairs == rows * k and touched == e:  # a chunk: every row routed at random
        ids = np.stack([rng.choice(e, k, replace=False) for _ in range(rows)])
    else:
        experts = rng.choice(e, touched, replace=False)
        ids = np.full(rows * k, e, np.int32)
        ids[:pairs] = experts[np.arange(pairs) % touched]
        ids = rng.permutation(ids).reshape(rows, k)
    ids = ids.astype(np.int32)
    w = np.where(ids < e, rng.random((rows, k)) + 0.1, 0).astype(np.float32)
    return jnp.asarray(ids), jnp.asarray(w)


def routed_pairs(rows, k, held, routed, seed=0):
    """[rows, k] ids as `Routing.held` gives them: every row's k distinct
    experts among `routed`, those below `held` kept, the rest the sentinel."""
    rng = np.random.default_rng(seed)
    ids = np.stack([rng.choice(routed, k, replace=False) for _ in range(rows)])
    ids = np.where(ids < held, ids, held).astype(np.int32)
    w = np.where(ids < held, rng.random((rows, k)) + 0.1, 0).astype(np.float32)
    return jnp.asarray(ids), jnp.asarray(w)


def landed_cap(rows, k, held, routed):
    """`_landed_cap`'s count for the call, whatever rows it saves."""
    was, mk._LANDED_MIN_SAVED = mk._LANDED_MIN_SAVED, 0
    try:
        return mk._landed_cap(rows * k, mk._held_rows(rows * k, True), held, routed)
    finally:
        mk._LANDED_MIN_SAVED = was


def grid_steps(ids, e, row_tile):
    """Distinct (row tile, expert) among the sorted held pairs."""
    held = np.sort(np.asarray(ids)[np.asarray(ids) < e])
    return len(set(zip(np.arange(len(held)) // row_tile, held)))


def loop_over_layers(layers, calls, **tiles):
    @jax.jit
    def run(x, stacks, ids, w):
        def body(i, acc):
            y = mk.moe_held_experts_q40(x, *stacks, ids, w, i % layers, **tiles)
            # a share case reads the whole output: from one element of it the
            # compiler could drop the rest of the landed form's sum of gathers
            return acc + (jnp.sum(y) if tiles.get("cap") else y[0, 0])
        return jax.lax.fori_loop(0, calls, body, jnp.float32(0))
    return run


def time_call(run, args, calls, reps=5):
    jax.block_until_ready(run(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        best = min(best, time.perf_counter() - t0)
    return best / calls * 1e6


def stack_shapes(form, layers, e, d, f):
    pack, dt = (qm.NIBBLES, jnp.int32) if form == "packed" else (1, jnp.int8)
    w13 = [((layers, e, d // pack, f), dt), ((layers, e, d // 32, f), jnp.float32)]
    w2 = [((layers, e, f // pack, d), dt), ((layers, e, f // 32, d), jnp.float32)]
    return w13 + w2 + w13  # w1, w2, w3 as the kernel takes them


def describe_compile(cases):
    """Compile every case for a described v5e; nothing runs."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    s = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=s)
    for name in cases:
        if name in SHARE_CASES:
            d, f, held, routed, layers, rows, k = SHARE_CASES[name]
            for cap in (rows * k, landed_cap(rows, k, held, routed)):
                t0 = time.perf_counter()
                loop_over_layers(layers, layers, cap=cap).lower(
                    sds((rows, d), jnp.bfloat16),
                    [sds(*a) for a in stack_shapes("packed", layers, held, d, f)],
                    sds((rows, k), jnp.int32), sds((rows, k), jnp.float32),
                ).compile()
                print(f"compiled {name} cap={cap} {time.perf_counter() - t0:.1f}s",
                      flush=True)
            continue
        d, f, e, layers, rows, k, _, _, bfs = CASES[name]
        for form in FORMS:
            for bf in bfs:
                run = loop_over_layers(layers, layers, block_f=bf)
                t0 = time.perf_counter()
                try:
                    run.lower(
                        sds((rows, d), jnp.bfloat16),
                        [sds(*a) for a in stack_shapes(form, layers, e, d, f)],
                        sds((rows, k), jnp.int32), sds((rows, k), jnp.float32),
                    ).compile()
                    print(f"compiled {name} {form} bf={bf} "
                          f"{time.perf_counter() - t0:.1f}s", flush=True)
                except Exception as ex:  # what the chip's compiler would refuse
                    msg = " ".join(str(ex).split())
                    at = max(msg.find("exceed"), msg.find("vmem"), 0)
                    print(f"REFUSED  {name} {form} bf={bf}: "
                          f"{msg[max(at - 200, 0):at + 300]}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--cases", default=",".join([*CASES, *SHARE_CASES]))
    ap.add_argument("--row-tiles", default="128,32",
                    help="row tiles of the sweep at equal tiles (F blocks: CASES)")
    a = ap.parse_args()
    cases = a.cases.split(",")
    row_tiles = [int(r) for r in a.row_tiles.split(",")]
    if a.compile_only:
        describe_compile(cases)
        return 0
    if jax.default_backend() != "tpu":
        print("no chip: a kernel time comes only from a chip run", file=sys.stderr)
        return 2
    out = []
    stacks, held_shape = {}, None
    for ci, name in enumerate(cases):
        if name in SHARE_CASES:
            d, f, e, routed, layers, rows, k = SHARE_CASES[name]
            pairs = touched = None
        else:
            d, f, e, layers, rows, k, pairs, touched, bfs = CASES[name]
        if held_shape != (d, f, e, layers):  # the decode sweep shares stacks
            stacks.clear()
            held_shape = (d, f, e, layers)
            key = jax.random.PRNGKey(ci)
            for wi, (i, o) in enumerate(((d, f), (f, d), (d, f))):
                q, s = make_stack(jax.random.fold_in(key, wi), layers, e, i, o)
                stacks.setdefault("int8", []).extend((q, s))
                stacks.setdefault("packed", []).extend((jax.jit(pack_stack)(q, s), s))
            jax.block_until_ready(stacks)
        x = jax.random.normal(jax.random.PRNGKey(100 + ci), (rows, d), jnp.bfloat16)
        calls = 4 * layers
        if name in SHARE_CASES:
            ids, w = routed_pairs(rows, k, e, routed, seed=ci)
            cap = landed_cap(rows, k, e, routed)
            landed = int((np.asarray(ids) < e).sum())
            rec = dict(case=name, d=d, f=f, rows=rows, pairs=rows * k, landed=landed,
                       cap=cap, served_cap=mk._landed_cap(
                           rows * k, mk._held_rows(rows * k, True), e, routed))
            got = {}
            for form, c in (("whole", rows * k), ("landed", cap)):
                rec[f"us_per_call_{form}"] = time_call(
                    loop_over_layers(layers, calls, cap=c),
                    (x, stacks["packed"], ids, w), calls)
                got[form] = mk.moe_held_experts_q40(
                    x, *stacks["packed"], ids, w, layers - 1, cap=c)
            rec["landed_form_ran"] = landed <= cap < rows * k
            rec["bit_equal"] = bool(jnp.array_equal(got["whole"], got["landed"]))
            out.append(rec)
            print(json.dumps(rec), flush=True)
            continue
        ids, w = held_pairs(rows, k, e, pairs, touched, seed=ci)
        # as served (each form's own tiles), then the sweep at equal tiles
        tilings = [None] + [(r, bf) for r in row_tiles for bf in bfs]
        served = {}
        for tiling in tilings:
            ref = None
            for form, bpw in FORMS.items():
                packed = form == "packed"
                r, bf = tiling or (
                    mk._held_rows(rows * k, packed), mk._held_f_block(f, d, packed))
                try:
                    us = time_call(
                        loop_over_layers(layers, calls, block_f=bf, row_tile=r),
                        (x, stacks[form], ids, w), calls)
                    got = mk.moe_held_experts_q40(
                        x, *stacks[form], ids, w, layers - 1, block_f=bf, row_tile=r)
                    steps = grid_steps(ids, e, r) * (f // bf)
                    rec = dict(
                        case=name, d=d, f=f, rows=rows, pairs=pairs, touched=touched,
                        form=form, served=tiling is None, block_f=bf, row_tile=r,
                        us_per_call=us, steps=steps, us_per_grid_step=us / steps,
                        gb_per_s_held=bpw * 3 * d * f * touched / us / 1e3,
                    )
                    if tiling is None:  # other tiles sum F in another order
                        served[form] = got
                        if packed and "int8" in served:
                            gap = jnp.abs(got - served["int8"]).max()
                            rec["max_gap_to_int8_served"] = float(
                                gap / jnp.abs(served["int8"]).max())
                    elif ref is None:
                        ref = got
                    else:  # same tiles, same dots: equal bit for bit
                        rec["bit_equal_int8"] = bool(jnp.array_equal(ref, got))
                    out.append(rec)
                    print(json.dumps(rec), flush=True)
                except Exception as ex:
                    print(f"FAILED {name} {form} bf={bf} rows={r}: "
                          f"{' '.join(str(ex).split())[:400]}", flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_packed_probe.json", "w") as fh:
        json.dump({"device": jax.devices()[0].device_kind, "results": out}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
