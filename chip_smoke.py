#!/usr/bin/env python
"""Chip smoke: the serving path on the TPU at Llama-3.1-8B width.

    python chip_smoke.py              # one chip, what the driver runs
    python chip_smoke.py --chips 4    # tp=4 over the host's four chips vs tp=1
    python chip_smoke.py --rehearse   # tiny model on the CPU; never "ok"

One process, the only one that touches JAX. It writes a seeded Llama-3.1-8B
Q40 `.m` (all 32 layers, rows that differ) and a `.t` into
`chip_smoke_work/`, builds the engine and HTTP server from the API server's
own parser the way `api_server.main` does, sends five
`/v1/chat/completions` requests, checks that the compiled prefill and decode
programs hold Pallas kernels, and compares the served tokens and the kernel
path's logits with the repo's plain `jax.numpy` path (`qmatmul_ref`,
`_attention`) on the same chip from the same file. Any phase that fails
raises: the last line, `{"ok": true, "device": {...}}`, is only reached
when every check passed on a TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import http.client
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, "chip_smoke_work")
N_NEW = 32  # tokens asked of every request
N_CHECK = 16  # greedy tokens compared with the reference
# Stated tolerance: every kernel-path logit (2 million of them: 16 positions
# x the vocabulary) must lie within this fraction of the reference logits'
# standard deviation. The kernel feeds the MXU bf16 weights where the
# reference dequantizes to f32; the largest error the chip has shown is
# 0.044 of a standard deviation, and a wrong kernel is off by whole ones.
# Served tokens must equal the reference's top-1; where they differ, the
# reference must hold the two tokens within the same tolerance of each
# other (a numerical tie).
LOGIT_TOL_STD = 0.1
FIXED_PROMPT = "Tell me about tensor parallel inference on a TPU."


def log(msg: str) -> None:
    print(msg, flush=True)


class Phase:
    """Times a phase and prints it; an exception inside propagates."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.seconds = time.perf_counter() - self.t0
        if exc_type is None:
            log(f"[phase] {self.name}: {self.seconds:.1f} s")
        return False


def post_chat(port: int, content: str, stream: bool) -> dict:
    """One chat completion over HTTP; returns status, the dllama metadata
    block and, when not streaming, the body."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    t0 = time.perf_counter()
    conn.request(
        "POST",
        "/v1/chat/completions",
        json.dumps(
            {
                "messages": [{"role": "user", "content": content}],
                "max_tokens": N_NEW,
                "temperature": 0,
                "stream": stream,
            }
        ),
        {"Content-Type": "application/json"},
    )
    resp = conn.getresponse()
    raw = resp.read().decode("utf-8", errors="replace")
    conn.close()
    out = {"status": resp.status, "seconds": time.perf_counter() - t0}
    if resp.status != 200:
        raise RuntimeError(f"HTTP {resp.status}: {raw[:500]}")
    if stream:
        frames = [
            ln[len("data: "):] for ln in raw.splitlines() if ln.startswith("data: ")
        ]
        if not frames or frames[-1].strip() != "[DONE]":
            raise RuntimeError(f"stream did not end with [DONE]: {raw[-300:]}")
        chunks = [json.loads(f) for f in frames[:-1]]
        out["meta"] = chunks[-1].get("dllama") or {}
        out["n_deltas"] = sum(
            1 for c in chunks if c["choices"][0].get("delta", {}).get("content")
        )
    else:
        body = json.loads(raw)
        out["meta"] = body.get("dllama") or {}
        out["body"] = body
    return out


def check_tokens(served, ref_logits, tol: float) -> tuple[int, int]:
    """Teacher-forced greedy agreement: `ref_logits[i]` are the reference's
    logits for the position that produced `served[i]`. Returns (exact,
    ties); raises when a served token is neither the reference's top-1 nor
    within `tol` of it in the reference's own logits."""
    import numpy as np

    exact = ties = 0
    for i, tok in enumerate(served):
        row = np.asarray(ref_logits[i], np.float32)
        top = int(row.argmax())
        if tok == top:
            exact += 1
        elif float(row[top] - row[tok]) <= tol:
            ties += 1
        else:
            raise AssertionError(
                f"token {i}: served {tok} but the reference's top-1 is {top} "
                f"(reference logits {row[tok]:.4f} vs {row[top]:.4f}, "
                f"tolerance {tol:.4f})"
            )
    return exact, ties


@contextlib.contextmanager
def plain_path():
    """Steer the program onto its plain jax.numpy path while a function is
    traced: its kernel branches ask `jax.default_backend()`. The arrays
    stay where they are, so the traced program still runs on the chip."""
    import jax

    real = jax.default_backend
    jax.default_backend = lambda: "cpu"
    try:
        yield
    finally:
        jax.default_backend = real


def logits_fn(engine, t_pad: int, plain: bool):
    """Compiled [1, t_pad] -> logits [t_pad, V] over the engine's own
    parameters, through `forward` — the Pallas kernels the served programs
    use, or with `plain` the jax.numpy reference path."""
    import jax
    import jax.numpy as jnp

    from dllama_tpu.models import forward, init_kv_cache

    h, mesh = engine.header, engine.mesh
    cache = {
        k: jax.device_put(v, engine._cache_sharding[k])
        for k, v in init_kv_cache(
            h, 1, dtype=engine.kv_dtype, seq_len=max(512, t_pad)
        ).items()
    }

    def f(params, tokens, cache):
        logits, _ = forward(
            params, h, tokens, jnp.int32(0), cache, mesh=mesh, logits_mode="all"
        )
        return logits[0]

    spec = jax.ShapeDtypeStruct(
        (1, t_pad), jnp.int32, sharding=engine._token_sharding
    )
    with plain_path() if plain else contextlib.nullcontext():
        compiled = jax.jit(f).lower(engine.params, spec, cache).compile()
    has_kernel = "tpu_custom_call" in compiled.as_text()
    if plain and has_kernel:
        raise AssertionError("the reference program contains a Pallas kernel")
    return lambda tokens: compiled(engine.params, tokens, cache)


def choose_context(limit_bytes: int | None, preset: dict, lanes: int) -> int:
    """4096 as launch.py runs it, halved while the budget says it cannot
    fit: q40 weights (1.125 B/weight) + bf16 embedding + lane KV + the
    prefix pool + 2 GB for temporaries, against 95% of what the device
    reports. (On a 16 GiB v5e that is 15.0 of 16.9 GB and nothing is cut;
    the chip's own peak at 4096 was 13.2 GB.) Width and depth are never
    cut."""
    d, ff, layers = preset["dim"], preset["hidden_dim"], preset["n_layers"]
    kv_dim = preset["n_kv_heads"] * preset["head_dim"]
    q_dim = preset["n_heads"] * preset["head_dim"]
    per_layer = d * (q_dim + 2 * kv_dim) + q_dim * d + 3 * d * ff
    weights = 1.125 * (layers * per_layer + d * preset["vocab_size"])
    embed = 2 * d * preset["vocab_size"]
    row = layers * 2 * kv_dim * 2  # bf16 K and V bytes per cached position
    ctx = 4096
    while limit_bytes:
        lane_kv = lanes * (ctx + 512) * row
        pool = (2 * ctx + 16) * row
        need = weights + embed + lane_kv + pool + 2e9
        log(
            f"budget @ context {ctx}: {need / 1e9:.2f} GB needed of "
            f"{limit_bytes / 1e9:.2f} GB (weights {weights / 1e9:.2f}, embed "
            f"{embed / 1e9:.2f}, lane KV {lane_kv / 1e9:.2f}, pool "
            f"{pool / 1e9:.2f}, temporaries 2.00)"
        )
        if need <= 0.95 * limit_bytes or ctx <= 512:
            break
        log(f"cut: context {ctx} -> {ctx // 2} (width and depth untouched)")
        ctx //= 2
    return ctx


def trace_path(tp: int) -> str:
    return os.path.join(WORK, f"trace_tp{tp}.jsonl")


def start_server(model: str, tok: str, ctx: int, lanes: int, tp: int, extra):
    """The server a deployment would start: the API server's own parser,
    `cli.load_engine` and `serve(...)` via `serve_from_args`, on a thread
    on an ephemeral port. No retry loop."""
    from dllama_tpu.runtime.api_server import build_arg_parser, serve_from_args

    trace = trace_path(tp)
    if os.path.exists(trace):
        os.remove(trace)
    argv = [
        "--model", model, "--tokenizer", tok, "--max-seq-len", str(ctx),
        "--weight-format", "auto", "--batch-size", str(lanes),
        "--tp", str(tp), "--temperature", "0", "--host", "127.0.0.1",
        "--port", "0", "--trace-out", trace, *extra,
    ]
    log("server args: " + " ".join(argv))
    server = serve_from_args(build_arg_parser().parse_args(argv))
    thread = threading.Thread(
        target=server.serve_forever, daemon=True, name="chip-smoke-http"
    )
    thread.start()
    return server, thread


def stop_server(server, thread) -> None:
    server.shutdown()
    server.server_close()
    thread.join(timeout=30)
    if thread.is_alive():
        raise RuntimeError("HTTP server thread did not stop")


def free_engine(engine) -> None:
    """Give the engine's device memory back now (the metrics registry and
    recorder keep the object itself alive past `del`)."""
    import jax

    for x in jax.tree.leaves((engine.params, engine.cache, engine.kv_pool)):
        x.delete()


def record_decode(engine) -> list:
    """Keep what the scheduler's decode dispatches return (token ids per
    lane): the HTTP body carries text only. The scheduler dispatches a
    block and collects it a tick later; a call's rows are filled in then."""
    calls: list = []
    dispatch, collect = engine.dispatch_lanes, engine.collect_lanes

    def dispatch_lanes(tokens, pos, n_steps, active=None, *a, **kw):
        block = dispatch(tokens, pos, n_steps, active, *a, **kw)
        if block is not None:
            calls.append([list(tokens), list(pos), list(active or []), block])
        return block

    def collect_lanes(block):
        rows = collect(block)
        for call in calls:
            if call[3] is block:
                call[3] = rows
        return rows

    engine.dispatch_lanes, engine.collect_lanes = dispatch_lanes, collect_lanes
    return calls


def lane_tokens(calls: list, lane: int) -> tuple[int, int, list[int]]:
    """(fed token, position, generated ids) of the stream that ran alone
    on `lane` over the recorded dispatches."""
    mine = [c for c in calls if c[2] and c[2][lane]]
    if not mine:
        raise AssertionError(f"no decode dispatch recorded for lane {lane}")
    out = [row[lane] for c in mine for row in c[3]]
    return mine[0][0][lane], mine[0][1][lane], out[:N_NEW]


def compile_and_check_programs(engine, block_size: int) -> float:
    """Wait for the admission-path programs the scheduler started building,
    fail on any that a prefetch thread could not build, and return the
    seconds spent waiting."""
    t0 = time.perf_counter()
    engine.rehearse_admission(block_size, wait=True)
    dt = time.perf_counter() - t0
    failed = [
        k for k, o in engine._compile_origin.items() if o == "prefetch-failed"
    ]
    if failed:
        raise RuntimeError(f"programs failed to compile: {failed}")
    return dt


def assert_kernels(engine, require: bool) -> None:
    """The compiled decode and prefill programs must hold Pallas kernels."""
    seen = {"lane_block": 0, "lane_prefill": 0}
    for key, fn in list(engine._compiled.items()):
        if key[0] not in seen:
            continue
        has = "tpu_custom_call" in fn.as_text()
        log(f"program {key}: tpu_custom_call {'found' if has else 'MISSING'}")
        if require and not has:
            raise AssertionError(f"no Pallas kernel in compiled {key}")
        seen[key[0]] += 1
    if not all(seen.values()):
        raise AssertionError(f"programs not compiled: {seen}")


def prompt_ids(server, content: str) -> list[int]:
    """The ids the scheduler feeds for a one-message chat."""
    from dllama_tpu.tokenizer import ChatItem

    state = server.state
    prompt = state.template.generate(
        [ChatItem("user", content)], append_generation_prompt=True
    )
    return state.tokenizer.encode(
        prompt.content, is_start=True, add_special_tokens=True
    )


def fixed_request(server, calls) -> dict:
    """Send the fixed prompt alone (non-streaming) and tie what came back
    over HTTP to the ids the decode program produced."""
    tok = server.state.tokenizer
    port = server.server_address[1]
    n0 = len(calls)
    r = post_chat(port, FIXED_PROMPT, stream=False)
    usage = r["body"]["usage"]
    ids = prompt_ids(server, FIXED_PROMPT)
    fed, pos, served = lane_tokens(calls[n0:], r["meta"]["lane"])
    if usage["completion_tokens"] != N_NEW or len(served) != N_NEW:
        raise AssertionError(f"asked {N_NEW} tokens, got {usage}, {len(served)}")
    if usage["prompt_tokens"] != len(ids) or fed != ids[-1] or pos != len(ids) - 1:
        raise AssertionError(
            f"prompt mismatch: usage {usage}, fed {fed}@{pos}, ids {len(ids)}"
        )
    # the body may stop short of the last token's text: the server holds
    # back what could still turn into a stop string
    text = r["body"]["choices"][0]["message"]["content"]
    full = tok.decode_tokens(served)
    if not full.startswith(text) or len(text) < len(full) // 2:
        raise AssertionError(
            f"HTTP text {text!r} is not the decode of the recorded ids {full!r}"
        )
    log(f"request 1 (non-streaming, fixed prompt, {len(ids)} prompt tokens): "
        f"200, {N_NEW} tokens, {r['seconds']:.2f} s, ids {served[:N_CHECK]}")
    if len(set(served)) < 4:
        raise AssertionError(f"degenerate output: {served}")
    return {"prompt": ids, "served": served}


def kernel_logits(engine, fixed: dict, plain: bool):
    """Logits for the N_CHECK positions after the fixed prompt,
    teacher-forced on `fixed`'s served ids, as a numpy array."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    seq = fixed["prompt"] + fixed["served"][: N_CHECK - 1]
    t_pad = -(-len(seq) // 8) * 8  # the flash kernel wants rows in eights
    arr = jax.device_put(
        jnp.asarray([seq + [0] * (t_pad - len(seq))], jnp.int32),
        engine._token_sharding,
    )
    out = logits_fn(engine, t_pad, plain)(arr)
    first = len(fixed["prompt"]) - 1
    return np.asarray(jax.block_until_ready(out)[first : first + N_CHECK])


def compare(name: str, got, want, served) -> None:
    """`got` logits against `want` (the comparison's reference), and the
    served ids against `want` teacher-forced. Prints and enforces the
    stated tolerance."""
    import numpy as np

    if got.shape != want.shape or not np.isfinite(got).all():
        raise AssertionError(f"{name}: logits {got.shape} not finite/shaped")
    std = float(want[0].std())
    tol = LOGIT_TOL_STD * std
    err_first = float(np.abs(got[0] - want[0]).max())
    err_all = float(np.abs(got - want).max())
    err_rms = float(np.sqrt(np.mean((got - want) ** 2)))
    top_got, top_want = int(got[0].argmax()), int(want[0].argmax())
    log(f"{name}: first-token logits max |err| {err_first:.5f}, over "
        f"{N_CHECK} positions max {err_all:.5f} rms {err_rms:.5f}; "
        f"tolerance {tol:.5f} (= {LOGIT_TOL_STD} x logit std {std:.4f}); "
        f"top-1 {top_got} vs {top_want}")
    if top_got != top_want:
        raise AssertionError(f"{name}: first-token top-1 differs")
    if err_all > tol:
        raise AssertionError(f"{name}: logit error {err_all} > {tol}")
    served = served[:N_CHECK]
    exact, ties = check_tokens(served, want, tol)
    log(f"{name}: {exact}/{len(served)} served tokens equal the reference's "
        f"top-1, {ties} numerical ties within tolerance")


def traffic(server) -> None:
    """Requests 2-5: one streaming, then three concurrent with different
    prompt lengths, one of them past the largest prefill bucket."""
    port = server.server_address[1]
    engine = server.state.engine
    r = post_chat(port, "Stream me a short story about a chip.", stream=True)
    if r["n_deltas"] < 1:
        raise AssertionError("streaming request carried no content")
    log(f"request 2 (streaming): 200, {r['n_deltas']} deltas, "
        f"{r['seconds']:.2f} s")
    bucket = max(engine.prefill_buckets)
    prompts = [
        "Hi.",
        "Explain the KV cache. " * 6,
        # byte-level tokenizer: a token per character, so this prompt runs
        # past the largest prefill bucket into a second chunk
        ("long context " * bucket)[: bucket + 40],
    ]
    results: list = [None] * len(prompts)

    def run(i: int) -> None:
        try:
            results[i] = post_chat(port, prompts[i], stream=i % 2 == 0)
        except Exception as e:  # re-raised on the main thread below
            results[i] = e

    threads = [
        threading.Thread(target=run, args=(i,), name=f"chip-smoke-client-{i}")
        for i in range(len(prompts))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i, r in enumerate(results):
        if isinstance(r, Exception):
            raise r
        log(f"request {i + 3} (concurrent, {len(prompts[i])} chars): 200, "
            f"{r['seconds']:.2f} s")


def check_trace(path: str, n_requests: int, bucket: int) -> None:
    """Every request finished with the asked number of tokens, and one
    prompt crossed the largest prefill bucket."""
    from dllama_tpu.obs.trace import read_jsonl

    recs = read_jsonl(path)
    if len(recs) != n_requests:
        raise AssertionError(f"{len(recs)} trace records, sent {n_requests}")
    for r in recs:
        if r["n_completion"] != N_NEW or r["finish_reason"] != "length":
            raise AssertionError(f"request did not run to {N_NEW} tokens: {r}")
    for r in recs:
        log(f"trace: {r['n_prompt_tokens']} prompt tokens, lane {r['lane']}: "
            f"queue {r['queue_wait_s']:.2f} s, prefill {r['prefill_s']:.2f} s, "
            f"ttft {r['ttft_s']:.2f} s, total {r['total_s']:.2f} s")
    longest = max(r["n_prompt_tokens"] for r in recs)
    log(f"{len(recs)} requests answered, {N_NEW} tokens each; prompt tokens "
        f"{sorted(r['n_prompt_tokens'] for r in recs)} (largest prefill "
        f"bucket {bucket})")
    if longest <= bucket:
        raise AssertionError("no prompt crossed the largest prefill bucket")


def device_bytes(dev, key: str):
    stats = dev.memory_stats() or {}
    return stats.get(key)


LANES = 4


def pick_context(args, dev, preset: dict) -> int:
    if args.rehearse:
        return preset["seq_len"]
    return choose_context(device_bytes(dev, "bytes_limit"), preset, LANES)


def serve_fixed(args, model: str, tok: str, ctx: int, tp: int, extra=()):
    """Server up, admission-path programs compiled, the fixed prompt
    answered alone. Returns (server, thread, fixed request's ids)."""
    with Phase(f"tp={tp} load (file -> engine + server)"):
        server, thread = start_server(model, tok, ctx, LANES, tp, list(extra))
    engine = server.state.engine
    log(f"engine: weight_format {engine.weight_format}, "
        f"{engine.header.n_layers} layers, dim {engine.header.dim}, "
        f"seq_len {engine.header.seq_len}, lanes {engine.batch_size}, "
        f"kv {engine.kv_dtype.__name__}, tp {engine.tp}")
    if engine.weight_format != ("dense" if args.rehearse else "q40"):
        raise AssertionError(f"weight_format {engine.weight_format}")
    calls = record_decode(engine)
    dt = compile_and_check_programs(engine, server.state.scheduler.block_size)
    log(f"[phase] tp={tp} compile (admission-path programs): {dt:.1f} s")
    return server, thread, fixed_request(server, calls)


def run_one_chip(args, model: str, tok: str, preset: dict) -> None:
    import jax

    dev = jax.devices()[0]
    extra = ["--kv-dtype", args.kv_dtype] if args.kv_dtype else []
    server, thread, fixed = serve_fixed(
        args, model, tok, pick_context(args, dev, preset), 1, extra
    )
    engine = server.state.engine
    traffic(server)
    stop_server(server, thread)
    check_trace(trace_path(1), 5, max(engine.prefill_buckets))
    assert_kernels(engine, require=not args.rehearse)
    with Phase("reference comparison"):
        got = kernel_logits(engine, fixed, plain=False)
        want = kernel_logits(engine, fixed, plain=True)
        compare("kernel path vs plain jax.numpy path", got, want,
                fixed["served"])
    log(f"peak_bytes_in_use: {device_bytes(dev, 'peak_bytes_in_use')}")


def run_four_chips(args, model: str, tok: str, preset: dict) -> None:
    """tp=4 over the host's chips against tp=1 on chip 0: same file, same
    fixed prompt, through the server both times, one engine at a time."""
    import jax

    devs = jax.devices()
    if len(devs) < 4:
        raise RuntimeError(f"--chips 4 needs four devices, found {len(devs)}")
    ctx = pick_context(args, devs[0], preset)
    base = None  # tp=1's prompt, served ids and logits
    for tp in (1, 4):
        server, thread, fixed = serve_fixed(args, model, tok, ctx, tp)
        engine = server.state.engine
        stop_server(server, thread)
        assert_kernels(engine, require=not args.rehearse)
        # both engines are teacher-forced on tp=1's ids, so their logits
        # line up at every position even if the streams part on a tie
        logits = kernel_logits(engine, base or fixed, plain=False)
        in_use = [device_bytes(d, "bytes_in_use") for d in devs[:4]]
        log(f"tp={tp} bytes_in_use per device: {in_use}")
        if tp == 1:
            base = {**fixed, "logits": logits}
        else:
            big = [
                x for x in jax.tree.leaves((engine.params, engine.cache))
                if x.nbytes > 1 << 20
            ]
            spread = [
                x for x in big
                if len({s.device for s in x.addressable_shards}) == 4
                and x.addressable_shards[0].data.nbytes * 4 == x.nbytes
            ]
            log(f"tp=4: {len(spread)} of {len(big)} parameter/KV arrays "
                f"over 1 MiB are split four ways over 4 distinct devices")
            if len(spread) < len(big) - 3:  # embed and rope tables may not be
                raise AssertionError("parameters are not spread over 4 chips")
            if not args.rehearse and min(in_use) < 0.5 * max(in_use):
                raise AssertionError(f"device memory is lopsided: {in_use}")
            same = next(
                (i for i, (a, b) in enumerate(
                    zip(base["served"], fixed["served"])) if a != b),
                N_CHECK,
            )
            if same < N_CHECK:
                log(f"streams part at token {same}: tp=1 "
                    f"{base['served'][:N_CHECK]} tp=4 "
                    f"{fixed['served'][:N_CHECK]} (a tie, or compare fails)")
            # ids up to and including the first that differs are comparable
            compare("tp=4 vs tp=1", logits, base["logits"],
                    fixed["served"][: min(same + 1, N_CHECK)])
        free_engine(engine)
        del server, engine
        gc.collect()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--kv-dtype", default=None, choices=["bf16", "int8"],
                    help="one-chip run with this engine KV dtype")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny model on the CPU to rehearse the control "
                    "flow; exits non-zero and never prints a result")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        print(f"chip_smoke: no TPU (platform {dev.platform!r})", file=sys.stderr)
        return 2

    from dllama_tpu.models.synthetic import (
        PRESETS,
        write_synth_model,
        write_synth_tokenizer,
    )
    from dllama_tpu.parallel.mesh import enable_compilation_cache
    from dllama_tpu.utils import native

    import jaxlib

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not installed"
    log(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{libtpu_version}; devices {len(jax.devices())} x {dev.device_kind} "
        f"({dev.platform})")
    cache_dir = enable_compilation_cache()
    warm = os.path.isdir(cache_dir) and any(os.scandir(cache_dir))
    log(f"compile cache: {cache_dir} ({'warm' if warm else 'cold'} run)")
    log("native loader library: "
        + ("built/loaded from native/" if native.load_library() else
           "unavailable, pure-Python path"))

    preset = dict(
        PRESETS["tiny"], vocab_size=512, seq_len=1024, n_kv_heads=4
    ) if args.rehearse else dict(PRESETS["llama-8b"])
    os.makedirs(WORK, exist_ok=True)
    model = os.path.join(WORK, "model.m")
    tok = os.path.join(WORK, "tokenizer.t")
    with Phase("model write"):
        h = write_synth_model(
            model, preset, seed=args.seed, max_seq_len=4096
        )
        write_synth_tokenizer(tok, h.vocab_size)
    log(f"model: Llama-3.1-8B shapes (preset llama-8b), {h.n_layers} layers, "
        f"dim {h.dim}, ffn {h.hidden_dim}, vocab {h.vocab_size}, Q40, seed "
        f"{args.seed}, {os.path.getsize(model) / 1e9:.2f} GB"
        if not args.rehearse else f"model: tiny rehearsal preset {preset}")

    if args.chips == 4:
        run_four_chips(args, model, tok, preset)
    else:
        run_one_chip(args, model, tok, preset)
    os.remove(model)  # 6 GB of seeded noise: regenerated by the next run

    if args.rehearse:
        log("rehearsal reached the end: no result is printed off the chip")
        return 3
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
