"""dllama-compatible CLI: inference / chat / perplexity modes.

Keeps the reference's flag surface (src/app.cpp:24-135) so a
distributed-llama user can switch with the same command lines, with
TPU-native replacements where the concept changed:

    --workers h:p ...   ->  --tp N      (chips on the slice, not LAN hosts;
                                         --workers N is accepted as an alias)
    --nthreads          ->  accepted, ignored (XLA owns threading)
    --buffer-float-type ->  honored on multi-host launches (Q80 psum
                            payloads, parallel/collectives.py); moot on
                            single-host ICI where exact f32 is used
    --gpu-index/--gpu-segments -> rejected (the TPU *is* the device)

Per-token timing surface mirrors dllama.cpp:59-66,88-95 (Eval/Pred + Sync
per line, tokens/s summary blocks).
"""

from __future__ import annotations

import argparse
import sys
import time

import jax


def add_engine_args(p: argparse.ArgumentParser) -> None:
    """Engine/model flags shared by the CLI and the API server
    (reference flag surface: src/app.cpp:24-135)."""
    p.add_argument("--model", required=False)
    p.add_argument("--tokenizer", required=False)
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--topp", type=float, default=0.9)
    p.add_argument("--seed", type=int, default=int(time.time()))
    p.add_argument("--max-seq-len", type=int, default=0)
    p.add_argument("--buffer-float-type", default="q80",
                   choices=["q80", "f32"],
                   help="partial-sum all-reduce payload; applied on "
                        "multi-host (DCN) launches, where sync bytes "
                        "matter like the reference's 1 GbE clusters — "
                        "single-host ICI always syncs exact f32")
    p.add_argument("--nthreads", type=int, default=1, help="accepted for CLI parity; XLA owns threading")
    p.add_argument("--net-turbo", type=int, default=1, help="accepted for CLI parity")
    p.add_argument("--nbatches", "--n-batches", type=int, default=256, dest="nbatches",
                   help="the smallest prefill chunk bucket above 1 (default "
                        "256; the reference's CPU batch was 32). A chunk runs "
                        "the smallest bucket that covers it: 1, this one, 512, "
                        "and the program's own rungs at 128 and 256 where they "
                        "lie between (engine.prefill_ladder): (1, 256, 512) by "
                        "default, (1, 128, 256, 512) under 128, (1, 512) under 512")
    p.add_argument("--batch-size", type=int, default=1, dest="batch_size",
                   help="decode lanes: >1 lets the API server stream "
                        "multiple requests concurrently (per-lane "
                        "positions over the dp batch axis)")
    p.add_argument("--lane-block-size", type=int, default=8,
                   dest="lane_block_size", metavar="N",
                   help="decode tokens per lane-scheduler block (default "
                        "8) — with --admission-chunk this bounds the "
                        "worst-case inter-token gap at one chunk + one block")
    p.add_argument("--kv-page-size", type=int, default=0,
                   dest="kv_page_size", metavar="TOKENS",
                   help="paged-KV pool page size for cross-lane prefix "
                        "sharing on the lane-scheduler path (default 0 = "
                        "the manager's, 16); negative disables "
                        "the shared pool entirely (no prefix reuse)")
    p.add_argument("--kv-pool-pages", type=int, default=0,
                   dest="kv_pool_pages", metavar="N",
                   help="pages in the shared KV pool (default 0 = auto: "
                        "two sequences' worth, 2*seqLen/pageSize + 1)")
    p.add_argument("--kv-native", type=int, default=0,
                   dest="kv_native", metavar="0|1",
                   help="pool-native paged decode on the lane path: "
                        "lanes read/write KV through a per-lane page "
                        "table straight into the shared pool, so prefix "
                        "adoption is a refcount bump (zero device-copy "
                        "bytes on page-aligned matches) and publish an "
                        "ownership transfer (default 0 = per-lane slab KV "
                        "with adopt/publish page copies); requires "
                        "pp=1 and sp=1")
    p.add_argument("--max-streams", type=int, default=0,
                   dest="max_streams", metavar="N",
                   help="concurrent streams the scheduler may admit, "
                        "oversubscribing the decode lanes: when N > "
                        "batch-size and requests queue, the "
                        "most-progressed lane parks (KV published to "
                        "the shared pool, page list dropped) and the "
                        "parked stream later resumes via radix "
                        "re-match (default 0 = streams cap at the lane "
                        "count)")
    p.add_argument("--admission-chunk", type=int, default=0,
                   dest="admission_chunk", metavar="TOKENS",
                   help="max prompt tokens prefilled per scheduler tick "
                        "while admitting a request (default 0 = the "
                        "largest prefill bucket); smaller = tighter "
                        "inter-token gaps for active streams, larger = "
                        "faster TTFT for the incoming prompt")
    from .runtime.spec import DEFAULT_SPEC_K, SPEC_MODES

    p.add_argument("--speculation", default="off", choices=SPEC_MODES,
                   help="speculative decoding on the lane path: 'ngram' "
                        "drafts each greedy lane's continuation from its "
                        "own context (prompt lookup) and verifies k tokens "
                        "in one batched dispatch, keeping output "
                        "token-exact; 'shared' also publishes accepted "
                        "runs into a cross-lane store keyed by radix-tree "
                        "node identity, so lanes sharing a prefix draft "
                        "from each other's continuations; 'draft' "
                        "additionally runs a resident draft model "
                        "(--draft-model) when both n-gram sources run "
                        "dry; temperature>0 lanes fall back to the "
                        "normal decode block per lane (default off = "
                        "pure bypass)")
    p.add_argument("--spec-k", type=int, default=DEFAULT_SPEC_K,
                   dest="spec_k", metavar="K",
                   help="max draft tokens per speculative verify dispatch "
                        "(compiled shapes are power-of-2 bucketed; each "
                        "lane's drafter adapts below this on low "
                        f"acceptance; default {DEFAULT_SPEC_K})")
    p.add_argument("--draft-model", default=None, dest="draft_model",
                   metavar="PATH",
                   help="tiny same-tokenizer checkpoint loaded as the "
                        "resident draft model for --speculation draft: "
                        "runs k cheap greedy steps through its own "
                        "AOT-compiled draft_step program and its own KV "
                        "cache; every draft is verified by the target, so "
                        "output stays token-exact")
    p.add_argument("--tp", type=int, default=0, help="tensor-parallel chips (default: all)")
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel chips: shard the KV cache's "
                        "sequence axis for long contexts (ring prefill + "
                        "merged-stats decode); total chips = tp x sp")
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline stages: each holds nLayers/pp layers + "
                        "that range's KV cache — fits models past the "
                        "tp <= nKvHeads ceiling; composes with --tp "
                        "(stages of tp groups; chips = pp x tp), --dp, "
                        "--sp and --batch-size lanes")
    p.add_argument("--dp", type=int, default=1,
                   help="data-parallel chips: batch lanes shard across "
                        "dp (requires batch-size %% dp == 0); the "
                        "throughput axis for pp (docs/pp_decode_model.md)")
    p.add_argument("--workers", nargs="*", default=None, help="alias for --tp: pass a chip count (host:port lists are a LAN-cluster concept)")
    p.add_argument("--dtype", default="bf16", choices=["bf16", "f32"])
    p.add_argument("--kv-dtype", default=None,
                   choices=[None, "bf16", "f32", "int8"],
                   help="int8 = per-row quantized KV cache (~2x capacity "
                   "vs bf16; models/transformer.QuantKV)")
    from .tokenizer import CHAT_TEMPLATE_NAMES

    p.add_argument("--chat-template", default=None,
                   choices=[None, *CHAT_TEMPLATE_NAMES])
    p.add_argument("--gpu-index", type=int, default=None)
    p.add_argument("--gpu-segments", default=None)
    p.add_argument("--weight-format", default="auto",
                   choices=["auto", "q40", "dense"],
                   help="auto = on a TPU with a Q40 file the weights stay "
                        "quantized on device, the nibbles packed (0.625 "
                        "B/weight, unpacked in the kernel) wherever every "
                        "dense matmul's in dim is a multiple of 256 a shard "
                        "(the routed experts with them where one device "
                        "holds a sparse layer whole), int8 values elsewhere; "
                        "dense off a TPU. q40 keeps int8 values (1.125 "
                        "B/weight); dense dequantizes at load")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="write a jax.profiler trace of the run to DIR")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="append one JSON line per finished request/"
                        "generation (request id, queue wait, prefill "
                        "span, TTFT, token counts, finish reason) to "
                        "PATH (obs/trace.py)")
    p.add_argument("--postmortem-dir", default=None, metavar="DIR",
                   help="write the engine flight recorder's event ring "
                        "as a JSON postmortem into DIR when a step or "
                        "the lane-scheduler loop raises (obs/recorder.py)")
    p.add_argument("--timeline-out", default=None, metavar="PATH",
                   help="write the span timeline as Chrome-trace/Perfetto "
                        "JSON to PATH (obs/spans.py; the API server "
                        "rewrites it throttled per finished request, the "
                        "CLI writes it once at exit)")
    p.add_argument("--slo-ttft-ms", type=float, default=None,
                   help="TTFT SLO target in ms for the windowed "
                        "attainment/goodput gauges (obs/slo.py; unset = "
                        "no target)")
    p.add_argument("--slo-tpot-ms", type=float, default=None,
                   help="mean-TPOT SLO target in ms for the windowed "
                        "attainment/goodput gauges (obs/slo.py; unset = "
                        "no target)")
    p.add_argument("--series-retention", type=float, default=3600.0,
                   metavar="SECONDS",
                   help="in-process metrics time-series retention in "
                        "seconds (obs/timeseries.py; default 3600; the "
                        "sampling interval is env "
                        "DLLAMA_SERIES_INTERVAL_S; serves /v1/debug/series "
                        "and the /dashboard sparklines)")
    p.add_argument("--moe-decode-dedup", default="auto", nargs="?",
                   const="on",  # bare flag keeps its r4 meaning (force on)
                   choices=["auto", "on", "off"],
                   help="two-tier MoE decode: lax.cond into a small-grid "
                        "grouped kernel when concurrent lanes share most "
                        "experts (docs/moe_decode_dedup.md); auto = on at "
                        ">= 8 decode lanes (routing-correlation study, "
                        "scripts/moe_routing_sim.py). Acts only on a mesh "
                        "of more than one device: one device reads each "
                        "distinct expert once whatever this says")
    p.add_argument("--replica-id", default=None, dest="replica_id",
                   metavar="NAME",
                   help="name this server instance as a fleet replica: "
                        "reported in /v1/health and used as the chaos "
                        "op filter so a fault spec like "
                        "'sse_flush:op=r1:nth=3' targets one replica "
                        "(fleet/launch.py sets it; docs/fleet.md)")
    p.add_argument("--faults", default=None, metavar="SPEC",
                   help="arm the deterministic chaos plane with a fault "
                        "schedule, e.g. 'dispatch:p=0.05:seed=7,"
                        "kv_alloc:nth=12' (runtime/faults.py; env "
                        "DLLAMA_FAULTS; docs/resilience.md)")
    p.add_argument("--retry-max", type=int, default=3,
                   help="transient-dispatch retries before failing the "
                        "request (scheduler backoff loop; default 3; "
                        "0 disables)")
    p.add_argument("--retry-backoff-ms", type=int, default=5,
                   help="base backoff in ms between dispatch retries, "
                        "doubling per attempt (default 5)")
    p.add_argument("--max-queue-depth", type=int, default=0,
                   help="shed (429 + Retry-After) once this many requests "
                        "wait for a lane; priority 'low' sheds at half "
                        "this, 'high' at double (default 0 = unbounded)")
    p.add_argument("--admission-predict", action="store_true",
                   help="predictive admission control: estimate TTFT/TPOT "
                        "per request from the cost model + occupancy, "
                        "reject-or-queue infeasible deadline-hinted work "
                        "before admitting it, and order admission EDF-style "
                        "(runtime/admission.py; default off)")
    p.add_argument("--admission-max-wait-ms", type=int, default=30_000,
                   help="cap on the predicted queue-drain time advertised "
                        "via Retry-After on shed responses (default 30000)")
    p.add_argument("--deadline-default-ms", type=int, default=600_000,
                   help="effective deadline assigned to requests with no "
                        "deadline_ms/ttft_budget_ms hint, anchoring the "
                        "EDF admission order (default 600000)")
    p.add_argument("--deadline-priority-step-ms", type=int, default=60_000,
                   help="deadline offset per priority rung for unhinted "
                        "requests: high = -1 step, low = +1 step, so the "
                        "PR 12 priority ladder survives as EDF offsets "
                        "(default 60000)")
    p.add_argument("--sync-measure", default="auto", choices=["auto", "off"],
                   help="measure per-step collective time via a short "
                   "profiled re-run (multi-device greedy runs only; 'off' "
                   "skips the extra warmup steps)")


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="dllama-tpu",
        description="TPU-native distributed-llama: tensor-parallel LLM inference",
    )
    p.add_argument("mode", choices=["inference", "chat", "perplexity", "worker"])
    p.add_argument("--prompt", default=None)
    p.add_argument("--steps", type=int, default=0)
    add_engine_args(p)
    return p


def _resolve_tp(args) -> int:
    if args.gpu_index is not None or args.gpu_segments is not None:
        raise SystemExit(
            "--gpu-index/--gpu-segments are Vulkan-backend options; on TPU "
            "the accelerator is the only device (use --tp to scale chips)"
        )
    if args.tp:
        return args.tp
    if args.workers:
        if len(args.workers) == 1 and args.workers[0].isdigit():
            return int(args.workers[0])
        # host:port lists: map N workers -> N chips, like-for-like
        print(
            f"⚠️  --workers host:port lists are a LAN-cluster concept; using "
            f"tp={len(args.workers)} chips over ICI instead"
        )
        return len(args.workers)
    return 0  # auto: resolved against the model header in _load


def load_engine(args):
    import jax.numpy as jnp

    from .runtime.engine import InferenceEngine, prefill_ladder
    from .tokenizer import Tokenizer

    if not args.model or not args.tokenizer:
        raise SystemExit("--model and --tokenizer are required")
    dtype = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    kv_dtype = args.kv_dtype  # engine normalizes the name (incl. int8)
    tok = Tokenizer(args.tokenizer)
    tp = _resolve_tp(args)
    dp = getattr(args, "dp", 1) or 1
    sp = getattr(args, "sp", 1) or 1
    pp = getattr(args, "pp", 1) or 1
    if pp > 1 and tp == 0:
        tp = 1  # with --pp, scale tp explicitly (chips needed = pp x tp)
    if tp == 0:
        from .parallel.mesh import auto_tp

        tp = auto_tp(args.model, n_devices=len(jax.devices()) // (sp * dp))
    # the reference's q80 sync compression pays on DCN (multi-host), not
    # ICI: honor the flag only when processes > 1 (parallel/collectives.py)
    buffer_ft = (
        args.buffer_float_type if jax.process_count() > 1 else "f32"
    )
    engine = InferenceEngine(
        args.model,
        tokenizer=tok,
        tp=tp,
        dp=dp,
        sp=sp,
        pp=pp,
        dtype=dtype,
        kv_dtype=kv_dtype,
        max_seq_len=args.max_seq_len,
        temperature=args.temperature,
        topp=args.topp,
        seed=args.seed,
        prefill_buckets=prefill_ladder(args.nbatches),
        weight_format=args.weight_format,
        batch_size=getattr(args, "batch_size", 1),
        buffer_float_type=buffer_ft,
        moe_decode_dedup={"on": True, "off": False}.get(
            getattr(args, "moe_decode_dedup", "auto"), "auto"
        ),
    )
    h = engine.header
    print(f"💡 Arch: {h.arch.name}")
    print(f"💡 Dim: {h.dim}")
    print(f"💡 HeadDim: {h.head_dim}")
    print(f"💡 HiddenDim: {h.hidden_dim}")
    print(f"💡 VocabSize: {h.vocab_size}")
    print(f"💡 nLayers: {h.n_layers}")
    print(f"💡 nHeads: {h.n_heads}")
    print(f"💡 nKvHeads: {h.n_kv_heads}")
    if h.n_experts:
        print(f"💡 nExperts: {h.n_experts}")
        print(f"💡 nActiveExperts: {h.n_active_experts}")
    print(f"💡 SeqLen: {h.seq_len}")
    print(f"💡 Tp: {tp} chip(s) [{jax.default_backend()}]")
    if dp > 1:
        print(f"💡 Dp: {dp} lane shards")
    if sp > 1:
        print(f"💡 Sp: {sp} sequence shards")
    if pp > 1:
        print(f"💡 Pp: {pp} pipeline stages")
    if tok.vocab_size != h.vocab_size:
        print(
            f"⚠️  tokenizer vocab ({tok.vocab_size}) != model vocab "
            f"({h.vocab_size}); decoding may fail for out-of-range tokens"
        )
    wb = engine.weight_bytes
    print(
        f"💡 WeightFormat: {engine.weight_format} (resident: packed "
        f"{wb['packed'] / 1e9:.2f} GB, int8 {wb['int8'] / 1e9:.2f} GB, float "
        f"{wb['float'] / 1e9:.2f} GB; packed share of a decode step's "
        f"quantized bytes {wb['decode_packed_share']:.2f})"
    )
    from .utils.telemetry import memory_report

    mem = memory_report(
        engine.params, engine.cache, n_devices=tp * dp * sp * pp, tp=tp
    )
    mem.print()
    # startup roofline: the analytic HBM floor for decode next to the
    # memory report — what "as fast as the hardware allows" means in
    # ms/token for THIS model/format/layout (obs/cost.py)
    from .obs.cost import print_roofline_report
    from .obs.device import compare_with_analytic, sample_device_memory
    from .obs.recorder import get_recorder

    from .models.loader import weight_forms

    print_roofline_report(
        h,
        weight_forms(engine.reader.specs, engine.weight_format, engine.mesh.devices.size),
        tp=tp, pp=pp,
        spec_k=max(1, args.spec_k) if args.speculation != "off" else 0,
    )
    # live per-chip memory vs the analytic figure: a >10% gap logs a
    # warning (leak / unplanned replication / stale analytic model)
    compare_with_analytic(
        mem.per_device_bytes, sample_device_memory(engine.obs)
    )
    if getattr(args, "postmortem_dir", None):
        get_recorder().postmortem_dir = args.postmortem_dir
    tok.print_header()
    return engine, tok


def run_inference(args) -> None:
    """(reference: dllama.cpp:13-116)"""
    import jax.numpy as jnp

    from .utils.telemetry import profile

    engine, tok = load_engine(args)
    if args.prompt is None:
        raise SystemExit("Prompt is required")
    if args.steps == 0:
        raise SystemExit("Number of steps is required")
    tokens = tok.encode(args.prompt, is_start=True, add_special_tokens=True)
    if len(tokens) > engine.header.seq_len:
        raise SystemExit("The number of prompt tokens is greater than the sequence length")

    # estimated ICI collective traffic fills the reference's Sent/Recv
    # columns (socket bytes there; deterministic from the sharding layout
    # here). The logits all-gather happens once per forward, the per-layer
    # all-reduces once per token.
    from .utils.telemetry import ici_traffic_per_token as _ici
    from .utils.telemetry import measure_sync_ms

    # q80-compressed sync moves 1.125 B/elem (int8 + f32/32 scales);
    # exact f32 psum moves 4. The pp hand-offs always ride uncompressed
    # in the model activation dtype.
    act_bytes = 1.125 if engine._sync_quant else 4.0
    pp_bytes = float(jnp.dtype(engine.dtype).itemsize)
    per_tok_bytes = _ici(
        engine.header, engine.tp, activation_bytes=act_bytes,
        include_logits=False, pp=engine.pp, pp_activation_bytes=pp_bytes,
    )
    logits_bytes = (
        _ici(
            engine.header, engine.tp, activation_bytes=act_bytes,
            pp=engine.pp, pp_activation_bytes=pp_bytes,
        )
        - per_tok_bytes
    )

    # MEASURED sync (collective) time per step type — the reference's
    # per-step sync clock (src/nn/nn-executor.cpp:158-163). Profiled
    # once per step type by re-running the upcoming step at a fixed
    # position (idempotent KV rewrites), then printed on every line;
    # Sent/Recv stay the deterministic sharding-layout estimate (the
    # reference counts actual socket bytes, nn-network.cpp:524-539 —
    # on-chip collectives have no socket to count, so the estimate IS
    # the traffic model). Greedy only: the sampled path's host RNG
    # state would advance during measurement runs.
    measure = (
        engine.mesh.devices.size > 1
        and not args.profile
        and engine.temperature == 0.0
        and getattr(args, "sync_measure", "auto") != "off"
    )
    sync_eval = sync_pred = None

    # one JSONL record for the whole generation, same schema as the API
    # server's --trace-out sink (obs/trace.py)
    from .obs.trace import NULL_SPAN, Tracer

    tracer = (
        Tracer(sink_path=args.trace_out)
        if getattr(args, "trace_out", None)
        else None
    )
    span = tracer.span(path="cli") if tracer is not None else NULL_SPAN
    span.mark_admitted()

    # span timeline of the run (--timeline-out; obs/spans.py): the engine
    # records prefill/decode_step spans itself, this one is the request-
    # attributed envelope the per-request summary hangs off
    from .obs.spans import get_span_tracker

    spans = get_span_tracker()
    gen_span = spans.begin(
        "generate", component="cli", request_id=span.request_id,
        n_prompt=len(tokens), steps=args.steps,
    )

    print(args.prompt)
    with profile(args.profile):
        if measure:
            # steps=1: ONE extra prefill (idempotent row rewrites), so
            # TTFT pays 2x, not 4x; it also warms the compile, so the
            # Eval ms below reports warm-program time
            sync_eval = measure_sync_ms(
                lambda: engine.prefill(tokens), steps=1
            )
        eval_stats = engine.prefill(tokens)
        span.set_prefill_seconds(eval_stats.time_ms / 1000.0)
        eval_kb = (
            per_tok_bytes * max(eval_stats.n_tokens, 1) + logits_bytes
        ) // 1024
        eval_sync = f"{sync_eval:5.1f}" if sync_eval is not None else "    0"
        print(
            f"🔷️ Eval{eval_stats.time_ms:5.0f} ms Sync{eval_sync} ms | "
            f"Sent{eval_kb:6d} kB Recv{eval_kb:6d} kB | "
            f"({eval_stats.n_tokens} tokens)"
        )
        tok.reset_decoder()
        pos = len(tokens) - 1
        token = tokens[-1]
        max_pos = min(engine.header.seq_len, args.steps)
        pred_ms = 0.0
        n_pred = 0
        while pos < max_pos:
            if measure and sync_pred is None:
                # rewriting the same row: the real step below repeats it
                sync_pred = measure_sync_ms(
                    lambda: engine.decode_step(token, pos)
                )
            token, stats = engine.decode_step(token, pos)
            pos += 1
            pred_ms += stats.time_ms
            n_pred += 1
            if n_pred == 1:
                span.mark_first_token()
            piece = tok.decode(token)
            step_kb = (per_tok_bytes + logits_bytes) // 1024
            pred_sync = (
                f"{sync_pred:5.1f}" if sync_pred is not None else "    0"
            )
            print(
                f"🔶 Pred{stats.time_ms:5.0f} ms Sync{pred_sync} ms | "
                f"Sent{step_kb:6d} kB Recv{step_kb:6d} kB | "
                f"{piece if piece is not None else chr(126)}"
            )
            sys.stdout.flush()

    spans.end(gen_span, n_completion=n_pred)
    span.finish("length", n_prompt=len(tokens), n_completion=n_pred)
    if tracer is not None:
        tracer.close()
    if getattr(args, "timeline_out", None):
        n_spans = spans.export_file(args.timeline_out)
        print(f"🧭 timeline: {n_spans} spans -> {args.timeline_out}")

    n_eval = max(len(tokens) - 1, 1)
    print()
    print("Evaluation")
    print(f"   nBatches: {args.nbatches}")
    print(f"    nTokens: {n_eval}")
    print(
        f"   tokens/s: {n_eval * 1000 / max(eval_stats.time_ms, 1e-9):3.2f} "
        f"({eval_stats.time_ms / n_eval:3.2f} ms/tok)"
    )
    print("Prediction")
    print(f"    nTokens: {n_pred}")
    if n_pred:
        print(
            f"   tokens/s: {n_pred * 1000 / max(pred_ms, 1e-9):3.2f} "
            f"({pred_ms / n_pred:3.2f} ms/tok)"
        )


def run_chat(args) -> None:
    """Interactive REPL (reference: dllama.cpp:174-258)."""
    from .tokenizer import (
        CHAT_TEMPLATE_NAMES,
        ChatItem,
        ChatTemplateGenerator,
        ChatTemplateType,
        EosDetector,
        EosResult,
    )

    engine, tok = load_engine(args)
    eos_piece = (
        tok.vocab[tok.eos_token_ids[0]].decode("utf-8", "replace")
        if tok.eos_token_ids
        else ""
    )
    ttype = (
        CHAT_TEMPLATE_NAMES[args.chat_template]
        if args.chat_template
        else ChatTemplateType.UNKNOWN
    )
    gen = ChatTemplateGenerator(ttype, tok.chat_template, eos_piece)
    stops = [tok.vocab[t].decode("utf-8", "replace") for t in tok.eos_token_ids]
    pos = 0
    is_start = True
    print("💬 Chat mode. Type your message (Ctrl-D to exit).")
    while True:
        try:
            user = input("\n👱 You: ")
        except EOFError:
            break
        if not user.strip():
            continue
        chat = gen.generate([ChatItem("user", user)], append_generation_prompt=True)
        tokens = tok.encode(chat.content, is_start=is_start, add_special_tokens=True)
        is_start = False
        # Context exhaustion: stop explicitly instead of silently generating
        # zero tokens forever (the reference prints an explicit stop when the
        # window fills, src/dllama.cpp:242-253).
        if pos + len(tokens) >= engine.header.seq_len:
            print(
                f"\n🚫 Context window full ({engine.header.seq_len} tokens); "
                "restart the chat to continue."
            )
            break
        detector = EosDetector(
            tok.eos_token_ids, stops, padding_left=2, padding_right=2
        )
        print("\n🤖 Assistant: ", end="", flush=True)
        tok.reset_decoder()

        def on_token(t: int):
            piece = tok.decode(t)
            res = detector.append(t, piece)
            if res == EosResult.NOT_EOS:
                delta = detector.get_delta()
                if delta:
                    print(delta, end="", flush=True)
                detector.reset()
            elif res == EosResult.EOS:
                delta = detector.get_delta()
                if delta:
                    print(delta, end="", flush=True)
                return False
            return True

        try:
            out, _, _ = engine.generate(
                tokens,
                max_steps=engine.header.seq_len - 1 - pos,
                on_token=on_token,
                start_pos=pos,
            )
        except KeyboardInterrupt:
            raise
        except Exception as e:
            # a failed dispatch dropped the donated KV cache
            # (engine._cache_guard); the conversation context is gone, so
            # restart the session instead of crashing the REPL (the
            # reference's server retries whole-app init the same way,
            # src/dllama-api.cpp:616-628 — its CLI just dies)
            print(f"\n🚫 Generation failed ({e}); conversation reset.")
            engine.reset()
            pos, is_start = 0, True
            continue
        pos += len(tokens) - 1 + len(out)
        print()


def run_perplexity(args) -> None:
    """Teacher-forced NLL over the prompt — the numerical-quality oracle.
    Scored chunk-by-chunk on device through the engine's bucketed prefill
    programs, shipping one scalar per chunk instead of a [T, vocab] logits
    tensor (the reference walks the prompt in nBatches chunks and reads
    the logits pipe per batch, src/dllama.cpp:132-172)."""
    engine, tok = load_engine(args)
    if args.prompt is None:
        raise SystemExit("Prompt is required")
    tokens = tok.encode(args.prompt, is_start=True, add_special_tokens=True)
    if len(tokens) < 2:
        raise SystemExit("Prompt too short for perplexity")
    nll, ppl, n_scored = engine.perplexity(tokens)
    print(f"    nTokens: {len(tokens)}")
    print(f"    nScored: {n_scored}")
    print(f"        nll: {nll:.4f}")
    print(f" perplexity: {ppl:.4f}")


def main(argv=None) -> None:
    from .parallel.mesh import enable_compilation_cache

    enable_compilation_cache()
    args = _build_parser().parse_args(argv)
    if args.mode == "worker":
        raise SystemExit(
            "worker mode is a LAN-cluster concept: under SPMD every chip runs "
            "the same program — launch the root command with --tp N instead "
            "(multi-host: one identical launch per host, see "
            "dllama_tpu.parallel.mesh.initialize_multihost)"
        )
    if args.mode == "inference":
        run_inference(args)
    elif args.mode == "chat":
        run_chat(args)
    elif args.mode == "perplexity":
        run_perplexity(args)


if __name__ == "__main__":
    main()
