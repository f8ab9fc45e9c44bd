"""The system under test: the API server a deployment starts, in this process.

`api_server.serve_from_args` on the program's own parser, on a thread on an
ephemeral port; one process owns the chip, so the load generator, the server
and afterwards the reference all live here.
"""

from __future__ import annotations

import os
import threading


class Served:
    def __init__(self, cfg: dict, model: str, tok: str, run_dir: str,
                 timeline: bool = False):
        from dllama_tpu.runtime.api_server import build_arg_parser, serve_from_args

        sv = cfg["serving"]
        self.trace_path = os.path.join(run_dir, "server_trace.jsonl")
        self.timeline_path = os.path.join(run_dir, "timeline.json") if timeline else None
        for p in (self.trace_path, self.timeline_path):
            if p and os.path.exists(p):
                os.remove(p)
        argv = [
            "--model", model, "--tokenizer", tok,
            "--max-seq-len", str(sv["max_seq_len"]),
            "--batch-size", str(sv["lanes"]), "--tp", str(sv["tp"]),
            "--host", "127.0.0.1", "--port", "0",
            "--trace-out", self.trace_path, *sv["args"],
        ]
        if self.timeline_path:
            argv += ["--timeline-out", self.timeline_path]
        self.argv = argv
        self.server = serve_from_args(build_arg_parser().parse_args(argv))
        self.state = self.server.state
        self.engine = self.state.engine
        self.port = self.server.server_address[1]
        self.thread = threading.Thread(
            target=self.server.serve_forever, daemon=True, name="bench-http"
        )
        self.thread.start()

    def compile_admission_path(self) -> None:
        """Wait for the programs the scheduler builds at start-up; a program
        a prefetch thread could not build is an error here, not a stall in
        the window."""
        self.engine.rehearse_admission(self.state.scheduler.block_size, wait=True)
        failed = [k for k, o in self.engine._compile_origin.items()
                  if o == "prefetch-failed"]
        if failed:
            raise RuntimeError(f"programs failed to compile: {failed}")

    def build_programs(self, longest_prompt: int, deepest: int) -> None:
        """Build the prefill and decode programs for prompts up to
        `longest_prompt` tokens and positions up to `deepest`. Prefix adoption
        shifts where chunks start, so which (bucket, window) pairs occur
        cannot be told from the lengths alone: every bucket is built at every
        window up to the deepest a padded chunk can touch, through the
        engine's own builders (it has no public call for this: PERF.md)."""
        e = self.engine
        self.compile_admission_path()
        ctx, block = e.header.seq_len, self.state.scheduler.block_size
        fill_window = e._attn_window(min(ctx, longest_prompt + max(e.prefill_buckets)))
        last_window = e._attn_window(min(ctx, deepest + block))
        window = e._attn_window(1)
        while True:
            if window <= fill_window:
                for bucket in e.prefill_buckets:
                    e._lane_prefill_fn(bucket, window=window, origin="prefetch")
            e._lane_decode_fn(block, window, origin="prefetch")
            if window >= max(fill_window, last_window):
                return
            window = e._attn_window(window + 1)

    def prompt_ids(self, content: str) -> list[int]:
        """The ids the scheduler feeds for a one-message chat."""
        from dllama_tpu.tokenizer import ChatItem

        prompt = self.state.template.generate(
            [ChatItem("user", content)], append_generation_prompt=True
        )
        return self.state.tokenizer.encode(
            prompt.content, is_start=True, add_special_tokens=True
        )

    def stop(self) -> None:
        self.server.shutdown()
        self.server.server_close()
        self.thread.join(timeout=60)
        if self.thread.is_alive():
            raise RuntimeError("HTTP server thread did not stop")
        tracer = getattr(self.state, "tracer", None)
        if tracer is not None:
            tracer.close()

    def free(self) -> None:
        """Give the engine's device memory back (the metrics registry and the
        recorder keep the object alive past `del`), for the reference."""
        import jax

        e = self.engine
        for x in jax.tree.leaves((e.params, e.cache, e.kv_pool)):
            x.delete()
