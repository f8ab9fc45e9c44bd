"""One clock: the scheduler thread's spans inside a profiler trace.

A tiny-model server with four lanes streams to six clients at once under
`jax.profiler.start_trace` on the CPU. The trace's host plane then has to
hold the `dllama.*` annotations of the scheduler thread, nested as begun
and with the `mono_ns` anchor, the span ring has to cover each tick with
leaf spans, and the streamed `--timeline-out` file has to hold every span.
The compiled lane programs have to carry the layer scopes in `op_name`.
"""

import glob
import json
import os
import re
import statistics
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from dllama_tpu.formats import FloatType
from dllama_tpu.formats.model_file import LlmArch
from dllama_tpu.obs.spans import read_timeline
from dllama_tpu.runtime.api_server import serve
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.tokenizer import Tokenizer

from helpers import make_tiny_model, make_tiny_tokenizer

CFG = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
           head_dim=16, vocab_size=288, seq_len=384)
TICK = "dllama.scheduler.sched_tick"


def _engine(d, arch=LlmArch.LLAMA, cfg=CFG, lanes=4):
    mp, tp_ = str(d / "m.m"), str(d / "t.t")
    make_tiny_model(mp, arch=arch, weight_type=FloatType.Q40, cfg=cfg)
    make_tiny_tokenizer(tp_, chat_template="<|start_header_id|>")
    tok = Tokenizer(tp_)
    engine = InferenceEngine(
        mp, tokenizer=tok, tp=1, dtype=jnp.float32, temperature=0.0, seed=3,
        batch_size=lanes,
    )
    return engine, tok


def _stream(url, i, n_tokens):
    req = urllib.request.Request(
        url + "/v1/chat/completions",
        data=json.dumps({
            "messages": [{"role": "user", "content": f"hello number {i}"}],
            "max_tokens": n_tokens, "temperature": 0, "stream": True,
        }).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=300) as r:
        r.read()


def _scheduler_events(trace_dir):
    """(name, start_ns, end_ns, stats) of the `dllama.*` events of the host
    thread that holds the scheduler's ticks."""
    (path,) = glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                      for e in line.events if e.name.startswith("dllama.")]
            if any(name == TICK for name, *_ in events):
                return events
    return []


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """Six concurrent streams of 120 tokens through four lanes (two wait
    for a lane, so the scheduler runs a block ahead) under a profiler
    session."""
    d = tmp_path_factory.mktemp("tracing")
    engine, tok = _engine(d)
    recorded = engine.recorder.total_recorded  # the recorder is the process's
    timeline = str(d / "timeline.json")
    srv = serve(engine, tok, host="127.0.0.1", port=0, timeline_out=timeline)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    _stream(url, 99, 16)  # compile the programs outside the trace
    # the scheduler runs one block ahead: let it collect what is in flight
    # and go to its wait before the session and the count of spans begin
    sched = srv.state.scheduler
    deadline = time.time() + 60
    while time.time() < deadline and (
            sched._flight is not None or any(sched.lanes) or sched.admitting):
        time.sleep(0.01)
    time.sleep(0.3)
    spans = srv.state.spans
    first = spans.total_recorded
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(d / "profile"), profiler_options=options)
    clients = [threading.Thread(target=_stream, args=(url, i, 120))
               for i in range(6)]
    for c in clients:
        c.start()
    for c in clients:
        c.join(timeout=300)
    jax.profiler.stop_trace()
    assert not any(c.is_alive() for c in clients)
    n_new = spans.total_recorded - first
    ring = spans.completed()[-n_new:]
    srv.shutdown()
    srv.server_close()
    return {"events": _scheduler_events(str(d / "profile")), "ring": ring,
            "timeline": timeline, "tracker": spans, "engine": engine, "recorded": recorded}


def _inside(events, outer):
    return [e for e in events if outer[1] <= e[1] and e[2] <= outer[2] and e is not outer]


def test_scheduler_spans_are_in_the_profile_nested_as_begun(traced_run):
    events = traced_run["events"]
    names = {name for name, *_ in events}
    assert {TICK, "dllama.scheduler.emit", "dllama.scheduler.step_prep",
            "dllama.engine.dispatch_prep", "dllama.engine.decode_lanes",
            "dllama.engine.decode_lanes.device"} <= names
    # spans that end on another thread, or outlive their tick, stay out
    assert not names & {"dllama.scheduler.queue", "dllama.scheduler.decode"}
    ticks = [e for e in events if e[0] == TICK]
    # the session ends when the last client has its `done`, which the last
    # tick sends from inside its `emit`: where that tick is cut off, what it
    # had completed is in the profile without it
    cut = max(t[2] for t in ticks)
    events = [e for e in events if e[1] < cut]
    for name in ("emit", "step_prep"):
        for e in (e for e in events if e[0] == f"dllama.scheduler.{name}"):
            assert any(t[1] <= e[1] and e[2] <= t[2] for t in ticks), e
    # a block's span is its call (the enqueue and the transfer of its host
    # arrays); its `.device` wait is the collect's, a tick later, and the
    # loop runs one block ahead: in most ticks the next block's call comes
    # BEFORE the wait for the one in flight, and `emit` after that wait
    blocks = [e for e in events if e[0] == "dllama.engine.decode_lanes"]
    waits = [e for e in events if e[0] == "dllama.engine.decode_lanes.device"]
    assert len(blocks) >= 20 and abs(len(blocks) - len(waits)) <= 1
    ahead = 0
    for block in blocks:
        (tick,) = [t for t in ticks if t[1] <= block[1] and block[2] <= t[2]]
        inside = sorted(_inside(events, tick), key=lambda e: e[1])
        order = [e[0].rsplit(".", 1)[-1] for e in inside if e[1] <= block[1]
                 and e[0].rsplit(".", 1)[-1] in ("step_prep", "dispatch_prep", "decode_lanes")]
        assert order[-3:] == ["step_prep", "dispatch_prep", "decode_lanes"]
        assert block[3]["n_live"] >= 1 and block[3]["n_steps"] >= 1
        assert not [w for w in waits if block[1] <= w[1] < block[2]]
        later = [e[0].rsplit(".", 2)[-1] for e in inside if e[1] >= block[2]
                 and e[0].endswith(("decode_lanes.device", "scheduler.emit"))]
        ahead += later[:2] == ["device", "emit"]
    # (until a request waits for a lane, and after the last has got one,
    # the tick is dispatch, then collect)
    assert ahead >= 12
    for wait in waits:
        (tick,) = [t for t in ticks if t[1] <= wait[1] and wait[2] <= t[2]]
        (collect,) = [e for e in _inside(events, tick)
                      if e[0] == "dllama.scheduler.collect" and e[1] <= wait[1] <= e[2]]
        assert wait[2] <= collect[2]
        assert any(e[0] == "dllama.scheduler.emit" and e[1] >= collect[2]
                   for e in _inside(events, tick))


def test_mono_ns_anchors_the_host_clock_to_the_profile(traced_run):
    """The profile's clock less `time.monotonic_ns()` at each tick's begin
    is one offset: at least 20 ticks agree within 1 ms."""
    offsets = [start - stats["mono_ns"]
               for name, start, _, stats in traced_run["events"] if name == TICK]
    assert len(offsets) >= 20
    assert max(offsets) - min(offsets) < 1e6, (min(offsets), max(offsets))


def test_leaf_spans_cover_the_scheduler_tick(traced_run):
    """The rule: inside a `sched_tick`, at least 95% of its wall time lies
    inside the spans begun within it (median over the run's ticks)."""
    ring = traced_run["ring"]
    ticks = [s for s in ring if s["name"] == "sched_tick"]
    assert len(ticks) >= 20
    shares = []
    for tick in ticks:
        lo, hi = tick["t0"], tick["t0"] + tick["dur_s"]
        inner = sorted((max(s["t0"], lo), min(s["t0"] + s["dur_s"], hi)) for s in ring
                       if s is not tick
                       and s["name"] not in ("queue", "decode", "device_drained")
                       and lo <= s["t0"] and s["t0"] + s["dur_s"] <= hi)
        covered, end = 0.0, lo
        for a, b in inner:
            covered += max(0.0, b - max(a, end))
            end = max(end, b)
        shares.append(covered / tick["dur_s"])
    assert statistics.median(shares) >= 0.95, sorted(shares)[:5]


def test_streamed_timeline_holds_every_span_on_the_recorders_clock(traced_run):
    tracker = traced_run["tracker"]
    meta, spans = read_timeline(traced_run["timeline"])
    assert meta["epoch_monotonic"] == tracker.epoch_monotonic
    assert meta["epoch_unix"] == tracker.epoch_unix
    by_name = {}
    for s in spans:
        by_name[s["name"]] = by_name.get(s["name"], 0) + 1
    ring = {}
    for s in traced_run["ring"]:
        ring[s["name"]] = ring.get(s["name"], 0) + 1
    for name in ("sched_tick", "emit", "dispatch_prep", "decode_lanes",
                 "begin_admission", "finish", "sched_wait"):
        assert by_name[name] >= ring[name] > 0, name
    # a tick's mono_ns attribute is its own start on the recorder's clock
    tick = next(s for s in spans if s["name"] == "sched_tick")
    start = meta["epoch_monotonic"] + tick["ts"] / 1e6
    assert abs(start - tick["args"]["mono_ns"] / 1e9) < 1e-3


def test_streamed_timeline_nests_by_parent_off_the_profiler(traced_run):
    """Every span says its thread and the span it ran under, so the
    timeline nests without the profile: a child lies inside its parent on
    its parent's thread, and a tick's self time is what its children leave.
    A tick also says whether the profiler was collecting when it began."""
    _, spans = read_timeline(traced_run["timeline"])
    by_id = {s["args"]["id"]: s for s in spans}
    assert len(by_id) == len(spans)
    ticks = [s for s in spans if s["name"] == "sched_tick"]
    (thread,) = {t["args"]["thread"] for t in ticks}
    parents = {}
    for s in spans:
        parent = by_id.get(s["args"].get("parent"))
        if parent is None:
            assert s["args"].get("parent") is None
            continue
        assert parent["args"]["thread"] == s["args"]["thread"]
        assert parent["ts"] - 0.01 <= s["ts"]
        assert s["ts"] + s["dur"] <= parent["ts"] + parent["dur"] + 0.01
        parents.setdefault(s["name"], set()).add(parent["name"])
    assert parents["emit"] == parents["step_prep"] == {"sched_tick"}
    assert parents["finish"] == {"emit"} and parents["decode_lanes.device"] == {"collect"}
    assert parents["decode_lanes"] == parents["collect"] == {"sched_tick"}
    assert "sched_tick" not in parents and "sched_wait" not in parents
    # spans that end on another thread have a thread and no parent
    assert all(("parent" in s["args"]) == (s["name"] not in ("queue", "decode", "device_drained"))
               for s in spans)
    self_us = [t["dur"] - sum(s["dur"] for s in spans if s["args"].get("parent") == t["args"]["id"])
               for t in ticks]
    assert all(us >= -0.01 for us in self_us)
    # the warm-up request's ticks ran before the session, the streams' inside
    profiled = [t["args"]["profiled"] for t in ticks]
    assert set(profiled) == {0, 1} and profiled == sorted(profiled)
    assert sum(profiled) >= 20 and thread != threading.get_ident()


def test_drained_spans_lie_between_a_programs_end_and_the_next_dispatch(traced_run):
    """Served streams: every `device_drained` begins where the completion
    stamps say the program before left the device (`device_done`'s `at`)
    and ends where the dispatch named by `before` begins, on the scheduler's
    thread; no dispatch but a pool copy lies inside it, and no program runs
    in it; the counter holds their sum. The loop runs one block ahead, and
    the recorder holds one `device_done` for every dispatch of the lane
    path, in dispatch order, a block dispatched ahead among them."""
    meta, spans = read_timeline(traced_run["timeline"])
    (thread,) = {s["args"]["thread"] for s in spans if s["name"] == "sched_tick"}
    drained = [s for s in spans if s["name"] == "device_drained"]
    copies = {"kv_adopt", "kv_publish", "kv_page_copy"}
    steps = {"decode_lanes", "prefill_lane_chunk"}
    dispatches = [s for s in spans if s["pid"] == 2 and s["name"] in steps | copies]
    assert {"kv_publish", "kv_adopt"} <= {s["name"] for s in dispatches}
    recorder, since = traced_run["engine"].recorder, traced_run["recorded"]
    done = [e for e in recorder.events("device_done") if e["seq"] > since]
    began = [e for e in recorder.events("step_dispatch")
             if e["seq"] > since and e["step"] in steps]
    assert [e["step"] for e in done] == [e["step"] for e in began][: len(done)]
    assert len(began) - len(done) <= 1 and not [e for e in done if "error" in e]
    assert [e["program"] for e in done] == list(
        range(done[0]["program"], done[0]["program"] + len(done)))
    assert sum(e["ahead"] for e in began if e["step"] == "decode_lanes") >= 12
    assert {e["dry"] for e in began} == {0, 1}
    assert not any("drained" in key for e in began for key in e)
    # on the timeline's clock, in microseconds
    ends = [(e["at"] - meta["epoch_monotonic"]) * 1e6 for e in done]
    enqueues = sorted(s["ts"] for s in dispatches if s["name"] not in copies)
    assert len(drained) >= 1
    for d in drained:
        assert d["args"]["thread"] == thread and d["pid"] == 2
        lo, hi = d["ts"], d["ts"] + d["dur"]
        assert any(abs(at - lo) < 0.01 for at in ends), d
        assert any(abs(s["ts"] - hi) < 0.01 and s["name"] == d["args"]["before"]
                   for s in dispatches), d
        within = {s["name"] for s in dispatches if lo - 0.01 <= s["ts"] < hi - 0.01}
        assert within <= copies, (d, within)
        # the program dispatched at its end is the next to leave the device
        assert not any(lo + 0.01 < at < hi for at in ends), d
    # what the stamps say of a program and of the wait before it are one account
    assert [e["dry_ms"] for e in done if e["dry_ms"]] == pytest.approx(
        [d["dur"] / 1e3 for d in sorted(drained, key=lambda d: d["ts"])], abs=2e-3)
    assert enqueues == sorted(enqueues) and len(enqueues) == len(began)
    # the counters are the process's own: other engines may have added to them
    engine = traced_run["engine"]
    for before in {d["args"]["before"] for d in drained}:
        seconds = sum(d["dur"] for d in drained if d["args"]["before"] == before) / 1e6
        assert engine._m_drained.labels(before=before).value >= seconds - 1e-6 > 0
    for step in steps:
        seconds = sum(e["device_ms"] for e in done if e["step"] == step) / 1e3
        assert engine._m_busy.labels(step=step).value >= seconds - 1e-3 > 0
    assert sum(engine._m_dispatches.labels(step="decode_lanes", device=d).value
               for d in ("dry", "busy")) >= sum(e["step"] == "decode_lanes" for e in began)


@pytest.fixture(scope="module", params=["dense", "moe"])
def lane_programs(request, tmp_path_factory):
    d = tmp_path_factory.mktemp(f"scopes-{request.param}")
    if request.param == "moe":
        engine, _ = _engine(d, arch=LlmArch.QWEN3_MOE, cfg=None, lanes=2)
    else:
        engine, _ = _engine(d, lanes=2)
    window = engine._attn_window(1)
    return request.param, {
        "decode": engine._lane_decode_fn(4, window).as_text(),
        "prefill": engine._lane_prefill_fn(8, window=window).as_text(),
    }


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_lane_programs_carry_the_layer_scopes(lane_programs, program):
    kind, texts = lane_programs
    op_names = set(re.findall(r'op_name="([^"]*)"', texts[program]))
    ffn = "moe" if kind == "moe" else "ffn"
    for scope in ("attn", ffn, "kv_write", "norm"):
        # (a chunk's expert block is a loop over the live lanes inside the scan's body)
        assert any(re.search(rf"/layers/((while|body|closed_call)/)*{scope}(/|$|;)", n)
                   for n in op_names), scope
    if program == "decode":  # a prefill chunk's logits are dead code
        assert any("/logits_head" in n for n in op_names)
        assert any("/sample" in n for n in op_names)


@pytest.fixture(scope="module")
def held_share(tmp_path_factory):
    """Four lanes of a tiny `afmoe` that holds 4 of the 16 experts its
    router scores, chunks of 64 rows: 128 pairs a chunk and layer, of which
    the landed form holds 48 where the row tile is 16 and any rows saved
    count (both set here: the served constants leave a call this small one
    form)."""
    from helpers import make_tiny_afmoe

    from dllama_tpu.ops import moe_kernel as mk

    d = tmp_path_factory.mktemp("forms")
    was = mk._HELD_ROWS, mk._LANDED_MIN_SAVED
    mk._HELD_ROWS, mk._LANDED_MIN_SAVED = 16, 0
    try:
        assert mk._landed_cap(128, mk._held_rows(128, False), 4, 16) == 48
        make_tiny_afmoe(str(d / "m.m"), num_routed_experts=16)
        make_tiny_tokenizer(str(d / "t.t"), chat_template="<|start_header_id|>", pad_to=512)
        tok = Tokenizer(str(d / "t.t"))
        engine = InferenceEngine(
            str(d / "m.m"), tokenizer=tok, tp=1, dtype=jnp.float32, temperature=0.0,
            seed=3, batch_size=4, prefill_buckets=(1, 64), max_seq_len=256)
        yield engine, tok
    finally:
        mk._HELD_ROWS, mk._LANDED_MIN_SAVED = was


def _forms(engine):
    return {form: engine._m_moe_forms.labels(program="chunk", form=form).value
            for form in ("landed", "whole")}


def test_a_chunks_forms_are_counted_where_the_next_block_is_collected(held_share, monkeypatch):
    """A chunk program's form counts stay on the device, un-read, until a
    decode block enqueued after it has been read back: that collect adds
    them to `dllama_moe_block_forms_total` and the recorder, and reads
    nothing back of its own (`_read_back`: the one wait of a collect). A
    chunk enqueued behind the block waits for the next."""
    engine, _ = held_share
    assert engine._counts_forms
    n_expert_layers = 4
    waits = []
    read_back = engine._read_back
    monkeypatch.setattr(
        engine, "_read_back", lambda step, *a, **kw: waits.append(step) or read_back(
            step, *a, **kw))
    before, n0 = _forms(engine), len(engine.recorder.events("moe_block_forms"))
    engine.prefill_lane(0, list(range(5, 105)))  # 99 rows: a chunk of 64, one of 35 padded
    assert len(engine._chunk_forms) == 2 and not waits
    assert _forms(engine) == before
    assert len(engine.recorder.events("moe_block_forms")) == n0
    block = engine.dispatch_lanes([7, 0, 0, 0], [99, 0, 0, 0], 2,
                                  active=[True, False, False, False])
    engine.prefill_lane_chunk(1, list(range(9, 40)), 0)  # behind the block
    engine.collect_lanes(block)
    assert waits == ["decode_lanes"]
    (event,) = engine.recorder.events("moe_block_forms")[n0:]
    assert event["program"] == "chunk" and event["chunks"] == 2
    assert event["landed"] + event["whole"] == 2 * n_expert_layers
    assert event["landed"] > 0  # a chunk of 128 pairs, a quarter held: about 32 land
    assert 0 < event["pairs_landed"] <= 2 * n_expert_layers * 128
    after = _forms(engine)
    assert after["landed"] - before["landed"] == event["landed"]
    assert after["whole"] - before["whole"] == event["whole"]
    assert len(engine._chunk_forms) == 1
    out = engine.decode_lanes([7, 8, 0, 0], [101, 31, 0, 0], 1,
                              active=[True, True, False, False])
    assert len(out) == 1 and not engine._chunk_forms
    assert engine.recorder.events("moe_block_forms")[-1]["chunks"] == 1
    assert 'dllama_moe_block_forms_total{program="chunk",form="landed"}' in engine.obs.render()


def test_a_server_stopped_with_a_chunks_forms_pending_drops_them(held_share):
    """Nothing waits for a chunk's form counts: a server whose last chunk
    no decode block followed stops as any other, and the counts go with
    the engine."""
    engine, tok = held_share
    srv = serve(engine, tok, host="127.0.0.1", port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    _stream(f"http://127.0.0.1:{srv.server_address[1]}", 1, 4)
    sched = srv.state.scheduler
    deadline = time.time() + 60
    while time.time() < deadline and (
            sched._flight is not None or any(sched.lanes) or sched.admitting):
        time.sleep(0.01)
    assert engine.recorder.events("moe_block_forms")  # the request's own chunks
    counted = _forms(engine)
    engine.prefill_lane_chunk(2, list(range(9, 40)), 0)
    assert len(engine._chunk_forms) == 1
    t0 = time.time()
    srv.shutdown()
    srv.server_close()
    assert time.time() - t0 < 30
    assert len(engine._chunk_forms) == 1 and _forms(engine) == counted
