"""API server tests: OpenAI-compatible surface over a tiny model."""

import json
import re
import socket
import struct
import threading
import time
import urllib.error
import urllib.request

import jax.numpy as jnp
import pytest

from dllama_tpu.formats import FloatType
from dllama_tpu.runtime.api_server import ApiState, NaiveCache, ChatMessage, serve
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.tokenizer import Tokenizer

from helpers import make_tiny_model, make_tiny_tokenizer


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    d = tmp_path_factory.mktemp("api")
    mp, tp_ = str(d / "m.m"), str(d / "t.t")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=288, seq_len=384)
    make_tiny_model(mp, weight_type=FloatType.Q40, cfg=cfg)
    make_tiny_tokenizer(tp_, chat_template="<|start_header_id|>")
    tok = Tokenizer(tp_)
    engine = InferenceEngine(
        mp, tokenizer=tok, tp=1, dtype=jnp.float32, temperature=0.0, seed=3
    )
    srv = serve(engine, tok, host="127.0.0.1", port=0)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    yield f"http://127.0.0.1:{srv.server_address[1]}"
    srv.shutdown()


def _post(url, payload):
    req = urllib.request.Request(
        url + "/v1/chat/completions",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    return urllib.request.urlopen(req, timeout=300)


def test_models_endpoint(server):
    with urllib.request.urlopen(server + "/v1/models") as r:
        data = json.loads(r.read())
    assert data["object"] == "list"
    assert data["data"][0]["object"] == "model"


def test_chat_completion(server):
    with _post(
        server,
        {
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 8,
            "temperature": 0,
        },
    ) as r:
        data = json.loads(r.read())
    assert data["object"] == "chat.completion"
    choice = data["choices"][0]
    assert choice["message"]["role"] == "assistant"
    # random tiny model almost never emits EOS within 8 tokens -> "length"
    assert choice["finish_reason"] in ("stop", "length")
    usage = data["usage"]
    assert usage["prompt_tokens"] > 0
    assert usage["total_tokens"] == usage["prompt_tokens"] + usage["completion_tokens"]
    assert usage["completion_tokens"] <= 8


def test_chat_completion_streaming(server):
    with _post(
        server,
        {
            "messages": [{"role": "user", "content": "hello world"}],
            "max_tokens": 6,
            "temperature": 0,
            "stream": True,
        },
    ) as r:
        assert r.headers["Content-Type"].startswith("text/event-stream")
        raw = r.read().decode()
    events = [
        json.loads(line[len("data: "):])
        for line in raw.splitlines()
        if line.startswith("data: ") and line != "data: [DONE]"
    ]
    assert raw.rstrip().endswith("data: [DONE]")
    assert events, "no SSE chunks"
    # max_tokens truncation on the random model reports "length" (stream
    # now mirrors the non-stream finish_reason)
    assert events[-1]["choices"][0]["finish_reason"] in ("stop", "length")
    for e in events[:-1]:
        assert e["object"] == "chat.completion.chunk"
        assert e["choices"][0]["delta"]["role"] == "assistant"


def test_naive_cache_reuses_prefix(server):
    msgs = [{"role": "user", "content": "first question"}]
    with _post(server, {"messages": msgs, "max_tokens": 4, "temperature": 0}) as r:
        first = json.loads(r.read())
    reply = first["choices"][0]["message"]["content"]
    msgs2 = msgs + [
        {"role": "assistant", "content": reply},
        {"role": "user", "content": "second question"},
    ]
    with _post(server, {"messages": msgs2, "max_tokens": 4, "temperature": 0}) as r:
        second = json.loads(r.read())
    # prefix reuse: the second request's prompt covers only the delta
    # (assistant echo + new user message), strictly fewer tokens than a
    # full re-encode of the 3-message conversation would need; the first
    # 1-message prompt is the lower bound that a full re-encode must exceed
    assert second["usage"]["prompt_tokens"] < first["usage"]["prompt_tokens"] + 40
    assert second["choices"][0]["message"]["role"] == "assistant"


def test_seed_param_deterministic(server):
    payload = {
        "messages": [{"role": "user", "content": "tell me"}],
        "max_tokens": 6,
        "temperature": 0.9,
        "seed": 42,
    }
    with _post(server, payload) as r:
        a = json.loads(r.read())["choices"][0]["message"]["content"]
    with _post(server, payload) as r:
        b = json.loads(r.read())["choices"][0]["message"]["content"]
    assert a == b


def test_not_found(server):
    try:
        urllib.request.urlopen(server + "/nope", timeout=30)
        assert False
    except urllib.error.HTTPError as e:
        assert e.code == 404


def test_bad_request(server):
    try:
        _post(server, {"no_messages": True})
        assert False
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_naive_cache_unit():
    c = NaiveCache()
    m1 = ChatMessage("user", "a")
    c.push(type("I", (), {"end_pos": 5, "message": m1})())
    msgs, pos = c.resolve_delta_prompt([m1, ChatMessage("user", "b")])
    assert pos == 5
    assert len(msgs) == 1 and msgs[0].content == "b"
    # mismatch clears
    msgs, pos = c.resolve_delta_prompt([ChatMessage("user", "x"), ChatMessage("user", "y")])
    assert pos == 0 and len(msgs) == 2
    assert c.items == []


def test_stop_as_string_and_mismatched_count(server):
    # OpenAI allows `stop` as a bare string; also more stops than eos ids
    with _post(
        server,
        {
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 4,
            "temperature": 0,
            "stop": "###",
        },
    ) as r:
        assert json.loads(r.read())["object"] == "chat.completion"
    with _post(
        server,
        {
            "messages": [{"role": "user", "content": "hi again"}],
            "max_tokens": 4,
            "temperature": 0,
            "stop": ["###", "END", "@@@"],
        },
    ) as r:
        assert json.loads(r.read())["object"] == "chat.completion"


def test_stream_error_still_terminates(server):
    # a prompt that overflows seq_len raises inside complete(); the SSE
    # stream must still deliver an error payload and [DONE]
    big = "x" * 4000
    with _post(
        server,
        {
            "messages": [{"role": "user", "content": big}],
            "stream": True,
        },
    ) as r:
        raw = r.read().decode()
    assert '"error"' in raw
    assert raw.rstrip().endswith("data: [DONE]")


@pytest.fixture(scope="module")
def lane_server(tmp_path_factory):
    """batch_size > 1 engine -> the LaneScheduler concurrent path."""
    d = tmp_path_factory.mktemp("api_lanes")
    mp, tp_ = str(d / "m.m"), str(d / "t.t")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=288, seq_len=384)
    make_tiny_model(mp, weight_type=FloatType.Q40, cfg=cfg)
    make_tiny_tokenizer(tp_, chat_template="<|start_header_id|>")
    tok = Tokenizer(tp_)
    engine = InferenceEngine(
        mp, tokenizer=tok, tp=1, dtype=jnp.float32, temperature=0.0, seed=3,
        batch_size=3,
    )
    srv = serve(engine, tok, host="127.0.0.1", port=0)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    yield f"http://127.0.0.1:{srv.server_address[1]}"
    srv.shutdown()


def test_lane_server_concurrent_requests(server, lane_server):
    """Three simultaneous greedy requests through the lane scheduler must
    each reproduce the single-lane server's answer for the same prompt
    (same tiny model in both fixtures)."""
    prompts = ["hello", "the quick brown", "zebra"]

    def single(prompt):
        with _post(server, {
            "messages": [{"role": "user", "content": prompt}],
            "max_tokens": 10, "temperature": 0,
        }) as r:
            return json.loads(r.read())["choices"][0]["message"]["content"]

    expected = [single(p) for p in prompts]

    results = [None] * len(prompts)
    errors = []

    def worker(i):
        try:
            with _post(lane_server, {
                "messages": [{"role": "user", "content": prompts[i]}],
                "max_tokens": 10, "temperature": 0,
            }) as r:
                results[i] = json.loads(r.read())["choices"][0]["message"]["content"]
        except Exception as e:  # pragma: no cover
            errors.append((i, e))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    assert results == expected, (results, expected)


def test_lane_server_streaming(lane_server):
    """SSE streaming through the scheduler path terminates with [DONE]."""
    req = urllib.request.Request(
        lane_server + "/v1/chat/completions",
        data=json.dumps({
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 6, "temperature": 0, "stream": True,
        }).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=300) as r:
        body = r.read().decode()
    assert "data: [DONE]" in body
    assert '"finish_reason"' in body


def test_lane_server_conversation_continuation_reuses_prefix(lane_server):
    """A continuing conversation reuses its stored prefix from the shared
    radix pool — on WHATEVER lane it lands (PR6 replaced per-lane
    NaiveCache affinity with cross-lane paged-KV sharing): turn 2 must
    report reused_prefix_tokens > 0 even with an unrelated request
    interleaved, and keep reusing as the conversation extends."""
    def ask(messages):
        # max_tokens kept tiny: the random model's replies re-encode
        # verbosely, and the fully-retokenized turn-3 conversation must
        # stay inside the tiny model's seq_len
        with _post(lane_server, {
            "messages": messages, "max_tokens": 4, "temperature": 0,
        }) as r:
            body = json.loads(r.read())
        return (body["choices"][0]["message"]["content"],
                body["dllama"]["reused_prefix_tokens"],
                body["dllama"]["lane"])

    convo = [{"role": "user", "content": "tell me a story"}]
    a1, _, lane1 = ask(convo)
    # interleave an unrelated request (occupies some lane, publishes its
    # own prefix — must not disturb the conversation's stored pages)
    ask([{"role": "user", "content": "unrelated"}])
    convo += [{"role": "assistant", "content": a1},
              {"role": "user", "content": "continue"}]
    a2, reused2, lane2 = ask(convo)
    # the turn-2 render begins with turn 1's fed tokens: the radix match
    # must cover at least one page of them
    assert reused2 > 0, (reused2, lane1, lane2)
    # the conversation keeps extending through the shared pool: turn 3
    # reuses at least as much as turn 2 (its prefix grew)
    convo += [{"role": "assistant", "content": a2},
              {"role": "user", "content": "more"}]
    a3, reused3, _ = ask(convo)
    assert isinstance(a3, str) and reused3 >= reused2, (reused3, reused2)


def test_api_main_chat_template_flag(tmp_path):
    """--chat-template forces the template type even when the tokenizer
    carries a different/absent jinja template."""
    import subprocess
    import sys
    import os as _os
    from helpers import REPO_ROOT, make_tiny_model, make_tiny_tokenizer

    mp = str(tmp_path / "m.m")
    tp = str(tmp_path / "t.t")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=288, seq_len=384)
    make_tiny_model(mp, cfg=cfg)
    make_tiny_tokenizer(tp, pad_to=288)  # no chat template in the file
    import socket

    with socket.socket() as s0:
        s0.bind(("127.0.0.1", 0))
        port = s0.getsockname()[1]
    log_path = tmp_path / "server.log"
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "dllama_tpu.runtime.api_server",
             "--model", mp, "--tokenizer", tp, "--port", str(port),
             "--host", "127.0.0.1", "--tp", "1", "--dtype", "f32",
             "--temperature", "0", "--chat-template", "chatml"],
            env={**_os.environ, "JAX_PLATFORMS": "cpu"},
            cwd=REPO_ROOT,
            stdout=log, stderr=subprocess.STDOUT,
        )
    try:
        import time as _t
        import urllib.request

        deadline = _t.time() + 120
        while _t.time() < deadline:
            if proc.poll() is not None:
                raise AssertionError(
                    f"server exited rc={proc.returncode}:\n"
                    + log_path.read_text()[-1000:]
                )
            try:
                urllib.request.urlopen(f"http://127.0.0.1:{port}/health", timeout=2)
                break
            except Exception:
                _t.sleep(1)
        else:
            raise AssertionError(
                "server did not come up:\n" + log_path.read_text()[-1000:]
            )
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/chat/completions",
            data=json.dumps({"messages": [{"role": "user", "content": "hi"}],
                             "max_tokens": 3, "temperature": 0}).encode(),
            headers={"Content-Type": "application/json"},
        )
        data = json.loads(urllib.request.urlopen(req, timeout=120).read())
        assert data["object"] == "chat.completion"
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_lane_server_seed_reproducible(lane_server):
    """A `seed` under the lane scheduler IS honored per lane (r5:
    decode_lanes derives each lane's sampling keys from its own seed and
    absolute positions): a seeded sampled request reproduces through the
    concurrent path, and the response no longer carries the old
    best-effort warning."""
    payload = {
        "messages": [{"role": "user", "content": "tell me"}],
        "max_tokens": 6, "temperature": 0.9, "seed": 42,
    }
    with _post(lane_server, payload) as r:
        body = json.loads(r.read())
    assert "warning" not in body, body
    a = body["choices"][0]["message"]["content"]
    with _post(lane_server, payload) as r:
        b = json.loads(r.read())["choices"][0]["message"]["content"]
    assert a == b


def test_chat_completion_q40_fused_engine(tmp_path):
    """The serving path over a weight_format='q40' engine (which fuses
    wqkv/w13 by default) must produce the same completion as the dense
    engine for a greedy request — server x fusion x NaiveCache in one
    pass."""
    mp, tp_ = str(tmp_path / "m.m"), str(tmp_path / "t.t")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=288, seq_len=384)
    make_tiny_model(mp, weight_type=FloatType.Q40, cfg=cfg)
    make_tiny_tokenizer(tp_, chat_template="<|start_header_id|>")

    payload = {
        "messages": [{"role": "user", "content": "hello"}],
        "max_tokens": 8,
        "temperature": 0,
    }
    outs = {}
    for fmt in ("q40", "dense"):
        tok = Tokenizer(tp_)
        engine = InferenceEngine(
            mp, tokenizer=tok, tp=1, dtype=jnp.float32, temperature=0.0,
            seed=3, weight_format=fmt,
        )
        if fmt == "q40":
            assert "wqkv" in engine.params["layers"]
            assert "w13" in engine.params["layers"]
        srv = serve(engine, tok, host="127.0.0.1", port=0)
        thread = threading.Thread(target=srv.serve_forever, daemon=True)
        thread.start()
        url = f"http://127.0.0.1:{srv.server_address[1]}"
        try:
            with _post(url, payload) as r:
                outs[fmt] = json.loads(r.read())["choices"][0]["message"]
        finally:
            srv.shutdown()
    assert outs["q40"] == outs["dense"], outs


def test_single_stream_crash_recovery(tmp_path):
    """An injected engine error mid-request yields a
    500, the donated KV cache and the stale NaiveCache entries are
    dropped (cache epoch moved), and the next request succeeds."""
    mp, tp_ = str(tmp_path / "m.m"), str(tmp_path / "t.t")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=288, seq_len=384)
    make_tiny_model(mp, weight_type=FloatType.Q40, cfg=cfg)
    make_tiny_tokenizer(tp_, chat_template="<|start_header_id|>")
    tok = Tokenizer(tp_)
    engine = InferenceEngine(
        mp, tokenizer=tok, tp=1, dtype=jnp.float32, temperature=0.0, seed=3
    )
    srv = serve(engine, tok, host="127.0.0.1", port=0)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    payload = {
        "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 6,
        "temperature": 0,
    }
    try:
        # 1. clean request works
        with _post(url, payload) as r:
            ok1 = json.loads(r.read())
        assert ok1["choices"][0]["message"]["content"] is not None

        # 2. poison the next dispatch: donate the cache, then fail
        real = engine._decode_block_fn

        def poisoned(n_steps, greedy, window=0):
            block = real(n_steps, greedy, window)

            def bad(params, token, cache, pos, rng, temp, topp):
                block(params, token, cache, pos, rng, temp, topp)
                raise RuntimeError("injected dispatch failure")

            return bad

        engine._decode_block_fn = poisoned
        epoch0 = engine.cache_epoch
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(url, payload).read()
        assert exc.value.code == 500
        assert "injected" in json.loads(exc.value.read())["error"]["message"]
        engine._decode_block_fn = real
        assert engine.cache_epoch > epoch0

        # 3. next request (same conversation prefix) succeeds and matches
        #    the clean run — nothing resumed from poisoned state
        with _post(url, payload) as r:
            ok2 = json.loads(r.read())
        assert (
            ok2["choices"][0]["message"]["content"]
            == ok1["choices"][0]["message"]["content"]
        )
    finally:
        srv.shutdown()


def test_chat_completion_q40_kv8_engine(tmp_path):
    """Serving over quantized weights + the int8 KV cache: a greedy
    request completes and is reproducible across two identical requests
    (NaiveCache prefix path included)."""
    mp, tp_ = str(tmp_path / "m8.m"), str(tmp_path / "t.t")
    cfg = dict(dim=64, hidden_dim=256, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=288, seq_len=384)
    make_tiny_model(mp, weight_type=FloatType.Q40, cfg=cfg)
    make_tiny_tokenizer(tp_, chat_template="<|start_header_id|>")
    tok = Tokenizer(tp_)
    engine = InferenceEngine(
        mp, tokenizer=tok, tp=1, dtype=jnp.float32, temperature=0.0,
        seed=3, weight_format="q40", kv_dtype="int8",
    )
    srv = serve(engine, tok, host="127.0.0.1", port=0)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    payload = {
        "messages": [{"role": "user", "content": "hello"}],
        "max_tokens": 8,
        "temperature": 0,
    }
    try:
        with _post(url, payload) as r:
            one = json.loads(r.read())["choices"][0]["message"]["content"]
        with _post(url, payload) as r:
            two = json.loads(r.read())["choices"][0]["message"]["content"]
        assert one == two and isinstance(one, str)
    finally:
        srv.shutdown()


# -- observability (obs/): /metrics, /v1/health, --trace-out ----------------
#
# These tests own their server (unlike the URL-only fixtures above) so they
# can reach `srv.state` — the metric handles, the tracer ring, and the lane
# scheduler. The metrics registry is process-global, so every assertion on
# a counter is a DELTA against a before-value, never an absolute count.


@pytest.fixture(scope="module")
def obs_server(tmp_path_factory):
    """batch_size-3 engine + --trace-out sink; yields the HTTPServer."""
    d = tmp_path_factory.mktemp("api_obs")
    mp, tp_ = str(d / "m.m"), str(d / "t.t")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=288, seq_len=384)
    make_tiny_model(mp, weight_type=FloatType.Q40, cfg=cfg)
    make_tiny_tokenizer(tp_, chat_template="<|start_header_id|>")
    tok = Tokenizer(tp_)
    engine = InferenceEngine(
        mp, tokenizer=tok, tp=1, dtype=jnp.float32, temperature=0.0, seed=3,
        batch_size=3,
    )
    trace_path = str(d / "trace.jsonl")
    srv = serve(engine, tok, host="127.0.0.1", port=0, trace_out=trace_path)
    srv.trace_path = trace_path
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield srv
    srv.shutdown()


def _url(srv):
    return f"http://127.0.0.1:{srv.server_address[1]}"


def _scrape(srv):
    with urllib.request.urlopen(_url(srv) + "/metrics", timeout=30) as r:
        return r.headers["Content-Type"], r.read().decode()


def _sample(text, name):
    m = re.search(rf"^{re.escape(name)} ([0-9.e+-]+)$", text, re.M)
    assert m, f"{name} not in scrape"
    return float(m.group(1))


def test_metrics_under_concurrent_streams(obs_server):
    """The acceptance scrape: >=3 concurrent streaming requests against a
    batch_size>1 engine, then GET /metrics serves Prometheus text with
    non-empty TTFT/TPOT histograms, queue-wait, lane gauges, and the
    NaiveCache hit/miss counters."""
    state = obs_server.state
    b_ttft, b_adm = state.m_ttft.count, state.m_admissions.value
    b_qw, b_fin = state.m_queue_wait.count, state.m_finished.child_values()
    prompts = ["alpha", "beta stream", "gamma ray"]
    results, errors = [None] * 3, []

    def worker(i):
        try:
            with _post(_url(obs_server), {
                "messages": [{"role": "user", "content": prompts[i]}],
                "max_tokens": 8, "temperature": 0, "stream": True,
            }) as r:
                results[i] = r.read().decode()
        except Exception as e:  # pragma: no cover
            errors.append((i, e))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    for raw in results:
        assert raw.rstrip().endswith("data: [DONE]")
    # the final SSE chunk carries the span-derived request metadata
    events = [json.loads(line[len("data: "):])
              for line in results[0].splitlines()
              if line.startswith("data: ") and line != "data: [DONE]"]
    meta = events[-1]["dllama"]
    assert meta["request_id"].startswith("req-")
    assert meta["lane"] is not None and meta["ttft_ms"] > 0

    # every request got admitted, waited in queue, and marked a TTFT
    assert state.m_ttft.count >= b_ttft + 3
    assert state.m_queue_wait.count >= b_qw + 3
    assert state.m_admissions.value >= b_adm + 3
    fin = state.m_finished.child_values()
    assert sum(fin.values()) >= sum(b_fin.values()) + 3

    ctype, text = _scrape(obs_server)
    assert ctype == state.obs.CONTENT_TYPE
    for fam in (
        "dllama_ttft_seconds", "dllama_tpot_seconds",
        "dllama_queue_wait_seconds", "dllama_prefill_seconds",
        "dllama_lanes_total", "dllama_lanes_active", "dllama_queue_depth",
        "dllama_prefix_cache_hits_total", "dllama_prefix_cache_misses_total",
        "dllama_requests_finished_total", "dllama_http_requests_total",
        "dllama_engine_step_seconds", "dllama_engine_compiles_total",
    ):
        assert f"# TYPE {fam} " in text, fam
    m = re.search(r"^dllama_ttft_seconds_count (\d+)$", text, re.M)
    assert m and int(m.group(1)) >= 3
    m = re.search(r"^dllama_tpot_seconds_count (\d+)$", text, re.M)
    assert m and int(m.group(1)) >= 1
    assert _sample(text, "dllama_lanes_total") == 3
    # cumulative buckets: the +Inf bucket equals the count
    inf = re.search(r'^dllama_ttft_seconds_bucket\{le="\+Inf"\} (\d+)$',
                    text, re.M)
    cnt = re.search(r"^dllama_ttft_seconds_count (\d+)$", text, re.M)
    assert inf and cnt and inf.group(1) == cnt.group(1)


def test_health_endpoint(obs_server):
    with urllib.request.urlopen(_url(obs_server) + "/v1/health",
                                timeout=30) as r:
        data = json.loads(r.read())
    assert data["status"] == "ok"
    assert data["model"]
    assert data["uptime_s"] >= 0
    assert data["lanes"]["total"] == 3
    assert data["lanes"]["active"] + data["lanes"]["free"] == 3
    assert data["queue_depth"] >= 0
    assert isinstance(data["cache_epoch"], int)


def test_trace_out_roundtrip_completed(obs_server):
    """A finished request's lifecycle lands in the --trace-out JSONL with
    queue wait, prefill span, first-token time, token counts, and finish
    reason — matched to the request by the response's request_id."""
    from dllama_tpu.obs.trace import read_jsonl

    with _post(_url(obs_server), {
        "messages": [{"role": "user", "content": "trace me"}],
        "max_tokens": 5, "temperature": 0,
    }) as r:
        body = json.loads(r.read())
    rid = body["dllama"]["request_id"]
    assert body["dllama"]["ttft_ms"] > 0

    rec = None
    deadline = time.time() + 60
    while rec is None and time.time() < deadline:
        recs = [x for x in read_jsonl(obs_server.trace_path)
                if x["request_id"] == rid]
        rec = recs[0] if recs else None
        if rec is None:
            time.sleep(0.1)
    assert rec is not None, "trace record never hit the sink"
    assert rec["path"] == "lanes" and rec["finish_reason"] in ("stop", "length")
    assert rec["cancelled"] is False
    assert rec["queue_wait_s"] >= 0 and rec["prefill_s"] > 0
    assert rec["ttft_s"] >= rec["queue_wait_s"]
    assert rec["n_prompt_tokens"] > 0
    assert 1 <= rec["n_completion"] <= 5
    assert rec["total_s"] >= rec["ttft_s"]
    # the in-memory ring holds the same record
    assert any(x["request_id"] == rid
               for x in obs_server.state.tracer.records())


def test_trace_cancelled_stream(obs_server):
    """A client that disconnects mid-stream produces a `cancelled` trace
    record and bumps the SSE-cancellation counter: raw socket, read until
    the first delta, then RST-close."""
    state = obs_server.state
    b_cancel = state.m_cancellations.value
    b_recs = sum(1 for x in state.tracer.records()
                 if x["finish_reason"] == "cancelled")
    payload = json.dumps({
        "messages": [{"role": "user", "content": "stream then vanish"}],
        "max_tokens": 300, "temperature": 0, "stream": True,
    }).encode()
    s = socket.create_connection(
        ("127.0.0.1", obs_server.server_address[1]), timeout=120)
    try:
        s.sendall(b"POST /v1/chat/completions HTTP/1.1\r\n"
                  b"Host: t\r\nContent-Type: application/json\r\n"
                  b"Content-Length: " + str(len(payload)).encode()
                  + b"\r\n\r\n" + payload)
        buf = b""
        while b"data:" not in buf:
            chunk = s.recv(4096)
            assert chunk, f"stream closed before first delta: {buf!r}"
            buf += chunk
        # RST on close so the server's next write fails immediately
        s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     struct.pack("ii", 1, 0))
    finally:
        s.close()

    rec = None
    deadline = time.time() + 120
    while rec is None and time.time() < deadline:
        recs = [x for x in state.tracer.records()
                if x["finish_reason"] == "cancelled"]
        rec = recs[-1] if len(recs) > b_recs else None
        if rec is None:
            time.sleep(0.2)
    assert rec is not None, "cancellation never reached the tracer"
    assert rec["cancelled"] is True
    assert rec["n_completion"] >= 1  # it really was mid-stream
    assert rec["queue_wait_s"] is not None and rec["ttft_s"] is not None
    assert state.m_cancellations.value >= b_cancel + 1


def test_cross_lane_radix_reuse_and_kv_debug(obs_server):
    """Shared-prefix fanout through the radix pool: the same conversation
    asked repeatedly is admitted onto DIFFERENT lanes yet reuses the
    stored pages (trace records the reused length), the pool's
    page accounting proves the prefix is physically stored once (repeat
    publishes dedup to zero new pages), and /v1/debug/kv exposes it all.
    After the lanes drain, no page retains leak."""
    state = obs_server.state
    sched = state.scheduler
    kv = state.kv_manager
    assert kv is not None and sched.kv is kv

    def drain():
        deadline = time.time() + 60
        while (any(ls is not None for ls in sched.lanes) or sched.pending
               or sched.admitting):
            assert time.time() < deadline, "lanes never drained"
            time.sleep(0.05)

    drain()
    kv.reset()  # deterministic accounting below

    def ask(messages):
        with _post(_url(obs_server), {
            "messages": messages, "max_tokens": 5, "temperature": 0,
        }) as r:
            return json.loads(r.read())

    b_hits = state.m_prefix_hits.value
    convo = [{"role": "user", "content":
              "shared system preamble: you are a careful assistant who "
              "always answers in rhyming couplets about the sea"}]
    a1 = ask(convo)
    assert a1["dllama"]["reused_prefix_tokens"] == 0
    used_once = kv.pool.stats().used
    assert used_once > 0  # the first stream's prefix was published

    # fan the SAME conversation out twice more (greedy -> identical
    # continuations): each lands on a different (LRU) lane, reuses the
    # stored prefix, and publishes NOTHING new — stored once, physically
    a2 = ask(list(convo))
    a3 = ask(list(convo))
    assert a2["dllama"]["lane"] != a1["dllama"]["lane"]
    assert a2["dllama"]["reused_prefix_tokens"] > 0
    assert a3["dllama"]["reused_prefix_tokens"] > 0
    assert state.m_prefix_hits.value >= b_hits + 2
    assert kv.pool.stats().used == used_once, "fanout duplicated pages"
    # identical greedy requests reproduce through adopted pages
    assert (a2["choices"][0]["message"]["content"]
            == a1["choices"][0]["message"]["content"])

    # the trace record carries the reused length, same as the response
    rec = next(x for x in state.tracer.records()
               if x["request_id"] == a2["dllama"]["request_id"])
    assert rec["reused_prefix_tokens"] == a2["dllama"]["reused_prefix_tokens"]
    assert rec["lane"] == a2["dllama"]["lane"]

    # /v1/debug/kv: live accounting, consistent with the pool
    with urllib.request.urlopen(_url(obs_server) + "/v1/debug/kv",
                                timeout=30) as r:
        dbg = json.loads(r.read())
    assert dbg["enabled"] is True
    assert dbg["pool"]["total"] == kv.pool.n_pages - 1
    assert dbg["pool"]["free"] + dbg["pool"]["used"] == dbg["pool"]["total"]
    assert dbg["pool"]["used"] == used_once
    assert dbg["radix"]["pages"] == used_once
    assert dbg["radix"]["nodes"] >= 1

    # a continuation reuses at least the whole stored prefix
    convo += [
        {"role": "assistant", "content": a1["choices"][0]["message"]["content"]},
        {"role": "user", "content": "continue"},
    ]
    c1 = ask(convo)
    assert c1["dllama"]["reused_prefix_tokens"] >= a2["dllama"]["reused_prefix_tokens"]

    # leak check: drained lanes hold no page retains; every allocated
    # page is accounted to the tree (refcount exactly 1 -> shared == 0)
    drain()
    kv.check()
    st = kv.pool.stats()
    assert st.shared == 0, st
    assert not kv._lane_pages
    # the /metrics scrape carries the new pool gauges + radix counters
    _, text = _scrape(obs_server)
    for fam in ("dllama_kv_pages_total", "dllama_kv_pages_free",
                "dllama_kv_pages_shared", "dllama_radix_hits_total",
                "dllama_radix_evictions_total",
                "dllama_shared_prefix_tokens_total",
                "dllama_kv_cow_forks_total"):
        assert f"# TYPE {fam} " in text, fam
    assert _sample(text, "dllama_radix_hits_total") >= 2
    assert _sample(text, "dllama_shared_prefix_tokens_total") > 0


def test_scheduler_error_counter(obs_server):
    """An engine error inside the scheduler loop is counted (satellite:
    the loop used to swallow these silently), the in-flight request gets
    a structured retryable 503 + Retry-After (PR 12: the cache epoch
    never moved, so this is a transient-class failure the client should
    simply retry), and the server keeps serving."""
    state = obs_server.state
    engine = state.engine
    b_err = state.m_sched_errors.value
    b_retry = state.m_dispatch_retries.value
    real = engine.dispatch_lanes

    def boom(*a, **k):
        raise RuntimeError("injected lane dispatch failure")

    engine.dispatch_lanes = boom
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(_url(obs_server), {
                "messages": [{"role": "user", "content": "doomed"}],
                "max_tokens": 4, "temperature": 0,
            }).read()
        assert exc.value.code == 503
        assert exc.value.headers.get("Retry-After") is not None
        err = json.loads(exc.value.read())["error"]
        assert "injected" in err["message"]
        assert err["retryable"] is True
    finally:
        engine.dispatch_lanes = real
    assert state.m_sched_errors.value == b_err + 1
    # the deterministic failure was retried with backoff before the drop
    assert state.m_dispatch_retries.value == b_retry + state.retry_max
    # scheduler thread survived: the next request completes normally
    with _post(_url(obs_server), {
        "messages": [{"role": "user", "content": "still alive?"}],
        "max_tokens": 4, "temperature": 0,
    }) as r:
        assert json.loads(r.read())["object"] == "chat.completion"


# -- /v1/debug introspection + postmortem ------------------------------------


def _get_json(srv, path):
    with urllib.request.urlopen(_url(srv) + path, timeout=30) as r:
        return json.loads(r.read())


def test_debug_recorder_endpoint(obs_server):
    """After real traffic the flight-recorder dump shows the whole story:
    scheduler admits/finishes bracketing engine dispatch/complete pairs,
    in recording order, with wall times on the completes."""
    with _post(_url(obs_server), {
        "messages": [{"role": "user", "content": "record me"}],
        "max_tokens": 4, "temperature": 0,
    }) as r:
        r.read()
    dump = _get_json(obs_server, "/v1/debug/recorder")
    assert dump["capacity"] > 0 and dump["n_events"] > 0
    assert dump["total_recorded"] >= dump["n_events"]
    kinds = {e["kind"] for e in dump["events"]}
    assert {"admit", "finish", "step_dispatch", "step_complete"} <= kinds
    for e in dump["events"]:
        assert e["t"] > 0 and e["seq"] > 0
        if e["kind"] == "step_complete":
            assert e["ms"] >= 0
    seqs = [e["seq"] for e in dump["events"]]
    assert seqs == sorted(seqs)


def test_debug_memory_endpoint(obs_server):
    data = _get_json(obs_server, "/v1/debug/memory")
    assert len(data["devices"]) >= 1
    for d in data["devices"]:
        assert {"device", "platform", "available"} <= set(d)
    an = data["analytic"]
    assert an["params_bytes"] > 0 and an["cache_bytes"] > 0
    assert an["total_bytes"] == an["params_bytes"] + an["cache_bytes"]
    assert 0 < an["per_device_bytes"] <= an["total_bytes"]
    cmp_ = data["comparison"]
    assert cmp_["analytic_per_chip_bytes"] == an["per_device_bytes"]
    if not any(d["available"] for d in data["devices"]):
        # CPU test backend: explicit unavailability, no fabricated figures
        assert cmp_["available"] is False


def test_debug_compile_endpoint(obs_server):
    """The acceptance probe: /v1/debug/compile reports non-empty XLA cost
    analysis for at least the decode step on CPU (AOT-compiled block
    programs), and lazily jitted programs carry the explicit
    'unavailable' marker instead of nothing."""
    with _post(_url(obs_server), {
        "messages": [{"role": "user", "content": "compile me"}],
        "max_tokens": 4, "temperature": 0,
    }) as r:
        r.read()
    data = _get_json(obs_server, "/v1/debug/compile")
    programs = data["programs"]
    assert programs
    for p in programs:
        assert p["kind"] in (
            "prefill", "prefill_lane", "decode_block", "decode_lanes",
            "score", "kv_adopt", "kv_publish", "kv_page_copy",
        )
        assert p["origin"] in ("dispatch", "prefetch", "prefetch-failed")
        assert p["cost"] == "unavailable" or p["cost"]["bytes_accessed"] >= 0
    decode = [p for p in programs
              if p["kind"] in ("decode_block", "decode_lanes")]
    assert decode, "no decode program in the compile cache after a request"
    assert any(isinstance(p["cost"], dict) and p["cost"]["flops"] > 0
               for p in decode)
    assert all(p["compile_seconds"] is None or p["compile_seconds"] >= 0
               for p in programs)

    cost = data["cost"]
    assert "hbm_peak_bytes_per_s" in cost  # None on CPU, a number on TPU
    kinds = cost["kinds"]
    assert any(k in kinds for k in ("decode_block", "decode_lanes"))
    for info in kinds.values():
        assert info["bytes_accessed"] > 0
        if cost["hbm_peak_bytes_per_s"] is None:
            assert info["roofline_fraction"] is None


def test_debug_endpoints_count_http_metrics(obs_server):
    """Debug paths ride the same HTTP accounting as the serving paths."""
    state = obs_server.state
    before = state.m_http.child_values().get(("/v1/debug/recorder",), 0)
    _get_json(obs_server, "/v1/debug/recorder")
    after = state.m_http.child_values()[("/v1/debug/recorder",)]
    assert after == before + 1


def test_scheduler_error_writes_postmortem(obs_server, tmp_path):
    """An injected scheduler-loop failure produces a postmortem JSON
    containing the event ring (the tentpole's black-box guarantee), and
    the server keeps serving afterwards."""
    state = obs_server.state
    engine = state.engine
    pm_dir = tmp_path / "pm"
    old_dir = state.recorder.postmortem_dir
    state.recorder.postmortem_dir = str(pm_dir)
    real = engine.dispatch_lanes

    def boom(*a, **k):
        raise RuntimeError("injected postmortem failure")

    engine.dispatch_lanes = boom
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(_url(obs_server), {
                "messages": [{"role": "user", "content": "doomed again"}],
                "max_tokens": 4, "temperature": 0,
            }).read()
        assert exc.value.code == 503
    finally:
        engine.dispatch_lanes = real
        state.recorder.postmortem_dir = old_dir

    files = sorted(pm_dir.glob("postmortem-*.json"))
    assert files, "scheduler error never wrote a postmortem"
    payload = json.loads(files[-1].read_text())
    assert payload["reason"] == "scheduler-loop"
    assert "injected postmortem failure" in payload["error"]
    assert payload["error_type"] == "RuntimeError"
    kinds = [e["kind"] for e in payload["events"]]
    assert "scheduler_error" in kinds  # the ring captured the failure
    assert "step_dispatch" in kinds    # ...and the engine history before it
    # PR 12 satellite: the dump embeds the server-level evidence — a
    # /v1/health snapshot and the trailing anomaly-signal series — so a
    # ring file is diagnosable without the live server
    ctx = payload["context"]
    assert ctx["health"]["model"] == state.model_name
    assert "lanes" in ctx["health"] and "cache_epoch" in ctx["health"]
    assert isinstance(ctx["series_60s"], dict)
    # the loop survived: a normal request completes and the dump shows it
    with _post(_url(obs_server), {
        "messages": [{"role": "user", "content": "recovered?"}],
        "max_tokens": 4, "temperature": 0,
    }) as r:
        assert json.loads(r.read())["object"] == "chat.completion"


def test_debug_timeline_endpoint_and_coverage(obs_server):
    """A finished request's span timeline is served as Chrome-trace JSON
    and its phase accounting covers >=95% of the request's wall time (the
    tentpole acceptance bar: queue + admission + decode + publish spans
    leave only scheduler-tick bookkeeping uncovered)."""
    with _post(_url(obs_server), {
        "messages": [{"role": "user", "content": "time me"}],
        "max_tokens": 6, "temperature": 0,
    }) as r:
        body = json.loads(r.read())
    rid = body["dllama"]["request_id"]

    trace = _get_json(obs_server, f"/v1/debug/timeline?request_id={rid}")
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert xs, "no spans for the request"
    names = {e["name"] for e in xs}
    assert "queue" in names and "decode" in names
    assert all(e["args"]["request_id"] == rid for e in xs)
    assert all(e["dur"] >= 0 for e in xs)
    summary = trace["dllama"]["summary"]
    assert summary["request_id"] == rid
    assert summary["wall_ms"] > 0
    assert summary["coverage"] >= 0.95, summary
    assert "queue" in summary["phases"] and "decode" in summary["phases"]
    # phase totals are consistent with the span list
    assert summary["n_spans"] == len(xs)

    # the unfiltered timeline aggregates every component's spans
    full = _get_json(obs_server, "/v1/debug/timeline")
    assert full["dllama"]["n_spans"] >= len(xs)
    comps = {e["args"]["name"]
             for e in full["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert {"scheduler", "engine"} <= comps


def test_debug_slo_endpoint_and_gauges(obs_server):
    """/v1/debug/slo serves the three sliding windows with finite
    attainment/goodput, and the scrape-time snapshot refreshes the
    dllama_slo_* gauges in /metrics."""
    with _post(_url(obs_server), {
        "messages": [{"role": "user", "content": "meet my slo"}],
        "max_tokens": 4, "temperature": 0,
    }) as r:
        r.read()
    snap = _get_json(obs_server, "/v1/debug/slo")
    assert set(snap["targets"]) == {"ttft_ms", "tpot_ms"}
    assert set(snap["windows"]) == {"10s", "1m", "5m"}
    for w in snap["windows"].values():
        assert w["n_requests"] >= 0
        assert 0.0 <= w["attainment"] <= 1.0
        assert 0.0 <= w["ttft_attainment"] <= 1.0
        assert w["goodput_tokens_per_s"] >= 0.0
        assert w["throughput_tokens_per_s"] >= 0.0
    # the request we just finished is inside the 5m window
    assert snap["windows"]["5m"]["n_requests"] >= 1
    assert snap["windows"]["5m"]["throughput_tokens_per_s"] > 0

    _, text = _scrape(obs_server)
    for fam in ("dllama_slo_attainment", "dllama_slo_ttft_attainment",
                "dllama_slo_tpot_attainment",
                "dllama_slo_goodput_tokens_per_s",
                "dllama_slo_throughput_tokens_per_s",
                "dllama_slo_window_requests"):
        assert f"# TYPE {fam} " in text, fam
    assert re.search(
        r'^dllama_slo_window_requests\{window="5m"\} \d+$', text, re.M)


def test_watchdog_trips_on_injected_stall(obs_server, tmp_path):
    """A dispatch left hanging past the timeout (driven by a fake clock,
    so the test is fast) flips /v1/health to degraded, increments
    dllama_watchdog_stalls_total, and writes a watchdog postmortem; when
    the dispatch clears the watchdog recovers."""
    wd = obs_server.state.watchdog
    assert wd is not None, "lane server must run a watchdog"
    # the watchdog audits nothing until the scheduler has ticked once; an
    # idle scheduler beats as it goes to sleep, so wait for that beat
    # instead of leaning on whatever test ran before
    deadline = time.monotonic() + 30
    while wd._last_beat is None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert wd._last_beat is not None and wd.status()["n_active"] == 0
    pm_dir = tmp_path / "pm"
    old_dir = wd.recorder.postmortem_dir
    old_clock = wd._clock
    fake = {"t": 10_000.0}
    stalls = wd.m_stalls.labels(reason="dispatch-hung")
    b_stalls = stalls.value
    try:
        wd.recorder.postmortem_dir = str(pm_dir)
        wd._clock = lambda: fake["t"]
        wd.dispatch_begin("decode_lanes")  # ...and never ends: a hang
        fake["t"] += wd.dispatch_timeout_s + 1.0
        assert wd.check_once() == "dispatch-hung"
        assert wd.degraded

        health = _get_json(obs_server, "/v1/health")
        assert health["status"] == "degraded"
        assert health["watchdog"]["degraded"] is True
        assert health["watchdog"]["reason"] == "dispatch-hung"
        assert "decode_lanes" in health["watchdog"]["detail"]
        assert stalls.value == b_stalls + 1
        _, text = _scrape(obs_server)
        assert "dllama_watchdog_degraded 1" in text

        files = sorted(pm_dir.glob("postmortem-*.json"))
        assert files, "watchdog stall never wrote a postmortem"
        payload = json.loads(files[-1].read_text())
        assert payload["reason"] == "watchdog"
        assert "dispatch-hung" in payload["error"]

        # the dispatch completes: one check later the episode is over
        wd.dispatch_end()
        assert wd.check_once() is None
        assert not wd.degraded
        assert _get_json(obs_server, "/v1/health")["status"] == "ok"
        # edge-triggered: the whole episode cost exactly one postmortem
        assert len(sorted(pm_dir.glob("postmortem-*.json"))) == 1
    finally:
        wd.dispatch_end()
        wd._clock = old_clock
        wd.recorder.postmortem_dir = old_dir
        wd.check_once()  # clear any degraded state with the real clock


# -- time-series store, /dashboard, anomaly detection (obs/timeseries,
# obs/anomaly, obs/dashboard) ------------------------------------------------


def _post_json(srv, path, payload):
    req = urllib.request.Request(
        _url(srv) + path,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def test_debug_series_index_and_query(obs_server):
    """/v1/debug/series with no ?name= lists the tracked series plus the
    anomaly monitor's status; with ?name=&window= it serves the trailing
    points the dashboard sparklines poll."""
    state = obs_server.state
    # one deterministic tick so the store is populated regardless of the
    # background sampler's phase
    state.sampler.sample_once()
    idx = _get_json(obs_server, "/v1/debug/series")
    assert idx["interval_s"] == state.series.interval_s
    assert idx["retention_s"] == state.series.retention_s
    assert "dllama_lanes_active" in idx["names"]
    assert "dllama_queue_depth" in idx["names"]
    # the scrape-only SLO gauges ride the shared refresh hooks into the
    # store too (the stale-gauge fix: sampler and scraper run the SAME
    # refresh path)
    assert any(n.startswith("dllama_slo_goodput_tokens_per_s")
               for n in idx["names"])
    anom = idx["anomaly"]
    assert anom["enabled"] is True and anom["n_rules"] >= 5
    assert {"decode_stall", "ttft", "tpot", "kv_free_slope", "goodput"} <= (
        set(anom["baselines"])
    )

    res = _get_json(
        obs_server, "/v1/debug/series?name=dllama_lanes_active&window=60")
    assert res["name"] == "dllama_lanes_active"
    assert res["kind"] == "gauge" and res["tier"] == "1s"
    assert res["points"] and all(len(p) == 2 for p in res["points"])
    ts = [p[0] for p in res["points"]]
    assert ts == sorted(ts)


def test_debug_series_bad_window_and_missing_series(obs_server):
    with pytest.raises(urllib.error.HTTPError) as exc:
        _get_json(
            obs_server,
            "/v1/debug/series?name=dllama_lanes_active&window=bogus")
    assert exc.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as exc:
        _get_json(obs_server, "/v1/debug/series?name=no_such_series")
    assert exc.value.code == 404
    assert "no series" in json.loads(exc.value.read())["error"]["message"]


def test_dashboard_serves_self_contained_page(obs_server):
    """GET /dashboard is a single self-contained HTML page — inline CSS,
    inline JS, canvas sparklines, polling only same-origin endpoints (the
    air-gap promise the dashboard-static dlint rule enforces)."""
    with urllib.request.urlopen(_url(obs_server) + "/dashboard",
                                timeout=30) as r:
        ctype = r.headers["Content-Type"]
        html = r.read().decode("utf-8")
    assert ctype.startswith("text/html")
    assert "<canvas" in html and "<script>" in html
    # it polls the in-process endpoints, nothing else
    assert "/v1/debug/series" in html and "/v1/health" in html
    low = html.lower()
    assert "http://" not in low and "https://" not in low
    assert "<script src" not in low and "@import" not in low
    assert 'src="//' not in low and 'href="//' not in low


def test_dashboard_series_reflect_fake_clock_traffic(obs_server):
    """The acceptance loop, closed end-to-end: real traffic lands in the
    registry, injected fake-clock sampler ticks snapshot it into the
    store, and the exact queries the dashboard's sparklines poll
    (/v1/debug/series?name=&window=) serve those points back over HTTP."""
    state = obs_server.state
    state.sampler.stop()  # only the injected fake-clock ticks below
    try:
        with _post(_url(obs_server), {
            "messages": [{"role": "user", "content": "draw me"}],
            "max_tokens": 5, "temperature": 0,
        }) as r:
            assert json.loads(r.read())["object"] == "chat.completion"
        base = time.monotonic() + 1e6  # newer than every real-clock tick
        ticks = [base + i for i in range(5)]
        for t in ticks:
            state.sampler.sample_once(now=t)
        for name in ("dllama_lanes_active", "dllama_queue_depth",
                     "dllama_ttft_seconds_p50"):
            res = _get_json(
                obs_server, f"/v1/debug/series?name={name}&window=60")
            assert [p[0] for p in res["points"]] == ticks, name
        # the TTFT sparkline really reflects the request served above
        res = _get_json(
            obs_server,
            "/v1/debug/series?name=dllama_ttft_seconds_p50&window=60")
        assert all(v > 0 for _, v in res["points"])
    finally:
        state.sampler.start()


def test_debug_profile_endpoint(obs_server, tmp_path):
    """POST /v1/debug/profile captures an on-demand profile (CPU-safe:
    the hardened telemetry.profile logs-and-continues where tracing is
    unavailable), validates the capture length, and serializes captures
    through the non-blocking profile lock."""
    state = obs_server.state
    b_events = len(state.recorder.events(kind="profile_capture"))
    out = str(tmp_path / "prof")
    data = _post_json(obs_server, "/v1/debug/profile",
                      {"seconds": 0.05, "out_dir": out})
    assert data["log_dir"] == out and data["seconds"] == 0.05
    assert data["n_files"] >= 0
    events = state.recorder.events(kind="profile_capture")
    assert len(events) == b_events + 1
    assert events[-1]["log_dir"] == out

    # out-of-range capture lengths are rejected before any tracing
    for bad in (0, -1, 61):
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post_json(obs_server, "/v1/debug/profile", {"seconds": bad})
        assert exc.value.code == 400

    # one capture at a time: while the lock is held the endpoint is 409
    assert state.profile_lock.acquire(blocking=False)
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post_json(obs_server, "/v1/debug/profile", {"seconds": 0.05})
        assert exc.value.code == 409
    finally:
        state.profile_lock.release()


def test_anomaly_fires_and_recovers_through_server(obs_server):
    """The anomaly acceptance bar, on the live monitor under a fake
    clock: a signal leaving its baseline fires exactly one
    dllama_anomaly_total{signal=} increment (visible in a /metrics
    scrape), flips /v1/health to degraded with the anomaly reason, and
    recovers back to ok after the calm-tick hysteresis — all
    deterministic (edge-triggered, frozen baseline while active)."""
    from dllama_tpu.obs.anomaly import AnomalyRule, _RuleState

    def calm_health():
        """/v1/health, judged on this test's signal alone: on a loaded
        host one of the server's own rules may be degraded at the same
        moment, and that is not what is under test here."""
        health = _get_json(obs_server, "/v1/health")
        reasons = health.get("degraded_reasons", [])
        assert "anomaly:test_e2e" not in reasons
        assert (health["status"] == "ok") == (not reasons), health
        return health

    state = obs_server.state
    mon = state.anomaly
    # The background sampler evaluates the same monitor on the real clock
    # and counts an edge after it has dropped the lock: between this
    # test's own ticks that changes which thread fires and when the
    # counter moves. It stands still for this test. The fake clock ends at
    # the monitor's own now, so that `active_s` is a time that has passed
    # on any host, one booted a minute ago too.
    state.sampler.stop()
    t0 = mon._clock() - 40.0
    val = {"v": 1.0}
    rule = AnomalyRule(
        "test_e2e", lambda: val["v"], direction="high", z_threshold=4.0,
        min_samples=5, min_abs=0.1, std_floor=1e-3, recover_ticks=2,
    )
    counter = mon.m_anomalies.labels(signal="test_e2e")
    b_count = counter.value
    b_events = len(state.recorder.events(kind="anomaly"))
    with mon._lock:
        mon.rules.append(rule)
        mon._state["test_e2e"] = _RuleState(rule.alpha)
    try:
        # teach the baseline with calm ticks
        for i in range(10):
            mon.evaluate(now=t0 + i)
        assert "test_e2e" not in mon.active_signals()
        calm_health()

        # the signal leaves its baseline: exactly one edge
        val["v"] = 100.0
        mon.evaluate(now=t0 + 20.0)
        assert "test_e2e" in mon.active_signals()
        assert counter.value == b_count + 1

        health = _get_json(obs_server, "/v1/health")
        assert health["status"] == "degraded"
        assert "anomaly:test_e2e" in health["degraded_reasons"]
        detail = health["anomaly"]["active"]["test_e2e"]
        assert detail["z"] >= 4.0 and detail["value"] == 100.0
        assert detail["active_s"] >= 0

        _, text = _scrape(obs_server)
        m = re.search(
            r'^dllama_anomaly_total\{signal="test_e2e"\} ([0-9.]+)$',
            text, re.M)
        assert m and float(m.group(1)) == b_count + 1
        assert _sample(text, "dllama_anomaly_degraded") == 1.0

        # still abnormal on a later tick: edge-triggered, no re-count
        mon.evaluate(now=t0 + 21.0)
        assert counter.value == b_count + 1
        fired = state.recorder.events(kind="anomaly")[b_events:]
        assert [e for e in fired if e.get("signal") == "test_e2e"]

        # calm again: recover_ticks consecutive calm ticks clear it (the
        # baseline was frozen at ~1.0, so 1.0 reads as calm immediately)
        val["v"] = 1.0
        mon.evaluate(now=t0 + 30.0)
        mon.evaluate(now=t0 + 31.0)
        assert "test_e2e" not in mon.active_signals()
        calm_health()
        recovered = state.recorder.events(kind="anomaly_recovered")
        assert any(e.get("signal") == "test_e2e" for e in recovered)
        assert counter.value == b_count + 1  # the episode cost one count
    finally:
        with mon._lock:
            if rule in mon.rules:
                mon.rules.remove(rule)
            mon._state.pop("test_e2e", None)
        mon.g_degraded.set(1.0 if mon.degraded else 0.0)
        state.sampler.start()


def test_health_degraded_reasons_compose(obs_server):
    """A watchdog stall AND an active anomaly at once: /v1/health lists
    BOTH reasons (composition, never last-writer-wins), keeps the
    surviving reason when one source recovers, and returns to "ok" only
    when both have cleared."""
    from dllama_tpu.obs.anomaly import AnomalyRule, _RuleState

    state = obs_server.state
    wd = state.watchdog
    mon = state.anomaly
    old_clock = wd._clock
    fake = {"t": 50_000.0}
    # value_fn=None ticks are calm for an ACTIVE rule, so a huge
    # recover_ticks keeps the background sampler from clearing the
    # injected episode under the test
    rule = AnomalyRule("test_compose", lambda: None, recover_ticks=10**6)
    with mon._lock:
        mon.rules.append(rule)
        st = _RuleState(rule.alpha)
        st.active = True
        st.since = mon._clock()
        st.detail = {"signal": "test_compose", "value": 9.0,
                     "baseline_mean": 1.0, "z": 8.0}
        mon._state["test_compose"] = st
    try:
        wd._clock = lambda: fake["t"]
        # re-stamp the heartbeat in fake time with idle lanes, so stale
        # real-clock liveness state from earlier tests can't trip the
        # scheduler-stalled rule under the fake clock
        wd.beat(n_active=0, n_admitting=0)
        wd.dispatch_begin("decode_lanes")  # ...and never ends: a hang
        fake["t"] += wd.dispatch_timeout_s + 1.0
        assert wd.check_once() == "dispatch-hung"

        health = _get_json(obs_server, "/v1/health")
        assert health["status"] == "degraded"
        reasons = health["degraded_reasons"]
        assert "watchdog:dispatch-hung" in reasons
        assert "anomaly:test_compose" in reasons
        assert health["watchdog"]["degraded"] is True
        assert "test_compose" in health["anomaly"]["active"]

        # watchdog recovers first: still degraded on the anomaly alone
        wd.dispatch_end()
        wd.beat(n_active=0, n_admitting=0)
        assert wd.check_once() is None
        health = _get_json(obs_server, "/v1/health")
        assert health["status"] == "degraded"
        assert health["degraded_reasons"] == ["anomaly:test_compose"]
        assert "watchdog" not in health

        # the anomaly clears too: back to ok, no degraded payload at all
        with mon._lock:
            mon._state["test_compose"].active = False
        health = _get_json(obs_server, "/v1/health")
        assert health["status"] == "ok"
        assert "degraded_reasons" not in health
        assert "anomaly" not in health
    finally:
        wd.dispatch_end()
        wd._clock = old_clock
        wd.check_once()  # clear any degraded state with the real clock
        with mon._lock:
            if rule in mon.rules:
                mon.rules.remove(rule)
            mon._state.pop("test_compose", None)


def test_server_close_joins_sampler_thread(tmp_path):
    """server_close() joins the named sampler thread: a closed server
    (and test churn) can never leak a sampler mutating the process-global
    registry behind the next server's back."""
    mp, tp_ = str(tmp_path / "m.m"), str(tmp_path / "t.t")
    cfg = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
               head_dim=16, vocab_size=288, seq_len=384)
    make_tiny_model(mp, weight_type=FloatType.Q40, cfg=cfg)
    make_tiny_tokenizer(tp_, chat_template="<|start_header_id|>")
    tok = Tokenizer(tp_)
    engine = InferenceEngine(
        mp, tokenizer=tok, tp=1, dtype=jnp.float32, temperature=0.0, seed=3
    )
    srv = serve(engine, tok, host="127.0.0.1", port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    sampler = srv.state.sampler
    t = sampler._thread
    assert t is not None and t.is_alive()
    assert t.name == "dllama-series-sampler" and t.daemon
    srv.shutdown()
    srv.server_close()
    assert sampler._thread is None
    assert not t.is_alive(), "server_close left the sampler running"
