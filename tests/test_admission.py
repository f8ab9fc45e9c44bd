"""Chunked, stall-free admission (ISSUE 5).

The lane scheduler admits a request one bounded prefill chunk per loop
tick, interleaved with decode blocks, instead of one monolithic
`prefill_lane` that freezes every active stream for the whole prompt.
These tests pin the three contract points:

* token parity — chunked/interleaved admission writes the same KV rows
  as the monolithic path, so a seeded stream is byte-identical (fresh
  lane AND prefix-reuse resume with a pending token);
* the regression the rework fixes — a decode block runs between any two
  admission chunks while an active lane exists, and concurrent
  admissions round-robin fairly;
* the stall model — `dllama_decode_stall_seconds` observes gaps bounded
  by one chunk + one block (fake-clock), never the whole prefill.
"""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.runtime.api_server import (
    ApiState,
    ChatMessage,
    InferenceParams,
    LaneJob,
)
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.tokenizer import Tokenizer

from helpers import assert_one_spelling, make_tiny_model, make_tiny_tokenizer

CFG = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
           head_dim=16, vocab_size=288, seq_len=384)


@pytest.fixture(scope="module")
def tiny_paths(tmp_path_factory):
    d = tmp_path_factory.mktemp("admission")
    mp, tp_ = str(d / "m.m"), str(d / "t.t")
    make_tiny_model(mp, cfg=CFG)
    make_tiny_tokenizer(tp_, chat_template="<|start_header_id|>")
    return mp, tp_


@pytest.fixture(scope="module")
def sched_state(tiny_paths):
    """A scheduler-backed ApiState driven directly (no HTTP): tests reach
    the recorder, the metrics handles, and the scheduler internals."""
    mp, tp_ = tiny_paths
    tok = Tokenizer(tp_)
    engine = InferenceEngine(
        mp, tokenizer=tok, tp=1, dtype=jnp.float32, temperature=0.0, seed=3,
        batch_size=3,
    )
    state = ApiState(
        engine, tok, lane_block_size=4, admission_chunk=6,
    )
    assert state.scheduler is not None
    return state


def _drain(job, timeout=300):
    deltas = []
    deadline = time.time() + timeout
    while True:
        kind, payload = job.events.get(timeout=max(0.1, deadline - time.time()))
        if kind == "delta":
            deltas.append(payload)
        elif kind == "done":
            return "".join(deltas), payload
        else:
            raise AssertionError(f"job errored: {payload}")


def _submit_together(state, *params):
    """Enqueue several jobs atomically so the scheduler's admission pick
    sees them in the same tick (the round-robin fairness scenario)."""
    sched = state.scheduler
    jobs = []
    for p in params:
        job = LaneJob(p)
        job.span = state.tracer.span(path="lanes")
        jobs.append(job)
    with sched.cv:
        sched.pending.extend(jobs)
        state.m_queue_depth.set(len(sched.pending))
        sched.cv.notify()
    return jobs


def _wait_active(state, timeout=300):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if any(state.scheduler.lanes):
            return
        time.sleep(0.02)
    raise AssertionError("no lane became active")


# -- tentpole: token parity ---------------------------------------------------


@pytest.mark.fast
def test_chunked_prefill_token_parity(tiny_paths):
    """Chunked admission (small budget, interleaved with live decode on
    another lane) produces the byte-identical seeded stream of the
    monolithic prefill_lane path — fresh lane AND prefix-reuse resume
    where the conversation's pending final token is fed at the recorded
    end position."""
    mp, _ = tiny_paths
    e = InferenceEngine(
        mp, tp=1, dtype=jnp.float32, temperature=0.8, batch_size=2
    )
    prompt = [2 + (i * 7) % 250 for i in range(23)]
    delta = [3 + (i * 5) % 250 for i in range(9)]

    def decode_stream(token, pos, steps, seed):
        """Seeded lane-0 decode; per-lane seeding makes the stream depend
        only on (seed, positions), not on other-lane traffic."""
        toks, t, p = [], token, pos
        while len(toks) < steps:
            n = min(4, steps - len(toks))
            rows = e.decode_lanes(
                [t, 0], [p, 0], n, [True, False],
                [0.8, 0.8], [0.9, 0.9], seeds=[seed, None],
            )
            toks.extend(r[0] for r in rows)
            t, p = toks[-1], p + n
        return toks

    # -- run A: monolithic admission ------------------------------------
    e.reset()
    e.prefill_lane(0, prompt, pos0=0)
    a1 = decode_stream(prompt[-1], len(prompt) - 1, 12, seed=42)
    resume_pos = len(prompt) - 1 + 12
    # resume: pending token (the last generated one; its KV row was never
    # written) feeds first at the recorded end position
    tokens2 = [a1[-1]] + delta
    e.prefill_lane(0, tokens2, pos0=resume_pos)
    a2 = decode_stream(
        tokens2[-1], resume_pos + len(tokens2) - 1, 8, seed=7
    )

    # -- run B: chunked admission, interleaved with lane-1 decode --------
    e.reset()
    e.prefill_lane(1, [9, 11, 13, 15])
    s1 = {"t": 15, "p": 3}

    def chunked_prefill_interleaved(tokens, pos0):
        fills, cur = tokens[:-1], 0
        while cur < len(fills):
            width = e.prefill_lane_chunk(
                0, fills[cur:], pos0 + cur, budget=3
            )
            assert 0 < width <= 3
            cur += width
            # live traffic between chunks — exactly what the scheduler
            # interleaves; lane 0's KV must come out identical anyway
            rows = e.decode_lanes(
                [0, s1["t"]], [0, s1["p"]], 2, [False, True],
                [0.8, 0.8], [0.9, 0.9], seeds=[None, 5],
            )
            s1["t"], s1["p"] = rows[-1][1], s1["p"] + len(rows)

    chunked_prefill_interleaved(prompt, 0)
    b1 = decode_stream(prompt[-1], len(prompt) - 1, 12, seed=42)
    tokens2b = [b1[-1]] + delta
    chunked_prefill_interleaved(tokens2b, resume_pos)
    b2 = decode_stream(
        tokens2b[-1], resume_pos + len(tokens2b) - 1, 8, seed=7
    )

    assert b1 == a1  # fresh-lane parity
    assert b2 == a2  # prefix-reuse resume (pending token) parity


def _greedy_16(e, toks, end):
    """Four blocks of four greedy tokens for each lane of `end` (the position
    its prompt `toks[lane]` ends at) of a four-lane engine, the others parked."""
    t = [toks[lane][-1] if lane in end else 0 for lane in range(4)]
    pos = [end.get(lane, 0) for lane in range(4)]
    live = [lane in end for lane in range(4)]
    out = []
    for _ in range(4):
        block = e.decode_lanes(t, pos, 4, live, [0.0] * 4, [0.9] * 4)
        out += block
        t, pos = list(block[-1]), [p + 4 for p in pos]
    return [[row[lane] for row in out] for lane in end]


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["f32", "int8"])
def test_lanes_filled_by_one_program_equal_lanes_filled_one_a_tick(tiny_paths, kv_dtype):
    """Three lanes admitted together, each tick's chunk program carrying the
    next chunk of every one of them (`prefill_lanes_chunk`), at different
    positions, lengths and last-chunk sizes: the cache rows of each lane's
    prompt and its first 16 greedy tokens are those of the same three
    admitted one lane a tick, while a fourth lane stands parked."""
    mp, _ = tiny_paths
    e = InferenceEngine(
        mp, tp=1, dtype=jnp.float32, kv_dtype=kv_dtype, temperature=0.0,
        batch_size=4, prefill_buckets=(1, 8, 32),
    )
    budget = 32
    # lane -> (a context it already holds, the fill tokens it admits behind it)
    held = {0: 0, 1: 21, 3: 130}
    fills = {0: 71, 1: 32, 3: 9}  # last chunks of 7, 32 and 9 rows
    toks = {lane: [2 + (lane * 31 + i * 7) % 250 for i in range(held[lane] + fills[lane] + 1)]
            for lane in held}

    def admit(together: bool):
        e.reset()
        for lane, n in held.items():
            if n:
                e.prefill_lane(lane, toks[lane][: n + 1])
        cur = dict(held)
        end = {lane: held[lane] + fills[lane] for lane in held}
        programs, lanes0 = 0, e._m_prefill_lanes.value
        while any(cur[lane] < end[lane] for lane in cur):
            left = [lane for lane in cur if cur[lane] < end[lane]]
            for group in ([left] if together else [[lane] for lane in left]):
                chunks = [(lane, toks[lane][cur[lane]:end[lane]], cur[lane]) for lane in group]
                if together:
                    widths = e.prefill_lanes_chunk(chunks, budget=budget)
                else:
                    widths = [e.prefill_lane_chunk(*chunks[0], budget=budget)]
                programs += 1
                for lane, width in zip(group, widths):
                    assert 0 < width <= budget
                    cur[lane] += width
        rows = {}
        for lane in held:
            for name, leaf in e.cache.items():
                leaf = leaf.q.astype(jnp.float32) * leaf.s if kv_dtype else leaf
                rows[lane, name] = np.asarray(leaf[:, lane, :, : end[lane]])
        return rows, _greedy_16(e, toks, end), programs, e._m_prefill_lanes.value - lanes0

    rows_one, tokens_one, programs_one, lanes_one = admit(together=False)
    rows_all, tokens_all, programs_all, lanes_all = admit(together=True)
    assert programs_one == 3 + 1 + 1 and programs_all == 3
    assert tokens_all == tokens_one and all(len(t) == 16 for t in tokens_all)
    for key, want in rows_one.items():
        # (an int8 row: within a step of its scale, where a sum rounded apart)
        tol = 2e-2 * np.abs(want).max() if kv_dtype else 1e-5
        assert np.abs(rows_all[key] - want).max() <= tol, key
    # lanes a program: 5 over 5, then 5 over 3
    assert (lanes_one, lanes_all) == (5, 5)


# -- bugfix regression: decode between chunks, round-robin fairness -----------


def test_decode_runs_between_admission_chunks(sched_state):
    """The old loop admitted pending jobs back-to-back as consecutive full
    prefills before any decode ran. Under the chunked state machine, a
    decode block must run between any two admission chunks while an
    active lane exists — and two concurrent admissions must round-robin
    (strictly alternating leads while both have fills left). A tick
    dispatches ONE chunk program however many lanes admit: a dense model's
    carries both admitting lanes' chunks, each counted as its lane's."""
    state = sched_state
    sched, rec = state.scheduler, state.recorder

    job_a = sched.submit(InferenceParams(
        messages=[ChatMessage("user", "hi")], max_tokens=220,
        temperature=0.0,
    ))
    _wait_active(state)  # A is decoding; its lane stays active throughout
    base = rec.total_recorded
    b_chunks = state.m_admission_chunks.value

    long_txt = " ".join(f"tok{i:02d}" for i in range(25))
    jobs = _submit_together(
        state,
        InferenceParams(messages=[ChatMessage("user", long_txt + " b")],
                        max_tokens=3, temperature=0.0),
        InferenceParams(messages=[ChatMessage("user", long_txt + " c")],
                        max_tokens=3, temperature=0.0),
    )
    for job in jobs:
        _drain(job)
    job_a.cancelled = True
    _, reason_a = _drain(job_a)
    assert reason_a in ("cancelled", "length", "stop")

    # Replay the recorder: (op, lane, n_active_lanes_at_dispatch). The
    # admit/finish events bracket each lane's decode-active window, so we
    # know per chunk whether a stream was live at that moment (lane A may
    # legitimately hit its length limit before the admissions finish, at
    # which point back-to-back chunks are fine — nobody is stalled).
    ops, active = [], {0}  # lane A was admitted before `base`
    carried, lane_chunks = [], []  # a program's lanes; every lane's chunks
    for ev in rec.events():
        if ev["seq"] <= base:
            continue
        if ev["kind"] == "admit":
            active.add(ev["lane"])
        elif ev["kind"] == "finish":
            active.discard(ev["lane"])
        elif ev["kind"] == "step_dispatch":
            if ev.get("step") == "prefill_lane_chunk":
                ops.append(("chunk", ev["lane"], len(active)))
                carried.append(ev["lanes"])
            elif ev.get("step") == "decode_lanes":
                ops.append(("decode", None, len(active)))
        elif ev["kind"] == "admission_chunk":
            lane_chunks.append(ev["lane"])
    chunk_idx = [i for i, op in enumerate(ops) if op[0] == "chunk"]
    live_pairs = 0
    # the regression assert: never two admission chunks back-to-back
    # while any lane is actively decoding
    for i, j in zip(chunk_idx, chunk_idx[1:]):
        if ops[i][2] > 0:
            live_pairs += 1
            assert any(ops[x][0] == "decode" for x in range(i + 1, j)), ops
    # ... and the scenario genuinely exercised that: many chunks landed
    # while lane A's stream was live
    assert live_pairs >= 4, ops
    # round-robin fairness: while BOTH admissions still have chunks
    # coming, consecutive chunks never go to the same lane
    lanes_seq = [lane for op, lane, _ in ops if op == "chunk"]
    for i in range(len(lanes_seq) - 1):
        if len(set(lanes_seq[i + 1:])) > 1:
            assert lanes_seq[i + 1] != lanes_seq[i], lanes_seq
    # one program a tick carried both admissions' chunks, the lead first
    assert all(lanes[0] == lead for lanes, lead in zip(carried, lanes_seq))
    assert [len(lanes) for lanes in carried].count(2) >= 4, carried
    assert max(len(lanes) for lanes in carried) == 2
    assert sorted(lane_chunks) == sorted(lane for lanes in carried for lane in lanes)
    assert state.m_admission_chunks.value - b_chunks == len(lane_chunks) > len(lanes_seq)


# -- a tick's chunk program carries every admitting lane that can ride in it --
#
# Driven by hand: the scheduler's thread is stopped and the test calls
# `_begin_admission` and `_admission_tick` itself, with raw token ids for
# prompts, so that every tick's dispatch is known.


def _driven(mp, tp_, **engine_kw):
    tok = Tokenizer(tp_)
    engine = InferenceEngine(
        mp, tokenizer=tok, tp=1, dtype=jnp.float32, temperature=0.0, seed=3,
        batch_size=4, **engine_kw,
    )
    state = ApiState(engine, tok, lane_block_size=4, admission_chunk=100)
    state.scheduler.stop()
    state.scheduler._sleep = lambda s: None
    return state


@pytest.fixture(scope="module")
def driven_state(tiny_paths):
    return _driven(*tiny_paths)


@pytest.fixture
def driven(driven_state):
    from dllama_tpu.runtime.faults import set_fault_plane

    sched = driven_state.scheduler
    assert not sched.admitting and not any(sched.lanes)
    kv = sched.kv
    yield driven_state
    set_fault_plane("")
    sched.kv = kv
    sched._drop_all(RuntimeError("the test is over"))
    sched._rr = -1


def _begin(state, lane, n_tokens, max_tokens=4):
    """`lane` begins the admission of `n_tokens` raw ids (no template)."""
    ids = [2 + (lane * 41 + i * 7) % 250 for i in range(n_tokens)]
    job = LaneJob(InferenceParams(resume_tokens=ids, max_tokens=max_tokens, temperature=0.0))
    job.span = state.tracer.span(path="lanes")
    state.scheduler._begin_admission(lane, job)
    return state.scheduler.admitting[lane]


def _chunk_programs(state, base):
    return [e for e in state.recorder.events() if e["seq"] > base
            and e["kind"] == "step_dispatch" and e["step"] == "prefill_lane_chunk"]


def _ended(adm):
    kinds = []
    while not adm.job.events.empty():
        kinds.append(adm.job.events.get()[0])
    return kinds


def test_a_lane_that_would_pass_the_contexts_end_takes_a_tick_of_its_own(driven):
    """Lane 0 stands at position 300 of 384 and asks for a rung of 8; lane
    1 starts and asks for one of 128, which lane 0's rows would not fit:
    whoever leads, the other is left out, its cursor where it was, and is
    carried by the tick that it leads."""
    state, sched, eng = driven, driven.scheduler, driven.engine
    assert eng.chunk_lanes == 4 and eng.header.seq_len == 384
    a = _begin(state, 0, 330)
    for _ in range(3):
        sched._admission_tick()
    assert a.cursor == 300 and eng._bucket_for(29, 300) == 8
    b = _begin(state, 1, 151)
    base = state.recorder.total_recorded
    sched._admission_tick()  # lane 1 leads
    assert (a.cursor, b.cursor) == (300, 100)
    sched._admission_tick()  # lane 0 leads
    assert (a.cursor, b.cursor) == (308, 100)
    while sched.admitting:
        sched._admission_tick()
    got = [(e["lanes"], e["pos"], e["n_tokens"], e["bucket"]) for e in _chunk_programs(state, base)]
    assert got == [([1], 0, 100, 128), ([0], 300, 8, 8), ([1], 100, 50, 128), ([0], 308, 8, 8),
                   ([0], 316, 8, 8), ([0], 324, 5, 8)]
    assert (a.n_chunks, b.n_chunks) == (7, 2)
    assert sched.lanes[0] is not None and sched.lanes[1] is not None


@pytest.mark.parametrize("case", ["cancelled", "adopt", "fault", "poison"])
def test_who_rides_in_a_shared_chunk_program_and_what_a_fault_of_it_costs(driven, case):
    """Three lanes admit. `cancelled`: a lane whose client went away rides
    in no program, and the tick it leads aborts it. `adopt`: a lane whose
    next action is its adopt rides in none either; its adopt is a tick of
    its own, and then it rides. `fault`: a shared dispatch that fails on an
    intact cache, retries spent, fails every lane it carried, cursors
    unmoved, and no other. `poison`: one that took the cache with it fails
    its lead alone; the riders start over."""
    from dllama_tpu.runtime.faults import set_fault_plane

    state, sched = driven, driven.scheduler
    adms = [_begin(state, lane, n) for lane, n in ((0, 250), (1, 180), (2, 120))]
    sched._admission_tick()  # lane 0 leads, all three ride
    assert [adm.cursor for adm in adms] == [100, 100, 100]
    base = state.recorder.total_recorded
    retries0 = state.m_dispatch_retries.value
    if case == "cancelled":
        adms[2].job.cancelled = True
        sched._admission_tick()  # lane 1 leads
        assert [adm.cursor for adm in adms] == [200, 179, 100] and 1 not in sched.admitting
        sched._admission_tick()  # lane 2 leads: aborted, and no program
        assert 2 not in sched.admitting and _ended(adms[2]) == ["done"]
        sched._admission_tick()
        assert [e["lanes"] for e in _chunk_programs(state, base)] == [[1, 0], [0]]
    elif case == "adopt":
        adopted = []
        sched.kv = type("Pool", (), {
            "native": False, "adopt": lambda self, lane, pages: adopted.append((lane, pages)),
            "release_lane": lambda self, lane: None, "release_all_lanes": lambda self: None})()
        adms[2].adopt_pages = [5, 6]
        sched._admission_tick()  # lane 1 leads, lane 0 rides
        assert [adm.cursor for adm in adms] == [200, 179, 100] and not adopted
        sched._admission_tick()  # lane 2 leads: its adopt, and no program
        assert adopted == [(2, [5, 6])] and adms[2].adopted and adms[2].cursor == 100
        sched._admission_tick()  # lane 0 leads, lane 2 rides
        assert [e["lanes"] for e in _chunk_programs(state, base)] == [[1, 0], [0, 2]]
        assert 0 not in sched.admitting and adms[2].cursor == 119
    elif case == "fault":
        adms[2].job.cancelled = True
        set_fault_plane("dispatch:op=prefill_lane_chunk:every=1")
        sched._admission_tick()  # lane 1 leads, lane 0 rides: both fail
        assert state.m_dispatch_retries.value - retries0 == sched.retry_max
        assert [adm.cursor for adm in adms] == [100, 100, 100]
        assert list(sched.admitting) == [2] and not _chunk_programs(state, base)
        assert _ended(adms[0]) == _ended(adms[1]) == ["error"] and not _ended(adms[2])
    else:
        epoch = state.engine.cache_epoch
        set_fault_plane("dispatch:op=prefill_lane_chunk:nth=1:kind=poison")
        sched._admission_tick()  # lane 1 leads; lanes 0 and 2 ride and start over
        assert state.engine.cache_epoch == epoch + 1
        assert _ended(adms[1]) == ["error"] and sorted(sched.admitting) == [0, 2]
        assert [adm.cursor for adm in adms] == [0, 100, 0]
        while sched.admitting:
            sched._admission_tick()
        assert [e["lanes"] for e in _chunk_programs(state, base)[1:]] == [[2, 0], [0, 2], [0]]
        assert sched.lanes[0] is not None and sched.lanes[2] is not None


def _one_lane_a_tick(eng, rr, script, budget):
    """The chunk programs of a scheduler that gives ONE admitting lane a
    chunk a tick, round-robin: `script[tick]` = the (lane, fill tokens) that
    begin before that tick. (lane, pos, n_tokens, bucket, window) each."""
    left, cur, out, tick = {}, {}, [], 0
    while left or tick < len(script):
        for lane, n in script[tick] if tick < len(script) else ():
            left[lane], cur[lane] = n, 0
        tick += 1
        order = sorted(left)
        rr = lane = min((i for i in order if i > rr), default=order[0])
        width = min(left[lane], budget)
        bucket = eng._bucket_for(width, cur[lane])
        width = min(width, bucket)
        out.append((lane, cur[lane], width, bucket, eng._attn_window(cur[lane] + bucket)))
        cur[lane] += width
        left[lane] -= width
        if not left[lane]:
            del left[lane]
    return out


def _tiny_family(tmp_path_factory, family: str, seq_len: int = 256):
    """(model, tokenizer) files of a tiny model of `family`."""
    import helpers
    from dllama_tpu.formats.model_file import LlmArch
    from dllama_tpu.models.synthetic import write_synth_tokenizer

    d = tmp_path_factory.mktemp(family)
    mp, tp_ = str(d / "m.m"), str(d / "t.t")
    if family == "qwen3_moe":
        make_tiny_model(mp, arch=LlmArch.QWEN3_MOE, cfg=dict(
            CFG, moe_hidden_dim=96, n_experts=4, n_active_experts=2, seq_len=seq_len))
    else:
        helpers.TINY_FAMILY_WRITERS[family](mp)
    write_synth_tokenizer(tp_, 512)
    return mp, tp_


@pytest.mark.parametrize("family", ["deepseek_v32", "lfm2_moe"])
def test_a_chunk_program_that_takes_one_lanes_rows_is_dispatched_as_before(
        tmp_path_factory, family):
    """A model with a latent index, and one with lane state: `chunk_lanes`
    is 1 (an index builds one lane's mask, a state layer takes one lane's
    state), and three overlapping admissions dispatch what one lane a tick,
    round-robin, dispatches: the same programs with the same arguments, and
    no tick yields."""
    mp, tp_ = _tiny_family(tmp_path_factory, family)
    state = _driven(mp, tp_, prefill_buckets=(1, 8, 16), max_seq_len=256)
    sched, eng = state.scheduler, state.engine
    sched.admission_chunk = 16
    assert eng.chunk_lanes == 1 and eng.header.n_experts
    assert eng.header.stateful == (family == "lfm2_moe")
    assert eng.header.indexed == (family == "deepseek_v32")
    script = [[(0, 70), (1, 41)], [], [(2, 30)]]
    base, lanes0 = state.recorder.total_recorded, eng._m_prefill_lanes.value
    want = _one_lane_a_tick(eng, sched._rr, script, 16)
    tick = 0
    while sched.admitting or tick < len(script):
        for lane, n in script[tick] if tick < len(script) else ():
            _begin(state, lane, n + 1)
        tick += 1
        sched._admission_tick()
    got = _chunk_programs(state, base)
    assert [(e["lane"], e["pos"], e["n_tokens"], e["bucket"], e["window"]) for e in got] == want
    assert all(e["lanes"] == [e["lane"]] for e in got) and len(got) == 5 + 3 + 2
    assert eng._m_prefill_lanes.value - lanes0 == len(got)
    assert all(e["expert_rows"] == e["bucket"] for e in got)
    assert all(ls is not None for ls in sched.lanes[:3])
    assert not state.m_admission_yielded.value and not sched._chunk_debt
    sched._drop_all(RuntimeError("the test is over"))


@pytest.mark.parametrize("family", ["qwen3_moe", "afmoe", "pangu_ultra_moe"])
def test_expert_lanes_filled_by_one_program_equal_lanes_filled_one_a_tick(
        tmp_path_factory, family):
    """A model with experts and neither lane state nor an index: one chunk
    program fills every admitting lane (`chunk_lanes` = the lanes), its
    expert block a live lane after another. Three lanes at different
    positions, lengths and last-chunk sizes, filled by one program a tick:
    each lane's cache rows (every stack; a ring's rows by position, the
    window's) and its first 16 greedy tokens are those of the same three
    filled one lane a tick, while a fourth lane stands parked. `afmoe`'s ring
    of 48 rows is shorter than the prompts: lane 1's second chunk, at 37,
    runs over the ring's end in the program that writes lane 0's at 16,
    which does not."""
    mp, _ = _tiny_family(tmp_path_factory, family)
    e = InferenceEngine(
        mp, tp=1, dtype=jnp.float32, temperature=0.0, batch_size=4,
        prefill_buckets=(1, 8, 16), max_seq_len=256,
    )
    assert e.chunk_lanes == 4 and e.chunk_rider_adds_rows
    budget, ring, pad = 16, e.kv_ring, e._lane_pad
    assert ring == (48 if family == "afmoe" else 0)
    held = {0: 0, 1: 21, 3: 60}
    fills = {0: 71, 1: 32, 3: 9}  # last chunks of 7, 16 and 9 rows
    toks = {lane: [2 + (lane * 31 + i * 7) % 250 for i in range(held[lane] + fills[lane] + 1)]
            for lane in held}

    def admit(together: bool):
        e.reset()
        for lane, n in held.items():
            if n:
                e.prefill_lane(lane, toks[lane][: n + 1])
        cur = dict(held)
        end = {lane: held[lane] + fills[lane] for lane in held}
        programs = []
        rows0 = e._m_moe_chunk_rows.labels(rows="computed").value
        base = e.recorder.total_recorded
        while any(cur[lane] < end[lane] for lane in cur):
            left = [lane for lane in cur if cur[lane] < end[lane]]
            for group in ([left] if together else [[lane] for lane in left]):
                chunks = [(lane, toks[lane][cur[lane]:end[lane]], cur[lane]) for lane in group]
                widths = e.prefill_lanes_chunk(chunks, budget=budget)
                programs.append([(lane, cur[lane]) for lane in group])
                for lane, width in zip(group, widths):
                    assert 0 < width <= budget
                    cur[lane] += width
        events = [ev for ev in e.recorder.events() if ev["seq"] > base
                  and ev["kind"] == "step_dispatch" and ev["step"] == "prefill_lane_chunk"]
        assert [ev["expert_rows"] for ev in events] == [
            ev["bucket"] * len(ev["lanes"]) for ev in events]
        assert e._m_moe_chunk_rows.labels(rows="computed").value - rows0 == sum(
            ev["expert_rows"] for ev in events)
        rows = {}
        for lane in held:
            for name, leaf in e.cache.items():
                if name in ("kw", "vw"):
                    at = [pad + p % ring for p in range(
                        max(0, end[lane] - e.header.sliding_window), end[lane])]
                    rows[lane, name] = np.asarray(leaf[:, lane])[:, :, at]
                else:
                    rows[lane, name] = np.asarray(leaf[:, lane, :, : end[lane]])
        return rows, _greedy_16(e, toks, end), programs

    rows_one, tokens_one, programs_one = admit(together=False)
    rows_all, tokens_all, programs_all = admit(together=True)
    assert len(programs_one) == 5 + 2 + 1
    assert programs_all == [[(0, 0), (1, 21), (3, 60)], [(0, 16), (1, 37)], [(0, 32)],
                            [(0, 48)], [(0, 64)]]
    assert tokens_all == tokens_one and all(len(t) == 16 for t in tokens_all)
    assert len({tuple(t) for t in tokens_all}) == 3
    for key, want in rows_one.items():
        assert want.size and np.abs(rows_all[key] - want).max() <= 1e-5, key


@pytest.fixture(scope="module")
def driven_experts_state(tmp_path_factory):
    return _driven(*_tiny_family(tmp_path_factory, "qwen3_moe", seq_len=384))


@pytest.fixture
def driven_experts(driven_experts_state):
    from dllama_tpu.runtime.faults import set_fault_plane

    sched = driven_experts_state.scheduler
    assert not sched.admitting and not any(sched.lanes) and not sched._chunk_debt
    sched.kv = None  # no stored prefix: every test's prompts start at their first token
    yield driven_experts_state
    set_fault_plane("")
    sched._drop_all(RuntimeError("the test is over"))
    sched._rr = -1
    sched._admission_tick()  # nothing admits: what was owed is dropped
    assert not sched._chunk_debt


def _tick(sched):
    """What the scheduler's loop does of a tick for admission and decode,
    inside the tick's span as there (its end takes with it what a failed
    dispatch left open above it)."""
    tick_sp = sched.state.spans.begin("sched_tick", component="scheduler")
    try:
        sched._admission_tick()
        if any(sched.lanes) or sched._flight is not None:
            sched._guarded_step(sched._step_block)
    finally:
        sched.state.spans.end(tick_sp)


def _programs(state, base):
    """The chunk programs' lanes and the decode blocks (None) since `base`,
    in the order of their dispatch."""
    return [e["lanes"] if e["step"] == "prefill_lane_chunk" else None
            for e in state.recorder.events() if e["seq"] > base
            and e["kind"] == "step_dispatch" and e["step"] in ("prefill_lane_chunk", "decode_lanes")]


def _decoding_then_three_admit(state):
    """Lane 3 decodes (an answer of 400 tokens); lanes 0 to 2 begin prompts of
    250, 180 and 120 tokens and one tick's program has carried all three."""
    sched = state.scheduler
    _begin(state, 3, 5, max_tokens=400)
    _tick(sched)
    assert sched.lanes[3] is not None and not sched._chunk_debt
    adms = [_begin(state, lane, n) for lane, n in ((0, 250), (1, 180), (2, 120))]
    base = state.recorder.total_recorded
    _tick(sched)
    assert [adm.cursor for adm in adms] == [100, 100, 100]
    assert _programs(state, base) == [[0, 1, 2], None]
    return adms, base


@pytest.mark.parametrize("decoding", [True, False], ids=["a_lane_decodes", "none_decodes"])
def test_a_program_with_riders_that_add_rows_owes_a_block_a_rider(driven_experts, decoding):
    """A model with experts: a rider adds rows to the chunk program, so a
    program that carried three lanes while a lane decodes is followed by two
    ticks with a block and no chunk program, then a chunk program (whose two
    lanes owe one tick more); the counter counts the yielded ticks. With no
    lane decoding nothing is owed to anyone: chunk programs follow each
    other."""
    state, sched = driven_experts, driven_experts.scheduler
    assert state.engine.chunk_lanes == 4 and state.engine.chunk_rider_adds_rows
    yielded0 = state.m_admission_yielded.value
    if not decoding:
        adms = [_begin(state, lane, n) for lane, n in ((0, 350), (1, 330), (2, 310))]
        base = state.recorder.total_recorded
        for _ in range(3):
            _tick(sched)  # no prompt ends in these: none decodes
        assert _programs(state, base) == [[0, 1, 2], [1, 2, 0], [2, 0, 1]]
        assert [adm.cursor for adm in adms] == [300, 300, 300]
        assert state.m_admission_yielded.value == yielded0
        return
    adms, base = _decoding_then_three_admit(state)
    assert sched._chunk_debt == 2 and sched._rr == 0
    for owed in (1, 0):
        _tick(sched)
        assert sched._chunk_debt == owed and sched._rr == 0
        assert [adm.cursor for adm in adms] == [100, 100, 100]
    assert state.m_admission_yielded.value - yielded0 == 2
    _tick(sched)  # lane 1 leads; lanes 1 and 2 end their prompts
    assert [adm.cursor for adm in adms] == [200, 179, 119] and sched._chunk_debt == 2
    assert _programs(state, base) == [[0, 1, 2], None, None, None, [1, 2, 0], None]
    for _ in range(3):
        _tick(sched)
    assert _programs(state, base)[6:] == [None, None, [0], None]
    assert not sched.admitting and not sched._chunk_debt
    assert state.m_admission_yielded.value - yielded0 == 4


def test_a_dense_program_owes_nothing(tiny_paths, tmp_path):
    """A dense program computes every lane's rows whatever they hold: its
    riders are free, and a chunk program follows the last tick's however
    many lanes that carried, a lane decoding or not."""
    from dllama_tpu.models.synthetic import write_synth_tokenizer

    write_synth_tokenizer(str(tmp_path / "t.t"), 512)  # one that holds every token the model gives
    state = _driven(tiny_paths[0], str(tmp_path / "t.t"))
    sched = state.scheduler
    sched.kv = None
    assert state.engine.chunk_lanes == 4 and not state.engine.chunk_rider_adds_rows
    yielded0 = state.m_admission_yielded.value
    adms, base = _decoding_then_three_admit(state)
    assert not sched._chunk_debt
    _tick(sched)
    _tick(sched)
    assert _programs(state, base) == [[0, 1, 2], None, [1, 2, 0], None, [0], None]
    assert not sched.admitting and state.m_admission_yielded.value == yielded0
    sched._drop_all(RuntimeError("the test is over"))


@pytest.mark.parametrize("case", ["cancelled", "fault", "poison"])
def test_what_a_program_owed_goes_with_the_lanes_it_was_owed_to(driven_experts, case):
    """Two blocks are owed when the admitting lanes' clients go away, or the
    tick's block fails on an intact cache (every request dropped), or takes
    the cache with it (every lane starts over, none decodes, and the next
    tick's program runs): once nothing admits nothing stays owed, and the
    next admission's first chunk is the next tick's."""
    from dllama_tpu.runtime.faults import set_fault_plane

    state, sched = driven_experts, driven_experts.scheduler
    adms, base = _decoding_then_three_admit(state)
    assert sched._chunk_debt == 2
    yielded0 = state.m_admission_yielded.value
    if case == "cancelled":
        for adm in adms:
            adm.job.cancelled = True
        for _ in range(3):
            _tick(sched)  # a tick aborts its lead
        assert not sched.admitting and sched.lanes[3] is not None
        _tick(sched)
    elif case == "fault":
        set_fault_plane("dispatch:op=decode_lanes:every=1")
        _tick(sched)  # the tick yields, and its block fails: all four dropped
        set_fault_plane("")
        assert not sched.admitting and not any(sched.lanes)
        assert state.m_admission_yielded.value - yielded0 == 1
        yielded0 += 1
        _tick(sched)
    else:
        epoch = state.engine.cache_epoch
        set_fault_plane("dispatch:op=decode_lanes:nth=1:kind=poison")
        _tick(sched)  # the tick yields, and its block takes the cache
        assert state.engine.cache_epoch == epoch + 1 and sorted(sched.admitting) == [0, 1, 2, 3]
        assert state.m_admission_yielded.value - yielded0 == 1
        yielded0 += 1
        at = state.recorder.total_recorded
        _tick(sched)
        # no lane decodes, so the next tick's program runs, every lane aboard;
        # it ends lane 3's few rows, which decodes again and is owed for three
        assert _programs(state, at) == [[1, 2, 3, 0], None] and sched._chunk_debt == 3
        assert state.m_admission_yielded.value == yielded0
        sched._drop_all(RuntimeError("enough"))
        _tick(sched)
    assert not sched._chunk_debt
    b = _begin(state, 1, 50)
    at = state.recorder.total_recorded
    _tick(sched)
    assert b.cursor == 49 and [p for p in _programs(state, at) if p] == [[1]]
    assert state.m_admission_yielded.value == yielded0


# -- stall model: chunk events + bounded decode gaps (fake clock) -------------


def test_fake_clock_stall_bounded_by_chunk_plus_block(
    sched_state, monkeypatch
):
    """Fake-clock scheduler run: every engine dispatch (chunk or decode
    block) advances the clock by exactly 1.0 'seconds'. While a long
    prompt admits against an active stream, every
    dllama_decode_stall_seconds observation must then be <= one chunk
    (1.0) + host epsilon — NOT the whole prefill (n_chunks) — and the
    admission must emit exactly ceil(n_fills / chunk_budget) recorder
    chunk events."""
    state = sched_state
    sched, eng, rec = state.scheduler, state.engine, state.recorder

    fake = {"t": 0.0}
    monkeypatch.setattr(sched, "_clock", lambda: fake["t"])
    real_chunk, real_decode = eng.prefill_lane_chunk, eng.collect_lanes

    def chunk_wrapped(*a, **k):
        out = real_chunk(*a, **k)
        fake["t"] += 1.0
        return out

    def decode_wrapped(*a, **k):
        out = real_decode(*a, **k)
        fake["t"] += 1.0
        return out

    monkeypatch.setattr(eng, "prefill_lane_chunk", chunk_wrapped)
    monkeypatch.setattr(eng, "collect_lanes", decode_wrapped)
    samples: list[float] = []
    real_observe = state.m_decode_stall.observe
    monkeypatch.setattr(
        state.m_decode_stall, "observe",
        lambda v: (samples.append(v), real_observe(v))[1],
    )

    job_a = sched.submit(InferenceParams(
        messages=[ChatMessage("user", "go")], max_tokens=220,
        temperature=0.0,
    ))
    _wait_active(state)
    base = rec.total_recorded
    samples.clear()

    long_txt = " ".join(f"w{i:03d}" for i in range(30))
    job_b = sched.submit(InferenceParams(
        messages=[ChatMessage("user", long_txt)], max_tokens=2,
        temperature=0.0,
    ))
    _drain(job_b)
    job_a.cancelled = True
    _drain(job_a)
    # let the loop go idle so the monkeypatched clock is never read again
    deadline = time.time() + 60
    while time.time() < deadline and (sched.admitting or any(sched.lanes)):
        time.sleep(0.02)

    # the radix pool may have matched a stored prefix (the rendered
    # template header is shared across conversations): the chunked
    # prefill covers only the unmatched fill suffix
    admit = next(
        e for e in rec.events()
        if e["seq"] > base and e["kind"] == "admit"
        and e["n_prompt"] == job_b.n_prompt_tokens
    )
    n_fills = job_b.n_prompt_tokens - 1 - admit["reused_prefix_tokens"]
    budget = sched.admission_chunk
    expected_chunks = -(-n_fills // budget)  # ceil
    chunk_events = [
        e for e in rec.events()
        if e["seq"] > base and e["kind"] == "admission_chunk"
    ]
    assert len(chunk_events) == expected_chunks
    assert expected_chunks >= 5  # a genuinely long admission
    assert sum(e["n_tokens"] for e in chunk_events) == n_fills
    assert chunk_events[-1]["done"] and not chunk_events[0]["done"]

    # the stall bound: one chunk (1.0 fake second) + one block of host
    # work; the monolithic path would have shown expected_chunks seconds
    assert samples, "no decode-stall observations"
    assert max(samples) <= 1.5, samples
    assert max(samples) < expected_chunks - 1
    # and the admission really did sit between decode dispatches: at
    # least one observed gap contains a whole chunk
    assert any(s >= 1.0 for s in samples), samples


# -- rehearsal: admission programs pre-compiled off-thread --------------------


def test_admission_rehearsal_precompiles_chunk_programs(sched_state):
    """LaneScheduler startup rehearses the admission path: every prefill
    bucket's lane-prefill chunk program (and the decode block) lands in
    the compile cache via the background prefetch, so the first admission
    under load pays no synchronous compile stall."""
    eng = sched_state.engine
    # the engine's own ladder, `prefill_ladder(8)`, as far as 384 positions
    # hold a rung: the middle rungs at 128 and 256 are rehearsed like any
    assert eng.prefill_buckets == (1, 8, 128, 256)
    keys = [
        ("lane_prefill", b, eng._attn_window(b)) for b in eng.prefill_buckets
    ]
    keys.append(
        ("lane_block", sched_state.scheduler.block_size,
         eng._attn_window(sched_state.scheduler.block_size))
    )
    deadline = time.time() + 180
    while time.time() < deadline and any(k not in eng._compiled for k in keys):
        time.sleep(0.2)
    for k in keys:
        assert k in eng._compiled, k
        assert eng._compile_origin[k] in ("prefetch", "dispatch"), (
            k, eng._compile_origin[k],
        )


# -- knobs: one spelling each, the flag's --------------------------------------

# the scheduler's, the pool's and the retry policy's knobs, whose DLLAMA_*
# twins `resolve_lane_knobs`, `resolve_kv_knobs`, `resolve_stream_knobs` and
# `resolve_resilience_knobs` read until PR 45
KNOB_TWINS = (
    "DLLAMA_LANE_BLOCK", "DLLAMA_ADMISSION_CHUNK", "DLLAMA_KV_PAGE_SIZE",
    "DLLAMA_KV_POOL_PAGES", "DLLAMA_KV_NATIVE", "DLLAMA_MAX_STREAMS",
    "DLLAMA_RETRY_MAX", "DLLAMA_RETRY_BACKOFF_MS", "DLLAMA_MAX_QUEUE_DEPTH",
)


@pytest.mark.parametrize("name", KNOB_TWINS)
def test_lane_knob_resolution(unflagged, flagged, name):
    """The parser holds the flag's default and the state what that default
    means, with the former variable set; an explicit flag reaches the
    scheduler, the pool or the state."""
    assert_one_spelling(name, unflagged, flagged)


def test_scheduler_knob_threading(sched_state):
    """The knobs reach the LaneScheduler (no hardcoded block_size=8)."""
    sched = sched_state.scheduler
    assert sched.block_size == 4
    assert sched.admission_chunk == 6


# -- the decode loop runs one block ahead --------------------------------------
#
# With a block in flight the scheduler dispatches the next one before it
# collects: each request still streams the tokens the drained order (collect,
# then dispatch) streams, and a stream whose end only its tokens show runs
# through one more block, for nobody.


@pytest.fixture(scope="module")
def ahead_state(tmp_path_factory):
    """`sched_state` with a tokenizer as wide as the model's vocabulary, so
    a sampled lane's ids all decode."""
    d = tmp_path_factory.mktemp("ahead")
    mp, tp_ = str(d / "m.m"), str(d / "t.t")
    make_tiny_model(mp, cfg=CFG)
    make_tiny_tokenizer(tp_, chat_template="<|start_header_id|>", pad_to=CFG["vocab_size"])
    tok = Tokenizer(tp_)
    engine = InferenceEngine(
        mp, tokenizer=tok, tp=1, dtype=jnp.float32, temperature=0.0, seed=3,
        batch_size=3,
    )
    return ApiState(engine, tok, lane_block_size=4, admission_chunk=6)


def _ids(job, timeout=300, cancel_after=None):
    """(token ids, finish reason) of a job submitted with `include_tokens`;
    `cancel_after` deltas the client goes away."""
    ids, n = [], 0
    deadline = time.time() + timeout
    while True:
        kind, payload = job.events.get(timeout=max(0.1, deadline - time.time()))
        if kind == "delta":
            ids += payload["tokens"] if isinstance(payload, dict) else []
            n += 1
            if n == cancel_after:
                job.cancelled = True
        elif kind == "done":
            return ids, payload
        else:
            raise AssertionError(f"job errored: {payload}")


def _idle(sched, timeout=60):
    deadline = time.time() + timeout
    while time.time() < deadline:
        # (a block being collected is in nobody's hands but the watchdog's)
        if not (sched.admitting or any(sched.lanes) or sched.pending
                or sched._flight is not None
                or sched.state.watchdog._dispatch_kind is not None):
            return
        time.sleep(0.02)
    raise AssertionError("the scheduler did not go idle")


def _run_order(state, order, params, cancel_after=None, beside=()):
    """Submit `params` together and drain them under one order: `ahead` is
    the scheduler's own; `drained` collects the block in flight before
    every decode tick. `beside`: more jobs, whose clients stay. Returns what each job streamed, what was published,
    the run's recorder events, the jobs' token counts and what every tick
    found at its begin."""
    sched, rec = state.scheduler, state.recorder
    base = rec.total_recorded
    published = []
    real_publish, real_step = sched.kv.publish, sched._step_block
    real_tick, wd = sched._admission_tick, state.watchdog
    sched.kv.publish = lambda lane, tokens: (
        published.append(len(tokens)), real_publish(lane, tokens))[1]
    if order == "drained":
        sched._step_block = lambda: (sched._drain("verify"), real_step())
    # every tick, before its dispatches: is a block in flight, and does the
    # watchdog hold a dispatch open across ticks (it must not: its bracket
    # is the collect's, and a dispatch's)
    ticks = []
    sched._admission_tick = lambda: (
        ticks.append((sched._flight is not None, wd._dispatch_kind)), real_tick())[1]
    try:
        jobs = _submit_together(state, *params, *beside)
        out = [_ids(job, cancel_after=cancel_after) for job in jobs[:len(params)]]
        out += [_ids(job) for job in jobs[len(params):]]
        _idle(sched)
    finally:
        sched.kv.publish = real_publish
        sched.__dict__.pop("_step_block", None)
        sched.__dict__.pop("_admission_tick", None)
    assert wd._dispatch_kind is None
    events = [e for e in rec.events() if e["seq"] > base]
    return out, sorted(published), events, [job.n_completion for job in jobs], ticks


def _blocks(events):
    return [e for e in events if e["kind"] == "step_dispatch" and e["step"] == "decode_lanes"]


def _params(text, **kw):
    return InferenceParams(messages=[ChatMessage("user", text)],
                           include_tokens=True, **kw)


def test_running_ahead_streams_what_the_drained_order_streams(ahead_state):
    """Six requests through three lanes, greedy and seeded sampled, lengths
    that end inside a block and at its edge: token for token the drained
    order's. While a request waits for a lane the blocks are dispatched
    ahead; once nobody waits (a request that came now would stand behind a
    block queued ahead) the tick is dispatch, then collect, again."""
    state = ahead_state
    params = [
        _params("alpha one", max_tokens=38, temperature=0.0),
        _params("beta two two", max_tokens=32, temperature=0.9, seed=5),
        _params("gamma three " * 3, max_tokens=9, temperature=0.0),
        _params("delta four", max_tokens=29, temperature=0.7, seed=6),
        _params("epsilon five", max_tokens=12, temperature=0.0),
        _params("zeta six", max_tokens=10, temperature=0.8, seed=7),
    ]
    counted = {reason: state.m_decode_blocks.labels(
        order="drained_first" if reason else "ahead", reason=reason)
        for reason in ("", "verify", "no_queue")}
    before = {k: c.value for k, c in counted.items()}
    want, pub_want, ev_want, n_want, _ = _run_order(state, "drained", params)
    assert counted[""].value == before[""]
    assert counted["verify"].value > before["verify"]
    assert not any(e["ahead"] for e in _blocks(ev_want))
    mid = counted[""].value
    got, pub_got, ev_got, n_got, ticks = _run_order(state, "ahead", params)
    assert got == want and n_got == n_want
    # a block is in flight from one tick to the next; no watchdog bracket is
    assert sum(flying for flying, _ in ticks) >= 4
    assert {kind for _, kind in ticks} == {None}
    # (the ids a delta carries lag by what the stop detector holds back)
    assert n_got == [38, 32, 9, 29, 12, 10] and all(ids for ids, _ in got)
    assert all(reason == "length" for _, reason in got)
    assert pub_got == pub_want  # every stream's pages, for its history
    blocks = _blocks(ev_got)
    n_ahead = sum(e["ahead"] for e in blocks)
    assert counted[""].value - mid == n_ahead >= 4
    # and the blocks after the last waiting request got its lane say why not
    assert counted["no_queue"].value > before["no_queue"]
    assert not blocks[-1]["ahead"]
    # the lengths are known ahead, so no lane ran a block for nobody
    assert sum(e["n_live"] for e in blocks) == sum(e["n_live"] for e in _blocks(ev_want))


@pytest.mark.parametrize("end", ["stop_string", "cancelled"])
def test_a_stream_that_ends_on_its_tokens_runs_one_block_for_nobody(ahead_state, end):
    """A stop string, or a client that goes away, ends a stream inside block
    n with block n + 1 already in flight (two longer streams keep the other
    lanes and two more requests wait for one): nothing of n + 1 is emitted,
    what is published is the history up to the stop, and the lane is free."""
    state = ahead_state
    sched = state.scheduler
    prompt = f"ends on its tokens {end}"
    ((full, _),), _, _, n_full, _ = _run_order(
        state, "ahead", [_params(prompt, max_tokens=24, temperature=0.0)])
    assert n_full == [24]
    kw = dict(max_tokens=24, temperature=0.0)
    if end == "stop_string":
        # a piece the stream reaches inside its third block
        piece = state.tokenizer.stream_decoder()
        pieces = [piece.decode(t) or "" for t in full]
        at = next(i for i in range(9, 12) if pieces[i].strip())
        kw["stop"] = [pieces[at]]
    params = [_params(prompt, **kw),
              _params("a longer stream beside it", max_tokens=60, temperature=0.0),
              _params("and another one", max_tokens=56, temperature=0.0),
              _params("one that waits for a lane", max_tokens=12, temperature=0.0),
              _params("and a second", max_tokens=10, temperature=0.0)]
    cancel_after = 2 if end == "cancelled" else None

    def run(order):
        # only the first job's client goes away
        out, pub, events, n, _ = _run_order(state, order, params[:1], cancel_after, beside=params[1:])
        return out, pub, events, n

    want, pub_want, ev_want, n_want = run("drained")
    got, pub_got, ev_got, n_got = run("ahead")
    (ids, reason), beside = got[0], got[1:]
    assert beside == want[1:] and n_got[1:] == n_want[1:] == [60, 56, 12, 10]
    finish, = [e for e in ev_got if e["kind"] == "finish" and e["reason"] != "length"]
    assert finish["n_completion"] == n_got[0]
    blocks = _blocks(ev_got)
    if end == "stop_string":
        assert got == want and n_got == n_want and reason == "stop"
        assert len(ids) < 16 and ids == full[:len(ids)]
        assert pub_got == pub_want and len(pub_got) == 5
        # the lane ran on through the block in flight, and only that one
        assert sum(e["n_live"] for e in blocks) == 1 + sum(e["n_live"] for e in _blocks(ev_want))
    else:
        # when the scheduler sees the flag is the client's timing; what it
        # streamed is a prefix of the stream, and nothing of it is published
        assert reason == "cancelled" and ids == full[:len(ids)] and len(ids) < 24
        assert pub_got == pub_want and len(pub_got) == 4
    # the block in flight when the stream ended ran its lane live, beside
    # the streams admitted by then (which of the three an adopt tick led
    # first is the round-robin cursor's, left by the runs before)
    last = [e for e in blocks if e["seq"] < finish["seq"]][-1]
    live = set()
    for e in ev_got:
        if e["seq"] < last["seq"] and e["kind"] in ("admit", "finish"):
            (live.add if e["kind"] == "admit" else live.discard)(e["lane"])
    assert finish["lane"] in live and len(live) >= 2
    assert last["ahead"] == 1 and last["n_live"] == len(live)
    assert sched._flight is None and not any(sched.lanes)
    sched.kv.check()


def test_streams_dropped_with_a_block_in_flight_leave_none_to_continue(ahead_state):
    """A read-back that fails on an intact cache drops every stream while
    the block dispatched ahead of it is still in flight: that block is
    abandoned un-read, in the engine too, so the waiting requests' first
    block is nobody's successor (`ahead: 0`, `first_block`)."""
    state = ahead_state
    sched, eng, rec = state.scheduler, state.engine, state.recorder
    first = state.m_decode_blocks.labels(order="drained_first", reason="first_block")
    params = [_params(f"dropped beside a block in flight {i}", max_tokens=40, temperature=0.0)
              for i in range(5)]
    real, failed = eng.collect_lanes, {}

    def collect_lanes(block):
        flight = sched._flight
        if not failed and flight is not None and flight.block is not block:
            failed.update(seq=rec.total_recorded, first=first.value, ahead=flight.block)
            raise RuntimeError("injected read-back failure")
        return real(block)

    eng.collect_lanes = collect_lanes
    try:
        jobs = _submit_together(state, *params)
        ends = []
        for job in jobs:
            kind = None
            while kind not in ("done", "error"):
                kind, payload = job.events.get(timeout=300)
            ends.append((kind, payload))
        _idle(sched)
    finally:
        del eng.collect_lanes
    assert failed and eng._uncollected is None and sched._flight is None
    assert [kind for kind, _ in ends] == ["error"] * 3 + ["done"] * 2
    assert all(err["retryable"] and "injected" in err["message"] for _, err in ends[:3])
    assert [job.n_completion for job in jobs[3:]] == [40, 40]
    after = [e for e in _blocks(rec.events()) if e["seq"] > failed["seq"]]
    assert after[0]["ahead"] == 0 and first.value == failed["first"] + 1
    sched.kv.check()
