"""Second-generation speculation (ISSUE 18): cross-lane shared n-gram
store + resident draft model.

The contract is unchanged from ISSUE 10 — speculation must be invisible
in the output — but the draft SOURCES grow:

* radix node identity — every tree node carries a stable ``node_id``,
  ``match`` reports the deepest matching edge's id as the anchor, and an
  edge SPLIT keeps the id on the shared-prefix head, so streams grouped
  under an anchor stay grouped after later inserts carve the edge up;
* shared store — accepted runs publish under the lane's anchor; a
  sibling lane that matched the same node drafts the published
  continuation (never its own), LRU-capped at both levels;
* source ladder — private n-gram vs shared store by longest suffix
  match (ties private), resident draft model when both run dry or when
  a fully rejected n-gram draft put the lane in cooldown (mode
  ``draft``), with one AIMD budget across all three and per-source
  accounting;
* parity — greedy spec-on streams are byte-identical to spec-off for
  BOTH new sources, including rejected-draft rewinds composing with
  pool publish/reuse, mid-stream park/resume, and poison recovery
  (the warm-start satellite: a resumed stream keeps its drafter);
* concurrency — publish-while-draft replays deterministically under the
  seeded Interleaver and is lockwatch-clean.
"""

import time

import jax.numpy as jnp
import pytest

from dllama_tpu.kv.radix import RadixTree
from dllama_tpu.runtime.api_server import (
    ApiState,
    ChatMessage,
    InferenceParams,
)
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.runtime.spec import (
    SOURCE_DRAFT,
    NgramDrafter,
    NgramIndex,
    SharedNgramStore,
)
from dllama_tpu.tokenizer import Tokenizer

from helpers import FORMER_TWINS, flags_state, make_tiny_model, make_tiny_tokenizer

CFG = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
           head_dim=16, vocab_size=288, seq_len=384)

# natural-language-ish content: non-repetitive, so the PRIVATE n-gram
# index has little to lock onto and the new sources carry the drafting
NL = "walk through how the scheduler shares computed prefixes, step by step"


@pytest.fixture(scope="module")
def tiny_paths(tmp_path_factory):
    d = tmp_path_factory.mktemp("spec2")
    mp, tp_ = str(d / "m.m"), str(d / "t.t")
    make_tiny_model(mp, cfg=CFG)
    make_tiny_tokenizer(
        tp_, chat_template="<|start_header_id|>", pad_to=CFG["vocab_size"]
    )
    return mp, tp_


def _mk_state(tiny_paths, *, draft=False, **kw):
    mp, tp_ = tiny_paths
    tok = Tokenizer(tp_)
    engine = InferenceEngine(
        mp, tokenizer=tok, tp=1, dtype=jnp.float32, temperature=0.0, seed=3,
        batch_size=3,
    )
    if draft:
        # the tiny target doubles as its own resident draft (same
        # tokenizer by construction) — serve() does this via
        # --draft-model; scheduler-level tests load it directly
        engine.init_draft_model(mp)
    state = ApiState(
        engine, tok, lane_block_size=4, admission_chunk=6, **kw
    )
    assert state.scheduler is not None
    return state


@pytest.fixture(scope="module")
def shared_state(tiny_paths):
    return _mk_state(tiny_paths, speculation="shared", spec_k=4)


@pytest.fixture(scope="module")
def draft_state(tiny_paths):
    return _mk_state(tiny_paths, draft=True, speculation="draft", spec_k=4)


@pytest.fixture(scope="module")
def off_state(tiny_paths):
    return _mk_state(tiny_paths)


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


def _greedy(content, max_tokens=48):
    return InferenceParams(
        messages=[ChatMessage(role="user", content=content)],
        temperature=0.0, max_tokens=max_tokens, stream=True,
    )


def _source_count(state, source):
    if state.m_spec_source is None:
        return 0.0
    return state.m_spec_source.labels(source=source).value


# -- radix node identity ------------------------------------------------------


@pytest.mark.fast
def test_radix_anchor_reported_and_absent():
    t = RadixTree(4)
    assert t.match([1, 2, 3]).anchor is None  # empty tree: no anchor
    t.insert([1, 2, 3, 4, 5, 6, 7, 8], [10, 11], 0)
    mr = t.match([1, 2, 3, 4, 5, 6, 7, 8])
    assert mr.n_tokens == 8 and mr.anchor is not None
    # a PARTIAL edge match still anchors on that edge
    assert t.match([1, 2, 9]).anchor == mr.anchor
    assert t.match([9, 9, 9]).anchor is None


@pytest.mark.fast
def test_radix_anchor_survives_edge_split():
    """The id streams anchored on must follow the shared prefix through
    a split: the head node inherits it, the tail gets a fresh one."""
    t = RadixTree(4)
    t.insert([1, 2, 3, 4, 5, 6, 7, 8], [10, 11], 0)
    before = t.match([1, 2, 3, 4]).anchor
    # diverge after 4 tokens: splits the single 8-token edge
    t.insert([1, 2, 3, 4, 9, 9, 9, 9], [12, 13], 0)
    assert t.match([1, 2, 3, 4]).anchor == before
    # the two continuations hang off distinct (fresh) identities
    old_tail = t.match([1, 2, 3, 4, 5, 6, 7, 8]).anchor
    new_tail = t.match([1, 2, 3, 4, 9, 9, 9, 9]).anchor
    assert before not in (old_tail, new_tail)
    assert old_tail != new_tail


@pytest.mark.fast
def test_radix_node_ids_unique():
    t = RadixTree(2)
    t.insert([1, 2, 3, 4], [10, 11], 0)
    t.insert([1, 2, 5, 6], [12], 1)
    t.insert([7, 8], [13], 0)
    seen, stack = [], [t.root]
    while stack:
        n = stack.pop()
        seen.append(n.node_id)
        stack.extend(n.children.values())
    assert len(seen) == len(set(seen))


# -- shared store -------------------------------------------------------------


@pytest.mark.fast
def test_shared_store_sibling_lookup_and_self_exclusion():
    st = SharedNgramStore(max_n=3)
    st.publish(7, "a", [1, 2, 3, 4, 5, 6])
    # a sibling with the same anchor drafts a's continuation of (2,3)
    assert st.lookup(7, [2, 3], 3, exclude_stream="b") == [4, 5, 6]
    # ... but a stream never drafts from its own publishes
    assert st.lookup(7, [2, 3], 3, exclude_stream="a") == []
    # unknown anchor: miss
    assert st.lookup(99, [2, 3], 3) == []
    s = st.stats()
    assert s["groups"] == 1 and s["streams"] == 1 and s["tokens"] == 6
    assert s["hits"] == 1 and s["misses"] == 2


@pytest.mark.fast
def test_shared_store_incremental_publish_and_lru():
    st = SharedNgramStore(max_n=2, max_groups=2, max_streams_per_group=2)
    st.publish(1, "a", [1, 2, 3])
    st.publish(1, "a", [4, 5])          # same stream: extends the index
    assert st.lookup(1, [3], 2, exclude_stream="b") == [4, 5]
    st.publish(1, "b", [9, 9])
    st.publish(1, "c", [8, 8])          # 3rd stream: LRU-evicts "a"
    assert st.lookup(1, [3], 2, exclude_stream="z") == []
    st.publish(2, "x", [1])
    st.publish(3, "y", [1])             # 3rd group: LRU-evicts group 1
    assert st.stats()["groups"] == 2
    assert st.lookup(1, [9], 1) == []


@pytest.mark.fast
def test_ngram_index_suffix_lookup():
    ix = NgramIndex(max_n=3)
    ix.extend([5, 6, 7, 8, 5, 6])
    # an EXTERNAL suffix (another lane's context) drives the lookup
    assert ix.lookup_suffix([0, 5, 6], 2) == [7, 8]
    # continuation only at the index's own end: fall back to the
    # previous occurrence rather than running off the edge
    assert ix.lookup_suffix([9, 9], 2) == []


@pytest.mark.fast
def test_drafter_shared_source_ladder():
    store = SharedNgramStore(max_n=3)
    store.publish(5, "other", [1, 2, 3, 4, 5, 6])
    dr = NgramDrafter(
        k_max=3, shared_store=store, stream_id="me", anchor=5,
        anchor_offset=2,
    )
    # private index has no repeat -> the shared sibling supplies a draft
    dr.update([7, 1, 2, 3])
    assert dr.draft() == [4, 5, 6]
    assert dr.last_source == "shared"
    # private hit wins the ladder
    dr2 = NgramDrafter(
        k_max=2, shared_store=store, stream_id="me", anchor=5,
        anchor_offset=0,
    )
    dr2.update([1, 2, 1, 2, 1])
    assert dr2.draft() == [2, 1]
    assert dr2.last_source == "ngram"


@pytest.mark.fast
def test_drafter_publishes_from_anchor_offset_and_rebinds():
    store = SharedNgramStore(max_n=3)
    dr = NgramDrafter(
        k_max=4, shared_store=store, stream_id="s1", anchor=9,
        anchor_offset=3,
    )
    # the first publish seeds the JUNCTION — the last max_n-1 tokens of
    # the shared anchor prefix ride along so a sibling whose suffix
    # still ends in prefix tokens can match the run's opening tokens
    dr.update([1, 2, 3, 4, 5])
    assert store.stats()["tokens"] == 4  # [2, 3] junction + [4, 5] run
    assert store.lookup(9, [4], 1, exclude_stream="zz") == [5]
    # the bridge: a prefix-tail suffix finds the first run token
    assert store.lookup(9, [2, 3], 1, exclude_stream="zz") == [4]
    # rebinding to a new anchor resets the publish cursor
    dr.rebind(12, 1)
    dr.update([1, 2, 3, 4, 5, 6])
    assert store.lookup(12, [5], 1, exclude_stream="zz") == [6]
    # same-anchor rebind is a no-op (no double publish)
    before = store.stats()["tokens"]
    dr.rebind(12, 0)
    dr.update([1, 2, 3, 4, 5, 6])
    assert store.stats()["tokens"] == before


@pytest.mark.fast
def test_drafter_model_budget_gating():
    dr = NgramDrafter(k_max=4, cooldown=2, use_draft_model=True)
    dr.update([1, 2, 3, 4])
    assert dr.draft() == []            # nothing from the n-gram sources
    assert dr.model_budget() == 4      # -> the model gets the full budget
    assert dr.model_budget(budget=2) == 2
    dr.feedback(4, 0)                  # zero acceptance: halve + cooldown
    assert dr.draft() == []
    # the n-gram cooldown re-routes the budget to the model (the model
    # carries none of the just-discredited n-gram evidence)
    assert dr.model_budget() == 2
    dr.last_source = SOURCE_DRAFT      # as the scheduler records it
    dr.feedback(2, 0)                  # a failed MODEL draft must NOT
    assert dr._cooldown == 1           # re-arm the cooldown (no
    dr.draft()                         # model->cooldown->model pin)
    assert dr.model_budget() == 1      # k halved again, cooldown tick
    dr.draft()
    assert dr.model_budget() == 1      # cooldown over: dry-sources path
    dr2 = NgramDrafter(k_max=4, use_draft_model=False)
    dr2.update([1, 2, 3, 4])
    dr2.draft()
    assert dr2.model_budget() == 0     # mode shared: no model drafting
    dr3 = NgramDrafter(k_max=3, use_draft_model=True)
    dr3.update([1, 2, 1, 2])
    assert dr3.draft() == [1, 2, 1]    # n-gram hit: model not consulted
    assert dr3.model_budget() == 0


@pytest.fixture(scope="module")
def flagged(tmp_path_factory, tiny_paths):
    """`--speculation draft --draft-model <the target itself>`."""
    mp, _ = tiny_paths
    yield from flags_state(
        tmp_path_factory, "--speculation", "draft", "--draft-model", mp,
        paths=tiny_paths)


@pytest.mark.parametrize("name", ["DLLAMA_DRAFT_MODEL"])
def test_resolve_draft_model(monkeypatch, unflagged, flagged, tiny_paths, name):
    """No draft model unless `--draft-model` names one, with the former
    variable set (`resolve_draft_model` read it until PR 45): mode `draft`
    without the flag fails at start-up though the variable names a path,
    and the flag's checkpoint is the one the engine loads."""
    from dllama_tpu.runtime.api_server import serve

    _, dest, default, read = FORMER_TWINS[name]
    args, state = unflagged
    assert getattr(args, dest) is default and read(state) is None
    args, state = flagged
    assert args.draft_model == tiny_paths[0]
    assert state.engine.has_draft_model and state.scheduler.spec_mode == "draft"
    monkeypatch.setenv(name, tiny_paths[0])
    _, bare = unflagged
    with pytest.raises(ValueError, match="--draft-model"):
        serve(bare.engine, bare.tokenizer, speculation="draft")
    assert not bare.engine.has_draft_model


@pytest.mark.fast
def test_draft_cli_flags():
    import argparse

    from dllama_tpu.cli import add_engine_args

    parser = argparse.ArgumentParser()
    add_engine_args(parser)
    args = parser.parse_args(
        ["--model", "m", "--speculation", "draft", "--draft-model", "d.m"]
    )
    assert args.speculation == "draft" and args.draft_model == "d.m"
    args = parser.parse_args(["--model", "m", "--speculation", "shared"])
    assert args.speculation == "shared" and args.draft_model is None


# -- publish-while-draft race (seeded replay, lockwatch-clean) ----------------


@pytest.mark.fast
@pytest.mark.parametrize("seed", [0, 3, 11])
def test_shared_store_publish_while_draft_race(seed):
    """A publisher extending an anchor group while a sibling drafts from
    it, replayed under a seeded schedule: every interleaving yields
    either a miss or a prefix of the final continuation — never garbage
    — and identical seeds replay identical schedules."""
    from dllama_tpu.analysis.lockwatch import Interleaver

    def round_():
        store = SharedNgramStore(max_n=3)
        itl = Interleaver(seed=seed)
        results = []

        def publisher():
            store.publish(4, "w", [1, 2, 3, 4])
            itl.step("published-head")
            store.publish(4, "w", [5, 6])
            itl.step("published-tail")
            store.publish(4, "w", [7, 8])

        def drafter():
            for label in ("d1", "d2", "d3"):
                results.append(store.lookup(
                    4, [3, 4], 4, exclude_stream="me"
                ))
                itl.step(label)

        itl.spawn("pub", publisher)
        itl.spawn("draft", drafter)
        trace = itl.run()
        return trace, results

    trace1, res1 = round_()
    trace2, res2 = round_()
    assert trace1 == trace2 and res1 == res2  # seeded replay
    # every interleaving yields a miss or a draft built purely from the
    # tokens published SO FAR: a prefix of the final continuation, or
    # the cyclic extension of a shorter published prefix (e.g.
    # [5, 6, 5, 6] when the lookup lands between the two publishes).
    # Either is safe — every draft token is verified before emission.
    full = [5, 6, 7, 8]
    for d in res1:
        assert d == [] or (
            d[0] == full[0] and set(d) <= set(full)
        ), (res1, trace1)


# -- resident draft model: engine level ---------------------------------------


def test_engine_draft_model_load_and_greedy_parity(tiny_paths):
    """The draft model loads through the normal reader, keeps its own
    cache, and (being the target's own checkpoint here) proposes exactly
    the target's greedy continuation."""
    mp, _ = tiny_paths
    e = InferenceEngine(
        mp, tp=1, dtype=jnp.float32, temperature=0.0, seed=3, batch_size=2
    )
    assert not e.has_draft_model
    e.init_draft_model(mp)
    assert e.has_draft_model and e.draft_seq_len == CFG["seq_len"]

    prompt = [2 + (i * 5) % 250 for i in range(13)]
    pos0, pending = len(prompt) - 1, prompt[-1]
    # prefill_lane takes the FULL prompt and drops the pending token
    # itself; draft_prefill is a raw catch-up and writes every token
    # it is given, so it gets the explicit prompt[:-1] fill rows
    e.prefill_lane(0, prompt, 0)
    ref = [r[0] for r in e.decode_lanes(
        [pending, 0], [pos0, 0], 4, [True, False]
    )]
    e.draft_prefill(0, prompt[:-1], 0)
    props = e.draft_propose([pending, 0], [pos0, 0], [True, False], 4)
    assert props[0] == ref
    # draft programs live under their own compile-cache family
    kinds = {k[0] for k in e._compiled if isinstance(k, tuple)}
    assert "draft_prefill" in kinds and "draft_step" in kinds


def test_engine_draft_model_rejects_vocab_mismatch(tiny_paths, tmp_path):
    mp, _ = tiny_paths
    other = str(tmp_path / "othervocab.m")
    make_tiny_model(other, cfg={**CFG, "vocab_size": 128})
    e = InferenceEngine(
        mp, tp=1, dtype=jnp.float32, temperature=0.0, seed=3, batch_size=2
    )
    with pytest.raises(ValueError, match="vocab"):
        e.init_draft_model(other)
    assert not e.has_draft_model


# -- scheduler parity: shared store -------------------------------------------


def test_shared_mode_fanout_parity_and_source(shared_state, off_state):
    """A seeded fanout — identical greedy requests in sequence — stays
    byte-identical to spec-off while later streams draft from earlier
    streams' published continuations through the shared store."""
    want = _drain(off_state.scheduler.submit(_greedy(NL)))
    outs = [
        _drain(shared_state.scheduler.submit(_greedy(NL)))
        for _ in range(4)
    ]
    assert all(o == want for o in outs), (outs, want)
    # sibling continuations actually flowed: the shared source counted
    # drafts, and the store's gauges show live occupancy
    assert _source_count(shared_state, "shared") > 0
    assert shared_state.g_spec_store_tokens.value > 0
    assert shared_state.g_spec_store_hits.value > 0
    # mode shared never touches the draft model
    assert _source_count(shared_state, "draft") == 0
    assert not shared_state.engine.has_draft_model
    kinds = {
        k[0] for k in shared_state.engine._compiled if isinstance(k, tuple)
    }
    assert "draft_step" not in kinds and "draft_prefill" not in kinds


def test_shared_mode_distinct_prompts_stay_private(shared_state, off_state):
    """Streams with unrelated prompts share no anchor: their outputs
    still match spec-off (the store can only ever LOWER acceptance to
    zero, never corrupt output)."""
    for prompt in ("completely unrelated first topic",
                   "another topic with no common prefix at all"):
        want = _drain(off_state.scheduler.submit(_greedy(prompt, 24)))
        got = _drain(shared_state.scheduler.submit(_greedy(prompt, 24)))
        assert got == want


def test_shared_mode_poison_recovery_warm_parity(shared_state, off_state):
    """A mid-stream decode poison forces the lane through recovery
    admission; the resumed stream keeps its drafter (warm-start
    satellite) and the bytes still match spec-off."""
    from dllama_tpu.runtime.faults import set_fault_plane

    prompt = NL + " and repeat the walk again from the top"
    want = _drain(off_state.scheduler.submit(_greedy(prompt, 40)))
    b_recovered = shared_state.m_lanes_recovered.value
    job = shared_state.scheduler.submit(_greedy(prompt, 40))
    deadline = time.time() + 300
    while job.n_completion < 6 and time.time() < deadline:
        time.sleep(0.02)
    assert job.n_completion >= 6
    set_fault_plane("dispatch:nth=1:kind=poison")
    try:
        got = _drain(job)
    finally:
        set_fault_plane("")
    assert got == want, "recovered spec stream diverged from spec-off"
    assert shared_state.m_lanes_recovered.value > b_recovered
    # the recovery path re-anchored the drafter rather than dropping it
    assert shared_state.scheduler.drafters == {} or all(
        isinstance(d, NgramDrafter)
        for d in shared_state.scheduler.drafters.values()
    )


def test_shared_mode_park_resume_parity(tiny_paths):
    """Oversubscription parks/resumes mid-stream; parked streams carry
    their drafter through _LaneState and the fanout still matches the
    off server byte for byte."""
    on = _mk_state(tiny_paths, speculation="shared", spec_k=4, max_streams=5)
    off = _mk_state(tiny_paths, max_streams=5)

    def fanout(state):
        jobs = [
            state.scheduler.submit(_greedy(NL, 32)) for _ in range(5)
        ]
        return [_drain(j) for j in jobs]

    try:
        want = fanout(off)
        got = fanout(on)
        assert got == want
        assert on.recorder.events(kind="stream_park"), (
            "oversubscription round never parked — parity not exercised"
        )
    finally:
        on.scheduler.stop()
        off.scheduler.stop()


# -- scheduler parity: resident draft model -----------------------------------


def test_draft_mode_stream_parity_and_sources(draft_state, off_state):
    """Draft-model speculation is byte-invisible on a non-repetitive
    prompt (where the n-gram sources run dry and the model drafts), and
    the per-source counter + step-time histogram actually moved."""
    want = _drain(off_state.scheduler.submit(_greedy(NL)))
    got = _drain(draft_state.scheduler.submit(_greedy(NL)))
    assert got == want
    assert _source_count(draft_state, "draft") > 0
    h = draft_state.engine._m_spec_draft_ms
    assert h is not None and h.labels(kind="propose").count > 0
    kinds = {
        k[0] for k in draft_state.engine._compiled if isinstance(k, tuple)
    }
    assert "draft_prefill" in kinds and "draft_step" in kinds


def test_draft_mode_rewind_publish_radix_compose(draft_state):
    """Rejected model drafts rewind, the finished stream publishes only
    verified rows, and the identical follow-up adopts the prefix AND
    streams the same bytes — the three subsystems compose."""
    prompt = "compose rewind publish and reuse in one stream"
    text1, reason1 = _drain(draft_state.scheduler.submit(_greedy(prompt)))
    evs = draft_state.recorder.events(kind="spec_verify")
    assert any(e["accepted"] < e["k"] for e in evs), (
        "expected at least one rejected-draft rewind"
    )
    reused0 = draft_state.m_reused_tokens.value
    text2, reason2 = _drain(draft_state.scheduler.submit(_greedy(prompt)))
    assert (text2, reason2) == (text1, reason1)
    assert draft_state.m_reused_tokens.value > reused0


def test_draft_mode_poison_recovery_parity(draft_state, off_state):
    """Recovery with a resident draft model: the target cache rebuild +
    re-prefill resume must not let stale DRAFT-cache rows leak into
    output (cursors reset, catch-up re-feeds verified history)."""
    from dllama_tpu.runtime.faults import set_fault_plane

    prompt = "recover the draft cache cursors after a poisoned dispatch"
    want = _drain(off_state.scheduler.submit(_greedy(prompt, 40)))
    job = draft_state.scheduler.submit(_greedy(prompt, 40))
    deadline = time.time() + 300
    while job.n_completion < 6 and time.time() < deadline:
        time.sleep(0.02)
    assert job.n_completion >= 6
    set_fault_plane("dispatch:nth=1:kind=poison")
    try:
        got = _drain(job)
    finally:
        set_fault_plane("")
    assert got == want


# -- off stays a pure bypass --------------------------------------------------


@pytest.mark.fast
def test_off_mode_has_no_store_no_draft_no_metrics(off_state):
    sched = off_state.scheduler
    assert sched.spec_store is None and not sched.drafters
    assert off_state.m_spec_source is None
    assert off_state.g_spec_tokens_per_pass is None
    assert off_state.g_spec_store_tokens is None
    assert not off_state.engine.has_draft_model
    kinds = {
        k[0] for k in off_state.engine._compiled if isinstance(k, tuple)
    }
    assert not kinds & {"lane_verify", "draft_prefill", "draft_step"}
