"""Model-free speculative decoding on the lane path (ISSUE 10).

Prompt-lookup speculation must be invisible in the output: greedy
streams with speculation ON are byte-identical to speculation OFF,
because the scheduler only ever emits tokens the batched verify pass
itself argmax'd. These tests pin the contract points:

* drafter mechanics — the n-gram index proposes the continuation of the
  most recent EARLIER occurrence of the current suffix, and the adaptive
  k backs off (halve + cooldown) on low acceptance;
* engine verify parity — one `verify_lanes` dispatch accepts exactly the
  prefix a step-by-step greedy decode would produce, and a rejected
  draft's rewind leaves the lane's KV able to continue byte-identically;
* scheduler parity — spec-on vs spec-off greedy SSE streams match, also
  when a temperature>0 lane joins the batch mid-stream (per-lane
  fallback shares the dispatch group);
* pool composition — a finish after rejected-draft rewinds publishes
  only valid rows, so a follow-up request reuses the prefix AND streams
  the same bytes;
* knobs — --speculation/--spec-k resolution (explicit > env > default)
  and `off` as a pure bypass (no drafters, no verify programs).
"""

import time

import jax.numpy as jnp
import pytest

from dllama_tpu.runtime.api_server import (
    ApiState,
    ChatMessage,
    InferenceParams,
)
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.runtime.spec import (
    NgramDrafter,
    NgramIndex,
    bucket_for,
    spec_buckets,
)
from dllama_tpu.tokenizer import Tokenizer

from helpers import assert_one_spelling, make_tiny_model, make_tiny_tokenizer

CFG = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
           head_dim=16, vocab_size=288, seq_len=384)

# repetitive (JSON-ish) user content: the workload class prompt-lookup
# exists for — the model's own output also cycles quickly on a tiny
# net, so drafts get accepted and rejected within a short stream
REPETITIVE = '{"a": 1, "b": 2}, {"a": 1, "b": 2}, {"a": 1, "b": 2}'


@pytest.fixture(scope="module")
def tiny_paths(tmp_path_factory):
    d = tmp_path_factory.mktemp("spec")
    mp, tp_ = str(d / "m.m"), str(d / "t.t")
    make_tiny_model(mp, cfg=CFG)
    # pad the tokenizer out to the model's vocab: the mixed-lane test
    # SAMPLES (temperature>0), so any model-vocab id may be emitted
    make_tiny_tokenizer(
        tp_, chat_template="<|start_header_id|>", pad_to=CFG["vocab_size"]
    )
    return mp, tp_


def _mk_state(tiny_paths, **kw):
    mp, tp_ = tiny_paths
    tok = Tokenizer(tp_)
    engine = InferenceEngine(
        mp, tokenizer=tok, tp=1, dtype=jnp.float32, temperature=0.0, seed=3,
        batch_size=3,
    )
    state = ApiState(
        engine, tok, lane_block_size=4, admission_chunk=6, **kw
    )
    assert state.scheduler is not None
    return state


@pytest.fixture(scope="module")
def spec_state(tiny_paths):
    return _mk_state(tiny_paths, speculation="ngram", spec_k=4)


@pytest.fixture(scope="module")
def off_state(tiny_paths):
    return _mk_state(tiny_paths)  # default: speculation off


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


# -- drafter unit tests -------------------------------------------------------


@pytest.mark.fast
def test_ngram_index_proposes_continuation():
    ix = NgramIndex(max_n=3)
    ix.extend([1, 2, 3, 4, 1, 2, 3])
    # suffix (1,2,3) occurred earlier at offset 0; its continuation was 4
    assert ix.lookup(4) == [4, 1, 2, 3]
    assert ix.lookup(1) == [4]
    # unseen suffix: nothing to propose
    ix2 = NgramIndex(max_n=3)
    ix2.extend([9, 8, 7])
    assert ix2.lookup(4) == []


@pytest.mark.fast
def test_ngram_index_prefers_longest_and_latest():
    ix = NgramIndex(max_n=3)
    # (5,6) appears twice with different continuations: 7 then 9; the
    # LATEST earlier occurrence wins
    ix.extend([5, 6, 7, 0, 5, 6, 9, 0, 5, 6])
    assert ix.lookup(1) == [9]
    # longest-suffix preference: a 3-gram match beats the 1-gram's entry
    ix3 = NgramIndex(max_n=3)
    ix3.extend([1, 2, 3, 7, 0, 3, 8, 0, 1, 2, 3])
    assert ix3.lookup(1) == [7]


@pytest.mark.fast
def test_drafter_update_is_incremental():
    dr = NgramDrafter(k_max=4)
    h = [5, 6, 7, 5, 6]
    dr.update(h)
    # continuation [7, 5, 6] runs out of history one short of k_max=4;
    # the cyclic extension predicts the period-3 repeat continues
    assert dr.draft() == [7, 5, 6, 7]
    # only the unseen tail is indexed on the next sync
    h += [7]
    dr.update(h)
    assert len(dr.index.tokens) == 6


@pytest.mark.fast
def test_drafter_adaptive_k_and_cooldown():
    dr = NgramDrafter(k_max=4, cooldown=2)
    assert dr.k == 4
    dr.feedback(4, 4)  # full acceptance: already at cap
    assert dr.k == 4
    dr.feedback(4, 0)  # zero acceptance: halve + pause drafting
    assert dr.k == 2
    dr.update([1, 2, 1, 2, 1])
    assert dr.draft() == []  # cooling down
    assert dr.draft() == []
    assert dr.draft() == [2, 1]  # cooldown over, k now caps the draft
    dr.feedback(2, 2)
    assert dr.k == 3  # additive regrowth


@pytest.mark.fast
def test_spec_buckets_and_bucket_for():
    assert spec_buckets(8) == (1, 2, 4, 8)
    assert spec_buckets(6) == (1, 2, 4, 6)
    assert spec_buckets(1) == (1,)
    assert spec_buckets(0) == ()
    assert bucket_for(3, (1, 2, 4, 8)) == 4
    assert bucket_for(8, (1, 2, 4, 8)) == 8


KNOB_TWINS = ("DLLAMA_SPECULATION", "DLLAMA_SPEC_K")


@pytest.mark.parametrize("name", KNOB_TWINS)
def test_spec_knob_resolution(unflagged, flagged, name):
    """`--speculation off` and `--spec-k 4` with the former variable set
    (`resolve_spec_knobs` read it until PR 45); an explicit flag is the
    scheduler's mode and k. A mode outside `SPEC_MODES` is the parser's to
    refuse, and `ApiState`'s where it is built directly."""
    from dllama_tpu.runtime.api_server import ApiState, build_arg_parser

    assert_one_spelling(name, unflagged, flagged)
    with pytest.raises(SystemExit):
        build_arg_parser().parse_args(["--speculation", "eagle"])
    _, state = unflagged
    with pytest.raises(ValueError, match="speculation must be one of"):
        ApiState(state.engine, state.tokenizer, speculation="eagle")


@pytest.mark.fast
def test_spec_cli_flags(tmp_path):
    import argparse

    from dllama_tpu.cli import add_engine_args

    parser = argparse.ArgumentParser()
    add_engine_args(parser)
    args = parser.parse_args(
        ["--model", "m", "--speculation", "ngram", "--spec-k", "8"]
    )
    assert args.speculation == "ngram" and args.spec_k == 8
    args = parser.parse_args(["--model", "m"])
    assert args.speculation == "off" and args.spec_k == 4


# -- engine verify parity -----------------------------------------------------


@pytest.mark.fast
def test_engine_verify_matches_stepwise_greedy(tiny_paths):
    """One verify_lanes dispatch accepts exactly the prefix a greedy
    decode emits token by token, and the rewind after a rejected draft
    leaves the lane able to continue byte-identically."""
    mp, _ = tiny_paths
    prompt = [2 + (i * 7) % 250 for i in range(17)]
    pos0, pending = len(prompt) - 1, prompt[-1]

    e = InferenceEngine(
        mp, tp=1, dtype=jnp.float32, temperature=0.0, seed=3, batch_size=2
    )
    e.prefill_lane(0, prompt[:-1], 0)
    ref = [r[0] for r in e.decode_lanes(
        [pending, 0], [pos0, 0], 10, [True, False]
    )]

    e2 = InferenceEngine(
        mp, tp=1, dtype=jnp.float32, temperature=0.0, seed=3, batch_size=2
    )
    e2.prefill_lane(0, prompt[:-1], 0)
    # perfect draft: the model's own continuation gets fully accepted
    d = ref[:4]
    grid = e2.verify_lanes([[pending, *d], [0] * 5], [pos0, 0], [True, False])
    a = 0
    while a < len(d) and grid[0][a] == d[a]:
        a += 1
    assert a == 4
    emitted = d[:a] + [grid[0][a]]
    assert emitted == ref[:5]
    # wrong draft: accepted prefix stops at the divergence, the emitted
    # run is still the greedy stream, and the lane continues from the
    # rewound position as if the rejected rows never existed
    pos1 = pos0 + len(emitted)
    bad = [(ref[5] + 1) % CFG["vocab_size"], 3, 5, 9]
    grid = e2.verify_lanes(
        [[emitted[-1], *bad], [0] * 5], [pos1, 0], [True, False]
    )
    a = 0
    while a < len(bad) and grid[0][a] == bad[a]:
        a += 1
    assert a == 0
    emitted2 = bad[:a] + [grid[0][a]]
    assert emitted2 == ref[5:6]
    pos2 = pos1 + len(emitted2)
    cont = [r[0] for r in e2.decode_lanes(
        [emitted2[-1], 0], [pos2, 0], 10 - (pos2 - pos0), [True, False]
    )]
    assert cont == ref[pos2 - pos0:]


# -- scheduler parity (the tentpole's acceptance criterion) -------------------


def test_spec_stream_parity_and_metrics(spec_state, off_state):
    """Spec-on and spec-off greedy streams are byte-identical on a
    repetitive workload, drafts actually flowed, and the dllama_spec_*
    metrics + spec_verify recorder events are live."""
    drafted0 = spec_state.m_spec_drafted.value
    on_text, on_reason = _drain(
        spec_state.scheduler.submit(_greedy(REPETITIVE))
    )
    off_text, off_reason = _drain(
        off_state.scheduler.submit(_greedy(REPETITIVE))
    )
    assert (on_text, on_reason) == (off_text, off_reason)
    assert on_reason in ("stop", "length") and len(on_text) > 0
    # speculation really ran: draft volume moved, the acceptance-length
    # histogram sampled, and the rate gauge is a valid ratio
    assert spec_state.m_spec_drafted.value > drafted0
    assert spec_state.m_spec_accept_len.count >= 1
    assert 0.0 <= spec_state.g_spec_rate.value <= 1.0
    evs = spec_state.recorder.events(kind="spec_verify")
    assert evs and all(
        0 <= e["accepted"] <= e["k"] for e in evs
    )
    # verify programs were rehearsed + dispatched under the bucketed
    # keys — no unbucketed shape may compile mid-serve
    kinds = {k[0] for k in spec_state.engine._compiled if isinstance(k, tuple)}
    assert "lane_verify" in kinds
    widths = {
        k[1] for k in spec_state.engine._compiled
        if isinstance(k, tuple) and k[0] == "lane_verify"
    }
    allowed = {1 + b for b in spec_buckets(spec_state.scheduler.spec_k)}
    assert widths <= allowed


def test_spec_mixed_lane_fallback_parity(spec_state, off_state):
    """A temperature>0 lane joining mid-stream shares the dispatch group
    but transparently takes the decode block: the greedy lane's stream
    and the seeded sampled lane's stream both match spec-off."""
    def run(state):
        g_job = state.scheduler.submit(_greedy(REPETITIVE, max_tokens=64))
        # let the greedy stream get going before the sampled lane joins
        deadline = time.time() + 300
        while g_job.n_completion < 4 and time.time() < deadline:
            time.sleep(0.02)
        assert g_job.n_completion >= 4
        s_job = state.scheduler.submit(InferenceParams(
            messages=[ChatMessage(role="user", content="tell me a story")],
            temperature=0.8, top_p=0.9, seed=11, max_tokens=24, stream=True,
        ))
        return _drain(g_job), _drain(s_job)

    assert run(spec_state) == run(off_state)


def test_spec_rewind_composes_with_kv_publish(spec_state):
    """A stream that saw rejected drafts still publishes a valid prefix:
    the identical follow-up request adopts pool pages (prefix hit) and
    streams the same bytes — garbage KV from rejected rows never lands
    in the pool (publish covers only history[:pos])."""
    prompt = REPETITIVE + " and then some more of the same pattern"
    text1, reason1 = _drain(spec_state.scheduler.submit(_greedy(prompt)))
    evs = spec_state.recorder.events(kind="spec_verify")
    assert any(e["accepted"] < e["k"] for e in evs), (
        "expected at least one rejected-draft rewind in this stream"
    )
    reused0 = spec_state.m_reused_tokens.value
    text2, reason2 = _drain(spec_state.scheduler.submit(_greedy(prompt)))
    assert (text2, reason2) == (text1, reason1)
    assert spec_state.m_reused_tokens.value > reused0


@pytest.mark.fast
def test_spec_off_is_pure_bypass(off_state):
    """speculation=off keeps the scheduler on the plain decode path: no
    drafters ever exist and no verify program is built."""
    sched = off_state.scheduler
    assert not sched.spec_on and not sched.drafters
    kinds = {k[0] for k in off_state.engine._compiled if isinstance(k, tuple)}
    assert "lane_verify" not in kinds
