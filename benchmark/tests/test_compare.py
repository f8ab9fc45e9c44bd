"""The comparison rule passes the program's honest output and fails three
injected faults, at the `tiny` widths on the CPU."""

import numpy as np
import pytest

import run as bench
from benchmark.harness import compare, weights
from benchmark.references import dense_gqa

TOL = 0.15  # far above the program's bf16 noise here, far below any fault
N_PROMPT, N_NEW = 48, 24


def tiny(config: str) -> dict:
    return dict(bench.load_config(config, rehearse=True), gap_tol=TOL)


@pytest.fixture(scope="module", params=["mistral-7b-v0.3", "qwen3-30b-a3b-l12"])
def served(request, tmp_path_factory):
    """(cfg, model path, prompt ids, ids the program decodes greedily)."""
    from dllama_tpu.runtime.engine import InferenceEngine
    from dllama_tpu.tokenizer import Tokenizer

    cfg = tiny(request.param)
    work = tmp_path_factory.mktemp(request.param)
    model, tok = weights.write_pair(str(work), cfg, seed=5)
    engine = InferenceEngine(model, tokenizer=Tokenizer(tok), max_seq_len=256)
    prompt = [int(t) for t in np.random.default_rng(5).integers(0, 500, N_PROMPT)]
    out, _, _ = engine.generate(prompt, N_PROMPT + N_NEW)
    assert len(set(out)) > 4, "degenerate output cannot fail a comparison"
    return cfg, model, prompt, [int(t) for t in out[:N_NEW]]


def report(cfg, model, prompt, ids):
    return compare.check(cfg, model, [{"prompt_ids": prompt, "served": ids}])[0]


def test_honest_output_passes(served):
    cfg, model, prompt, ids = served
    rep = report(cfg, model, prompt, ids)
    assert rep["passed"] and rep["max_gap_std"] <= TOL, rep


def test_reference_with_rotary_base_1e4_fails(served):
    cfg, model, prompt, ids = served
    rep = report(dict(cfg, rope_theta=1e4), model, prompt, ids)
    assert rep["passed"] is False and rep["max_gap_std"] > 2 * TOL, rep


def test_an_earlier_chunks_keys_zeroed_fails(served):
    cfg, model, prompt, ids = served
    with dense_gqa.ZeroedKeys(8, 24):
        rep = report(cfg, model, prompt, ids)
    assert rep["passed"] is False and rep["max_gap_std"] > 2 * TOL, rep


def test_served_ids_shifted_by_one_fail(served):
    cfg, model, prompt, ids = served
    rep = report(cfg, model, prompt, ids[1:] + ids[:1])
    assert rep["passed"] is False and rep["max_gap_std"] > 2 * TOL, rep
