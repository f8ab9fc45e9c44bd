"""The shipped example scripts must keep working."""

import os
import subprocess

import pytest

from helpers import REPO_ROOT, make_tiny_model, make_tiny_tokenizer

# heavyweight end-to-end surface: run with the full suite / CI;
# deselect via -m 'not slow' for the fast local loop
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def tiny_pair(tmp_path_factory):
    d = tmp_path_factory.mktemp("examples")
    # pad the vocab so tp=4 divides it (validate_tp mirrors the
    # reference's shardability constraints)
    tok = make_tiny_tokenizer(str(d / "tok.t"), pad_to=288)
    # seq_len must cover the Macbeth prompt (~79 byte-level tokens) plus
    # decode room: --steps is an absolute position cap, so steps beyond
    # the prompt length are what actually generate
    make_tiny_model(
        str(d / "m.m"),
        cfg=dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
                 head_dim=16, vocab_size=len(tok.vocab), seq_len=128),
    )
    return str(d / "m.m"), str(d / "tok.t")


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(extra)
    return env


def test_macbeth_determinism(tiny_pair):
    """Greedy long-generation twice -> byte-identical (the reference's
    examples/macbeth.sh check, on the tiny model)."""
    mp, tp = tiny_pair
    r = subprocess.run(
        ["bash", os.path.join(REPO_ROOT, "examples", "macbeth.sh"),
         mp, tp, "120"],
        capture_output=True, text=True, timeout=600, env=_env(),
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "deterministic" in r.stdout, r.stdout


def test_n_chips_cli(tiny_pair):
    """examples/n-chips.sh runs the real CLI over a 4-virtual-chip mesh."""
    mp, tp = tiny_pair
    r = subprocess.run(
        ["bash", os.path.join(REPO_ROOT, "examples", "n-chips.sh"),
         "4", mp, tp],
        capture_output=True, text=True, timeout=600,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "Tp: 4" in r.stdout, r.stdout


def test_chat_context_exhaustion_stops_explicitly(tiny_pair):
    """When the context window fills, the chat REPL must print an explicit
    stop and exit instead of silently generating nothing forever
    (reference behavior: src/dllama.cpp:242-253)."""
    mp, tp = tiny_pair
    # seq_len 128: a few user turns exhaust it (each turn re-encodes the
    # chat template around the message and then decodes until EOS/stop)
    msgs = "\n".join(["tell me more about it please"] * 12) + "\n"
    r = subprocess.run(
        ["python", "-m", "dllama_tpu", "chat", "--model", mp,
         "--tokenizer", tp, "--temperature", "0.0", "--max-seq-len", "128",
         "--chat-template", "llama3"],
        input=msgs, capture_output=True, text=True, timeout=900,
        env=_env(PYTHONPATH=REPO_ROOT + os.pathsep
                 + os.environ.get("PYTHONPATH", "")),
        cwd=REPO_ROOT,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "Context window full" in r.stdout, r.stdout[-2000:]
