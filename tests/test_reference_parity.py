"""End-to-end parity against the actual reference C++ implementation.

Builds b4rtaz/distributed-llama's `dllama` binary from the read-only mount
(out-of-tree, cached under /tmp/refbuild), generates a tiny f32 model +
tokenizer with OUR writers, runs greedy inference on BOTH implementations,
and requires byte-identical per-token output.

This is the strongest possible cross-implementation check (SURVEY.md §4):
it covers the `.m`/`.t` wire formats, BPE encoding, the full transformer
numerics (prefill + decode argmax stream), and the streaming UTF-8 display
semantics in one shot. Skipped when the reference mount or a toolchain is
unavailable.

Reference quirk discovered while building this test: `dllama inference`
seeds the decode loop with `inputTokens[pos + 1]` (dllama.cpp:54) — one
slot PAST the prompt, which holds a stale intermediate of the in-place BPE
merge loop rather than the last prompt token. (For some prompts the stale
slot happens to contain the right token, which is why the bug is invisible
in casual use.) Our framework feeds the last prompt token (the correct
semantics, matching HF transformers); the comparison below replays the
reference's stale-seed behavior via `reference_decode_seed` so the
numerics can still be compared token-for-token.
"""

import os
import re
import shutil
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.formats import FloatType
from dllama_tpu.formats.model_file import LlmArch
from dllama_tpu.runtime.engine import InferenceEngine
from dllama_tpu.tokenizer import Tokenizer

from helpers import REPO_ROOT, make_tiny_model, make_tiny_tokenizer

# heavyweight end-to-end surface: run with the full suite / CI;
# deselect via -m 'not slow' for the fast local loop
pytestmark = pytest.mark.slow

REFERENCE = "/root/reference"
BUILD_DIR = "/tmp/refbuild"  # session cache; the mount is immutable


@pytest.fixture(scope="module")
def dllama_binary():
    if not os.path.isdir(os.path.join(REFERENCE, "src")):
        pytest.skip("reference source not mounted")
    if shutil.which("g++") is None or shutil.which("make") is None:
        pytest.skip("no C++ toolchain")
    binary = os.path.join(BUILD_DIR, "dllama")
    if not os.path.isfile(binary):
        if not os.path.isdir(BUILD_DIR):
            shutil.copytree(REFERENCE, BUILD_DIR)
        r = subprocess.run(
            ["make", "dllama"], cwd=BUILD_DIR, capture_output=True, timeout=600
        )
        if r.returncode != 0 or not os.path.isfile(binary):
            pytest.skip(f"reference build failed: {r.stderr[-500:]}")
    return binary


def reference_decode_seed(tok: Tokenizer, prompt: str) -> int:
    """The token the reference actually feeds at the first decode step:
    simulate its encode buffer (greedy byte accumulation, then in-place
    best-score pair merging with left shifts, tokenizer.cpp:311-390) and
    return the stale slot at index nTokens (dllama.cpp:54)."""
    buf: list[int] = []
    if tok.add_bos and tok.bos_id >= 0:
        buf.append(tok.bos_id)
    raw = prompt.encode("utf-8")
    acc = bytearray()
    i = 0
    while i < len(raw):
        sid = tok.find_special_token_start_with(raw, i)
        if sid >= 0 and not acc:
            buf.append(sid)
            i += len(tok.vocab[sid])
            continue
        acc.append(raw[i])
        i += 1
        tid = tok.find_regular_token(bytes(acc))
        if tid != -1:
            buf.append(tid)
            acc.clear()
    n = len(buf)
    while True:
        best_score, best_id, best_idx = -1e10, -1, -1
        for j in range(n - 1):
            mid = tok.find_regular_token(tok.vocab[buf[j]] + tok.vocab[buf[j + 1]])
            if mid != -1 and tok.scores[mid] > best_score:
                best_score, best_id, best_idx = tok.scores[mid], mid, j
        if best_idx == -1:
            break
        buf[best_idx] = best_id
        for j in range(best_idx + 1, n - 1):
            buf[j] = buf[j + 1]
        n -= 1
    # buf[n] is the stale slot (zero-initialized if never written)
    return buf[n] if n < len(buf) else 0


def reference_render(tok: Tokenizer, ids: list[int]) -> str:
    """The reference's per-token display (Tokenizer::decode + detokUtf8,
    src/tokenizer.cpp:224-309 + dllama.cpp:88-95): '~' for null pieces,
    partial UTF-8 held across tokens, invalid bytes kept in the buffer and
    materialized as one U+FFFD only once valid text follows (consecutive
    invalid bytes collapse — the recovery resets the output cursor to the
    last checkpoint). BOS renders null; EOS flushes the raw pending buffer;
    the C scan stops at a NUL byte."""
    out = []
    pending = b""
    for t in ids:
        if t == tok.bos_id:
            out.append(None)
            continue
        if tok.is_eos(t):
            out.append(pending.decode("utf-8", "replace") if pending else None)
            continue
        buf = pending + tok.vocab[t]
        res = b""
        checkpoint = 0
        checkpoint_src = 0
        src = 0
        expect = 0
        while src < len(buf) and buf[src] != 0:  # C scan stops at NUL
            c = buf[src]
            recovery = False
            if expect:
                if (c & 0xC0) == 0x80:
                    res += bytes([c])
                    src += 1
                    expect -= 1
                else:
                    recovery = True
            elif c <= 0x7F:
                res += bytes([c])
                src += 1
            elif 0xC0 <= c <= 0xF7:
                res += bytes([c])
                src += 1
                expect = 1 if c <= 0xDF else (2 if c <= 0xEF else 3)
            else:
                recovery = True
            if not recovery:
                if not expect:
                    checkpoint = len(res)
                    checkpoint_src = src
            else:
                if expect:
                    expect = 0
                else:
                    src += 1
                res = res[:checkpoint] + b"\xef\xbf\xbd"
                # checkpoint intentionally NOT advanced — the reference only
                # commits the replacement char when valid text follows
        emitted = res[:checkpoint]
        pending = buf[checkpoint_src:src]  # a scanned NUL byte vanishes
        out.append(emitted.decode("utf-8") if emitted else None)
    return "".join(p if p is not None else "~" for p in out)


# fixed-width per-token prefix printed by the reference (dllama.cpp:88-95)
_PRED_PREFIX = re.compile(
    r"Pred\s*\d+ ms Sync\s*\d+ ms \| Sent\s*\d+ kB Recv\s*\d+ kB \| "
)


def extract_reference_pieces(stdout: str) -> str:
    """Concatenated per-token text from the reference's 🔶 lines. Splitting
    on the 🔶 marker (not on newlines) keeps pieces that themselves contain
    newlines intact; each printf appends exactly one trailing newline."""
    chunks = stdout.split("🔶 ")[1:]
    pieces = []
    for chunk in chunks:
        m = _PRED_PREFIX.match(chunk)
        if not m:
            break  # end of the prediction block (summary follows)
        body = chunk[m.end():]
        # the final chunk carries the run summary after its newline
        piece = body.split("\n\nEvaluation", 1)[0]
        if piece.endswith("\n"):
            piece = piece[:-1]  # printf's own trailing newline
        pieces.append(piece)
    return "".join(pieces)


PARITY_CFG = dict(dim=64, hidden_dim=160, n_layers=2, n_heads=8, n_kv_heads=4,
                  head_dim=16, vocab_size=288, seq_len=96)
# the MoE variant adds expert routing on top of the same attention shapes
PARITY_CFG_MOE = dict(PARITY_CFG, moe_hidden_dim=96, n_experts=4,
                      n_active_experts=2)


def make_parity_fixture(tmp_path, seed, arch=LlmArch.LLAMA):
    # NB: f32 weights — the reference can't run QWEN3_MOE at f32 sync (its
    # REPEAT_Z op has no F32 kernel), so the MoE test builds its own Q40
    # model instead of using this fixture.
    mp = str(tmp_path / "m.m")
    tp = str(tmp_path / "t.t")
    make_tiny_model(
        mp, arch=arch, weight_type=FloatType.F32, cfg=dict(PARITY_CFG), seed=seed
    )
    make_tiny_tokenizer(tp, pad_to=PARITY_CFG["vocab_size"])
    return mp, tp


def run_parity(dllama_binary, tmp_path, arch, seed, prompt, steps):
    mp, tp = make_parity_fixture(tmp_path, seed, arch)

    r = subprocess.run(
        [dllama_binary, "inference", "--model", mp, "--tokenizer", tp,
         "--prompt", prompt, "--steps", str(steps), "--temperature", "0.0",
         "--nthreads", "1", "--buffer-float-type", "f32"],
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-800:]
    ref_text = extract_reference_pieces(r.stdout)

    tok = Tokenizer(tp)
    prompt_tokens = tok.encode(prompt, is_start=True, add_special_tokens=True)
    engine = InferenceEngine(mp, tp=1, dtype=jnp.float32, temperature=0.0)
    engine.prefill(prompt_tokens)
    pos = len(prompt_tokens) - 1
    token = reference_decode_seed(tok, prompt)  # replay the reference quirk
    ids = []
    while pos < min(engine.header.seq_len, steps):
        token, _ = engine.decode_step(token, pos)
        pos += 1
        ids.append(token)

    ours = reference_render(tok, ids)
    assert ours == ref_text, f"\nref:  {ref_text!r}\nours: {ours!r}\nids: {ids}"


def test_greedy_stream_matches_reference(dllama_binary, tmp_path):
    run_parity(dllama_binary, tmp_path, LlmArch.LLAMA, 11, "hello world", 20)


def test_greedy_stream_matches_reference_qwen3(dllama_binary, tmp_path):
    """Same cross-binary check for the Qwen3 arch (falcon RoPE, QK-norm)."""
    run_parity(dllama_binary, tmp_path, LlmArch.QWEN3, 13, "the world", 16)


def test_greedy_stream_matches_reference_fresh(dllama_binary, tmp_path):
    """A third seed/prompt to guard against fixture-tuned coincidences."""
    run_parity(dllama_binary, tmp_path, LlmArch.LLAMA, 23, "hi there world", 18)


def test_perplexity_matches_reference(dllama_binary, tmp_path):
    """Perplexity (teacher-forced NLL) parity — the numerical-quality oracle
    (reference: dllama.cpp:132-172) compared across implementations."""
    mp, tp = make_parity_fixture(tmp_path, seed=31)
    prompt = "hello world the world hello"

    r = subprocess.run(
        [dllama_binary, "perplexity", "--model", mp, "--tokenizer", tp,
         "--prompt", prompt, "--nthreads", "1", "--buffer-float-type", "f32"],
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-800:]
    m = re.search(r"perplexity: ([0-9.]+)", r.stdout)
    assert m, r.stdout[-500:]
    ref_ppl = float(m.group(1))

    cli = subprocess.run(
        [sys.executable, "-m", "dllama_tpu", "perplexity", "--model", mp,
         "--tokenizer", tp, "--prompt", prompt, "--dtype", "f32", "--tp", "1"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=REPO_ROOT,
    )
    assert cli.returncode == 0, cli.stderr[-800:]
    m2 = re.search(r"perplexity: ([0-9.]+)", cli.stdout)
    assert m2, cli.stdout[-500:]
    ours_ppl = float(m2.group(1))
    assert abs(ours_ppl - ref_ppl) / ref_ppl < 2e-3, (ours_ppl, ref_ppl)


def test_perplexity_close_reference_qwen3_moe(dllama_binary, tmp_path):
    """Cross-binary check for Qwen3-MoE (gate softmax/top-k/expert SwiGLU).

    The reference cannot run MoE at f32 sync type — its REPEAT_Z op only
    has a Q80-output kernel (`Unsupported CPU op code: REPEAT_Z, quant:
    F32_F32_F32`), an undocumented gap behind the README's "q40 weights +
    q80 buffer" rule — so byte-exact greedy parity is impossible: with
    q40+q80 the reference quantizes expert-matmul activations to 8 bits,
    ours computes them dense. Perplexity with a quantization-noise
    tolerance still validates the routing + expert pipeline end-to-end."""
    mp = str(tmp_path / "m.m")
    tp = str(tmp_path / "t.t")
    make_tiny_model(mp, arch=LlmArch.QWEN3_MOE, weight_type=FloatType.Q40,
                    cfg=dict(PARITY_CFG_MOE), seed=17)
    make_tiny_tokenizer(tp, pad_to=PARITY_CFG["vocab_size"])
    prompt = "hello world the world"

    r = subprocess.run(
        [dllama_binary, "perplexity", "--model", mp, "--tokenizer", tp,
         "--prompt", prompt, "--nthreads", "1", "--buffer-float-type", "q80"],
        capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, (r.stdout[-400:], r.stderr[-400:])
    m = re.search(r"perplexity: ([0-9.]+)", r.stdout)
    assert m, r.stdout[-500:]
    ref_ppl = float(m.group(1))

    cli = subprocess.run(
        [sys.executable, "-m", "dllama_tpu", "perplexity", "--model", mp,
         "--tokenizer", tp, "--prompt", prompt, "--dtype", "f32", "--tp", "1"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=REPO_ROOT,
    )
    assert cli.returncode == 0, cli.stderr[-800:]
    m2 = re.search(r"perplexity: ([0-9.]+)", cli.stdout)
    assert m2, cli.stdout[-500:]
    ours_ppl = float(m2.group(1))
    # Q80 activation quantization in the reference's expert matmuls is the
    # only systematic difference; a few percent covers it
    assert abs(ours_ppl - ref_ppl) / ref_ppl < 0.05, (ours_ppl, ref_ppl)


# ~100M-param stress: realistic depth/width/GQA — drift
# that 2-layer fixtures can't catch (accumulation depth, RoPE at real
# dims, 256-token error growth).
MID_CFG = dict(dim=768, hidden_dim=2560, n_layers=12, n_heads=12,
               n_kv_heads=4, head_dim=64, vocab_size=4096, seq_len=512)


def _mid_prompt(n_words: int = 60) -> str:
    words = ["hello", "world", "the", "hi", "there"]
    import random

    rng = random.Random(7)
    return " ".join(rng.choice(words) for _ in range(n_words))


def test_midsize_greedy_stream_256_matches_reference(dllama_binary, tmp_path):
    """256-token greedy stream on a ~100M-param f32 model vs the reference
    binary. Token-for-token equality required; a divergence is excused
    ONLY if our top-2 logit gap at that step is within f32 cross-
    implementation noise (argmax tie — both orders defensible), and the
    matched prefix must already be deep enough to have teeth."""
    from dllama_tpu.models import forward, init_kv_cache, load_params
    from dllama_tpu.formats.model_file import ModelReader

    mp = str(tmp_path / "mid.m")
    tp = str(tmp_path / "mid.t")
    make_tiny_model(mp, weight_type=FloatType.F32, cfg=dict(MID_CFG), seed=41)
    make_tiny_tokenizer(tp, pad_to=MID_CFG["vocab_size"])
    prompt = _mid_prompt(12)
    steps = 280  # ~256 decode tokens after the prompt

    r = subprocess.run(
        [dllama_binary, "inference", "--model", mp, "--tokenizer", tp,
         "--prompt", prompt, "--steps", str(steps), "--temperature", "0.0",
         "--nthreads", "1", "--buffer-float-type", "f32"],
        capture_output=True, text=True, timeout=1200,
    )
    assert r.returncode == 0, r.stderr[-800:]
    ref_text = extract_reference_pieces(r.stdout)

    tok = Tokenizer(tp)
    prompt_tokens = tok.encode(prompt, is_start=True, add_special_tokens=True)
    reader = ModelReader(mp)
    h = reader.header
    params = load_params(reader)  # f32 dense
    cache = init_kv_cache(h, 1)
    arr = jnp.asarray([prompt_tokens], jnp.int32)
    _, cache = forward(params, h, arr, jnp.int32(0), cache)
    pos = len(prompt_tokens) - 1
    token = reference_decode_seed(tok, prompt)
    ids, gaps = [], []
    while pos < min(h.seq_len, steps):
        lg, cache = forward(
            params, h, jnp.asarray([[token]], jnp.int32), jnp.int32(pos),
            cache,
        )
        row = np.asarray(lg)[0, -1].astype(np.float64)
        top2 = np.partition(row, -2)[-2:]
        gaps.append(float(top2[1] - top2[0]))
        token = int(row.argmax())
        pos += 1
        ids.append(token)

    ours = reference_render(tok, ids)
    if ours != ref_text:
        # locate the first diverging rendered piece -> step index
        ref_pieces = ref_text
        k = 0
        while k < min(len(ours), len(ref_pieces)) and ours[k] == ref_pieces[k]:
            k += 1
        # map char offset back to a conservative step index: count pieces
        # fully matched so far
        step = 0
        for i, t in enumerate(ids):
            if len(reference_render(tok, ids[: i + 1])) > k:
                step = i
                break
        assert gaps[step] < 1e-3, (
            f"diverged at step {step} with top-2 gap {gaps[step]:.2e} "
            f"(not a tie)\nref:  {ref_text[:400]!r}\nours: {ours[:400]!r}"
        )
        assert step >= 32, (
            f"diverged too early (step {step}) to count as drift-free"
        )


def test_midsize_q40_perplexity_nll_bound(dllama_binary, tmp_path):
    """Perplexity on the ~100M model with Q40 weights: the reference runs
    Q40 x Q80 integer dots, ours dequantizes to f32 — the NLL must agree
    within the activation-quantization noise bound at depth 12."""
    mp = str(tmp_path / "midq.m")
    tp = str(tmp_path / "midq.t")
    make_tiny_model(mp, weight_type=FloatType.Q40, cfg=dict(MID_CFG), seed=43)
    make_tiny_tokenizer(tp, pad_to=MID_CFG["vocab_size"])
    prompt = _mid_prompt(60)

    r = subprocess.run(
        [dllama_binary, "perplexity", "--model", mp, "--tokenizer", tp,
         "--prompt", prompt, "--nthreads", "1",
         "--buffer-float-type", "q80"],
        capture_output=True, text=True, timeout=1200,
    )
    assert r.returncode == 0, r.stderr[-800:]
    m = re.search(r"perplexity: ([0-9.]+)", r.stdout)
    assert m, r.stdout[-500:]
    ref_nll = float(np.log(float(m.group(1))))  # nats/token

    cli = subprocess.run(
        [sys.executable, "-m", "dllama_tpu", "perplexity", "--model", mp,
         "--tokenizer", tp, "--prompt", prompt, "--dtype", "f32",
         "--tp", "1", "--weight-format", "q40"],
        capture_output=True, text=True, timeout=1800,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=REPO_ROOT,
    )
    assert cli.returncode == 0, cli.stderr[-800:]
    m2 = re.search(r"perplexity: ([0-9.]+)", cli.stdout)
    assert m2, cli.stdout[-500:]
    ours_nll = float(np.log(float(m2.group(1))))
    # per-token NLL delta bound: Q80 activation quantization noise at
    # depth 12 stays well under 0.02 nats on this fixture
    assert abs(ours_nll - ref_nll) < 0.02, (ours_nll, ref_nll)
