"""Seeded `.m`/`.t` pair for a configuration file, written in parallel.

The engine takes only a file path, so every run writes its weights to disk
first (PERF.md lists the program change that would let them be made on the
device). Q40 tensors go out as wire blocks directly: 16 random nibble bytes
under an f16 scale of either sign, every row different, zero mean.

The sizes are chosen so that the output depends on the whole context, or a
comparison with the reference could not see a wrong mask, a missed chunk or
a wrong rotary base. Every matrix has gain 1 (weight std 1 / sqrt(fan-in)),
the embedding std 1, so each block adds about as much to the residual stream
as a token's embedding put there; the q and k projections have gain
SCORE_GAIN (with per-head q/k norms, the norm weights carry it), so
attention scores have std about SCORE_GAIN squared = 2.5 and the softmax
rests on a few positions at any context length and not on their mean.
(PR 21's synthetic weights, std 0.009 under an N(0, 3) embedding, make the
next token a function of the last one alone: the ladder's rehearsal read a
gap of 0 against a reference with the wrong rotary base.)

The head's rows for the tokenizer's two end-of-sequence ids (the last two of
the vocabulary, `write_synth_tokenizer`) are zero, so their logits are 0, some
three std below the largest, and no stream ends before the count asked for:
with random rows some seeds favour an end-of-sequence id (6 of 63 requests
stopped early under one seed, none under four others; my chip runs, PR 23)
and the seed would change the work.

The file is cut into tiles of at most TILE bytes; tile i is drawn from
`SeedSequence([seed, i])`, so the bytes depend on the seed and the
configuration only, not on the number of threads.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from dllama_tpu.formats.model_file import LlmArch, LlmHeader, tensor_plan
from dllama_tpu.formats.quants import Q40_BLOCK_BYTES, FloatType
from dllama_tpu.formats.writer import HEADER_KEYS, write_header

TILE = 32 << 20
EMBED_STD = 1.0
SCORE_GAIN = 1.6
N_EOS = 2
# 256 block scales: magnitudes evenly over [0.5, 1], both signs; a weight is
# a nibble in [-8, 7] (rms 4.61) times its block's scale (rms 0.764 here)
_UNIT = np.concatenate([s * np.linspace(0.5, 1.0, 128) for s in (1.0, -1.0)])
_UNIT_STD = 4.61 * float(np.sqrt(np.mean(_UNIT**2)))


def header_for(cfg: dict) -> tuple[LlmHeader, dict]:
    """The `.m` header a configuration file describes, and its wire form."""
    f = cfg["file"]
    h = LlmHeader()
    h.arch = LlmArch[f["arch"]]
    h.dim = cfg["hidden_size"]
    h.hidden_dim = cfg["intermediate_size"]
    h.n_layers = cfg["num_hidden_layers"]
    h.n_heads = cfg["num_attention_heads"]
    h.n_kv_heads = cfg["num_key_value_heads"]
    h.head_dim = cfg.get("head_dim") or cfg["assumed"]["head_dim"]
    h.n_experts = cfg.get("num_experts", 0)
    h.n_active_experts = cfg.get("num_experts_per_tok", 0)
    h.moe_hidden_dim = cfg.get("moe_intermediate_size", 0)
    h.vocab_size = cfg["vocab_size"]
    h.seq_len = cfg["max_position_embeddings"]
    h.weight_type = FloatType.Q40
    wire = {
        "version": 0,
        "arch_type": int(h.arch),
        "dim": h.dim,
        "hidden_dim": h.hidden_dim,
        "n_layers": h.n_layers,
        "n_heads": h.n_heads,
        "n_kv_heads": h.n_kv_heads,
        "n_experts": h.n_experts,
        "n_active_experts": h.n_active_experts,
        "vocab_size": h.vocab_size,
        "max_seq_len": h.seq_len,
        "hidden_act": 1,  # SiLU
        "rope_theta": int(cfg["rope_theta"]),
        "weights_float_type": int(FloatType.Q40),
        "head_dim": h.head_dim,
        "norm_epsilon": f["norm_epsilon_enum"],
    }
    if h.n_experts:
        wire["moe_hidden_dim"] = h.moe_hidden_dim
    h.header_bytes = 8 + 8 * len(wire)
    assert set(wire) <= set(HEADER_KEYS)
    return h, wire


def _segments(h: LlmHeader) -> list[tuple[int, int, str, float]]:
    """(offset, nbytes, kind, size) tiles of the tensor section. `size` is
    the weight std of a Q40 or f32 matrix and the centre of a norm weight;
    neighbouring Q40 tensors of one size form one run."""
    qk_norm = h.arch in (LlmArch.QWEN3, LlmArch.QWEN3_MOE)
    runs: list[list] = []
    for s in tensor_plan(h):
        leaf = s.name.rsplit(".", 1)[-1]
        if "norm" in leaf:
            kind, size = "norm", SCORE_GAIN if leaf in ("q_norm", "k_norm") else 1.0
        elif s.name == "embed":
            kind, size = "f32", EMBED_STD
        else:
            kind = "q40" if s.float_type == FloatType.Q40 else "f32"
            gain = SCORE_GAIN if leaf in ("q", "k") and not qk_norm else 1.0
            size = gain / float(np.sqrt(s.shape[-1]))
        if runs and kind == "q40" and runs[-1][2:] == [kind, size]:
            runs[-1][1] += s.nbytes
        else:
            runs.append([s.offset, s.nbytes, kind, size])
    out = []
    for off, n, kind, size in runs:
        unit = Q40_BLOCK_BYTES if kind == "q40" else 4
        step = TILE // unit * unit
        out += [(off + a, min(step, n - a), kind, size) for a in range(0, n, step)]
    return out


def _tile(seed: int, i: int, nbytes: int, kind: str, size: float) -> bytes:
    rng = np.random.default_rng(np.random.SeedSequence([seed, i]))
    if kind == "q40":
        n_blocks = nbytes // Q40_BLOCK_BYTES
        words = rng.integers(0, 1 << 64, -(-nbytes // 8), dtype=np.uint64)
        blocks = words.view(np.uint8)[:nbytes].reshape(n_blocks, Q40_BLOCK_BYTES)
        scales = (_UNIT * (size / _UNIT_STD)).astype(np.float16).view(np.uint16)
        # the block's own first random byte picks its scale
        blocks[:, :2] = scales[blocks[:, 0]].view(np.uint8).reshape(-1, 2)
        return blocks.tobytes()
    n = nbytes // 4
    if kind == "norm":
        return rng.uniform(0.8 * size, 1.2 * size, n).astype(np.float32).tobytes()
    return (rng.standard_normal(n, dtype=np.float32) * size).tobytes()


def write_model(path: str, cfg: dict, seed: int, threads: int = 8) -> LlmHeader:
    """Write the configuration's `.m` at `path` from `seed`."""
    h, wire = header_for(cfg)
    segs = _segments(h)
    total = segs[-1][0] + segs[-1][1]
    with open(path, "wb") as f:
        write_header(f, wire)
        if f.tell() != h.header_bytes:
            raise RuntimeError("header size mismatch")
        f.truncate(total)
    fd = os.open(path, os.O_WRONLY)
    try:
        def job(i: int) -> None:
            off, n, kind, size = segs[i]
            buf = _tile(seed, i, n, kind, size)
            if os.pwrite(fd, buf, off) != n:
                raise OSError(f"short write at {off}")

        with ThreadPoolExecutor(min(threads, os.cpu_count() or 1)) as pool:
            for fut in [pool.submit(job, i) for i in range(len(segs))]:
                fut.result()
        # wcls is the file's last tensor, a row per vocabulary id
        eos_rows = N_EOS * (h.dim // 32) * Q40_BLOCK_BYTES
        os.pwrite(fd, bytes(eos_rows), total - eos_rows)
    finally:
        os.close(fd)
    return h


def write_pair(work: str, cfg: dict, seed: int) -> tuple[str, str]:
    """`model.m` and `tokenizer.t` under `work` (made if missing)."""
    from dllama_tpu.models.synthetic import write_synth_tokenizer

    os.makedirs(work, exist_ok=True)
    model, tok = os.path.join(work, "model.m"), os.path.join(work, "tokenizer.t")
    write_model(model, cfg, seed)
    write_synth_tokenizer(tok, cfg["vocab_size"])
    return model, tok
