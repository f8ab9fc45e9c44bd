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

What the file format knows of an architecture, the harness takes from the
program (`HEADER_KEYS`, `read_llm_header`, `tensor_plan`); what a
configuration knows, from its file. `file.header` maps a wire key to the
integer written for it, after the keys every model has; `file.tensors` maps
a tensor's leaf name to what its values are drawn from, where the rule
above would make the comparison blind (a decay rate drawn N(0, 1/8) forgets
the context in a few tokens):

    {"gain": g}                              a Q40 matrix, std g / sqrt(fan-in)
    {"dist": "normal", "std": s}             an f32 tensor
    {"dist": "uniform", "lo": a, "hi": b}    an f32 tensor
    ... "map": "log" | "inv_softplus"        applied to an f32 draw

A leaf that is not listed keeps the rule above, and a listed one is drawn
from tiles of its own, numbered after the rule's, and written over what the
rule put there: a configuration that lists none gets the bytes it always
got, and one that lists some changes those tensors' bytes and no others.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from dllama_tpu.formats.model_file import (
    LlmArch, LlmHeader, TensorSpec, read_llm_header, tensor_plan)
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


def header_for(cfg: dict) -> dict:
    """The wire form of the `.m` header a configuration file describes: the
    keys every model has, from the published sizes, then `file.header`
    verbatim and in the file's order. Raises, naming it, on an `arch` or a
    key the program's file format lacks."""
    f = cfg["file"]
    if f["arch"] not in LlmArch.__members__:
        raise ValueError(f"{cfg['name']}: the file format has no arch {f['arch']!r} "
                         f"(LlmArch: {', '.join(LlmArch.__members__)})")
    n_experts = cfg.get("num_experts", 0)
    wire = {
        "version": 0,
        "arch_type": int(LlmArch[f["arch"]]),
        "dim": cfg["hidden_size"],
        "hidden_dim": cfg["intermediate_size"],
        "n_layers": cfg["num_hidden_layers"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "n_experts": n_experts,
        "n_active_experts": cfg.get("num_experts_per_tok", 0),
        "vocab_size": cfg["vocab_size"],
        "max_seq_len": cfg["max_position_embeddings"],
        "hidden_act": 1,  # SiLU
        "rope_theta": int(cfg["rope_theta"]),
        "weights_float_type": int(FloatType.Q40),
        "head_dim": cfg.get("head_dim") or cfg["assumed"]["head_dim"],
        "norm_epsilon": f["norm_epsilon_enum"],
    }
    if n_experts:
        wire["moe_hidden_dim"] = cfg.get("moe_intermediate_size", 0)
    for key, value in f.get("header", {}).items():
        if key not in HEADER_KEYS:
            raise ValueError(f"{cfg['name']}: file.header names {key!r}, a key the "
                             f"file format lacks (formats/writer.HEADER_KEYS)")
        if key in wire:
            raise ValueError(f"{cfg['name']}: file.header restates {key!r}, which is "
                             f"written from the published sizes")
        if type(value) is not int or not -(1 << 31) <= value < 1 << 31:
            raise ValueError(f"{cfg['name']}: file.header {key!r} is {value!r}; the "
                             f"format stores 32-bit integers")
        wire[key] = value
    return wire


def _default_draw(s: TensorSpec, leaf: str, qk_norm: bool) -> tuple:
    """How the rule above draws a tensor: ("q40", std), or for an f32 tensor
    ("normal", std, map) or ("uniform", lo, hi, map)."""
    if "norm" in leaf:
        size = SCORE_GAIN if leaf in ("q_norm", "k_norm") else 1.0
        return ("uniform", 0.8 * size, 1.2 * size, None)
    if s.name == "embed":
        return ("normal", EMBED_STD, None)
    gain = SCORE_GAIN if leaf in ("q", "k") and not qk_norm else 1.0
    std = gain / float(np.sqrt(s.shape[-1]))
    return ("q40", std) if s.float_type == FloatType.Q40 else ("normal", std, None)


def _stated_draw(s: TensorSpec, leaf: str, stated: dict) -> tuple:
    """The same, for a tensor that `file.tensors` states."""
    if s.float_type == FloatType.Q40:
        if set(stated) != {"gain"}:
            raise ValueError(f"file.tensors {leaf!r}: a Q40 tensor takes {{'gain': g}}, "
                             f"not {stated}")
        return ("q40", stated["gain"] / float(np.sqrt(s.shape[-1])))
    how, keys = stated.get("map"), set(stated) - {"map"}
    if how not in _MAPS:
        raise ValueError(f"file.tensors {leaf!r}: unknown map {how!r}")
    if stated.get("dist") == "normal" and keys == {"dist", "std"}:
        return ("normal", float(stated["std"]), how)
    if stated.get("dist") == "uniform" and keys == {"dist", "lo", "hi"}:
        return ("uniform", float(stated["lo"]), float(stated["hi"]), how)
    raise ValueError(f"file.tensors {leaf!r}: an f32 tensor takes a normal dist (std) "
                     f"or a uniform one (lo, hi), not {stated}")


def _segments(h: LlmHeader, plan: list[TensorSpec], tensors: dict) -> tuple[list, list]:
    """(offset, nbytes, draw) tiles in two lists, numbered on from the first
    through the second. The first covers the whole tensor section by the
    rule above, neighbouring Q40 tensors of one std forming one run. The
    second holds each tensor that `tensors` (the configuration's
    `file.tensors`) states, to be written over what the rule put there: so
    stating a tensor moves no other tensor's bytes. A leaf name that the plan
    lacks is an error."""
    qk_norm = h.arch in (LlmArch.QWEN3, LlmArch.QWEN3_MOE)
    leaves = [s.name.rsplit(".", 1)[-1] for s in plan]
    if set(tensors) - set(leaves):
        raise ValueError(f"file.tensors names {sorted(set(tensors) - set(leaves))}, which "
                         f"the tensor plan of {h.arch.name} lacks")
    runs: list[list] = []
    for s, leaf in zip(plan, leaves):
        draw = _default_draw(s, leaf, qk_norm)
        if runs and draw[0] == "q40" and runs[-1][2] == draw:
            runs[-1][1] += s.nbytes
        else:
            runs.append([s.offset, s.nbytes, draw])
    over = [[s.offset, s.nbytes, _stated_draw(s, leaf, tensors[leaf])]
            for s, leaf in zip(plan, leaves) if leaf in tensors]

    def tiles(runs: list) -> list:
        out = []
        for off, n, draw in runs:
            unit = Q40_BLOCK_BYTES if draw[0] == "q40" else 4
            step = TILE // unit * unit
            out += [(off + a, min(step, n - a), draw) for a in range(0, n, step)]
        return out

    return tiles(runs), tiles(over)


def _inv_softplus(y):
    """x with log(1 + exp(x)) = y, for y > 0."""
    return y + np.log(-np.expm1(-y))


_MAPS = {None: lambda x: x, "log": np.log, "inv_softplus": _inv_softplus}


def _tile(seed: int, i: int, nbytes: int, draw: tuple) -> bytes:
    rng = np.random.default_rng(np.random.SeedSequence([seed, i]))
    if draw[0] == "q40":
        n_blocks = nbytes // Q40_BLOCK_BYTES
        words = rng.integers(0, 1 << 64, -(-nbytes // 8), dtype=np.uint64)
        blocks = words.view(np.uint8)[:nbytes].reshape(n_blocks, Q40_BLOCK_BYTES)
        scales = (_UNIT * (draw[1] / _UNIT_STD)).astype(np.float16).view(np.uint16)
        # the block's own first random byte picks its scale
        blocks[:, :2] = scales[blocks[:, 0]].view(np.uint8).reshape(-1, 2)
        return blocks.tobytes()
    n = nbytes // 4
    if draw[0] == "uniform":
        values = rng.uniform(draw[1], draw[2], n)
    else:
        values = rng.standard_normal(n, dtype=np.float32) * draw[1]
    if draw[-1] is not None:
        values = _MAPS[draw[-1]](values.astype(np.float64))
    return values.astype(np.float32).tobytes()


def write_model(path: str, cfg: dict, seed: int, threads: int = 8) -> LlmHeader:
    """Write the configuration's `.m` at `path` from `seed`. The tensor plan
    is the program's for the header as the program's own reader parses it
    back, so a key that reader knows reaches the plan with no line here."""
    wire = header_for(cfg)
    try:
        with open(path, "wb") as f:
            write_header(f, wire)
        h = read_llm_header(path)
        plan = tensor_plan(h)
        if plan[-1].name != "wcls":
            raise ValueError(f"the tensor plan of {h.arch.name} ends in "
                             f"{plan[-1].name!r}, not in wcls: the end-of-sequence "
                             f"rows zeroed below would be another tensor's")
        ruled, stated = _segments(h, plan, cfg["file"].get("tensors", {}))
    except BaseException:
        if os.path.exists(path):
            os.remove(path)
        raise
    total = plan[-1].offset + plan[-1].nbytes
    os.truncate(path, total)
    fd = os.open(path, os.O_WRONLY)
    try:
        def job(i: int, seg: tuple) -> None:
            off, n, draw = seg
            if os.pwrite(fd, _tile(seed, i, n, draw), off) != n:
                raise OSError(f"short write at {off}")

        with ThreadPoolExecutor(min(threads, os.cpu_count() or 1)) as pool:
            for first, segs in ((0, ruled), (len(ruled), stated)):  # one after the other
                for fut in [pool.submit(job, first + i, seg) for i, seg in enumerate(segs)]:
                    fut.result()
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
