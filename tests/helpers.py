"""Shared test fixtures: synthetic tiny models written in the real `.m`/`.t`
wire formats, so the whole read path (header parse -> tensor plan -> dequant)
is exercised exactly as it is for real checkpoints."""

from __future__ import annotations

import os

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from dllama_tpu.formats import FloatType
from dllama_tpu.formats.model_file import LlmArch
from dllama_tpu.formats.tokenizer_file import TokenizerData, write_tokenizer
from dllama_tpu.formats.writer import write_header, write_tensor

TINY = dict(
    dim=64,
    hidden_dim=160,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    vocab_size=256,
    seq_len=64,
)

TINY_MOE = dict(
    dim=64,
    hidden_dim=160,
    moe_hidden_dim=96,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    vocab_size=256,
    seq_len=64,
    n_experts=4,
    n_active_experts=2,
)


def make_tiny_model(
    path,
    arch: LlmArch = LlmArch.LLAMA,
    weight_type: FloatType = FloatType.Q40,
    seed: int = 0,
    cfg: dict | None = None,
    rope_scaling: bool = False,
) -> dict[str, np.ndarray]:
    """Write a tiny random model to `path`; returns the exact f32 tensors
    (pre-quantization) keyed by plan name."""
    if cfg is None:
        cfg = dict(TINY_MOE if arch == LlmArch.QWEN3_MOE else TINY)
    rng = np.random.default_rng(seed)
    d = cfg["dim"]
    hd = cfg["head_dim"]
    q_dim = hd * cfg["n_heads"]
    kv_dim = hd * cfg["n_kv_heads"]
    n_experts = cfg.get("n_experts", 0)
    ff = cfg["moe_hidden_dim"] if arch == LlmArch.QWEN3_MOE else cfg["hidden_dim"]

    params = {
        "version": 0,
        "arch_type": int(arch),
        "dim": d,
        "hidden_dim": cfg["hidden_dim"],
        "n_layers": cfg["n_layers"],
        "n_heads": cfg["n_heads"],
        "n_kv_heads": cfg["n_kv_heads"],
        "n_experts": n_experts,
        "n_active_experts": cfg.get("n_active_experts", 0),
        "vocab_size": cfg["vocab_size"],
        "max_seq_len": cfg["seq_len"],
        "hidden_act": 1,  # silu
        "rope_theta": 10000,
        "weights_float_type": int(weight_type),
        "head_dim": hd,
        "norm_epsilon": 5,
    }
    if arch == LlmArch.QWEN3_MOE:
        params["moe_hidden_dim"] = cfg["moe_hidden_dim"]
    if rope_scaling:
        params.update(
            rope_type=2,  # llama3.1
            rope_scaling_factor=8,
            rope_scaling_low_freq_factor=1,
            rope_scaling_high_freq_factory=4,
            rope_scaling_orig_max_seq_len=cfg["seq_len"] // 2,
        )

    def t(*shape):
        return (rng.standard_normal(shape) * 0.08).astype(np.float32)

    tensors: dict[str, tuple[np.ndarray, FloatType]] = {}

    def add(name, arr, ft):
        tensors[name] = (arr, ft)

    wt = weight_type
    add("embed", t(cfg["vocab_size"], d), FloatType.F32)
    for l in range(cfg["n_layers"]):
        add(f"layers.{l}.q", t(q_dim, d), wt)
        add(f"layers.{l}.k", t(kv_dim, d), wt)
        add(f"layers.{l}.v", t(kv_dim, d), wt)
        add(f"layers.{l}.wo", t(d, q_dim), wt)
        if n_experts > 0:
            add(f"layers.{l}.moe_gate", t(n_experts, d), FloatType.F32)
            for e in range(n_experts):
                add(f"layers.{l}.experts.{e}.w1", t(ff, d), wt)
                add(f"layers.{l}.experts.{e}.w2", t(d, ff), wt)
                add(f"layers.{l}.experts.{e}.w3", t(ff, d), wt)
        else:
            add(f"layers.{l}.w1", t(ff, d), wt)
            add(f"layers.{l}.w2", t(d, ff), wt)
            add(f"layers.{l}.w3", t(ff, d), wt)
        if arch in (LlmArch.QWEN3, LlmArch.QWEN3_MOE):
            add(f"layers.{l}.q_norm", 1.0 + t(hd), FloatType.F32)
            add(f"layers.{l}.k_norm", 1.0 + t(hd), FloatType.F32)
        add(f"layers.{l}.att_norm", 1.0 + t(d), FloatType.F32)
        add(f"layers.{l}.ffn_norm", 1.0 + t(d), FloatType.F32)
    add("final_norm", 1.0 + t(d), FloatType.F32)
    add("wcls", t(cfg["vocab_size"], d), wt)

    with open(path, "wb") as f:
        write_header(f, params)
        for name, (arr, ft) in tensors.items():
            write_tensor(f, arr, ft)

    return {name: arr for name, (arr, ft) in tensors.items()}


def make_tiny_tokenizer(
    path, chat_template: str | None = None, pad_to: int = 0
) -> TokenizerData:
    """A tiny byte-level tokenizer: 256 single-byte regular tokens, then a few
    merged tokens, then specials. Regular/special split at bos_id, matching
    the reference layout assumption (src/tokenizer.cpp:138-140)."""
    vocab: list[bytes] = [bytes([i]) for i in range(256)]
    scores: list[float] = [0.0] * 256
    merges = [
        b"he", b"ll", b"llo", b"hello",
        b" w", b" wo", b" wor", b" worl", b" world",
        b"hi", b"th", b"the",
    ]
    for i, m in enumerate(merges):
        vocab.append(m)
        scores.append(float(i + 1))
    specials = [b"<s>", b"</s>", b"<|eot|>"]
    # pad the regular vocab so tokenizer size can match a model's vocab
    # (reference decode indexes vocab[token] for any sampled id)
    if pad_to:
        assert pad_to >= len(vocab) + len(specials), (pad_to, len(vocab))
        while len(vocab) < pad_to - len(specials):
            vocab.append(f"<pad{len(vocab)}>".encode())
            scores.append(0.0)
    bos_id = len(vocab)
    for s in specials:
        vocab.append(s)
        scores.append(0.0)
    if pad_to:
        assert len(vocab) == pad_to, (len(vocab), pad_to)
    data = TokenizerData(
        vocab=vocab,
        scores=scores,
        bos_id=bos_id,
        add_bos=True,
        eos_token_ids=[bos_id + 1, bos_id + 2],
        chat_template=chat_template,
        max_token_length=max(len(v) for v in vocab),
    )
    write_tokenizer(path, data)
    return data


AFMOE_WINDOW = 32


def tiny_afmoe_config(layer_types=None, **over) -> dict:
    """A benchmark configuration file's worth of the `afmoe` architecture
    (window and full layers, a dense layer and then experts, a share of the
    experts) at test widths, for `benchmark/harness/weights.write_model`."""
    types = layer_types or ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
    sliding = [t == "sliding_attention" for t in types]
    period = 0 if all(sliding) else 4
    cfg = {
        "name": "afmoe-tiny", "family": "afmoe",
        "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 128,
        "num_hidden_layers": len(types), "num_attention_heads": 8,
        "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 512,
        "max_position_embeddings": 4096, "rope_theta": 10000, "rms_norm_eps": 1e-5,
        "num_experts": 4, "num_routed_experts": 8, "first_expert": 0,
        "num_experts_per_tok": 2, "num_shared_experts": 1, "num_dense_layers": 1,
        "route_norm": True, "route_scale": 2.448, "score_func": "sigmoid",
        "mup_enabled": True, "sliding_window": AFMOE_WINDOW if any(sliding) else 0,
        "layer_types": types,
    }
    cfg.update(over)
    cfg["file"] = {
        "arch": "AFMOE", "rope_pairing": "half", "qk_norm": True, "norm_epsilon_enum": 5,
        "header": {
            "sliding_window": cfg["sliding_window"], "full_attn_period": period,
            "full_attn_no_rope": 1, "n_dense_layers": cfg["num_dense_layers"],
            "n_shared_experts": cfg["num_shared_experts"], "score_func": 1,
            "route_norm": 1, "route_scale_milli": 2448,
            "n_routed_experts": cfg["num_routed_experts"],
            "first_expert": cfg["first_expert"], "embed_scale": 1,
        },
        "tensors": {"embed": {"dist": "normal", "std": 0.125}},
    }
    if cfg["num_dense_layers"] < len(types):
        cfg["file"]["tensors"]["expert_bias"] = {"dist": "normal", "std": 0.05}
    return cfg


def _write_tiny(path: str, cfg: dict, seed: int) -> dict:
    """Write the seeded model of a configuration at `path` through the
    benchmark's own writer (the program's format code under it)."""
    import sys

    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    from benchmark.harness import weights

    weights.write_model(path, cfg, seed)
    return cfg


def make_tiny_afmoe(path: str, seed: int = 3, **over) -> dict:
    """Write a seeded tiny `afmoe` model at `path`; returns its configuration."""
    return _write_tiny(path, tiny_afmoe_config(**over), seed)


def tiny_pangu_config(**over) -> dict:
    """A benchmark configuration file's worth of the `pangu_ultra_moe`
    architecture (latent attention, a dense layer and then experts with a
    shared one, a share of the experts) at test widths that keep every
    ratio: the query's latent, the cached latent, the nope, rope and value
    widths all differ from each other."""
    cfg = {
        "name": "pangu-tiny", "family": "pangu_ultra_moe",
        "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 128,
        "num_hidden_layers": 5, "first_k_dense_replace": 1,
        "num_attention_heads": 8, "num_key_value_heads": 8,
        "q_lora_rank": 96, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 24, "vocab_size": 512,
        "max_position_embeddings": 4096, "rope_theta": 25600000, "rms_norm_eps": 1e-5,
        "n_routed_experts": 4, "num_routed_experts": 8, "first_expert": 0,
        "num_experts_per_tok": 2, "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "assumed": {"head_dim": 24},
    }
    cfg.update(over)
    cfg["num_experts"] = cfg["n_routed_experts"]  # the harness's name for the experts held
    sparse = cfg["first_k_dense_replace"] < cfg["num_hidden_layers"]
    if not sparse:
        cfg["num_experts"] = 0
    cfg["file"] = {
        "arch": "PANGU_MOE", "rope_pairing": "half", "norm_epsilon_enum": 5,
        "header": {
            "n_dense_layers": cfg["first_k_dense_replace"],
            "n_shared_experts": cfg["n_shared_experts"] if sparse else 0,
            "score_func": 1, "route_norm": 1, "route_scale_milli": 2500,
            "n_routed_experts": cfg["num_routed_experts"] if sparse else 0,
            "first_expert": cfg["first_expert"],
            **{k: cfg[k] for k in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                                   "qk_rope_head_dim", "v_head_dim")},
        },
        "tensors": {"q_a_norm": {"dist": "uniform", "lo": 2.0, "hi": 3.0}},
    }
    return cfg


def make_tiny_pangu(path: str, seed: int = 3, **over) -> dict:
    """The same for `pangu_ultra_moe`, as `make_tiny_afmoe`."""
    return _write_tiny(path, tiny_pangu_config(**over), seed)


DSV32_TOPK = 16


def tiny_dsv32_config(**over) -> dict:
    """A benchmark configuration file's worth of the `deepseek_v32`
    architecture (latent attention over the rows an index picks, a router
    limited to some of its groups, a rotary table scaled by band) at test
    widths at which each still bites: an index of 4 heads of 16 that keeps
    16 rows, 8 routed experts in 4 groups of which 2 stay, the first two
    groups held, a rotary scaling over an original length of 64."""
    cfg = {
        "name": "dsv32-tiny", "family": "deepseek_v32",
        "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 128,
        "num_hidden_layers": 5, "first_k_dense_replace": 1,
        "num_attention_heads": 8, "num_key_value_heads": 8,
        "q_lora_rank": 96, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 24, "vocab_size": 512,
        "index_n_heads": 4, "index_head_dim": 16, "index_topk": DSV32_TOPK,
        "max_position_embeddings": 4096, "rope_theta": 10000, "rms_norm_eps": 1e-6,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                         "mscale_all_dim": 1, "original_max_position_embeddings": 64,
                         "type": "yarn"},
        "n_routed_experts": 4, "num_routed_experts": 8, "first_expert": 0,
        "n_group": 4, "topk_group": 2,
        "num_experts_per_tok": 2, "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "assumed": {"head_dim": 24},
    }
    cfg.update(over)
    cfg["num_experts"] = cfg["n_routed_experts"]  # the harness's name for the experts held
    scaling = cfg["rope_scaling"]
    cfg["file"] = {
        "arch": "DEEPSEEK_V32", "rope_pairing": "interleaved", "norm_epsilon_enum": 6,
        "header": {
            "n_dense_layers": cfg["first_k_dense_replace"],
            "n_shared_experts": cfg["n_shared_experts"],
            "score_func": 1, "route_norm": 1, "route_scale_milli": 2500,
            "n_routed_experts": cfg["num_routed_experts"],
            "first_expert": cfg["first_expert"],
            **{k: cfg[k] for k in (
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "index_n_heads", "index_head_dim", "index_topk",
                "n_group", "topk_group")},
            "rope_type": 3, "rope_scaling_factor": scaling["factor"],
            "rope_scaling_orig_max_seq_len": scaling["original_max_position_embeddings"],
            "rope_beta_fast": scaling["beta_fast"], "rope_beta_slow": scaling["beta_slow"],
            "rope_mscale_milli": round(1000 * scaling["mscale"]),
            "rope_mscale_all_dim_milli": round(1000 * scaling["mscale_all_dim"]),
        },
        "tensors": {"q_a_norm": {"dist": "uniform", "lo": 2.0, "hi": 3.0},
                    "expert_bias": {"dist": "normal", "std": 0.05}},
    }
    return cfg


def make_tiny_dsv32(path: str, seed: int = 3, **over) -> dict:
    """The same for `deepseek_v32`, as `make_tiny_afmoe`."""
    return _write_tiny(path, tiny_dsv32_config(**over), seed)


LFM2_TYPES = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 2 + [
    "full_attention", "conv"]


def attn_layer_words(layer_types) -> dict:
    """The header's two words that name the attention layers of a pattern of
    state layers and `full_attention` or `attention` layers (keys 48 and 49:
    30 layers a word)."""
    mask = sum(1 << l for l, t in enumerate(layer_types) if t in ("full_attention", "attention"))
    return {"attn_layers_lo": mask & ((1 << 30) - 1), "attn_layers_hi": mask >> 30}


def tiny_lfm2_config(layer_types=None, **over) -> dict:
    """A benchmark configuration file's worth of the `lfm2_moe` architecture
    (gated short convolutions that keep a state a lane, an attention layer
    every fourth, two leading dense layers and then a share of the experts
    behind a biased sigmoid router) at test widths. The default pattern is
    the published one's shape: its sparse run is two whole periods of four
    and a tail of two, as the published 38 are nine and a tail of two."""
    types = list(layer_types or LFM2_TYPES)
    cfg = {
        "name": "lfm2-tiny", "family": "lfm2_moe",
        "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 128,
        "num_hidden_layers": len(types), "num_attention_heads": 8,
        "num_key_value_heads": 4, "head_dim": 8, "vocab_size": 512,
        "max_position_embeddings": 4096, "rope_theta": 1000000, "rms_norm_eps": 1e-5,
        "conv_L_cache": 3, "layer_types": types,
        "num_experts": 4, "num_routed_experts": 8, "first_expert": 0,
        "num_experts_per_tok": 2, "num_dense_layers": 2,
        "norm_topk_prob": True, "routed_scaling_factor": 1,
    }
    cfg.update(over)
    cfg["file"] = {
        "arch": "LFM2_MOE", "rope_pairing": "half", "qk_norm": True, "norm_epsilon_enum": 5,
        "header": {
            "n_dense_layers": cfg["num_dense_layers"], "score_func": 1, "route_norm": 1,
            "n_routed_experts": cfg["num_routed_experts"],
            "first_expert": cfg["first_expert"],
            "conv_l_cache": cfg["conv_L_cache"], **attn_layer_words(types),
        },
        "tensors": {"expert_bias": {"dist": "normal", "std": 0.05}},
    }
    return cfg


def make_tiny_lfm2(path: str, seed: int = 3, **over) -> dict:
    """The same for `lfm2_moe`, as `make_tiny_afmoe`."""
    return _write_tiny(path, tiny_lfm2_config(**over), seed)


# -- one spelling a serving knob (PR 45) -----------------------------------------
#
# Until PR 45 nineteen flags of `cli.add_engine_args` defaulted to None so that
# a DLLAMA_* variable of the same meaning could fill them in. The variables
# are no longer read. Each entry: the former variable -> (a value that is not
# the flag's default, the flag's dest, what the flag's default leaves on the
# state, how to read it off an ApiState). A default may be a function of the
# state where 0 or None means "work it out".
FORMER_TWINS = {
    "DLLAMA_LANE_BLOCK": ("5", "lane_block_size", 8, lambda st: st.scheduler.block_size),
    "DLLAMA_ADMISSION_CHUNK": (
        "24", "admission_chunk", lambda st: max(st.engine.prefill_buckets),
        lambda st: st.scheduler.admission_chunk),
    "DLLAMA_KV_PAGE_SIZE": ("4", "kv_page_size", 16, lambda st: st.kv_manager.page_size),
    "DLLAMA_KV_POOL_PAGES": (
        "97", "kv_pool_pages", lambda st: 2 * (st.engine.header.seq_len // 16) + 1,
        lambda st: st.engine._kv_pool_pages),
    "DLLAMA_KV_NATIVE": ("1", "kv_native", 0, lambda st: st.engine.kv_native),
    "DLLAMA_MAX_STREAMS": ("6", "max_streams", 0, lambda st: st.scheduler.max_streams),
    "DLLAMA_SPECULATION": ("ngram", "speculation", "off", lambda st: st.scheduler.spec_mode),
    "DLLAMA_SPEC_K": ("8", "spec_k", 4, lambda st: st.scheduler.spec_k),
    "DLLAMA_DRAFT_MODEL": (
        "/env/d.m", "draft_model", None, lambda st: st.engine._draft_params),
    "DLLAMA_RETRY_MAX": ("1", "retry_max", 3, lambda st: st.scheduler.retry_max),
    "DLLAMA_RETRY_BACKOFF_MS": (
        "70", "retry_backoff_ms", 5, lambda st: st.scheduler.retry_backoff_s * 1000.0),
    "DLLAMA_MAX_QUEUE_DEPTH": ("9", "max_queue_depth", 0, lambda st: st.max_queue_depth),
    "DLLAMA_ADMISSION_PREDICT": (
        "1", "admission_predict", False, lambda st: st.admission_predict),
    "DLLAMA_ADMISSION_MAX_WAIT_MS": (
        "9000", "admission_max_wait_ms", 30_000, lambda st: st.admission_max_wait_ms),
    "DLLAMA_DEADLINE_DEFAULT_MS": (
        "120000", "deadline_default_ms", 600_000, lambda st: st.deadline_default_ms),
    "DLLAMA_DEADLINE_PRIORITY_STEP_MS": (
        "5000", "deadline_priority_step_ms", 60_000,
        lambda st: st.deadline_priority_step_ms),
    "DLLAMA_SLO_TTFT_MS": ("250", "slo_ttft_ms", None, lambda st: st.slo.ttft_target_ms),
    "DLLAMA_SLO_TPOT_MS": ("40", "slo_tpot_ms", None, lambda st: st.slo.tpot_target_ms),
    "DLLAMA_SERIES_RETENTION_S": (
        "120", "series_retention", 3600.0, lambda st: st.series.retention_s),
}


def flags_state(tmp_path_factory, *argv, former_twins: bool = False, paths=None):
    """The `ApiState` that `python -m dllama_tpu.runtime.api_server` builds
    from `argv` over a tiny model with two lanes (`serve_from_args`, never
    started; `paths`: a model and a tokenizer that exist already), with
    every one of `FORMER_TWINS` set in the environment while it is built if
    `former_twins`. Yields (the parsed args, the state)."""
    import pytest

    from dllama_tpu.runtime.api_server import build_arg_parser, serve_from_args

    if paths is None:
        d = tmp_path_factory.mktemp("flags")
        paths = str(d / "m.m"), str(d / "t.t")
        make_tiny_model(paths[0], cfg=dict(TINY, vocab_size=288, seq_len=384))
        make_tiny_tokenizer(paths[1], chat_template="<|start_header_id|>")
    mp, tp_ = paths
    args = build_arg_parser().parse_args([
        "--model", mp, "--tokenizer", tp_, "--port", "0", "--batch-size", "2",
        "--dtype", "f32", "--temperature", "0.0", "--seed", "3", *argv,
    ])
    with pytest.MonkeyPatch.context() as env:
        for name, (value, *_) in FORMER_TWINS.items():
            if former_twins:
                env.setenv(name, value)
            else:
                env.delenv(name, raising=False)
        server = serve_from_args(args)
    try:
        yield args, server.state
    finally:
        # the rehearsal compiles on threads of its own: an interpreter that
        # exits in the middle of one aborts
        server.state.engine.rehearse_admission(wait=True)
        server.server_close()


def twin_flags(*names: str) -> list[str]:
    """argv that passes each of `names`' flags with its variable's value."""
    argv = []
    for name in names:
        value, dest, default, _ = FORMER_TWINS[name]
        argv.append("--" + dest.replace("_", "-"))
        if default is not False:  # a store_true flag takes no value
            argv.append(value)
    return argv


def assert_one_spelling(name: str, unflagged, flagged) -> None:
    """`unflagged` is `flags_state(..., former_twins=True)` with none of the
    nineteen flags passed: the parser and the state hold the default of
    `name`'s flag, whatever the variable says. `flagged` was built from
    `twin_flags(name, ...)`: the flag's value is what the state holds."""
    from dllama_tpu.runtime.api_server import build_arg_parser

    _, dest, default, read = FORMER_TWINS[name]
    flag_default = getattr(build_arg_parser().parse_args([]), dest)
    args, state = unflagged
    assert getattr(args, dest) == flag_default
    want = default(state) if callable(default) else default
    got = read(state)
    assert got == want if want is not None else got is None, (name, got, want)
    args, state = flagged
    assert getattr(args, dest) != flag_default
    assert read(state) == getattr(args, dest), name


GRANITE_TYPES = ["mamba", "mamba", "attention", "mamba"] * 2


def tiny_granite_config(layer_types=None, **over) -> dict:
    """A benchmark configuration file's worth of the `granitemoehybrid`
    architecture (Mamba-2 layers that keep a recurrent state a lane, an
    attention layer without rope now and then, a share of the experts behind
    a softmax router beside a shared expert, multipliers on the embedding, the
    residual adds, the scores and the logits) at test widths. The default
    pattern is the published one's shape: two whole periods."""
    types = list(layer_types or GRANITE_TYPES)
    cfg = {
        "name": "granite-tiny", "family": "granitemoehybrid",
        "hidden_size": 64, "intermediate_size": 32, "shared_intermediate_size": 64,
        "num_hidden_layers": len(types), "num_attention_heads": 8,
        "num_key_value_heads": 4, "head_dim": 8, "vocab_size": 512,
        "max_position_embeddings": 4096, "rope_theta": 10000, "rms_norm_eps": 1e-5,
        "layer_types": types, "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_state": 16,
        "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 256,
        "embedding_multiplier": 12, "residual_multiplier": 0.22,
        "attention_multiplier": 0.25, "logits_scaling": 16,
        "num_local_experts": 4, "num_experts": 4, "num_routed_experts": 8,
        "first_expert": 0, "num_experts_per_tok": 3,
    }
    cfg.update(over)
    cfg["file"] = {
        "arch": "GRANITE_MOE_HYBRID", "rope_pairing": "half", "qk_norm": False,
        "norm_epsilon_enum": 5,
        "header": {
            "full_attn_no_rope": 1,
            "n_shared_experts": cfg["shared_intermediate_size"] // cfg["intermediate_size"],
            "score_func": 0, "route_norm": 1,
            "n_routed_experts": cfg["num_routed_experts"],
            "first_expert": cfg["first_expert"], **attn_layer_words(types),
            "ssm_n_heads": cfg["mamba_n_heads"], "ssm_head_dim": cfg["mamba_d_head"],
            "ssm_state_dim": cfg["mamba_d_state"], "ssm_n_groups": cfg["mamba_n_groups"],
            "ssm_conv_taps": cfg["mamba_d_conv"],
            "embed_multiplier_milli": round(cfg["embedding_multiplier"] * 1e3),
            "residual_multiplier_nano": round(cfg["residual_multiplier"] * 1e9),
            "attention_multiplier_nano": round(cfg["attention_multiplier"] * 1e9),
            "logits_scaling_milli": round(cfg["logits_scaling"] * 1e3),
        },
        "tensors": {
            "ssm_a_log": {"dist": "uniform", "lo": 1.0, "hi": 16.0, "map": "log"},
            "ssm_dt_bias": {"dist": "uniform", "lo": 0.02, "hi": 0.2, "map": "inv_softplus"},
            "ssm_d": {"dist": "uniform", "lo": 0.9, "hi": 1.1},
            "ssm_conv_b": {"dist": "normal", "std": 0.1},
            "ssm_in_dt": {"gain": 0.25},
            # the multipliers are the published ones; the seeded weights are
            # sized so that the embedding enters at std 1 and every mixer
            # adds as much, or the next token would follow from the last alone
            "embed": {"dist": "normal", "std": 1 / cfg["embedding_multiplier"]},
            "ssm_out": {"gain": 1 / cfg["residual_multiplier"]},
            "wo": {"gain": 1 / cfg["residual_multiplier"]},
            "w2": {"gain": 0.25 / cfg["residual_multiplier"]},
        },
    }
    return cfg


def make_tiny_granite(path: str, seed: int = 3, **over) -> dict:
    """The same for `granitemoehybrid`, as `make_tiny_afmoe`."""
    return _write_tiny(path, tiny_granite_config(**over), seed)


# the benchmark's families at test widths, by the name of their `LlmArch`
TINY_FAMILY_WRITERS = {
    "afmoe": make_tiny_afmoe, "pangu_ultra_moe": make_tiny_pangu,
    "deepseek_v32": make_tiny_dsv32, "lfm2_moe": make_tiny_lfm2,
    "granitemoehybrid": make_tiny_granite,
}


# -- the prefill ladder's middle rungs (PR 49) ------------------------------------

# prompts whose fills take the 128-row and the 256-row rung of the served ladder
MIDDLE_RUNG_PROMPTS = {100: 128, 200: 256}


_RUNG_ENGINES: dict = {}


def _rung_engine(path: str, buckets: tuple):
    """One two-lane engine a model file and ladder, kept over a file's cases
    (each would build its chunk programs anew)."""
    import jax.numpy as jnp

    from dllama_tpu.runtime.engine import InferenceEngine

    key = path, buckets
    if key not in _RUNG_ENGINES:
        _RUNG_ENGINES[key] = InferenceEngine(
            path, tp=1, dtype=jnp.float32, temperature=0.0, batch_size=2,
            prefill_buckets=buckets, max_seq_len=1024)
    _RUNG_ENGINES[key].reset()
    return _RUNG_ENGINES[key]


def assert_a_middle_rung_equals_the_largest(path: str, n_prompt: int, tol: float = 1e-5) -> None:
    """A prompt prefilled through a middle rung of the ladder the CLI serves
    (`engine.prefill_ladder(128)`: 128 and 256 rows under the 512 every
    family's chunk program already ran at) leaves what the same prompt
    leaves through the largest rung alone, the ladder (1, 512): the lane's
    cache rows, its lane states, the next token's logits, within the
    tolerance the families' own chunk tests use. Only rows of padding,
    which no query reads and no state moves by, differ between the two."""
    import jax

    from dllama_tpu.runtime.engine import prefill_ladder

    lane, n_fills = 1, n_prompt - 1
    ids = [int(t) for t in np.random.default_rng(n_prompt).integers(1, 250, n_prompt)]
    left = {}
    for name, buckets in (("ladder", prefill_ladder(128)), ("largest", (1, 512))):
        e = _rung_engine(path, buckets)
        assert e.prefill_buckets == buckets
        # the recorder outlives an engine and its ring drops the oldest: by `seq`
        base = max((d["seq"] for d in e.recorder.events("step_dispatch")), default=-1)
        e.prefill_lane(lane, ids)
        ran = [(d["bucket"], d["n_tokens"]) for d in e.recorder.events("step_dispatch")
               if d["seq"] > base and d["step"] == "prefill_lane_chunk"]
        want = MIDDLE_RUNG_PROMPTS[n_prompt] if name == "ladder" else 512
        assert ran == [(want, n_fills)], (name, ran)
        tok = np.zeros((2, 1), np.int32)
        tok[lane] = ids[-1]
        pos = np.full((2,), e._park, np.int32)
        pos[lane] = n_fills
        logits, _ = jax.jit(lambda params, t, p, cache, e=e: e._fwd(
            params, t, p, cache, attn_window=e._attn_window(n_prompt),
            attn_park_threshold=e._park, logits_mode="last"))(e.params, tok, pos, e.cache)
        def filled(k, v):
            if k in ("s", "r"):  # a state is rows a lane, not a position
                return v[:, lane]
            # a ring stack has spare rows before the ring (`_ring_append`)
            row0 = (v.shape[3] - e.kv_ring) // 2 if k in ("kw", "vw") else 0
            return v[:, lane, :, row0:row0 + n_fills]

        rows = {k: np.asarray(filled(k, v)) for k, v in e.cache.items()}
        left[name] = rows, np.asarray(logits[lane, -1])
    (rows, logits), (want_rows, want_logits) = left["ladder"], left["largest"]
    assert np.abs(logits - want_logits).max() < tol * want_logits.std()
    assert rows.keys() == want_rows.keys()
    for k, want in want_rows.items():
        assert want.any(), k
        assert np.abs(rows[k] - want).max() < tol * np.abs(want).max(), k
