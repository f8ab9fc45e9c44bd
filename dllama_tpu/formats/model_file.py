"""Reader for distributed-llama's `.m` model file format.

Format (reference: src/llm.cpp:36-116, converter/writer.py:109-148):

    int32 magic = 0xA00ABCD
    int32 headerSize          # bytes, counting magic+headerSize themselves
    int32 key, int32 value    # repeated; keys from LlmHeaderKey (src/llm.hpp:8-31)
    ...tensor data...         # fixed order, see `tensor_plan`

Quirks faithfully reproduced:
  * float-valued header fields (rope theta, rope scaling factors) are stored
    as ints and cast (src/llm.cpp:86-91) — only integer values survive;
  * norm epsilon is an enum: 5 -> 1e-5, 6 -> 1e-6 (src/llm.cpp:30-34);
  * ``head_dim`` defaults to dim/nHeads when absent (src/llm.cpp:106-108);
  * Qwen3 / Qwen3-MoE force Falcon (half-rotation) RoPE (src/llm.cpp:113-114).

The tensor section is walked lazily via a single ``np.memmap``; per-tensor
views are zero-copy, so a 40 GB 70B file never materializes on host. The
tensor order matches the converter exactly (converter/convert-hf.py:59-104)
which is the same order `loadLlmNetWeight` consumes (src/llm.cpp:614-669).
"""

from __future__ import annotations

import dataclasses
import enum
import math
import struct
from typing import Iterator

import numpy as np

from .quants import (
    FloatType,
    dequantize_q40,
    dequantize_q80,
    q40_to_planar,
    tensor_bytes,
)

MODEL_MAGIC = 0x0A00ABCD
_OLD_MAGICS = (0xABCD00, 0xABCD01)


class LlmArch(enum.IntEnum):
    """Model architectures (reference: src/llm.hpp:38-42)."""

    LLAMA = 0xABCD00
    QWEN3 = 0xABCD01
    QWEN3_MOE = 0xABCD02
    # window and full attention layers mixed, gated attention, sandwich
    # norms, a sigmoid router with a shared expert (`model_type: afmoe`);
    # not in the reference, whose enum ends above
    AFMOE = 0xABCD10
    # latent attention (MLA) over one cache stack of `[c | k_rope]` rows,
    # sandwich norms, a sigmoid router with a shared expert after leading
    # dense layers (`model_type: pangu_ultra_moe`)
    PANGU_MOE = 0xABCD11
    # latent attention whose queries attend to the `index_topk` rows a
    # learned index picks (a second cache stack of index keys), a sigmoid
    # router with a selection bias and a group limit, one norm before each
    # block, a rotary table scaled by frequency band
    # (`model_type: deepseek_v32`)
    DEEPSEEK_V32 = 0xABCD12
    # gated short convolutions where most layers' attention would be: such
    # a layer keeps the last `conv_l_cache - 1` gated rows of a lane as its
    # state and no cache row; the layers named by the attention mask are
    # grouped-query attention with a norm on each head's q and k; leading
    # dense layers, then a sigmoid router with a selection bias
    # (`model_type: lfm2_moe`)
    LFM2_MOE = 0xABCD13
    # Mamba-2 layers where most layers' attention would be: such a layer
    # keeps a recurrent state a lane (`ssm_n_heads` x `ssm_head_dim` x
    # `ssm_state_dim`, float32, reaching back to position 0) and the last
    # `ssm_conv_taps - 1` rows of its convolution's input, and no cache row;
    # the layers named by the attention mask are grouped-query attention
    # without rope under a stated score scale; every layer's FFN is a softmax
    # router's experts and a shared expert; multipliers on the embedding, the
    # residual adds and the logits (`model_type: granitemoehybrid`)
    GRANITE_MOE_HYBRID = 0xABCD14


class RopeType(enum.IntEnum):
    """RoPE variants (reference: src/nn/nn-core.hpp:125-129)."""

    LLAMA = 0  # interleaved pairs (x[2i], x[2i+1])
    FALCON = 1  # half-rotation (x[j], x[j + headDim/2])
    LLAMA3_1 = 2  # interleaved + llama-3.1 frequency scaling
    YARN = 3  # interleaved + frequencies scaled by band (`rope_scaling.type: yarn`)


class HiddenAct(enum.IntEnum):
    """FFN activation (reference: src/llm.hpp:33-36)."""

    GELU = 0
    SILU = 1


class HeaderKey(enum.IntEnum):
    """`.m` header keys (reference: src/llm.hpp:8-31)."""

    VERSION = 0
    ARCH_TYPE = 1
    DIM = 2
    HIDDEN_DIM = 3
    N_LAYERS = 4
    N_HEADS = 5
    N_KV_HEADS = 6
    N_EXPERTS = 7
    N_ACTIVE_EXPERTS = 8
    VOCAB_SIZE = 9
    SEQ_LEN = 10
    HIDDEN_ACT = 11
    ROPE_THETA = 12
    WEIGHT_FLOAT_TYPE = 13
    ROPE_SCALING_FACTOR = 14
    ROPE_SCALING_LOW_FREQ_FACTOR = 15
    ROPE_SCALING_HIGH_FREQ_FACTORY = 16
    ROPE_SCALING_ORIG_MAX_SEQ_LEN = 17
    ROPE_TYPE = 18
    HEAD_DIM = 19
    NORM_EPSILON = 20
    MOE_HIDDEN_DIM = 21
    # keys past the reference's (src/llm.hpp ends at 21); absent = 0, and
    # 0 is what every architecture of the reference means by them
    SLIDING_WINDOW = 22  # rows a window layer's query sees (0: no window layers)
    FULL_ATTN_PERIOD = 23  # layer l attends in full where (l + 1) % period == 0
    FULL_ATTN_NO_ROPE = 24  # 1: full layers take no rotary embedding
    N_DENSE_LAYERS = 25  # leading layers with a dense FFN of HIDDEN_DIM
    N_SHARED_EXPERTS = 26  # experts every token passes through
    SCORE_FUNC = 27  # router scores: 0 softmax over all experts, 1 sigmoid
    ROUTE_NORM = 28  # 0: the chosen scores are not divided by their sum (absent: they are)
    ROUTE_SCALE_MILLI = 29  # the routed sum's factor, in thousandths (0: 1)
    N_ROUTED_EXPERTS = 30  # the router's width, where N_EXPERTS are held here
    FIRST_EXPERT = 31  # id of the first expert held here
    EMBED_SCALE = 32  # 1: embeddings are multiplied by sqrt(DIM)
    # latent attention (0: none, what every model above means)
    Q_LORA_RANK = 33  # width of the query's latent
    KV_LORA_RANK = 34  # width of the cached latent `c`; > 0 makes every layer latent
    QK_NOPE_HEAD_DIM = 35  # a head's query and key columns that take no rope
    QK_ROPE_HEAD_DIM = 36  # a head's query columns, and the one shared key's, that do
    V_HEAD_DIM = 37  # a head's value width
    # a learned index over the latent cache (0: none, every row is attended to)
    INDEX_N_HEADS = 38  # heads of the index
    INDEX_HEAD_DIM = 39  # width of an index head, and of the cached index key
    INDEX_TOPK = 40  # rows a query attends to: the best by index score; > 0 adds the `i` stack
    # a group limit on the router's selection (0: none, one group of all)
    N_GROUP = 41  # the routed experts lie in this many groups of equal size
    TOPK_GROUP = 42  # groups a token's experts may come from
    # what RopeType.YARN needs beyond keys 14 (factor) and 17 (original length)
    ROPE_BETA_FAST = 43  # rotations over the original length above which a band keeps its frequency
    ROPE_BETA_SLOW = 44  # rotations below which a band's frequency is divided by the factor
    ROPE_MSCALE_MILLI = 45  # `mscale`, in thousandths
    ROPE_MSCALE_ALL_DIM_MILLI = 46  # `mscale_all_dim`, in thousandths
    # gated short convolutions (0: none, every layer attends)
    CONV_L_CACHE = 47  # taps of the depthwise convolution; > 0: a layer is one unless named below
    ATTN_LAYERS_LO = 48  # bit l: layer l is attention (`layer_types`), l < 30
    ATTN_LAYERS_HI = 49  # bit l - 30: layer l is attention, 30 <= l < 60
    # Mamba-2 layers (0: none); > 0: a layer is one unless the mask above names it
    SSM_N_HEADS = 50  # heads of the recurrence
    SSM_HEAD_DIM = 51  # a head's width: heads x this is the mixer's inner width
    SSM_STATE_DIM = 52  # columns of a head's state, and of B and C
    SSM_N_GROUPS = 53  # groups that share B and C
    SSM_CONV_TAPS = 54  # taps of the depthwise convolution over `[x | B | C]`
    # multipliers (0 or absent: 1, or what EMBED_SCALE says)
    EMBED_MULTIPLIER_MILLI = 55  # embeddings times this, in thousandths
    RESIDUAL_MULTIPLIER_NANO = 56  # each block's output times this before its add, in 1e-9
    ATTENTION_MULTIPLIER_NANO = 57  # attention scores times this, not head_dim^-1/2, in 1e-9
    LOGITS_SCALING_MILLI = 58  # logits divided by this, in thousandths


@dataclasses.dataclass
class LlmHeader:
    """Parsed `.m` header (mirror of reference LlmHeader, src/llm.hpp:44-74)."""

    version: int = 0
    arch: LlmArch = LlmArch.LLAMA
    dim: int = 0
    hidden_dim: int = 0
    n_layers: int = 0
    n_heads: int = 0
    n_kv_heads: int = 0
    n_experts: int = 0
    n_active_experts: int = 0
    vocab_size: int = 0
    orig_seq_len: int = 0
    seq_len: int = 0
    hidden_act: HiddenAct = HiddenAct.SILU
    rope_theta: float = 10000.0
    weight_type: FloatType = FloatType.Q40
    rope_scaling_factor: float = 1.0
    rope_scaling_low_freq_factor: float = 0.0
    rope_scaling_high_freq_factor: float = 0.0
    rope_scaling_orig_max_seq_len: int = 0
    rope_type: RopeType = RopeType.LLAMA
    head_dim: int = 0
    norm_epsilon: float = 1e-5
    moe_hidden_dim: int = 0
    sliding_window: int = 0
    full_attn_period: int = 0
    full_attn_no_rope: bool = False
    n_dense_layers: int = 0
    n_shared_experts: int = 0
    score_sigmoid: bool = False
    route_norm: bool = True
    route_scale: float = 1.0
    n_routed_experts: int = 0
    first_expert: int = 0
    embed_scale: bool = False
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    n_group: int = 1
    topk_group: int = 1
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.0
    rope_mscale_all_dim: float = 0.0
    conv_l_cache: int = 0
    attn_layers: int = 0  # bit l: layer l is attention, where some layers keep a state
    ssm_n_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state_dim: int = 0
    ssm_n_groups: int = 1
    ssm_conv_taps: int = 0
    embed_multiplier: float = 0.0  # 0: none (or sqrt(dim), where embed_scale)
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0  # 0: head_dim^-1/2
    logits_scaling: float = 1.0
    header_bytes: int = 0
    file_size: int = 0
    sync_type: FloatType = FloatType.Q80

    @property
    def q_dim(self) -> int:
        return self.head_dim * self.n_heads

    @property
    def kv_dim(self) -> int:
        return self.head_dim * self.n_kv_heads

    @property
    def latent(self) -> bool:
        """Attention over a cache of latent rows (MLA)."""
        return self.kv_lora_rank > 0

    @property
    def latent_row(self) -> int:
        """Width of a latent cache row: `[c | k_rope]`."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def indexed(self) -> bool:
        """A learned index picks the rows a latent layer's query attends to."""
        return self.index_topk > 0

    @property
    def stateful(self) -> bool:
        """Some layers keep a state a lane (the last gated rows of a short
        convolution; a Mamba-2 layer's recurrent state and convolution rows)
        and no cache row a position."""
        return self.conv_l_cache > 0 or self.ssm_n_heads > 0

    @property
    def state_unbounded(self) -> bool:
        """A lane's state reaches back to position 0 (a recurrence), so no
        replay of a few positions rebuilds it behind an adopted prefix."""
        return self.ssm_n_heads > 0

    @property
    def ssm_inner(self) -> int:
        """Width of a Mamba-2 mixer's `x` and `z`: heads x head width."""
        return self.ssm_n_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels its convolution runs over: `[x | B | C]`."""
        return self.ssm_inner + 2 * self.ssm_n_groups * self.ssm_state_dim

    @property
    def conv_state_rows(self) -> int:
        """Rows of a state layer's convolution state: the taps before the newest."""
        return (self.ssm_conv_taps or self.conv_l_cache) - 1

    @property
    def kv_pack(self) -> int:
        """Key-value heads that share one cache row. The chip lays an array
        whose last axis is narrower than its 128 lanes out with the positions
        minor, and the flash kernel, which wants the head's columns there, had
        the whole stack copied in and out of every chunk program (described
        v5e, bf16[10,16,8,4608,64]). So a model with lane state, the first
        served with heads of 64, caches neighbouring heads side by side in rows
        of up to 128 columns (`models/transformer.py` pads the queries to
        match); every other model's cache is as it was."""
        pack = 1
        while (self.stateful and 2 * pack * self.head_dim <= 128
               and self.n_kv_heads % (2 * pack) == 0):
            pack *= 2
        return pack

    @property
    def softmax_scale(self) -> float:
        """What attention scores are multiplied by: 1 / sqrt(head_dim) (or the
        header's `attention_multiplier`), and
        under a rotary table scaled by band the square of the magnitude
        factor that goes with it."""
        scale = self.attention_multiplier or float(self.head_dim) ** -0.5
        if self.rope_type == RopeType.YARN and self.rope_mscale_all_dim:
            scale *= yarn_mscale(self.rope_scaling_factor, self.rope_mscale_all_dim) ** 2
        return scale

    @property
    def rope_dim(self) -> int:
        """Columns of a head that the rotary embedding turns."""
        return self.qk_rope_head_dim if self.latent else self.head_dim

    @property
    def ff_dim(self) -> int:
        """Per-expert (MoE) or dense FFN intermediate dim (src/llm.cpp:152-157)."""
        if self.arch in _WIDE_DENSE or self.arch == LlmArch.QWEN3_MOE:
            return self.moe_hidden_dim
        return self.hidden_dim

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0


# leading dense layers HIDDEN_DIM wide beside experts of MOE_HIDDEN_DIM
_WIDE_DENSE = (LlmArch.AFMOE, LlmArch.PANGU_MOE, LlmArch.DEEPSEEK_V32, LlmArch.LFM2_MOE)
# one more norm after each block
_SANDWICH = (LlmArch.AFMOE, LlmArch.PANGU_MOE)
# a selection bias beside the router
_EXPERT_BIAS = (LlmArch.AFMOE, LlmArch.DEEPSEEK_V32, LlmArch.LFM2_MOE)
# a norm on each head's q and k
_QK_NORM = (LlmArch.QWEN3, LlmArch.QWEN3_MOE, LlmArch.AFMOE, LlmArch.LFM2_MOE)
# layers a word of the attention mask names (the format stores int32)
_ATTN_MASK_BITS = 30


def yarn_mscale(factor: float, mscale: float) -> float:
    """The magnitude factor of a rotary table scaled by `factor`."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def _norm_epsilon(value: int) -> float:
    if value == 5:
        return 1e-5
    if value == 6:
        return 1e-6
    raise ValueError(f"unsupported norm epsilon enum: {value}")


def read_llm_header(
    path: str, max_seq_len: int = 0, sync_type: FloatType = FloatType.Q80
) -> LlmHeader:
    """Parse the `.m` header (reference: src/llm.cpp:36-116)."""
    h = LlmHeader()
    with open(path, "rb") as f:
        (magic,) = struct.unpack("<i", f.read(4))
        if magic in _OLD_MAGICS:
            raise ValueError("old model format is not supported")
        if magic != MODEL_MAGIC:
            raise ValueError(f"unsupported magic number: {magic:#x}")
        (header_size,) = struct.unpack("<i", f.read(4))
        n_kv_bytes = header_size - 8
        buf = f.read(n_kv_bytes)
        values = struct.unpack(f"<{n_kv_bytes // 4}i", buf)
        weight_type = None
        for key, value in zip(values[0::2], values[1::2]):
            key = HeaderKey(key)
            if key == HeaderKey.VERSION:
                h.version = value
            elif key == HeaderKey.ARCH_TYPE:
                h.arch = LlmArch(value)
            elif key == HeaderKey.DIM:
                h.dim = value
            elif key == HeaderKey.HIDDEN_DIM:
                h.hidden_dim = value
            elif key == HeaderKey.N_LAYERS:
                h.n_layers = value
            elif key == HeaderKey.N_HEADS:
                h.n_heads = value
            elif key == HeaderKey.N_KV_HEADS:
                h.n_kv_heads = value
            elif key == HeaderKey.N_EXPERTS:
                h.n_experts = value
            elif key == HeaderKey.N_ACTIVE_EXPERTS:
                h.n_active_experts = value
            elif key == HeaderKey.VOCAB_SIZE:
                h.vocab_size = value
            elif key == HeaderKey.SEQ_LEN:
                h.seq_len = value
            elif key == HeaderKey.HIDDEN_ACT:
                h.hidden_act = HiddenAct(value)
            elif key == HeaderKey.ROPE_THETA:
                h.rope_theta = float(value)
            elif key == HeaderKey.WEIGHT_FLOAT_TYPE:
                weight_type = FloatType(value)
            elif key == HeaderKey.ROPE_SCALING_FACTOR:
                h.rope_scaling_factor = float(value)
            elif key == HeaderKey.ROPE_SCALING_LOW_FREQ_FACTOR:
                h.rope_scaling_low_freq_factor = float(value)
            elif key == HeaderKey.ROPE_SCALING_HIGH_FREQ_FACTORY:
                h.rope_scaling_high_freq_factor = float(value)
            elif key == HeaderKey.ROPE_SCALING_ORIG_MAX_SEQ_LEN:
                h.rope_scaling_orig_max_seq_len = value
            elif key == HeaderKey.ROPE_TYPE:
                h.rope_type = RopeType(value)
            elif key == HeaderKey.HEAD_DIM:
                h.head_dim = value
            elif key == HeaderKey.NORM_EPSILON:
                h.norm_epsilon = _norm_epsilon(value)
            elif key == HeaderKey.MOE_HIDDEN_DIM:
                h.moe_hidden_dim = value
            elif key == HeaderKey.SLIDING_WINDOW:
                h.sliding_window = value
            elif key == HeaderKey.FULL_ATTN_PERIOD:
                h.full_attn_period = value
            elif key == HeaderKey.FULL_ATTN_NO_ROPE:
                h.full_attn_no_rope = bool(value)
            elif key == HeaderKey.N_DENSE_LAYERS:
                h.n_dense_layers = value
            elif key == HeaderKey.N_SHARED_EXPERTS:
                h.n_shared_experts = value
            elif key == HeaderKey.SCORE_FUNC:
                h.score_sigmoid = bool(value)
            elif key == HeaderKey.ROUTE_NORM:
                h.route_norm = bool(value)
            elif key == HeaderKey.ROUTE_SCALE_MILLI:
                h.route_scale = value / 1000.0 if value else 1.0
            elif key == HeaderKey.N_ROUTED_EXPERTS:
                h.n_routed_experts = value
            elif key == HeaderKey.FIRST_EXPERT:
                h.first_expert = value
            elif key == HeaderKey.EMBED_SCALE:
                h.embed_scale = bool(value)
            elif key == HeaderKey.Q_LORA_RANK:
                h.q_lora_rank = value
            elif key == HeaderKey.KV_LORA_RANK:
                h.kv_lora_rank = value
            elif key == HeaderKey.QK_NOPE_HEAD_DIM:
                h.qk_nope_head_dim = value
            elif key == HeaderKey.QK_ROPE_HEAD_DIM:
                h.qk_rope_head_dim = value
            elif key == HeaderKey.V_HEAD_DIM:
                h.v_head_dim = value
            elif key == HeaderKey.INDEX_N_HEADS:
                h.index_n_heads = value
            elif key == HeaderKey.INDEX_HEAD_DIM:
                h.index_head_dim = value
            elif key == HeaderKey.INDEX_TOPK:
                h.index_topk = value
            elif key == HeaderKey.N_GROUP:
                h.n_group = value or 1
            elif key == HeaderKey.TOPK_GROUP:
                h.topk_group = value or 1
            elif key == HeaderKey.ROPE_BETA_FAST:
                h.rope_beta_fast = float(value)
            elif key == HeaderKey.ROPE_BETA_SLOW:
                h.rope_beta_slow = float(value)
            elif key == HeaderKey.ROPE_MSCALE_MILLI:
                h.rope_mscale = value / 1000.0
            elif key == HeaderKey.ROPE_MSCALE_ALL_DIM_MILLI:
                h.rope_mscale_all_dim = value / 1000.0
            elif key == HeaderKey.CONV_L_CACHE:
                h.conv_l_cache = value
            elif key == HeaderKey.ATTN_LAYERS_LO:
                h.attn_layers |= value
            elif key == HeaderKey.ATTN_LAYERS_HI:
                h.attn_layers |= value << _ATTN_MASK_BITS
            elif key == HeaderKey.SSM_N_HEADS:
                h.ssm_n_heads = value
            elif key == HeaderKey.SSM_HEAD_DIM:
                h.ssm_head_dim = value
            elif key == HeaderKey.SSM_STATE_DIM:
                h.ssm_state_dim = value
            elif key == HeaderKey.SSM_N_GROUPS:
                h.ssm_n_groups = value or 1
            elif key == HeaderKey.SSM_CONV_TAPS:
                h.ssm_conv_taps = value
            elif key == HeaderKey.EMBED_MULTIPLIER_MILLI:
                h.embed_multiplier = value / 1e3
            elif key == HeaderKey.RESIDUAL_MULTIPLIER_NANO:
                h.residual_multiplier = value / 1e9 if value else 1.0
            elif key == HeaderKey.ATTENTION_MULTIPLIER_NANO:
                h.attention_multiplier = value / 1e9
            elif key == HeaderKey.LOGITS_SCALING_MILLI:
                h.logits_scaling = value / 1e3 if value else 1.0

        if weight_type is None:
            raise ValueError("model does not specify weight type")
        h.weight_type = weight_type
        h.header_bytes = header_size
        f.seek(0, 2)
        h.file_size = f.tell()

    h.orig_seq_len = h.seq_len
    if max_seq_len > 0 and h.seq_len > max_seq_len:
        h.seq_len = max_seq_len
    if h.latent:
        if not (h.q_lora_rank and h.qk_nope_head_dim and h.qk_rope_head_dim
                and h.v_head_dim):
            raise ValueError(
                "latent attention (kv_lora_rank > 0) needs q_lora_rank, "
                "qk_nope_head_dim, qk_rope_head_dim and v_head_dim"
            )
        # a query head is its nope and its rope columns
        h.head_dim = h.qk_nope_head_dim + h.qk_rope_head_dim
    if h.head_dim == 0:
        h.head_dim = h.dim // h.n_heads
    h.sync_type = sync_type
    if h.arch in (LlmArch.QWEN3, LlmArch.QWEN3_MOE, LlmArch.AFMOE, LlmArch.PANGU_MOE,
                  LlmArch.LFM2_MOE):
        h.rope_type = RopeType.FALCON
    if h.stateful:
        taps = h.ssm_conv_taps or h.conv_l_cache
        if taps < 2 or h.latent or h.sliding_window or (h.conv_l_cache and h.ssm_n_heads):
            raise ValueError(
                f"state layers with a convolution of {taps} taps beside latent or "
                "window attention, or of two kinds: conv_l_cache >= 2 or "
                "ssm_conv_taps >= 2, one kind of state layer, and full attention alone"
            )
        if h.ssm_n_heads and not (
            h.ssm_head_dim and h.ssm_state_dim and h.ssm_n_groups == 1
        ):
            raise ValueError(
                f"Mamba-2 layers of {h.ssm_n_heads} heads need ssm_head_dim and "
                f"ssm_state_dim, and one group that shares B and C "
                f"(ssm_n_groups {h.ssm_n_groups})"
            )
        if h.attn_layers in (0, (1 << h.n_layers) - 1):
            raise ValueError(
                f"attention layers {h.attn_layers:#x} of {h.n_layers} layers: a model "
                "with state layers has layers of both kinds (conv_l_cache and "
                "ssm_n_heads 0: every layer attends)"
            )
        if h.n_layers > 2 * _ATTN_MASK_BITS or h.attn_layers >> h.n_layers:
            raise ValueError(
                f"attention layers {h.attn_layers:#x} of {h.n_layers} layers: the "
                f"mask names layers below {min(h.n_layers, 2 * _ATTN_MASK_BITS)}"
            )
    if h.indexed and not (h.latent and h.index_n_heads and h.index_head_dim >= h.rope_dim):
        raise ValueError(
            "an index (index_topk > 0) needs latent attention, index_n_heads "
            "and an index_head_dim of at least the rope columns"
        )
    if h.n_routed_experts == 0:
        h.n_routed_experts = h.n_experts
    if not (1 <= h.topk_group <= h.n_group
            and (h.n_routed_experts or h.n_group) % h.n_group == 0):
        raise ValueError(
            f"{h.topk_group} of {h.n_group} groups over {h.n_routed_experts} routed experts"
        )
    if not 0 <= h.first_expert <= h.n_routed_experts - h.n_experts:
        raise ValueError(
            f"experts [{h.first_expert}, {h.first_expert + h.n_experts}) held "
            f"of {h.n_routed_experts} routed over"
        )
    return h


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """One row of the layer table: what a layer is, as data. `row` is the
    layer's place in its cache stack (the full layers', the window layers'
    or the latent layers'; a convolution layer's in the state stack, where
    it keeps a state a lane and no cache row), `ffn_row` its place among the
    layers of its FFN kind, whose weights are stacked apart."""

    window: bool  # attention: over the last `sliding_window` rows, or in full
    rope: bool  # the layer's whole heads take the rotary embedding
    experts: bool  # FFN: a mixture of experts, or dense
    row: int
    ffn_row: int
    latent: bool = False  # the cache row is `[c | k_rope]`, one head for all
    conv: bool = False  # a gated short convolution stands where attention would
    ssm: bool = False  # a Mamba-2 mixer does

    @property
    def keeps_state(self) -> bool:
        """The layer keeps a state a lane and writes no cache row."""
        return self.conv or self.ssm

    @property
    def cache(self) -> str:
        """The kind of cache the layer's rows live in; `state`: none, the
        layer keeps a state a lane."""
        if self.keeps_state:
            return "state"
        return "latent" if self.latent else "window" if self.window else "full"


def layer_table(h: LlmHeader) -> tuple[LayerKind, ...]:
    """The kinds of the model's layers, read once from the header. The
    reference's architectures are its uniform rows: every layer full, with
    rope, and the same FFN."""
    table, ffn_rows = [], [0, 0]
    rows = {"full": 0, "window": 0, "latent": 0, "state": 0}
    for l in range(h.n_layers):
        window = not h.latent and h.sliding_window > 0 and not (
            h.full_attn_period and (l + 1) % h.full_attn_period == 0
        )
        experts = h.n_experts > 0 and l >= h.n_dense_layers
        state = h.stateful and not h.attn_layers >> l & 1
        cache = "state" if state else "latent" if h.latent else "window" if window else "full"
        table.append(LayerKind(
            # a latent layer turns the rope columns of its heads itself
            window,
            not state and not h.latent and (window or not h.full_attn_no_rope),
            experts, rows[cache], ffn_rows[experts], h.latent,
            state and not h.ssm_n_heads, state and h.ssm_n_heads > 0,
        ))
        rows[cache] += 1
        ffn_rows[experts] += 1
    return tuple(table)


@dataclasses.dataclass(frozen=True)
class TensorSpec:
    """One tensor's location inside the `.m` file."""

    name: str
    float_type: FloatType
    shape: tuple[int, ...]  # row-major, HF convention: (out_features, in_features)
    offset: int  # absolute byte offset in the file
    nbytes: int

    @property
    def n_elements(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


def tensor_plan(h: LlmHeader) -> list[TensorSpec]:
    """The fixed tensor order of a `.m` file.

    Mirrors converter/convert-hf.py:59-104 (writer side) and
    src/llm.cpp:614-669 (reader side). Shapes are (out, in) row-major as
    exported from HF safetensors.
    """
    specs: list[TensorSpec] = []
    offset = h.header_bytes  # header_bytes counts magic+headerSize+kv data
    wt = h.weight_type

    def add(name: str, ft: FloatType, shape: tuple[int, ...]) -> None:
        nonlocal offset
        n = 1
        for s in shape:
            n *= s
        nbytes = tensor_bytes(ft, n)
        specs.append(TensorSpec(name, ft, shape, offset, nbytes))
        offset += nbytes

    afmoe = h.arch == LlmArch.AFMOE
    sandwich = h.arch in _SANDWICH

    def swiglu(prefix: str, width: int) -> None:
        add(f"{prefix}.w1", wt, (width, h.dim))
        add(f"{prefix}.w2", wt, (h.dim, width))
        add(f"{prefix}.w3", wt, (width, h.dim))

    add("embed", FloatType.F32, (h.vocab_size, h.dim))
    for l, kind in enumerate(layer_table(h)):
        if kind.latent:
            # queries through a latent of q_lora_rank, keys and values
            # through the cached one: `wkv_a` gives `[c | k_rope]`, `wkv_b`
            # a head's `[k_nope | v]` from `c`
            per_head = h.qk_nope_head_dim + h.v_head_dim
            add(f"layers.{l}.wq_a", wt, (h.q_lora_rank, h.dim))
            add(f"layers.{l}.q_a_norm", FloatType.F32, (h.q_lora_rank,))
            add(f"layers.{l}.wq_b", wt, (h.q_dim, h.q_lora_rank))
            add(f"layers.{l}.wkv_a", wt, (h.latent_row, h.dim))
            add(f"layers.{l}.kv_a_norm", FloatType.F32, (h.kv_lora_rank,))
            add(f"layers.{l}.wkv_b", wt, (h.n_heads * per_head, h.kv_lora_rank))
            add(f"layers.{l}.wo", wt, (h.dim, h.n_heads * h.v_head_dim))
            if h.indexed:
                # the index: its queries from the query's latent, one key a
                # position from the layer's input (LayerNorm: weight and
                # bias), and a weight a head from the same input
                add(f"layers.{l}.idx_wq_b", wt,
                    (h.index_n_heads * h.index_head_dim, h.q_lora_rank))
                add(f"layers.{l}.idx_wk", wt, (h.index_head_dim, h.dim))
                add(f"layers.{l}.idx_k_norm", FloatType.F32, (h.index_head_dim,))
                add(f"layers.{l}.idx_k_bias", FloatType.F32, (h.index_head_dim,))
                add(f"layers.{l}.idx_w", FloatType.F32, (h.index_n_heads, h.dim))
        elif kind.conv:
            # `[B | C | x]` from the layer's input, the depthwise taps (the
            # last meets the newest row), and the projection back
            add(f"layers.{l}.conv_in", wt, (3 * h.dim, h.dim))
            add(f"layers.{l}.conv_w", FloatType.F32, (h.dim, h.conv_l_cache))
            add(f"layers.{l}.conv_out", wt, (h.dim, h.dim))
        elif kind.ssm:
            # `in_proj`'s rows `[z | x B C | dt]` as three tensors one behind
            # the other (the bytes of one [inner + conv_dim + heads, dim]
            # tensor), the depthwise taps (the last meets the newest row) and
            # their bias, a head's step bias, log decay rate and skip, the
            # gains of the norm behind the gate, and the projection back
            add(f"layers.{l}.ssm_in_z", wt, (h.ssm_inner, h.dim))
            add(f"layers.{l}.ssm_in_xbc", wt, (h.ssm_conv_dim, h.dim))
            add(f"layers.{l}.ssm_in_dt", wt, (h.ssm_n_heads, h.dim))
            add(f"layers.{l}.ssm_conv_w", FloatType.F32, (h.ssm_conv_dim, h.ssm_conv_taps))
            add(f"layers.{l}.ssm_conv_b", FloatType.F32, (h.ssm_conv_dim,))
            add(f"layers.{l}.ssm_dt_bias", FloatType.F32, (h.ssm_n_heads,))
            add(f"layers.{l}.ssm_a_log", FloatType.F32, (h.ssm_n_heads,))
            add(f"layers.{l}.ssm_d", FloatType.F32, (h.ssm_n_heads,))
            add(f"layers.{l}.ssm_norm", FloatType.F32, (h.ssm_inner,))
            add(f"layers.{l}.ssm_out", wt, (h.dim, h.ssm_inner))
        else:
            add(f"layers.{l}.q", wt, (h.q_dim, h.dim))
            add(f"layers.{l}.k", wt, (h.kv_dim, h.dim))
            add(f"layers.{l}.v", wt, (h.kv_dim, h.dim))
            add(f"layers.{l}.wo", wt, (h.dim, h.q_dim))
        if afmoe:  # the gate on the attention output, one value a channel
            add(f"layers.{l}.att_gate", wt, (h.q_dim, h.dim))
        if kind.experts:
            add(f"layers.{l}.moe_gate", FloatType.F32, (h.n_routed_experts, h.dim))
            if h.arch in _EXPERT_BIAS:
                add(f"layers.{l}.expert_bias", FloatType.F32, (h.n_routed_experts,))
            if h.n_shared_experts:
                swiglu(f"layers.{l}.shared", h.n_shared_experts * h.ff_dim)
            for e in range(h.n_experts):
                swiglu(f"layers.{l}.experts.{e}", h.ff_dim)
        else:  # leading dense layers are HIDDEN_DIM wide beside experts of ff_dim
            swiglu(f"layers.{l}", h.hidden_dim if h.arch in _WIDE_DENSE else h.ff_dim)
        if h.arch in _QK_NORM and not kind.keeps_state:
            add(f"layers.{l}.q_norm", FloatType.F32, (h.head_dim,))
            add(f"layers.{l}.k_norm", FloatType.F32, (h.head_dim,))
        add(f"layers.{l}.att_norm", FloatType.F32, (h.dim,))
        if sandwich:  # one more norm after each block
            add(f"layers.{l}.post_att_norm", FloatType.F32, (h.dim,))
        add(f"layers.{l}.ffn_norm", FloatType.F32, (h.dim,))
        if sandwich:
            add(f"layers.{l}.post_ffn_norm", FloatType.F32, (h.dim,))
    add("final_norm", FloatType.F32, (h.dim,))
    add("wcls", wt, (h.vocab_size, h.dim))
    return specs


class ModelReader:
    """Lazy reader over a `.m` file's tensor section.

    Uses a read-only memmap (TPU-native analogue of the reference's
    mmap + slice-by-slice streaming weight loader, src/mmap.hpp +
    src/llm.cpp:614-669): tensors are materialized one at a time, so peak
    host memory stays at one tensor regardless of model size.
    """

    def __init__(self, path: str, max_seq_len: int = 0):
        self.path = path
        self.header = read_llm_header(path, max_seq_len=max_seq_len)
        self.specs = tensor_plan(self.header)
        self.by_name = {s.name: s for s in self.specs}
        expected_end = self.specs[-1].offset + self.specs[-1].nbytes
        if expected_end != self.header.file_size:
            raise ValueError(
                f"model file size mismatch: expected {expected_end} bytes, "
                f"file has {self.header.file_size} (wrong arch/config?)"
            )
        self._mmap = np.memmap(path, dtype=np.uint8, mode="r")

    def raw(self, name: str) -> np.ndarray:
        """Zero-copy packed bytes of a tensor."""
        s = self.by_name[name]
        return self._mmap[s.offset : s.offset + s.nbytes]

    def dense_f32(self, name: str) -> np.ndarray:
        """Tensor dequantized to f32, in its file shape."""
        s = self.by_name[name]
        raw = self.raw(name)
        if s.float_type == FloatType.F32:
            out = raw.view(np.float32).copy()
        elif s.float_type == FloatType.F16:
            out = raw.view(np.float16).astype(np.float32)
        elif s.float_type == FloatType.Q40:
            out = dequantize_q40(raw, s.n_elements)
        elif s.float_type == FloatType.Q80:
            out = dequantize_q80(raw, s.n_elements)
        else:
            raise ValueError(f"unsupported float type: {s.float_type}")
        return out.reshape(s.shape)

    def planar_q40_range(
        self, name: str, o0: int, o1: int, b0: int = 0, b1: int | None = None
    ) -> tuple[np.ndarray, np.ndarray]:
        """Planar unpack of a rectangular Q40 sub-range: file rows
        [o0, o1) (the out axis) x 32-element blocks [b0, b1) of each row.

        Copies only the covered bytes out of the memmap — the unit of the
        STREAMING loader (models/loader), which pulls exactly one device
        shard's bytes at a time instead of materializing whole layer
        stacks on host (the TPU-native analogue of the reference's
        slice-by-slice socket streaming, src/llm.cpp:614-669). 2-D
        tensors only. Returns (q int8 [o1-o0, (b1-b0)*32],
        d f16 [o1-o0, b1-b0])."""
        from .quants import Q40_BLOCK_BYTES

        s = self.by_name[name]
        if s.float_type != FloatType.Q40 or len(s.shape) != 2:
            raise ValueError(f"{name}: ranged read needs a 2-D Q40 tensor")
        out, inner = s.shape
        nb = inner // 32
        if b1 is None:
            b1 = nb
        if not (0 <= o0 <= o1 <= out and 0 <= b0 <= b1 <= nb):
            raise ValueError(
                f"{name}: range rows [{o0},{o1}) blocks [{b0},{b1}) "
                f"outside ({out}, {nb})"
            )
        raw = self.raw(name).reshape(out, nb, Q40_BLOCK_BYTES)
        sub = np.ascontiguousarray(raw[o0:o1, b0:b1])
        q, d = q40_to_planar(sub.reshape(-1), (o1 - o0) * (b1 - b0) * 32)
        return q.reshape(o1 - o0, (b1 - b0) * 32), d.reshape(o1 - o0, b1 - b0)

    def planar_q40(self, name: str) -> tuple[np.ndarray, np.ndarray]:
        """Tensor as planar int8 values [out, in] + f16 scales [out, in//32].

        This is the device layout for the Pallas quantized matmul path.
        """
        s = self.by_name[name]
        if s.float_type != FloatType.Q40:
            raise ValueError(f"{name} is {s.float_type}, not Q40")
        q, d = q40_to_planar(self.raw(name), s.n_elements)
        out, inner = s.shape[-2], s.shape[-1]
        lead = s.shape[:-2]
        return (
            q.reshape(*lead, out, inner),
            d.reshape(*lead, out, inner // 32),
        )

    def __iter__(self) -> Iterator[TensorSpec]:
        return iter(self.specs)
