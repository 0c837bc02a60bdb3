"""Model configuration — the `.m` header schema as a dataclass.

Key ids and semantics mirror the reference header kv-list (llm.hpp:8-28,
llm.cpp:26-98) for drop-in model-file compatibility: same magic, same keys,
same int-valued floats, same derived quantities (head_size, kv_dim), and the
same `--max-seq-len` clamping rule (llm.cpp:89-91).
"""

from __future__ import annotations

import dataclasses
from enum import IntEnum

from dllama_tpu.ops.power import state_dims
from dllama_tpu.ops.quant import FloatType

MODEL_MAGIC = 0x0A00ABCD  # llm.cpp:46-48 (magic 0xA00ABCD)


class ArchType(IntEnum):
    LLAMA = 0xABCD00
    # decoder whose layers are of two kinds under one residual/MLP skeleton:
    # softmax attention or a Mamba-2 state-space mixer, by the header's
    # per-layer kind keys (a GraniteMoeHybrid-style stack; source:
    # huggingface.co/ibm-granite/granite-4.0-h-micro config.json)
    HYBRID_SSM = 0xABCD10


class LayerKind(IntEnum):
    ATTENTION = 0
    SSM = 1
    KDA = 2  # gated delta-rule linear attention, a decay per key channel:
    # a [key, value] matrix state a head (ops/delta.py)
    MLA = 3  # latent attention: one shared low-rank row a token in the cache
    RETENTION = 4  # power retention: gated linear attention over the symmetric
    # square of rotated keys, a [value + 1, expanded key] state a kv head that
    # its query heads share (ops/power.py)


#: the kinds whose layers hold per-sequence recurrent state (at most one of
#: them in a model) and those that hold cache rows (likewise)
STATE_KINDS = (LayerKind.SSM, LayerKind.KDA, LayerKind.RETENTION)


class HiddenAct(IntEnum):
    GELU = 0
    SILU = 1
    RELU = 2  # relu(w1 x) * w3 x: the "sparse ReGLU" expert


class RopeType(IntEnum):
    LLAMA = 0
    FALCON = 1  # present in the reference enum order (nn-core.hpp), unused
    LLAMA3_1 = 2
    NONE = 3  # no positional rotation at all: q and k are used as projected
    YARN = 4  # YaRN (Peng et al. 2023) as the public `transformers` library's
    # `yarn` rope type computes it; a `RopeSpec`'s type only (the global
    # layers' second table, or a latent model's one: `LlamaConfig.rope_spec`)


class HeaderKey(IntEnum):
    """llm.hpp:8-28."""

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
    # dllama-tpu extension (not in the reference schema, which hardcodes
    # normEpsilon=1e-5, llm.cpp:33): written only when eps != 1e-5, value is
    # eps * 1e12 as an int. Reference binaries reject files carrying it.
    NORM_EPSILON_X1E12 = 100
    # ---- dllama-tpu extensions for ArchType.HYBRID_SSM (floats int-coded
    # like NORM_EPSILON_X1E12; a key that is absent keeps the LLAMA meaning)
    HEAD_SIZE = 101  # explicit attention head size (n_heads * it == dim)
    ATTN_SCALE_X1E6 = 102  # score scale; absent = 1/sqrt(head_size)
    EMBEDDING_MULT_X1E6 = 103  # h0 = mult * E[token]
    RESIDUAL_MULT_X1E6 = 104  # h += mult * block(norm(h)), both adds
    LOGITS_DIV_X1E6 = 105  # logits = head(h) / div
    TIED_HEAD = 106  # 1 = wcls on disk is the Q40 of the embedding
    SSM_HEADS = 110
    SSM_HEAD_DIM = 111
    SSM_STATE = 112
    SSM_GROUPS = 113
    SSM_CONV = 114  # conv taps
    SSM_CHUNK = 115  # rows a block of the chunked scan holds
    # ---- dllama-tpu extensions for attention layers of more than one kind
    # and for where the expert router reads (absent = today's meaning; with
    # HEAD_SIZE present in a LLAMA file, n_heads * head size need not be dim)
    WINDOW_SIZE = 120  # rows a windowed layer's query sees, itself included
    ROUTER_INPUT = 121  # 1 = the router reads the ATTENTION norm's output
    # ---- dllama-tpu extensions for LayerKind.KDA layers
    KDA_HEADS = 130
    KDA_HEAD_DIM = 131  # key and value size of a head
    KDA_CONV = 132  # conv taps over q, k and v
    KDA_RANK = 133  # inner size of the decay's and the output gate's
    # two-matrix projections
    # ---- dllama-tpu extensions for LayerKind.MLA layers. The shared key
    # dims ride unrotated where ROPE_TYPE is NONE; else q_pe a head and the
    # one k_pe a token are rotated over ALL the MLA_PE_DIM dims before the
    # row is written: by the plain table of ROPE_THETA, or by the GLOBAL_ROPE
    # keys' table where they are present (the layers all see the whole
    # context), and ATTN_SCALE_X1E6 carries a score scale such as YaRN's
    MLA_KV_RANK = 140  # the latent c a token leaves in the cache
    MLA_NOPE_DIM = 141  # a head's key dims that come out of the latent
    MLA_PE_DIM = 142  # a head's key dims shared by all heads, beside c
    MLA_V_DIM = 143
    MLA_Q_RANK = 144  # q = W_qb rmsnorm(W_qa h; g_q) through this inner size
    # (tensors mla_qa, mla_q_norm, mla_qb); absent = one matrix mla_q
    # ---- dllama-tpu extensions for the expert layers (absent = softmax
    # over the top k, no shared expert, every expert held, expert width =
    # HIDDEN_DIM)
    ROUTER_KIND = 150  # 1 = sigmoid scores, top k of score + bias,
    # weights renormalised over the chosen scores
    ROUTED_SCALE_X1E6 = 151  # the routed sum is multiplied by it
    N_SHARED_EXPERTS = 152  # always-on experts, one SwiGLU of their width
    EXPERTS_HELD = 153  # this file holds experts [offset, offset + held)
    EXPERT_OFFSET = 154
    MOE_HIDDEN_DIM = 155  # an expert's width where dense layers differ
    N_EXPERT_GROUPS = 156  # the experts routed among lie in this many
    # contiguous groups of equal size; a token's experts are the top k among
    # the EXPERT_GROUPS_KEPT groups whose two best scores sum highest
    # (absent or 1 = the plain top k)
    EXPERT_GROUPS_KEPT = 157
    # ---- dllama-tpu extensions for attention whose shape goes by the layer's
    # KIND, windowed or global (absent = one head count, one rope table, no
    # norm over the head, no gate)
    WINDOW_HEADS = 160  # query heads of a WINDOWED layer (N_HEADS: the
    # others'); present = the windowed layers' attention tensors are stacked
    # apart from the global layers' (`*_win`)
    QK_NORM = 161  # 1 = q and k are RMS-normed over the head before the
    # rotation, one gain vector each a layer, shared by its heads
    ATTN_GATE = 162  # 1 = a head's output is multiplied by softplus(n W_g)
    # (one gate a head, read from the attention block's normed input)
    # the GLOBAL layers' own rope (absent = they rotate as ROPE_TYPE says,
    # like every other layer): a `RopeSpec`, floats int-coded x 1e6
    GLOBAL_ROPE_TYPE = 170
    GLOBAL_ROPE_THETA = 171
    GLOBAL_ROPE_SHARE_X1E6 = 172  # the leading share of a head that rotates
    GLOBAL_ROPE_FACTOR_X1E6 = 173
    GLOBAL_ROPE_ORIG_LEN = 174
    GLOBAL_ROPE_BETA_FAST_X1E6 = 175
    GLOBAL_ROPE_BETA_SLOW_X1E6 = 176
    GLOBAL_ROPE_ATTN_FACTOR_X1E6 = 177  # cos and sin are multiplied by it
    # ---- dllama-tpu extensions for LayerKind.RETENTION layers (their q, k,
    # v, o and head norms are the attention tensors; N_HEADS query heads read
    # N_KV_HEADS states)
    RET_DEGREE = 180  # the power p of (q . k)^p; 2 is what is computed
    RET_GATE = 181  # 1 = the state decays by sigmoid(x W_g + b_g), one gate
    # a kv head and row (tensors ret_gate, ret_gate_bias)


#: the kind of layer i is header key LAYER_KIND_BASE + i (one key a layer,
#: not a period and not a name), value a LayerKind
LAYER_KIND_BASE = 1000
#: layer i is windowed iff key LAYER_WINDOW_BASE + i is 1, and rotates q and k
#: iff key LAYER_ROPE_BASE + i is 1 (absent lists: no layer is windowed, every
#: layer rotates as ROPE_TYPE says)
LAYER_WINDOW_BASE = 2000
LAYER_ROPE_BASE = 3000
#: layer i of a file with experts has a DENSE feed-forward block (width
#: HIDDEN_DIM) iff key LAYER_FFN_BASE + i is 1 (absent list: every layer of
#: such a file is an expert layer)
LAYER_FFN_BASE = 4000
_LAYER_LISTS_END = 5000

#: `LlamaConfig.schedule_kinds` entries: a LayerKind in the low three bits,
#: and beside it whether the layer is windowed and whether it leaves q and k
#: unrotated where the model rotates
SCHEDULE_KIND_MASK = 7
SCHEDULE_WINDOWED = 8
SCHEDULE_UNROTATED = 16
SCHEDULE_DENSE_FFN = 32  # a dense feed-forward block in a model with experts


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """A rope table of its own for one kind of attention layer
    (ops/layers.rope_table builds it): plain (`RopeType.LLAMA`) or YaRN, over
    the leading `share` of a head; the dims behind it pass through."""

    type: RopeType = RopeType.LLAMA
    theta: float = 10000.0
    share: float = 1.0
    factor: float = 1.0  # YaRN: positions are interpolated by it
    orig_len: int = 0  # YaRN: the context the model was trained at
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attn_factor: float = 1.0

    def describe(self) -> str:
        yarn = (f" x{self.factor:g} from {self.orig_len} beta "
                f"{self.beta_fast:g}/{self.beta_slow:g} attn "
                f"{self.attn_factor:.4f}" if self.type == RopeType.YARN else "")
        return f"{self.type.name} theta={self.theta:g} share={self.share:g}{yarn}"


@dataclasses.dataclass
class LlamaConfig:
    dim: int
    hidden_dim: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    vocab_size: int
    seq_len: int
    version: int = 0
    arch: ArchType = ArchType.LLAMA
    n_experts: int = 0
    n_active_experts: int = 0
    hidden_act: HiddenAct = HiddenAct.SILU
    rope_theta: float = 10000.0
    rope_type: RopeType = RopeType.LLAMA
    rope_scaling_factor: float = 1.0
    rope_scaling_low_freq_factor: float = 0.0
    rope_scaling_high_freq_factor: float = 0.0
    rope_scaling_orig_max_seq_len: int = 0
    norm_epsilon: float = 1e-5
    weight_type: FloatType = FloatType.Q40
    orig_seq_len: int = 0  # pre-clamp seq len from the file
    # ---- HYBRID_SSM (defaults are the LLAMA meaning)
    head_dim: int = 0  # explicit head size; 0 = dim // n_heads
    attn_scale: float = 0.0  # 0 = 1/sqrt(head_size)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    tied_head: bool = False
    layer_kinds: tuple = ()  # LayerKind per layer; () = all attention
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # ---- attention layers of two kinds (defaults are the LLAMA meaning)
    window: int = 0  # rows a windowed layer's query sees (itself included)
    layer_windows: tuple = ()  # 0/1 per layer; () = no layer is windowed
    layer_ropes: tuple = ()  # 0/1 per layer; () = every layer rotates
    router_pre_attention: bool = False  # the router reads the attention
    # block's normed input, not the feed-forward block's
    # ---- delta-rule linear-attention layers (LayerKind.KDA)
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_rank: int = 0
    # ---- latent attention layers (LayerKind.MLA)
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_pe_dim: int = 0
    v_head_dim: int = 0
    q_lora_rank: int = 0  # 0 = q is one projection
    # ---- expert layers (defaults: softmax over the top k, all held)
    router_sigmoid: bool = False
    routed_scale: float = 1.0
    n_shared_experts: int = 0
    experts_held: int = 0  # 0 = all n_experts; else experts
    # [expert_offset, expert_offset + experts_held) are in the file: one
    # chip's share of a layer, routed over all n_experts
    expert_offset: int = 0
    moe_hidden_dim: int = 0  # 0 = hidden_dim
    n_expert_groups: int = 0  # 0 or 1 = the plain top k over all the experts
    expert_groups_kept: int = 0
    layer_ffn: tuple = ()  # 0/1 per layer, 1 = dense; () = none is dense
    # ---- attention by layer kind (defaults: one kind of attention layer)
    window_heads: int = 0  # query heads of a windowed layer; 0 = n_heads and
    # one stack of attention tensors for every layer
    qk_norm: bool = False
    attn_gate: bool = False
    global_rope: RopeSpec | None = None  # the global (not windowed) layers'
    # own rope table; None = they rotate as the others
    # ---- power retention layers (LayerKind.RETENTION)
    ret_degree: int = 0
    ret_gate: bool = False

    def __post_init__(self):
        if self.orig_seq_len == 0:
            self.orig_seq_len = self.seq_len
        self.layer_kinds = tuple(int(k) for k in self.layer_kinds)
        if self.layer_kinds and len(self.layer_kinds) != self.n_layers:
            raise ValueError(
                f"{len(self.layer_kinds)} layer kinds for {self.n_layers} layers")
        self.layer_windows = tuple(int(bool(w)) for w in self.layer_windows)
        self.layer_ropes = tuple(int(bool(r)) for r in self.layer_ropes)
        self.layer_ffn = tuple(int(bool(f)) for f in self.layer_ffn)
        for name, flags in (("window", self.layer_windows),
                            ("rope", self.layer_ropes),
                            ("feed-forward", self.layer_ffn)):
            if flags and len(flags) != self.n_layers:
                raise ValueError(
                    f"{len(flags)} {name} flags for {self.n_layers} layers")
        if any(self.layer_windows) and self.window <= 0:
            raise ValueError("windowed layers need a window size")
        if any(w and k != LayerKind.ATTENTION for w, k in
               zip(self.layer_windows, self.layer_kinds)):
            raise ValueError("only a softmax attention layer can be windowed")
        kinds = set(self.layer_kinds)
        if len(kinds & set(STATE_KINDS)) > 1:
            raise ValueError("recurrent layers of two kinds in one model "
                             "are not supported (one recurrent state a slot)")
        if LayerKind.RETENTION in kinds and not (
                self.ret_degree == 2 and self.ret_gate
                and self.head_size % 2 == 0
                and self.n_heads % self.n_kv_heads == 0):
            raise ValueError("power retention layers need RET_DEGREE 2 (the "
                             "symmetric square is what is computed), RET_GATE "
                             "1, an even head size and a whole number of "
                             "query heads a kv head")
        if {int(LayerKind.RETENTION), int(LayerKind.ATTENTION)} <= kinds:
            raise ValueError("power retention and softmax attention layers in "
                             "one model are not supported (they hold the same "
                             "tensors, stacked by name)")
        if {int(LayerKind.ATTENTION), int(LayerKind.MLA)} <= kinds:
            raise ValueError("softmax and latent attention layers in one "
                             "model are not supported (one row width a cache)")
        if LayerKind.MLA in kinds and not self.kv_lora_rank:
            raise ValueError("latent attention layers need MLA_KV_RANK")
        if LayerKind.MLA in kinds and self.rope_type not in (
                RopeType.NONE, RopeType.LLAMA):
            raise ValueError("latent attention layers rotate by the plain "
                             "table or by a GLOBAL_ROPE table of their own "
                             f"(ROPE_TYPE {self.rope_type.name} is not "
                             "supported)")
        if LayerKind.MLA in kinds and self.rope_type != RopeType.NONE and (
                self.qk_pe_dim <= 0 or self.qk_pe_dim % 2):
            raise ValueError("rotated latent attention needs an even number "
                             "of shared key dims (MLA_PE_DIM)")
        if self.q_lora_rank and LayerKind.MLA not in kinds:
            raise ValueError("MLA_Q_RANK is for latent attention layers")
        if LayerKind.KDA in kinds and not (self.kda_heads and self.kda_rank):
            raise ValueError("delta-rule layers need KDA_HEADS and KDA_RANK")
        if self.window_heads and (
                not any(self.layer_windows) or LayerKind.MLA in kinds
                or self.window_heads % self.n_kv_heads):
            raise ValueError("WINDOW_HEADS is for softmax attention with "
                             "windowed layers, a whole number of query heads "
                             "a kv head")
        if self.global_rope is not None and (
                self.rope_type == RopeType.NONE
                or not 0 < self.global_rope.share <= 1
                or int(self.rope_dims * self.global_rope.share) % 2):
            raise ValueError("a rope table of the global layers' own needs a "
                             "model that rotates and an even number of "
                             "rotated dims")
        if any(self.layer_ffn) and not self.n_experts:
            raise ValueError("per-layer feed-forward kinds are for a model "
                             "with experts")
        if self.experts_held and not (
                0 <= self.expert_offset
                and self.expert_offset + self.experts_held <= self.n_experts):
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset} + "
                f"{self.experts_held}) are not among {self.n_experts}")
        if self.n_expert_groups > 1 and not self.router_sigmoid:
            raise ValueError("expert groups are for the sigmoid router")
        if self.n_expert_groups > 1 and not (
                self.n_experts % self.n_expert_groups == 0
                and 0 < self.expert_groups_kept <= self.n_expert_groups
                and self.n_active_experts <= self.expert_groups_kept
                * (self.n_experts // self.n_expert_groups)
                and self.n_experts // self.n_expert_groups >= 2):
            raise ValueError(
                f"{self.n_experts} experts do not lie in {self.n_expert_groups}"
                f" equal groups of two or more of which "
                f"{self.expert_groups_kept} hold {self.n_active_experts}")
        if self.n_ssm_layers and self.ssm_groups != 1:
            raise ValueError("state-space layers with more than one B/C group "
                             "are not supported")

    @property
    def head_size(self) -> int:
        return self.head_dim or self.dim // self.n_heads

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_size

    @property
    def rope_dims(self) -> int:
        """The dims a rope table spans: the head, or a latent model's shared
        key dims (q_pe a head, the one k_pe a token)."""
        return self.qk_pe_dim if self.latent else self.head_size

    @property
    def rope_spec(self) -> RopeSpec | None:
        """The model's ONE rope table where the header gives it as a
        `RopeSpec`: a latent model's layers all see the whole context, so the
        GLOBAL_ROPE keys describe its only table and ROPE_THETA's plain table
        is not built. None: the table comes from ROPE_TYPE / ROPE_THETA, and
        `global_rope`, where present, is the global layers' SECOND table."""
        return self.global_rope if self.latent else None

    @property
    def grouped_routing(self) -> bool:
        return self.n_expert_groups > 1

    @property
    def attn_dim(self) -> int:
        """Columns of wq and rows of wo: heads x head size (the model's dim
        unless the header gives a head size of its own)."""
        return self.n_heads * self.head_size

    def heads_of(self, windowed: bool) -> int:
        """Query heads of a layer of that kind (`n_heads`, `attn_dim` and
        `q_per_kv` are the global kind's, and every layer's where the header
        gives one head count)."""
        return self.window_heads if windowed and self.window_heads else self.n_heads

    def attn_dim_of(self, windowed: bool) -> int:
        return self.heads_of(windowed) * self.head_size

    def q_per_kv_of(self, windowed: bool) -> int:
        return self.heads_of(windowed) // self.n_kv_heads

    def attn_suffix(self, windowed: bool) -> str:
        """What a windowed layer's attention tensors are named by where the
        kinds are stacked apart: `wq_win`, ... beside the global `wq`."""
        return "_win" if windowed and self.window_heads else ""

    def kind_index(self, layer: int) -> int:
        """Layer `layer`'s index among the cache-holding layers of ITS kind,
        windowed or global: into a pool a kind, and into the attention
        stacks where they are stacked apart."""
        kinds = self.layer_kinds or (int(LayerKind.ATTENTION),) * self.n_layers
        mine = bool(self.layer_window(layer))
        return sum(1 for i in range(layer)
                   if kinds[i] not in STATE_KINDS
                   and bool(self.layer_window(i)) == mine)

    @property
    def n_window_layers(self) -> int:
        return sum(self.layer_windows)

    def layer_window(self, layer: int) -> int:
        """Rows layer `layer`'s queries see, 0 = the whole context."""
        return self.window if self.layer_windows and self.layer_windows[layer] else 0

    def layer_rotates(self, layer: int) -> bool:
        if self.rope_type == RopeType.NONE:
            return False
        return bool(self.layer_ropes[layer]) if self.layer_ropes else True

    @property
    def schedule_kinds(self) -> tuple:
        """What `models/llama.layer_schedule` groups by: a layer's kind, and
        beside it SCHEDULE_WINDOWED, SCHEDULE_UNROTATED and
        SCHEDULE_DENSE_FFN. () for a homogeneous stack."""
        if not (self.layer_kinds or self.layer_windows or self.layer_ropes
                or self.layer_ffn):
            return ()
        kinds = self.layer_kinds or (int(LayerKind.ATTENTION),) * self.n_layers
        return tuple(
            int(k) + (SCHEDULE_WINDOWED if self.layer_window(i) else 0)
            + (SCHEDULE_UNROTATED if k == LayerKind.ATTENTION
               and self.layer_ropes and not self.layer_ropes[i] else 0)
            + (SCHEDULE_DENSE_FFN if self.layer_ffn and self.layer_ffn[i] else 0)
            for i, k in enumerate(kinds))

    @property
    def n_ssm_layers(self) -> int:
        return sum(1 for k in self.layer_kinds if k == LayerKind.SSM)

    @property
    def n_kda_layers(self) -> int:
        return sum(1 for k in self.layer_kinds if k == LayerKind.KDA)

    @property
    def n_retention_layers(self) -> int:
        return sum(1 for k in self.layer_kinds if k == LayerKind.RETENTION)

    @property
    def n_state_layers(self) -> int:
        """Layers that hold recurrent state: the state's layer axis."""
        return (self.n_ssm_layers + self.n_kda_layers
                + self.n_retention_layers)

    @property
    def state_kind(self) -> str:
        """What the routes, `/health` and the launch record call the
        recurrent layers' kind ('' where the model has none)."""
        return ("retention" if self.n_retention_layers else
                "kda" if self.n_kda_layers else
                "ssm" if self.n_ssm_layers else "")

    @property
    def n_attn_layers(self) -> int:
        """Layers that hold KV rows: what a cache's layer axis is sized by."""
        return self.n_layers - self.n_state_layers

    @property
    def recurrent(self) -> bool:
        """The model carries per-sequence state that cannot be rewound."""
        return self.n_state_layers > 0

    @property
    def state_shape(self) -> tuple:
        """(heads, rows, lanes) of one slot's state in one layer: a
        state-space head's [P, N], a delta-rule head's [key, value], a
        retention kv head's [value + 1, expanded key]: the head's values
        and, as one more row, the normaliser, over the products of two key
        dims, rows and lanes rounded up to whole tiles (ops/power.state_dims
        has why): 8 x 136 x 8,320 float32 = 36.2 MB held for the 34.1 MB of
        8 x 129 x 8,256 at 8 kv heads of 128, where the other two kinds
        hold about 2 MB."""
        if self.n_retention_layers:
            return (self.n_kv_heads, *state_dims(self.head_size))
        if self.n_kda_layers:
            return (self.kda_heads, self.kda_head_dim, self.kda_head_dim)
        return (self.ssm_heads, self.ssm_head_dim, self.ssm_state)

    @property
    def state_conv(self) -> tuple:
        """(rows, channels) of one slot's conv window in one layer: the
        last taps - 1 rows of x|B|C (state-space) or q|k|v (delta rule);
        EMPTY for retention layers, which have no conv."""
        if self.n_retention_layers:
            return (0, 0)
        if self.n_kda_layers:
            return (self.kda_conv - 1, 3 * self.kda_inner)
        return (self.ssm_conv - 1, self.ssm_conv_dim)

    @property
    def kda_inner(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def kda_proj(self) -> int:
        """Output columns of a delta-rule layer's input projection as the
        file holds it: q | k | v | decay's inner | gate's inner | beta."""
        return 3 * self.kda_inner + 2 * self.kda_rank + self.kda_heads

    @property
    def latent(self) -> bool:
        """The cache row is one latent shared by all query heads."""
        return any(k == LayerKind.MLA for k in self.layer_kinds)

    @property
    def cache_kv_heads(self) -> int:
        return 1 if self.latent else self.n_kv_heads

    @property
    def cache_row(self) -> int:
        """Width of a cache row: a kv head's size, or the latent and the
        shared key dims side by side."""
        return self.kv_lora_rank + self.qk_pe_dim if self.latent else self.head_size

    @property
    def expert_width(self) -> int:
        return self.moe_hidden_dim or self.hidden_dim

    @property
    def n_held_experts(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def n_dense_ffn_layers(self) -> int:
        return sum(self.layer_ffn) if self.n_experts else self.n_layers

    def ffn_index(self, layer: int) -> int:
        """Layer `layer`'s index into the stack of ITS feed-forward kind's
        weights (dense layers and expert layers are stacked apart)."""
        if not self.layer_ffn:
            return layer
        mine = self.layer_ffn[layer]
        return sum(1 for f in self.layer_ffn[:layer] if f == mine)

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the causal conv runs over: x | B | C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def ssm_in_proj(self) -> int:
        """Output columns of in_proj as published: z | x | B | C | dt."""
        return self.ssm_inner + self.ssm_conv_dim + self.ssm_heads

    @property
    def softmax_scale_ratio(self) -> float:
        """What q is multiplied by before kernels that bake 1/sqrt(hd) in,
        so the scores come out at the configured scale."""
        if not self.attn_scale:
            return 1.0
        return self.attn_scale * self.head_size ** 0.5

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    def describe(self) -> str:
        """One-line summary in the spirit of the reference's header dump
        (llm.cpp:100-123)."""
        return (
            f"{self.arch.name} dim={self.dim} hidden={self.hidden_dim} "
            f"layers={self.n_layers} heads={self.n_heads}"
            + (f"g,{self.window_heads}w" if self.window_heads else "")
            + f"/{self.n_kv_heads} "
            f"vocab={self.vocab_size} seq={self.seq_len} "
            f"act={self.hidden_act.name} rope={self.rope_type.name}"
            + (f" latent rope {self.rope_spec.describe()}" if self.rope_spec
               else f" theta={self.rope_theta:g} (window); global rope "
               f"{self.global_rope.describe()}" if self.global_rope else "")
            + f" weights={self.weight_type.name}"
            + (" qk_norm" if self.qk_norm else "")
            + (" attn_gate=per_head" if self.attn_gate else "")
            + (f" experts={self.n_experts}/{self.n_active_experts}" if self.n_experts else "")
            + (f" held={self.expert_offset}+{self.experts_held}"
               if self.experts_held else "")
            + (f" shared={self.n_shared_experts}" if self.n_shared_experts else "")
            + (f" dense_ffn={sum(self.layer_ffn)}" if any(self.layer_ffn) else "")
            + (f" window={self.window}x{self.n_window_layers}" if self.n_window_layers else "")
            + (f" ssm_layers={self.n_ssm_layers}/{self.n_layers} "
               f"ssm={self.ssm_heads}x{self.ssm_head_dim}x{self.ssm_state}"
               if self.n_ssm_layers else "")
            + (f" kda_layers={self.n_kda_layers}/{self.n_layers} "
               f"kda={self.kda_heads}x{self.kda_head_dim}x{self.kda_head_dim}"
               if self.n_kda_layers else "")
            + (f" retention_layers={self.n_retention_layers}/{self.n_layers} "
               f"p={self.ret_degree} state={'x'.join(map(str, self.state_shape))}"
               if self.n_retention_layers else "")
            + (f" latent={self.kv_lora_rank}+{self.qk_pe_dim}" if self.latent else "")
            + (f" q_rank={self.q_lora_rank}" if self.q_lora_rank else "")
            + (f" expert_groups={self.expert_groups_kept}/{self.n_expert_groups}"
               if self.grouped_routing else "")
        )

    def clamp_seq_len(self, max_seq_len: int | None) -> "LlamaConfig":
        """The reference's --max-seq-len RAM clamp (llm.cpp:89-91)."""
        if max_seq_len and self.seq_len > max_seq_len:
            return dataclasses.replace(self, seq_len=max_seq_len, orig_seq_len=self.orig_seq_len)
        return self

    def to_header_kv(self) -> list[tuple[int, int]]:
        """Serialize to the `.m` kv pairs (float values stored as ints, as the
        reference converter does — writer.py:109-143)."""
        kv = [
            (HeaderKey.VERSION, self.version),
            (HeaderKey.ARCH_TYPE, int(self.arch)),
            (HeaderKey.DIM, self.dim),
            (HeaderKey.HIDDEN_DIM, self.hidden_dim),
            (HeaderKey.N_LAYERS, self.n_layers),
            (HeaderKey.N_HEADS, self.n_heads),
            (HeaderKey.N_KV_HEADS, self.n_kv_heads),
            (HeaderKey.N_EXPERTS, self.n_experts),
            (HeaderKey.N_ACTIVE_EXPERTS, self.n_active_experts),
            (HeaderKey.VOCAB_SIZE, self.vocab_size),
            (HeaderKey.SEQ_LEN, self.orig_seq_len),
            (HeaderKey.HIDDEN_ACT, int(self.hidden_act)),
            (HeaderKey.ROPE_THETA, int(self.rope_theta)),
            (HeaderKey.WEIGHT_FLOAT_TYPE, int(self.weight_type)),
        ]
        if self.rope_type == RopeType.LLAMA3_1:
            kv += [
                (HeaderKey.ROPE_SCALING_FACTOR, int(self.rope_scaling_factor)),
                (HeaderKey.ROPE_SCALING_LOW_FREQ_FACTOR, int(self.rope_scaling_low_freq_factor)),
                (HeaderKey.ROPE_SCALING_HIGH_FREQ_FACTORY, int(self.rope_scaling_high_freq_factor)),
                (HeaderKey.ROPE_SCALING_ORIG_MAX_SEQ_LEN, self.rope_scaling_orig_max_seq_len),
                (HeaderKey.ROPE_TYPE, int(self.rope_type)),
            ]
        elif self.rope_type == RopeType.NONE:
            kv.append((HeaderKey.ROPE_TYPE, int(self.rope_type)))
        if abs(self.norm_epsilon - 1e-5) > 1e-12:
            kv.append((HeaderKey.NORM_EPSILON_X1E12, int(round(self.norm_epsilon * 1e12))))
        if self.arch == ArchType.HYBRID_SSM:
            kv.append((HeaderKey.HEAD_SIZE, self.head_size))
            kv += [(key, int(round(getattr(self, name) * 1e6)))
                   for key, name in _X1E6_KEYS.items()]
            kv.append((HeaderKey.TIED_HEAD, int(self.tied_head)))
            kv += [(key, getattr(self, name)) for key, name in _SSM_INT_KEYS.items()]
            kv += [(LAYER_KIND_BASE + i, k) for i, k in enumerate(self.layer_kinds)]
        else:
            if self.head_dim:
                kv.append((HeaderKey.HEAD_SIZE, self.head_size))
            if self.attn_scale:
                kv.append((HeaderKey.ATTN_SCALE_X1E6,
                           int(round(self.attn_scale * 1e6))))
        if self.layer_kinds and self.arch != ArchType.HYBRID_SSM:
            kv += [(LAYER_KIND_BASE + i, k) for i, k in enumerate(self.layer_kinds)]
        kv += [(key, getattr(self, name)) for key, name in _EXTRA_INT_KEYS.items()
               if getattr(self, name) != _FIELD_DEFAULTS[name]]
        if self.router_sigmoid:
            kv.append((HeaderKey.ROUTER_KIND, 1))
        if self.routed_scale != 1.0:
            kv.append((HeaderKey.ROUTED_SCALE_X1E6,
                       int(round(self.routed_scale * 1e6))))
        kv += [(LAYER_FFN_BASE + i, f) for i, f in enumerate(self.layer_ffn)]
        if self.router_pre_attention:
            kv.append((HeaderKey.ROUTER_INPUT, 1))
        if self.window:
            kv.append((HeaderKey.WINDOW_SIZE, self.window))
        kv += [(LAYER_WINDOW_BASE + i, w) for i, w in enumerate(self.layer_windows)]
        kv += [(LAYER_ROPE_BASE + i, r) for i, r in enumerate(self.layer_ropes)]
        if self.window_heads:
            kv.append((HeaderKey.WINDOW_HEADS, self.window_heads))
        if self.qk_norm:
            kv.append((HeaderKey.QK_NORM, 1))
        if self.attn_gate:
            kv.append((HeaderKey.ATTN_GATE, 1))
        if self.ret_degree:
            kv.append((HeaderKey.RET_DEGREE, self.ret_degree))
        if self.ret_gate:
            kv.append((HeaderKey.RET_GATE, 1))
        if self.global_rope is not None:
            kv += [(key, int(round(getattr(self.global_rope, name) * mult)))
                   for key, (name, mult) in _GLOBAL_ROPE_KEYS.items()]
        return [(int(k), int(v)) for k, v in kv]

    @classmethod
    def from_header_kv(cls, kv: list[tuple[int, int]]) -> "LlamaConfig":
        vals: dict = {}
        grope: dict = {}
        kinds: dict = {}
        windows: dict = {}
        ropes: dict = {}
        ffns: dict = {}
        for key, value in kv:
            if key >= _LAYER_LISTS_END:
                raise ValueError(f"unknown header key {key}")
            if key >= LAYER_FFN_BASE:
                ffns[key - LAYER_FFN_BASE] = value
                continue
            if key >= LAYER_ROPE_BASE:
                ropes[key - LAYER_ROPE_BASE] = value
                continue
            if key >= LAYER_WINDOW_BASE:
                windows[key - LAYER_WINDOW_BASE] = value
                continue
            if key >= LAYER_KIND_BASE:
                kinds[key - LAYER_KIND_BASE] = LayerKind(value)
                continue
            key = HeaderKey(key)
            if key == HeaderKey.VERSION:
                vals["version"] = value
            elif key == HeaderKey.ARCH_TYPE:
                vals["arch"] = ArchType(value)
            elif key == HeaderKey.DIM:
                vals["dim"] = value
            elif key == HeaderKey.HIDDEN_DIM:
                vals["hidden_dim"] = value
            elif key == HeaderKey.N_LAYERS:
                vals["n_layers"] = value
            elif key == HeaderKey.N_HEADS:
                vals["n_heads"] = value
            elif key == HeaderKey.N_KV_HEADS:
                vals["n_kv_heads"] = value
            elif key == HeaderKey.N_EXPERTS:
                vals["n_experts"] = value
            elif key == HeaderKey.N_ACTIVE_EXPERTS:
                vals["n_active_experts"] = value
            elif key == HeaderKey.VOCAB_SIZE:
                vals["vocab_size"] = value
            elif key == HeaderKey.SEQ_LEN:
                vals["seq_len"] = value
            elif key == HeaderKey.HIDDEN_ACT:
                vals["hidden_act"] = HiddenAct(value)
            elif key == HeaderKey.ROPE_THETA:
                vals["rope_theta"] = float(value)
            elif key == HeaderKey.WEIGHT_FLOAT_TYPE:
                vals["weight_type"] = FloatType(value)
            elif key == HeaderKey.ROPE_SCALING_FACTOR:
                vals["rope_scaling_factor"] = float(value)
            elif key == HeaderKey.ROPE_SCALING_LOW_FREQ_FACTOR:
                vals["rope_scaling_low_freq_factor"] = float(value)
            elif key == HeaderKey.ROPE_SCALING_HIGH_FREQ_FACTORY:
                vals["rope_scaling_high_freq_factor"] = float(value)
            elif key == HeaderKey.ROPE_SCALING_ORIG_MAX_SEQ_LEN:
                vals["rope_scaling_orig_max_seq_len"] = value
            elif key == HeaderKey.ROPE_TYPE:
                vals["rope_type"] = RopeType(value)
            elif key == HeaderKey.NORM_EPSILON_X1E12:
                vals["norm_epsilon"] = value / 1e12
            elif key == HeaderKey.HEAD_SIZE:
                vals["head_dim"] = value
            elif key in _X1E6_KEYS:
                vals[_X1E6_KEYS[key]] = value / 1e6
            elif key == HeaderKey.TIED_HEAD:
                vals["tied_head"] = bool(value)
            elif key in _SSM_INT_KEYS:
                vals[_SSM_INT_KEYS[key]] = value
            elif key == HeaderKey.WINDOW_SIZE:
                vals["window"] = value
            elif key == HeaderKey.ROUTER_INPUT:
                vals["router_pre_attention"] = bool(value)
            elif key in _EXTRA_INT_KEYS:
                vals[_EXTRA_INT_KEYS[key]] = value
            elif key == HeaderKey.ROUTER_KIND:
                vals["router_sigmoid"] = bool(value)
            elif key == HeaderKey.ROUTED_SCALE_X1E6:
                vals["routed_scale"] = value / 1e6
            elif key == HeaderKey.WINDOW_HEADS:
                vals["window_heads"] = value
            elif key == HeaderKey.QK_NORM:
                vals["qk_norm"] = bool(value)
            elif key == HeaderKey.ATTN_GATE:
                vals["attn_gate"] = bool(value)
            elif key == HeaderKey.RET_DEGREE:
                vals["ret_degree"] = value
            elif key == HeaderKey.RET_GATE:
                vals["ret_gate"] = bool(value)
            elif key in _GLOBAL_ROPE_KEYS:
                name, mult = _GLOBAL_ROPE_KEYS[key]
                grope[name] = type(_ROPE_DEFAULTS[name])(value / mult)
        if grope:
            vals["global_rope"] = RopeSpec(**grope)
        for name, flags in (("layer_windows", windows), ("layer_ropes", ropes),
                            ("layer_ffn", ffns)):
            if flags:
                n = vals.get("n_layers", 0)
                if sorted(flags) != list(range(n)):
                    raise ValueError(
                        f"the header gives {name} for {len(flags)} layers of {n}")
                vals[name] = tuple(flags[i] for i in range(n))
        if kinds:
            n = vals.get("n_layers", 0)
            if sorted(kinds) != list(range(n)):
                raise ValueError(
                    f"the header names the kind of {len(kinds)} layers of {n}")
            vals["layer_kinds"] = tuple(kinds[i] for i in range(n))
        return cls(**vals)


_X1E6_KEYS = {HeaderKey.ATTN_SCALE_X1E6: "attn_scale",
              HeaderKey.EMBEDDING_MULT_X1E6: "embedding_multiplier",
              HeaderKey.RESIDUAL_MULT_X1E6: "residual_multiplier",
              HeaderKey.LOGITS_DIV_X1E6: "logits_scaling"}
_EXTRA_INT_KEYS = {HeaderKey.KDA_HEADS: "kda_heads",
                   HeaderKey.KDA_HEAD_DIM: "kda_head_dim",
                   HeaderKey.KDA_CONV: "kda_conv",
                   HeaderKey.KDA_RANK: "kda_rank",
                   HeaderKey.MLA_KV_RANK: "kv_lora_rank",
                   HeaderKey.MLA_NOPE_DIM: "qk_nope_dim",
                   HeaderKey.MLA_PE_DIM: "qk_pe_dim",
                   HeaderKey.MLA_V_DIM: "v_head_dim",
                   HeaderKey.MLA_Q_RANK: "q_lora_rank",
                   HeaderKey.N_SHARED_EXPERTS: "n_shared_experts",
                   HeaderKey.EXPERTS_HELD: "experts_held",
                   HeaderKey.EXPERT_OFFSET: "expert_offset",
                   HeaderKey.MOE_HIDDEN_DIM: "moe_hidden_dim",
                   HeaderKey.N_EXPERT_GROUPS: "n_expert_groups",
                   HeaderKey.EXPERT_GROUPS_KEPT: "expert_groups_kept"}
_FIELD_DEFAULTS = {f.name: f.default for f in dataclasses.fields(LlamaConfig)}
#: header key -> (RopeSpec field, what its value is multiplied by on disk)
_GLOBAL_ROPE_KEYS = {
    HeaderKey.GLOBAL_ROPE_TYPE: ("type", 1),
    HeaderKey.GLOBAL_ROPE_THETA: ("theta", 1),
    HeaderKey.GLOBAL_ROPE_SHARE_X1E6: ("share", 1e6),
    HeaderKey.GLOBAL_ROPE_FACTOR_X1E6: ("factor", 1e6),
    HeaderKey.GLOBAL_ROPE_ORIG_LEN: ("orig_len", 1),
    HeaderKey.GLOBAL_ROPE_BETA_FAST_X1E6: ("beta_fast", 1e6),
    HeaderKey.GLOBAL_ROPE_BETA_SLOW_X1E6: ("beta_slow", 1e6),
    HeaderKey.GLOBAL_ROPE_ATTN_FACTOR_X1E6: ("attn_factor", 1e6)}
_ROPE_DEFAULTS = {f.name: f.default for f in dataclasses.fields(RopeSpec)}
_SSM_INT_KEYS = {HeaderKey.SSM_HEADS: "ssm_heads",
                 HeaderKey.SSM_HEAD_DIM: "ssm_head_dim",
                 HeaderKey.SSM_STATE: "ssm_state",
                 HeaderKey.SSM_GROUPS: "ssm_groups",
                 HeaderKey.SSM_CONV: "ssm_conv",
                 HeaderKey.SSM_CHUNK: "ssm_chunk"}
