"""Model architecture configs for the dense decoder families the reference
trains through unsloth (train_distributed.py:11 — any FastLanguageModel
checkpoint; the reference's recipes name Qwen2.5 and Llama-3).

One ``ModelConfig`` covers the supported families — Qwen2.5, Llama-3,
Mistral, Gemma — via the knobs where they actually differ: GQA attention
with optional QKV bias (Qwen2 yes), gated MLP with SiLU or tanh-GELU
(Gemma), RMSNorm with optional +1 weight offset (Gemma), optional
sqrt(hidden) embedding scaling (Gemma), optional tied embeddings, and a
recorded sliding window (Mistral v0.1 — full attention is exact for
sequences within the window; the engines enforce that).

A model whose layers are not all alike (MiniCPM-SALA: block-sparse attention
layers beside lightning linear-attention layers) carries ``mixer_types``, the
published per-layer list. ``None`` means every layer is the dense GQA layer
above, and nothing else in this file applies. The list is kept WHOLE when the
depth is cut: ``num_layers`` runs its first entries, and a lightning layer's
decay reads its published index.

A latent-attention model with routed experts (``deepseek_v3``: Kimi-VL-A3B's
language model) sets ``kv_lora_rank`` and ``n_routed_experts``. Every layer
keeps ONE latent row a token (``kv_lora_rank + qk_rope_head_dim`` values, no
K or V per head); the first ``first_dense_layers`` layers have a dense gated
MLP (kind "latent"), the rest a router over ``n_routed_experts`` experts and
one shared expert (kind "latent_moe"). It runs through ``models/hybrid.py``
like any model whose layers are not all alike. With ``q_lora_rank`` the query
goes through a normed latent of its own (``q_a -> RMSNorm -> q_b``), and the
model may state a share as below (``router_experts`` / ``expert_shard``).

**A learned index over tokens** (``glm_moe_dsa``: GLM-5, DeepSeek's sparse
attention) stands beside latent attention in every layer where ``index_topk``
is set: ``index_heads`` small query heads of ``index_head_dim`` (projected from
the normed query latent) score ONE cached index key a token, and token ``t``
attends the ``min(index_topk, t + 1)`` tokens of largest score and no other
(``ops/token_index.py``). A cached token is then the latent row and, in a
second paged array under the same page table, its index key.

A gated delta-rule model with routed experts (``solar_open2``) has two layer
kinds in a published period: "softmax" (gated GQA attention without RoPE, K/V
pages) and "delta" (linear attention by a gated delta rule with a per-channel
decay behind short convolutions: a float32 matrix state and a convolution
tail a slot, ``ops/delta_attention.py``), each followed by routed experts
beside one shared expert. **A share.** Its configuration may state ONE CHIP'S
share of a deployment that divides each layer over several chips:
``n_routed_experts`` is then the experts this program HOLDS, ``router_experts``
the published width the router scores and chooses over, ``expert_shard`` which
contiguous run of ids is held (``held_experts``); pairs routed elsewhere add
nothing here. With no share the two counts are equal and every expert is held.

A power-retention model (``brumby``) is Qwen3's block with every attention
layer replaced by one kind, "power" (``ops/power_retention.py``): GQA q/k/v
with a per-head RMSNorm and RoPE, a scalar log-decay a KV head, and in place
of a K/V cache a float32 state of the symmetric second power of the keys,
``[D, head_dim]`` with ``D = head_dim (head_dim + 1) / 2``, and a normaliser
``[D]``, ONE a KV head, read by the query heads that share it. No layer keeps
a page: ``paged_layers`` is 0 and a slot's whole cache is state.

A state-space model (``jamba``, AI21-Jamba2-3B) has two kinds, placed by
``attn_layer_period`` / ``attn_layer_offset`` (layer ``i`` is attention where
``i % period == offset``): "softmax" (multi-query attention without RoPE, K/V
pages; published name "attention") and "mamba" (Mamba-1,
``ops/selective_scan.py``: a float32 state ``[d_state, d_inner]`` and the last
``d_conv - 1`` tokens of its convolution's input a slot), each followed by the
dense gated MLP: its "softmax" layers have no routed experts.

A window model with routed experts (``exaone_moe``, K-EXAONE-236B-A23B) has
two mixers, placed by the published ``layer_types``: "window"
(``sliding_attention``: GQA with a per-head RMSNorm of q and k and RoPE, token
t attending the last ``sliding_window`` tokens, itself included; a slot keeps
a RING of that many tokens' K and V and no page) and "softmax"
(``full_attention``: the same norm, NO positional encoding, K/V pages). The
second half of a layer is read from ``mlp_layer_types`` A LAYER (``mlp_types``):
``sparse`` is routed experts beside one shared expert, under a share as above;
``dense`` is the gated MLP of ``intermediate_size``, and its layer's kind
carries the suffix ``_dense`` (its stack has other leaves). ``sliding_window``
is then the ring's length and refuses nothing.

A second window family (``mimo_v2_flash``, MiMo-V2-Flash) runs through the same
two mixers with what ITS configuration states, each a field and none a second
code path: KV heads a KIND (``num_kv_heads`` in the "softmax" layers and their
pages, ``window_kv_heads`` in the "window" layers and their rings), keys and
values of two widths (``head_dim`` for q and k, ``v_head_dim`` for v, so
``W_o`` reads ``num_heads x v_head_dim``), RoPE on the first ``rotary_dim``
values of a head in BOTH kinds with a base a kind (``rope_theta``,
``window_rope_theta``; ``attn_use_rope`` says that the "softmax" layers rotate),
a value scaled by ``value_scale`` before it is kept, one learned sink logit a
query head in a window layer's softmax (``window_sink``: a column of the
denominator whose value is nothing), and no q/k norm (``qk_norm`` false).

Compressed convolutional attention with an MLP router (``zaya``, ZAYA1-8B) is
one kind, "cca", in every layer (published name ``hybrid``): softmax attention
that runs WHOLE in a latent of ``num_heads + num_kv_heads`` heads below the
hidden width, its queries and keys mixed over the sequence by two causal
convolutions of ``cca_time0`` and ``cca_time1`` taps, half of its value heads
taken from the token before. A slot keeps K/V pages AND, in the same layer, a
row state: the convolutions' tail and the late value (``cca_tail_dim`` values).
The second half of every layer is routed experts chosen ONE a token by an MLP
router of ``router_hidden_size`` whose input carries the previous layer's
(``models/moe.py::route_mlp``), and each sublayer joins the stream through
learned scales and shifts (``models/hybrid.py``).

A shortcut-connected expert model (``longcat_flash``, LongCat-Flash-Chat;
``shortcut_moe``) holds in ONE published layer two latent-attention sublayers,
each with a dense gated MLP, and one expert block that reads the first MLP's
normed input and joins the stream after the second MLP. ``num_layers`` stays
the published count; ``layer_kinds`` counts SUBLAYERS ("latent_fork" then
"latent_join", ``SHORTCUT_KINDS``), and with it the stacks, the page pools
(``paged_layers``: two a published layer) and the adapters. Its router is a
softmax (``router_softmax``) over the routed experts AND ``zero_experts`` further
outputs that compute nothing (a chosen one adds ``w * u``): ``router_width``
counts both, the chosen scores are the weights unnormalised, and the two
latents are scaled by constants before their up-projections (``latent_q_scale``,
``latent_kv_scale``).

A looped model (``ouro``, Ouro-2.6B; ``loop_steps`` > 1) is the dense GQA
decoder with ONE stack of weights run ``loop_steps`` times a token: pass ``u``
walks layers ``0..num_layers-1``, the final norm closes EVERY pass and its
output feeds the next, and one ``Linear(hidden, 1)`` exit gate reads each
pass's output (``models/transformer.py``). Three counts that were one number:
``num_layers`` stays the published count of WEIGHT layers (the stacks, the
adapters, the loader); ``loop_steps`` is the passes; ``layer_steps`` =
``num_layers x loop_steps`` is the layer applications a token, and each is a
CACHE layer of its own (pass ``u``'s layer ``l`` attends the keys pass ``u``
wrote: cache layer ``u * num_layers + l``), which is what ``paged_layers``
returns and both engines, the budget and the learner's kept-products
arithmetic size by. ``sublayer_out_norm`` norms each sublayer's OUTPUT too
(``x + N2(attn(N1 x))``, ``x + N4(mlp(N3 x))``). The published
``early_exit_threshold`` of 1 runs every pass for every token; the gates are
computed and reported, and decide nothing.

A state-space expert model whose layers are ONE sublayer each (``nemotron_h``,
NVIDIA-Nemotron-3-Nano-30B-A3B) places three kinds by the characters of
``hybrid_override_pattern`` (kept WHOLE when the depth is cut, like every
per-layer list here; ``mixer_types`` holds them by name, "mamba-2",
"attention-only" and "moe"): ``M`` -> "mamba2"
(Mamba-2, ``ops/ssd.py``: ``ssd_heads`` heads of ``ssd_head_dim`` whose
``B`` and ``C`` are shared by the heads of one of ``ssd_groups`` groups, ONE
scalar decay a head; a slot keeps a float32 state ``[heads, head_dim,
mamba_d_state]`` a layer, the state's columns along the lanes, and the last
``mamba_d_conv - 1`` tokens of the convolution's input, x, B and C alike, kept
flat ``[rows, 3 x channels]``),
``*`` -> "softmax_alone" (GQA attention without RoPE, K/V pages, and NO second
half) and ``E`` -> "experts" (NO mixer: the routed experts HELD, under a share
as above, beside one shared expert of ``shared_expert_width``). A layer is
``x + Mixer(RMSNorm(x))``: ``layer_ffn`` says "none" for the first two kinds
and ``mixer_of`` a kind's mixer. An expert of this family is UNGATED,
``W_down relu(W_up h)^2``: its stacks hold no gate (``models/moe.py`` reads
the form off the stack), and so is the shared expert. The family's dense
relu^2 MLP layer (``-``) is refused by name.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

#: published ``mixer_types`` entry -> the kind the program names its stacks by
MIXER_KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning",
               # solar_open2 publishes ``gqa_layers``; from_hf_config names the rest
               "gqa": "softmax", "kda": "delta",
               # brumby publishes no list: from_hf_config names every layer
               "power-retention": "power",
               # jamba publishes a period and an offset; from_hf_config names
               # every layer. Its attention is a "softmax" layer with a dense MLP
               "attention": "softmax", "mamba": "mamba",
               # exaone_moe publishes ``layer_types``
               "sliding_attention": "window", "full_attention": "softmax",
               # zaya publishes ``layer_types``, every entry "hybrid"
               "hybrid": "cca",
               # nemotron_h publishes a pattern of characters; from_hf_config
               # names every layer (M, *, E): each kind is ONE sublayer
               "mamba-2": "mamba2", "attention-only": "softmax_alone", "moe": "experts"}
#: what a kind's name carries where its layer's second half is the dense gated
#: MLP in a model whose other layers have routed experts (``mlp_types``)
DENSE_FFN = "_dense"
#: what a kind's name carries where its layer is the mixer ALONE, with no second
#: half at all (nemotron_h's attention layers; its "mamba2" kind is always so)
ALONE = "_alone"


#: lanes of a tile's minor dimension on the chip: what ``ModelConfig.key_row``
#: rounds a wide key up to (the tests name a smaller one to reach it at their size)
KEY_ROW_LANES = 128


def mixer_of(kind: str) -> str:
    """A layer kind's MIXER, whatever its second half is."""
    return kind.removesuffix(DENSE_FFN).removesuffix(ALONE)
#: ``model_type`` values ``from_hf_config`` can represent; "" is a bare config
KNOWN_MODEL_TYPES = (
    "", "qwen2", "llama", "mistral", "gemma", "minicpm_sala", "deepseek_v3",
    "solar_open2", "brumby", "jamba", "exaone_moe", "glm_moe_dsa", "zaya",
    "mimo_v2_flash", "longcat_flash", "ouro", "nemotron_h",
)
#: the two SUBLAYERS of one published layer of a shortcut-connected expert model
#: (``longcat_flash``): the first forks the expert block off its MLP's input, the
#: second adds what the experts gave after its own MLP
SHORTCUT_KINDS = ("latent_fork", "latent_join")
#: what a slot holds for a layer of each kind, for a refusal
_STATE_NAMES = {
    "sparse": "a selector cache of pooled keys",
    "lightning": "a recurrent float32 state",
    **dict.fromkeys(("latent", "latent_moe", *SHORTCUT_KINDS),
                    "one latent row a token in place of K and V per head"),
    "softmax": "K/V pages for its softmax layers only",
    "delta": "a float32 delta-rule state and a convolution tail",
    "power": "a float32 power-retention state and its normaliser a KV head, "
             "and no K/V at all",
    "mamba": "a float32 state-space state and a convolution window",
    "mamba2": "a float32 state a head of its state-space (Mamba-2) layers and a "
              "convolution tail",
    "window": "a ring of the last sliding_window tokens' K and V and no page",
    "cca": "K/V pages and, beside them in the same layer, a row state: the "
           "convolutions' tail and the value taken a token late",
}
#: what a latent layer's token keeps beside its row where the model has an index
_INDEX_STATE = " beside one index key a token in a second paged array"
#: layer kind -> the published name a refusal gives it
_LATENT_NAMES = {"latent": "latent-attention (MLA)", "latent_moe": "routed-expert",
                 "latent_fork": "shortcut-connected routed-expert",
                 "latent_join": "latent-attention (MLA)"}


@dataclass(frozen=True)
class ModelConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    attention_bias: bool = False  # Qwen2: bias on q/k/v only
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 32768
    hidden_act: str = "silu"  # "silu" | "gelu_tanh" (Gemma)
    rmsnorm_offset: bool = False  # Gemma: norm scales by (1 + weight)
    scale_embeddings: bool = False  # Gemma: embeddings × sqrt(hidden_size)
    # Mistral v0.1 sliding-window size; recorded so the forward/engines can
    # REFUSE sequences longer than the window (full attention ≡ SWA within
    # it) rather than silently change the model's semantics
    sliding_window: int | None = None
    # ---- per-layer mixers (MiniCPM-SALA). ``mixer_types`` is the PUBLISHED
    # list, whole; None = every layer is dense GQA and nothing below is read
    mixer_types: tuple[str, ...] | None = None
    qk_norm: bool = False  # RMSNorm over head_dim on q and k, one weight each
    attn_use_rope: bool = True  # sparse layers: MiniCPM-SALA rotates nothing
    attn_output_gate: bool = False  # sparse layers: y = Wo(o * sigmoid(Wz h))
    lightning_heads: int = 0  # q, k and v heads alike (lightning_nh == nkv)
    lightning_head_dim: int = 0
    lightning_use_rope: bool = True
    lightning_output_gate: bool = False
    lightning_output_norm: bool = False  # RMSNorm over the joined head dims
    # InfLLM-V2 selector (MiniCPM4.1's published sparse_config)
    sparse_kernel_size: int = 32  # keys mean-pooled into one selector key
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64  # the unit of choice (and the engine's page)
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2048
    sparse_dense_len: int = 8192  # a context at most this long attends densely
    # muP: x0 = scale_emb*E, x += scale_depth/sqrt(L_published)*f(x),
    # logits = head(norm(x) / (hidden/dim_model_base)); 0 = off
    scale_emb: float = 1.0
    scale_depth: float = 0.0
    dim_model_base: int = 0
    # ---- latent attention (MLA) and routed experts (deepseek_v3). 0 = off.
    # ``head_dim`` is then the QUERY head (nope + rope); a cached token is one
    # row of ``latent_dim`` values for all heads
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    n_routed_experts: int = 0
    n_shared_experts: int = 0  # run as ONE gated MLP of n x moe_intermediate_size
    experts_per_token: int = 0
    moe_intermediate_size: int = 0
    first_dense_layers: int = 0  # first_k_dense_replace
    q_lora_rank: int = 0  # the query's own normed latent (q_a, q_b); 0 = one q_proj
    # ---- a learned index over tokens beside latent attention (glm_moe_dsa's
    # index_* keys; module docstring). index_topk 0 = no index
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # ---- one chip's share of the routed experts (module docstring). 0 = the
    # router is as wide as the experts held: every expert is here
    router_experts: int = 0
    expert_shard: int = 0  # which run of n_routed_experts ids is held
    # ---- gated delta-rule layers (solar_open2's linear_attn_config, kda_*)
    delta_heads: int = 0  # q, k and v heads alike
    delta_head_dim: int = 0  # d_k = d_v
    delta_conv_size: int = 4  # short_conv_kernel_size
    delta_low_rank: int = 0  # inner width of the decay's and the gate's pair
    delta_beta_scale: float = 1.0  # 2 with kda_allow_neg_eigval
    # ---- Mamba-1 state-space layers (jamba's mamba_* keys). 0 = none
    mamba_d_state: int = 0  # N: columns of a channel's state
    mamba_d_conv: int = 4  # taps of the causal depth-wise convolution
    mamba_expand: int = 2  # d_inner = expand x hidden
    mamba_dt_rank: int = 0  # inner width of the step size's low-rank pair
    # ---- Mamba-2 layers of one sublayer (nemotron_h; module docstring); the
    # state's columns are ``mamba_d_state``, the taps ``mamba_d_conv``
    ssd_heads: int = 0  # mamba_num_heads
    ssd_head_dim: int = 0  # mamba_head_dim
    ssd_groups: int = 1  # n_groups: B and C are a group's, read by its heads
    ssd_chunk: int = 128  # chunk_size: tokens a chunk of the matrix form
    shared_expert_width: int = 0  # the shared expert's own; 0 = n_shared x moe width
    # ---- a layer's second half, a LAYER (exaone_moe's ``mlp_layer_types``, the
    # published list whole: "dense" | "sparse"). None = the family's own rule
    mlp_types: tuple[str, ...] | None = None
    # ---- compressed convolutional attention and an MLP router (zaya's cca_* and
    # router_hidden_size keys; module docstring). 0 = none
    cca_time0: int = 0  # taps of the depth-wise convolution over [q~ | k~]
    cca_time1: int = 0  # taps of the convolution grouped by head after it
    rotary_dim: int = 0  # values of a head RoPE rotates, its first; 0 = all
    router_hidden_size: int = 0  # width of the router's MLP and of what it hands on
    # ---- what a window family's two mixers may state apart (mimo_v2_flash;
    # module docstring). With ``v_head_dim`` (the VALUE head's width outside the
    # latent family; 0 = ``head_dim``), ``rotary_dim`` and ``attn_use_rope``
    window_kv_heads: int = 0  # KV heads of a "window" layer; 0 = num_kv_heads
    window_rope_theta: float = 0.0  # RoPE's base in a "window" layer; 0 = rope_theta
    window_sink: bool = False  # one learned sink logit a query head, window layers
    value_scale: float = 1.0  # v = value_scale * (h W_v), before it is cached
    # ---- a shortcut-connected expert layer (longcat_flash; module docstring):
    # ONE published layer is two latent-attention sublayers, each with a dense
    # MLP, and an expert block from the first MLP's input to the layer's end
    shortcut_moe: bool = False
    zero_experts: int = 0  # router outputs past the experts that compute nothing: w * u
    router_softmax: bool = False  # s = softmax(u W_r) in place of the sigmoid
    latent_q_scale: float = 1.0  # the normed query latent's constant (mla_scale_q_lora)
    latent_kv_scale: float = 1.0  # the normed KV latent's (mla_scale_kv_lora)
    # ---- a looped model (ouro; module docstring): the one stack of weights is
    # run ``loop_steps`` times a token, a cache layer a (pass, layer)
    loop_steps: int = 1  # total_ut_steps
    sublayer_out_norm: bool = False  # RMSNorm on each sublayer's OUTPUT as well

    def __post_init__(self):
        if self.hidden_act not in ("silu", "gelu_tanh"):
            raise ValueError(
                f"hidden_act must be silu/gelu_tanh, got {self.hidden_act!r}"
            )
        if self.loop_steps < 1 or (self.loop_steps > 1 and (
                self.mixer_types is not None or self.kv_lora_rank)):
            raise ValueError(
                f"loop_steps {self.loop_steps}: a looped model is the dense GQA "
                "decoder run one or more times a token; a model whose layers "
                "differ in kind runs once")
        if self.shortcut_moe and not (self.latent and self.n_routed_experts):
            raise ValueError(
                "shortcut_moe is a latent-attention layer pair round routed experts: "
                "it needs kv_lora_rank and n_routed_experts")
        if self.router_experts and (
                self.router_experts % max(self.n_routed_experts, 1)
                or self.expert_shard * self.n_routed_experts >= self.router_experts):
            raise ValueError(
                f"a chip holds run {self.expert_shard} of {self.n_routed_experts} "
                f"routed experts, and the router scores {self.router_experts}: "
                "the held run must be one of a whole number of runs")
        if self.index_topk and not (
                self.latent and self.q_lora_rank and self.index_heads
                and self.index_head_dim >= self.qk_rope_head_dim):
            raise ValueError(
                "index_topk needs latent attention with q_lora_rank (the index's "
                "queries are projected from the normed query latent), index_heads "
                "and an index_head_dim no smaller than qk_rope_head_dim")
        if self.mixer_types is not None:
            unknown = sorted(set(self.mixer_types) - set(MIXER_KINDS))
            if unknown:
                raise ValueError(
                    f"mixer_types holds {unknown}: the layer kinds this program "
                    f"runs are {sorted(MIXER_KINDS)}"
                )
            if self.num_layers > len(self.mixer_types):
                raise ValueError(
                    f"num_layers {self.num_layers} exceeds the {len(self.mixer_types)} "
                    "published mixer_types"
                )
            if self.mlp_types is not None and (
                    len(self.mlp_types) != len(self.mixer_types)
                    or set(self.mlp_types) - {"dense", "sparse"}):
                raise ValueError(
                    f"mlp_types holds {sorted(set(self.mlp_types))} over "
                    f"{len(self.mlp_types)} layers: one of 'dense' / 'sparse' for "
                    f"each of the {len(self.mixer_types)} published layers")
            if self.ssd_moe and (not self.ssd_heads or self.ssd_heads % self.ssd_groups):
                raise ValueError(
                    f"mamba2 layers need ssd_heads ({self.ssd_heads}) a multiple of "
                    f"ssd_groups ({self.ssd_groups}): a head reads its group's B and C")
            if self.window_moe and not self.sliding_window:
                raise ValueError(
                    "sliding_attention layers need sliding_window: the tokens a "
                    "slot's ring keeps")
            if (self.sparse_block_size % self.sparse_kernel_stride
                    or self.sparse_kernel_size % self.sparse_kernel_stride):
                raise ValueError(
                    "the selector needs kernel_size and block_size to be multiples "
                    "of kernel_stride"
                )

    # ------------------------------------------------------ per-layer pattern

    @property
    def latent(self) -> bool:
        """True for latent attention (``kv_lora_rank`` is set)."""
        return self.kv_lora_rank > 0

    @property
    def delta_moe(self) -> bool:
        """True for a gated delta-rule model (``solar_open2``): "softmax" and
        "delta" layers, each with routed experts."""
        return self.mixer_types is not None and bool(
            {"gqa", "kda"} & set(self.mixer_types[: self.num_layers]))

    @property
    def power(self) -> bool:
        """True for a power-retention model (``brumby``): every layer "power"."""
        return self.mixer_types is not None and (
            "power-retention" in self.mixer_types[: self.num_layers])

    @property
    def mamba(self) -> bool:
        """True for a state-space model (``jamba``): "mamba" layers beside
        "softmax" layers, each with a dense MLP."""
        return self.mixer_types is not None and (
            "mamba" in self.mixer_types[: self.num_layers])

    @property
    def ssd_moe(self) -> bool:
        """True for a state-space expert model of one sublayer a layer
        (``nemotron_h``): "mamba2", "softmax_alone" and "experts" layers."""
        return self.mixer_types is not None and bool(
            {"mamba-2", "attention-only", "moe"} & set(self.mixer_types[: self.num_layers]))

    @property
    def ssd_inner(self) -> int:
        """Channels of a Mamba-2 layer: heads x head_dim (NOT expand x hidden)."""
        return self.ssd_heads * self.ssd_head_dim

    @property
    def ssd_conv_dim(self) -> int:
        """Channels the convolution mixes and a slot's tail keeps: ``[x | B | C]``."""
        return self.ssd_inner + 2 * self.ssd_groups * self.mamba_d_state

    @property
    def ssd_in_dim(self) -> int:
        """Columns of ``W_in``: ``[z | xBC | dt]``."""
        return self.ssd_inner + self.ssd_conv_dim + self.ssd_heads

    @property
    def window_moe(self) -> bool:
        """True for a window model (``exaone_moe``): "window" layers beside
        "softmax" layers, the second half read from ``mlp_types`` a layer."""
        return self.mixer_types is not None and bool(
            {"sliding_attention", "full_attention"} & set(self.mixer_types[: self.num_layers]))

    @property
    def cca(self) -> bool:
        """True for compressed convolutional attention (``zaya``): every layer
        "cca", each with routed experts behind an MLP router."""
        return self.mixer_types is not None and (
            "hybrid" in self.mixer_types[: self.num_layers])

    @property
    def cca_tail_dim(self) -> int:
        """Values a slot keeps a layer beside its pages: the last token's
        ``[q~ | k~]``, its first convolution's output, and the half of the
        value heads the NEXT token reads."""
        return 2 * (self.q_dim + self.kv_dim) + self.kv_dim // 2

    @property
    def mamba_inner(self) -> int:
        """E: channels of a Mamba layer (``d_inner``)."""
        return self.mamba_expand * self.hidden_size

    @property
    def power_state_dim(self) -> int:
        """D: entries of the symmetric second power of a ``head_dim`` key."""
        return self.head_dim * (self.head_dim + 1) // 2

    @property
    def hybrid(self) -> bool:
        """True where a layer is not the dense GQA layer: ``mixer_types`` is
        set, or attention is latent. Such a model runs through
        ``models/hybrid.py`` and keeps one parameter stack per layer kind."""
        return self.mixer_types is not None or self.latent

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        """Kind of each layer that is RUN: "dense" | "sparse" | "lightning" |
        "latent" (latent attention, dense MLP) | "latent_moe" (experts) |
        "softmax" | "delta" | "power" | "mamba" | "window" | "cca" | "mamba2" |
        "softmax_alone" | "experts"; with ``mlp_types`` a layer whose second
        half is the dense MLP carries ``DENSE_FFN``."""
        if self.shortcut_moe:  # a published layer is two SUBLAYERS
            return SHORTCUT_KINDS * self.num_layers
        if self.latent:
            dense = min(self.first_dense_layers, self.num_layers)
            if not self.n_routed_experts:
                dense = self.num_layers
            return ("latent",) * dense + ("latent_moe",) * (self.num_layers - dense)
        if self.mixer_types is None:
            return ("dense",) * self.num_layers
        kinds = tuple(MIXER_KINDS[m] for m in self.mixer_types[: self.num_layers])
        if self.mlp_types is None:
            return kinds
        return tuple(k + DENSE_FFN * (f == "dense") for k, f in zip(kinds, self.mlp_types))

    def layer_ffn(self, kind: str) -> str:
        """"dense" | "experts" | "none": the second half of a "softmax",
        "delta", "mamba" or "window" layer of ``kind``. A kind that says so is
        dense, or the mixer alone ("none"; a "mamba2" layer always is);
        otherwise the model's routed experts, where it has any (an "experts"
        layer is they and no mixer)."""
        if kind.endswith(ALONE) or kind == "mamba2":
            return "none"
        if (kind.endswith(DENSE_FFN) or kind in ("latent", "latent_join")
                or not self.n_routed_experts):
            return "dense"
        return "experts"

    def mixer_count(self, mixer: str) -> int:
        """Layers that are run whose MIXER is ``mixer``, whatever follows it."""
        return sum(1 for k in self.layer_kinds if mixer_of(k) == mixer)

    @property
    def router_width(self) -> int:
        """Outputs the router scores and chooses among: the published count of
        routed experts, and after them the experts that compute nothing."""
        return (self.router_experts or self.n_routed_experts) + self.zero_experts

    @property
    def held_experts(self) -> tuple[int, ...] | None:
        """Ids of the routed experts this program holds, in the order its
        stacks keep them, or None: all of them."""
        if self.router_width == self.n_routed_experts:
            return None
        first = self.expert_shard * self.n_routed_experts
        return tuple(range(first, first + self.n_routed_experts))

    @property
    def delta_dim(self) -> int:
        return self.delta_heads * self.delta_head_dim

    @property
    def latent_dim(self) -> int:
        """Values a cached token holds in a latent layer: ``[c, k_pe]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """Lanes a cached token's row takes in a page: ``latent_dim`` rounded
        up to whole 128-lane tiles (576 -> 640), the rest zeros. The TPU's
        tiling pads the row to that in any case, and with a width that is no
        multiple of 128 the compiler keeps the pool token-minor, which the
        decode step's row write would pay for with two pool copies a layer
        (tests/test_tpu_compile.py)."""
        return -(-self.latent_dim // 128) * 128

    @property
    def layer_steps(self) -> int:
        """Layer applications a token: the weight layers times the passes a
        looped model makes over them (``num_layers`` where there is no loop)."""
        return self.num_layers * self.loop_steps

    @property
    def looped(self) -> bool:
        return self.loop_steps > 1

    @property
    def paged_layers(self) -> int:
        """Layers that keep K/V in an engine's cache: CACHE layers. A looped
        model keeps one a (pass, layer), ``layer_steps`` of them."""
        if self.latent:  # every SUBLAYER where a published layer holds two
            return len(self.layer_kinds)
        if not self.hybrid:
            return self.layer_steps
        return sum(self.mixer_count(m) for m in ("sparse", "softmax", "cca"))

    def page_pool_shape(self, pages: int, page_size: int) -> tuple[int, ...]:
        """Shape of one layer's page array: K ``[K, pages, page, key_row]``
        (``head_dim`` lanes a token unless that is a tile and a half), or one
        latent array ``[pages, page, latent_row]`` with no kv-head axis (and no
        V array beside it), ``latent_row`` lanes a token."""
        if self.latent:
            return (pages, page_size, self.latent_row)
        return (self.num_kv_heads, pages, page_size, self.key_row)

    def second_pool_shape(self, pages: int, page_size: int) -> tuple[int, ...] | None:
        """Shape of the array a layer keeps in the pool's SECOND slot, under
        the same page table: V beside K, ``value_head_dim`` wide; a latent layer's index keys ``[pages,
        page, index_head_dim]`` where the model has an index; None where a
        latent layer keeps its rows alone (the slot is an empty tuple)."""
        if not self.latent:  # V at its own width, under K's table
            return (self.num_kv_heads, pages, page_size, self.value_head_dim)
        return (pages, page_size, self.index_head_dim) if self.index_topk else None

    @property
    def shared_expert_size(self) -> int:
        return self.shared_expert_width or self.n_shared_experts * self.moe_intermediate_size

    def kind_count(self, kind: str) -> int:
        return sum(1 for k in self.layer_kinds if k == kind)

    @property
    def layer_runs(self) -> tuple[tuple[str, int, int, int], ...]:
        """Runs of like layers in published order: (kind, first layer's index
        in the model, first layer's index in its kind's stack, count)."""
        runs, seen = [], {}
        for i, kind in enumerate(self.layer_kinds):
            at = seen.get(kind, 0)
            if runs and runs[-1][0] == kind:
                runs[-1][3] += 1
            else:
                runs.append([kind, i, at, 1])
            seen[kind] = at + 1
        return tuple(tuple(r) for r in runs)

    @property
    def mixer_names(self) -> str:
        """The published names of the non-dense layer kinds, for a refusal."""
        if self.latent:
            return " and ".join(
                _LATENT_NAMES[k] for k in dict.fromkeys(self.layer_kinds))
        return ", ".join(sorted(set(self.mixer_types or ())))

    @property
    def slot_state_names(self) -> str:
        """What a slot holds for this model's layers that is no K/V of one
        kind for every layer, for a refusal."""
        return " and ".join(dict.fromkeys(
            _STATE_NAMES[m] for m in map(mixer_of, self.layer_kinds) if m in _STATE_NAMES
        )) + _INDEX_STATE * bool(self.index_topk)

    def refuse_hybrid(self, what: str) -> None:
        """Raise, naming the mixer kinds and the state they keep, where
        ``what`` holds K/V of one kind for every layer and so cannot hold
        this model. The single owner of that sentence for every engine and
        feature."""
        if self.hybrid:
            raise ValueError(
                f"{what} cannot hold a model with {self.mixer_names} layers: it "
                "keeps one kind of K/V for every layer, and these layers keep "
                f"{self.slot_state_names}. Use engine_impl='paged' without it."
            )

    def refuse_looped(self, what: str) -> None:
        """Raise where ``what`` has not been held to the reference for a model
        whose layers run several times a token. The single owner of that
        sentence for every engine and feature."""
        if self.looped:
            raise ValueError(
                f"{what} is not supported for a looped model (model_type "
                f"'ouro'): its {self.num_layers} weight layers run "
                f"{self.loop_steps} times a token and keep {self.paged_layers} "
                "cache layers, one a (pass, layer), and this path has not been "
                "held to the reference over them. Use engine_impl='dense' or "
                "'paged' (waves or the refill scheduler) without it.")

    @property
    def residual_scale(self) -> float:
        """What each block's output is multiplied by before it joins the
        stream: scale_depth / sqrt(published depth), or 1."""
        if not self.scale_depth:
            return 1.0
        depth = len(self.mixer_types) if self.mixer_types else self.num_layers
        return self.scale_depth / math.sqrt(depth)

    @property
    def logit_scale(self) -> float:
        """What the final hidden state is multiplied by before the head."""
        return self.dim_model_base / self.hidden_size if self.dim_model_base else 1.0

    @property
    def lightning_dim(self) -> int:
        return self.lightning_heads * self.lightning_head_dim

    def lightning_decay_rates(self):
        """[n_lightning, heads] float32 numpy: the per-token log-decay
        ``s_h * (1 - l/(L-1) + 1e-5)`` of each lightning layer that is run,
        with ``s_h = 2^(-8h/H)``, h = 1..H, and ``l`` the layer's PUBLISHED
        index (Lightning Attention's ALiBi-style slopes)."""
        import numpy as np

        heads = self.lightning_heads
        slopes = 2.0 ** (-8.0 * np.arange(1, heads + 1) / heads)
        last = max(len(self.mixer_types) - 1, 1)
        rows = [
            slopes * (1.0 - i / last + 1e-5)
            for i, k in enumerate(self.layer_kinds) if k == "lightning"
        ]
        return np.asarray(rows, np.float32).reshape(len(rows), heads)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def value_head_dim(self) -> int:
        """Width of a VALUE head of a GQA layer: ``head_dim`` unless the
        configuration states another (``v_head_dim``)."""
        return self.v_head_dim or self.head_dim

    @property
    def o_dim(self) -> int:
        """What a GQA layer's ``W_o`` reads: every query head's value."""
        return self.num_heads * self.value_head_dim

    @property
    def key_row(self) -> int:
        """Lanes a cached KEY takes in a page or a ring: ``head_dim``, rounded
        up to whole 128-lane tiles where it is wider than one tile and no
        multiple of it (192 -> 256), the rest zeros. The TPU's row-major tiling
        pads the row to that in any case, and at a width that is no multiple of
        128 the compiler keeps such an array TOKEN-MINOR at every program's
        boundary, which the paged launch (row-major operands) and the ring's
        write would pay for with a copy of the pool and of each ring in and out
        a step (as ``latent_row``; tests/test_tpu_compile.py). A width within
        one tile (64, 128) is kept as it is."""
        lanes = KEY_ROW_LANES
        if self.head_dim <= lanes or self.head_dim % lanes == 0:
            return self.head_dim
        return -(-self.head_dim // lanes) * lanes

    def kv_heads_of(self, mixer: str) -> int:
        """KV heads of a layer whose mixer is ``mixer``: a "window" layer's own
        count where the configuration states one."""
        return (self.window_kv_heads if mixer == "window" else 0) or self.num_kv_heads

    def ring_shapes(self, rows: int) -> tuple[tuple[int, ...], tuple[int, ...]]:
        """A window layer's two rings for ``rows`` slots: K ``[B, K_w, W,
        key_row]`` and V ``[B, K_w, W, value_head_dim]``."""
        kv = self.kv_heads_of("window")
        return ((rows, kv, self.sliding_window, self.key_row),
                (rows, kv, self.sliding_window, self.value_head_dim))

    def check_within_window(self, key_span: int) -> None:
        """Raise if attending over ``key_span`` keys would exceed the
        checkpoint's sliding window — full attention ≡ SWA only within it;
        running past it would silently change the model (Mistral v0.1).
        Single owner of the check for the forward and both engines."""
        if self.window_moe:  # the window layers keep a ring: nothing to refuse
            return
        if self.sliding_window is not None and key_span > self.sliding_window:
            raise ValueError(
                f"key window {key_span} exceeds the checkpoint's "
                f"sliding_window {self.sliding_window}; sliding-window "
                "attention is not implemented — keep prompt+generation "
                "within the window"
            )

    @property
    def matmul_param_count(self) -> int:
        """Parameters participating in matmuls (projections + MLP + lm_head;
        biases/norms excluded as FLOP-negligible, embedding lookups are not
        matmuls). The 2·N term of every FLOPs-per-token estimate — the
        single owner for the telemetry MFU series."""
        mlp = 3 * self.hidden_size * self.intermediate_size  # gate, up, down
        attn = (
            2 * self.hidden_size * self.q_dim       # q, o proj
            + 2 * self.hidden_size * self.kv_dim    # k, v proj
        )
        if self.latent:
            # of a token's choices, those that run a routed expert under an even
            # router: a zero-compute choice multiplies nothing
            return self._latent_param_count(
                self.experts_per_token * (self.router_width - self.zero_experts)
                // max(self.router_width, 1))
        if self.delta_moe:
            return self._delta_moe_param_count(self.experts_per_token)
        if self.window_moe:
            return self._window_moe_param_count(self.experts_per_token)
        if self.cca:
            return self._cca_param_count(self.experts_per_token)
        if self.ssd_moe:
            return self._ssd_moe_param_count(self.experts_per_token)
        if not self.hybrid:
            return self.num_layers * (attn + mlp) + self.hidden_size * self.vocab_size
        sparse = attn + self.hidden_size * self.q_dim * self.attn_output_gate
        lightning = self.hidden_size * self.lightning_dim * (
            4 + self.lightning_output_gate
        )
        power = attn + self.hidden_size * self.num_kv_heads  # and the decay's W_g
        if self.mamba:
            inner = self.mamba_inner
            mamba = (  # W_in and W_out, W_x, W_dt
                3 * self.hidden_size * inner
                + inner * (self.mamba_dt_rank + 2 * self.mamba_d_state)
                + self.mamba_dt_rank * inner)
            return (
                self.kind_count("softmax") * (attn + mlp)
                + self.kind_count("mamba") * (mamba + mlp)
                + self.hidden_size * self.vocab_size
            )
        return (
            self.kind_count("sparse") * (sparse + mlp)
            + self.kind_count("lightning") * (lightning + mlp)
            + self.kind_count("power") * (power + mlp)
            + self.hidden_size * self.vocab_size
        )

    def _latent_param_count(self, experts: int) -> int:
        """Matmul parameters of a latent-attention model with ``experts``
        routed experts counted a layer: the ones a token RUNS
        (``experts_per_token``) for operations, all that are held for bytes."""
        d, h, r = self.hidden_size, self.num_heads, self.q_lora_rank
        attn = (
            (d * r + r * self.q_dim if r else d * self.q_dim) + d * self.latent_dim
            + self.kv_lora_rank * h * (self.qk_nope_head_dim + self.v_head_dim)
            + h * self.v_head_dim * d
        )
        if self.index_topk:  # the index's queries, its one key and its head weights
            attn += (r * self.index_heads * self.index_head_dim
                     + d * self.index_head_dim + d * self.index_heads)
        moe = 3 * d * (
            experts * self.moe_intermediate_size + self.shared_expert_size
        ) + d * self.router_width
        if self.shortcut_moe:
            # two attention sublayers and two dense MLPs a published layer, and
            # between them the experts (no shared one)
            return self.num_layers * (
                2 * (attn + 3 * d * self.intermediate_size) + moe
            ) + d * self.vocab_size
        return (
            self.kind_count("latent") * (attn + 3 * d * self.intermediate_size)
            + self.kind_count("latent_moe") * (attn + moe)
            + d * self.vocab_size
        )

    def _delta_moe_param_count(self, experts: int) -> int:
        """Matmul parameters of a ``solar_open2`` model with ``experts`` routed
        experts counted a layer (``_latent_param_count`` says which). Under a
        share the experts HELD are the bytes; a token's operations are its
        ``experts_per_token`` wherever they are held."""
        d, r = self.hidden_size, self.delta_low_rank
        moe = 3 * d * (
            experts * self.moe_intermediate_size + self.shared_expert_size
        ) + d * self.router_width
        softmax = 3 * d * self.q_dim + 2 * d * self.kv_dim  # q, o, gate; k, v
        delta = 4 * d * self.delta_dim + 2 * (d * r + r * self.delta_dim) + (
            d * self.delta_heads)
        return (
            self.kind_count("softmax") * (softmax + moe)
            + self.kind_count("delta") * (delta + moe)
            + d * self.vocab_size
        )

    def _window_moe_param_count(self, experts: int) -> int:
        """Matmul parameters of an ``exaone_moe`` model with ``experts`` routed
        experts counted an expert layer (``_delta_moe_param_count`` says
        which): q, k, v, o in every layer (k and v at the KV heads of the
        layer's KIND, v and o at the value's width), then the layer's own
        second half."""
        d = self.hidden_size
        attn = sum(
            d * self.q_dim + self.o_dim * d
            + d * self.kv_heads_of(m) * (self.head_dim + self.value_head_dim)
            for m in map(mixer_of, self.layer_kinds))
        moe = 3 * d * (
            experts * self.moe_intermediate_size + self.shared_expert_size
        ) + d * self.router_width
        dense = sum(1 for k in self.layer_kinds if self.layer_ffn(k) == "dense")
        return (
            attn + dense * 3 * d * self.intermediate_size
            + (self.num_layers - dense) * moe + d * self.vocab_size
        )

    def _cca_param_count(self, experts: int) -> int:
        """Matmul parameters of a ``zaya`` model with ``experts`` routed experts
        counted a layer (``_latent_param_count`` says which): q and o at the
        latent's query width, k and the two value halves at its KV width, the
        convolution grouped by head, the router's four matrices."""
        d, r, hd = self.hidden_size, self.router_hidden_size, self.head_dim
        attn = 2 * d * self.q_dim + 2 * d * self.kv_dim
        conv = self.cca_time1 * (self.num_heads + self.num_kv_heads) * hd * hd
        router = d * r + 2 * r * r + r * self.n_routed_experts
        return self.num_layers * (
            attn + conv + router + 3 * d * self.moe_intermediate_size * experts
        ) + d * self.vocab_size

    def _ssd_moe_param_count(self, experts: int) -> int:
        """Matmul parameters of a ``nemotron_h`` model with ``experts`` routed
        experts counted an expert layer (``_delta_moe_param_count`` says
        which): a layer is ONE of W_in and W_out; q, k, v, o; or the router,
        the experts' two matrices each and the shared expert's two."""
        d = self.hidden_size
        mamba = d * self.ssd_in_dim + self.ssd_inner * d
        attn = 2 * d * self.q_dim + 2 * d * self.kv_dim
        moe = 2 * d * (experts * self.moe_intermediate_size + self.shared_expert_size) + (
            d * self.router_width)
        return (
            self.kind_count("mamba2") * mamba + self.mixer_count("softmax") * attn
            + self.kind_count("experts") * moe + d * self.vocab_size)

    @property
    def total_matmul_param_count(self) -> int:
        """``matmul_param_count`` over every expert HELD, not only those a
        token runs: what a decode step of many rows reads."""
        if self.latent:
            return self._latent_param_count(self.n_routed_experts)
        if self.delta_moe:
            return self._delta_moe_param_count(self.n_routed_experts)
        if self.window_moe:
            return self._window_moe_param_count(self.n_routed_experts)
        if self.cca:
            return self._cca_param_count(self.n_routed_experts)
        if self.ssd_moe:
            return self._ssd_moe_param_count(self.n_routed_experts)
        return self.matmul_param_count

    def decode_flops_per_token(self, mean_kv_len: float = 0.0) -> float:
        """Model FLOPs per decoded token: 2·(matmul params) for the dense
        path plus the attention score/value dot-products (2 FLOPs × q_dim
        keys-side + values-side) at the mean resident KV length."""
        # a shortcut-connected layer attends twice: its sublayers are counted
        layers = len(self.layer_kinds) if self.shortcut_moe else self.num_layers
        attn = 4.0 * layers * self.q_dim * mean_kv_len
        if self.index_topk:
            # a token attends at most ``index_topk`` tokens, and scores every
            # visible token's index key with every index head
            attn = self.num_layers * (
                4.0 * self.q_dim * min(mean_kv_len, self.index_topk)
                + 2.0 * self.index_heads * self.index_head_dim * mean_kv_len)
        if self.kind_count("delta"):
            # softmax layers attend over the context; a delta-rule layer's
            # token costs its state whatever the context (decay, S^T k, the
            # outer product, S^T q: 7 D^2 a head)
            attn = 4.0 * self.kind_count("softmax") * self.q_dim * mean_kv_len + (
                7.0 * self.kind_count("delta") * self.delta_dim * self.delta_head_dim)
        if self.power:
            # a token costs its state whatever the context: decay and the outer
            # product a KV head (3 D d), S^T phi(q) a query head (2 D d)
            attn = float(self.kind_count("power") * self.power_state_dim * self.head_dim
                         * (3 * self.num_kv_heads + 2 * self.num_heads))
        if self.mamba:
            # the attention layers attend over the context; a Mamba layer's token
            # costs its state whatever the context (the decay, dt c B, the
            # multiply-add, the reduction against C: 6 a state entry, and an exp)
            attn = 4.0 * self.kind_count("softmax") * self.q_dim * mean_kv_len + (
                7.0 * self.kind_count("mamba") * self.mamba_inner * self.mamba_d_state)
        if self.ssd_moe:
            # as above with heads of state: the decay a head, dt x B^T, the
            # multiply-add and the reduction against C, 6 a state entry
            attn = 4.0 * self.mixer_count("softmax") * self.q_dim * mean_kv_len + (
                6.0 * self.kind_count("mamba2") * self.ssd_inner * self.mamba_d_state)
        if self.window_moe:
            # a window layer's token attends at most ``sliding_window`` keys;
            # a key costs a query head its q.k and its p v, each at its width
            attn = 2.0 * self.num_heads * (self.head_dim + self.value_head_dim) * (
                self.mixer_count("softmax") * mean_kv_len
                + self.mixer_count("window") * min(mean_kv_len, self.sliding_window))
        if self.looped:
            # the layers' weights and their attention once a PASS; the head once
            head = self.hidden_size * self.vocab_size
            return (2.0 * head + self.loop_steps * (
                2.0 * (self.matmul_param_count - head) + attn))
        return 2.0 * self.matmul_param_count + attn

    def train_flops_per_token(self, seq_len: int) -> float:
        """Model FLOPs per trained token: 3× the forward's cost (fwd + ~2×
        for backward through frozen base + LoRA), causal attention at mean
        key length ``seq_len / 2``."""
        return 3.0 * self.decode_flops_per_token(seq_len / 2.0)

    @property
    def model_type(self) -> str:
        """The HF model_type this config round-trips through
        ``from_hf_config`` as (used by HF-format snapshot export)."""
        if self.shortcut_moe:
            return "longcat_flash"
        if self.latent:
            return "glm_moe_dsa" if self.index_topk else "deepseek_v3"
        if self.delta_moe:
            return "solar_open2"
        if self.power:
            return "brumby"
        if self.mamba:
            return "jamba"
        if self.ssd_moe:
            return "nemotron_h"
        if self.window_moe:
            return "mimo_v2_flash" if self.window_sink else "exaone_moe"
        if self.cca:
            return "zaya"
        if self.hybrid:
            return "minicpm_sala"
        if self.looped or self.sublayer_out_norm:
            return "ouro"
        if self.rmsnorm_offset:
            return "gemma"
        if self.sliding_window is not None:
            return "mistral"
        return "qwen2" if self.attention_bias else "llama"

    @staticmethod
    def from_hf_config(hf) -> "ModelConfig":
        """Build from a transformers PretrainedConfig (Qwen2/Llama/Mistral/
        Gemma Config)."""
        get = lambda k, d=None: getattr(hf, k, d)
        num_heads = hf.num_attention_heads
        mt = str(get("model_type", ""))
        if mt.startswith("gemma") and mt != "gemma":
            # Gemma-2/3 add pre/post-FFN norms, logit softcapping, and
            # alternating SWA — loading them as Gemma-1 would silently
            # produce wrong logits (the state-dict mapper ignores keys it
            # doesn't know)
            raise ValueError(
                f"model_type {mt!r} is not supported (Gemma-1 only); "
                "its extra norms/softcapping would be silently dropped"
            )
        if mt not in KNOWN_MODEL_TYPES:
            # every key this function does not know is ignored, so an unknown
            # architecture would load, silently, as a dense GQA decoder
            raise ValueError(
                f"model_type {mt!r} is not supported (known: "
                f"{', '.join(t for t in KNOWN_MODEL_TYPES if t)}); loading it as "
                "a dense GQA decoder would silently drop what makes it differ"
            )
        gemma = mt == "gemma"
        hybrid: dict = {}
        mixers = get("mixer_types")
        if mixers is not None or mt == "minicpm_sala":
            if not mixers:
                raise ValueError(f"model_type {mt!r} needs its mixer_types list")
            if get("lightning_nkv", get("lightning_nh")) != get("lightning_nh"):
                raise ValueError(
                    "lightning layers with fewer k/v heads than q heads "
                    f"(lightning_nkv {get('lightning_nkv')} != lightning_nh "
                    f"{get('lightning_nh')}) are not supported"
                )
            if str(get("lightning_scale", "1/sqrt(d)")) != "1/sqrt(d)":
                raise ValueError(
                    f"lightning_scale {get('lightning_scale')!r} is not supported"
                )
            sparse = dict(get("sparse_config") or {})
            hybrid = dict(
                mixer_types=tuple(mixers),
                qk_norm=bool(get("qk_norm", False)),
                attn_use_rope=bool(get("attn_use_rope", True)),
                attn_output_gate=bool(get("attn_use_output_gate", False)),
                lightning_heads=int(get("lightning_nh", num_heads)),
                lightning_head_dim=int(
                    get("lightning_head_dim", None) or get("head_dim", None)
                    or hf.hidden_size // num_heads
                ),
                lightning_use_rope=bool(get("lightning_use_rope", True)),
                lightning_output_gate=bool(get("use_output_gate", False)),
                lightning_output_norm=bool(get("use_output_norm", False)),
                scale_emb=float(get("scale_emb", 1.0)),
                scale_depth=float(get("scale_depth", 0.0)),
                dim_model_base=int(get("dim_model_base", 0)),
                **{
                    f"sparse_{key}": int(sparse[key])
                    for key in ("kernel_size", "kernel_stride", "block_size", "topk",
                                "init_blocks", "window_size", "dense_len")
                    if key in sparse
                },
            )
        head_dim = get("head_dim", None) or hf.hidden_size // num_heads
        if mt in ("deepseek_v3", "glm_moe_dsa"):
            # the published ``head_dim`` of a glm_moe_dsa file is the rope width
            # and is read by nothing: the query head is nope + rope
            hybrid = _latent_fields(get, mt)
            head_dim = hybrid["qk_nope_head_dim"] + hybrid["qk_rope_head_dim"]
        if mt == "solar_open2":
            hybrid = _delta_moe_fields(get)
        if mt == "brumby":
            hybrid = _power_fields(get)
        if mt == "jamba":
            hybrid = _jamba_fields(get, vars(hf))
        if mt == "exaone_moe":
            hybrid = _window_moe_fields(get)
        if mt == "zaya":
            hybrid = _cca_fields(get)
        if mt == "mimo_v2_flash":
            hybrid = _swa_sink_moe_fields(get)
        if mt == "longcat_flash":
            hybrid = _shortcut_moe_fields(get)
            head_dim = hybrid["qk_nope_head_dim"] + hybrid["qk_rope_head_dim"]
        if mt == "ouro":
            hybrid = _looped_fields(get)
        if mt == "nemotron_h":
            hybrid = _ssd_moe_fields(get)
        act = str(get("hidden_activation", None) or get("hidden_act", "silu"))
        # Qwen2 configs carry sliding_window but gate it off by default
        window = get("sliding_window") if get("use_sliding_window", True) else None
        return ModelConfig(
            vocab_size=hf.vocab_size,
            hidden_size=hf.hidden_size,
            # a zaya file has no dense MLP and no such key: its experts' width
            intermediate_size=hybrid.pop("intermediate_size", None) or hf.intermediate_size,
            # a longcat_flash file counts its layers under ``num_layers``
            num_layers=hybrid.pop("num_layers", None) or hf.num_hidden_layers,
            num_heads=num_heads,
            num_kv_heads=get("num_key_value_heads", num_heads),
            head_dim=head_dim,
            rope_theta=hybrid.pop("rope_theta", get("rope_theta", 10000.0)),
            rms_norm_eps=hybrid.pop("rms_norm_eps", get("rms_norm_eps", 1e-6)),
            attention_bias=mt == "qwen2" or bool(get("attention_bias", False)),
            tie_word_embeddings=bool(get("tie_word_embeddings", False)),
            max_position_embeddings=get("max_position_embeddings", 32768),
            hidden_act="gelu_tanh" if "gelu" in act else "silu",
            rmsnorm_offset=gemma,
            scale_embeddings=gemma,
            sliding_window=int(window) if window else None,
            **hybrid,
        )


def _looped_fields(get) -> dict:
    """The ``ouro`` keys as ``ModelConfig`` fields: ``total_ut_steps`` passes
    over the one stack of layers, every ``layer_types`` entry that is run
    ``full_attention``. What the published file does not state (the two output
    norms and their order, the final norm after every pass, the gate's form:
    the readings under ``assumed`` in the benchmark's configuration file) is
    the LoopLM family's published description. What this program cannot run is
    refused by key."""
    def refuse(key: str, why: str):
        raise ValueError(f"model_type 'ouro': {key} {get(key)!r} is not supported: {why}")

    steps = get("total_ut_steps")
    if not isinstance(steps, int) or steps < 1:
        refuse("total_ut_steps", "the passes over the layers, a whole number of at least 1")
    if float(get("early_exit_threshold", 1)) != 1.0:
        refuse("early_exit_threshold",
               "under 1 rows stop at different passes, and a step here runs every "
               "pass for every row (the gates are computed and reported)")
    if get("use_sliding_window", False):
        refuse("use_sliding_window", "every layer attends its whole context")
    if get("sliding_window") is not None:
        refuse("sliding_window", "every layer attends its whole context")
    if get("rope_scaling") is not None:
        refuse("rope_scaling", "positions are rotated at rope_theta alone")
    kinds = get("layer_types")
    run = list(kinds or ())[: int(get("num_hidden_layers"))]
    if kinds is not None and (len(run) < int(get("num_hidden_layers"))
                              or set(run) != {"full_attention"}):
        refuse("layer_types", "one 'full_attention' entry for every layer that is run")
    return dict(loop_steps=steps, sublayer_out_norm=True)


def _cca_fields(get) -> dict:
    """The ``zaya`` keys as ``ModelConfig`` fields: every ``layer_types`` entry
    ``hybrid`` (compressed convolutional attention, then routed experts), the
    two convolutions' taps, the router's width, the experts under their own
    count keys, RoPE's base and share of a head from ``rope_parameters.hybrid``.
    What the published file does not state (the readings under ``assumed`` in
    ``perfbench/configs/zaya1-8b-L20.json``) is fixed in the program: the
    convolutions' groups and biases, the q-k mean, the value split, the
    router's three layers with the value it hands through depth, the scaled
    residual. A variant that is not implemented is REFUSED by key."""
    def refuse(key: str, why: str):
        raise ValueError(f"zaya with {key}={get(key)!r} is not supported: {why}")

    layers = get("layer_types")
    if not layers:
        raise ValueError("model_type 'zaya' needs its layer_types list")
    if set(layers) - {"hybrid"}:
        refuse("layer_types", "every layer is 'hybrid' (compressed convolutional "
               "attention, then routed experts); a sliding variant of the layer "
               f"({sorted(set(layers) - {'hybrid'})}) is not implemented")
    if get("sliding_window") is not None:
        refuse("sliding_window", "a layer attends over the whole context; a window "
               "is not implemented")
    if int(get("num_experts_per_tok", 1) or 0) != 1:
        refuse("num_experts_per_tok", "the router chooses ONE expert a token and "
               "weights it by its probability; top-k over it is not implemented")
    for key in ("cca_time0", "cca_time1"):
        if int(get(key, 2) or 0) != 2:
            refuse(key, "both convolutions have two taps (a slot's tail keeps ONE "
                   "token of each); another reach is not implemented")
    for key in ("attention_bias", "lm_head_bias"):
        if get(key, False):
            refuse(key, "the attention projections and the head carry no bias")
    if get("share") is not None:
        refuse("share", "this family reads no share yet: every expert and the whole "
               "vocabulary are held (one chip's share of the experts, with their "
               "exchange, is not implemented)")
    rope = dict(get("rope_parameters") or {})
    own = dict(rope.get("hybrid") or {})
    kind = str(own.get("rope_type", rope.get("rope_type", "default")))
    if get("rope_scaling") or kind != "default":
        refuse("rope_scaling" if get("rope_scaling") else "rope_parameters",
               "q and k are rotated by plain RoPE at rope_parameters.hybrid."
               "rope_theta; scaled positions are not implemented")
    for key, must in (("zaya_use_mod", False), ("zaya_use_eda", True),
                      ("scale_residual_merge", True)):
        if get(key) is not None and bool(get(key)) != must:
            refuse(key, "the router scores the published experts and no choice that "
                   "skips them, hands its value on through depth, and each sublayer "
                   "joins the stream through learned scales and shifts; the other "
                   "setting is not implemented")
    head_dim = int(get("head_dim") or get("hidden_size") // get("num_attention_heads"))
    share = float(own.get("partial_rotary_factor", get("partial_rotary_factor", 1.0)))
    width = int(get("moe_intermediate_size") or 0)
    return dict(
        mixer_types=tuple(layers),
        cca_time0=int(get("cca_time0", 2)), cca_time1=int(get("cca_time1", 2)),
        rotary_dim=int(head_dim * share),
        rope_theta=float(own.get("rope_theta", get("rope_theta", 10000.0))),
        router_hidden_size=int(get("router_hidden_size")),
        n_routed_experts=int(get("num_experts") or 0),
        experts_per_token=1,
        moe_intermediate_size=width, intermediate_size=width,
    )


def _window_moe_fields(get) -> dict:
    """The ``exaone_moe`` keys as ``ModelConfig`` fields: the mixers from
    ``layer_types`` (checked against ``sliding_windows``), each layer's second
    half from ``mlp_layer_types``, the experts under DeepSeek-V3's router keys
    and their own count keys (``num_experts``: the experts HELD under a
    ``share``, as ``_delta_moe_fields`` reads ``n_routed_experts``), RoPE's
    base from ``rope_parameters``. q and k take a per-head RMSNorm in every
    layer; only the window layers rotate. The multi-token-prediction module
    (``num_nextn_predict_layers``, ``mtp_*``) is a draft head no logit of the
    main head depends on: its keys are read by nothing. A variant that is not
    implemented is REFUSED by key."""
    def refuse(key: str, why: str):
        raise ValueError(f"exaone_moe with {key}={get(key)!r} is not supported: {why}")

    layers, mlps = get("layer_types"), get("mlp_layer_types")
    window = get("sliding_window")
    if not layers or not mlps or len(layers) != len(mlps):
        raise ValueError(
            "model_type 'exaone_moe' needs its layer_types and mlp_layer_types "
            "lists, one entry a published layer each")
    unknown = sorted(set(layers) - {"sliding_attention", "full_attention"})
    if unknown:
        refuse("layer_types", f"a layer is sliding_attention or full_attention, not {unknown}")
    if set(mlps) - {"dense", "sparse"}:
        refuse("mlp_layer_types", "a layer's second half is dense or sparse")
    windows = get("sliding_windows")
    if windows is not None and list(windows) != [
            int(window or 0) * (t == "sliding_attention") for t in layers]:
        refuse("sliding_windows", "every sliding_attention layer keeps sliding_window "
               "tokens and every full_attention layer 0; a window a layer of its own "
               "is not implemented")
    dense = int(get("first_k_dense_replace", 0) or 0)
    if list(mlps) != ["dense"] * dense + ["sparse"] * (len(mlps) - dense):
        refuse("first_k_dense_replace", "it disagrees with mlp_layer_types, which is "
               "what is read")
    rope = dict(get("rope_parameters") or {})
    if str(rope.get("rope_type", "default")) != "default" or get("rope_scaling") is not None:
        refuse("rope_parameters", "the window layers' q and k are rotated by plain "
               "RoPE at rope_theta; scaled positions are not implemented")
    _refuse_router_variants(get, refuse)
    held = int(get("num_experts") or 0)
    published = dict((get("share") or {}).get("published") or {})
    width = int(published.get("num_experts", held))
    return dict(
        mixer_types=tuple(layers), mlp_types=tuple(mlps), qk_norm=True,
        attn_use_rope=False,  # the full_attention layers; a window layer always rotates
        rope_theta=float(rope.get("rope_theta", get("rope_theta", 10000.0))),
        n_routed_experts=held,
        router_experts=width if width != held else 0,
        expert_shard=int(get("expert_shard", 0) or 0),
        n_shared_experts=int(get("num_shared_experts") or 0),
        experts_per_token=int(get("num_experts_per_tok") or 0),
        moe_intermediate_size=int(get("moe_intermediate_size") or 0),
        norm_topk_prob=bool(get("norm_topk_prob", True)),
        routed_scaling_factor=float(get("routed_scaling_factor", 1.0)),
    )


def _swa_sink_moe_fields(get) -> dict:
    """The ``mimo_v2_flash`` keys as ``ModelConfig`` fields: the mixers from
    ``hybrid_layer_pattern`` (0 a full layer, 1 a window layer), each layer's
    second half from ``moe_layer_freq`` (0 the dense MLP, 1 routed experts),
    the full layers' KV heads and the window layers' (``num_key_value_heads``,
    ``swa_num_key_value_heads``), q/k and v widths (``head_dim``,
    ``v_head_dim``), the rotated share of a head and a base a kind
    (``partial_rotary_factor``, ``rope_theta``, ``swa_rope_theta``), the
    window layers' sink, the value's scale, the experts under DeepSeek-V3's
    router keys and a ``share`` as ``_delta_moe_fields`` reads one. The norm's
    eps is ``layernorm_epsilon``. What the published file does not state (the
    readings under ``assumed`` in ``perfbench/configs/mimo-v2-flash-ep16-L7.json``)
    is fixed in the program. The multi-token-prediction layers have no key
    here and are not instantiated. A variant that is not implemented is
    REFUSED by key."""
    def refuse(key: str, why: str):
        raise ValueError(f"mimo_v2_flash with {key}={get(key)!r} is not supported: {why}")

    pattern, freq = get("hybrid_layer_pattern"), get("moe_layer_freq")
    window = get("sliding_window")
    if not pattern or not freq or len(pattern) != len(freq):
        raise ValueError(
            "model_type 'mimo_v2_flash' needs its hybrid_layer_pattern and "
            "moe_layer_freq lists, one entry a published layer each")
    if set(pattern) - {0, 1}:
        refuse("hybrid_layer_pattern", "a layer is 0 (full attention) or 1 (window)")
    if set(freq) - {0, 1}:
        refuse("moe_layer_freq", "a layer's second half is 0 (dense) or 1 (experts)")
    heads = get("num_attention_heads")
    if get("swa_num_attention_heads", heads) != heads:
        refuse("swa_num_attention_heads", "the window layers have the full layers' "
               "query heads (one W_o width); another count is not implemented")
    for key, full in (("swa_head_dim", "head_dim"), ("swa_v_head_dim", "v_head_dim")):
        if get(key, get(full)) != get(full):
            refuse(key, f"the window layers' widths are the full layers' ({full} "
                   f"{get(full)}); a width a kind is not implemented")
    if get("add_full_attention_sink_bias", False):
        refuse("add_full_attention_sink_bias", "the sink is a column of a window "
               "layer's softmax over its ring; the paged launch of a full layer "
               "has no such column")
    for key in ("sliding_window_size", "attention_chunk_size"):
        if get(key, window) != window:
            refuse(key, f"it must equal sliding_window {window}, which is what is "
                   "read; chunked attention is not implemented")
    if get("n_shared_experts") is not None:
        refuse("n_shared_experts", "an expert layer is the routed experts alone; a "
               "shared expert beside them is not implemented for this family")
    _refuse_router_variants(get, refuse)
    if str(get("topk_method", "noaux_tc")) != "noaux_tc":
        refuse("topk_method", "the router chooses by score plus "
               "e_score_correction_bias (noaux_tc) only")
    if get("attention_bias", False):
        refuse("attention_bias", "q, k, v and o carry no bias")
    if get("rope_scaling") is not None:
        refuse("rope_scaling", "q and k are rotated by plain RoPE at rope_theta / "
               "swa_rope_theta; scaled positions are not implemented")
    if str(get("hidden_act", "silu")) != "silu":
        refuse("hidden_act", "the gated MLPs and the experts are SiLU's")
    head_dim = int(get("head_dim"))
    held = int(get("n_routed_experts") or 0)
    published = dict((get("share") or {}).get("published") or {})
    width = int(published.get("n_routed_experts", held))
    scale = get("routed_scaling_factor")
    return dict(
        mixer_types=tuple("sliding_attention" if p else "full_attention" for p in pattern),
        mlp_types=tuple("sparse" if f else "dense" for f in freq),
        attn_use_rope=True,  # the full layers rotate too, at their own base
        rotary_dim=int(head_dim * float(get("partial_rotary_factor", 1.0))),
        rope_theta=float(get("rope_theta", 10000.0)),
        window_rope_theta=float(get("swa_rope_theta", get("rope_theta", 10000.0))),
        window_kv_heads=int(get("swa_num_key_value_heads", get("num_key_value_heads"))),
        v_head_dim=int(get("v_head_dim", head_dim)),
        window_sink=bool(get("add_swa_attention_sink_bias", False)),
        value_scale=float(get("attention_value_scale") or 1.0),
        rms_norm_eps=float(get("layernorm_epsilon", get("rms_norm_eps", 1e-6))),
        n_routed_experts=held,
        router_experts=width if width != held else 0,
        expert_shard=int(get("expert_shard", 0) or 0),
        experts_per_token=int(get("num_experts_per_tok") or 0),
        moe_intermediate_size=int(get("moe_intermediate_size") or 0),
        norm_topk_prob=bool(get("norm_topk_prob", True)),
        routed_scaling_factor=1.0 if scale is None else float(scale),
    )


#: the ``mamba_*`` keys ``_jamba_fields`` reads; another one is a variant
_MAMBA_KEYS = ("mamba_conv_bias", "mamba_d_conv", "mamba_d_state", "mamba_dt_rank",
               "mamba_expand", "mamba_proj_bias")


def _jamba_fields(get, keys) -> dict:
    """The ``jamba`` keys as ``ModelConfig`` fields: the layer pattern from
    ``attn_layer_period`` / ``attn_layer_offset`` (the published rule: layer
    ``i`` is attention where ``i % period == offset``), the ``mamba_*`` sizes,
    no RoPE. A variant that is not implemented is REFUSED by key, as
    ``_latent_fields`` does: routed experts beside the Mamba layers (the
    family's larger members), a bias on the Mamba projections, a convolution
    without its bias, a window, and any ``mamba_*`` key this function does not
    read (Mamba-2's heads and groups)."""
    def refuse(key: str, why: str):
        raise ValueError(f"jamba with {key}={get(key)!r} is not supported: {why}")

    if int(get("num_experts", 1) or 1) > 1:
        refuse("num_experts", "every layer's second half is the dense gated MLP; "
               "routed experts beside Mamba layers (expert_layer_period / _offset, "
               "num_experts_per_tok) are not implemented, and a guessed router is "
               "worse than none")
    for key in sorted(k for k in keys if k.startswith("mamba_") and k not in _MAMBA_KEYS):
        refuse(key, "the state-space layers are Mamba-1's (mamba_d_state, "
               "mamba_d_conv, mamba_dt_rank, mamba_expand); another mamba_* key "
               "names a variant that is not implemented")
    if get("mamba_proj_bias", False):
        refuse("mamba_proj_bias", "W_in and W_out carry no bias")
    if not get("mamba_conv_bias", True):
        refuse("mamba_conv_bias", "the convolution's bias is always read")
    if get("sliding_window") is not None:
        refuse("sliding_window", "the attention layers attend over the whole "
               "context; a window is not implemented")
    period, offset = get("attn_layer_period"), get("attn_layer_offset")
    if not period or offset is None:
        raise ValueError(
            "model_type 'jamba' needs attn_layer_period and attn_layer_offset")
    rank = get("mamba_dt_rank", "auto")
    hidden = int(get("hidden_size"))
    return dict(
        mixer_types=tuple(
            "attention" if i % int(period) == int(offset) else "mamba"
            for i in range(int(get("num_hidden_layers")))),
        attn_use_rope=False,
        mamba_d_state=int(get("mamba_d_state", 16)),
        mamba_d_conv=int(get("mamba_d_conv", 4)),
        mamba_expand=int(get("mamba_expand", 2)),
        mamba_dt_rank=-(-hidden // 16) if rank == "auto" else int(rank),
    )


#: ``hybrid_override_pattern``'s characters -> the names ``mixer_types`` holds
_PATTERN_NAMES = {"M": "mamba-2", "*": "attention-only", "E": "moe"}


def _ssd_moe_fields(get) -> dict:
    """The ``nemotron_h`` keys as ``ModelConfig`` fields: the layers from the
    characters of ``hybrid_override_pattern`` (whole, whatever depth is run),
    Mamba-2's sizes (``mamba_num_heads`` x ``mamba_head_dim`` channels: ``expand``
    is read by nothing), the experts under DeepSeek-V3's router keys with a
    ``share`` as ``_delta_moe_fields`` reads one, the shared expert's own width,
    the norm's eps from ``layer_norm_epsilon``. The attention layers rotate
    nothing: the family's published attention applies no rotary, so
    ``rope_theta`` and ``partial_rotary_factor`` are read by nothing (an
    ``assumed`` entry of the benchmark's file). A variant that is not
    implemented is REFUSED by key; the family's dense relu^2 MLP layer (``-``)
    is refused by name."""
    def refuse(key: str, why: str):
        raise ValueError(f"nemotron_h with {key}={get(key)!r} is not supported: {why}")

    pattern = get("hybrid_override_pattern")
    layers = int(get("num_hidden_layers"))
    if not pattern:
        raise ValueError("model_type 'nemotron_h' needs its hybrid_override_pattern")
    if len(pattern) < layers:
        refuse("hybrid_override_pattern", f"it places {len(pattern)} layers and "
               f"num_hidden_layers is {layers}")
    if "-" in pattern:
        refuse("hybrid_override_pattern", "'-' is the family's dense relu^2 MLP "
               "layer, which is not implemented: a layer is M (Mamba-2), * "
               "(attention) or E (experts)")
    unknown = sorted(set(pattern) - set(_PATTERN_NAMES))
    if unknown:
        refuse("hybrid_override_pattern", f"a layer is M, * or E, not {unknown}")
    for key in ("mamba_proj_bias", "use_bias", "attention_bias", "mlp_bias"):
        if get(key, False):
            refuse(key, "no projection of any layer carries a bias")
    if not get("use_conv_bias", True):
        refuse("use_conv_bias", "the convolution's bias is always read")
    if str(get("mlp_hidden_act", "relu2")) != "relu2":
        refuse("mlp_hidden_act", "an expert is W_down relu(W_up h)^2, ungated")
    if str(get("mamba_hidden_act", "silu")) != "silu":
        refuse("mamba_hidden_act", "the convolution's and the gate's activation is SiLU")
    _refuse_router_variants(get, refuse)
    if not get("norm_topk_prob", True):
        refuse("norm_topk_prob", "the chosen scores are normalised to sum to 1 "
               "before routed_scaling_factor")
    if get("sliding_window") is not None:
        refuse("sliding_window", "the attention layers attend over the whole "
               "context; a window is not implemented")
    if get("tie_word_embeddings", False):
        refuse("tie_word_embeddings", "the head is a matrix of its own")
    heads, groups = int(get("mamba_num_heads")), int(get("n_groups", 1))
    if heads % groups:
        refuse("mamba_num_heads", f"head h reads group h // (heads / n_groups) of "
               f"n_groups {groups}: the heads must be a multiple of the groups")
    held = int(get("n_routed_experts") or 0)
    published = dict((get("share") or {}).get("published") or {})
    width = int(published.get("n_routed_experts", held))
    return dict(
        mixer_types=tuple(_PATTERN_NAMES[c] for c in pattern),
        attn_use_rope=False,
        rms_norm_eps=float(get("layer_norm_epsilon", get("norm_eps", 1e-5))),
        ssd_heads=heads, ssd_head_dim=int(get("mamba_head_dim")), ssd_groups=groups,
        ssd_chunk=int(get("chunk_size", 128)),
        mamba_d_state=int(get("ssm_state_size")), mamba_d_conv=int(get("conv_kernel", 4)),
        n_routed_experts=held,
        router_experts=width if width != held else 0,
        expert_shard=int(get("expert_shard", 0) or 0),
        n_shared_experts=int(get("n_shared_experts") or 0),
        shared_expert_width=int(get("moe_shared_expert_intermediate_size") or 0),
        experts_per_token=int(get("num_experts_per_tok") or 0),
        moe_intermediate_size=int(get("moe_intermediate_size") or 0),
        norm_topk_prob=True,
        routed_scaling_factor=float(get("routed_scaling_factor", 1.0)),
    )


def _power_fields(get) -> dict:
    """The ``brumby`` keys as ``ModelConfig`` fields: Qwen3's shape keys, every
    layer a power-retention layer (per-head RMSNorm on q and k, full-width
    RoPE). The published config gives no degree: 2 is what runs, and a
    ``power_degree`` key that says otherwise is refused, like the variants of
    Qwen3's attention that are not implemented."""
    def refuse(key: str, why: str):
        raise ValueError(f"brumby with {key}={get(key)!r} is not supported: {why}")

    if get("use_sliding_window", False):
        refuse("use_sliding_window", "a power-retention layer keeps a state of every "
               "token it has seen; a window over it is not implemented")
    if get("rope_scaling") is not None:
        refuse("rope_scaling", "q and k are rotated by plain RoPE at rope_theta; "
               "scaled positions are not implemented")
    if int(get("power_degree", 2) or 2) != 2:
        refuse("power_degree", "the state is the symmetric SECOND power of a key")
    return dict(
        mixer_types=("power-retention",) * int(get("num_hidden_layers")), qk_norm=True)


def _refuse_router_variants(get, refuse, scoring: str = "sigmoid") -> None:
    """The routers ``models/moe.py`` does not run, for every family with
    DeepSeek-V3's key names. ``scoring`` is the family's own: sigmoid for every
    family but the one whose field function says softmax."""
    if get("n_group", 1) != 1 or get("topk_group", 1) != 1:
        refuse("n_group" if get("n_group", 1) != 1 else "topk_group",
               "grouped routing (choose groups, then experts inside them) is "
               "not implemented; n_group and topk_group must be 1")
    if str(get("scoring_func", scoring)) != scoring:
        refuse("scoring_func", f"the router scores by {scoring} only")


def _latent_fields(get, family: str = "deepseek_v3") -> dict:
    """The ``deepseek_v3`` keys as ``ModelConfig`` fields, and ``glm_moe_dsa``'s
    (the same keys, RoPE's base under ``rope_parameters``, and the ``index_*``
    keys of its learned index over tokens). A ``share`` is read as
    ``_delta_moe_fields`` reads one. The multi-token-prediction module
    (``num_nextn_predict_layers``) is a draft head no logit of the main head
    depends on: its key is read by nothing. A variant that is not implemented
    is REFUSED by name: every key this function did not read would be ignored,
    and the model would load as something else."""
    def refuse(key: str, why: str):
        raise ValueError(
            f"{family} with {key}={get(key)!r} is not supported: {why}")

    _refuse_router_variants(get, refuse)
    if str(get("topk_method", "noaux_tc")) != "noaux_tc":
        refuse("topk_method", "the router chooses by score plus "
               "e_score_correction_bias (noaux_tc) only")
    rope = dict(get("rope_parameters") or {})
    if get("rope_scaling") is not None:
        refuse("rope_scaling", "scaled RoPE (YaRN and its softmax-scale "
               "correction) is not implemented")
    if str(rope.get("rope_type", "default")) != "default":
        refuse("rope_parameters", "scaled RoPE (a rope_type that is not "
               "'default') is not implemented")
    if get("moe_layer_freq", 1) != 1:
        refuse("moe_layer_freq", "every layer after first_k_dense_replace is "
               "an expert layer; another period is not implemented")
    if get("attention_bias", False):
        refuse("attention_bias", "the latent projections carry no bias")
    index = {}
    if family == "glm_moe_dsa":
        if not get("indexer_rope_interleave", True):
            refuse("indexer_rope_interleave", "the index rotates interleaved "
                   "pairs (x[2i], x[2i+1]); the half-split layout is not implemented")
        if not get("rope_interleave", True):
            refuse("rope_interleave", "q_pe and k_pe are rotated in interleaved "
                   "pairs; the half-split layout is not implemented")
        index = dict(index_heads=int(get("index_n_heads")),
                     index_head_dim=int(get("index_head_dim")),
                     index_topk=int(get("index_topk")))
    held = int(get("n_routed_experts") or 0)
    published = dict((get("share") or {}).get("published") or {})
    width = int(published.get("n_routed_experts", held))
    fields = dict(
        kv_lora_rank=int(get("kv_lora_rank")),
        q_lora_rank=int(get("q_lora_rank") or 0),
        qk_nope_head_dim=int(get("qk_nope_head_dim")),
        qk_rope_head_dim=int(get("qk_rope_head_dim")),
        v_head_dim=int(get("v_head_dim")),
        n_routed_experts=held,
        router_experts=width if width != held else 0,
        expert_shard=int(get("expert_shard", 0) or 0),
        n_shared_experts=int(get("n_shared_experts") or 0),
        experts_per_token=int(get("num_experts_per_tok") or 0),
        moe_intermediate_size=int(get("moe_intermediate_size") or 0),
        first_dense_layers=int(get("first_k_dense_replace", 0)),
        norm_topk_prob=bool(get("norm_topk_prob", True)),
        routed_scaling_factor=float(get("routed_scaling_factor", 1.0)),
        **index,
    )
    if "rope_theta" in rope:
        fields["rope_theta"] = float(rope["rope_theta"])
    return fields


def _shortcut_moe_fields(get) -> dict:
    """The ``longcat_flash`` keys (LongCat-Flash-Chat) as ``ModelConfig``
    fields: the depth under ``num_layers``, the dense MLPs' width under
    ``ffn_hidden_size``, the experts' under ``expert_ffn_hidden_size``, the
    choices a token under ``moe_topk``, the router's further outputs under
    ``zero_expert_num``, the two latents' constants under ``mla_scale_*``, and
    a ``share`` as ``_latent_fields`` reads one. The router is a softmax for
    THIS family alone. A variant that is not implemented is REFUSED by name,
    as ``_latent_fields`` does."""
    def refuse(key: str, why: str):
        raise ValueError(
            f"longcat_flash with {key}={get(key)!r} is not supported: {why}")

    if str(get("zero_expert_type", "identity")) != "identity":
        refuse("zero_expert_type", "an expert that computes nothing returns its "
               "input (identity); another kind is not implemented")
    if str(get("attention_method", "MLA")) != "MLA":
        refuse("attention_method", "both sublayers' attention is latent (MLA)")
    if get("attention_bias", False):
        refuse("attention_bias", "the latent projections carry no bias")
    if get("rope_scaling") is not None:
        refuse("rope_scaling", "scaled RoPE (YaRN and its softmax-scale "
               "correction) is not implemented")
    if get("router_bias", False):
        refuse("router_bias", "the router's classifier carries no bias of its own; "
               "e_score_correction_bias enters the choice only")
    _refuse_router_variants(get, refuse, scoring="softmax")
    if get("norm_topk_prob", False):
        refuse("norm_topk_prob", "the chosen scores are weights as they are "
               "(times routed_scaling_factor); renormalising them is not implemented")
    if not get("q_lora_rank"):
        refuse("q_lora_rank", "the query is projected from its own normed latent")
    hidden, q_rank, kv_rank = (int(get(k)) for k in (
        "hidden_size", "q_lora_rank", "kv_lora_rank"))
    held = int(get("n_routed_experts") or 0)
    published = dict((get("share") or {}).get("published") or {})
    width = int(published.get("n_routed_experts", held))
    return dict(
        num_layers=int(get("num_layers")),
        intermediate_size=int(get("ffn_hidden_size")),
        shortcut_moe=True, router_softmax=True,
        kv_lora_rank=kv_rank, q_lora_rank=q_rank,
        qk_nope_head_dim=int(get("qk_nope_head_dim")),
        qk_rope_head_dim=int(get("qk_rope_head_dim")),
        v_head_dim=int(get("v_head_dim")),
        latent_q_scale=(hidden / q_rank) ** 0.5 if get("mla_scale_q_lora", False) else 1.0,
        latent_kv_scale=(hidden / kv_rank) ** 0.5 if get("mla_scale_kv_lora", False) else 1.0,
        n_routed_experts=held,
        router_experts=width if width != held else 0,
        expert_shard=int(get("expert_shard", 0) or 0),
        zero_experts=int(get("zero_expert_num") or 0),
        experts_per_token=int(get("moe_topk")),
        moe_intermediate_size=int(get("expert_ffn_hidden_size")),
        norm_topk_prob=False,
        routed_scaling_factor=float(get("routed_scaling_factor", 1.0)),
    )


def _delta_moe_fields(get) -> dict:
    """The ``solar_open2`` keys as ``ModelConfig`` fields: the layer pattern
    from ``gqa_layers`` over the layers that are run, ``linear_attn_config``,
    the experts, and the share (module docstring). A variant that is not
    implemented is REFUSED by name, as ``_latent_fields`` does."""
    def refuse(key: str, why: str):
        raise ValueError(
            f"solar_open2 with {key}={get(key)!r} is not supported: {why}")

    linear = dict(get("linear_attn_config") or {})
    if not linear or get("gqa_layers") is None:
        raise ValueError(
            "model_type 'solar_open2' needs its gqa_layers list and its "
            "linear_attn_config")
    if get("use_rope", False):
        refuse("use_rope", "the softmax layers rotate nothing (use_rope false); "
               "RoPE in them is not implemented")
    if get("kda_use_full_proj", False):
        refuse("kda_use_full_proj", "the decay and the output gate are low-rank "
               "pairs; full projections are not implemented")
    if get("first_k_dense_replace", 0) != 0:
        refuse("first_k_dense_replace", "every layer is an expert layer; a dense "
               "MLP layer is not implemented for this family")
    _refuse_router_variants(get, refuse)
    heads = int(linear.get("num_heads") or get("num_attention_heads"))
    if linear.get("num_kv_heads") not in (None, heads):
        raise ValueError(
            f"solar_open2 with linear_attn_config.num_kv_heads="
            f"{linear['num_kv_heads']!r} is not supported: the delta-rule layers' "
            "k and v have as many heads as q")
    layers = int(get("num_hidden_layers"))
    softmax = {int(i) for i in get("gqa_layers")}
    held = int(get("n_routed_experts") or 0)
    share = get("share")
    published = dict((share or {}).get("published") or {})
    width = int(published.get("n_routed_experts", held))
    head_dim = int(linear.get("head_dim") or get("head_dim"))
    return dict(
        mixer_types=tuple("gqa" if i in softmax else "kda" for i in range(layers)),
        attn_use_rope=False,
        attn_output_gate=bool(get("use_gqa_gate", False)),
        delta_heads=heads,
        delta_head_dim=head_dim,
        delta_conv_size=int(linear.get("short_conv_kernel_size", 4)),
        delta_low_rank=head_dim,
        delta_beta_scale=2.0 if get("kda_allow_neg_eigval", False) else 1.0,
        n_routed_experts=held,
        router_experts=width if width != held else 0,
        expert_shard=int(get("expert_shard", 0) or 0),
        n_shared_experts=int(get("n_shared_experts") or 0),
        experts_per_token=int(get("num_experts_per_tok") or 0),
        moe_intermediate_size=int(get("moe_intermediate_size") or 0),
        norm_topk_prob=bool(get("norm_topk_prob", True)),
        routed_scaling_factor=float(get("routed_scaling_factor", 1.0)),
    )


# Tiny config for unit/golden tests — shapes chosen to exercise GQA (heads !=
# kv_heads) while staying sub-millisecond on CPU.
TINY = ModelConfig(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=128,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    head_dim=16,
    rope_theta=10000.0,
    attention_bias=True,
    tie_word_embeddings=False,
)

# latent attention and routed experts at a size the CPU tests run: a dense
# first layer, then 8 experts, 2 a token, 1 shared (Kimi-VL-A3B's shape)
TINY_LATENT_MOE = ModelConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=3,
    num_heads=4, num_kv_heads=4, head_dim=24, rope_theta=10000.0,
    rms_norm_eps=1e-5, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8, n_shared_experts=1,
    experts_per_token=2, moe_intermediate_size=32, first_dense_layers=1,
    routed_scaling_factor=2.446,
)

# latent attention behind a learned index over tokens at a size the CPU tests
# run (GLM-5's shape): a low-rank query path, 4 index heads choosing 8 tokens,
# a dense first layer, then 2 of 16 experts a chip of 8, 4 a token, 1 shared
TINY_DSA = ModelConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=3,
    num_heads=4, num_kv_heads=4, head_dim=24, rope_theta=1000000.0,
    rms_norm_eps=1e-5, kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=20, index_heads=4, index_head_dim=16,
    index_topk=8, n_routed_experts=2, router_experts=16, n_shared_experts=1,
    experts_per_token=4, moe_intermediate_size=32, first_dense_layers=1,
    routed_scaling_factor=2.5,
)

# a gated delta-rule model with routed experts at a size the CPU tests run: a
# period of four (softmax at 0, delta rule at 1-3), 2 of 16 experts a chip of 8
TINY_DELTA_MOE = ModelConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=4,
    num_heads=4, num_kv_heads=2, head_dim=16, rms_norm_eps=1e-5,
    mixer_types=("gqa", "kda", "kda", "kda"), attn_use_rope=False,
    attn_output_gate=True, delta_heads=4, delta_head_dim=16, delta_low_rank=16,
    delta_beta_scale=2.0, n_routed_experts=2, router_experts=16,
    n_shared_experts=1, experts_per_token=4, moe_intermediate_size=32,
)

# a power-retention model at a size the CPU tests run: 10 query heads over 2 KV
# heads (five read one state, the sixth the next), a state of 136 x 16 a KV head
TINY_POWER = ModelConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=3,
    num_heads=10, num_kv_heads=2, head_dim=16, rope_theta=1000000.0,
    mixer_types=("power-retention",) * 3, qk_norm=True,
)

# a state-space model at a size the CPU tests run: one period's kinds (attention
# at 1, Mamba at 0, 2, 3), 4 query heads over ONE KV head, a state of 16 x 64
TINY_JAMBA = ModelConfig(
    vocab_size=256, hidden_size=32, intermediate_size=64, num_layers=4,
    num_heads=4, num_kv_heads=1, head_dim=16, tie_word_embeddings=True,
    mixer_types=("mamba", "attention", "mamba", "mamba"), attn_use_rope=False,
    mamba_d_state=16, mamba_dt_rank=8,
)

# a window model with routed experts at a size the CPU tests run: the published
# period (window, window, window, full) and one more, a dense layer 0 before
# expert layers, a ring of 8 tokens, 2 of 16 experts a chip of 8
TINY_EXAONE_MOE = ModelConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=5,
    num_heads=4, num_kv_heads=2, head_dim=16, rope_theta=1000000.0,
    rms_norm_eps=1e-5, sliding_window=8,
    mixer_types=("sliding_attention",) * 3 + ("full_attention",)
    + ("sliding_attention",) * 3 + ("full_attention",),
    mlp_types=("dense",) + ("sparse",) * 7, qk_norm=True, attn_use_rope=False,
    n_routed_experts=2, router_experts=16, n_shared_experts=1,
    experts_per_token=4, moe_intermediate_size=32, routed_scaling_factor=2.5,
)

# the second window family at a size the CPU tests run (MiMo-V2-Flash's shape):
# layer 0 full and dense, then one whole period (five window layers, one full);
# 2 KV heads in the full layers and 4 in the window layers under 8 query heads,
# q and k of 24 over v of 16, the first 8 of a head rotated at a base a kind, a
# sink a query head in the window layers, a ring of 8, 2 of 8 experts a chip of 4
TINY_SWA_SINK_MOE = ModelConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=7,
    num_heads=8, num_kv_heads=2, head_dim=24, rope_theta=5000000.0,
    rms_norm_eps=1e-5, sliding_window=8,
    mixer_types=("full_attention",) + ("sliding_attention",) * 4
    + ("full_attention",) + ("sliding_attention",) * 5 + ("full_attention",),
    mlp_types=("dense",) + ("sparse",) * 11, attn_use_rope=True, rotary_dim=8,
    window_rope_theta=10000.0, window_kv_heads=4, v_head_dim=16, window_sink=True,
    value_scale=0.707, n_routed_experts=2, router_experts=8,
    experts_per_token=3, moe_intermediate_size=32,
)

# compressed convolutional attention with an MLP router at a size the CPU tests
# run (ZAYA1-8B's shape): 4 query heads over 2 KV heads of 16 in the latent, the
# first 8 of a head rotated, 1 of 4 experts a token behind a router of width 16
TINY_CCA = ModelConfig(
    vocab_size=256, hidden_size=64, intermediate_size=32, num_layers=3,
    num_heads=4, num_kv_heads=2, head_dim=16, rope_theta=5000000.0,
    rms_norm_eps=1e-5, tie_word_embeddings=True, mixer_types=("hybrid",) * 3,
    cca_time0=2, cca_time1=2, rotary_dim=8, router_hidden_size=16,
    n_routed_experts=4, experts_per_token=1, moe_intermediate_size=32,
)

# a shortcut-connected expert model at a size the CPU tests run (LongCat-Flash's
# shape): two published layers of two latent sublayers each, 2 of 8 experts a
# chip of 4 and 4 that compute nothing behind a softmax router of 12, 3 a token
TINY_SCMOE = ModelConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
    num_heads=4, num_kv_heads=4, head_dim=24, rope_theta=10000000.0,
    rms_norm_eps=1e-5, kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, shortcut_moe=True, router_softmax=True,
    zero_experts=4, n_routed_experts=2, router_experts=8, experts_per_token=3,
    moe_intermediate_size=32, norm_topk_prob=False, routed_scaling_factor=6.0,
    latent_q_scale=(64 / 48) ** 0.5, latent_kv_scale=2 ** 0.5,
)

QWEN2_0_5B = ModelConfig(
    vocab_size=151936, hidden_size=896, intermediate_size=4864, num_layers=24,
    num_heads=14, num_kv_heads=2, head_dim=64, rope_theta=1000000.0,
    attention_bias=True, tie_word_embeddings=True,
)

QWEN2_7B = ModelConfig(
    vocab_size=152064, hidden_size=3584, intermediate_size=18944, num_layers=28,
    num_heads=28, num_kv_heads=4, head_dim=128, rope_theta=1000000.0,
    attention_bias=True, tie_word_embeddings=False,
)

QWEN2_72B = ModelConfig(
    vocab_size=152064, hidden_size=8192, intermediate_size=29568, num_layers=80,
    num_heads=64, num_kv_heads=8, head_dim=128, rope_theta=1000000.0,
    attention_bias=True, tie_word_embeddings=False,
)

LLAMA3_8B = ModelConfig(
    vocab_size=128256, hidden_size=4096, intermediate_size=14336, num_layers=32,
    num_heads=32, num_kv_heads=8, head_dim=128, rope_theta=500000.0,
    rms_norm_eps=1e-5, attention_bias=False, tie_word_embeddings=False,
)

MISTRAL_7B = ModelConfig(  # v0.1: 4k sliding window (v0.2+ configs drop it)
    vocab_size=32000, hidden_size=4096, intermediate_size=14336, num_layers=32,
    num_heads=32, num_kv_heads=8, head_dim=128, rope_theta=10000.0,
    rms_norm_eps=1e-5, attention_bias=False, tie_word_embeddings=False,
    sliding_window=4096,
)

GEMMA_2B = ModelConfig(  # MQA (1 kv head), GeGLU, +1 norm offset, tied
    vocab_size=256000, hidden_size=2048, intermediate_size=16384, num_layers=18,
    num_heads=8, num_kv_heads=1, head_dim=256, rope_theta=10000.0,
    rms_norm_eps=1e-6, attention_bias=False, tie_word_embeddings=True,
    hidden_act="gelu_tanh", rmsnorm_offset=True, scale_embeddings=True,
    max_position_embeddings=8192,
)

GEMMA_7B = ModelConfig(
    vocab_size=256000, hidden_size=3072, intermediate_size=24576, num_layers=28,
    num_heads=16, num_kv_heads=16, head_dim=256, rope_theta=10000.0,
    rms_norm_eps=1e-6, attention_bias=False, tie_word_embeddings=True,
    hidden_act="gelu_tanh", rmsnorm_offset=True, scale_embeddings=True,
    max_position_embeddings=8192,
)

# a looped model at a size the CPU tests run: 2 weight layers, 3 passes (so
# that cache layer u * L + l and l * T + u differ), no grouping of the heads
TINY_OURO = ModelConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
    num_heads=4, num_kv_heads=4, head_dim=16, rope_theta=10000.0,
    rms_norm_eps=1e-6, loop_steps=3, sublayer_out_norm=True,
)

# a state-space expert model of one sublayer a layer at a size the CPU tests run
# (Nemotron-3-Nano's shape): the three kinds (M E M * E M), 4 heads of state
# 8 x 16 in two groups (chunks of 12), 4 query heads over 2 KV heads, 4 of 8 experts a chip of
# 2, 3 a token, ungated relu^2, a shared expert twice an expert's width
TINY_NEMOTRON_H = ModelConfig(
    vocab_size=256, hidden_size=64, intermediate_size=48, num_layers=6,
    num_heads=4, num_kv_heads=2, head_dim=16, rms_norm_eps=1e-5,
    mixer_types=("mamba-2", "moe", "mamba-2", "attention-only", "moe", "mamba-2", "moe"),
    attn_use_rope=False, ssd_heads=4, ssd_head_dim=8, ssd_groups=2, ssd_chunk=12,
    mamba_d_state=16, n_routed_experts=4, router_experts=8, n_shared_experts=1,
    shared_expert_width=96, experts_per_token=3, moe_intermediate_size=48,
    routed_scaling_factor=2.5,
)

PRESETS: dict[str, ModelConfig] = {
    "tiny": TINY,
    "tiny-ouro": TINY_OURO,
    "tiny-latent-moe": TINY_LATENT_MOE,
    "tiny-delta-moe": TINY_DELTA_MOE,
    "tiny-power": TINY_POWER,
    "tiny-jamba": TINY_JAMBA,
    "tiny-exaone-moe": TINY_EXAONE_MOE,
    "tiny-swa-sink-moe": TINY_SWA_SINK_MOE,
    "tiny-dsa": TINY_DSA,
    "tiny-cca": TINY_CCA,
    "tiny-scmoe": TINY_SCMOE,
    "tiny-nemotron-h": TINY_NEMOTRON_H,
    "qwen2.5-0.5b": QWEN2_0_5B,
    "qwen2.5-7b": QWEN2_7B,
    "qwen2.5-72b": QWEN2_72B,
    "llama-3-8b": LLAMA3_8B,
    "mistral-7b": MISTRAL_7B,
    "gemma-2b": GEMMA_2B,
    "gemma-7b": GEMMA_7B,
}


def preset_for_model_name(name: str) -> ModelConfig | None:
    """Map an HF-style model id (e.g. 'Qwen/Qwen2.5-7B-Instruct') to a preset."""
    low = name.lower()
    if low == "tiny":  # exact only — "tiny" substrings occur in real model ids
        return TINY
    if "r1-distill" in low:
        # reference recipe 4's model family: tensor dims match the Qwen2/Llama
        # presets but NOT the RoPE config (R1-Distill-Qwen-7B derives from
        # Qwen2.5-MATH-7B: rope_theta 1e4 vs the preset's 1e6, 131k context).
        # A preset would silently rotate positions at the wrong frequencies —
        # force config.json-driven loading instead.
        return None
    for key, cfg in PRESETS.items():
        # tiny: exact-match only; mistral-7b: guarded below (the v0.1 preset
        # must not claim v0.2/v0.3 checkpoints, which drop the window)
        if key in ("tiny", "mistral-7b"):
            continue
        if key in low.replace("_", "-"):
            return cfg
    if "0.5b" in low and "qwen" in low:
        return QWEN2_0_5B
    if "7b" in low and "qwen" in low:
        return QWEN2_7B
    if "72b" in low and "qwen" in low:
        return QWEN2_72B
    if "8b" in low and "llama" in low:
        return LLAMA3_8B
    if (
        "mistral-7b" in low.replace("_", "-")
        and "mixtral" not in low
        and not any(v in low for v in ("v0.2", "v0.3"))
        # v0.2/v0.3 drop the sliding window (and v0.3 grows the vocab);
        # the v0.1 preset would wrongly cap their sequence length — let
        # those fall through to config.json-driven loading
    ):
        return MISTRAL_7B
    if "gemma-2b" in low.replace("_", "-"):
        return GEMMA_2B
    if "gemma-7b" in low.replace("_", "-"):
        return GEMMA_7B
    return None
