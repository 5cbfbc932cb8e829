"""A decoder whose layers differ in kind (MiniCPM-SALA): block-sparse attention
layers (``minicpm4``, InfLLM-V2) beside lightning linear-attention layers.

``transformer.forward`` / ``init_params`` hand over to this module when
``cfg.mixer_types`` is set; a dense GQA model never reaches it. What is shared
with the dense decoder is imported from it: ``_proj`` (the LoRA delta),
``rms_norm``, RoPE, ``_head``, a layer's MLP half (``_mlp_half``) and the
stack initialisers. This module holds the two mixers and the cache plumbing.

**Parameters.** One stack per layer KIND under ``params["layers"]``:
``{"sparse": {...[n_sparse, ...]}, "lightning": {...[n_lightning, ...]}}``,
each in the order its layers appear in the model. ``cfg.layer_runs`` walks the
published order: runs of like layers are scanned (no cache) or unrolled
(cache), so a run of eight lightning layers compiles one body.

**Equations** (``c = cfg.residual_scale``, ``h = RMSNorm(x)``)::

    x0 = scale_emb * Embed(ids)
    x <- x + c * Mixer(h);   x <- x + c * W_down(silu(W_gate h) * (W_up h))
    logits = W_head(RMSNorm(x) * dim_model_base / hidden)

    lightning: q, k, v = W h  [T, H, D];  q, k <- RMSNorm_D;  q, k <- RoPE
               S_t = lam_h S_{t-1} + k_t v_t^T;  o_t = S_t^T q_t / sqrt(D)
               y = W_o(RMSNorm_{H*D}(o) * sigmoid(W_z h))
    sparse:    q [T, H, D], k, v [T, K, D];  q, k <- RMSNorm_D;  no RoPE
               o = attention over the chosen blocks (ops/sparse_attention.py)
               y = W_o(o * sigmoid(W_z h))

**Three modes**, by the cache handed in:

* no cache: the whole sequence (training, scoring). Rows are packed to the
  left first (the learner left-pads prompts, and a sparse layer's blocks are
  counted from a row's first real token) and unpacked before the head.
* a paged cache and one token a row: a decode step. Sparse layers write K/V to
  pages, complete a pooled key every ``kernel_stride`` tokens and attend over
  the chosen pages; lightning layers step their state.
* a paged cache with ``"segment_start"``: one page-aligned SEGMENT of a prompt
  prefill, every row at the same offset. Sparse layers write the segment's
  pages whole, choose once for the segment and attend over the row's pages a
  segment's width of keys at a time, the choice the mask of the fold the
  full-attention layers run (``_segment_softmax``); lightning layers run the
  segment chunked from the carried state. A prompt is prefilled
  segment after segment (``engine/paged_engine.py``): a 20k-token prompt at
  once would need the MLP's activations and the attention scores for all of it.

**Latent attention with routed experts** (``cfg.latent``: DeepSeek-V3's block,
Kimi-VL-A3B's language model) is two more kinds through the same three modes:
"latent" (a dense gated MLP) and "latent_moe" (``models/moe.py`` beside ONE
shared expert, which is this module's ``_mlp_half``)::

    q = W_q h [T, H, nope + rope];  [c_raw, k_pe] = W_kva h;  c = RMSNorm(c_raw)
    q_pe, k_pe <- RoPE (interleaved pairs);  the cache row is [c, k_pe]
    no cache, segment: K, V = c W_kvb a head, scores over nope + rope (expanded)
    decode:            the heads attend over the latent rows (absorbed)
    y = W_o o;   x <- x + y;   x <- x + Shared(h') + sum_k w_k E_k(h')

Its cache has ``k`` alone: one latent array ``[pages, page, latent_row]`` a
layer (``rank + rope`` values and zeros to whole 128-lane tiles), no kv-head
axis and no V (``v`` is an empty tuple), and three counters of [2] int32, summed
over layers and decode steps: ``moe_stats`` (token-expert pairs computed, the
fullest expert's), ``moe_blocks`` (the blocks of the experts' grouped form
that ran and that were laid, ``models/moe.py``: every expert family's, and the
one counter a prefill's segments carry too) and ``latent_stats`` (the (row,
page) pairs absorbed attention covered, the pages it fetched: a page that a
group's rows share is fetched once, ``ops/latent_attention.py``). With
``cfg.q_lora_rank`` the query is ``q = W_qb RMSNorm(W_qa h)``, and an expert
layer is told which experts this program holds (``cfg.held_experts``) like the
families below.

**A shortcut-connected expert layer** (``cfg.shortcut_moe``: ``longcat_flash``,
LongCat-Flash-Chat) is two more kinds, the two SUBLAYERS of one published
layer, "latent_fork" then "latent_join" (``n_k``, ``m_k`` the norms before
sublayer ``k``'s attention and MLP)::

    fork:  a = x + MLA_0(n_0 x);  u = m_0 a;  e = Experts(u);  b = a + MLP_0(u)
    join:  c = b + MLA_1(n_1 b);  y = c + MLP_1(m_1 c) + e

Each sublayer has a stack, a page pool and adapters of its own, like any layer
(``cfg.layer_kinds`` counts sublayers: a cache holds ``2 x num_layers`` latent
arrays), and the layer loop carries ``(x, e)`` for this family alone, as it
carries the MLP router's value for ``zaya``: the experts run ONCE, where the
fork has their input, and what they gave rides to the join. ``Experts`` is a
softmax router over the routed experts and ``cfg.zero_experts`` outputs that
compute nothing (``models/moe.py``); no shared expert. The normed query latent
is multiplied by ``cfg.latent_q_scale`` and the normed KV latent by
``cfg.latent_kv_scale`` before their up-projections (the cached row holds the
scaled latent; the rotary key is not scaled). ``moe_zero`` [1] int32 counts
the pairs that chose an expert that computes nothing.

**A learned index over tokens** (``cfg.index_topk``: ``glm_moe_dsa``, GLM-5)
stands beside latent attention in every layer (``ops/token_index.py``)::

    q_I = W_qI c_q [T, H_I, D_I];  k_I = LayerNorm(W_kI h) [T, D_I];  w = W_w h [T, H_I]
    q_I, k_I <- RoPE on their first ``rope`` values (interleaved pairs)
    I[t, s] = sum_h w[t, h] relu(q_I[t, h] . k_I[s]);  token t attends the
    min(index_topk, t + 1) tokens of largest I[t, .] and no other

``k_I`` is cached: the cache's ``v`` slot holds one array ``[pages, page,
D_I]`` a layer under the latent rows' own page table, so whatever aliases,
copies or grows a prompt's pages does it to both. No cache: the ``[S, S]``
scores and the choice as a mask over the expanded form. A prefill segment:
the segment's scores over the row's cached keys, the choice as a mask over the
folds (``expanded_segment``: the fold kernel reads a tile of it on a TPU).
Decode: the scores walk the page table as absorbed attention does (a group's
shared blocks of keys once), and then either the SAME launch as a model
without an index runs, ``latent_attention.absorbed_decode`` walking the rows'
pages whole behind the choice's mask (where the launch runs and a group's
rows gather at least as many tokens as the table holds:
``_choice_walks_pages``), or the chosen positions are read off the mask and
their latent rows GATHERED ``[B, index_topk, latent_row]`` and attended in the
absorbed form.
The choice is not differentiated and the index carries no adapter. The round's
counter ``index_stats`` [2] int32 takes the place of ``latent_stats``: tokens
attended and tokens visible, a live row, layer and decode step, in units of
``INDEX_COUNT_UNIT``.

**A gated delta rule with routed experts** (``solar_open2``) is two more
kinds, "softmax" and "delta", each followed by routed experts told which
experts this program HOLDS (``cfg.held_experts``: one chip's share of a layer)
beside one shared expert (``_mlp_half``), with no RoPE anywhere::

    softmax: q [T, H, D], k, v [T, K, D] = W h;  o = causal softmax attention
             y = W_o(o * sigmoid(W_g h))
    delta:   [q', k', v] = silu(conv4([W_q h, W_k h, W_v h]))   causal, depth-wise
             q = l2norm(q') / sqrt(D);  k = l2norm(k')          a head
             g = -exp(A_log) * softplus(W_fb W_fa h + dt_bias)  [T, H, D], a = exp(g)
             beta = beta_scale * sigmoid(W_b h)                 [T, H]
             S_t = (I - beta k k^T) diag(a) S_{t-1} + beta k v^T;  o_t = S_t^T q_t
             y = W_o(RMSNorm_D(o) * sigmoid(W_gb W_ga h))
    x <- x + y;   x <- x + Shared(h') + sum_{k held here} w_k E_k(h')

A softmax layer keeps K/V pages (decode through ``ops/paged.py``'s kernel, a
prefill segment over the row's pages a segment's width of keys at a time: the
fold ``ops/latent_attention.py::expanded_segment`` dispatches, the Mosaic
kernel on a TPU); a delta layer keeps a
THIRD kind of slot state: ``delta`` (a float32 ``[B, H, D, D]`` state) and
``conv`` (the last three tokens' ``[W_q h, W_k h, W_v h]``, ``[B, 3, 3 H D]``),
tuples over the delta layers. ``moe_routed`` [1] int32 counts the pairs the
router chose over ALL experts (``moe_stats`` counts those of experts held).

**Power retention** (``brumby``) is one more kind, "power", in every layer of
its model: Qwen3's block (this module's ``_block`` with ``qk_norm``, RoPE and
the dense MLP) around ``ops/power_retention.py``::

    q [T, H, D], k, v [T, K, D] = W h;  q, k <- RMSNorm_D;  q, k <- RoPE
    g = logsigmoid(W_g h + b_g)  [T, K] float32, one value a KV head
    S_t = e^{g_t} S_{t-1} + phi(k_t) v_t^T;  z_t = e^{g_t} z_{t-1} + phi(k_t)
    o_t = S_t^T phi(q_t) / (z_t . phi(q_t) + eps);   y = W_o o

It keeps a FOURTH kind of slot state and nothing else: ``power`` (a float32
``[B, K, D2, D]`` state a layer, ``D2 = D (D + 1) / 2``, ONE a KV head, read by
the query heads that share it) and ``power_z`` (its normaliser ``[B, K, D2]``).
No layer keeps a page: ``k`` and ``v`` are empty tuples. ``power_stats`` [1]
int32 counts the (live row, layer) states the decode steps read and wrote.

**State-space layers** (``jamba``, AI21-Jamba2-3B) are one more kind, "mamba"
(Mamba-1, ``ops/selective_scan.py``), beside "softmax" layers (here
multi-query attention without RoPE and without a gate), each followed by the
dense gated MLP (``_mlp_half``; this family's "softmax" layers have no routed
experts), with no positional encoding anywhere::

    [u, z]      = W_in h                                   u first, z second
    c_t         = silu(b_conv + conv4(u)_t)                causal, depth-wise
    [d, B, C]_t = W_x c_t;   d, B, C <- RMSNorm            three inner norms
    dt_t        = softplus(W_dt d_t + b_dt);   A = -exp(A_log)      float32
    h_t         = exp(dt_t A) h_{t-1} + (dt_t c_t) B_t^T;  y_t = h_t C_t + D c_t
    out         = W_out (y_t * silu(z_t))

A Mamba layer keeps a FIFTH kind of slot state: ``ssm`` (a float32
``[B, d_state, d_inner]`` state, the published ``[d_inner, d_state]``
transposed so that the channels lie along the lanes) and its convolution
window, which is the entry ``conv`` (the last three tokens' ``u``,
``[B, 3, d_inner]``), tuples over the Mamba layers. ``ssm_stats`` [1] int32
counts the (live row, layer) states the decode steps read and wrote.

**Window layers with routed experts** (``exaone_moe``, K-EXAONE-236B-A23B) are
one more kind, "window" (``sliding_attention``), beside "softmax" layers
(``full_attention``; here with a per-head norm of q and k, no gate and no
RoPE). The second half is the LAYER's (``cfg.layer_ffn(kind)``): routed experts
told what this program holds beside one shared expert, or the dense gated MLP
where the kind carries ``_dense`` (the model's layer 0)::

    q [T, H, D], k, v [T, K, D] = W h;  q, k <- RMSNorm_D
    window: q, k <- RoPE;  token t attends tokens max(0, t - W + 1) .. t
    softmax: no positional encoding;  token t attends 0 .. t
    y = W_o softmax(q k^T / sqrt(D)) v

A window layer keeps a SIXTH kind of slot state and no page: ``win_k`` /
``win_v``, a RING of the last W tokens' K (rotated before it is kept, so the
ring's order does not matter to a softmax) and V, ``[B, K, W, D]`` in the
cache's type, written at ``position % W``, tuples over the window layers.
``window_stats`` [2] int32 counts, per live row, window layer and decode step,
the keys attended and the keys a full layer would attend, in units of
``WINDOW_COUNT_UNIT`` keys rounded up.

**Compressed convolutional attention with an MLP router** (``zaya``,
ZAYA1-8B) is one more kind, "cca", in every layer of its model: softmax
attention that runs whole in a latent of H + K heads below the hidden width,
then routed experts chosen one a token (``models/moe.py::route_mlp``), each
sublayer joined to the stream through learned scales and shifts (``_merge``)::

    h = RMSNorm(x);  q~ = W_q h [T, H, D];  k~ = W_k h [T, K, D];  u = [q~ | k~]
    c1_t = a_0 u_{t-1} + a_1 u_t + b_1                       depth-wise; zeros before a row
    c2_t[g] = C_0[g] c1_{t-1}[g] + C_1[g] c1_t[g] + b_2[g]   a head g of the H + K
    m^q[i] = (q~[i] + k~[i // (H/K)]) / 2;   m^k[j] = mean of its group's m^q
    q[i] = c2[i] + m^q[i];   k[j] = c2[H + j] + m^k[j]
    q <- sqrt(D) l2norm(q);  k <- tau_j sqrt(D) l2norm(k);  RoPE on the first rotary_dim
    v_t = [W_v1 h_t | W_v2 h_{t-1}]          the later half of the KV heads a token late
    y = W_o softmax(q k^T / sqrt(D)) v;   x <- (a_r x + b_r) + (a_o y + b_o)
    x <- (a_r' x + b_r') + (a_o' p_e E_e(h') + b_o'),   e chosen by the router from h' and r_{l-1}

A layer keeps K/V pages like a "softmax" layer (k and v AFTER all of the above)
AND a SEVENTH kind of slot state, ``cca_tail`` ``[B, 2 (H + K) D + K D / 2]``:
the last token's ``u``, its ``c1`` and ``W_v2 h``, what the next token's
convolutions and value read. A prefill segment takes it at each ROW's last
real token, a candidate from its prompt. The router's ``r`` is a second value
that flows from layer to layer: for this family alone the layer loop carries
``(x, r)``.

**Layers of ONE sublayer** (``nemotron_h``, NVIDIA-Nemotron-3-Nano-30B-A3B) are
three kinds, each ``x <- x + Mixer(RMSNorm(x))`` and nothing after it: a kind
states which halves it has (``cfg.layer_ffn``: "none" for the first two;
``mixer_of("experts")`` names no mixer)::

    mamba2:        [z | xBC | dt] = W_in h                       no bias
                   xBC = silu(conv4(xBC) + b_conv)               x, B and C alike
                   x [T, H, P];  B, C [T, G, N];  head h reads group h // (H / G)
                   dt = softplus(dt + dt_bias) a head, float32;  A = -exp(A_log) a head
                   S_t = exp(dt_t A) S_{t-1} + (dt_t x_t) B_t^T;  y_t = S_t C_t + D x_t
                   out = W_out (RMSNorm_group(y * silu(z)) * w)  the gate BEFORE the norm
    softmax_alone: "softmax" above without RoPE, gate or norm, and no second half
    experts:       x + Shared(h) + sum_{k held here} w_k E_k(h),  E(h) = W_down relu(W_up h)^2

A "mamba2" layer (``ops/ssd.py``) keeps the state-space entries of a slot's
state under the names the "mamba" kind uses, at its own shapes: ``ssm``, a
float32 ``[B, H, P, N]`` state a layer (64 x 64 x 128 = 2 MiB a slot at the
published sizes, the 128 state columns along the lanes, no padding), and
``conv``, the last three tokens' ``xBC`` kept FLAT, ``[B, 3 (E + 2 G N)]``
(three rows on the sublanes would be padded to a tile or moved by a copy a
step: ``ops/ssd.py::conv_step``). A
"softmax_alone" layer keeps K/V pages as a "softmax" layer does; an "experts"
layer keeps nothing. ``ssm_stats`` counts the (live row, "mamba2" layer)
states the decode steps read and wrote, the expert counters as above.

The second window family (``mimo_v2_flash``, MiMo-V2-Flash) is the SAME two
kinds with what its configuration states (``models/configs.py``): ``K_l`` KV
heads a kind (the pages' ``num_kv_heads``, the rings' ``window_kv_heads``),
k of ``head_dim`` and v of ``v_head_dim`` (pages, rings and ``W_o`` at the two
widths), RoPE on a head's first ``rotary_dim`` values in both kinds with a base
a kind, ``v <- value_scale * v`` before it is kept, no q/k norm, and in a
window layer one learned sink logit a query head (the stack's ``sink`` [H])::

    p[t, j] = exp(s[t, j]) / (exp(sink_h) + sum_j' exp(s[t, j']))

in all three modes, float32: a column of the softmax whose value is nothing.
A cached key takes ``cfg.key_row`` lanes (192 -> 256, zeros after its values:
``_to_row``) in the pages and in the rings; q is given as many where it meets
them, and the scores keep ``1 / sqrt(head_dim)``.

The cache is a dict: ``k``/``v``/``pooled`` (a tuple over SPARSE layers: pages
``[K, pages, block, hd]`` and selector keys ``[B, NP, K, hd]``), ``lin`` (a
tuple over LIGHTNING layers of ``[B, H, D, D]`` float32), ``lengths`` [B],
``page_indices`` [B, W], and optionally ``alive`` [B] and ``sel_stats`` [2]
int32 (blocks attended, blocks visible, summed over sparse layers and alive
rows: a counter the engine carries through a round).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.models.configs import SHORTCUT_KINDS, ModelConfig, mixer_of
from distrl_llm_tpu.models.transformer import (
    _head, _init_around_layers, _init_layer_stack, _mlp_half, _normal_init, _proj,
    _slice_layer, apply_rope, rms_norm, rope_cos_sin,
)
from distrl_llm_tpu.models.moe import moe_half, route_mlp
from distrl_llm_tpu.ops.attention import attention, attention_reference
from distrl_llm_tpu.ops.delta_attention import (
    delta_chunked, delta_step, l2norm, short_conv,
)
from distrl_llm_tpu.ops.latent_attention import (
    FOLD_MASK_DTYPE, absorbed_attention, absorbed_decode, absorbed_decode_impl,
    absorbed_output,
    absorbed_query, expanded_attention, expanded_finish, expanded_segment,
    rope_interleaved, shared_page_walk, shared_pages_per_block, split_kvb,
)
from distrl_llm_tpu.ops.linear import linear
from distrl_llm_tpu.ops.linear_attention import lightning_chunked, lightning_step
from distrl_llm_tpu.ops.power_retention import init_state, power_chunked, power_step
from distrl_llm_tpu.ops.selective_scan import ssm_chunked, ssm_step
from distrl_llm_tpu.ops.ssd import conv_step, gated_group_norm, ssd_chunked, ssd_step
from distrl_llm_tpu.ops.token_index import (
    chosen_mask, chosen_tokens, index_paged_scores, index_scores,
)
from distrl_llm_tpu.ops.sparse_attention import (
    pool_keys, pooled_count, segment_choice, sparse_attend, sparse_decode, update_pooled,
)

Params = dict[str, Any]
#: columns of a row's page table absorbed decode attention gathers at a time
#: for that row alone, and rows that walk their tables together
LATENT_DECODE_PAGES = 8
LATENT_DECODE_ROWS = 16
#: the entries of a slot's state that hold one array a ROW for each layer of a
#: kind (tuples): what a candidate is handed from its prompt
ROW_STATES = ("lin", "pooled", "delta", "conv", "power", "power_z", "ssm",
              "win_k", "win_v", "cca_tail")
#: keys a unit of ``window_stats`` stands for (module docstring)
WINDOW_COUNT_UNIT = 128
#: tokens a unit of ``index_stats`` stands for (module docstring)
INDEX_COUNT_UNIT = 128
#: eps of the index key's LayerNorm (torch's default; the config has no key for it)
INDEX_NORM_EPS = 1e-6
#: the mixers whose layers ``_block`` runs as a mixer and then the layer's own
#: second half (``cfg.layer_ffn``: either may be absent), and the cache entries
#: each keeps a layer; "experts" is a layer with no mixer, which keeps nothing
_MIXER_CACHE = {"softmax": ("k", "v"), "delta": ("delta", "conv"),
                "mamba": ("ssm", "conv"), "window": ("win_k", "win_v"),
                "cca": ("k", "v", "cca_tail"), "mamba2": ("ssm", "conv"),
                "experts": ()}


def init_hybrid_params(rng: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    """Random init, one stack per layer kind (module docstring)."""
    init = _normal_init(rng, 32, dtype)

    def stack(n: int, q_dim: int, kv_dim: int, head_dim: int, gate: bool,
              out_norm: bool) -> Params:
        p = _init_layer_stack(init, cfg, n, q_dim, kv_dim, dtype)
        if cfg.qk_norm:
            p["q_norm"] = jnp.ones((n, head_dim), dtype)
            p["k_norm"] = jnp.ones((n, head_dim), dtype)
        if gate:
            p["wz"] = init((n, cfg.hidden_size, q_dim))
        if out_norm:
            p["o_norm"] = jnp.ones((n, q_dim), dtype)
        return p

    def latent_stack(n: int, moe: bool, f: int) -> Params:
        """``f``: the width of the gated MLP under the MLP's names (the dense
        MLP, or the shared expert beside routed experts; 0 = none)."""
        d, heads, experts = cfg.hidden_size, cfg.num_heads, cfg.n_routed_experts
        p = {
            "attn_norm": jnp.ones((n, d), dtype),
            "mlp_norm": jnp.ones((n, d), dtype),
            # q_proj, or q_b_proj from the query's normed latent
            "wq": init((n, cfg.q_lora_rank or d, cfg.q_dim)),
            "wkv_a": init((n, d, cfg.latent_dim)),
            "kv_a_norm": jnp.ones((n, cfg.kv_lora_rank), dtype),
            "wkv_b": init((n, cfg.kv_lora_rank,
                           heads * (cfg.qk_nope_head_dim + cfg.v_head_dim))),
            "wo": init((n, heads * cfg.v_head_dim, d)),
        }
        if f:
            p.update(w_gate=init((n, d, f)), w_up=init((n, d, f)),
                     w_down=init((n, f, d)))
        if moe:
            fm = cfg.moe_intermediate_size
            p.update(
                router=init((n, d, cfg.router_width)),
                e_score_bias=jnp.zeros((n, cfg.router_width), dtype),
                experts_gate=init((n, experts, d, fm)),
                experts_up=init((n, experts, d, fm)),
                experts_down=init((n, experts, fm, d)),
            )
        # drawn after every leaf a model without them has: its draws stay
        if cfg.q_lora_rank:
            p.update(wq_a=init((n, d, cfg.q_lora_rank)),
                     q_a_norm=jnp.ones((n, cfg.q_lora_rank), dtype))
        if cfg.index_topk:
            p.update(
                w_index_q=init((n, cfg.q_lora_rank, cfg.index_heads * cfg.index_head_dim)),
                w_index_k=init((n, d, cfg.index_head_dim)),
                index_k_norm=jnp.ones((n, cfg.index_head_dim), dtype),
                b_index_k=jnp.zeros((n, cfg.index_head_dim), dtype),
                w_index_w=init((n, d, cfg.index_heads)),
            )
        return p

    def expert_half(n: int) -> Params:
        """An expert layer's second half: the shared expert under the MLP's
        names, the router at its published width, the experts HELD."""
        d, fm, held = cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_routed_experts
        f, gated = cfg.shared_expert_size, not cfg.ssd_moe  # ungated: two matrices each
        p = {
            "mlp_norm": jnp.ones((n, d), dtype),
            "router": init((n, d, cfg.router_width)),
            "e_score_bias": jnp.zeros((n, cfg.router_width), dtype),
        }
        if gated:
            p["experts_gate"] = init((n, held, d, fm))
        p.update(experts_up=init((n, held, d, fm)), experts_down=init((n, held, fm, d)))
        if f and gated:
            p["w_gate"] = init((n, d, f))
        if f:
            p.update(w_up=init((n, d, f)), w_down=init((n, f, d)))
        return p

    def mixer_stack(n: int, q_dim: int, kv_dim: int, v_dim: int = 0,
                    o_dim: int = 0) -> Params:
        d = cfg.hidden_size
        return {
            "attn_norm": jnp.ones((n, d), dtype),
            "wq": init((n, d, q_dim)), "wk": init((n, d, kv_dim)),
            "wv": init((n, d, v_dim or kv_dim)), "wo": init((n, o_dim or q_dim, d)),
        }

    def mlp_half(n: int) -> Params:
        d, f = cfg.hidden_size, cfg.intermediate_size
        return {"mlp_norm": jnp.ones((n, d), dtype), "w_gate": init((n, d, f)),
                "w_up": init((n, d, f)), "w_down": init((n, f, d))}

    layers: Params = {}
    if cfg.ssd_moe:  # ONE sublayer a layer: a mixer's stack, or the experts'
        if cfg.kind_count("softmax_alone"):
            layers["softmax_alone"] = mixer_stack(
                cfg.kind_count("softmax_alone"), cfg.q_dim, cfg.kv_dim)
        if cfg.kind_count("mamba2"):
            n, d, heads = cfg.kind_count("mamba2"), cfg.hidden_size, cfg.ssd_heads
            layers["mamba2"] = {
                "attn_norm": jnp.ones((n, d), dtype),
                "w_in": init((n, d, cfg.ssd_in_dim)),  # z, then x B C, then dt
                "conv": init((n, cfg.mamba_d_conv, cfg.ssd_conv_dim)),
                "b_conv": jnp.zeros((n, cfg.ssd_conv_dim), dtype),
                # A = -(1..16) over the heads; the inverse softplus of a step of 0.01
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.linspace(1.0, 16.0, heads)), (n, heads)).astype(dtype),
                "dt_bias": jnp.full((n, heads), -4.6, dtype),
                "ssd_d": jnp.ones((n, heads), dtype),
                "gate_norm": jnp.ones((n, cfg.ssd_inner), dtype),
                "w_out": init((n, cfg.ssd_inner, d)),
            }
        if cfg.kind_count("experts"):
            layers["experts"] = expert_half(cfg.kind_count("experts"))
    elif cfg.window_moe:  # the mixer as its kind states it; the second half is the layer's
        for kind in dict.fromkeys(cfg.layer_kinds):
            n, kv = cfg.kind_count(kind), cfg.kv_heads_of(mixer_of(kind))
            layers[kind] = {
                **mixer_stack(n, cfg.q_dim, kv * cfg.head_dim,
                              kv * cfg.value_head_dim, cfg.o_dim),
                **({"q_norm": jnp.ones((n, cfg.head_dim), dtype),
                    "k_norm": jnp.ones((n, cfg.head_dim), dtype)} if cfg.qk_norm else {}),
                **(mlp_half(n) if cfg.layer_ffn(kind) == "dense" else expert_half(n))}
            if cfg.window_sink and mixer_of(kind) == "window":
                layers[kind]["sink"] = jnp.zeros((n, cfg.num_heads), dtype)
    elif cfg.kind_count("softmax"):
        n = cfg.kind_count("softmax")
        layers["softmax"] = {
            **mixer_stack(n, cfg.q_dim, cfg.kv_dim),
            # a state-space model's attention layers have the dense MLP
            **(expert_half(n) if cfg.delta_moe else mlp_half(n))}
        if cfg.attn_output_gate:
            layers["softmax"]["wg"] = init((n, cfg.hidden_size, cfg.q_dim))
    if cfg.kind_count("delta"):
        n, d, r, wide = (cfg.kind_count("delta"), cfg.hidden_size, cfg.delta_low_rank,
                         cfg.delta_dim)
        layers["delta"] = {
            **mixer_stack(n, wide, wide), **expert_half(n),
            "conv": init((n, cfg.delta_conv_size, 3 * wide)),
            "wf_a": init((n, d, r)), "wf_b": init((n, r, wide)),
            "A_log": jnp.zeros((n, cfg.delta_heads), dtype),
            "dt_bias": jnp.zeros((n, wide), dtype),
            "wb": init((n, d, cfg.delta_heads)),
            "wg_a": init((n, d, r)), "wg_b": init((n, r, wide)),
            "head_norm": jnp.ones((n, cfg.delta_head_dim), dtype),
        }
    if cfg.kind_count("mamba"):
        n, d, e = cfg.kind_count("mamba"), cfg.hidden_size, cfg.mamba_inner
        cols, r = cfg.mamba_d_state, cfg.mamba_dt_rank
        layers["mamba"] = {
            "attn_norm": jnp.ones((n, d), dtype),
            "w_in": init((n, d, 2 * e)),  # u first, z second
            "conv": init((n, cfg.mamba_d_conv, e)), "b_conv": jnp.zeros((n, e), dtype),
            "w_x": init((n, e, r + 2 * cols)),  # d, B, C
            "ssm_dt_norm": jnp.ones((n, r), dtype),
            "ssm_b_norm": jnp.ones((n, cols), dtype),
            "ssm_c_norm": jnp.ones((n, cols), dtype),
            "w_dt": init((n, r, e)),
            # the inverse softplus of a step of 0.01
            "b_dt": jnp.full((n, e), -4.6, dtype),
            # A = -(1..N) a channel, held [N, E] (ops/selective_scan.py)
            "ssm_a_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, cols + 1, dtype=jnp.float32))[None, :, None],
                (n, cols, e)).astype(dtype),
            "ssm_d": jnp.ones((n, e), dtype),
            "w_out": init((n, e, d)),
            **mlp_half(n),
        }
    if cfg.kind_count("cca"):
        n, d, r = cfg.kind_count("cca"), cfg.hidden_size, cfg.router_hidden_size
        mixed, groups, hd = cfg.q_dim + cfg.kv_dim, cfg.num_heads + cfg.num_kv_heads, cfg.head_dim
        experts, fm = cfg.n_routed_experts, cfg.moe_intermediate_size
        # the residual's vectors, [n, 2, d]: what x takes, what f(x) takes
        scales, shifts = jnp.ones((n, 2, d), dtype), jnp.zeros((n, 2, d), dtype)
        layers["cca"] = {
            "attn_norm": jnp.ones((n, d), dtype), "mlp_norm": jnp.ones((n, d), dtype),
            "wq": init((n, d, cfg.q_dim)), "wk": init((n, d, cfg.kv_dim)),
            # the value's two halves: of this token, and of the one before
            "wv1": init((n, d, cfg.kv_dim // 2)), "wv2": init((n, d, cfg.kv_dim // 2)),
            "wo": init((n, cfg.q_dim, d)),
            "conv0": init((n, cfg.cca_time0, mixed)), "b_conv0": jnp.zeros((n, mixed), dtype),
            "conv1": init((n, cfg.cca_time1, groups, hd, hd)),
            "b_conv1": jnp.zeros((n, mixed), dtype),
            "k_temp": jnp.ones((n, cfg.num_kv_heads), dtype),
            "attn_res_scale": scales, "attn_res_shift": shifts,
            "mlp_res_scale": scales, "mlp_res_shift": shifts,
            "router_down": init((n, d, r)), "b_router_down": jnp.zeros((n, r), dtype),
            "router_gamma": jnp.ones((n, r), dtype), "router_norm": jnp.ones((n, r), dtype),
            "router_w1": init((n, r, r)), "b_router_w1": jnp.zeros((n, r), dtype),
            "router_w2": init((n, r, r)), "b_router_w2": jnp.zeros((n, r), dtype),
            "router_w3": init((n, r, experts)),
            "e_score_bias": jnp.zeros((n, experts), dtype),
            "experts_gate": init((n, experts, d, fm)),
            "experts_up": init((n, experts, d, fm)),
            "experts_down": init((n, experts, fm, d)),
        }
    for kind, moe, f in (
            ("latent", False, cfg.intermediate_size),
            ("latent_moe", True, cfg.shared_expert_size),
            # a shortcut-connected layer's sublayers: a dense MLP in both, the
            # experts in the first's stack
            ("latent_fork", True, cfg.intermediate_size),
            ("latent_join", False, cfg.intermediate_size)):
        if cfg.kind_count(kind):
            layers[kind] = latent_stack(cfg.kind_count(kind), moe, f)
    if cfg.kind_count("sparse"):
        layers["sparse"] = stack(
            cfg.kind_count("sparse"), cfg.q_dim, cfg.kv_dim, cfg.head_dim,
            cfg.attn_output_gate, False,
        )
    if cfg.kind_count("power"):
        n = cfg.kind_count("power")
        layers["power"] = {
            **stack(n, cfg.q_dim, cfg.kv_dim, cfg.head_dim, False, False),
            # the log-decay's projection, one value a KV head, and its bias
            "w_decay": init((n, cfg.hidden_size, cfg.num_kv_heads)),
            "b_decay": jnp.zeros((n, cfg.num_kv_heads), dtype),
        }
    if cfg.kind_count("lightning"):
        layers["lightning"] = stack(
            cfg.kind_count("lightning"), cfg.lightning_dim, cfg.lightning_dim,
            cfg.lightning_head_dim, cfg.lightning_output_gate,
            cfg.lightning_output_norm,
        )
    return _init_around_layers(init, cfg, layers, dtype)


def init_mixer_state(cfg: ModelConfig, rows: int, max_tokens: int,
                     cache_dtype=jnp.bfloat16) -> Params:
    """What a slot holds beside its K/V pages: a float32 state per lightning
    layer, the selector's pooled keys per sparse layer, a float32 state and a
    convolution tail per delta-rule layer, a float32 state and its normaliser
    per power-retention layer, a float32 state and a convolution window per
    Mamba layer (of either generation: "mamba" ``[B, N, E]``, "mamba2" ``[B, H,
    P, N]``), a tail per compressed-convolutional layer, the round's
    counters. The entries named in ``ROW_STATES`` are tuples of one array a row."""
    if cfg.latent:  # all of a slot's cache is in pages; the round's counters
        state = {"lin": (), "pooled": (), "moe_stats": jnp.zeros((2,), jnp.int32),
                 "moe_blocks": jnp.zeros((2,), jnp.int32)}
        if cfg.held_experts is not None:  # the pairs chosen over ALL experts
            state["moe_routed"] = jnp.zeros((1,), jnp.int32)
        if cfg.zero_experts:  # and those of them that chose one that computes nothing
            state["moe_zero"] = jnp.zeros((1,), jnp.int32)
        state["index_stats" if cfg.index_topk else "latent_stats"] = jnp.zeros(
            (2,), jnp.int32)
        return state
    if cfg.cca:  # pages AND a tail in every layer; every expert is held
        return {
            "lin": (), "pooled": (),
            "cca_tail": tuple(jnp.zeros((rows, cfg.cca_tail_dim), cache_dtype)
                              for _ in range(cfg.num_layers)),
            "moe_stats": jnp.zeros((2,), jnp.int32),
            "moe_blocks": jnp.zeros((2,), jnp.int32),
        }
    if cfg.delta_moe:
        h, d, n = cfg.delta_heads, cfg.delta_head_dim, cfg.kind_count("delta")
        return {
            "lin": (), "pooled": (),
            "delta": tuple(jnp.zeros((rows, h, d, d), jnp.float32) for _ in range(n)),
            "conv": tuple(
                jnp.zeros((rows, cfg.delta_conv_size - 1, 3 * cfg.delta_dim), cache_dtype)
                for _ in range(n)),
            "moe_stats": jnp.zeros((2,), jnp.int32),
            "moe_blocks": jnp.zeros((2,), jnp.int32),
            "moe_routed": jnp.zeros((1,), jnp.int32),
        }
    if cfg.window_moe:
        ring_k, ring_v = cfg.ring_shapes(rows)
        n = cfg.mixer_count("window")
        return {
            "lin": (), "pooled": (),
            "win_k": tuple(jnp.zeros(ring_k, cache_dtype) for _ in range(n)),
            "win_v": tuple(jnp.zeros(ring_v, cache_dtype) for _ in range(n)),
            "moe_stats": jnp.zeros((2,), jnp.int32),
            "moe_blocks": jnp.zeros((2,), jnp.int32),
            "moe_routed": jnp.zeros((1,), jnp.int32),
            "window_stats": jnp.zeros((2,), jnp.int32),
        }
    if cfg.ssd_moe:
        n = cfg.kind_count("mamba2")
        state = {
            "lin": (), "pooled": (),
            "ssm": tuple(
                jnp.zeros((rows, cfg.ssd_heads, cfg.ssd_head_dim, cfg.mamba_d_state),
                          jnp.float32) for _ in range(n)),
            "conv": tuple(
                jnp.zeros((rows, (cfg.mamba_d_conv - 1) * cfg.ssd_conv_dim), cache_dtype)
                for _ in range(n)),
            "ssm_stats": jnp.zeros((1,), jnp.int32),
            "moe_stats": jnp.zeros((2,), jnp.int32),
            "moe_blocks": jnp.zeros((2,), jnp.int32),
        }
        if cfg.held_experts is not None:  # the pairs chosen over ALL experts
            state["moe_routed"] = jnp.zeros((1,), jnp.int32)
        return state
    if cfg.mamba:
        n, e = cfg.kind_count("mamba"), cfg.mamba_inner
        return {
            "lin": (), "pooled": (),
            "ssm": tuple(
                jnp.zeros((rows, cfg.mamba_d_state, e), jnp.float32) for _ in range(n)),
            "conv": tuple(
                jnp.zeros((rows, cfg.mamba_d_conv - 1, e), cache_dtype) for _ in range(n)),
            "ssm_stats": jnp.zeros((1,), jnp.int32),
        }
    if cfg.power:
        held = [init_state(rows, cfg.num_kv_heads, cfg.head_dim)
                for _ in range(cfg.kind_count("power"))]
        return {
            "lin": (), "pooled": (),
            "power": tuple(s for s, _ in held), "power_z": tuple(z for _, z in held),
            "power_stats": jnp.zeros((1,), jnp.int32),
        }
    h, d = cfg.lightning_heads, cfg.lightning_head_dim
    pooled = (rows, pooled_count(max_tokens, cfg), cfg.num_kv_heads, cfg.head_dim)
    return {
        "lin": tuple(
            jnp.zeros((rows, h, d, d), jnp.float32)
            for _ in range(cfg.kind_count("lightning"))
        ),
        "pooled": tuple(
            jnp.zeros(pooled, cache_dtype) for _ in range(cfg.kind_count("sparse"))
        ),
        "sel_stats": jnp.zeros((2,), jnp.int32),
    }


def _mode(kv_cache, s: int, flags: dict) -> str:
    if kv_cache is None:
        return "full"
    refused = [name for name, on in flags.items() if on]
    if "page_indices" not in kv_cache or refused:
        raise NotImplementedError(
            "a model with per-layer mixers runs without a cache, one decode "
            "token a row over a paged cache, or a page-aligned prefill segment; "
            f"not {refused or ['a dense K/V cache']}"
        )
    if "segment_start" in kv_cache:
        return "segment"
    if s != 1:
        raise NotImplementedError(
            "several tokens a row over a paged cache need 'segment_start' "
            "(a page-aligned prefill segment)"
        )
    return "decode"


def _write_segment_pages(pages, new, dest, page_size: int):
    """A page-aligned segment's K or V ``new [B, S, K, hd]`` written whole into
    the pages ``dest [B, S // page_size]`` of a pool ``[K, pages, ps, hd]``."""
    b, s, kv, hd = new.shape
    per = s // page_size
    tiles = new.reshape(b, per, page_size, kv, hd)
    tiles = tiles.transpose(3, 0, 1, 2, 4).reshape(kv, b * per, page_size, hd)
    return pages.at[:, dest.reshape(-1)].set(tiles.astype(pages.dtype))


def _sparse_mix(q, k, v, cache, *, cfg, mode, env):
    """The block-sparse layer's attention in each mode. Returns
    (o [B, S, H, hd], the layer's new cache pieces, stats or None)."""
    from distrl_llm_tpu.ops.paged import (
        gather_pages_dense, write_token_to_pages,
    )

    if mode == "full":
        if q.shape[1] <= cfg.sparse_dense_len:  # no query's context is longer
            with jax.named_scope(telemetry.MODEL_SPARSE_ATTN):
                o = attention(q, k, v, None, impl=env["attn_impl"],
                              key_valid=env["valid"])
            return o, None, None
        with jax.named_scope(telemetry.MODEL_SPARSE_SELECT):
            pooled = pool_keys(k, cfg)
        return sparse_attend(q, k, v, pooled, env["q_pos"], cfg), None, None
    pages_k, pages_v, pooled = cache
    idx, ps = env["page_indices"], env["page_size"]
    if mode == "decode":
        lengths = env["lengths"]
        with jax.named_scope(telemetry.ENGINE_KV_WRITE):
            pages_k = write_token_to_pages(pages_k, k[:, 0], lengths, idx, ps)
            pages_v = write_token_to_pages(pages_v, v[:, 0], lengths, idx, ps)
        with jax.named_scope(telemetry.MODEL_SPARSE_SELECT):
            pooled = update_pooled(pooled, pages_k, lengths + 1, idx, cfg)
        o, stats = sparse_decode(
            q[:, 0], pages_k, pages_v, pooled, lengths, idx, cfg, alive=env["alive"]
        )
        return o[:, None], (pages_k, pages_v, pooled), stats
    # one page-aligned segment of a prefill, every row at offset ``start``: the
    # choice once for the segment, as the mask of the fold the full-attention
    # layers run over the rows' pages (K is gathered dense for the selector's
    # pooled keys alone; V never is)
    s = q.shape[1]
    start = env["segment_start"]
    with jax.named_scope(telemetry.ENGINE_KV_WRITE):
        dest = jax.lax.dynamic_slice_in_dim(idx, start // ps, s // ps, axis=1)
        pages_k = _write_segment_pages(pages_k, k, dest, ps)
        pages_v = _write_segment_pages(pages_v, v, dest, ps)
        ctx_k = gather_pages_dense(pages_k, idx, dtype=q.dtype)
    with jax.named_scope(telemetry.MODEL_SPARSE_SELECT):
        pooled = pool_keys(ctx_k, cfg, count=pooled.shape[1]).astype(pooled.dtype)
        chosen = segment_choice(
            q, pooled, env["q_pos"], cfg, idx.shape[1] * ps).astype(FOLD_MASK_DTYPE)
    with jax.named_scope(telemetry.MODEL_SPARSE_ATTN):
        o = _segment_softmax(q, pages_k, pages_v, idx, start, ps, chosen)
    return o, (pages_k, pages_v, pooled), None


def _lightning_mix(q, k, v, state, rate, *, cfg, mode, env):
    with jax.named_scope(telemetry.MODEL_LINEAR_ATTN):
        if cfg.lightning_use_rope:
            q = apply_rope(q, env["cos"], env["sin"])
            k = apply_rope(k, env["cos"], env["sin"])
        if mode == "decode":
            o, state = lightning_step(q[:, 0], k[:, 0], v[:, 0], rate, state)
            return o[:, None], state
        o, state = lightning_chunked(q, k, v, rate, env["valid"], state=state)
        return o, (state if mode == "segment" else None)


def _power_decay(h, p):
    """A power-retention layer's log-decay, ``logsigmoid(W_g h + b_g)``: one
    float32 value a KV head, <= 0."""
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        raw = jnp.einsum("bsh,hk->bsk", h, p["w_decay"],
                         preferred_element_type=jnp.float32)
    with jax.named_scope(telemetry.MODEL_POWER_ATTN):
        return jax.nn.log_sigmoid(raw + p["b_decay"].astype(jnp.float32))


def _power_mix(q, k, v, g, state, *, mode, env):
    """Power retention in each mode: (o [B, S, H, hd], (S, z) or None)."""
    with jax.named_scope(telemetry.MODEL_POWER_ATTN):
        q = apply_rope(q, env["cos"], env["sin"])
        k = apply_rope(k, env["cos"], env["sin"])
        if mode == "decode":
            o, state = power_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], state)
            return o[:, None], state
        o, state = power_chunked(q, k, v, g, env["valid"], state=state)
        return o, (state if mode == "segment" else None)


def _block(x, p, lora, rate, cache, *, kind: str, cfg: ModelConfig, mode: str,
           env: dict, lora_scale: float, lora_dropout: float, dropout_rng):
    """One layer of any kind: (x, new cache pieces, stats)."""
    proj = partial(_proj, lora_dropout=lora_dropout, dropout_rng=dropout_rng)
    if cfg.latent:  # "latent", "latent_moe", or a shortcut-connected layer's sublayer
        return _latent_block(x, p, lora, cache, kind=kind, cfg=cfg,
                             mode=mode, env=env, proj=proj, lora_scale=lora_scale)
    mixer = mixer_of(kind)
    if mixer in _MIXER_CACHE:
        mix = {"softmax": _softmax_mix, "delta": _delta_mix, "mamba": _mamba_mix,
               "window": _window_mix, "cca": _cca_mix, "mamba2": _ssd_mix}.get(mixer)
        carried = None
        if mixer == "cca":  # the stream, and the router's value from the layer before
            x, carried = x
        if mix is not None:  # an "experts" layer has no mixer
            x, cache = mix(x, p, lora, cache, cfg=cfg, mode=mode, env=env, proj=proj,
                           lora_scale=lora_scale)
        ffn = cfg.layer_ffn(kind)  # the layer's own second half, if it has one
        if ffn == "none":
            return x, cache, None
        if ffn == "dense":
            return _mlp_half(x, p, lora, cfg=cfg, proj=proj,
                             lora_scale=lora_scale), cache, None
        x, stats = _expert_half(x, p, lora, cfg=cfg, env=env, proj=proj,
                                lora_scale=lora_scale, carried=carried)
        return x, cache, stats
    b, s, _ = x.shape
    c = jnp.asarray(cfg.residual_scale, x.dtype)
    sparse = kind == "sparse"
    heads, kv_heads, hd = (
        (cfg.lightning_heads, cfg.lightning_heads, cfg.lightning_head_dim)
        if kind == "lightning" else (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim)
    )
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        q = proj(h, p, lora, "wq", "bq", lora_scale).reshape(b, s, heads, hd)
        k = proj(h, p, lora, "wk", "bk", lora_scale).reshape(b, s, kv_heads, hd)
        v = proj(h, p, lora, "wv", "bv", lora_scale).reshape(b, s, kv_heads, hd)
        if "q_norm" in p:
            q = rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
        gate = jax.nn.sigmoid(linear(h, p["wz"])) if "wz" in p else None
    stats = None
    if kind == "power":
        o, cache = _power_mix(q, k, v, _power_decay(h, p), cache, mode=mode, env=env)
    elif sparse:
        if cfg.attn_use_rope:
            raise NotImplementedError("sparse layers with RoPE (attn_use_rope)")
        o, cache, stats = _sparse_mix(q, k, v, cache, cfg=cfg, mode=mode, env=env)
    else:
        o, cache = _lightning_mix(q, k, v, cache, rate, cfg=cfg, mode=mode, env=env)
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        o = o.reshape(b, s, heads * hd)
        if "o_norm" in p:
            o = rms_norm(o, p["o_norm"], cfg.rms_norm_eps)
        if gate is not None:
            o = o * gate
        x = x + c * proj(o, p, lora, "wo", "bo", lora_scale)
    x = _mlp_half(x, p, lora, cfg=cfg, proj=proj, lora_scale=lora_scale,
                  residual_scale=c)
    return x, cache, stats


def _merge(x, y, p, half: str):
    """A sublayer's output ``y`` joined to the stream: ``x + y``, or, where the
    layer has the vectors, ``(a_r x + b_r) + (a_o y + b_o)`` (module docstring)."""
    scale = p.get(half + "_res_scale")
    if scale is None:
        return x + y
    scale, shift = scale.astype(x.dtype), p[half + "_res_shift"].astype(x.dtype)
    return (scale[0] * x + shift[0]) + (scale[1] * y + shift[1])


def _expert_half(x, p, lora, *, cfg, env, proj, lora_scale, carried=None):
    """An expert layer's second half: the routed experts HELD here
    (``cfg.held_experts``; the router scores them all) beside the shared
    expert. Returns (x, the layer's [4] stats: ``moe_half``). With ``carried``
    (the MLP router's value from the layer before, ``route_mlp``) the router
    reads it and what is returned in ``x``'s place is ``(x, the value to hand
    on)``."""
    with jax.named_scope(telemetry.MODEL_MOE_ROUTER):
        h = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
    if carried is None:
        routed, stats = moe_half(h, p, cfg, held=cfg.held_experts, alive=env.get("alive"))
    else:  # the MLP router chooses, and hands its value on
        *choice, carried = route_mlp(h, carried, p, cfg)
        routed, stats = moe_half(h, p, cfg, held=cfg.held_experts, alive=env.get("alive"),
                                 choice=tuple(choice))
    if "w_up" in p:  # the shared expert: x + S(h)
        x = _mlp_half(x, p, lora, cfg=cfg, proj=proj, lora_scale=lora_scale)
    x = _merge(x, routed, p, "mlp")
    return (x if carried is None else (x, carried)), stats


def _segment_softmax(q, pages_k, pages_v, idx, start, page_size: int, chosen=None):
    """A prefill segment's causal attention over the rows' PAGES (the
    segment's own are written already), a segment's width of keys at a time
    under a running softmax: the scores of all of a 20k-token context at once
    would not fit. ``q [B, S, H, hd]`` at positions ``start ..`` (every row
    alike) -> ``[B, S, H, hv]``, ``hv`` the value pages' width. Each block of
    keys is gathered from the pools once (25 MB at 8 rows of 4 KV heads) and
    folded by ``expanded_segment``: the fold the latent layers run, handed a
    GQA layer's head layout (K and V two arrays a KV head, no rope part: the
    keys were rotated before they were written) and, where a key's row is
    wider than its head, the head's own scale. One path over GQA pages for
    the "softmax" and "cca" layers, which attend all they see, and the
    "sparse" ones, which hand it ``chosen [B, K, S, W * page_size]`` of
    ``FOLD_MASK_DTYPE``: a KV head's choice over the positions of the row's
    page table, causality in it, read by the folds in place of the
    positions' order."""
    b, s, heads, hd = q.shape
    kv, per = pages_k.shape[0], s // page_size
    head = jnp.arange(kv)[None, :, None]
    no_rope = jnp.zeros((b, s, 0), q.dtype)

    def block(j):
        with jax.named_scope(telemetry.ENGINE_KV_WRITE):
            at = jax.lax.dynamic_slice_in_dim(idx, j * per, per, axis=1)[:, None]
            held = lambda pages: pages[head, at].reshape(  # [B, K, per, ps, .]
                b, kv, s, pages.shape[-1]).astype(q.dtype)
            return (held(pages_k), held(pages_v)), no_rope

    return expanded_segment(
        _to_row(q, pages_k.shape[-1]),  # the lanes a key's row takes in a page
        q[..., :0], block, start, pages_v.shape[-1], q.dtype, chosen, scale=hd ** -0.5)


def _qkv_heads(x, p, lora, *, cfg, proj, lora_scale, mixer: str = "softmax"):
    """A GQA layer's normed input and its q ``[B, S, H, hd]``, k ``[B, S, K,
    hd]`` and v ``[B, S, K, hv]`` at the KV heads of its ``mixer``, with the
    per-head RMSNorm of q and k where the layer has one and the value's scale
    where the configuration states one (a cached value holds it)."""
    b, s, _ = x.shape
    heads, kv, hd = cfg.num_heads, cfg.kv_heads_of(mixer), cfg.head_dim
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        q = proj(h, p, lora, "wq", "bq", lora_scale).reshape(b, s, heads, hd)
        k = proj(h, p, lora, "wk", "bk", lora_scale).reshape(b, s, kv, hd)
        v = proj(h, p, lora, "wv", "bv", lora_scale).reshape(
            b, s, kv, cfg.value_head_dim)
        if "q_norm" in p:
            q = rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
        if cfg.value_scale != 1.0:
            v = v * jnp.asarray(cfg.value_scale, v.dtype)
    return h, q, k, v


def _rotate(z, cos, sin, rot: int):
    """RoPE on the first ``rot`` values of each head of ``z [B, S, H, hd]`` and
    none on the rest; ``rot`` 0 (or the whole head) rotates all of it."""
    if not rot or rot == z.shape[-1]:
        return apply_rope(z, cos, sin)
    return jnp.concatenate([apply_rope(z[..., :rot], cos, sin), z[..., rot:]], axis=-1)


def _to_row(x, width: int):
    """``x [..., hd]`` in the ``width`` lanes a cached key takes
    (``ModelConfig.key_row``): zeros after its own values, which add nothing to
    a score; ``x`` itself where the row is the head."""
    if x.shape[-1] == width:
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, width - x.shape[-1]),))


def _window_mix(x, p, lora, cache, *, cfg, mode, env, proj, lora_scale):
    """A sliding-window layer: q/k norm where it has one, RoPE, token t over
    tokens ``max(0, t - W + 1) .. t`` and the layer's sink where it has one:
    (x + y, (ring_k, ring_v) or None). The rings ``[B, K, W, hd]`` and ``[B, K,
    W, hv]`` hold position ``p`` at ``p % W``, k rotated already, in
    ``cfg.key_row`` lanes (q then takes as many, and the scores their own scale)."""
    b, s, _ = x.shape
    kv, win, sink = cfg.kv_heads_of("window"), cfg.sliding_window, p.get("sink")
    row = cfg.key_row  # a padded row keeps the head's own scale, not its width's
    scale = None if row == cfg.head_dim else cfg.head_dim ** -0.5
    _, q, k, v = _qkv_heads(x, p, lora, cfg=cfg, proj=proj, lora_scale=lora_scale,
                            mixer="window")
    with jax.named_scope(telemetry.MODEL_WINDOW_ATTN):
        q = _rotate(q, env["cos"], env["sin"], cfg.rotary_dim)
        k = _rotate(k, env["cos"], env["sin"], cfg.rotary_dim)
    if mode == "full":
        with jax.named_scope(telemetry.MODEL_WINDOW_ATTN):
            o = attention(q, k, v, None, impl=env["attn_impl"],
                          key_valid=env["valid"], window=win, sink=sink)
    elif mode == "decode":
        ring_k, ring_v = cache
        at = env["lengths"]  # the token's position: tokens before it
        with jax.named_scope(telemetry.ENGINE_KV_WRITE):
            # a point scatter: row, KV head and slot are indices, the head's
            # values the window (a KV head taken as a slice relays the array out)
            where = (jnp.arange(b)[:, None], jnp.arange(kv)[None, :], (at % win)[:, None])
            ring_k = ring_k.at[where].set(_to_row(k[:, 0], row).astype(ring_k.dtype))
            ring_v = ring_v.at[where].set(v[:, 0].astype(ring_v.dtype))
        with jax.named_scope(telemetry.MODEL_WINDOW_ATTN):
            # slots 0 .. min(t, W - 1) are filled, in whatever order
            seen = jnp.arange(win)[None, :] < jnp.minimum(at + 1, win)[:, None]
            o = attention_reference(  # the ring's keys lie in no order: a softmax
                _to_row(q, row), ring_k.transpose(0, 2, 1, 3).astype(q.dtype),
                ring_v.transpose(0, 2, 1, 3).astype(q.dtype), seen[:, None, None, :],
                scale=scale, sink=sink)
        cache = (ring_k, ring_v)
    else:  # one segment of a prefill, every row at ``start``: the ring, then itself
        ring_k, ring_v = cache
        start, slots = env["segment_start"], jnp.arange(win)
        k = _to_row(k, row)
        with jax.named_scope(telemetry.MODEL_WINDOW_ATTN):
            # the position a slot holds before this segment: the largest p < start
            # with p % W == slot (negative: nothing yet)
            held = start - 1 - ((start - 1 - slots) % win)
            key_pos = jnp.concatenate([held, start + jnp.arange(s)])  # [W + S]
            q_pos = env["q_pos"][:, :, None]  # [B, S, 1]
            seen = (key_pos >= 0) & (key_pos <= q_pos) & (q_pos - key_pos < win)
            seen = seen & jnp.concatenate(
                [jnp.ones((b, win), bool), env["valid"] > 0], axis=1)[:, None, :]
            o = attention_reference(
                _to_row(q, row),
                jnp.concatenate([ring_k.transpose(0, 2, 1, 3).astype(k.dtype), k], 1),
                jnp.concatenate([ring_v.transpose(0, 2, 1, 3).astype(v.dtype), v], 1),
                seen[:, None], scale=scale, sink=sink)
        with jax.named_scope(telemetry.ENGINE_KV_WRITE):
            # each slot takes the row's LAST real token of the segment that falls
            # on it (rows are packed left: real tokens first), or keeps what it has
            last = (start + (env["valid"] > 0).sum(-1).astype(jnp.int32) - 1)[:, None]
            newest = last - ((last - slots[None, :]) % win)  # [B, W]
            take = (newest >= start)[:, None, :, None]
            src = jnp.clip(newest - start, 0, s - 1)[:, :, None, None]
            pick = lambda new: jnp.take_along_axis(new, src, axis=1).transpose(0, 2, 1, 3)
            ring_k = jnp.where(take, pick(k).astype(ring_k.dtype), ring_k)
            ring_v = jnp.where(take, pick(v).astype(ring_v.dtype), ring_v)
        cache = (ring_k, ring_v)
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        return x + proj(o.reshape(b, s, -1), p, lora, "wo", "bo", lora_scale), cache


def _paged_softmax(q, k, v, cache, *, mode, env):
    """Causal softmax attention of ``q [B, S, H, hd]`` over ``k [B, S, K, hd]``
    and ``v [B, S, K, hv]`` in each mode, K and V kept in pages of their own
    widths: (o [B, S, H, hv], (pages_k, pages_v) or None)."""
    from distrl_llm_tpu.ops.paged import paged_attention_op, write_token_to_pages

    s = q.shape[1]
    if mode == "full":
        with jax.named_scope(telemetry.MODEL_ATTN_CORE):
            return attention(q, k, v, None, impl=env["attn_impl"],
                             key_valid=env["valid"]), None
    pages_k, pages_v = cache
    idx, ps = env["page_indices"], env["page_size"]
    # a key's row in a page (``ModelConfig.key_row``): its head, or the head in
    # whole lane tiles with zeros after it; q takes as many, the scores their scale
    hd, row = q.shape[-1], pages_k.shape[-1]
    scale = None if row == hd else hd ** -0.5
    k = _to_row(k, row)
    if mode == "decode":
        lengths = env["lengths"]
        with jax.named_scope(telemetry.ENGINE_KV_WRITE):
            pages_k = write_token_to_pages(pages_k, k[:, 0], lengths, idx, ps)
            pages_v = write_token_to_pages(pages_v, v[:, 0], lengths, idx, ps)
        o = paged_attention_op(
            _to_row(q[:, 0], row), pages_k, pages_v, lengths + 1, idx,
            impl=env["paged_impl"], scale=scale,
        )[:, None]
        return o, (pages_k, pages_v)
    # one page-aligned segment of a prefill, every row at ``start``
    start = env["segment_start"]
    with jax.named_scope(telemetry.ENGINE_KV_WRITE):
        dest = jax.lax.dynamic_slice_in_dim(idx, start // ps, s // ps, axis=1)
        pages_k = _write_segment_pages(pages_k, k, dest, ps)
        pages_v = _write_segment_pages(pages_v, v, dest, ps)
    with jax.named_scope(telemetry.MODEL_ATTN_CORE):
        o = _segment_softmax(q, pages_k, pages_v, idx, start, ps)
    return o, (pages_k, pages_v)


def _shifted(x, before, valid):
    """``x [B, S, C]`` a token late, ``before [B, C]`` (None: zeros, a row's
    start) ahead of its first: ``(x_{t-1} [B, S, C], what the NEXT token reads
    [B, C])``, which is the row's last REAL token's ``x`` (``valid [B, S]``,
    real tokens first; ``before`` where the row has none here; None: all are)."""
    b, _, c = x.shape
    before = jnp.zeros((b, c), x.dtype) if before is None else before.astype(x.dtype)
    whole = jnp.concatenate([before[:, None], x], axis=1)
    if valid is None:
        return whole[:, :-1], x[:, -1]
    end = (valid > 0).sum(-1).astype(jnp.int32)
    return whole[:, :-1], jnp.take_along_axis(whole, end[:, None, None], axis=1)[:, 0]


def _qk_mean(q_raw, k_raw):
    """The mean of a query head and its group's key, from BEFORE the
    convolutions, ``[B, S, K, H/K, D]``, and a group's mean of those ``[B, S,
    K, D]``: what joins the convolutions' q and k."""
    mean_q = (q_raw + k_raw) * jnp.asarray(0.5, q_raw.dtype)
    return mean_q, mean_q.mean(axis=3)


def _cca_mix(x, p, lora, cache, *, cfg, mode, env, proj, lora_scale):
    """A compressed-convolutional-attention layer (module docstring): (the
    stream with y merged in, (pages_k, pages_v, tail) or None)."""
    b, s, _ = x.shape
    heads, kv, hd, rot = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.rotary_dim
    mixed = cfg.q_dim + cfg.kv_dim
    pages, tail = (cache[:2], cache[2]) if cache is not None else (None, None)
    # what the last token left: its u, its c1, its W_v2 h (None: a row's start)
    late_u, late_c1, late_v = (None,) * 3 if tail is None else (
        tail[:, :mixed], tail[:, mixed: 2 * mixed], tail[:, 2 * mixed:])
    valid = None if mode == "decode" else env["valid"]
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        q_raw, k_raw, v_now, v_next = (
            proj(h, p, lora, name, None, lora_scale) for name in ("wq", "wk", "wv1", "wv2"))
    with jax.named_scope(telemetry.MODEL_CCA_MIX):
        u = jnp.concatenate([q_raw, k_raw], axis=-1)
        u_late, u_last = _shifted(u, late_u, valid)
        taps = p["conv0"].astype(u.dtype)
        c1 = taps[0] * u_late + taps[1] * u + p["b_conv0"].astype(u.dtype)
        c1_late, c1_last = _shifted(c1, late_c1, valid)
        by_head = lambda z, w: jnp.einsum(
            "bsgi,gio->bsgo", z.reshape(b, s, heads + kv, hd), w)
        c2 = (by_head(c1_late, p["conv1"][0]) + by_head(c1, p["conv1"][1])
              + p["b_conv1"].astype(u.dtype).reshape(heads + kv, hd))
        v_late, v_last = _shifted(v_next, late_v, valid)
        mean_q, mean_k = _qk_mean(q_raw.reshape(b, s, kv, heads // kv, hd),
                                  k_raw.reshape(b, s, kv, 1, hd))
        q = c2[:, :, :heads] + mean_q.reshape(b, s, heads, hd)
        k = c2[:, :, heads:] + mean_k
        q = (l2norm(q) * hd ** 0.5).astype(u.dtype)
        k = (l2norm(k) * (hd ** 0.5 * p["k_temp"].astype(jnp.float32))[:, None]).astype(u.dtype)
        q, k = (_rotate(z, env["cos"], env["sin"], rot) for z in (q, k))
        v = jnp.concatenate([v_now, v_late], axis=-1).reshape(b, s, kv, hd)
        if tail is not None:
            tail = jnp.concatenate([u_last, c1_last, v_last], axis=-1).astype(tail.dtype)
    o, pages = _paged_softmax(q, k, v, pages, mode=mode, env=env)
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        y = proj(o.reshape(b, s, heads * hd), p, lora, "wo", None, lora_scale)
        x = _merge(x, y, p, "attn")
    return x, (None if pages is None else (*pages, tail))


def _softmax_mix(x, p, lora, cache, *, cfg, mode, env, proj, lora_scale):
    """A softmax layer, gated or with a per-head norm of q and k where its
    stack says so, rotated (``env["cos_full"]``: the full layers' own base)
    where the configuration says so: (x + y, (pages_k, pages_v) or None)."""
    b, s, _ = x.shape
    h, q, k, v = _qkv_heads(x, p, lora, cfg=cfg, proj=proj, lora_scale=lora_scale)
    if cfg.attn_use_rope:
        if not cfg.window_moe:  # no other family's full layers have a table
            raise NotImplementedError("softmax layers with RoPE (use_rope)")
        with jax.named_scope(telemetry.MODEL_ATTN_CORE):
            q = _rotate(q, env["cos_full"], env["sin_full"], cfg.rotary_dim)
            k = _rotate(k, env["cos_full"], env["sin_full"], cfg.rotary_dim)
    o, cache = _paged_softmax(q, k, v, cache, mode=mode, env=env)
    o = o.reshape(b, s, -1)
    if "wg" in p:
        with jax.named_scope(telemetry.MODEL_ATTN_GATE):
            o = o * jax.nn.sigmoid(linear(h, p["wg"]))
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        return x + proj(o, p, lora, "wo", "bo", lora_scale), cache


def _delta_gate(h, p):
    """A delta-rule layer's output gate, ``sigmoid(W_gb W_ga h)``: a low-rank pair."""
    return jax.nn.sigmoid(linear(linear(h, p["wg_a"]), p["wg_b"]))


def _delta_mix(x, p, lora, cache, *, cfg, mode, env, proj, lora_scale):
    """A gated delta-rule layer: (x + y, (state, tail) or None)."""
    b, s, _ = x.shape
    heads, hd, wide = cfg.delta_heads, cfg.delta_head_dim, cfg.delta_dim
    state, tail = cache if cache is not None else (None, None)
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        qkv = jnp.concatenate(
            [proj(h, p, lora, name, None, lora_scale) for name in ("wq", "wk", "wv")],
            axis=-1)
    with jax.named_scope(telemetry.MODEL_SHORT_CONV):
        mixed, kept = short_conv(
            qkv, p["conv"], None if mode == "decode" else env["valid"], tail)
        tail = None if tail is None else kept.astype(tail.dtype)  # the cache's type
        mixed = jax.nn.silu(mixed).reshape(b, s, 3, heads, hd)
    with jax.named_scope(telemetry.MODEL_DELTA_ATTN):
        q = l2norm(mixed[:, :, 0]) * hd ** -0.5
        k = l2norm(mixed[:, :, 1])
        v = mixed[:, :, 2]
        rate = linear(linear(h, p["wf_a"]), p["wf_b"]).astype(jnp.float32) + (
            p["dt_bias"].astype(jnp.float32))
        g = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
            rate).reshape(b, s, heads, hd)
        beta = cfg.delta_beta_scale * jax.nn.sigmoid(
            linear(h, p["wb"]).astype(jnp.float32))
        if mode == "decode":
            o, state = delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state)
            o = o[:, None]
        else:
            o, state = delta_chunked(q, k, v, g, beta, env["valid"], state=state)
    with jax.named_scope(telemetry.MODEL_ATTN_GATE):
        o = rms_norm(o, p["head_norm"], cfg.rms_norm_eps).astype(x.dtype).reshape(
            b, s, wide) * _delta_gate(h, p)
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        x = x + proj(o, p, lora, "wo", "bo", lora_scale)
    return x, (None if mode == "full" else (state, tail))


def _mamba_mix(x, p, lora, cache, *, cfg, mode, env, proj, lora_scale):
    """A Mamba-1 layer: (x + y, (state, window) or None)."""
    inner, cols, rank = cfg.mamba_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    state, tail = cache if cache is not None else (None, None)
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        uz = proj(h, p, lora, "w_in", None, lora_scale)
        u, z = uz[..., :inner], uz[..., inner:]
    with jax.named_scope(telemetry.MODEL_SHORT_CONV):
        mixed, kept = short_conv(
            u, p["conv"], None if mode == "decode" else env["valid"], tail)
        if mode == "segment":
            # the window is read out of the segment's u before the scan runs: left
            # to the scheduler, every layer's u (0.3 GB at 30 x 1,024) is kept to
            # the end of the segment for a gather of three tokens
            mixed, kept = jax.lax.optimization_barrier((mixed, kept))
        tail = None if tail is None else kept.astype(tail.dtype)  # the cache's type
        c = jax.nn.silu(mixed + p["b_conv"].astype(mixed.dtype))
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        dbc = linear(c, p["w_x"])
    with jax.named_scope(telemetry.MODEL_SSM):
        d, b, cc = (
            rms_norm(dbc[..., lo:hi], p[name], cfg.rms_norm_eps)
            for name, lo, hi in (("ssm_dt_norm", 0, rank),
                                 ("ssm_b_norm", rank, rank + cols),
                                 ("ssm_c_norm", rank + cols, rank + 2 * cols)))
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        raw = jnp.einsum("bsr,re->bse", d, p["w_dt"],
                         preferred_element_type=jnp.float32)
    with jax.named_scope(telemetry.MODEL_SSM):
        dt = jax.nn.softplus(raw + p["b_dt"].astype(jnp.float32))
        a = -jnp.exp(p["ssm_a_log"].astype(jnp.float32))
        if mode == "decode":
            y, state = ssm_step(
                c[:, 0], dt[:, 0], b[:, 0], cc[:, 0], a, p["ssm_d"], state, z[:, 0])
            y = y[:, None]
        else:
            y, state = ssm_chunked(
                c, dt, b, cc, a, p["ssm_d"], env["valid"], state=state, z=z)
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        x = x + proj(y, p, lora, "w_out", None, lora_scale)
    return x, (None if mode == "full" else (state, tail))


def _ssd_mix(x, p, lora, cache, *, cfg, mode, env, proj, lora_scale):
    """A Mamba-2 layer (module docstring; ``ops/ssd.py``): (x + y, (state,
    tail) or None)."""
    b, s, _ = x.shape
    heads, groups, cols = cfg.ssd_heads, cfg.ssd_groups, cfg.mamba_d_state
    inner, mixed_dim = cfg.ssd_inner, cfg.ssd_conv_dim
    state, tail = cache if cache is not None else (None, None)
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        zxd = proj(h, p, lora, "w_in", None, lora_scale)
        z, xbc, dt = (zxd[..., :inner], zxd[..., inner: inner + mixed_dim],
                      zxd[..., inner + mixed_dim:])
    with jax.named_scope(telemetry.MODEL_SHORT_CONV):
        if mode == "decode":  # a slot's tail is FLAT, [B, 3 (E + 2 G N)]: ops/ssd.py
            mixed, kept = conv_step(xbc[:, 0], p["conv"], tail)
            mixed = mixed[:, None]
        else:
            mixed, kept = short_conv(
                xbc, p["conv"], env["valid"],
                None if tail is None else tail.reshape(b, -1, mixed_dim))
            kept = kept.reshape(b, -1)
        if mode == "segment":  # the tail is read out before the chunks run (_mamba_mix)
            mixed, kept = jax.lax.optimization_barrier((mixed, kept))
        tail = None if tail is None else kept.astype(tail.dtype)  # the cache's type
        xbc = jax.nn.silu(mixed + p["b_conv"].astype(mixed.dtype))
    with jax.named_scope(telemetry.MODEL_SSM):
        u = xbc[..., :inner].reshape(b, s, heads, cfg.ssd_head_dim)
        bb = xbc[..., inner: inner + groups * cols].reshape(b, s, groups, cols)
        cc = xbc[..., inner + groups * cols:].reshape(b, s, groups, cols)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(p["A_log"].astype(jnp.float32))
        if mode == "decode":
            y, state = ssd_step(u[:, 0], dt[:, 0], bb[:, 0], cc[:, 0], a, p["ssd_d"], state)
            y = y[:, None]
        else:
            y, state = ssd_chunked(u, dt, bb, cc, a, p["ssd_d"], env["valid"],
                                   state=state, chunk=cfg.ssd_chunk)
        y = gated_group_norm(y.reshape(b, s, inner), z, p["gate_norm"], groups,
                             cfg.rms_norm_eps).astype(x.dtype)
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        x = x + proj(y, p, lora, "w_out", None, lora_scale)
    return x, (None if mode == "full" else (state, tail))


def _latent_page_walk(env: dict, cfg: ModelConfig, pages: jax.Array):
    """How absorbed decode attention walks a step's page tables ``[B, W]``:
    LATENT_DECODE_ROWS rows together, the columns every row of the group holds
    in common once for all of them, in blocks of ``wide``, then a row's own
    as far as the longest of the group reaches (a short row's group does not
    walk a long row's width). Where the walk is the Mosaic launch
    (``absorbed_decode_impl`` of a layer's ``pages``: it sizes its own blocks)
    ``wide`` is ONE column, so that everything the rows share is read once; the
    XLA form gathers a shared block of as many columns as its scores allow
    and a row's own LATENT_DECODE_PAGES at a time.
    Returns (the block shapes, what ``shared_page_walk`` read off the tables):
    the same for every layer of the step. A model with an index walks its
    INDEX KEYS so (``index_paged_scores``: the index's heads size a block)."""
    idx, ps = env["page_indices"], env["page_size"]
    b, width = idx.shape
    per = min(LATENT_DECODE_PAGES, width)
    rows = LATENT_DECODE_ROWS if b % LATENT_DECODE_ROWS == 0 else b
    heads = cfg.index_heads if cfg.index_topk else cfg.num_heads
    if not cfg.index_topk and absorbed_decode_impl(cfg.num_heads, pages, rows) == "kernel":
        wide = 1
    else:
        wide = shared_pages_per_block(rows, heads, ps, per, width)
    walk = shared_page_walk(
        idx, env["lengths"], env.get("alive"), page_size=ps, wide=wide, rows=rows)
    return {"per": per, "wide": wide, "rows": rows}, walk


def _absorbed_decode(q_nope, q_pe, row, pages, p, lora, *, cfg, env, lora_scale,
                     index=None):
    """One decode token a row: write its latent row, then attend over the
    row's pages with W_kvb absorbed (its adapter too), in the form
    ``latent_attention.absorbed_decode`` reads off the backend and the pages:
    one Mosaic launch over the pool where it lies on a TPU, the XLA walk that
    gathers its blocks elsewhere. ``q_nope [B, H, nope]``, ``q_pe [B, H,
    rope]``, ``row [B, latent_row]``. With ``index`` (``q_i [B, H_I, D_I]``,
    ``w [B, H_I]``, ``k_i [B, D_I]``, the layer's index-key pages) the new
    token's index key is written beside its row, and the row attends the
    tokens its index chooses and no other: the SAME launch under the choice
    as a mask over the table's positions where the launch runs and walking a
    group's pages whole reads no more than gathering each row's choice
    (``_choice_walks_pages``), their latent rows gathered into one block
    otherwise. Returns (o [B, 1, H, v], the page array, the index-key pages
    or None)."""
    b, heads, nope = q_nope.shape
    idx, ps, lengths = env["page_indices"], env["page_size"], env["lengths"]
    shape, walk = env["page_walk"]
    key_pages = None
    with jax.named_scope(telemetry.ENGINE_KV_WRITE):
        # a point scatter: page and slot are indices, the row the window
        at = (idx[jnp.arange(b), lengths // ps], lengths % ps)
        pages = pages.at[at].set(row, mode="drop")
        if index is not None:
            q_i, w_i, k_i, key_pages = index
            key_pages = key_pages.at[at].set(k_i.astype(key_pages.dtype), mode="drop")
    walked = index is not None and _choice_walks_pages(cfg, pages, walk, shape["rows"])
    mask = None
    if index is not None:
        with jax.named_scope(telemetry.MODEL_INDEX_SCORE):
            scores = index_paged_scores(q_i, w_i, key_pages, walk, **shape)
        with jax.named_scope(telemetry.MODEL_INDEX_SELECT):
            if walked:  # the choice as it is made: a mask over the table's positions
                visible = jnp.arange(scores.shape[-1], dtype=jnp.int32) <= lengths[:, None]
                mask = chosen_mask(scores, visible, cfg.index_topk).astype(FOLD_MASK_DTYPE)
            else:
                chosen, seen = chosen_tokens(scores, lengths, cfg.index_topk)
    with jax.named_scope(
            telemetry.MODEL_LATENT_ATTN if index is None else telemetry.MODEL_INDEXED_ATTN):
        w = p["wkv_b"]
        if lora is not None and "wkv_b" in lora:
            ab = lora["wkv_b"]
            w = (w.astype(jnp.float32)
                 + lora_scale * (ab["a"] @ ab["b"])).astype(w.dtype)
        w_k, w_v = split_kvb(w, heads, nope, cfg.v_head_dim)
        q_row = absorbed_query(q_nope, q_pe, w_k)
        q_row = jnp.pad(q_row, ((0, 0), (0, 0), (0, cfg.latent_row - cfg.latent_dim)))
        scale = cfg.head_dim ** -0.5
        if index is None or walked:
            carry = absorbed_decode(
                q_row, pages, walk, lengths, scale, mask, rank=cfg.kv_lora_rank, **shape)
        else:  # the chosen tokens' rows, [B, index_topk, latent_row], in one block
            # a token's page by a compare over the row's few columns: a gather of
            # 131k scalars took 6.7 ms a step on the v5e where this takes none
            column = jnp.arange(walk.cols.shape[1], dtype=jnp.int32)
            page = jnp.where((chosen // ps)[..., None] == column, walk.cols[:, None], 0).sum(-1)
            held = pages[page, chosen % ps]
            carry = absorbed_attention(q_row, held, seen, scale)
        return absorbed_output(carry, w_v, q_nope.dtype)[:, None], pages, key_pages


def _choice_walks_pages(cfg: ModelConfig, pages, walk, rows: int) -> bool:
    """Whether a decode step under a learned index's choice walks the rows'
    pages whole behind the choice's mask (``absorbed_decode``'s launch)
    rather than gathering each row's chosen tokens: where the launch runs, and
    a group's ``rows`` rows gather at least as many tokens (``index_topk``
    each) as the table's width holds (a group reads its shared pages once): a
    group of one row, or a table of 200k tokens, keeps the gather. From
    shapes alone."""
    width = walk.cols.shape[1] * pages.shape[1]
    return (rows * cfg.index_topk >= width
            and absorbed_decode_impl(cfg.num_heads, pages, rows) == "kernel")


def _layer_norm(x, weight, bias, eps: float):
    """LayerNorm over the last axis, float32 inside."""
    y = x.astype(jnp.float32)
    y = y - y.mean(axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def _index_inputs(h, c_q, p, *, cfg, env):
    """The index's side of a layer (module docstring): ``(q_I [B, S, H_I, D_I]``
    from the normed query latent, ``w [B, S, H_I]`` and ``k_I [B, S, D_I])``
    from the layer's normed input; q_I and k_I rotated on their first
    ``qk_rope_head_dim`` values. No adapter, and nothing differentiated: the
    choice they make is a set."""
    b, s, _ = h.shape
    rope = cfg.qk_rope_head_dim
    rotate = lambda x: jnp.concatenate(
        [rope_interleaved(x[..., :rope], env["cos"], env["sin"]), x[..., rope:]], axis=-1)
    q_i = linear(c_q, p["w_index_q"]).reshape(b, s, cfg.index_heads, cfg.index_head_dim)
    k_i = _layer_norm(linear(h, p["w_index_k"]), p["index_k_norm"], p["b_index_k"],
                      INDEX_NORM_EPS)
    return jax.lax.stop_gradient((rotate(q_i), linear(h, p["w_index_w"]), rotate(k_i)))


def _segment_choice(q_i, w_i, key_pages, *, cfg, env):
    """A prefill segment's choice, ``[B, S, W * page_size]`` over the positions
    of the row's page table, non-zero where the query attends, in the type the
    folds read it in (``FOLD_MASK_DTYPE``, made by the operation that makes the
    mask, not by a pass of its own): each query's index scores over the blocks
    of keys up to and including its own (from the pages, the segment's own just
    written), then ``chosen_mask``. While the segment ends within
    ``index_topk`` tokens every query attends all it sees, and nothing is
    scored."""
    idx, ps, start = env["page_indices"], env["page_size"], env["segment_start"]
    b, s = q_i.shape[:2]
    per, width = s // ps, idx.shape[1] * ps
    visible = jnp.arange(width, dtype=jnp.int32)[None, None, :] <= env["q_pos"][:, :, None]

    def choose():
        def block(j, out):
            at = jax.lax.dynamic_slice_in_dim(idx, j * per, per, axis=1)
            keys = key_pages[at].reshape(b, s, -1)
            return jax.lax.dynamic_update_slice_in_dim(
                out, index_scores(q_i, w_i, keys), j * s, axis=2)

        with jax.named_scope(telemetry.MODEL_INDEX_SCORE):
            scores = jax.lax.fori_loop(
                0, start // s + 1, block, jnp.zeros((b, s, width), jnp.float32))
        with jax.named_scope(telemetry.MODEL_INDEX_SELECT):
            return chosen_mask(scores, visible, cfg.index_topk).astype(FOLD_MASK_DTYPE)

    return jax.lax.cond(
        start + s <= cfg.index_topk, lambda: visible.astype(FOLD_MASK_DTYPE), choose)


def _latent_mix(q_nope, q_pe, c, k_pe, pages, p, lora, *, cfg, mode, env, proj,
                lora_scale, index=None):
    """Latent attention in each mode, behind the index's choice where
    ``index = (q_I, w, k_I, the layer's index-key pages or None)`` is given.
    Returns (o [B, S, H, v], the layer's page array or None, its index-key
    pages or None)."""
    b, s, heads, nope = q_nope.shape
    expand = lambda rows: proj(rows, p, lora, "wkv_b", "bkv_b", lora_scale).reshape(
        rows.shape[0], rows.shape[1], heads, nope + cfg.v_head_dim)
    if mode == "full":
        with jax.named_scope(telemetry.MODEL_ATTN_CORE):
            pos = jnp.arange(s)
            mask = (pos[None, :] <= pos[:, None])[None] & (env["valid"] > 0)[:, None, :]
        if index is not None:
            with jax.named_scope(telemetry.MODEL_INDEX_SCORE):
                scores = index_scores(*index[:3])
            with jax.named_scope(telemetry.MODEL_INDEX_SELECT):
                mask = chosen_mask(scores, mask, cfg.index_topk)
        with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
            kv = expand(c)
        with jax.named_scope(telemetry.MODEL_ATTN_CORE):
            return expanded_finish(
                expanded_attention(q_nope, q_pe, kv, k_pe, mask), c.dtype), None, None
    idx, ps, rank = env["page_indices"], env["page_size"], cfg.kv_lora_rank
    rope = cfg.qk_rope_head_dim
    row = jnp.concatenate(  # [B, S, latent_row]: [c, k_pe] and zeros to whole tiles
        [c, k_pe, jnp.zeros((b, s, cfg.latent_row - cfg.latent_dim), c.dtype)],
        axis=-1).astype(pages.dtype)
    if mode == "decode":
        if index is not None:  # one token a row
            index = (*(x[:, 0] for x in index[:3]), index[3])
        return _absorbed_decode(q_nope[:, 0], q_pe[:, 0], row[:, 0], pages, p, lora,
                                cfg=cfg, env=env, lora_scale=lora_scale, index=index)
    # one page-aligned segment of a prefill, every row at offset ``start``:
    # write its pages whole, then attend over the row's pages up to and
    # including them, a segment's worth of keys at a time, expanded
    start, per = env["segment_start"], s // ps
    key_pages = chosen = None
    with jax.named_scope(telemetry.ENGINE_KV_WRITE):
        dest = jax.lax.dynamic_slice_in_dim(idx, start // ps, per, axis=1)
        pages = pages.at[dest.reshape(-1)].set(row.reshape(b * per, ps, -1))
        if index is not None:
            q_i, w_i, k_i, key_pages = index
            key_pages = key_pages.at[dest.reshape(-1)].set(
                k_i.reshape(b * per, ps, -1).astype(key_pages.dtype))
    if index is not None:
        chosen = _segment_choice(q_i, w_i, key_pages, cfg=cfg, env=env)

    def block(j):  # the segment's own keys come from its pages too
        with jax.named_scope(telemetry.ENGINE_KV_WRITE):
            at = jax.lax.dynamic_slice_in_dim(idx, j * per, per, axis=1)
            held = pages[at].reshape(b, s, -1).astype(c.dtype)
        with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
            return expand(held[..., :rank]), held[..., rank: rank + rope]

    with jax.named_scope(telemetry.MODEL_ATTN_CORE):
        return expanded_segment(
            q_nope, q_pe, block, start, cfg.v_head_dim, c.dtype, chosen), pages, key_pages


def _latent_block(x, p, lora, cache, *, kind: str, cfg: ModelConfig, mode: str,
                  env: dict, proj, lora_scale: float):
    """One latent-attention layer with a dense MLP ("latent") or routed
    experts ("latent_moe"), or one SUBLAYER of a shortcut-connected layer
    ("latent_fork", "latent_join": module docstring; ``x`` and what is
    returned in its place are then ``(the stream, the layer's experts' part)``):
    (x, the layer's page array, the expert layer's stats or None). Where the
    model has an index, ``cache`` and what is returned in its place are the
    pair (latent pages, index-key pages)."""
    carried = None
    if kind in SHORTCUT_KINDS:
        x, carried = x
    b, s, _ = x.shape
    heads, nope, rank = cfg.num_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    pages, key_pages = cache if cfg.index_topk and cache is not None else (cache, None)
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        c_q = h
        if "wq_a" in p:  # the query's own normed latent
            c_q = rms_norm(proj(h, p, lora, "wq_a", "bq_a", lora_scale), p["q_a_norm"],
                           cfg.rms_norm_eps)
            if cfg.latent_q_scale != 1.0:
                c_q = c_q * jnp.asarray(cfg.latent_q_scale, c_q.dtype)
        q = proj(c_q, p, lora, "wq", "bq", lora_scale).reshape(b, s, heads, cfg.head_dim)
        kva = proj(h, p, lora, "wkv_a", "bkv_a", lora_scale)
        c = rms_norm(kva[..., :rank], p["kv_a_norm"], cfg.rms_norm_eps)
        if cfg.latent_kv_scale != 1.0:  # the row that is cached holds the scaled latent
            c = c * jnp.asarray(cfg.latent_kv_scale, c.dtype)
    with jax.named_scope(telemetry.MODEL_ATTN_CORE):
        q_pe = rope_interleaved(q[..., nope:], env["cos"], env["sin"])
        k_pe = rope_interleaved(kva[..., rank:], env["cos"], env["sin"])
    index = None
    if cfg.index_topk:
        with jax.named_scope(telemetry.MODEL_INDEX_SCORE):
            index = (*_index_inputs(h, c_q, p, cfg=cfg, env=env), key_pages)
    o, pages, key_pages = _latent_mix(
        q[..., :nope], q_pe, c, k_pe, pages, p, lora, cfg=cfg, mode=mode, env=env,
        proj=proj, lora_scale=lora_scale, index=index)
    if cfg.index_topk:
        pages = (pages, key_pages)
    with jax.named_scope(telemetry.MODEL_ATTN_PROJ):
        x = x + proj(o.reshape(b, s, heads * cfg.v_head_dim), p, lora, "wo", "bo",
                     lora_scale)
    if kind == "latent_moe":
        x, stats = _expert_half(x, p, lora, cfg=cfg, env=env, proj=proj,
                                lora_scale=lora_scale)
        return x, pages, stats
    stats = None
    if kind == "latent_fork":  # the experts read what this sublayer's MLP reads
        with jax.named_scope(telemetry.MODEL_MOE_ROUTER):
            u = rms_norm(x, p["mlp_norm"], cfg.rms_norm_eps)
        carried, stats = moe_half(u, p, cfg, held=cfg.held_experts,
                                  alive=env.get("alive"))
    x = _mlp_half(x, p, lora, cfg=cfg, proj=proj, lora_scale=lora_scale)
    if kind == "latent_join":  # ... and join the stream after the second MLP
        x = x + carried
    return (x if carried is None else (x, carried)), pages, stats


def _shared_layers(block):
    """``block`` with the layers of one kind traced and lowered ONCE a call of
    ``forward``: each is a ``jax.jit`` of its own, so a second layer of the
    same kind and shapes is a call of the first one's function (the compiler
    inlines it: the executable is what the unrolled layers give). A prefill
    program holds a segment body a stage (``paged_engine._paged_prefill_hybrid``)
    and every body is every layer again: at start-up the tracing and lowering
    of a body of 28 layers of two kinds is that of two. An expert layer reads
    its own slice of the stacked experts at a static index
    (``p["experts_layer"]``), so it shares with no other and is traced in
    line, as before: a ``jax.jit`` of its own would cost it a trace more."""
    traced: dict = {}

    def run(kind, x, p, lora_p, rate, held, key):
        return block(x, p, lora_p, rate, held, kind=kind, dropout_rng=key)

    def call(x, p, lora_p, rate, held, *, kind: str, dropout_rng):
        if "experts_layer" in p:  # nothing to share, and a jit of its own costs a trace
            return block(x, p, lora_p, rate, held, kind=kind, dropout_rng=dropout_rng)
        if kind not in traced:
            traced[kind] = jax.jit(partial(run, kind))
        return traced[kind](x, p, lora_p, rate, held, dropout_rng)

    return call


def _pack_left(ids, mask):
    """Rows whose real tokens are contiguous, moved to column 0. Returns
    (ids, valid, the column each packed column came from)."""
    s = ids.shape[1]
    real = mask.sum(axis=-1).astype(jnp.int32)
    shift = jnp.argmax(mask > 0, axis=-1).astype(jnp.int32)
    cols = (jnp.arange(s)[None, :] + shift[:, None]) % s
    valid = (jnp.arange(s)[None, :] < real[:, None]).astype(jnp.int32)
    return jnp.take_along_axis(ids, cols, axis=1) * valid, valid, shift


def forward_hybrid(
    params: Params, cfg: ModelConfig, input_ids: jax.Array, *,
    attention_mask=None, positions=None, lora=None, lora_scale: float = 1.0,
    kv_cache: Params | None = None, remat: bool = False,
    attn_impl: str = "reference", logits_slice=None, logits_positions=None,
    page_size: int = 0, lora_dropout: float = 0.0, dropout_rng=None,
    skip_lm_head: bool = False, paged_impl: str = "auto", **unsupported,
):
    """``transformer.forward`` for a model with per-layer mixers: same
    arguments, same returns. ``unsupported`` holds the dense decoder's other
    switches; one that is on is refused by name."""
    b, s = input_ids.shape
    mode = _mode(kv_cache, s, {
        name: unsupported.get(name) for name in
        ("paged_verify", "paged_chunked", "paged_prefix", "attn_mesh")
    })
    if attn_impl in ("ring", "ulysses"):
        raise NotImplementedError(
            f"attn_impl={attn_impl!r} shards dense attention over the sequence; "
            f"{cfg.mixer_names} layers are not dense attention"
        )
    shift = None
    if mode == "full":
        if attention_mask is None:
            attention_mask = jnp.ones((b, s), jnp.int32)
        with jax.named_scope(telemetry.MODEL_EMBED):
            input_ids, valid, shift = _pack_left(input_ids, attention_mask)
        q_pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
        rope_pos = q_pos
        env: dict = {"valid": valid, "q_pos": q_pos, "attn_impl": attn_impl}
    elif mode == "decode":
        lengths = kv_cache["lengths"]
        rope_pos = lengths[:, None] if positions is None else positions
        env = {
            "lengths": lengths, "page_indices": kv_cache["page_indices"],
            "page_size": page_size, "alive": kv_cache.get("alive"),
            "paged_impl": paged_impl,
        }
        if cfg.latent:  # read off the table once a step, for every layer
            with jax.named_scope(telemetry.MODEL_INDEX_SCORE if cfg.index_topk
                                 else telemetry.MODEL_LATENT_ATTN):
                env["page_walk"] = _latent_page_walk(env, cfg, kv_cache["k"][0])
    else:
        start = kv_cache["segment_start"]
        q_pos = start + jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
        rope_pos = q_pos
        env = {
            "segment_start": start, "q_pos": q_pos,
            "valid": attention_mask, "page_indices": kv_cache["page_indices"],
            "page_size": page_size,
        }
    if (cfg.latent or cfg.power or cfg.window_moe or cfg.cca
            or cfg.kind_count("lightning")):  # the others rotate nothing
        with jax.named_scope(
                telemetry.MODEL_ATTN_CORE if cfg.latent else
                telemetry.MODEL_POWER_ATTN if cfg.power else
                telemetry.MODEL_WINDOW_ATTN if cfg.window_moe else
                telemetry.MODEL_CCA_MIX if cfg.cca else
                telemetry.MODEL_LINEAR_ATTN):
            env["cos"], env["sin"] = rope_cos_sin(
                rope_pos, cfg.rotary_dim or cfg.qk_rope_head_dim
                or cfg.lightning_head_dim or cfg.head_dim,
                (cfg.window_rope_theta if cfg.window_moe else 0.0) or cfg.rope_theta)
        if cfg.window_moe and cfg.attn_use_rope:  # the full layers' own base
            with jax.named_scope(telemetry.MODEL_ATTN_CORE):
                env["cos_full"], env["sin_full"] = rope_cos_sin(
                    rope_pos, cfg.rotary_dim or cfg.head_dim, cfg.rope_theta)

    with jax.named_scope(telemetry.MODEL_EMBED):
        x = jnp.take(params["embed"], input_ids, axis=0)
        if cfg.scale_emb != 1.0:
            x = x * jnp.asarray(cfg.scale_emb, x.dtype)
    if cfg.cca:  # the layer loop's carry: the stream, and the router's r_{l-1}
        x = (x, jnp.zeros((b, s, cfg.router_hidden_size), jnp.float32))
    if cfg.shortcut_moe:  # the stream, and what the layer's experts gave
        x = (x, jnp.zeros_like(x))

    rates = (jnp.asarray(cfg.lightning_decay_rates())
             if cfg.kind_count("lightning") else None)
    use_dropout = dropout_rng is not None and lora_dropout > 0.0
    layer_keys = (jax.random.split(dropout_rng, len(cfg.layer_kinds))
                  if use_dropout else None)
    block = partial(
        _block, cfg=cfg, mode=mode, env=env, lora_scale=lora_scale,
        lora_dropout=lora_dropout if use_dropout else 0.0,
    )
    stacks = params["layers"]
    lora_stacks = lora["layers"] if lora is not None else {}
    if mode == "segment":
        block = _shared_layers(block)

    if mode == "full":
        runs = cfg.layer_runs
        if cfg.shortcut_moe:  # ONE scan over the published layers, a step both sublayers
            runs = ((SHORTCUT_KINDS, 0, 0, cfg.num_layers),)
        for kinds, first, at, count in runs:
            kinds = kinds if isinstance(kinds, tuple) else (kinds,)
            take = lambda tree: jax.tree_util.tree_map(
                lambda w: w[at: at + count], tree)
            keys = None
            if use_dropout:  # a key a sublayer: [count, len(kinds), ...]
                keys = layer_keys[first: first + count * len(kinds)].reshape(
                    count, len(kinds), *layer_keys.shape[1:])
            xs = tuple((
                take(stacks[kind]),
                take(lora_stacks[kind]) if kind in lora_stacks else None,
                rates[at: at + count] if kind == "lightning" else None,
                keys[:, j] if use_dropout else None,
            ) for j, kind in enumerate(kinds))

            def body(x, xs, kinds=kinds):
                for kind, (p, lora_p, rate, key) in zip(kinds, xs):
                    x = block(x, p, lora_p, rate, None, kind=kind, dropout_rng=key)[0]
                return x, None

            if remat:
                # keeps a layer's input and nothing else, whatever policy the
                # caller brings. The dense decoder's scan keeps the weights'
                # products that fit the device's memory (learner/remat.py);
                # here a layer's cost differs by kind (states, experts) and no
                # benchmark cell times these families' learners (ROADMAP R15),
                # so a policy for them would be a guess: left for the PR that
                # measures one
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.nothing_saveable)
            x, _ = jax.lax.scan(body, x, xs)
        if cfg.cca or cfg.shortcut_moe:
            x = x[0]
        with jax.named_scope(telemetry.MODEL_HEAD):
            # back to the caller's columns before it slices the positions it wants
            cols = (jnp.arange(s)[None, :] - shift[:, None]) % s
            x = jnp.take_along_axis(x, cols[:, :, None], axis=1)
            return _head(x, params, cfg, logits_slice, logits_positions,
                         skip_lm_head), None

    # cache modes: an unrolled loop over per-layer cache buffers (a stacked
    # cache carried through a scan is ping-ponged whole: transformer.forward)
    new = {name: list(kv_cache[name]) for name in ("k", "v", *ROW_STATES)
           if name in kv_cache}
    stats = kv_cache.get("sel_stats")
    # an expert layer's four (``moe_half``): the pairs' two, the blocks' two; a
    # prefill's cache carries the blocks alone
    moe_stats = (jnp.zeros((4 + bool(cfg.zero_experts),), jnp.int32)
                 if "moe_stats" in kv_cache or "moe_blocks" in kv_cache else None)
    at = dict.fromkeys(cfg.layer_kinds, 0)
    held_at = dict.fromkeys(_MIXER_CACHE, 0)  # a mixer's layers, whatever follows them
    for i, kind in enumerate(cfg.layer_kinds):
        j = at[kind]
        at[kind] += 1
        mixer = mixer_of(kind)
        whole = {k: v for k, v in stacks[kind].items() if k.startswith("experts_")}
        p = _slice_layer(
            {k: v for k, v in stacks[kind].items() if k not in whole}, j)
        if whole:  # the grouped products read the stack whole (models/moe.py)
            p.update(whole, experts_layer=j)
        lora_p = _slice_layer(lora_stacks[kind], j) if kind in lora_stacks else None
        if cfg.latent:  # every layer keeps pages: layer i's are new["k"][i]
            held = (new["k"][i], new["v"][i]) if cfg.index_topk else new["k"][i]
            x, held, layer_stats = block(
                x, p, lora_p, None, held, kind=kind,
                dropout_rng=layer_keys[i] if use_dropout else None)
            if cfg.index_topk:  # and its index keys new["v"][i]
                new["k"][i], new["v"][i] = held
            else:
                new["k"][i] = held
            if moe_stats is not None and layer_stats is not None:
                moe_stats = moe_stats + layer_stats
        elif mixer in _MIXER_CACHE:
            names, m = _MIXER_CACHE[mixer], held_at[mixer]
            held_at[mixer] += 1
            x, held, layer_stats = block(
                x, p, lora_p, None, tuple(new[name][m] for name in names), kind=kind,
                dropout_rng=layer_keys[i] if use_dropout else None)
            for name, piece in zip(names, held):
                new[name][m] = piece
            if moe_stats is not None and layer_stats is not None:
                moe_stats = moe_stats + layer_stats
        elif kind == "power":
            x, (new["power"][j], new["power_z"][j]), _ = block(
                x, p, lora_p, None, (new["power"][j], new["power_z"][j]), kind=kind,
                dropout_rng=layer_keys[i] if use_dropout else None)
        elif kind == "sparse":
            held = (new["k"][j], new["v"][j], new["pooled"][j])
            x, held, layer_stats = block(
                x, p, lora_p, None, held, kind=kind,
                dropout_rng=layer_keys[i] if use_dropout else None)
            new["k"][j], new["v"][j], new["pooled"][j] = held
            if stats is not None and layer_stats is not None:
                stats = stats + layer_stats.astype(stats.dtype)
        else:
            x, new["lin"][j], _ = block(
                x, p, lora_p, rates[j], new["lin"][j], kind=kind,
                dropout_rng=layer_keys[i] if use_dropout else None)
    if cfg.cca or cfg.shortcut_moe:
        x = x[0]
    with jax.named_scope(telemetry.MODEL_HEAD):
        logits = _head(x, params, cfg, logits_slice, logits_positions, skip_lm_head)
    out = {**kv_cache, **{name: tuple(vals) for name, vals in new.items()}}
    if stats is not None:
        out["sel_stats"] = stats
    for name, part in (("moe_stats", slice(0, 2)), ("moe_blocks", slice(2, 4)),
                       ("moe_zero", slice(4, 5))):
        if name in kv_cache:
            out[name] = kv_cache[name] + moe_stats[part]
    if "moe_routed" in kv_cache:  # the router's choices over ALL experts: live tokens x k a layer
        live = b * s if env.get("alive") is None else env["alive"].sum() * s
        expert_layers = sum(1 for k in cfg.layer_kinds if cfg.layer_ffn(k) == "experts")
        out["moe_routed"] = kv_cache["moe_routed"] + jnp.asarray(
            expert_layers * cfg.experts_per_token * live, jnp.int32)
    if "power_stats" in kv_cache and mode == "decode":  # live rows' states, every layer
        live = b if env.get("alive") is None else env["alive"].sum()
        out["power_stats"] = kv_cache["power_stats"] + jnp.asarray(
            cfg.num_layers * live, jnp.int32)
    if "ssm_stats" in kv_cache and mode == "decode":  # live rows' states, Mamba layers
        live = b if env.get("alive") is None else env["alive"].sum()
        out["ssm_stats"] = kv_cache["ssm_stats"] + jnp.asarray(
            (cfg.kind_count("mamba") + cfg.kind_count("mamba2")) * live, jnp.int32)
    # keys a live row's decoded token attends of those it sees, in whole units:
    # every window layer alike, and every layer of a model with an index
    for name, layers, most, unit in (
            ("window_stats", cfg.mixer_count("window"), cfg.sliding_window, WINDOW_COUNT_UNIT),
            ("index_stats", cfg.num_layers, cfg.index_topk, INDEX_COUNT_UNIT)):
        if name in kv_cache and mode == "decode":
            alive = env.get("alive")
            keys = (env["lengths"] + 1) * (1 if alive is None else alive.astype(jnp.int32))
            units = lambda n: (-(-n // unit)).sum()
            out[name] = kv_cache[name] + layers * (
                jnp.stack([units(jnp.minimum(keys, most)), units(keys)]))
    if "latent_stats" in kv_cache and mode == "decode":  # every layer walks alike
        out["latent_stats"] = (
            kv_cache["latent_stats"] + cfg.paged_layers * env["page_walk"][1].stats)
    return logits, out
