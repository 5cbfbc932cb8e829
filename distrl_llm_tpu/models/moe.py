"""Routed experts (DeepSeek-V3's block: sigmoid router with a correction bias,
top-k without groups, a shared expert beside the routed ones).

``h`` is the layer's normed input, ``[T, D]`` (any leading shape is flattened)::

    s   = sigmoid(h W_g)                         float32, [T, E]
    idx = top-k of (s + b)                       b: e_score_correction_bias
    w   = s[idx] / (sum s[idx] + 1e-20) * routed_scaling_factor
    y   = sum_k w_k E_idx_k(h)                   E_e(h) = W_down_e(silu(W_gate_e h) * (W_up_e h))

The shared expert is a plain gated MLP and goes through the decoder's own
``_mlp_half`` (``models/hybrid.py``); this module is the routed part.

**An ungated expert** (``nemotron_h``, NVIDIA-Nemotron-3-Nano: experts in a
layer of their own, no mixer before them) is TWO matrices and a squared ReLU,
``E_e(h) = W_down_e relu(W_up_e h)^2``. The form is read off the stack: where
it holds no ``gate`` (the layer no ``experts_gate`` leaf) both forms below
multiply two matrices an expert; routing, dispatch, the share held and the
counters are the same code. The shared expert beside them is ungated too
(``_mlp_half`` reads it off the layer the same way).

**Dropless.** There is no capacity: every (token, expert) pair is computed at
any imbalance. Plain XLA throughout, in two forms. ``expert_form`` chooses
between them, and the grouped form's block, from the rows a HELD expert is
given by the call, ``t * k / n_experts``: all of it static shapes (PERF.md,
PR 33, PR 58 and PR 66 have the chip's times at seven cells' widths):

* **grouped** (a prefill segment of 4,096 tokens, the learner with its
  backward): the pairs are sorted by expert and laid out so that every block
  of rows belongs to ONE expert (a group is padded to whole blocks: at most
  one block of padding an expert, whatever the imbalance); a ``lax.scan`` over
  the blocks multiplies each by its expert's three matrices, picked from the
  stack by a dynamic index that fuses into the products; the results go back
  by one gather and a weighted sum over k. The scan steps over ``pairs //
  block + groups`` blocks, enough for any imbalance, and a block does work
  only if it holds a pair of an expert held HERE (a ``lax.cond`` a block, in
  forward and in reverse mode alike: the live branch gathers the block's own
  rows and multiplies them, the other returns zeros that no pair reads). The
  pairs held elsewhere sort last, so the live blocks are a prefix: an eighth
  or a sixteenth of the blocks where a program holds 16 of 128 or of 256
  experts (``blocks`` says how many). No scatter-add: the combine is
  deterministic and its transpose cheap. ``lax.ragged_dot`` (XLA's native
  grouped kernel) read the same at 4,096 tokens and 1.6x slower at 64,
  carries no scope name into the trace, and copies a layer sliced from the
  stack for its custom call; it was not kept.
* **dense** (a decode step: 64 tokens x 6 choices touch all 64 experts; 256
  tokens x 12 choices over 768 outputs give each of 16 held experts 4 pairs):
  every expert held runs on every token, one batched product an expert matrix,
  and a combine matrix ``[T, E]``, zero outside the chosen k, weights the
  results. The step must read every expert once in any case; this form reads
  them once and nothing else (90% of the experts' bandwidth roofline where
  grouped blocks of 64 rows read 62%): no sort, no scatter, no gather over
  ALL the call's pairs, which is what the grouped form pays whoever holds
  their experts.

**Experts held.** ``held`` names the experts whose weights this program holds
(``experts`` is stacked over them, in that order), the layer a chip of an
expert-parallel deployment runs: the router scores ALL experts and chooses
among all; pairs whose expert is elsewhere add nothing here. Over disjoint
shares the results sum to the whole layer's (``tests/test_latent_moe.py``).
``None`` holds all.

**An MLP router that reads the layer before** (``zaya``: ``route_mlp``) is the
second routing function: ONE expert a token, weighted by its probability::

    r_l = h W_d + b_d + gamma_l * r_{l-1}        [T, R] float32; r_{-1} = 0, r_l handed on
    z   = RMSNorm_R(r_l)
    p   = softmax(W_3 gelu(W_2 gelu(W_1 z + b_1) + b_2))        [T, E]
    e   = argmax(p + beta), the lower index among equals;   y = p_e E_e(h)

The experts' side is ``routed_experts`` as it stands, ``k = 1``.

**A softmax router over experts of which some compute nothing**
(``longcat_flash``: ``cfg.router_softmax``, ``cfg.zero_experts``) is ``route``
with the softmax in the sigmoid's place over ``cfg.router_width`` outputs, the
routed experts' ids first and ``zero_experts`` further ids after them, and the
chosen scores as weights unnormalised (``norm_topk_prob`` false)::

    s = softmax(h W_r);  idx = top-k of (s + b);  w = routed_scaling_factor * s[idx]
    y = sum_{k: idx_k routed, held here} w_k E_idx_k(h) + (sum_{k: idx_k zero} w_k) * h

A zero-compute choice is an identity: it costs no product, belongs to no
block of either form (``_local_ids`` sends it where the pairs held elsewhere
go) and is added WHOLE here whatever share of the routed experts is held, as
a shared expert is (``zero_part``; counted apart, the fifth of ``moe_half``'s
stats).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from distrl_llm_tpu import telemetry
from distrl_llm_tpu.models.configs import ModelConfig

#: added to the sum of the chosen scores before dividing (the published code's)
NORM_EPS = 1e-20
#: pairs one call of the grouped form lays out at most: a call of more tokens
#: runs in equal runs of tokens, one after another (``moe_half``). The form's
#: buffers are sized by ALL the pairs, whoever holds their experts (a padded row
#: of the hidden width a pair, and the same again gathered back): 2.5 GB each at
#: 16 x 1,024 tokens x 12 choices of 6,144, where 1 pair in 48 is held here.
#: No call of a cell that was there before this line has more (65,536 at most)
GROUPED_MAX_PAIRS = 65536


def route(h: jax.Array, router: jax.Array, bias: jax.Array, cfg: ModelConfig):
    """``h [T, D]`` -> ``(idx [T, k] int32, w [T, k] float32)``. Scores, the
    choice and the weights are float32 at full precision: with bf16 scores two
    experts tie often, and the choice is not continuous."""
    with jax.named_scope(telemetry.MODEL_MOE_ROUTER):
        logits = jnp.dot(
            h.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        scores = (jax.nn.softmax(logits, axis=-1) if cfg.router_softmax
                  else jax.nn.sigmoid(logits))
        _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32),
                               cfg.experts_per_token)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        if cfg.norm_topk_prob:
            w = w / (w.sum(axis=-1, keepdims=True) + NORM_EPS)
        return idx.astype(jnp.int32), w * cfg.routed_scaling_factor


def route_mlp(h: jax.Array, carried: jax.Array, p: dict, cfg: ModelConfig):
    """``h [..., D]``, ``carried [..., R]`` (the previous layer's ``r``, zeros
    before the first) -> ``(idx [T, 1] int32, w [T, 1] float32, r [..., R])``
    (module docstring). Float32 at full precision throughout, as ``route`` is
    and for its reason; the balancing bias ``beta`` (``e_score_bias``) is in
    the choice and not in the weight."""
    with jax.named_scope(telemetry.MODEL_MOE_ROUTER):
        f32 = lambda name: p[name].astype(jnp.float32)
        dot = lambda x, name: jnp.dot(x, f32(name), precision=jax.lax.Precision.HIGHEST)
        r = dot(h.astype(jnp.float32), "router_down") + f32("b_router_down")
        r = r + f32("router_gamma") * carried
        z = r * jax.lax.rsqrt(
            jnp.mean(r * r, axis=-1, keepdims=True) + cfg.rms_norm_eps) * f32("router_norm")
        gelu = lambda x: jax.nn.gelu(x, approximate=False)
        z = gelu(dot(z, "router_w1") + f32("b_router_w1"))
        z = gelu(dot(z, "router_w2") + f32("b_router_w2"))
        prob = jax.nn.softmax(dot(z, "router_w3"), axis=-1)
        prob = prob.reshape(-1, prob.shape[-1])
        idx = jnp.argmax(prob + f32("e_score_bias"), axis=-1)[:, None]
        return idx.astype(jnp.int32), jnp.take_along_axis(prob, idx, axis=-1), r


def expert_form(t: int, k: int, n_experts: int) -> int:
    """The form of ``routed_experts`` for a call of ``t`` tokens x ``k`` choices
    over ``n_experts`` router outputs: the rows of one block of the grouped
    form, or 0, the dense form. Both follow from the rows a HELD expert is
    given, ``t * k / n_experts`` whoever holds the others:

    * a block is the power of two above them (room for an uneven router: a
      group that fills its block takes a second, and reads its expert again),
      within [64, 256]: under 64 rows nothing is left to save, a block's time
      is its expert's bytes, and past 256 the matrix unit's time is, so a
      larger block only pads more (the fastest of 32-256 in 22 of 23 calls
      timed, 4-384 rows an expert, experts all held or a share held alike,
      the other within 1.3%: PERF.md, PR 66);
    * the dense form is ONE block of ``t`` rows an expert with every token in
      it: where that pads an expert by no more than the grouped form may (a
      block of 256), it multiplies no more rows than the grouped form's worst
      and lays nothing out. 64 x 6 over 64, 192 x 1 over 16 and 256 x 12 over
      768 are dense by it, a segment of 1,024 tokens a row is grouped; timed,
      the two forms cross at 320-430 tokens at five cells' widths.

    A test that needs one form whatever the shapes puts its own rule here."""
    given = -(-t * k // n_experts)
    if t - given <= 256:
        return 0
    return min(256, max(64, 1 << given.bit_length()))


def grouped_runs(t: int, k: int) -> int:
    """The equal runs of tokens a call of ``t`` tokens x ``k`` choices is made
    in: the fewest that divide ``t`` and lay ``GROUPED_MAX_PAIRS`` a run at most."""
    return next(n for n in range(-(-t * k // GROUPED_MAX_PAIRS), t + 1) if t % n == 0)


def relu2(x):
    """``relu(x)^2``: an ungated expert's activation (module docstring)."""
    return jnp.square(jax.nn.relu(x))


#: an expert's matrices in the order its product takes them; an ungated
#: expert's stack holds the last two (module docstring)
_MATRICES = ("gate", "up", "down")


def _gated(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _expert(x, *matrices):
    """One expert on its rows: gated (gate, up, down) or ungated (up, down)."""
    if len(matrices) == 3:
        return _gated(x, *matrices)
    up, down = matrices
    return relu2(x @ up) @ down


def _local_ids(idx, n: int, n_experts: int, held):
    """Each pair's place in the stack of experts held, or ``n``: not here."""
    if held is None:
        return idx
    place = np.full((n_experts,), n, np.int32)  # ``held`` is static: a constant
    place[np.asarray(held)] = np.arange(n, dtype=np.int32)
    return jnp.asarray(place)[idx]


def routed_experts(h: jax.Array, idx: jax.Array, w: jax.Array, experts: dict,
                   *, n_experts: int, held=None, alive=None, layer=None):
    """``sum_k w_k E_idx_k(h)`` over the experts held. ``h [T, D]``, ``idx`` /
    ``w [T, k]``; ``experts = {"gate", "up" [n, D, F], "down" [n, F, D]}``, or
    ``up`` and ``down`` alone: ungated experts (module docstring).
    Returns ``(y [T, D], load [n] int32, blocks [2] int32)``: ``load`` counts
    the pairs each held expert computed (of ``alive`` tokens, if given),
    ``blocks`` the grouped form's blocks that ran and that were laid (zeros
    from the dense form; ``expert_form`` says which).

    With ``layer`` the stacks are ALL layers' ``[L, n, ...]``: the grouped
    form indexes (layer, expert) in one step, so no layer's experts are
    sliced out of the stack first."""
    t, k = idx.shape
    n = experts["down"].shape[-3]
    with jax.named_scope(telemetry.MODEL_MOE_DISPATCH):
        local = _local_ids(idx, n, n_experts, held)  # [T, k]
        counted = jnp.ones((t,), jnp.int32) if alive is None else alive.astype(jnp.int32)
        load = jnp.zeros((n + 1,), jnp.int32).at[local.reshape(-1)].add(
            jnp.repeat(counted, k))[:n]
    bm = expert_form(t, k, n_experts)
    if not bm:
        if layer is not None:
            experts = {name: x[layer] for name, x in experts.items()}
        with jax.named_scope(telemetry.MODEL_MOE_DISPATCH):
            comb = jnp.zeros((t, n + 1), jnp.float32).at[
                jnp.arange(t)[:, None], local].set(w)[:, :n]
        with jax.named_scope(telemetry.MODEL_MOE_EXPERTS):
            if "gate" in experts:
                act = jax.nn.silu(jnp.einsum("td,edf->etf", h, experts["gate"])) * (
                    jnp.einsum("td,edf->etf", h, experts["up"]))
            else:
                act = relu2(jnp.einsum("td,edf->etf", h, experts["up"]))
            y = jnp.einsum("etf,efd->etd", act, experts["down"])
        with jax.named_scope(telemetry.MODEL_MOE_DISPATCH):
            y = jnp.einsum("te,etd->td", comb, y.astype(jnp.float32)).astype(h.dtype)
        return y, load, jnp.zeros((2,), jnp.int32)
    groups = n + (held is not None)  # the pairs of experts held elsewhere: one more
    rows_a = t * k
    blocks = rows_a // bm + groups
    with jax.named_scope(telemetry.MODEL_MOE_DISPATCH):
        flat = local.reshape(rows_a)
        order = jnp.argsort(flat, stable=True)
        sizes = jnp.zeros((groups,), jnp.int32).at[flat].add(1)
        padded = -(-sizes // bm) * bm
        ends = jnp.cumsum(padded)
        # the padded row of each pair: its group's first block, then its rank there
        rank = jnp.zeros((rows_a,), jnp.int32).at[order].set(
            jnp.arange(rows_a, dtype=jnp.int32) - (jnp.cumsum(sizes) - sizes)[flat[order]])
        at = (ends - padded)[flat] + rank  # [T*k]
        # the token of each padded row (a row of padding reads token 0: no pair reads it back)
        src = jnp.zeros((blocks * bm,), jnp.int32).at[at].set(
            jnp.arange(rows_a, dtype=jnp.int32) // k)
        first = jnp.arange(blocks) * bm
        owner = jnp.minimum(jnp.searchsorted(ends, first, side="right"), n - 1)
        if layer is not None:
            owner = owner + layer * n
        # the pairs held elsewhere sort LAST: the blocks of the pairs held here
        # are a prefix, and the blocks after it (that group's, and what
        # ``blocks`` allots beyond the groups' padded sizes) hold nothing
        live = first < ends[n - 1]
    with jax.named_scope(telemetry.MODEL_MOE_EXPERTS):
        stacks = [experts[name].reshape(-1, *experts[name].shape[-2:])
                  for name in _MATRICES if name in experts]
        nothing = jnp.zeros((bm, stacks[-1].shape[-1]), jnp.result_type(h, *stacks))

        def product(tokens, e):
            with jax.named_scope(telemetry.MODEL_MOE_DISPATCH):
                x = h[tokens]  # this block's rows, and no other block's
            return _expert(x, *(stack[e] for stack in stacks))

        @jax.checkpoint  # reverse mode keeps a block's tokens, not its rows or matrices
        def one(_, block):
            tokens, e, run = block
            return None, jax.lax.cond(run, product, lambda *_: nothing, tokens, e)

        _, y = jax.lax.scan(one, None, (src.reshape(blocks, bm), owner, live))
    with jax.named_scope(telemetry.MODEL_MOE_DISPATCH):
        y = y.reshape(blocks * bm, -1)[at].reshape(t, k, -1)
        y = jnp.where((local < n)[..., None], y, 0)  # held elsewhere: nothing here
        y = jnp.einsum("tk,tkd->td", w, y.astype(jnp.float32)).astype(h.dtype)
    return y, load, jnp.stack([live.sum(dtype=jnp.int32), jnp.int32(blocks)])


def zero_part(h: jax.Array, idx: jax.Array, w: jax.Array, first: int, alive=None):
    """The experts that compute nothing (ids ``first`` and up; module
    docstring): ``(sum of a token's weights on them) * h`` and the pairs that
    chose one (of ``alive`` tokens, if given). ``h [T, D]``, ``idx`` / ``w [T, k]``."""
    with jax.named_scope(telemetry.MODEL_MOE_ZERO):
        chose = idx >= first
        weight = jnp.where(chose, w, 0.0).sum(axis=-1, keepdims=True)
        pairs = chose.sum(axis=-1, dtype=jnp.int32)
        if alive is not None:
            pairs = pairs * alive.astype(jnp.int32)
        return (weight * h.astype(jnp.float32)).astype(h.dtype), pairs.sum()


def moe_half(h: jax.Array, p: dict, cfg: ModelConfig, *, held=None, alive=None,
             choice=None):
    """The routed part of an expert layer on ``h [..., D]`` (normed). Returns
    ``(y like h, stats [4] int32)``: pairs computed, the fullest expert's, and
    the grouped form's blocks that ran and that were laid (``routed_experts``);
    where the router has zero-compute outputs their part is in ``y`` and the
    pairs that chose one are a fifth entry (``zero_part``).
    ``p["experts_layer"]``, if there, says that ``p["experts_*"]`` are every
    layer's and which is this one (``routed_experts``). ``choice`` is ``(idx,
    w)`` where the caller's own router chose (``route_mlp``)."""
    lead = h.shape[:-1]
    flat = h.reshape(-1, h.shape[-1])
    idx, w = choice or route(flat, p["router"], p["e_score_bias"], cfg)
    # ``alive`` is a flag a ROW: each of the row's tokens takes it
    alive = None if alive is None else jnp.repeat(alive, flat.shape[0] // alive.shape[0])
    experts = {name: p["experts_" + name] for name in _MATRICES if "experts_" + name in p}

    def some(tokens):
        h_r, idx_r, w_r, alive_r = tokens
        return routed_experts(
            h_r, idx_r, w_r, experts, n_experts=cfg.router_width, held=held,
            layer=p.get("experts_layer"), alive=alive_r)

    t, k = idx.shape
    runs = grouped_runs(t, k)
    if runs == 1:
        y, load, blocks = some((flat, idx, w, alive))
    else:  # equal runs of tokens, one after another: each lays out its own pairs
        cut = lambda x: None if x is None else x.reshape(runs, t // runs, *x.shape[1:])
        y, load, blocks = jax.lax.map(some, tuple(map(cut, (flat, idx, w, alive))))
        y, load, blocks = y.reshape(t, -1), load.sum(0), blocks.sum(0)
    zero_pairs = []
    zero_experts = getattr(cfg, "zero_experts", 0)  # a bag of sizes may not state any
    if zero_experts:
        zero, pairs = zero_part(flat, idx, w, cfg.router_width - zero_experts, alive)
        y, zero_pairs = y + zero, [pairs[None]]
    return y.reshape(*lead, -1), jnp.concatenate(
        [jnp.stack([load.sum(), load.max()]), blocks, *zero_pairs])
