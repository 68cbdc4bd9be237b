"""Mixture-of-Experts layer: top-k router with token-routed dispatch + EP.

New TPU-first capability (no reference analogue — the reference predates
MoE): E expert FFNs with a learned router. Two execution paths:

- ``routing="routed"`` (default): GShard/Switch-style capacity-factor
  einsum dispatch. Tokens are split into groups of ``router_group_size``;
  within each group every token's top-k experts claim a slot in that
  expert's capacity buffer (C = ceil(S * top_k * capacity_factor / E),
  token-order priority), a one-hot dispatch tensor [G,S,E,C] gathers the
  claimed tokens into [E,G,C,D], the expert FFNs run as batched einsums
  over the E-leading stacked weights, and a combine einsum (dispatch x
  renormalized gate) scatters results back. Everything is static-shaped
  einsum — MXU-friendly, differentiable, and GSPMD shards it over an
  'expert' mesh axis from the weight shardings alone (the combine's
  contraction over E becomes the psum; data-sharded tokens x
  expert-sharded buffers become the all-to-all). Tokens over capacity are
  dropped (contribute zero; the surrounding residual carries them) — the
  router is regularized toward balance by a Switch-style aux loss
  (``router_aux_weight``) surfaced through the layer-state channel as
  ``__aux_loss__`` and summed into the training loss by the containers.

- ``routing="dense"``: every expert on every token, zero-masked by the
  gate. Exact, smooth (finite-difference-checkable), no drops — the
  parity oracle for the routed path and the manual EP shard_map
  (parallel/expert_parallel.py). At top_k/E compute overcost E/top_k.

With ample capacity (capacity_factor >= E/top_k) the routed path drops
nothing and matches the dense path to float tolerance.

`DroplessMoELayer` (below) is the expert layer of the served models:
sigmoid scores, or a softmax over the top k logits, the top k normalised
and scaled, gated experts, a shared expert (gated, where the conf says
so), NO dropped (token, expert) pair at any imbalance, and a
configuration that says which of the router's experts this chip holds.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import FeedForwardLayer
from deeplearning4j_tpu.nn.conf.serde import register_config
from deeplearning4j_tpu.nn.layers.feedforward import gated_ffn
from deeplearning4j_tpu.nn.layers.base import (
    AUX_LOSS_KEY,
    LayerImpl,
    apply_dropout,
    register_impl,
)
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.ops.activations import get_activation


@register_config
@dataclasses.dataclass
class MixtureOfExpertsLayer(FeedForwardLayer):
    """Top-k gated expert FFNs: y = sum_k gate_k * FFN_{e_k}(x)."""

    n_experts: int = 8
    top_k: int = 2
    d_hidden: int = 0  # defaults to 4*n_in
    routing: str = "routed"  # "routed" (capacity dispatch) | "dense" (oracle)
    capacity_factor: float = 1.25
    router_group_size: int = 0  # tokens per routing group; 0 = auto (256)
    router_aux_weight: float = 0.01  # Switch-style load-balance loss weight

    def get_output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "recurrent":
            return InputType.recurrent(self.n_out, input_type.timeseries_length)
        return InputType.feed_forward(self.n_out)


def moe_topk_from_logits(logits, top_k):
    """(gates [N, E], expert ids [N, k], renormalized probs [N, k]).

    For the practical regime (small k, modest E) the top-k runs as k
    argmax+mask passes and the gate matrix is built from one-hots —
    lax.top_k lowers to a full sort and the scatter writing [N, E] cost
    ~2 ms each at [16k, 8] on v5e (r4 trace); the iterative form fuses
    into cheap VPU elementwise work. Tie-breaking (first index wins)
    matches lax.top_k.
    """
    E = logits.shape[-1]
    N = logits.shape[0]
    if top_k <= 4 and E <= 64:
        x = logits
        onehots, vals, ids = [], [], []
        for _ in range(top_k):
            i = jnp.argmax(x, axis=-1)
            oh = jax.nn.one_hot(i, E, dtype=logits.dtype)   # [N, E]
            vals.append(jnp.max(x, axis=-1))
            onehots.append(oh)
            ids.append(i)
            x = jnp.where(oh > 0, jnp.finfo(x.dtype).min, x)
        probs = jax.nn.softmax(jnp.stack(vals, -1), axis=-1)  # [N, k]
        gates = sum(oh * probs[:, j:j + 1] for j, oh in enumerate(onehots))
        return gates, jnp.stack(ids, -1), probs
    top_vals, top_idx = jax.lax.top_k(logits, top_k)      # [N, k]
    probs = jax.nn.softmax(top_vals, axis=-1)             # renormalized
    gates = jnp.zeros((N, E), logits.dtype).at[
        jnp.arange(N)[:, None], top_idx].set(probs)
    return gates, top_idx, probs


def moe_gates_from_logits(logits, top_k):
    """Top-k renormalized softmax gates [N, E] (zeros outside the top-k)."""
    return moe_topk_from_logits(logits, top_k)[0]


def moe_gates(x2d, Wg, top_k):
    """Top-k renormalized softmax gates [N, E] (zeros outside the top-k)."""
    return moe_gates_from_logits(x2d @ Wg, top_k)


def moe_expert_outputs(params, x2d, activation):
    """All experts applied to all tokens: [N, E, n_out] (dense oracle)."""
    act = get_activation(activation)
    h = jnp.einsum("nd,edh->neh", x2d, params["We1"]) + params["be1"]
    h = act(h)
    return jnp.einsum("neh,eho->neo", h, params["We2"]) + params["be2"]


def moe_apply_dense(params, x2d, *, top_k, activation):
    """Dense-path MoE forward: compute-all-experts, gate-masked combine."""
    gates = moe_gates(x2d, params["Wg"], top_k)            # [N, E]
    outs = moe_expert_outputs(params, x2d, activation)     # [N, E, O]
    return jnp.einsum("ne,neo->no", gates, outs)


def expert_capacity(group_size, top_k, capacity_factor, n_experts):
    """Per-group per-expert capacity, rounded up to a multiple of 8
    (sublane-friendly), capped at group_size — a token claims a given
    expert at most once (the argmax gate masks each chosen expert), so
    an expert can never receive more than the group's tokens."""
    c = math.ceil(group_size * top_k * capacity_factor / n_experts)
    c = -(-c // 8) * 8
    return min(c, group_size)


def moe_load_balance_loss(logits, gates, top_k):
    """Switch Transformer aux loss (arXiv:2101.03961 eq. 4 generalized to
    top-k): E * sum_e f_e * P_e, where f_e is the fraction of routing
    assignments sent to expert e and P_e the mean full-softmax router
    probability. Minimized (=1) at uniform routing; gradient reaches the
    router only (f is piecewise-constant)."""
    E = logits.shape[-1]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)   # [N, E]
    frac = jnp.mean((gates > 0).astype(jnp.float32), axis=0) / top_k
    importance = jnp.mean(probs, axis=0)
    return E * jnp.sum(frac * importance)


# Routed dispatch implementation: "einsum" (GShard one-hot formulation,
# r5 default — with MXU-friendly float routing metadata) or "gather"
# (index-based take_along_axis/scatter). The r5 trace showed BOTH
# formulations' real cost was the routing METADATA — an s32 cumsum
# lowered to reduce-window (~1.2 ms/step) plus pred/s32 elementwise and
# small-axis gathers (several ms) — while the einsum dispatch itself is
# ~50 us of MXU time; the gather form additionally pays TPU's slow
# generic gather lowering (take_along_axis at ~50 GB/s effective). The
# einsum path therefore computes positions via a STRICTLY-LOWER-
# TRIANGULAR MATMUL (exclusive prefix counts on the MXU; counts <= S
# are exact in the f32 accumulator) and keeps every mask in the compute
# dtype — no s32/pred bands at all.
DISPATCH = "einsum"


def moe_apply_routed(params, x2d, *, top_k, capacity_factor, activation,
                     group_size=0, return_aux=False, dispatch=None):
    """Token-routed MoE forward via capacity-factor dispatch.

    Returns y [N, O] (and the unweighted load-balance aux loss when
    ``return_aux``). Within each group, slots are claimed in token order;
    a token whose expert buffer is full is dropped (zero output row).
    """
    N, D = x2d.shape
    E = params["We1"].shape[0]
    O = params["We2"].shape[-1]
    # default group 256: the r4 einsum dispatch cost scaled with group
    # size (one-hots ∝ S); the gather dispatch is size-insensitive but
    # the drop WINDOW semantics stay per-group, so the default holds
    S = group_size or min(N, 256)
    G = -(-N // S)
    pad = G * S - N

    logits = x2d @ params["Wg"]                            # [N, E]
    gates, top_idx, top_probs = moe_topk_from_logits(logits, top_k)
    aux = moe_load_balance_loss(logits, gates, top_k) if return_aux else None

    xp = jnp.pad(x2d, ((0, pad), (0, 0))) if pad else x2d
    gg = (jnp.pad(gates, ((0, pad), (0, 0))) if pad else gates)
    gg = gg.reshape(G, S, E)
    C = expert_capacity(S, top_k, capacity_factor, E)

    act = get_activation(activation)
    if (dispatch or DISPATCH) == "einsum":
        # float routing metadata end to end: exclusive prefix counts via
        # a strict-lower-triangular matmul (MXU; exact for counts <= S in
        # the f32 accumulator), masks by arithmetic compare — no s32
        # cumsum/gather bands (see DISPATCH note)
        cdt = xp.dtype
        routed_f = (gg > 0).astype(cdt)                    # [G, S, E]
        tril = jnp.tril(jnp.ones((S, S), cdt), -1)         # t < s
        pos = jnp.einsum("st,gte->gse", tril, routed_f,
                         preferred_element_type=jnp.float32)
        keep_f = routed_f * (pos < C).astype(cdt)          # [G, S, E]
        slots = jnp.arange(C, dtype=jnp.float32)
        disp = (keep_f[..., None]
                * (pos[..., None] == slots).astype(cdt))   # [G, S, E, C]
        combine = disp * gg[..., None].astype(cdt)
        xg = xp.reshape(G, S, D)
        expert_in = jnp.einsum("gsec,gsd->egcd", disp, xg)  # [E, G, C, D]
        h = act(jnp.einsum("egcd,edh->egch", expert_in, params["We1"])
                + params["be1"][:, None, None, :])
        out = (jnp.einsum("egch,eho->egco", h, params["We2"])
               + params["be2"][:, None, None, :])
        y = jnp.einsum("gsec,egco->gso", combine, out).reshape(G * S, O)
        y = y[:N] if pad else y
        return (y, aux) if return_aux else y

    # ---- gather dispatch ----
    routed = gg > 0                                        # [G, S, E]
    pos = jnp.cumsum(routed.astype(jnp.int32), axis=1) - 1  # slot per expert
    keep = routed & (pos < C)
    # per-(token, k): its expert id, whether it won a slot, and which
    if pad:
        top_idx = jnp.pad(top_idx, ((0, pad), (0, 0)))
        top_probs = jnp.pad(top_probs, ((0, pad), (0, 0)))
    e_k = top_idx.reshape(G, S, top_k)                     # [G, S, k]
    kept_k = jnp.take_along_axis(keep, e_k, axis=2)        # [G, S, k]
    slot_k = jnp.take_along_axis(pos, e_k, axis=2)         # [G, S, k]
    prob_k = top_probs.reshape(G, S, top_k).astype(xp.dtype)

    # inverse map (e, c) -> source token s, built by scatter; slot C-or-
    # greater (capacity overflow) and sentinel writes drop out of range
    g_idx = jax.lax.broadcasted_iota(jnp.int32, (G, S, top_k), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (G, S, top_k), 1)
    slot_w = jnp.where(kept_k, slot_k, C)                  # C -> dropped
    idx_buf = jnp.full((G, E, C), S, jnp.int32)            # S -> zero row
    idx_buf = idx_buf.at[g_idx, e_k, slot_w].set(s_idx, mode="drop")

    xg_pad = jnp.pad(xp.reshape(G, S, D), ((0, 0), (0, 1), (0, 0)))
    expert_in = jnp.take_along_axis(
        xg_pad, idx_buf.reshape(G, E * C, 1), axis=1)      # [G, E*C, D]
    expert_in = jnp.moveaxis(
        expert_in.reshape(G, E, C, D), 1, 0)               # [E, G, C, D]
    h = act(jnp.einsum("egcd,edh->egch", expert_in, params["We1"])
            + params["be1"][:, None, None, :])
    out = (jnp.einsum("egch,eho->egco", h, params["We2"])
           + params["be2"][:, None, None, :])              # [E, G, C, O]

    # combine: each token gathers its k slot outputs; dropped (e, slot)
    # pairs point at the padded zero row E*C
    out_pad = jnp.pad(jnp.moveaxis(out, 0, 1).reshape(G, E * C, O),
                      ((0, 0), (0, 1), (0, 0)))            # [G, E*C+1, O]
    flat = jnp.where(kept_k, e_k * C + slot_k, E * C)      # [G, S, k]
    picked = jnp.take_along_axis(
        out_pad, flat.reshape(G, S * top_k, 1), axis=1
    ).reshape(G, S, top_k, O)
    y = jnp.einsum("gsk,gsko->gso", prob_k, picked).reshape(G * S, O)
    y = y[:N] if pad else y
    return (y, aux) if return_aux else y


@register_impl(MixtureOfExpertsLayer)
class MixtureOfExpertsImpl(LayerImpl):
    region = "moe"

    def init(self, conf, rng, dtype):
        E = conf.n_experts
        D, O = conf.n_in, conf.n_out or conf.n_in
        H = conf.d_hidden or 4 * D
        kg, k1, k2 = jax.random.split(rng, 3)
        We1 = jnp.stack([
            init_weights(k, (D, H), conf.weight_init, conf.dist, dtype)
            for k in jax.random.split(k1, E)])
        We2 = jnp.stack([
            init_weights(k, (H, O), conf.weight_init, conf.dist, dtype)
            for k in jax.random.split(k2, E)])
        return {
            "Wg": init_weights(kg, (D, E), conf.weight_init, conf.dist, dtype),
            "We1": We1, "be1": jnp.zeros((E, H), dtype),
            "We2": We2, "be2": jnp.zeros((E, O), dtype),
        }, {}

    def apply(self, conf, params, state, x, *, train=False, rng=None,
              mask=None):
        if conf.dropout:
            x = apply_dropout(x, conf.dropout, rng, train=train)
        shape = x.shape
        x2d = x.reshape(-1, shape[-1])
        new_state = {k: v for k, v in state.items() if k != AUX_LOSS_KEY}
        if conf.routing == "dense":
            y = moe_apply_dense(params, x2d, top_k=conf.top_k,
                                activation=conf.activation or "gelu")
        else:
            want_aux = train and conf.router_aux_weight > 0
            out = moe_apply_routed(
                params, x2d, top_k=conf.top_k,
                capacity_factor=conf.capacity_factor,
                activation=conf.activation or "gelu",
                group_size=conf.router_group_size, return_aux=want_aux)
            if want_aux:
                y, aux = out
                new_state[AUX_LOSS_KEY] = conf.router_aux_weight * aux
            else:
                y = out
        y = y.reshape(*shape[:-1], y.shape[-1])
        return y, new_state


# ---------------------------------------------------------------- dropless

@register_config
@dataclasses.dataclass
class DroplessMoELayer(FeedForwardLayer):
    """Routed gated experts with a shared expert, no pair dropped, told
    which experts it holds.

        s = sigmoid(x_f32 Wg_f32)               all `n_experts` scores
        the top_k are the largest of s, or, with `selection_bias`, of
        s + bsel (`bsel` [n_experts] float32: a bias that CHOOSES and
        never weighs; training moves it towards a balanced load)
        w = s_top / (sum of the top_k + 1e-20) * routed_scaling
      or, with `router` "softmax":
        l = x_f32 Wg_f32; the top_k are the largest of l, and
        w = softmax over those top_k logits * routed_scaling (the
        normalised top k of the softmax over all experts)
        y = sum over the selected experts HELD HERE of w_e E_e(x)
            + Shared(x), times sigmoid(x Ws_g) with `shared_gate`
        E_e(x) = (act(x Wgate_e) * (x Wup_e)) Wdown_e,  d_hidden wide

    The router keeps its whole width and the weights are normalised over
    all top_k selected experts, held or not; this layer holds experts
    `first_expert .. first_expert + n_held - 1` and leaves out what the
    others would add (one chip's share of an expert-parallel layer: the
    shares of all chips, with the shared expert counted once, add up to
    the whole layer). `n_held` 0 holds all of them. The shared expert is
    one gated block `n_shared * d_hidden` wide, computed for every
    token."""

    n_experts: int = 8      # the router's width
    top_k: int = 2
    d_hidden: int = 0       # an expert's inner width; defaults to 4 * n_in
    first_expert: int = 0
    n_held: int = 0
    n_shared: int = 0
    routed_scaling: float = 1.0
    selection_bias: bool = False    # hold `bsel` and select by s + bsel
    router: str = "sigmoid"         # "sigmoid" | "softmax"
    shared_gate: bool = False       # hold `Ws_g` [n_in, 1]: the shared
                                    # expert times sigmoid(x Ws_g)

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = input_type.flat_size()
        if self.n_out == 0:
            self.n_out = self.n_in


# A round of the dispatch gives every held expert `round_rows` rows:
# ROUND_SLACK times the mean a uniform router would send it, at least 8
# (a sublane tile). Under a uniform router an expert's count is near
# Poisson, and the fullest of 16 held experts reads, in one layer of a
# hundred, 4 times a mean of 2 (a decode batch), 2.4 times a mean of 8
# and 1.6 times a mean of 32 (a prefill chunk): 4 keeps the small
# batches to one round, and at the large ones it costs rows alone. Those are
# cheap: an expert's three products are bound by reading its weights up
# to the device's ridge (some 240 rows at bfloat16 on a v5e), while a
# second round reads every held expert's weights again. A router that
# is NOT uniform (text of one domain; seeded weights) spends the spare
# rows of the large batches first and the second round after them.
ROUND_SLACK = 4.0
COUNTERS = ("moe_pairs", "moe_rows", "moe_max_load")


def round_rows(n_tokens: int, top_k: int, n_experts: int) -> int:
    c = math.ceil(ROUND_SLACK * n_tokens * top_k / n_experts)
    return min(max(8, -(-c // 8) * 8), max(8, -(-n_tokens // 8) * 8))


def route_sigmoid_topk(x2d, Wg, top_k, routed_scaling, bsel=None):
    """(expert ids [N, k], weights [N, k] float32). The router's product
    and the sigmoid in float32 at full precision, as the published
    models compute them: a bfloat16 rounding of a score picks another
    expert than the reference where two scores lie close. With `bsel`
    [E] the top k are those of s + bsel and are weighed by s alone."""
    logits = jnp.matmul(x2d.astype(jnp.float32), Wg.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if bsel is None:
        top_s, top_i = jax.lax.top_k(jax.nn.sigmoid(logits), top_k)
    else:
        s = jax.nn.sigmoid(logits)
        _, top_i = jax.lax.top_k(s + bsel.astype(jnp.float32), top_k)
        top_s = jnp.take_along_axis(s, top_i, axis=-1)
    w = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    return top_i, w * routed_scaling


def route_softmax_topk(x2d, Wg, top_k, routed_scaling):
    """(expert ids [N, k], weights [N, k] float32): the top k of the
    float32 logits at full precision, weighed by a softmax over those k
    (the top k of the softmax over every expert, normalised)."""
    logits = jnp.matmul(x2d.astype(jnp.float32), Wg.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    top_l, top_i = jax.lax.top_k(logits, top_k)
    return top_i, jax.nn.softmax(top_l, axis=-1) * routed_scaling


def dropless_moe(conf, params, x2d, valid=None):
    """The layer on x2d [N, D]; `valid` [N] marks real tokens (pad rows
    and idle slots select nothing). -> (y [N, O], counts).

    Dispatch: a token selects a held expert at most once, so the pairs
    are an [N, held] grid; `rank` numbers each expert's pairs in token
    order. A ROUND gives every held expert its next `round_rows` pairs:
    row j = e * round_rows + c of the round's [held * round_rows, D]
    buffer is the pair of expert e with rank c (+ the rounds before). The
    buffer is filled and emptied by two products with the round's
    [N, rows] one-hot matrix (the GShard dispatch and combine: on a TPU
    the MXU moves rows an order of magnitude faster than a gather or a
    scatter-add; a one-hot product copies exactly), the second carrying
    the pairs' weights; between them the held experts run as one batched
    gated block. Rounds repeat until the fullest expert is served, so no
    pair is dropped whatever the imbalance, and a batch whose fullest
    expert has at most `round_rows` pairs costs one. The round count is a
    value, not a shape: the loop runs its static bound (every token on
    one expert) and skips the rounds past the last needed one
    (`lax.cond`), which keeps it differentiable.

    counts (int32 scalars): `moe_pairs` the (token, held selected
    expert) pairs, `moe_rows` the expert rows computed for them (rounds
    x held x round_rows: padding included), `moe_max_load` the most pairs
    on one held expert.

    Its ops lie in three child regions of the layer's `moe`: the
    routing and the dispatch's bookkeeping in `router`, the rounds of
    the held experts' products in `experts`, the shared expert in
    `shared_expert`."""
    N, D = x2d.shape
    held = conf.n_held or conf.n_experts
    act = conf.activation or "silu"
    C = round_rows(N, conf.top_k, conf.n_experts)
    O = params["We_down"].shape[-1]
    with jax.named_scope("router"):
        if conf.router == "softmax":
            top_i, w = route_softmax_topk(x2d, params["Wg"], conf.top_k,
                                          conf.routed_scaling)
        else:
            top_i, w = route_sigmoid_topk(x2d, params["Wg"], conf.top_k,
                                          conf.routed_scaling,
                                          params.get("bsel"))
        local = top_i - conf.first_expert                    # [N, k]
        mine = (local >= 0) & (local < held)
        if valid is not None:
            mine = mine & valid.reshape(N, 1).astype(bool)
        onehot = (local[:, :, None] == jnp.arange(held)) & mine[:, :, None]
        sel = jnp.any(onehot, axis=1)                        # [N, held]
        gate = jnp.sum(jnp.where(onehot, w[:, :, None], 0.0), axis=1)
        rank = jnp.cumsum(sel.astype(jnp.int32), axis=0) - 1
        max_load = jnp.max(jnp.sum(sel.astype(jnp.int32), axis=0))
        rounds = (max_load + C - 1) // C
        # per buffer row j: its expert's column of the [N, held] grids,
        # and the rank within the round that it serves
        rank_r = jnp.repeat(jnp.where(sel, rank, -1), C, axis=1)
        gate_r = jnp.repeat(gate, C, axis=1)
        slot = jnp.tile(jnp.arange(C), held)

    def one_round(r, y):
        here = rank_r == slot + r * C                        # [N, held*C]
        xin = jnp.einsum("nj,nd->jd", here.astype(x2d.dtype),
                         x2d).reshape(held, C, D)
        g = get_activation(act)(
            jnp.einsum("ecd,edf->ecf", xin, params["We_gate"]))
        h = g * jnp.einsum("ecd,edf->ecf", xin, params["We_up"])
        out = jnp.einsum("ecf,efo->eco", h, params["We_down"])
        return y + jnp.einsum(
            "nj,jo->no", jnp.where(here, gate_r, 0.0).astype(out.dtype),
            out.reshape(held * C, O), preferred_element_type=jnp.float32)

    with jax.named_scope("experts"):
        y = jax.lax.fori_loop(
            0, -(-N // C),
            lambda r, y: jax.lax.cond(r < rounds, one_round,
                                      lambda _r, y: y, r, y),
            jnp.zeros((N, O), jnp.float32))
    if conf.n_shared:
        with jax.named_scope("shared_expert"):
            shared = gated_ffn(x2d, params["Ws_gate"], params["Ws_up"],
                               params["Ws_down"], act).astype(jnp.float32)
            if conf.shared_gate:
                shared = shared * jax.nn.sigmoid(
                    (x2d @ params["Ws_g"]).astype(jnp.float32))
            y = y + shared
    counts = {"moe_pairs": jnp.sum(sel.astype(jnp.int32)),
              "moe_rows": rounds * (held * C),
              "moe_max_load": max_load}
    return y.astype(x2d.dtype), counts


@register_impl(DroplessMoELayer)
class DroplessMoEImpl(LayerImpl):
    region = "moe"
    counters = COUNTERS

    @staticmethod
    def merge_counts(counts: list) -> dict:
        """One step's counters from its expert layers': pairs and rows
        add up, the fullest expert is the fullest of any layer."""
        return {"moe_pairs": sum(c["moe_pairs"] for c in counts),
                "moe_rows": sum(c["moe_rows"] for c in counts),
                "moe_max_load": jnp.max(jnp.stack(
                    [c["moe_max_load"] for c in counts]))}

    def init(self, conf, rng, dtype):
        D, O = conf.n_in, conf.n_out or conf.n_in
        F = conf.d_hidden or 4 * D
        held = conf.n_held or conf.n_experts
        if not 0 <= conf.first_expert <= conf.n_experts - held:
            raise ValueError(
                f"experts {conf.first_expert}..{conf.first_expert + held - 1}"
                f" are not among the router's {conf.n_experts}")
        if conf.router not in ("sigmoid", "softmax") or (
                conf.router == "softmax" and conf.selection_bias):
            raise ValueError(
                f"router {conf.router!r}: 'sigmoid' or 'softmax', and only "
                f"a sigmoid router takes a selection bias")
        if conf.shared_gate and not conf.n_shared:
            raise ValueError("a shared_gate needs a shared expert")
        k = jax.random.split(rng, 7)

        def w(key, shape, fan_in, fan_out):
            return init_weights(key, shape, conf.weight_init, conf.dist,
                                dtype, fan_in=fan_in, fan_out=fan_out)

        params = {"Wg": w(k[0], (D, conf.n_experts), D, conf.n_experts),
                  "We_gate": w(k[1], (held, D, F), D, F),
                  "We_up": w(k[2], (held, D, F), D, F),
                  "We_down": w(k[3], (held, F, O), F, O)}
        if conf.selection_bias:
            params["bsel"] = jnp.zeros((conf.n_experts,), jnp.float32)
        if conf.n_shared:
            Fs = conf.n_shared * F
            params.update(Ws_gate=w(k[4], (D, Fs), D, Fs),
                          Ws_up=w(k[5], (D, Fs), D, Fs),
                          Ws_down=w(k[6], (Fs, O), Fs, O))
        if conf.shared_gate:
            params["Ws_g"] = w(jax.random.fold_in(rng, 7), (D, 1), D, 1)
        return params, {}

    def apply_counted(self, conf, params, x, valid=None):
        """-> (y like x, counts): what `apply` computes, with the
        dispatch's counters (the serving steps of nn/decode.py hand them
        home in the fetch they already make)."""
        shape = x.shape
        y, counts = dropless_moe(conf, params, x.reshape(-1, shape[-1]),
                                 None if valid is None else valid.reshape(-1))
        return y.reshape(*shape[:-1], y.shape[-1]), counts

    def apply(self, conf, params, state, x, *, train=False, rng=None,
              mask=None):
        if conf.dropout:
            x = apply_dropout(x, conf.dropout, rng, train=train)
        valid = mask if mask is not None and x.ndim == 3 else None
        return self.apply_counted(conf, params, x, valid)[0], state
