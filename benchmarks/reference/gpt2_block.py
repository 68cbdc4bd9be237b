"""The plain reference of the GPT-2 block family: float32 `jax.numpy`,
no kernels, no cache, no batching, one row at a time.

    x = embed[tokens] + sinusoid(positions)
    L x [ x += Wo . attention(LN1(x) Wqkv + bqkv) + bo
          x += W2 . gelu_tanh(LN2(x) W1 + b1) + b2 ]
    logits = LNf(x) Wout + bout          loss = mean over tokens of the nll

It follows the block the configurations' `assumed` lists describe (fixed
sinusoidal positions, untied head, tanh gelu), which is what the program
under test builds; departures from the published model are theirs, not
the reference's. It imports nothing of the program and takes no array
the program made: its weights come from `benchmarks/harness/weights.py`
in the stacked layout {leaf: [L, ...]}.

Every matrix product goes through `mm`. `mm_highest` is the reference
proper (a float32 product on a TPU runs in bfloat16 passes unless told
otherwise). `mm_fp8` is the control: the same reference with both
operands of every product, forward and backward, rounded to float8
(e4m3, one scale per tensor) — the nearest precision below the bfloat16
the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def mm_highest(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _t(x):
    return jnp.swapaxes(x, -1, -2)


@jax.custom_vjp
def mm_fp8(a, b):
    return jnp.matmul(_fp8(a), _fp8(b), precision=HIGHEST)


def _mm_fp8_fwd(a, b):
    return mm_fp8(a, b), (a, b)


def _mm_fp8_bwd(res, g):
    a, b = res
    gq = _fp8(g)
    da = jnp.matmul(gq, _t(_fp8(b)), precision=HIGHEST)
    db = jnp.matmul(_t(_fp8(a)), gq, precision=HIGHEST)
    # a [T, k] @ b [k, n] has no batch axes here; attention's products
    # carry the head axis on both operands
    return da.reshape(a.shape), db.reshape(b.shape)


mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def sinusoid(T: int, d: int, offset=0):
    pos = (offset + jnp.arange(T))[:, None].astype(jnp.float32)
    dim = jnp.arange(0, d, 2).astype(jnp.float32)
    angle = pos / jnp.power(10000.0, dim / d)
    pe = jnp.zeros((T, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(angle))
    return pe.at[:, 1::2].set(jnp.cos(angle[:, : d // 2]))


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def block(x, w, dims, mm):
    """One pre-norm block on one row x [T, d]."""
    T, d = x.shape
    H = dims["H"]
    D = d // H
    h = layer_norm(x, w["ln1_g"], w["ln1_b"], dims["eps"])
    qkv = mm(h, w["Wqkv"]) + w["bqkv"]
    q, k, v = (t.reshape(T, H, D).transpose(1, 0, 2)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = mm(q, _t(k)) / jnp.sqrt(float(D))
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    a = mm(p, v).transpose(1, 0, 2).reshape(T, d)
    x = x + mm(a, w["Wo"]) + w["bo"]
    h = layer_norm(x, w["ln2_g"], w["ln2_b"], dims["eps"])
    f = gelu_tanh(mm(h, w["W1"]) + w["b1"])
    return x + mm(f, w["W2"]) + w["b2"]


def hidden(W, tokens, dims, mm=mm_highest):
    """Final-LayerNormed hidden states [T, d] of one row of tokens [T]."""
    x = W["embed"][tokens] + sinusoid(tokens.shape[0], dims["d"])

    @jax.checkpoint
    def body(x, w):
        return block(x, w, dims, mm), None

    x, _ = jax.lax.scan(body, x, W["blocks"])
    return layer_norm(x, W["lnf_g"], W["lnf_b"], dims["eps"])


def logits_of(W, h, mm=mm_highest):
    return mm(h, W["Wout"]) + W["bout"]


def row_nll(W, tokens, labels, dims, mm=mm_highest):
    """Sum over one row's positions of -log p(label)."""
    logp = jax.nn.log_softmax(logits_of(W, hidden(W, tokens, dims, mm), mm))
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def loss(W, tokens, labels, dims, mm=mm_highest):
    """Mean nll over every position of every row: tokens, labels [B, T]."""
    per_row = jax.lax.map(
        jax.checkpoint(lambda tl: row_nll(W, tl[0], tl[1], dims, mm)),
        (tokens, labels))
    return jnp.sum(per_row) / tokens.size


def adam_update(W, m, v, g, t, hp):
    """One Adam step, as Kingma & Ba write it (bias-corrected moments)."""
    b1, b2 = hp["adam_b1"], hp["adam_b2"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    W = jax.tree.map(
        lambda p, m, v: p - hp["learning_rate"] * (m / c1) / (
            jnp.sqrt(v / c2) + hp["adam_eps"]), W, m, v)
    return W, m, v


def _split3(x):
    return jnp.split(x, 3, axis=-1)


def sq_norms(tree) -> dict:
    """Squared norm of every compared leaf of a stacked tree: global
    leaves give scalars, block leaves [L]. The fused Wqkv / bqkv are
    compared as their q, k and v thirds (a key's bias has no gradient
    under softmax, the other two thirds have)."""
    out = {n: jnp.sum(jnp.square(x.astype(jnp.float32)))
           for n, x in tree.items() if n != "blocks"}
    for n, x in tree["blocks"].items():
        x = x.astype(jnp.float32)
        parts = ({n: x} if n not in ("Wqkv", "bqkv") else
                 dict(zip((n[0] + "q", n[0] + "k", n[0] + "v"), _split3(x))))
        for pn, px in parts.items():
            out["blocks." + pn] = jnp.sum(
                jnp.square(px), axis=tuple(range(1, px.ndim)))
    return out


def _attention_row(q, k, v, mm):
    """Causal attention of one row: q, k, v [H, T, D]."""
    T, D = q.shape[1], q.shape[2]
    scores = mm(q, _t(k)) / jnp.sqrt(float(D))
    scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores, -jnp.inf)
    return mm(jax.nn.softmax(scores, axis=-1), v)


def block_rows(x, w, dims, mm):
    """The same block on a batch of rows x [B, T, d]: every product with
    a weight runs over all B*T positions at once, attention row by row
    (its [H, T, T] scores are the large intermediate)."""
    B, T, d = x.shape
    H = dims["H"]
    D = d // H
    x = x.reshape(B * T, d)
    h = layer_norm(x, w["ln1_g"], w["ln1_b"], dims["eps"])
    qkv = mm(h, w["Wqkv"]) + w["bqkv"]
    q, k, v = (t.reshape(B, T, H, D).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    a = jax.lax.map(jax.checkpoint(
        lambda qkv_: _attention_row(*qkv_, mm)), (q, k, v))
    a = a.transpose(0, 2, 1, 3).reshape(B * T, d)
    x = x + mm(a, w["Wo"]) + w["bo"]
    h = layer_norm(x, w["ln2_g"], w["ln2_b"], dims["eps"])
    f = gelu_tanh(mm(h, w["W1"]) + w["b1"])
    return (x + mm(f, w["W2"]) + w["b2"]).reshape(B, T, d)


PROJ_LEAVES = ("embed", "lnf_g", "lnf_b", "Wout", "bout", "ln1_g", "ln1_b",
               "Wqkv", "bqkv", "Wo", "bo", "ln2_g", "ln2_b", "W1", "b1", "W2",
               "b2")


def project(x, key, leaf: str, layer=0):
    """<x, r> for a fixed vector r of +-1 drawn from (key, leaf, layer).
    A norm feels a rounding error only at second order; this feels it at
    first order, so it tells precisions apart where the norms do not."""
    k = jax.random.fold_in(jax.random.fold_in(key, PROJ_LEAVES.index(leaf)),
                           layer)
    return jnp.sum(x.astype(jnp.float32)
                   * jax.random.rademacher(k, x.shape, jnp.float32))


def _block_sq_norms(tree) -> dict:
    out = {}
    for n, x in tree.items():
        parts = ({n: x} if n not in ("Wqkv", "bqkv") else
                 dict(zip((n[0] + "q", n[0] + "k", n[0] + "v"), _split3(x))))
        for pn, px in parts.items():
            out["blocks." + pn] = jnp.sum(jnp.square(px))
    return out


def _adam_leaf(p, m, v, g, t, hp):
    b1, b2 = hp["adam_b1"], hp["adam_b2"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    p = p - hp["learning_rate"] * (m / (1 - b1 ** t)) / (
        jnp.sqrt(v / (1 - b2 ** t)) + hp["adam_eps"])
    return p, m, v


def train_step(W, m, v, tokens, labels, t, dims, hp, mm=mm_highest,
               proj_key=None):
    """One optimizer step on tokens, labels [B, T]: the loss, its gradient
    by backpropagation written out layer by layer, and Adam's update.

    The gradient of the mean nll is what `jax.grad(loss)` gives (a test
    holds the two together at a small size); it is written out so that a
    block's gradient is used for its update and its squared norms as soon
    as it exists, and the whole model's gradient is never held beside the
    weights and both moments. Returns (W, m, v, loss, squared norms of
    the gradient; with `proj_key`, also each leaf's `project` under
    "proj.<leaf>").
    """
    B, T = tokens.shape
    n_tok = B * T
    glob = {n: W[n] for n in ("lnf_g", "lnf_b", "Wout", "bout")}

    x0 = W["embed"][tokens] + sinusoid(T, dims["d"])
    xL, xs = jax.lax.scan(lambda x, w: (block_rows(x, w, dims, mm), x),
                          x0, W["blocks"])

    def head_nll(g, x_row, lab_row):
        h = layer_norm(x_row, g["lnf_g"], g["lnf_b"], dims["eps"])
        logp = jax.nn.log_softmax(mm(h, g["Wout"]) + g["bout"])
        return -jnp.sum(jnp.take_along_axis(logp, lab_row[:, None], -1)) / n_tok

    def head_row(acc, row):
        nll, (dg, dx) = jax.value_and_grad(head_nll, argnums=(0, 1))(glob, *row)
        return jax.tree.map(jnp.add, acc, (nll, dg)), dx

    zero = (jnp.zeros(()), jax.tree.map(jnp.zeros_like, glob))
    (nll, dglob), dxL = jax.lax.scan(head_row, zero, (xL, labels))

    def back(carry, l):
        dx, Wb, mb, vb = carry
        w = jax.tree.map(lambda a: a[l], Wb)
        _y, vjp = jax.vjp(lambda x_, w_: block_rows(x_, w_, dims, mm), xs[l], w)
        dx, dw = vjp(dx)
        new = {n: _adam_leaf(w[n], mb[n][l], vb[n][l], dw[n], t, hp) for n in w}
        put = lambda big, k: {n: big[n].at[l].set(new[n][k]) for n in big}
        out = _block_sq_norms(dw)
        if proj_key is not None:
            out.update({"proj.blocks." + n: project(g, proj_key, n, l)
                        for n, g in dw.items()})
        return (dx, put(Wb, 0), put(mb, 1), put(vb, 2)), out

    L = dims["L"]
    (dx0, Wb, mb, vb), gn = jax.lax.scan(
        back, (dxL, W["blocks"], m["blocks"], v["blocks"]),
        jnp.arange(L - 1, -1, -1))
    dembed = jnp.zeros_like(W["embed"]).at[tokens].add(dx0)

    norms = {n: x[::-1] for n, x in gn.items()}
    newW, newm, newv = {"blocks": Wb}, {"blocks": mb}, {"blocks": vb}
    for n, g in dict(dglob, embed=dembed).items():
        norms[n] = jnp.sum(jnp.square(g))
        if proj_key is not None:
            norms["proj." + n] = project(g, proj_key, n)
        newW[n], newm[n], newv[n] = _adam_leaf(W[n], m[n], v[n], g, t, hp)
    return newW, newm, newv, nll, norms


@functools.lru_cache(maxsize=None)
def _train_programs(dims_items, hp_items, mm):
    dims, hp = dict(dims_items), dict(hp_items)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(W, m, v, tokens, labels, t, proj_key):
        return train_step(W, m, v, tokens, labels, t, dims, hp, mm, proj_key)

    @jax.jit
    def change(W, W0):
        return sq_norms(jax.tree.map(lambda a, b: a - b, W, W0))

    return step, change


def train_steps(make_W0, batches, dims, hp, proj_key, mm=mm_highest,
                rows=None):
    """Follow the optimizer for len(batches) steps from make_W0().

    make_W0 is called twice, at the start and for the change at the end,
    so that the starting weights are not held through the steps.
    batches: [(tokens [B, T], labels [B, T]), ...]. `rows` keeps only
    the first `rows` rows of every batch and takes the mean over those
    (the half-batch fault). Returns (losses, squared norms of the first
    gradient and its projections, squared norms of the parameters' change
    over all steps).
    """
    keep = ("learning_rate", "adam_b1", "adam_b2", "adam_eps")
    step, change = _train_programs(
        tuple(sorted(dims.items())),
        tuple((k, float(hp[k])) for k in keep), mm)
    W = make_W0()
    m = jax.tree.map(jnp.zeros_like, W)
    v = jax.tree.map(jnp.zeros_like, W)
    losses, g1 = [], None
    for t, (tokens, labels) in enumerate(batches, start=1):
        if rows is not None:
            tokens, labels = tokens[:rows], labels[:rows]
        W, m, v, l, gn = step(W, m, v, tokens, labels, float(t), proj_key)
        losses.append(l)
        if g1 is None:
            g1 = gn
    del m, v
    return losses, g1, change(W, make_W0())


def served_logits(W, tokens, at, dims, mm=mm_highest):
    """Logits [len(at), V] of one row of tokens [T] (padded at the end;
    causal, so padding does not reach back) at the positions `at`."""
    return logits_of(W, hidden(W, tokens, dims, mm)[at], mm)


def served_gap(W, tokens, at, served, valid, dims):
    """By how much each served token's logit lies below the reference's
    best at its position; 0 where the served token is the reference's
    own choice. `valid` masks the padding of `at`."""
    lg = served_logits(W, tokens, at, dims)
    gap = jnp.max(lg, axis=-1) - jnp.take_along_axis(
        lg, served[:, None], axis=-1)[:, 0]
    return jnp.where(valid, gap, 0.0)


def lowprec_gap(W, tokens, at, valid, dims, mm=mm_fp8):
    """The control's reading: the same gap for the token that the
    reference computed in the lower precision puts first."""
    first = jnp.argmax(served_logits(W, tokens, at, dims, mm), axis=-1)
    return served_gap(W, tokens, at, first, valid, dims)
