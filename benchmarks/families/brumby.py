"""The `brumby` family (`"model_type": "brumby"`): Qwen3-14B's frame with
every attention layer replaced by power retention of degree 2 (grouped
heads, per-head RMS norms on query and key, rotary position, one scalar
gate a key-value head a token), a gated feed-forward block, pre-norm, an
untied head. Behind the interface of `families/__init__.py`.

Sizes from the configuration's own keys (the published `config.json`
names); the program's net through `models.retention.retention_lm`; the
seeded weights; the plain reference (`benchmarks/reference/brumby.py`,
imported here alone); the counts. A serving family: the training entries
raise (see `_no_training`).

What a server keeps for a sequence is a STATE of fixed size a layer
(`state_bytes_per_slot`), not a row a token: `kv_bytes_per_token` is 0
and a decode step's bytes go with its live SLOTS, whatever their
context length.

The benchmark makes the weights, a layer at a time on both sides:
`layer_weights(fold_in(key, i + 2), dims)` gives layer i the same
float32 numbers for the program (cast to its `param_dtype` as they are
made, one jitted call a layer whose key and layer number are arguments)
and for the reference (made, used over every sampled request, dropped: the
float32 copy of this cut is 16.8 GB and fits no chip whole).

Seeded weights: every matrix N(0, gain^2 / fan_in), so a product keeps
its input's scale times the gain; norm gains 1 + N(0, 0.02). The
configuration's `seeded_weights` group gives what is not 1 (PERF.md
section 2 has the readings they were set from):
  embed_gain      the token embedding's standard deviation (no fan: a row
                  is looked up, not summed)
  gate_bias,      `bg` of key-value head c is gate_bias + gate_bias_step *
  gate_bias_step  (c - (Hk - 1) / 2): heads that forget within a hundred
                  tokens beside heads that remember thousands, as trained
                  gates spread. With a bias of 0 a seeded gate forgets in
                  two tokens and no reading could tell a stale state from
                  a sound one; the heads that remember thousands of
                  tokens keep a slot's last tenant in an un-reset state
                  through a whole prompt. Multiples of 1/16, so that
                  bfloat16 holds every bias exactly (a bias off by a
                  bfloat16 step is a decay rate off by 6 %, every token)
  gate_gain       `Wg`: how far a token moves its own gate
  out_gain        `Wo`. 1, and not more: a layer's retention output is a
                  weighted mean over hundreds of values, so part of it is
                  the same for every token of a row; the next layer's
                  values inherit that common part and `Wo` multiplies it
                  again, and at a gain of 2 and more, eight layers deep,
                  every served stream is one token repeated, which no
                  comparison can read (at 4 the float8 control came out
                  correct on one seed in five: PERF.md section 6)
  head_gain       the output head: the logits' spread
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import fit_program_tree, param_shapes, seed_key
from reference import brumby as ref

GAINS = ("embed_gain", "gate_gain", "out_gain", "head_gain")
AT_PAD = 256            # served positions are read in multiples of this


def dims_of(config: dict) -> dict:
    """The sizes the makers, the reference and the counts need. `d` is
    the size of a head (the reference's name), `hidden` the stream's."""
    gains = config.get("seeded_weights", {})
    return {
        "hidden": int(config["hidden_size"]),
        "Hq": int(config["num_attention_heads"]),
        "Hk": int(config["num_key_value_heads"]),
        "d": int(config["head_dim"]), "L": int(config["num_hidden_layers"]),
        "F": int(config["intermediate_size"]), "V": int(config["vocab_size"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "state_dtype": str(config.get("state_dtype", "float32")),
        "gate_bias": float(gains.get("gate_bias", 0.0)),
        "gate_bias_step": float(gains.get("gate_bias_step", 0.0)),
        **{k: float(gains.get(k, 1.0)) for k in GAINS}}


# ------------------------------------------------------------------ weights

def _mat(key, shape, fan_in, gain=1.0):
    return (gain / fan_in ** 0.5) * jax.random.normal(key, shape, jnp.float32)


def _gain_vec(key, n):
    return 1.0 + 0.02 * jax.random.normal(key, (n,), jnp.float32)


def layer_weights(key, dims: dict) -> dict:
    """One layer's float32 weights under the reference's names."""
    h, Hq, Hk, d, F = (dims["hidden"], dims["Hq"], dims["Hk"], dims["d"],
                       dims["F"])
    k = jax.random.split(key, 12)
    return {"n1": _gain_vec(k[0], h), "n2": _gain_vec(k[1], h),
            "Wq": _mat(k[2], (h, Hq * d), h), "Wk": _mat(k[3], (h, Hk * d), h),
            "Wv": _mat(k[4], (h, Hk * d), h),
            "Wg": _mat(k[5], (h, Hk), h, dims["gate_gain"]),
            "bg": dims["gate_bias"] + dims["gate_bias_step"] * (
                jnp.arange(Hk, dtype=jnp.float32) - (Hk - 1) / 2),
            "q_norm": _gain_vec(k[6], d), "k_norm": _gain_vec(k[7], d),
            "Wo": _mat(k[8], (Hq * d, h), Hq * d, dims["out_gain"]),
            "Wgate": _mat(k[9], (h, F), h), "Wup": _mat(k[10], (h, F), h),
            "Wdown": _mat(k[11], (F, h), F)}


def embed_weights(key, dims: dict):
    return dims["embed_gain"] * jax.random.normal(
        jax.random.fold_in(key, 0), (dims["V"], dims["hidden"]), jnp.float32)


def head_weights(key, dims: dict) -> dict:
    k = jax.random.split(jax.random.fold_in(key, 1), 2)
    return {"norm_f": _gain_vec(k[0], dims["hidden"]),
            "Wout": _mat(k[1], (dims["hidden"], dims["V"]), dims["hidden"],
                         dims["head_gain"])}


def _layer_key(key, i):
    return jax.random.fold_in(key, i + 2)


def reference_weights(key, dims: dict) -> dict:
    """All of the reference's weights at once (the tests' sizes)."""
    W = dict(head_weights(key, dims), embed=embed_weights(key, dims))
    W["layers"] = [layer_weights(_layer_key(key, i), dims)
                   for i in range(dims["L"])]
    return W


_FF = ("Wgate", "Wup", "Wdown")


def program_layer(w: dict, i: int) -> dict:
    """One layer's weights under the names `retention_lm` gives them."""
    p = f"blk{i}"
    return {f"{p}_n1": {"gamma": w["n1"]}, f"{p}_n2": {"gamma": w["n2"]},
            f"{p}_ret": {n: x for n, x in w.items()
                         if n not in _FF and n not in ("n1", "n2")},
            f"{p}_ff": {n: w[n] for n in _FF}}


def serving_net(config: dict, seed: int, dims: dict):
    """The program's net for `GenerationEngine`, holding the seeded weights
    in the configuration's `param_dtype` and no optimizer state."""
    from deeplearning4j_tpu.models.retention import retention_lm

    net = retention_lm(
        vocab_size=dims["V"], d_model=dims["hidden"], n_heads=dims["Hq"],
        n_kv_heads=dims["Hk"], n_layers=dims["L"], d_ff=dims["F"],
        head_dim=dims["d"], rope_theta=dims["theta"], eps=dims["eps"],
        sum_eps=ref.SUM_EPS, state_dtype=dims["state_dtype"],
        seed=int(seed) & 0x7FFFFFFF,
        dtype=config["compute_dtype"], param_dtype=config["param_dtype"])
    like = param_shapes(net)
    give_weights(net, seed, dims, like)
    net.state = {n: {} for n in like}
    return net


def give_weights(net, seed: int, dims: dict, like=None) -> None:
    """Replace the net's parameters by the benchmark's seeded ones, in the
    tree `like` (shapes and dtypes; the net's own parameters by default).
    One jitted call for the embedding, one for the head, one a layer (the
    key and the layer number are arguments: one program for all layers),
    so that no more than 3.1 GB of float32 numbers exist beside the
    weights held."""
    like = net.params if like is None else like
    key = seed_key(seed)

    def part(names, made):
        return fit_program_tree(made, {n: like[n] for n in names})

    params = jax.jit(lambda k: part(
        ("embed",), {"embed": {"W": embed_weights(k, dims)}}))(key)

    def head(k):
        g = head_weights(k, dims)
        return part(("norm_f", "out"), {"norm_f": {"gamma": g["norm_f"]},
                                        "out": {"W": g["Wout"]}})

    params.update(jax.jit(head)(key))
    names = [n for n in like if n.startswith("blk0_")]
    make = jax.jit(lambda k, j: part(names, program_layer(
        layer_weights(_layer_key(k, j), dims), 0)))
    for i in range(dims["L"]):
        params.update({n.replace("blk0_", f"blk{i}_", 1): x
                       for n, x in make(key, i).items()})
    net.params = params


def _no_training(*_a, **_k):
    raise NotImplementedError(
        "the brumby family is served, not trained: at 16 bytes a parameter "
        "one layer (330.4 M parameters) takes 5.3 GB, and the smallest cut "
        "the floors allow (four layers, an eighth of the vocabulary: 1.52 B "
        "parameters) takes 24 GB and fits no chip (ISSUE 35)")


training_net = first_moment_tree = program_sq_norms = _no_training
program_projections = seeded_program_tree = reference_readings = _no_training
train_flops_per_token = _no_training


# ---------------------------------------------------------------- `correct`

def _pad_len(n: int) -> int:
    """Reference rows are padded to few lengths, so that few programs
    are compiled: multiples of 256 up to 1,024, then of 1,024."""
    step = 256 if n <= 1024 else 1024
    return -(-n // step) * step


def served_gaps(sample, prompts, seed, dims, lowprec=False):
    """For each sampled request, the gap by which each served token's
    reference logit lies below the reference's best, as one array per
    request — or, for the control (`lowprec`), the gap of the token the
    float8 reference puts first at each of the same positions. The
    reference's weights are made, used over every request and dropped a
    piece at a time (the embedding, each layer, the head); the hidden
    states of all requests (and, for the control, their float8 twins)
    wait between the layers."""
    key = seed_key(seed)
    rows = []
    for r in sample:
        prompt = prompts[r["id"].split(".")[0]]
        served = list(r["tokens"])
        L, n = len(prompt), len(served)
        seq = np.zeros(_pad_len(L + n), np.int32)
        seq[:L] = prompt
        seq[L:L + n - 1] = served[:-1]
        A = -(-n // AT_PAD) * AT_PAD
        at = np.zeros(A, np.int32)
        at[:n] = np.arange(L - 1, L - 1 + n)
        tok = np.zeros(A, np.int32)
        tok[:n] = served
        rows.append((jnp.asarray(seq), jnp.asarray(at), jnp.asarray(tok),
                     jnp.asarray(np.arange(A) < n), n))

    embed = jax.jit(lambda k: embed_weights(k, dims))(key)
    xs = [embed[seq] for seq, *_ in rows]
    del embed
    lows = list(xs) if lowprec else None
    make = jax.jit(lambda k, j: layer_weights(_layer_key(k, j), dims))
    run = jax.jit(lambda x, w: ref.layer(x, w, dims))
    run_low = jax.jit(lambda x, w: ref.layer(x, w, dims, ref.mm_fp8))
    for i in range(dims["L"]):
        w = make(key, i)
        xs = [run(x, w) for x in xs]
        if lowprec:
            lows = [run_low(x, w) for x in lows]
        del w
    # the head's weights are arguments: closed over, a [hidden, V] constant
    # would be folded into each program
    G = jax.jit(lambda k: head_weights(k, dims))(key)
    logits = jax.jit(lambda x, at, g, W: ref.logits_at(x, at, g, W, dims))
    logits_low = jax.jit(lambda x, at, g, W: ref.logits_at(
        x, at, g, W, dims, ref.mm_fp8))
    out = []
    for j, (_seq, at, tok, valid, n) in enumerate(rows):
        lg = logits(xs[j], at, G["norm_f"], G["Wout"])
        if lowprec:
            tok = jnp.argmax(logits_low(lows[j], at, G["norm_f"], G["Wout"]),
                             axis=-1)
        out.append(np.asarray(ref.served_gap(lg, tok, valid), np.float64)[:n])
    return out


# ------------------------------------------------------------------- counts

def _layer_params(dims: dict) -> int:
    h, Hq, Hk, d = dims["hidden"], dims["Hq"], dims["Hk"], dims["d"]
    ret = 2 * h * Hq * d + 2 * h * Hk * d + h * Hk + Hk + 2 * d
    return ret + 3 * h * dims["F"] + 2 * h


def matmul_param_count(dims: dict) -> int:
    """Parameters a decode step has to read: every layer's matrices and
    gains, the final norm and the head. The embedding table is gathered
    by row, not read."""
    return (dims["L"] * _layer_params(dims) + dims["hidden"]
            + dims["hidden"] * dims["V"])


def count_params(dims: dict) -> int:
    """Parameters as held: the layers of the cut, the whole vocabulary
    twice (embedding and untied head)."""
    return matmul_param_count(dims) + dims["V"] * dims["hidden"]


def pairs(dims: dict) -> int:
    """The entries of a head's symmetric square the algorithm needs: the
    pairs i <= j, 8,256 at d = 128. The program holds 8,320 (the half
    diagonal twice: ops/power_retention.py), so a count from this number
    is the algorithm's and 0.8 % under what the program moves."""
    return dims["d"] * (dims["d"] + 1) // 2


def state_values_per_slot(dims: dict, D: int) -> int:
    return dims["L"] * dims["Hk"] * (dims["d"] + 1) * D


def state_bytes_per_slot(dims: dict) -> int:
    """Bytes of state one slot HOLDS over all layers, as the program lays
    it out (`s` [Hk, d, D] and `z` [Hk, D] float32 a layer with D =
    d (d / 2 + 1) = 8,320: 34.34 MB a layer; the 8,256 pairs alone would
    be 34.08 MB)."""
    D = dims["d"] * (dims["d"] // 2 + 1)
    return 4 * state_values_per_slot(dims, D)


def _retention_flops(dims: dict, context: float) -> float:
    """One token's retention in one layer against `context` earlier
    tokens, the cheaper of the two forms: the recurrence (the state of
    every key-value head decayed and added to, 3 operations an entry,
    and read by every query head, 2 an entry), or the attention form (a
    score and a weighted value a key a query head)."""
    Hq, Hk, d = dims["Hq"], dims["Hk"], dims["d"]
    recurrent = (3 * Hk + 2 * Hq) * (d + 1) * pairs(dims)
    attention = 4.0 * Hq * d * context
    return min(recurrent, attention)


def forward_flops_per_token(dims: dict, context: float) -> float:
    """Forward FLOPs of one token with `context` tokens before it: every
    matrix once, retention, the head."""
    h = dims["hidden"]
    matrices = _layer_params(dims) - 2 * h - dims["Hk"] - 2 * dims["d"]
    return (dims["L"] * (2 * matrices + _retention_flops(dims, context))
            + 2 * h * dims["V"])


def prefill_flops(dims: dict, prompt_len: int) -> float:
    """Forward FLOPs of a whole prompt (mean context (L + 1) / 2); the
    head runs on its last row only."""
    head = 2 * dims["hidden"] * dims["V"]
    body = forward_flops_per_token(dims, (prompt_len + 1) / 2.0) - head
    return prompt_len * body + head


def decode_flops(dims: dict, context: float) -> float:
    """Forward FLOPs of one generated token after `context` tokens."""
    return forward_flops_per_token(dims, context)


def kv_bytes_per_token(dims: dict, bytes_per_value: int = 2) -> int:
    """A cached token holds nothing: the cache is a state a slot."""
    return 0


def retention_decode_bytes(dims: dict, live_slots: float) -> float:
    """The least the `retention_decode` kernel calls of one decode step
    move, over all layers: every live slot's state (the pairs, float32)
    read once and written once, and the step's q, k, v, g in and y and
    the normaliser out (float32, as the kernel takes them)."""
    Hq, Hk, d = dims["Hq"], dims["Hk"], dims["d"]
    small = dims["L"] * 4 * (2 * Hq * d + 2 * Hk * d + Hk + Hq)
    return live_slots * (2 * 4 * state_values_per_slot(dims, pairs(dims))
                         + small)


def decode_step_min_bytes(dims: dict, live_tokens: float,
                          bytes_per_value: int = 2) -> float:
    """The least a decode step WITH ONE LIVE TOKEN moves: the held
    weights once at the stated compute precision and one slot's state
    read and written. A step's state traffic goes with its live SLOTS,
    not with the live tokens this function is told
    (`layer_metrics/decode_step_roofline.py` passes the summed context
    lengths and no batch), so it counts one slot, the least any step
    needs: `decode_step_roofline` UNDER-reads in a cell of this family
    (at 16 live slots the least is 15.5 GB, this says 7.4 GB) and can
    never read over 100 %. `retention_decode_roofline` has the kernel's
    own count with the batch (PERF.md section 7)."""
    return (matmul_param_count(dims) * bytes_per_value
            + retention_decode_bytes(dims, 1.0))
