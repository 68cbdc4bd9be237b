"""The `ouro` family (`"model_type": "ouro"`, ByteDance Ouro, a looped
language model): a stack of sandwich-norm blocks of plain multi-head
attention (rotary over the whole head, no per-head norm, no gate) and a
gated SiLU feed-forward, run `total_ut_steps` times a token with ONE set
of weights, the final norm closing every pass; an untied head over the
last pass. Served whole. Behind the interface of `families/__init__.py`.

Sizes from the configuration's own keys (the published `config.json`
names); the program's net through `models.looped.looped_lm`; the seeded
weights; the plain reference (`benchmarks/reference/ouro.py`, imported
here alone); the counts. A serving family: the training entries raise
(see `_no_training`).

What a server keeps for a sequence is a row a token in every (layer,
pass): the loop multiplies the cache by its passes. What a decode step
reads is every layer's weights once a pass (pass t + 1 of layer 0 needs
pass t of layer L - 1, and L - 1 other layers lie between a layer's
uses) and every live row of every (layer, pass).

The benchmark makes the weights, a block at a time on both sides:
`layer_weights(fold_in(key, i + 1), dims)` gives block i the same
float32 numbers for the program (cast to its `param_dtype` as they are
made, one jitted call a block whose key and block number are arguments)
and for the reference (made for each pass, used over every sampled
request, dropped).

Seeded weights: every matrix N(0, gain^2 / fan_in), so a product keeps
its input's scale times the gain; norm gains 1 + N(0, 0.02). The
configuration's `seeded_weights` group gives what is not 1:
  embed_gain   the standard deviation of x0 = E[id] (no embedding scale)
  q_gain       `Wq`: with no per-head norm, the spread of the scores is
               the query's scale, so this is what lets attention pick
               rows of the cache
  head_gain    the output head: the logits' spread
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import fit_program_tree, param_shapes, seed_key
from reference import ouro as ref

GAINS = ("embed_gain", "q_gain", "head_gain")
# the wrong models `served_gaps` can run in the program's place
# (`tools/faults.py`): the reference's docstring says what each is
FAULTS = ref.FAULTS


def dims_of(config: dict) -> dict:
    """The sizes the makers, the reference and the counts need. `times`
    is the passes a token makes through the stack; `d` the size of a
    head."""
    gains = config.get("seeded_weights", {})
    L = int(config["num_hidden_layers"])
    if float(config["early_exit_threshold"]) < 1.0:
        raise ValueError("the ouro family runs every token through every "
                         "pass: an exit gate that lets rows leave the loop "
                         "at different passes is not served (ROADMAP, "
                         "layers run several times)")
    if config.get("use_sliding_window") or any(
            t != "full_attention" for t in config["layer_types"]) \
            or len(config["layer_types"]) != L:
        raise ValueError("the ouro family holds full-attention layers only, "
                         "one a hidden layer")
    return {
        "hidden": int(config["hidden_size"]),
        "Hq": int(config["num_attention_heads"]),
        "Hk": int(config["num_key_value_heads"]),
        "d": int(config["head_dim"]), "L": L,
        "times": int(config["total_ut_steps"]),
        "F": int(config["intermediate_size"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]), "V": int(config["vocab_size"]),
        **{k: float(gains.get(k, 1.0)) for k in GAINS}}


# ------------------------------------------------------------------ weights

def _mat(key, shape, fan_in, gain=1.0):
    return (gain / fan_in ** 0.5) * jax.random.normal(key, shape, jnp.float32)


def _gain_vec(key, n, gain=1.0):
    return gain * (1.0 + 0.02 * jax.random.normal(key, (n,), jnp.float32))


def layer_weights(key, dims: dict) -> dict:
    """One block's float32 weights under the reference's names."""
    h, Hq, Hk, d, F = (dims[k] for k in ("hidden", "Hq", "Hk", "d", "F"))
    k = jax.random.split(key, 11)
    return {"n1": _gain_vec(k[0], h), "n2": _gain_vec(k[1], h),
            "n3": _gain_vec(k[2], h), "n4": _gain_vec(k[3], h),
            "Wq": _mat(k[4], (h, Hq * d), h, dims["q_gain"]),
            "Wk": _mat(k[5], (h, Hk * d), h),
            "Wv": _mat(k[6], (h, Hk * d), h),
            "Wo": _mat(k[7], (Hq * d, h), Hq * d),
            "Wgate": _mat(k[8], (h, F), h), "Wup": _mat(k[9], (h, F), h),
            "Wdown": _mat(k[10], (F, h), F)}


def global_weights(key, dims: dict) -> dict:
    h, V = dims["hidden"], dims["V"]
    k = jax.random.split(key, 3)
    return {"embed": dims["embed_gain"] * jax.random.normal(
                k[0], (V, h), jnp.float32),
            "norm_f": _gain_vec(k[1], h),
            "Wout": _mat(k[2], (h, V), h, dims["head_gain"])}


def _layer_key(key, i):
    return jax.random.fold_in(key, i + 1)


def reference_weights(key, dims: dict) -> dict:
    """All of the reference's weights at once (the tests' sizes)."""
    W = global_weights(jax.random.fold_in(key, 0), dims)
    W["layers"] = [layer_weights(_layer_key(key, i), dims)
                   for i in range(dims["L"])]
    return W


_ATTN = ("Wq", "Wk", "Wv", "Wo")
_FF = ("Wgate", "Wup", "Wdown")


def program_layer(w: dict, i: int) -> dict:
    """One block's weights under the names `looped_lm` gives them."""
    p = f"blk{i}"
    return {**{f"{p}_n{j}": {"gamma": w[f"n{j}"]} for j in (1, 2, 3, 4)},
            f"{p}_attn": {n: w[n] for n in _ATTN},
            f"{p}_ff": {n: w[n] for n in _FF}}


def program_globals(g: dict) -> dict:
    return {"embed": {"W": g["embed"]}, "norm_f": {"gamma": g["norm_f"]},
            "out": {"W": g["Wout"]}}


def serving_net(config: dict, seed: int, dims: dict):
    """The program's net for `GenerationEngine`, holding the seeded weights
    in the configuration's `param_dtype` and no optimizer state."""
    from deeplearning4j_tpu.models.looped import looped_lm

    net = looped_lm(
        dims["V"], dims["hidden"], dims["Hq"], dims["L"], dims["times"],
        d_ff=dims["F"], n_kv_heads=dims["Hk"], head_dim=dims["d"],
        rope_theta=dims["theta"], eps=dims["eps"],
        seed=int(seed) & 0x7FFFFFFF, dtype=config["compute_dtype"],
        param_dtype=config["param_dtype"])
    like = param_shapes(net)
    give_weights(net, seed, dims, like)
    net.state = {n: {} for n in like}
    return net


def give_weights(net, seed: int, dims: dict, like=None) -> None:
    """Replace the net's parameters by the benchmark's seeded ones, in the
    tree `like` (shapes and dtypes; the net's own parameters by default).
    One jitted call a block, so that no more than a block's float32
    numbers (206 MB) exist beside the weights held."""
    like = net.params if like is None else like
    key = seed_key(seed)

    def part(names, made):
        return fit_program_tree(made, {n: like[n] for n in names})

    first = [n for n in like if n.startswith("blk0_")]
    params = jax.jit(lambda k: part(
        ("embed", "norm_f", "out"),
        program_globals(global_weights(jax.random.fold_in(k, 0), dims))))(key)
    # compiled under block 0's names; the block number is an argument, so
    # every block reuses the program
    make = jax.jit(lambda k, j: part(first, program_layer(
        layer_weights(_layer_key(k, j), dims), 0)))
    for i in range(dims["L"]):
        params.update({n.replace("blk0_", f"blk{i}_", 1): x
                       for n, x in make(key, i).items()})
    net.params = params


def _no_training(*_a, **_k):
    raise NotImplementedError(
        "the ouro family is served, not trained: at 16 bytes a parameter a "
        "training step holds 9 of the 48 layers, while serving holds all "
        "of them and the whole vocabulary; the loop's training objective "
        "(a per-pass exit gate) is not given by the configuration "
        "(ROADMAP, layers run several times)")


training_net = first_moment_tree = program_sq_norms = _no_training
program_projections = seeded_program_tree = reference_readings = _no_training
train_flops_per_token = _no_training


# ---------------------------------------------------------------- `correct`

def _row_len(n: int) -> int:
    """The positions a row is padded to in the reference: 1,280 (the
    server's capacity) or the next multiple of 256, so that one program
    serves every row."""
    return max(1280, -(-n // 256) * 256)


def served_gaps(sample, prompts, seed, dims, lowprec=False, fault=None):
    """For each sampled request, the gap by which each served token's
    reference logit lies below the reference's best, as one array per
    request — or, for the control (`lowprec`), the gap of the token the
    float8 reference puts first at each of the same positions, and for a
    `fault` (`ref.FAULTS`) that of the token the faulty model puts first.
    The reference's weights are ARGUMENTS of its jitted programs (closed
    over they would be folded into each), made for each pass a block at
    a time, used over every request and dropped; the rows' hidden states
    (and the control's or the fault's twins) wait between the blocks. A
    row goes through a block whole, padded at its end to one length
    (`_row_len`): one program a kind of row, whatever its length."""
    key = seed_key(seed)
    rows = []
    for r in sample:
        prompt = prompts[r["id"].split(".")[0]]
        served = list(r["tokens"])
        L, n = len(prompt), len(served)
        rows.append((np.concatenate([np.asarray(prompt, np.int32),
                                     np.asarray(served[:-1], np.int32)]),
                     np.arange(L - 1, L - 1 + n),
                     np.asarray(served, np.int32), n))
    S = _row_len(max(seq.shape[0] for seq, *_ in rows))

    def padded(seq):
        return jnp.asarray(np.pad(seq, (0, S - seq.shape[0])))

    G = jax.jit(lambda k: global_weights(jax.random.fold_in(k, 0), dims))(key)
    make = jax.jit(lambda k, j: layer_weights(_layer_key(k, j), dims))
    run = jax.jit(lambda x, w, kv: ref.block(x, w, dims, ref.mm_highest, kv))
    run_low = jax.jit(lambda x, w: ref.block(x, w, dims, ref.mm_fp8))
    norm = jax.jit(lambda x, g: ref.rms_norm(x, g, dims["eps"]))
    xs = [G["embed"][padded(seq)] for seq, *_ in rows]
    other = list(xs) if lowprec or fault else None
    first = []          # the fault "shared_rows": pass 0's block inputs
    for t in range(dims["times"]):
        for i in range(dims["L"]):
            w = make(key, i)
            xs = [run(x, w, None) for x in xs]
            if lowprec:
                other = [run_low(x, w) for x in other]
            elif fault and t < ref.passes_of(dims, fault):
                if fault == "shared_rows" and t == 0:
                    first.append(list(other))
                kv = first[i] if fault == "shared_rows" and t else None
                other = [run(x, w, None if kv is None else kv[j])
                         for j, x in enumerate(other)]
            del w
        xs = [norm(x, G["norm_f"]) for x in xs]
        if lowprec or (fault and t < ref.passes_of(dims, fault)
                       and ref.closes_pass(dims, t, fault)):
            other = [norm(x, G["norm_f"]) for x in other]
    head = jax.jit(lambda x, W: ref.mm_highest(x, W))
    head_low = jax.jit(lambda x, W: ref.mm_fp8(x, W))
    out = []
    for j, (_seq, at, tok, n) in enumerate(rows):
        lg = head(xs[j][at], G["Wout"])
        t = jnp.asarray(tok)
        if lowprec:
            t = jnp.argmax(head_low(other[j][at], G["Wout"]), axis=-1)
        elif fault:
            t = jnp.argmax(head(other[j][at], G["Wout"]), axis=-1)
        gap = ref.served_gap(lg, t, jnp.ones((n,), bool))
        out.append(np.asarray(gap, np.float64))
    return out


# ------------------------------------------------------------------- counts

def _layer_params(dims: dict) -> int:
    """A block as held: attention (no gate, no per-head norm), the
    gated feed-forward and four norms."""
    h, Hq, Hk, d = dims["hidden"], dims["Hq"], dims["Hk"], dims["d"]
    return 2 * h * Hq * d + 2 * h * Hk * d + 3 * h * dims["F"] + 4 * h


def matmul_param_count(dims: dict) -> int:
    """Parameters of the stack, the final norm and the head (the embedding
    table is gathered by row, not read)."""
    return (dims["L"] * _layer_params(dims) + dims["hidden"]
            + dims["hidden"] * dims["V"])


def count_params(dims: dict) -> int:
    """Parameters as held: one set for every pass."""
    return matmul_param_count(dims) + dims["V"] * dims["hidden"]


def _row_bytes(dims: dict, bytes_per_value: int = 2) -> int:
    """A token's key and value in one (layer, pass)."""
    return 2 * dims["Hk"] * dims["d"] * bytes_per_value


def kv_bytes_per_token(dims: dict, bytes_per_value: int = 2) -> int:
    """Bytes a cached token holds over all layers and passes: a row in
    every (layer, pass)."""
    return dims["times"] * dims["L"] * _row_bytes(dims, bytes_per_value)


def cache_bytes_per_slot(dims: dict, capacity: int,
                         bytes_per_value: int = 2) -> int:
    """Bytes of cache one slot holds: `capacity` rows in every (layer,
    pass)."""
    return capacity * kv_bytes_per_token(dims, bytes_per_value)


def _rows_read(dims: dict, context):
    """Cache rows a query at `context` keys reads over all layers and
    passes."""
    return dims["times"] * dims["L"] * context


def gqa_decode_bytes(dims: dict, contexts, bytes_per_value: int = 2) -> float:
    """The least the `gqa_decode` kernel calls move for decoded tokens
    that see `contexts` keys each (their own among them), over every
    (layer, pass): every visible row's key and value read once, and the
    token's queries in and outputs out."""
    contexts = np.asarray(contexts, np.float64)
    small = (dims["times"] * dims["L"] * 2 * dims["Hq"] * dims["d"]
             * bytes_per_value)
    return float(np.sum(_rows_read(dims, contexts))
                 * _row_bytes(dims, bytes_per_value)
                 + contexts.size * small)


def decode_step_min_bytes(dims: dict, live_tokens: float,
                          bytes_per_value: int = 2) -> float:
    """The least a decode step moves, TOLD THE SUM OF ITS LIVE ROWS'
    CONTEXTS ALONE: every block's weights once a PASS at the stated
    compute precision (no exact program reads a block once for all
    passes: the other L - 1 blocks lie between its uses), the final norm
    and the head once, and `live_tokens` rows in every (layer, pass)."""
    return ((dims["times"] * dims["L"] * _layer_params(dims)
             + dims["hidden"] + dims["hidden"] * dims["V"]) * bytes_per_value
            + float(_rows_read(dims, live_tokens))
            * _row_bytes(dims, bytes_per_value))


def forward_flops_per_token(dims: dict, keys: float) -> float:
    """Forward FLOPs of one token that attends to `keys` keys: in every
    (layer, pass) the projections, attention (a score and a weighted value
    a key a query head) and the feed-forward block; the head once."""
    h, Hq, Hk, d = dims["hidden"], dims["Hq"], dims["Hk"], dims["d"]
    block = (2 * (2 * h * Hq * d + 2 * h * Hk * d) + 4 * Hq * d * keys
             + 2 * 3 * h * dims["F"])
    return dims["times"] * dims["L"] * block + 2 * h * dims["V"]


def prefill_flops(dims: dict, prompt_len: int) -> float:
    """Forward FLOPs of a whole prompt (a query's mean keys (L + 1) / 2);
    the head runs on its last row only."""
    head = 2 * dims["hidden"] * dims["V"]
    body = forward_flops_per_token(dims, (prompt_len + 1) / 2.0) - head
    return prompt_len * body + head


def decode_flops(dims: dict, context: float) -> float:
    """Forward FLOPs of one generated token against `context` keys."""
    return forward_flops_per_token(dims, context)
