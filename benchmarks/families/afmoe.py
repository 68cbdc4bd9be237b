"""The `afmoe` family (`"model_type": "afmoe"`, Arcee Trinity): grouped
softmax attention (per-head RMS norms on query and key, a sigmoid gate
on the output) in window layers with rotary position and full layers
with no position at all, side by side; sandwich norms; leading dense
layers, then sigmoid-routed gated experts with a shared expert and a
selection bias, served as ONE CHIP'S SHARE of an expert-parallel
deployment. Behind the interface of `families/__init__.py`.

Sizes from the configuration's own keys (the published `config.json`
names); the program's net through `models.grouped_moe.grouped_moe_lm`;
the seeded weights; the plain reference (`benchmarks/reference/afmoe.py`,
imported here alone); the counts. A serving family: the training entries
raise (see `_no_training`).

What a server keeps for a sequence depends on the layer's kind and the
token's age: a full layer holds every token's row, a window layer the
newest `window` rows in a ring. `kv_bytes_per_token` is what a token
costs while every layer still holds it; a slot's bytes are
`cache_bytes_per_slot(dims, capacity)`.

The benchmark makes the weights, a layer at a time on both sides:
`layer_weights(fold_in(key, i + 1), dims, dense)` gives layer i the same
float32 numbers for the program (cast to its `param_dtype` as they are
made, one jitted call a layer whose key and layer number are arguments;
the selection bias stays float32) and for the reference (made, used over
every sampled request, dropped).

Seeded weights: every matrix N(0, gain^2 / fan_in), so a product keeps
its input's scale times the gain; norm gains 1 + N(0, 0.02). The
configuration's `seeded_weights` group gives what is not 1:
  embed_gain   the standard deviation of x0 = embed[id] * sqrt(hidden):
               the table holds N(0, embed_gain^2 / hidden), so a token
               weighs as much in the stream as a sublayer's normalised
               output (a unit table times sqrt(3072) would drown them)
  qk_gain      the gain vector of the query's per-head RMS norm: under
               that norm a larger `Wq` changes nothing, so this is what
               spreads the scores and lets attention pick rows of the
               cache (at 1 a query's weights over 4,096 keys are near
               uniform and no reading tells a window from none)
  router_gain  `Wr`: the spread of the router's scores before the sigmoid
  bsel_std     the selection bias, N(0, bsel_std^2) an expert a layer:
               large enough that it changes which experts most tokens
               select (the configuration says what share), or a program
               that ignores it reads `correct` true
  head_gain    the output head: the logits' spread
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import fit_program_tree, param_shapes, seed_key
from reference import afmoe as ref

GAINS = ("embed_gain", "qk_gain", "router_gain", "head_gain")
SLIDING = "sliding_attention"


def dims_of(config: dict) -> dict:
    """The sizes the makers, the reference and the counts need. `held`
    and `V` are what this chip holds (the keys `reduced` lists); the
    router's width `E` is the published count, which the configuration
    states beside the deployment. `d` is the size of a head."""
    share = config["share"]
    gains = config.get("seeded_weights", {})
    types = tuple(config["layer_types"])
    if len(types) != int(config["num_hidden_layers"]):
        raise ValueError("layer_types does not name num_hidden_layers layers")
    if int(config["num_shared_experts"]) != 1:
        raise ValueError("the afmoe family holds one shared expert")
    return {
        "hidden": int(config["hidden_size"]),
        "Hq": int(config["num_attention_heads"]),
        "Hk": int(config["num_key_value_heads"]),
        "d": int(config["head_dim"]), "L": len(types),
        "sliding": tuple(t == SLIDING for t in types),
        "window": int(config["sliding_window"]),
        "n_dense": int(config["num_dense_layers"]),
        "F": int(config["intermediate_size"]),
        "Fe": int(config["moe_intermediate_size"]),
        "E": int(share["router_experts"]),
        "held": int(config["num_experts"]),
        "first_expert": int(share["first_expert"]),
        "top_k": int(config["num_experts_per_tok"]),
        "scaling": float(config["route_scale"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]), "V": int(config["vocab_size"]),
        "bsel_std": float(gains.get("bsel_std", 0.0)),
        **{k: float(gains.get(k, 1.0)) for k in GAINS}}


# ------------------------------------------------------------------ weights

def _mat(key, shape, fan_in, gain=1.0):
    return (gain / fan_in ** 0.5) * jax.random.normal(key, shape, jnp.float32)


def _gain_vec(key, n, gain=1.0):
    return gain * (1.0 + 0.02 * jax.random.normal(key, (n,), jnp.float32))


def layer_weights(key, dims: dict, dense: bool) -> dict:
    """One layer's float32 weights under the reference's names."""
    h, Hq, Hk, d = dims["hidden"], dims["Hq"], dims["Hk"], dims["d"]
    k = jax.random.split(key, 22)
    w = {"n1": _gain_vec(k[0], h), "n2": _gain_vec(k[1], h),
         "n3": _gain_vec(k[2], h), "n4": _gain_vec(k[3], h),
         "Wq": _mat(k[4], (h, Hq * d), h), "Wk": _mat(k[5], (h, Hk * d), h),
         "Wv": _mat(k[6], (h, Hk * d), h), "Wg": _mat(k[7], (h, Hq * d), h),
         "q_norm": _gain_vec(k[8], d, dims["qk_gain"]),
         "k_norm": _gain_vec(k[9], d),
         "Wo": _mat(k[10], (Hq * d, h), Hq * d)}
    if dense:
        F = dims["F"]
        w.update(Wgate=_mat(k[11], (h, F), h), Wup=_mat(k[12], (h, F), h),
                 Wdown=_mat(k[13], (F, h), F))
        return w
    Fe, held = dims["Fe"], dims["held"]
    w.update(Wr=_mat(k[11], (h, dims["E"]), h, dims["router_gain"]),
             bsel=dims["bsel_std"] * jax.random.normal(
                 k[18], (dims["E"],), jnp.float32),
             We_gate=_mat(k[12], (held, h, Fe), h),
             We_up=_mat(k[13], (held, h, Fe), h),
             We_down=_mat(k[14], (held, Fe, h), Fe),
             Ws_gate=_mat(k[15], (h, Fe), h), Ws_up=_mat(k[16], (h, Fe), h),
             Ws_down=_mat(k[17], (Fe, h), Fe))
    return w


def global_weights(key, dims: dict) -> dict:
    h, V = dims["hidden"], dims["V"]
    k = jax.random.split(key, 3)
    return {"embed": _mat(k[0], (V, h), h, dims["embed_gain"]),
            "norm_f": _gain_vec(k[1], h),
            "Wout": _mat(k[2], (h, V), h, dims["head_gain"])}


def _layer_key(key, i):
    return jax.random.fold_in(key, i + 1)


def reference_weights(key, dims: dict) -> dict:
    """All of the reference's weights at once (the tests' sizes)."""
    W = global_weights(jax.random.fold_in(key, 0), dims)
    W["layers"] = [layer_weights(_layer_key(key, i), dims, i < dims["n_dense"])
                   for i in range(dims["L"])]
    return W


_ATTN = ("Wq", "Wk", "Wv", "Wg", "q_norm", "k_norm", "Wo")


def program_layer(w: dict, i: int) -> dict:
    """One layer's weights under the names `grouped_moe_lm` gives them
    (the expert layer calls its router `Wg`)."""
    p = f"blk{i}"
    ff = {n: x for n, x in w.items()
          if n not in _ATTN and not n.startswith("n")}
    if "Wr" in ff:
        ff["Wg"] = ff.pop("Wr")
    return {**{f"{p}_n{j}": {"gamma": w[f"n{j}"]} for j in (1, 2, 3, 4)},
            f"{p}_attn": {n: w[n] for n in _ATTN}, f"{p}_ff": ff}


def program_globals(g: dict) -> dict:
    return {"embed": {"W": g["embed"]}, "norm_f": {"gamma": g["norm_f"]},
            "out": {"W": g["Wout"]}}


def serving_net(config: dict, seed: int, dims: dict):
    """The program's net for `GenerationEngine`, holding the seeded weights
    in the configuration's `param_dtype` and no optimizer state."""
    from deeplearning4j_tpu.models.grouped_moe import grouped_moe_lm

    net = grouped_moe_lm(
        dims["V"], dims["hidden"], dims["Hq"], dims["Hk"], dims["d"],
        list(config["layer_types"]), dims["window"], dims["n_dense"],
        dims["F"], dims["E"], dims["top_k"], dims["Fe"],
        dims["first_expert"], dims["held"], n_shared=1,
        routed_scaling=dims["scaling"],
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
    One jitted call a layer, so that no more than a layer's float32
    numbers (1.27 GB) exist beside the weights held."""
    like = net.params if like is None else like
    key = seed_key(seed)

    def part(names, made):
        return fit_program_tree(made, {n: like[n] for n in names})

    def names_of(i):
        return [n for n in like if n.startswith(f"blk{i}_")]

    params = jax.jit(lambda k: part(
        ("embed", "norm_f", "out"),
        program_globals(global_weights(jax.random.fold_in(k, 0), dims))))(key)
    makers = {}
    for i in range(dims["L"]):
        dense = i < dims["n_dense"]
        if dense not in makers:
            # compiled under this layer's names; the layer number is an
            # argument, so the later layers of its kind reuse the program
            makers[dense] = (i, jax.jit(lambda k, j, i=i, dense=dense: part(
                names_of(i), program_layer(
                    layer_weights(_layer_key(k, j), dims, dense), i))))
        first, make = makers[dense]
        params.update({n.replace(f"blk{first}_", f"blk{i}_", 1): x
                       for n, x in make(key, i).items()})
    net.params = params


def _no_training(*_a, **_k):
    raise NotImplementedError(
        "the afmoe family is served, not trained: at 16 bytes a parameter "
        "one expert layer with the floor of 8 experts (318.5 M parameters) "
        "takes 5.1 GB, and the smallest cut the floors allow (one dense "
        "layer, four expert layers, an eighth of the vocabulary: 1.60 B "
        "parameters) takes 25.7 GB and fits no chip (ISSUE 37)")


training_net = first_moment_tree = program_sq_norms = _no_training
program_projections = seeded_program_tree = reference_readings = _no_training
train_flops_per_token = _no_training


# ---------------------------------------------------------------- `correct`

PIECE = 1024            # rows the reference takes at a time
ROW_LENS = (2048, 4096, 8192, 17408)


def _row_len(n: int) -> int:
    """The positions the reference's keys and values are held for: the
    sample's longest row, up to one of few lengths (then multiples of
    4,096), so that few programs are compiled: on the chip a program
    with float32 products takes a quarter of a minute to compile."""
    return next((p for p in ROW_LENS if n <= p), -(-n // 4096) * 4096)


def served_gaps(sample, prompts, seed, dims, lowprec=False):
    """For each sampled request, the gap by which each served token's
    reference logit lies below the reference's best, as one array per
    request — or, for the control (`lowprec`), the gap of the token the
    float8 reference puts first at each of the same positions. The
    reference's weights are ARGUMENTS of its jitted programs (closed
    over they would be folded into each), made, used over every request
    and dropped a layer at a time; the hidden states of all requests
    (and, for the control, their float8 twins) wait between the layers.
    A row goes through a layer `PIECE` rows at a time against its keys
    and values so far (`ref.block_rows`): one program a kind of
    feed-forward block, whatever the rows' lengths and the layer's kind
    of attention, and a 17,408-token row's scores fit."""
    key = seed_key(seed)
    rows = []
    for r in sample:
        prompt = prompts[r["id"].split(".")[0]]
        served = list(r["tokens"])
        L, n = len(prompt), len(served)
        seq = np.zeros(-(-(L + n) // PIECE) * PIECE, np.int32)
        seq[:L] = prompt
        seq[L:L + n - 1] = served[:-1]
        A = -(-n // PIECE) * PIECE
        at = np.zeros(A, np.int32)
        at[:n] = np.arange(L - 1, L - 1 + n)
        tok = np.zeros(A, np.int32)
        tok[:n] = served
        rows.append((jnp.asarray(seq), jnp.asarray(at), jnp.asarray(tok),
                     jnp.asarray(np.arange(A) < n), n))
    S = _row_len(max(seq.shape[0] for seq, *_ in rows))

    G = jax.jit(lambda k: global_weights(jax.random.fold_in(k, 0), dims))(key)
    xs = [ref.embed(G["embed"], seq, dims) for seq, *_ in rows]
    make = {dense: jax.jit(lambda k, j, dense=dense: layer_weights(
        _layer_key(k, j), dims, dense)) for dense in (True, False)}

    def through(mm):
        """One layer over one row, a piece at a time; the row's keys and
        values are donated from piece to piece."""
        piece = jax.jit(lambda x, K, V, t0, sliding, w: ref.block_rows(
            x, K, V, t0, sliding, w, dims, mm), donate_argnums=(1, 2))

        def run(x, w, sliding):
            K, V = ref.empty_rows(S, dims)
            out = []
            for t0 in range(0, x.shape[0], PIECE):
                y, K, V = piece(x[t0:t0 + PIECE], K, V, jnp.int32(t0),
                                jnp.bool_(sliding), w)
                out.append(y)
            return jnp.concatenate(out)
        return run

    run, run_low = through(ref.mm_highest), through(ref.mm_fp8)
    lows = list(xs) if lowprec else None
    for i in range(dims["L"]):
        w = make[i < dims["n_dense"]](key, i)
        xs = [run(x, w, dims["sliding"][i]) for x in xs]
        if lowprec:
            lows = [run_low(x, w, dims["sliding"][i]) for x in lows]
        del w
    logits = jax.jit(lambda x, g, W: ref.logits_at(
        x, jnp.arange(x.shape[0]), g, W, dims))
    logits_low = jax.jit(lambda x, g, W: ref.logits_at(
        x, jnp.arange(x.shape[0]), g, W, dims, ref.mm_fp8))
    out = []
    for j, (_seq, at, tok, valid, n) in enumerate(rows):
        gaps = []
        for a in range(0, at.shape[0], PIECE):
            sl = slice(a, a + PIECE)
            lg = logits(xs[j][at[sl]], G["norm_f"], G["Wout"])
            t = tok[sl]
            if lowprec:
                t = jnp.argmax(logits_low(lows[j][at[sl]], G["norm_f"],
                                          G["Wout"]), axis=-1)
            gaps.append(ref.served_gap(lg, t, valid[sl]))
        out.append(np.asarray(jnp.concatenate(gaps), np.float64)[:n])
    return out


# ------------------------------------------------------------------- counts

def _attn_params(dims: dict) -> int:
    h, Hq, Hk, d = dims["hidden"], dims["Hq"], dims["Hk"], dims["d"]
    return 3 * h * Hq * d + 2 * h * Hk * d + 2 * d


def _layer_params(dims: dict, dense: bool) -> int:
    """A layer as held: attention, four norms, and the dense block, or
    the router, its bias, the held experts and the shared one."""
    h = dims["hidden"]
    ff = (3 * h * dims["F"] if dense else
          h * dims["E"] + dims["E"] + (dims["held"] + 1) * 3 * h * dims["Fe"])
    return _attn_params(dims) + 4 * h + ff


def matmul_param_count(dims: dict) -> int:
    """Parameters a decode step has to read: every layer's matrices and
    gains as held (EVERY held expert once) and the head. The embedding
    table is gathered by row, not read."""
    nd = dims["n_dense"]
    return (nd * _layer_params(dims, True)
            + (dims["L"] - nd) * _layer_params(dims, False)
            + dims["hidden"] + dims["hidden"] * dims["V"])


def count_params(dims: dict) -> int:
    """Parameters as held: the share's experts, the vocabulary's slice."""
    return matmul_param_count(dims) + dims["V"] * dims["hidden"]


def _kinds(dims: dict) -> tuple:
    """(window layers, full layers)."""
    n = sum(dims["sliding"])
    return n, dims["L"] - n


def _row_bytes(dims: dict, bytes_per_value: int = 2) -> int:
    """A token's key and value in one layer."""
    return 2 * dims["Hk"] * dims["d"] * bytes_per_value


def kv_bytes_per_token(dims: dict, bytes_per_value: int = 2) -> int:
    """Bytes a cached token holds over all layers WHILE EVERY LAYER STILL
    HOLDS IT: a window layer lets go of it `window` tokens later."""
    return dims["L"] * _row_bytes(dims, bytes_per_value)


def cache_bytes_per_slot(dims: dict, capacity: int,
                         bytes_per_value: int = 2) -> int:
    """Bytes of cache one slot holds: a ring of min(capacity, window)
    rows in every window layer, `capacity` rows in every full one."""
    n_win, n_full = _kinds(dims)
    return _row_bytes(dims, bytes_per_value) * (
        n_win * min(capacity, dims["window"]) + n_full * capacity)


def _rows_read(dims: dict, context):
    """Cache rows a query at `context` keys reads over all layers."""
    n_win, n_full = _kinds(dims)
    return n_full * context + n_win * np.minimum(context, dims["window"])


def gqa_decode_bytes(dims: dict, contexts, bytes_per_value: int = 2) -> float:
    """The least the `gqa_decode` kernel calls move for decoded tokens
    that see `contexts` keys each (their own among them), over all
    layers: every visible row's key and value read once (a window
    layer's are min(context, window)), and the token's queries in and
    outputs out."""
    contexts = np.asarray(contexts, np.float64)
    small = dims["L"] * 2 * dims["Hq"] * dims["d"] * bytes_per_value
    return float(np.sum(_rows_read(dims, contexts))
                 * _row_bytes(dims, bytes_per_value)
                 + contexts.size * small)


def decode_step_min_bytes(dims: dict, live_tokens: float,
                          bytes_per_value: int = 2) -> float:
    """The least a decode step moves, TOLD THE SUM OF ITS LIVE ROWS'
    CONTEXTS ALONE (`layer_metrics/decode_step_roofline.py` passes no
    batch): the held weights once at the stated compute precision (the
    embedding's rows not among them), `live_tokens` rows in the full
    layers and min(live_tokens, window) in the window layers, which is
    the least any batch of that sum needs (one row holding it all). A
    batch of 32 rows past the window reads 32 windows a window layer,
    so `decode_step_roofline` UNDER-reads in a cell of this family and
    can never read over 100 %; `gqa_decode_roofline` has the kernel's
    own count with every row's context (PERF.md section 7)."""
    return (matmul_param_count(dims) * bytes_per_value
            + float(_rows_read(dims, live_tokens))
            * _row_bytes(dims, bytes_per_value))


def _mean_keys(n: float, limit: float) -> float:
    """The mean over the queries t = 1..n of min(t, limit)."""
    if n <= limit:
        return (n + 1) / 2.0
    return (limit * (limit + 1) / 2.0 + (n - limit) * limit) / n


def forward_flops_per_token(dims: dict, keys_full: float,
                            keys_window: float) -> float:
    """Forward FLOPs of one token that attends to `keys_full` keys in a
    full layer and `keys_window` in a window layer: the projections and
    the gate, attention (a score and a weighted value a key a query
    head), the feed-forward block (dense, or the router with the
    selected experts a uniform router sends to this share, `top_k * held
    / E` of them, and the shared expert) and the head."""
    h, Hq, Hk, d = dims["hidden"], dims["Hq"], dims["Hk"], dims["d"]
    n_win, n_full = _kinds(dims)
    proj = 2 * (3 * h * Hq * d + 2 * h * Hk * d)
    attn = 4 * Hq * d * (n_full * keys_full + n_win * keys_window)
    dense = 2 * 3 * h * dims["F"]
    routed = dims["top_k"] * dims["held"] / dims["E"] + 1
    expert = 2 * h * dims["E"] + routed * 2 * 3 * h * dims["Fe"]
    nd = dims["n_dense"]
    return (dims["L"] * proj + attn + nd * dense + (dims["L"] - nd) * expert
            + 2 * h * dims["V"])


def prefill_flops(dims: dict, prompt_len: int) -> float:
    """Forward FLOPs of a whole prompt (a query's mean keys: (L + 1) / 2
    in a full layer, no more than the window in a window layer); the
    head runs on its last row only."""
    head = 2 * dims["hidden"] * dims["V"]
    body = forward_flops_per_token(
        dims, (prompt_len + 1) / 2.0,
        _mean_keys(prompt_len, dims["window"])) - head
    return prompt_len * body + head


def decode_flops(dims: dict, context: float) -> float:
    """Forward FLOPs of one generated token against `context` keys; a
    window layer's keys are min(context, window)."""
    return forward_flops_per_token(dims, context,
                                   min(context, dims["window"]))
