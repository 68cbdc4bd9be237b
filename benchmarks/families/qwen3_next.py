"""The `qwen3_next` family (`"model_type": "qwen3_next"`, Qwen3-Next): gated
delta-rule layers (a short convolution in front, a state a value head that
the delta rule corrects) and gated full-attention layers (grouped heads,
per-head RMS norms, rotary over a quarter of the head), three to one; every
layer an expert layer routed by a softmax over its top k, with a shared
expert behind a scalar gate; pre-norm; an untied head. Served as ONE
CHIP'S SHARE of an expert-parallel deployment. Behind the interface of
`families/__init__.py`.

Sizes from the configuration's own keys (the published `config.json`
names); the program's net through `models.hybrid_moe.hybrid_moe_lm`; the
seeded weights; the plain reference (`benchmarks/reference/qwen3_next.py`,
imported here alone); the counts. A serving family: the training entries
raise (see `_no_training`).

What a server keeps for a sequence is of two kinds: a delta-rule layer
holds a STATE and the convolution's last inputs, of fixed size a slot
(`state_bytes_per_slot`), a full layer a row a token
(`kv_bytes_per_token`). A decode step's bytes go with its live SLOTS in
the six delta-rule layers and with its rows' contexts in the two full
ones; a slot's bytes are `cache_bytes_per_slot(dims, capacity)`.

The benchmark makes the weights, a layer at a time on both sides:
`layer_weights(fold_in(key, i + 1), dims, full)` gives layer i the same
float32 numbers for the program (cast to its `param_dtype` as they are
made, one jitted call a layer whose key and layer number are arguments;
`A_log` and `dt_bias` stay float32) and for the reference (made, used
over every sampled request, dropped).

Seeded weights: every matrix N(0, gain^2 / fan_in), so a product keeps
its input's scale times the gain; norm gains 1 + N(0, 0.02). The
configuration's `seeded_weights` group gives what is not 1:
  qk_gain      the gain vector of a full layer's query norm: under that
               norm a larger `Wq` changes nothing, so this is what spreads
               the scores and lets attention pick rows
  memory_tokens  [shortest, longest]: the tokens over which a delta-rule
               head's state fades to 1/e, spread geometrically over the
               heads. A_log is 0 and dt_bias_j = softplus^-1(1 / memory_j),
               so g = -softplus(a + dt_bias) is near -1 / memory_j. The
               published init (A up to 16, dt_bias 1) forgets in a token,
               and then no reading could tell a stale or missing state
               from a sound one; trained decays spread so
  a_gain       `Wa`: how far a token moves its own decay
  router_gain  `Wr`: the spread of the router's logits
  head_gain    the output head: the logits' spread
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import fit_program_tree, param_shapes, seed_key
from reference import qwen3_next as ref

GAINS = ("embed_gain", "qk_gain", "a_gain", "router_gain", "head_gain")


def dims_of(config: dict) -> dict:
    """The sizes the makers, the reference and the counts need. `held`
    and `V` are what this chip holds (the keys `reduced` lists); the
    router's width `E` is the published count, which the configuration
    states beside the deployment. `d` is the size of a full layer's
    head, `dk` and `dv` of a delta-rule layer's."""
    share = config["share"]
    gains = config.get("seeded_weights", {})
    L = int(config["num_hidden_layers"])
    every = int(config["full_attention_interval"])
    if int(config["decoder_sparse_step"]) != 1 or config["mlp_only_layers"]:
        raise ValueError("the qwen3_next family makes every layer an expert "
                         "layer")
    Fe, Fs = (int(config["moe_intermediate_size"]),
              int(config["shared_expert_intermediate_size"]))
    if Fs != Fe:
        raise ValueError("the qwen3_next family holds one shared expert as "
                         "wide as a routed one")
    d = int(config["head_dim"])
    low, high = gains.get("memory_tokens", (1.0, 1.0))
    return {
        "hidden": int(config["hidden_size"]),
        "Hq": int(config["num_attention_heads"]),
        "Hk": int(config["num_key_value_heads"]), "d": d,
        "rotary": int(round(d * float(config["partial_rotary_factor"]))),
        "theta": float(config["rope_theta"]),
        "Hk_lin": int(config["linear_num_key_heads"]),
        "Hv_lin": int(config["linear_num_value_heads"]),
        "dk": int(config["linear_key_head_dim"]),
        "dv": int(config["linear_value_head_dim"]),
        "conv": int(config["linear_conv_kernel_dim"]),
        "L": L, "full": tuple((i + 1) % every == 0 for i in range(L)),
        "Fe": Fe, "E": int(share["router_experts"]),
        "held": int(config["num_experts"]),
        "first_expert": int(share["first_expert"]),
        "top_k": int(config["num_experts_per_tok"]),
        "eps": float(config["rms_norm_eps"]), "V": int(config["vocab_size"]),
        "memory_tokens": (float(low), float(high)),
        **{k: float(gains.get(k, 1.0)) for k in GAINS}}


# ------------------------------------------------------------------ weights

def _mat(key, shape, fan_in, gain=1.0):
    return (gain / fan_in ** 0.5) * jax.random.normal(key, shape, jnp.float32)


def _gain_vec(key, n, gain=1.0):
    return gain * (1.0 + 0.02 * jax.random.normal(key, (n,), jnp.float32))


def _channels(dims: dict) -> int:
    return 2 * dims["Hk_lin"] * dims["dk"] + dims["Hv_lin"] * dims["dv"]


def _decays(dims: dict):
    """(A_log, dt_bias) [Hv]: head j's state fades over memory_j tokens,
    memory spread geometrically from the shortest to the longest."""
    Hv = dims["Hv_lin"]
    low, high = dims["memory_tokens"]
    memory = low * (high / low) ** (jnp.arange(Hv, dtype=jnp.float32)
                                    / max(Hv - 1, 1))
    rate = 1.0 / memory
    return jnp.zeros((Hv,), jnp.float32), jnp.log(jnp.expm1(rate))


def layer_weights(key, dims: dict, full: bool) -> dict:
    """One layer's float32 weights under the reference's names."""
    h, E, Fe, held = dims["hidden"], dims["E"], dims["Fe"], dims["held"]
    k = jax.random.split(key, 24)
    w = {"n1": _gain_vec(k[0], h), "n2": _gain_vec(k[1], h),
         "Wr": _mat(k[2], (h, E), h, dims["router_gain"]),
         "We_gate": _mat(k[3], (held, h, Fe), h),
         "We_up": _mat(k[4], (held, h, Fe), h),
         "We_down": _mat(k[5], (held, Fe, h), Fe),
         "Ws_gate": _mat(k[6], (h, Fe), h), "Ws_up": _mat(k[7], (h, Fe), h),
         "Ws_down": _mat(k[8], (Fe, h), Fe), "Ws_g": _mat(k[9], (h, 1), h)}
    if full:
        Hq, Hk, d = dims["Hq"], dims["Hk"], dims["d"]
        w.update(Wq=_mat(k[10], (h, Hq * d), h), Wk=_mat(k[11], (h, Hk * d), h),
                 Wv=_mat(k[12], (h, Hk * d), h), Wg=_mat(k[13], (h, Hq * d), h),
                 q_norm=_gain_vec(k[14], d, dims["qk_gain"]),
                 k_norm=_gain_vec(k[15], d),
                 Wo=_mat(k[16], (Hq * d, h), Hq * d))
        return w
    Hv, dv, K = dims["Hv_lin"], dims["dv"], dims["conv"]
    A_log, dt_bias = _decays(dims)
    w.update(Wqkv=_mat(k[10], (h, _channels(dims)), h),
             Wz=_mat(k[11], (h, Hv * dv), h), Wb=_mat(k[12], (h, Hv), h),
             Wa=_mat(k[13], (h, Hv), h, dims["a_gain"]),
             conv=_mat(k[14], (K, _channels(dims)), K),
             A_log=A_log, dt_bias=dt_bias, norm=_gain_vec(k[15], dv),
             Wo=_mat(k[16], (Hv * dv, h), Hv * dv))
    return w


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
    W["layers"] = [layer_weights(_layer_key(key, i), dims, dims["full"][i])
                   for i in range(dims["L"])]
    return W


_ATTN = ("Wq", "Wk", "Wv", "Wg", "q_norm", "k_norm", "Wo")
_GDN = ("Wqkv", "Wz", "Wb", "Wa", "conv", "A_log", "dt_bias", "norm", "Wo")
_FF = ("We_gate", "We_up", "We_down", "Ws_gate", "Ws_up", "Ws_down", "Ws_g")


def program_layer(w: dict, i: int) -> dict:
    """One layer's weights under the names `hybrid_moe_lm` gives them
    (the expert layer calls its router `Wg`)."""
    p = f"blk{i}"
    mixer = (f"{p}_attn", _ATTN) if "Wq" in w else (f"{p}_gdn", _GDN)
    return {f"{p}_n1": {"gamma": w["n1"]}, f"{p}_n2": {"gamma": w["n2"]},
            mixer[0]: {n: w[n] for n in mixer[1]},
            f"{p}_ff": dict({n: w[n] for n in _FF}, Wg=w["Wr"])}


def program_globals(g: dict) -> dict:
    return {"embed": {"W": g["embed"]}, "norm_f": {"gamma": g["norm_f"]},
            "out": {"W": g["Wout"]}}


def layer_types(dims: dict) -> list:
    return ["full_attention" if f else "linear_attention"
            for f in dims["full"]]


def serving_net(config: dict, seed: int, dims: dict):
    """The program's net for `GenerationEngine`, holding the seeded weights
    in the configuration's `param_dtype` and no optimizer state."""
    from deeplearning4j_tpu.models.hybrid_moe import hybrid_moe_lm

    net = hybrid_moe_lm(
        dims["V"], dims["hidden"], layer_types(dims),
        n_k_heads=dims["Hk_lin"], n_v_heads=dims["Hv_lin"],
        k_head_dim=dims["dk"], v_head_dim=dims["dv"],
        conv_kernel=dims["conv"], n_heads=dims["Hq"], n_kv_heads=dims["Hk"],
        head_dim=dims["d"], rotary_dim=dims["rotary"],
        rope_theta=dims["theta"], n_experts=dims["E"], top_k=dims["top_k"],
        d_expert=dims["Fe"], first_expert=dims["first_expert"],
        n_held=dims["held"], eps=dims["eps"],
        state_dtype=str(config.get("state_dtype", "float32")),
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
    numbers (353 MB) exist beside the weights held."""
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
    for i, full in enumerate(dims["full"]):
        if full not in makers:
            # compiled under this layer's names; the layer number is an
            # argument, so the later layers of its kind reuse the program
            makers[full] = (i, jax.jit(lambda k, j, i=i, full=full: part(
                names_of(i), program_layer(
                    layer_weights(_layer_key(k, j), dims, full), i))))
        first, make = makers[full]
        params.update({n.replace(f"blk{first}_", f"blk{i}_", 1): x
                       for n, x in make(key, i).items()})
    net.params = params


def _no_training(*_a, **_k):
    raise NotImplementedError(
        "the qwen3_next family is served, not trained: in a training step "
        "the delta rule's chunked core is about 5 of 72 MFLOP a token a "
        "layer (6 %) and matrix products take the rest, while the state "
        "slot and a cache with two kinds of entry exist only on the "
        "serving path; a training cell waits for the chunked form's "
        "backward kernel (ROADMAP M6)")


training_net = first_moment_tree = program_sq_norms = _no_training
program_projections = seeded_program_tree = reference_readings = _no_training
train_flops_per_token = _no_training


# ---------------------------------------------------------------- `correct`

PIECE = 512             # rows the reference takes at a time
ROW_LENS = (4096, 8192, 16384, 36864)


def _row_len(n: int) -> int:
    """The positions a full layer's keys and values are held for in the
    reference: the sample's longest row, up to one of few lengths (then
    multiples of 4,096), so that few programs are compiled: on the chip a
    program with float32 products takes a quarter of a minute to
    compile."""
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
    A row goes through a layer `PIECE` rows at a time with the layer's
    carry as the rows before left it (`ref.block_rows`): one program a
    kind of layer, whatever the rows' lengths, and a full layer's scores
    of a 36,864-token row fit."""
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
    xs = [G["embed"][seq] for seq, *_ in rows]
    make = {full: jax.jit(lambda k, j, full=full: layer_weights(
        _layer_key(k, j), dims, full)) for full in (True, False)}

    def through(mm):
        """One layer over one row, a piece at a time; the layer's carry
        is donated from piece to piece."""
        piece = jax.jit(lambda x, carry, t0, w: ref.block_rows(
            x, carry, w, dims, mm, t0), donate_argnums=(1,))

        def run(x, w, full):
            carry = ref.empty_carry(full, S, dims)
            out = []
            for t0 in range(0, x.shape[0], PIECE):
                y, carry = piece(x[t0:t0 + PIECE], carry, jnp.int32(t0), w)
                out.append(y)
            return jnp.concatenate(out)
        return run

    run, run_low = through(ref.mm_highest), through(ref.mm_fp8)
    lows = list(xs) if lowprec else None
    for i, full in enumerate(dims["full"]):
        w = make[full](key, i)
        xs = [run(x, w, full) for x in xs]
        if lowprec:
            lows = [run_low(x, w, full) for x in lows]
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

def _gdn_params(dims: dict) -> int:
    h, Hv, dv = dims["hidden"], dims["Hv_lin"], dims["dv"]
    C = _channels(dims)
    return (h * C + h * Hv * dv + 2 * h * Hv + dims["conv"] * C + 2 * Hv
            + dv + Hv * dv * h)


def _attn_params(dims: dict) -> int:
    h, Hq, Hk, d = dims["hidden"], dims["Hq"], dims["Hk"], dims["d"]
    return 3 * h * Hq * d + 2 * h * Hk * d + 2 * d


def _moe_params(dims: dict) -> int:
    """The router, the held experts, the shared expert and its gate."""
    h = dims["hidden"]
    return h * dims["E"] + (dims["held"] + 1) * 3 * h * dims["Fe"] + h


def _layer_params(dims: dict, full: bool) -> int:
    mixer = _attn_params(dims) if full else _gdn_params(dims)
    return mixer + _moe_params(dims) + 2 * dims["hidden"]


def _kinds(dims: dict) -> tuple:
    """(delta-rule layers, full layers)."""
    n = sum(dims["full"])
    return dims["L"] - n, n


def matmul_param_count(dims: dict) -> int:
    """Parameters a decode step has to read: every layer's matrices and
    gains as held (EVERY held expert once) and the head. The embedding
    table is gathered by row, not read."""
    n_gdn, n_full = _kinds(dims)
    return (n_gdn * _layer_params(dims, False)
            + n_full * _layer_params(dims, True)
            + dims["hidden"] + dims["hidden"] * dims["V"])


def count_params(dims: dict) -> int:
    """Parameters as held: the share's experts, the vocabulary's slice."""
    return matmul_param_count(dims) + dims["V"] * dims["hidden"]


def _row_bytes(dims: dict, bytes_per_value: int = 2) -> int:
    """A token's key and value in one full layer."""
    return 2 * dims["Hk"] * dims["d"] * bytes_per_value


def kv_bytes_per_token(dims: dict, bytes_per_value: int = 2) -> int:
    """Bytes a cached token holds over all layers: rows in the full
    layers alone (a delta-rule layer holds a state a slot)."""
    return _kinds(dims)[1] * _row_bytes(dims, bytes_per_value)


def _state_bytes_per_layer(dims: dict, bytes_per_value: int = 2) -> int:
    """S [Hv, dk, dv] float32 and the convolution's last K - 1 inputs in
    the compute dtype."""
    return (4 * dims["Hv_lin"] * dims["dk"] * dims["dv"]
            + (dims["conv"] - 1) * _channels(dims) * bytes_per_value)


def state_bytes_per_slot(dims: dict, bytes_per_value: int = 2) -> int:
    """Bytes of state one slot HOLDS over all delta-rule layers."""
    return _kinds(dims)[0] * _state_bytes_per_layer(dims, bytes_per_value)


def cache_bytes_per_slot(dims: dict, capacity: int,
                         bytes_per_value: int = 2) -> int:
    """Bytes of cache one slot holds: the states, and `capacity` rows in
    every full layer."""
    return (state_bytes_per_slot(dims, bytes_per_value)
            + capacity * kv_bytes_per_token(dims, bytes_per_value))


def _rows_read(dims: dict, context):
    """Cache rows a query at `context` keys reads over all layers."""
    return _kinds(dims)[1] * context


def gqa_decode_bytes(dims: dict, contexts, bytes_per_value: int = 2) -> float:
    """The least the `gqa_decode` kernel calls move for decoded tokens
    that see `contexts` keys each (their own among them), over the full
    layers: every visible row's key and value read once, and the token's
    queries in and outputs out."""
    contexts = np.asarray(contexts, np.float64)
    small = _kinds(dims)[1] * 2 * dims["Hq"] * dims["d"] * bytes_per_value
    return float(np.sum(_rows_read(dims, contexts))
                 * _row_bytes(dims, bytes_per_value)
                 + contexts.size * small)


def gated_delta_decode_bytes(dims: dict, live_slots: float,
                             bytes_per_value: int = 2) -> float:
    """The least the `gated_delta_decode` kernel calls of one decode step
    move, over the delta-rule layers: every live slot's state (float32)
    read once and written once, its window read, and the step's q, k, v,
    z, a and b in the compute dtype."""
    Hk, Hv, dk, dv = dims["Hk_lin"], dims["Hv_lin"], dims["dk"], dims["dv"]
    state = 2 * 4 * Hv * dk * dv
    window = (dims["conv"] - 1) * _channels(dims) * bytes_per_value
    small = (2 * Hk * dk + 2 * Hv * dv + 2 * Hv) * bytes_per_value
    return live_slots * _kinds(dims)[0] * (state + window + small)


def decode_step_min_bytes(dims: dict, live_tokens: float,
                          bytes_per_value: int = 2) -> float:
    """The least a decode step moves, TOLD THE SUM OF ITS LIVE ROWS'
    CONTEXTS ALONE (`layer_metrics/decode_step_roofline.py` passes no
    batch): the held weights once at the stated compute precision (the
    embedding's rows not among them), ONE slot's states read and written
    (a step's state traffic goes with its live SLOTS, which this function
    is not told), and `live_tokens` rows in the full layers: the least
    any step with a live token needs. At 64 live slots the states alone
    are 64 times what this counts, so `decode_step_roofline` UNDER-reads
    in a cell of this family and can never read over 100 %;
    `gated_delta_decode_roofline` has the kernel's own count with the
    batch (PERF.md section 7)."""
    return (matmul_param_count(dims) * bytes_per_value
            + gated_delta_decode_bytes(dims, 1.0, bytes_per_value)
            + float(_rows_read(dims, live_tokens))
            * _row_bytes(dims, bytes_per_value))


def forward_flops_per_token(dims: dict, keys: float) -> float:
    """Forward FLOPs of one token that attends to `keys` keys in a full
    layer: the projections, the convolution and the delta rule's
    recurrence (the decay, S^T k, the rank-one correction and S^T q: 7
    operations a state entry a value head), attention (a score and a
    weighted value a key a query head), the router with the selected
    experts a uniform router sends to this share (`top_k * held / E` of
    them), the gated shared expert, and the head."""
    h, Hq, Hk, d = dims["hidden"], dims["Hq"], dims["Hk"], dims["d"]
    Hv, dk, dv = dims["Hv_lin"], dims["dk"], dims["dv"]
    n_gdn, n_full = _kinds(dims)
    C = _channels(dims)
    gdn = (2 * (h * C + h * Hv * dv + 2 * h * Hv + Hv * dv * h)
           + 2 * dims["conv"] * C + 7 * Hv * dk * dv)
    attn = 2 * (3 * h * Hq * d + 2 * h * Hk * d) + 4 * Hq * d * keys
    routed = dims["top_k"] * dims["held"] / dims["E"] + 1
    moe = 2 * h * dims["E"] + routed * 2 * 3 * h * dims["Fe"] + 2 * h
    return (n_gdn * gdn + n_full * attn + dims["L"] * moe
            + 2 * h * dims["V"])


def prefill_flops(dims: dict, prompt_len: int) -> float:
    """Forward FLOPs of a whole prompt (a query's mean keys (L + 1) / 2
    in a full layer); the head runs on its last row only."""
    head = 2 * dims["hidden"] * dims["V"]
    body = forward_flops_per_token(dims, (prompt_len + 1) / 2.0) - head
    return prompt_len * body + head


def decode_flops(dims: dict, context: float) -> float:
    """Forward FLOPs of one generated token against `context` keys."""
    return forward_flops_per_token(dims, context)
