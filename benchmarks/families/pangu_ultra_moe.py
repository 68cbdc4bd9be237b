"""The `pangu_ultra_moe` family (`"model_type": "pangu_ultra_moe"`):
latent attention with sandwich norms, leading dense layers, then routed
gated experts with a shared expert, served as ONE CHIP'S SHARE of an
expert-parallel deployment. Behind the interface of `families/__init__.py`.

Sizes from the configuration's own keys (the published `config.json`
names); the program's net through `models.latent_moe.latent_moe_lm`;
the seeded weights; the plain reference
(`benchmarks/reference/pangu_ultra_moe.py`, imported here alone); the
counts. A serving family: the training entries raise (see `_no_training`).

The benchmark makes the weights, a layer at a time on both sides:
`layer_weights(fold_in(key, i + 1), dims, dense)` gives layer i the same
float32 numbers for the program (cast to its `param_dtype` as they are
made, one jitted call a layer whose key and layer number are arguments)
and for the reference (made, used over every sampled request, dropped:
the float32 copy of this cut is 19.7 GB and fits no chip whole).

Seeded weights: every matrix N(0, gain^2 / fan_in), so a product keeps
its input's scale times the gain; norm gains 1 + N(0, 0.02). The
configuration's `seeded_weights` group gives the gains that are not 1:
  embed_gain   the token embedding's standard deviation (no fan: a row is
               looked up, not summed), so a token weighs as much in the
               stream as a sublayer's normalised output
  qk_gain      `Wqb`: scores grow by it, attention picks rows of the cache
  router_gain  `Wg`: the spread of the router's scores before the sigmoid
  head_gain    the output head: the logits' spread
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.weights import fit_program_tree, param_shapes, seed_key
from reference import pangu_ultra_moe as ref

GAINS = ("embed_gain", "qk_gain", "router_gain", "head_gain")
AT_PAD = 256            # served positions are read in multiples of this


def dims_of(config: dict) -> dict:
    """The sizes the makers, the reference and the counts need. `held`
    and `V` are what this chip holds (the keys `reduced` lists); the
    router's width `E` is the published count, which the configuration
    states beside the deployment."""
    share = config["share"]
    gains = config.get("seeded_weights", {})
    return {
        "d": int(config["hidden_size"]),
        "H": int(config["num_attention_heads"]),
        "L": int(config["num_hidden_layers"]),
        "n_dense": int(config["first_k_dense_replace"]),
        "q_rank": int(config["q_lora_rank"]),
        "kv_rank": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v": int(config["v_head_dim"]), "F": int(config["intermediate_size"]),
        "Fe": int(config["moe_intermediate_size"]),
        "E": int(share["router_experts"]),
        "held": int(config["n_routed_experts"]),
        "first_expert": int(share["first_expert"]),
        "top_k": int(config["num_experts_per_tok"]),
        "n_shared": int(config["n_shared_experts"]),
        "scaling": float(config["routed_scaling_factor"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]), "V": int(config["vocab_size"]),
        **{k: float(gains.get(k, 1.0)) for k in GAINS}}


# ------------------------------------------------------------------ weights

def _mat(key, shape, fan_in, gain=1.0):
    return (gain / fan_in ** 0.5) * jax.random.normal(key, shape, jnp.float32)


def _gain_vec(key, n):
    return 1.0 + 0.02 * jax.random.normal(key, (n,), jnp.float32)


def layer_weights(key, dims: dict, dense: bool) -> dict:
    """One layer's float32 weights under the reference's names."""
    d, H = dims["d"], dims["H"]
    qr, c, n, r, v = (dims["q_rank"], dims["kv_rank"], dims["nope"],
                      dims["rope"], dims["v"])
    k = jax.random.split(key, 20)
    w = {"n1": _gain_vec(k[0], d), "n2": _gain_vec(k[1], d),
         "n3": _gain_vec(k[2], d), "n4": _gain_vec(k[3], d),
         "Wqa": _mat(k[4], (d, qr), d), "q_norm": _gain_vec(k[5], qr),
         "Wqb": _mat(k[6], (qr, H * (n + r)), qr, dims["qk_gain"]),
         "Wkva": _mat(k[7], (d, c + r), d), "kv_norm": _gain_vec(k[8], c),
         "Wkvb": _mat(k[9], (c, H * (n + v)), c),
         "Wo": _mat(k[10], (H * v, d), H * v)}
    if dense:
        F = dims["F"]
        w.update(Wgate=_mat(k[11], (d, F), d), Wup=_mat(k[12], (d, F), d),
                 Wdown=_mat(k[13], (F, d), F))
        return w
    Fe, held, Fs = dims["Fe"], dims["held"], dims["n_shared"] * dims["Fe"]
    w.update(Wg=_mat(k[11], (d, dims["E"]), d, dims["router_gain"]),
             We_gate=_mat(k[12], (held, d, Fe), d),
             We_up=_mat(k[13], (held, d, Fe), d),
             We_down=_mat(k[14], (held, Fe, d), Fe),
             Ws_gate=_mat(k[15], (d, Fs), d), Ws_up=_mat(k[16], (d, Fs), d),
             Ws_down=_mat(k[17], (Fs, d), Fs))
    return w


def global_weights(key, dims: dict) -> dict:
    d, V = dims["d"], dims["V"]
    k = jax.random.split(key, 3)
    return {"embed": dims["embed_gain"] * jax.random.normal(
                k[0], (V, d), jnp.float32),
            "norm_f": _gain_vec(k[1], d),
            "Wout": _mat(k[2], (d, V), d, dims["head_gain"])}


def _layer_key(key, i):
    return jax.random.fold_in(key, i + 1)


def reference_weights(key, dims: dict) -> dict:
    """All of the reference's weights at once (the tests' sizes)."""
    W = global_weights(jax.random.fold_in(key, 0), dims)
    W["layers"] = [layer_weights(_layer_key(key, i), dims, i < dims["n_dense"])
                   for i in range(dims["L"])]
    return W


_ATTN = ("Wqa", "q_norm", "Wqb", "Wkva", "kv_norm", "Wkvb", "Wo")


def program_attention(w: dict, dims: dict) -> dict:
    """The attention weights as the program holds them: the two
    up-projections split by what their columns make (per head
    [q_nope | q_pe] and [k_nope | v] in the reference's single
    matrices), the query's two output-major."""
    H, n, r, v = dims["H"], dims["nope"], dims["rope"], dims["v"]
    Wqb = w["Wqb"].reshape(-1, H, n + r)
    Wkvb = w["Wkvb"].reshape(-1, H, n + v)
    return {"Wqa": w["Wqa"], "q_norm": w["q_norm"],
            "Wqb_nope": Wqb[..., :n].reshape(-1, H * n).T,
            "Wqb_rope": Wqb[..., n:].reshape(-1, H * r).T,
            "Wkva": w["Wkva"], "kv_norm": w["kv_norm"],
            "Wkvb_k": Wkvb[..., :n].reshape(-1, H * n),
            "Wkvb_v": Wkvb[..., n:].reshape(-1, H * v), "Wo": w["Wo"]}


def program_layer(w: dict, i: int, dims: dict) -> dict:
    """One layer's weights under the names `latent_moe_lm` gives them."""
    p = f"blk{i}"
    return {**{f"{p}_n{j}": {"gamma": w[f"n{j}"]} for j in (1, 2, 3, 4)},
            f"{p}_attn": program_attention(w, dims),
            f"{p}_ff": {n: x for n, x in w.items()
                        if n not in _ATTN and not n.startswith("n")}}


def program_globals(g: dict) -> dict:
    return {"embed": {"W": g["embed"]}, "norm_f": {"gamma": g["norm_f"]},
            "out": {"W": g["Wout"]}}


def serving_net(config: dict, seed: int, dims: dict):
    """The program's net for `GenerationEngine`, holding the seeded weights
    in the configuration's `param_dtype` and no optimizer state."""
    from deeplearning4j_tpu.models.latent_moe import latent_moe_lm

    net = latent_moe_lm(
        vocab_size=dims["V"], d_model=dims["d"], n_heads=dims["H"],
        n_layers=dims["L"], q_rank=dims["q_rank"], kv_rank=dims["kv_rank"],
        nope_dim=dims["nope"], rope_dim=dims["rope"], v_dim=dims["v"],
        d_ff=dims["F"], n_dense_layers=dims["n_dense"], n_experts=dims["E"],
        top_k=dims["top_k"], d_expert=dims["Fe"], n_shared=dims["n_shared"],
        first_expert=dims["first_expert"], n_held=dims["held"],
        routed_scaling=dims["scaling"], rope_theta=dims["theta"],
        eps=dims["eps"], seed=int(seed) & 0x7FFFFFFF,
        dtype=config["compute_dtype"], param_dtype=config["param_dtype"])
    like = param_shapes(net)
    give_weights(net, seed, dims, like)
    net.state = {n: {} for n in like}
    return net


def give_weights(net, seed: int, dims: dict, like=None) -> None:
    """Replace the net's parameters by the benchmark's seeded ones, in the
    tree `like` (shapes and dtypes; the net's own parameters by default).
    One jitted call a layer, so that no more than a layer's float32
    numbers exist beside the weights held."""
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
                    layer_weights(_layer_key(k, j), dims, dense), i, dims))))
        first, make = makers[dense]
        params.update({n.replace(f"blk{first}_", f"blk{i}_", 1): x
                       for n, x in make(key, i).items()})
    net.params = params


def _no_training(*_a, **_k):
    raise NotImplementedError(
        "the pangu_ultra_moe family is served, not trained: at 16 bytes a "
        "parameter the smallest cut the floors allow (one dense layer, four "
        "expert layers of 8 experts, an eighth of the vocabulary: 3.41 B "
        "parameters) takes 54.5 GB and fits no chip (ISSUE 31)")


training_net = first_moment_tree = program_sq_norms = _no_training
program_projections = seeded_program_tree = reference_readings = _no_training
train_flops_per_token = _no_training


# ---------------------------------------------------------------- `correct`

def _pad_len(n: int) -> int:
    """Reference rows are padded to few lengths, so that few programs
    are compiled (a layer's program takes longer to compile than to
    run): multiples of 256 up to 1,024, then 2,304 and 4,608."""
    if n <= 1024:
        return -(-n // 256) * 256
    return -(-n // 2304) * 2304


def served_gaps(sample, prompts, seed, dims, lowprec=False):
    """For each sampled request, the gap by which each served token's
    reference logit lies below the reference's best, as one array per
    request — or, for the control (`lowprec`), the gap of the token the
    float8 reference puts first at each of the same positions. The
    reference's weights are made, used over every request and dropped a
    layer at a time; the hidden states of all requests (and, for the
    control, their float8 twins) wait between the layers."""
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

    G = jax.jit(lambda k: global_weights(jax.random.fold_in(k, 0), dims))(key)
    xs = [G["embed"][seq] for seq, *_ in rows]
    lows = list(xs) if lowprec else None
    make = {dense: jax.jit(lambda k, j, dense=dense: layer_weights(
        _layer_key(k, j), dims, dense)) for dense in (True, False)}
    run = jax.jit(lambda x, w: ref.layer(x, w, dims))
    run_low = jax.jit(lambda x, w: ref.layer(x, w, dims, ref.mm_fp8))
    for i in range(dims["L"]):
        w = make[i < dims["n_dense"]](key, i)
        xs = [run(x, w) for x in xs]
        if lowprec:
            lows = [run_low(x, w) for x in lows]
        del w
    # the head's weights are arguments: closed over, a [d, V] constant
    # would be folded into each program (1.6 GB of executable, minutes)
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

def _attn_params(dims: dict) -> int:
    d, H, qr, c = dims["d"], dims["H"], dims["q_rank"], dims["kv_rank"]
    n, r, v = dims["nope"], dims["rope"], dims["v"]
    return (d * qr + qr + qr * H * (n + r) + d * (c + r) + c
            + c * H * (n + v) + H * v * d)


def _layer_params(dims: dict, dense: bool) -> int:
    d = dims["d"]
    experts = dims["held"] + dims["n_shared"]
    ff = (3 * d * dims["F"] if dense else
          d * dims["E"] + experts * 3 * d * dims["Fe"])
    return _attn_params(dims) + 4 * d + ff


def matmul_param_count(dims: dict) -> int:
    """Parameters a decode step has to read: every layer's matrices and
    gains as held (EVERY held expert once: a program that skips the
    experts no token of a small batch selected could move less) and the
    head. The embedding table is gathered by row, not read."""
    nd = dims["n_dense"]
    return (nd * _layer_params(dims, True)
            + (dims["L"] - nd) * _layer_params(dims, False)
            + dims["d"] + dims["d"] * dims["V"])


def count_params(dims: dict) -> int:
    """Parameters as held: the share's experts, the vocabulary's slice."""
    return matmul_param_count(dims) + dims["V"] * dims["d"]


def _attention_flops(dims: dict, context: float, own_rows: float) -> float:
    """One token's attention against `context` keys, the cheaper of the
    two forms: in the latent space (the key half of `Wkvb` folded into
    the query, the value half applied to the result: nothing rebuilt for
    the context), or expanded (`own_rows` rows of keys and values
    rebuilt from their latents a token: 1 where a whole prompt is
    prefilled and every row is rebuilt once, `context` for a lone
    token)."""
    H, c, n, r, v = (dims["H"], dims["kv_rank"], dims["nope"], dims["rope"],
                     dims["v"])
    latent = 2 * H * (n * c + c * v) + 2 * H * context * (c + r + c)
    expanded = own_rows * 2 * c * H * (n + v) + 2 * H * context * (n + r + v)
    return min(latent, expanded)


def forward_flops_per_token(dims: dict, context: float,
                            own_rows: float) -> float:
    """Forward FLOPs of one token that attends to `context` keys: the
    latent projections, attention, the feed-forward block (dense, or the
    router with the selected experts a uniform router sends to this
    share, `top_k * held / E` of them, and the shared expert) and the
    head."""
    d, H, qr, c = dims["d"], dims["H"], dims["q_rank"], dims["kv_rank"]
    n, r, v = dims["nope"], dims["rope"], dims["v"]
    proj = 2 * (d * qr + qr * H * (n + r) + d * (c + r) + H * v * d)
    attn = proj + _attention_flops(dims, context, own_rows)
    dense = 2 * 3 * d * dims["F"]
    routed = dims["top_k"] * dims["held"] / dims["E"] + dims["n_shared"]
    expert = 2 * d * dims["E"] + routed * 2 * 3 * d * dims["Fe"]
    nd = dims["n_dense"]
    return (dims["L"] * attn + nd * dense + (dims["L"] - nd) * expert
            + 2 * d * dims["V"])


def prefill_flops(dims: dict, prompt_len: int) -> float:
    """Forward FLOPs of a whole prompt (mean context (L + 1) / 2); the
    head runs on its last row only."""
    head = 2 * dims["d"] * dims["V"]
    body = forward_flops_per_token(dims, (prompt_len + 1) / 2.0, 1.0) - head
    return prompt_len * body + head


def decode_flops(dims: dict, context: float) -> float:
    """Forward FLOPs of one generated token against `context` keys."""
    return forward_flops_per_token(dims, context, context)


def kv_bytes_per_token(dims: dict, bytes_per_value: int = 2) -> int:
    """Bytes one cached token holds over all layers: one latent row each."""
    return dims["L"] * (dims["kv_rank"] + dims["rope"]) * bytes_per_value


def decode_step_min_bytes(dims: dict, live_tokens: float,
                          bytes_per_value: int = 2) -> float:
    """The least a decode step moves: the held weights once at the stated
    compute precision and every live latent row once."""
    return (matmul_param_count(dims) * bytes_per_value
            + live_tokens * kv_bytes_per_token(dims, bytes_per_value))
