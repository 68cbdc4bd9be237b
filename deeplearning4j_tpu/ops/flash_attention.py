"""Fused blockwise (flash) attention for TPU via Pallas.

Replaces the naive [B, H, T, T] score materialization in
`nn/layers/attention.dot_product_attention` for the causal/unmasked LM hot
path (the VERDICT-flagged MFU risk): scores never leave VMEM; the softmax
is computed online per key block (running max + running sum), and the
backward pass recomputes probabilities from the saved logsumexp instead of
storing them — O(T) HBM traffic instead of O(T^2).

Kernel layout (per (batch*head group, q-block) program):
  fwd:  loop key blocks -> online softmax into an f32 accumulator; saves
        out and logsumexp.
  bwd:  two kernels — dq (loop over key blocks per q block) and dk/dv
        (loop over q blocks per key block) — using the standard
        ds = p * (dp - delta) identity with delta = rowsum(do * o).

Per-program G-batching: at LM-scale shapes ([B*H, 512, 64]) one (bh,
q-block) program runs ~1us of MXU work against ~2us of fixed program
cost, so the grid is batched G batch-head slices per program (batched
dot_generals amortize the overhead; measured 263us -> 129us per fwd call
at B32 H4 T512 D64 on v5e). G is sized against the 16MB scoped-VMEM
budget and drops to 1 when key/value blocks stream (T > block cap).

Constraints: T divisible by the block size (128); [B, T] key padding
masks fold into the block predicates, so variable-length batches keep the
fused path; attention dropout runs IN-KERNEL via a counter-hash keep mask
keyed on GLOBAL (q, k) coordinates (r4, chunk-invariant since r6 — it
composes with the chunked long-context loop and ring hops); head_dim is
padded to the 128-lane tile internally by Mosaic when smaller, and
head_dim % 128 == 0 unlocks the packed-qkv no-relayout entry point
(flash_attention_qkv).

Block sizes, G-batching, and the long-context chunk tile resolve per
config through the tuning layer (ops/autotune.py, r8): a checked-in
TPU-only tuning table with the swept v5e defaults as the deterministic
fallback — graftlint G016 keeps re-frozen literals out of this file.

Falls back to interpret mode off-TPU so the unit tests exercise the same
kernel code on CPU (where the tuning table is inactive, so interpret
results are bit-identical to the defaults).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from deeplearning4j_tpu.ops import autotune
from deeplearning4j_tpu.ops.partition import rows_per_device
from deeplearning4j_tpu.util.compat import tpu_compiler_params

BLOCK = autotune.BLOCK
LANES = autotune.LANES  # lane width (used by fused_softmax_xent sizing)
NEG_INF = -1e30

# Block-size caps: resolved per config through the tuning layer
# (ops/autotune.py — table entry when tuned on TPU, else the swept v5e
# defaults). These names remain the DISPATCH envelope (supports_qkv's
# single-block bound); per-call grid sizing goes through
# autotune.flash_blocks.
BLOCK_Q_MAX = autotune.DEFAULT_BLOCK_Q_MAX
BLOCK_K_MAX = autotune.DEFAULT_BLOCK_K_MAX

# Scoped-VMEM budget a G-batched program's working set must fit. The
# kernels raise their scoped limit to 32MB (v5e has 128MB of VMEM; the
# default 16MB limit rejects G=8, measured the fastest fwd config).
_VMEM_LIMIT = 32 * 1024 * 1024
_VMEM_BUDGET = 26 * 1024 * 1024


# shared divisor search (moved to the tuning layer in r8; re-exported —
# fused_softmax_xent and the tests import it from here)
pick_block = autotune.pick_block


def _block_sizes(T, D, causal, dropout, masked, kernel):
    """(block_q, block_k) for one monolithic kernel call, resolved
    through the tuning layer: override > TPU table entry > the swept
    512-cap divisor search. Off-TPU the table is inactive, so interpret
    runs keep the deterministic defaults bit-identically."""
    return autotune.flash_blocks(T, D, causal=causal,
                                 dropout=bool(dropout), masked=masked,
                                 kernel=kernel)


def _resolve_g(kernel, BH, T, D, slice_bytes, causal, dropout, masked):
    """Per-program G-batching: a valid tuned G (divides BH) wins, else
    the VMEM-budget heuristic."""
    g = autotune.flash_g(kernel, BH, T, D, causal=causal,
                         dropout=bool(dropout), masked=masked)
    return g if g else _pick_g(BH, T, D, slice_bytes)


def _pick_g(BH: int, T: int, D: int, bytes_per_slice: int) -> int:
    """Largest divisor-of-BH group size whose working set fits the scoped
    VMEM budget. G>1 only pays off when per-program work is small (the
    block == T case); callers pass the per-slice byte estimate."""
    g = 1
    for cand in (2, 4, 8):
        if BH % cand == 0 and cand * bytes_per_slice <= _VMEM_BUDGET:
            g = cand
    return g


def _fwd_slice_bytes(T, D):
    # double-buffered q/k/v/o bf16 + scores AND p f32 + f32 acc/carries
    # (measured: the compiled G=8 fwd stack is ~2.6MB per slice at
    # T=512 D=64)
    return 2 * 4 * T * D * 2 + 2 * T * T * 4 + 2 * T * D * 4


def _bwd_slice_bytes(T, D):
    # double-buffered q/k/v/do/o/dq/dk/dv bf16 + s/p/dp f32 + ds bf16
    # (o streams in since the fused kernel computes delta = rowsum(do*o)
    # in-kernel, r4)
    return 2 * 8 * T * D * 2 + 3 * T * T * 4 + T * T * 2 + 3 * T * D * 4


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ------------------------------------------------- in-kernel dropout hash
#
# Attention dropout inside the kernels (VERDICT r3 #6) uses a COUNTER-BASED
# hash instead of pltpu.prng_*: the keep decision for score element
# (bh, gq, gk) is murmur3-fmix32 of its absolute coordinates + the step
# seed, so every kernel (fwd/dq/dkv/fused, any block size or G-batching)
# regenerates the identical mask, and CPU interpret mode matches TPU
# bit-for-bit (pltpu's PRNG is a zero-stub under interpret). ~10 u32 VPU
# ops per element — noise next to the exp.

def _fmix32(x):
    u = jnp.uint32
    x = x ^ (x >> u(16))
    x = x * u(0x85EBCA6B)
    x = x ^ (x >> u(13))
    x = x * u(0xC2B2AE35)
    x = x ^ (x >> u(16))
    return x


def _keep_mask(seed, bh0, stride, G, q0, k0, bq, bk, hash_t, rate):
    """[G, bq, bk] bool keep mask. seed: traced scalar; bh0: this
    program's first absolute batch*head row; stride: bh step between the
    G slices; q0/k0: GLOBAL row/col offsets of the block in the full
    sequence (may be traced); hash_t: the GLOBAL sequence length used as
    the row stride of the linearized hash coordinate. Keying on global
    (q0, k0, hash_t) makes the keep decision for logical element
    (bh, i, j) CHUNK-INVARIANT: a tile computed at origin (q0, k0) of a
    length-hash_t sequence drops exactly what the monolithic kernel at
    T=hash_t would — the chunked flash loop and the ring's per-hop
    kernels regenerate identical masks (r6).

    The per-ROW key gets the full murmur finalizer (cheap: G values);
    the per-ELEMENT mix is the shorter mul/xorshift/mul/xorshift tail —
    the full fmix32 per element cost ~0.09 ms per layer fwd+bwd pair at
    the r5 bench shapes (hash VPU ops, measured), and with a well-mixed
    key the shorter tail keeps the keep-fraction / row-balance /
    adjacency-decorrelation statistics (measured corr < 0.003;
    test_dropout_statistics_and_determinism)."""
    u = jnp.uint32
    bh = (jnp.asarray(bh0).astype(jnp.uint32)
          + jax.lax.broadcasted_iota(jnp.uint32, (G, 1, 1), 0) * u(stride))
    key = _fmix32(seed.astype(jnp.uint32) + bh * u(0x9E3779B9))
    gq = (jnp.asarray(q0).astype(jnp.uint32)
          + jax.lax.broadcasted_iota(jnp.uint32, (bq, bk), 0))
    gk = (jnp.asarray(k0).astype(jnp.uint32)
          + jax.lax.broadcasted_iota(jnp.uint32, (bq, bk), 1))
    h = key + (gq * u(hash_t) + gk)[None]
    h = h * u(0xCC9E2D51)
    h = h ^ (h >> u(15))
    h = h * u(0x1B873593)
    h = h ^ (h >> u(13))
    thr = u(min(int((1.0 - rate) * 4294967296.0), 4294967295))
    return h < thr


def dropout_keep_mask_host(seed, bh, T, rate):
    """NumPy twin of the kernels' keep mask for one bh slice: [T, T]
    bool. Test oracle — reconstructs the exact in-kernel mask."""
    def fmix(x):
        x = np.uint32(x).copy()
        x ^= x >> np.uint32(16)
        x = (x * np.uint32(0x85EBCA6B)).astype(np.uint32)
        x ^= x >> np.uint32(13)
        x = (x * np.uint32(0xC2B2AE35)).astype(np.uint32)
        x ^= x >> np.uint32(16)
        return x

    with np.errstate(over="ignore"):
        key = fmix(np.uint32(seed) + np.uint32(bh) * np.uint32(0x9E3779B9))
        gq, gk = np.meshgrid(np.arange(T, dtype=np.uint32),
                             np.arange(T, dtype=np.uint32), indexing="ij")
        h = (key + gq * np.uint32(T) + gk).astype(np.uint32)
        h = (h * np.uint32(0xCC9E2D51)).astype(np.uint32)
        h ^= h >> np.uint32(15)
        h = (h * np.uint32(0x1B873593)).astype(np.uint32)
        h ^= h >> np.uint32(13)
        thr = np.uint32(min(int((1.0 - rate) * 4294967296.0), 4294967295))
    return h < thr


def _step_seed(dropout_rng):
    """[1, 1] int32 per-step dropout key derived from a jax PRNG key."""
    return jax.random.randint(dropout_rng, (1, 1), 0, 2**31 - 1,
                              dtype=jnp.int32)


def _drop_ctx(seed, q_origin=0, k_origin=0):
    """[1, 3] int32 dropout-context operand the kernels read: (step seed,
    global q origin, global k origin) — the absolute sequence offsets of
    this kernel call's window. `seed` is the [1, 1] int32 step key;
    origins may be Python ints (the unrolled chunk loop) or traced
    scalars (ring hops, whose k origin depends on the hop index)."""
    orig = jnp.stack([jnp.asarray(q_origin, jnp.int32).reshape(()),
                      jnp.asarray(k_origin, jnp.int32).reshape(())])
    return jnp.concatenate([jnp.reshape(seed, (1, 1)), orig[None]], axis=1)


# ------------------------------------------------------------------ forward

def _attn_single_block(q, kb, vb, km, keep_scale_vals, sm_scale, causal,
                       seq_len):
    """Whole-sequence attention for one G-batched slice: q/kb/vb
    [G, T, D], km [G, T] key mask or None, keep_scale_vals [G, T, T]
    dropout keep*1/(1-r) or None. Returns (o [G, T, D] f32-normalized,
    lse [G, T]). Shared by the flat/packed kernels and the D=64
    head-pair kernel."""
    s = sm_scale * jax.lax.dot_general(
        q, kb, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                # [G, T, T]
    if causal:
        qpos = jax.lax.broadcasted_iota(jnp.int32, (seq_len, seq_len), 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (seq_len, seq_len), 1)
        s = jnp.where((qpos >= kpos)[None], s, NEG_INF)
    if km is not None:
        s = jnp.where(km[:, None, :] > 0, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    if km is not None:
        m = jnp.maximum(m, -1e20)  # all-masked rows underflow to 0
    # exp in the operand dtype (see the backward's note); l is
    # accumulated f32 so the normalizer and lse stay accurate
    p = jnp.exp((s - m[..., None]).astype(vb.dtype))
    l = jnp.maximum(jnp.sum(p.astype(jnp.float32), axis=-1), 1e-30)
    pd = p
    if keep_scale_vals is not None:
        # drop normalized-attention mass: l comes from the UNDROPPED
        # p (dense semantics: dropout applies to softmax output)
        pd = p * keep_scale_vals.astype(p.dtype)
    acc = jax.lax.dot_general(
        pd, vb, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    return acc / l[..., None], m + jnp.log(l)


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, sm_scale, causal, masked,
                block_q, block_k, seq_len, dropout=0.0, bh_stride=1,
                packed_heads=False, hash_t=None):
    rest = list(rest)
    kmask_ref = rest.pop(0) if masked else None
    seed_ref = rest.pop(0) if dropout else None
    o_ref, lse_ref = rest
    qi = pl.program_id(1)
    if dropout:
        G_ = q_ref.shape[0]
        # absolute batch*head row of this program's first slice. Flat
        # grid (BH//G, nq): rows are pid0*G..+G-1 (stride 1). Packed grid
        # (B//G, H): batch b = pid0*G + g at head pid1 -> row b*H + pid1
        # (stride H) — the SAME (b*H + h) numbering as the flat layout,
        # so the host oracle and the flat kernels reproduce the mask.
        bh0 = pl.program_id(0) * G_ * bh_stride
        if packed_heads:
            bh0 = bh0 + pl.program_id(1)
        # chunk-invariance (r6): the ctx operand carries the window's
        # global (q, k) origin; hash_t is the GLOBAL sequence length —
        # per-chunk/per-hop calls hash the same coordinates the
        # monolithic kernel would
        qo, ko = seed_ref[0, 1], seed_ref[0, 2]

        def keep_scale(q0, k0, bq, bk):
            keep = _keep_mask(seed_ref[0, 0], bh0, bh_stride, G_,
                              qo + q0, ko + k0, bq, bk,
                              hash_t or seq_len, dropout)
            return keep.astype(jnp.float32) * (1.0 / (1.0 - dropout))
    # keep the MXU operands in the input dtype (bf16 on TPU runs the MXU at
    # full rate; f32 operands decompose into multiple passes) and accumulate
    # in f32 via preferred_element_type; only softmax math is f32.
    q = q_ref[...]                                         # [G, bq, D]
    G = q.shape[0]
    nk = seq_len // block_k

    if nk == 1 and block_q == seq_len:
        # single-block specialization: a direct softmax (no running
        # max/sum carries, no fori_loop) — the loop+rescale structure
        # costs ~2x at these shapes even when it runs exactly once
        # (measured 286us vs 129us per call at [128,512,64] G=8 on v5e)
        kb = k_ref[...]
        vb = v_ref[...]
        km = kmask_ref[:, 0] if masked else None
        o, lse = _attn_single_block(
            q, kb, vb, km, keep_scale(0, 0, seq_len, seq_len)
            if dropout else None, sm_scale, causal, seq_len)
        o_ref[...] = o.astype(o_ref.dtype)
        # reshape-write keeps this branch layout-agnostic: the flat path
        # passes a [G, 1, T] lse block, the packed-qkv path [G, 1, 1, T]
        lse_ref[...] = lse.reshape(lse_ref.shape)
        return

    # last key block the q block's LAST row reaches — correct for any
    # block_q/block_k ratio (the pre-r8 `qi*bq//bk + 1` silently dropped
    # key blocks when a tuned block_q exceeded block_k; equal blocks,
    # the default, reduce to the same value bit-for-bit)
    hi = ((qi + 1) * block_q - 1) // block_k + 1 if causal else nk

    def body(j, carry):
        m, l, acc = carry
        kb = k_ref[:, pl.ds(j * block_k, block_k), :]      # [G, bk, D]
        vb = v_ref[:, pl.ds(j * block_k, block_k), :]
        s = sm_scale * jax.lax.dot_general(
            q, kb, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # [G, bq, bk]
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where((qpos >= kpos)[None], s, NEG_INF)
        if masked:
            # padding mask gates KEYS (dense-path semantics,
            # nn/layers/attention.dot_product_attention)
            km = kmask_ref[:, 0, pl.ds(j * block_k, block_k)]  # [G, bk]
            s = jnp.where(km[:, None, :] > 0, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        if masked:
            # an all-masked row (fully padded sequence) must not softmax
            # into uniform weights: floor the running max so exp(s - m)
            # underflows to 0 and the l-guard zeroes the output row
            m_new = jnp.maximum(m_new, -1e20)
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        pd = p
        if dropout:
            pd = p * keep_scale(qi * block_q, j * block_k,
                                block_q, block_k)
        acc = acc * alpha[..., None] + jax.lax.dot_general(
            pd.astype(vb.dtype), vb, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # [G, bq, D]
        return m_new, l, acc

    D = q_ref.shape[-1]
    m0 = jnp.full((G, block_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((G, block_q), jnp.float32)
    acc0 = jnp.zeros((G, block_q, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, acc0))
    l = jnp.maximum(l, 1e-30)
    o_ref[...] = (acc / l[..., None]).astype(o_ref.dtype)
    # per-row scalars ride a [G, 1, block_q] block (middle dim equals the
    # array dim, so the (8,128) tile rule is satisfied) — no 128-lane
    # broadcast, which cost ~0.6ms/step of pure HBM traffic in the r2
    # [BH, T, LANES] layout
    lse_ref[:, 0] = m + jnp.log(l)


def _flash_fwd(q, k, v, kmask, sm_scale, causal, dropout=0.0, seed=None,
               hash_t=None):
    BH, T, D = q.shape
    masked = kmask is not None
    block_q, block_k = _block_sizes(T, D, causal, dropout, masked,
                                    "flash_fwd")
    extra = int(T * T * 4) if dropout else 0  # f32 keep mask per slice
    G = (_resolve_g("flash_fwd", BH, T, D,
                    _fwd_slice_bytes(T, D) + extra, causal, dropout,
                    masked)
         if block_q == T and block_k == T else 1)
    grid = (BH // G, T // block_q)
    kern = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                             masked=masked, block_q=block_q,
                             block_k=block_k, seq_len=T, dropout=dropout,
                             hash_t=hash_t)
    in_specs = [
        pl.BlockSpec((G, block_q, D), lambda bh, qi: (bh, qi, 0)),
        pl.BlockSpec((G, T, D), lambda bh, qi: (bh, 0, 0)),
        pl.BlockSpec((G, T, D), lambda bh, qi: (bh, 0, 0)),
    ]
    args = [q, k, v]
    if masked:
        in_specs.append(pl.BlockSpec((G, 1, T), lambda bh, qi: (bh, 0, 0)))
        args.append(kmask)
    if dropout:
        in_specs.append(pl.BlockSpec((1, 3), lambda bh, qi: (0, 0)))
        args.append(seed)
    o, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((G, block_q, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((G, 1, block_q), lambda bh, qi: (bh, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, T), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(vmem_limit_bytes=_VMEM_LIMIT),
        name="flash_fwd",
        interpret=_use_interpret(),
    )(*args)
    return o, lse[:, 0, :]


# ----------------------------------------------------------------- backward

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               sm_scale, causal, masked, block_q, block_k, seq_len,
               dropout=0.0, bh_stride=1, hash_t=None):
    rest = list(rest)
    kmask_ref = rest.pop(0) if masked else None
    seed_ref = rest.pop(0) if dropout else None
    (dq_ref,) = rest
    qi = pl.program_id(1)
    # program_id must be read OUTSIDE the fori_loop body (interpret mode
    # cannot lower it from inside the loop's closed jaxpr)
    bh0 = pl.program_id(0) if dropout else None
    qo = seed_ref[0, 1] if dropout else None  # global window origin (r6)
    ko = seed_ref[0, 2] if dropout else None
    q = q_ref[...]                                          # [G, bq, D]
    do = do_ref[...]
    lse = lse_ref[:, 0]                                     # [G, bq]
    delta = delta_ref[:, 0]
    G = q.shape[0]
    nk = seq_len // block_k
    # see _fwd_kernel's bound note: reach the LAST row's key block
    hi = ((qi + 1) * block_q - 1) // block_k + 1 if causal else nk

    def body(j, dq):
        kb = k_ref[:, pl.ds(j * block_k, block_k), :]
        vb = v_ref[:, pl.ds(j * block_k, block_k), :]
        s = sm_scale * jax.lax.dot_general(
            q, kb, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where((qpos >= kpos)[None], s, NEG_INF)
        if masked:
            km = kmask_ref[:, 0, pl.ds(j * block_k, block_k)]
            s = jnp.where(km[:, None, :] > 0, s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                    # [G, bq, bk]
        dp = jax.lax.dot_general(do, vb, (((2,), (2,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        if dropout:
            ks = _keep_mask(seed_ref[0, 0], bh0 * G * bh_stride,
                            bh_stride, G, qo + qi * block_q,
                            ko + j * block_k, block_q, block_k,
                            hash_t or seq_len, dropout).astype(jnp.float32)
            dp = dp * (ks * (1.0 / (1.0 - dropout)))
        ds = (p * (dp - delta[..., None]) * sm_scale).astype(kb.dtype)
        return dq + jax.lax.dot_general(
            ds, kb, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    dq0 = jnp.zeros((G, block_q, q_ref.shape[-1]), jnp.float32)
    dq = jax.lax.fori_loop(0, hi, body, dq0)
    dq_ref[...] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                sm_scale, causal, masked, block_q, block_k, seq_len,
                dropout=0.0, bh_stride=1, hash_t=None):
    rest = list(rest)
    kmask_ref = rest.pop(0) if masked else None
    seed_ref = rest.pop(0) if dropout else None
    dk_ref, dv_ref = rest
    ki = pl.program_id(1)
    bh0 = pl.program_id(0) if dropout else None  # see _dq_kernel note
    qo = seed_ref[0, 1] if dropout else None
    ko = seed_ref[0, 2] if dropout else None
    kb = k_ref[...]                                         # [G, bk, D]
    vb = v_ref[...]
    G = kb.shape[0]
    nq = seq_len // block_q
    lo = (ki * block_k) // block_q if causal else 0

    def body(j, carry):
        dk, dv = carry
        qb = q_ref[:, pl.ds(j * block_q, block_q), :]
        dob = do_ref[:, pl.ds(j * block_q, block_q), :]
        lse = lse_ref[:, 0, pl.ds(j * block_q, block_q)]
        delta = delta_ref[:, 0, pl.ds(j * block_q, block_q)]
        s = sm_scale * jax.lax.dot_general(
            qb, kb, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        if causal:
            qpos = j * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where((qpos >= kpos)[None], s, NEG_INF)
        if masked:
            km = kmask_ref[:, 0]                           # [G, bk]
            s = jnp.where(km[:, None, :] > 0, s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                    # [G, bq, bk]
        pd = p
        dp = jax.lax.dot_general(dob, vb, (((2,), (2,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        if dropout:
            ks = _keep_mask(seed_ref[0, 0], bh0 * G * bh_stride,
                            bh_stride, G, qo + j * block_q,
                            ko + ki * block_k, block_q, block_k,
                            hash_t or seq_len, dropout).astype(jnp.float32)
            ks = ks * (1.0 / (1.0 - dropout))
            pd = p * ks
            dp = dp * ks
        dv = dv + jax.lax.dot_general(
            pd.astype(dob.dtype), dob, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # [G, bk, D]
        ds = (p * (dp - delta[..., None]) * sm_scale).astype(qb.dtype)
        dk = dk + jax.lax.dot_general(
            ds, qb, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        return dk, dv

    D = k_ref.shape[-1]
    dk0 = jnp.zeros((G, block_k, D), jnp.float32)
    dv0 = jnp.zeros((G, block_k, D), jnp.float32)
    dk, dv = jax.lax.fori_loop(lo, nq, body, (dk0, dv0))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _attn_single_block_bwd(qb, kb, vb, dob, ob, lse, km, ks, dlse,
                           sm_scale, causal, seq_len):
    """Whole-sequence fused backward for one G-batched slice: recomputes
    p from lse, returns (dq, dk, dv) [G, T, D] f32. km: [G, T] key mask
    or None; ks: [G, T, T] dropout keep*1/(1-r) or None; dlse: [G, T]
    ring-lse cotangent or None. Shared by the flat/packed fused-backward
    kernels and the D=64 head-pair kernel."""
    delta = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32),
                    axis=-1)                                # [G, T]
    if dlse is not None:
        delta = delta - dlse
    s = sm_scale * jax.lax.dot_general(
        qb, kb, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                 # [G, T, T]
    if causal:
        qpos = jax.lax.broadcasted_iota(jnp.int32, (seq_len, seq_len), 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (seq_len, seq_len), 1)
        s = jnp.where((qpos >= kpos)[None], s, NEG_INF)
    if km is not None:
        s = jnp.where(km[:, None, :] > 0, s, NEG_INF)
    # softmax math in the operand dtype: for bf16 models the exp and
    # the ds product run at 2x VPU rate with ~0.4% p error (f32 models
    # keep f32 — the parity tests exercise that path); the MXU consumes
    # p/ds as bf16 regardless
    cdt = kb.dtype
    p = jnp.exp((s - lse[..., None]).astype(cdt))
    pd = p
    dp = jax.lax.dot_general(dob, vb, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    if ks is not None:
        pd = p * ks.astype(cdt)
        dp = dp * ks
    ds = (p * ((dp - delta[..., None]) * sm_scale).astype(cdt))
    dq = jax.lax.dot_general(
        ds, kb, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    dv = jax.lax.dot_general(
        pd.astype(dob.dtype), dob, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    dk = jax.lax.dot_general(
        ds, qb, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    return dq, dk, dv


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                      *rest, sm_scale, causal, masked, seq_len,
                      dropout=0.0, bh_stride=1, has_dlse=False,
                      packed_heads=False, hash_t=None):
    """Single-pass backward for the block == T case (T <= BLOCK_K_MAX,
    i.e. _block_sizes gave both blocks the whole sequence): with Q, K and
    V all resident, one recompute of the probabilities feeds dq, dk AND
    dv — the two-kernel path recomputes them twice. Grid is (BH/G,); no
    cross-block accumulation exists at this size. delta = rowsum(do*o)
    is computed IN-KERNEL (r4: the host-side delta pass cost ~0.6 ms/step
    of reduce+relayout traffic on the packed layout); an optional dlse
    operand (ring-attention lse cotangent) subtracts from it."""
    rest = list(rest)
    kmask_ref = rest.pop(0) if masked else None
    seed_ref = rest.pop(0) if dropout else None
    dlse_ref = rest.pop(0) if has_dlse else None
    dq_ref, dk_ref, dv_ref = rest
    qb = q_ref[...]                                         # [G, T, D]
    dob = do_ref[...]
    kb = k_ref[...]
    vb = v_ref[...]
    G = qb.shape[0]
    lse = lse_ref[...].reshape(G, seq_len)                  # [G, T]
    ks = None
    if dropout:
        bh0 = pl.program_id(0) * G * bh_stride
        if packed_heads:
            bh0 = bh0 + pl.program_id(1)  # see _fwd_kernel's numbering
        ks = _keep_mask(seed_ref[0, 0], bh0, bh_stride, G,
                        seed_ref[0, 1], seed_ref[0, 2], seq_len,
                        seq_len, hash_t or seq_len,
                        dropout).astype(jnp.float32)
        ks = ks * (1.0 / (1.0 - dropout))
    dq, dk, dv = _attn_single_block_bwd(
        qb, kb, vb, dob, o_ref[...], lse,
        kmask_ref[:, 0] if masked else None, ks,
        dlse_ref[...].reshape(G, seq_len) if has_dlse else None,
        sm_scale, causal, seq_len)
    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _flash_bwd_fused(q, k, v, do, o, lse, kmask, sm_scale, causal,
                     dropout=0.0, seed=None, dlse=None, hash_t=None):
    BH, T, D = q.shape
    masked = kmask is not None
    extra = int(T * T * 4) if dropout else 0
    G = _resolve_g("flash_bwd", BH, T, D, _bwd_slice_bytes(T, D) + extra,
                   causal, dropout, masked)
    fullblock = pl.BlockSpec((G, T, D), lambda bh: (bh, 0, 0))
    lblock = pl.BlockSpec((G, 1, T), lambda bh: (bh, 0, 0))
    in_specs = [fullblock, fullblock, fullblock, fullblock, fullblock,
                lblock]
    args = [q, k, v, do, o, lse]
    if masked:
        in_specs.append(pl.BlockSpec((G, 1, T), lambda bh: (bh, 0, 0)))
        args.append(kmask)
    if dropout:
        in_specs.append(pl.BlockSpec((1, 3), lambda bh: (0, 0)))
        args.append(seed)
    if dlse is not None:
        in_specs.append(lblock)
        args.append(dlse)
    return pl.pallas_call(
        functools.partial(_bwd_fused_kernel, sm_scale=sm_scale,
                          causal=causal, masked=masked, seq_len=T,
                          dropout=dropout, has_dlse=dlse is not None,
                          hash_t=hash_t),
        grid=(BH // G,),
        in_specs=in_specs,
        out_specs=[fullblock, fullblock, fullblock],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, T, D), k.dtype),
            jax.ShapeDtypeStruct((BH, T, D), v.dtype),
        ],
        compiler_params=tpu_compiler_params(vmem_limit_bytes=_VMEM_LIMIT),
        name="flash_bwd_fused",
        interpret=_use_interpret(),
    )(*args)


def _flash_bwd_impl(q, k, v, o, lse, do, kmask, sm_scale, causal,
                    dlse=None, dropout=0.0, seed=None, hash_t=None):
    BH, T, D = q.shape
    masked = kmask is not None
    block_q, block_k = _block_sizes(T, D, causal, dropout, masked,
                                    "flash_bwd")

    if block_q == T and block_k == T:
        # whole Q/K/V per program: one fused kernel emits dq, dk and dv
        # from a single probability recompute; delta = rowsum(do*o) (and
        # the optional ring dlse fold) happens in-kernel
        return _flash_bwd_fused(
            q, k, v, do, o, lse[:, None, :], kmask, sm_scale, causal,
            dropout=dropout, seed=seed, hash_t=hash_t,
            dlse=None if dlse is None else
            dlse.astype(jnp.float32)[:, None, :])

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        # lse cotangent (ring-attention merge weights differentiate
        # through lse): d lse/d s = p, so ds = p*(dp - delta + dlse) —
        # folding -dlse into delta reuses the kernels unchanged
        delta = delta - dlse.astype(jnp.float32)
    # [BH, 1, T] layout for the per-row scalars (tile-legal via the
    # middle singleton dim) — replaces the r2 [BH, T, LANES] broadcast
    lse = lse[:, None, :]
    delta = delta[:, None, :]

    dq_specs = [
        pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0)),
        pl.BlockSpec((1, T, D), lambda bh, qi: (bh, 0, 0)),
        pl.BlockSpec((1, T, D), lambda bh, qi: (bh, 0, 0)),
        pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0)),
        pl.BlockSpec((1, 1, block_q), lambda bh, qi: (bh, 0, qi)),
        pl.BlockSpec((1, 1, block_q), lambda bh, qi: (bh, 0, qi)),
    ]
    dq_args = [q, k, v, do, lse, delta]
    if masked:
        dq_specs.append(pl.BlockSpec((1, 1, T), lambda bh, qi: (bh, 0, 0)))
        dq_args.append(kmask)
    if dropout:
        dq_specs.append(pl.BlockSpec((1, 3), lambda bh, qi: (0, 0)))
        dq_args.append(seed)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                          masked=masked, block_q=block_q, block_k=block_k,
                          seq_len=T, dropout=dropout, hash_t=hash_t),
        grid=(BH, T // block_q),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        name="flash_bwd_dq",
        interpret=_use_interpret(),
    )(*dq_args)

    dkv_specs = [
        pl.BlockSpec((1, T, D), lambda bh, ki: (bh, 0, 0)),
        pl.BlockSpec((1, block_k, D), lambda bh, ki: (bh, ki, 0)),
        pl.BlockSpec((1, block_k, D), lambda bh, ki: (bh, ki, 0)),
        pl.BlockSpec((1, T, D), lambda bh, ki: (bh, 0, 0)),
        pl.BlockSpec((1, 1, T), lambda bh, ki: (bh, 0, 0)),
        pl.BlockSpec((1, 1, T), lambda bh, ki: (bh, 0, 0)),
    ]
    dkv_args = [q, k, v, do, lse, delta]
    if masked:
        dkv_specs.append(pl.BlockSpec((1, 1, block_k),
                                      lambda bh, ki: (bh, 0, ki)))
        dkv_args.append(kmask)
    if dropout:
        dkv_specs.append(pl.BlockSpec((1, 3), lambda bh, ki: (0, 0)))
        dkv_args.append(seed)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          masked=masked, block_q=block_q, block_k=block_k,
                          seq_len=T, dropout=dropout, hash_t=hash_t),
        grid=(BH, T // block_k),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), k.dtype),
            jax.ShapeDtypeStruct((BH, T, D), v.dtype),
        ],
        name="flash_bwd_dkv",
        interpret=_use_interpret(),
    )(*dkv_args)
    return dq, dk, dv


# ---------------------------------------------------------------- public op

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_core(q, k, v, sm_scale, causal):
    o, _ = _flash_fwd(q, k, v, None, sm_scale, causal)
    return o


def _flash_core_fwd(q, k, v, sm_scale, causal):
    o, lse = _flash_fwd(q, k, v, None, sm_scale, causal)
    return o, (q, k, v, o, lse)


def _flash_core_bwd(sm_scale, causal, res, do):
    q, k, v, o, lse = res
    return _flash_bwd_impl(q, k, v, o, lse, do, None, sm_scale, causal)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_core_masked(q, k, v, kmask, sm_scale, causal):
    o, _ = _flash_fwd(q, k, v, kmask, sm_scale, causal)
    return o


def _flash_core_masked_fwd(q, k, v, kmask, sm_scale, causal):
    o, lse = _flash_fwd(q, k, v, kmask, sm_scale, causal)
    return o, (q, k, v, o, lse, kmask)


def _flash_core_masked_bwd(sm_scale, causal, res, do):
    q, k, v, o, lse, kmask = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, o, lse, do, kmask, sm_scale,
                                 causal)
    return dq, dk, dv, jnp.zeros_like(kmask)


_flash_core_masked.defvjp(_flash_core_masked_fwd, _flash_core_masked_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_core_drop(q, k, v, kmask, seed, sm_scale, causal, dropout):
    """Dropout-enabled core (kmask always an operand — pass ones when
    there is no padding mask; seed: [1,3] int32 dropout ctx from
    _drop_ctx)."""
    o, _ = _flash_fwd(q, k, v, kmask, sm_scale, causal, dropout=dropout,
                      seed=seed)
    return o


def _flash_core_drop_fwd(q, k, v, kmask, seed, sm_scale, causal, dropout):
    o, lse = _flash_fwd(q, k, v, kmask, sm_scale, causal, dropout=dropout,
                        seed=seed)
    return o, (q, k, v, o, lse, kmask, seed)


def _flash_core_drop_bwd(sm_scale, causal, dropout, res, do):
    q, k, v, o, lse, kmask, seed = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, o, lse, do, kmask, sm_scale,
                                 causal, dropout=dropout, seed=seed)
    # int primals take a float0 cotangent (zero_from_primal), not an int
    # zeros array — custom_vjp's cotangent check enforces this
    return (dq, dk, dv, jnp.zeros_like(kmask),
            jax.custom_derivatives.zero_from_primal(seed))


_flash_core_drop.defvjp(_flash_core_drop_fwd, _flash_core_drop_bwd)


# --------------------------------------------- (o, lse) core for ring hops

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_lse(q, k, v, sm_scale, causal):
    """Flat-layout flash returning BOTH outputs: (o [BH, T, D], lse
    [BH, T]) — differentiable in o AND lse. This is the per-hop primitive
    of ring attention (parallel/ring_attention.py): each hop's normalized
    block result merges with the carry via the two-way lse combine, whose
    weights need d(lse) to flow. Requires T % 128 == 0."""
    return _flash_fwd(q, k, v, None, sm_scale, causal)


def _fal_fwd(q, k, v, sm_scale, causal):
    o, lse = _flash_fwd(q, k, v, None, sm_scale, causal)
    return (o, lse), (q, k, v, o, lse)


def _fal_bwd(sm_scale, causal, res, cts):
    do, dlse = cts
    q, k, v, o, lse = res
    return _flash_bwd_impl(q, k, v, o, lse, do, None, sm_scale, causal,
                           dlse=dlse)


flash_attention_lse.defvjp(_fal_fwd, _fal_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def flash_attention_lse_masked(q, k, v, kmask, sm_scale, causal):
    """flash_attention_lse with a [BH, 1, T] key padding mask operand —
    the per-tile primitive of the MASKED chunk loop
    (chunked_flash_attention_lse): each kv tile sees its slice of the
    mask, so variable-length batches keep the fused path at chunked
    lengths. A fully-masked tile emits lse ~ -1e20 and a zero block,
    which the lse merge weights away."""
    o, lse = _flash_fwd(q, k, v, kmask, sm_scale, causal)
    return o, lse


def _falm_fwd(q, k, v, kmask, sm_scale, causal):
    o, lse = _flash_fwd(q, k, v, kmask, sm_scale, causal)
    return (o, lse), (q, k, v, kmask, o, lse)


def _falm_bwd(sm_scale, causal, res, cts):
    do, dlse = cts
    q, k, v, kmask, o, lse = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, o, lse, do, kmask, sm_scale,
                                 causal, dlse=dlse)
    return dq, dk, dv, jnp.zeros_like(kmask)


flash_attention_lse_masked.defvjp(_falm_fwd, _falm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def flash_attention_lse_drop(q, k, v, kmask, ctx, sm_scale, causal,
                             dropout, hash_t):
    """flash_attention_lse_masked + in-kernel dropout whose keep mask is
    keyed on GLOBAL coordinates (r6): ctx is the [1, 3] int32 dropout
    context from `_drop_ctx` (step seed, q origin, k origin) and hash_t
    the GLOBAL sequence length, so a tile at origin (q0, k0) drops
    exactly the elements the monolithic kernel at T=hash_t would. This
    is the per-tile primitive of the dropout-enabled chunk loop
    (chunked_flash_attention_lse) and the ring's dropout hops
    (parallel/ring_attention.py). kmask is always an operand — pass ones
    when unpadded."""
    return _flash_fwd(q, k, v, kmask, sm_scale, causal, dropout=dropout,
                      seed=ctx, hash_t=hash_t)


def _fald_fwd(q, k, v, kmask, ctx, sm_scale, causal, dropout, hash_t):
    o, lse = _flash_fwd(q, k, v, kmask, sm_scale, causal, dropout=dropout,
                        seed=ctx, hash_t=hash_t)
    return (o, lse), (q, k, v, kmask, ctx, o, lse)


def _fald_bwd(sm_scale, causal, dropout, hash_t, res, cts):
    do, dlse = cts
    q, k, v, kmask, ctx, o, lse = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, o, lse, do, kmask, sm_scale,
                                 causal, dlse=dlse, dropout=dropout,
                                 seed=ctx, hash_t=hash_t)
    return (dq, dk, dv, jnp.zeros_like(kmask),
            jax.custom_derivatives.zero_from_primal(ctx))


flash_attention_lse_drop.defvjp(_fald_fwd, _fald_bwd)


# ------------------------------------------------- packed-qkv (no relayout)
#
# When head_dim is a multiple of the 128-lane tile, the kernels can read
# Q/K/V STRAIGHT out of the [B, T, 3n] projection output — BlockSpecs
# slice the head's D-column window (legal: the last block dim is a
# multiple of 128) — and write the output back in [B, T, n]. The
# [B,T,H,D]->[B,H,T,D] head transposes and their backward twins (~0.9
# ms/step at the r4 bench shapes) disappear entirely. Scope: the
# single-block regime (T <= BLOCK_Q_MAX) that covers the T=512 flagship;
# longer sequences keep the flat [B*H, T, D] streaming path.


def _fwd_kernel_pair(q_ref, k_ref, v_ref, *rest, sm_scale, causal, masked,
                     seq_len, dropout=0.0, n_heads=2):
    """Head-PAIR forward for D=64: each program reads a 128-lane column
    slice spanning two adjacent heads (the lane-tile rule forbids 64-wide
    BlockSpecs) and runs the single-block attention per head. The two
    64-wide dots still fill only half the MXU contraction — inherent to
    D=64 — but the [B,T,H,D]<->[B,H,T,D] HBM relayouts and their backward
    twins disappear, and G-batching amortizes program cost."""
    rest = list(rest)
    kmask_ref = rest.pop(0) if masked else None
    seed_ref = rest.pop(0) if dropout else None
    o_ref, lse_ref = rest
    G = q_ref.shape[0]
    km = kmask_ref[:, 0] if masked else None
    os, lses = [], []
    for hh in range(2):
        sl = slice(hh * 64, hh * 64 + 64)
        keep = None
        if dropout:
            # absolute row b*H + (2*pid1 + hh) — the flat-layout numbering
            bh0 = (pl.program_id(0) * G * n_heads
                   + 2 * pl.program_id(1) + hh)
            keep = (_keep_mask(seed_ref[0, 0], bh0, n_heads, G, 0, 0,
                               seq_len, seq_len, seq_len, dropout)
                    .astype(jnp.float32) * (1.0 / (1.0 - dropout)))
        o, lse = _attn_single_block(
            q_ref[:, :, sl], k_ref[:, :, sl], v_ref[:, :, sl], km, keep,
            sm_scale, causal, seq_len)
        os.append(o)
        lses.append(lse)
    o_ref[...] = jnp.concatenate(os, axis=-1).astype(o_ref.dtype)
    lse_ref[...] = jnp.stack(lses, axis=1)[:, :, None, :]


def _bwd_kernel_pair(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *rest,
                     sm_scale, causal, masked, seq_len, dropout=0.0,
                     n_heads=2):
    rest = list(rest)
    kmask_ref = rest.pop(0) if masked else None
    seed_ref = rest.pop(0) if dropout else None
    dq_ref, dk_ref, dv_ref = rest
    G = q_ref.shape[0]
    km = kmask_ref[:, 0] if masked else None
    lse_pair = lse_ref[...]                                 # [G, 2, 1, T]
    dqs, dks, dvs = [], [], []
    for hh in range(2):
        sl = slice(hh * 64, hh * 64 + 64)
        ks = None
        if dropout:
            bh0 = (pl.program_id(0) * G * n_heads
                   + 2 * pl.program_id(1) + hh)
            ks = (_keep_mask(seed_ref[0, 0], bh0, n_heads, G, 0, 0,
                             seq_len, seq_len, seq_len, dropout)
                  .astype(jnp.float32) * (1.0 / (1.0 - dropout)))
        dq, dk, dv = _attn_single_block_bwd(
            q_ref[:, :, sl], k_ref[:, :, sl], v_ref[:, :, sl],
            do_ref[:, :, sl], o_ref[:, :, sl],
            lse_pair[:, hh, 0, :], km, ks, None, sm_scale, causal,
            seq_len)
        dqs.append(dq)
        dks.append(dk)
        dvs.append(dv)
    dq_ref[...] = jnp.concatenate(dqs, axis=-1).astype(dq_ref.dtype)
    dk_ref[...] = jnp.concatenate(dks, axis=-1).astype(dk_ref.dtype)
    dv_ref[...] = jnp.concatenate(dvs, axis=-1).astype(dv_ref.dtype)


def _flash_fwd_qkv_pair(qkv, H, kmask, sm_scale, causal, dropout=0.0,
                        seed=None):
    B, T, three_n = qkv.shape
    n = three_n // 3
    HP = H // 2
    masked = kmask is not None
    extra = int(T * T * 4) if dropout else 0
    G = _resolve_g("flash_fwd_qkv_pair", B, T, LANES,
                   _fwd_slice_bytes(T, LANES) + extra, causal, dropout,
                   masked)
    kern = functools.partial(_fwd_kernel_pair, sm_scale=sm_scale,
                             causal=causal, masked=masked, seq_len=T,
                             dropout=dropout, n_heads=H)
    # column blocks are 128 wide: q pair hp sits at block hp, k at
    # HP + hp, v at 2*HP + hp (block indices in 128-lane units)
    in_specs = [
        pl.BlockSpec((G, T, 128), lambda b, hp: (b, 0, hp)),
        pl.BlockSpec((G, T, 128), lambda b, hp: (b, 0, HP + hp)),
        pl.BlockSpec((G, T, 128), lambda b, hp: (b, 0, 2 * HP + hp)),
    ]
    args = [qkv, qkv, qkv]
    if masked:
        in_specs.append(pl.BlockSpec((G, 1, T), lambda b, hp: (b, 0, 0)))
        args.append(kmask)
    if dropout:
        in_specs.append(pl.BlockSpec((1, 3), lambda b, hp: (0, 0)))
        args.append(seed)
    o, lse = pl.pallas_call(
        kern,
        grid=(B // G, HP),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((G, T, 128), lambda b, hp: (b, 0, hp)),
            pl.BlockSpec((G, 2, 1, T), lambda b, hp: (b, hp, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, n), qkv.dtype),
            jax.ShapeDtypeStruct((B, H, 1, T), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(vmem_limit_bytes=_VMEM_LIMIT),
        name="flash_fwd_qkv_pair",
        interpret=_use_interpret(),
    )(*args)
    return o, lse


def _flash_bwd_qkv_pair(qkv, o, lse, do, H, kmask, sm_scale, causal,
                        dropout=0.0, seed=None):
    B, T, three_n = qkv.shape
    n = three_n // 3
    HP = H // 2
    masked = kmask is not None
    extra = int(T * T * 4) if dropout else 0
    G = _resolve_g("flash_bwd_qkv_pair", B, T, LANES,
                   _bwd_slice_bytes(T, LANES) + extra, causal, dropout,
                   masked)
    col = pl.BlockSpec((G, T, 128), lambda b, hp: (b, 0, hp))
    in_specs = [
        col,
        pl.BlockSpec((G, T, 128), lambda b, hp: (b, 0, HP + hp)),
        pl.BlockSpec((G, T, 128), lambda b, hp: (b, 0, 2 * HP + hp)),
        col,                                                # do pair
        col,                                                # o pair
        pl.BlockSpec((G, 2, 1, T), lambda b, hp: (b, hp, 0, 0)),
    ]
    args = [qkv, qkv, qkv, do, o, lse]
    if masked:
        in_specs.append(pl.BlockSpec((G, 1, T), lambda b, hp: (b, 0, 0)))
        args.append(kmask)
    if dropout:
        in_specs.append(pl.BlockSpec((1, 3), lambda b, hp: (0, 0)))
        args.append(seed)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel_pair, sm_scale=sm_scale,
                          causal=causal, masked=masked, seq_len=T,
                          dropout=dropout, n_heads=H),
        grid=(B // G, HP),
        in_specs=in_specs,
        out_specs=[col, col, col],
        out_shape=[jax.ShapeDtypeStruct((B, T, n), qkv.dtype)] * 3,
        compiler_params=tpu_compiler_params(vmem_limit_bytes=_VMEM_LIMIT),
        name="flash_bwd_qkv_pair",
        interpret=_use_interpret(),
    )(*args)
    return jnp.concatenate([dq, dk, dv], axis=-1)


def _flash_fwd_qkv(qkv, H, kmask, sm_scale, causal, dropout=0.0, seed=None):
    B, T, three_n = qkv.shape
    n = three_n // 3
    D = n // H
    if D == 64:
        return _flash_fwd_qkv_pair(qkv, H, kmask, sm_scale, causal,
                                   dropout=dropout, seed=seed)
    masked = kmask is not None
    extra = int(T * T * 4) if dropout else 0  # f32 keep mask per slice
    G = _resolve_g("flash_fwd_qkv", B, T, D,
                   _fwd_slice_bytes(T, D) + extra, causal, dropout,
                   masked)
    grid = (B // G, H)
    kern = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                             masked=masked, block_q=T, block_k=T, seq_len=T,
                             dropout=dropout, bh_stride=H, packed_heads=True)
    in_specs = [
        pl.BlockSpec((G, T, D), lambda b, h: (b, 0, h)),           # q cols
        pl.BlockSpec((G, T, D), lambda b, h: (b, 0, H + h)),       # k cols
        pl.BlockSpec((G, T, D), lambda b, h: (b, 0, 2 * H + h)),   # v cols
    ]
    args = [qkv, qkv, qkv]
    if masked:
        in_specs.append(pl.BlockSpec((G, 1, T), lambda b, h: (b, 0, 0)))
        args.append(kmask)
    if dropout:
        in_specs.append(pl.BlockSpec((1, 3), lambda b, h: (0, 0)))
        args.append(seed)
    o, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((G, T, D), lambda b, h: (b, 0, h)),
            pl.BlockSpec((G, 1, 1, T), lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, n), qkv.dtype),
            jax.ShapeDtypeStruct((B, H, 1, T), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(vmem_limit_bytes=_VMEM_LIMIT),
        name="flash_fwd_qkv",
        interpret=_use_interpret(),
    )(*args)
    return o, lse


def _flash_bwd_qkv(qkv, o, lse, do, H, kmask, sm_scale, causal,
                   dropout=0.0, seed=None):
    B, T, three_n = qkv.shape
    n = three_n // 3
    D = n // H
    if D == 64:
        return _flash_bwd_qkv_pair(qkv, o, lse, do, H, kmask, sm_scale,
                                   causal, dropout=dropout, seed=seed)
    masked = kmask is not None
    extra = int(T * T * 4) if dropout else 0
    G = _resolve_g("flash_bwd_qkv", B, T, D,
                   _bwd_slice_bytes(T, D) + extra, causal, dropout,
                   masked)
    rows = pl.BlockSpec((G, 1, 1, T), lambda b, h: (b, h, 0, 0))
    col = pl.BlockSpec((G, T, D), lambda b, h: (b, 0, h))
    in_specs = [
        col,                                                       # q
        pl.BlockSpec((G, T, D), lambda b, h: (b, 0, H + h)),       # k
        pl.BlockSpec((G, T, D), lambda b, h: (b, 0, 2 * H + h)),   # v
        col,                                                       # do cols
        col,                                                       # o cols
        rows,
    ]
    # delta = rowsum(do*o) happens in-kernel from the o column slice —
    # the host-side per-head reduce + [B,T,H]->[B,H,1,T] relayout cost
    # ~0.6 ms/step at the r4 flagship shapes
    args = [qkv, qkv, qkv, do, o, lse]
    if masked:
        in_specs.append(pl.BlockSpec((G, 1, T), lambda b, h: (b, 0, 0)))
        args.append(kmask)
    if dropout:
        in_specs.append(pl.BlockSpec((1, 3), lambda b, h: (0, 0)))
        args.append(seed)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, sm_scale=sm_scale,
                          causal=causal, masked=masked, seq_len=T,
                          dropout=dropout, bh_stride=H, packed_heads=True),
        grid=(B // G, H),
        in_specs=in_specs,
        out_specs=[col, col, col],
        out_shape=[jax.ShapeDtypeStruct((B, T, n), qkv.dtype)] * 3,
        compiler_params=tpu_compiler_params(vmem_limit_bytes=_VMEM_LIMIT),
        name="flash_bwd_qkv",
        interpret=_use_interpret(),
    )(*args)
    return jnp.concatenate([dq, dk, dv], axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _flash_qkv_core(qkv, H, sm_scale, causal):
    o, _ = _flash_fwd_qkv(qkv, H, None, sm_scale, causal)
    return o


def _flash_qkv_core_fwd(qkv, H, sm_scale, causal):
    o, lse = _flash_fwd_qkv(qkv, H, None, sm_scale, causal)
    return o, (qkv, o, lse)


def _flash_qkv_core_bwd(H, sm_scale, causal, res, do):
    qkv, o, lse = res
    return (_flash_bwd_qkv(qkv, o, lse, do, H, None, sm_scale, causal),)


_flash_qkv_core.defvjp(_flash_qkv_core_fwd, _flash_qkv_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _flash_qkv_core_masked(qkv, kmask, H, sm_scale, causal):
    o, _ = _flash_fwd_qkv(qkv, H, kmask, sm_scale, causal)
    return o


def _flash_qkv_core_masked_fwd(qkv, kmask, H, sm_scale, causal):
    o, lse = _flash_fwd_qkv(qkv, H, kmask, sm_scale, causal)
    return o, (qkv, o, lse, kmask)


def _flash_qkv_core_masked_bwd(H, sm_scale, causal, res, do):
    qkv, o, lse, kmask = res
    dqkv = _flash_bwd_qkv(qkv, o, lse, do, H, kmask, sm_scale, causal)
    return dqkv, jnp.zeros_like(kmask)


_flash_qkv_core_masked.defvjp(_flash_qkv_core_masked_fwd,
                              _flash_qkv_core_masked_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_qkv_core_drop(qkv, kmask, seed, H, sm_scale, causal, dropout):
    """Dropout-enabled packed core (r5 — VERDICT r4 #2: the dropout
    config no longer falls off the no-relayout path). kmask is always an
    operand (ones when unpadded); seed: [1,3] int32 dropout ctx."""
    o, _ = _flash_fwd_qkv(qkv, H, kmask, sm_scale, causal,
                          dropout=dropout, seed=seed)
    return o


def _flash_qkv_core_drop_fwd(qkv, kmask, seed, H, sm_scale, causal,
                             dropout):
    o, lse = _flash_fwd_qkv(qkv, H, kmask, sm_scale, causal,
                            dropout=dropout, seed=seed)
    return o, (qkv, o, lse, kmask, seed)


def _flash_qkv_core_drop_bwd(H, sm_scale, causal, dropout, res, do):
    qkv, o, lse, kmask, seed = res
    dqkv = _flash_bwd_qkv(qkv, o, lse, do, H, kmask, sm_scale, causal,
                          dropout=dropout, seed=seed)
    return (dqkv, jnp.zeros_like(kmask),
            jax.custom_derivatives.zero_from_primal(seed))


_flash_qkv_core_drop.defvjp(_flash_qkv_core_drop_fwd,
                            _flash_qkv_core_drop_bwd)


def supports_qkv(B, T, n, H, *, dropout) -> bool:
    """Envelope of the packed no-relayout path: head_dim a lane-tile
    multiple — or exactly 64 with an even head count (head-PAIR column
    slices, r5 — the config users actually run, VERDICT r4 #5) — single-
    block sequence length, head count dividing a G-batchable batch.
    Attention dropout runs in-kernel on this path too (r5)."""
    if n % H:
        return False
    D = n // H
    dim_ok = D % 128 == 0 or (D == 64 and H % 2 == 0)
    return dim_ok and MIN_FLASH_SEQ <= T <= BLOCK_Q_MAX and T % BLOCK == 0


def flash_attention_qkv(qkv, n_heads, *, causal=True, sm_scale=None,
                        mask=None, dropout=0.0, dropout_rng=None):
    """Packed-projection attention: qkv [B, T, 3n] (the x @ Wqkv output,
    q|k|v each n = H*D wide) -> out [B, T, n], never materializing a
    [B, H, T, D] relayout. Check `supports_qkv` first. dropout masks are
    generated in-kernel from the same (b*H + h) counter-hash stream as
    the flat layout, so both paths drop identical score elements for a
    given rng."""
    B, T, three_n = qkv.shape
    n = three_n // 3
    D = n // n_heads
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(D))
    if dropout:
        if dropout_rng is None:
            raise ValueError("dropout > 0 requires dropout_rng")
        ctx = _drop_ctx(_step_seed(dropout_rng))
        kmask = (jnp.ones((B, 1, T), jnp.float32) if mask is None
                 else jnp.asarray(mask, jnp.float32)[:, None, :])
        return _flash_qkv_core_drop(qkv, kmask, ctx, n_heads, sm_scale,
                                    bool(causal), float(dropout))
    if mask is None:
        return rows_per_device(
            lambda x: _flash_qkv_core(x, n_heads, sm_scale, bool(causal)),
            (qkv,))
    kmask = jnp.asarray(mask, jnp.float32)[:, None, :]      # [B, 1, T]
    return rows_per_device(
        lambda x, km: _flash_qkv_core_masked(x, km, n_heads, sm_scale,
                                             bool(causal)),
        (qkv, kmask))


# Below this sequence length XLA's fused dense attention wins on TPU (the
# kernel's fixed per-program cost dominates once [T,T] traffic is small).
# Measured on v5e with bf16 MXU operands + 512-blocks: flash fwd+bwd beats
# dense 0.84ms vs 1.58ms at T=512 (B32 H4 D64) and 1.3ms vs 14.9ms at
# T=4096, so the crossover sits at or below 512.
MIN_FLASH_SEQ = 512

# Largest T the monolithic long-T kernels are performance-proven at: the
# dq/dkv backward streams full-T K/V (resp. Q/dO) blocks through VMEM
# (double-buffered bf16 [T, D] pairs), which fits at 8192 (0.69 MFU
# in-model) and busts VMEM at 15360+ with 512-blocks. Beyond this,
# attention prefers chunked_flash_attention — same kernels over
# chunk-length tiles.
MAX_FLASH_T = 8192

# Hard compile ceiling of the monolithic backward (measured at D=128,
# 512-blocks: 14336 compiles, 15360 fails). T in (MAX_FLASH_T,
# MONOLITHIC_COMPILE_MAX] that the tile loop cannot take — padding
# masks, attention dropout, or a non-tileable length — falls back to the
# monolithic kernels (the pre-r5 behavior for every such config) instead
# of raising.
MONOLITHIC_COMPILE_MAX = 14336


def supports(q_shape, *, causal, dropout, mask) -> bool:
    """Whether the MONOLITHIC fused kernel handles this case. q_shape is
    [B, H, T, D] — T at index 2. Padding masks fold into the kernels'
    block predicates (VERDICT r2 #3); attention dropout runs IN-KERNEL
    via the counter-hash keep mask (VERDICT r3 #6), so dropout configs
    keep the fused path too. T above MAX_FLASH_T: see supports_chunked."""
    T = q_shape[2]
    return MIN_FLASH_SEQ <= T <= MAX_FLASH_T and T % BLOCK == 0


# What must be bounded is the TRACE SIZE of the chunk loop — the pallas
# calls one jaxpr accumulates — and since r8 that depends on causality
# STRUCTURALLY, not just in pair count: causal rows mix full and
# diagonal-causal tiles, so the (q_i, kv_j) pairs stay Python-unrolled
# and the budget is the PAIR count (136 = the causal 16-chunk budget the
# seq-131072 config measured at 0.70 MFU with tolerable compile time).
# Non-causal rows are UNIFORM (every tile full), so their kv loop is a
# lax.scan — ONE traced kernel per q chunk — and the budget is the CHUNK
# count. ADVICE r5 #1's n^2 unroll (16 non-causal chunks = 256 forward
# calls + VJPs) is structurally gone; an uncapped awkward T (e.g.
# 25088 -> 49 chunks of 512) would still unroll 1200+ causal pallas
# calls, hence the caps.
MAX_CHUNKS = 16
MAX_CHUNK_PAIRS = MAX_CHUNKS * (MAX_CHUNKS + 1) // 2  # 136


def chunk_pairs(n: int, causal: bool) -> int:
    """RUNTIME tile-pair kernel launches of an n-chunk loop. For causal
    this is also the trace size; non-causal pairs run under a scan (see
    traced_tile_calls)."""
    return n * (n + 1) // 2 if causal else n * n


def traced_tile_calls(n: int, causal: bool) -> int:
    """Pallas calls the n-chunk loop traces into ONE jaxpr — the
    compile-size unit the budgets bound. Causal unrolls every pair;
    non-causal scans the kv tiles, so one traced kernel per q chunk."""
    return chunk_pairs(n, True) if causal else n


def _fits_unroll(n: int, causal: bool) -> bool:
    if causal:
        return chunk_pairs(n, causal) <= MAX_CHUNK_PAIRS
    return n <= MAX_CHUNKS


def max_chunks(causal: bool) -> int:
    """Largest chunk count whose trace size fits the budget: 16 both
    ways since r8 (the causal 16-chunk unroll is the original 136-pair
    budget; non-causal kv loops scan instead of unrolling)."""
    n = MAX_CHUNKS
    while n > 1 and not _fits_unroll(n, causal):
        n -= 1
    return n


# Kernel-proven tile lengths, largest first — owned by the tuning layer
# (autotune.CHUNK_TILES), re-exported as the envelope quoted in error
# messages (chunked_unsupported_reason, the ring hop dispatch). The
# usable cap shrinks with head_dim (autotune.max_tile_for_dim): the
# backward streams full-tile [T, D] K/V pairs, so D=256 proves tiles to
# 4096, D=512 to 2048 — the D>128 long-T tier ADVICE r5 #2 asked for.
CHUNK_TILES = autotune.CHUNK_TILES


def pick_chunk(T: int, causal: bool = True, head_dim: int | None = None) \
        -> int:
    """Largest kernel-proven tile length (within the D-aware bound when
    `head_dim` is given) that divides T into 2+ chunks fitting the trace
    budget (0: T not chunkable). Tiles are tried largest-first, so the
    dispatch prefers FEWER, larger chunks."""
    cap = autotune.max_tile_for_dim(head_dim)
    for c in CHUNK_TILES:
        if c > cap:
            continue
        if T % c == 0 and 2 <= T // c and _fits_unroll(T // c, causal):
            return c
    return 0


def _tiles_str(head_dim=None) -> str:
    cap = autotune.max_tile_for_dim(head_dim)
    return "/".join(str(c) for c in reversed(CHUNK_TILES) if c <= cap)


def supports_chunked(q_shape, *, causal, dropout, mask) -> bool:
    """Envelope of the blockwise long-context path: T beyond the
    monolithic kernels, divisible into kernel-proven tiles (D-aware —
    head dims past 128 use shorter tiles, r8) whose trace size fits the
    budget (causality-aware — causal pairs unroll, non-causal kv tiles
    scan). Padding masks ride the loop (each kv tile sees its mask
    slice — flash_attention_lse_masked); attention dropout rides it too
    (r6: the keep mask hashes GLOBAL (q, k) coordinates through
    flash_attention_lse_drop, so every tile regenerates exactly the
    monolithic kernel's mask)."""
    T, D = q_shape[2], q_shape[3]
    return T > MAX_FLASH_T and pick_chunk(T, causal, head_dim=D) > 0


def supports_monolithic_fallback(q_shape, *, causal, dropout, mask) -> bool:
    """T in (MAX_FLASH_T, MONOLITHIC_COMPILE_MAX] the tile loop cannot
    take (non-tileable lengths) still compiles on the monolithic kernels
    with every in-kernel feature — the pre-r5 dispatch for those shapes,
    kept so they don't regress to an error. Gated at D <= 128: the
    compile ceiling was measured there, and the backward's VMEM working
    set scales with D — D > 128 long-T routes through the chunked tier's
    D-aware tiles instead (supports_chunked, r8)."""
    T, D = q_shape[2], q_shape[3]
    return (MAX_FLASH_T < T <= MONOLITHIC_COMPILE_MAX and T % BLOCK == 0
            and D <= 128)


def servable_seq(T: int, head_dim: int, *, causal: bool = True,
                 dropout: bool = False, mask: bool = True) -> bool:
    """Whether a [*, H, T, head_dim] attention shape has SOME compilable
    path — the envelope the serving bucket lattice validates against
    (serving/buckets.py) before warmup freezes its shapes. T at or below
    MAX_FLASH_T always compiles (fused kernels where the shape
    qualifies, the dense einsum fallback otherwise); beyond it the shape
    must fit the chunked tier or the monolithic-fallback tier, else the
    attention layer raises chunked_unsupported_reason mid-traffic."""
    if T <= MAX_FLASH_T:
        return True
    shape = (1, 1, T, head_dim)
    return (supports_chunked(shape, causal=causal, dropout=dropout,
                             mask=mask)
            or supports_monolithic_fallback(shape, causal=causal,
                                            dropout=dropout, mask=mask))


def chunked_unsupported_reason(T, *, dropout, mask, causal=True,
                               head_dim=None) -> str:
    """Why a long-T shape has no fused path — raised by the attention
    layer so long-context misconfigurations fail with instructions
    instead of a dense-path device OOM. Dropout is NOT an exclusion
    anymore (r6) and neither are non-causal lengths up to 16 tiles (r8:
    scanned kv loops) nor head dims past 128 (r8: D-aware tile bound);
    what remains is tile-divisibility under those bounds, plus the
    D <= 128 gate on the monolithic fallback tier."""
    nmax = max_chunks(causal)
    cap = autotune.max_tile_for_dim(head_dim)
    msg = (f"attention at T={T} cannot be tiled: the chunked flash path "
           f"needs T divisible into 2-{nmax} "
           f"{'causal' if causal else 'non-causal'} tiles of "
           f"{_tiles_str(head_dim)}")
    if head_dim and head_dim > 128:
        msg += (f" (head_dim={head_dim} caps tiles at {cap}: the "
                "backward's VMEM working set scales with head_dim)")
    msg += (f" (causal trace budget {MAX_CHUNK_PAIRS} unrolled tile "
            f"pairs, non-causal kv tiles scan at {MAX_CHUNKS} chunks "
            f"max; max single-chip T here = {nmax * cap})")
    if T <= MONOLITHIC_COMPILE_MAX:
        msg += (f", and the monolithic fallback (T <= "
                f"{MONOLITHIC_COMPILE_MAX}) requires head_dim <= 128"
                + (f" — got head_dim={head_dim}" if head_dim else ""))
    return msg + (" — pad T to a tile-divisible length or shard T over a "
                  "'seq' mesh axis (ring attention)")


def lse_combine(o, lse, o_hop, lse_hop):
    """Two-way logsumexp merge of normalized attention partials: carry
    (o [.., T, D] f32, lse [.., T]) absorbs a hop's (o_hop, lse_hop).
    The single numerics home for BOTH the serial chunk loop
    (chunked_flash_attention) and the cross-device ring
    (parallel/ring_attention.py) — f32 accumulate, 1e-30 denom floor."""
    m = jnp.maximum(lse, lse_hop)
    a, b = jnp.exp(lse - m), jnp.exp(lse_hop - m)
    denom = jnp.maximum(a + b, 1e-30)
    o = (o * a[..., None]
         + o_hop.astype(jnp.float32) * b[..., None]) / denom[..., None]
    return o, m + jnp.log(denom)


def chunked_flash_attention(q, k, v, *, causal=True, sm_scale=None,
                            mask=None, chunk=None, dropout=0.0,
                            dropout_rng=None):
    """Single-chip long-context attention: Q/KV cut into chunk-length
    tiles, each (q_i, kv_j) pair running the monolithic Pallas kernel
    (j < i full, j == i causal diagonal, j > i skipped), results merged
    with the two-way logsumexp combine — the SAME per-hop primitive +
    merge ring attention uses across devices (parallel/ring_attention.py),
    serialized on one chip. VMEM stays bounded by the tile length, so any
    chunk-divisible T compiles; HBM never holds [T, T] anything.

    q, k, v: [B, H, T, D] -> [B, H, T, D]; differentiable (the lse-merge
    weights flow through flash_attention_lse's custom VJP). mask:
    optional [B, T] key padding mask (1 = valid), sliced per kv tile.
    dropout: attention-weight dropout generated in-kernel from
    `dropout_rng` — chunk-invariant (r6): each tile hashes its GLOBAL
    (q, k) coordinates, so the keep mask equals the monolithic kernel's
    at this T bit-for-bit. `chunk` defaults to pick_chunk(T, causal)."""
    B, H, T, D = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(D))
    kmask = None if mask is None else _broadcast_kmask(mask, B, H, T)
    seed = None
    if dropout:
        if dropout_rng is None:
            raise ValueError("dropout > 0 requires dropout_rng")
        seed = _step_seed(dropout_rng)
    o, _ = chunked_flash_attention_lse(
        q.reshape(B * H, T, D), k.reshape(B * H, T, D),
        v.reshape(B * H, T, D), sm_scale, causal, kmask=kmask, chunk=chunk,
        dropout=dropout, seed=seed)
    return o.reshape(B, H, T, D)


def chunked_flash_attention_lse(q, k, v, sm_scale, causal, kmask=None,
                                chunk=None, dropout=0.0, seed=None,
                                q_origin=0, k_origin=0, hash_t=None):
    """Flat-layout chunked attention returning (o [BH, T, D], lse
    [BH, T]) — the long-local-block form of flash_attention_lse: ring
    hops whose PER-SHARD block exceeds MAX_FLASH_T route here
    (parallel/ring_attention.py), so the seq mesh axis composes with
    single-chip chunking to sequences of n_shards * 128k tokens.
    Differentiable the same way (per-tile custom VJPs + lse_combine).
    kmask: optional [BH, 1, T] key padding mask, sliced per kv tile.

    dropout/seed: in-kernel dropout (seed from _step_seed) whose keep
    mask hashes GLOBAL coordinates — q_origin/k_origin are this call's
    window offsets in the full sequence (nonzero for ring hops; may be
    traced) and hash_t the GLOBAL sequence length (defaults to T), so
    the mask is invariant to the chunk count AND to how the sequence is
    sharded across ring hops."""
    BH, T, D = q.shape

    # explicit/tuned chunks obey the same guards as pick_chunk:
    # lane-legal tiles no longer than the D-aware proven envelope, with
    # a trace size inside the budget (an uncapped hop_chunk would
    # compile for minutes; an oversized one would hand the monolithic
    # kernel the VMEM-busting length this path avoids)
    def _fits(cand):
        return (isinstance(cand, int) and cand > 0 and T % cand == 0
                and cand % BLOCK == 0
                and cand <= autotune.max_tile_for_dim(D)
                and T // cand >= 2 and _fits_unroll(T // cand, causal))

    c = chunk
    if not c:
        c = (autotune.chunk_tile(T, D, causal=causal,
                                 dropout=bool(dropout),
                                 masked=kmask is not None, fits=_fits)
             or pick_chunk(T, causal, head_dim=D))
    n = T // c if c else 0
    if not _fits(c):
        raise ValueError(
            f"T={T} not divisible into 2-{max_chunks(causal)} kernel tiles"
            + (f" of {chunk}" if chunk else "")
            + (f" ({chunk_pairs(n, causal)} unrolled tile pairs exceed "
               f"the {MAX_CHUNK_PAIRS} budget)"
               if n >= 2 and not _fits_unroll(n, causal) else "")
            + (f" (head_dim={D} caps tiles at "
               f"{autotune.max_tile_for_dim(D)})"
               if c and c % BLOCK == 0 and n >= 2
               and c > autotune.max_tile_for_dim(D) else ""))
    ht = hash_t if hash_t is not None else T
    km = kmask
    if dropout and km is None:
        # the dropout cores take kmask unconditionally (ones = unpadded)
        km = jnp.ones((BH, 1, T), jnp.float32)
    if not causal:
        return _chunked_noncausal(q, k, v, sm_scale, c, n, km, dropout,
                                  seed, q_origin, k_origin, ht)
    outs, lses = [], []
    for i in range(n):
        qi = q[:, i * c:(i + 1) * c]
        o = lse = None
        for j in range(i + 1):
            kj = k[:, j * c:(j + 1) * c]
            vj = v[:, j * c:(j + 1) * c]
            if dropout:
                ctx = _drop_ctx(seed, q_origin + i * c, k_origin + j * c)
                o_hop, lse_hop = flash_attention_lse_drop(
                    qi, kj, vj, km[:, :, j * c:(j + 1) * c], ctx,
                    sm_scale, j == i, float(dropout), ht)
            elif km is None:
                o_hop, lse_hop = flash_attention_lse(
                    qi, kj, vj, sm_scale, j == i)
            else:
                o_hop, lse_hop = flash_attention_lse_masked(
                    qi, kj, vj, km[:, :, j * c:(j + 1) * c],
                    sm_scale, j == i)
            if o is None:
                # stay in the kernel dtype until a merge NEEDS f32 — a
                # single-hop row (i == 0 causal) otherwise round-trips
                # bf16 -> f32 -> bf16 for nothing (graftlint P003)
                o, lse = o_hop, lse_hop
            else:
                o, lse = lse_combine(o.astype(jnp.float32), lse,
                                     o_hop, lse_hop)
        outs.append(o.astype(q.dtype))
        lses.append(lse)
    return jnp.concatenate(outs, axis=1), jnp.concatenate(lses, axis=1)


def _chunked_noncausal(q, k, v, sm_scale, c, n, km, dropout, seed,
                       q_origin, k_origin, hash_t):
    """Non-causal chunk loop: kv tiles are UNIFORM (every (q_i, kv_j)
    pair runs the full kernel — no diagonal specialization), so the
    inner loop is a lax.scan over stacked kv tiles — ONE traced kernel
    per q chunk instead of the n^2 Python unroll ADVICE r5 #1 flagged
    (16 chunks would have unrolled 256 forward calls plus their VJPs).
    Numerics match the unrolled loop bit-for-bit: the carry starts at
    (0, NEG_INF), whose first lse_combine is exact (a = exp(NEG_INF -
    lse_hop) underflows to 0.0, b = exp(0) = 1.0, denom = 1.0 — the old
    direct first-hop assignment), and hops run in the same j = 0..n-1
    order. Dropout stays chunk-invariant: the per-hop ctx hashes the
    GLOBAL (q, k) origin computed from the scanned hop index."""
    BH, T, D = q.shape
    ks = jnp.moveaxis(k.reshape(BH, n, c, D), 1, 0)       # [n, BH, c, D]
    vs = jnp.moveaxis(v.reshape(BH, n, c, D), 1, 0)
    kms = (None if km is None
           else jnp.moveaxis(km.reshape(BH, 1, n, c), 2, 0))
    js = jnp.arange(n, dtype=jnp.int32)
    outs, lses = [], []
    for i in range(n):
        qi = q[:, i * c:(i + 1) * c]

        def hop(carry, xs, qi=qi, i=i):
            o, lse = carry
            if dropout:
                kj, vj, kmj, j = xs
                ctx = _drop_ctx(seed, q_origin + i * c, k_origin + j * c)
                o_hop, lse_hop = flash_attention_lse_drop(
                    qi, kj, vj, kmj, ctx, sm_scale, False,
                    float(dropout), hash_t)
            elif km is None:
                kj, vj = xs
                o_hop, lse_hop = flash_attention_lse(qi, kj, vj,
                                                     sm_scale, False)
            else:
                kj, vj, kmj = xs
                o_hop, lse_hop = flash_attention_lse_masked(
                    qi, kj, vj, kmj, sm_scale, False)
            return lse_combine(o, lse, o_hop, lse_hop), None

        if dropout:
            xs = (ks, vs, kms, js)
        elif km is None:
            xs = (ks, vs)
        else:
            xs = (ks, vs, kms)
        carry0 = (jnp.zeros((BH, c, D), jnp.float32),
                  jnp.full((BH, c), NEG_INF, jnp.float32))
        (o, lse), _ = jax.lax.scan(hop, carry0, xs)
        outs.append(o.astype(q.dtype))
        lses.append(lse)
    return jnp.concatenate(outs, axis=1), jnp.concatenate(lses, axis=1)


def _broadcast_kmask(mask, B, H, T):
    """[B, T] key padding mask -> the kernels' [B*H, 1, T] operand (the
    singleton row dim satisfies Mosaic's (8,128)-divisible-or-equal block
    rule). The single home for this layout — flash_attention's masked and
    dropout branches and the chunk loop all build it here."""
    return jnp.broadcast_to(
        jnp.asarray(mask, jnp.float32)[:, None, :], (B, H, T)
    ).reshape(B * H, 1, T)


def flash_attention(q, k, v, *, causal=True, sm_scale=None, mask=None,
                    dropout=0.0, dropout_rng=None):
    """q, k, v: [B, H, T, D] -> [B, H, T, D]; differentiable (custom VJP).

    mask: optional [B, T] padding mask keyed on KEYS (1 = valid), the
    dense path's semantics (nn/layers/attention.dot_product_attention) —
    masked keys contribute no probability mass and receive zero dk/dv.
    dropout: attention-weight dropout rate, generated INSIDE the kernels
    from `dropout_rng` (a jax PRNG key) via the counter-based hash — the
    [B, H, T, T] mask never materializes in HBM."""
    B, H, T, D = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(D))
    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * H, T, D)
    vf = v.reshape(B * H, T, D)
    if dropout:
        if dropout_rng is None:
            raise ValueError("dropout > 0 requires dropout_rng")
        ctx = _drop_ctx(_step_seed(dropout_rng))
        kmask = (jnp.ones((B * H, 1, T), jnp.float32) if mask is None
                 else _broadcast_kmask(mask, B, H, T))
        o = _flash_core_drop(qf, kf, vf, kmask, ctx, sm_scale,
                             bool(causal), float(dropout))
    elif mask is None:
        o = rows_per_device(
            lambda q_, k_, v_: _flash_core(q_, k_, v_, sm_scale,
                                           bool(causal)),
            (qf, kf, vf))
    else:
        kmask = _broadcast_kmask(mask, B, H, T)
        o = rows_per_device(
            lambda q_, k_, v_, km: _flash_core_masked(
                q_, k_, v_, km, sm_scale, bool(causal)),
            (qf, kf, vf, kmask))
    return o.reshape(B, H, T, D)
