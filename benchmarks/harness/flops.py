"""Operations and bytes the algorithm needs, from shapes alone.

The training count is a copy of the program's
`models/transformer.transformer_flops_per_token_executed` (sound
arithmetic; the original is listed in PERF.md for a later PR to
delete): forward + backward = 3 x forward, the attention term counted
at exactly T(T+1)/2 causal pairs, recomputation not counted.
"""

from __future__ import annotations


def causal_attention_factor(seq_len: int) -> float:
    """Share of the dense [T, T] score matrix a causal mask keeps:
    T(T+1)/2 of T*T pairs."""
    return (seq_len + 1) / (2.0 * seq_len)


def forward_flops_per_token(dims: dict, context: float) -> float:
    """Forward FLOPs of one token that attends to `context` keys: the
    four [d, d] projections, the two feed-forward products, qk^T and
    attention x v over the context, and the output head."""
    d, F, V, L = dims["d"], dims["F"], dims["V"], dims["L"]
    per_layer = 4 * 2 * d * d + 2 * 2 * d * F + 2 * 2 * context * d
    return L * per_layer + 2 * d * V


def train_flops_per_token(dims: dict, seq_len: int) -> int:
    """Forward + backward FLOPs per trained token at sequence length
    seq_len under a causal mask (mean context (T+1)/2)."""
    return int(3 * forward_flops_per_token(
        dims, causal_attention_factor(seq_len) * seq_len))


def prefill_flops(dims: dict, prompt_len: int) -> float:
    """Forward FLOPs of a whole prompt; the head runs on its last row
    only (a server needs no other logits)."""
    d, V = dims["d"], dims["V"]
    body = forward_flops_per_token(dims, (prompt_len + 1) / 2.0) - 2 * d * V
    return prompt_len * body + 2 * d * V


def decode_flops(dims: dict, context: float) -> float:
    """Forward FLOPs of one generated token against `context` keys."""
    return forward_flops_per_token(dims, context)


def matmul_param_count(dims: dict) -> int:
    """Parameters a decode step has to read: every block's matrices and
    vectors and the head. The embedding table is gathered by row, not
    read."""
    d, F, V, L = dims["d"], dims["F"], dims["V"], dims["L"]
    block = 2 * d + d * 3 * d + 3 * d + d * d + d + 2 * d + d * F + F \
        + F * d + d
    return L * block + 2 * d + d * V + V


def kv_bytes_per_token(dims: dict, bytes_per_value: int = 2) -> int:
    """Bytes of keys and values one cached token holds over all layers."""
    return dims["L"] * 2 * dims["d"] * bytes_per_value


def decode_step_min_bytes(dims: dict, live_tokens: float,
                          bytes_per_value: int = 2) -> float:
    """The least a decode step moves: the weights once at the stated
    compute precision and every live cached row once."""
    return (matmul_param_count(dims) * bytes_per_value
            + live_tokens * kv_bytes_per_token(dims, bytes_per_value))


# FLOPs of each flash kernel as a multiple of the forward's two products
# (qk^T and p x v). The split backward recomputes the scores and dP in
# both of its kernels: dq = scores, dP, dS.k (3 products); dkv = scores,
# dP, P^T.dO, dS^T.q (4 products). The fused backward shares them (5).
FLASH_PRODUCTS = {"flash_fwd": 2, "flash_fwd_qkv": 2, "flash_fwd_qkv_pair": 2,
                  "flash_bwd_dq": 3, "flash_bwd_dkv": 4, "flash_bwd_fused": 5,
                  "flash_bwd_qkv": 5, "flash_bwd_qkv_pair": 5}
# tensors of [B, H, T, D] each kernel reads or writes at least once
FLASH_TENSORS = {"flash_fwd": 4, "flash_fwd_qkv": 4, "flash_fwd_qkv_pair": 4,
                 "flash_bwd_dq": 6, "flash_bwd_dkv": 7, "flash_bwd_fused": 8,
                 "flash_bwd_qkv": 8, "flash_bwd_qkv_pair": 8}


def flash_kernel_cost(kernel: str, batch: int, heads: int, seq_len: int,
                      head_dim: int, bytes_per_value: int = 2) -> tuple:
    """(FLOPs, bytes) one call of a causal flash kernel needs over
    [batch, heads, seq_len, head_dim]: 2 FLOPs per product per (query,
    key) pair per channel over T(T+1)/2 pairs; q, k, v, o (and do, dq,
    dk, dv for the backward kernels) moved once each."""
    pairs = seq_len * (seq_len + 1) / 2.0
    flops = batch * heads * FLASH_PRODUCTS[kernel] * 2 * pairs * head_dim
    tensor = batch * heads * seq_len * head_dim * bytes_per_value
    return flops, FLASH_TENSORS[kernel] * tensor
