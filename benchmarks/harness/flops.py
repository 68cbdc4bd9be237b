"""Operations and bytes a kernel needs, from shapes alone. What a whole
model needs (FLOPs per trained, prefilled or decoded token, the bytes of a
decode step) is its family's: `benchmarks/families/<model_type>.py`."""

from __future__ import annotations


def causal_attention_factor(seq_len: int) -> float:
    """Share of the dense [T, T] score matrix a causal mask keeps:
    T(T+1)/2 of T*T pairs."""
    return (seq_len + 1) / (2.0 * seq_len)


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
