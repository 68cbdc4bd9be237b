"""The FLOP and byte counts against hand arithmetic."""
import json
import os

import pytest
from harness import flops, spec
from presets import GPT2, ROOT

gpt2 = spec.family_of(GPT2)


def dims(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as fh:
        return gpt2.dims_of(json.load(fh))


def test_train_flops_per_token_590m():
    # per layer: 8 d^2 = 18,874,368; 4 d F = 37,748,736; attention at the
    # causal mean context 1024.5: 4 * 1024.5 * 1536 = 6,294,528
    per_layer = 18_874_368 + 37_748_736 + 6_294_528
    head = 2 * 1536 * 50257
    assert gpt2.train_flops_per_token(dims("cerebras-gpt-590m"), 2048) \
        == 3 * (18 * per_layer + head)
    assert gpt2.train_flops_per_token(dims("cerebras-gpt-590m"), 2048) \
        == pytest.approx(3.86e9, rel=2e-3)


def test_causal_factor():
    assert flops.causal_attention_factor(2048) == 2049 / 4096


def test_kv_bytes_per_token_1p3b():
    # 24 layers x (k + v) x 2048 values x 2 bytes = 196,608 = "197 KB"
    assert gpt2.kv_bytes_per_token(dims("cerebras-gpt-1.3b")) == 196_608


def test_param_counts():
    assert gpt2.count_params(dims("cerebras-gpt-590m")) == 664_410_193
    assert gpt2.count_params(dims("cerebras-gpt-1.3b")) == 1_414_505_553
    d = dims("cerebras-gpt-1.3b")
    assert gpt2.matmul_param_count(d) == 1_414_505_553 - 50257 * 2048


def test_decode_step_min_bytes():
    d = dims("cerebras-gpt-1.3b")
    # weights once in bf16 plus 16 slots x 300 live tokens
    want = 2 * gpt2.matmul_param_count(d) + 4800 * 196_608
    assert gpt2.decode_step_min_bytes(d, 4800) == want
    assert want == pytest.approx(2.62e9 + 0.944e9, rel=1e-2)


def test_flash_kernel_cost():
    # B 4, H 12, T 2048, D 128: pairs = 2048 * 2049 / 2 = 2,098,176
    f, b = flops.flash_kernel_cost("flash_fwd", 4, 12, 2048, 128)
    assert f == 4 * 12 * 2 * 2 * 2_098_176 * 128
    assert b == 4 * (4 * 12 * 2048 * 128 * 2)
    f_dq, _ = flops.flash_kernel_cost("flash_bwd_dq", 4, 12, 2048, 128)
    f_dkv, _ = flops.flash_kernel_cost("flash_bwd_dkv", 4, 12, 2048, 128)
    assert (f_dq, f_dkv) == (1.5 * f, 2 * f)


def test_prefill_and_decode_flops():
    d = dims("cerebras-gpt-1.3b")
    one = gpt2.decode_flops(d, 100)
    assert one == 24 * (8 * 2048**2 + 4 * 2048 * 8192 + 4 * 100 * 2048) \
        + 2 * 2048 * 50257
    # a prompt of one token is a decode against itself
    assert gpt2.prefill_flops(d, 1) == gpt2.decode_flops(d, 1)
