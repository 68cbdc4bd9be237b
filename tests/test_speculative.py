"""Speculative decoding + int8 paged KV cache correctness gates.

These are the parity gates the raw-speed arc hangs off: the n-gram
proposer and greedy acceptance mask are unit-proven, the int8 page
round-trip error bound from the quantize_pages docstring is verified
numerically, and the engine-level contract — greedy speculative (and
int8, and both together) emits a BIT-IDENTICAL stream to plain greedy
decode, with zero post-warmup retraces and every page returned to the
pool — is asserted end to end on the real GenerationEngine.
"""

import numpy as np
import pytest

from deeplearning4j_tpu.ops.decode_attention import (dequantize_pages,
                                                     quantize_pages)
from deeplearning4j_tpu.serving import replay
from deeplearning4j_tpu.serving.buckets import BucketLattice
from deeplearning4j_tpu.serving.engine import GenerationEngine
from deeplearning4j_tpu.serving.speculative import (NgramProposer,
                                                    accept_greedy)
from deeplearning4j_tpu.telemetry import Recorder


# ------------------------------------------------------------- proposer

def test_ngram_proposer_mines_repeating_structure():
    """A history that repeats an n-gram proposes the tokens that
    followed its earlier occurrence — the prompt-lookup oracle."""
    p = NgramProposer(max_order=3)
    # ... 7 8 9 [5 6] 1 2 3 [5 6] -> the earlier [5 6] was followed by 1 2 3
    hist = [7, 8, 9, 5, 6, 1, 2, 3, 5, 6]
    assert p.propose(hist, 3) == [1, 2, 3]
    # continuation running off the end extends cyclically from the match
    assert p.propose([1, 2, 3, 1, 2, 3], 5) == [1, 2, 3, 1, 2]


def test_ngram_proposer_fallbacks():
    p = NgramProposer(max_order=3)
    # no repeat anywhere: order-0 guess repeats the last token
    assert p.propose([4, 9, 2], 3) == [2, 2, 2]
    assert p.propose([], 2) == [0, 0]
    assert p.propose([5], 0) == []
    # most RECENT precedent wins over an older one
    hist = [1, 2, 7, 7, 1, 2, 9, 9, 1, 2]
    assert p.propose(hist, 2) == [9, 9]
    with pytest.raises(ValueError):
        NgramProposer(max_order=0)


def test_accept_greedy_mask():
    """n_accepted = longest prefix of drafts matching the argmax before
    them; emitted = those argmaxes plus the bonus token ending the run,
    so every emitted token is an argmax given its true prefix."""
    # all drafts right: k-1 accepted, k emitted
    assert accept_greedy([5, 6, 7], [5, 6, 7, 8]) == (3, [5, 6, 7, 8])
    # first draft wrong: 0 accepted, bonus token m_0 still emitted
    assert accept_greedy([9, 6, 7], [5, 6, 7, 8]) == (0, [5])
    # middle rejection truncates the window there
    assert accept_greedy([5, 0, 7], [5, 6, 7, 8]) == (1, [5, 6])
    with pytest.raises(ValueError):
        accept_greedy([1, 2], [1, 2])  # k-1 drafts need k verify rows


# ---------------------------------------------------- int8 paged cache

def test_int8_page_roundtrip_error_bound():
    """quantize_pages promises |x - dequant(quant(x))| <= scale/2 per
    element, with scale = per-(row, page, head) maxabs / 127 — the
    symmetric-rounding bound, checked on adversarial magnitudes."""
    rng = np.random.default_rng(0)
    B, S, H, D, ps = 3, 32, 2, 8, 8
    x = rng.normal(0, 1, (B, S, H, D)).astype(np.float32)
    # mix in wildly different page magnitudes so scales actually vary
    x[:, :ps] *= 100.0
    x[:, ps:2 * ps] *= 1e-3
    codes, scales = quantize_pages(x, ps)
    assert codes.dtype == np.int8 and codes.shape == x.shape
    assert scales.shape == (B, S // ps, H)
    back = np.asarray(dequantize_pages(codes, scales, ps))
    err = np.abs(x - back).reshape(B, S // ps, ps, H, D)
    bound = np.asarray(scales)[:, :, None, :, None] / 2.0
    assert np.all(err <= bound + 1e-7)
    # re-quantizing the round-trip is exact: values already sit on the
    # int8 grid, so codes and scales are both fixed points
    codes2, scales2 = quantize_pages(back, ps)
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(codes2))
    np.testing.assert_allclose(np.asarray(scales), np.asarray(scales2),
                               rtol=1e-6)


# --------------------------------------------- engine-level parity gate

_PROMPT_MIX = ((3, 2), (8, 5), (11, 1), (16, 8), (5, 3),
               (1, 4), (13, 2), (16, 1), (2, 6), (7, 8))


def _run_engine(net, k, kv_dtype):
    rec = Recorder(path=None)
    lat = BucketLattice(batch_sizes=(1,), seq_lens=(8, 16))
    eng = GenerationEngine(net, lat, slots=2, max_new_tokens=8,
                           page_size=8, recorder=rec,
                           speculative_k=k, kv_dtype=kv_dtype)
    eng.warmup()
    traced = eng.trace_count
    eng.start()
    rng = np.random.default_rng(11)
    outs = []
    for plen, olen in _PROMPT_MIX:
        out = eng.generate(rng.integers(0, 64, plen).astype(np.int32),
                           olen, timeout=60)
        assert len(out) == olen
        outs.append(list(out))
    # zero-retrace contract: the mixed stream adds no shapes, in plain,
    # speculative ([B, k] verify step), and quantized modes alike
    assert eng.trace_count == traced, "a shape escaped warmup"
    # rollback/teardown gate: every page is back in the pool
    pools = [e for e in rec.events if e.get("event") == "page_pool"]
    assert pools and pools[-1]["pages_in_use"] == 0
    assert max(p["pages_in_use"] for p in pools) > 0
    stats = eng.stats()
    eng.drain()
    return outs, stats, rec


def test_verify_step_aliases_the_whole_cache():
    """The verify step donates its cache like the decode step it stands
    in for: its warmed `cost` event's `alias_bytes` reads the tree's bytes."""
    from deeplearning4j_tpu.telemetry.memstat import tree_bytes

    rec = Recorder(path=None)
    eng = GenerationEngine(
        replay._tiny_lm(24),
        BucketLattice(batch_sizes=(1,), seq_lens=(8, 16)), slots=2,
        max_new_tokens=8, page_size=8, recorder=rec, speculative_k=2)
    eng.warmup()
    nbytes = tree_bytes(eng.fleet_workers()[0].cache)
    costs = {e["entry"]: e for e in rec.events if e.get("event") == "cost"}
    assert sorted(costs) == ["prefill", "verify"]  # no decode step warmed
    assert costs["verify"]["alias_bytes"] == nbytes > 0


def test_greedy_speculative_bit_identity():
    """The arc's headline gate: speculative greedy emits a token stream
    bit-identical to plain greedy decode — acceptance is a mask over
    verify rows, never a sampler."""
    net = replay._tiny_lm(24)
    base, s0, _ = _run_engine(net, 0, "f32")
    assert not s0["speculative"]["enabled"]

    spec, s1, rec1 = _run_engine(net, 4, "f32")
    assert spec == base
    sp = s1["speculative"]
    assert sp["enabled"] and sp["k"] == 4
    assert sp["verify_steps"] > 0
    # each verify step emits >= 1 token, so the headline floor is 1.0;
    # the n-gram proposer must beat it on this repeat-heavy tiny LM
    assert sp["accepted_tokens_per_step"] > 1.0
    assert 0.0 <= sp["draft_acceptance_rate"] <= 1.0
    drafts = [e for e in rec1.events if e.get("event") == "draft"]
    assert drafts and all(e["k"] == 4 for e in drafts)
    assert any(e.get("event") == "span" and e.get("name") == "verify_step"
               for e in rec1.events)


@pytest.mark.slow
def test_int8_arms_bit_identity():
    """int8 greedy — alone and stacked with speculation — matches the
    f32 baseline stream exactly: per-page scales keep enough precision
    to preserve every argmax at this scale. (Slow tier: three engine
    warmups; the committed SERVE_r04 parity rows re-check the same
    contract on every round, and the round-trip bound test above stays
    in tier-1.)"""
    net = replay._tiny_lm(24)
    base, _, _ = _run_engine(net, 0, "f32")
    q8, _, _ = _run_engine(net, 0, "int8")
    assert q8 == base
    both, s3, _ = _run_engine(net, 4, "int8")
    assert both == base
    assert s3["speculative"]["accepted_tokens_per_step"] > 1.0
