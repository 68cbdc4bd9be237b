"""The regions of a compiled program: `jax.named_scope` around each layer's
ops (nn/decode._walk, nn/graph._forward), read back from the compiled
text as a table of instruction -> region (telemetry/costbook.py
`hlo_regions`, the `regions` event of each warmed serving program).

A region is metadata: a program built with its scopes and one built with
none compile to the same instructions once `metadata={...}` is taken off.
"""

from __future__ import annotations

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.grouped_moe import grouped_moe_lm
from deeplearning4j_tpu.models.latent_moe import latent_moe_lm
from deeplearning4j_tpu.models.retention import retention_lm
from deeplearning4j_tpu.models.transformer import transformer_lm
from deeplearning4j_tpu.serving.buckets import BucketLattice
from deeplearning4j_tpu.serving.engine import GenerationEngine
from deeplearning4j_tpu.telemetry import REGION_NAMES, REGIONS, Recorder
from deeplearning4j_tpu.telemetry.costbook import hlo_regions, region_path

NETS = {
    "gpt2_block": lambda: transformer_lm(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_length=64),
    "latent_attention_experts": lambda: latent_moe_lm(
        64, 32, 2, 2, q_rank=16, kv_rank=16, nope_dim=8, rope_dim=8,
        v_dim=8, d_ff=64, n_experts=8, top_k=2, d_expert=16, n_held=4),
    "grouped_attention": lambda: grouped_moe_lm(
        64, 32, 4, 2, 8, ("sliding_attention", "full_attention"), 8, 1, 64,
        8, 2, 16, 0, 4),
    "retention": lambda: retention_lm(64, 32, 4, 2, 2, 64, head_dim=8),
}
# what every program of the net holds, by the kinds of layer it has
WANT = {
    "gpt2_block": {"embed", "norm", "attention", "attention/cache_write",
                   "ffn", "head"},
    "latent_attention_experts": {
        "embed", "norm", "attention", "attention/cache_write", "ffn",
        "moe/router", "moe/experts", "moe/shared_expert", "head"},
    "grouped_attention": {
        "embed", "norm", "attention", "attention/cache_write", "ffn",
        "moe/router", "moe/experts", "moe/shared_expert", "head"},
    "retention": {"embed", "norm", "attention", "ffn", "head"},
}


def _engine(net, rec):
    net.init()
    engine = GenerationEngine(
        net, BucketLattice(batch_sizes=(1,), seq_lens=(8, 16)), slots=2,
        max_new_tokens=8, page_size=8, prefill_chunk=8, recorder=rec)
    engine.warmup()
    return engine


def _unscoped(monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())


def _instructions(text: str) -> list:
    """The compiled text's computations and instructions, with the
    metadata (the one place a scope lands) taken off."""
    body = text[text.index("\n%"):] if "\n%" in text else text
    return [re.sub(r", metadata=\{[^}]*\}", "", line)
            for line in body.splitlines() if line.strip()]


@pytest.mark.parametrize("kind", sorted(NETS))
def test_every_layer_kind_reaches_the_served_programs(kind):
    """The warmed decode and prefill programs of a small served net carry
    the region of every kind of layer it has in their instructions'
    `op_name`, as the `regions` event beside each `cost` event tables
    them; a region names only registered regions, and the chunk of a
    net whose layers write a cache holds the write."""
    rec = Recorder(path=None, keep=100_000)
    _engine(NETS[kind](), rec)
    events = [e for e in rec.events if e.get("event") == "regions"]
    assert sorted(e["entry"] for e in events) == ["decode", "prefill"]
    for e in events:
        assert e["module"] == f"jit_counted_{'step' if e['entry'] == 'decode' else 'prefill'}"
        found = set(e["ops"].values())
        assert WANT[kind] <= found, (e["entry"], WANT[kind] - found)
        assert {r.split("/")[0] for r in found} <= set(REGIONS)
        assert {p for r in found for p in r.split("/")} <= REGION_NAMES
    chunk = next(e for e in events if e["entry"] == "prefill")
    assert "attention/cache_write" in set(chunk["ops"].values())


@pytest.mark.parametrize("entry", ["decode", "prefill"])
def test_scopes_change_no_instruction_of_a_served_program(entry,
                                                          monkeypatch):
    """The latent-attention net with experts (every child region) built
    with its scopes and with `jax.named_scope` made a no-op: the two
    compiled programs are the same instruction for instruction once
    their metadata is stripped, and only the scoped one names regions."""
    texts = {}
    for scoped in (True, False):
        if not scoped:
            _unscoped(monkeypatch)
        rec = Recorder(path=None, keep=100_000)
        engine = _engine(NETS["latent_attention_experts"](), rec)
        worker = engine.fleet_workers()[0]
        ws = engine.weights.current
        if entry == "decode":
            B = engine.plan.n_slots
            args = (ws.params, ws.state, worker.cache, worker._tokens,
                    np.full(B, engine.plan.capacity - 1, np.int32),
                    worker._live())
            jitted = worker._decode_jit
        else:
            args = (ws.params, ws.state, worker.cache, worker._tokens,
                    np.zeros((1, 8), np.int32), np.ones((1, 8), np.float32),
                    np.zeros(1, np.int32), np.zeros(1, np.int32),
                    np.asarray([7], np.int32))
            jitted = worker._prefill_jit
        texts[scoped] = jitted.lower(*args).compile().as_text()
    assert _instructions(texts[True]) == _instructions(texts[False])
    assert hlo_regions(texts[True])["ops"]
    assert not hlo_regions(texts[False])["ops"]


def test_scopes_change_no_instruction_of_the_train_step(monkeypatch):
    """The training step of a GPT-2 block graph: `loss` around the
    forward and backward (each layer in its region inside it, the
    backward through transpose(jvp(...))), `optimizer` around the
    update; compiled with and without the scopes, the same
    instructions."""
    feats = np.random.default_rng(0).integers(0, 64, (2, 16)).astype(
        np.int32)
    batch = {"features": (jnp.asarray(feats),),
             "labels": (jnp.asarray(np.roll(feats, -1, axis=1)),)}
    texts = {}
    for scoped in (True, False):
        if not scoped:
            _unscoped(monkeypatch)
        net = NETS["gpt2_block"]()
        net.init()
        step = net._get_train_step()
        texts[scoped] = step.lower(net.params, net.opt_state, net.state,
                                   jax.random.PRNGKey(0),
                                   batch).compile().as_text()
    assert _instructions(texts[True]) == _instructions(texts[False])
    found = set(hlo_regions(texts[True])["ops"].values())
    assert {"embed", "norm", "attention", "ffn", "head", "optimizer"} <= \
        found, found


HLO = """HloModule jit_counted_step, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(counted_step)/moe/experts/add"}
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%p), index=1
  %dot.3 = f32[8]{0} dot(%gte.1, %gte.1), metadata={op_name="jit(counted_step)/moe/experts/while/body/dot_general"}
  ROOT %t = (s32[], f32[8]{0}) tuple(%gte.1, %dot.3)
}

ENTRY %main (w: f32[8], c: f32[4,8]) -> f32[4,8] {
  %w = f32[8]{0} parameter(0)
  %c = f32[4,8]{1,0} parameter(1)
  %slice-start = ((f32[8]{0}), f32[8]{0}, u32[]) slice-start(%w), slice={[0:8]}
  %slice-done = f32[8]{0} slice-done(%slice-start)
  %add_fusion = f32[8]{0} fusion(%slice-done), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(counted_step)/moe/experts/add"}
  %copy-start = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%add_fusion), metadata={op_name="jit(counted_step)/attention/cache_write/scatter"}
  %copy-done = f32[8]{0} copy-done(%copy-start)
  %while.2 = (s32[], f32[8]{0}) while(%copy-done), condition=%cond, body=%body, metadata={op_name="jit(counted_step)/moe/experts/while"}
  %kernel.1 = f32[8]{0} custom-call(%add_fusion), custom_call_target="tpu_custom_call", metadata={op_name="jit(counted_step)/transpose(jvp(attention))/probe/pallas_call"}
  %copy.9 = f32[8]{0} copy(%kernel.1)
  %argmax_fusion = s32[] fusion(%copy.9), kind=kInput, calls=%fused_computation, metadata={op_name="jit(counted_step)/head/argmax"}
  %tail = f32[8]{0} negate(%w)
  ROOT %out = (s32[], f32[8]{0}) tuple(%argmax_fusion, %tail)
}
"""


def test_hlo_regions_reads_a_compiled_text():
    """The table from a hand-made compiled text: a fusion and a loop by
    their own scope, a body's instructions by theirs, a kernel under a
    transform's wrapping, an async `*-done` by its `*-start`, a prefetch
    and a relayout copy with no scope by what they feed; nothing from
    inside a fusion's body, no parameter or tuple; an instruction that
    neither feeds nor reads a scoped one is left out (`other`)."""
    got = hlo_regions(HLO)
    assert got["module"] == "jit_counted_step"
    assert got["ops"] == {
        "dot.3": "moe/experts",
        "slice-start": "moe/experts",
        "slice-done": "moe/experts",
        "add_fusion": "moe/experts",
        "copy-start": "attention/cache_write",
        "copy-done": "attention/cache_write",
        "while.2": "moe/experts",
        "kernel.1": "attention",
        "copy.9": "head",
        "argmax_fusion": "head",
    }


@pytest.mark.parametrize("op_name,region", [
    ("jit(counted_step)/jit(main)/attention/cache_write/scatter",
     "attention/cache_write"),
    ("jit(step)/loss/transpose(jvp(attention))/dot_general", "attention"),
    ("jit(step)/loss/jvp(moe)/router/sigmoid", "moe/router"),
    ("jit(step)/loss/reduce_sum", "loss"),
    ("jit(step)/optimizer/mul", "optimizer"),
    ("jit(step)/rms_norm/mul", None),
    ("jit(counted_step)/cache_write/scatter", None),
    ("params['embed']['W']", None),
])
def test_region_path_takes_the_innermost_top_level_region(op_name, region):
    assert region_path(op_name) == region
