"""Compile the main path's kernels for the real chip, without the chip.

The TPU compiler is installed here and compiles for a v5e that is
described, not attached (`topologies.get_topology_desc`). Interpret mode
cannot show what these compiles show: a block not aligned to the tiling,
a kernel over its VMEM limit, a program that cannot be partitioned. The
shapes are the ones `chip_smoke.py` runs: the d_model-1024 Transformer
LM (8 heads of 128, vocab 10000) at batch 32 x seq 512, and its
/generate serving shapes (4 slots, page size 16).

The code under test asks `jax.default_backend()` (here: cpu) to choose
interpret mode and to ignore the tuning table; each test steers both the
way the chip would (compiled kernels, table active) through the
`as_on_chip` fixture — in the test, not through an option of the program.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and under xdist every
worker imports this file. Nothing runs; a compile that passes is not a
chip run.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

B, T, D_MODEL, HEADS, VOCAB = 32, 512, 1024, 8, 10000
N_TOK = B * T
SLOTS, PAGE = 4, 16

OPS_WITH_INTERPRET = ("flash_attention", "fused_layernorm",
                      "fused_softmax_xent", "fused_sampling",
                      "fused_neg_softmax")


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip; keep it off around these."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def as_on_chip(monkeypatch, no_persistent_cache):
    """Compiled kernels and an active tuning table, as on the chip."""
    import importlib

    for name in OPS_WITH_INTERPRET:
        mod = importlib.import_module(f"deeplearning4j_tpu.ops.{name}")
        monkeypatch.setattr(mod, "_use_interpret", lambda: False)
    monkeypatch.setenv("DL4J_TPU_TUNING", "force")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.as_text()


def _kernels(text):
    """{kernel name: count}, read the way chip_smoke.py reads the
    compiled train step on the chip."""
    from chip_smoke import kernel_counts

    return kernel_counts(text)


def test_flash_packed_qkv_forward(as_on_chip, one_chip):
    """The attention layer's path at head_dim 128: packed [B, T, 3n]."""
    from deeplearning4j_tpu.ops.flash_attention import (
        flash_attention_qkv, supports_qkv)

    assert supports_qkv(B, T, D_MODEL, HEADS, dropout=0.0)
    qkv = _sds((B, T, 3 * D_MODEL), jnp.bfloat16, one_chip)
    _, text = _compile(
        lambda x: flash_attention_qkv(x, HEADS, causal=True), qkv)
    assert _kernels(text) == {"flash_fwd_qkv": 1}


def test_flash_packed_qkv_backward(as_on_chip, one_chip):
    from deeplearning4j_tpu.ops.flash_attention import flash_attention_qkv

    qkv = _sds((B, T, 3 * D_MODEL), jnp.bfloat16, one_chip)

    def loss(x):
        return flash_attention_qkv(x, HEADS, causal=True).astype(
            jnp.float32).sum()

    _, text = _compile(jax.grad(loss), qkv)
    assert _kernels(text) == {"flash_fwd_qkv": 1, "flash_bwd_qkv": 1}


@pytest.mark.parametrize("masked", [False, True])
def test_flash_flat_layout_forward_backward(as_on_chip, one_chip, masked):
    """[B, H, T, D] layout — the serving prefill's within-chunk attention
    and the layer's fallback. With the table active this resolves the
    flash_fwd/flash_bwd|T512|D128 entries (512x512 blocks, g=4)."""
    from deeplearning4j_tpu.ops.flash_attention import flash_attention

    q = _sds((B, HEADS, T, D_MODEL // HEADS), jnp.bfloat16, one_chip)
    args = [q, q, q]
    if masked:
        args.append(_sds((B, T), jnp.float32, one_chip))

    def loss(q, k, v, mask=None):
        return flash_attention(q, k, v, causal=True, mask=mask).astype(
            jnp.float32).sum()

    _, text = _compile(jax.grad(loss, argnums=(0, 1, 2)), *args)
    assert _kernels(text) == {"flash_fwd": 1, "flash_bwd_fused": 1}


@pytest.mark.parametrize("backward", [False, True])
def test_fused_layernorm(as_on_chip, one_chip, backward):
    from deeplearning4j_tpu.ops.fused_layernorm import (
        fused_layer_norm, supports)

    assert supports((N_TOK, D_MODEL))
    x = _sds((N_TOK, D_MODEL), jnp.bfloat16, one_chip)
    g = _sds((D_MODEL,), jnp.bfloat16, one_chip)

    def fwd(x, g, b):
        return fused_layer_norm(x, g, b)

    def loss(x, g, b):
        return fused_layer_norm(x, g, b).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd
    _, text = _compile(fn, x, g, g)
    want = {"fused_layer_norm_fwd": 1}
    if backward:
        want["fused_layer_norm_bwd"] = 1
    assert _kernels(text) == want


@pytest.mark.parametrize("backward", [False, True])
def test_softmax_xent_head(as_on_chip, one_chip, backward):
    """V=10000 is not a multiple of 128: the head pads the vocab to whole
    chunks. Backward = forward + dx kernel + dW/db kernel."""
    from deeplearning4j_tpu.ops.fused_softmax_xent import (
        softmax_xent_head, supports)

    assert supports(N_TOK, D_MODEL, VOCAB)
    x = _sds((N_TOK, D_MODEL), jnp.bfloat16, one_chip)
    w = _sds((D_MODEL, VOCAB), jnp.bfloat16, one_chip)
    b = _sds((VOCAB,), jnp.bfloat16, one_chip)
    lab = _sds((N_TOK,), jnp.int32, one_chip)

    def loss(x, w, b, lab):
        return softmax_xent_head(x, w, b, lab).mean()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else loss
    _, text = _compile(fn, x, w, b, lab)
    want = {"softmax_xent_fwd": 1}
    if backward:
        want.update(softmax_xent_dx=1, softmax_xent_dwdb=1)
    assert _kernels(text) == want


@pytest.mark.parametrize("rows,vocab", [
    (8, 10112),    # the serving vocab padded to the 128-lane tile
    (128, 2048),   # the tuning table's sample|T128|D2048 shape
])
def test_fused_sampling(as_on_chip, one_chip, rows, vocab):
    from deeplearning4j_tpu.ops.fused_sampling import fused_sample, supports

    assert supports(rows, vocab)
    lg = _sds((rows, vocab), jnp.float32, one_chip)
    _, text = _compile(
        lambda lg, nz: fused_sample(lg, nz, temperature=0.8, top_k=40,
                                    top_p=0.95), lg, lg)
    assert _kernels(text) == {"fused_sample": 1}


def test_fused_neg_softmax_at_table_shape(as_on_chip, one_chip):
    """neg_softmax|T256|D128 (B=256, K=5, D=128): the embedding engine's
    sampled-softmax scores."""
    from deeplearning4j_tpu.ops.fused_neg_softmax import (
        neg_softmax_scores, supports)

    assert supports(256, 5, 128)
    c = _sds((256, 128), jnp.float32, one_chip)
    neg = _sds((256, 5, 128), jnp.float32, one_chip)
    _, text = _compile(neg_softmax_scores, c, c, neg)
    assert _kernels(text) == {"neg_softmax": 1}


@pytest.fixture(scope="module")
def lm_shapes():
    """The d1024 LM at depth 2 (width is what the kernels see), params
    as shapes only."""
    from deeplearning4j_tpu.models.transformer import transformer_lm

    net = transformer_lm(vocab_size=VOCAB, d_model=D_MODEL, n_heads=HEADS,
                         n_layers=2, d_ff=4 * D_MODEL, max_length=T,
                         dtype="bfloat16")
    net.init()
    return net


def _on(tree, sharding):
    return jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, sharding), tree)


def test_decode_step_over_paged_cache(as_on_chip, one_chip, lm_shapes):
    """nn/decode.py single-query step: 4 slots against a cache of
    256 + 32 positions on the page grid."""
    net = lm_shapes
    capacity = (256 + 32 + PAGE - 1) // PAGE * PAGE
    step = net.incremental_decode_fn("f32", PAGE)
    cache = jax.eval_shape(
        lambda: net.init_kv_cache(SLOTS, capacity, "f32", PAGE))
    tok = _sds((SLOTS,), jnp.int32, one_chip)
    compiled, _ = _compile(
        step, _on(net.params, one_chip), _on(net.state, one_chip),
        _on(cache, one_chip), tok, tok)
    assert compiled.memory_analysis() is not None


def test_retention_decode_kernel_writes_the_state_in_place(
        no_persistent_cache, one_chip):
    """ops/power_retention.py's decode kernel at Brumby-14B's widths
    (16 slots, 40 queries on 8 states of 128 x 8,320 float32): it
    compiles for the chip (the 8,320-lane rows, the lane rotations, a
    2 MB value tile in and out), aliases the whole donated state, 550 MB
    a layer, and leaves nothing of the state's size beside it: no
    temporary, no relaid copy (a last axis that is no multiple of 128
    lanes got one, in and out, every step)."""
    from deeplearning4j_tpu.ops import power_retention as pr

    B, Hq, Hk, d = 16, 40, 8, 128
    D = pr.state_dim(d)

    def f32(*shape):
        return _sds(shape, jnp.float32, one_chip)

    compiled = jax.jit(pr.retention_decode_kernel, donate_argnums=(0, 1)).lower(
        f32(B, Hk, d, D), f32(B, Hk, D), f32(B, Hq, d), f32(B, Hk, d),
        f32(B, Hk, d), f32(B, Hk)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(text) == {"retention_decode": 1}
    assert mem.alias_size_in_bytes == 4 * B * Hk * (d + 1) * D == 549_519_360
    assert mem.temp_size_in_bytes < 2**20, mem
    assert not re.search(rf"= f32\[{B},{Hk},{d},{D}\]\S* copy\(", text)


def test_gated_delta_decode_kernel_writes_the_state_in_place(
        no_persistent_cache, one_chip):
    """ops/gated_delta.py's decode kernel at Qwen3-Next's widths (64
    slots, 32 value heads of 128 x 128 float32, the key heads already
    repeated to the value heads): it compiles for the chip (blocks of 8
    heads' states, 512 KB in and out, q and k as lane rows), aliases the
    whole donated state, 134 MB a layer, and leaves nothing of the
    state's size beside it: no temporary, no copy, and no q or k laid
    out a place a lane row (a [..., 128, 1] column would be padded to
    128 lanes: 134 MB written and read again every layer)."""
    from deeplearning4j_tpu.ops import gated_delta as gd

    B, H, dk, dv = 64, 32, 128, 128

    def f32(*shape):
        return _sds(shape, jnp.float32, one_chip)

    compiled = jax.jit(gd.gated_delta_decode_kernel, donate_argnums=0).lower(
        f32(B, H, dk, dv), f32(B, H, dk), f32(B, H, dk), f32(B, H, dv),
        f32(B, H), f32(B, H), _sds((B,), jnp.bool_, one_chip)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(text) == {"gated_delta_decode": 1}
    assert mem.alias_size_in_bytes == 4 * B * H * dk * dv == 134_217_728
    assert mem.temp_size_in_bytes < 16 * 2**20, mem
    assert not re.search(rf"= f32\[{B},{H},{dk},{dv}\]\S* copy\(", text)


@pytest.mark.parametrize("rows", [4096, 17408], ids=["ring", "full"])
def test_gqa_decode_kernel_reads_the_cache_where_it_lies(
        no_persistent_cache, one_chip, rows):
    """ops/decode_attention.py's grouped decode kernel at Trinity's
    widths (32 slots, 48 queries on 8 key-value heads of 128, bfloat16),
    over a window layer's ring of 4,096 rows and a full layer's 17,408:
    it compiles for the chip (a block of 512 rows of all 8 heads, 6
    queries padded to a sublane tile, the per-slot block counts
    prefetched as scalars), and takes the entry as it lies: no copy and
    no temporary of an entry's size (the entry is 268 MB and 1.14 GB a
    layer)."""
    from deeplearning4j_tpu.ops import decode_attention as da

    B, Hq, Hk, d = 32, 48, 8, 128
    bf16 = jnp.bfloat16
    compiled = jax.jit(da.gqa_decode_kernel).lower(
        _sds((B, Hq, d), bf16, one_chip), _sds((B, Hk, rows, d), bf16, one_chip),
        _sds((B, Hk, rows, d), bf16, one_chip), _sds((B,), jnp.int32, one_chip),
        _sds((B,), jnp.bool_, one_chip)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(text) == {"gqa_decode": 1}
    assert da.gqa_block(rows) == 512
    assert mem.temp_size_in_bytes < 2**20, mem
    assert not re.search(rf"= bf16\[{B},{Hk},{rows},{d}\]\S* copy\(", text)


def test_grouped_kernels_take_qwen3_nexts_full_layer(
        no_persistent_cache, one_chip, monkeypatch):
    """The two grouped kernels at the shape of Qwen3-Next's full layers,
    which no other cell gives them: 16 queries on 2 key-value heads of
    256 (8 a group), rotary over the first 64, a full entry of 36,864
    rows over 64 slots (bfloat16, 4.8 GB a layer). The decode step takes
    `gqa_decode` and the 1,024-token chunk `gqa_prefill`; both compile for
    the chip and read the entry where it lies, with no copy of it."""
    from deeplearning4j_tpu.nn.conf.layers import GroupedAttentionLayer
    from deeplearning4j_tpu.nn.decode import CacheStep
    from deeplearning4j_tpu.nn.layers.grouped_attention import (
        GroupedAttentionImpl,
    )
    from deeplearning4j_tpu.ops import decode_attention as da
    from deeplearning4j_tpu.ops import prefill_attention as pa

    h, Hq, Hk, d, Tc, slots, cap = 2048, 16, 2, 256, 1024, 64, 36864
    conf = GroupedAttentionLayer(n_in=h, n_out=h, n_heads=Hq, n_kv_heads=Hk,
                                 head_dim=d, rope_theta=1e7, rotary_dim=64,
                                 eps=1e-6)
    impl = GroupedAttentionImpl()
    bf16 = jnp.bfloat16
    shapes = {"Wq": (h, Hq * d), "Wk": (h, Hk * d), "Wv": (h, Hk * d),
              "Wg": (h, Hq * d), "Wo": (Hq * d, h), "q_norm": (d,),
              "k_norm": (d,)}
    params = {k: _sds(s, bf16, one_chip) for k, s in shapes.items()}
    cache = {n: _sds((slots,) + a[0], bf16, one_chip)
             for n, a in impl.cache_arrays(conf, cap, "f32", PAGE,
                                           bf16).items()}
    monkeypatch.setattr(pa, "use_kernel", lambda: True)
    monkeypatch.setattr(da, "_use_kernel", lambda: True)

    def chunk(params, x, cache, row, start, keep):
        step = CacheStep(row, start[:, None] + jnp.arange(Tc)[None, :],
                         keep=keep, chunk=True)
        return impl.apply_cached(conf, params, x, cache, step)

    def decode(params, x, cache, pos, live):
        step = CacheStep(None, pos[:, None], live=live)
        return impl.apply_cached(conf, params, x, cache, step)

    one = _sds((1,), jnp.int32, one_chip)
    for fn, args, kernel in (
            (chunk, (params, _sds((1, Tc, h), bf16, one_chip), cache, one,
                     one, _sds((1, Tc), jnp.float32, one_chip)),
             "gqa_prefill"),
            (decode, (params, _sds((slots, 1, h), bf16, one_chip), cache,
                      _sds((slots,), jnp.int32, one_chip),
                      _sds((slots,), jnp.bool_, one_chip)), "gqa_decode")):
        compiled = jax.jit(fn, donate_argnums=2).lower(*args).compile()
        text = compiled.as_text()
        assert _kernels(text) == {kernel: 1}
        assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
        assert not re.search(rf"= bf16\[{slots},{Hk},{cap},{d}\]\S* copy\(",
                             text)


def test_grouped_kernels_read_a_pass_of_ouros_entry_where_it_lies(
        no_persistent_cache, one_chip, monkeypatch):
    """The two grouped kernels at the looped model's widths, which no
    other cell gives them: 16 queries on 16 key-value heads of 128 (one
    query a head), no per-head norm and no gate, a layer's entry of 4
    passes of 1,280 rows over 4 slots (bfloat16, 168 MB a layer). The
    decode step and the 256-token chunk of a layer inside the loop, told
    the pass as a traced scalar, take `gqa_decode` and `gqa_prefill`;
    both compile for the chip and read and write the pass's rows in the
    whole entry, with no copy of it or of a pass's rows."""
    from deeplearning4j_tpu.nn.conf.layers import GroupedAttentionLayer
    from deeplearning4j_tpu.nn.decode import CacheStep
    from deeplearning4j_tpu.nn.layers.grouped_attention import (
        GroupedAttentionImpl,
    )
    from deeplearning4j_tpu.ops import decode_attention as da
    from deeplearning4j_tpu.ops import prefill_attention as pa

    h, H, d, Tc, slots, cap, P = 2048, 16, 128, 256, 4, 1280, 4
    conf = GroupedAttentionLayer(n_in=h, n_out=h, n_heads=H, n_kv_heads=H,
                                 head_dim=d, rope_theta=1e6, eps=1e-6,
                                 qk_norm=False, gate=False)
    impl = GroupedAttentionImpl()
    bf16 = jnp.bfloat16
    params = {k: _sds(s, bf16, one_chip) for k, s in (
        ("Wq", (h, H * d)), ("Wk", (h, H * d)), ("Wv", (h, H * d)),
        ("Wo", (H * d, h)))}
    cache = {n: _sds((slots, P) + a[0], bf16, one_chip)
             for n, a in impl.cache_arrays(conf, cap, "f32", PAGE,
                                           bf16).items()}
    monkeypatch.setattr(pa, "use_kernel", lambda: True)
    monkeypatch.setattr(da, "_use_kernel", lambda: True)

    def chunk(params, x, cache, row, start, keep, p):
        step = CacheStep(row, start[:, None] + jnp.arange(Tc)[None, :],
                         keep=keep, chunk=True).in_pass(p)
        return impl.apply_cached(conf, params, x, cache, step)

    def decode(params, x, cache, pos, live, p):
        step = CacheStep(None, pos[:, None], live=live).in_pass(p)
        return impl.apply_cached(conf, params, x, cache, step)

    one, p = _sds((1,), jnp.int32, one_chip), _sds((), jnp.int32, one_chip)
    for fn, args, kernel in (
            (chunk, (params, _sds((1, Tc, h), bf16, one_chip), cache, one,
                     one, _sds((1, Tc), jnp.float32, one_chip), p),
             "gqa_prefill"),
            (decode, (params, _sds((slots, 1, h), bf16, one_chip), cache,
                      _sds((slots,), jnp.int32, one_chip),
                      _sds((slots,), jnp.bool_, one_chip), p), "gqa_decode")):
        compiled = jax.jit(fn, donate_argnums=2).lower(*args).compile()
        text, mem = compiled.as_text(), compiled.memory_analysis()
        assert _kernels(text) == {kernel: 1}
        assert mem.alias_size_in_bytes == 2 * slots * P * H * cap * d * 2
        assert mem.temp_size_in_bytes < 16 * 2**20, mem
        for shape in ((slots, P, H, cap, d), (slots, H, cap, d)):
            dims = ",".join(map(str, shape))
            assert not re.search(rf"= bf16\[{dims}\]\S* copy\(", text)


def test_looped_decode_step_traces_its_body_once_and_holds_one_cache(
        no_persistent_cache, one_chip, monkeypatch):
    """A looped net's decode step at the looped model's widths (hidden
    2048, 16 heads of 128, a feed-forward of 5632, a vocabulary of
    49,152), two of its blocks run 4 times a token, over 4 slots of
    1,280 positions: the walk's loop over the pass holds ONE copy of the
    body, so the program calls `gqa_decode` once a block (2), not once a
    (block, pass) (8), and it donates the whole cache, every pass's rows
    of every block, and copies none of it."""
    from deeplearning4j_tpu.models.looped import looped_lm
    from deeplearning4j_tpu.ops import decode_attention as da

    monkeypatch.setattr(da, "_use_kernel", lambda: True)
    slots, cap, P = 4, 1280, 4
    held = {}

    def build():
        held["net"] = looped_lm(49152, 2048, 16, 2, P, d_ff=5632,
                                head_dim=128, rope_theta=1e6,
                                dtype="bfloat16", param_dtype="bfloat16"
                                ).init()
        return held["net"].params

    params = jax.eval_shape(build)
    net = held["net"]
    net.params = None
    cache = jax.eval_shape(lambda: net.init_kv_cache(slots, cap))
    cache_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert cache_bytes == slots * P * 2 * 2 * 16 * cap * 128 * 2
    step_raw = net.incremental_decode_fn()

    def step(params, state, cache, tok, pos, live):
        probs, cache, counts = step_raw(params, state, cache, tok, pos, live)
        return jnp.argmax(probs, -1), cache, counts

    on = lambda t: jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), t)
    compiled = jax.jit(step, donate_argnums=2).lower(
        on(params), {n: {} for n in params}, on(cache),
        _sds((slots,), jnp.int32, one_chip), _sds((slots,), jnp.int32,
                                                  one_chip),
        _sds((slots,), jnp.bool_, one_chip)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(text) == {"gqa_decode": 2}
    assert mem.alias_size_in_bytes == cache_bytes
    assert not re.search(rf"= bf16\[{slots},{P},16,{cap},128\]\S* copy\(",
                         text)


def test_prefill_flash_kernel_keeps_the_scores_off_the_memory(
        no_persistent_cache, one_chip, monkeypatch):
    """Latent attention's cached prefill chunk at openPangu's widths (128
    heads of 128 + 64 and 128, latents of 1,536 and 512, hidden 7,680),
    the 1,024 bucket over 64 slots of 4,608 positions, as the layer runs
    it in the prefill program on a TPU: it attends through the
    `prefill_flash` kernel (which compiles for the chip: blocks of 1,024
    queries and 512 keys of 4 heads, the row and the start prefetched),
    no float32 array of a score block's shape is left, and the program's
    temporaries stay well under the 256 MB that one block of the `jnp`
    walk's float32 scores took ([128, 1024, 512]; 507 MB in all there)."""
    from deeplearning4j_tpu.nn.conf.layers import LatentAttentionLayer
    from deeplearning4j_tpu.nn.decode import CacheStep
    from deeplearning4j_tpu.nn.layers.latent_attention import (
        LatentAttentionImpl,
    )
    from deeplearning4j_tpu.ops import prefill_attention as pa

    monkeypatch.setattr(pa, "use_kernel", lambda: True)
    H, d, Tc, slots, cap = 128, 7680, 1024, 64, 4608
    conf = LatentAttentionLayer(n_in=d, n_out=d, n_heads=H, q_rank=1536,
                                kv_rank=512, nope_dim=128, rope_dim=64,
                                v_dim=128, rope_theta=25.6e6)
    bf16 = jnp.bfloat16
    shapes = {"Wqa": (d, 1536), "q_norm": (1536,),
              "Wqb_nope": (H * 128, 1536), "Wqb_rope": (H * 64, 1536),
              "Wkva": (d, 576), "kv_norm": (512,), "Wkvb_k": (512, H * 128),
              "Wkvb_v": (512, H * 128), "Wo": (H * 128, d)}
    params = {k: _sds(s, bf16, one_chip) for k, s in shapes.items()}
    cache = {"ckv": _sds((slots, cap, 512), bf16, one_chip),
             "kpe": _sds((slots, cap, 64), bf16, one_chip)}

    def chunk(params, x, cache, rows, start, keep):
        step = CacheStep(rows, start[:, None] + jnp.arange(Tc)[None, :],
                         keep=keep, chunk=True)
        return LatentAttentionImpl().apply_cached(conf, params, x, cache,
                                                  step)

    one = _sds((1,), jnp.int32, one_chip)
    compiled = jax.jit(chunk, donate_argnums=2).lower(
        params, _sds((1, Tc, d), bf16, one_chip), cache, one, one,
        _sds((1, Tc), jnp.float32, one_chip)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert _kernels(text) == {"prefill_flash": 1}
    assert not re.search(r"f32\[(1,)?128,1024,512\]", text)
    assert mem.temp_size_in_bytes < 2**28 * 0.6, mem


@pytest.mark.parametrize("window, rows", [(4096, 4096), (0, 17408)],
                         ids=["ring", "full"])
def test_gqa_prefill_kernel_keeps_the_scores_off_the_memory(
        no_persistent_cache, one_chip, monkeypatch, window, rows):
    """Grouped attention's cached prefill chunk at Trinity's widths (48
    queries on 8 key-value heads of 128, hidden 3,072, bfloat16), the
    1,024 bucket over the cell's 32-slot entry (a window layer's ring of
    4,096 rows, a full layer's 17,408), as the layer runs it in the
    prefill program on a TPU: it attends through the `gqa_prefill`
    kernel, which compiles for the chip; no float32 array of the walk's
    score block ([8, 6144, 512]) or larger is left, no copy of the entry
    is made, and the program's temporaries are a few MB where the walk's
    are 246-306 MB."""
    from deeplearning4j_tpu.nn.conf.layers import GroupedAttentionLayer
    from deeplearning4j_tpu.nn.decode import CacheStep
    from deeplearning4j_tpu.nn.layers.grouped_attention import (
        GroupedAttentionImpl,
    )
    from deeplearning4j_tpu.ops import prefill_attention as pa

    h, Hq, Hk, d, Tc, slots, cap = 3072, 48, 8, 128, 1024, 32, 17408
    conf = GroupedAttentionLayer(n_in=h, n_out=h, n_heads=Hq, n_kv_heads=Hk,
                                 head_dim=d, window=window,
                                 rope_theta=10000.0 if window else 0.0)
    impl = GroupedAttentionImpl()
    bf16 = jnp.bfloat16
    shapes = {"Wq": (h, Hq * d), "Wk": (h, Hk * d), "Wv": (h, Hk * d),
              "Wg": (h, Hq * d), "Wo": (Hq * d, h), "q_norm": (d,),
              "k_norm": (d,)}
    params = {k: _sds(s, bf16, one_chip) for k, s in shapes.items()}
    cache = {n: _sds((slots,) + a[0], bf16, one_chip)
             for n, a in impl.cache_arrays(conf, cap, "f32", PAGE,
                                           bf16).items()}
    assert {a.shape[2] for a in cache.values()} == {rows}

    def chunk(params, x, cache, row, start, keep):
        step = CacheStep(row, start[:, None] + jnp.arange(Tc)[None, :],
                         keep=keep, chunk=True)
        return impl.apply_cached(conf, params, x, cache, step)

    one = _sds((1,), jnp.int32, one_chip)
    args = (params, _sds((1, Tc, h), bf16, one_chip), cache, one, one,
            _sds((1, Tc), jnp.float32, one_chip))
    temp = {}
    for kernel in (True, False):
        monkeypatch.setattr(pa, "use_kernel", lambda: kernel)
        # a new function each time: jit would reuse the first's trace
        compiled = jax.jit(lambda *a: chunk(*a),
                           donate_argnums=2).lower(*args).compile()
        text = compiled.as_text()
        temp[kernel] = compiled.memory_analysis().temp_size_in_bytes
        big_scores = re.search(r"f32\[(1,)?8,6144,\d{3,}\]", text)
        if kernel:
            assert _kernels(text) == {"gqa_prefill": 1}
            assert not big_scores
            assert not re.search(
                rf"= bf16\[{slots},{Hk},{rows},{d}\]\S* copy\(", text)
        else:
            assert not _kernels(text) and big_scores
    assert temp[True] < 8 * 2**20 and temp[False] > 30 * temp[True], temp


def _matrix_shapes(params):
    return {f"[{a.shape[0]},{a.shape[1]}]"
            for a in jax.tree.leaves(params) if a.ndim == 2}


def test_decode_step_with_served_parameters_reads_no_float32_weight(
        as_on_chip, one_chip, lm_shapes):
    """The step as the generation engine calls it since ISSUE 34: its
    parameters arrive in the compute dtype (nn/decode.serving_params),
    so the compiled program holds no float32 array of a weight matrix's
    shape, converts none into one, and reads fewer bytes than the step
    over the stored float32 tree by at least that tree's half."""
    from deeplearning4j_tpu.nn.decode import serving_params

    net = lm_shapes
    capacity = (256 + 32 + PAGE - 1) // PAGE * PAGE
    step = net.incremental_decode_fn("f32", PAGE)
    cache = jax.eval_shape(
        lambda: net.init_kv_cache(SLOTS, capacity, "f32", PAGE))
    tok = _sds((SLOTS,), jnp.int32, one_chip)
    served = serving_params(net)
    assert {a.dtype.name for a in jax.tree.leaves(served)} == {"bfloat16"}
    read, texts = {}, {}
    for name, params in (("stored", net.params), ("served", served)):
        compiled, texts[name] = _compile(
            step, _on(params, one_chip), _on(net.state, one_chip),
            _on(cache, one_chip), tok, tok)
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        read[name] = cost["bytes accessed"]
    shapes = _matrix_shapes(net.params)
    for dims in shapes:
        # the check can fail: the stored tree's step holds every one
        assert "f32" + dims in texts["stored"], dims
        assert "f32" + dims not in texts["served"], dims
        assert re.search(r"= bf16" + re.escape(dims) + r"\S* convert\(",
                         texts["served"]) is None, dims
    stored_bytes = sum(a.nbytes for a in jax.tree.leaves(net.params))
    assert read["stored"] - read["served"] >= stored_bytes / 2, read


def test_gpt2_server_fits_beside_the_callers_float32_tree(
        as_on_chip, one_chip, monkeypatch):
    """`cerebras-gpt-1.3b` as the benchmark serves it, built through its
    family's `serving_net` the way benchmarks/tests/test_chip_fit.py
    does: the caller's float32 tree stays on the device beside the
    engine's bfloat16 copy, and with it the decode step and the 1,024
    bucket still fit what the v5e's compiler allows a program."""
    import json
    import os

    from deeplearning4j_tpu.nn.training import tree_cast

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    monkeypatch.syspath_prepend(bench)
    from harness import spec

    with open(os.path.join(bench, "configs", "cerebras-gpt-1.3b.json")) as fh:
        config = json.load(fh)
    family = spec.family_of(config)
    dims, held = family.dims_of(config), {}

    def traced():
        held["net"] = family.serving_net(config, 0, dims)
        return held["net"].params

    stored = jax.eval_shape(traced)
    net = held["net"]
    net.params = None                   # the tracers it was built on
    served = jax.eval_shape(lambda p: tree_cast(p, net.compute_dtype),
                            stored)
    stored_bytes = sum(a.size * a.dtype.itemsize
                       for a in jax.tree.leaves(stored))
    state = {n: {} for n in stored}
    dep = config["deployment"]
    page, slots = dep["page_size"], dep["slots"]
    bucket = max(dep["prefill_seq_lens"])
    cap = -(-(bucket + dep["max_new_tokens"]) // page) * page
    cache = jax.eval_shape(
        lambda: net.init_kv_cache(slots, cap, dep["kv_dtype"], page))
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    i32 = lambda *s: _sds(s, jnp.int32, one_chip)

    def greedy(raw):        # as `_GenWorker` jits it: the cache donated
        def step(params, state, cache, *rest):
            probs, cache = raw(params, state, cache, *rest)
            return jnp.argmax(probs, axis=-1).astype(jnp.int32), cache
        return jax.jit(step, donate_argnums=2)

    hbm = 15.75 * 2**30     # test_chip_fit.py's HBM
    programs = {
        "decode": (greedy(net.incremental_decode_fn(dep["kv_dtype"], page)),
                   (i32(slots), i32(slots),
                    _sds((slots,), jnp.bool_, one_chip))),
        "prefill": (greedy(net.prefill_fn(dep["kv_dtype"], page)),
                    (i32(1, bucket), _sds((1, bucket), jnp.float32, one_chip),
                     i32(1), i32(1), i32(1)))}
    for name, (fn, rest) in programs.items():
        mem = fn.lower(*_on((served, state, cache), one_chip), *rest) \
            .compile().memory_analysis()
        assert mem.alias_size_in_bytes == cache_bytes, (name, mem)
        total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        assert stored_bytes + total < hbm, (name, stored_bytes, mem)


@pytest.mark.parametrize("bucket", [64, 256, 512])
def test_prefill_chunk(as_on_chip, one_chip, lm_shapes, bucket):
    """nn/decode.py prefill at the serving buckets; 512 is inside the
    flash envelope and must carry the masked flash kernel."""
    net = lm_shapes
    capacity = (512 + 32 + PAGE - 1) // PAGE * PAGE
    prefill = net.prefill_fn("f32", PAGE)
    cache = jax.eval_shape(
        lambda: net.init_kv_cache(SLOTS, capacity, "f32", PAGE))
    toks = _sds((1, bucket), jnp.int32, one_chip)
    km = _sds((1, bucket), jnp.float32, one_chip)
    one = _sds((1,), jnp.int32, one_chip)
    _, text = _compile(
        prefill, _on(net.params, one_chip), _on(net.state, one_chip),
        _on(cache, one_chip), toks, km, one, one, one)
    # depth 2: one masked flash forward per layer, inside the envelope
    assert _kernels(text) == ({"flash_fwd": 2} if bucket >= 512 else {})


# ------------------------------------------------- four chips: the 2x2 mesh

@pytest.fixture(scope="module")
def mesh_2x2(topo):
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(topo.devices[:4]).reshape(2, 2),
                ("data", "model"))


def test_mosaic_kernel_under_a_mesh_needs_the_row_shard_map(
        as_on_chip, mesh_2x2):
    """What stopped set_mesh on real chips: GSPMD cannot partition a
    Mosaic kernel, so a jit over four devices refuses to lower it —
    unless the train step names its mesh (ops/partition.kernel_mesh) and
    the kernel runs per device over batch rows."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.ops.flash_attention import flash_attention_qkv
    from deeplearning4j_tpu.ops.partition import kernel_mesh

    qkv = _sds((B, T, 3 * D_MODEL), jnp.bfloat16,
               NamedSharding(mesh_2x2, P("data", None, "model")))

    def attn(x):
        return flash_attention_qkv(x, HEADS, causal=True)

    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(attn).lower(qkv)

    def attn_per_device(x):
        with kernel_mesh(mesh_2x2):
            return attn(x)

    compiled, text = _compile(attn_per_device, qkv)
    assert _kernels(text) == {"flash_fwd_qkv": 1}
    # batch 32 over all four devices: each program sees 8 rows
    assert "bf16[8,512,3072]" in text


def test_xent_head_under_a_mesh_splits_tokens_over_every_axis(
        as_on_chip, mesh_2x2):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.ops.fused_softmax_xent import softmax_xent_head
    from deeplearning4j_tpu.ops.partition import kernel_mesh

    def sh(*spec):
        return NamedSharding(mesh_2x2, P(*spec))

    x = _sds((B, T, D_MODEL), jnp.bfloat16, sh("data"))
    w = _sds((D_MODEL, VOCAB), jnp.bfloat16, sh(None, "model"))
    b = _sds((VOCAB,), jnp.bfloat16, sh("model"))
    lab = _sds((B, T), jnp.int32, sh("data"))

    def loss(x, w, b, lab):
        with kernel_mesh(mesh_2x2):
            return softmax_xent_head(x, w, b, lab).mean()

    _, text = _compile(jax.grad(loss, argnums=(0, 1, 2)), x, w, b, lab)
    assert _kernels(text) == {"softmax_xent_fwd": 1, "softmax_xent_dx": 1,
                              "softmax_xent_dwdb": 1}
    assert f"bf16[{N_TOK // 4},{D_MODEL}]" in text


def test_regions_change_no_instruction_of_the_chip_program(
        as_on_chip, one_chip, monkeypatch):
    """A grouped-attention net with experts, its decode step compiled for
    the chip with the `gqa_decode` kernel: with every layer's region and
    with `jax.named_scope` made a no-op, the same instructions once the
    metadata is stripped (the kernel's own body included); the scoped
    program lays the kernel in `attention` and every kind of layer in
    its region (telemetry/costbook.hlo_regions)."""
    import contextlib

    from deeplearning4j_tpu.models.grouped_moe import grouped_moe_lm
    from deeplearning4j_tpu.ops import decode_attention as da
    from deeplearning4j_tpu.telemetry.costbook import hlo_regions

    monkeypatch.setattr(da, "_use_kernel", lambda: True)
    slots, capacity = 8, 1024
    tok = _sds((slots,), jnp.int32, one_chip)
    live = _sds((slots,), jnp.bool_, one_chip)
    texts = {}
    for scoped in (True, False):
        if not scoped:
            monkeypatch.setattr(jax, "named_scope",
                                lambda name: contextlib.nullcontext())
        net = grouped_moe_lm(256, 256, 4, 2, 128,
                             ("sliding_attention", "full_attention"), 512, 1,
                             512, 8, 2, 256, 0, 4, dtype="bfloat16",
                             param_dtype="bfloat16")
        net.init()
        cache = jax.eval_shape(
            lambda: net.init_kv_cache(slots, capacity, "f32", PAGE))
        _, texts[scoped] = _compile(
            net.incremental_decode_fn("f32", PAGE), _on(net.params, one_chip),
            _on(net.state, one_chip), _on(cache, one_chip), tok, tok, live)

    def instructions(text):
        return [re.sub(r", metadata=\{[^}]*\}", "", line)
                for line in text[text.index("\n%"):].splitlines()]

    assert _kernels(texts[True]) == {"gqa_decode": 2}
    assert instructions(texts[True]) == instructions(texts[False])
    ops = hlo_regions(texts[True])["ops"]
    kernels = {r for n, r in ops.items() if n.startswith("gqa_decode")}
    assert kernels == {"attention"}
    assert {"embed", "norm", "attention", "attention/cache_write", "ffn",
            "moe/router", "moe/experts", "moe/shared_expert", "head"} <= \
        set(ops.values())
    assert not hlo_regions(texts[False])["ops"]
