"""`openpangu-ultra-moe-718b-ep16`'s serving programs at the published
widths, compiled for a described v5e (no chip attached) as `_GenWorker`
jits them: the decode step over 64 slots and the 1,024-token prefill
chunk. Each has to fit the chip's 16 GB beside the 9.84 GB of weights,
donate the whole latent cache, and read the cache where it lies: no
array of a cache array's size is copied (a [B, S, 576] row was, twice a
layer a step: nn/layers/latent_attention.py). Slow (a minute): not in
the repo's tier-1 run. The topology is test_chip_fit.py's fixture.
"""
import re

import jax
import jax.numpy as jnp
from test_chip_fit import HBM, built, load, on, one_chip, total  # noqa: F401


def copies_of(text: str, shape: tuple) -> int:
    """`copy` instructions of the compiled program whose result has that
    shape."""
    dims = ",".join(str(d) for d in shape)
    return len(re.findall(rf"= bf16\[{dims}\]\S* copy\(", text))


def test_server_programs_fit_and_read_the_cache_where_it_lies(one_chip):
    config = load("configs/openpangu-ultra-moe-718b-ep16.json")
    dep = config["deployment"]
    family, dims, net, params, _opt = built(config, "serving_net")
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == family.count_params(dims)
    state = {n: {} for n in params}
    page, slots = dep["page_size"], dep["slots"]
    cap = max(dep["prefill_seq_lens"]) + dep["max_new_tokens"]
    cache = jax.eval_shape(lambda: net.init_kv_cache(
        slots, cap, dep["kv_dtype"], page))
    cache_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert cache_bytes == slots * cap * family.kv_bytes_per_token(dims)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)

    def greedy(raw):
        def step(params, state, cache, *rest):
            probs, cache, counts = raw(params, state, cache, *rest)
            tok = jnp.argmax(probs, axis=-1).astype(jnp.int32)
            return jnp.concatenate([tok.reshape(-1), counts]), cache
        return jax.jit(step, donate_argnums=2)

    Tc = dep["prefill_chunk"]
    programs = {
        "decode": (greedy(net.incremental_decode_fn(dep["kv_dtype"], page)),
                   (i32(slots), i32(slots))),
        "prefill": (greedy(net.prefill_fn(dep["kv_dtype"], page)),
                    (i32(1, Tc), jax.ShapeDtypeStruct((1, Tc), jnp.float32),
                     i32(1), i32(1), i32(1)))}
    for name, (fn, rest) in programs.items():
        compiled = fn.lower(*on(one_chip, (params, state, cache) + rest)
                            ).compile()
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == cache_bytes, (name, mem)
        assert total(mem) < HBM, (name, mem)
        # weights 9.84 GB + cache 1.70 GB + under a gigabyte of temporaries
        assert total(mem) < 12.6e9, (name, total(mem))
        assert copies_of(compiled.as_text(), (slots, cap, 512)) == 0, name
