"""`trinity-large-preview-ep32-l8`'s serving programs at the published
widths, compiled for a described v5e (no chip attached) as `_GenWorker`
jits them: the decode step over 32 slots, with the `gqa_decode` kernel as
on the chip, and the largest prefill program, the 1,024-token chunk. Each
has to fit the chip's 16 GB beside the 5.12 GB of weights and donate the
whole 7.78 GB of cache, so that a step holds ONE copy of it: a ring's and
a full entry's rows are written in place and no array of an entry's size
is copied (a chunk reads the ring before it writes into it: the compiler
must order the two, not copy). Slow (a minute): not in the repo's tier-1
run. The topology is test_chip_fit.py's fixture.
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


def test_server_programs_fit_and_hold_one_copy_of_the_cache(one_chip,
                                                            monkeypatch):
    from deeplearning4j_tpu.ops import decode_attention

    # compiled for the chip, the decode step takes the kernel
    monkeypatch.setattr(decode_attention, "_use_kernel", lambda: True)
    config = load("configs/trinity-large-preview-ep32-l8.json")
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
    assert cache_bytes == slots * family.cache_bytes_per_slot(dims, cap) \
        == 7_784_628_224
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
                   (i32(slots), i32(slots),
                    jax.ShapeDtypeStruct((slots,), jnp.bool_))),
        "prefill": (greedy(net.prefill_fn(dep["kv_dtype"], page)),
                    (i32(1, Tc), jax.ShapeDtypeStruct((1, Tc), jnp.float32),
                     i32(1), i32(1), i32(1)))}
    for name, (fn, rest) in programs.items():
        compiled = fn.lower(*on(one_chip, (params, state, cache) + rest)
                            ).compile()
        mem, text = compiled.memory_analysis(), compiled.as_text()
        assert mem.alias_size_in_bytes == cache_bytes, (name, mem)
        assert total(mem) < HBM, (name, mem)
        # weights 5.12 GB + cache 7.78 GB + under 2 GB of temporaries
        assert total(mem) < 14.9e9, (name, total(mem))
        for rows in (4096, cap):
            assert copies_of(text, (slots, 8, rows, 128)) == 0, (name, rows)
        assert (text.count("gqa_decode") > 0) == (name == "decode")
