"""`qwen3-next-80b-a3b-ep32-l8`'s serving programs at the published widths,
compiled for a described v5e (no chip attached) as `_GenWorker` jits them:
the decode step over 64 slots, with the `gated_delta_decode` and
`gqa_decode` kernels as on the chip, and the largest prefill program, the
1,024-token chunk of a prompt that may be 32,768 long, with `gqa_prefill`.
Each has to fit the chip's 16 GB beside the 1.54 GB of weights and donate
the whole 10.49 GB of cache, so that a step holds ONE copy of it: a state
is written in place by its kernel, a full entry's rows by their scatter,
and no array of an entry's size is copied. Slow (a minute): not in the
repo's tier-1 run. The topology is test_chip_fit.py's fixture.
"""
import re

import jax
import jax.numpy as jnp
from test_chip_fit import HBM, built, load, on, one_chip, total  # noqa: F401


def copies_of(text: str, dtype: str, shape: tuple) -> int:
    """`copy` instructions of the compiled program whose result has that
    shape."""
    dims = ",".join(str(d) for d in shape)
    return len(re.findall(rf"= {dtype}\[{dims}\]\S* copy\(", text))


def test_server_programs_fit_and_hold_one_copy_of_the_cache(one_chip,
                                                            monkeypatch):
    from deeplearning4j_tpu.ops import (
        decode_attention,
        gated_delta,
        prefill_attention,
    )

    # compiled for the chip, the steps take the kernels
    for mod, name in ((decode_attention, "_use_kernel"),
                      (gated_delta, "_use_kernel"),
                      (prefill_attention, "use_kernel")):
        monkeypatch.setattr(mod, name, lambda: True)
    config = load("configs/qwen3-next-80b-a3b-ep32-l8.json")
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
        == 10_487_857_152
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
        # weights 1.54 GB + cache 10.49 GB + under 2.5 GB of temporaries
        assert total(mem) < 14.5e9, (name, total(mem))
        assert copies_of(text, "f32", (slots, 32, 128, 128)) == 0, name
        assert copies_of(text, "bf16", (slots, 2, cap, 256)) == 0, name
        assert (text.count("gated_delta_decode") > 0) == (name == "decode")
        assert (text.count("gqa_decode") > 0) == (name == "decode")
        assert (text.count("gqa_prefill") > 0) == (name == "prefill")
