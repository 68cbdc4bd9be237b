"""`ouro-2.6b`'s serving programs at the published widths, compiled for a
described v5e (no chip attached) as `_GenWorker` jits them: the decode
step over 4 slots and the 256-token prefill chunk, with `gqa_decode` and
`gqa_prefill` as on the chip. The 48 blocks run 4 times a token in ONE
loop over the pass, so the program holds one copy of the body: 48 calls
of its kernel, not 192. Each program has to fit the chip's 16 GB beside
the 5.34 GB of weights and donate the whole 8.05 GB of cache (4 passes x
48 layers of rows), so that a step holds ONE copy of it: the carried
entries are written in place and no array of an entry's size is copied.
Slow (minutes): not in the repo's tier-1 run. The topology is
test_chip_fit.py's fixture.
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
    from deeplearning4j_tpu.ops import decode_attention, prefill_attention

    # compiled for the chip, the steps take the kernels
    monkeypatch.setattr(decode_attention, "_use_kernel", lambda: True)
    monkeypatch.setattr(prefill_attention, "use_kernel", lambda: True)
    config = load("configs/ouro-2.6b.json")
    dep = config["deployment"]
    family, dims, net, params, _opt = built(config, "serving_net")
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == family.count_params(dims) == 2_667_972_608
    state = {n: {} for n in params}
    page, slots = dep["page_size"], dep["slots"]
    cap = max(dep["prefill_seq_lens"]) + dep["max_new_tokens"]
    cache = jax.eval_shape(lambda: net.init_kv_cache(
        slots, cap, dep["kv_dtype"], page))
    cache_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    assert cache_bytes == slots * family.cache_bytes_per_slot(dims, cap) \
        == 8_053_063_680
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
                    jax.ShapeDtypeStruct((slots,), jnp.bool_)), "gqa_decode"),
        "prefill": (greedy(net.prefill_fn(dep["kv_dtype"], page)),
                    (i32(1, Tc), jax.ShapeDtypeStruct((1, Tc), jnp.float32),
                     i32(1), i32(1), i32(1)), "gqa_prefill")}
    for name, (fn, rest, kernel) in programs.items():
        compiled = fn.lower(*on(one_chip, (params, state, cache) + rest)
                            ).compile()
        mem, text = compiled.memory_analysis(), compiled.as_text()
        assert mem.alias_size_in_bytes == cache_bytes, (name, mem)
        assert total(mem) < HBM, (name, mem)
        # weights 5.34 GB + cache 8.05 GB + under 1.5 GB of temporaries
        assert total(mem) < 14.9e9, (name, total(mem))
        assert copies_of(text, "bf16", (slots, 4, 16, cap, 128)) == 0, name
        assert copies_of(text, "bf16", (slots, 16, cap, 128)) == 0, name
        calls = re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*", text)
        assert len(calls) == 48, (name, len(calls))
        assert all(kernel in c for c in calls), name
