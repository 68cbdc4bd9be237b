"""Direct unit tests for util/compat.py — the single import point for
`shard_map`, `tpu_compiler_params` and `pcast_varying` on the installed
jax. A recording fake stands in for jax.shard_map so the pass-through is
asserted exactly; the rest runs against the real jax."""

import types

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.util import compat


class _FakeMesh:
    axis_names = ("data", "model", "seq")


def _record(calls):
    def fake_shard_map(f, **kwargs):
        calls.append((f, kwargs))
        return f
    return fake_shard_map


def test_shard_map_passes_kwargs_through(monkeypatch):
    calls = []
    monkeypatch.setattr(compat, "_shard_map", _record(calls))
    fn = lambda x: x  # noqa: E731
    compat.shard_map(fn, mesh=_FakeMesh(), check_vma=False,
                     axis_names=("seq",))
    (_f, kwargs), = calls
    assert _f is fn
    assert kwargs["check_vma"] is False
    assert kwargs["axis_names"] == ("seq",)
    assert "check_rep" not in kwargs and "auto" not in kwargs


def test_shard_map_runs_for_real_on_this_jax():
    """Not a fake: the call must be accepted by the installed jax."""
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices("cpu")[:1]), ("d",))
    out = compat.shard_map(
        lambda x: x * 2, mesh=mesh, in_specs=P(), out_specs=P(),
        check_vma=False)(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out), [0.0, 2.0, 4.0, 6.0])


def test_tpu_compiler_params_real_class_accepts_vmem_limit():
    obj = compat.tpu_compiler_params(vmem_limit_bytes=64 * 1024 * 1024)
    assert obj.vmem_limit_bytes == 64 * 1024 * 1024


def test_pcast_varying_calls_pcast(monkeypatch):
    calls = {}

    def fake_pcast(x, axis_names, to):
        calls["args"] = (x, axis_names, to)
        return x

    monkeypatch.setattr(
        compat, "_jax",
        types.SimpleNamespace(lax=types.SimpleNamespace(pcast=fake_pcast)))
    x = jnp.ones((3,))
    assert compat.pcast_varying(x, ("seq",)) is x
    assert calls["args"] == (x, ("seq",), "varying")


def test_module_binds_the_installed_spellings():
    """One installation, no version probe: the module binds jax.shard_map
    and pltpu.CompilerParams directly."""
    from jax.experimental.pallas import tpu as pltpu
    assert compat._shard_map is jax.shard_map
    assert isinstance(compat.tpu_compiler_params(), pltpu.CompilerParams)
    assert not hasattr(compat, "_SHARD_MAP_VMA_KW")
