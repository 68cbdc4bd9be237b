"""Single import point for the jax symbols the repo's sharding and Pallas
code depends on (graftlint G007 routes callers here), spelled for the one
installed jax (0.9.0): `jax.shard_map` with `check_vma` / `axis_names`,
`pltpu.CompilerParams`, `lax.pcast`; and the one test by which the
serving kernels (ops/decode_attention, ops/power_retention,
ops/gated_delta, ops/prefill_attention) choose the kernel over their
`jnp` form.
"""

from __future__ import annotations

import jax as _jax
from jax import shard_map as _shard_map
from jax.experimental.pallas import tpu as _pltpu


def shard_map(f, **kwargs):
    """jax.shard_map (`check_vma` opts out of the varying-axis check,
    `axis_names` selects the manual axes)."""
    return _shard_map(f, **kwargs)


def tpu_compiler_params(**kwargs):
    """pltpu.CompilerParams (vmem_limit_bytes etc.)."""
    return _pltpu.CompilerParams(**kwargs)


def pcast_varying(x, axis_names):
    """lax.pcast(x, axis_names, to="varying") — the vma type-system cast."""
    return _jax.lax.pcast(x, axis_names, to="varying")


def on_tpu() -> bool:
    """Whether the default backend is a TPU: the serving kernels run
    compiled there and keep their `jnp` form elsewhere."""
    return _jax.default_backend() == "tpu"
