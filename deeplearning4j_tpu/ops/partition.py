"""Run the Pallas kernels per device under a jit that spans devices.

GSPMD cannot partition a Mosaic kernel: lowering one under a jit over
more than one TPU device raises "Mosaic kernels cannot be automatically
partitioned. Please wrap the call in a shard_map." (Off-TPU the kernels
run in interpret mode, which is plain XLA, so virtual-device CPU runs
never see this.) The GSPMD train step (nn/training.make_train_step)
names its mesh here while it traces, and the kernel entry points go
through `rows_per_device`: a shard_map over the operands' leading dim.
Attention batch rows and xent tokens are independent, so every mesh axis
can share them — no head or vocab sharding inside the kernel, no
collective beyond the resharding GSPMD inserts at the boundary.

Without an active mesh (one device, or a step that is already manual
over every axis) the call is the plain function call.
"""

from __future__ import annotations

import contextlib
import threading

from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.util.compat import shard_map

_active = threading.local()


@contextlib.contextmanager
def kernel_mesh(mesh):
    """Name the mesh the enclosing jit spans, for the duration of a
    trace. None or a one-device mesh: kernels are called directly."""
    prev = getattr(_active, "mesh", None)
    _active.mesh = mesh if mesh is not None and mesh.size > 1 else None
    try:
        yield
    finally:
        _active.mesh = prev


def _row_axes(mesh, n_rows: int) -> tuple:
    """Mesh axes (in mesh order) whose joint size divides n_rows."""
    axes, size = [], 1
    for name in mesh.axis_names:
        if n_rows % (size * mesh.shape[name]) == 0:
            axes.append(name)
            size *= mesh.shape[name]
    return tuple(axes)


def rows_per_device(fn, rows, replicated=()):
    """fn(*rows, *replicated) — under an active mesh, as a shard_map in
    which every `rows` operand (and every output) is split on dim 0 over
    the mesh axes that divide it, and the `replicated` operands are
    whole on every device. Axes that do not divide the row count
    compute redundantly."""
    mesh = getattr(_active, "mesh", None)
    if mesh is None:
        return fn(*rows, *replicated)
    n_rows = {a.shape[0] for a in rows}
    if len(n_rows) != 1:
        raise ValueError(f"row-wise operands disagree on dim 0: {n_rows}")
    axes = _row_axes(mesh, n_rows.pop())
    split = P(axes) if axes else P()
    return shard_map(
        fn, mesh=mesh,
        in_specs=(split,) * len(rows) + (P(),) * len(replicated),
        out_specs=split, check_vma=False)(*rows, *replicated)
