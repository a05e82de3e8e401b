"""Production mesh construction.

Kept as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before
first jax init.

Every mesh is built with ``Auto`` axis types: the model code shards by
``with_sharding_constraint`` hints and GSPMD propagation, which JAX only
accepts on ``Auto`` axes (``jax.make_mesh`` defaults to ``Explicit``).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], devices=None):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """The assignment's production mesh: 16×16 single-pod (256 chips) or
    2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Optional[Tuple[str, ...]] = None,
              devices=None):
    """General mesh builder for planner-chosen shapes.  ``devices``
    defaults to ``jax.devices()``; pass described (topology) devices to
    compile for a chip that is not attached."""
    if axes is None:
        axes = {
            1: ("data",),
            2: ("data", "model"),
            3: ("pod", "data", "model"),
        }[len(shape)]
    return _auto_mesh(shape, axes, devices)


def local_mesh():
    """Single-device mesh with the production axis names (CPU paths)."""
    return _auto_mesh((1, 1), ("data", "model"))


def _largest_divisor_at_most(n: int, cap: int) -> int:
    best = 1
    for c in range(1, min(n, cap) + 1):
        if n % c == 0:
            best = c
    return best


def mesh_for_placement(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """A planned mesh folded onto the locally visible devices.

    Keeps the plan's axis *names* (so sharding specs resolve unchanged)
    but clamps each dimension so the product fits ``jax.device_count()``
    — on a 1-device CPU container every planned mesh degenerates to all
    1s; on a real slice whose device count matches, the planned shape is
    used as-is.  Later axes (model/tensor) get first claim on devices so
    the clamped mesh preserves the plan's innermost parallelism."""
    n = jax.device_count()
    want = 1
    for d in shape:
        want *= d
    if want <= n:
        return _auto_mesh(shape, axes)
    dims = [1] * len(shape)
    rem = n
    for i in range(len(shape) - 1, -1, -1):
        dims[i] = _largest_divisor_at_most(rem, shape[i])
        rem //= dims[i]
    return _auto_mesh(tuple(dims), axes)
