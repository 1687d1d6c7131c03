"""Production mesh construction.

Kept as functions (never module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False, tp: int = 0):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axes: ``model`` is the fast (ICI-contiguous) axis for tensor
    parallelism; ``data`` (and ``pod``) form the FSDP/batch axis group —
    ``pod`` maps to the DCN-connected slow axis in a real deployment,
    which is why gradient compression targets exactly that axis
    (repro.optim.compress).

    Test hook: ``REPRO_MESH_SHAPE`` / ``REPRO_MESH_SHAPE_MULTI`` override
    the shapes (e.g. "2,4" / "2,2,2") so the dry-run *machinery* can be
    exercised with 8 host devices in CI; the production deliverable runs
    unoverridden at 256/512.
    """
    env = os.environ.get(
        "REPRO_MESH_SHAPE_MULTI" if multi_pod else "REPRO_MESH_SHAPE"
    )
    if env:
        shape = tuple(int(x) for x in env.split(","))
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    assert len(shape) == len(axes), (shape, axes)
    if tp:
        # per-arch TP override: same chip count, (…, data·model/tp, tp)
        chips = shape[-1] * shape[-2]
        assert chips % tp == 0, (chips, tp)
        shape = (*shape[:-2], chips // tp, tp)
    return _auto_mesh(shape, axes)


def make_host_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh for tests (host platform devices)."""
    return _auto_mesh(shape, axes)


def single_device_mesh():
    return _auto_mesh((1, 1), ("data", "model"))


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with Auto axes: the sharding rules here are
    propagated by the compiler, not carried in the array types (which
    ``make_mesh``'s default Explicit axes would require)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))
