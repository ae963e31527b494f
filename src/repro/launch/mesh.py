"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state; ``dryrun.py`` sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before jax init
to obtain 512 placeholder devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with ``Auto`` axes: the partitioner places
    values and ``with_sharding_constraint`` may name any axis (jax 0.9
    defaults new meshes to ``Explicit`` axes, which refuse those
    constraints)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod; 2 pods when ``multi_pod``.

    Axes: (pod,) data x model.  DP spans ("pod", "data"); TP spans
    "model"; SP reuses "data" for batch=1 long-context cells.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (elastic-scaling tests resize DP with this)."""
    return _mesh(shape, axes)


def make_host_mesh():
    """1x1 mesh over the real local device (CPU smoke paths)."""
    return _mesh((1, 1), ("data", "model"))


def make_test_mesh(n: int = 8):
    """(data, model) mesh over ``n`` forced host devices (CPU CI).

    Callers must already run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=<n>`` -- set
    before jax init, which in-process test code cannot do, hence the
    ``run_sharded`` subprocess fixture in ``tests/conftest.py``.
    ``n=1`` degenerates to the host mesh so the same test body runs
    un-forced.
    """
    if n <= 1:
        return make_host_mesh()
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"make_test_mesh({n}) needs {n} devices, have "
            f"{len(jax.devices())}; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before jax init")
    return _mesh((n // 2, 2), ("data", "model"))
