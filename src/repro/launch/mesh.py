"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count at first backend init — see dryrun.py).

Every axis is ``Auto``: the models place activations with
``with_sharding_constraint`` at the cut points in ``distributed.ctx`` and
leave the rest to the partitioner. ``jax.make_mesh`` otherwise builds
``Explicit`` axes, under which a gather from the vocab-sharded embedding
has no single output sharding.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(devices: int = 8):
    """Small mesh for CI-light dry-run tests (subprocess with fake devs)."""
    return _auto_mesh((devices // 4, 4), ("data", "model"))


def make_single_device_mesh():
    return _auto_mesh((1, 1), ("data", "model"))
