"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
device initialization.

Axis semantics (see DESIGN.md §5):
  "pod"   : cross-pod data parallelism over per-adapter batch (DCN)
  "data"  : ADAPTER PARALLELISM — each data-rank owns a disjoint slice of
            the adapter slots Z; adapter params/grads/opt-state never cross
            this axis (the paper's rank-local AP)
  "model" : tensor/sequence sharding of the frozen backbone (ICI)
"""
from __future__ import annotations

from typing import Tuple

import jax

from repro.configs.base import MeshConfig

SINGLE_POD = MeshConfig(shape=(16, 16), axes=("data", "model"))
MULTI_POD = MeshConfig(shape=(2, 16, 16), axes=("pod", "data", "model"))


def abstract_mesh(shape: Tuple[int, ...],
                  axes: Tuple[str, ...]) -> "jax.sharding.AbstractMesh":
    """Device-free mesh of the given axis sizes and names (dry-run/spec
    tests lower against it without any devices)."""
    assert len(shape) == len(axes), (shape, axes)
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axes. The sharding rules of this package
    are GSPMD annotations (``launch/partitioning.py``); jax's default
    Explicit axes reject them at the first embedding gather."""
    return jax.make_mesh(shape, axes,
                         (jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    cfg = MULTI_POD if multi_pod else SINGLE_POD
    n = cfg.num_devices
    devices = jax.devices()
    assert len(devices) >= n, (
        f"need {n} devices (run under dryrun.py, which sets "
        f"--xla_force_host_platform_device_count), have {len(devices)}")
    return make_mesh(cfg.shape, cfg.axes, devices=devices[:n])


def make_local_mesh(shape: Tuple[int, ...] = (1, 1),
                    axes: Tuple[str, ...] = ("data", "model")
                    ) -> jax.sharding.Mesh:
    """Tiny mesh over however many devices exist (tests/examples)."""
    return make_mesh(shape, axes)


def mesh_config(mesh: jax.sharding.Mesh) -> MeshConfig:
    return MeshConfig(shape=tuple(mesh.devices.shape),
                      axes=tuple(mesh.axis_names))
