"""Production mesh definitions (TPU v5e target).

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model").

``make_production_mesh`` is a function (never a module-level constant) so that
importing this module does not touch jax device state.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax


def make_mesh(shape: Sequence[int], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with Auto axes (JAX's default is Explicit): the
    partitioner places whatever the shardings leave open."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_serving_mesh(tp: int = 1, dp: int = 1):
    """Mesh for the sharded paged engine: ("model",) for pure TP, ("data",
    "model") when DP replicas are requested. Fails loudly when the host
    doesn't expose tp*dp devices (force them on CPU with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)."""
    n = len(jax.devices())
    if tp * dp > n:
        raise ValueError(
            f"serving mesh tp={tp} dp={dp} needs {tp * dp} devices, have {n}"
        )
    if dp > 1:
        return make_mesh((dp, tp), ("data", "model"))
    return make_mesh((tp,), ("model",))


def mesh_axis_sizes(mesh) -> Dict[str, int]:
    """Axis name -> size for any mesh built here; round-trips through
    ``make_mesh`` (mesh_axis_sizes(make_mesh(shape, axes)) ==
    dict(zip(axes, shape)))."""
    return dict(zip(mesh.axis_names, mesh.devices.shape))


# v5e hardware constants for the roofline model
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
ICI_LINKS = 4                 # 2D torus: 4 links/chip (v5e)
CHIP_HBM_BYTES = 16 * 2**30   # 16 GiB per chip
