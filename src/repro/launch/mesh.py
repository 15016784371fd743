"""Production mesh construction (TPU v5e-class pods).

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets the fake device count before
any jax initialization).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """THE mesh constructor: every axis Auto-typed.

    ``jax.make_mesh`` defaults to Explicit axes, under which the model's
    ``with_sharding_constraint`` calls and gathers are refused; the train
    step's shard_map regions expect the auto (SPMD) model axis."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# Hardware constants for the roofline (TPU v5e-class target).
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link (per direction), ~4 links/chip usable
ICI_LINKS = 4
DCN_BW = 6.25e9  # inter-pod bytes/s per chip (25 GbE-class share x2)

def topology_from_mesh(mesh, *, reduce_axes=("data", "pod"),
                       scarce_budget_bytes: float = float("inf")):
    """The mesh as a scheduler `Topology` (DESIGN.md §3).

    ``scarce_budget_bytes`` bounds the bytes one exchange round may put on
    the scarcest (inter-pod) level across ALL concurrent jobs.  Per-axis
    bandwidths come from the canonical table in ``core/tree.py``
    (`Topology.from_mesh`'s default).
    """
    from repro.core.planner import Topology

    return Topology.from_mesh(mesh, reduce_axes=reduce_axes,
                              scarce_budget_bytes=scarce_budget_bytes)
