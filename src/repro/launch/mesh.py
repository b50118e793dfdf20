"""Production mesh construction.

Importing this module never touches jax device state — meshes are built by
functions only (the dry-run forces 512 host devices via XLA_FLAGS before
any jax import; tests and benches see the real single device).

Topology (TPU v5e): one pod = 256 chips as a 16x16 mesh
``("data", "model")``; two pods add a leading ``pod`` axis
``(2, 16, 16) = ("pod", "data", "model")``. The ``pod`` axis carries only
data parallelism (per-pod gradient all-reduce crosses the inter-pod links
once per step), composing with ``data`` via the logical ``batch``/``fsdp``
rules.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

SINGLE_POD_SHAPE = (16, 16)
SINGLE_POD_AXES = ("data", "model")
MULTI_POD_SHAPE = (2, 16, 16)
MULTI_POD_AXES = ("pod", "data", "model")

# v5e hardware constants (roofline):
PEAK_FLOPS_BF16 = 197e12          # per chip
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW = 50e9                     # bytes/s per link
HBM_PER_CHIP = 16 * 2**30         # 16 GiB


def _auto_mesh(shape, axes, devices) -> Mesh:
    """The one place meshes are built: every axis is Auto, so GSPMD
    partitions and ``with_sharding_constraint`` accepts the axes
    (``jax.make_mesh`` defaults to Explicit axes since jax 0.9)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = MULTI_POD_SHAPE if multi_pod else SINGLE_POD_SHAPE
    axes = MULTI_POD_AXES if multi_pod else SINGLE_POD_AXES
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "run under launch/dryrun.py (forces 512 host devices)")
    return _auto_mesh(shape, axes, devices[:n])


def make_test_mesh(shape=(2, 4), axes=("data", "model")) -> Mesh:
    """Small mesh for subprocess sharding tests (8 forced host devices)."""
    n = 1
    for s in shape:
        n *= s
    return _auto_mesh(shape, axes, jax.devices()[:n])


def num_chips(mesh: Mesh) -> int:
    return mesh.devices.size


# -- topology geometry (pure; no device access) -----------------------------

#: rows per pod and chips per row in the production topology — the
#: coordinate system ``mesh_slice`` strings ("pod0/rows0-7") address.
ROWS_PER_POD = SINGLE_POD_SHAPE[0]
CHIPS_PER_ROW = SINGLE_POD_SHAPE[1]
NUM_PODS = MULTI_POD_SHAPE[0]


def pod_row_chips(pod: int, row_lo: int, row_hi: int) -> tuple:
    """Flat chip indices of rows ``[row_lo, row_hi]`` (inclusive) of
    ``pod`` in the production topology. Chips are row-major within a pod;
    pods are consecutive ``ROWS_PER_POD * CHIPS_PER_ROW``-chip blocks —
    the same ordering ``make_production_mesh`` lays devices out in, so a
    row range is a contiguous, disjointly-partitionable device span."""
    if not 0 <= pod < NUM_PODS:
        raise ValueError(f"pod {pod} out of range (topology has "
                         f"{NUM_PODS} pods)")
    if not 0 <= row_lo <= row_hi < ROWS_PER_POD:
        raise ValueError(
            f"rows {row_lo}-{row_hi} out of range (each pod has "
            f"{ROWS_PER_POD} rows)")
    base = pod * ROWS_PER_POD * CHIPS_PER_ROW
    return tuple(range(base + row_lo * CHIPS_PER_ROW,
                       base + (row_hi + 1) * CHIPS_PER_ROW))
