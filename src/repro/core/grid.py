"""3D process grid for SUMMA (paper §III-B).

A grid is `pr × pc × l` with mesh axes ("gr", "gc", "gl"): process rows,
process columns, layers. `P(:,:,k)` is layer k (a 2D SUMMA grid), and
`P(i,j,:)` is a *fiber* (AllToAll-Fiber runs along it).

The paper uses square per-layer grids (pr == pc == sqrt(p/l)); we enforce the
same. The production mapping folds the training mesh axes onto the grid:
("data" → gr, "model" → gc, "pod" → gl).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

ROW_AX, COL_AX, LAYER_AX = "gr", "gc", "gl"


@dataclasses.dataclass(frozen=True)
class Grid:
    mesh: jax.sharding.Mesh
    pr: int
    pc: int
    l: int

    @property
    def p(self) -> int:
        return self.pr * self.pc * self.l

    @property
    def axis_names(self) -> Tuple[str, str, str]:
        return (ROW_AX, COL_AX, LAYER_AX)

    def tile_sharding(self) -> NamedSharding:
        """Sharding for (pr, pc, l, ...) stacked per-tile arrays."""
        return NamedSharding(self.mesh, P(ROW_AX, COL_AX, LAYER_AX))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())


def make_grid(pr: int, pc: int, l: int, devices: Optional[Sequence] = None) -> Grid:
    """Build a pr×pc×l grid mesh. Layers must be square (pr == pc) or the
    grid single-layer (l == 1): rectangular per-layer grids only align the
    contraction slices when there is one layer (see host_symbolic_counts)."""
    assert pr == pc or l == 1, \
        f"need square per-layer grids or l == 1, got {pr}x{pc}x{l}"
    ndev = pr * pc * l
    if devices is None:
        devices = jax.devices()[:ndev]
    assert len(devices) >= ndev, f"need {ndev} devices, have {len(devices)}"
    import numpy as np

    dev_array = np.asarray(devices[:ndev]).reshape(pr, pc, l)
    mesh = Mesh(
        dev_array,
        (ROW_AX, COL_AX, LAYER_AX),
        axis_types=(AxisType.Auto,) * 3,
    )
    return Grid(mesh, pr, pc, l)


def grid_from_mesh(
    mesh: jax.sharding.Mesh,
    row_axis: str = "data",
    col_axis: str = "model",
    layer_axis: Optional[str] = "pod",
) -> Grid:
    """Reinterpret a training mesh as a SUMMA grid (production path).

    A single-pod ("data", "model") mesh becomes an l=1 grid; a multi-pod
    ("pod", "data", "model") mesh maps pods to layers — the communication-
    avoiding dimension spans the slowest links, which is exactly where the
    paper's analysis says replication pays off (broadcasts shrink by sqrt(l)
    within pods; only the fiber all-to-all crosses pods).
    """
    names = mesh.axis_names
    sizes = dict(zip(names, mesh.devices.shape))
    pr, pc = sizes[row_axis], sizes[col_axis]
    l = sizes.get(layer_axis, 1) if layer_axis else 1
    assert pr == pc, f"square per-layer grid required, got {pr}x{pc}"
    # reorder devices to (gr, gc, gl)
    perm = [names.index(row_axis), names.index(col_axis)]
    if layer_axis and layer_axis in names:
        perm.append(names.index(layer_axis))
        dev = mesh.devices.transpose(perm)
    else:
        dev = mesh.devices.transpose(perm)[..., None]
    new_mesh = Mesh(
        dev, (ROW_AX, COL_AX, LAYER_AX), axis_types=(AxisType.Auto,) * 3
    )
    return Grid(new_mesh, pr, pc, l)


def square_grid_for(p: int, l: int) -> Tuple[int, int, int]:
    """Paper's grid shape: sqrt(p/l) × sqrt(p/l) × l."""
    side = math.isqrt(p // l)
    assert side * side * l == p, f"p={p} not expressible as s*s*{l}"
    return side, side, l
