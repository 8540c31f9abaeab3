"""Device layouts a TPU DMA can address without a copy.

A DMA from HBM moves whole (8, 128) tiles of a 2-D array, so the
retrieval kernels read three views of the resident tables:

  * row tiles ``[N/8, 8, D]`` of a row table ``[N, D]`` (``row_tiles``):
    a kernel fetches the aligned 8-row tile that holds a row;
  * lane rows ``[N/128, 128]`` of a per-row scalar table ``[N]``
    (``lane_rows``): the int8 codec's decode scales;
  * packed rows ``[N/R, 128]`` of a narrow ``[N, W]`` int table
    (``PackedRows``): the layer-0 adjacency, R = 128 // next_pow2(W)
    rows per 128-lane row. Unpacked, an ``[N, 10]`` int32 table would sit
    lane-padded to 128 lanes per row in HBM.

Each view is a bitcast only when N is a multiple of its tile. The owner
of a resident table (``core.hnsw.DeviceGraph``) therefore sizes it at
``device_capacity(n)`` rows and keeps the adjacency packed, so a search
passes its kernels tables that need no copy. Kernels still accept any N
(the tests' small odd shapes); then the view pads, which costs a copy of
the table on every call.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

ROW_TILE = 8           # rows per HBM tile
LANES = 128            # lanes per HBM tile
CAPACITY_ALIGN = ROW_TILE * LANES   # whole tiles in all three views


def next_pow2(x: int) -> int:
    return 1 << (max(int(x), 1) - 1).bit_length()


def device_capacity(n: int) -> int:
    """Rows of a resident table that holds ``n``: ``n`` rounded up to a
    multiple of 1024, so that its row tiles, lane rows and packed rows
    are all bitcasts (module docstring). Costs at most 1023 rows."""
    return max(-(-int(n) // CAPACITY_ALIGN), 1) * CAPACITY_ALIGN


def row_tiles(vectors: jax.Array) -> jax.Array:
    """[N, D] -> [ceil(N/8), 8, D]: the DMA-able view of a row table. A
    bitcast when N % 8 == 0; otherwise the tail tile is zero-padded (a
    copy of the table)."""
    n, d = vectors.shape
    n8 = -(-n // ROW_TILE) * ROW_TILE
    if n8 > n:
        vectors = jnp.concatenate(
            [vectors, jnp.zeros((n8 - n, d), vectors.dtype)])
    return vectors.reshape(n8 // ROW_TILE, ROW_TILE, d)


def lane_rows(x: jax.Array) -> jax.Array:
    """[N] -> [ceil(N/1024)·8, 128]: a per-row scalar table as 128-lane
    rows a DMA can fetch. A bitcast of the 1-D layout when N % 1024 == 0;
    otherwise a padded copy."""
    n = x.shape[0]
    n_p = -(-n // CAPACITY_ALIGN) * CAPACITY_ALIGN
    if n_p > n:
        x = jnp.pad(x, (0, n_p - n))
    return x.reshape(n_p // LANES, LANES)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PackedRows:
    """A narrow [N, W] int32 table stored as [ceil(N/R), 128] rows, R =
    128 // lanes rows of ``lanes`` = next_pow2(W) slots each: row r is
    lanes [(r % R)·lanes, +W) of packed row r // R, and the slots past W
    hold the fill. ``width`` is static."""
    table: jax.Array        # [ceil(N/R), 128] int32
    width: int              # W

    def tree_flatten(self):
        return (self.table,), (self.width,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux[0])

    @property
    def lanes(self) -> int:
        return next_pow2(self.width)

    @property
    def per(self) -> int:
        return LANES // self.lanes

    @property
    def rows(self) -> int:
        """Row capacity (a multiple of R)."""
        return self.table.shape[0] * self.per

    def _slots(self, ids):
        ids = jnp.asarray(ids, jnp.int32)
        lane = ((ids % self.per) * self.lanes)[..., None] \
            + jnp.arange(self.width, dtype=jnp.int32)
        return ids // self.per, lane

    def take(self, ids) -> jax.Array:
        """Rows ``ids`` (any shape, in range) -> [..., W]."""
        row, lane = self._slots(ids)
        return jnp.take_along_axis(jnp.take(self.table, row, axis=0), lane,
                                   axis=-1)

    def set_rows(self, ids, values) -> "PackedRows":
        """Rows ``ids`` [K] replaced by ``values`` [K, W] (repeated ids
        must carry equal values)."""
        row, lane = self._slots(ids)
        table = self.table.at[row[:, None], lane].set(
            jnp.asarray(values, self.table.dtype))
        return PackedRows(table, self.width)


def pack_rows(table, fill: int = -1) -> PackedRows:
    """[N, W] (numpy or jax) -> PackedRows, the same array kind. Built
    from R strided row slices side by side, so XLA never holds the table
    at a lane-padded width."""
    xp = np if isinstance(table, np.ndarray) else jnp
    n, w = table.shape
    lanes = next_pow2(w)
    assert lanes <= LANES, f"row width {w} exceeds {LANES} lanes"
    per = LANES // lanes
    n_p = -(-n // per) * per
    if n_p > n:
        table = xp.concatenate(
            [table, xp.full((n_p - n, w), fill, table.dtype)])
    parts = []
    for s in range(per):
        parts.append(table[s::per])
        if lanes > w:
            parts.append(xp.full((n_p // per, lanes - w), fill, table.dtype))
    return PackedRows(xp.concatenate(parts, axis=1), w)


def as_packed(table) -> PackedRows:
    """A PackedRows as it is; a dense [N, W] table packed (a copy)."""
    if isinstance(table, PackedRows):
        return table
    return pack_rows(jnp.asarray(table, jnp.int32))


def take_rows(table, ids) -> jax.Array:
    """Rows ``ids`` of a dense [N, W] table or a PackedRows -> [..., W]."""
    if isinstance(table, PackedRows):
        return table.take(ids)
    return jnp.take(table, ids, axis=0)


def row_width(table) -> int:
    return table.width if isinstance(table, PackedRows) else table.shape[1]
