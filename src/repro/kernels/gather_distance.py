"""Fused gather(HBM)→VMEM + distance kernel — MeMemo's prefetch (C2) on TPU.

HNSW frontier expansion reads K graph-neighbor vectors per query and scores
them against the query. The browser version amortises IndexedDB transactions
by prefetching ``p`` neighbors per miss; here the analogue is batched async
DMA: the database stays in HBM (``memory_space=ANY``), all K fetches of the
next query are in flight while the current query's distances compute.

Row tiles. A TPU DMA slices an (8, 128)-tiled HBM array only at whole
tiles, so one row of [N, D] cannot be fetched alone. Each candidate
fetches the aligned 8-row tile that holds it (``layout.row_tiles``: the
[N/8, 8, D] view, a bitcast when N % 8 == 0, as the resident graph's
capacity always is), the distance runs on all 8 rows, and a sublane mask
picks row ``id % 8`` (``tile_distance``). The DMA moves 8x
the row bytes; the kernel is bound by DMA latency, not bytes.

Codec-encoded databases (DESIGN.md §9): ``vectors`` may be any dtype the
codec emits (f32 / bf16 / int8) — the scratch buffer matches it. When a
per-row ``scales`` [N] f32 table is passed, XLA gathers the [B, K]
candidate scales up front (scalars into SMEM) and the decode
(``row · scale`` in f32) fuses into the distance — the asymmetric-distance
contract: fp32 query vs encoded rows, fp32 accumulation.

Shapes / dtypes
  vectors [N, D]  f32 / bf16 / int8 (stays in HBM — ``memory_space=ANY``;
                  scratch matches it, distances compute in f32)
  q       [B, D]  f32
  ids     [B, K]  i32 row ids into ``vectors`` (callers pre-clip to
                  [0, N); invalid slots are masked AFTER the kernel)
  scales  [N] f32 optional per-row decode scales (int8 codec)
  ->      dists [B, K] f32  (cosine/ip: 1 - <q, x>; l2: squared distance)

Grid / block layout
  grid = (ceil(B / block_q),): one step per query block (the batch is
  padded, never the block shrunk). Per step the ids [BQ, K] (and the
  gathered scales) sit in SMEM, where they address the DMAs, and the q
  tile [BQ, D] in VMEM. Scratch [2K, 8, D] + 2 DMA semaphores double-buffer
  the row tiles across the block's queries; each query's K distances are
  assembled on one lane row and stored once.

Platforms
  ``interpret=None`` resolves platform-aware (kernels.resolve_interpret):
  the compiled kernel on TPU, always; the Pallas interpreter elsewhere.
  ``ops.gather_distance`` runs this kernel on every TPU call. Off-TPU it
  runs the jnp oracle ``ref.gather_distance_ref`` — ``take`` + fused dot,
  same results — unless REPRO_PALLAS=interpret. The HNSW search
  (core/hnsw.py) layers its own -1-padding mask on top either way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.layout import ROW_TILE, row_tiles


def tile_distance(metric: str, qv, tile, row, scale=None):
    """Distance from query row ``qv`` [1, D] to row ``row % 8`` of one
    fetched tile [8, D] -> [1, 1] f32. The decode (``row · scale``) and
    the distance run on all 8 rows, then a sublane mask picks the row."""
    x = tile.astype(jnp.float32)
    if scale is not None:
        x = x * scale                                     # fused decode
    if metric in ("cosine", "ip"):
        col = 1.0 - jnp.sum(qv * x, axis=1, keepdims=True)
    else:
        col = jnp.sum((x - qv) ** 2, axis=1, keepdims=True)
    sub = jax.lax.broadcasted_iota(jnp.int32, col.shape, 0)
    return jnp.sum(jnp.where(sub == row % ROW_TILE, col, 0.0), axis=0,
                   keepdims=True)


def _kernel(metric: str, has_scales: bool, *refs):
    if has_scales:
        ids_ref, scl_ref, q_ref, db_ref, out_ref, buf, sems = refs
    else:
        ids_ref, q_ref, db_ref, out_ref, buf, sems = refs
        scl_ref = None
    bq, k = out_ref.shape
    n_tiles = db_ref.shape[0]

    def copy(b, c, slot):
        tile = jnp.clip(ids_ref[b, c] // ROW_TILE, 0, n_tiles - 1)
        return pltpu.make_async_copy(db_ref.at[tile], buf.at[slot * k + c],
                                     sems.at[slot])

    def issue(b, slot):
        def one(c, _):
            copy(b, c, slot).start()
            return 0
        jax.lax.fori_loop(0, k, one, 0)

    issue(0, 0)

    def per_query(b, _):
        slot = b % 2

        @pl.when(b + 1 < bq)
        def _():
            issue(b + 1, 1 - slot)

        qv = q_ref[pl.ds(b, 1), :].astype(jnp.float32)       # [1, D]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

        def one(c, row_d):
            copy(b, c, slot).wait()
            scale = None if scl_ref is None else scl_ref[b, c]
            d = tile_distance(metric, qv, buf[slot * k + c], ids_ref[b, c],
                              scale)
            return jnp.where(lane == c, d, row_d)

        out_ref[pl.ds(b, 1), :] = jax.lax.fori_loop(
            0, k, one, jnp.zeros((1, k), jnp.float32))
        return 0

    jax.lax.fori_loop(0, bq, per_query, 0)


@functools.partial(jax.jit, static_argnames=("metric", "block_q",
                                             "interpret"))
def _call(vectors, q, ids, scales, metric, block_q, interpret):
    b, k = ids.shape
    d = q.shape[1]
    block_q = min(block_q, b)
    pb = -(-b // block_q) * block_q
    if pb > b:                   # pad the batch, never shrink the block
        q = jnp.concatenate([q, jnp.zeros((pb - b, d), q.dtype)])
        ids = jnp.concatenate([ids, jnp.zeros((pb - b, k), ids.dtype)])
    has_scales = scales is not None
    smem = pltpu.SMEM
    in_specs = [pl.BlockSpec((block_q, k), lambda i: (i, 0),
                             memory_space=smem)]                 # ids
    args = [ids.astype(jnp.int32)]
    if has_scales:
        # per-candidate decode scales, gathered by XLA: [B, K] scalars
        in_specs.append(pl.BlockSpec((block_q, k), lambda i: (i, 0),
                                     memory_space=smem))
        g = jnp.take(scales.astype(jnp.float32),
                     jnp.clip(ids, 0, vectors.shape[0] - 1))
        args.append(g)
    in_specs += [
        pl.BlockSpec((block_q, d), lambda i: (i, 0)),                # q
        pl.BlockSpec(memory_space=pl.ANY),                           # db
    ]
    args += [q, row_tiles(vectors)]
    out = pl.pallas_call(
        functools.partial(_kernel, metric, has_scales),
        grid=(pb // block_q,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_q, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((pb, k), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2 * k, ROW_TILE, d), vectors.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
    )(*args)
    return out[:b]


def gather_distance_pallas(vectors: jax.Array, q: jax.Array, ids: jax.Array,
                           *, metric: str = "cosine",
                           scales: jax.Array | None = None,
                           block_q: int = 8,
                           interpret: bool | None = None) -> jax.Array:
    """vectors [N,D] (HBM, any codec dtype) + optional scales [N], q [B,D],
    ids [B,K] -> dists [B,K] f32. ``interpret=None`` resolves
    platform-aware."""
    return _call(vectors, q, ids, scales, metric, block_q,
                 resolve_interpret(interpret))
