"""Blocked distance-matrix kernel with a running top-k (flat exact search).

The flat-index hot loop (and the recsys ``retrieval_cand`` cell): score a
query block against the whole database and keep the k best.

  grid (B tiles x N tiles), the db axis sequential: each step loads a
    [BQ, D] query tile and a [BN, D] database tile into VMEM (BlockSpec)
    and computes the [BQ, BN] distance tile on the MXU. The query tile's
    running top-k lives in its output block, which stays resident across
    the db axis: filled with (+inf, in-range id) at the first db tile,
    then each tile runs extraction passes (min/where/iota only —
    Mosaic-safe) only while some row has a distance strictly below its
    running k-th best, which it puts in place of that row's worst entry.
    A pass that finds no such row ends the tile; so does the k-th pass.
  the wrapper sorts the [B, k] result ascending by (distance, id) in the
    same jit. There are no per-tile partials and no merge.

Once the first tiles have filled the running lists, a tile rarely holds
more than a few rows that beat them, so most tiles run one or two passes
in place of k. The worst case, a tile that improves every row k times,
runs k passes, as one extraction per slot would. Strict ``<`` keeps the
ordering of ``lax.top_k``: a distance equal to a row's k-th best, in a
later tile and so at a higher id, never displaces it; within a tile the
first argmin enters first, and of equal worst entries the higher id is
the one evicted. ``distance_topk_passes`` runs the same body with a
third output, the passes each (query tile, db tile) ran.

MXU alignment: D and BN should be multiples of 128 for peak; the kernel is
shape-generic. The grids are ceil-divisions: the query batch is padded to
the block multiple (padded rows score +inf and are sliced off the output),
and the last database tile may run past N, where the kernel masks the
rows to +inf (they can never enter the top-k). The database itself is
never copied to pad it. Earlier versions instead SHRANK block_q/block_n to
the largest divisor, which degenerates to 1-row blocks (a B×N program
grid) whenever B or N is prime — the regression test at N=997, B=7 in
tests/test_kernels.py pins the fix.

Codec-encoded databases (DESIGN.md §9): ``db`` may be any dtype the codec
emits (f32 / bf16 / int8); rows are cast to f32 in-kernel and, when a
``scales`` [N] table is passed, each score column is multiplied by its
row's scale (q·(s x) = s (q·x)): the fused decode-distance (asymmetric:
fp32 query vs encoded rows, fp32 accumulation on the MXU at full f32
precision). The scales arrive as a [1, N] lane row, since an [N, 1]
operand would sit padded to 128 lanes per row in HBM.

Shapes / dtypes
  db     [N, D]  any float/int8 dtype (cast to f32 in-kernel)
  q      [B, D]  f32
  scales [N] f32 optional per-row decode scales (int8 codec)
  valid  [N] bool optional row mask: rows where it is False score +inf,
         as padding does (free slots of a sharded block, DESIGN.md §8)
  ->     dists [B, k] f32 ascending, ids [B, k] i32 — the final top-k.
         A row with fewer than k live db rows ends in (+inf, id) slots
         whose ids are in range but name no live row.

Grid / block layout
  grid = (ceil(B / block_q), ceil(N / block_n)); block (i, j) loads q
  tile i and db tile j via BlockSpec (automatic HBM->VMEM pipelining).
  The two outputs are [1, B, k] arrays with one (1, block_q, k) block per
  query tile, at (0, i, 0) for every j: a k-wide block is the whole minor
  dim, which the TPU block-shape rule accepts for any k, and the block
  index does not change along j, so the block stays in VMEM until the
  query tile is done.

Platforms
  ``interpret=None`` resolves platform-aware (kernels.resolve_interpret):
  the compiled kernel on TPU, always; the Pallas interpreter elsewhere.
  ``ops.flat_topk`` runs this kernel on every TPU call. Off-TPU it uses
  the jnp oracle ``ref.distance_topk_ref`` — one [B, N] distance matrix +
  ``lax.top_k``, numerically identical — unless REPRO_PALLAS=interpret.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import dispatch
from repro.kernels import resolve_interpret

BIG = 3.0e38   # plain float: pallas kernels must not capture traced constants
# full-f32 contraction on the MXU: the distances agree with an f32 host
# oracle instead of carrying bf16 input rounding
_HIGHEST = jax.lax.Precision.HIGHEST
# the pass-count output's block: one (8, 128) tile per grid step, the
# smallest block the TPU block-shape rule takes
_TILE = (8, 128)


def _kernel(metric: str, k: int, n_total: int, b_total: int,
            has_scales: bool, has_valid: bool, count: bool, *refs):
    refs = list(refs)
    q_ref, db_ref = refs[:2]
    s_ref = refs.pop(2) if has_scales else None
    v_ref = refs.pop(2) if has_valid else None
    dist_ref, idx_ref = refs[2:4]
    i, j = pl.program_id(0), pl.program_id(1)
    bq, bn = q_ref.shape[0], db_ref.shape[0]
    q = q_ref[...].astype(jnp.float32)                    # [BQ, D]
    x = db_ref[...].astype(jnp.float32)                   # [BN, D]
    dims = (((1,), (1,)), ((), ()))
    scores = jax.lax.dot_general(q, x, dims, precision=_HIGHEST,
                                 preferred_element_type=jnp.float32)
    if s_ref is not None:
        # decode: q . (s_n x_n) == s_n (q . x_n); [1, BN] lane row
        scores = scores * s_ref[...]
    if metric in ("cosine", "ip"):
        d = 1.0 - scores                                  # [BQ, BN]
    else:
        qn = jnp.sum(q * q, axis=1, keepdims=True)        # [BQ, 1]
        # row norms as a lane row [1, BN] (a ones-row contraction keeps
        # the result on lanes, where the distance tile needs it)
        xn = jax.lax.dot_general(jnp.ones((1, x.shape[1]), jnp.float32),
                                 x * x, dims, precision=_HIGHEST,
                                 preferred_element_type=jnp.float32)
        if s_ref is not None:
            xn = xn * (s_ref[...] * s_ref[...])
        d = qn - 2.0 * scores + xn
    col = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    base = j * bn
    # mask db PADDING rows (global id >= N): they can never enter
    keep = col + base < n_total
    if v_ref is not None:
        keep = keep & (v_ref[...] != 0)          # caller's row mask [1, BN]
    if b_total % bq:
        # padded query rows: +inf everywhere, so they never drive a pass
        row = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
        keep = keep & (row + i * bq < b_total)
    d = jnp.where(keep, d, BIG)

    @pl.when(j == 0)
    def _fill():
        dist_ref[0] = jnp.full((bq, k), BIG, jnp.float32)
        idx_ref[0] = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1)

    def go_on(carry):
        passes, more = carry[:2]
        return (more > 0) & (passes < k)

    def extract(carry):
        passes, _, d, run_d, run_i = carry
        m = jnp.min(d, axis=1, keepdims=True)             # [BQ, 1]
        pos = jnp.min(jnp.where(d == m, col, jnp.int32(2 ** 30)),
                      axis=1, keepdims=True)              # first argmin
        kth = jnp.max(run_d, axis=1, keepdims=True)       # running k-th
        # the worst slot: the k-th distance, and of equals the higher id
        worst = jnp.max(jnp.where(run_d == kth, run_i, -1), axis=1,
                        keepdims=True)
        enter = m < kth                                   # [BQ, 1]
        slot = enter & (run_d == kth) & (run_i == worst)  # [BQ, k]
        run_d = jnp.where(slot, m, run_d)
        run_i = jnp.where(slot, pos + base, run_i)
        d = jnp.where(col == pos, BIG, d)
        more = jnp.max(enter.astype(jnp.int32))
        return passes + 1, more, d, run_d, run_i

    passes, _, _, run_d, run_i = jax.lax.while_loop(
        go_on, extract,
        (jnp.int32(0), jnp.int32(1), d, dist_ref[0], idx_ref[0]))
    dist_ref[0] = run_d
    idx_ref[0] = run_i
    if count:
        refs[4][...] = jnp.full((1, 1) + _TILE, passes, jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "metric", "block_q",
                                             "block_n", "interpret",
                                             "count"))
def _call(db, q, scales, valid, k, metric, block_q, block_n, interpret,
          count=False):
    b, d = q.shape
    n = db.shape[0]
    block_q = min(block_q, b)
    block_n = min(block_n, n)
    assert k <= block_n, (k, block_n)
    # ceil-div grids instead of shrinking the tiles (see module
    # docstring): padded q rows are sliced off; the last db tile may run
    # past N, and the kernel masks those rows. The db is never copied to
    # pad it: at corpus size that copy would be the whole index.
    pb = -(-b // block_q) * block_q
    if pb > b:
        q = jnp.concatenate([q, jnp.zeros((pb - b, d), q.dtype)])
    tiles = -(-n // block_n)
    has_scales = scales is not None

    in_specs = [
        pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),      # q
        pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),      # db tile
    ]
    args = [q, db]
    if has_scales:
        # a lane row per tile: [N, 1] would be padded to 128 lanes in HBM
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j: (0, j)))
        args.append(scales.reshape(1, n).astype(jnp.float32))
    has_valid = valid is not None
    if has_valid:
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j: (0, j)))
        args.append(valid.reshape(1, n).astype(jnp.int32))

    # the running top-k: one block per query tile, resident along j
    out_specs = [pl.BlockSpec((1, block_q, k), lambda i, j: (0, i, 0))] * 2
    out_shape = [jax.ShapeDtypeStruct((1, pb, k), jnp.float32),
                 jax.ShapeDtypeStruct((1, pb, k), jnp.int32)]
    grid = (pb // block_q, tiles)
    if count:
        out_specs.append(pl.BlockSpec((1, 1) + _TILE,
                                      lambda i, j: (i, j, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct(grid + _TILE, jnp.int32))
    out = pl.pallas_call(
        functools.partial(_kernel, metric, k, n, b, has_scales, has_valid,
                          count),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="distance_topk",
    )(*args)
    dists, ids = jax.lax.sort((out[0][0, :b], out[1][0, :b]), dimension=1,
                              num_keys=2)
    if count:
        return dists, ids, out[2][:, :, 0, 0]
    return dists, ids


def distance_topk_pallas(db: jax.Array, q: jax.Array, k: int,
                         *, metric: str = "cosine",
                         scales: jax.Array | None = None,
                         valid: jax.Array | None = None,
                         block_q: int = 128, block_n: int = 1024,
                         interpret: bool | None = None):
    """db [N,D] (+ optional scales [N], row mask ``valid`` [N]), q [B,D]
    -> the exact top-k (dists [B,k] ascending by (distance, id), ids
    [B,k]). ``interpret=None`` resolves platform-aware.
    """
    return _call(db, q, scales, valid, k, metric, block_q, block_n,
                 resolve_interpret(interpret))


def distance_topk_passes(db: jax.Array, q: jax.Array, k: int,
                         *, metric: str = "cosine",
                         scales: jax.Array | None = None,
                         valid: jax.Array | None = None,
                         block_q: int = 128, block_n: int = 1024,
                         interpret: bool | None = None):
    """``distance_topk_pallas`` plus the extraction passes each (query
    tile, db tile) ran: -> (dists [B,k], ids [B,k], passes [QT, T] i32).

    The same kernel body with a third output, for measuring how often
    the running top-k lets a tile stop early; the search path never
    calls it. Adds the total to the ``distance_topk.passes`` counter.
    """
    dists, ids, passes = _call(db, q, scales, valid, k, metric, block_q,
                               block_n, resolve_interpret(interpret),
                               count=True)
    dispatch.bump("distance_topk.passes", int(np.asarray(passes).sum()))
    return dists, ids, passes
