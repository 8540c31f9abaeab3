"""Blocked distance-matrix + per-tile top-k kernel (flat exact search).

The flat-index hot loop (and the recsys ``retrieval_cand`` cell): score a
query block against the whole database and keep the k best. Two-phase
split-K top-k:

  phase 1 (this kernel): grid (B tiles x N tiles). Each step loads a
    [BQ, D] query tile and a [BN, D] database tile into VMEM (BlockSpec),
    computes the [BQ, BN] distance tile on the MXU, then extracts the tile's
    top-k with k min-extraction passes (min/where/iota only — Mosaic-safe).
  phase 2 (ops.flat_topk): one tiny ``lax.top_k`` over the [B, n_tiles*k]
    partials.

MXU alignment: D and BN should be multiples of 128 for peak; the kernel is
shape-generic. The grids are ceil-divisions: the query batch is padded to
the block multiple (padded rows are sliced off the output), and the last
database tile may run past N, where the kernel masks the rows to +inf (they
can never reach the top-k). The database itself is never copied to pad it.
Earlier versions instead SHRANK block_q/block_n to the largest divisor,
which degenerates to 1-row blocks (a B×N program grid) whenever B or N is
prime — the regression test at N=997, B=7 in tests/test_kernels.py pins
the fix.

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
  ->     dists [B, T*k] f32, ids [B, T*k] i32   (T = ceil(N / block_n)
         tiles; per-tile partials — NOT the final top-k, see phase 2)

Grid / block layout
  grid = (ceil(B / block_q), ceil(N / block_n)); block (i, j) loads q
  tile i and db tile j via BlockSpec (automatic HBM->VMEM pipelining) and
  writes its k partials as block (j, i) of a [T, B, k] array: a k-wide
  block is then the whole minor dim, which the TPU block-shape rule
  accepts for any k. The wrapper transposes to [B, T*k].

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
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

BIG = 3.0e38   # plain float: pallas kernels must not capture traced constants
# full-f32 contraction on the MXU: the distances agree with an f32 host
# oracle instead of carrying bf16 input rounding
_HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(metric: str, k: int, n_total: int, has_scales: bool,
            has_valid: bool, *refs):
    refs = list(refs)
    q_ref, db_ref = refs[:2]
    s_ref = refs.pop(2) if has_scales else None
    v_ref = refs.pop(2) if has_valid else None
    dist_ref, idx_ref = refs[2:]
    j = pl.program_id(1)
    bn = db_ref.shape[0]
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
    # mask db PADDING rows (global id >= N) out of the tile's top-k; a
    # no-op on fully-valid tiles, so divisible shapes are bit-identical
    keep = col + base < n_total
    if v_ref is not None:
        keep = keep & (v_ref[...] != 0)          # caller's row mask [1, BN]
    d = jnp.where(keep, d, BIG)

    slot = jax.lax.broadcasted_iota(jnp.int32, (d.shape[0], k), 1)
    out_d = jnp.zeros((d.shape[0], k), jnp.float32)
    out_i = jnp.zeros((d.shape[0], k), jnp.int32)
    for i in range(k):                                    # static, k small
        m = jnp.min(d, axis=1, keepdims=True)             # [BQ, 1]
        pos = jnp.min(jnp.where(d == m, col, jnp.int32(2 ** 30)),
                      axis=1, keepdims=True)              # first argmin
        out_d = jnp.where(slot == i, m, out_d)
        out_i = jnp.where(slot == i, pos + base, out_i)
        d = jnp.where(col == pos, BIG, d)
    dist_ref[0] = out_d
    idx_ref[0] = out_i


@functools.partial(jax.jit, static_argnames=("k", "metric", "block_q",
                                             "block_n", "interpret"))
def _call(db, q, scales, valid, k, metric, block_q, block_n, interpret):
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

    # partials are [tiles, B, k]: the k-wide block is the whole minor
    # dim, which the TPU block-shape rule accepts for any k
    grid = (pb // block_q, tiles)
    dists, ids = pl.pallas_call(
        functools.partial(_kernel, metric, k, n, has_scales, has_valid),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, k), lambda i, j: (j, i, 0)),
            pl.BlockSpec((1, block_q, k), lambda i, j: (j, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((tiles, pb, k), jnp.float32),
            jax.ShapeDtypeStruct((tiles, pb, k), jnp.int32),
        ],
        interpret=interpret,
        name="distance_topk",
    )(*args)
    with jax.named_scope("flat.merge"):       # [T, B, k] -> [B, T*k]
        dists = jnp.transpose(dists[:, :b], (1, 0, 2)).reshape(b, tiles * k)
        ids = jnp.transpose(ids[:, :b], (1, 0, 2)).reshape(b, tiles * k)
    return dists, ids


def distance_topk_pallas(db: jax.Array, q: jax.Array, k: int,
                         *, metric: str = "cosine",
                         scales: jax.Array | None = None,
                         valid: jax.Array | None = None,
                         block_q: int = 128, block_n: int = 1024,
                         interpret: bool | None = None):
    """db [N,D] (+ optional scales [N], row mask ``valid`` [N]), q [B,D]
    -> per-tile partials (dists [B,T*k], ids [B,T*k]).

    Callers finish with a [B, T*k] -> [B, k] top-k merge (see
    ops.flat_topk). ``interpret=None`` resolves platform-aware.
    """
    return _call(db, q, scales, valid, k, metric, block_q, block_n,
                 resolve_interpret(interpret))
