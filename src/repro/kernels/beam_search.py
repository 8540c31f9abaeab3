"""Fused layer-0 beam search — the whole ef-beam HNSW search in ONE
kernel launch per query block (DESIGN.md §12).

The jnp search (``core.hnsw._beam_search``) pays per hop: a separate
``gather_distance`` dispatch plus two full [B, ef+2M] ``lax.sort``s,
with the ``while_loop`` state bouncing through HBM between hops. This
kernel keeps the ENTIRE search resident: the beam (dist, id, expanded)
lives in VMEM scratch across hops, neighbor lists and candidate row
tiles stream in by DMA (the row tiles double-buffered across the
block's queries, as in ``gather_distance``), and the merge is a single
bitonic merge of the sorted beam against bitonic-sorted candidates.

Per hop, the top-T unexpanded beam entries expand together (``expand_t``
static, default 4) so each DMA round amortizes over multiple frontier
nodes: hops = ceil(budget / T) instead of budget, with the last hop's
selection truncated to the total expansion budget (``max_iters``;
default ef, plus one slack hop at T>1 to match the re-ranking
one-at-a-time order's recall). The frontier and merge math is the SAME
code the jnp oracle runs (``ref.beam_frontier`` / ``ref.beam_merge_wide``
with a lane rotate for ``jnp.roll``); the dedup is the lane-rotate form
of ``ref.beam_dedup_valid``. The tests hold kernel and oracle to equal
ids.

What the TPU compiler needs, and how the kernel provides it:
  * DMA addresses are scalars in SMEM. The frontier ids are vector
    values, so they go VMEM -> SMEM by a local DMA each hop; neighbor
    lists arrive straight into SMEM.
  * A DMA moves whole 128-lane rows of a narrow table: the [N, 2M]
    adjacency comes packed into 128-lane rows (``layout.PackedRows``),
    and an int8 codec's scales as [N/128, 128] rows (``layout.lane_rows``),
    from which the kernel picks lane ``id % 128``.
  * Rows come as aligned 8-row tiles (``layout.row_tiles``).
  * The resident graph (``core.hnsw.DeviceGraph``) keeps its adjacency
    packed and its capacity a multiple of 1024 rows, so none of these
    views copies a table; a dense [N, 2M] adjacency is packed per call.
  * Vectors stay 2-D: candidate distances are placed on a lane row with
    a mask, the beam arrays are one lane width L (a power of two, at
    least 128), and the bitonic network rotates lanes (``pltpu.roll``).

Shapes / dtypes
  vectors    [N, D]   f32 / bf16 / int8 (HBM, ``memory_space=ANY``;
                      the per-row decode fuses into the distance)
  neighbors0 [N, 2M]  i32 layer-0 adjacency, -1 pad; 2M <= 128; a
                      ``layout.PackedRows`` or a dense array
  q          [B, D]   f32 prepped queries
  ep, ep_dist [B]     layer-0 entry points (from the greedy descent)
  scales     [N] f32  optional per-row decode scales (int8 codec)
  ->  (ids [B, ef] i32, dists [B, ef] f32) ascending by (d, id);
      empty slots (-1, INF). Tombstone filtering stays in the caller
      (``core.hnsw.search_core``), as on the jnp path.

Grid / memory plan
  grid = (ceil(B / block_q),) over a padded batch. Beam state and the
  hop's candidates are [BQ, L] in VMEM scratch; frontier ids and
  neighbor rows sit in SMEM; the early-exit flag is one SMEM word
  guarding each hop body (``pl.when``), so converged blocks skip the
  remaining hops' DMA entirely. Row tiles ride a [2·T·2M, 8, D] double
  buffer.

Platforms
  ``interpret=None`` resolves platform-aware (kernels.resolve_interpret):
  the compiled kernel on TPU, always; the interpreter elsewhere.
  ``ops.beam_search`` runs this kernel on every TPU call. Off-TPU it
  runs ``ref.beam_search_ref`` — the same algorithm on the same
  helpers — unless REPRO_PALLAS=interpret.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref, resolve_interpret
from repro.kernels.gather_distance import tile_distance
from repro.kernels.layout import (LANES, ROW_TILE, as_packed, lane_rows,
                                  row_tiles)

INF = ref.BEAM_INF


def _rotate(x, shift: int):
    """``jnp.roll`` along lanes as a TPU lane rotate (non-negative)."""
    return pltpu.roll(x, shift % x.shape[-1], x.ndim - 1)


def _dedup_wide(cand, valid, bi, w: int):
    """``ref.beam_dedup_valid`` on lane-wide [B, L] rows, as lane
    rotates: a candidate is dropped when its id is in the beam, or when
    an EARLIER valid candidate (within the first ``w`` lanes) has it."""
    lane = ref._lanes(cand)
    vi = valid.astype(jnp.int32)
    in_beam = bi == cand
    for s in range(1, cand.shape[-1]):
        in_beam = in_beam | (_rotate(bi, s) == cand)
    dup = lane < 0
    for s in range(1, w):
        dup = dup | ((lane >= s) & (_rotate(vi, s) != 0)
                     & (_rotate(cand, s) == cand))
    return valid & ~in_beam & ~dup


def _kernel(metric: str, ef: int, efp: int, t: int, m2: int, lanes: int,
            per: int, hops: int, budget: int, n_rows: int,
            has_scales: bool, *refs):
    if has_scales:
        (ep_ref, epd_ref, q_ref, nbr_tbl, db_ref, scl_tbl,
         outi_ref, outd_ref,
         bd_ref, bi_ref, bx_ref, cd_ref, ci_ref, cv_ref, sel_v, vbuf, sbuf,
         sel_s, nbr_s, done_ref, sel_sem, nbr_sem, row_sems, scl_sems) = refs
    else:
        (ep_ref, epd_ref, q_ref, nbr_tbl, db_ref,
         outi_ref, outd_ref,
         bd_ref, bi_ref, bx_ref, cd_ref, ci_ref, cv_ref, sel_v, vbuf,
         sel_s, nbr_s, done_ref, sel_sem, nbr_sem, row_sems) = refs
        scl_tbl = sbuf = scl_sems = None
    bq, width = bd_ref.shape
    w = t * m2
    n_tiles = db_ref.shape[0]

    # beam init: slot 0 = the entry point, the rest (INF, -1, expanded)
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, width), 1)
    bd_ref[...] = jnp.where(col == 0, epd_ref[...], INF)
    bi_ref[...] = jnp.where(col == 0, ep_ref[...], -1)
    bx_ref[...] = (col != 0).astype(jnp.int32)
    done_ref[0] = 0

    def candidate(b, c):
        """(frontier node, raw neighbor id, packed lane) of candidate c
        of query b, read from SMEM."""
        j = c // m2
        node = sel_s[b, j]
        lane = (jnp.clip(node, 0, n_rows - 1) % per) * lanes + c % m2
        return node, nbr_s[b * t + j, lane], lane

    def row_copies(b, c, slot):
        """The candidate's row tile, and the 128-lane row of decode
        scales that holds its scale."""
        _, raw, _ = candidate(b, c)
        cid = jnp.clip(raw, 0, n_rows - 1)
        cps = [pltpu.make_async_copy(
            db_ref.at[jnp.minimum(cid // ROW_TILE, n_tiles - 1)],
            vbuf.at[slot * w + c], row_sems.at[slot])]
        if has_scales:
            cps.append(pltpu.make_async_copy(
                scl_tbl.at[pl.ds(cid // LANES, 1)], sbuf.at[slot * w + c],
                scl_sems.at[slot]))
        return cps

    def issue_rows(b, slot):
        def one(c, _):
            for cp in row_copies(b, c, slot):
                cp.start()
            return 0
        jax.lax.fori_loop(0, w, one, 0)

    def nbr_copy(i):
        row = jnp.clip(sel_s[i // t, i % t], 0, n_rows - 1) // per
        return pltpu.make_async_copy(nbr_tbl.at[pl.ds(row, 1)],
                                     nbr_s.at[pl.ds(i, 1)], nbr_sem.at[0])

    def hop(h, _):
        @pl.when(done_ref[0] == 0)
        def _():
            bd = bd_ref[...]
            bi = bi_ref[...]
            bx = bx_ref[...] != 0
            t_live = jnp.minimum(t, budget - h * t)
            bx2, cols = ref.beam_frontier(bd, bi, bx, t_live, t)

            # the frontier ids become DMA addresses: vector -> VMEM ->
            # SMEM, where the scalar unit can read them
            lane = jax.lax.broadcasted_iota(jnp.int32, (bq, LANES), 1)
            nodes = jnp.full((bq, LANES), -1, jnp.int32)
            for j, cj in enumerate(cols):
                nodes = jnp.where(lane == j, cj, nodes)
            sel_v[...] = nodes
            cp = pltpu.make_async_copy(sel_v, sel_s, sel_sem.at[0])
            cp.start()
            cp.wait()

            # phase 1: the packed neighbor-list row of every frontier node
            def issue_n(i, _):
                nbr_copy(i).start()
                return 0
            jax.lax.fori_loop(0, bq * t, issue_n, 0)

            def wait_n(i, _):
                nbr_copy(i).wait()
                return 0
            jax.lax.fori_loop(0, bq * t, wait_n, 0)

            # phase 2: candidate row tiles, double-buffered across the
            # block's queries; fused codec decode + distance per row
            issue_rows(0, 0)
            lane_w = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)

            def per_query(b, _):
                slot = b % 2

                @pl.when(b + 1 < bq)
                def _():
                    issue_rows(b + 1, 1 - slot)

                qv = q_ref[pl.ds(b, 1), :].astype(jnp.float32)

                def one(c, acc):
                    rd, ri, rv = acc
                    for cp in row_copies(b, c, slot):
                        cp.wait()
                    node, raw, _ = candidate(b, c)
                    cid = jnp.clip(raw, 0, n_rows - 1)
                    scale = None
                    if has_scales:
                        srow = sbuf[slot * w + c]             # [1, 128]
                        lane_s = jax.lax.broadcasted_iota(
                            jnp.int32, srow.shape, 1)
                        scale = jnp.sum(jnp.where(lane_s == cid % LANES,
                                                  srow, 0.0),
                                        axis=1, keepdims=True)
                    d = tile_distance(metric, qv, vbuf[slot * w + c], cid,
                                      scale)
                    ok = ((node >= 0) & (raw >= 0)).astype(jnp.int32)
                    at = lane_w == c
                    return (jnp.where(at, d, rd), jnp.where(at, cid, ri),
                            jnp.where(at, ok, rv))

                rd, ri, rv = jax.lax.fori_loop(
                    0, w, one,
                    (jnp.full((1, width), INF, jnp.float32),
                     jnp.zeros((1, width), jnp.int32),
                     jnp.zeros((1, width), jnp.int32)))
                cd_ref[pl.ds(b, 1), :] = rd
                ci_ref[pl.ds(b, 1), :] = ri
                cv_ref[pl.ds(b, 1), :] = rv
                return 0

            jax.lax.fori_loop(0, bq, per_query, 0)

            # phase 3: dedup + single bitonic merge, all VMEM vector work
            cand = ci_ref[...]
            valid = _dedup_wide(cand, cv_ref[...] != 0, bi, w)
            cd = jnp.where(valid, cd_ref[...], INF)
            ci = jnp.where(valid, cand, -1)
            nbd, nbi, nbx = ref.beam_merge_wide(
                bd, bi, bx2.astype(jnp.int32), cd, ci, ef, efp, _rotate)
            bd_ref[...] = nbd
            bi_ref[...] = nbi
            bx_ref[...] = nbx
            done_ref[0] = 1 - jnp.max(((nbx == 0) & (nbi >= 0))
                                      .astype(jnp.int32))
        return 0

    if hops > 0:
        jax.lax.fori_loop(0, hops, hop, 0)
    outd_ref[...] = bd_ref[...]
    outi_ref[...] = bi_ref[...]


@functools.partial(jax.jit, static_argnames=("metric", "ef", "expand_t",
                                             "max_iters", "block_q",
                                             "interpret"))
def _call(vectors, nbr, q, ep, ep_dist, scales, metric, ef,
          expand_t, max_iters, block_q, interpret):
    b, d = q.shape
    n, m2 = vectors.shape[0], nbr.width
    lanes, per = nbr.lanes, nbr.per
    assert nbr.rows >= n, (nbr.rows, n)
    t = max(1, min(int(expand_t), int(ef)))
    # default budget: ef expansions, plus ONE slack hop at t>1 — group
    # frontier selection spends some budget on nodes the re-ranking
    # one-at-a-time order would skip, and the slack hop restores its
    # recall (measured; see DESIGN.md §12). t=1 stays exactly ef so the
    # visit order is bitwise the sequential reference.
    budget = ((int(ef) + (t if t > 1 else 0)) if max_iters is None
              else int(max_iters))
    hops = -(-budget // t) if budget > 0 else 0
    efp = ref.next_pow2(ef)
    w = t * m2
    assert t <= LANES, t
    width = max(LANES, ref.merge_width(efp, w))
    block_q = min(block_q, b)
    pb = -(-b // block_q) * block_q
    if pb > b:                   # pad the batch, never shrink the block
        q = jnp.concatenate([q, jnp.zeros((pb - b, d), q.dtype)])
        ep = jnp.concatenate([ep, jnp.zeros(pb - b, ep.dtype)])
        ep_dist = jnp.concatenate([ep_dist, jnp.zeros(pb - b,
                                                      ep_dist.dtype)])
    has_scales = scales is not None

    in_specs = [
        pl.BlockSpec((block_q, 1), lambda i: (i, 0)),     # entry ids
        pl.BlockSpec((block_q, 1), lambda i: (i, 0)),     # entry dists
        pl.BlockSpec((block_q, d), lambda i: (i, 0)),     # queries
        pl.BlockSpec(memory_space=pl.ANY),                # packed nbrs
    ]
    args = [ep.reshape(pb, 1).astype(jnp.int32),
            ep_dist.reshape(pb, 1).astype(jnp.float32),
            q.astype(jnp.float32), nbr.table]
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))  # db row tiles
    args.append(row_tiles(vectors))
    if has_scales:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))  # decode scales
        args.append(lane_rows(scales.astype(jnp.float32)))

    scratch_shapes = [
        pltpu.VMEM((block_q, width), jnp.float32),        # beam dists
        pltpu.VMEM((block_q, width), jnp.int32),          # beam ids
        pltpu.VMEM((block_q, width), jnp.int32),          # expanded flags
        pltpu.VMEM((block_q, width), jnp.float32),        # candidate dists
        pltpu.VMEM((block_q, width), jnp.int32),          # candidate ids
        pltpu.VMEM((block_q, width), jnp.int32),          # candidate valid
        pltpu.VMEM((block_q, LANES), jnp.int32),          # frontier (vector)
        pltpu.VMEM((2 * w, ROW_TILE, d), vectors.dtype),  # row tiles x2
    ]
    if has_scales:
        scratch_shapes.append(
            pltpu.VMEM((2 * w, 1, LANES), jnp.float32))   # scale rows x2
    scratch_shapes += [
        pltpu.SMEM((block_q, LANES), jnp.int32),          # frontier (scalar)
        pltpu.SMEM((block_q * t, LANES), jnp.int32),      # neighbor rows
        pltpu.SMEM((1,), jnp.int32),                      # early-exit flag
        pltpu.SemaphoreType.DMA((1,)),                    # frontier sem
        pltpu.SemaphoreType.DMA((1,)),                    # neighbor sem
        pltpu.SemaphoreType.DMA((2,)),                    # row sem pair
    ]
    if has_scales:
        scratch_shapes.append(pltpu.SemaphoreType.DMA((2,)))  # scale sems

    ids, dists = pl.pallas_call(
        functools.partial(_kernel, metric, int(ef), efp, t, m2, lanes, per,
                          hops, budget, n, has_scales),
        grid=(pb // block_q,),
        in_specs=in_specs,
        out_specs=(pl.BlockSpec((block_q, width), lambda i: (i, 0)),
                   pl.BlockSpec((block_q, width), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((pb, width), jnp.int32),
                   jax.ShapeDtypeStruct((pb, width), jnp.float32)),
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        name="beam_search",
    )(*args)
    return ids[:b, :ef], dists[:b, :ef]


def beam_search_pallas(vectors: jax.Array, neighbors0: jax.Array,
                       q: jax.Array, ep: jax.Array, ep_dist: jax.Array,
                       *, ef: int, metric: str = "cosine",
                       scales: jax.Array | None = None, expand_t: int = 4,
                       max_iters: int | None = None, block_q: int = 8,
                       interpret: bool | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """One kernel launch per query block for the whole layer-0 ef-beam
    search. ``interpret=None`` resolves platform-aware."""
    return _call(vectors, as_packed(neighbors0), q, ep, ep_dist, scales,
                 metric,
                 int(ef), int(expand_t),
                 None if max_iters is None else int(max_iters),
                 block_q, resolve_interpret(interpret))
