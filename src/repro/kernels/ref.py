"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Every kernel test sweeps shapes/dtypes and asserts allclose against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.layout import next_pow2, row_width, take_rows


def gather_distance_ref(vectors: jax.Array, q: jax.Array, ids: jax.Array,
                        *, metric: str = "cosine",
                        scales: jax.Array | None = None) -> jax.Array:
    """vectors [N,D], q [B,D], ids [B,K] (valid, clamped) -> dists [B,K].

    ``scales`` [N] decodes codec-encoded rows (DESIGN.md §9): each
    gathered row is ``row · scale`` in fp32 — the asymmetric-distance
    contract (fp32 query vs encoded rows, fp32 accumulation)."""
    x = jnp.take(vectors, ids, axis=0).astype(jnp.float32)  # [B,K,D]
    if scales is not None:
        x = x * jnp.take(scales, ids).astype(jnp.float32)[..., None]
    if metric in ("cosine", "ip"):
        return 1.0 - jnp.einsum("bd,bkd->bk", q.astype(jnp.float32), x)
    d = x - q.astype(jnp.float32)[:, None, :]
    return jnp.einsum("bkd,bkd->bk", d, d)


def distance_topk_ref(db: jax.Array, q: jax.Array, k: int,
                      *, metric: str = "cosine",
                      scales: jax.Array | None = None,
                      valid: jax.Array | None = None
                      ) -> tuple[jax.Array, jax.Array]:
    """db [N,D], q [B,D] -> (dists [B,k] ascending, ids [B,k]).

    ``scales`` [N] decodes codec-encoded db rows in fp32 before the
    distance (asymmetric distance, DESIGN.md §9). Rows where ``valid``
    [N] is False score 3e38, the kernel's padding distance."""
    x = db.astype(jnp.float32)
    if scales is not None:
        x = x * scales.astype(jnp.float32)[:, None]
    if metric in ("cosine", "ip"):
        d = 1.0 - jnp.einsum("bd,nd->bn", q.astype(jnp.float32), x)
    else:
        d = (jnp.sum(q.astype(jnp.float32) ** 2, -1)[:, None]
             - 2.0 * jnp.einsum("bd,nd->bn", q.astype(jnp.float32), x)
             + jnp.sum(x ** 2, -1)[None, :])
    if valid is not None:
        d = jnp.where(valid[None, :], d, jnp.float32(3.0e38))
    neg, ids = jax.lax.top_k(-d, k)
    return -neg, ids


def embedding_bag_ref(table: jax.Array, ids: jax.Array,
                      weights: jax.Array | None = None,
                      *, combine: str = "sum") -> jax.Array:
    """table [R,E], ids [B,L] -> bags [B,E]; weights [B,L] optional."""
    g = jnp.take(table, ids, axis=0).astype(jnp.float32)   # [B,L,E]
    if weights is not None:
        g = g * weights.astype(jnp.float32)[..., None]
    s = jnp.sum(g, axis=1)
    if combine == "mean":
        n = (ids.shape[1] if weights is None
             else jnp.maximum(jnp.sum(weights, -1, keepdims=True), 1e-9))
        s = s / n
    return s


# ---------------------------------------------------------------------------
# fused beam search (kernels/beam_search.py): shared algorithm + jnp oracle
# ---------------------------------------------------------------------------
# The helpers below are used BOTH by ``beam_search_ref`` and by the Pallas
# kernel body (which swaps the gather for double-buffered DMA but runs the
# identical frontier/dedup/merge math on the fetched values) — one
# implementation, so fused-vs-jnp parity is structural, not coincidental.

# == core.hnsw.INF (empty-slot distance); a Python float so the Pallas
# kernel body can close over it without capturing a device constant
BEAM_INF = 3.0e38



def _lanes(x):
    """Lane index of every element of ``x`` along its last axis (a 2-D
    iota: Mosaic has no 1-D iota)."""
    return jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)


def _roll(x, shift: int):
    """``jnp.roll`` along the last axis (the default ``roll`` of the
    network helpers below; a kernel passes a lane rotate instead)."""
    return jnp.roll(x, shift, axis=-1)


def _compare_exchange(d, i, x, stride: int, asc_mask, roll=_roll):
    """One bitonic compare-exchange stage on (dist, id, payload) triples
    along the last axis, ordered by the two-key (d, id) lexicographic
    compare. ``asc_mask`` (same shape) is each position's block
    direction. The partner of position p is p ^ stride — p+stride in
    lower halves, p-stride in upper halves — so a pair of rolls never
    wraps a pair across the array edge."""
    lower = (_lanes(d) & stride) == 0
    pd = jnp.where(lower, roll(d, -stride), roll(d, stride))
    pi = jnp.where(lower, roll(i, -stride), roll(i, stride))
    px = jnp.where(lower, roll(x, -stride), roll(x, stride))
    le = (d < pd) | ((d == pd) & (i <= pi))
    # where(lower == asc_mask, le, ~le) as xors: Mosaic cannot select
    # between two masks
    keep = le ^ lower ^ asc_mask
    return (jnp.where(keep, d, pd), jnp.where(keep, i, pi),
            jnp.where(keep, x, px))


def bitonic_sort(d, i, x, *, ascending: bool = True, roll=_roll):
    """Full bitonic sort along the last axis (width must be a power of
    two) by the two-key (d, id) order. ~log²W compare-exchange stages of
    pure vector ops — no lax.sort, so the same network runs inside the
    Pallas kernel body (with ``roll`` a lane rotate). The payload ``x``
    is int32 inside a kernel: a lane rotate of a bool mask does not
    lower."""
    w = d.shape[-1]
    idx = _lanes(d)
    size = 2
    while size <= w:
        asc_mask = ((idx & size) == 0) == bool(ascending)
        stride = size // 2
        while stride:
            d, i, x = _compare_exchange(d, i, x, stride, asc_mask, roll)
            stride //= 2
        size *= 2
    return d, i, x


def bitonic_merge(d, i, x, roll=_roll):
    """Bitonic merge: a bitonic input along the last axis (power-of-two
    width) sorts ascending in log W compare-exchange stages — the cheap
    half of a full sort, and the reason the beam stays sorted between
    hops instead of being re-sorted."""
    asc = _lanes(d) >= 0
    stride = d.shape[-1] // 2
    while stride:
        d, i, x = _compare_exchange(d, i, x, stride, asc, roll)
        stride //= 2
    return d, i, x


def beam_frontier(bd, bi, bx, t_live, t: int):
    """Mark the first ``t_live`` (<= t) unexpanded entries of the
    (ascending-sorted) beam as expanded and extract their node ids.
    Returns (new_bx, [t columns [B, 1]], -1 for unfilled slots). Rank
    among unexpanded entries comes from a strict-lower-triangular matmul
    — MXU-friendly and Mosaic-safe, where a lane cumsum is not."""
    w = bd.shape[-1]
    unexp = (~bx) & (bi >= 0)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
           < jax.lax.broadcasted_iota(jnp.int32, (w, w), 1)
           ).astype(jnp.float32)
    rank = jnp.dot(unexp.astype(jnp.float32), tri,
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    sel = unexp & (rank < t_live)
    cols = [jnp.max(jnp.where(sel & (rank == j), bi, -1), axis=-1,
                    keepdims=True) for j in range(t)]
    return bx | sel, cols


def beam_dedup_valid(cand, valid, bi):
    """Drop candidates already in the beam, or duplicated EARLIER in the
    flat candidate list (cross-list dups from multi-node expansion; the
    builder guarantees uniqueness within one neighbor list, not across
    lists). Keeping the earliest copy matches the reference semantics:
    duplicate copies carry bitwise-identical distances."""
    w = cand.shape[-1]
    in_beam = jnp.any(cand[:, :, None] == bi[:, None, :], axis=-1)
    eq = cand[:, :, None] == cand[:, None, :]
    earlier = jnp.arange(w)[:, None] > jnp.arange(w)[None, :]
    dup = jnp.any(eq & earlier[None] & valid[:, None, :], axis=-1)
    return valid & ~in_beam & ~dup


def merge_width(efp: int, w: int) -> int:
    """Lane width of the one-network merge of an ``efp`` beam with ``w``
    candidates (a power of two >= efp + w)."""
    return next_pow2(efp + next_pow2(w))


def beam_merge_wide(bd, bi, bx, cd, ci, ef: int, efp: int, roll=_roll):
    """The bitonic merge on arrays that are all one power-of-two width L
    >= efp + (live candidates): the beam ascending in lanes [0, efp) and
    pads after, the candidates anywhere with (INF, -1) pads. Sorting the
    candidates DESCENDING puts their pads first, so taking lanes
    [0, efp) from the beam and the rest from the candidates is bitonic
    by construction; one merge sorts it. Returns L-wide (dist, id,
    payload) with every lane past ``ef`` reset to (INF, -1, expanded)."""
    cd, ci, cx = bitonic_sort(cd, ci, jnp.zeros_like(bx), ascending=False,
                              roll=roll)
    head = _lanes(bd) < efp
    md, mi, mx = bitonic_merge(jnp.where(head, bd, cd),
                               jnp.where(head, bi, ci),
                               jnp.where(head, bx, cx), roll)
    live = _lanes(md) < ef
    return (jnp.where(live, md, BEAM_INF), jnp.where(live, mi, -1),
            jnp.where(live, mx, jnp.ones_like(mx)))


def beam_merge(bd, bi, bx, cd, ci, ef: int, use_bitonic: bool = True):
    """One-hop beam merge of the ascending beam [B, efp] with candidates
    [B, w]: :func:`beam_merge_wide` after padding both to the merge
    width. Entries past ``ef`` reset to (INF, -1, expanded) so the
    logical beam width stays exactly ef (recall parity with the ef-wide
    reference beam).

    ``use_bitonic=False`` swaps the network for one ``lax.sort`` over
    the plain concatenation — output-identical (live (d, id) keys are
    unique after dedup; ties exist only among (INF, -1) pads, whose
    expanded bit is never read downstream) but much cheaper as compiled
    XLA, where the network's O(log^2 W) elementwise stages lose to the
    native sort. The kernel keeps the network: Mosaic has no sort."""
    b, efp = bd.shape
    w = cd.shape[-1]
    if not use_bitonic:
        md = jnp.concatenate([bd, cd], axis=-1)
        mi = jnp.concatenate([bi, ci], axis=-1)
        mx = jnp.concatenate([bx, jnp.zeros((b, w), bool)], axis=-1)
        md, mi, mx = jax.lax.sort((md, mi, mx), dimension=-1, num_keys=2)
        live = jnp.arange(efp) < ef
        return (jnp.where(live, md[:, :efp], BEAM_INF),
                jnp.where(live, mi[:, :efp], -1),
                jnp.where(live, mx[:, :efp], True))
    width = merge_width(efp, w)

    def pad(a, fill):
        return jnp.concatenate(
            [a, jnp.full((b, width - a.shape[-1]), fill, a.dtype)], axis=-1)

    md, mi, mx = beam_merge_wide(pad(bd, BEAM_INF), pad(bi, -1),
                                 pad(bx, True), pad(cd, BEAM_INF),
                                 pad(ci, -1), ef, efp)
    return md[:, :efp], mi[:, :efp], mx[:, :efp]


def beam_search_ref(vectors: jax.Array, neighbors0: jax.Array,
                    q: jax.Array, ep: jax.Array, ep_dist: jax.Array,
                    *, ef: int, metric: str = "cosine",
                    scales: jax.Array | None = None, expand_t: int = 4,
                    max_iters: int | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """jnp oracle for the fused layer-0 ef-beam search kernel: identical
    frontier selection, dedup, and bitonic merge, with the kernel's
    per-hop DMA gather replaced by ``gather_distance_ref``.

    vectors [N, D] (any codec dtype; ``scales`` [N] decodes), neighbors0
    [N, 2M] i32 (-1 pad), dense or as ``layout.PackedRows``, q [B, D] f32, ep/ep_dist [B] layer-0 entry
    points. Returns (ids [B, ef], dists [B, ef]) ascending by (d, id);
    empty slots are (-1, INF).

    ``expand_t`` nodes expand per hop against a TOTAL expansion budget of
    ``max_iters`` (default ef, plus one slack hop when expand_t > 1), so
    hops = ceil(budget / expand_t) with the last hop truncated. At
    expand_t=1 the visit order is exactly the sequential-semantics
    ``core.hnsw._beam_search`` order."""
    b = q.shape[0]
    n, m2 = vectors.shape[0], row_width(neighbors0)
    t = max(1, min(int(expand_t), int(ef)))
    # default budget: ef, plus one slack hop at t>1 (kept in lockstep
    # with kernels/beam_search.py — group frontier selection needs the
    # slack to match the one-at-a-time order's recall, DESIGN.md §12)
    budget = ((int(ef) + (t if t > 1 else 0)) if max_iters is None
              else int(max_iters))
    hops = -(-budget // t) if budget > 0 else 0
    efp = next_pow2(ef)
    col = jnp.arange(efp)[None, :]
    bd = jnp.where(col == 0, ep_dist[:, None].astype(jnp.float32), BEAM_INF)
    bi = jnp.where(col == 0, ep[:, None].astype(jnp.int32), -1)
    bx = jnp.broadcast_to(col != 0, (b, efp))

    def cond(state):
        bd, bi, bx, hop = state
        return (hop < hops) & jnp.any((~bx) & (bi >= 0))

    def body(state):
        bd, bi, bx, hop = state
        t_live = jnp.minimum(t, budget - hop * t)
        bx, cols = beam_frontier(bd, bi, bx, t_live, t)
        nodes = jnp.concatenate(cols, axis=-1)                # [B, t]
        nbrs = take_rows(neighbors0, jnp.clip(nodes, 0, n - 1))
        valid = ((nodes >= 0)[:, :, None] & (nbrs >= 0)).reshape(b, t * m2)
        cand = jnp.clip(nbrs, 0, n - 1).reshape(b, t * m2)
        d = gather_distance_ref(vectors, q, cand, metric=metric,
                                scales=scales)
        valid = beam_dedup_valid(cand, valid, bi)
        cd = jnp.where(valid, d, BEAM_INF)
        ci = jnp.where(valid, cand, -1)
        bd, bi, bx = beam_merge(bd, bi, bx, cd, ci, int(ef),
                                use_bitonic=False)
        return bd, bi, bx, hop + 1

    bd, bi, bx, _ = jax.lax.while_loop(
        cond, body, (bd, bi, bx, jnp.zeros((), jnp.int32)))
    return bi[:, :ef], bd[:, :ef]


# ---------------------------------------------------------------------------
# batched neighbor-selection heuristic (HNSW construction, DESIGN.md §13)
# ---------------------------------------------------------------------------
def select_neighbors_ref(vectors: jax.Array, q: jax.Array,
                         cand_ids: jax.Array, *, m: int,
                         metric: str = "cosine",
                         scales: jax.Array | None = None
                         ) -> tuple[jax.Array, jax.Array]:
    """Batched Malkov & Yashunin Alg. 4 (the neighbor-selection heuristic
    with ``keepPrunedConnections=True``), output-identical per row to the
    host oracle ``hnsw_build.select_heuristic_host``.

    vectors [N, D] (any codec dtype; ``scales`` [N] decodes), q [B, D]
    f32, cand_ids [B, C] i32 with -1 padding -> (ids [B, m] i32 -1-pad,
    dists [B, m] f32 INF-pad, ascending by selection order).

    Per row: candidates sort by the two-key (dist-to-q, id) order (the
    host sorts (d, e) tuples — ties break on id); a masked keep-scan
    walks them in that order keeping candidate ``i`` iff no
    already-kept ``j`` is closer to ``i`` than ``q`` is
    (``pd[i, j] < d[i]`` rejects); the first ``m`` keeps are the
    heuristic picks, and pruned/untested candidates backfill in sorted
    order. The pairwise block ``pd`` is one [B, C, C] einsum — the
    O(B·C²·D) work the per-node host loops serialized.

    Duplicate ids keep their first occurrence (the reciprocal-connect
    caller merges an existing adjacency row with new back-edge sources,
    where an intra-batch source can already be a forward neighbor)."""
    b, c = cand_ids.shape
    if c < m:                      # width must cover the output slots
        cand_ids = jnp.concatenate(
            [cand_ids, jnp.full((b, m - c), -1, jnp.int32)], axis=1)
        c = m
    n = vectors.shape[0]
    valid = cand_ids >= 0
    idc = jnp.clip(cand_ids, 0, n - 1)
    # keep-first dedup (same mask construction as beam_dedup_valid)
    eq = idc[:, :, None] == idc[:, None, :]
    earlier = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
    dup = jnp.any(eq & earlier[None] & valid[:, None, :], axis=-1)
    valid = valid & ~dup
    d = gather_distance_ref(vectors, q, idc, metric=metric, scales=scales)
    d = jnp.where(valid, d, BEAM_INF)
    sid = jnp.where(valid, cand_ids, jnp.iinfo(jnp.int32).max)
    sd, si = jax.lax.sort((d, sid), num_keys=2)          # (d, id) ascending
    svalid = sd < BEAM_INF
    # pairwise distances between the sorted candidates, decoded in fp32
    x = jnp.take(vectors, jnp.clip(si, 0, n - 1), axis=0).astype(jnp.float32)
    if scales is not None:
        x = x * jnp.take(scales, jnp.clip(si, 0, n - 1)
                         ).astype(jnp.float32)[..., None]
    if metric in ("cosine", "ip"):
        pd = 1.0 - jnp.einsum("bid,bjd->bij", x, x,
                              preferred_element_type=jnp.float32)
    else:
        sq = jnp.sum(x * x, axis=-1)
        pd = (sq[:, :, None] - 2.0 * jnp.einsum(
            "bid,bjd->bij", x, x, preferred_element_type=jnp.float32)
            + sq[:, None, :])

    def step(i, kept):
        # candidate i survives iff no already-kept j dominates it:
        # pd[i, j] < d(i, q) is the host oracle's strict rejection test
        ok = svalid[:, i] & ~jnp.any(kept & (pd[:, i, :] < sd[:, i, None]),
                                     axis=-1)
        return kept.at[:, i].set(ok)

    kept = jax.lax.fori_loop(0, c, step, jnp.zeros((b, c), bool))
    rank = jnp.cumsum(kept, axis=-1) - kept.astype(jnp.int32)
    primary = kept & (rank < m)
    # heuristic picks first (in sorted order), then backfill in sorted
    # order; invalid slots sorted to the very end by construction
    pos = jnp.broadcast_to(jnp.arange(c)[None, :], (b, c))
    key = jnp.where(primary, pos, pos + c)
    order = jnp.argsort(key, axis=-1)[:, :m]
    out_i = jnp.take_along_axis(si, order, axis=1)
    out_d = jnp.take_along_axis(sd, order, axis=1)
    out_v = jnp.take_along_axis(svalid, order, axis=1)
    return (jnp.where(out_v, out_i, -1).astype(jnp.int32),
            jnp.where(out_v, out_d, BEAM_INF))


def flash_decode_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                     cur_len: jax.Array) -> jax.Array:
    """q [B,H,Dh]; k,v [B,S,KVH,Dh]; mask pos >= cur_len -> out [B,H,Dh].

    ``cur_len`` is a scalar or [B] (continuous batching: each serving
    slot masks at its own depth within one dispatch)."""
    b, h, dh = q.shape
    s, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    qg = q.reshape(b, kvh, g, dh).astype(jnp.float32) * dh ** -0.5
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(jnp.float32))
    cur = jnp.broadcast_to(
        jnp.asarray(cur_len, jnp.int32).reshape(-1), (b,))
    mask = jnp.arange(s)[None, None, None, :] < cur[:, None, None, None]
    scores = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return out.reshape(b, h, dh)
