"""Collective building blocks: hierarchical top-k merge and compressed
all-reduce. All are shard_map-side functions (use inside `shard_map`).

Two merge strategies live here:

* all-gather oracle (``axis_size=None``) — gather [S, B, k] then one full
  sort/top_k.  O(S*k) wire bytes per shard, single round.  Kept as the
  parity reference: every tree-merge result must be bitwise identical to
  it under ``tie_break_ids``.
* ppermute tree reduction (``axis_size=S``) — ceil(log2 S) pairwise
  rounds over ``lax.ppermute``; each round exchanges exactly k candidates
  with a partner and keeps the k best of 2k via a two-key sort.  Wire
  bytes per shard per round are k, not S*k, so total traffic is
  k*ceil(log2 S) instead of k*S — the merge stays bandwidth-bound as the
  shard count grows (DESIGN.md §8).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _wire_exchange(dists: jax.Array, ids: jax.Array, axis_name: str,
                   perm: list[tuple[int, int]], wire_bf16: bool
                   ) -> tuple[jax.Array, jax.Array]:
    """One ppermute hop of (dists, ids).  When ``wire_bf16`` and the
    distances are already bf16, ship raw u16 bits: a bitcast cannot be
    commuted above the collective the way a convert can, so the wire
    really carries 2 bytes/value."""
    if wire_bf16 and dists.dtype == jnp.bfloat16:
        bits = jax.lax.bitcast_convert_type(dists, jnp.uint16)
        rd = jax.lax.bitcast_convert_type(
            jax.lax.ppermute(bits, axis_name, perm), jnp.bfloat16)
    else:
        rd = jax.lax.ppermute(dists, axis_name, perm)
    ri = jax.lax.ppermute(ids, axis_name, perm)
    return rd, ri


def _merge_pair(d1: jax.Array, i1: jax.Array, d2: jax.Array, i2: jax.Array,
                k: int, tie_break_ids: bool) -> tuple[jax.Array, jax.Array]:
    """Keep the k best of two per-shard candidate sets [B, k] each."""
    dd = jnp.concatenate([d1, d2], axis=1)
    ii = jnp.concatenate([i1, i2], axis=1)
    if tie_break_ids:
        sd, si = jax.lax.sort((dd, ii), num_keys=2)
        return sd[:, :k], si[:, :k]
    neg, j = jax.lax.top_k(-dd, k)
    return -neg, jnp.take_along_axis(ii, j, axis=1)


def _tree_merge_axis(dists: jax.Array, ids: jax.Array, k: int,
                     axis_name: str, axis_size: int, wire_bf16: bool,
                     tie_break_ids: bool) -> tuple[jax.Array, jax.Array]:
    """Recursive-doubling top-k merge over ``lax.ppermute``.

    Non-power-of-two sizes use the classic MPI scheme: with
    p = 2**floor(log2 S) and rem = S - p, the rem tail shards first fold
    their candidates into shards [0, rem); the butterfly then runs over
    the p-shard power-of-two subset (partner = rank XOR stride); finally
    shards [0, rem) send the finished result back to the tail so every
    shard exits replicated (the fan-out wrappers use out_specs=P(None)).

    Under a total order — (distance, id) with globally unique ids, i.e.
    ``tie_break_ids`` — every pairwise keep-k step discards only
    candidates that can never appear in the global top-k, so the result
    is bitwise identical to the all-gather-then-full-sort oracle
    regardless of the merge-tree shape.  Without tie-breaking, equal
    distances may resolve to different ids than the oracle.

    ppermute delivers zeros to shards no permutation entry targets; a
    zero distance would masquerade as a best-possible candidate, so every
    receive is masked to (+inf, -1) on shards outside the round's static
    receiver set before merging.
    """
    s = int(axis_size)
    if s <= 1:
        return dists, ids
    me = jax.lax.axis_index(axis_name)
    p = 1 << (s.bit_length() - 1)           # largest power of two <= s
    rem = s - p
    inf = jnp.asarray(jnp.inf, dists.dtype)

    def recv(d, i, perm, is_receiver):
        rd, ri = _wire_exchange(d, i, axis_name, perm, wire_bf16)
        rd = jnp.where(is_receiver, rd, inf)
        ri = jnp.where(is_receiver, ri, jnp.asarray(-1, ids.dtype))
        return rd, ri

    d, i = dists, ids
    if rem:
        # fold tail shards p+j into j (j < rem)
        rd, ri = recv(d, i, [(p + j, j) for j in range(rem)], me < rem)
        md, mi = _merge_pair(d, i, rd, ri, k, tie_break_ids)
        active = me < p
        d = jnp.where(active, md, d)
        i = jnp.where(active, mi, i)
    for r in range(p.bit_length() - 1):     # log2(p) butterfly rounds
        stride = 1 << r
        rd, ri = recv(d, i, [(a, a ^ stride) for a in range(p)], me < p)
        d, i = _merge_pair(d, i, rd, ri, k, tie_break_ids)
    if rem:
        # broadcast the finished result back to the tail shards
        rd, ri = recv(d, i, [(j, p + j) for j in range(rem)], me >= p)
        tail = me >= p
        d = jnp.where(tail, rd, d)
        i = jnp.where(tail, ri, i)
    return d, i


def topk_merge_axis(dists: jax.Array, ids: jax.Array, k: int,
                    axis_name: str, wire_bf16: bool = False,
                    tie_break_ids: bool = False,
                    axis_size: int | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """Merge per-shard top-k over one mesh axis (log-depth building block).

    dists/ids [B, k] per shard -> merged [B, k] (replicated along the axis).
    ``wire_bf16`` halves the distance payload on the wire (ordering is
    preserved to bf16 resolution; ids stay exact).

    ``axis_size`` selects the strategy: pass the static mesh-axis size to
    run the ppermute tree reduction (k wire values per shard per round,
    ceil(log2 S) rounds); leave it None for the single-round all-gather
    path (k*S wire values per shard), which doubles as the parity oracle
    for the tree.  The size must be static because the installed JAX has
    no ``jax.lax.axis_size`` and the permutation tables are Python-built.

    ``tie_break_ids`` resolves equal distances toward the smallest id via
    a two-key sort — the same order a single-device ``top_k`` over the
    id-sorted candidate set produces, which is what keeps the sharded
    index's merge bit-compatible with the 1-shard path (DESIGN.md §8).
    (Ties that straddle a shard's LOCAL top-k boundary are still cut by
    shard-local order; with real-valued distances that requires > k
    exactly-tied duplicate rows in one shard.)
    """
    if axis_size is not None:
        return _tree_merge_axis(dists, ids, k, axis_name, axis_size,
                                wire_bf16, tie_break_ids)
    if wire_bf16 and dists.dtype == jnp.bfloat16:
        # ship raw u16 bits: a bitcast cannot be commuted above the gather
        # the way a convert can, so the wire really carries 2 bytes/value
        bits = jax.lax.bitcast_convert_type(dists, jnp.uint16)
        d_all = jax.lax.bitcast_convert_type(
            jax.lax.all_gather(bits, axis_name), jnp.bfloat16)
    else:
        d_all = jax.lax.all_gather(dists, axis_name)   # [S, B, k]
    i_all = jax.lax.all_gather(ids, axis_name)
    s = d_all.shape[0]
    b = dists.shape[0]
    d_flat = jnp.transpose(d_all, (1, 0, 2)).reshape(b, s * k)
    i_flat = jnp.transpose(i_all, (1, 0, 2)).reshape(b, s * k)
    if tie_break_ids:
        sd, si = jax.lax.sort((d_flat, i_flat), num_keys=2)
        return sd[:, :k], si[:, :k]
    neg, j = jax.lax.top_k(-d_flat, k)
    return -neg, jnp.take_along_axis(i_flat, j, axis=1)


def hierarchical_topk(dists: jax.Array, ids: jax.Array, k: int,
                      axis_names: tuple[str, ...],
                      wire_bf16: bool = False,
                      tie_break_ids: bool = False,
                      axis_sizes: tuple[int, ...] | None = None
                      ) -> tuple[jax.Array, jax.Array]:
    """Merge local top-k across every mesh axis, innermost (fastest) first:
    'model' -> 'data' -> 'pod' gives log-depth tree reduction whose traffic
    per hop is k*axis_size rather than sum of shard sizes. ``wire_bf16``
    runs the whole merge in bf16 (converting once before the first hop, so
    no convert sits above a gather for XLA to commute): half the distance
    payload on every hop; ids stay exact, ordering is bf16-resolution.
    ``axis_sizes`` (parallel to ``axis_names``) switches each axis to the
    ppermute tree reduction; None keeps the all-gather oracle."""
    out_dtype = dists.dtype
    if wire_bf16:
        dists = dists.astype(jnp.bfloat16)
    for j, ax in enumerate(axis_names):
        size = axis_sizes[j] if axis_sizes is not None else None
        dists, ids = topk_merge_axis(dists, ids, k, ax, wire_bf16,
                                     tie_break_ids, axis_size=size)
    return dists.astype(out_dtype), ids


def compressed_psum(x: jax.Array, axis_name: str) -> jax.Array:
    """int8 chunk-quantized all-reduce: reduce-scatter + all-gather with int8
    payloads — 4x wire-byte reduction vs f32 ring all-reduce. Per-shard
    scale factors travel as f32 scalars (negligible).
    """
    s = jax.lax.axis_size(axis_name)
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % s
    flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(s, -1)                       # [S, n/S]
    scale = jnp.max(jnp.abs(chunks), axis=1, keepdims=True) / 127.0 + 1e-20
    q = jnp.clip(jnp.round(chunks / scale), -127, 127).astype(jnp.int8)
    # reduce-scatter: all_to_all the int8 chunks, dequantise + sum locally
    q_t = jax.lax.all_to_all(q[:, None], axis_name, split_axis=0,
                             concat_axis=1)            # [1, S, n/S] int8
    scale_t = jax.lax.all_gather(scale, axis_name)     # [S, S, 1]
    my = jax.lax.axis_index(axis_name)
    sc = scale_t[:, my]                                # [S, 1] scales for my chunk
    part = jnp.sum(q_t[0].astype(jnp.float32) * sc, axis=0)   # [n/S] f32
    # all-gather the reduced chunks, int8-quantised again
    psc = jnp.max(jnp.abs(part)) / 127.0 + 1e-20
    pq = jnp.clip(jnp.round(part / psc), -127, 127).astype(jnp.int8)
    all_q = jax.lax.all_gather(pq, axis_name)          # [S, n/S] int8
    all_sc = jax.lax.all_gather(psc, axis_name)        # [S]
    out = (all_q.astype(jnp.float32) * all_sc[:, None]).reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(x.shape).astype(x.dtype)
