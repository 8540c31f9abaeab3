"""Public jit'd wrappers for the kernel layer, with backend dispatch.

On a TPU backend every op here runs its compiled Pallas kernel, always:
no environment variable routes a TPU process to the jnp reference or
to the interpreter.

Off-TPU (the CPU tests), ``REPRO_PALLAS=interpret`` runs the Pallas
kernel under the interpreter; unset, or any other value, runs the
pure-jnp reference.

The jnp reference paths are the same oracles the kernel tests assert
against, so behaviour is identical either way.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels import ref as _ref


def _use_pallas() -> tuple[bool, bool]:
    """-> (use_pallas, interpret)."""
    if on_tpu():
        return True, False
    interp = os.environ.get("REPRO_PALLAS") == "interpret"
    return interp, interp


# ---------------------------------------------------------------------------
def gather_distance(vectors: jax.Array, q: jax.Array, ids: jax.Array,
                    *, metric: str = "cosine",
                    scales: jax.Array | None = None) -> jax.Array:
    """Fused gather+distance: vectors [N,D], q [B,D], ids [B,K] -> [B,K].

    ``vectors`` may be codec-encoded (f32 / bf16 / int8, DESIGN.md §9);
    ``scales`` [N] fuses the per-row decode into the distance."""
    use, interp = _use_pallas()
    if use:
        from repro.kernels.gather_distance import gather_distance_pallas
        return gather_distance_pallas(vectors, q, ids, metric=metric,
                                      scales=scales, interpret=interp)
    return _ref.gather_distance_ref(vectors, q, ids, metric=metric,
                                    scales=scales)


def beam_search(vectors: jax.Array, neighbors0: jax.Array, q: jax.Array,
                ep: jax.Array, ep_dist: jax.Array, *, ef: int,
                metric: str = "cosine", scales: jax.Array | None = None,
                expand_t: int = 4, max_iters: int | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """Whole layer-0 ef-beam HNSW search in ONE launch (DESIGN.md §12):
    per-hop neighbor gather, fused codec-decode distance, and in-kernel
    bitonic beam merge, expanding the top ``expand_t`` frontier nodes
    per hop. vectors [N,D] (any codec dtype, ``scales`` [N] decodes),
    neighbors0 [N,2M] i32, q [B,D], ep/ep_dist [B] entry points ->
    (ids [B,ef], dists [B,ef]) ascending by (d, id), empty slots
    (-1, INF). The jnp fallback is the identical algorithm on the same
    helpers (``ref.beam_search_ref``)."""
    use, interp = _use_pallas()
    if use:
        from repro.kernels.beam_search import beam_search_pallas
        return beam_search_pallas(vectors, neighbors0, q, ep, ep_dist,
                                  ef=ef, metric=metric, scales=scales,
                                  expand_t=expand_t, max_iters=max_iters,
                                  interpret=interp)
    return _ref.beam_search_ref(vectors, neighbors0, q, ep, ep_dist,
                                ef=ef, metric=metric, scales=scales,
                                expand_t=expand_t, max_iters=max_iters)


@functools.partial(jax.jit, static_argnames=("m", "metric"))
def _select_neighbors_jit(vectors, q, cand_ids, *, m, metric, scales):
    return _ref.select_neighbors_ref(vectors, q, cand_ids, m=m,
                                     metric=metric, scales=scales)


def select_neighbors(vectors: jax.Array, q: jax.Array, cand_ids: jax.Array,
                     *, m: int, metric: str = "cosine",
                     scales: jax.Array | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """Batched HNSW neighbor-selection heuristic (Malkov Alg. 4 with
    pruned-candidate backfill, DESIGN.md §13): vectors [N,D] (any codec
    dtype, ``scales`` [N] decodes), q [B,D], cand_ids [B,C] i32 -1-pad
    -> (ids [B,m] i32 -1-pad, dists [B,m] f32 INF-pad), per row
    output-identical to the host ``select_heuristic_host`` oracle.

    jnp-only: the op is one [B,C,C] einsum + a C-step masked keep-scan,
    which XLA already fuses well at construction's C = efConstruction
    sizes — a hand-written Pallas lowering has nothing left to fuse, so
    every backend runs the reference (unlike the query-path ops above,
    where the win is cross-hop fusion)."""
    return _select_neighbors_jit(vectors, q, cand_ids, m=m, metric=metric,
                                 scales=scales)


def flat_topk(db: jax.Array, q: jax.Array, k: int,
              *, metric: str = "cosine",
              scales: jax.Array | None = None,
              valid: jax.Array | None = None
              ) -> tuple[jax.Array, jax.Array]:
    """Exact k-NN: db [N,D], q [B,D] -> (dists [B,k], ids [B,k]).

    ``db`` may be codec-encoded (f32 / bf16 / int8, DESIGN.md §9);
    ``scales`` [N] fuses the per-row decode into the distance. Rows
    where ``valid`` [N] is False never outrank a valid row."""
    use, interp = _use_pallas()
    if use:
        from repro.kernels.distance_topk import distance_topk_pallas
        pd, pi = distance_topk_pallas(db, q, k, metric=metric,
                                      scales=scales, valid=valid,
                                      interpret=interp)
        with jax.named_scope("flat.merge"):        # tiny [B, T*k] merge
            neg, j = jax.lax.top_k(-pd, k)
            return -neg, jnp.take_along_axis(pi, j, axis=1)
    return _ref.distance_topk_ref(db, q, k, metric=metric, scales=scales,
                                  valid=valid)


def embedding_bag(table: jax.Array, ids: jax.Array,
                  weights: jax.Array | None = None,
                  *, combine: str = "sum") -> jax.Array:
    """EmbeddingBag: table [R,E], ids [B,L] -> [B,E]."""
    use, interp = _use_pallas()
    if use:
        from repro.kernels.embedding_bag import embedding_bag_pallas
        return embedding_bag_pallas(table, ids, weights, combine=combine,
                                    interpret=interp)
    return _ref.embedding_bag_ref(table, ids, weights, combine=combine)


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 cur_len) -> jax.Array:
    """Decode attention: q [B,H,Dh], k/v [B,S,KVH,Dh] -> [B,H,Dh] f32.

    ``cur_len`` is a scalar or a per-sequence [B] vector of live prefix
    lengths — the serving hot loop (``models/transformer.decode_step``)
    passes [B] so one dispatch decodes continuous-batching slots at
    different depths (DESIGN.md §11)."""
    use, interp = _use_pallas()
    if use:
        from repro.kernels.flash_decode import flash_decode_pallas
        return flash_decode_pallas(q, k, v, cur_len, interpret=interp)
    return _ref.flash_decode_ref(q, k, v, jnp.asarray(cur_len, jnp.int32))
