"""Exact flat index: brute-force top-k (the recall oracle + retrieval_cand).

Routes through kernels/ops.flat_topk (Pallas distance+top-k on TPU, jnp
reference on CPU). This is also the "real time at 1M" claim's workload
(paper section 5): one query against the full database.

Two layers here:
  * ``FlatIndex`` — the immutable device-array core (kept as-is: it is the
    oracle other backends call into);
  * ``FlatVectorIndex`` — the keyed, mutable ``VectorIndex`` backend
    (DESIGN.md §1), built on the shard-aware ``ShardedRows`` substrate
    (DESIGN.md §8): rows live in per-shard device blocks routed by key
    hash, queries fan out to every shard and merge through the
    hierarchical top-k tree. With ``n_shards=1`` (the default) the
    substrate collapses to the historical single-device path —
    bit-for-bit, so the existing suite doubles as the parity oracle.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codec import (check_codec_arrays as _check_codec_arrays,
                              effective_rerank, get_codec)
from repro.core.dispatch import span
from repro.core.hnsw_build import normalize_rows
from repro.core.index import VectorIndex
from repro.core.sharded import ShardedRows
from repro.kernels import ops


@dataclasses.dataclass
class FlatIndex:
    vectors: jax.Array          # [N, D] (normalised if cosine); may be
                                # codec-encoded (f32/bf16/int8, DESIGN.md §9)
    metric: str = "cosine"
    scales: jax.Array | None = None   # [N] per-row decode scales (int8)

    @classmethod
    def build(cls, vectors, metric: str = "cosine") -> "FlatIndex":
        v = np.asarray(vectors, np.float32)
        if metric == "cosine":
            v = normalize_rows(v)
        return cls(vectors=jnp.asarray(v), metric=metric)

    def query(self, queries, k: int = 10):
        q = jnp.asarray(queries, jnp.float32)
        squeeze = q.ndim == 1
        if squeeze:
            q = q[None]
        if self.metric == "cosine":
            q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
        d, i = ops.flat_topk(self.vectors, q, k, metric=self.metric,
                             scales=self.scales)
        if squeeze:
            return d[0], i[0]
        return d, i

    @property
    def n(self) -> int:
        return self.vectors.shape[0]


def _pad_results(keys: list[list], d: np.ndarray, k: int
                 ) -> tuple[list[list], np.ndarray]:
    """Protocol shape contract: k > live pads keys with None, dists with
    INF, so every backend returns exactly k slots (DESIGN.md §1)."""
    short = k - d.shape[1]
    if short <= 0:
        return keys, d
    keys = [row + [None] * short for row in keys]
    d = np.concatenate(
        [d, np.full((d.shape[0], short), np.float32(3e38))], axis=1)
    return keys, d


class FlatVectorIndex(VectorIndex):
    """Mutable keyed flat index. Exact by construction, so ``query`` and
    ``exact_query`` coincide. Storage, key->shard routing, and free-slot
    bookkeeping live in ``ShardedRows``; mutations mark the device
    block(s) stale and the next query re-packs once (DESIGN.md §8).

    ``dtype`` picks the row codec (fp32 | bf16 | int8, DESIGN.md §9):
    device blocks and snapshot pages hold the encoded rows; lossy
    searches run the asymmetric scan, over-fetch ``k·rerank_factor``
    candidates, and rerank exactly in fp32 from the canonical host rows.
    """

    kind = "flat"

    def __init__(self, *, metric: str = "cosine", dim: int | None = None,
                 n_shards: int = 1, dtype: str = "fp32",
                 rerank_factor: int | None = None):
        if metric not in ("cosine", "ip", "l2"):
            raise ValueError(f"unknown metric {metric!r}")
        self.metric = metric
        self.dim = dim
        self.n_shards = int(n_shards)
        self.dtype = str(dtype)
        self.rerank_factor = rerank_factor
        self._codec = get_codec(self.dtype)
        self._rows = ShardedRows(n_shards=self.n_shards, metric=metric,
                                 dim=dim, normalize_on_pack=True,
                                 codec=self._codec)

    # ------------------------------------------------------------ mutation
    def _insert_impl(self, key: str, value: np.ndarray) -> None:
        self._rows.upsert(key, np.asarray(value, np.float32).reshape(-1))
        self.dim = self._rows.dim
        self._bump_epoch()

    def _bulk_insert_impl(self, keys: list[str], values: np.ndarray) -> None:
        self._rows.upsert_many(keys, values)
        self.dim = self._rows.dim
        self._bump_epoch()

    def _update_impl(self, key: str, value: np.ndarray) -> None:
        self._insert_impl(key, value)

    def _delete_impl(self, key: str) -> None:
        self._rows.tombstone(key)
        self._bump_epoch()

    def _compact_impl(self) -> None:
        """Physically drop tombstoned rows (DESIGN.md §7): live rows are
        re-packed contiguously — host-side AND in every shard's block —
        and dead vectors cease to exist."""
        self._rows.compact()
        self._bump_epoch()

    # --------------------------------------------------------------- query
    def query_batch(self, queries, k: int = 10, **kw):
        """ONE sharded device dispatch for the whole [B, D] batch: every
        shard scans its own rows, per-shard top-k merges through the
        hierarchical tree (exact top-k either way). Under a lossy codec
        the scan is asymmetric (fp32 query vs encoded rows), over-fetches
        ``k·rerank_factor`` candidates, and reranks exactly in fp32 from
        the canonical host rows (DESIGN.md §9)."""
        q = np.asarray(queries, np.float32)
        if q.ndim != 2:
            raise ValueError(f"query_batch expects [B, D], got {q.shape}")
        rf = effective_rerank(self._codec, self.rerank_factor)
        if rf <= 1:
            d, rows = self._rows.topk(q, k)
        else:
            _, cand = self._rows.topk(q, k * rf)
            with span("index.rerank", rows=q.shape[0]):
                d, rows = self._rows.rerank_topk(q, cand, k)
        with span("index.keys", rows=q.shape[0]):
            keys = [[self._rows.key_of_row(int(r)) if r >= 0 else None
                     for r in row] for row in rows]
            return _pad_results(keys, d, k)

    def exact_query(self, query, k: int = 10):
        return self.query(query, k)        # flat IS the brute-force oracle

    # --------------------------------------------------------- persistence
    # Canonical state only (DESIGN.md §8): shard placement is derived
    # from the keys, so the SAME state_dict restores onto any shard count.
    # Under a lossy codec the persisted rows are the ENCODED bytes +
    # scales (DESIGN.md §9) — the fp32 side is their exact decode, so
    # snapshots shrink with the codec and restore stays bit-for-bit.
    def config_dict(self) -> dict:
        return {"metric": self.metric, "dim": self.dim,
                "n_shards": self.n_shards, "dtype": self.dtype,
                "rerank_factor": self.rerank_factor}

    def state_dict(self) -> tuple[dict, dict]:
        if self._codec.lossy:
            arrays = {"vectors_enc":
                      self._codec.to_storage(self._rows.encoded),
                      "alive": self._rows.alive}
            if self._rows.scales is not None:
                arrays["scales"] = self._rows.scales
        else:
            arrays = {"vectors": self._rows.vectors,
                      "alive": self._rows.alive}
        meta = {"keys": list(self._rows.key_list), "epoch": self._epoch}
        return arrays, meta

    def restore_state(self, arrays: dict, meta: dict) -> None:
        _check_codec_arrays(self._codec, arrays, self.kind)
        if self._codec.lossy:
            self._rows.restore_encoded(arrays["vectors_enc"],
                                       arrays.get("scales"),
                                       list(meta["keys"]),
                                       np.asarray(arrays["alive"], bool))
        else:
            self._rows.restore(np.asarray(arrays["vectors"], np.float32),
                               list(meta["keys"]),
                               np.asarray(arrays["alive"], bool))
        if self._rows.dim:
            self.dim = self._rows.dim
        self._epoch = int(meta["epoch"])

    def _row_count(self) -> int:
        return self._rows.row_count

    @property
    def size(self) -> int:
        return self._rows.size

    def _contains(self, key: str) -> bool:
        return self._rows.contains(key)

    def keys(self) -> list[str]:
        return self._rows.live_keys()

    @property
    def shard_count(self) -> int:
        return self.n_shards

    def shard_stats(self) -> list[dict]:
        return self._rows.shard_stats()
