"""The plain reference and the comparison that decides ``correct``.

It imports nothing of the program. Each configuration's reference file
(``configs/<config>.py``) says how the configuration stores a row
(``stored_rows``) and whether its search is exact (``EXACT``); this module
holds what is common: the exact top-k, the readings, and the control.

Readings, each held to the limit of its own in the configuration's
``checks``:

  unanswered  requests due in the window that never came back, or came
              back with an error
  malformed   answers that are not k distinct keys of the corpus with
              finite distances in ascending order
  dist_err    the widest gap between a distance the engine returned and
              the reference's float64 distance from the same query to the
              same key's stored row
  rank_gap    exact configurations only: on a sample drawn from the seed,
              the widest amount by which the i-th returned key's reference
              distance exceeds the i-th of the reference's exact top-k

The control is the reference in the program's place at the next
precision down, bfloat16 (the configurations state float32 arithmetic):
it answers with bfloat16 distances and, where the search is exact, with
its own bfloat16 top-k.
"""
from __future__ import annotations

import numpy as np

CANDIDATES = 64          # exact top-k: device candidates refined in f64
QUERY_BLOCK = 256


def normalize32(x: np.ndarray) -> np.ndarray:
    """Cosine normalisation in float32, as the configurations store rows."""
    x = np.asarray(x, np.float32)
    return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True),
                          np.float32(1e-12))


def unit64(q: np.ndarray) -> np.ndarray:
    q = np.asarray(q, np.float64)
    return q / np.maximum(np.linalg.norm(q, axis=-1, keepdims=True), 1e-300)


def key_ids(keys: list, n_rows: int) -> np.ndarray | None:
    """Row ids of keys ``d<i>``; None unless every key names a row."""
    out = []
    for key in keys:
        if not isinstance(key, str) or not key.startswith("d"):
            return None
        try:
            i = int(key[1:])
        except ValueError:
            return None
        if not 0 <= i < n_rows:
            return None
        out.append(i)
    return np.asarray(out, np.int64)


def exact_topk(stored: np.ndarray, queries: np.ndarray, k: int
               ) -> tuple[np.ndarray, np.ndarray]:
    """Exact cosine top-k over stored rows -> (ids [Q, k], float64 dists).

    The device ranks at full float32 precision to CANDIDATES per query;
    the host re-scores those in float64 and keeps the best k."""
    import jax
    import jax.numpy as jnp

    rows = jnp.asarray(stored, jnp.float32)
    top = jax.jit(lambda r, q: jax.lax.top_k(
        jnp.dot(q, r.T, precision=jax.lax.Precision.HIGHEST),
        min(CANDIDATES, r.shape[0]))[1])
    ids_out, d_out = [], []
    for lo in range(0, len(queries), QUERY_BLOCK):
        q64 = unit64(queries[lo:lo + QUERY_BLOCK])
        cand = np.asarray(top(rows, jnp.asarray(q64, jnp.float32)))
        d = 1.0 - np.einsum("qcd,qd->qc", stored[cand].astype(np.float64),
                            q64)
        order = np.argsort(d, axis=1, kind="stable")[:, :k]
        ids_out.append(np.take_along_axis(cand, order, 1))
        d_out.append(np.take_along_axis(d, order, 1))
    del rows
    return np.concatenate(ids_out), np.concatenate(d_out)


def readings(reference, stored: np.ndarray, queries, answers: dict,
             attempted: int, k: int, sample: np.ndarray) -> dict:
    """The numbers compared. ``answers`` maps a request's stream index to
    (keys, dists) for every request that came back without error;
    ``sample`` lists the stream indices whose rank_gap is read."""
    n = len(stored)
    malformed, dist_err = 0, 0.0
    ids_of = {}
    for i, (keys, dists) in answers.items():
        ids = key_ids(keys, n)
        d = np.asarray(dists, np.float64)
        if (ids is None or len(ids) != k or len(set(ids.tolist())) != k
                or d.shape != (k,) or not np.isfinite(d).all()
                or (np.diff(d) < 0).any()):
            malformed += 1
            continue
        ids_of[i] = ids
    if ids_of:
        order = sorted(ids_of)
        for lo in range(0, len(order), 8192):
            idx = order[lo:lo + 8192]
            q64 = unit64(np.stack([queries[i] for i in idx]))
            rows = stored[np.stack([ids_of[i] for i in idx])]
            ref = 1.0 - np.einsum("bkd,bd->bk", rows.astype(np.float64), q64)
            got = np.stack([np.asarray(answers[i][1], np.float64)
                            for i in idx])
            dist_err = max(dist_err, float(np.abs(got - ref).max()))
    out = {"unanswered": attempted - len(answers), "malformed": malformed,
           "dist_err": dist_err}
    if reference.EXACT:
        picked = [int(i) for i in sample if int(i) in ids_of]
        gap = 0.0
        if picked:
            q = np.stack([queries[i] for i in picked])
            _, ex_d = exact_topk(stored, q, k)
            q64 = unit64(q)
            rows = stored[np.stack([ids_of[i] for i in picked])]
            ref = np.sort(1.0 - np.einsum("bkd,bd->bk",
                                          rows.astype(np.float64), q64), 1)
            gap = float((ref - ex_d).max())
        out["rank_gap"] = gap
    return out


def verdict(read: dict, limits: dict) -> tuple[bool, dict]:
    """-> (correct, {name: {"value", "limit"}}); every reading must be at
    or under its limit, and every limit must have its reading."""
    checks = {name: {"value": read[name], "limit": limits[name]}
              for name in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


class Control:
    """The reference in the program's place at bfloat16, standing in for
    the index's ``query_batch``. Where the configuration's search is
    exact it answers with its own bfloat16 top-k; otherwise it keeps the
    keys the program's ``query_batch`` found and gives them bfloat16
    distances."""

    def __init__(self, program_query_batch, reference, stored: np.ndarray):
        import jax
        import jax.numpy as jnp
        self.program = program_query_batch
        self.exact = reference.EXACT
        self.rows = jnp.asarray(stored, jnp.bfloat16)
        bf16 = jnp.bfloat16
        self._topk = jax.jit(lambda r, q, k: jax.lax.top_k(
            jnp.dot(q, r.T, preferred_element_type=bf16), k),
            static_argnums=2)
        self._dist = jax.jit(lambda r, q, ids: 1 - jnp.einsum(
            "bkd,bd->bk", r[ids], q, preferred_element_type=bf16))

    def query_batch(self, queries, k: int = 10, **kw):
        import jax.numpy as jnp
        q = jnp.asarray(normalize32(queries), jnp.bfloat16)
        if self.exact:
            top, ids = self._topk(self.rows, q, k)
            d = np.asarray((1 - top).astype(jnp.float32))
            return [[f"d{i}" for i in row] for row in np.asarray(ids)], d
        keys, _ = self.program(queries, k=k, **kw)
        ids = np.asarray([[int(key[1:]) for key in row] for row in keys])
        d = self._dist(self.rows, q, jnp.asarray(ids))
        return keys, np.asarray(d.astype(jnp.float32))
