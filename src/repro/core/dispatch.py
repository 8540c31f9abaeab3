"""Counters and spans of the search path (DESIGN.md §12).

Counters are named host-side integers, bumped at the PYTHON boundary
of each compiled entry point (never inside a trace): they count what a
call *submits* per invocation under the compiled program's static
launch structure. Not thread-safe by design — the serving layer already
serializes device work onto one dispatcher. The counters and who reads
them:

  ``hnsw.beam_launches``    device launches of the layer-0 beam per
                            ``search_graph`` (1 fused, O(ef) jnp):
                            tests/test_beam_search.py, benchmarks/
                            bench_query.py and bench_build.py
  ``hnsw.h2d_bytes``        bytes a device-graph upload or sync moves:
                            tests/test_build.py, benchmarks/bench_build.py
  ``stacked.search_stacked`` compiled dispatches of the sharded graph
                            search (one per ``query_batch`` at any S):
                            tests/test_sharded.py
  ``distance_topk.passes``  extraction passes of the flat scan kernel,
                            summed over its (query tile, db tile) steps,
                            when ``distance_topk_passes`` runs (never on
                            the search path): tests/test_kernels.py and
                            the pass measurement in PERF.md

Spans are ``jax.profiler.TraceAnnotation``s on the profiler's own
clock: free of cost beyond a context manager when no trace is taken.
They are read from a profiler trace (TensorBoard's trace viewer, or
``jax.profiler.ProfileData``), where each device idle gap falls inside
the innermost span the host was in; tests/test_spans.py checks where
each is written. The benchmark's trace reduction (``bench/devtrace.py``)
keeps only its own span names so far (PERF.md, Open questions). None
sits inside a per-row or per-request loop. ``SPANS`` lists every name:

  ``engine.coalesce``   one per ``RetrievalEngine.step``: epoch check,
                        cache keys, LRU lookups, dedup, grouping
  ``engine.dispatch``   one per chunk: stack and pad, then the index call
  ``engine.fanout``     results to handles and cache, per chunk and for
                        the tick's dedup followers
  ``index.scan``        query upload and prep, enqueueing the device
                        search and its merge
  ``index.fetch``       the host blocked on the device results, then D2H
                        (and on one shard the map from packed to stored
                        rows)
  ``index.rerank``      the exact fp32 rerank of a lossy codec's
                        over-fetch (HNSW; flat only over the bf16
                        wire)
  ``index.keys``        row ids to keys, padding to k
"""
from __future__ import annotations

from collections import defaultdict

import jax

_COUNTS: defaultdict[str, int] = defaultdict(int)

SPANS = ("engine.coalesce", "engine.dispatch", "engine.fanout",
         "index.scan", "index.fetch", "index.rerank", "index.keys")


def bump(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` (created at 0 on first use)."""
    _COUNTS[name] += int(n)


def get(name: str) -> int:
    return _COUNTS[name]


def reset(*names: str) -> None:
    """Reset the given counters, or ALL counters when called bare."""
    if names:
        for name in names:
            _COUNTS.pop(name, None)
    else:
        _COUNTS.clear()


def snapshot() -> dict[str, int]:
    return dict(_COUNTS)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` (one of ``SPANS``) on the profiler's
    clock; ``args`` ride along as the event's stats."""
    return jax.profiler.TraceAnnotation(name, **args)


def beam_launches(beam_impl: str, ef: int,
                  max_iters: int | None = None) -> int:
    """Device launches one search contributes on the layer-0 beam path.

    ``fused`` runs the whole ef-beam as ONE kernel launch
    (kernels/beam_search.py). ``jnp`` compiles to a ``while_loop`` whose
    body re-dispatches the gather+sort work every hop — its static hop
    bound (``max_iters``, default ef) is the per-call launch count the
    fused kernel eliminates."""
    if beam_impl == "fused":
        return 1
    return max(int(ef if max_iters is None else max_iters), 1)
