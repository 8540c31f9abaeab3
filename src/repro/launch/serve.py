"""End-to-end serving driver: continuous-batching LM serving (optionally
with RAG augmentation, retrieval overlapped behind the decode loop).

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b \
        --requests 12 --max-new 16 [--rag]

RAG requests arrive closed-loop (a bounded window of outstanding
requests is kept topped up, like real traffic) and ride the engine's
tick state machine: late arrivals' ANN searches run behind the decode
dispatches of earlier requests (DESIGN.md §11) — the run reports
``overlap_ratio`` (fraction of retrieval ticks hidden behind decode)
and ``slot_occupancy`` alongside req/s.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.data.corpus import BUILTIN_CORPUS
from repro.models import transformer as tf
from repro.serve.engine import ServeEngine
from repro.serve.rag import RAGPipeline
from repro.utils import logger, use_compile_cache


def _power_of_two(v: str) -> int:
    n = int(v)
    if n < 1 or n & (n - 1):
        raise argparse.ArgumentTypeError(f"{v} is not a power of two")
    return n


def _serve_closed_loop(engine, queries, tenants, *, k, max_new):
    """Drive the engine closed-loop: keep up to 2*slots requests
    outstanding so retrieval for late arrivals overlaps decode ticks
    already running (an open-loop burst would retrieve everything on
    tick 1 with nothing to hide behind)."""
    window = 2 * engine.slots
    pend = list(zip(queries, tenants))
    reqs = []
    t0 = time.perf_counter()
    while pend or engine._work_pending():
        while pend and sum(not r.done for r in reqs) < window:
            q, t = pend.pop(0)
            reqs.append(engine.submit_rag(q, k=k, tenant=t,
                                          max_new_tokens=max_new))
        engine.step()
    dt = time.perf_counter() - t0
    engine.poll()
    return reqs, dt


def _log_engine_stats(engine):
    s = engine.stats.as_dict()
    logger.info(
        f"engine: {s['ticks']} ticks ({s['decode_ticks']} decode, "
        f"{s['prefills']} prefills), overlap_ratio "
        f"{s['overlap_ratio']:.2f} ({s['overlapped_ticks']}/"
        f"{s['retrieval_ticks']} retrieval ticks hidden behind decode), "
        f"slot_occupancy {s['slot_occupancy']:.2f}, "
        f"{s['re_retrievals']} epoch-guard re-retrievals")


def main(argv: list[str] | None = None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--rag", action="store_true")
    ap.add_argument("--index", default="hnsw",
                    choices=("flat", "ivf", "hnsw", "tiered"),
                    help="VectorIndex backend for the RAG retriever")
    ap.add_argument("--index-dtype", default=None,
                    choices=("fp32", "bf16", "int8"),
                    help="row-storage codec (DESIGN.md §9): encoded "
                         "device blocks + snapshot pages (int8 ≈ 4x "
                         "smaller), asymmetric search with fp32 rerank. "
                         "Default: fp32 (or the stored codec on a warm "
                         "restore — a mismatch is rejected)")
    ap.add_argument("--beam-impl", default=None,
                    choices=("fused", "jnp"),
                    help="HNSW layer-0 beam implementation (DESIGN.md "
                         "§12): 'fused' runs the whole ef-beam as one "
                         "kernel launch; 'jnp' is the per-hop while_loop "
                         "reference. Default: fused")
    ap.add_argument("--retrieval-batch", type=_power_of_two, default=128,
                    help="RetrievalEngine bucket cap (power of two)")
    ap.add_argument("--retrieval-cache", type=int, default=1024,
                    help="RetrievalEngine LRU entries (0 disables)")
    ap.add_argument("--shards", type=int, default=None,
                    help="partition the index over N mesh shards "
                         "(DESIGN.md §8): CRUD routes by key hash, "
                         "queries fan out + merge. Default: single "
                         "device (or the stored shard count on a warm "
                         "restore). CPU simulation needs XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--store-dir", default=None,
                    help="durable IndexStore directory (DESIGN.md §7): "
                         "restarts restore the index warm — snapshot + "
                         "WAL replay — instead of re-embedding the corpus")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="auto-snapshot the store every N mutations "
                         "(0: only the final snapshot on exit)")
    ap.add_argument("--tenants", type=int, default=0,
                    help="multi-tenant serving (DESIGN.md §10): front the "
                         "retriever with an IndexPool of N per-tenant "
                         "private corpora over one shared device arena. "
                         "Requests round-robin across tenants and still "
                         "coalesce into one retrieval dispatch per tick. "
                         "Implies a flat per-tenant index; --store-dir "
                         "becomes the pool root (per-tenant subdirs)")
    ap.add_argument("--max-resident", type=int, default=64,
                    help="with --tenants: LRU cap on arena-resident "
                         "tenants; the rest page to their store dirs")
    ap.add_argument("--sampler", default="greedy",
                    choices=("greedy", "temperature"),
                    help="token sampler; temperature draws fold (request, "
                         "position) into --seed, so output is independent "
                         "of the admission schedule")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_smoke_config(args.arch)
    params = tf.init_lm(jax.random.PRNGKey(args.seed), cfg)

    def build_engine(pipeline=None):
        return ServeEngine(params, cfg, pipeline=pipeline, slots=args.slots,
                           max_len=args.max_len, dtype=jnp.float32,
                           sampler=args.sampler,
                           temperature=args.temperature, seed=args.seed)

    if args.rag and args.tenants > 0:
        from repro.core import IndexPool
        from repro.data.corpus import HashingEncoder
        encoder = HashingEncoder()
        pool = IndexPool(args.store_dir, dim=encoder.dim,
                         n_shards=args.shards or 1,
                         dtype=args.index_dtype or "fp32",
                         max_resident=args.max_resident,
                         snapshot_every=args.snapshot_every or None)
        rag = RAGPipeline(encoder=encoder, index=pool,
                          retrieval_batch=args.retrieval_batch,
                          retrieval_cache=args.retrieval_cache)
        tids = [f"tenant{i}" for i in range(args.tenants)]
        for tid in tids:
            # each tenant holds a PRIVATE copy of the corpus — keys and
            # embeddings are namespaced, so identical texts never collide
            try:
                known = pool.size(tid)      # pages a durable tenant in
            except KeyError:
                known = 0
            if known:
                logger.info(f"{tid}: warm restore, {known} docs "
                            f"@ epoch {pool.epoch(tid)}")
                rag.register_texts(BUILTIN_CORPUS, tenant=tid)
            else:
                rag.add_documents(BUILTIN_CORPUS, tenant=tid)
        engine = build_engine(rag)
        queries = [["how does hnsw search work",
                    "why is on device retrieval private",
                    "what does efConstruction control"][i % 3]
                   for i in range(args.requests)]
        tenants = [tids[i % len(tids)] for i in range(args.requests)]
        reqs, dt = _serve_closed_loop(engine, queries, tenants, k=3,
                                      max_new=args.max_new)
        for i, r in enumerate(reqs):
            logger.info(f"req {i} [{r.tenant}]: retrieved "
                        f"{[d.key for d in r.docs]}")
        logger.info(f"RAG[pool x{args.tenants}]: {args.requests} requests "
                    f"in {dt:.1f}s ({args.requests / dt:.2f} req/s, "
                    f"overlapped continuous batching)")
        _log_engine_stats(engine)
        rs = rag.retriever.stats.as_dict()
        logger.info(
            f"retrieval: {rs['requests']} requests in {rs['searches']} "
            f"device dispatches across {len(set(tenants))} tenants "
            f"(cache hit rate {rs['hit_rate']:.2f})")
        ps = pool.pool_stats()
        logger.info(f"pool: {ps['tenants']} tenants, {ps['resident']} "
                    f"resident, {ps['arena_rows']} arena rows in "
                    f"{ps['slabs']} slabs ({ps['arena_bytes']} device "
                    f"bytes), {ps['evictions']} evictions")
        if args.store_dir:
            pool.flush()
            logger.info(f"pool flushed to {args.store_dir} "
                        f"(per-tenant snapshot + WAL; next start "
                        f"restores warm)")
        return

    if args.rag:
        store = None
        if args.store_dir:
            from repro.store import IndexStore
            store = IndexStore(args.store_dir,
                               snapshot_every=args.snapshot_every or None)
        rag = RAGPipeline(index_kind=args.index, index_store=store,
                          retrieval_batch=args.retrieval_batch,
                          retrieval_cache=args.retrieval_cache,
                          index_shards=args.shards,
                          index_dtype=args.index_dtype,
                          index_beam_impl=args.beam_impl)
        if rag.index.shard_count > 1:
            logger.info(f"index sharded over {rag.index.shard_count} "
                        f"devices (key-hash routing + fan-out search)")
        if rag.index.storage_dtype != "fp32":
            logger.info(f"index rows stored as {rag.index.storage_dtype} "
                        "(encoded device blocks + snapshot pages, "
                        "asymmetric search + fp32 rerank; DESIGN.md §9)")
        if rag.index.size:
            # warm restore: embeddings came back from the store (epoch
            # included — the retrieval cache keys on it); only the text
            # side-table needs repopulating
            logger.info(
                f"warm restore from {args.store_dir}: {rag.index.size} "
                f"docs @ mutation_epoch {rag.index.mutation_epoch}")
            rag.register_texts(BUILTIN_CORPUS)
        else:
            rag.add_documents(BUILTIN_CORPUS)
        engine = build_engine(rag)
        queries = [["how does hnsw search work",
                    "why is on device retrieval private",
                    "what does efConstruction control"][i % 3]
                   for i in range(args.requests)]
        reqs, dt = _serve_closed_loop(engine, queries,
                                      [None] * len(queries), k=3,
                                      max_new=args.max_new)
        for i, r in enumerate(reqs):
            logger.info(f"req {i}: retrieved {[d.key for d in r.docs]}")
        logger.info(f"RAG[{args.index}]: {args.requests} requests in {dt:.1f}s "
                    f"({args.requests / dt:.2f} req/s, overlapped "
                    f"continuous batching)")
        _log_engine_stats(engine)
        rs = rag.retriever.stats.as_dict()
        logger.info(
            f"retrieval: {rs['requests']} requests in {rs['searches']} device "
            f"dispatches ({rs['searched_queries']} searched + "
            f"{rs['padded_queries']} bucket pad, "
            f"cache hit rate {rs['hit_rate']:.2f})")
        if store is not None:
            path = store.snapshot(rag.index)
            logger.info(f"store snapshot: {path} "
                        f"(epoch {rag.index.mutation_epoch}; next start "
                        f"restores warm)")
        return

    engine = build_engine()
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab, size=rng.integers(4, 24))
               for _ in range(args.requests)]
    t0 = time.perf_counter()
    outs = engine.generate(prompts, max_new_tokens=args.max_new)
    dt = time.perf_counter() - t0
    logger.info(f"{args.requests} requests, {engine.tokens_out} tokens in "
                f"{dt:.1f}s -> {engine.tokens_out / dt:.1f} tok/s "
                f"({engine.ticks} engine ticks, {args.slots} slots)")
    assert all(len(o) == args.max_new for o in outs)


if __name__ == "__main__":
    main()
