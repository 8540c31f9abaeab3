"""EmbeddingBag kernel: wave-DMA gather + in-VMEM reduce (recsys hot loop).

JAX has no native EmbeddingBag; the jnp path (gather [B,L,E] then reduce)
materialises the full gathered tensor in HBM. This kernel keeps the bag
reduction in VMEM: the table stays in HBM (memory_space=ANY), bag member
rows stream in via double-buffered DMA waves, and each wave accumulates into
the output tile — HBM traffic is exactly rows-read + bags-written.

Shapes / dtypes
  table    [R, E]  any float (accumulation in f32)
  ids      [B, L]  i32 rows into ``table`` (pad a short bag with weight-0
                   slots — ids must still be in [0, R))
  weights  [B, L]  f32 or None (None -> all-ones; "mean" divides by the
                   weight sum per bag, clamped away from 0)
  ->       bags [B, E] f32; combine in {"sum", "mean"}

Grid / block layout
  grid = (B / block_b,): one step per bag block. ids/weights tiles
  [block_b, L] live in VMEM (BlockSpec); the table is never tiled in.
  scratch [2, wave, E] + 2 DMA semaphores double-buffer the row fetches
  (block_b*L fetches issued ``wave`` at a time), and acc [block_b, E]
  holds the running weighted sums; the combine normalisation happens once
  at the end. ``wave`` is shrunk to divide block_b*L.

Platforms
  ``interpret=None`` resolves platform-aware (kernels.resolve_interpret):
  compiled on TPU, the interpreter elsewhere. ``ops.embedding_bag``
  picks Pallas on every TPU call, and off-TPU only under
  REPRO_PALLAS=interpret; otherwise the jnp oracle
  ``ref.embedding_bag_ref`` does the gather-then-reduce in HBM — same
  numbers, more traffic. The recsys models route through ``ops``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _kernel(combine: str, wave: int, ids_ref, w_ref, table_ref, out_ref,
            scratch, acc, sems):
    bq, l = ids_ref.shape
    e = out_ref.shape[1]
    total = bq * l
    total_waves = total // wave

    def dma(slot, w_idx):
        def issue(i, _):
            flat = w_idx * wave + i
            row = ids_ref[flat // l, flat % l]
            pltpu.make_async_copy(
                table_ref.at[pl.ds(row, 1)], scratch.at[slot, pl.ds(i, 1)],
                sems.at[slot]).start()
            return 0
        jax.lax.fori_loop(0, wave, issue, 0)

    def wait(slot):
        def w(i, _):
            pltpu.make_async_copy(
                table_ref.at[pl.ds(0, 1)], scratch.at[slot, pl.ds(i, 1)],
                sems.at[slot]).wait()
            return 0
        jax.lax.fori_loop(0, wave, w, 0)

    acc[...] = jnp.zeros_like(acc)
    dma(0, 0)

    def step(w_idx, _):
        slot = w_idx % 2

        @pl.when(w_idx + 1 < total_waves)
        def _():
            dma((w_idx + 1) % 2, w_idx + 1)

        wait(slot)
        rows = scratch[slot].astype(jnp.float32)            # [wave, E]

        def one(i, _):
            flat = w_idx * wave + i
            b_i, l_i = flat // l, flat % l
            wgt = w_ref[b_i, l_i].astype(jnp.float32)
            acc[b_i, :] = acc[b_i, :] + rows[i, :] * wgt
            return 0

        jax.lax.fori_loop(0, wave, one, 0)
        return 0

    jax.lax.fori_loop(0, total_waves, step, 0)
    if combine == "mean":
        denom = jnp.maximum(jnp.sum(w_ref[...].astype(jnp.float32), axis=1,
                                    keepdims=True), 1e-9)
        out_ref[...] = acc[...] / denom
    else:
        out_ref[...] = acc[...]


def embedding_bag_pallas(table: jax.Array, ids: jax.Array,
                         weights: jax.Array | None = None,
                         *, combine: str = "sum", block_b: int = 8,
                         wave: int = 8,
                         interpret: bool | None = None) -> jax.Array:
    """table [R,E] (HBM), ids [B,L], weights [B,L] -> bags [B,E] f32.
    ``interpret=None`` resolves platform-aware."""
    return _call(table, ids, weights, combine, block_b, wave,
                 resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("combine", "block_b", "wave",
                                             "interpret"))
def _call(table, ids, weights, combine, block_b, wave, interpret):
    b, l = ids.shape
    e = table.shape[1]
    if weights is None:
        weights = jnp.ones((b, l), jnp.float32)
    block_b = min(block_b, b)
    while b % block_b:
        block_b -= 1
    wave = min(wave, block_b * l)
    while (block_b * l) % wave:
        wave -= 1

    return pl.pallas_call(
        functools.partial(_kernel, combine, wave),
        grid=(b // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, l), lambda i: (i, 0)),    # ids
            pl.BlockSpec((block_b, l), lambda i: (i, 0)),    # weights
            pl.BlockSpec(memory_space=pl.ANY),            # table
        ],
        out_specs=pl.BlockSpec((block_b, e), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, e), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, wave, e), table.dtype),
            pltpu.VMEM((block_b, e), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(ids, weights, table)
