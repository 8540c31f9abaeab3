"""Flash-decode kernel: one-token GQA attention over a long KV cache.

Split-K over the sequence: grid (B, S tiles); running (m, l, acc) scratch
carries the online softmax across tiles (classic flash decoding). The KV
tiles stream HBM->VMEM via BlockSpec; per tile the score/PV matmuls run per
KV head (static loop, G query heads per KV head).

Shapes / dtypes
  q        [B, H, Dh]       any float (cast to f32 for scores)
  k, v     [B, S, KVH, Dh]  any float; H = G * KVH (GQA groups)
  cur_len  i32 scalar or [B]  live prefix length; positions >= cur_len are
                            masked (cache slots are capacity-padded). The
                            [B] form is the continuous-batching contract
                            (DESIGN.md §11): every serving slot carries its
                            OWN position, so one dispatch decodes slots at
                            different depths — admissions/evictions never
                            change the compiled shape, only the mask.
  ->       out [B, H, Dh] f32

Grid / block layout
  grid = (B, S / block_s); program (i, j) loads query row i (VMEM) and KV
  tile j [1, block_s, KVH, Dh] (BlockSpec-pipelined). cur_len sits in
  SMEM as a [B] vector; program (i, j) reads its own row's length.
  Scratch m/l [H, 1] + acc [H, Dh] carry the online softmax across
  the j axis (sequential grid dim on TPU); tile 0 initialises them, the
  last tile writes acc / l. block_s is shrunk to divide S.

Platforms
  ``interpret=None`` resolves platform-aware (kernels.resolve_interpret):
  the compiled kernel on TPU, always; the Pallas interpreter elsewhere.
  ``ops.flash_decode`` runs this kernel on every TPU call. Off-TPU it
  runs the jnp oracle ``ref.flash_decode_ref`` (the same masked
  softmax-attention in one shot) unless REPRO_PALLAS=interpret.
  ``models/transformer.py``'s decode step consumes either.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG = -1e30   # plain float: pallas kernels must not capture traced constants


def _kernel(st: int, kvh: int, g: int, cur_ref, q_ref, k_ref, v_ref, out_ref,
            m_sc, l_sc, acc_sc):
    i = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    dh = q_ref.shape[2]
    scale = dh ** -0.5

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full_like(m_sc, NEG)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    kt = k_ref[0]                                   # [st, KVH, Dh]
    vt = v_ref[0]
    q = q_ref[0]                                    # [H, Dh]
    pos = j * st + jax.lax.broadcasted_iota(jnp.int32, (1, st), 1)[0]
    valid = pos < cur_ref[i]                        # [st]; per-sequence length

    for h in range(kvh):
        sl = slice(h * g, (h + 1) * g)
        qg = q[sl, :].astype(jnp.float32) * scale   # [G, Dh]
        kh = kt[:, h, :].astype(jnp.float32)        # [st, Dh]
        s = jax.lax.dot_general(qg, kh, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [G, st]
        s = jnp.where(valid[None, :], s, NEG)
        m_prev = m_sc[sl, 0]
        l_prev = l_sc[sl, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        m_safe = jnp.where(m_new <= NEG / 2, 0.0, m_new)
        p = jnp.where(valid[None, :], jnp.exp(s - m_safe[:, None]), 0.0)
        alpha = jnp.where(m_prev <= NEG / 2, 0.0, jnp.exp(m_prev - m_safe))
        vh = vt[:, h, :].astype(jnp.float32)        # [st, Dh]
        pv = jax.lax.dot_general(p, vh, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        m_sc[sl, 0] = m_new
        l_sc[sl, 0] = l_prev * alpha + jnp.sum(p, axis=1)
        acc_sc[sl, :] = acc_sc[sl, :] * alpha[:, None] + pv

    @pl.when(j == nj - 1)
    def _():
        out_ref[0] = (acc_sc[...]
                      / jnp.maximum(l_sc[...], 1e-30)).astype(out_ref.dtype)


def flash_decode_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                        cur_len: jax.Array, *, block_s: int = 512,
                        interpret: bool | None = None) -> jax.Array:
    """q [B,H,Dh]; k,v [B,S,KVH,Dh]; cur_len scalar or [B] i32 -> [B,H,Dh] f32.
    ``interpret=None`` resolves platform-aware."""
    return _call(q, k, v, cur_len, block_s, resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def _call(q, k, v, cur_len, block_s, interpret):
    b, h, dh = q.shape
    s, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    block_s = min(block_s, s)
    while s % block_s:
        block_s -= 1
    # scalar cur_len broadcasts to one length per batch row; [B] passes
    # through — every slot masks at its own depth (one compiled shape)
    cur = jnp.broadcast_to(
        jnp.asarray(cur_len, jnp.int32).reshape(-1), (b,))

    grid = (b, s // block_s)
    return pl.pallas_call(
        functools.partial(_kernel, block_s, kvh, g),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                    # cur_len
            pl.BlockSpec((1, h, dh), lambda i, j: (i, 0, 0)),         # q
            pl.BlockSpec((1, block_s, kvh, dh), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, block_s, kvh, dh), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, dh), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, dh), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),       # m
            pltpu.VMEM((h, 1), jnp.float32),       # l
            pltpu.VMEM((h, dh), jnp.float32),      # acc
        ],
        interpret=interpret,
        name="flash_decode",
    )(cur, q, k, v)
