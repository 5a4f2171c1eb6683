"""Pallas TPU kernel: flash-decoding attention (single-token query over a
long KV cache, online-softmax across KV blocks).

Decode attention reads the whole KV cache once per token — pure
HBM-bandwidth work.  The kernel streams [block_s, Dh] KV tiles through
VMEM, keeps the (m, l, acc) online-softmax state for one (batch, kv-head)
group in VMEM scratch across the KV-block grid axis, and finalizes the
output on the last block.  The GQA query group (G = H/KH heads) rides in
the second-to-last tile dimension so the score matmul [G, Dh] x [Dh, bs]
hits the MXU.

Grid: (B, KH, S_blocks) — S innermost so scratch carries are local to each
(batch, head).  Per-row cache lengths arrive via scalar prefetch and mask
tail blocks.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _decode_kernel(
    len_ref,                    # i32[B] scalar prefetch: per-row cache length
    q_ref,                      # f32[1, 1, G, Dh]
    k_ref,                      # f32[1, bs, 1, Dh]
    v_ref,                      # f32[1, bs, 1, Dh]
    o_ref,                      # f32[1, 1, G, Dh]
    m_scr,                      # f32[G, 1]   running max
    l_scr,                      # f32[G, 1]   running denominator
    acc_scr,                    # f32[G, Dh]  running numerator
    *,
    block_s: int,
    n_blocks: int,
    scale: float,
):
    b = pl.program_id(0)
    sblk = pl.program_id(2)

    @pl.when(sblk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    q = q_ref[0, 0] * scale                      # [G, Dh]
    k = k_ref[0, :, 0]                           # [bs, Dh]
    v = v_ref[0, :, 0]                           # [bs, Dh]
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                            # [G, bs]
    pos = sblk * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < len_ref[b], s, NEG_INF)

    m_prev = m_scr[...]                          # [G, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                       # [G, bs]
    corr = jnp.exp(m_prev - m_new)               # [G, 1]
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p, v, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                            # [G, Dh]
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = m_new

    @pl.when(sblk == n_blocks - 1)
    def _finalize():
        o_ref[0, 0] = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)


@functools.partial(
    jax.jit, static_argnames=("block_s", "interpret")
)
def decode_attention_pallas(
    q,             # [B, H, Dh]
    k_cache,       # [B, S, KH, Dh]
    v_cache,       # [B, S, KH, Dh]
    cache_len,     # i32[B]
    *,
    block_s: int = 512,
    interpret: Optional[bool] = None,
):
    """Returns o [B, H, Dh] = softmax(q k^T / sqrt(Dh)) v over valid cache."""
    B, H, Dh = q.shape
    S, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    block_s = min(block_s, S)
    pad = (-S) % block_s
    if pad:
        zeros = jnp.zeros((B, pad, KH, Dh), k_cache.dtype)
        k_cache = jnp.concatenate([k_cache, zeros], axis=1)
        v_cache = jnp.concatenate([v_cache, zeros], axis=1)
    n_blocks = (S + pad) // block_s
    qr = q.reshape(B, KH, G, Dh).astype(jnp.float32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KH, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, G, Dh), lambda b, h, s, L: (b, h, 0, 0)),
            pl.BlockSpec((1, block_s, 1, Dh), lambda b, h, s, L: (b, s, h, 0)),
            pl.BlockSpec((1, block_s, 1, Dh), lambda b, h, s, L: (b, s, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dh), lambda b, h, s, L: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, block_s=block_s, n_blocks=n_blocks,
            scale=1.0 / math.sqrt(Dh),
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, Dh), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(
        jnp.asarray(cache_len, jnp.int32), qr,
        k_cache.astype(jnp.float32), v_cache.astype(jnp.float32),
    )
    return out.reshape(B, H, Dh).astype(q.dtype)
