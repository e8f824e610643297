"""Pallas TPU decode attention: one query token vs. a long KV cache.

Decode is HBM-bandwidth-bound (the cache read dominates); the kernel
streams the cache through VMEM in ``block_k`` chunks with the running
softmax state in scratch, exactly one pass over K and V.  Per-sequence
valid lengths live in SMEM (scalar prefetch) so padded cache tail blocks
are masked, and blocks entirely past the length are skipped — for
mixed-length continuous-batching this prunes the tail reads.

Layout contract: q [B, H, D]; k/v caches [B, KV, S, D]; lengths i32[B].
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *,
                   scale: float, block_k: int, group: int):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    length = len_ref[b]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)          # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [G, bk]
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (group, block_k), 1)
        s = jnp.where(k_pos < length, s, _NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    # Skip cache blocks entirely past this sequence's length.
    pl.when(ki * block_k < length)(_compute)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention_pallas(q, k_cache, v_cache, lengths, *,
                            scale: float | None = None,
                            block_k: int = 512, interpret: bool = True):
    """q: [B,H,D]; caches [B,KV,S,D]; lengths i32[B] -> [B,H,D].

    Grid: (B, KV, S/block_k); each (b, kv) step processes the G = H/KV
    query heads of that KV group together (one cache read serves the
    whole group — the GQA bandwidth saving, realized in VMEM).
    """
    B, H, D = q.shape
    KV, S = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_k = min(block_k, S)
    nk = -(-S // block_k)
    Sp = nk * block_k
    if Sp != S:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    qg = q.reshape(B, KV, G, D)

    kernel = functools.partial(_decode_kernel, scale=scale,
                               block_k=block_k, group=G)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, kv, ki, *_: (b, kv, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, kv, ki, *_: (b, kv, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, kv, ki, *_: (b, kv, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, kv, ki, *_: (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths, qg, k_cache, v_cache)
    return out.reshape(B, H, D)
