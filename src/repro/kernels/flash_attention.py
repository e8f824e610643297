"""Pallas TPU flash attention (causal + bidirectional, GQA-aware).

TPU adaptation of the FlashAttention blocking (DESIGN.md §2): the
(T, S) score matrix never leaves VMEM; the grid walks
(batch, q_head, q_block) in parallel and the KV axis sequentially
("arbitrary" semantics) with the running (m, l, acc) softmax state in
VMEM scratch.  Block shapes are multiples of 128 on the last two dims
so the MXU sees aligned matmuls; GQA is handled in the BlockSpec index
maps (q head h reads kv head h // G) — no KV replication in HBM.

Layout contract: q [B, H, T, D]; k/v [B, KV, S, D].
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal: bool, scale: float, block_q: int, block_k: int,
                  seq_len_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)          # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_len_k
        if causal:
            mask = mask & (q_pos >= k_pos)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    if causal:
        # Skip KV blocks strictly in the future of this whole q block.
        needed = (ki * block_k) <= (qi * block_q + block_q - 1)
        pl.when(needed)(_compute)
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           scale: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = True):
    """q: [B,H,T,D]; k/v: [B,KV,S,D] -> [B,H,T,D]."""
    B, H, T, D = q.shape
    KV, S = k.shape[1], k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_q = min(block_q, T)
    block_k = min(block_k, S)
    if T % block_q:
        raise ValueError(f"T={T} must be a multiple of block_q={block_q}")
    nq = T // block_q
    nk = -(-S // block_k)
    Sp = nk * block_k
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))

    grid = (B, H, nq, nk)
    kernel = functools.partial(
        _flash_kernel, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, seq_len_k=S)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # running max m
            pltpu.VMEM((block_q,), jnp.float32),      # running denom l
            pltpu.VMEM((block_q, D), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
