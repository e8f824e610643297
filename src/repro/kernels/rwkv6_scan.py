"""Pallas TPU kernel for the RWKV6 WKV chunked scan.

The CUDA RWKV kernel is a per-thread sequential recurrence; the TPU
adaptation (DESIGN.md §2) is the chunked form: inside a chunk the decay
factorizes as exp(A_t - A_s) (A = cumsum(log w)), so the intra-chunk
work is two [L,L]·[L,K] MXU matmuls, and only the [K,V] state crosses
chunks — held in VMEM scratch across the sequential chunk grid axis.

Layout contract: r/k/v/logw [B, H, T, K]; u [H, K]; output [B, H, T, K].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_CLIP = 30.0


def _rwkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_scr, *,
                 chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0, 0].astype(jnp.float32)          # [L, K]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)             # [K]

    acum = jnp.cumsum(lw, axis=0)                # [L, K]
    a_before = acum - lw                         # sum_{j<=t-1} log w_j

    # Intra-chunk pair decays computed EXACTLY: for t > s the exponent
    # A_before[t] - Acum[s] = sum_{j=s+1}^{t-1} log w_j <= 0, so
    # exp() is bounded by 1 — no clipping, stable for any decay
    # strength.  (The factorized r·exp(A) @ k·exp(-A) form underflows
    # when the in-chunk cumulative decay is deep; see tests
    # test_rwkv6_chunk_invariance.)  [L, L, K] lives in VMEM.
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = (li > lj)[:, :, None]                  # strictly lower
    expo = a_before[:, None, :] - acum[None, :, :]
    pair = jnp.where(tri, jnp.exp(jnp.where(tri, expo, 0.0)), 0.0)
    scores = jnp.einsum("tk,sk,tsk->ts", r, k, pair)   # [L, L]
    y_intra = jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y_diag = jnp.sum(r * u[None, :] * k, axis=-1, keepdims=True) * v
    # Inter-chunk readout: decay from chunk start, exponent <= 0, exact.
    r_dec = r * jnp.exp(a_before)
    y_inter = jax.lax.dot_general(
        r_dec, s_scr[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0, 0] = (y_intra + y_diag + y_inter).astype(o_ref.dtype)

    # State update normalized to the chunk END: exponent
    # Acum[-1] - Acum[s] = sum_{j=s+1}^{L-1} log w_j <= 0, exact.
    wtot = jnp.exp(acum[-1])                     # [K]
    k_state = k * jnp.exp(acum[-1][None, :] - acum)
    s_scr[...] = wtot[:, None] * s_scr[...] + jax.lax.dot_general(
        k_state, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def rwkv6_scan_pallas(r, k, v, logw, u, *, chunk: int = 64,
                      interpret: bool = True):
    """r/k/v/logw: [B,H,T,K]; u: [H,K] -> y [B,H,T,K] (fp32)."""
    B, H, T, K = r.shape
    chunk = min(chunk, T)
    nc = -(-T // chunk)
    Tp = nc * chunk
    if Tp != T:
        pads = ((0, 0), (0, 0), (0, Tp - T), (0, 0))
        # zero k on padding -> zero state/output contributions;
        # logw = 0 -> w = 1 keeps the state decay neutral.
        r = jnp.pad(r, pads)
        k = jnp.pad(k, pads)
        v = jnp.pad(v, pads)
        logw = jnp.pad(logw, pads)

    kernel = functools.partial(_rwkv_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, K), lambda b, h, c: (h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, K),
                               lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Tp, K), jnp.float32),
        scratch_shapes=[pltpu.VMEM((K, K), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, logw, u)
    return out[:, :, :T]
