"""Jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile through Mosaic (``interpret=False``); on the
CPU backend they run in interpret mode, which executes the kernel body
faithfully for correctness validation
(:func:`repro.kernels.interpret_mode`).  The models call these through
``attn_impl='pallas'``; layout translation from the models' [B,T,H,D]
to the kernels' [B,H,T,D] happens here.
"""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels import interpret_mode
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.rwkv6_scan import rwkv6_scan_pallas


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    """q: [B,T,H,D]; k/v: [B,S,KV,D] (model layout) -> [B,T,H,D]."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = flash_attention_pallas(qt, kt, vt, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret_mode())
    return o.transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("block_k",))
def decode_attention(q, k_cache, v_cache, lengths, *, block_k: int = 512):
    """q: [B,H,D]; caches: [B,S,KV,D] (model layout) -> [B,H,D]."""
    kt = k_cache.transpose(0, 2, 1, 3)
    vt = v_cache.transpose(0, 2, 1, 3)
    return decode_attention_pallas(q, kt, vt, lengths, block_k=block_k,
                                   interpret=interpret_mode())


@partial(jax.jit, static_argnames=("chunk",))
def rwkv6_scan(r, k, v, logw, u, *, chunk: int = 64):
    """r/k/v/logw: [B,H,T,K]; u: [H,K] -> [B,H,T,K] fp32."""
    return rwkv6_scan_pallas(r, k, v, logw, u, chunk=chunk,
                             interpret=interpret_mode())


@partial(jax.jit, static_argnames=("chunk", "block_i"))
def mamba_scan(xdt, dt, bc, cc, a, *, chunk: int = 32,
               block_i: int = 256):
    """Selective scan: xdt/dt [B,T,I]; bc/cc [B,T,N]; a [I,N] -> fp32."""
    return mamba_scan_pallas(xdt, dt, bc, cc, a, chunk=chunk,
                             block_i=block_i, interpret=interpret_mode())
