"""Pallas TPU kernel for the Mamba selective scan (SSM recurrence).

The §Perf Cell-C finding (EXPERIMENTS.md): the pure-JAX chunked scan
materializes the state-expansion tensors (a, u, h_t — [B, L, d_inner,
d_state] fp32) to HBM every chunk, ~40 % of jamba-train's memory term.
The CUDA mamba kernel never materializes h; this is the TPU analogue:
the recurrence runs INSIDE the kernel with the state held in VMEM
scratch across the sequential chunk axis — h never touches HBM.

Layout contract (channels-last blocks, MXU/VPU aligned):
    xdt:  [B, T, I]   pre-scaled input  (dt * x, fp32)
    a:    [B, T, I]   per-channel log-decay carrier (dt, fp32) — the
                      kernel forms exp(dt * A[c, n]) internally
    Bc:   [B, T, N]   input projections  (fp32)
    Cc:   [B, T, N]   output projections (fp32)
    A:    [I, N]      state matrix (negative, fp32)
    out:  [B, T, I]

Grid: (B, I/block_i, T/chunk); the chunk axis is sequential
("arbitrary") with h [block_i, N] persisting in scratch.  Inside a
chunk the recurrence is an unrolled loop over the chunk length — each
step is VPU elementwise work plus an [block_i, N] reduction, exactly the
per-thread structure of the CUDA kernel mapped onto the vector unit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mamba_kernel(xdt_ref, dt_ref, b_ref, c_ref, a_ref, o_ref, h_scr, *,
                  chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    xdt = xdt_ref[0].astype(jnp.float32)       # [L, bi]
    dt = dt_ref[0].astype(jnp.float32)         # [L, bi]
    bc = b_ref[0].astype(jnp.float32)          # [L, N]
    cc = c_ref[0].astype(jnp.float32)          # [L, N]
    a = a_ref[...].astype(jnp.float32)         # [bi, N]

    h = h_scr[...]                             # [bi, N]
    ys = []
    for t in range(chunk):
        decay = jnp.exp(dt[t][:, None] * a)            # [bi, N]
        h = decay * h + xdt[t][:, None] * bc[t][None, :]
        ys.append(jnp.sum(h * cc[t][None, :], axis=-1))  # [bi]
    h_scr[...] = h
    o_ref[0] = jnp.stack(ys, axis=0).astype(o_ref.dtype)  # [L, bi]


def mamba_scan_pallas(xdt, dt, bc, cc, a, *, chunk: int = 32,
                      block_i: int = 256, interpret: bool = True):
    """Selective scan: h_t = exp(dt_t·A)h_{t-1} + (dt_t x_t)B_t;
    y_t = C_t·h_t.

    xdt/dt: [B, T, I]; bc/cc: [B, T, N]; a: [I, N] -> y [B, T, I] fp32.
    (The D-skip term and gating stay outside the kernel — elementwise.)
    """
    B, T, I = xdt.shape
    N = bc.shape[-1]
    block_i = min(block_i, I)
    chunk = min(chunk, T)
    if I % block_i:
        raise ValueError(f"I={I} % block_i={block_i}")
    nc = -(-T // chunk)
    Tp = nc * chunk
    if Tp != T:
        pads = ((0, 0), (0, Tp - T), (0, 0))
        # dt = 0 on padding -> decay = 1, update = 0: state unchanged.
        xdt = jnp.pad(xdt, pads)
        dt = jnp.pad(dt, pads)
        bc = jnp.pad(bc, pads)
        cc = jnp.pad(cc, pads)

    kernel = functools.partial(_mamba_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(B, I // block_i, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_i),
                         lambda b, ib, c: (b, c, ib)),
            pl.BlockSpec((1, chunk, block_i),
                         lambda b, ib, c: (b, c, ib)),
            pl.BlockSpec((1, chunk, N), lambda b, ib, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, ib, c: (b, c, 0)),
            pl.BlockSpec((block_i, N), lambda b, ib, c: (ib, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_i),
                               lambda b, ib, c: (b, c, ib)),
        out_shape=jax.ShapeDtypeStruct((B, Tp, I), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_i, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xdt, dt, bc, cc, a)
    return out[:, :T]
