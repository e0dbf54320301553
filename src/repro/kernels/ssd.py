"""Mamba-2 SSD (state-space duality) Pallas TPU kernel.

The SSD block decomposition (Dao & Gu 2024, Listing 1) maps naturally onto
the TPU: the intra-chunk quadratic term is an MXU matmul chain over a
(chunk x chunk) tile, and the inter-chunk recurrence is a tiny (P x N) state
carried in VMEM scratch across sequential grid steps — the TPU-native
replacement for the GPU implementation's warp-level scan.

Grid: (B, H, n_chunks) with the chunk dimension "arbitrary" (sequential).
Per step, VMEM holds the chunk's x (Q x P), dt (Q x 1), B/C (Q x N) blocks
and the f32 running state (P x N); the wrapper lays heads ahead of the
(seq, feature) dims so every block is tile-legal.  All matmul tiles are
MXU-aligned for the default Q=128, P=64, N=64/128.

Outputs y (B,S,H,P) and the final state (B,H,P,N) (for prefill-into-cache).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, h0_ref, y_ref,
                state_out_ref, state_ref, *, nchunks, chunk, has_h0):
    h = pl.program_id(1)
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        if has_h0:
            state_ref[...] = h0_ref[...].astype(jnp.float32)
        else:
            state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[...].astype(jnp.float32)               # (Q, P)
    dt = dt_ref[...].astype(jnp.float32)             # (Q, 1)
    Bm = b_ref[...].astype(jnp.float32)              # (Q, N)
    Cm = c_ref[...].astype(jnp.float32)              # (Q, N)

    xdt = x * dt
    a = a_ref[h] * dt                                # (Q, 1) log-decay
    # inclusive cumsum of a, as a column and as a row, from masked
    # reductions over the (Q, Q) iota grid (no cumsum or vector transpose
    # is needed on the chip)
    r = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    a_row = jnp.sum(jnp.where(r == c, a, 0.0), axis=0, keepdims=True)
    cs_col = jnp.sum(jnp.where(c <= r, a_row, 0.0), axis=1, keepdims=True)
    cs_row = jnp.sum(jnp.where(r <= c, a, 0.0), axis=0, keepdims=True)
    a_sum = jnp.sum(a, axis=0, keepdims=True)        # (1, 1)

    # intra-chunk: L[i,j] = exp(cs[i]-cs[j]) for i>=j (decay from j+1..i,
    # matching ref._segsum)
    L = jnp.exp(jnp.where(c <= r, cs_col - cs_row, -jnp.inf))
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # (Q,Q)
    y_diag = (scores * L) @ xdt                                     # (Q,P)

    # inter-chunk contribution from the carried state
    state = state_ref[...]                                          # (P,N)
    y_off = jnp.exp(cs_col) * jax.lax.dot_general(
        Cm, state, (((1,), (1,)), ((), ())))                        # (Q,P)

    y_ref[...] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: state = state * exp(sum a) + sum_k decay_k * xdt_k ⊗ B_k
    decay = jnp.exp(a_sum - cs_col)                                 # (Q,1)
    inc = jnp.transpose(xdt * decay) @ Bm                           # (P,N)
    state_ref[...] = state * jnp.exp(a_sum) + inc

    @pl.when(ic == nchunks - 1)
    def _emit_state():
        state_out_ref[...] = state_ref[...].astype(state_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, dt, A, B, C, D=None, h0=None, *, chunk=128, interpret=False):
    """x: (Bb,S,H,P); dt: (Bb,S,H); A: (H,); B/C: (Bb,S,G,N).
    Returns (y (Bb,S,H,P), final_state (Bb,H,P,N))."""
    Bb, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    assert S % chunk == 0, (S, chunk)
    nchunks = S // chunk
    g = H // G
    has_h0 = h0 is not None
    if h0 is None:
        h0 = jnp.zeros((Bb, H, P, N), jnp.float32)

    # heads ahead of the tiled (seq, feature) dims; dt as a (seq, 1) column
    xt = x.transpose(0, 2, 1, 3)                      # (Bb, H, S, P)
    dtt = dt.transpose(0, 2, 1)[..., None]            # (Bb, H, S, 1)
    bt = B.transpose(0, 2, 1, 3)                      # (Bb, G, S, N)
    ct = C.transpose(0, 2, 1, 3)

    kernel = functools.partial(_ssd_kernel, nchunks=nchunks, chunk=chunk,
                               has_h0=has_h0)
    seq_spec = lambda w: pl.BlockSpec((None, None, chunk, w),
                                      lambda b, h, c: (b, h, c, 0))
    grp_spec = pl.BlockSpec((None, None, chunk, N),
                            lambda b, h, c, g=g: (b, h // g, c, 0))
    state_spec = pl.BlockSpec((None, None, P, N),
                              lambda b, h, c: (b, h, 0, 0))
    y, state = pl.pallas_call(
        kernel,
        grid=(Bb, H, nchunks),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            seq_spec(P), seq_spec(1), grp_spec, grp_spec, state_spec,
        ],
        out_specs=[seq_spec(P), state_spec],
        out_shape=[
            jax.ShapeDtypeStruct(xt.shape, x.dtype),
            jax.ShapeDtypeStruct((Bb, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(A.astype(jnp.float32), xt, dtt, bt, ct, h0)
    y = y.transpose(0, 2, 1, 3)
    if D is not None:
        y = (y.astype(jnp.float32)
             + x.astype(jnp.float32) * D[None, None, :, None]).astype(x.dtype)
    return y, state
