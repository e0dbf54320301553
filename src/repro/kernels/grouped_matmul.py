"""Grouped (expert) matmul Pallas TPU kernel for MoE layers.

(G, M, K) x (G, K, N) -> (G, M, N): one MXU-tiled matmul per expert group,
f32 accumulation in VMEM scratch across the sequential K dimension.  The
expert dim is the outermost parallel grid axis, so under expert sharding
each core sweeps only its local experts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _gmm_kernel(lhs_ref, rhs_ref, out_ref, acc_ref, *, nk):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        lhs_ref[0].astype(jnp.float32), rhs_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())))

    @pl.when(ik == nk - 1)
    def _emit():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _pad_dim(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    w = [(0, 0)] * x.ndim
    w[axis] = (0, pad)
    return jnp.pad(x, w)


@functools.partial(jax.jit, static_argnames=("block_m", "block_k", "block_n",
                                             "interpret"))
def grouped_matmul(lhs, rhs, *, block_m=128, block_k=512, block_n=512,
                   interpret=False):
    G, M, K = lhs.shape
    _, _, N = rhs.shape
    block_m = min(block_m, max(M, 8))
    block_k = min(block_k, max(K, 8))
    block_n = min(block_n, max(N, 8))
    lp = _pad_dim(_pad_dim(lhs, 1, block_m), 2, block_k)
    rp = _pad_dim(_pad_dim(rhs, 1, block_k), 2, block_n)
    nm, nk, nn = (lp.shape[1] // block_m, lp.shape[2] // block_k,
                  rp.shape[2] // block_n)

    out = pl.pallas_call(
        functools.partial(_gmm_kernel, nk=nk),
        name="grouped_matmul",
        grid=(G, nm, nn, nk),
        in_specs=[
            pl.BlockSpec((1, block_m, block_k),
                         lambda g, im, jn, ik: (g, im, ik)),
            pl.BlockSpec((1, block_k, block_n),
                         lambda g, im, jn, ik: (g, ik, jn)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda g, im, jn, ik: (g, im, jn)),
        out_shape=jax.ShapeDtypeStruct((G, lp.shape[1], rp.shape[2]),
                                       lhs.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(lp, rp)
    return out[:, :M, :N]
