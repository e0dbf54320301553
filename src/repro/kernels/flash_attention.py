"""Flash attention Pallas TPU kernels (online softmax, VMEM-tiled).

TPU-native adaptation notes (vs the CUDA flash-attention algorithm):
  * tiling targets VMEM instead of SM shared memory: the q block, one k/v
    block and the f32 accumulator live in VMEM;
  * the kv-block loop is the innermost ("arbitrary") grid dimension so the
    running max/denominator/accumulator persist in VMEM scratch across
    sequential grid steps — no atomics / warp shuffles needed;
  * causal + sliding-window masks skip fully-masked kv blocks via pl.when;
  * every block's last two dims are either (8, 128)-aligned or the full
    array dims, as the TPU compiler requires: the prefill kernel lays heads
    out ahead of (seq, head_dim), and the decode kernel reads a cache block
    as ALL kv heads' rows at once.

Supports: causal / bidirectional, sliding-window (gemma2 local layers),
logit softcap (gemma2), GQA, and short-query attention over a KV cache —
dense (B, S, K, D) or paged (n_blocks, bs, K, D) pools, optionally stacked
over layers — with per-slot ragged valid lengths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# v5e has 128 MiB of VMEM; the 16 MiB default scope is too small for the
# decode kernel's (K * Sq * g)-row q block at prefill-chunk sizes
_VMEM_LIMIT = 96 * 1024 * 1024
# cap on a decode block's (rows * kv heads) columns: the f32 logits and
# probabilities are (K * Sq * g, cols) each
_MAX_DECODE_COLS = 256


def _online_softmax_step(logits, v, m_ref, l_ref, acc_ref):
    """One kv block of the running-max / running-denominator update.
    logits: (rows, cols) f32, already masked; v: (cols, D)."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _scaled_logits(q, k, scale, softcap):
    """q (rows, D) . k (cols, D)^T in the inputs' dtype with f32
    accumulation, scaled (and soft-capped) in f32."""
    logits = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    return logits


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale, causal, local_window, softcap, sk_actual, block_q,
                 block_kv, nkv):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_kv

    # block-level skip: fully-masked kv blocks do no work at all
    run = jnp.bool_(True)
    if causal:
        run = run & (k_start <= q_start + block_q - 1)
        if local_window is not None:
            # newest q in block is q_start+block_q-1; oldest visible k is
            # q - window + 1; block is dead if its last k < that
            run = run & (k_start + block_kv - 1
                         >= q_start - (local_window - 1))

    @pl.when(run)
    def _body():
        logits = _scaled_logits(q_ref[...], k_ref[...], scale, softcap)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_kv), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_kv), 1)
        mask = k_pos < sk_actual
        if causal:
            mask &= q_pos >= k_pos
        if local_window is not None:
            mask &= q_pos - k_pos < local_window
        _online_softmax_step(jnp.where(mask, logits, NEG_INF), v_ref[...],
                             m_ref, l_ref, acc_ref)

    @pl.when(ik == nkv - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=(
    "causal", "local_window", "softcap", "scale", "block_q", "block_kv",
    "interpret"))
def flash_attention(q, k, v, *, causal=True, local_window=None, softcap=None,
                    scale=None, block_q=512, block_kv=1024, interpret=False):
    """q: (B, Sq, H, D); k/v: (B, Sk, K, D) with H % K == 0."""
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    scale = scale if scale is not None else D ** -0.5
    block_q = min(block_q, max(Sq, 8))
    block_kv = min(block_kv, max(Sk, 8))
    # heads ahead of the tiled (seq, head_dim) dims
    qt = _pad_to(q, 1, block_q).transpose(0, 2, 1, 3)     # (B, H, Sq', D)
    kt = _pad_to(k, 1, block_kv).transpose(0, 2, 1, 3)    # (B, K, Sk', D)
    vt = _pad_to(v, 1, block_kv).transpose(0, 2, 1, 3)
    nq = qt.shape[2] // block_q
    nkv = kt.shape[2] // block_kv
    g = H // K

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, local_window=local_window,
        softcap=softcap, sk_actual=Sk, block_q=block_q, block_kv=block_kv,
        nkv=nkv)
    q_spec = pl.BlockSpec((None, None, block_q, D),
                          lambda b, h, iq, ik: (b, h, iq, 0))
    kv_spec = pl.BlockSpec((None, None, block_kv, D),
                           lambda b, h, iq, ik, g=g: (b, h // g, ik, 0))
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nkv),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)[:, :Sq]


# ---------------------------------------------------------------------------
# paged flash-decode: a short query block (1..chunk new tokens per slot)
# against KV rows that live in a pool of fixed-size blocks
# (n_blocks, bs, K, D) — optionally stacked over layers — read through a
# per-slot block table.  It is the serving runtime's decode step AND its
# chunked-prefill attention.  The table rides as a SCALAR PREFETCH argument
# (PrefetchScalarGridSpec): the k/v BlockSpec index_maps dereference it, so
# the DMA engine fetches exactly the slot's blocks and the dense view is
# never materialized (the vLLM paged-attention idiom).
#
# One grid step holds one pool block for ALL kv heads: a (bs, K, D) block is
# tile-legal for any K, where a single head's (bs, 1, D) slice is not.  The
# q rows of every head are stacked into one (K * Sq * g, D) operand, the
# block into (bs * K, D), and a block-diagonal head mask keeps each q row on
# its own kv head's columns.  That costs K x the MXU work of a per-head
# product, which decode (memory-bound) does not notice.
# ---------------------------------------------------------------------------

def _decode_paged_kernel(layer_ref, len_ref, bt_ref, q_ref, k_ref, v_ref,
                         o_ref, m_ref, l_ref, acc_ref, *, scale, softcap,
                         local_window, block_size, n_blk, sq, g, n_kv):
    del layer_ref, bt_ref                   # consumed by the index_maps
    b = pl.program_id(0)
    ib = pl.program_id(1)

    @pl.when(ib == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[b]
    k_start = ib * block_size          # LOGICAL position of this block

    @pl.when(k_start < kv_len)
    def _body():
        d = k_ref.shape[-1]
        k = k_ref[...].reshape(block_size * n_kv, d)     # row = pos*K + head
        v = v_ref[...].reshape(block_size * n_kv, d)
        logits = _scaled_logits(q_ref[...], k, scale, softcap)
        # q row r: kv head r // (sq*g), query position kv_len - sq + the
        # row's index within its head group // g (the sq new tokens sit at
        # the END of the valid kv window; causal within the chunk)
        r = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
        c = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        q_pos = kv_len - sq + (r % (sq * g)) // g
        k_pos = k_start + c // n_kv
        mask = (r // (sq * g) == c % n_kv) & (k_pos <= q_pos)
        if local_window is not None:
            mask &= k_pos > q_pos - local_window
        _online_softmax_step(jnp.where(mask, logits, NEG_INF), v,
                             m_ref, l_ref, acc_ref)

    @pl.when(ib == n_blk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _layer_stack(pool, layer):
    """(pool with a leading layer axis, (1,) int32 layer index): a 4-D
    pool is a one-layer stack."""
    if layer is None:
        return pool[None], jnp.zeros((1,), jnp.int32)
    return pool, jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))


@functools.partial(jax.jit, static_argnames=(
    "softcap", "local_window", "scale", "interpret"))
def flash_decode_paged(q, k_pool, v_pool, kv_len, block_tables, layer=None,
                       *, softcap=None, local_window=None, scale=None,
                       interpret=False):
    """q: (B, Sq, H, D); pools: (n_blocks, bs, K, D), or (L, n_blocks, bs,
    K, D) read at ``layer``; kv_len: (B,) int32 valid length INCLUDING the
    Sq new tokens; block_tables: (B, max_blocks) int32 — slot b's logical
    rows [i*bs, (i+1)*bs) live in pool block ``block_tables[b, i]``.  The kv
    grid dimension walks the slot's table; blocks past kv_len skip their
    compute."""
    B, Sq, H, D = q.shape
    k_pool, layer_arr = _layer_stack(k_pool, layer)
    v_pool, _ = _layer_stack(v_pool, layer)
    bs, K = k_pool.shape[2], k_pool.shape[3]
    max_blocks = block_tables.shape[1]
    scale = scale if scale is not None else D ** -0.5
    g = H // K
    rows = K * Sq * g
    # rows grouped by kv head: (B, K * Sq * g, D)
    qg = q.reshape(B, Sq, K, g, D).transpose(0, 2, 1, 3, 4) \
          .reshape(B, rows, D)

    kernel = functools.partial(_decode_paged_kernel, scale=scale,
                               softcap=softcap, local_window=local_window,
                               block_size=bs, n_blk=max_blocks, sq=Sq, g=g,
                               n_kv=K)
    q_spec = pl.BlockSpec((None, rows, D),
                          lambda b, ib, ly, ln, bt: (b, 0, 0))
    kv_spec = pl.BlockSpec((None, None, bs, K, D),
                           lambda b, ib, ly, ln, bt:
                           (ly[0], bt[b, ib], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, max_blocks),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(layer_arr, kv_len.astype(jnp.int32), block_tables.astype(jnp.int32),
      qg, k_pool, v_pool)
    return out.reshape(B, K, Sq, g, D).transpose(0, 2, 1, 3, 4) \
              .reshape(B, Sq, H, D)


@functools.partial(jax.jit, static_argnames=(
    "softcap", "local_window", "scale", "block_kv", "interpret"))
def flash_decode(q, k_cache, v_cache, kv_len, *, softcap=None,
                 local_window=None, scale=None, block_kv=1024,
                 interpret=False):
    """q: (B, Sq, H, D); caches: (B, S, K, D); kv_len: (B,) int32 valid
    length INCLUDING the Sq new tokens, per slot (ragged).  Sq == 1 is the
    classic flash-decode step; Sq > 1 is a chunked-prefill block laid at
    the end of each slot's valid window (requires kv_len >= Sq).

    A dense cache is a paged pool whose slot b owns blocks
    [b * nb, (b + 1) * nb) in order, so this is ``flash_decode_paged`` over
    a reshaped view (free when S is a multiple of ``block_kv``)."""
    B, S, K, D = k_cache.shape
    block_kv = min(block_kv, max(S, 8), max(8, _MAX_DECODE_COLS // K))
    kp = _pad_to(k_cache, 1, block_kv)
    vp = _pad_to(v_cache, 1, block_kv)
    nb = kp.shape[1] // block_kv
    pool_shape = (B * nb, block_kv, K, D)
    tables = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    return flash_decode_paged(q, kp.reshape(pool_shape),
                              vp.reshape(pool_shape), kv_len, tables,
                              softcap=softcap, local_window=local_window,
                              scale=scale, interpret=interpret)


# ---------------------------------------------------------------------------
# paged KV cache write: each grid step lands ONE new row (all kv heads) into
# the pool row its slot's table maps that logical position to.  The table,
# the per-slot offsets and the layer ride as scalar prefetch, so the
# destination row is computed in the BlockSpec index_map and the kernel only
# ever moves K * D elements in and out.  The pool is aliased in place.  A
# slot whose write would cross the logical end (max_blocks * bs) is dropped
# WHOLE — the done-slot convention (index = max_seq) and the OOB guard; its
# steps read their (clamped) row and write it back unchanged.
# ---------------------------------------------------------------------------

def _cache_update_kernel(layer_ref, idx_ref, bt_ref, kn_ref, vn_ref,
                         kc_ref, vc_ref, ko_ref, vo_ref, *, s_new,
                         s_logical):
    del layer_ref, bt_ref                   # consumed by the index_maps
    idx = idx_ref[pl.program_id(0)]
    write = (idx >= 0) & (idx + s_new <= s_logical)
    ko_ref[...] = jnp.where(write, kn_ref[...].astype(ko_ref.dtype),
                            kc_ref[...])
    vo_ref[...] = jnp.where(write, vn_ref[...].astype(vo_ref.dtype),
                            vc_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def cache_update_paged(k_pool, v_pool, k_new, v_new, index, block_tables,
                       layer=None, *, interpret=False):
    """Scatter k/v_new (B, Sn, K, D) into paged pools (n_blocks, bs, K, D)
    — or (L, n_blocks, bs, K, D) at ``layer`` — at the (block, offset)
    destinations slot b's ``block_tables`` row maps logical positions
    [index[b], index[b]+Sn) to.  Slots whose write would cross the logical
    end (max_blocks*bs) are dropped whole.  The engine guarantees
    destination blocks are private (CoW at admission), so no two slots
    write the same pool row.  Returns (k_pool', v_pool')."""
    stacked = layer is not None
    k_pool, layer_arr = _layer_stack(k_pool, layer)
    v_pool, _ = _layer_stack(v_pool, layer)
    B, Sn, K, D = k_new.shape
    bs = k_pool.shape[2]
    max_blocks = block_tables.shape[1]
    s_logical = max_blocks * bs

    def _row_map(b, j, ly, idx, bt):
        pos = jnp.clip(idx[b] + j, 0, s_logical - 1)
        return (ly[0], bt[b, pos // bs], pos % bs, 0, 0)

    new_spec = pl.BlockSpec((None, None, K, D),
                            lambda b, j, ly, idx, bt: (b, j, 0, 0))
    row_spec = pl.BlockSpec((None, None, None, K, D), _row_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Sn),
        in_specs=[new_spec, new_spec, row_spec, row_spec],
        out_specs=[row_spec, row_spec],
    )
    kernel = functools.partial(_cache_update_kernel, s_new=Sn,
                               s_logical=s_logical)
    k_out, v_out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(layer_arr, index.astype(jnp.int32), block_tables.astype(jnp.int32),
      k_new, v_new, k_pool, v_pool)
    if not stacked:
        return k_out[0], v_out[0]
    return k_out, v_out


@functools.partial(jax.jit, static_argnames=("interpret",))
def cache_update(k_cache, v_cache, k_new, v_new, index, *, interpret=False):
    """Scatter k/v_new (B, Sn, K, D) into the caches (B, S, K, D) at
    per-slot offsets ``index`` (B,) int32.  Rows with index + Sn > S are
    dropped whole (done-slot semantics).  Returns (k_cache', v_cache').

    A dense cache is a pool of B blocks of S rows with slot b owning block
    b, so this is ``cache_update_paged`` with the identity table."""
    tables = jnp.arange(k_cache.shape[0], dtype=jnp.int32)[:, None]
    return cache_update_paged(k_cache, v_cache, k_new, v_new, index, tables,
                              interpret=interpret)
