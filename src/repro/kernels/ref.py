"""Pure-jnp oracles for every Pallas kernel (and the model reference path).

Each Pallas kernel in this package has its oracle here; kernel tests sweep
shapes/dtypes and assert_allclose against these.  The *blockwise* variants use
the same online-softmax / chunked-state algorithms as the kernels but in plain
jnp — they are the memory-safe reference path the models run on CPU and what
the dry-run lowers (XLA:CPU cannot lower TPU Pallas calls).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _softcap(x, cap):
    return cap * jnp.tanh(x / cap) if cap is not None else x


def _mask(q_pos, k_pos, causal: bool, local_window):
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if local_window is not None:
        m &= q_pos[:, None] - k_pos[None, :] < local_window
    return m


# ===========================================================================
# attention
# ===========================================================================

def attention_naive(q, k, v, *, causal=True, local_window=None, softcap=None,
                    scale=None, kv_len=None):
    """Full-matrix GQA attention oracle.

    q: (B, Sq, H, D); k/v: (B, Sk, K, D) with H % K == 0.
    kv_len: optional (B,) active cache length (decode); when given, q
    positions are laid at the END of the kv window.
    """
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    qf = q.astype(jnp.float32).reshape(B, Sq, K, G, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qf, kf) * scale
    logits = _softcap(logits, softcap)
    q_pos = jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    if kv_len is not None:
        q_pos = q_pos[None, :] + kv_len[:, None] - Sq        # (B, Sq)
        mask = (q_pos[:, :, None] >= k_pos[None, None, :])
        if local_window is not None:
            mask &= q_pos[:, :, None] - k_pos[None, None, :] < local_window
        mask = mask[:, None, None, :, :]
    else:
        mask = _mask(q_pos, k_pos, causal, local_window)[None, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, vf)
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def attention_blockwise(q, k, v, *, causal=True, local_window=None,
                        softcap=None, scale=None, block_kv=1024):
    """Online-softmax attention: same algorithm as the Pallas kernel, in jnp.

    Memory is O(Sq * block_kv) instead of O(Sq * Sk); this is the model
    reference path for long sequences.
    """
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    block_kv = min(block_kv, Sk)
    nkv = (Sk + block_kv - 1) // block_kv
    pad = nkv * block_kv - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, K, G, D)
    kb = k.astype(jnp.float32).reshape(B, nkv, block_kv, K, D)
    vb = v.astype(jnp.float32).reshape(B, nkv, block_kv, K, D)
    q_pos = jnp.arange(Sq)

    def step(carry, blk):
        m, l, acc = carry
        kc, vc, j = blk
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qf, kc)
        logits = _softcap(logits, softcap)
        k_pos = j * block_kv + jnp.arange(block_kv)
        msk = jnp.ones((Sq, block_kv), bool)
        msk &= k_pos[None, :] < Sk
        if causal:
            msk &= q_pos[:, None] >= k_pos[None, :]
        if local_window is not None:
            msk &= q_pos[:, None] - k_pos[None, :] < local_window
        logits = jnp.where(msk[None, None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bkgqs,bskd->bkgqd", p, vc)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, K, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, K, G, Sq, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nkv)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 3, 1).reshape(B, Sq, H, D).astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, kv_len, *, scale=None,
                         softcap=None, local_window=None):
    """Decode/chunked-prefill oracle: q (B, Sq, H, D) laid at the END of
    the valid kv window, cache (B, S, K, D), kv_len (B,) valid lengths
    INCLUDING the Sq current tokens (per-slot ragged)."""
    return attention_naive(q, k_cache, v_cache, causal=True,
                           local_window=local_window, softcap=softcap,
                           scale=scale, kv_len=kv_len)


def kv_cache_update_ref(k_cache, v_cache, k_new, v_new, index):
    """Per-slot-offset cache write oracle: scatter k/v_new (B, Sn, K, D)
    into (B, S, K, D) at row offsets ``index`` (B,).  A row whose write
    would cross the cache end is dropped WHOLE (matching the Pallas
    kernel's done-slot convention), not element-wise clipped."""
    B, Sn = k_new.shape[:2]
    S = k_cache.shape[1]
    oob = (index < 0) | (index + Sn > S)
    pos = jnp.where(oob[:, None], S, index[:, None] + jnp.arange(Sn)[None, :])
    rows = jnp.arange(B)[:, None]
    ck = k_cache.at[rows, pos].set(k_new.astype(k_cache.dtype), mode="drop")
    cv = v_cache.at[rows, pos].set(v_new.astype(v_cache.dtype), mode="drop")
    return ck, cv


def paged_gather_ref(pool, block_tables):
    """Materialize the dense per-slot view of a paged KV pool.

    pool: (n_blocks, bs, K, D) fixed-size cache blocks; block_tables:
    (B, max_blocks) int32 per-slot block ids.  Returns the dense
    (B, max_blocks * bs, K, D) cache each slot's table describes.  Rows
    beyond a slot's kv_len may come from unmapped / recycled blocks —
    attention masks them exactly (NEG_INF before softmax), so the paged
    path is BIT-IDENTICAL to a dense cache of the same logical shape."""
    n_blocks, bs = pool.shape[:2]
    B, max_blocks = block_tables.shape
    dense = jnp.take(pool, block_tables.reshape(-1), axis=0,
                     mode="clip")
    return dense.reshape((B, max_blocks * bs) + pool.shape[2:])


def decode_attention_paged_ref(q, k_pool, v_pool, kv_len, block_tables, *,
                               scale=None, softcap=None, local_window=None):
    """Paged decode/chunked-prefill oracle: gather each slot's blocks into
    the dense (B, max_blocks*bs, K, D) view, then run the ragged-kv_len
    decode attention.  Identical shapes and reduction order to the dense
    path, so outputs are bit-identical to ``decode_attention_ref`` over a
    dense cache holding the same valid rows.

    A slot whose kv_len exceeds the table's logical length
    (max_blocks * bs rows) attends to nothing and gets zeros: the
    done-slot convention, whose write ``kv_cache_update_paged_ref``
    drops whole."""
    k_dense = paged_gather_ref(k_pool, block_tables)
    v_dense = paged_gather_ref(v_pool, block_tables)
    out = decode_attention_ref(q, k_dense, v_dense, kv_len, scale=scale,
                               softcap=softcap, local_window=local_window)
    done = kv_len > k_dense.shape[1]
    return jnp.where(done[:, None, None, None], 0, out).astype(out.dtype)


def kv_cache_update_paged_ref(k_pool, v_pool, k_new, v_new, index,
                              block_tables):
    """Paged per-slot cache write oracle: scatter k/v_new (B, Sn, K, D)
    into the pools (n_blocks, bs, K, D) at the (block, offset)
    destinations each slot's table maps its rows [index, index+Sn) to.
    A slot whose write would cross its table's logical end
    (max_blocks * bs rows) is dropped WHOLE — the same done-slot
    convention as the dense ``kv_cache_update_ref``; such a slot's
    attention (``decode_attention_paged_ref``) reads nothing and is
    zero."""
    B, Sn = k_new.shape[:2]
    n_blocks, bs = k_pool.shape[:2]
    max_blocks = block_tables.shape[1]
    S = max_blocks * bs
    oob = (index < 0) | (index + Sn > S)
    pos = index[:, None] + jnp.arange(Sn)[None, :]            # (B, Sn)
    blk_idx = jnp.clip(pos // bs, 0, max_blocks - 1)
    blk = jnp.take_along_axis(block_tables, blk_idx, axis=1)  # (B, Sn)
    # dropped rows target block n_blocks: out of range -> mode="drop"
    blk = jnp.where(oob[:, None], n_blocks, blk)
    off = jnp.clip(pos, 0, S - 1) % bs
    kp = k_pool.at[blk, off].set(k_new.astype(k_pool.dtype), mode="drop")
    vp = v_pool.at[blk, off].set(v_new.astype(v_pool.dtype), mode="drop")
    return kp, vp


def slot_gather_ref(a, slot, axis: int = 1):
    """Lift one slot's lane out of a stacked cache leaf: drop ``axis``
    (the batch/slot dim) at index ``slot``.  (L, B, ...) -> (L, ...)."""
    return jax.lax.index_in_dim(a, slot, axis=axis, keepdims=False)


def slot_scatter_ref(a, sub, slot, axis: int = 1):
    """Install a lifted lane into a stacked cache leaf at index ``slot``
    along ``axis`` (dtype-cast to the destination).  The inverse of
    ``slot_gather_ref`` for matching trailing shapes."""
    return jax.lax.dynamic_update_index_in_dim(
        a, sub.astype(a.dtype), slot, axis=axis)


def int8_quantize_ref(a, axis: int = -1):
    """Symmetric per-row int8 quantization oracle: ``scale`` is the row's
    absmax over ``axis`` divided by 127 (f32 sidecar, kept-dim), ``q`` the
    rounded/clipped int8 payload.  The row absmax maps to exactly +-127,
    so the worst-case reconstruction error is ``scale / 2 = absmax / 254``
    per element (plus the storage dtype's own rounding on dequantize) —
    the error budget ``tests/test_migration.py`` asserts per leaf."""
    f = a.astype(jnp.float32)
    scale = jnp.max(jnp.abs(f), axis=axis, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(f / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_dequantize_ref(q, scale, dtype):
    """Inverse of ``int8_quantize_ref``: q * scale, cast to ``dtype``."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


# ===========================================================================
# mamba-2 SSD (state-space duality)
# ===========================================================================

def ssd_naive(x, dt, A, B, C, D=None, h0=None):
    """Sequential recurrence oracle (exact, O(S) steps).

    x: (Bb, S, H, P); dt: (Bb, S, H); A: (H,) negative; B/C: (Bb, S, G, N).
    Returns y: (Bb, S, H, P) and final state (Bb, H, P, N).
    """
    Bb, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    xf, dtf = x.astype(jnp.float32), dt.astype(jnp.float32)
    Bf, Cf = B.astype(jnp.float32), C.astype(jnp.float32)
    Bh = jnp.repeat(Bf, rep, axis=2)   # (Bb,S,H,N)
    Ch = jnp.repeat(Cf, rep, axis=2)

    def step(h, t):
        a = jnp.exp(A[None] * dtf[:, t])               # (Bb,H)
        inc = jnp.einsum("bhp,bhn->bhpn", xf[:, t] * dtf[:, t, :, None],
                         Bh[:, t])
        h = h * a[..., None, None] + inc
        y = jnp.einsum("bhpn,bhn->bhp", h, Ch[:, t])
        return h, y

    h = (jnp.zeros((Bb, H, P, N), jnp.float32) if h0 is None
         else h0.astype(jnp.float32))
    h, ys = jax.lax.scan(step, h, jnp.arange(S))
    y = jnp.moveaxis(ys, 0, 1)                         # (Bb,S,H,P)
    if D is not None:
        y = y + xf * D[None, None, :, None]
    return y.astype(x.dtype), h


def _segsum(a):
    """Stable segment-sum: M[..., i, j] = sum_{j<k<=i} a[..., k], -inf j>i."""
    S = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    M = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((S, S), bool), 0)
    return jnp.where(mask, M, -jnp.inf)


def ssd_chunked(x, dt, A, B, C, D=None, h0=None, chunk=128):
    """Chunked SSD (Mamba-2 Listing 1): quadratic intra-chunk + linear
    inter-chunk state passing.  Same math as ssd_naive."""
    Bb, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    xf = x.astype(jnp.float32).reshape(Bb, nc, chunk, H, P)
    dtf = dt.astype(jnp.float32).reshape(Bb, nc, chunk, H)
    Bf = jnp.repeat(B.astype(jnp.float32), rep, 2).reshape(Bb, nc, chunk, H, N)
    Cf = jnp.repeat(C.astype(jnp.float32), rep, 2).reshape(Bb, nc, chunk, H, N)
    xdt = xf * dtf[..., None]
    a = A[None, None, None] * dtf                     # (Bb,nc,Q,H) log-decay
    a = jnp.moveaxis(a, -1, -2)                       # (Bb,nc,H,Q)
    a_cs = jnp.cumsum(a, axis=-1)

    # 1) intra-chunk (diagonal blocks)
    L = jnp.exp(_segsum(a))                           # (Bb,nc,H,Q,Q)
    scores = jnp.einsum("bcqhn,bckhn->bchqk", Cf, Bf)
    y_diag = jnp.einsum("bchqk,bchqk,bckhp->bcqhp", scores, L, xdt)

    # 2) per-chunk final states
    decay = jnp.exp(a_cs[..., -1:] - a_cs)            # (Bb,nc,H,Q)
    states = jnp.einsum("bchq,bcqhn,bcqhp->bchpn", decay, Bf, xdt)

    # 3) inter-chunk recurrence over chunk states
    chunk_decay = jnp.exp(a_cs[..., -1])              # (Bb,nc,H)

    def pass_state(h, t):
        h_new = h * chunk_decay[:, t][..., None, None] + states[:, t]
        return h_new, h                                # emit state BEFORE chunk t

    h_init = (jnp.zeros((Bb, H, P, N), jnp.float32) if h0 is None
              else h0.astype(jnp.float32))
    h_last, h_prevs = jax.lax.scan(pass_state, h_init, jnp.arange(nc))
    h_prev = jnp.moveaxis(h_prevs, 0, 1)              # (Bb,nc,H,P,N)

    # 4) inter-chunk contribution
    out_decay = jnp.exp(a_cs)                         # (Bb,nc,H,Q)
    y_off = jnp.einsum("bcqhn,bchpn,bchq->bcqhp", Cf, h_prev, out_decay)

    y = (y_diag + y_off).reshape(Bb, S, H, P)
    if D is not None:
        y = y + x.astype(jnp.float32) * D[None, None, :, None]
    return y.astype(x.dtype), h_last


def ssd_decode_step(state, x_t, dt_t, A, B_t, C_t, D=None):
    """One decode step of the SSM recurrence.  state: (Bb,H,P,N)."""
    H = x_t.shape[-2]
    G = B_t.shape[-2]
    rep = H // G
    a = jnp.exp(A[None] * dt_t.astype(jnp.float32))   # (Bb,H)
    Bh = jnp.repeat(B_t.astype(jnp.float32), rep, axis=-2)
    Ch = jnp.repeat(C_t.astype(jnp.float32), rep, axis=-2)
    inc = jnp.einsum("bhp,bhn->bhpn",
                     x_t.astype(jnp.float32) * dt_t[..., None], Bh)
    state = state * a[..., None, None] + inc
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch)
    if D is not None:
        y = y + x_t.astype(jnp.float32) * D[None, :, None]
    return y.astype(x_t.dtype), state


# ===========================================================================
# grouped matmul (MoE expert GEMM)
# ===========================================================================

def grouped_matmul_ref(lhs, rhs):
    """lhs: (G, M, K), rhs: (G, K, N) -> (G, M, N), f32 accumulation."""
    return jnp.einsum("gmk,gkn->gmn", lhs.astype(jnp.float32),
                      rhs.astype(jnp.float32)).astype(lhs.dtype)
