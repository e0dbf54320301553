"""Jit'd dispatch wrappers around the Pallas kernels and their jnp references.

``mode`` selects the execution path:
  None               chosen from the platform (``resolve_mode``)
  reference          pure-jnp (XLA) — the CPU path, the dry-run lowering and
                     the differentiated training forward
  pallas             real TPU Pallas kernels (target hardware)
  pallas_interpret   Pallas kernel body executed in Python on CPU — used by
                     the test suite to validate kernels against ref.py
"""

from __future__ import annotations

import jax

from repro.kernels import ref


def resolve_mode(mode: str | None) -> str:
    """``mode``, or where it is None the platform's path: the Pallas
    kernels on TPU, the jnp reference anywhere else."""
    if mode is not None:
        return mode
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def _pallas(mode: str | None) -> tuple[bool, bool]:
    """(run the Pallas kernel, in interpret mode)."""
    mode = resolve_mode(mode)
    return mode in ("pallas", "pallas_interpret"), mode == "pallas_interpret"


def _layer(a, layer):
    return a if layer is None else jax.lax.dynamic_index_in_dim(
        a, layer, axis=0, keepdims=False)


def attention(q, k, v, *, causal=True, local_window=None, softcap=None,
              scale=None, mode=None, block_q=512, block_kv=1024,
              naive_below=2049):
    """GQA attention dispatch. q: (B,S,H,D); k/v: (B,S,K,D)."""
    kernel, interpret = _pallas(mode)
    if kernel:
        from repro.kernels import flash_attention
        return flash_attention.flash_attention(
            q, k, v, causal=causal, local_window=local_window,
            softcap=softcap, scale=scale, block_q=block_q, block_kv=block_kv,
            interpret=interpret)
    if q.shape[1] < naive_below and k.shape[1] < naive_below:
        return ref.attention_naive(q, k, v, causal=causal,
                                   local_window=local_window,
                                   softcap=softcap, scale=scale)
    return ref.attention_blockwise(q, k, v, causal=causal,
                                   local_window=local_window,
                                   softcap=softcap, scale=scale,
                                   block_kv=block_kv)


def decode_attention(q, k_cache, v_cache, kv_len, *, softcap=None,
                     local_window=None, scale=None, mode=None,
                     block_kv=1024):
    """Decode-step (Sq=1) or chunked-prefill (Sq>1) attention over a
    (B,S,K,D) cache with per-slot valid lengths kv_len (B,)."""
    kernel, interpret = _pallas(mode)
    if kernel:
        from repro.kernels import flash_attention
        return flash_attention.flash_decode(
            q, k_cache, v_cache, kv_len, softcap=softcap,
            local_window=local_window, scale=scale, block_kv=block_kv,
            interpret=interpret)
    return ref.decode_attention_ref(q, k_cache, v_cache, kv_len,
                                    softcap=softcap,
                                    local_window=local_window, scale=scale)


def kv_cache_update(k_cache, v_cache, k_new, v_new, index, *, mode=None):
    """Write k/v_new (B,Sn,K,D) into the caches at per-slot offsets
    ``index`` (B,); rows whose write would cross the cache end are dropped
    whole (done-slot semantics).  Returns (k_cache', v_cache')."""
    kernel, interpret = _pallas(mode)
    if kernel:
        from repro.kernels import flash_attention
        return flash_attention.cache_update(
            k_cache, v_cache, k_new, v_new, index, interpret=interpret)
    return ref.kv_cache_update_ref(k_cache, v_cache, k_new, v_new, index)


def decode_attention_paged(q, k_pool, v_pool, kv_len, block_tables,
                           layer=None, *, softcap=None, local_window=None,
                           scale=None, mode=None):
    """Decode-step / chunked-prefill attention over a PAGED cache: the
    pools (n_blocks, bs, K, D) — or a layer stack (L, n_blocks, bs, K, D)
    read at ``layer`` — hold fixed-size blocks and each slot reads its
    rows through its ``block_tables`` row ((B, max_blocks) int32), ragged
    up to kv_len (B,).  The reference path gathers the dense per-slot view
    and reuses the dense decode oracle (bit-identical by construction);
    the Pallas path gathers block-by-block through the table via scalar
    prefetch, never materializing the dense view."""
    kernel, interpret = _pallas(mode)
    if kernel:
        from repro.kernels import flash_attention
        return flash_attention.flash_decode_paged(
            q, k_pool, v_pool, kv_len, block_tables, layer, softcap=softcap,
            local_window=local_window, scale=scale, interpret=interpret)
    return ref.decode_attention_paged_ref(
        q, _layer(k_pool, layer), _layer(v_pool, layer), kv_len,
        block_tables, softcap=softcap, local_window=local_window,
        scale=scale)


def kv_cache_update_paged(k_pool, v_pool, k_new, v_new, index, block_tables,
                          layer=None, *, mode=None):
    """Write k/v_new (B, Sn, K, D) into the paged pools (or, with
    ``layer``, into that layer of the stacked pools) at the (block,
    offset) destinations each slot's table maps rows [index, index+Sn) to;
    a slot whose write crosses its table's logical end is dropped whole
    (done-slot semantics, index = max_seq).  The engine guarantees write
    destinations are PRIVATE blocks (copy-on-write happens at admission),
    so no two slots scatter into the same row.  Returns (k_pool', v_pool')."""
    kernel, interpret = _pallas(mode)
    if kernel:
        from repro.kernels import flash_attention
        return flash_attention.cache_update_paged(
            k_pool, v_pool, k_new, v_new, index, block_tables, layer,
            interpret=interpret)
    kp, vp = ref.kv_cache_update_paged_ref(
        _layer(k_pool, layer), _layer(v_pool, layer), k_new, v_new, index,
        block_tables)
    if layer is None:
        return kp, vp
    return (jax.lax.dynamic_update_index_in_dim(k_pool, kp, layer, axis=0),
            jax.lax.dynamic_update_index_in_dim(v_pool, vp, layer, axis=0))


def slot_gather(a, slot, *, axis=1, mode=None):
    """Lift one slot's lane out of a stacked cache leaf along ``axis``
    (the batch/slot dim): (L, B, ...) -> (L, ...).  The export half of
    portable slot state (``repro.models.lm.export_slot``).

    Every mode routes to the XLA slice: this is one contiguous DMA with
    no compute to fuse, which is exactly the case a hand Pallas kernel
    cannot beat (unlike ``kv_cache_update``, whose per-slot scatter +
    OOB-drop semantics XLA scatters handle poorly)."""
    del mode
    return ref.slot_gather_ref(a, slot, axis=axis)


def slot_scatter(a, sub, slot, *, axis=1, mode=None):
    """Install a lifted lane into a stacked cache leaf at ``slot`` along
    ``axis`` — the import half of portable slot state.  Same
    single-contiguous-DMA argument as ``slot_gather``: all modes route
    to the XLA dynamic-update-slice."""
    del mode
    return ref.slot_scatter_ref(a, sub, slot, axis=axis)


def int8_quantize(a, *, axis=-1, mode=None):
    """Symmetric per-row int8 quantization: (q int8, scale f32 kept-dim
    over ``axis``).  Shared by the MoE ``_a2a_int8`` wire format and the
    at-rest snapshot-payload compression (``repro.models.lm.export_slot``).

    Every mode routes to the jnp implementation: the absmax reduce, the
    scale divide and the int8 cast fuse into one XLA pass over the array —
    a bandwidth-bound elementwise pipeline a hand Pallas kernel cannot
    improve on (same argument as ``slot_gather``)."""
    del mode
    return ref.int8_quantize_ref(a, axis=axis)


def int8_dequantize(q, scale, dtype, *, mode=None):
    """Inverse of ``int8_quantize``: q * scale cast to ``dtype``."""
    del mode
    return ref.int8_dequantize_ref(q, scale, dtype)


def ssd(x, dt, A, B, C, D=None, h0=None, *, chunk=128, mode=None):
    """Mamba-2 SSD scan. Returns (y, final_state)."""
    kernel, interpret = _pallas(mode)
    if kernel:
        from repro.kernels import ssd as ssd_kernel
        return ssd_kernel.ssd(x, dt, A, B, C, D, h0=h0, chunk=chunk,
                              interpret=interpret)
    return ref.ssd_chunked(x, dt, A, B, C, D, h0=h0, chunk=chunk)


def grouped_matmul(lhs, rhs, *, mode=None, block_m=128, block_k=512,
                   block_n=512):
    """MoE expert GEMM: (G,M,K) x (G,K,N) -> (G,M,N)."""
    kernel, interpret = _pallas(mode)
    if kernel:
        from repro.kernels import grouped_matmul as gmm
        return gmm.grouped_matmul(lhs, rhs, block_m=block_m, block_k=block_k,
                                  block_n=block_n, interpret=interpret)
    return ref.grouped_matmul_ref(lhs, rhs)
