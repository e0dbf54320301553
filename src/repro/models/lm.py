"""Unified LM assembly for all 10 assigned architectures.

One declarative parameter tree + one forward covering:

  dense       pre-norm decoder (llama3.2, minitron, nemotron-4) with optional
              post-norms / softcaps / local-global alternation (gemma2)
  moe         every-layer token-choice top-k MoE (phi3.5-moe, olmoe)
  ssm         mamba-2 (SSD) attention-free stack (mamba2-370m)
  hybrid      mamba-2 backbone + SHARED attention block applied periodically
              with per-invocation LoRA (zamba2)
  audio       encoder-only transformer over precomputed frame embeddings
              (hubert-xlarge; frontend is a stub per the assignment)
  vlm         decoder with M-RoPE; precomputed patch embeddings merged into
              the token stream (qwen2-vl; frontend is a stub)

Layers are scan-stacked (jax.lax.scan over the leading "layers" dim) so the
HLO stays one-layer-sized for 80-layer models; remat policy wraps the body.

Forward modes:
  forward(...)                     full-sequence hidden states (train/prefill)
  forward(..., cache, cache_index) single/multi-token decode step
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import layers as L
from repro.models.params import PD


# ===========================================================================
# declarations
# ===========================================================================

def _tf_layer_decls(cfg: ModelConfig, n: int, moe: bool) -> dict:
    d = {
        "ln1": L.norm_decls(cfg, layers=n),
        "attn": L.attention_decls(cfg, layers=n),
        "ln2": L.norm_decls(cfg, layers=n),
        "mlp": L.moe_decls(cfg, layers=n) if moe else L.mlp_decls(cfg, layers=n),
    }
    if cfg.post_norms:
        d["post_ln1"] = L.norm_decls(cfg, layers=n)
        d["post_ln2"] = L.norm_decls(cfg, layers=n)
    return d


def _shared_attn_decls(cfg: ModelConfig, n_inv: int) -> dict:
    """Zamba2 shared transformer block over concat(h, emb) (width 2*d_model),
    plus per-invocation LoRA adapters on the q projection."""
    d2 = 2 * cfg.d_model
    r = cfg.shared_attn_lora or 32
    return {
        "ln1": L.norm_decls(cfg, d=d2),
        "attn": L.attention_decls(cfg, d_in=d2),
        "ln2": L.norm_decls(cfg, d=d2),
        "mlp": {
            "w_up": PD((d2, cfg.d_ff), ("embed", "mlp")),
            "w_gate": PD((d2, cfg.d_ff), ("embed", "mlp")),
            "w_down": PD((cfg.d_ff, cfg.d_model), ("mlp", "embed")),
        },
        "lora_a": PD((n_inv, d2, r), ("layers", "embed", "lora"),
                     scale=d2 ** -0.5),
        "lora_b": PD((n_inv, r, cfg.n_heads * cfg.head_dim),
                     ("layers", "lora", "qkv_flat"), "zeros"),
    }


def zamba_structure(cfg: ModelConfig) -> tuple[int, int, int]:
    """(n_super, mamba_per_super, trailing) with
    n_super*mamba_per_super + trailing == n_layers."""
    period = max(cfg.shared_attn_period, 1)
    n_super = cfg.n_layers // period
    trailing = cfg.n_layers - n_super * period
    return n_super, period, trailing


def model_decls(cfg: ModelConfig) -> dict:
    d: dict[str, Any] = {}
    if cfg.family == "audio":
        d["frontend"] = {
            "proj": PD((cfg.frontend_dim, cfg.d_model), ("frontend", "embed")),
            "pos": PD((cfg.max_wavelength_pos, cfg.d_model),
                      (None, "embed"), "embed", scale=0.02),
        }
    else:
        d["embed"] = L.embed_decls(cfg)

    if cfg.family in ("dense", "moe", "audio", "vlm"):
        moe = cfg.n_experts > 0
        if cfg.layer_pattern == "local_global":
            half = cfg.n_layers // 2
            d["layers_local"] = _tf_layer_decls(cfg, half, moe)
            d["layers_global"] = _tf_layer_decls(cfg, half, moe)
        else:
            d["layers"] = _tf_layer_decls(cfg, cfg.n_layers, moe)
    elif cfg.family == "ssm":
        d["layers"] = {"ln": L.norm_decls(cfg, layers=cfg.n_layers),
                       "mamba": L.mamba_decls(cfg, layers=cfg.n_layers)}
    elif cfg.family == "hybrid":
        n_super, per, trailing = zamba_structure(cfg)
        d["layers"] = {"ln": L.norm_decls(cfg, layers=cfg.n_layers),
                       "mamba": L.mamba_decls(cfg, layers=cfg.n_layers)}
        d["shared"] = _shared_attn_decls(cfg, n_super)
    else:
        raise ValueError(cfg.family)

    d["final_norm"] = L.norm_decls(cfg)
    ue = L.unembed_decls(cfg)
    if ue:
        d["unembed"] = ue
    return d


# ===========================================================================
# forward
# ===========================================================================

def _remat(ctx: L.Ctx, fn):
    if ctx.run.remat == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if ctx.run.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return fn


def _stack_scan(ctx: L.Ctx, body, carry, xs):
    """lax.scan over stacked layer params, or a python unroll when
    run.scan_layers=False (used by the dry-run's cost-extrapolation variants
    and available as a compile-size/perf lever)."""
    if ctx.run.scan_layers:
        return jax.lax.scan(body, carry, xs, unroll=ctx.run.scan_unroll)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        xi = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if ys and jax.tree.leaves(ys[0]):
        y_stack = jax.tree.map(lambda *a: jnp.stack(a, 0), *ys)
    else:
        y_stack = ys[0] if ys else None
    return carry, y_stack


def _tf_block(ctx: L.Ctx, cfg: ModelConfig, p, h, cos, sin, *,
              local_window=None, cache=None, cache_index=None,
              block_tables=None, layer=None):
    """One transformer block; returns (h, new_cache, aux)."""
    post = "post_ln1" in p
    a_in = L.apply_norm(cfg, p["ln1"], h)
    attn_out, new_cache = L.apply_attention(
        ctx, cfg, p["attn"], a_in, cos, sin, local_window=local_window,
        cache=cache, cache_index=cache_index, block_tables=block_tables,
        layer=layer)
    if post:
        attn_out = L.apply_norm(cfg, p["post_ln1"], attn_out)
    # NOTE: do NOT pin the residual adds with sharding constraints — it
    # costs ~17 % extra accounted traffic fleet-wide and the multi-pod MoE
    # backward gathers were fixed at the shard_map boundary instead
    # (local token flattening; EXPERIMENTS.md §Perf C3).
    h = h + attn_out
    m_in = L.apply_norm(cfg, p["ln2"], h)
    aux = jnp.zeros((), jnp.float32)
    if cfg.n_experts:
        mlp_out, aux = L.apply_moe(ctx, cfg, p["mlp"], m_in)
    else:
        mlp_out = L.apply_mlp(ctx, cfg, p["mlp"], m_in)
    if post:
        mlp_out = L.apply_norm(cfg, p["post_ln2"], mlp_out)
    return h + mlp_out, new_cache, aux


def _scan_tf_layers(ctx: L.Ctx, cfg: ModelConfig, stack, h, cos, sin, *,
                    local_window=None, cache=None, cache_index=None,
                    block_tables=None):
    """Scan one homogeneous transformer stack.  cache: stacked kv or None.
    ``block_tables`` rides as a closure capture — it is layer-invariant, so
    it must not be scanned over with the per-layer cache leaves."""
    if block_tables is not None:
        # paged pools ride in the scan CARRY, updated in place at a traced
        # layer index: scanned as xs -> ys they would be held twice, the
        # input stack and the output stack (the pool is most of HBM)
        def paged_body(carry, xs):
            h, aux, pools = carry
            p, layer = xs
            h, pools, a = _tf_block(ctx, cfg, p, h, cos, sin,
                                    local_window=local_window, cache=pools,
                                    cache_index=cache_index,
                                    block_tables=block_tables, layer=layer)
            return (h, aux + a, pools), None

        n = jax.tree.leaves(stack)[0].shape[0]
        (h, aux, new_cache), _ = _stack_scan(
            ctx, _remat(ctx, paged_body),
            (h, jnp.zeros((), jnp.float32), cache),
            (stack, jnp.arange(n, dtype=jnp.int32)))
        return h, aux, new_cache

    def body(carry, xs):
        h, aux = carry
        p, c = xs
        h, new_c, a = _tf_block(ctx, cfg, p, h, cos, sin,
                                local_window=local_window, cache=c,
                                cache_index=cache_index,
                                block_tables=block_tables)
        return (h, aux + a), new_c

    body = _remat(ctx, body)
    (h, aux), new_cache = _stack_scan(
        ctx, body, (h, jnp.zeros((), jnp.float32)), (stack, cache))
    return h, aux, new_cache


def _positions_default(batch: int, seq: int, cache_index=None):
    pos = jnp.arange(seq, dtype=jnp.int32)[None, :]
    if cache_index is not None:
        idx = jnp.asarray(cache_index, jnp.int32)
        # scalar index: shared decode offset; (B,) index: per-slot offsets
        # (continuous batching — each slot is at its own position)
        pos = pos + (idx[:, None] if idx.ndim == 1 else idx)
    return jnp.broadcast_to(pos, (batch, seq))


def forward(ctx: L.Ctx, cfg: ModelConfig, params, batch: dict, *,
            cache=None, cache_index=None):
    """Returns (hidden (B,S,D), aux_loss, new_cache)."""
    if cfg.family == "audio":
        frames = batch["frames"].astype(ctx.cdtype)
        B, S = frames.shape[:2]
        h = jnp.einsum("bsf,fd->bsd", frames,
                       params["frontend"]["proj"].astype(ctx.cdtype))
        pos_tab = jax.lax.dynamic_slice_in_dim(
            params["frontend"]["pos"], 0, S, axis=0)
        h = h + pos_tab[None].astype(ctx.cdtype)
        h = ctx.cst(h, "act_batch", "act_seq", "act_embed")
        positions = _positions_default(B, S)
    else:
        tokens = batch["tokens"]
        B, S = tokens.shape
        h = L.apply_embed(ctx, cfg, params["embed"], tokens)
        if cfg.family == "vlm" and "vision_embeds" in batch:
            # frontend stub: precomputed patch embeddings replace the leading
            # token positions (train + prefill; decode batches omit them)
            ve = batch["vision_embeds"].astype(ctx.cdtype)
            h = jax.lax.dynamic_update_slice(h, ve, (0, 0, 0))
        positions = batch.get("positions")
        if positions is None:
            positions = _positions_default(B, S, cache_index)
            if cfg.mrope_sections is not None:
                positions = jnp.broadcast_to(positions[None], (3, B, S))

    cos, sin = (L.rope_cos_sin(cfg, positions) if cfg.use_rope
                else (None, None))

    aux = jnp.zeros((), jnp.float32)
    new_cache = None

    # A paged cache carries one top-level "block_tables" entry ((B, max
    # blocks) int32) shared by every rows-key — per-slot kv_len is uniform
    # across layers/keys, so one table addresses all pools.  Pop it here,
    # thread it to the attention layers, and reattach it (unchanged: the
    # model never remaps blocks) to the new cache.
    block_tables = None
    if cache is not None and "block_tables" in cache:
        cache = dict(cache)
        block_tables = cache.pop("block_tables")

    if cfg.family in ("dense", "moe", "audio", "vlm"):
        if cfg.layer_pattern == "local_global":
            # gemma2: scan over (local, global) pairs
            def body(carry, xs):
                h, aux = carry
                (pl, pg), (cl, cg) = xs
                h, ncl, a1 = _tf_block(ctx, cfg, pl, h, cos, sin,
                                       local_window=cfg.local_window,
                                       cache=cl, cache_index=cache_index,
                                       block_tables=block_tables)
                h, ncg, a2 = _tf_block(ctx, cfg, pg, h, cos, sin,
                                       local_window=None,
                                       cache=cg, cache_index=cache_index,
                                       block_tables=block_tables)
                return (h, aux + a1 + a2), (ncl, ncg)

            body = _remat(ctx, body)
            cl = cache["kv_local"] if cache is not None else None
            cg = cache["kv_global"] if cache is not None else None
            (h, aux), pair_caches = _stack_scan(
                ctx, body, (h, aux),
                ((params["layers_local"], params["layers_global"]), (cl, cg)))
            ncl, ncg = (pair_caches if pair_caches is not None
                        else (None, None))
            if cache is not None:
                new_cache = {"kv_local": ncl, "kv_global": ncg}
        else:
            kv = cache["kv"] if cache is not None else None
            h, aux, nkv = _scan_tf_layers(ctx, cfg, params["layers"], h,
                                          cos, sin, cache=kv,
                                          cache_index=cache_index,
                                          block_tables=block_tables)
            if cache is not None:
                new_cache = {"kv": nkv}

    elif cfg.family == "ssm":
        def body(h, xs):
            p, st = xs
            x_in = L.apply_norm(cfg, p["ln"], h)
            ssm = st["ssm"] if st is not None else None
            conv = st["conv"] if st is not None else None
            out, (new_ssm, new_conv) = L.apply_mamba(
                ctx, cfg, p["mamba"], x_in, ssm_state=ssm, conv_state=conv)
            new_st = ({"ssm": new_ssm, "conv": new_conv}
                      if st is not None else None)
            return h + out, new_st

        body = _remat(ctx, body)
        st = cache["mamba"] if cache is not None else None
        h, new_st = _stack_scan(ctx, body, h, (params["layers"], st))
        if cache is not None:
            new_cache = {"mamba": new_st}

    elif cfg.family == "hybrid":
        h, aux, new_cache = _zamba_forward(ctx, cfg, params, h, cos, sin,
                                           cache=cache,
                                           cache_index=cache_index,
                                           block_tables=block_tables)
    else:
        raise ValueError(cfg.family)

    if block_tables is not None and new_cache is not None:
        new_cache["block_tables"] = block_tables
    h = L.apply_norm(cfg, params["final_norm"], h)
    return h, aux, new_cache


# ---------------------------------------------------------------------------
# zamba2 hybrid
# ---------------------------------------------------------------------------

def _mamba_segment(ctx, cfg, stack, h, st):
    def body(h, xs):
        p, s = xs
        x_in = L.apply_norm(cfg, p["ln"], h)
        ssm = s["ssm"] if s is not None else None
        conv = s["conv"] if s is not None else None
        out, (new_ssm, new_conv) = L.apply_mamba(
            ctx, cfg, p["mamba"], x_in, ssm_state=ssm, conv_state=conv)
        new_s = {"ssm": new_ssm, "conv": new_conv} if s is not None else None
        return h + out, new_s

    body = _remat(ctx, body)
    return _stack_scan(ctx, body, h, (stack, st))


def _shared_block(ctx, cfg, p, inv_idx, h, emb0, cos, sin, *,
                  cache=None, cache_index=None, block_tables=None):
    """Zamba2 shared attention block on concat(h, emb0), with per-invocation
    LoRA on q."""
    c = ctx.cdtype
    xcat = jnp.concatenate([h, emb0], axis=-1)
    a_in = L.apply_norm(cfg, p["ln1"], xcat)
    # LoRA delta on q for this invocation
    la = p["lora_a"][inv_idx].astype(c)
    lb = p["lora_b"][inv_idx].astype(c)
    B, S = a_in.shape[:2]
    q_delta = (a_in @ la @ lb).reshape(B, S, cfg.n_heads, cfg.head_dim)

    # attention with q = Wq x + LoRA(x)
    attn_p = dict(p["attn"])
    out, new_cache = _attention_with_qdelta(
        ctx, cfg, attn_p, a_in, q_delta, cos, sin, cache=cache,
        cache_index=cache_index, block_tables=block_tables)
    h = h + out
    m_in = L.apply_norm(cfg, p["ln2"], jnp.concatenate([h, emb0], axis=-1))
    gate = jnp.einsum("bsd,df->bsf", m_in, p["mlp"]["w_gate"].astype(c))
    up = jnp.einsum("bsd,df->bsf", m_in, p["mlp"]["w_up"].astype(c))
    up = ctx.cst(up, "act_batch", "act_seq", "act_mlp")
    mlp_out = jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up,
                         p["mlp"]["w_down"].astype(c))
    return h + mlp_out, new_cache


def _attention_with_qdelta(ctx, cfg, p, x, q_delta, cos, sin, *,
                           cache=None, cache_index=None, block_tables=None):
    c = ctx.cdtype
    B, S = x.shape[:2]
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, p["wq"].astype(c)
                   ).reshape(B, S, H, hd) + q_delta
    k = jnp.einsum("bsd,de->bse", x, p["wk"].astype(c)).reshape(B, S, K, hd)
    v = jnp.einsum("bsd,de->bse", x, p["wv"].astype(c)).reshape(B, S, K, hd)
    q = ctx.cst(q, "act_batch", "act_seq", "act_heads", None)
    if cfg.use_rope:
        q = L.apply_rope(q, cos, sin, cfg.rotary_pct)
        k = L.apply_rope(k, cos, sin, cfg.rotary_pct)
    scale = cfg.head_dim ** -0.5
    from repro.kernels import ops
    new_cache = None
    if cache is not None and block_tables is not None:
        # paged kv_shared pool: same table as the rows keys of the other
        # families (uniform per-slot kv_len), same no-cst rationale as the
        # paged branch of L.apply_attention
        per_slot = jnp.ndim(cache_index) >= 1
        idx_vec = (jnp.asarray(cache_index, jnp.int32) if per_slot
                   else jnp.full((B,), cache_index, jnp.int32))
        ck, cv = ops.kv_cache_update_paged(cache["k"], cache["v"], k, v,
                                           idx_vec, block_tables,
                                           mode=ctx.run.kernel_mode)
        new_cache = {"k": ck, "v": cv}
        kv_len = idx_vec + x.shape[1]
        out = ops.decode_attention_paged(q, ck.astype(c), cv.astype(c),
                                         kv_len, block_tables, scale=scale,
                                         mode=ctx.run.kernel_mode)
        y = jnp.einsum("bse,ed->bsd", out.reshape(B, out.shape[1], H * hd),
                       p["wo"].astype(c))
        return ctx.cst(y, "act_batch", "act_seq", "act_embed"), new_cache
    if cache is not None:
        per_slot = jnp.ndim(cache_index) >= 1
        if not per_slot and L._use_seqsharded_decode(ctx, cfg, x, cache):
            out, new_cache = L._decode_attention_seqsharded(
                ctx, cfg, q, cache, k, v, cache_index, scale=scale)
            y = jnp.einsum("bse,ed->bsd",
                           out.reshape(B, out.shape[1],
                                       cfg.n_heads * cfg.head_dim),
                           p["wo"].astype(c))
            return ctx.cst(y, "act_batch", "act_seq", "act_embed"), new_cache
        if per_slot:
            ck, cv = ops.kv_cache_update(
                cache["k"], cache["v"], k, v,
                jnp.asarray(cache_index, jnp.int32),
                mode=ctx.run.kernel_mode)
        else:
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, cache_index, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, cache_index, 0, 0))
        ck = ctx.cst(ck, "act_batch", "act_kv_seq", None, None)
        cv = ctx.cst(cv, "act_batch", "act_kv_seq", None, None)
        new_cache = {"k": ck, "v": cv}
        kv_len = jnp.broadcast_to(
            jnp.asarray(cache_index + x.shape[1], jnp.int32), (x.shape[0],))
        out = ops.decode_attention(q, ck.astype(c), cv.astype(c), kv_len,
                                   scale=scale, mode=ctx.run.kernel_mode,
                                   block_kv=ctx.run.attn_block_kv)
    else:
        out = ops.attention(q, k, v, causal=cfg.causal, scale=scale,
                            mode=ctx.run.kernel_mode,
                            block_q=ctx.run.attn_block_q,
                            block_kv=ctx.run.attn_block_kv,
                            naive_below=ctx.run.naive_attn_below)
    y = jnp.einsum("bse,ed->bsd", out.reshape(B, out.shape[1], H * hd),
                   p["wo"].astype(c))
    return ctx.cst(y, "act_batch", "act_seq", "act_embed"), new_cache


def _zamba_forward(ctx, cfg, params, h, cos, sin, *, cache=None,
                   cache_index=None, block_tables=None):
    n_super, per, trailing = zamba_structure(cfg)
    emb0 = h
    aux = jnp.zeros((), jnp.float32)
    slice_stack = lambda tree, s, e: jax.tree.map(lambda a: a[s:e], tree)
    st_all = cache["mamba"] if cache is not None else None
    kv_shared = cache["kv_shared"] if cache is not None else None
    new_st, new_kv = [], []
    for i in range(n_super):
        seg = slice_stack(params["layers"], i * per, (i + 1) * per)
        st = slice_stack(st_all, i * per, (i + 1) * per) if st_all is not None else None
        h, ns = _mamba_segment(ctx, cfg, seg, h, st)
        if ns is not None:
            new_st.append(ns)
        kv_i = (jax.tree.map(lambda a: a[i], kv_shared)
                if kv_shared is not None else None)
        h, nkv = _shared_block(ctx, cfg, params["shared"], i, h, emb0,
                               cos, sin, cache=kv_i, cache_index=cache_index,
                               block_tables=block_tables)
        if nkv is not None:
            new_kv.append(nkv)
    if trailing:
        seg = slice_stack(params["layers"], n_super * per, cfg.n_layers)
        st = (slice_stack(st_all, n_super * per, cfg.n_layers)
              if st_all is not None else None)
        h, ns = _mamba_segment(ctx, cfg, seg, h, st)
        if ns is not None:
            new_st.append(ns)
    new_cache = None
    if cache is not None:
        cat = lambda *ts: jnp.concatenate(ts, axis=0)
        new_cache = {
            "mamba": jax.tree.map(cat, *new_st) if len(new_st) > 1 else new_st[0],
            "kv_shared": jax.tree.map(lambda *ts: jnp.stack(ts, 0), *new_kv),
        }
    return h, aux, new_cache


# ===========================================================================
# logits / caches
# ===========================================================================

def unembed_matrix(cfg: ModelConfig, params, dtype):
    if cfg.tie_embeddings:
        return params["embed"]["table"].T.astype(dtype)
    return params["unembed"]["w"].astype(dtype)


def logits_for(ctx: L.Ctx, cfg: ModelConfig, params, h):
    """Full logits (decode path; small S).  Pad-vocab columns masked."""
    w = unembed_matrix(cfg, params, ctx.cdtype)
    logits = jnp.einsum("bsd,dv->bsv", h, w).astype(jnp.float32)
    if cfg.final_softcap is not None:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    if cfg.vocab_padded != cfg.vocab:
        pad_mask = jnp.arange(cfg.vocab_padded) < cfg.vocab
        logits = jnp.where(pad_mask[None, None, :], logits, -1e30)
    return ctx.cst(logits, "act_batch", "act_seq", "act_vocab")


def init_cache(ctx: L.Ctx, cfg: ModelConfig, batch: int, max_seq: int,
               abstract: bool = False):
    """Decode-state pytree per family (concrete zeros or ShapeDtypeStructs)."""
    c = ctx.cdtype
    kv = L.abstract_kv_cache if abstract else L.empty_kv_cache
    ms = L.abstract_mamba_state if abstract else L.empty_mamba_state
    if cfg.family in ("dense", "moe", "vlm"):
        if cfg.layer_pattern == "local_global":
            half = cfg.n_layers // 2
            return {"kv_local": kv(cfg, batch, max_seq, c, layers=half),
                    "kv_global": kv(cfg, batch, max_seq, c, layers=half)}
        return {"kv": kv(cfg, batch, max_seq, c, layers=cfg.n_layers)}
    if cfg.family == "ssm":
        return {"mamba": ms(cfg, batch, c, layers=cfg.n_layers)}
    if cfg.family == "hybrid":
        n_super, _, _ = zamba_structure(cfg)
        return {"mamba": ms(cfg, batch, c, layers=cfg.n_layers),
                "kv_shared": kv(cfg, batch, max_seq, c, layers=n_super)}
    raise ValueError(f"{cfg.family} has no decode cache (encoder-only)")


def init_paged_cache(ctx: L.Ctx, cfg: ModelConfig, batch: int, max_seq: int,
                     block_size: int, n_blocks: int | None = None,
                     abstract: bool = False):
    """Paged decode-state pytree: every rows-key becomes a block POOL
    (layers, n_blocks, block_size, K, hd) shared by all slots, plus one
    top-level ``block_tables`` ((batch, max_seq // block_size) int32)
    mapping each slot's logical row range to pool blocks.  State keys
    (recurrent Mamba lanes) are not row-addressable and stay dense.

    Tables init to zero: an unmapped entry aliases block 0, which is
    harmless — reads past kv_len are masked and writes never target
    unmapped entries (the allocator maps blocks before the cursor reaches
    them).  ``n_blocks`` defaults to ``batch * max_blocks`` (capacity
    parity with the dense cache; pass less to oversubscribe)."""
    if max_seq % block_size:
        raise ValueError(f"max_seq {max_seq} must be a multiple of "
                         f"block_size {block_size}")
    if cfg.family == "ssm":
        raise ValueError("ssm caches have no sequence rows to page")
    c = ctx.cdtype
    max_blocks = max_seq // block_size
    if n_blocks is None:
        n_blocks = batch * max_blocks
    pkv = L.abstract_paged_kv_cache if abstract else L.empty_paged_kv_cache
    ms = L.abstract_mamba_state if abstract else L.empty_mamba_state
    tab_shape = (batch, max_blocks)
    table = (jax.ShapeDtypeStruct(tab_shape, jnp.int32) if abstract
             else jnp.zeros(tab_shape, jnp.int32))
    if cfg.family in ("dense", "moe", "vlm"):
        if cfg.layer_pattern == "local_global":
            half = cfg.n_layers // 2
            return {"kv_local": pkv(cfg, n_blocks, block_size, c, layers=half),
                    "kv_global": pkv(cfg, n_blocks, block_size, c,
                                     layers=half),
                    "block_tables": table}
        return {"kv": pkv(cfg, n_blocks, block_size, c, layers=cfg.n_layers),
                "block_tables": table}
    if cfg.family == "hybrid":
        n_super, _, _ = zamba_structure(cfg)
        return {"mamba": ms(cfg, batch, c, layers=cfg.n_layers),
                "kv_shared": pkv(cfg, n_blocks, block_size, c,
                                 layers=n_super),
                "block_tables": table}
    raise ValueError(f"{cfg.family} has no decode cache (encoder-only)")


def cache_logical_axes(cfg: ModelConfig):
    if cfg.family in ("dense", "moe", "vlm"):
        if cfg.layer_pattern == "local_global":
            return {"kv_local": L.KV_CACHE_AXES, "kv_global": L.KV_CACHE_AXES}
        return {"kv": L.KV_CACHE_AXES}
    if cfg.family == "ssm":
        return {"mamba": L.MAMBA_STATE_AXES}
    if cfg.family == "hybrid":
        return {"mamba": L.MAMBA_STATE_AXES, "kv_shared": L.KV_CACHE_AXES}
    raise ValueError(cfg.family)


# ===========================================================================
# portable slot state
# ===========================================================================
#
# Every decode-cache leaf is laid out (stack, B, ...): axis 0 is the layer
# stack (n_layers, or n_super for the zamba2 shared-attention cache) and
# axis 1 is the batch SLOT.  ``cache_slot_spec`` names, per top-level cache
# key, what one slot's lane means; ``export_slot``/``import_slot`` lift a
# lane out of one engine's cache and install it into another's — including
# engines with different batch sizes (``max_slots``) and ``max_seq`` — so a
# drained request travels as data instead of being regenerated.

#: Slot semantics per cache kind: "rows" leaves carry sequence rows on
#: axis 2, valid up to the slot's kv_len (attention masks the rest);
#: "state" leaves carry the whole lane unconditionally (recurrent SSM /
#: conv state has no row mask — it is the left context itself).
SLOT_ROWS, SLOT_STATE = "rows", "state"


def cache_slot_spec(cfg: ModelConfig) -> dict[str, str]:
    """Per-top-level-key slot schema of ``init_cache``'s pytree."""
    if cfg.family in ("dense", "moe", "vlm"):
        if cfg.layer_pattern == "local_global":
            return {"kv_local": SLOT_ROWS, "kv_global": SLOT_ROWS}
        return {"kv": SLOT_ROWS}
    if cfg.family == "ssm":
        return {"mamba": SLOT_STATE}
    if cfg.family == "hybrid":
        return {"mamba": SLOT_STATE, "kv_shared": SLOT_ROWS}
    raise ValueError(f"{cfg.family} has no decode cache (encoder-only)")


@jax.tree_util.register_pytree_node_class
class QuantizedLeaf:
    """One payload leaf compressed at rest: per-row symmetric int8 with an
    f32 scale sidecar (the ``_a2a_int8`` wire trick applied to storage).

    The original dtype travels as static aux data so ``dequantize_payload``
    can restore the exact leaf type.  Registered as a pytree node, so
    ``jax.tree`` traversals (device_get, ``slot_payload_bytes``) see the
    int8 payload and the scale as ordinary leaves — the on-wire size of a
    quantized payload is therefore counted exactly (q bytes + scale
    bytes ~= half the raw bf16 bytes for head_dim-sized rows)."""

    def __init__(self, q, scale, dtype: str):
        self.q, self.scale, self.dtype = q, scale, str(dtype)

    def tree_flatten(self):
        return (self.q, self.scale), self.dtype

    @classmethod
    def tree_unflatten(cls, dtype, children):
        return cls(children[0], children[1], dtype)

    def __repr__(self):
        return (f"QuantizedLeaf(q={getattr(self.q, 'shape', None)}, "
                f"dtype={self.dtype})")


def quantize_payload(payload):
    """int8-compress every leaf of an ``export_slot`` payload (per-row
    scale over the last axis — head_dim for KV rows, the state feature
    axis for Mamba lanes).  Lossy: worst-case per-element error is the
    row absmax / 254 plus the storage dtype's own rounding — the error
    budget documented in docs/fleet.md and asserted per leaf in
    tests/test_migration.py."""
    return jax.tree.map(
        lambda a: QuantizedLeaf(*ops.int8_quantize(a), dtype=a.dtype),
        payload)


def dequantize_payload(payload):
    """Undo ``quantize_payload`` (identity on raw payloads)."""
    return jax.tree.map(
        lambda x: (ops.int8_dequantize(jnp.asarray(x.q),
                                       jnp.asarray(x.scale), x.dtype)
                   if isinstance(x, QuantizedLeaf) else x),
        payload, is_leaf=lambda x: isinstance(x, QuantizedLeaf))


def payload_is_quantized(payload) -> bool:
    return any(isinstance(x, QuantizedLeaf)
               for x in jax.tree.leaves(
                   payload, is_leaf=lambda x: isinstance(x, QuantizedLeaf)))


def int8_payload_ratio(cfg: ModelConfig, itemsize: int = 2) -> float:
    """Modeled on-wire size ratio of an int8-quantized payload vs raw:
    1 int8 byte per element plus a 4-byte f32 scale per ``head_dim`` row,
    over ``itemsize`` raw bytes per element.  Used by the engineless
    ``ServeJob`` to model compressed snapshot transfers; the real payload
    ratio is measured by ``slot_payload_bytes`` over quantized leaves."""
    row = max(int(getattr(cfg, "head_dim", 64) or 64), 1)
    return (1.0 + 4.0 / row) / float(itemsize)


def export_slot(cfg: ModelConfig, cache, slot: int, kv_len: int,
                mode: str | None = None, quantize: bool = False,
                row_start: int = 0) -> dict:
    """Lift slot ``slot``'s state out of a batched decode cache.

    Returns a payload pytree mirroring the cache structure with the batch
    axis removed: "rows" leaves are trimmed to ``kv_len`` valid rows
    (the only rows attention can ever read at this fill), "state" leaves
    travel whole.  The payload is engine-geometry-free — it can be
    installed into any slot of any cache built from the same ``cfg``
    whose ``max_seq`` accommodates the request (``import_slot``).

    ``quantize=True`` compresses the payload at rest (``quantize_payload``:
    per-row int8 + f32 scale, roughly halving the on-wire bytes at a
    bounded parity cost); ``import_slot`` dequantizes transparently.

    ``row_start > 0`` ships only rows [row_start, kv_len) — the PRIVATE
    suffix of a prefix-shared slot.  The receiver rebuilds the leading
    rows (registry hit or re-prefill of the prompt prefix, exact by the
    chunked-prefill invariance: row p depends only on tokens <= p) and
    installs the payload at ``row_offset=row_start``.  Only valid for
    pure-rows schemas: a state lane encodes the WHOLE left context and
    cannot be split at a row boundary."""
    if kv_len < 0:
        raise ValueError(f"kv_len must be >= 0, got {kv_len}")
    if not 0 <= row_start <= kv_len:
        raise ValueError(f"row_start {row_start} outside [0, {kv_len}]")
    spec = cache_slot_spec(cfg)
    if row_start and any(k == SLOT_STATE for k in spec.values()):
        raise ValueError("row_start > 0 requires a pure-rows cache schema")
    if set(spec) != set(cache):
        raise ValueError(f"cache keys {sorted(cache)} do not match the "
                         f"slot schema {sorted(spec)}")
    payload = {}
    for key, kind in spec.items():
        lane = jax.tree.map(
            lambda a: ops.slot_gather(a, slot, axis=1, mode=mode),
            cache[key])
        if kind == SLOT_ROWS:
            if any(kv_len > a.shape[1] for a in jax.tree.leaves(lane)):
                raise ValueError(f"kv_len {kv_len} exceeds the cache rows "
                                 f"of {key}")
            lane = jax.tree.map(lambda a: a[:, row_start:kv_len], lane)
        payload[key] = lane
    return quantize_payload(payload) if quantize else payload


def import_slot(cfg: ModelConfig, cache, payload, slot: int,
                mode: str | None = None, row_offset: int = 0):
    """Install an ``export_slot`` payload into slot ``slot`` of ``cache``.

    "rows" leaves are zero-padded to the destination's ``max_seq`` and
    the whole lane is overwritten (rows past the payload's kv_len are
    masked by the per-slot kv_len until decode writes them); "state"
    leaves overwrite the lane as-is.  The destination may have any batch
    size and any ``max_seq`` >= the payload's kv_len.  Quantized payloads
    (``export_slot(..., quantize=True)``) are dequantized here — at
    install time, so the payload stays int8 at rest and on the wire.

    ``row_offset > 0`` installs a prefix-trimmed payload
    (``export_slot(..., row_start=...)``) at its original position.  The
    lane rows BELOW the offset are zeroed by the whole-lane overwrite, so
    the prefix must be rebuilt (re-prefilled) AFTER this call.  Returns
    the updated cache."""
    payload = dequantize_payload(payload)
    spec = cache_slot_spec(cfg)
    if row_offset and any(k == SLOT_STATE for k in spec.values()):
        raise ValueError("row_offset > 0 requires a pure-rows cache schema")
    if set(spec) != set(payload) or set(spec) != set(cache):
        raise ValueError(f"payload keys {sorted(payload)} do not match the "
                         f"slot schema {sorted(spec)}")
    new_cache = dict(cache)
    for key, kind in spec.items():
        sub = payload[key]
        dst = cache[key]
        if kind == SLOT_ROWS:
            def pad_rows(a, full):
                rows = full.shape[2]           # destination max_seq
                if a.shape[0] != full.shape[0] or a.shape[2:] != full.shape[3:]:
                    raise ValueError(
                        f"{key}: payload lane {a.shape} does not fit "
                        f"cache {full.shape}")
                if row_offset + a.shape[1] > rows:
                    raise ValueError(
                        f"{key}: payload carries rows up to "
                        f"{row_offset + a.shape[1]} but the destination "
                        f"cache holds only {rows}")
                pad = [(0, 0)] * a.ndim
                pad[1] = (row_offset, rows - row_offset - a.shape[1])
                return jnp.pad(jnp.asarray(a), pad)
            sub = jax.tree.map(pad_rows, sub, dst)
        else:
            def check_state(a, full):
                if a.shape[0] != full.shape[0] or a.shape[1:] != full.shape[2:]:
                    raise ValueError(
                        f"{key}: payload lane {a.shape} does not fit "
                        f"cache {full.shape}")
                return jnp.asarray(a)
            sub = jax.tree.map(check_state, sub, dst)
        new_cache[key] = jax.tree.map(
            lambda full, lane: ops.slot_scatter(full, lane, slot, axis=1,
                                                mode=mode),
            dst, sub)
    return new_cache


def _paged_row_coords(blocks, block_size: int, row_start: int, row_stop: int):
    """(pool block ids, in-block offsets) int32 vectors addressing logical
    rows [row_start, row_stop) of a slot whose table maps logical block i
    to pool block ``blocks[i]`` (host-side list, in logical order)."""
    rows = range(row_start, row_stop)
    blk = jnp.asarray([blocks[r // block_size] for r in rows], jnp.int32)
    off = jnp.asarray([r % block_size for r in rows], jnp.int32)
    return blk, off


def export_slot_paged(cfg: ModelConfig, cache, slot: int, blocks,
                      block_size: int, kv_len: int, *, row_start: int = 0,
                      mode: str | None = None, quantize: bool = False):
    """``export_slot`` for a paged cache: rows-leaves are gathered out of
    the block pools through the slot's host-side block list, producing the
    SAME payload schema as the dense exporter — payloads are
    layout-portable (paged <-> dense migrations round-trip).  One fused
    gather per leaf (single DMA, same rationale as ``slot_gather``).
    ``row_start`` ships only the private suffix of a prefix-shared slot."""
    if not 0 <= row_start <= kv_len:
        raise ValueError(f"row_start {row_start} outside [0, {kv_len}]")
    if kv_len > len(blocks) * block_size:
        raise ValueError(f"kv_len {kv_len} exceeds the {len(blocks)} mapped "
                         f"blocks of size {block_size}")
    spec = cache_slot_spec(cfg)
    if row_start and any(k == SLOT_STATE for k in spec.values()):
        raise ValueError("row_start > 0 requires a pure-rows cache schema")
    if set(spec) != set(cache) - {"block_tables"}:
        raise ValueError(f"cache keys {sorted(cache)} do not match the "
                         f"slot schema {sorted(spec)}")
    blk, off = _paged_row_coords(blocks, block_size, row_start, kv_len)
    payload = {}
    for key, kind in spec.items():
        if kind == SLOT_STATE:
            payload[key] = jax.tree.map(
                lambda a: ops.slot_gather(a, slot, axis=1, mode=mode),
                cache[key])
        else:
            payload[key] = jax.tree.map(lambda a: a[:, blk, off], cache[key])
    return quantize_payload(payload) if quantize else payload


def import_slot_paged(cfg: ModelConfig, cache, payload, slot: int, blocks,
                      block_size: int, *, row_offset: int = 0,
                      mode: str | None = None):
    """Install an ``export_slot``/``export_slot_paged`` payload into a
    paged cache: rows scatter to the (block, offset) rows the slot's block
    list maps [row_offset, row_offset + rows) to.  Unlike the dense
    importer this writes ONLY the payload rows — shared prefix blocks
    below ``row_offset`` are never touched (they may be mapped into other
    slots' tables).  Returns the updated cache."""
    payload = dequantize_payload(payload)
    spec = cache_slot_spec(cfg)
    if row_offset and any(k == SLOT_STATE for k in spec.values()):
        raise ValueError("row_offset > 0 requires a pure-rows cache schema")
    if set(spec) != set(payload):
        raise ValueError(f"payload keys {sorted(payload)} do not match the "
                         f"slot schema {sorted(spec)}")
    new_cache = dict(cache)
    for key, kind in spec.items():
        if kind == SLOT_STATE:
            new_cache[key] = jax.tree.map(
                lambda full, lane: ops.slot_scatter(
                    full, jnp.asarray(lane), slot, axis=1, mode=mode),
                cache[key], payload[key])
            continue
        rows = jax.tree.leaves(payload[key])[0].shape[1]
        if row_offset + rows > len(blocks) * block_size:
            raise ValueError(
                f"{key}: payload rows reach {row_offset + rows} but only "
                f"{len(blocks)} blocks of size {block_size} are mapped")
        blk, off = _paged_row_coords(blocks, block_size, row_offset,
                                     row_offset + rows)

        def scatter_rows(full, lane):
            if (lane.shape[0] != full.shape[0]
                    or lane.shape[2:] != full.shape[3:]):
                raise ValueError(f"{key}: payload lane {lane.shape} does "
                                 f"not fit pool {full.shape}")
            return full.at[:, blk, off].set(
                jnp.asarray(lane).astype(full.dtype))

        new_cache[key] = jax.tree.map(scatter_rows, cache[key], payload[key])
    return new_cache


def slot_payload_bytes(payload) -> int:
    """On-wire size of an ``export_slot`` payload — what a cross-node
    migration must move over the interconnect."""
    return int(sum(a.size * jnp.dtype(a.dtype).itemsize
                   for a in jax.tree.leaves(payload)))
