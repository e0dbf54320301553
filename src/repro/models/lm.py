"""Unified LM assembly for every registry architecture.

One declarative parameter tree + one forward covering:

  dense       pre-norm decoder (llama3.2, minitron, nemotron-4) with optional
              post-norms / softcaps / local-global alternation (gemma2)
  moe         every-layer token-choice top-k MoE (phi3.5-moe, olmoe)
  ssm         mamba-2 (SSD) attention-free stack (mamba2-370m)
  hybrid      mamba-2 backbone + SHARED attention blocks: the repo's block
              applied periodically with per-invocation LoRA (zamba2-1.2b),
              or the published Zamba2 blocks at listed layer ids
              (zamba2-7b)
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
    """The repo's zamba2 shared transformer block over concat(h, emb)
    (width 2*d_model), plus per-invocation LoRA adapters on the q
    projection."""
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


def _published_shared_decls(cfg: ModelConfig, n_uses: int) -> dict:
    """The published Zamba2 shared blocks: ``num_mem_blocks`` attention+MLP
    blocks, attention over concat(h, emb) (width 2*d_model), the MLP over
    the normed attention output; and per use its MLP adapters and its
    output projection.  Each block and each use is a leaf group of its
    own, so a use reads its weights whole, with no slice of a stack."""
    d, f, r = cfg.d_model, cfg.d_ff, cfg.adapter_rank

    def block():
        return {"ln1": L.norm_decls(cfg, d=2 * d),
                "attn": L.attention_decls(cfg, d_in=2 * d),
                "ln2": L.norm_decls(cfg), "mlp": L.mlp_decls(cfg)}

    def use():
        return {"adapter_a": PD((d, r), ("embed", "lora")),
                "adapter_b": PD((r, 2 * f), ("lora", "mlp")),
                "out": PD((d, d), (None, "embed"))}

    return {"blocks": [block() for _ in range(cfg.num_mem_blocks)],
            "uses": [use() for _ in range(n_uses)]}


def shared_block_uses(cfg: ModelConfig) -> list[int]:
    """The layer before which each use of a shared block runs, in order:
    the published ``hybrid_layer_ids`` below ``n_layers``; else (the repo's
    block) one after every ``shared_attn_period`` Mamba layers, the last of
    which may follow the final layer."""
    if cfg.hybrid_layer_ids:
        return [i for i in cfg.hybrid_layer_ids if i < cfg.n_layers]
    period = max(cfg.shared_attn_period, 1)
    return [period * (j + 1) for j in range(cfg.n_layers // period)]


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
        n_uses = len(shared_block_uses(cfg))
        d["layers"] = {"ln": L.norm_decls(cfg, layers=cfg.n_layers),
                       "mamba": L.mamba_decls(cfg, layers=cfg.n_layers)}
        d["shared"] = (_published_shared_decls(cfg, n_uses)
                       if cfg.num_mem_blocks
                       else _shared_attn_decls(cfg, n_uses))
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
            return _mamba_layer(ctx, cfg, p, h, st)

        body = _remat(ctx, body)
        st = cache["mamba"] if cache is not None else None
        h, new_st = _stack_scan(ctx, body, h, (params["layers"], st))
        if cache is not None:
            new_cache = {"mamba": new_st}

    elif cfg.family == "hybrid":
        h, aux, new_cache = _hybrid_forward(ctx, cfg, params, h, cos, sin,
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

def _mamba_layer(ctx, cfg, p, h, s, t=None):
    """h + mamba(norm(h + t)) (t: a published shared block's output, or
    None); s: the layer's {"ssm", "conv"} state or None."""
    x_in = L.apply_norm(cfg, p["ln"], h if t is None else h + t)
    out, (new_ssm, new_conv) = L.apply_mamba(
        ctx, cfg, p["mamba"], x_in,
        ssm_state=s["ssm"] if s is not None else None,
        conv_state=s["conv"] if s is not None else None)
    new_s = {"ssm": new_ssm, "conv": new_conv} if s is not None else None
    return h + out, new_s


def _mamba_range(ctx, cfg, stack, h, st, start, stop, t=None):
    """Mamba layers [start, stop) of the whole layer ``stack``; the first
    one's input takes ``t``.  Without state the range's weights are
    scanned.  With state (``st``: the whole stack's) each layer's weights
    and state are read at its index and the state written back in place,
    so a serving step copies no range of weights or of state."""
    if start >= stop:
        return h, st
    if st is None:
        def body(carry, p):
            h, t = carry
            h, _ = _mamba_layer(ctx, cfg, p, h, None, t)
            return (h, None if t is None else jnp.zeros_like(t)), None

        seg = jax.tree.map(lambda a: a[start:stop], stack)
        (h, _), _ = _stack_scan(ctx, _remat(ctx, body), (h, t), seg)
        return h, None

    def body(carry, i):
        h, t, st = carry
        with jax.named_scope("ssm"):       # the state's traffic is the layer's
            s = jax.tree.map(lambda a: a[i], st)
        h, s = _mamba_layer(ctx, cfg, jax.tree.map(lambda a: a[i], stack),
                            h, s, t)
        with jax.named_scope("ssm"):
            st = jax.tree.map(
                lambda a, n: jax.lax.dynamic_update_index_in_dim(
                    a, n.astype(a.dtype), i, axis=0), st, s)
        return (h, None if t is None else jnp.zeros_like(t), st), None

    (h, _, st), _ = _stack_scan(ctx, _remat(ctx, body), (h, t, st),
                                jnp.arange(start, stop, dtype=jnp.int32))
    return h, st


def _use_attention(ctx, cfg, p, x, cos, sin, j, kv, cache_index,
                   block_tables, q_delta=None):
    """Shared attention of use j, whose rows are layer j of the
    ``kv_shared`` stack: a paged pool stack is passed whole and updated in
    place at that layer; a dense cache is sliced and put back."""
    if kv is None or block_tables is not None:
        return L.apply_attention(ctx, cfg, p, x, cos, sin, cache=kv,
                                 cache_index=cache_index,
                                 block_tables=block_tables,
                                 layer=None if kv is None else j,
                                 q_delta=q_delta)
    out, kv_j = L.apply_attention(ctx, cfg, p, x, cos, sin,
                                  cache=jax.tree.map(lambda a: a[j], kv),
                                  cache_index=cache_index, q_delta=q_delta)
    return out, jax.tree.map(lambda a, n: a.at[j].set(n), kv, kv_j)


def _repo_block(ctx, cfg, p, j, h, emb0, cos, sin, kv, cache_index,
                block_tables):
    """The repo's zamba2 shared block, use j: attention with a per-use
    LoRA on q, then a SwiGLU MLP, both over norm(concat(h, emb0)), each
    added to the residual.  Returns (h, None, kv)."""
    c = ctx.cdtype
    a_in = L.apply_norm(cfg, p["ln1"], jnp.concatenate([h, emb0], axis=-1))
    with jax.named_scope("attn_proj"):
        B, S = a_in.shape[:2]
        q_delta = (a_in @ p["lora_a"][j].astype(c) @ p["lora_b"][j].astype(c)
                   ).reshape(B, S, cfg.n_heads, cfg.head_dim)
    out, kv = _use_attention(ctx, cfg, p["attn"], a_in, cos, sin, j, kv,
                             cache_index, block_tables, q_delta)
    h = h + out
    m_in = L.apply_norm(cfg, p["ln2"], jnp.concatenate([h, emb0], axis=-1))
    return h + L.apply_mlp(ctx, cfg, p["mlp"], m_in), None, kv


def _published_block(ctx, cfg, p, j, h, emb0, cos, sin, kv, cache_index,
                     block_tables):
    """The published Zamba2 shared block, use j: block j mod
    ``num_mem_blocks`` attends over norm(concat(h, emb0)); its MLP runs on
    the normed attention output with use j's adapters; use j's projection
    of the result is t.  No residual of its own: t enters the input of the
    Mamba layer that follows.  Returns (h, t, kv)."""
    blk, use = p["blocks"][j % cfg.num_mem_blocks], p["uses"][j]
    x = L.apply_norm(cfg, blk["ln1"], jnp.concatenate([h, emb0], axis=-1))
    o, kv = _use_attention(ctx, cfg, blk["attn"], x, cos, sin, j, kv,
                           cache_index, block_tables)
    m = L.apply_mlp(ctx, cfg, blk["mlp"], L.apply_norm(cfg, blk["ln2"], o),
                    adapter=(use["adapter_a"], use["adapter_b"]))
    with jax.named_scope("mlp"):
        t = jnp.einsum("bsd,de->bse", m, use["out"].astype(ctx.cdtype))
    return h, t, kv


def _hybrid_forward(ctx, cfg, params, h, cos, sin, *, cache=None,
                    cache_index=None, block_tables=None):
    """The Mamba stack with a shared block before the layers of
    ``shared_block_uses`` (use j's KV rows: layer j of ``kv_shared``)."""
    emb0 = h
    stack = params["layers"]
    st = cache["mamba"] if cache is not None else None
    kv = cache["kv_shared"] if cache is not None else None
    block = _published_block if cfg.num_mem_blocks else _repo_block
    uses = shared_block_uses(cfg)
    stops = uses + [cfg.n_layers]
    h, st = _mamba_range(ctx, cfg, stack, h, st, 0, stops[0])
    for j, start in enumerate(uses):
        h, t, kv = block(ctx, cfg, params["shared"], j, h, emb0, cos, sin,
                         kv, cache_index, block_tables)
        h, st = _mamba_range(ctx, cfg, stack, h, st, start, stops[j + 1], t)
    new_cache = None if cache is None else {"mamba": st, "kv_shared": kv}
    return h, jnp.zeros((), jnp.float32), new_cache


# ===========================================================================
# logits / caches
# ===========================================================================

def unembed_matrix(cfg: ModelConfig, params, dtype):
    if cfg.tie_embeddings:
        return params["embed"]["table"].T.astype(dtype)
    return params["unembed"]["w"].astype(dtype)


@L.scoped("logits")
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
        return {"mamba": ms(cfg, batch, c, layers=cfg.n_layers),
                "kv_shared": kv(cfg, batch, max_seq, c,
                                layers=len(shared_block_uses(cfg)))}
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
        return {"mamba": ms(cfg, batch, c, layers=cfg.n_layers),
                "kv_shared": pkv(cfg, n_blocks, block_size, c,
                                 layers=len(shared_block_uses(cfg))),
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
# stack (n_layers, or the shared blocks' uses for the zamba2 shared-
# attention cache) and
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
            # pool rows carry zero columns past head_dim
            # (layers.paged_head_dim): the payload holds head_dim
            payload[key] = jax.tree.map(
                lambda a: a[:, blk, off, ..., :cfg.head_dim], cache[key])
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
                    or lane.shape[2:-1] != full.shape[3:-1]
                    or lane.shape[-1] != cfg.head_dim):
                raise ValueError(f"{key}: payload lane {lane.shape} does "
                                 f"not fit pool {full.shape}")
            return full.at[:, blk, off].set(ops.pad_head(
                jnp.asarray(lane), full.shape[-1]).astype(full.dtype))

        new_cache[key] = jax.tree.map(scatter_rows, cache[key], payload[key])
    return new_cache


def slot_payload_bytes(payload) -> int:
    """On-wire size of an ``export_slot`` payload — what a cross-node
    migration must move over the interconnect."""
    return int(sum(a.size * jnp.dtype(a.dtype).itemsize
                   for a in jax.tree.leaves(payload)))
