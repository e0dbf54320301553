"""Shared neural layers for the model zoo (pure functional, PD-declared).

Every ``*_decls`` returns a nested dict of PD declarations; the matching
``apply_*`` consumes the materialized params.  A ``Ctx`` threads execution
config (dtypes, kernel mode), sharding rules and the mesh through the stack.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.kernels import ops
from repro.models.params import PD
from repro.sharding.rules import LogicalRules, with_constraint


@dataclasses.dataclass(frozen=True)
class Ctx:
    run: RunConfig
    rules: LogicalRules
    mesh: Any = None     # jax.sharding.Mesh | None

    @property
    def cdtype(self):
        return jnp.dtype(self.run.compute_dtype)

    def cst(self, x, *axes):
        return with_constraint(x, self.rules, self.mesh, *axes)


def scoped(name: str):
    """Decorate a model part so that every op it stages runs under
    ``jax.named_scope(name)``: the compiled program's metadata, and so the
    profiler's ``tf_op`` path of each device op, then name the part
    (``embed``, ``norm``, ``attn_proj``, ``attn_core``, ``mlp``, ``ssm``,
    ``logits``, ``sample``).  Compile-time metadata only: the ops and their
    HLO names do not change."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped_fn(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped_fn
    return wrap


def _stack(shape, layers):
    return (layers,) + tuple(shape) if layers else tuple(shape)


def _saxes(axes, layers):
    return ("layers",) + tuple(axes) if layers else tuple(axes)


# ===========================================================================
# norms
# ===========================================================================

def norm_decls(cfg: ModelConfig, layers: int = 0,
               d: int | None = None) -> dict:
    d = d if d is not None else cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"scale": PD(_stack((d,), layers), _saxes(("embed",), layers),
                            "ones")}
    if cfg.norm == "layernorm":
        return {"scale": PD(_stack((d,), layers), _saxes(("embed",), layers),
                            "ones"),
                "bias": PD(_stack((d,), layers), _saxes(("embed",), layers),
                           "zeros")}
    if cfg.norm == "layernorm1p":  # nemotron: (1 + scale) reparameterization
        return {"scale": PD(_stack((d,), layers), _saxes(("embed",), layers),
                            "zeros"),
                "bias": PD(_stack((d,), layers), _saxes(("embed",), layers),
                           "zeros")}
    raise ValueError(cfg.norm)


@scoped("norm")
def apply_norm(cfg: ModelConfig, p: dict, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    else:
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        scale = p["scale"].astype(jnp.float32)
        if cfg.norm == "layernorm1p":
            scale = scale + 1.0
        out = (xf - mean) * jax.lax.rsqrt(var + eps) * scale \
            + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def rmsnorm_gated(scale, y, z, eps: float = 1e-6):
    """Mamba-2 RMSNormGated: rmsnorm(y * silu(z)) * scale."""
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(yf * yf, axis=-1, keepdims=True)
    return (yf * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(y.dtype)


# ===========================================================================
# rotary position embeddings (RoPE / partial-rotary / M-RoPE)
# ===========================================================================

@scoped("attn_proj")
def rope_cos_sin(cfg: ModelConfig, positions):
    """positions: (B, S) int for RoPE, or (3, B, S) for M-RoPE.
    Returns cos/sin of shape (B, S, rot_half)."""
    rot_dim = int(cfg.head_dim * cfg.rotary_pct) // 2 * 2
    half = rot_dim // 2
    inv_freq = cfg.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32)
                                  / half)
    if cfg.mrope_sections is not None:
        assert sum(cfg.mrope_sections) == half, (cfg.mrope_sections, half)
        parts, start = [], 0
        for i, sec in enumerate(cfg.mrope_sections):
            f = inv_freq[start:start + sec]
            parts.append(positions[i].astype(jnp.float32)[..., None]
                         * f[None, None, :])
            start += sec
        ang = jnp.concatenate(parts, axis=-1)
    else:
        ang = positions.astype(jnp.float32)[..., None] * inv_freq[None, None]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin, rotary_pct: float = 1.0):
    """x: (B, S, H, D); cos/sin: (B, S, rot_half)."""
    D = x.shape[-1]
    rot_dim = int(D * rotary_pct) // 2 * 2
    half = rot_dim // 2
    xr, xp = x[..., :rot_dim], x[..., rot_dim:]
    x1, x2 = xr[..., :half], xr[..., half:]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    rotated = jnp.concatenate([x1f * c - x2f * s, x2f * c + x1f * s], -1)
    return jnp.concatenate([rotated.astype(x.dtype), xp], -1)


# ===========================================================================
# attention (GQA + optional KV cache)
# ===========================================================================

def attention_decls(cfg: ModelConfig, layers: int = 0,
                    d_in: int | None = None) -> dict:
    """Projections are stored FLAT ((d, H*hd) etc.) and sharded on the
    flattened column dim ("qkv_flat"/"kv_flat"): unlike per-head sharding
    this stays divisible on a 16-way model axis even for 24-head or
    8-kv-head archs (3072 % 16 == 0), avoiding GSPMD padding or replicated
    attention weights."""
    d = d_in if d_in is not None else cfg.d_model
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "wq": PD(_stack((d, H * hd), layers),
                 _saxes(("embed", "qkv_flat"), layers), scale=d ** -0.5),
        "wk": PD(_stack((d, K * hd), layers),
                 _saxes(("embed", "kv_flat"), layers), scale=d ** -0.5),
        "wv": PD(_stack((d, K * hd), layers),
                 _saxes(("embed", "kv_flat"), layers), scale=d ** -0.5),
        "wo": PD(_stack((H * hd, cfg.d_model), layers),
                 _saxes(("qkv_flat", "embed"), layers),
                 scale=(H * hd) ** -0.5),
    }


def apply_attention(ctx: Ctx, cfg: ModelConfig, p: dict, x, cos, sin, *,
                    local_window=None, cache=None, cache_index=None,
                    x_kv=None, block_tables=None, layer=None):
    """x: (B, S, d_in).  With ``cache`` (dict k/v (B, Smax, K, hd)) performs a
    decode step and returns (y, new_cache).  With ``block_tables``
    ((B, max_blocks) int32) the cache leaves are PAGED pools
    (n_blocks, bs, K, hd) and every read/write goes through the table;
    with ``layer`` too they are the whole layer stack
    (L, n_blocks, bs, K, hd), read and updated in place at that layer."""
    c = ctx.cdtype
    x_kv = x if x_kv is None else x_kv
    B, S = x.shape[:2]
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("attn_proj"):
        q = jnp.einsum("bsd,de->bse", x, p["wq"].astype(c)
                       ).reshape(B, S, H, hd)
        k = jnp.einsum("bsd,de->bse", x_kv, p["wk"].astype(c)
                       ).reshape(B, S, K, hd)
        v = jnp.einsum("bsd,de->bse", x_kv, p["wv"].astype(c)
                       ).reshape(B, S, K, hd)
        q = ctx.cst(q, "act_batch", "act_seq", "act_heads", None)
        k = ctx.cst(k, "act_batch", "act_seq", "act_kv_heads", None)
        v = ctx.cst(v, "act_batch", "act_seq", "act_kv_heads", None)
        if cfg.use_rope:
            q = apply_rope(q, cos, sin, cfg.rotary_pct)
            k = apply_rope(k, cos, sin, cfg.rotary_pct)
    scale = (cfg.query_scale ** -0.5 if cfg.query_scale is not None
             else cfg.head_dim ** -0.5)
    out, new_cache = _attend(ctx, cfg, q, k, v, cache, cache_index,
                             block_tables, layer, local_window, scale)
    return attn_out_proj(ctx, p["wo"], out), new_cache


@scoped("attn_proj")
def attn_out_proj(ctx: Ctx, wo, out):
    """The output projection of attention heads out (B, S, H, hd)."""
    B, S, H, hd = out.shape
    y = jnp.einsum("bse,ed->bsd", out.reshape(B, S, H * hd),
                   wo.astype(ctx.cdtype))
    return ctx.cst(y, "act_batch", "act_seq", "act_embed")


@scoped("attn_core")
def _attend(ctx: Ctx, cfg: ModelConfig, q, k, v, cache, cache_index,
            block_tables, layer, local_window, scale):
    """The cache write and the attention kernel of ``apply_attention``;
    returns (out (B, S, H, hd), the new cache or None)."""
    c = ctx.cdtype
    B = q.shape[0]
    if cache is not None and block_tables is not None:
        # paged path: per-slot offsets (or a scalar prefill cursor broadcast
        # to all slots) resolve to (block, offset) pool rows via the table.
        # No cst() on pool leaves — the pool's leading dim is blocks, not
        # batch, so the dense cache's logical axes don't apply.
        per_slot = jnp.ndim(cache_index) >= 1
        idx_vec = (jnp.asarray(cache_index, jnp.int32) if per_slot
                   else jnp.full((B,), cache_index, jnp.int32))
        ck, cv = ops.kv_cache_update_paged(cache["k"], cache["v"], k, v,
                                           idx_vec, block_tables, layer,
                                           mode=ctx.run.kernel_mode)
        kv_len = idx_vec + q.shape[1]
        # pools are built in the compute dtype (lm.init_paged_cache)
        out = ops.decode_attention_paged(q, ck, cv, kv_len, block_tables,
                                         layer, softcap=cfg.attn_softcap,
                                         local_window=local_window,
                                         scale=scale,
                                         mode=ctx.run.kernel_mode)
        out = ctx.cst(out, "act_batch", "act_seq", "act_heads", None)
        return out, {"k": ck, "v": cv}
    new_cache = None
    if cache is not None:
        per_slot = jnp.ndim(cache_index) >= 1
        if not per_slot and _use_seqsharded_decode(ctx, cfg, q, cache):
            return _decode_attention_seqsharded(
                ctx, cfg, q, cache, k, v, cache_index, scale=scale,
                local_window=local_window)
        if per_slot:
            # continuous batching: every slot writes at its own offset
            # (scattered cache write; OOB rows — done slots — dropped)
            ck, cv = ops.kv_cache_update(cache["k"], cache["v"], k, v,
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
            jnp.asarray(cache_index + q.shape[1], jnp.int32), (B,))
        out = ops.decode_attention(q, ck.astype(c), cv.astype(c), kv_len,
                                   softcap=cfg.attn_softcap,
                                   local_window=local_window, scale=scale,
                                   mode=ctx.run.kernel_mode,
                                   block_kv=ctx.run.attn_block_kv)
    else:
        out = ops.attention(q, k, v, causal=cfg.causal,
                            local_window=local_window,
                            softcap=cfg.attn_softcap, scale=scale,
                            mode=ctx.run.kernel_mode,
                            block_q=ctx.run.attn_block_q,
                            block_kv=ctx.run.attn_block_kv,
                            naive_below=ctx.run.naive_attn_below)
    return ctx.cst(out, "act_batch", "act_seq", "act_heads", None), new_cache


def empty_kv_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype,
                   layers: int = 0):
    shape = _stack((batch, max_seq, cfg.n_kv_heads, cfg.head_dim), layers)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def abstract_kv_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype,
                      layers: int = 0):
    shape = _stack((batch, max_seq, cfg.n_kv_heads, cfg.head_dim), layers)
    sds = jax.ShapeDtypeStruct(shape, dtype)
    return {"k": sds, "v": sds}


KV_CACHE_AXES = {"k": ("layers", "act_batch", "act_kv_seq", None, None),
                 "v": ("layers", "act_batch", "act_kv_seq", None, None)}


def empty_paged_kv_cache(cfg: ModelConfig, n_blocks: int, block_size: int,
                         dtype, layers: int = 0):
    """Paged KV pool: (n_blocks, block_size, K, hd) per layer — a shared
    arena of fixed-size blocks addressed through per-slot block tables
    instead of a dense (batch, max_seq, ...) lane per slot."""
    shape = _stack((n_blocks, block_size, cfg.n_kv_heads, cfg.head_dim),
                   layers)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def abstract_paged_kv_cache(cfg: ModelConfig, n_blocks: int, block_size: int,
                            dtype, layers: int = 0):
    shape = _stack((n_blocks, block_size, cfg.n_kv_heads, cfg.head_dim),
                   layers)
    sds = jax.ShapeDtypeStruct(shape, dtype)
    return {"k": sds, "v": sds}


# pool leading dim is the block arena, not batch: replicate (the paged
# serving path is single-host today; block-sharded pools are future work)
PAGED_KV_CACHE_AXES = {"k": ("layers", None, None, None, None),
                       "v": ("layers", None, None, None, None)}


def _use_seqsharded_decode(ctx: Ctx, cfg: ModelConfig, x, cache) -> bool:
    """Single-token decode with a model-axis-seq-sharded cache.

    Only when the batch divides the dp axes: there GSPMD would all-gather
    the cache per layer (qwen decode_32k: 200x collective win, §Perf B1/B2).
    For B=1 latency decode GSPMD's own partial-softmax handling is already
    gather-free and the shard_map adds ~25 % op overhead (measured on
    zamba2 long_500k — hypothesis refuted, see §Perf)."""
    if ctx.mesh is None or "model" not in ctx.mesh.shape:
        return False
    if x.shape[1] != 1:
        return False                    # prefill writes use the plain path
    n_model = ctx.mesh.shape["model"]
    S = cache["k"].shape[1]
    B = cache["k"].shape[0]
    dp = 1
    for a in ("pod", "data"):
        dp *= ctx.mesh.shape.get(a, 1)
    return S % n_model == 0 and B % dp == 0


def _decode_attention_seqsharded(ctx: Ctx, cfg: ModelConfig, q, cache,
                                 k_new, v_new, cache_index, *, scale,
                                 local_window=None):
    """Distributed flash-decode over a sequence-sharded KV cache.

    GSPMD's auto-partitioner ALL-GATHERS a seq-sharded cache per layer
    (~531 MB/layer/device for qwen2-vl-72b decode_32k, measured in
    EXPERIMENTS.md §Perf) because the softmax reduces over the sharded dim.
    Instead: each model-axis shard computes partial attention over its local
    cache slice and the shards combine with the log-sum-exp trick — a
    pmax/psum of (B, H) stats + the (B, H, hd) partial output, ~4 MB/layer.
    The single-token cache write happens only on the owning shard."""
    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh
    c = ctx.cdtype
    B, _, H, hd = q.shape
    K = cfg.n_kv_heads
    G = H // K
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    if B % dp != 0:        # e.g. B=1 long-context latency decode
        dp_axes = None     # replicate batch over the dp axes
    cache_spec = P(dp_axes, "model", None, None)
    rep_spec = P(dp_axes, None, None, None)

    def local_fn(qv, ck, cv, kn, vn, idx):
        B_l, S_l = ck.shape[0], ck.shape[1]
        my = jax.lax.axis_index("model")
        owner = idx // S_l
        pos = idx % S_l
        pred = (owner == my)
        cur_k = jax.lax.dynamic_slice(ck, (0, pos, 0, 0), (B_l, 1, K, hd))
        cur_v = jax.lax.dynamic_slice(cv, (0, pos, 0, 0), (B_l, 1, K, hd))
        ck = jax.lax.dynamic_update_slice(
            ck, jnp.where(pred, kn.astype(ck.dtype), cur_k), (0, pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cv, jnp.where(pred, vn.astype(cv.dtype), cur_v), (0, pos, 0, 0))

        qf = qv.astype(jnp.float32).reshape(B_l, K, G, hd) * scale
        kf = ck.astype(jnp.float32)
        vf = cv.astype(jnp.float32)
        logits = jnp.einsum("bkgd,bskd->bkgs", qf, kf)
        if cfg.attn_softcap is not None:
            logits = cfg.attn_softcap * jnp.tanh(logits / cfg.attn_softcap)
        k_pos = my * S_l + jnp.arange(S_l)
        mask = k_pos[None, None, None, :] <= idx
        if local_window is not None:
            mask &= k_pos[None, None, None, :] > idx - local_window
        logits = jnp.where(mask, logits, -1e30)
        m_l = logits.max(axis=-1)                              # (B,K,G)
        p = jnp.exp(logits - m_l[..., None])
        p = jnp.where(mask, p, 0.0)
        l_l = p.sum(axis=-1)
        o_l = jnp.einsum("bkgs,bskd->bkgd", p, vf)
        m = jax.lax.pmax(m_l, "model")
        w = jnp.exp(m_l - m)
        l = jax.lax.psum(l_l * w, "model")
        o = jax.lax.psum(o_l * w[..., None], "model")
        o = o / jnp.maximum(l, 1e-30)[..., None]
        return o.reshape(B_l, 1, H, hd).astype(c), ck, cv

    out, ck, cv = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(rep_spec, cache_spec, cache_spec, rep_spec, rep_spec, P()),
        out_specs=(rep_spec, cache_spec, cache_spec),
    )(q, cache["k"], cache["v"], k_new, v_new,
      jnp.asarray(cache_index, jnp.int32))
    return out, {"k": ck, "v": cv}


# ===========================================================================
# dense MLPs
# ===========================================================================

def mlp_decls(cfg: ModelConfig, layers: int = 0) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    decls = {
        "w_up": PD(_stack((d, f), layers), _saxes(("embed", "mlp"), layers)),
        "w_down": PD(_stack((f, d), layers), _saxes(("mlp", "embed"), layers)),
    }
    if cfg.mlp in ("swiglu", "geglu"):
        decls["w_gate"] = PD(_stack((d, f), layers),
                             _saxes(("embed", "mlp"), layers))
    return decls


@scoped("mlp")
def apply_mlp(ctx: Ctx, cfg: ModelConfig, p: dict, x):
    c = ctx.cdtype
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(c))
    up = ctx.cst(up, "act_batch", "act_seq", "act_mlp")
    if cfg.mlp == "swiglu":
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(c))
        h = jax.nn.silu(gate) * up
    elif cfg.mlp == "geglu":
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(c))
        h = jax.nn.gelu(gate, approximate=True) * up
    elif cfg.mlp == "squared_relu":
        r = jax.nn.relu(up)
        h = r * r
    elif cfg.mlp == "gelu":
        h = jax.nn.gelu(up, approximate=True)
    else:
        raise ValueError(cfg.mlp)
    y = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(c))
    return ctx.cst(y, "act_batch", "act_seq", "act_embed")


# ===========================================================================
# mixture of experts (token-choice top-k, capacity-based dispatch)
# ===========================================================================

def moe_decls(cfg: ModelConfig, layers: int = 0) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": PD(_stack((d, e), layers), _saxes(("embed", None), layers),
                     scale=d ** -0.5),
        "w_gate": PD(_stack((e, d, f), layers),
                     _saxes(("expert", "embed", "expert_mlp"), layers),
                     scale=d ** -0.5),
        "w_up": PD(_stack((e, d, f), layers),
                   _saxes(("expert", "embed", "expert_mlp"), layers),
                   scale=d ** -0.5),
        "w_down": PD(_stack((e, f, d), layers),
                     _saxes(("expert", "expert_mlp", "embed"), layers),
                     scale=f ** -0.5),
    }


def _moe_router(cfg: ModelConfig, p: dict, xf):
    """Router probs + top-k + Switch-style load-balancing aux loss."""
    E, K = cfg.n_experts, cfg.top_k
    T = xf.shape[0]
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)                      # (T, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(
        1.0 / (T * K), mode="drop")
    aux = E * jnp.sum(me * ce)
    return top_p, top_e, aux


def _moe_dispatch_local(cfg: ModelConfig, xf, top_e, capacity):
    """Capacity dispatch of local tokens -> (E, capacity, D) + combine
    indices.  Pure local compute (cumsum position-in-expert, scatter with
    drop-on-overflow)."""
    E, K = cfg.n_experts, cfg.top_k
    flat_e = top_e.reshape(-1)                                  # (T*K,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    keep = pos < capacity
    slot = jnp.where(keep, pos, capacity)                       # overflow row
    src = jnp.repeat(xf, K, axis=0)
    expert_in = jnp.zeros((E, capacity + 1, xf.shape[-1]), xf.dtype)
    expert_in = expert_in.at[flat_e, slot].add(src, mode="drop")
    return expert_in[:, :capacity], flat_e, slot, keep


def _moe_combine_local(out, flat_e, slot, keep, top_p, B, S):
    """Gather expert outputs back to token order, weighted by router prob."""
    E, capacity, D = out.shape
    K = top_p.shape[-1]
    pad = jnp.zeros((E, 1, D), out.dtype)
    out_p = jnp.concatenate([out, pad], axis=1)
    gathered = out_p[flat_e, slot]
    gathered = gathered * (top_p.reshape(-1)[:, None].astype(out.dtype)
                           * keep[:, None].astype(out.dtype))
    return gathered.reshape(B * S, K, D).sum(axis=1).reshape(B, S, D)


def _moe_expert_ffn(ctx: Ctx, cfg: ModelConfig, p: dict, expert_in,
                    cast_w=True):
    c = ctx.cdtype
    mode = ctx.run.kernel_mode
    wg = p["w_gate"].astype(c) if cast_w else p["w_gate"]
    wu = p["w_up"].astype(c) if cast_w else p["w_up"]
    wd = p["w_down"].astype(c) if cast_w else p["w_down"]
    gate = ops.grouped_matmul(expert_in, wg, mode=mode)
    up = ops.grouped_matmul(expert_in, wu, mode=mode)
    return ops.grouped_matmul(jax.nn.silu(gate) * up, wd, mode=mode)


@scoped("mlp")
def apply_moe(ctx: Ctx, cfg: ModelConfig, p: dict, x):
    """Token-choice top-k MoE with capacity-based dispatch.

    Two execution paths:
      * dense (mesh-less smoke tests / meshes without expert parallelism):
        local scatter dispatch + grouped matmul;
      * shard_map (production): tokens stay batch-sharded, experts stay
        model-axis-sharded, and the dispatch/return are explicit
        ``lax.all_to_all`` exchanges along the model axis.  GSPMD's auto
        partitioner replicates scatter-based dispatch (560x flop waste,
        measured in EXPERIMENTS.md §Dry-run), so the collective is hand
        placed — this is the deployment-grade EP path.
    Returns (y, aux_loss).
    """
    c = ctx.cdtype
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    xf = x.reshape(B * S, D)
    top_p, top_e, aux = _moe_router(cfg, p, xf)

    mesh = ctx.mesh
    use_ep = (mesh is not None and "model" in mesh.shape
              and E % mesh.shape["model"] == 0
              and B % _dp_size(mesh) == 0
              and S % mesh.shape["model"] == 0)
    if not use_ep:
        import math
        T = B * S
        capacity = int(max(1, math.ceil(T * K * cfg.capacity_factor / E)))
        expert_in, flat_e, slot, keep = _moe_dispatch_local(
            cfg, xf, top_e, capacity)
        out = _moe_expert_ffn(ctx, cfg, p, expert_in.astype(c))
        y = _moe_combine_local(out, flat_e, slot, keep, top_p, B, S)
        return ctx.cst(y, "act_batch", "act_seq", "act_embed"), aux

    # keep (B, S, ...) shapes across the shard_map boundary: a global
    # (B,S,D)<->(T,D) reshape under a 3-axis token sharding loses its
    # sharding in the transpose pass (measured: full-residual all-gathers
    # in backward on the multi-pod mesh); flattening happens locally inside
    y = _moe_shard_map(ctx, cfg, p, x, top_p.reshape(B, S, K),
                       top_e.reshape(B, S, K))
    return ctx.cst(y, "act_batch", "act_seq", "act_embed"), aux


def _dp_size(mesh) -> int:
    n = 1
    for ax in ("pod", "data"):
        n *= mesh.shape.get(ax, 1)
    return n


@jax.custom_vjp
def _a2a_int8(t):
    """int8-wire all-to-all along the "model" axis (inside shard_map).

    Forward: per-row symmetric int8 quantization (f32 scale sidecar) —
    halves the dominant EP dispatch bytes vs bf16.  Backward: the cotangent
    rides a plain (bf16) reverse exchange — a2a along the same axis is its
    own transpose."""
    return _a2a_int8_fwd(t)[0]


def _a2a_int8_fwd(t):
    # scale-per-row int8 wire format, shared with the at-rest snapshot
    # compression in repro.models.lm.quantize_payload
    q, scale = ops.int8_quantize(t)
    q_x = jax.lax.all_to_all(q, "model", 0, 0, tiled=False)
    s_x = jax.lax.all_to_all(scale, "model", 0, 0, tiled=False)
    return ops.int8_dequantize(q_x, s_x, t.dtype), None


def _a2a_int8_bwd(_, g):
    return (jax.lax.all_to_all(g, "model", 0, 0, tiled=False),)


_a2a_int8.defvjp(_a2a_int8_fwd, _a2a_int8_bwd)


def _moe_shard_map(ctx: Ctx, cfg: ModelConfig, p: dict, x, top_p, top_e):
    """Expert-parallel MoE via explicit all-to-all under shard_map.
    x: (B, S, D); top_p/top_e: (B, S, K) — batch over dp axes, seq over the
    model axis; token flattening is local to each shard."""
    from jax.sharding import PartitionSpec as P

    c = ctx.cdtype
    mesh = ctx.mesh
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    n_model = mesh.shape["model"]
    e_local = E // n_model
    dp = _dp_size(mesh)
    import math
    t_local = (B // dp) * (S // n_model)
    cap = int(max(1, math.ceil(t_local * K * cfg.capacity_factor / E)))

    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    tok_spec = P(dp_axes, "model", None)
    w_spec = P("model", None, None)

    def _a2a(t):
        if ctx.run.moe_a2a_dtype == "int8":
            return _a2a_int8(t)
        return jax.lax.all_to_all(t, "model", split_axis=0,
                                  concat_axis=0, tiled=False)

    def local_fn(x_l, tp_l, te_l, wg, wu, wd):
        # x_l: (B_l, S_l, D); w*: (e_local, D, F) local expert shards;
        # flatten LOCALLY (a global reshape would cross the sharding)
        B_l, S_l, D_l = x_l.shape
        xf_l = x_l.reshape(B_l * S_l, D_l).astype(c)
        te_f = te_l.reshape(B_l * S_l, -1)
        tp_f = tp_l.reshape(B_l * S_l, -1)
        disp, flat_e, slot, keep = _moe_dispatch_local(
            cfg, xf_l, te_f, cap)                       # (E, cap, D)
        disp = disp.reshape(n_model, e_local, cap, -1)
        recv = _a2a(disp)
        # recv[i] = tokens from source shard i for MY experts
        recv = recv.transpose(1, 0, 2, 3).reshape(e_local, n_model * cap, -1)
        out = _moe_expert_ffn(ctx, cfg, {"w_gate": wg, "w_up": wu,
                                         "w_down": wd}, recv, cast_w=False)
        out = out.reshape(e_local, n_model, cap, -1).transpose(1, 0, 2, 3)
        back = _a2a(out)
        back = back.reshape(E, cap, -1)
        y_l = _moe_combine_local(back, flat_e, slot, keep, tp_f,
                                 1, B_l * S_l)
        return y_l.reshape(B_l, S_l, -1)

    y = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(tok_spec, tok_spec, tok_spec, w_spec, w_spec, w_spec),
        out_specs=tok_spec,
    )(x, top_p, top_e, p["w_gate"].astype(c), p["w_up"].astype(c),
      p["w_down"].astype(c))
    return y


# ===========================================================================
# mamba-2 block (SSD)
# ===========================================================================

def mamba_decls(cfg: ModelConfig, layers: int = 0) -> dict:
    d, din = cfg.d_model, cfg.d_inner
    H, N, G = cfg.ssm_heads, cfg.ssm_state, 1
    conv_dim = din + 2 * G * N
    d_in_proj = 2 * din + 2 * G * N + H
    return {
        "in_proj": PD(_stack((d, d_in_proj), layers),
                      _saxes(("embed", "ssm_inner"), layers)),
        "conv_w": PD(_stack((cfg.ssm_conv, conv_dim), layers),
                     _saxes(("conv", "ssm_inner"), layers),
                     scale=cfg.ssm_conv ** -0.5),
        "conv_b": PD(_stack((conv_dim,), layers),
                     _saxes(("ssm_inner",), layers), "zeros"),
        "A_log": PD(_stack((H,), layers), _saxes(("ssm_heads",), layers),
                    "embed", scale=0.5),
        "D": PD(_stack((H,), layers), _saxes(("ssm_heads",), layers), "ones"),
        "dt_bias": PD(_stack((H,), layers), _saxes(("ssm_heads",), layers),
                      "embed", scale=0.5),
        "norm": PD(_stack((din,), layers), _saxes(("ssm_inner",), layers),
                   "ones"),
        "out_proj": PD(_stack((din, d), layers),
                       _saxes(("ssm_inner", "embed"), layers)),
    }


def _causal_conv(x, w, b):
    """Depthwise causal conv via shifted adds. x: (B,S,C); w: (W,C)."""
    W = w.shape[0]
    out = x * w[-1][None, None, :]
    for i in range(1, W):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, :-i or None][:, :x.shape[1]]
        out = out + shifted * w[W - 1 - i][None, None, :]
    return out + b[None, None, :]


def _split_mamba(cfg: ModelConfig, zxbcdt):
    din, N, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    G = 1
    z = zxbcdt[..., :din]
    xbc = zxbcdt[..., din:din + din + 2 * G * N]
    dt = zxbcdt[..., din + din + 2 * G * N:]
    return z, xbc, dt


@scoped("ssm")
def apply_mamba(ctx: Ctx, cfg: ModelConfig, p: dict, x, *,
                ssm_state=None, conv_state=None):
    """Mamba-2 block.  Train/prefill when states are None; single-step decode
    when (ssm_state, conv_state) are provided (S must be 1).

    Returns (y, (new_ssm_state, new_conv_state))."""
    c = ctx.cdtype
    B, S, _ = x.shape
    din, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_headdim
    G = 1
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(c))
    zxbcdt = ctx.cst(zxbcdt, "act_batch", "act_seq", "act_ssm")
    z, xbc, dt_raw = _split_mamba(cfg, zxbcdt)

    conv_w = p["conv_w"].astype(c)
    conv_b = p["conv_b"].astype(c)
    decode = S == 1 and ssm_state is not None
    new_conv_state = None
    if decode:
        # decode: roll window, apply conv at the newest position
        window = jnp.concatenate([conv_state, xbc], axis=1)     # (B, W, C)
        xbc = (window * conv_w[None]).sum(axis=1, keepdims=True) + conv_b
        new_conv_state = window[:, 1:]
    elif conv_state is not None:
        # prefill into a cache slot, possibly CONTINUING from an earlier
        # chunk: the carried conv window is the true left context (a fresh
        # slot carries zeros, which reproduces plain zero-padding), so the
        # chunked prefill of the serving runtime is exact.  Also keeps the
        # saved window well-shaped for chunks shorter than ssm_conv - 1.
        window = jnp.concatenate([conv_state.astype(xbc.dtype), xbc], axis=1)
        new_conv_state = window[:, -(cfg.ssm_conv - 1):]
        xbc = _causal_conv(window, conv_w, conv_b)[:, cfg.ssm_conv - 1:]
    else:
        xbc = _causal_conv(xbc, conv_w, conv_b)
    xbc = jax.nn.silu(xbc)

    xs = xbc[..., :din].reshape(B, S, H, P)
    Bm = xbc[..., din:din + G * N].reshape(B, S, G, N)
    Cm = xbc[..., din + G * N:].reshape(B, S, G, N)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    if decode:
        from repro.kernels.ref import ssd_decode_step
        y1, last_state = ssd_decode_step(
            ssm_state, xs[:, 0], dt[:, 0].astype(c), A, Bm[:, 0], Cm[:, 0],
            D=p["D"].astype(jnp.float32))
        y = y1[:, None]
    else:
        chunk = min(cfg.ssm_chunk, S)
        while S % chunk:
            chunk -= 1
        y, last_state = ops.ssd(xs, dt.astype(c), A, Bm, Cm,
                                D=p["D"].astype(jnp.float32), h0=ssm_state,
                                chunk=chunk, mode=ctx.run.kernel_mode)
    y = y.reshape(B, S, din)
    y = rmsnorm_gated(p["norm"], y, z)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(c))
    out = ctx.cst(out, "act_batch", "act_seq", "act_embed")
    return out, (last_state, new_conv_state)


def empty_mamba_state(cfg: ModelConfig, batch: int, dtype, layers: int = 0):
    H, P, N = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    conv_dim = cfg.d_inner + 2 * cfg.ssm_state
    return {
        "ssm": jnp.zeros(_stack((batch, H, P, N), layers), jnp.float32),
        "conv": jnp.zeros(_stack((batch, cfg.ssm_conv - 1, conv_dim), layers),
                          dtype),
    }


def abstract_mamba_state(cfg: ModelConfig, batch: int, dtype, layers: int = 0):
    H, P, N = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    conv_dim = cfg.d_inner + 2 * cfg.ssm_state
    return {
        "ssm": jax.ShapeDtypeStruct(_stack((batch, H, P, N), layers),
                                    jnp.float32),
        "conv": jax.ShapeDtypeStruct(
            _stack((batch, cfg.ssm_conv - 1, conv_dim), layers), dtype),
    }


MAMBA_STATE_AXES = {"ssm": ("layers", "act_batch", "ssm_heads", None, None),
                    "conv": ("layers", "act_batch", None, "act_ssm")}


# ===========================================================================
# embeddings
# ===========================================================================

def embed_decls(cfg: ModelConfig) -> dict:
    return {"table": PD((cfg.vocab_padded, cfg.d_model), ("vocab", "embed"),
                        "embed", scale=0.02)}


@scoped("embed")
def apply_embed(ctx: Ctx, cfg: ModelConfig, p: dict, tokens):
    emb = jnp.take(p["table"].astype(ctx.cdtype), tokens, axis=0)
    if cfg.embed_scale_by_sqrt_dim:      # gemma-style input scaling
        emb = emb * jnp.asarray(cfg.d_model ** 0.5, ctx.cdtype)
    return ctx.cst(emb, "act_batch", "act_seq", "act_embed")


def unembed_decls(cfg: ModelConfig) -> dict:
    if cfg.tie_embeddings:
        return {}
    return {"w": PD((cfg.d_model, cfg.vocab_padded), ("embed", "vocab"),
                    scale=cfg.d_model ** -0.5)}
