"""Plain float32 building blocks of the reference forwards.

Straightforward ``jax.numpy``: no kernels, no cache, no batching, every
product at ``precision="highest"`` (on a TPU a float32 product otherwise
runs in bf16 passes).

``mode="fp8"`` is the control the comparison must reject: the same
forward computed one precision below the configuration's bf16, i.e.
rounded to fp8 (e4m3, one scale per tensor) wherever the program holds
bf16 -- weights and the inputs of every product, the residual stream
after each add, the queries, keys and values.  State the program keeps in
float32 (the SSM state) stays float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-6


def f32(a):
    return jnp.asarray(a, jnp.float32)


FP8_MAX = 448.0     # largest finite float8_e4m3fn


def rnd(x, mode: str):
    """``x`` as the mode's working precision holds it: unchanged in
    float32, rounded to fp8 e4m3 with one scale per tensor in the
    control."""
    if mode == "f32":
        return x
    if mode == "fp8":
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown mode {mode!r}")


def linear(x, w, mode: str = "f32"):
    """x (..., d_in) @ w (d_in, d_out) in float32, inputs rounded to the
    mode's precision."""
    return jnp.matmul(rnd(x, mode), rnd(f32(w), mode), precision=HIGHEST)


def rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) \
        * f32(scale)


def layernorm(x, scale, bias, one_plus: bool):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    s = f32(scale) + (1.0 if one_plus else 0.0)
    return (x - mean) * jax.lax.rsqrt(var + EPS) * s + f32(bias)


def norm(model: dict, p: dict, x):
    if model["norm"] == "rmsnorm":
        return rmsnorm(x, p["scale"])
    return layernorm(x, p["scale"], p["bias"],
                     model["norm"] == "layernorm1p")


def rope(x, pos, head_dim: int, rotary_pct: float, theta: float):
    """Rotary embedding of x (L, heads, head_dim) at positions pos (L,):
    the first ``rotary_pct`` of each head rotates, as two halves."""
    rot = int(head_dim * rotary_pct) // 2 * 2
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


def causal_attention(q, k, v, q_block: int = 512):
    """q (L, H, D), k/v (L, K, D), H a multiple of K; softmax(q k^T /
    sqrt(D)) v over positions <= the query's.  Queries go in blocks of
    ``q_block`` rows only to bound the score matrix's memory."""
    L, H, D = q.shape
    g = H // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    out = []
    for s0 in range(0, L, q_block):
        qb = q[s0:s0 + q_block]
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) * D ** -0.5
        rows = s0 + jnp.arange(qb.shape[0])
        mask = rows[:, None] >= jnp.arange(L)[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST))
    return jnp.concatenate(out, 0)


def attention(model: dict, p: dict, x, pos, mode: str, q_delta=None):
    """Projections, rotary, causal attention and the output projection."""
    L = x.shape[0]
    H, K, D = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    q = linear(x, p["wq"], mode)
    if q_delta is not None:
        q = q + q_delta
    q = q.reshape(L, H, D)
    k = linear(x, p["wk"], mode).reshape(L, K, D)
    v = rnd(linear(x, p["wv"], mode).reshape(L, K, D), mode)
    q = rnd(rope(q, pos, D, model["rotary_pct"], model["rope_theta"]), mode)
    k = rnd(rope(k, pos, D, model["rotary_pct"], model["rope_theta"]), mode)
    o = causal_attention(q, k, v).reshape(L, H * D)
    return linear(o, p["wo"], mode)


def mlp(model: dict, p: dict, x, mode: str):
    up = linear(x, p["w_up"], mode)
    if model["mlp"] == "swiglu":
        h = jax.nn.silu(linear(x, p["w_gate"], mode)) * up
    elif model["mlp"] == "squared_relu":
        h = jnp.square(jax.nn.relu(up))
    else:
        raise ValueError(f"unknown mlp {model['mlp']!r}")
    return linear(h, p["w_down"], mode)
