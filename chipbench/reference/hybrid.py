"""Reference forward of the repo's zamba2 hybrid.

A stack of Mamba-2 layers (h += mamba(rmsnorm(h))) with one SHARED
attention block after every ``shared_attn_period`` of them, whose input
is concat(h, e) -- e the token embeddings -- and whose query adds a
per-invocation low-rank term (x A_i) B_i.  This follows the repo's model
(``chipbench/tests/data/zamba2_repo.json`` lists where that departs from the
published Zamba2-1.2B).

The Mamba-2 mixer is written as its recurrence, one position at a time:
  state_t = exp(A dt_t) state_{t-1} + dt_t x_t (outer) B_t
  y_t     = state_t . C_t + D x_t
after a causal depthwise convolution (window ``ssm_conv``) and SiLU over
(x, B, C), with dt = softplus(dt_raw + dt_bias), A = -exp(A_log), and a
gated RMSNorm rmsnorm(y * silu(z)) before the output projection.

Also this kind's counts (``chipbench/flops.py``) and ``check``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference import common as C


def causal_conv(x, w, b):
    """Depthwise causal convolution of x (L, C) with w (W, C): position t
    sees x[t - W + 1 .. t], zeros before the start."""
    W = w.shape[0]
    xp = jnp.pad(x, ((W - 1, 0), (0, 0)))
    out = sum(xp[i:i + x.shape[0]] * C.f32(w[i]) for i in range(W))
    return out + C.f32(b)


def ssm_scan(x, dt, A, B, Cm, D):
    """x (L, H, P), dt (L, H), A (H,), B/C (L, N): the recurrence above."""
    H, P = x.shape[1], x.shape[2]
    N = B.shape[-1]

    def step(state, inp):
        xt, dtt, bt, ct = inp
        state = (state * jnp.exp(A * dtt)[:, None, None]
                 + (xt * dtt[:, None])[:, :, None] * bt[None, None, :])
        y = jnp.einsum("hpn,n->hp", state, ct, precision=C.HIGHEST)
        return state, y

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (x, dt, B, Cm))
    return y + x * D[None, :, None]


def mamba(model: dict, p: dict, x, mode: str):
    d_in = model["ssm_expand"] * model["d_model"]
    N, P = model["ssm_state"], model["ssm_headdim"]
    H = d_in // P
    zxbcdt = C.rnd(C.linear(x, p["in_proj"], mode), mode)
    z = zxbcdt[:, :d_in]
    xbc = zxbcdt[:, d_in:2 * d_in + 2 * N]
    dt_raw = zxbcdt[:, 2 * d_in + 2 * N:]
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    xs = xbc[:, :d_in].reshape(-1, H, P)
    Bm, Cm = xbc[:, d_in:d_in + N], xbc[:, d_in + N:]
    dt = jax.nn.softplus(dt_raw + C.f32(p["dt_bias"]))
    A = -jnp.exp(C.f32(p["A_log"]))
    y = C.rnd(ssm_scan(xs, dt, A, Bm, Cm, C.f32(p["D"])).reshape(-1, d_in),
              mode)
    y = C.rmsnorm(y * jax.nn.silu(z), p["norm"])
    return C.linear(y, p["out_proj"], mode)


@functools.lru_cache(maxsize=None)
def _mamba_fn(model_key, mode):
    model = dict(model_key)

    def layer(p, h):
        return C.rnd(h + mamba(model, p["mamba"],
                               C.rmsnorm(h, p["ln"]["scale"]), mode), mode)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _shared_fn(model_key, mode):
    model = dict(model_key)

    def block(p, lora_a, lora_b, h, e, pos):
        a = C.rmsnorm(jnp.concatenate([h, e], -1), p["ln1"]["scale"])
        q_delta = C.linear(C.linear(a, lora_a, mode), lora_b, mode)
        h = C.rnd(h + C.attention(model, p["attn"], a, pos, mode,
                                  q_delta=q_delta), mode)
        m = C.rmsnorm(jnp.concatenate([h, e], -1), p["ln2"]["scale"])
        return C.rnd(h + C.mlp(model, p["mlp"], m, mode), mode)

    return jax.jit(block)


def hidden(model: dict, params, tokens, mode: str = "f32"):
    """Final-normed hidden states (L, d) of ``tokens`` (L,) int32."""
    key = tuple(sorted(model.items()))
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    e = C.rnd(C.f32(jnp.take(params["embed"]["table"], tokens, axis=0)),
              mode)
    h = e
    layer, block = _mamba_fn(key, mode), _shared_fn(key, mode)
    period = model["shared_attn_period"]
    n_super = model["n_layers"] // period
    sh = params["shared"]
    shared = {k: v for k, v in sh.items() if k not in ("lora_a", "lora_b")}
    for i in range(model["n_layers"]):
        h = layer(jax.tree.map(lambda a: a[i], params["layers"]), h)
        inv = (i + 1) // period - 1
        if (i + 1) % period == 0 and inv < n_super:
            h = block(shared, sh["lora_a"][inv], sh["lora_b"][inv], h, e,
                      pos)
    return C.norm(model, params["final_norm"], h)


# ---------------------------------------------------------------------------
# counts (chipbench/flops.py) and the settings this reference computes
# ---------------------------------------------------------------------------

def n_super(m: dict) -> int:
    """Shared-attention invocations; the model's attention layers."""
    return m["n_layers"] // m["shared_attn_period"]


def _mamba_weights(m: dict) -> int:
    """in_proj (z, x, B, C, dt) and out_proj of one Mamba-2 layer."""
    d, din = m["d_model"], m["ssm_expand"] * m["d_model"]
    hs = din // m["ssm_headdim"]
    return d * (2 * din + bc_width(m) + hs) + din * d


def matmul_weights_per_token(m: dict) -> int:
    """Weights one token multiplies through once, embeddings and the
    unembedding excluded (logits are counted apart): a shared block that
    runs n times counts n times."""
    d, f = m["d_model"], m["d_ff"]
    H, K, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    d2, r = 2 * d, m["shared_attn_lora"]
    shared = (d2 * H * hd + 2 * d2 * K * hd + H * hd * d   # q k v o
              + d2 * r + r * H * hd                         # LoRA on q
              + 2 * d2 * f + f * d)                         # SwiGLU MLP
    return m["n_layers"] * _mamba_weights(m) + n_super(m) * shared


def attention_layers(m: dict) -> int:
    return n_super(m)


def ssm_layers(m: dict) -> int:
    return m["n_layers"]


def bc_width(m: dict) -> int:
    """B and C, one group of ``ssm_state`` columns each."""
    return 2 * m["ssm_state"]


def ssm_scope_weights(m: dict) -> int:
    """The Mamba projections, which the program runs in its ssm scope."""
    return m["n_layers"] * _mamba_weights(m)


# settings of the program's model this reference does not compute, at the
# one value it assumes; and the values it computes of the others it reads
FIXED = {"family": "hybrid", "attn_softcap": None, "final_softcap": None,
         "query_scale": None, "post_norms": False,
         "embed_scale_by_sqrt_dim": False, "mrope_sections": None,
         "use_rope": True, "causal": True, "local_window": None,
         "layer_pattern": "global", "n_experts": 0, "frontend": None,
         "norm": "rmsnorm"}
CHOICES = {"mlp": ("swiglu", "squared_relu")}
POSITIVE = ("ssm_state", "shared_attn_period", "shared_attn_lora")


def check(model: dict) -> None:
    """Raise on a setting of the program's model this reference does not
    compute."""
    bad = {k: model[k] for k, v in FIXED.items() if model.get(k, v) != v}
    bad.update({k: model[k] for k, ok in CHOICES.items()
                if model[k] not in ok})
    bad.update({k: model[k] for k in POSITIVE if not model[k] > 0})
    if bad:
        raise ValueError(f"{__name__}: the reference does not compute "
                         f"{bad}")
