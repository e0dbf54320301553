"""Reference forward of the published Zamba2 hybrid (Zamba2-7B-Instruct).

Let e be the token embeddings and h = e.  For layer i = 0 .. n-1:

  * if i is the j-th of ``hybrid_layer_ids``, block b = j mod
    ``num_mem_blocks`` runs:
      x = rmsnorm(concat(h, e));  q, k, v = x W_q, x W_k, x W_v;
      rotary embedding over the whole head; causal softmax attention with
      scale (head_dim / 2)^-1/2 (``query_scale``); o = attn W_o;
      y = rmsnorm(o);  [g | u] = y W_gu + (y A_j) B_j  (use j's adapters);
      m = (gelu(g) * u) W_down, with the exact (erf) GELU;  t = m L_j;
    the block has no residual of its own;
  * otherwise t = 0;
  * then h <- h + mamba_i(rmsnorm(h + t)): the block's output enters the
    Mamba layer's input, not the residual.

Logits are rmsnorm(h) E^T with the embedding E tied.

mamba_i(x): [z | xBC | dt] = x W_in; xBC <- silu(causal depthwise conv
(window ``ssm_conv``) of xBC + bias); xBC splits into x (heads x P) and
B, C (``ssm_ngroups`` groups of ``ssm_state`` each), head h reading group
h // (heads / groups); dt = softplus(dt + dt_bias), A = -exp(A_log); the
recurrence, one position at a time,
  state_t = exp(A dt_t) state_{t-1} + dt_t x_t (outer) B_t
  y_t     = state_t . C_t + D x_t
then y = rmsnorm(y * silu(z)) taken over each group of d_inner / groups
channels apart; out = y W_out.

The equations follow the published config.json and Hugging Face's
``modeling_zamba2.py``.  Departures, each shared with the program:
  * W_gu is held as two matrices, gate and up, and B_j as one (r, 2 d_ff)
    matrix whose first half adds to the gate; W_in as [z | xBC] and dt's
    columns apart: the same products;
  * the weights are the benchmark's random draw (``chipbench/weights.py``),
    not the published checkpoint;
  * a cut configuration keeps layers 0 .. n_layers - 1 and the ids below
    n_layers, and the final norm and the tied head of the whole model.

Computed layer by layer so that only one layer's weights are float32 at
a time.  Also this kind's counts (``chipbench/flops.py``) and ``check``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference import common as C


def rmsnorm(model: dict, x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + model["norm_eps"]) * C.f32(scale)


def causal_conv(x, w, b):
    """Depthwise causal convolution of x (L, C) with w (W, C): position t
    sees x[t - W + 1 .. t], zeros before the start."""
    W = w.shape[0]
    xp = jnp.pad(x, ((W - 1, 0), (0, 0)))
    out = sum(xp[i:i + x.shape[0]] * C.f32(w[i]) for i in range(W))
    return out + C.f32(b)


def ssm_scan(x, dt, A, B, Cm, D):
    """x (L, H, P), dt (L, H), A (H,), B/C (L, G, N): the recurrence above,
    head h reading group h // (H / G)."""
    H, P = x.shape[1], x.shape[2]
    G, N = B.shape[1], B.shape[2]
    Bh = jnp.repeat(B, H // G, axis=1)                 # (L, H, N)
    Ch = jnp.repeat(Cm, H // G, axis=1)

    def step(state, inp):
        xt, dtt, bt, ct = inp
        state = (state * jnp.exp(A * dtt)[:, None, None]
                 + (xt * dtt[:, None])[:, :, None] * bt[:, None, :])
        y = jnp.einsum("hpn,hn->hp", state, ct, precision=C.HIGHEST)
        return state, y

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (x, dt, Bh, Ch))
    return y + x * D[None, :, None]


def mamba(model: dict, p: dict, x, mode: str):
    d_in = model["ssm_expand"] * model["d_model"]
    N, P, G = model["ssm_state"], model["ssm_headdim"], model["ssm_ngroups"]
    H = d_in // P
    zxbc = C.rnd(C.linear(x, p["in_proj"], mode), mode)
    z, xbc = zxbc[:, :d_in], zxbc[:, d_in:]
    dt_raw = C.rnd(C.linear(x, p["dt_proj"], mode), mode)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_w"], p["conv_b"]))
    xs = xbc[:, :d_in].reshape(-1, H, P)
    Bm = xbc[:, d_in:d_in + G * N].reshape(-1, G, N)
    Cm = xbc[:, d_in + G * N:].reshape(-1, G, N)
    dt = jax.nn.softplus(dt_raw + C.f32(p["dt_bias"]))
    A = -jnp.exp(C.f32(p["A_log"]))
    y = C.rnd(ssm_scan(xs, dt, A, Bm, Cm, C.f32(p["D"])).reshape(-1, d_in),
              mode)
    g = (y * jax.nn.silu(z)).reshape(-1, G, d_in // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                          + model["norm_eps"])
    y = g.reshape(-1, d_in) * C.f32(p["norm"])
    return C.linear(y, p["out_proj"], mode)


def attention(model: dict, p: dict, x, pos, mode: str):
    """q, k, v, rotary over the whole head, causal softmax at scale
    query_scale^-1/2 (= (head_dim / 2)^-1/2), the output projection."""
    L = x.shape[0]
    H, K, D = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    q = linear_heads(x, p["wq"], mode, L, H, D)
    k = linear_heads(x, p["wk"], mode, L, K, D)
    v = C.rnd(linear_heads(x, p["wv"], mode, L, K, D), mode)
    q = C.rnd(C.rope(q, pos, D, 1.0, model["rope_theta"]), mode)
    k = C.rnd(C.rope(k, pos, D, 1.0, model["rope_theta"]), mode)
    # causal_attention scales by D^-1/2; the published scale is
    # query_scale^-1/2
    q = q * (D / model["query_scale"]) ** 0.5
    o = C.causal_attention(q, k, v).reshape(L, H * D)
    return C.rnd(C.linear(o, p["wo"], mode), mode)


def linear_heads(x, w, mode, L, heads, D):
    return C.linear(x, w, mode).reshape(L, heads, D)


@functools.lru_cache(maxsize=None)
def _mamba_fn(model_key, mode):
    model = dict(model_key)

    def layer(p, h, t):
        x = rmsnorm(model, C.rnd(h + t, mode), p["ln"]["scale"])
        return C.rnd(h + mamba(model, p["mamba"], x, mode), mode)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _block_fn(model_key, mode):
    model = dict(model_key)

    def block(blk, use, h, e, pos):
        x = rmsnorm(model, jnp.concatenate([h, e], -1), blk["ln1"]["scale"])
        o = attention(model, blk["attn"], x, pos, mode)
        y = rmsnorm(model, o, blk["ln2"]["scale"])
        mp = blk["mlp"]
        gate = C.linear(y, mp["w_gate"], mode)
        up = C.linear(y, mp["w_up"], mode)
        lo = C.linear(C.linear(y, use["adapter_a"], mode), use["adapter_b"],
                      mode)
        f = gate.shape[-1]
        gate, up = gate + lo[:, :f], up + lo[:, f:]
        m = C.linear(jax.nn.gelu(gate, approximate=False) * up,
                     mp["w_down"], mode)
        return C.rnd(C.linear(m, use["out"], mode), mode)

    return jax.jit(block)


def _key(model: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


def hidden(model: dict, params, tokens, mode: str = "f32"):
    """Final-normed hidden states (L, d) of ``tokens`` (L,) int32."""
    key = _key(model)
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    e = C.rnd(C.f32(jnp.take(params["embed"]["table"], tokens, axis=0)),
              mode)
    h = e
    layer, block = _mamba_fn(key, mode), _block_fn(key, mode)
    uses = hybrid_uses(model)
    blocks, per_use = params["shared"]["blocks"], params["shared"]["uses"]
    zero = jnp.zeros_like(h)
    for i in range(model["n_layers"]):
        t = zero
        if i in uses:
            j = uses.index(i)
            t = block(blocks[j % model["num_mem_blocks"]], per_use[j], h, e,
                      pos)
        h = layer(jax.tree.map(lambda a: a[i], params["layers"]), h, t)
    return rmsnorm(model, h, params["final_norm"]["scale"])


# ---------------------------------------------------------------------------
# counts (chipbench/flops.py) and the settings this reference computes
# ---------------------------------------------------------------------------

def hybrid_uses(m: dict) -> list[int]:
    """The layers a shared block runs before: the ids below n_layers."""
    return [i for i in m["hybrid_layer_ids"] if i < m["n_layers"]]


def _mamba_weights(m: dict) -> int:
    """in_proj (z, x, B, C, dt) and out_proj of one Mamba-2 layer."""
    d, din = m["d_model"], m["ssm_expand"] * m["d_model"]
    hs = din // m["ssm_headdim"]
    return d * (2 * din + bc_width(m) + hs) + din * d


def _use_weights(m: dict) -> int:
    """One use of a shared block: q k v o, the gated MLP, the adapters and
    the use's output projection."""
    d, f, r = m["d_model"], m["d_ff"], m["adapter_rank"]
    H, K, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    d2 = 2 * d
    return (d2 * H * hd + 2 * d2 * K * hd + H * hd * d
            + 3 * d * f + d * r + r * 2 * f + d * d)


def matmul_weights_per_token(m: dict) -> int:
    """Weights one token multiplies through once, embeddings and the
    unembedding excluded (logits are counted apart): a shared block that
    runs n times counts n times."""
    return (m["n_layers"] * _mamba_weights(m)
            + attention_layers(m) * _use_weights(m))


def attention_layers(m: dict) -> int:
    return len(hybrid_uses(m))


def ssm_layers(m: dict) -> int:
    return m["n_layers"]


def bc_width(m: dict) -> int:
    """B and C, ``ssm_ngroups`` groups of ``ssm_state`` columns each."""
    return 2 * m["ssm_ngroups"] * m["ssm_state"]


def ssm_scope_weights(m: dict) -> int:
    """The Mamba projections, which the program runs in its ssm scope."""
    return m["n_layers"] * _mamba_weights(m)


# settings of the program's model this reference does not compute, at the
# one value it assumes; and the values it computes of the others it reads
FIXED = {"family": "hybrid", "attn_softcap": None, "final_softcap": None,
         "post_norms": False, "embed_scale_by_sqrt_dim": False,
         "mrope_sections": None, "use_rope": True, "causal": True,
         "local_window": None, "layer_pattern": "global", "n_experts": 0,
         "frontend": None, "norm": "rmsnorm", "mlp": "geglu_exact",
         "rotary_pct": 1.0, "tie_embeddings": True, "shared_attn_period": 0,
         "shared_attn_lora": 0}
POSITIVE = ("ssm_state", "ssm_ngroups", "num_mem_blocks", "adapter_rank",
            "query_scale", "norm_eps")


def check(model: dict) -> None:
    """Raise on a setting of the program's model this reference does not
    compute."""
    bad = {k: model.get(k) for k, v in FIXED.items() if model.get(k, v) != v}
    bad.update({k: model.get(k) for k in POSITIVE
                if not (model.get(k) or 0) > 0})
    ids = list(model.get("hybrid_layer_ids") or [])
    if not ids or ids != sorted(set(ids)) or ids[0] < 0:
        bad["hybrid_layer_ids"] = ids
    heads = model["ssm_expand"] * model["d_model"] // model["ssm_headdim"]
    if model.get("ssm_ngroups") and heads % model["ssm_ngroups"]:
        bad["ssm_ngroups"] = model["ssm_ngroups"]
    if bad:
        raise ValueError(f"{__name__}: the reference does not compute "
                         f"{bad}")
