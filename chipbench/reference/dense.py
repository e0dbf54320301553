"""Reference forward of a dense pre-norm decoder (minitron-4b).

Per layer: h += attn(norm1(h)); h += mlp(norm2(h)), with grouped-query
attention, partial rotary embeddings and the configuration's norm
(layernorm1p: (1 + scale)) and MLP (squared ReLU).  Follows the
published Minitron/Nemotron block; computed layer by layer so that only
one layer's weights are float32 at a time.  Also this kind's counts
(``chipbench/flops.py``) and ``check``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference import common as C


@functools.lru_cache(maxsize=None)
def _layer_fn(model_key, mode):
    model = dict(model_key)

    def layer(p, h, pos):
        a = C.norm(model, p["ln1"], h)
        h = C.rnd(h + C.attention(model, p["attn"], a, pos, mode), mode)
        m = C.norm(model, p["ln2"], h)
        return C.rnd(h + C.mlp(model, p["mlp"], m, mode), mode)

    return jax.jit(layer)


def hidden(model: dict, params, tokens, mode: str = "f32"):
    """Final-normed hidden states (L, d) of ``tokens`` (L,) int32."""
    key = tuple(sorted(model.items()))
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    h = C.rnd(C.f32(jnp.take(params["embed"]["table"], tokens, axis=0)),
              mode)
    layer = _layer_fn(key, mode)
    stack = params["layers"]
    for i in range(model["n_layers"]):
        p = jax.tree.map(lambda a: a[i], stack)
        h = layer(p, h, pos)
    return C.norm(model, params["final_norm"], h)


# ---------------------------------------------------------------------------
# counts (chipbench/flops.py) and the settings this reference computes
# ---------------------------------------------------------------------------

def matmul_weights_per_token(m: dict) -> int:
    """Weights one token multiplies through once, embeddings and the
    unembedding excluded (logits are counted apart)."""
    d, f = m["d_model"], m["d_ff"]
    H, K, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    attn = d * H * hd + 2 * d * K * hd + H * hd * d
    mlp = (3 if m["mlp"] in ("swiglu", "geglu") else 2) * d * f
    return m["n_layers"] * (attn + mlp)


def attention_layers(m: dict) -> int:
    return m["n_layers"]


def ssm_layers(m: dict) -> int:
    return 0


def bc_width(m: dict) -> int:
    return 0


def ssm_scope_weights(m: dict) -> int:
    return 0


# settings of the program's model this reference does not compute, at the
# one value it assumes; and the values it computes of the others it reads
FIXED = {"family": "dense", "attn_softcap": None, "final_softcap": None,
         "query_scale": None, "post_norms": False,
         "embed_scale_by_sqrt_dim": False, "mrope_sections": None,
         "use_rope": True, "causal": True, "local_window": None,
         "layer_pattern": "global", "n_experts": 0, "ssm_state": 0,
         "shared_attn_period": 0, "frontend": None}
CHOICES = {"norm": ("rmsnorm", "layernorm", "layernorm1p"),
           "mlp": ("swiglu", "squared_relu")}


def check(model: dict) -> None:
    """Raise on a setting of the program's model this reference does not
    compute."""
    bad = {k: model[k] for k, v in FIXED.items() if model.get(k, v) != v}
    bad.update({k: model[k] for k, ok in CHOICES.items()
                if model[k] not in ok})
    if bad:
        raise ValueError(f"{__name__}: the reference does not compute "
                         f"{bad}")
