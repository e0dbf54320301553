"""Reference forward of a dense pre-norm decoder (minitron-4b).

Per layer: h += attn(norm1(h)); h += mlp(norm2(h)), with grouped-query
attention, partial rotary embeddings and the configuration's norm
(layernorm1p: (1 + scale)) and MLP (squared ReLU).  Follows the
published Minitron/Nemotron block; computed layer by layer so that only
one layer's weights are float32 at a time.
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
