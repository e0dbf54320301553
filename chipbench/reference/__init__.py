"""Plain float32 references and the comparison that decides ``correct``.

The references import nothing of the program.  They get the weights the
benchmark drew (``chipbench/weights.py``) and the tokens the timed path
served, and run each served request's prompt and answer through the
model once (teacher forcing).  At every position where the program
served a token, the comparison reads by how far that token's reference
logit lies below the reference's best logit there; a run's number is the
widest such gap over the sample.  Greedy decoding in bf16 serves the
reference's best token or, on a near tie, one within rounding of it; a
wrong cache row, mask or state serves tokens far below it.

``control_gaps`` is the same reading for the control: the reference put
in the program's place at the next precision down (fp8 where the program
holds bf16, ``common.rnd``): at each position the token the fp8 forward
ranks first, read against the float32 reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common as C

BUCKET = 1024     # sequences are padded to a multiple of this (causal: the
                  # padding after a position never reaches it)
LOGIT_BLOCK = 256


def hidden(model: dict, params, tokens, mode: str = "f32"):
    if model["family"] == "dense":
        from chipbench.reference.dense import hidden as h
    elif model["family"] == "hybrid":
        from chipbench.reference.hybrid import hidden as h
    else:
        raise ValueError(f"no reference for family {model['family']!r}")
    with jax.default_matmul_precision("highest"):
        return h(model, params, tokens, mode)


def unembedding(params):
    if "unembed" in params:
        return params["unembed"]["w"]
    return params["embed"]["table"].T


VOCAB_BLOCK = 32000   # unembedding columns per product: bounds the float32
                      # copy of the weights to one block


@functools.lru_cache(maxsize=None)
def _stats_fn(mode: str):
    def stats(h, w, col0, ids_a, ids_b):
        logits = C.linear(h, w, mode)
        cols = col0 + jnp.arange(w.shape[1])

        def pick(ids):
            hit = cols[None, :] == ids[:, None]
            return jnp.sum(jnp.where(hit, logits, 0.0), -1), hit.any(-1)

        best = logits.max(-1)
        top = (col0 + jnp.argmax(logits, -1)).astype(jnp.int32)
        return (best, top) + pick(ids_a) + pick(ids_b)

    return jax.jit(stats)


def logit_stats(model: dict, params, h, ids_a, ids_b=None, mode="f32"):
    """For each row of h (n, d): (best logit, its token, the logit of
    ids_a[i], the logit of ids_b[i]) as numpy arrays."""
    n, vocab = h.shape[0], model["vocab"]
    ids_b = ids_a if ids_b is None else ids_b
    fn = _stats_fn(mode)
    w = unembedding(params)
    out = [[], [], [], []]
    with jax.default_matmul_precision("highest"):
        for s in range(0, n, LOGIT_BLOCK):
            e = min(s + LOGIT_BLOCK, n)
            pad = LOGIT_BLOCK - (e - s)
            hb = jnp.pad(h[s:e], ((0, pad), (0, 0)))
            ia = jnp.pad(jnp.asarray(ids_a[s:e], jnp.int32), (0, pad))
            ib = jnp.pad(jnp.asarray(ids_b[s:e], jnp.int32), (0, pad))
            best = np.full(LOGIT_BLOCK, -np.inf, np.float32)
            top = np.zeros(LOGIT_BLOCK, np.int32)
            got_a = np.zeros(LOGIT_BLOCK, np.float32)
            got_b = np.zeros(LOGIT_BLOCK, np.float32)
            for c0 in range(0, vocab, VOCAB_BLOCK):
                # columns past the vocabulary (padding) are never read
                wb = w[:, c0:min(c0 + VOCAB_BLOCK, vocab)]
                b, t, la, ha, lb, hb_ = (np.asarray(x) for x in fn(
                    hb, wb, c0, ia, ib))
                better = b > best
                best = np.where(better, b, best)
                top = np.where(better, t, top)
                got_a = np.where(ha, la, got_a)
                got_b = np.where(hb_, lb, got_b)
            for acc, part in zip(out, (best, top, got_a, got_b)):
                acc.append(part[:e - s])
    return tuple(np.concatenate(a) for a in out)


def _positions(model, params, prompt, served, mode):
    """Hidden states at the positions that predict the served tokens."""
    tokens = list(prompt) + list(served)
    n = len(tokens)
    padded = np.zeros(-(-n // BUCKET) * BUCKET, np.int32)
    padded[:n] = tokens
    h = hidden(model, params, jnp.asarray(padded), mode)
    return h[len(prompt) - 1:n - 1]


def served_gaps(model: dict, params, prompt, served) -> np.ndarray:
    """Reference best logit minus the served token's, per served token."""
    h = _positions(model, params, prompt, served, "f32")
    best, _, got, _ = logit_stats(model, params, h, np.asarray(served))
    return best - got


def control_gaps(model: dict, params, prompt, served) -> np.ndarray:
    """The same gap for the fp8 control: the token the fp8 forward puts
    first at each position, against the float32 reference."""
    h8 = _positions(model, params, prompt, served, "fp8")
    _, top8, _, _ = logit_stats(model, params, h8, np.asarray(served),
                                mode="fp8")
    h = _positions(model, params, prompt, served, "f32")
    best, _, got, _ = logit_stats(model, params, h, top8)
    return best - got
