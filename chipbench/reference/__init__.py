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

Each kind of model is a module of its own, ``<kind>.py`` in this
directory (``KINDS``), found by name as the metric readers are: the
configuration file's top-level ``reference`` key names it, and without
that key the kind is the model's ``family``.  The name stays out of the
file's ``model`` section, which the program gets whole
(``harness.model_config``); the benchmark's view of a model
(``model_view``) is that section with ``reference`` added.  A kind module
exports
  * ``hidden(model, params, tokens, mode)``: the final-normed hidden
    states of one sequence;
  * the counts that depend on the model's structure
    (``chipbench/flops.py`` reads them): ``matmul_weights_per_token``,
    ``attention_layers``, ``ssm_layers``, ``bc_width`` (the B and C
    columns of one Mamba-2 layer's input) and ``ssm_scope_weights`` (the
    matmul weights the program runs under its ``ssm`` scope);
  * ``check(model)``: raises on any setting of the program's model (its
    ``ModelConfig`` as a dict) that the module does not compute.
"""

from __future__ import annotations

import functools
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common as C

BUCKET = 1024     # sequences are padded to a multiple of this (causal: the
                  # padding after a position never reaches it)
LOGIT_BLOCK = 256
KINDS = os.path.dirname(os.path.abspath(__file__))
KEY = "reference"
EXPORTS = ("hidden", "matmul_weights_per_token", "attention_layers",
           "ssm_layers", "bc_width", "ssm_scope_weights", "check")
_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]{0,63}$")


def kind_name(conf: dict) -> str:
    """The kind module a configuration file names, else its family."""
    return conf.get(KEY, conf["model"]["family"])


def model_view(conf: dict) -> dict:
    """The configuration's ``model`` section with its kind under ``KEY``."""
    return dict(conf["model"], **{KEY: kind_name(conf)})


@functools.lru_cache(maxsize=None)
def _load(kinds: str, name: str):
    """``<kinds>/<name>.py``, loaded once."""
    path = os.path.join(kinds, f"{name}.py")
    if not _NAME.match(name) or not os.path.exists(path):
        raise KeyError(f"no reference kind {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.reference.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in EXPORTS if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"reference kind {path} lacks {missing}")
    return mod


def kind(model: dict):
    """The kind module of ``model`` (``model_view``, or a bare ``model``
    section, whose kind is its family), found in ``KINDS``."""
    return _load(KINDS, model.get(KEY, model["family"]))


def hidden(model: dict, params, tokens, mode: str = "f32"):
    with jax.default_matmul_precision("highest"):
        return kind(model).hidden(model, params, tokens, mode)


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
