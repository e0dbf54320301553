"""A kind of model is a module found by name: a configuration that names a
new kind (a copy of ``dense.py`` under another name, in a directory of its
own) reaches that module for the reference, the counts and the spec check,
with no shared file edited; a kind with no module is an error that names
the file it looked for."""

import dataclasses
import json
import os
import shutil

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, harness, weights
from chipbench import reference as R
from chipbench.tests.test_chipbench_spec import check_config

NAME = "plain_decoder"
CONF = os.path.join(harness.chipbench.ROOT, "chipbench", "configs",
                    "minitron4b.json")


@pytest.fixture
def copy_kind(tmp_path, monkeypatch):
    """``dense.py`` copied as ``plain_decoder.py`` into a directory of its
    own, the lookup pointed there; returns the loaded copy, each of whose
    exports records its calls in ``calls``."""
    shutil.copy(os.path.join(os.path.dirname(R.__file__), "dense.py"),
                tmp_path / f"{NAME}.py")
    monkeypatch.setattr(R, "KINDS", str(tmp_path))
    mod = R.kind({"family": "dense", R.KEY: NAME})
    assert mod.__file__ == str(tmp_path / f"{NAME}.py")
    calls = []
    for f in R.EXPORTS:
        def counted(*a, _f=getattr(mod, f), _name=f, **k):
            calls.append(_name)
            return _f(*a, **k)
        monkeypatch.setattr(mod, f, counted)
    return mod, calls


def _conf():
    conf = json.load(open(CONF))
    conf[R.KEY] = NAME
    return conf


def test_counts_reach_the_named_kind(copy_kind, monkeypatch):
    mod, calls = copy_kind
    view = R.model_view(_conf())
    assert view[R.KEY] == NAME and R.KEY not in _conf()["model"]
    # minitron-4b's hand counts (test_chipbench_metrics.py)
    w = 32 * 81_788_928
    assert flops.matmul_weights_per_token(view) == w
    assert flops.decode_flops(view, 1000) == (
        2 * w + 4 * 1000 * 24 * 128 * 32 + 2 * 3072 * 256000)
    assert flops.ssd_flops(view, 256) == 0.0
    assert {"matmul_weights_per_token", "attention_layers",
            "ssm_layers"} <= set(calls)
    # what the copy says is what the counts use
    monkeypatch.setattr(mod, "attention_layers", lambda m: 7)
    assert flops.attention_flops(view, 1) == 4 * 24 * 128 * 7
    assert flops.decode_attention_bytes(view, 10) == \
        7 * 2 * (2 * 10 * 8 * 128 + 2 * 24 * 128)


def test_reference_reaches_the_named_kind(copy_kind):
    from repro.configs.base import reduced
    from repro.configs.registry import get_model_config
    from repro.models import lm

    _, calls = copy_kind
    cfg = reduced(get_model_config("minitron-4b"))
    model = dataclasses.asdict(cfg)
    params = weights.draw(lm.model_decls(cfg), 4, jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(2).integers(
        0, cfg.vocab, 24), jnp.int32)
    got = R.hidden(dict(model, **{R.KEY: NAME}), params, tokens)
    assert calls == ["hidden"]
    # the copy computes what dense.py does: the lookup found it by name
    want = R._load(os.path.dirname(R.__file__), "dense").hidden(
        model, params, tokens)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_spec_check_reaches_the_named_kind(copy_kind, monkeypatch):
    mod, calls = copy_kind
    conf = _conf()
    entry = {"name": "minitron4b", "source": conf["source"], "reduced": []}
    check_config(entry, conf)
    assert calls == ["check"]

    def refuse(model):
        raise ValueError("refused")
    monkeypatch.setattr(mod, "check", refuse)
    with pytest.raises(ValueError, match="refused"):
        check_config(entry, conf)


@pytest.mark.parametrize("model", [{"family": "dense", R.KEY: "nonesuch"},
                                   {"family": "moe"}])
def test_unknown_kind_names_its_file(model):
    name = model.get(R.KEY, model["family"])
    with pytest.raises(KeyError, match=f"{name}.py"):
        R.kind(model)
    with pytest.raises(KeyError, match=f"{name}.py"):
        flops.matmul_weights_per_token(dict(model, n_layers=1))


def test_kind_without_its_exports_is_refused(tmp_path, monkeypatch):
    (tmp_path / "half.py").write_text("def hidden(*a):\n    pass\n")
    monkeypatch.setattr(R, "KINDS", str(tmp_path))
    with pytest.raises(AttributeError, match="half.py"):
        R.kind({"family": "half"})
