"""The serving engine names its work on the profiler's clock: ``serve.*``
host spans at its boundaries (``engine.SPANS``) and ``jax.named_scope``
model scopes in its programs -- without changing a single token."""

import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import reduced
from repro.configs.registry import get_model_config, get_run_config
from repro.models import lm
from repro.models.layers import Ctx
from repro.models.params import init_params
from repro.serving import engine as E
from repro.serving.engine import Request, ServeEngine
from repro.sharding import RULE_SETS

MODEL_SCOPES = ("embed", "norm", "attn_proj", "attn_core", "mlp", "ssm",
                "logits", "sample")


def _engine(arch="llama3.2-3b"):
    cfg = reduced(get_model_config(arch))
    run = get_run_config(arch, remat="none")
    ctx = Ctx(run, RULE_SETS[run.serve_rules_name], None)
    params = init_params(lm.model_decls(cfg), jax.random.PRNGKey(0))
    return ServeEngine(cfg, run, ctx, params, batch_size=3, max_seq=64,
                       prefill_chunk=8, decode_chunk=4, paged=True,
                       block_size=8)


def _reqs():
    return [Request(uid=10 + i, prompt=[(5 * i + 3 * j) % 90 + 2
                                        for j in range(5 + 4 * i)],
                    max_new_tokens=3 + 2 * i) for i in range(4)]


def _serve(eng):
    eng.start(_reqs())
    while eng.pending:
        eng.step()
    return {r.uid: list(r.generated) for r in eng.finished}


def _scoped(text, scope):
    """Whether a lowering's debug locations name ``scope`` as a component
    of some op's name stack."""
    return re.search(rf'loc\("(?:[^"]*/)?{scope}/', text) is not None


def _spans(logdir):
    """(name, start, end, stats) of every serve.* host event."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    out.append((ev.name, ev.start_ns, ev.end_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: s[1])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Tokens served with no profiler, then under one, and the spans."""
    logdir = tmp_path_factory.mktemp("profile")
    plain = _serve(_engine())
    eng = _engine()
    with jax.profiler.trace(str(logdir)):
        served = _serve(eng)
    return plain, served, _spans(logdir)


def test_tokens_identical_with_the_profiler_on(traced):
    plain, served, _ = traced
    assert served == plain
    assert sorted(served) == [r.uid for r in _reqs()]


def test_step_spans_hold_decode_fetch_deliver_in_order(traced):
    _, _, spans = traced
    assert {name for name, *_ in spans} <= set(E.SPANS)
    steps = [s for s in spans if s[0] == "serve.step"]
    assert steps
    assert [s[3]["step_num"] for s in steps] == list(
        range(1, len(steps) + 1))
    for _, lo, hi, _ in steps:
        inside = [s for s in spans if s[0] != "serve.step"
                  and lo <= s[1] and s[2] <= hi]
        names = [s[0] for s in inside
                 if s[0] in ("serve.decode", "serve.fetch", "serve.deliver")]
        assert names == ["serve.decode", "serve.fetch", "serve.deliver"]
        ends = {s[0]: s for s in inside}
        assert ends["serve.decode"][2] <= ends["serve.fetch"][1]
        assert ends["serve.fetch"][2] <= ends["serve.deliver"][1]


def test_admit_spans_carry_uids_and_hold_the_prefills(traced):
    _, _, spans = traced
    admits = [s for s in spans if s[0] == "serve.admit"]
    assert sorted(s[3]["uid"] for s in admits) == [r.uid for r in _reqs()]
    prefills = [s for s in spans if s[0] == "serve.prefill"]
    assert prefills
    for _, lo, hi, _ in prefills:
        assert any(a[1] <= lo and hi <= a[2] for a in admits)


@pytest.mark.parametrize("arch", ["llama3.2-3b", "zamba2-1.2b"])
def test_decode_program_carries_every_model_scope(arch):
    eng = _engine(arch)
    eng.start(_reqs()[:1])
    cache = eng._cache
    lanes = (eng._cur, eng._index, eng._rem, eng._done)
    text = eng._decode_fn.lower(eng.params, cache, *lanes).as_text(
        debug_info=True)
    want = [s for s in MODEL_SCOPES
            if s != "ssm" or eng.cfg.family == "hybrid"]
    missing = [s for s in want if not _scoped(text, s)]
    assert not missing, missing


def test_prefill_program_scopes_the_kernel_path():
    """A prefill chunk runs attention in attn_core and its projections in
    attn_proj, like the decode step."""
    eng = _engine()
    eng.start(_reqs()[:1])
    toks = jnp.zeros((1, 8), jnp.int32)
    text = eng._prefill_step.lower(eng.params, eng._cache, toks, 0,
                                   0).as_text(debug_info=True)
    for s in ("embed", "norm", "attn_proj", "attn_core", "mlp", "logits"):
        assert _scoped(text, s), s
    assert not _scoped(text, "sample")
