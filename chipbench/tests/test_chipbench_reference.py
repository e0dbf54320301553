"""The plain references against the program's own forward, on the CPU at a
reduced size, both in float32: they must agree to float32 rounding.

Tolerance 2e-4 on the logits (absolute, logits of order 1): both sides
compute the same equations in float32, the program with the jnp reference
ops; what differs is the order of sums and the SSD scan's chunking, a few
float32 ulps per layer.  A wrong norm, rotary half, head mapping, conv tap
or shared-block placement moves logits by their own size."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference as R
from chipbench import weights

TOL = 2e-4


def _program_logits(cfg, params, tokens):
    from repro.configs.base import RunConfig
    from repro.models import lm
    from repro.models.layers import Ctx
    from repro.sharding import RULE_SETS

    run = RunConfig(compute_dtype="float32", kernel_mode="reference",
                    remat="none")
    ctx = Ctx(run, RULE_SETS["default"], None)
    h, _, _ = lm.forward(ctx, cfg, params, {"tokens": tokens[None]})
    return lm.logits_for(ctx, cfg, params, h)[0]


@pytest.mark.parametrize("arch", ["minitron-4b", "zamba2-1.2b"])
def test_reference_matches_program_forward(arch):
    from repro.configs.base import reduced
    from repro.configs.registry import get_model_config
    from repro.models import lm

    cfg = reduced(get_model_config(arch))
    model = dataclasses.asdict(cfg)
    params = weights.draw(lm.model_decls(cfg), 7, jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, 40), jnp.int32)
    want = _program_logits(cfg, params, tokens)
    h = R.hidden(model, params, tokens)
    got = h @ R.unembedding(params)
    assert got.shape == want.shape
    err = float(jnp.max(jnp.abs(got - want)))
    assert err < TOL, err
    # the logits are not degenerate: the comparison can see a wrong token
    assert float(jnp.std(want)) > 0.1


def test_served_gaps_zero_for_reference_argmax():
    """Serving the reference's own greedy tokens reads a gap of 0; serving
    the worst token reads a large one."""
    from repro.configs.base import reduced
    from repro.configs.registry import get_model_config
    from repro.models import lm

    cfg = reduced(get_model_config("minitron-4b"))
    model = dataclasses.asdict(cfg)
    params = weights.draw(lm.model_decls(cfg), 3, jnp.float32)
    prompt = list(np.random.default_rng(1).integers(0, cfg.vocab, 17))
    served = []
    for _ in range(6):
        h = R.hidden(model, params, jnp.asarray(prompt + served, jnp.int32))
        served.append(int(jnp.argmax(h[-1] @ R.unembedding(params))))
    gaps = R.served_gaps(model, params, prompt, served)
    assert gaps.shape == (6,)
    assert float(np.max(gaps)) < 1e-5
    h = R.hidden(model, params, jnp.asarray(prompt + served, jnp.int32))
    worst = [int(jnp.argmin(h[len(prompt) - 1 + i] @ R.unembedding(params)))
             for i in range(6)]
    assert float(np.min(R.served_gaps(model, params, prompt, worst))) > 1.0
