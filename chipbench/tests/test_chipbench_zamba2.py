"""The published Zamba2 block (``chipbench/reference/zamba2.py``) against
the program, on the CPU at ``reduced()`` size: 2 B/C groups, 2 shared
blocks each used twice at irregular layer ids, on seeded random weights.

Tolerance 2e-4 on the logits (absolute, logits of order 1), as in
``test_chipbench_reference.py``: both sides compute the same equations in
float32; what differs is the order of sums, the SSD scan's chunking and,
through the engine, the chunked prefill and the one-step decode recurrence
in place of the reference's scan -- a few float32 ulps per layer.  Each
planted fault moves the logits by their own size."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, harness, weights
from chipbench import reference as R

TOL = 2e-4
CONF = os.path.join(harness.chipbench.ROOT, "chipbench", "configs",
                    "zamba2_7b.json")


def _model(cfg) -> dict:
    return dict(dataclasses.asdict(cfg), **{R.KEY: "zamba2"})


def _small():
    from repro.configs.base import reduced
    from repro.configs.registry import get_model_config
    from repro.models import lm

    cfg = reduced(get_model_config("zamba2-7b"))
    params = weights.draw(lm.model_decls(cfg), 11, jnp.float32)
    return cfg, params


def _ctx(mode: str = "reference"):
    from repro.configs.base import RunConfig
    from repro.models.layers import Ctx
    from repro.sharding import RULE_SETS

    run = RunConfig(compute_dtype="float32", param_dtype="float32",
                    kernel_mode=mode, remat="none")
    return run, Ctx(run, RULE_SETS["default"], None)


def _program_logits(cfg, params, tokens):
    from repro.models import lm
    _, ctx = _ctx()
    h, _, _ = lm.forward(ctx, cfg, params, {"tokens": tokens[None]})
    return lm.logits_for(ctx, cfg, params, h)[0]


def _reference_logits(cfg, params, tokens):
    return R.hidden(_model(cfg), params, tokens) @ R.unembedding(params)


def _tokens(cfg, n=40, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab, n), jnp.int32)


def test_reduced_keeps_the_published_structure():
    from repro.configs.base import reduced
    from repro.configs.registry import get_model_config
    from repro.models import lm

    big = get_model_config("zamba2-7b")
    cfg = reduced(big)
    uses = lm.shared_block_uses(cfg)
    gaps = {b - a for a, b in zip(uses, uses[1:])}
    assert cfg.ssm_ngroups == big.ssm_ngroups == 2
    assert cfg.num_mem_blocks == big.num_mem_blocks == 2
    assert len(uses) >= 2 * cfg.num_mem_blocks and len(gaps) > 1
    assert 0 < uses[0] and uses[-1] < cfg.n_layers - 1
    assert cfg.ssm_heads % cfg.ssm_ngroups == 0
    assert cfg.adapter_rank > 0 and cfg.tie_embeddings
    assert (cfg.mlp, cfg.query_scale) == (big.mlp, big.query_scale)
    R.kind(_model(cfg)).check(dataclasses.asdict(cfg))


def test_counts_at_published_widths():
    """The five counts of the cut configuration, by hand: a Mamba-2 layer
    3584 x (2 x 7168 + 2 x 2 x 64 + 112) + 7168 x 3584; a use of a shared
    block q k v 3 x 7168^2, o 7168 x 3584, the gated MLP 3 x 3584 x 14336,
    the adapters 3584 x 128 + 128 x 28672 and the projection 3584^2."""
    view = R.model_view(json.load(open(CONF)))
    mamba = 3584 * (2 * 7168 + 2 * 2 * 64 + 112) + 7168 * 3584
    use = (3 * 7168 ** 2 + 7168 * 3584 + 3 * 3584 * 14336
           + 3584 * 128 + 128 * 28672 + 3584 ** 2)
    assert (mamba, use) == (78_389_248, 350_945_280)
    assert flops.matmul_weights_per_token(view) == 41 * mamba + 6 * use \
        == 5_319_630_848
    assert flops.attention_layers(view) == 6
    assert flops.ssm_layers(view) == 41
    assert R.kind(view).bc_width(view) == 256
    assert flops.ssm_scope_weights(view) == 41 * mamba == 3_213_959_168


def test_reference_matches_program_forward():
    cfg, params = _small()
    tokens = _tokens(cfg)
    want = _program_logits(cfg, params, tokens)
    got = _reference_logits(cfg, params, tokens)
    assert got.shape == want.shape
    err = float(jnp.max(jnp.abs(got - want)))
    assert err < TOL, err
    assert float(jnp.std(want)) > 0.1


def _served_logits(cfg, params, prompts, new, mode, monkeypatch):
    """Greedy serving through ``ServeEngine(paged=True)``: every logits row
    the engine computes is recorded, and for each request the rows that
    predicted its served tokens are returned with the tokens."""
    from repro.models import lm
    from repro.serving.engine import Request, ServeEngine

    rows = []
    logits_for = lm.logits_for

    def recorded(*a):
        out = logits_for(*a)
        jax.debug.callback(lambda x: rows.append(np.asarray(x)), out)
        return out
    monkeypatch.setattr(lm, "logits_for", recorded)

    run, ctx = _ctx(mode)
    eng = ServeEngine(cfg, run, ctx, params, batch_size=len(prompts),
                      max_seq=64, prefill_chunk=8, decode_chunk=4,
                      paged=True, block_size=8)
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompts, new))]
    eng.generate(reqs)
    # admission prefills each prompt in turn (the last chunk's row predicts
    # the first token); then each decode iteration's row b predicts the
    # next token of slot b's request
    pre = [r for r in rows if r.shape[0] == 1]
    dec = [r for r in rows if r.shape[0] == len(prompts)]
    out, at = [], 0
    for b, p in enumerate(prompts):
        at += len(flops.chunk_plan(len(p), 8))
        first = pre[at - 1][0, 0]
        later = [d[b, 0] for d in dec[:new[b] - 1]]
        out.append((np.stack([first] + later), reqs[b].generated))
    return out


@pytest.mark.parametrize("mode", ["reference", "pallas_interpret"])
def test_paged_serving_matches_reference(mode, monkeypatch):
    """Prefill through the paged engine (chunks of 8, 4, 2 and 1 rows: the
    SSD scan and the one-row recurrence) and then paged decode: at every
    served position the program's logits are the reference's."""
    cfg, params = _small()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in (15, 22)]
    new = [7, 5]
    for prompt, (got, served) in zip(
            prompts, _served_logits(cfg, params, prompts, new, mode,
                                    monkeypatch)):
        tokens = jnp.asarray(prompt + served[:-1], jnp.int32)
        want = _reference_logits(cfg, params, tokens)[len(prompt) - 1:]
        assert got.shape == want.shape
        err = float(np.max(np.abs(got - np.asarray(want))))
        assert err < TOL, err
        assert served == np.argmax(got, -1).tolist()


def _groups_swapped(monkeypatch):
    """Heads read the other B/C group."""
    from repro.kernels import ops
    ssd = ops.ssd
    monkeypatch.setattr(ops, "ssd", lambda x, dt, A, B, C, *a, **k: ssd(
        x, dt, A, B[:, :, ::-1], C[:, :, ::-1], *a, **k))


def _adapter_dropped(monkeypatch):
    """The second use's MLP adapters are left out."""
    from repro.models import lm
    block = lm._published_block

    def broken(ctx, cfg, p, j, *a):
        if j == 1:
            uses = [dict(u) for u in p["uses"]]
            uses[1]["adapter_b"] = jnp.zeros_like(uses[1]["adapter_b"])
            p = dict(p, uses=uses)
        return block(ctx, cfg, p, j, *a)
    monkeypatch.setattr(lm, "_published_block", broken)


def _output_to_residual(monkeypatch):
    """The block's output added to the residual, as the repo's block does,
    in place of the Mamba layer's input."""
    from repro.models import lm
    block = lm._published_block

    def broken(*a):
        h, t, kv = block(*a)
        return h + t, None, kv
    monkeypatch.setattr(lm, "_published_block", broken)


FAULTS = {"groups_swapped": _groups_swapped,
          "adapter_dropped": _adapter_dropped,
          "output_to_residual": _output_to_residual}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_fails_the_comparison(fault, monkeypatch):
    cfg, params = _small()
    tokens = _tokens(cfg)
    want = _reference_logits(cfg, params, tokens)
    FAULTS[fault](monkeypatch)
    got = _program_logits(cfg, params, tokens)
    err = float(jnp.max(jnp.abs(got - want)))
    assert err > 100 * TOL, err


def test_check_refuses_the_repo_block():
    """The kind computes the published block only: the repo's zamba2 and a
    dense model are refused."""
    from repro.configs.registry import get_model_config
    mod = R.kind({"family": "hybrid", R.KEY: "zamba2"})
    for arch in ("zamba2-1.2b", "minitron-4b"):
        with pytest.raises(ValueError):
            mod.check(dataclasses.asdict(get_model_config(arch)))
    mod.check(dataclasses.asdict(get_model_config("zamba2-7b")))


def test_ssm_decode_roofline_reads_the_ssm_scope():
    """Least time of the ssm ops: iterations x (the Mamba weights once, and
    every slot's state read and written in every layer, 2 bytes an
    element) / HBM bandwidth, over those ops' device time; ops under other
    scopes or in the prefill program do not count."""
    from chipbench import metrics, scopes, traffic
    from chipbench import trace as T

    view = R.model_view(json.load(open(CONF)))
    ssm = "jit(decode_chunk)/while/body/ssm/dot_general"
    ops = [T.Op("fusion", "jit_decode_chunk", 0, 4_000_000, 0),
           T.Op("fusion", "jit_decode_chunk", 0, 6_000_000, 0),
           T.Op("fusion", "jit_decode_chunk", 0, 9_000_000, 0),
           T.Op("fusion", "jit_prefill_chunk", 0, 7_000_000, 0)]
    paths = [ssm, ssm, "jit(decode_chunk)/while/body/mlp/dot_general", ssm]
    rec = harness.Record(name="t", conf={}, model=view,
                         serve={"batch": 24, "prefill_chunk": 256},
                         peaks={"bf16_flops": 197e12,
                                "hbm_bytes_per_s": 819e9},
                         seed=0, seconds=1.0)
    rec.reqs[0] = harness.Req(spec=traffic.Spec(uid=0, prompt=[0] * 300,
                                                max_new_tokens=9),
                              request=None, due=0.0)
    assert metrics.read("ssm_decode_roofline", rec) is None
    rec.steps.append(harness.Step(t0=0.0, t1=1.0, phase="window",
                                  tokens={0: (0, 2)}, traced=True))
    rec.trace = scopes.Scoped(window=(0, 10 ** 9), ops=ops, modules=[],
                              host=[], n_devices=1, scopes=paths, serve=[])
    state = 112 * 64 * 64 + 3 * (7168 + 256)
    need = 2 * (2 * 3_213_959_168 + 24 * 41 * 2 * 2 * state) / 819e9
    got = metrics.read("ssm_decode_roofline", rec)
    assert got == pytest.approx(100 * need / 10e-3)
