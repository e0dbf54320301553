"""Per-arch smoke tests: every assigned architecture instantiates a REDUCED
same-family config and runs one forward + one train step on CPU, asserting
output shapes and no NaNs (assignment deliverable f)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import reduced
from repro.configs.registry import ARCH_IDS, get_model_config, get_run_config
from repro.models import lm
from repro.models.layers import Ctx
from repro.models.params import init_params, param_count
from repro.sharding import RULE_SETS
from repro.train.step import init_state, make_train_step

B, S = 2, 32
KEY = jax.random.PRNGKey(0)
K1, K2, K3 = jax.random.split(KEY, 3)


def make_batch(cfg):
    batch = {}
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            K1, (B, S, cfg.frontend_dim), jnp.bfloat16)
    else:
        batch["tokens"] = jax.random.randint(K1, (B, S), 0, cfg.vocab)
    if cfg.family == "vlm":
        batch["vision_embeds"] = jax.random.normal(
            K2, (B, cfg.vision_tokens, cfg.d_model), jnp.bfloat16)
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, None], (3, B, S))
    batch["labels"] = jax.random.randint(K3, (B, S), 0, cfg.vocab)
    return batch


def ctx_for(arch, run=None):
    # warmup_steps=0: lr(step=0) must be nonzero so one step moves params
    run = run or get_run_config(arch, remat="none", logits_chunk=16,
                                warmup_steps=0)
    return run, Ctx(run, RULE_SETS[run.rules_name], None)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_forward_shapes_and_finite(arch):
    cfg = reduced(get_model_config(arch))
    run, ctx = ctx_for(arch)
    params = init_params(lm.model_decls(cfg), KEY)
    h, aux, cache = lm.forward(ctx, cfg, params, make_batch(cfg))
    assert h.shape == (B, S, cfg.d_model)
    assert not bool(jnp.isnan(h.astype(jnp.float32)).any())
    assert cache is None
    assert param_count(lm.model_decls(cfg)) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_train_step(arch):
    cfg = reduced(get_model_config(arch))
    run, ctx = ctx_for(arch)
    state = init_state(cfg, run, KEY)
    st = state.tree()
    step = jax.jit(make_train_step(cfg, run, ctx))
    st2, m = step(st, make_batch(cfg))
    loss = float(m["loss"])
    assert 0.0 < loss < 20.0 and not jnp.isnan(m["loss"])
    assert int(st2["step"]) == 1
    # params actually changed
    moved = jax.tree.map(
        lambda a, b: bool(jnp.any(a != b)), st["params"], st2["params"])
    assert any(jax.tree.leaves(moved))


def test_gemma2_softcap_and_pattern_applied():
    cfg = reduced(get_model_config("gemma2-2b"))
    assert cfg.layer_pattern == "local_global"
    assert cfg.attn_softcap and cfg.final_softcap
    run, ctx = ctx_for("gemma2-2b")
    params = init_params(lm.model_decls(cfg), KEY)
    h, _, _ = lm.forward(ctx, cfg, params, make_batch(cfg))
    logits = lm.logits_for(ctx, cfg, params, h)
    assert float(jnp.max(jnp.abs(logits))) <= cfg.final_softcap + 1e-3


def test_mrope_positions_change_output():
    cfg = reduced(get_model_config("qwen2-vl-72b"))
    run, ctx = ctx_for("qwen2-vl-72b")
    params = init_params(lm.model_decls(cfg), KEY)
    batch = make_batch(cfg)
    h1, _, _ = lm.forward(ctx, cfg, params, batch)
    shifted = dict(batch, positions=batch["positions"] + 7)
    h2, _, _ = lm.forward(ctx, cfg, params, shifted)
    assert float(jnp.max(jnp.abs(h1.astype(jnp.float32)
                                 - h2.astype(jnp.float32)))) > 1e-4


def test_moe_aux_loss_nonzero():
    cfg = reduced(get_model_config("olmoe-1b-7b"))
    run, ctx = ctx_for("olmoe-1b-7b")
    params = init_params(lm.model_decls(cfg), KEY)
    _, aux, _ = lm.forward(ctx, cfg, params, make_batch(cfg))
    assert float(aux) > 0.0


def test_zamba_structure_covers_layers():
    """The repo's block runs after every 6 of the 38 layers; the published
    Zamba2-7B's before the layers its config lists."""
    cfg = get_model_config("zamba2-1.2b")
    assert cfg.n_layers == 38
    assert lm.shared_block_uses(cfg) == [6, 12, 18, 24, 30, 36]
    big = get_model_config("zamba2-7b")
    assert lm.shared_block_uses(big) == big.hybrid_layer_ids
    assert lm.shared_block_uses(dataclasses.replace(big, n_layers=41)) == [
        6, 11, 17, 23, 29, 35]


def test_scan_vs_unrolled_equivalence():
    """run.scan_layers=False (used by dry-run cost variants) must be
    numerically identical to the scanned path."""
    cfg = reduced(get_model_config("llama3.2-3b"))
    run_s, ctx_s = ctx_for("llama3.2-3b")
    run_u = get_run_config("llama3.2-3b", remat="none", logits_chunk=16,
                           scan_layers=False)
    ctx_u = Ctx(run_u, RULE_SETS[run_u.rules_name], None)
    params = init_params(lm.model_decls(cfg), KEY)
    batch = make_batch(cfg)
    h_s, _, _ = lm.forward(ctx_s, cfg, params, batch)
    h_u, _, _ = lm.forward(ctx_u, cfg, params, batch)
    # bf16 reassociation between the scanned and unrolled layer loops
    assert float(jnp.max(jnp.abs(h_s.astype(jnp.float32)
                                 - h_u.astype(jnp.float32)))) < 6e-2


def test_causal_masking_is_causal():
    """Future tokens cannot influence past positions."""
    cfg = reduced(get_model_config("llama3.2-3b"))
    run, ctx = ctx_for("llama3.2-3b")
    params = init_params(lm.model_decls(cfg), KEY)
    batch = make_batch(cfg)
    h1, _, _ = lm.forward(ctx, cfg, params, batch)
    toks2 = batch["tokens"].at[:, -1].set(
        (batch["tokens"][:, -1] + 1) % cfg.vocab)
    h2, _, _ = lm.forward(ctx, cfg, params, dict(batch, tokens=toks2))
    diff = jnp.abs(h1.astype(jnp.float32) - h2.astype(jnp.float32))
    assert float(diff[:, :-1].max()) < 1e-5     # prefix unchanged
    assert float(diff[:, -1].max()) > 1e-4      # last position changed


def test_init_params_honours_param_dtype():
    """Serving draws weights directly in the compute dtype; the default
    keeps the declared f32 masters.  Both draw from the same per-leaf
    distribution."""
    decls = lm.model_decls(reduced(get_model_config("minitron-4b")))
    f32 = init_params(decls, jax.random.PRNGKey(0))
    bf16 = init_params(decls, jax.random.PRNGKey(0),
                       get_run_config("minitron-4b",
                                      param_dtype="bfloat16").param_dtype)
    assert {a.dtype for a in jax.tree.leaves(f32)} == {jnp.dtype("float32")}
    assert {a.dtype for a in jax.tree.leaves(bf16)} == {jnp.dtype("bfloat16")}
    for a, b in zip(jax.tree.leaves(f32), jax.tree.leaves(bf16)):
        assert a.shape == b.shape
        b = b.astype(jnp.float32)
        if a.size >= 4096:
            assert abs(float(a.mean() - b.mean())) <= 0.1 * float(a.std())
            assert abs(float(a.std() - b.std())) <= 0.1 * float(a.std())
