"""Sharding rule resolution + small-mesh distributed tests (subprocess with
forced host devices where a real mesh is needed)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs.base import SHAPES
from repro.configs.registry import all_cells, cell_supported
from repro.sharding import DEFAULT_RULES, RULE_SETS, resolve_spec

ROOT = os.path.join(os.path.dirname(__file__), "..")


class _FakeMesh:
    """Just enough Mesh interface for resolve_spec."""

    def __init__(self, shape):
        self.shape = shape


MESH = _FakeMesh({"data": 16, "model": 16})
MESH_MP = _FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_resolve_basic_tp():
    spec = resolve_spec(DEFAULT_RULES, MESH, ("embed", "mlp"), (1024, 8192))
    assert tuple(spec) == (None, "model")


def test_resolve_divisibility_fallback():
    # 24 heads % 16 != 0 -> replicated, no GSPMD padding
    spec = resolve_spec(DEFAULT_RULES, MESH, ("layers", "embed", "heads"),
                        (28, 1024, 24))
    assert tuple(spec) == ()  # trailing Nones trimmed


def test_resolve_batch_multi_pod():
    spec = resolve_spec(DEFAULT_RULES, MESH_MP, ("act_batch", "act_seq"),
                        (256, 4096))
    assert tuple(spec)[0] == ("pod", "data")


def test_resolve_drops_absent_pod_axis():
    spec = resolve_spec(DEFAULT_RULES, MESH, ("act_batch", "act_seq"),
                        (256, 4096))
    assert tuple(spec)[0] == "data"


def test_no_duplicate_mesh_axes():
    rules = DEFAULT_RULES.override(embed="model")
    spec = resolve_spec(rules, MESH, ("embed", "mlp"), (1024, 8192))
    axes = [s for s in tuple(spec) if s]
    assert len(axes) == len(set(axes))  # "model" used at most once


def test_batch_one_replicates():
    spec = resolve_spec(DEFAULT_RULES, MESH, ("act_batch", None), (1, 5))
    assert tuple(spec) == ()


def test_rule_sets_exist():
    assert set(RULE_SETS) >= {"default", "fsdp", "seqparallel"}


def test_cell_accounting_is_40():
    cells = all_cells()
    assert len(cells) == 44     # 11 architectures x 4 shapes
    runnable = [c for c in cells if c[2]]
    skipped = [c for c in cells if not c[2]]
    assert len(runnable) == 35
    assert len(skipped) == 9
    # every skip has a reason string
    assert all(c[3] for c in skipped)


def test_long500k_only_subquadratic():
    ok_archs = {a for a, s, ok, _ in all_cells() if s == "long_500k" and ok}
    assert ok_archs == {"mamba2-370m", "zamba2-1.2b", "zamba2-7b"}


def test_encoder_has_no_decode_cells():
    assert not cell_supported("hubert-xlarge", "decode_32k")[0]
    assert cell_supported("hubert-xlarge", "prefill_32k")[0]


DISTRIBUTED_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro.configs.base import reduced
    from repro.configs.registry import get_model_config, get_run_config
    from repro.launch.mesh import make_mesh_for
    from repro.models.layers import Ctx
    from repro.sharding import RULE_SETS, tree_shardings
    from repro.train.step import (abstract_state, init_state,
                                  make_train_step, state_logical_axes)

    cfg = reduced(get_model_config("llama3.2-3b"), n_heads=4, n_kv_heads=2)
    run = get_run_config("llama3.2-3b", remat="none", logits_chunk=16,
                         rules_name="default")
    mesh = make_mesh_for((2, 4), ("data", "model"))
    rules = RULE_SETS[run.rules_name]
    ctx = Ctx(run, rules, mesh)

    state = init_state(cfg, run, jax.random.PRNGKey(0)).tree()
    sh = tree_shardings(rules, mesh, state_logical_axes(cfg),
                        abstract_state(cfg, run))
    state = jax.device_put(state, sh)
    B, S = 4, 32
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),(B,S),0,cfg.vocab),
             "labels": jax.random.randint(jax.random.PRNGKey(2),(B,S),0,cfg.vocab)}
    step = jax.jit(make_train_step(cfg, run, ctx))
    st2, m = step(state, batch)

    # single-device reference
    ctx0 = Ctx(run, rules, None)
    st0 = init_state(cfg, run, jax.random.PRNGKey(0)).tree()
    st0, m0 = jax.jit(make_train_step(cfg, run, ctx0))(st0, batch)
    print(json.dumps({"sharded": float(m["loss"]),
                      "single": float(m0["loss"])}))
""")


@pytest.mark.slow
def test_distributed_train_step_matches_single_device():
    """8-fake-device pjit train step computes the same loss as 1 device."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", DISTRIBUTED_SNIPPET],
                         capture_output=True, text=True, env=env,
                         cwd=ROOT, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    vals = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(vals["sharded"] - vals["single"]) < 5e-2, vals


SEQSHARD_DECODE_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import reduced
    from repro.configs.registry import get_model_config, get_run_config
    from repro.launch.mesh import make_mesh_for
    from repro.models import lm
    from repro.models.layers import Ctx
    from repro.models.params import init_params
    from repro.serving.engine import make_decode_step, make_prefill_step
    from repro.sharding import RULE_SETS

    cfg = reduced(get_model_config("qwen2-vl-72b"))
    run = get_run_config("qwen2-vl-72b", remat="none", logits_chunk=16)
    mesh = make_mesh_for((2, 4), ("data", "model"))
    rules = RULE_SETS["default"]
    ctx_m, ctx_0 = Ctx(run, rules, mesh), Ctx(run, rules, None)
    params = init_params(lm.model_decls(cfg), jax.random.PRNGKey(0))
    B, S, MAX = 2, 32, 64
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),(B,S),0,cfg.vocab),
             "vision_embeds": jax.random.normal(jax.random.PRNGKey(2),
                 (B,cfg.vision_tokens,cfg.d_model), jnp.bfloat16),
             "positions": jnp.broadcast_to(
                 jnp.arange(S,dtype=jnp.int32)[None,None],(3,B,S))}
    pf0 = jax.jit(make_prefill_step(cfg, run, ctx_0, MAX))
    dec0 = jax.jit(make_decode_step(cfg, run, ctx_0))
    dec1 = jax.jit(make_decode_step(cfg, run, ctx_m))
    cache0, lg0 = pf0(params, batch)
    tok = jnp.argmax(lg0[:,0],-1)[:,None].astype(jnp.int32)
    cacheA, _ = pf0(params, batch)
    sh = NamedSharding(mesh, P(None, "data", "model", None, None))
    cacheA = jax.tree.map(lambda a: jax.device_put(a, sh), cacheA)
    errs = []
    for i in range(2):
        cache0, out0 = dec0(params, cache0, tok+i, jnp.asarray(S+i, jnp.int32))
        cacheA, out1 = dec1(params, cacheA, tok+i, jnp.asarray(S+i, jnp.int32))
        errs.append(float(jnp.max(jnp.abs(out0 - out1))))
    print(json.dumps({"errs": errs}))
""")


@pytest.mark.slow
def test_seqsharded_flash_decode_matches_reference():
    """shard_map LSE-combined decode == unsharded decode, 2 steps."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", SEQSHARD_DECODE_SNIPPET],
                         capture_output=True, text=True, env=env,
                         cwd=ROOT, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    vals = json.loads(out.stdout.strip().splitlines()[-1])
    assert max(vals["errs"]) < 0.05, vals


MOE_EP_SNIPPET = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, dataclasses
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import reduced
    from repro.configs.registry import get_model_config, get_run_config
    from repro.launch.mesh import make_mesh_for
    from repro.models import layers as L
    from repro.models.layers import Ctx
    from repro.models.params import init_params, logical_axes
    from repro.sharding import RULE_SETS, tree_shardings

    cfg = dataclasses.replace(reduced(get_model_config("olmoe-1b-7b")),
                              capacity_factor=8.0)
    mesh = make_mesh_for((2, 4), ("data", "model"))
    rules = RULE_SETS["default"]
    decls = L.moe_decls(cfg)
    params = init_params(decls, jax.random.PRNGKey(1))
    B, S = 4, 16
    x = jax.random.normal(jax.random.PRNGKey(2),
                          (B, S, cfg.d_model), jnp.float32) * 0.3
    p_sh = tree_shardings(rules, mesh, logical_axes(decls), params)
    params_s = jax.device_put(params, p_sh)
    x_s = jax.device_put(x.astype(jnp.bfloat16),
                         NamedSharding(mesh, P("data", None, None)))
    run = get_run_config("olmoe-1b-7b", remat="none")
    run_q = get_run_config("olmoe-1b-7b", remat="none", moe_a2a_dtype="int8")
    ctx0 = Ctx(run, rules, None)
    y0, _ = L.apply_moe(ctx0, cfg, params, x.astype(jnp.bfloat16))
    y1, _ = jax.jit(lambda p, xx: L.apply_moe(Ctx(run, rules, mesh),
                                              cfg, p, xx))(params_s, x_s)
    yq, _ = jax.jit(lambda p, xx: L.apply_moe(Ctx(run_q, rules, mesh),
                                              cfg, p, xx))(params_s, x_s)
    ep_err = float(jnp.max(jnp.abs(y0.astype(jnp.float32)
                                   - y1.astype(jnp.float32))))
    q_rel = float(jnp.linalg.norm((yq - y1).astype(jnp.float32))
                  / jnp.linalg.norm(y1.astype(jnp.float32)))
    g = jax.jit(jax.grad(lambda p, xx: L.apply_moe(
        Ctx(run_q, rules, mesh), cfg, p, xx)[0].astype(jnp.float32).sum())
        )(params_s, x_s)
    g_finite = all(bool(jnp.isfinite(a.astype(jnp.float32)).all())
                   for a in jax.tree.leaves(g))
    print(json.dumps({"ep_err": ep_err, "q_rel": q_rel,
                      "g_finite": g_finite}))
""")


@pytest.mark.slow
def test_moe_ep_and_int8_a2a():
    """EP shard_map MoE == dense path; int8-wire a2a within 5% rel."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", MOE_EP_SNIPPET],
                         capture_output=True, text=True, env=env,
                         cwd=ROOT, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    vals = json.loads(out.stdout.strip().splitlines()[-1])
    assert vals["ep_err"] < 0.01, vals
    assert vals["q_rel"] < 0.05, vals
    assert vals["g_finite"], vals
