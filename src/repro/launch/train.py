"""Distributed training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
      --mesh 2x4 --batch 8 --seq 256 --steps 50 --reduced

Builds the mesh from the available devices (or --mesh), shards the state
with the arch's logical rules, restores the newest valid checkpoint, and
runs the supervised, preemption-safe, energy-accounted training loop.  On a
real pod this is the per-host entrypoint (jax.distributed.initialize is
called when the usual cluster env vars are present).
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from repro.ckpt import checkpoint
from repro.configs.base import reduced as reduce_cfg
from repro.configs.registry import ARCH_IDS, get_model_config, get_run_config
from repro.data.pipeline import DataConfig, TokenSource
from repro.hw.tpu import DEFAULT_SUPERCHIP
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh_for
from repro.models.layers import Ctx
from repro.power import PodPowerArbiter, PowerManager, available_metrics
from repro.runtime.supervisor import PreemptionGuard, StragglerWatchdog, \
    Supervisor
from repro.sharding import RULE_SETS, tree_shardings
from repro.train.phases import training_phase_tasks
from repro.train.step import (abstract_state, init_state, make_train_step,
                              state_logical_axes)


def maybe_init_distributed() -> None:
    if "JAX_COORDINATOR_ADDRESS" in os.environ:
        jax.distributed.initialize()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--mesh", default=None, help="e.g. 2x4 or 2x16x16")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--power-metric", default="sed",
                    choices=available_metrics())
    ap.add_argument("--pod-budget-frac", type=float, default=0.85,
                    help="pod power budget as a fraction of N x p_max")
    ap.add_argument("--max-restarts", type=int, default=2)
    args = ap.parse_args()

    maybe_init_distributed()
    enable_compile_cache()
    cfg = get_model_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    run = get_run_config(args.arch, total_steps=args.steps,
                         power_metric=args.power_metric,
                         remat="none" if args.reduced else "full",
                         logits_chunk=min(args.seq, 1024))
    rules = RULE_SETS[run.rules_name]

    mesh = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
        names = ("pod", "data", "model")[-len(shape):]
        mesh = make_mesh_for(shape, names)
    ctx = Ctx(run, rules, mesh)

    data = TokenSource(DataConfig(
        vocab=cfg.vocab, global_batch=args.batch, seq_len=args.seq,
        num_hosts=jax.process_count(), host_id=jax.process_index()))
    os.makedirs(args.ckpt_dir, exist_ok=True)

    chips = max(jax.device_count(), 1)
    tasks = training_phase_tasks(cfg, batch=args.batch, seq=args.seq,
                                 chips=chips)
    pm = PowerManager(tasks=tasks, metric=args.power_metric,
                      spec=DEFAULT_SUPERCHIP, min_dwell_s=2e-4)
    if chips > 1 and pm.schedule.caps:
        # one pod budget split across superchips: each chip runs the same
        # phase mix here, so requests are uniform and grants symmetric.
        # Sized on the hungriest scheduled phase (phase names differ per
        # family: attention vs ssd_scan); the grant is INSTALLED as this
        # process's cap ceiling, so every phase cap the loop applies is
        # clamped to the pod's share (heterogeneous fleets go through
        # repro.fleet.FleetPowerController instead — see launch/fleet.py).
        phase0 = max(pm.schedule.caps, key=pm.schedule.caps.get)
        arbiter = PodPowerArbiter(
            budget_w=args.pod_budget_frac * chips * DEFAULT_SUPERCHIP.p_max)
        grants = arbiter.split_phase(
            {f"chip{i}": pm.schedule for i in range(chips)}, phase0)
        my_grant = grants[f"chip{jax.process_index() % chips}"]
        pm.set_grant(my_grant)
        print(f"[pod] budget {arbiter.budget_w:.0f}W over {chips} chips; "
              f"{phase0}-phase grant {my_grant:.0f}W (installed as cap "
              f"ceiling)")

    def train_once(restart: int) -> str:
        state = init_state(cfg, run, jax.random.PRNGKey(0)).tree()
        if mesh is not None:
            sh = tree_shardings(rules, mesh, state_logical_axes(cfg),
                                abstract_state(cfg, run))
            state = jax.device_put(state, sh)
        start = 0
        if checkpoint.available_steps(args.ckpt_dir):
            state, start = checkpoint.restore(args.ckpt_dir, state)
            state = jax.tree.map(jnp.asarray, state)
            print(f"[restore] step {start} (restart #{restart})")
        # the loop rebinds ``state`` every step: donate it (held once)
        step_fn = jax.jit(make_train_step(cfg, run, ctx), donate_argnums=(0,))
        watchdog = StragglerWatchdog()
        with PreemptionGuard() as guard:
            for i in range(start, args.steps):
                t0 = time.perf_counter()
                batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
                state, metrics = step_fn(state, batch)
                slow = watchdog.observe(i, time.perf_counter() - t0)
                if i % 10 == 0 or slow:
                    e = pm.account_step()
                    print(f"step {i:5d} loss={float(metrics['loss']):.4f} "
                          f"E={e['energy_j']:.2f}J "
                          f"(-{e['energy_saving_pct']:.1f}%)"
                          f"{' [STRAGGLER]' if slow else ''}")
                if (i + 1) % args.ckpt_every == 0 or guard.should_stop:
                    checkpoint.save(jax.device_get(state), i + 1,
                                    args.ckpt_dir)
                if guard.should_stop:
                    raise SystemExit(143)
        checkpoint.save(jax.device_get(state), args.steps, args.ckpt_dir)
        return f"completed at step {args.steps}"

    result = Supervisor(max_restarts=args.max_restarts).run(train_once)
    print(f"[supervisor] {result}")


if __name__ == "__main__":
    main()
