"""Serving launcher: batched generation with per-phase power capping.

  PYTHONPATH=src python -m repro.launch.serve --arch minitron-4b --reduced \
      --requests 8 --new 16

Without ``--reduced`` the model is built at its published widths, with
weights drawn directly in the compute dtype (bf16), as on the chip.

The engine runs prefill and decode under distinct phase caps from a
``repro.power.PowerManager`` (compute-bound prefill stays near max;
memory-bound decode drops low), and the modeled energy ledger is printed
after the batch drains.
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.configs.base import reduced as reduce_cfg
from repro.configs.registry import ARCH_IDS, get_model_config, get_run_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.models.layers import Ctx
from repro.models.params import init_params
from repro.power import PowerManager, available_metrics
from repro.serving.engine import Request, ServeEngine, serve_phase_tasks
from repro.sharding import RULE_SETS


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b", choices=ARCH_IDS)
    ap.add_argument("--reduced", action="store_true",
                    help="same-family toy widths (CPU-sized)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new", type=int, default=16)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--decode-chunk", type=int, default=8,
                    help="device-resident decode tokens per host sync AND "
                         "per power-phase entry (chunk-amortized observe)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="max power-of-two prompt chunk per prefill step")
    ap.add_argument("--power-metric", default="sed",
                    choices=available_metrics())
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_model_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    if cfg.family == "audio":
        raise SystemExit("encoder-only arch has no decode path")
    # serving keeps no f32 masters: weights are drawn in the compute dtype
    run = get_run_config(args.arch, remat="none", logits_chunk=64,
                         param_dtype="bfloat16")
    ctx = Ctx(run, RULE_SETS[run.serve_rules_name], None)
    params = init_params(lm.model_decls(cfg), jax.random.PRNGKey(0),
                         run.param_dtype)

    # phase caps for the FULL arch at production serving scale; the engine
    # below drives the same phases on the reduced model
    full = get_model_config(args.arch)
    pm = PowerManager(
        tasks=serve_phase_tasks(full, batch=128, prompt=32768,
                                new_tokens=args.new, chips=256),
        metric=args.power_metric)
    print(f"[caps:{args.power_metric}] "
          f"{ {k: round(v) for k, v in pm.schedule.caps.items()} }")

    engine = ServeEngine(cfg, run, ctx, params, batch_size=args.batch_size,
                         max_seq=args.max_seq, power=pm,
                         prefill_chunk=args.prefill_chunk,
                         decode_chunk=args.decode_chunk)
    reqs = [Request(uid=i, prompt=[(5 * i + j) % cfg.vocab
                                   for j in range(4 + i % 5)],
                    max_new_tokens=args.new)
            for i in range(args.requests)]
    t0 = time.perf_counter()
    done = engine.generate(reqs)
    wall = time.perf_counter() - t0
    for r in done:
        print(f"req {r.uid}: {len(r.generated)} tokens -> "
              f"{r.generated[:8]}{'...' if len(r.generated) > 8 else ''}")
    n_tok = sum(len(r.generated) for r in done)
    print(f"[run] {n_tok} tokens, {engine.sync_count} host syncs, "
          f"{wall:.2f}s host wall clock including compilation "
          f"({jax.devices()[0].platform})")
    e = pm.account_step()
    dt, de = pm.overhead_totals()
    print(f"[energy] modeled step {e['energy_j']:.1f}J "
          f"(-{e['energy_saving_pct']:.1f}% vs uncapped); "
          f"{pm.transitions} cap writes ({de*1e3:.1f} mJ overhead)")


if __name__ == "__main__":
    main()
