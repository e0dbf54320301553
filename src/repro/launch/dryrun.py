import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- everything below may touch jax ---------------------------------------
import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.base import SHAPES                      # noqa: E402
from repro.configs.registry import (ARCH_IDS, all_cells,   # noqa: E402
                                    cell_supported, get_model_config,
                                    get_run_config)
from repro.launch.mesh import make_production_mesh         # noqa: E402
from repro.launch.specs import input_specs                 # noqa: E402
from repro.models.layers import Ctx                        # noqa: E402
from repro.sharding import RULE_SETS, tree_shardings       # noqa: E402

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract the roofline terms.

For each cell this produces artifacts/dryrun/<arch>__<shape>__<mesh>.json:
  flops / bytes from compiled.cost_analysis()  (per-device SPMD program)
  per-op collective bytes parsed from the optimized HLO
  memory_analysis when the backend provides it
The roofline harness (benchmarks/roofline.py) and EXPERIMENTS.md read these.
"""

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "s8": 1, "s16": 2, "s32": 4, "s64": 8,
    "u4": 1, "u8": 1, "u16": 2, "u32": 4, "u64": 8,
    "f8e5m2": 1, "f8e4m3fn": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+?)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Sum bytes over every 'dtype[d0,d1,...]' in a shape string (handles
    tuples)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-op-kind output-bytes totals from optimized (post-SPMD) HLO.
    Shapes in the per-device program are per-device shapes, so these are
    per-chip communication volumes."""
    out = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)", line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        base = op
        for suffix in ("-start", "-done"):
            if base.endswith(suffix):
                base = base[: -len(suffix)]
        if base in _COLLECTIVES and not op.endswith("-done"):
            out[base]["count"] += 1
            out[base]["bytes"] += _shape_bytes(shape_str)
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    out["total_count"] = sum(v["count"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


def _make_step(cfg, run, ctx, shape):
    if shape.kind == "train":
        from repro.train.step import make_train_step
        return make_train_step(cfg, run, ctx)
    if shape.kind == "prefill":
        from repro.serving.engine import make_prefill_step
        return make_prefill_step(cfg, run, ctx, shape.seq_len)
    from repro.serving.engine import make_decode_step
    return make_decode_step(cfg, run, ctx)


# ---------------------------------------------------------------------------
# cost extrapolation
#
# XLA cost analysis counts a while-loop (lax.scan) body ONCE, regardless of
# trip count (verified in tests/test_dryrun_small.py), so the scanned full
# compile undercounts flops/bytes/collectives by ~n_layers.  We therefore
# also compile 2-3 UNROLLED reduced-layer variants of the same cell and
# extrapolate:   cost(L) = outer + L * per_layer   (affine in L for
# homogeneous stacks; zamba2 adds a shared-block term, gemma2 counts pairs).
# The full scanned compile remains the shardability/memory deliverable.
# ---------------------------------------------------------------------------

def _variant_ks(cfg) -> tuple[int, ...]:
    if cfg.family == "hybrid":
        # depths a, a + 1 with one use of the shared blocks, b with two
        import dataclasses

        from repro.models.lm import shared_block_uses

        def uses(k):
            return len(shared_block_uses(dataclasses.replace(cfg,
                                                             n_layers=k)))
        a = next(k for k in range(1, cfg.n_layers + 1) if uses(k) == 1)
        b = next(k for k in range(a, cfg.n_layers + 1) if uses(k) == 2)
        return (a, a + 1, b)
    if cfg.layer_pattern == "local_global":
        return (2, 4)
    return (1, 2)


def _cost_of(cfg, run, shape, mesh, rules) -> dict:
    ctx = Ctx(run, rules, mesh)
    args, axes, donate = input_specs(cfg, run, shape, ctx)
    in_sh = tuple(tree_shardings(rules, mesh, ax, sp)
                  for ax, sp in zip(axes, args))
    step = _make_step(cfg, run, ctx, shape)
    compiled = jax.jit(step, in_shardings=in_sh,
                       donate_argnums=donate).lower(*args).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    coll = parse_collectives(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": float(coll["total_bytes"])}


def corrected_costs(arch: str, shape_name: str, mesh, rules,
                    run_overrides: dict | None = None) -> dict:
    import dataclasses
    cfg = get_model_config(arch)
    run = get_run_config(arch, **(run_overrides or {}))
    shape = SHAPES[shape_name]
    run_v = dataclasses.replace(
        run, scan_layers=False, logits_chunk=shape.seq_len,
        naive_attn_below=1 << 62)
    ks = _variant_ks(cfg)
    costs = {}
    for k in ks:
        cfg_k = dataclasses.replace(cfg, n_layers=k)
        costs[k] = _cost_of(cfg_k, run_v, shape, mesh, rules)

    def combine(field: str) -> float:
        c = {k: costs[k][field] for k in ks}
        L = cfg.n_layers
        if cfg.family == "hybrid":
            from repro.models.lm import shared_block_uses
            a, a1, b = ks
            mb = c[a1] - c[a]                       # one Mamba layer
            sb = c[b] - c[a] - (b - a) * mb         # one use of a block
            outer = c[a] - a * mb - sb
            return outer + L * mb + len(shared_block_uses(cfg)) * sb
        if cfg.layer_pattern == "local_global":
            pair = c[4] - c[2]
            outer = c[2] - pair
            return outer + (L // 2) * pair
        lay = c[2] - c[1]
        outer = c[1] - lay
        return outer + L * lay

    return {"flops": combine("flops"), "bytes": combine("bytes"),
            "coll_bytes": combine("coll"),
            "variants": {str(k): costs[k] for k in ks}}


def dryrun_cell(arch: str, shape_name: str, *, multi_pod: bool,
                rules_name: str | None = None,
                run_overrides: dict | None = None) -> dict:
    cfg = get_model_config(arch)
    run = get_run_config(arch, **(run_overrides or {}))
    if rules_name:
        import dataclasses
        run = dataclasses.replace(run, rules_name=rules_name)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = RULE_SETS[run.rules_name if shape.kind == "train"
                      else run.serve_rules_name]
    if rules_name:
        rules = RULE_SETS[rules_name]
    if shape.kind != "train":
        # serving is forward-only: activation checkpointing is pure overhead
        import dataclasses as _dc
        run = _dc.replace(run, remat="none")
    ctx = Ctx(run, rules, mesh)

    args, axes, donate = input_specs(cfg, run, shape, ctx)
    in_sh = tuple(tree_shardings(rules, mesh, ax, sp)
                  for ax, sp in zip(axes, args))
    step = _make_step(cfg, run, ctx, shape)

    t0 = time.time()
    out_shape = jax.eval_shape(step, *args)
    # outputs: state-like trees keep their input shardings; everything else
    # (metrics, logits) is left to the partitioner
    jitted = jax.jit(step, in_shardings=in_sh, donate_argnums=donate)
    lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)

    # corrected (scan-body x trip-count) costs via unrolled variants
    corrected = corrected_costs(arch, shape_name, mesh, rules,
                                run_overrides)

    from repro.hw.flops import active_param_count, model_flops, \
        total_param_count
    chips = mesh.devices.size
    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "rules": rules.name,
        "kind": shape.kind,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "flops_per_device_raw": float(cost.get("flops", -1.0)),
        "bytes_per_device_raw": float(cost.get("bytes accessed", -1.0)),
        "collectives_raw": coll,
        "flops_per_device": corrected["flops"],
        "bytes_per_device": corrected["bytes"],
        "coll_bytes_per_device": corrected["coll_bytes"],
        "cost_variants": corrected["variants"],
        "model_flops_global": model_flops(get_model_config(arch),
                                          SHAPES[shape_name]),
        "params_total": total_param_count(get_model_config(arch)),
        "params_active": active_param_count(get_model_config(arch)),
        "lower_s": t_lower, "compile_s": t_compile,
        "memory_analysis": _mem_record(mem),
        "hlo_bytes": len(hlo),
    }
    return record


def _mem_record(mem) -> dict | None:
    if mem is None:
        return None
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    out = {}
    for k in keys:
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    return out or {"repr": str(mem)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--rules", default=None)
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = []
    if args.all:
        cells = [(a, s) for a, s, ok, _ in all_cells() if ok]
    else:
        archs = [args.arch] if args.arch else ARCH_IDS
        shapes = [args.shape] if args.shape else list(SHAPES)
        for a in archs:
            for s in shapes:
                ok, why = cell_supported(a, s)
                if ok:
                    cells.append((a, s))
                else:
                    print(f"SKIP {a} x {s}: {why}")

    meshes = [False, True] if (args.both_meshes or args.all) \
        else [args.multi_pod]
    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
            suffix = f"__{args.rules}" if args.rules else ""
            path = os.path.join(args.out, tag + suffix + ".json")
            try:
                rec = dryrun_cell(arch, shape, multi_pod=mp,
                                  rules_name=args.rules)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                print(f"OK   {tag}: flops/dev={rec['flops_per_device']:.3e} "
                      f"coll/dev={rec['coll_bytes_per_device']:.3e}B "
                      f"compile={rec['compile_s']:.1f}s", flush=True)
            except Exception as e:  # noqa: BLE001
                failures += 1
                print(f"FAIL {tag}: {type(e).__name__}: {e}")
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")


if __name__ == "__main__":
    main()
