"""Run one cell as ``run.py`` does, keep its trace, and print what the
program's own scopes and engine spans say about it.

  python3 chipbench/trace_run.py --workload <name> --seed <n> \
      --seconds <s> --trace <0|1> --out <dir>

Set-up and the window are the harness's (``harness.build``, the closed or
open loop, ``harness.Tracer``); the reference check is left out.  The last
stdout line is one JSON object: the engine steps that ended in the first
``harness.TRACE_SECONDS`` of the window and their rate -- the same count
with ``--trace 0`` and ``--trace 1`` gives the cost of tracing -- and, with
``--trace 1``, the trace's path under <dir>, the seconds ``trace.reduce``
and ``scopes.reduce`` took, every per-layer metric of the cell, device
seconds by program and each kernel's by program, and the device idle time
inside the harness's ``chipbench.step`` spans by the innermost
``serve.*`` span.
stderr gets the readers' splits and ``scopes.summary`` of the trace.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

KERNELS = ("flash_decode_paged", "cache_update_paged")


def first_steps(rec, seconds: float) -> dict:
    """Window steps that ended in its first ``seconds``, and their rate."""
    steps = [s for s in rec.window_steps() if s.t1 <= rec.window0 + seconds]
    if not steps:
        return {"steps": 0}
    span = steps[-1].t1 - rec.window0
    tokens = sum(n for s in steps for _, n in s.tokens.values())
    return {"steps": len(steps), "seconds": span,
            "steps_per_s": len(steps) / span, "tokens_per_s": tokens / span}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        print("trace_run.py: JAX found no TPU; no result", file=sys.stderr)
        return 1
    from chipbench import harness, metrics, scopes
    from chipbench import trace as T

    spec = harness.load_cell(args.workload)
    su = harness.build(spec, args.seed, args.seconds, T_START)
    rec, drv = su.rec, su.drv
    tracer = harness.Tracer(drv, bool(args.trace))
    drive = harness.drive_closed if su.gen.closed else harness.drive_open
    drive(drv, su.gen, args.seconds, tracer)
    out = {"workload": args.workload, "seed": args.seed, "trace": args.trace,
           "first": first_steps(rec, harness.TRACE_SECONDS)}
    if tracer.path:
        os.makedirs(args.out, exist_ok=True)
        out["path"] = os.path.join(
            args.out, f"{args.workload}.{args.seed}.xplane.pb")
        shutil.copy(tracer.path, out["path"])
        t0 = time.perf_counter()
        T.reduce(tracer.path)
        t1 = time.perf_counter()
        rec.trace = r = scopes.reduce(tracer.path)
        out["reduce_s"] = t1 - t0
        out["scoped_reduce_s"] = time.perf_counter() - t1
        out["metrics"] = {m["name"]: metrics.read(m["name"], rec)
                          for m in spec["per_layer"]}
        mods = sorted({m[0] for m in r.modules})
        out["device"] = {"busy_s": r.busy_s(), "window_s": r.window_s,
                         "module_s": {m: r.module_s(m) for m in mods},
                         "kernel_s": {k: {m: r.op_s(k, m) for m in mods
                                          if r.op_s(k, m) > 0}
                                      for k in KERNELS},
                         "top_ops": r.top_ops(10),
                         "idle_gaps": r.idle_gaps(10)}
        outer = [(s, s + d) for n, s, d in r.host if n == "chipbench.step"]
        per, split = r.idle_split(outer)
        out["harness_step_idle_s"] = {
            "total": sum(per) * 1e-9,
            **{k: v * 1e-9 for k, v in sorted(split.items())}}
        print(scopes.summary(r), file=sys.stderr)
    tracer.cleanup()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
