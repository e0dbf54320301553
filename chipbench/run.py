"""Run one cell of BENCHMARK.json once, on the machine it is started on.

  python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
      --trace <0|1>

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell needs.  The last stdout line is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` also ``breakdown``, and last ``check``: the
numbers the comparison with the reference made, each beside its limit
(also the last lines of stderr).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: JAX found no TPU (platform "
              f"{devices[0].platform!r}); no result", file=sys.stderr)
        return 1

    from chipbench import harness
    spec = harness.load_cell(args.workload)
    if len(devices) < spec["cell"]["chips"]:
        print(f"run.py: {args.workload} needs {spec['cell']['chips']} "
              f"chips, JAX sees {len(devices)}; no result", file=sys.stderr)
        return 1
    result = harness.run(spec, args.seed, args.seconds, bool(args.trace),
                         T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
