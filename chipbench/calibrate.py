"""Readings that set a cell's limit on ``widest_logit_gap``.

  python3 chipbench/calibrate.py --workload <cell> --seconds <s> \
      --seeds 1,2,3 [--control-seeds 1,2,3]

In one process, for each seed: the cell's full set-up and a window of
``--seconds`` at the cell's own load, then the same sample the benchmark
compares.  The program's reading is its widest gap (the sound runs' largest
over the seeds is the limit's lower reading).  On the control seeds the
same sample is also read for the control: the reference put in the
program's place at the next precision down, fp8 wherever the program holds
bf16 (``chipbench/reference/common.py``), the token it ranks first at each
position read against the float32 reference (the smallest over the seeds
is the upper reading).  Both go through the benchmark's own comparison
(``harness.check``), the control's with the control in the program's
place, and each row says whether it came out correct against the limit in
the configuration file, which is set between the two.  Not part of a
benchmark run.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def reading(spec: dict, seed: int, seconds: float, control: bool,
            peaks=None, require_tpu: bool = True) -> dict:
    """One seed: set-up, window, then the program's and (optionally) the
    control's widest gap over the benchmark's sample."""
    from chipbench import harness, traffic
    from chipbench import reference as R

    t0 = time.perf_counter()
    su = harness.build(spec, seed, seconds, t0, peaks, require_tpu)
    tracer = harness.Tracer(None, False)
    if su.gen.closed:
        harness.drive_closed(su.drv, su.gen, seconds, tracer)
    else:
        harness.drive_open(su.drv, su.gen, seconds, tracer)
    rec, params = su.rec, su.params
    su.drv.engine = su.engine = None
    gc.collect()
    sample = harness.check_sample(rec, traffic.rng_for(seed, 7))
    limit = spec["conf"]["check"]["widest_logit_gap"]
    row = {"seed": seed, "requests": len(sample)}
    runs = [("program", R.served_gaps)]
    if control:
        runs.append(("control", R.control_gaps))
    for who, gap_fn in runs:
        # the benchmark's own comparison, with the control put in the
        # program's place for the control's reading
        numbers = harness.check(rec, params, sample, limit, gap_fn)
        row[f"{who}_gap"] = numbers["widest_logit_gap"]["value"]
        row[f"{who}_correct"] = harness.check_ok(numbers)
        row["tokens"] = numbers["tokens_compared"]["value"]
        row["wrong_length_or_id"] = numbers["wrong_length_or_id"]["value"]
    row["seconds"] = time.perf_counter() - t0
    del su, params
    gc.collect()
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)

    from chipbench import harness

    spec = harness.load_cell(args.workload)
    control = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        row = reading(spec, seed, args.seconds, seed in control)
        rows.append(row)
        print("[calibrate] " + json.dumps(row), flush=True)
    lower = max(r["program_gap"] for r in rows)
    ctl = [r["control_gap"] for r in rows if "control_gap" in r]
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": min(ctl) if ctl else None, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
