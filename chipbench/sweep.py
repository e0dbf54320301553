"""Find an open-loop cell's knee once, by a sweep of steady rates.

  python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \
      --rates 4,8,12

One process, one set-up: the cell's engine is built and warmed once, then
each rate runs a window of ``--seconds`` of steady arrivals (the cell's
mix with its rate set, no bursts) and a drain.  A row per rate: requests
due and served in the drain limit, the queue at the window's close and its
growth over the window's second half, and the cell's own end-to-end
latencies and token rate (``harness.end_to_end``).  The knee is the
highest rate with no growing queue; the cell's traffic file takes it in
req/s.  Not part of a benchmark run.
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
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    from chipbench import harness, traffic

    # the key that sets each open-loop kind's steady rate
    rate_key = {"poisson_burst": "base_rps", "sessions": "session_rps"}
    spec = harness.load_cell(args.workload)
    su = harness.build(spec, args.seed, args.seconds, T_START)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        tp = dict(spec["traffic"], burst=None, every_s=args.seconds,
                  lead_in_s=0.0)
        tp[rate_key[tp["kind"]]] = rate
        gen = traffic.make(tp, args.seed + i, su.rec.model["vocab"],
                           su.rec.serve["batch"], su.rec.serve["max_seq"])
        rec = harness.Record(name=su.rec.name, conf=su.rec.conf,
                             model=su.rec.model, serve=su.rec.serve,
                             peaks=su.rec.peaks, seed=args.seed + i,
                             seconds=args.seconds)
        su.drv.rec, su.drv.inflight = rec, {}
        harness.drive_open(su.drv, gen, args.seconds,
                           harness.Tracer(None, False))
        att = [r for r in rec.reqs.values() if r.attempted]
        win = rec.window_steps()
        half = [s.queue for s in win
                if s.t1 >= rec.window0 + args.seconds / 2]
        e2e = harness.end_to_end(rec, 0.0)
        row = {
            "rate_rps": rate, "due": len(att),
            "served": sum(1 for r in att if r.done is not None),
            "queue_at_close": win[-1].queue if win else None,
            "queue_growth": (half[-1] - half[0]) if len(half) > 1 else None,
            **{k: e2e.get(k) for k in ("ttft_p95_ms", "tpot_p95_ms",
                                       "output_tok_s")},
        }
        rows.append(row)
        print("[sweep] " + json.dumps(row), flush=True)
        while su.engine.pending:          # nothing carries into the next rate
            su.drv.step()
    print(json.dumps({"sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
