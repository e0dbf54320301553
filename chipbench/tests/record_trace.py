"""Record the small TPU trace that test_chipbench_trace.py reduces.

  python3 chipbench/tests/record_trace.py <out_dir>     # on a TPU

The repo's zamba2-1.2b at its registry widths, cut to one period (6
Mamba layers and one shared-attention invocation), serves two requests through the paged
engine while the harness's driver books them; a few steps are traced.
Writes ``tiny_tpu.xplane.pb`` and ``tiny_tpu.json`` (the steps' booked
tokens and the prompts' lengths) into <out_dir>; both are checked in
under chipbench/tests/data/.
"""

import dataclasses
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import harness, traffic, weights  # noqa: E402

MODEL = dict(json.load(open(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "data",
    "zamba2_repo.json")))["model"], n_layers=6)
SERVE = dict(batch=4, max_seq=1024, block_size=16, n_blocks=128,
             prefill_chunk=256, decode_chunk=8)


def main(out: str) -> int:
    import jax

    from repro.configs.registry import get_model_config, get_run_config
    from repro.models import lm
    from repro.models.layers import Ctx
    from repro.serving.engine import Request, ServeEngine
    from repro.sharding import RULE_SETS

    if jax.devices()[0].platform != "tpu":
        print("record_trace.py needs a TPU", file=sys.stderr)
        return 1
    cfg = dataclasses.replace(get_model_config("zamba2-1.2b"), **MODEL)
    run = get_run_config("zamba2-1.2b", remat="none",
                         param_dtype="bfloat16")
    ctx = Ctx(run, RULE_SETS[run.serve_rules_name], None)
    params = weights.draw(lm.model_decls(cfg), 1, "bfloat16")
    engine = ServeEngine(cfg, run, ctx, params, batch_size=SERVE["batch"],
                         max_seq=SERVE["max_seq"],
                         prefill_chunk=SERVE["prefill_chunk"],
                         decode_chunk=SERVE["decode_chunk"], paged=True,
                         block_size=SERVE["block_size"],
                         n_blocks=SERVE["n_blocks"])
    rec = harness.Record(name="tiny", conf={}, model=MODEL, serve=SERVE,
                         peaks={}, seed=1, seconds=0.0)
    drv = harness.Driver(engine, rec, Request, jax.profiler.TraceAnnotation)
    harness.warm_up(drv, SERVE["prefill_chunk"], SERVE["decode_chunk"],
                    cfg.vocab, uid0=1000, shares_prefixes=False)
    rng = traffic.rng_for(1, 2)
    tmp = tempfile.mkdtemp()
    tracer = harness.Tracer(drv, True)
    drv.phase = "window"
    tracer.start(0.0)
    for uid, plen in ((0, 300), (1, 45)):
        drv.submit(traffic.Spec(uid=uid, prompt=rng.integers(
            0, cfg.vocab, plen).tolist(), max_new_tokens=20), due=0.0)
    for _ in range(3):
        drv.step()
    tracer.stop()
    os.makedirs(out, exist_ok=True)
    shutil.copy(tracer.path, os.path.join(out, "tiny_tpu.xplane.pb"))
    tracer.cleanup()
    shutil.rmtree(tmp, ignore_errors=True)
    steps = [{"tokens": {str(u): list(v) for u, v in s.tokens.items()},
              "admitted": s.admitted} for s in rec.steps
             if s.phase == "window"]
    prompts = {str(u): len(r.spec.prompt) for u, r in rec.reqs.items()
               if u >= 0}
    with open(os.path.join(out, "tiny_tpu.json"), "w") as f:
        json.dump({"model": MODEL, "serve": SERVE, "steps": steps,
                   "prompts": prompts}, f, indent=1)
    print(f"wrote {out}/tiny_tpu.xplane.pb and tiny_tpu.json")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
