"""A cell small enough for the CPU: the published architectures at the
repo's reduced widths (``configs.base.reduced``), a 4-slot engine and short
traffic of each kind.  The harness runs it whole, the chip check skipped."""

import json
import os

import pytest

from chipbench import harness

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
SERVE = {"param_dtype": "bfloat16", "batch": 4, "max_seq": 256,
         "block_size": 16, "n_blocks": 64, "prefill_chunk": 32,
         "decode_chunk": 4}
# widest-gap limits at the reduced widths, set like the cells' own: over
# ten seeds sound runs read at most 0.039 (minitron) and 0.109 (zamba2),
# the fp8 control at least 0.36 and 1.33, the faults several units
LIMIT = {"minitron-4b": 0.12, "zamba2-1.2b": 0.35}
TRAFFIC = {
    "backlog": {"kind": "backlog", "queued_per_slot": 2, "block": 16,
                "lead_in_completions_per_slot": 1,
                "prompt": {"lo": 16, "hi": 96, "alpha": 1.5},
                "output": {"lo": 4, "hi": 48, "alpha": 1.3}},
    "chat": {"kind": "poisson_burst", "base_rps": 2.0, "every_s": 2.0,
             "burst": {"at_s": 1.0, "length_s": 0.5, "extra_rps": 6.0},
             "lead_in_s": 1.0, "drain_limit_s": 30.0,
             "prompt": {"lo": 8, "hi": 120, "alpha": 1.2},
             "output": {"lo": 4, "hi": 40, "alpha": 1.2}},
    "sessions": {"kind": "sessions", "session_rps": 1.0, "every_s": 4.0,
                 "system_prompts": {"count": 3, "lo": 40, "hi": 80,
                                    "zipf": 1.0},
                 "turns": {"max": 3, "p": 0.5}, "think_s": 0.5,
                 "user": {"lo": 8, "hi": 32, "alpha": 1.2},
                 "output": {"lo": 4, "hi": 24, "alpha": 1.2},
                 "lead_in_s": 1.0, "drain_limit_s": 30.0},
}
# the metrics a run reports: the backlog's cell's own from BENCHMARK.json;
# the open-loop kinds, in no cell yet, report the latency tails
OPEN_LOOP = {
    "end_to_end": [{"name": "ttft_p95_ms", "unit": "ms"},
                   {"name": "tpot_p95_ms", "unit": "ms"},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [{"name": "decode_iter_ms.chat", "unit": "ms"},
                  {"name": "prefill_device_share", "unit": "%"},
                  {"name": "ssd_roofline", "unit": "%"},
                  {"name": "mfu.chat", "unit": "%"},
                  {"name": "compile_s", "unit": "s"}]}


def spec(arch: str, kind: str) -> dict:
    from repro.configs.base import reduced
    from repro.configs.registry import get_model_config

    here = os.path.dirname(os.path.abspath(__file__))
    conf_file = (os.path.join(here, os.pardir, "configs", "minitron4b.json")
                 if arch == "minitron-4b"
                 else os.path.join(here, "data", "zamba2_repo.json"))
    keys = json.load(open(conf_file))["model"]
    cfg = reduced(get_model_config(arch))
    model = {k: getattr(cfg, k) for k in keys}
    serve = dict(SERVE, prefix_sharing=cfg.family == "dense")
    conf = {"name": "tiny", "arch": arch, "model": model, "serve": serve,
            "check": {"widest_logit_gap": LIMIT[arch]}}
    out = {"cell": {"name": f"tiny.{kind}", "chips": 1}, "conf": conf,
           "traffic": TRAFFIC[kind]}
    if kind != "backlog":
        return dict(out, **OPEN_LOOP)
    cell = harness.load_cell("minitron4b.backlog")
    return dict(out, end_to_end=cell["end_to_end"],
                per_layer=cell["per_layer"])


@pytest.fixture
def quiet_cache(monkeypatch, tmp_path):
    """The harness turns JAX's persistent cache on; keep it out of the
    checkout and restore JAX's settings after."""
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    names = ("jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in was.items():
        jax.config.update(n, v)


def run(arch: str, kind: str, seconds: float = 3.0, seed: int = 2 ** 33 + 5,
        trace: bool = False) -> dict:
    import time
    return harness.run(spec(arch, kind), seed, seconds, trace,
                       time.perf_counter(), peaks=PEAKS, require_tpu=False)
