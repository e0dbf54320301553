"""The whole run on the CPU at a reduced size, the chip check skipped:
sound runs come out correct, and each fault the serving cells can have,
planted underneath the timed path, comes out not correct."""

import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from chipbench.tests import tiny
from chipbench.tests.tiny import quiet_cache  # noqa: F401  (fixture)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _token_altered(monkeypatch):
    """A token altered where it is produced: the decode loop's output
    buffer gets every live token shifted by one."""
    from repro.serving import engine as E
    make = E.make_decode_chunk_step

    def broken(*a, **k):
        step = make(*a, **k)

        def decode_chunk(*args):
            out = list(step(*args))
            out[5] = jnp.where(out[5] >= 0, (out[5] + 1) % 7, out[5])
            return tuple(out)
        return decode_chunk
    monkeypatch.setattr(E, "make_decode_chunk_step", broken)


def _state_unchanged(monkeypatch):
    """A step that returns its state unchanged: the paged cache write
    hands the pools back as they came."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "kv_cache_update_paged",
                        lambda k, v, *a, **kw: (k, v))


def _half_batch(monkeypatch):
    """Half of the batch left out: decode writes of the upper half of the
    slots are dropped, the rest served as usual."""
    from repro.kernels import ops
    write = ops.kv_cache_update_paged

    def broken(k, v, kn, vn, index, tables, layer=None, **kw):
        if kn.shape[1] == 1 and kn.shape[0] > 1:
            half = jnp.arange(kn.shape[0]) >= kn.shape[0] // 2
            index = jnp.where(half, tables.shape[1] * k.shape[-3], index)
        return write(k, v, kn, vn, index, tables, layer, **kw)
    monkeypatch.setattr(ops, "kv_cache_update_paged", broken)


FAULTS = {"token_altered": _token_altered,
          "state_unchanged": _state_unchanged,
          "half_batch": _half_batch}


@pytest.mark.parametrize("arch,kind", [("minitron-4b", "backlog"),
                                       ("zamba2-1.2b", "chat")])
def test_sound_run_is_correct(quiet_cache, arch, kind):  # noqa: F811
    res = tiny.run(arch, kind)
    assert res["correct"], res["check"]
    assert list(res)[-1] == "check"
    assert res["attempted"] > 0 and res["failed"] == 0
    names = set(res["metrics"])
    assert "setup_s" in names
    assert names & {"output_tok_s", "ttft_p95_ms"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["check"]["tokens_compared"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(quiet_cache, monkeypatch, fault):  # noqa: F811
    FAULTS[fault](monkeypatch)
    res = tiny.run("minitron-4b", "backlog")
    assert not res["correct"], res["check"]


def test_sessions_read_prefix_hits(quiet_cache):  # noqa: F811
    """Multi-turn sessions on the prefix-sharing engine: correct, and the
    prefix_hit_share reader finds shared rows."""
    from chipbench import harness, metrics
    spec = tiny.spec("minitron-4b", "sessions")
    spec["per_layer"] = [{"name": "prefix_hit_share", "unit": "%"}]
    seen = {}
    read = metrics.read

    def keep(name, rec):
        seen[name] = read(name, rec)
        return seen[name]
    metrics.read = keep
    try:
        import time
        res = harness.run(spec, 11, 6.0, True, time.perf_counter(),
                          peaks=tiny.PEAKS, require_tpu=False)
    finally:
        metrics.read = read
    assert res["correct"], res["check"]
    assert 0 < seen["prefix_hit_share"] < 100


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "minitron4b.backlog", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_sweep_reads_the_harness_metrics(quiet_cache, monkeypatch,  # noqa: F811
                                         capsys):
    """The knee sweep on the tiny multi-turn mix: a row per rate, its
    latencies and rate the harness's own, its queue from the steps."""
    import json

    from chipbench import harness, sweep
    build = harness.build
    monkeypatch.setattr(harness, "load_cell",
                        lambda name: tiny.spec("minitron-4b", "sessions"))
    monkeypatch.setattr(harness, "build", lambda *a, **k: build(
        *a, peaks=tiny.PEAKS, require_tpu=False))
    assert sweep.main(["--workload", "tiny", "--seed", "3", "--seconds",
                       "3", "--rates", "0.5,1.5"]) == 0
    rows = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    rows = rows["sweep"]
    assert [r["rate_rps"] for r in rows] == [0.5, 1.5]
    for r in rows:
        assert r["due"] > 0 and r["served"] == r["due"]
        assert r["queue_at_close"] is not None
        assert r["ttft_p95_ms"] > 0 and r["output_tok_s"] > 0
