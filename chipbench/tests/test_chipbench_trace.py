"""The trace reduction on a small TPU trace recorded by record_trace.py
(zamba2 at its widths, one period of layers, a few engine steps): the
programs and kernels are found by name, busy time stays inside the window,
idle gaps are named by the harness span, and the per-layer readers give
shares inside (0, 100]."""

import json
import os

import pytest

from chipbench import harness, metrics, traffic
from chipbench import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
XPLANE = os.path.join(DATA, "tiny_tpu.xplane.pb")
BOOK = os.path.join(DATA, "tiny_tpu.json")


@pytest.fixture(scope="module")
def reduced():
    return T.reduce(XPLANE)


def test_base_name():
    assert T.base_name("%flash_decode_paged.6 = bf16[32,24,128] "
                       "custom-call(s32[1] %x)") == "flash_decode_paged"
    assert T.base_name("%while.3 = (s32[]) while(...)") == "while"
    assert T.base_name("%copy-start.45 = (bf16[2])") == "copy-start"
    assert T.base_name("%fusion = f32[8] fusion(...)") == "fusion"


def test_programs_and_kernels_found(reduced):
    r = reduced
    assert r.n_devices == 1
    assert 0 < r.busy_s() <= r.window_s
    assert r.module_s("jit_decode_chunk") > 0
    assert r.module_s("jit_prefill_chunk") > 0
    assert r.op_s("flash_decode_paged", "jit_decode_chunk") > 0
    assert r.op_s("flash_decode_paged", "jit_prefill_chunk") > 0
    assert r.op_s("ssd", "jit_prefill_chunk") > 0
    assert r.op_s("ssd", "jit_decode_chunk") == 0
    names = [n for n, _ in r.top_ops(100)]
    assert not set(names) & T.CONTAINERS
    assert sum(s for _, s in r.top_ops(1000)) >= r.busy_s()


def test_idle_gaps_named_by_harness_spans(reduced):
    gaps = reduced.idle_gaps(10)
    assert gaps
    idle = reduced.window_s - reduced.busy_s()
    assert sum(s for _, s in gaps) <= idle + 1e-9
    for name, s in gaps:
        assert s > 0
        assert name.startswith(T.HOST_PREFIX) or name == "no harness span"
    assert any(name == "chipbench.step" for name, _ in gaps)


def _record(r):
    book = json.load(open(BOOK))
    rec = harness.Record(name="tiny", conf={}, model=book["model"],
                         serve=book["serve"],
                         peaks={"bf16_flops": 197e12,
                                "hbm_bytes_per_s": 819e9},
                         seed=1, seconds=0.0)
    for uid, plen in book["prompts"].items():
        rec.reqs[int(uid)] = harness.Req(
            spec=traffic.Spec(uid=int(uid), prompt=[0] * plen,
                              max_new_tokens=20),
            request=None, due=0.0)
    for st in book["steps"]:
        rec.steps.append(harness.Step(
            t0=0.0, t1=0.0, phase="window",
            admitted=st["admitted"], traced=True,
            tokens={int(u): tuple(v) for u, v in st["tokens"].items()}))
    rec.window1 = r.window_s
    rec.trace = r
    return rec


def test_readers_on_the_recorded_trace(reduced):
    rec = _record(reduced)
    it = metrics.read("decode_iter_ms.chat", rec)
    assert it is not None and it > 0
    for name in ("prefill_device_share", "flash_decode_paged_roofline",
                 "ssd_roofline", "device_idle_share.batch", "mfu.chat"):
        val = metrics.read(name, rec)
        assert val is not None and 0 < val <= 100, (name, val)
