"""The metric arithmetic: operation and byte counts against hand counts at
minitron-4b's and the repo's zamba2's shapes, percentiles over all
requests, and the readers on a small hand-made record."""

import json
import os
import types

import pytest

from chipbench import flops, harness, metrics, traffic

CONF = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")
MINI = json.load(open(os.path.join(CONF, "minitron4b.json")))["model"]
ZAM = json.load(open(os.path.join(os.path.dirname(__file__), "data",
                                  "zamba2_repo.json")))["model"]


def test_minitron_weights_per_token_hand_count():
    # per layer: q 3072x3072, k and v 3072x1024, o 3072x3072, squared-ReLU
    # MLP up 3072x9216 and down 9216x3072
    layer = 3072 * 3072 * 2 + 2 * 3072 * 1024 + 2 * 3072 * 9216
    assert layer == 81_788_928
    assert flops.matmul_weights_per_token(MINI) == 32 * layer
    # with the embedding, the unembedding and 2 x (scale, bias) norms per
    # layer plus the final norm, the program's parameter count
    total = 32 * layer + 2 * 256000 * 3072 + 32 * 4 * 3072 + 2 * 3072
    assert total == 4_190_509_056


def test_zamba2_weights_per_token_hand_count():
    d, din, N, H = 2048, 4096, 64, 64
    mamba = d * (2 * din + 2 * N + H) + din * d
    shared = (4096 * 2048 * 3 + 2048 * 2048 + 4096 * 64 + 64 * 2048
              + 2 * 4096 * 8192 + 8192 * 2048)
    assert flops.matmul_weights_per_token(ZAM) == 38 * mamba + 6 * shared


def test_attention_and_decode_counts():
    assert flops.attention_pairs(0, 4) == 10
    assert flops.attention_pairs(100, 1) == 101
    # QK^T and PV: 4 flops per pair, head and head dim, per layer
    assert flops.attention_flops(MINI, 1) == 4 * 24 * 128 * 32
    assert flops.attention_flops(ZAM, 1) == 4 * 32 * 64 * 6
    # one decoded token at 1,000 rows: 2 x weights + attention + logits
    want = (2 * flops.matmul_weights_per_token(MINI)
            + 4 * 1000 * 24 * 128 * 32 + 2 * 3072 * 256000)
    assert flops.decode_flops(MINI, 1000) == want
    # paged decode bytes: K and V rows (8 kv heads x 128, bf16) + q + out
    assert flops.decode_attention_bytes(MINI, 1000) == \
        32 * 2 * (2 * 1000 * 8 * 128 + 2 * 24 * 128)


def test_ssd_counts():
    assert flops.ssd_chunk(ZAM, 256) == 128
    assert flops.ssd_chunk(ZAM, 32) == 32
    per_row_head = 2 * (128 * 64 + 128 * 64 + 2 * 64 * 64)
    assert flops.ssd_flops(ZAM, 256) == per_row_head * 64 * 256 * 38
    assert flops.ssd_flops(MINI, 256) == 0.0
    per_layer = 2 * 256 * (2 * 64 * 64 + 64 + 2 * 64) + 4 * 2 * 64 * 64 * 64
    assert flops.ssd_bytes(ZAM, 256) == per_layer * 38


def test_chunk_plan_matches_the_engine():
    from repro.serving.scheduler import chunk_plan
    for n in (1, 13, 255, 256, 511, 563, 3072):
        assert flops.chunk_plan(n, 256) == chunk_plan(n, 256)
    assert flops.chunk_plan(511, 256) == [256, 128, 64, 32, 16, 8, 4, 2, 1]


def test_percentile_over_all_requests():
    assert harness.percentile(list(range(1, 101)), 95) == pytest.approx(95.05)
    assert harness.percentile([5.0], 95) == 5.0
    # a request that never got a token counts with its wait
    assert harness.percentile([1.0] * 19 + [1000.0], 95) == pytest.approx(
        1.0 + 0.05 * 999.0)


def _record(prompts, steps, serve=None, model=MINI):
    rec = harness.Record(name="t", conf={}, model=model,
                         serve=serve or {"batch": 4, "prefill_chunk": 256},
                         peaks={"bf16_flops": 197e12,
                                "hbm_bytes_per_s": 819e9},
                         seed=0, seconds=1.0)
    for uid, plen in prompts.items():
        rec.reqs[uid] = harness.Req(
            spec=traffic.Spec(uid=uid, prompt=[0] * plen, max_new_tokens=8),
            request=None, due=0.0)
    t = 0.0
    for admitted, tokens in steps:
        rec.steps.append(harness.Step(t0=t, t1=t + 0.5, phase="window",
                                      admitted=admitted,
                                      tokens=tokens, traced=True))
        t += 0.5
    rec.window0, rec.window1 = 0.0, t
    return rec


def test_readers_on_a_record():
    rec = _record({0: 100, 1: 300},
                  [([0, 1], {0: (0, 8), 1: (0, 8)}),
                   ([], {0: (8, 4)})])
    assert metrics.read("slot_occupancy.batch", rec) == pytest.approx(
        100 * 3 / 8)
    # no trace: the device readers find nothing and say so
    for name in ("decode_iter_ms.batch", "prefill_device_share",
                 "flash_decode_paged_roofline", "ssd_roofline",
                 "device_idle_share.batch"):
        assert metrics.read(name, rec) is None
    assert metrics.read("mfu.batch", rec) is None
    # mfu takes its window from the trace: the harness span, 1 s here
    rec.trace = types.SimpleNamespace(window_s=1.0)
    got = metrics.read("mfu.batch", rec)
    want = sum(flops.decode_flops(MINI, p + k) for p, n in
               ((100, 12), (300, 8)) for k in range(1, n + 1))
    # prompts split 100 -> 64 + 32 + 4 and 300 -> 256 + 32 + 8 + 4
    for chunks in ([(0, 64), (64, 32), (96, 4)],
                   [(0, 256), (256, 32), (288, 8), (296, 4)]):
        want += sum(flops.prefill_flops(MINI, o, n) for o, n in chunks)
    want += 2 * flops.logits_flops(MINI, 1)
    assert got == pytest.approx(100 * want / (1.0 * 197e12))
    rec.trace = None
    assert metrics.read("compile_s", rec) == 0.0
    assert metrics.read("prefix_hit_share", rec) is None


def test_every_per_layer_metric_has_a_reader():
    bench = json.load(open(harness.BENCHMARK))
    for m in bench["per_layer"]:
        assert os.path.exists(metrics.reader_path(m["name"]))
    assert os.path.exists(metrics.reader_path("prefix_hit_share"))
