"""The scoped reduction (``chipbench/scopes.py`` over ``xplane.py``) on the
two recorded TPU traces of record_trace.py: ``tiny_tpu`` from a program
with no scopes or engine spans, ``tiny_tpu_scoped`` from one with both.
The reduction adds to ``trace.reduce`` and changes nothing it returns; the
readers of the program's names find nothing in the first and read values
in range in the second, where every existing reader still finds the
programs and kernels it looks up by name."""

import json
import os

import pytest

from chipbench import harness, metrics, scopes, traffic, xplane
from chipbench import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PLAIN, SCOPED = "tiny_tpu", "tiny_tpu_scoped"
NEW_METRICS = ("decode_matmul_roofline", "decode_plumbing_share.batch",
               "engine_idle_ms.batch")
OLD_METRICS = ("prefill_device_share", "flash_decode_paged_roofline",
               "ssd_roofline", "device_idle_share.batch", "mfu.chat",
               "decode_iter_ms.chat")


def _xplane(stem):
    return os.path.join(DATA, f"{stem}.xplane.pb")


@pytest.fixture(scope="module", params=[PLAIN, SCOPED])
def pair(request):
    """(stem, trace.reduce, scopes.reduce) of one recorded trace."""
    path = _xplane(request.param)
    return request.param, T.reduce(path), scopes.reduce(path)


def _record(stem, r):
    book = json.load(open(os.path.join(DATA, f"{stem}.json")))
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
            t0=0.0, t1=0.0, phase="window", admitted=st["admitted"],
            traced=True,
            tokens={int(u): tuple(v) for u, v in st["tokens"].items()}))
    rec.window1 = r.window_s
    rec.trace = r
    return rec


def test_model_scope():
    ms = scopes.model_scope
    assert ms("jit(decode_chunk)/while/body/while/body/closed_call/"
              "attn_core/jit(flash_decode_paged)/flash_decode_paged/"
              "pallas_call") == "attn_core"
    assert ms("jit(decode_chunk)/while/body/sample/argmax:") == "sample"
    assert ms("jit(prefill_chunk)/while/body/closed_call/jit(silu)/mul:") \
        == scopes.PLUMBING
    # an argument's copy is named after the argument, not a scope
    assert ms("logits:") == scopes.PLUMBING
    assert ms("") == scopes.UNSCOPED


def test_scope_for_picks_the_program():
    paths = ["jit(prefill_chunk)/attn_proj/dot_general:",
             "jit(decode_chunk)/while/body/mlp/dot_general:"]
    assert xplane.scope_for(paths, "jit_decode_chunk") == paths[1]
    assert xplane.scope_for(paths, "jit_prefill_chunk") == paths[0]
    assert xplane.scope_for(paths[:1], "jit_decode_chunk") == paths[0]
    assert xplane.scope_for([], "jit_decode_chunk") == ""


def test_idle_split_by_the_innermost_span():
    """Two steps over one op each; idle time named by the shortest span
    around it, ``none`` where only the step covers it."""
    ops = [T.Op("fusion", "jit_decode_chunk", 20, 30, 0),
           T.Op("fusion", "jit_decode_chunk", 130, 50, 0)]
    serve = [("serve.step", 0, 100, {}), ("serve.admit", 0, 15, {"uid": 1}),
             ("serve.prefill", 5, 5, {}), ("serve.fetch", 50, 40, {}),
             ("serve.step", 100, 100, {}), ("serve.decode", 100, 20, {})]
    r = scopes.Scoped(window=(0, 200), ops=ops, modules=[], host=[],
                      n_devices=1, scopes=["", ""], serve=serve)
    per_step, split = r.step_idle()
    assert per_step == [70, 50]
    assert split == {"serve.admit": 10, "serve.prefill": 5, "none": 45,
                     "serve.fetch": 40, "serve.decode": 20}
    assert sum(split.values()) == sum(per_step)


@pytest.mark.parametrize("stem", [PLAIN, SCOPED])
def test_tf_op_paths_for_the_decode_program(stem):
    """Nearly every XLA op the decode program ran has a scope path."""
    from jax.profiler import ProfileData
    path = _xplane(stem)
    found = xplane.scopes(path)
    n = hit = 0
    for plane in ProfileData.from_file(path).planes:
        if T._device_index(plane.name) is None:
            continue
        lines = {line.name: line for line in plane.lines}
        mods = sorted(((T._SUFFIX.sub("", e.name), int(e.start_ns),
                        int(e.duration_ns), 0)
                       for e in lines["XLA Modules"].events),
                      key=lambda m: m[1])
        for ev in lines["XLA Ops"].events:
            if T._module_at(mods, int(ev.start_ns)) == "jit_decode_chunk":
                n += 1
                hit += bool(found[plane.name].get(ev.name))
    assert n > 1000
    assert hit >= 0.95 * n


def test_scoped_reduction_changes_nothing_of_the_reduction(pair):
    _, base, r = pair
    assert isinstance(r, T.Reduced)
    assert (r.window, r.ops, r.modules, r.host, r.n_devices) == (
        base.window, base.ops, base.modules, base.host, base.n_devices)
    assert r.top_ops(10) == base.top_ops(10)
    assert r.idle_gaps(10) == base.idle_gaps(10)
    assert len(r.scopes) == len(r.ops)


def test_existing_readers_find_programs_and_kernels(pair):
    _, base, r = pair
    for mod in ("jit_decode_chunk", "jit_prefill_chunk"):
        assert r.module_s(mod) > 0
        assert r.op_s("flash_decode_paged", mod) > 0
    assert r.op_s("ssd", "jit_prefill_chunk") > 0
    assert r.op_s("cache_update_paged", "jit_decode_chunk") > 0
    gaps = r.idle_gaps(10)
    assert any(name == "chipbench.step" for name, _ in gaps)
    assert all(name.startswith(T.HOST_PREFIX) or name == "no harness span"
               for name, _ in gaps)


def test_readers_of_the_program_names(pair):
    stem, base, r = pair
    rec = _record(stem, r)
    for name in OLD_METRICS:
        val = metrics.read(name, rec)
        assert val is not None and val > 0, (name, val)
    new = {name: metrics.read(name, rec) for name in NEW_METRICS}
    if stem == PLAIN:
        assert new == dict.fromkeys(NEW_METRICS)
        return
    assert 0 < new["decode_matmul_roofline"] <= 100
    assert 0 <= new["decode_plumbing_share.batch"] < 100
    assert new["engine_idle_ms.batch"] > 0
    # the program's old readers read the same on the plain reduction
    rec_base = _record(stem, base)
    for name in OLD_METRICS:
        assert metrics.read(name, rec_base) == metrics.read(name, rec)
    for name in NEW_METRICS:
        assert metrics.read(name, rec_base) is None


def test_scoped_trace_names_the_decode_program():
    """Where the program names its parts: every model part the hybrid
    decode step runs is found, and the engine's steps hold its spans."""
    r = scopes.reduce(_xplane(SCOPED))
    by = r.scope_s("jit_decode_chunk")
    for s in ("embed", "norm", "attn_proj", "attn_core", "mlp", "ssm",
              "logits", "sample"):
        assert by.get(s, 0.0) > 0, s
    per_step, split = r.step_idle()
    assert per_step and set(split) <= {n for n, *_ in r.serve} | {"none"}
    admits = [a for n, _, _, a in r.serve if n == "serve.admit"]
    assert {a["uid"] for a in admits} >= {0, 1}
