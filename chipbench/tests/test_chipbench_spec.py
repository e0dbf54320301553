"""BENCHMARK.json and the files it names: the benchmark's contract, and
the configuration files against the program's registry."""

import json
import os
import re

import pytest

from chipbench import harness, metrics, traffic
from chipbench import reference as R

BENCH = json.load(open(harness.BENCHMARK))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits its 12-hour allowance
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_units_and_entries():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({e["name"] for e in BENCH[k]}) == len(BENCH[k])
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    setup = {m["name"]: m for m in BENCH["end_to_end"]}["setup_s"]
    assert setup["bound"] == 0.25 and "workloads" not in setup
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_cell_reports_its_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for cell in BENCH["workloads"]:
        spec = harness.load_cell(cell["name"])
        got = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in got and len(got) >= 2
        assert spec["per_layer"]
        for m in spec["per_layer"]:
            assert cell["name"] in e2e[m["moves"]].get(
                "workloads", [cell["name"]])
            assert os.path.exists(metrics.reader_path(m["name"]))
        assert cell["chips"] == 1 and len(cell["why"]) <= 200
        traffic.load(cell["traffic"])


# keys that set a width: never cut (a cut configuration cuts depth)
WIDTHS = {"d_model", "d_ff", "n_heads", "n_kv_heads", "head_dim",
          "ssm_state", "ssm_headdim", "ssm_expand", "shared_attn_lora",
          "top_k", "frontend_dim"}


def check_config(entry: dict, conf: dict) -> None:
    """The configuration file ``conf`` (BENCHMARK.json's ``entry`` for it)
    is the model the program runs, every cut from the published model is
    stated, and the reference computes all of it (its kind's ``check``)."""
    import dataclasses

    from repro.configs.registry import get_model_config
    model, reduced = conf["model"], conf["reduced"]
    assert reduced == entry["reduced"]
    assert conf["source"] == entry["source"]
    for key in ("arch", "deployment", "assumed", "serve", "check"):
        assert key in conf
    assert R.KEY not in model        # the program gets the section whole
    published = conf.get("published", {})
    for key in reduced:
        assert key in model, key
        assert key in published, f"{key}: no published value"
        assert published[key] != model[key], key
        assert key not in WIDTHS and not key.endswith(
            ("_dim", "_rank", "_size")), f"{key} is a width"
    if reduced:
        # which chips would hold what was cut
        dep = conf["deployment"]
        assert isinstance(dep, dict) and dep.get("chips", 0) >= 2, dep
        assert dep.get("this_chip") and dep.get("other_chips"), dep
    else:
        assert isinstance(conf["deployment"], str) and not published
    reg = dataclasses.asdict(get_model_config(conf["arch"]))
    for key, val in model.items():
        # a cut key: the registry holds the cut or the published value
        want = (val, published[key]) if key in reduced else (val,)
        assert reg[key] in want, key
    # every key that shapes the model and differs from the defaults is
    # in the file, so the reference sees what the program runs
    run = dataclasses.asdict(harness.model_config(conf))
    assert run == dict(reg, **{k: model[k] for k in reduced})
    R.kind(R.model_view(conf)).check(run)


def test_config_files_are_the_models_the_program_runs():
    for entry in BENCH["configs"]:
        assert entry["file"].startswith("chipbench/configs/")
        conf = json.load(open(os.path.join(harness.chipbench.ROOT,
                                           entry["file"])))
        check_config(entry, conf)


def _cut(fault: str):
    """minitron4b.json cut to its first 16 layers, as one of two pipeline
    stages, with ``fault`` planted in what the file states."""
    conf = json.load(open(os.path.join(harness.chipbench.ROOT,
                                       "chipbench/configs/minitron4b.json")))
    conf["model"]["n_layers"] = 16
    conf["reduced"] = ["n_layers"]
    conf["published"] = {"n_layers": 32}
    conf["deployment"] = {
        "chips": 2, "this_chip": "layers 0-15, every width as published",
        "other_chips": "layers 16-31 on a second v5e, pipeline stage 2"}
    if fault == "no_published_value":
        del conf["published"]
    elif fault == "published_value_is_the_cut":
        conf["published"] = {"n_layers": 16}
    elif fault == "deployment_unsaid":
        conf["deployment"] = "one v5e"
    elif fault == "width_cut":
        conf["model"]["d_ff"] = 4608
        conf["reduced"].append("d_ff")
        conf["published"]["d_ff"] = 9216
    elif fault == "key_not_in_model":
        conf["reduced"].append("n_experts")
        conf["published"]["n_experts"] = 8
    entry = {"name": "cut", "source": conf["source"],
             "reduced": conf["reduced"]}
    return entry, conf


@pytest.mark.parametrize("fault", ["none", "no_published_value",
                                   "published_value_is_the_cut",
                                   "deployment_unsaid", "width_cut",
                                   "key_not_in_model"])
def test_cut_configuration_is_admitted_only_as_stated(fault):
    entry, conf = _cut(fault)
    if fault == "none":
        check_config(entry, conf)
        return
    with pytest.raises(AssertionError):
        check_config(entry, conf)


@pytest.mark.parametrize("arch,kind,fits", [
    ("minitron-4b", "dense", True), ("llama3.2-3b", "dense", True),
    ("gemma2-2b", "dense", False), ("olmoe-1b-7b", "dense", False),
    ("zamba2-1.2b", "dense", False), ("zamba2-1.2b", "hybrid", True),
    ("minitron-4b", "hybrid", False), ("mamba2-370m", "hybrid", False)])
def test_kind_check_refuses_what_its_reference_does_not_compute(
        arch, kind, fits):
    """Softcaps, windows, post-norms and experts (gemma2, olmoe), another
    family's blocks: the kind's ``check`` raises on each."""
    import dataclasses

    from repro.configs.registry import get_model_config
    run = dataclasses.asdict(get_model_config(arch))
    mod = R.kind({"family": kind})
    if fits:
        mod.check(run)
    else:
        with pytest.raises(ValueError):
            mod.check(run)


def test_peaks_table_has_the_v5e():
    p = harness.load_peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    try:
        harness.load_peaks("TPU v9 imaginary")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")
