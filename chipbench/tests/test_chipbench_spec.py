"""BENCHMARK.json and the files it names: the benchmark's contract, and
the configuration files against the program's registry."""

import json
import os
import re

from chipbench import harness, metrics, traffic

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


def test_config_files_are_the_models_the_program_runs():
    import dataclasses

    from repro.configs.registry import get_model_config
    for entry in BENCH["configs"]:
        assert entry["file"].startswith("chipbench/configs/")
        conf = json.load(open(os.path.join(harness.chipbench.ROOT,
                                           entry["file"])))
        assert conf["reduced"] == entry["reduced"] == []
        assert conf["source"] == entry["source"]
        for key in ("arch", "deployment", "assumed", "serve", "check"):
            assert key in conf
        reg = dataclasses.asdict(get_model_config(conf["arch"]))
        for key, val in conf["model"].items():
            assert reg[key] == val, key
        # every key that shapes the model and differs from the defaults is
        # in the file, so the reference sees what the program runs
        run = dataclasses.asdict(harness.model_config(conf))
        assert run == reg
        for key in ("attn_softcap", "final_softcap", "query_scale",
                    "local_window", "mrope_sections"):
            assert reg[key] is None
        assert not reg["post_norms"] and not reg["n_experts"]


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
