"""Every name in BENCHMARK.json obeys the contract's alphabet and resolves to
its files."""

import json
import os
import re

from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
HERE = os.path.join(ROOT, "chipbench")


def test_names_and_units(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0 < m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for c in bench["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])


def test_every_name_resolves_to_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["file"].startswith("chipbench/")
        conf = json.load(open(os.path.join(ROOT, c["file"])))
        assert conf["name"] == c["name"] and conf["source"] == c["source"] and conf["reduced"] == c["reduced"]
        family = conf["family"]
        for kind, name in (("flops", family), ("reference", family + "_ref"), ("correct", family)):
            assert os.path.isfile(os.path.join(HERE, kind, name + ".py")), (kind, name)
        assert set(conf["correct_limits"]) >= {"loss_world_model", "grad_norm_world_model", "param_change_world_model"}
    for w in bench["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4) and len(w["why"]) <= 200
        traffic = json.load(open(os.path.join(HERE, "traffic", w["traffic"] + ".json")))
        assert os.path.isfile(os.path.join(HERE, "entries", traffic["entry"] + ".py"))
    for m in bench["end_to_end"]:
        assert os.path.isfile(os.path.join(HERE, "end_to_end", m["name"] + ".py")), m["name"]
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(HERE, "layers", m["name"] + ".py")), m["name"]
        assert m["moves"] in e2e and set(m.get("workloads", [])) <= cells
    assert all(any(w["config"] == c for w in bench["workloads"]) for c in configs)


def test_run_py_names_no_cell_config_or_metric(bench):
    src = open(os.path.join(HERE, "run.py")).read()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert entry["name"] not in src, entry["name"]
