"""BENCHMARK.json and the files it names: every cell's configuration,
traffic, limits and metric readers exist, names and units keep to their
characters, and every per-layer metric moves an end-to-end metric that each
of its cells reports."""

from __future__ import annotations

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|projection|head|expand)"
                   r"|(_dim|_rank)$|experts_per_tok")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def here(*parts):
    return os.path.join(ROOT, "chipbench", *parts)


def test_keys_and_paths(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "chipbench/run.py"]
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p)) and not p.startswith("/")
    assert 1 <= bench["run_seconds"] <= 51


def test_names_and_units(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in bench[group]]
        assert len(names) == len(set(names))
        for x in bench[group]:
            assert NAME.match(x["name"]), x["name"]
            if "unit" in x:
                assert UNIT.match(x["unit"]), x["unit"]
                assert x["better"] in ("lower", "higher")
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200


def test_every_cell_finds_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        c = configs[w["config"]]
        assert c["file"] == f"chipbench/configs/{w['config']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert os.path.isfile(here("models", cfg["reference"] + ".py"))
        with open(here("traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        dims = [int(x) for x in traffic["mesh"].split("x")]
        assert dims[0] * dims[1] == w["chips"] == traffic["workers"]
        with open(here("limits", w["name"] + ".json")) as f:
            assert set(json.load(f)) == {"loss_gap", "grad_gap", "change_gap",
                                         "state_gap"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.isfile(here("metrics", m["name"] + ".py")), m["name"]


def test_reduced_keys_are_in_the_file_and_no_width(bench):
    """Every key changed from the source is named, with its published value,
    as a cut of depth or chip share or as a departure of the program."""
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert c["reduced"] == cfg["reduced"]
        assert sorted(cfg["cuts"] + list(cfg["departures"])) == sorted(c["reduced"])
        for k in c["reduced"]:
            assert k in cfg and k in cfg["published"] and not WIDTH.search(k)


def test_bounds(bench):
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"] for m in bench["end_to_end"]} >= {"setup_s"}


def test_per_layer_metrics_move_what_their_cells_report(bench):
    cells = [w["name"] for w in bench["workloads"]]

    def reports(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells and reports(e2e[m["moves"]], cell)
    for cell in cells:
        assert sum(reports(m, cell) for m in bench["end_to_end"]) >= 2
        assert any(reports(m, cell) for m in bench["per_layer"])
