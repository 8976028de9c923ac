"""BENCHMARK.json against the files it names, and against the contract's
limits that a test can check without a chip."""

import os
import re

import pytest

from perfbench_tiny import ROOT, manifest

from benchmark.lib.family import families_found, load_family
from benchmark.lib.weights import get_leaf

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _metrics(man):
    return man["end_to_end"] + man["per_layer"]


def test_keys_and_limits():
    man = manifest()
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert isinstance(man["run_seconds"], int) and 1 <= man["run_seconds"] <= 51
    assert 1 <= len(man["paths"]) <= 16
    for word in man["command"]:
        assert not word.startswith("/") and ".." not in word
    for e in man["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0 < e["bound"] <= 0.1
        assert e["source"] in {"host_clock", "device_trace"}
    for p in man["per_layer"]:
        assert set(p) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert p["source"] in SOURCES
    assert any(e["name"] == "setup_s" for e in man["end_to_end"])
    four = sum(1 for w in man["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(man["workloads"]) // 4)


def test_names_units_and_lines():
    man = manifest()
    names = [m["name"] for m in _metrics(man)]
    assert len(names) == len(set(names))
    for m in _metrics(man):
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_name_resolves_to_a_file():
    man = manifest()
    configs = {c["name"]: c for c in man["configs"]}
    used = set()
    for w in man["workloads"]:
        conf = configs[w["config"]]
        used.add(w["config"])
        assert any(conf["file"].startswith(p + "/") for p in man["paths"])
        assert os.path.isfile(os.path.join(ROOT, conf["file"]))
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark/traffic", w["traffic"] + ".json"))
    assert used == set(configs), "a configuration that no cell uses"
    for m in _metrics(man):
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark/metrics", m["name"] + ".py")), m["name"]


def test_reduced_lists_what_the_file_says_it_changed():
    import json
    man = manifest()
    widths = ("hidden_size", "intermediate_size", "head_dim")
    for c in man["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert not set(c["reduced"]) & set(widths)
        if cfg["model"] == "gpt":
            assert cfg["hidden_size"] == cfg["num_heads"] * cfg["head_dim"]


def test_every_configuration_names_a_family_that_is_there():
    """``"model"`` in a configuration's file names a directory under
    ``benchmark/families`` that holds all four roles; the family can say the
    shapes and the counts of the configuration without the program."""
    import json
    man = manifest()
    for c in man["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["model"] in families_found(ROOT)
        fam = load_family(ROOT, cfg)
        shapes = fam.weights.leaf_shapes(cfg)
        for name in fam.weights.leaf_names(cfg):
            assert isinstance(get_leaf(shapes, name), tuple), name
        assert fam.needs.weight_bytes(cfg) > 0
        assert fam.needs.train_flops_per_token(cfg, 2048) > 0


def test_moves_and_cells_line_up():
    man = manifest()
    cells = [w["name"] for w in man["workloads"]]
    e2e = {e["name"]: e.get("workloads", cells) for e in man["end_to_end"]}
    for p in man["per_layer"]:
        assert p["moves"] in e2e, p["name"]
        for cell in p.get("workloads", cells):
            assert cell in cells, (p["name"], cell)
            assert cell in e2e[p["moves"]], (p["name"], cell)
    layers = {}
    for p in man["per_layer"]:
        layers.setdefault(p["layer"].lower(), set()).add(p["layer"])
    assert all(len(v) == 1 for v in layers.values())
    for cell in cells:
        assert cell in e2e["setup_s"]
        assert any(cell in ws for n, ws in e2e.items() if n != "setup_s")
        assert any(cell in p.get("workloads", cells)
                   for p in man["per_layer"])
    for m in _metrics(man):
        n = m["name"]
        if n.endswith("_roofline") or "mfu" in re.split(r"[._]", n):
            assert m["unit"] == "%"


@pytest.mark.parametrize("kind", ["configs", "traffic", "metrics", "cells"])
def test_no_stray_cell_file(kind):
    """A cell's own file (mesh, limits) belongs to a cell, or to one that
    PERF.md keeps ready under Open questions; nothing else lives there."""
    d = os.path.join(ROOT, "benchmark", kind)
    assert os.path.isdir(d) and os.listdir(d)
    for f in os.listdir(d):
        assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f
