import json
import os
import re

import pytest

from bench import spec
from bench.tests.conftest import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_units_and_text_use_allowed_characters(bench):
    assert bench["command"][0] == "python3" and len(bench["command"]) <= 32
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and TEXT.match(c["source"])
        assert TEXT.match(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        for k in ("name", "config", "traffic"):
            assert NAME.match(w[k]), w[k]
        assert TEXT.match(w["why"]) and w["chips"] in (1, 4)
    metrics = bench["end_to_end"] + bench["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in bench["per_layer"]:
        assert TEXT.match(m["layer"])
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_every_entry_has_its_files(bench):
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            conf = json.load(f)
        assert all(k in conf for k in c["reduced"])
    for w in bench["workloads"]:
        cell = spec.resolve(REPO, w["name"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m.name for m in cell.end_to_end]
        assert os.path.isfile(os.path.join(REPO, "bench", "limits",
                                           f"{w['name']}.json"))


def test_added_configuration_and_traffic_are_picked_up(tiny_root):
    cell = spec.resolve(tiny_root, "tiny.als_r8")
    assert cell.config["dims"] == [40, 30, 8, 6]
    assert cell.traffic["rank"] == 8
    assert {m.name for m in cell.per_layer} >= {"ec_kernel_ms",
                                                "idle_share"}


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        spec.resolve(REPO, "nell2.als_r32")
