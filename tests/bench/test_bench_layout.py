"""BENCHMARK.json against the benchmark's rules, and the data-driven
layout: a new configuration, traffic mix, cell and per-layer metric are
found by name as new files, with no edit to a file already there."""
import json
import os
import re
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from bench import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MAN = json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def test_keys_names_and_units():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    metrics = MAN["end_to_end"] + MAN["per_layer"]
    for item in MAN["configs"] + MAN["workloads"] + metrics:
        assert NAME.match(item["name"]), item["name"]
    for w in MAN["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in MAN["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for group in (MAN["configs"], MAN["workloads"], metrics):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    assert "setup_s" in [m["name"] for m in MAN["end_to_end"]]
    assert len(json.dumps(MAN)) < 64 * 1024


def test_files_are_found_for_every_entry():
    for c in MAN["configs"]:
        assert os.path.isfile(os.path.join(REPO, c["file"]))
        assert c["file"].split("/")[0] in MAN["paths"]
    for w in MAN["workloads"]:
        cfg = harness.config_file(MAN, w["config"], REPO)
        assert os.path.isfile(os.path.join(
            REPO, "bench/runners", cfg["runner"] + ".py"))
        harness.traffic_file(w["traffic"])
        harness.limits_file(w["name"])
    for m in MAN["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def test_every_cell_reports_what_its_metrics_move():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    for w in MAN["workloads"]:
        reported = harness.cell_metrics(MAN, w, trace=False)
        names = {m["name"] for m in reported}
        assert "setup_s" in names and len(names) >= 2
        assert harness.cell_metrics(MAN, w, trace=True), w["name"]
    for m in MAN["per_layer"]:
        assert m["moves"] in e2e
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", [w["name"] for w in MAN["workloads"]
                                        if w["name"] in moved.get(
                                            "workloads", [w["name"]])]):
            assert cell in moved.get("workloads", [cell]), (m["name"], cell)


def test_at_most_one_cell_in_four_takes_four_chips():
    four = [w for w in MAN["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in MAN["workloads"])
    assert len(four) <= max(1, len(MAN["workloads"]) // 4)


def test_layers_are_named_alike():
    by_layer = {}
    for m in MAN["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        by_layer.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_layer.values())


def test_new_cell_is_new_files_only(tmp_path):
    """A dummy configuration, traffic mix, per-layer metric and cell are
    added as new files plus new entries; every file already there is
    left byte for byte as it was, and the harness finds the new ones."""
    root = tmp_path / "root"
    shutil.copytree(os.path.join(REPO, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    before = {p: open(p, "rb").read()
              for p in map(str, (root / "bench").rglob("*")) if
              os.path.isfile(p)}
    b = root / "bench"
    (b / "configs/dummy.json").write_text(json.dumps(
        {"name": "dummy", "runner": "train", "hidden_size": 8}))
    (b / "traffic/dummy-mix.json").write_text(json.dumps({"seq_len": 4}))
    (b / "limits/dummy.dummy-mix.json").write_text(json.dumps(
        {"loss_gap": 1.0}))
    (b / "metrics/dummy_ms.py").write_text(
        "def read(rec):\n    return None if rec is None else 1.5\n")
    man = json.load(open(root / "BENCHMARK.json"))
    man["configs"].append({"name": "dummy", "source": "x",
                           "file": "bench/configs/dummy.json",
                           "reduced": [], "why": "x"})
    man["workloads"].append({"name": "dummy.dummy-mix", "config": "dummy",
                             "traffic": "dummy-mix", "chips": 1,
                             "why": "x"})
    man["per_layer"].append({"name": "dummy_ms", "unit": "ms",
                             "better": "lower", "source": "host_clock",
                             "layer": "dummy", "moves": "setup_s",
                             "workloads": ["dummy.dummy-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    for p, data in before.items():
        assert open(p, "rb").read() == data, p

    m = harness.manifest(str(root))
    w = harness.workload(m, "dummy.dummy-mix")
    assert harness.config_file(m, w["config"], str(root))["hidden_size"] == 8
    assert harness.traffic_file(w["traffic"], str(b))["seq_len"] == 4
    assert harness.limits_file(w["name"], str(b)) == {"loss_gap": 1.0}
    layer = harness.cell_metrics(m, w, trace=True)
    assert [x["name"] for x in layer] == ["dummy_ms"]
    assert harness.metric_reader("dummy_ms", str(b))({}) == 1.5
    assert harness.runner("train", str(b)).run_cell


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        harness.workload(MAN, "no-such-cell")
    with pytest.raises(KeyError):
        harness.config_file(MAN, "no-such-config", REPO)
