"""Every name in ``BENCHMARK.json`` resolves to its files, and the file
keeps the benchmark contract's shape."""
import json
import re

import pytest

from chip import spec as S

BENCH = S.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
NUMBERS = {"loss0", "loss1", "dparam", "dparam_med", "dparam_client"}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((S.REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_lines():
    entries = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
               + BENCH["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k], e
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
    for group in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))


def test_configs_resolve_and_are_used():
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
        assert c["file"] not in files
        files.add(c["file"])
        cfg = S.config(c["name"])
        assert cfg["name"] == c["name"]
        assert hasattr(S.reference(cfg), "sfpl_round")
        fam = S.family(cfg)
        for attr in ("build", "make_inputs", "train_flops_per_sample",
                     "SPLIT", "tiny"):
            assert hasattr(fam, attr), (cfg["family"], attr)
        for k in c["reduced"]:
            assert k in cfg and NAME.match(k)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell = S.cell(name)
    assert cell["chips"] in (1, 4)
    assert cell["limits"], "a cell compares at least one number"
    assert set(cell["limits"]) <= NUMBERS
    assert cell["traffic"]["engine"] in ("single", "sharded")
    reported = {m["name"] for m in cell["end_to_end"]}
    assert {"setup_s", "samples_per_s"} <= reported
    assert cell["per_layer"], "every cell reports a per-layer metric"


def test_four_chip_cells_at_most_half():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_bounds():
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=[m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_has_a_reader_and_moves_a_reported_metric(metric):
    assert callable(S.reader(metric["name"]).read)
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    moves = [m for m in BENCH["end_to_end"] if m["name"] == metric["moves"]]
    assert moves, metric["moves"]
    for cell in metric.get("workloads", CELLS):
        assert cell in CELLS
        assert S.applies(moves[0], cell), (metric["name"], cell)


def test_traffic_files_are_data():
    for w in BENCH["workloads"]:
        path = S.HERE / "traffic" / f"{w['traffic']}.json"
        json.loads(path.read_text())
