"""BENCHMARK.json against the benchmark's contract, and the files it
names (CPU only; nothing here touches a chip)."""
import json
import pathlib
import re

import pytest

from chipbench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"]: m for m in MANIFEST["end_to_end"]}
CELLS = {c["name"]: c for c in MANIFEST["workloads"]}


def reported_in(metric: dict) -> list[str]:
    return metric.get("workloads", list(CELLS))


def test_top_level_keys_and_command():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["chipbench"]
    cmd = MANIFEST["command"]
    assert 1 <= len(cmd) <= 32 and cmd[1].startswith("chipbench/")
    assert all(not w.startswith("/") and ".." not in w for w in cmd)
    assert (ROOT / cmd[1]).is_file()


def test_run_seconds_fit_the_check_with_24_cells():
    rs = MANIFEST["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("entry", [*MANIFEST["configs"],
                                   *MANIFEST["workloads"],
                                   *MANIFEST["end_to_end"],
                                   *MANIFEST["per_layer"]],
                         ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"]), entry["name"]
    if "unit" in entry:
        assert UNIT.match(entry["unit"]), entry["unit"]
        assert entry["better"] in ("lower", "higher")
    texts = [entry[k] for k in ("why", "layer") if k in entry]
    if "file" in entry:                 # a configuration's source
        texts.append(entry["source"])
    for text in texts:
        assert 1 <= len(text) <= 200 and "\n" not in text \
            and "\t" not in text


def test_unique_names_and_pairs():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in MANIFEST["end_to_end"]
               + MANIFEST["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(c["config"], c["traffic"]) for c in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cfg", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_file_and_use(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert cfg["file"] == f"chipbench/configs/{cfg['name']}.json"
    body = json.loads((ROOT / cfg["file"]).read_text())
    assert body["source"] == cfg["source"]
    assert body["reduced"] == cfg["reduced"]
    assert isinstance(body["assumed"], list)
    assert any(c["config"] == cfg["name"] for c in MANIFEST["workloads"])
    assert all(NAME.match(k) for k in cfg["reduced"])


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda c: c["name"])
def test_cell_files_and_reports(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4)
    assert (ROOT / "chipbench/traffic" / f"{cell['traffic']}.json").is_file()
    config = json.loads((ROOT / "chipbench/configs"
                         / f"{cell['config']}.json").read_text())
    assert (ROOT / "chipbench/deploy" / f"{config['family']}.py").is_file()
    e2e = [m["name"] for m in harness.metrics_of(MANIFEST, cell["name"],
                                                 False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_of(MANIFEST, cell["name"], True)


def test_four_chip_cells_at_most_half():
    four = sum(c["chips"] == 4 for c in MANIFEST["workloads"])
    assert four <= max(1, len(MANIFEST["workloads"]) // 2)


def test_end_to_end_bounds_and_sources():
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert all(c in CELLS for c in reported_in(m))


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_moves_a_metric_of_each_of_its_cells(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    moved = E2E[metric["moves"]]
    for cell in metric.get("workloads", reported_in(moved)):
        assert cell in CELLS and cell in reported_in(moved)
    assert harness.reader(metric["name"]) is not None


def test_layers_are_named_alike_and_listed_in_perf_md():
    perf = (ROOT / "PERF.md").read_text()
    for layer in {m["layer"] for m in MANIFEST["per_layer"]}:
        assert f"`{layer}`" in perf, layer
