"""Cells, traffic mixes, configurations and metric readers are found by
name, and BENCHMARK.json keeps to its shape."""

import json
import re

import pytest

from harness import registry
from harness.common import BENCH_DIR, ROOT

import smoke

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCH = smoke.benchmark()


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["perfbench"]
    assert BENCH["command"][1].startswith("perfbench/")
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("w", [w["name"] for w in BENCH["workloads"]])
def test_cell_found_by_name(w):
    cell = registry.load_cell(w, BENCH)
    assert cell.runner().run
    assert hasattr(cell.reference, "hidden_states")
    assert cell.config["head"]["pattern_seed"] >= 0
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in names
    for m in cell.end_to_end + cell.per_layer:
        assert registry.metric_reader(m["name"]).read


def test_names_units_and_files():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for entry in BENCH["configs"] + BENCH["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        spec = json.loads((ROOT / c["file"]).read_text())
        assert spec["name"] == c["name"]
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_a_new_cell_is_new_entries_only(tmp_path):
    """A cell that pairs an existing configuration with an existing mix
    under a new name is found without touching any file."""
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "mamba2-2.7b.chat",
                               "config": "mamba2-2.7b", "traffic": "chat",
                               "chips": 1, "why": "x"})
    cell = registry.load_cell("mamba2-2.7b.chat", bench)
    assert cell.traffic["kind"] == "serve"
    assert cell.reference.model_config_kwargs(cell.config)["family"] == "ssm"
    assert not cell.per_layer or all(
        "workloads" in m for m in cell.per_layer)


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        registry.load_cell("no-such.cell", BENCH)


def test_every_reader_file_has_an_entry():
    names = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    files = {p.stem if p.suffix == ".py" else p.name
             for p in (BENCH_DIR / "metrics").glob("*.py")}
    assert files == names
