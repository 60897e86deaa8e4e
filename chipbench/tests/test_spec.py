"""``BENCHMARK.json`` and the files it names: every name, unit and file
in the form the benchmark requires, found by name with no code edit."""
from __future__ import annotations

import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "chipbench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head",
               "expansion", "experts_per_tok", "num_experts_per_tok")


@pytest.fixture(scope="module")
def spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    assert spec["paths"] == ["chipbench"]
    assert spec["command"][1].startswith("chipbench/")
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def _names(spec):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in spec[group]:
            yield group, e["name"]


def test_names_and_units(spec):
    seen = set()
    for group, name in _names(spec):
        assert NAME.match(name), name
        assert (group, name) not in seen
        seen.add((group, name))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for c in spec["workloads"]:
        assert NAME.match(c["config"]) and NAME.match(c["traffic"])
        assert c["chips"] in (1, 4)
        assert 1 <= len(c["why"]) <= 200 and "\n" not in c["why"]


def test_end_to_end_bounds(spec):
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}


def _reported(spec, cell):
    return {m["name"] for m in spec["end_to_end"]
            if cell in m.get("workloads", [cell])}


def test_every_cell_reports_enough(spec):
    for c in spec["workloads"]:
        e2e = _reported(spec, c["name"])
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = [m for m in spec["per_layer"]
                 if c["name"] in m.get("workloads", [c["name"]])]
        assert layer, c["name"]


def test_per_layer_moves_are_reported(spec):
    """Each per-layer metric moves an end-to-end metric that every one
    of its cells reports, and has a reader file."""
    e2e = {m["name"] for m in spec["end_to_end"]}
    cells = {c["name"] for c in spec["workloads"]}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert m["moves"] in _reported(spec, cell), (m["name"], cell)
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        if m["unit"] == "%" and m["name"].endswith("_roofline"):
            assert m["better"] == "higher"


def test_configs_state_source_reduced_assumed(spec):
    for c in spec["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert c["file"].startswith("chipbench/")
        assert conf["source"] == c["source"]
        assert isinstance(conf["assumed"], dict)
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank", "_size")), key
            assert not any(w in key for w in WIDTH_WORDS), key
        assert (BENCH / "reference" / f"{conf['reference']}.py").is_file()
        assert any(w["config"] == c["name"] for w in spec["workloads"])


def test_cells_find_their_files(spec):
    configs = {c["name"] for c in spec["configs"]}
    pairs = set()
    for c in spec["workloads"]:
        assert c["config"] in configs
        assert (c["config"], c["traffic"]) not in pairs
        pairs.add((c["config"], c["traffic"]))
        traffic = json.loads(
            (BENCH / "traffic" / f"{c['traffic']}.json").read_text())
        assert (BENCH / "generators" / f"{traffic['kind']}.py").is_file()
        limits = json.loads(
            (BENCH / "workloads" / f"{c['name']}.json").read_text())["limits"]
        assert limits and all(isinstance(v, (int, float))
                              for v in limits.values())
    assert sum(c["chips"] == 4 for c in spec["workloads"]) <= max(
        1, len(spec["workloads"]) // 2)


def test_metric_readers_load_and_stay_silent_without_a_trace(spec):
    sys.path.insert(0, str(ROOT))
    from chipbench import harness

    for m in spec["per_layer"]:
        reader = harness.load_module(BENCH / "metrics" / f"{m['name']}.py",
                                     "probe_" + m["name"].replace(".", "_"))
        if m["source"] == "device_trace":
            assert reader.read({"trace": None}) is None


def test_a_cell_added_as_files_is_found(tmp_path, spec):
    """A cell is added by adding a traffic file, a limits file and
    BENCHMARK.json entries: the harness finds it with no code edit."""
    sys.path.insert(0, str(ROOT))
    from chipbench import run as R

    root = tmp_path / "tree"
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    traffic = json.loads((BENCH / "traffic" / "serve-offline.json").read_text())
    traffic.update(prompt_len=128, gen=128)
    (root / "chipbench" / "traffic" / "serve-short.json").write_text(
        json.dumps(traffic))
    (root / "chipbench" / "workloads" / "serve-qwen2-0.5b-short.json").write_text(
        json.dumps({"limits": {"served_logit_gap": 1.0, "short_requests": 0}}))
    new = dict(spec)
    new["workloads"] = spec["workloads"] + [
        {"name": "serve-qwen2-0.5b-short", "config": "qwen2-0.5b",
         "traffic": "serve-short", "chips": 1, "why": "short decode control"}]
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    r = R.load_run("serve-qwen2-0.5b-short", 1, 1.0, False, root=root)
    assert r.traffic["prompt_len"] == 128 and r.conf["hidden_size"] == 896
    assert r.limits["served_logit_gap"] == 1.0
