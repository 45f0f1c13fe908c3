"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
finds its file: configuration module and JSON, traffic mix, metric reader."""
import importlib
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(SPEC["command"]) <= 32
    for word in SPEC["command"][1:]:
        assert any(word.startswith(p + "/") for p in SPEC["paths"])
        assert (ROOT / word).is_file()


def test_names_and_units():
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[kind]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((kind, entry["name"]))
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
    assert len(names) == len(set(names))
    for w in SPEC["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    path = ROOT / cfg["file"]
    assert path.is_file() and cfg["file"].startswith("bench/")
    data = json.loads(path.read_text())
    assert data["reduced"] == cfg["reduced"]
    mod = importlib.import_module(f"bench.configs.{cfg['name']}")
    for fn in ("make_inputs", "solver", "control", "check", "work"):
        assert callable(getattr(mod, fn))
    assert any(w["config"] == cfg["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    traffic = json.loads((ROOT / "bench" / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    assert traffic["n_traj"] > 0 and traffic["limits"]
    assert cell["config"] in {c["name"] for c in SPEC["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert pairs.count((cell["config"], cell["traffic"])) == 1


def test_metrics():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in SPEC["workloads"]}
    layers = set()
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", cells)) <= cells
        layers.add(m["layer"])
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        assert callable(mod.read)
    assert {"device", "lane layout", "kernel", "engines"} <= layers


def test_mosaic_check_reads_the_custom_call():
    from bench.checks import holds_mosaic_kernel
    assert holds_mosaic_kernel(
        '%k = f32[3] custom-call(%a), custom_call_target="tpu_custom_call"')
    assert not holds_mosaic_kernel("%k = f32[3] fusion(%a), kind=kLoop")
