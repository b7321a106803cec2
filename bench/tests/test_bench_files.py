"""BENCHMARK.json resolves to the benchmark's files by name, and every
name, unit and field keeps to the benchmark's format."""
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)


def _one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_bench_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1] == "bench/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_bench_config_resolves(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and _one_line(cfg["why"])
    assert cfg["file"] == f"bench/configs/{cfg['name']}.json"
    with open(os.path.join(ROOT, cfg["file"])) as f:
        sizes = json.load(f)
    assert sizes["name"] == cfg["name"]
    assert os.path.exists(os.path.join(BENCH, "configs", cfg["name"] + ".ref.py"))
    for key in cfg["reduced"]:
        assert NAME.match(key) and key in sizes
        assert not key.endswith(("_dim", "_rank", "_size"))


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_bench_cell_resolves(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert cell["chips"] in (1, 4) and _one_line(cell["why"])
    assert cell["config"] in {c["name"] for c in SPEC["configs"]}
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    assert os.path.exists(os.path.join(BENCH, "entries", traffic["entry"] + ".py"))
    with open(os.path.join(BENCH, "limits", cell["name"] + ".json")) as f:
        limits = json.load(f)
    assert limits and all(v >= 0 for v in limits.values())


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_bench_metric_resolves(metric):
    cells = {c["name"] for c in SPEC["workloads"]}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert set(metric.get("workloads", [])) <= cells
    if metric in SPEC["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["moves"] in {m["name"] for m in SPEC["end_to_end"]}
        assert _one_line(metric["layer"]) and metric["workloads"]
        assert os.path.exists(os.path.join(BENCH, "metrics", metric["name"] + ".py"))
        e2e = next(m for m in SPEC["end_to_end"] if m["name"] == metric["moves"])
        assert set(metric["workloads"]) <= set(e2e.get("workloads", cells))


def test_bench_every_cell_reports_setup_and_a_layer():
    from_layer = {w for m in SPEC["per_layer"] for w in m["workloads"]}
    for cell in SPEC["workloads"]:
        e2e = [m["name"] for m in SPEC["end_to_end"]
               if cell["name"] in m.get("workloads", [cell["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell["name"] in from_layer


def test_bench_exits_nonzero_without_a_tpu():
    cell = SPEC["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "no TPU" in p.stderr
