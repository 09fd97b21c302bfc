"""The benchmark's files are found by name, a run needs the chip, and a later
cell, mix or metric is added by adding files."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import harness

ROOT = harness.ROOT


def _env():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_every_name_has_its_file():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (harness.BENCH / "references" / f"{cell.config['reference']}.py").exists()
        assert cell.limits is not None, f"{w['name']} has no limits file"
        assert cell.per_layer and cell.end_to_end
    for m in spec["per_layer"]:
        reader = harness.load_module(harness.BENCH / "metrics" / f"{m['name']}.py")
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (m["layer"], m["unit"], m["moves"])
    for m in spec["end_to_end"]:
        reader = harness.load_module(harness.BENCH / "metrics" / f"{m['name']}.py")
        assert reader.UNIT == m["unit"]


def test_unknown_straggler_kind_refused():
    with pytest.raises(ValueError, match="straggler kind"):
        harness.stragglers({"kind": "fualt", "count": 1})


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    name = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", name, "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "correct" not in p.stdout


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    name = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", name, "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "correct" not in p.stdout


NEW_METRIC = '''"""A metric added by a later change: steps in the window."""

LAYER = "trainer loop"
UNIT = "steps"
MOVES = "useful_tokens_per_s"


def read(ctx):
    return float(len(ctx["steps_s"]))
'''

DRIVE = '''
import json, sys, time
sys.path.insert(0, "bench"); sys.path.insert(0, "bench/tests")
import harness, tiny
cell = harness.load_cell("tiny.added")
cell = tiny.shrink(cell)
r = harness.run(cell, 5, 0.5, True, t_start=time.perf_counter(), require_chip=False)
print(json.dumps(r))
'''


def test_a_new_mix_and_metric_are_picked_up_from_new_files(tmp_path):
    """Copy the checkout, add a traffic mix, a metric reader, a limits file
    and the entries naming them, and run the new cell: no existing file of
    the harness is edited."""
    for d in ("bench", "src"):
        shutil.copytree(ROOT / d, tmp_path / d, ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    base = spec["workloads"][0]
    mix = json.loads((harness.BENCH / "traffic" / f"{base['traffic']}.json").read_text())
    (tmp_path / "bench" / "traffic" / "added-mix.json").write_text(json.dumps({**mix, "s": 2}))
    (tmp_path / "bench" / "metrics" / "window_steps.py").write_text(NEW_METRIC)
    shutil.copy(harness.BENCH / "limits" / f"{base['name']}.json",
                tmp_path / "bench" / "limits" / "tiny.added.json")
    spec["workloads"].append({**base, "name": "tiny.added", "traffic": "added-mix"})
    spec["per_layer"].append({"name": "window_steps", "unit": "steps", "better": "higher",
                              "source": "host_clock", "layer": "trainer loop",
                              "moves": "useful_tokens_per_s", "workloads": ["tiny.added"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    p = subprocess.run([sys.executable, "-c", DRIVE], cwd=tmp_path, env=_env(),
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["metrics"]["window_steps"]["value"] == result["attempted"] > 0
    assert "control_plane_ms" in result["metrics"]  # the existing readers still run
