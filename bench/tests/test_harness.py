"""The harness's own behaviour: no chip, no program, which metrics a cell
reports, and that every name in BENCHMARK.json has its files."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

from bench import run as R

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "plan.m40-13b.mixed2048", "--seed", "3", "--seconds", "1",
         "--trace", "0", *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _run(ROOT)
    assert p.returncode == 3
    assert "{" not in p.stdout


def test_checkout_without_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode == 2
    assert "{" not in p.stdout


def test_every_name_has_its_files():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bm["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    for w in bm["workloads"]:
        t = json.loads((ROOT / "bench" / "traffic"
                        / f"{w['traffic']}.json").read_text())
        assert (ROOT / "bench" / "drivers" / f"{t['driver']}.py").is_file()
    for m in bm["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_metric_entries_follow_workloads_keys():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bm["workloads"]:
        e2e = [m["name"] for m in R.metric_entries(bm, w["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        per = R.metric_entries(bm, w["name"], "per_layer")
        assert per and all(m["moves"] in e2e for m in per)
