"""The planned four-chip train cell's check at a small size on four CPU
devices (a subprocess: JAX fixes its device count at its first use): the
program's run is correct; the lower-precision control and each planted
fault of :mod:`bench.tests.train_plan_faults` are not, half of the batch
left out by the first gradient's distance from the reference's.

The configuration is the cell's own, cut to 4 layers of width 64 and a
300-token vocabulary; the recorded link matrix plans it as the cell's
model, ``pp4`` with 4 microbatches, so the pipe axis's sum is there to
drop."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
KINDS = ("program", "control", "half_batch", "state_unchanged",
         "pipe_sum_dropped")


@pytest.fixture(scope="module")
def readings():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    code = textwrap.dedent(f"""
        import json
        import jax
        jax.config.update("jax_enable_compilation_cache", False)
        from bench import run as R
        from bench.tests import train_plan_faults as F

        bm, entry, config, traffic = R.cell_files(F.CELL)
        config = dict(config, num_hidden_layers=4, hidden_size=64,
                      num_attention_heads=4, num_key_value_heads=2,
                      intermediate_size=128, vocab_size=300,
                      reference_rows=2)
        traffic = dict(traffic, seq=32, global_batch=4)
        runs = [(k, 2**31 + 7) for k in {KINDS!r}]
        for r in F.readings((bm, entry, config, traffic), runs, 0.2,
                            jax.devices()):
            print("READING", json.dumps(r), flush=True)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, env=env, cwd=ROOT)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    out = [json.loads(line.split(" ", 1)[1])
           for line in r.stdout.splitlines() if line.startswith("READING ")]
    assert "plan: pp4·tp1·dp1" in r.stdout, r.stdout
    return {o["kind"]: o for o in out}


def test_program_is_correct(readings):
    got = readings["program"]
    assert got["correct"], got["checks"]


@pytest.mark.parametrize("kind", KINDS[1:])
def test_control_and_faults_are_not_correct(readings, kind):
    got = readings[kind]
    assert not got["correct"], got["checks"]


@pytest.mark.parametrize("kind", ["half_batch", "state_unchanged",
                                  "pipe_sum_dropped"])
def test_gradient_distance_finds_the_fault(readings, kind):
    c = readings[kind]["checks"]["grad_dist_gap"]
    assert c["value"] > c["limit"], c
