"""Readings of the correctness check on a TPU, at a cell's own size, for
setting its limits: the program on many seeds, the lower-precision
control on three, and each planted fault on three: half of each batch
left out (train cells); the runner-up named as the choice, and the
annealer's moves skipped (plan cells).

    python3 bench/tests/chip_readings.py <cell> <seconds> <n_sound> [first_seed]

One process runs them all, one after another, so set-up is paid once
for the compiles; each run is the harness's own ``run_cell``.  Each
reading is printed as one JSON line.
"""
import json
import sys
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(cell: str, seconds: float, n_sound: int, first: int = 2**31 + 101):
    import jax
    from bench import run as R
    from bench.tests.test_control import ChoiceAltered, HalfBatch, SASkipped

    assert jax.devices()[0].platform == "tpu", "needs a TPU"
    bm, entry, config, traffic = R.cell_files(cell)
    kinds = [("program", None, False)] * n_sound + [("control", None, True)] * 3
    faults = {"train": [("half_batch", HalfBatch)],
              "plan": [("choice_altered", ChoiceAltered),
                       ("sa_skipped", SASkipped)]}[traffic["driver"]]
    for name, cls in faults:
        kinds += [(name, types.SimpleNamespace(Cell=cls), False)] * 3
    for i, (kind, drv, control) in enumerate(kinds):
        t = time.perf_counter()
        res = R.run_cell(bm, entry, config, traffic, seed=first + i,
                         seconds=seconds, trace=False,
                         devices=jax.devices(), control=control,
                         t_start=t, driver=drv)
        print(json.dumps({"kind": kind, "seed": first + i,
                          "correct": res["correct"],
                          "checks": res["checks"],
                          "metrics": res["metrics"],
                          "seconds": time.perf_counter() - t}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], float(sys.argv[2]), int(sys.argv[3]),
         *[int(a) for a in sys.argv[4:5]])
