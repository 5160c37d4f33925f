#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names its configuration
(``bench/configs/<config>.json``: sizes, precision, limits of the
correctness check) and its traffic (``bench/traffic/<traffic>.json``:
the driver, ``bench/drivers/<driver>.py``, and its parameters).  Each
per-layer metric is a reader, ``bench/metrics/<metric>.py``.  A new cell
or metric is new files and entries; nothing here names one.

A run: check that JAX sees a TPU with enough chips (exit 3 otherwise,
and in a checkout without the program, exit 2); set-up (inputs, weights,
compiles, warm-up) counted as ``setup_s`` from process start; a window
of ``--seconds`` with the profiler on when ``--trace 1``; the device's
peak memory; then the comparison with the plain reference.  The last
lines of standard error are each compared number beside its limit; the
last line of standard output is the result as one JSON object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                           # noqa: E402
import contextlib                                         # noqa: E402
import importlib                                          # noqa: E402
import importlib.util                                     # noqa: E402
import json                                               # noqa: E402
import os                                                 # noqa: E402
import shutil                                             # noqa: E402
import sys                                                # noqa: E402
import traceback                                          # noqa: E402
from pathlib import Path                                  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class Context:
    """What a driver gets: its files, the seed, the devices, and the
    harness's span and log helpers."""

    def __init__(self, cell, config, traffic, seed, devices, control=False):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.devices, self.control = seed, devices, control

    @staticmethod
    def span(name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    @staticmethod
    def log(msg: str):
        print(f"[bench] {msg}", flush=True)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_files(name: str, bench_json: Path = ROOT / "BENCHMARK.json"):
    """``(benchmark, cell entry, config, traffic)`` of cell ``name``."""
    bm = json.loads(bench_json.read_text())
    cell = next((w for w in bm["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"bench: no workload named {name!r}")
    cfg_entry = next(c for c in bm["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    return bm, cell, config, traffic


def metric_entries(bm: dict, cell: str, which: str):
    """The cell's metrics of ``which`` (``end_to_end`` or ``per_layer``)."""
    reported = {m["name"] for m in bm["end_to_end"]
                if cell in m.get("workloads", [cell])}
    out = []
    for m in bm[which]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif which == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out


_EVENTS: list = []


def setup_jax() -> list:
    """Point JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache/`` and record JAX's compile events (once a process)."""
    import jax
    if not _EVENTS:
        CACHE_DIR.mkdir(exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _EVENTS.append((time.perf_counter(), "bench.start", 0.0))
        jax.monitoring.register_event_duration_secs_listener(
            lambda e, d, **kw: _EVENTS.append((time.perf_counter(), e, d)))
        jax.monitoring.register_event_listener(
            lambda e, **kw: _EVENTS.append((time.perf_counter(), e, 0.0)))
    return _EVENTS


def run_cell(bm, cell, config, traffic, *, seed, seconds, trace, devices,
             control=False, t_start=None, driver=None) -> dict:
    """Set-up, window and check of one cell on ``devices``; the result
    line as a dict (``checks`` last).  ``driver`` replaces the traffic's
    driver module (tests use it to plant faults)."""
    import jax
    events = setup_jax()
    t_start = T_START if t_start is None else t_start
    used = devices[:cell["chips"]]
    ctx = Context(cell, config, traffic, seed, used, control)
    mod = driver or importlib.import_module(f"bench.drivers.{traffic['driver']}")
    drv = mod.Cell(ctx)
    drv.setup()
    setup_s = time.perf_counter() - t_start
    ctx.log(f"set-up {setup_s:.4f} s")

    trace_dir = TRACE_DIR / cell["name"]
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        cm = jax.profiler.trace(str(trace_dir), profiler_options=opts)
    else:
        cm = contextlib.nullcontext()
    t_win = time.perf_counter()
    with cm:
        with ctx.span("bench.window"):
            win = drv.window(seconds)
    t_end = time.perf_counter()
    in_window = [e for e in events if t_win <= e[0] <= t_end]
    compiles = sum(1 for e in in_window if e[1] == CACHE_MISS_EVENT)
    ctx.log(f"window {win['window_s']:.4f} s, {win['attempted']} done, "
            f"{compiles} compile(s) inside it")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": int(peak)}
    ctx.log(f"peak device memory {peak} bytes "
            f"({peak / 2**30:.3f} GiB)")

    result = {"correct": False, "attempted": win["attempted"],
              "failed": win.get("failed", 0), "metrics": {},
              "device": device}
    if trace:
        from bench import trace as T
        tr = T.load(T.latest_xplane(str(trace_dir)))
        summary = T.summarize(tr, spans=drv.spans)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
        run = {"cell": cell, "config": config, "traffic": traffic,
               "window": win, "record": drv.layer_record(),
               "events": in_window, "trace": summary, "raw_trace": tr,
               "device": device,
               "peaks": peaks(used[0].device_kind)}
        for m in metric_entries(bm, cell["name"], "per_layer"):
            v = load_module(BENCH / "metrics" / f"{m['name']}.py").read(run)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(win["metrics"], setup_s=setup_s)
        for m in metric_entries(bm, cell["name"], "end_to_end"):
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}

    try:
        numbers, failed = drv.check()
    except Exception:                      # a check that breaks is a fail
        traceback.print_exc()
        numbers, failed = [("check_ran", 1, 0)], win["attempted"]
    result["failed"] += failed
    result["correct"] = (all(v <= lim for _, v, lim in numbers)
                         and result["failed"] == 0)
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in numbers}
    return result


def peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return table[kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="put the lower-precision control in the program's "
                         "place (correct must come out false)")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    bm, cell, config, traffic = cell_files(args.workload)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    # the TPU runtime would log to a fixed path under /tmp otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"JAX sees {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    peaks(devices[0].device_kind)
    result = run_cell(bm, cell, config, traffic, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      devices=devices, control=bool(args.control))
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
