#!/usr/bin/env python3
"""Bring-up check of the system's two device paths on a TPU.

    python chip_smoke.py               # plan + train phases, one chip
    python chip_smoke.py --four-chips  # plan, lower and train across 4 chips

Plan phase: the Pipette planner with the JAX annealer
(``Budget(backend="jax")``) on the seeded 2,048-GPU mixed A100/V100 fleet
of ``benchmarks/bench_configure.py``, hierarchical island search on.  The
annealer's group reductions must run as compiled Pallas kernels
(``tpu_custom_call`` in its executable), the plan's latency must match
the float64 re-score by ``pipette_latency_ref`` within
``jax_engine.TPU_REL_TOL``, and the static plan verifier must pass.

Train phase: 5 steps of gpt-1.1b at full width, depth cut to 12 of 24
layers, seq 2048, global batch 8 in 2 microbatches, on a synthetic corpus
of one batch from ``--seed``.  Every loss must be finite and the last
below the first.

``--four-chips`` runs only this: profile the 4x4 device links, plan the
same 12-layer model for that host, lower the plan to a mesh, train 3
sharded steps on it, and compare the first-step loss with a one-device
run of the same params and batch within :data:`BF16_REL_TOL`.

One process drives the chip(s); nothing falls back to the CPU.  The last
line of standard output is a JSON object naming the device, printed only
when every phase passed.  The script exits non-zero otherwise, and when
JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: Relative tolerance of the four-chip first-step loss against the
#: one-device loss.  Both runs compute in bfloat16, which keeps 8
#: significant bits: one rounding moves a value by up to 2**-9 relative,
#: and the sharded run rounds partial sums at other points (per-shard
#: matmuls, collectives) than the one-device run.  The loss is a mean
#: over 16,384 tokens of float32 cross-entropies, where these roundings
#: mostly cancel; a bound of one bfloat16 step at the loss itself
#: (2**-7, 0.8%) still fails any real layout or math error.
BF16_REL_TOL = 2.0 ** -7

SEQ = 2048
TRAIN_BATCH = 8


def _cut_model():
    from repro import configs
    full = configs.get("gpt-1.1b")
    cfg = full.replace(n_layers=12)
    print(f"[train] {full.name} at full width (d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}), "
          f"depth cut from {full.n_layers} to {cfg.n_layers} layers")
    return cfg


def _check(cond: bool, what: str):
    if not cond:
        raise AssertionError(what)


def plan_phase(seed: int) -> None:
    import jax
    import numpy as np

    from benchmarks.bench_configure import HUGE_BS_GLOBAL, M40, _huge_spec
    from repro.analysis import verify_plan_dict
    from repro.core import (Budget, Planner, PlanRequest, PipetteStrategy,
                            SearchSpace, Workload, build_profile,
                            perm_to_mapping, pipette_latency_ref,
                            profile_bandwidth)
    from repro.core.annealing import build_islands, make_move_plan
    from repro.core.jax_engine import TPU_REL_TOL, JaxDedicationEngine

    spec = _huge_spec(2048)
    bw, _ = profile_bandwidth(spec)
    w = Workload(M40, SEQ, HUGE_BS_GLOBAL)
    iters, chains = 200, 4

    def request(backend):
        return PlanRequest(
            workload=w, spec=spec,
            space=SearchSpace(max_tp=8, max_cp=2, fixed_micro=1),
            budget=Budget(sa_seconds=3600.0, sa_iters=iters,
                          n_chains=chains, sa_topk=2, backend=backend),
            seed=seed)

    print(f"[plan] {M40.name} seq {SEQ} global batch {HUGE_BS_GLOBAL} on "
          f"{spec.name} ({spec.n_gpus} GPUs), backend=jax "
          f"sa_iters={iters} n_chains={chains} sa_topk=2")
    t0 = time.perf_counter()
    plan = Planner(PipetteStrategy()).plan(request("jax"), bw)
    plan_s = time.perf_counter() - t0
    conf = plan.conf
    print(f"[plan] jax plan in {plan_s:.2f}s (compiles included): {conf} "
          f"latency {plan.latency!r} s")

    prof = build_profile(w, spec, conf)
    ref = float(pipette_latency_ref(conf, plan.mapping, bw, prof, spec))
    rel = abs(plan.latency - ref) / ref
    print(f"[plan] pipette_latency_ref re-score {ref!r} s, relative error "
          f"{rel:.3e} (tolerance {TPU_REL_TOL:.0e})")
    _check(rel <= TPU_REL_TOL, "jax plan latency off its float64 re-score")

    issues = verify_plan_dict(plan.to_json_dict(), spec=spec, bw=bw)
    errors = [i for i in issues if i.severity == "error"]
    for i in errors:
        print(f"[plan] {i}")
    print(f"[plan] plan verifier: {len(errors)} error(s) -> "
          f"{'PASS' if not errors else 'FAIL'}")
    _check(not errors, "plan verifier found errors")

    # the annealer alone, for the chosen configuration: compile, run, and
    # look for the kernels in what was compiled
    jeng = JaxDedicationEngine([conf], [prof], bw, spec)
    islands = build_islands(spec, hierarchical=True)
    mp = make_move_plan([len(i) for i in islands], iters, chains, seed)
    offsets = np.cumsum([0] + [len(i) for i in islands[:-1]])
    args = jeng.anneal_args(
        np.concatenate(islands)[None], (offsets[mp.isl] + mp.oa)[None],
        (offsets[mp.isl] + mp.ob)[None], mp.kind, mp.thresh, mp.valid,
        (offsets[mp.probe_isl] + mp.probe_oa)[None],
        (offsets[mp.probe_isl] + mp.probe_ob)[None], mp.probe_kind)
    t0 = time.perf_counter()
    exe = jeng.compile_anneal(args)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    best, bperm, *_ = jax.block_until_ready(exe(*args))
    anneal_s = time.perf_counter() - t0
    custom = "tpu_custom_call" in exe.as_text()
    print(f"[plan] annealer ({chains} chains x {iters} moves, "
          f"{spec.n_gpus} GPUs): compile {compile_s:.2f}s, anneal "
          f"{anneal_s:.4f}s; tpu_custom_call in executable: {custom}")
    _check(custom, "annealer executable has no Pallas kernel")
    k = int(np.argmin(np.asarray(best)[0]))
    val = float(np.asarray(best)[0, k])
    ref = float(pipette_latency_ref(conf, perm_to_mapping(
        np.asarray(bperm)[0, k], conf), bw, prof, spec))
    print(f"[plan] annealer best {val!r} s vs re-score {ref!r} s, relative "
          f"error {abs(val - ref) / ref:.3e}")
    _check(abs(val - ref) <= TPU_REL_TOL * ref,
           "annealer score off its float64 re-score")

    t0 = time.perf_counter()
    ref_plan = Planner(PipetteStrategy()).plan(request("numpy"), bw)
    print(f"[plan] numpy plan in {time.perf_counter() - t0:.2f}s: "
          f"{ref_plan.conf} latency {ref_plan.latency!r} s "
          f"(jax {plan.latency!r} s; for information)")


def train_phase(seed: int) -> None:
    import jax

    from repro.data.pipeline import SyntheticCorpus
    from repro.launch.train import train

    cfg = _cut_model()
    # Each synthetic sequence follows its own rule, so 5 steps over fresh
    # sequences leave the loss flat (it only drifts with the batch).  A
    # corpus of one batch makes the 5 steps 5 epochs over it: an optimizer
    # that works must fit it.
    corpus = SyntheticCorpus(cfg.vocab_size, seed=seed,
                             n_sequences=TRAIN_BATCH)
    print(f"[train] synthetic corpus of {TRAIN_BATCH} sequences (seed "
          f"{seed}): every step trains on the same batch")
    run = train(cfg, steps=5, global_batch=TRAIN_BATCH, seq_len=SEQ,
                n_micro=2, seed=seed, corpus=corpus)
    for i, (loss, dt) in enumerate(zip(run.losses, run.step_s)):
        print(f"[train] step {i} loss {loss!r} ({dt:.3f}s)")
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    print(f"[train] compile {run.compile_s:.2f}s, median step "
          f"{sorted(run.step_s)[len(run.step_s) // 2]:.3f}s, "
          f"peak_bytes_in_use {peak} ({peak / 2**30:.2f} GiB)")
    _check(all(math.isfinite(x) for x in run.losses), "non-finite loss")
    _check(run.losses[-1] < run.losses[0], "loss did not fall")


def four_chip_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data.pipeline import DataLoader, LoaderConfig, SyntheticCorpus
    from repro.launch.mesh import mesh_from_plan
    from repro.launch.train import plan_for_devices, train
    from repro.models import model as M
    from repro.models.sharding import ShardCtx

    devices = jax.devices()[:4]
    _check(len(devices) == 4, f"need 4 devices, found {len(devices)}")
    cfg = _cut_model()
    plan, spec, bw = plan_for_devices(cfg, SEQ, TRAIN_BATCH, seed=seed,
                                      devices=devices)
    with np.printoptions(precision=3):
        print(f"[4chip] measured link bandwidth, GB/s:\n{bw / 1e9}")
    mesh = mesh_from_plan(plan)
    order = np.vectorize(lambda d: d.id)(mesh.devices)
    print(f"[4chip] plan {plan.conf} latency {plan.latency!r} s; mesh axes "
          f"{mesh.axis_names} shape {dict(mesh.shape)}; device ids by mesh "
          f"position {order.tolist()}")

    # one-device reference: the same params and first batch, averaged
    # over the same microbatches as the plan's step
    n_mb = plan.conf.n_mb
    batch = DataLoader(SyntheticCorpus(cfg.vocab_size, seed=seed),
                       LoaderConfig(TRAIN_BATCH, SEQ)).batch_at(0)

    @jax.jit
    def ref_loss(key, tokens, labels):
        params = M.init_params(cfg, key)
        mb = lambda x: x.reshape(n_mb, -1, x.shape[-1])  # noqa: E731
        losses = jax.lax.map(
            lambda tl: M.loss_fn(params, cfg, ShardCtx(),
                                 {"tokens": tl[0], "labels": tl[1]})[0],
            (mb(tokens), mb(labels)))
        return losses.mean()

    with jax.default_device(devices[0]):
        ref = float(ref_loss(jax.random.PRNGKey(seed),
                             jnp.asarray(batch["tokens"]),
                             jnp.asarray(batch["labels"])))
    run = train(cfg, steps=3, global_batch=TRAIN_BATCH, seq_len=SEQ,
                seed=seed, plan=plan)
    for i, loss in enumerate(run.losses):
        print(f"[4chip] step {i} loss {loss!r}")
    rel = abs(run.losses[0] - ref) / ref
    print(f"[4chip] first-step loss {run.losses[0]!r} vs one-device "
          f"{ref!r}: relative difference {rel:.3e} (tolerance "
          f"{BF16_REL_TOL:.3e})")
    _check(all(math.isfinite(x) for x in run.losses), "non-finite loss")
    _check(rel <= BF16_REL_TOL, "sharded loss differs from one device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.compile_cache import enable_compile_cache
    print(f"[smoke] {len(devices)} x {devices[0].device_kind}; compile "
          f"cache {enable_compile_cache()}")

    phases = ([four_chip_phase] if args.four_chips
              else [plan_phase, train_phase])
    failed = []
    for phase in phases:
        t0 = time.perf_counter()
        try:
            phase(args.seed)
        except Exception:
            traceback.print_exc()
            failed.append(phase.__name__)
        print(f"[smoke] {phase.__name__}: "
              f"{'FAIL' if phase.__name__ in failed else 'PASS'} in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
    if failed:
        print(f"chip_smoke: failed: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
