"""The JAX annealer's process-level executable cache.

Engines of one static structure share one trace, one lowering and one
compile: a second engine fires ``/pipette/exe_hit/<site>`` where the
first fired ``/pipette/trace/<site>``, and gives bit-identical outputs.
Every field the trace reads keys its own entry, the cache keeps no
engine or device buffer alive, and its size is bounded."""
import collections
import dataclasses
import gc
import weakref

import numpy as np
import pytest

import jax

from repro import obs
from repro.core import (Conf, Workload,
                        build_profile, profile_bandwidth)
from repro.core import jax_engine
from repro.core.annealing import build_islands, make_move_plan
from repro.core.cluster import A100_TIER, V100_TIER, mixed_fleet_spec
from repro.core.jax_engine import JaxDedicationEngine, clear_executables
from repro.core.partition import uniform_partition
from repro.models.config import ModelConfig

GPT = ModelConfig(name="g12", family="dense", n_layers=12, d_model=1024,
                  n_heads=16, n_kv_heads=16, d_ff=4096, vocab_size=32000)
W = Workload(GPT, 2048, 32)
MIXED = mixed_fleet_spec("exe-mixed-16x1", 16, (A100_TIER, V100_TIER),
                         (0.5, 0.5), gpus_per_node=1, seed=31)
#: pp, tp, cp and dp all above 1, so every term of the score is traced
CONF = Conf(2, 2, 2, 1, 32, cp=2)
SITES = ("jax_engine.score", "jax_engine.score_batch", "jax_engine.anneal")


@pytest.fixture(autouse=True)
def _no_kept_executables():
    clear_executables()
    yield
    clear_executables()


@pytest.fixture
def events():
    """``Counter`` of ``(kind, site)`` for the trace and exe-hit events
    recorded while the test runs."""
    got = collections.Counter()

    def on_event(e, **kw):
        for kind, prefix in (("trace", obs.TRACE_EVENT),
                             ("hit", obs.EXE_HIT_EVENT)):
            if e.startswith(prefix):
                got[kind, e[len(prefix):]] += 1

    jax.monitoring.register_event_listener(on_event)
    yield got
    jax.monitoring.unregister_event_listener(on_event)


def _engine(spec=MIXED, conf=CONF, prof=None, **kw):
    bw, _ = profile_bandwidth(spec)
    if prof is None:
        prof = build_profile(W, spec, conf)
    return JaxDedicationEngine([conf], [prof], bw, spec, **kw), bw, prof


def _anneal_inputs(spec=MIXED, iters=20):
    mp = make_move_plan([len(i) for i in build_islands(spec, hierarchical=False)],
                        iters, 2, 0)
    return (np.arange(spec.n_gpus)[None], mp.oa[None], mp.ob[None], mp.kind,
            mp.thresh, mp.valid, mp.probe_oa[None], mp.probe_ob[None],
            mp.probe_kind)


def _drive(jeng, perms):
    """Every lowering site once (the score twice): the outputs as hex."""
    out = [float(jeng.score(p)).hex() for p in perms]
    out += [float(v).hex() for v in jeng.score_batch(np.stack(perms))]
    best, bperm, fin, acc, accb = jeng.anneal(*_anneal_inputs())
    out += [float(v).hex() for v in best.ravel()] + [bperm.tobytes()]
    return out + [fin.tobytes(), acc.tobytes(), accb.tobytes()]


def test_second_engine_hits_every_site_and_matches_a_cold_one(events):
    perms = [np.random.default_rng(s).permutation(MIXED.n_gpus)
             for s in range(3)]
    first = _drive(_engine()[0], perms)
    assert events == {("trace", s): 1 for s in SITES}
    events.clear()
    warm = _drive(_engine()[0], perms)
    assert events == {("hit", s): 1 for s in SITES}
    clear_executables()
    events.clear()
    cold = _drive(_engine()[0], perms)
    assert events == {("trace", s): 1 for s in SITES}
    assert warm == cold == first


def _distinct(**change):
    """An engine that differs from ``_engine()`` in one traced field."""
    if "kernels" in change or "compute_aware" in change:
        return _engine(**change)[0]
    if "vpp" in change:
        return _engine(conf=dataclasses.replace(CONF, vpp=2))[0]
    if "ref_bw" in change:
        prof = build_profile(W, MIXED, CONF)
        return _engine(prof=dataclasses.replace(prof, **change["ref_bw"]))[0]
    if "partition" in change:
        prof = build_profile(W, MIXED, CONF,
                             partition=uniform_partition(GPT.n_layers, 2))
        return _engine(prof=prof)[0]
    conf = Conf(2, 2, 4, 1, 32, cp=2)                       # n: 32 GPUs
    return _engine(spec=mixed_fleet_spec("exe-mixed-32x1", 32,
                                         (A100_TIER, V100_TIER), (0.5, 0.5),
                                         gpus_per_node=1, seed=31),
                   conf=conf)[0]


@pytest.mark.parametrize("change, field", [
    ({"kernels": "interpret"}, "kmode"),
    ({"compute_aware": False}, "tiered"),
    ({"partition": True}, "nonuniform"),
    ({"vpp": 2}, "vpp"),
    ({"ref_bw": {"tp_ref_bw": 1e11}}, "tp_ref"),
    ({"ref_bw": {"cp_ref_bw": 1e11}}, "cp_ref"),
    ({"n": 32}, "n")])
def test_each_traced_field_keys_its_own_entry(events, change, field):
    base = _engine()[0]
    other = _distinct(**change)
    assert getattr(other.statics, field) != getattr(base.statics, field)
    base.score(np.arange(base.statics.n))
    other.score(np.arange(other.statics.n))
    assert events == {("trace", "jax_engine.score"): 2}
    assert len(jax_engine._EXE_CACHE) == 2


def test_batch_shape_and_alpha_key_their_own_entries(events):
    jeng = _engine()[0]
    perms = np.stack([np.arange(MIXED.n_gpus)] * 3)
    jeng.score_batch(perms[:2])
    jeng.score_batch(perms)
    args = jeng.anneal_args(*_anneal_inputs())
    jeng.compile_anneal(args, 0.999)
    jeng.compile_anneal(args, 0.99)
    jeng.compile_anneal(jeng.anneal_args(*_anneal_inputs(iters=30)), 0.999)
    assert events == {("trace", "jax_engine.score_batch"): 2,
                      ("trace", "jax_engine.anneal"): 3}


def test_cache_keeps_no_engine_or_device_buffer_alive():
    jeng = _engine()[0]
    jeng.score(np.arange(MIXED.n_gpus))
    jeng.anneal(*_anneal_inputs())
    refs = (weakref.ref(jeng), weakref.ref(jeng.device_pairs["bw"]))
    del jeng
    gc.collect()
    assert [r() for r in refs] == [None, None]
    assert len(jax_engine._EXE_CACHE) == 2


def test_least_recently_used_entry_goes_first(events, monkeypatch):
    monkeypatch.setattr(jax_engine, "EXE_CACHE_SIZE", 2)
    perms = np.stack([np.arange(MIXED.n_gpus)] * 3)
    for r in (1, 2, 1, 3):          # the 1-row entry is used again before 3
        _engine()[0].score_batch(perms[:r])
    assert len(jax_engine._EXE_CACHE) == 2
    events.clear()
    _engine()[0].score_batch(perms[:1])
    _engine()[0].score_batch(perms[:3])
    assert events == {("hit", "jax_engine.score_batch"): 2}
    _engine()[0].score_batch(perms[:2])                       # evicted
    assert events[("trace", "jax_engine.score_batch")] == 1
    assert len(jax_engine._EXE_CACHE) == 2
