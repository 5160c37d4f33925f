"""The correctness check at a tiny size on the CPU: sound runs pass; the
lower-precision control and each planted fault fail.

The harness's look for a chip is skipped (``run_cell`` is handed the
CPU's devices); everything else of a run is driven as on the chip, with
each configuration's own limits."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import pytest

from bench.drivers import plan as plan_driver
from bench.drivers import train as train_driver
from bench.tests.helpers import run, small_plan, small_train


def _driver(cls):
    return types.SimpleNamespace(Cell=cls)


@pytest.mark.parametrize("files", [small_plan, small_train])
def test_sound_run_is_correct(files):
    res = run(files())
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("files", [small_plan, small_train])
def test_control_is_not_correct(files):
    res = run(files(), control=True)
    assert not res["correct"], res["checks"]


class StateUnchanged(train_driver.Cell):
    """A step that returns the state it was given."""

    def compile_step(self):
        real = super().compile_step()

        def step(params, opt_state, b):
            copy = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731
            _, _, loss = real(copy(params), copy(opt_state), b)
            return params, opt_state, loss
        return step


class HalfBatch(train_driver.Cell):
    """Half of every batch left out, the mean taken over the rest."""

    def compile_step(self):
        lay, fn = self.layout, self.layout.jit()

        def step(params, opt_state, b):
            half = {k: v[:len(v) // 2] for k, v in b.items()}
            params, opt_state, met = fn(params, opt_state, lay.put_batch(half))
            return params, opt_state, met["loss"]
        return step


class AnswerAltered(plan_driver.Cell):
    """The best candidate's latency altered where it is produced."""

    def plan(self, k):
        p = super().plan(k)
        best = dataclasses.replace(p.ranked[0],
                                   latency=p.latency * (1 + 1e-5))
        return dataclasses.replace(p, latency=best.latency,
                                   ranked=(best,) + p.ranked[1:])


class ChoiceAltered(plan_driver.Cell):
    """The search's choice altered where it is produced: the plan names
    its runner-up, with that candidate's own latency, as its best."""

    def plan(self, k, backend=None):
        p = super().plan(k, backend)
        if backend is not None:
            return p
        ranked = (p.ranked[1], p.ranked[0]) + p.ranked[2:]
        return dataclasses.replace(p, conf=ranked[0].conf,
                                   mapping=ranked[0].mapping,
                                   latency=ranked[0].latency, ranked=ranked)


class SASkipped(plan_driver.Cell):
    """The annealer's moves skipped: the program's plan keeps, but for one
    move (the least a ``Budget`` takes), the coarse mapping it starts
    from.  At the plan cell's budget the anneal accepts no move over that
    mapping, so this changes no answer there; ``chip_readings.py`` reads
    it to show so."""

    def plan(self, k, backend=None):
        if backend is not None:
            return super().plan(k, backend)
        from repro.core import Planner, PipetteStrategy
        req = self.request(k)
        req = dataclasses.replace(req, budget=dataclasses.replace(
            req.budget, sa_iters=1))
        return Planner(PipetteStrategy()).plan(req, self.bw)


@pytest.mark.parametrize("files, fault", [
    (small_train, StateUnchanged), (small_train, HalfBatch),
    (small_plan, AnswerAltered), (small_plan, ChoiceAltered)])
def test_fault_is_not_correct(files, fault):
    res = run(files(), driver=_driver(fault))
    assert not res["correct"], res["checks"]
