"""The train driver as a function (``launch.train.train``): one device,
a bounded synthetic corpus, and plans lowered to a mesh of several devices
(in a subprocess with forced host devices, as in test_multidevice)."""
import json
import math

import numpy as np
import pytest

from repro import configs
from repro.core.cluster import live_host_spec
from repro.data.pipeline import DataLoader, LoaderConfig, SyntheticCorpus
from repro.launch.train import train

from test_multidevice import run_py

TINY = configs.get("gpt-1.1b").reduced()


def test_bounded_corpus_revisits_its_sequences():
    corpus = SyntheticCorpus(97, seed=3, n_sequences=4)
    free = SyntheticCorpus(97, seed=3)
    for idx in range(4):
        assert (corpus.sequence(idx, 16) == free.sequence(idx, 16)).all()
        assert (corpus.sequence(idx + 4, 16) == corpus.sequence(idx, 16)).all()
    loader = DataLoader(corpus, LoaderConfig(4, 16))
    assert (loader.batch_at(0)["tokens"] == loader.batch_at(3)["tokens"]).all()


def test_train_one_device_reports_every_step():
    run = train(TINY, steps=3, global_batch=4, seq_len=32, n_micro=2,
                log=lambda s: None)
    assert len(run.losses) == len(run.step_s) == 3
    assert all(math.isfinite(x) for x in run.losses)
    assert run.compile_s > 0 and run.n_params > 0


def test_train_fits_a_one_batch_corpus():
    """chip_smoke.py's loss check at a CPU size: 5 steps over a corpus of
    one batch must lower the loss."""
    corpus = SyntheticCorpus(TINY.vocab_size, seed=0, n_sequences=4)
    run = train(TINY, steps=5, global_batch=4, seq_len=32, n_micro=2,
                lr=3e-3, corpus=corpus, log=lambda s: None)
    assert run.losses[-1] < run.losses[0], run.losses


def test_live_host_spec_prices_the_measured_links():
    bw = np.full((4, 4), 40e9)
    bw[0, 1] = bw[1, 0] = 10e9
    np.fill_diagonal(bw, np.inf)
    spec = live_host_spec(bw, gpu_mem=8e9)
    assert (spec.n_nodes, spec.gpus_per_node) == (1, 4)
    assert spec.intra_bw == 40e9 and spec.gpu_mem == 8e9


_PLANNED = """
    import json, types
    import numpy as np
    import jax
    from repro import configs
    from repro.core import Conf
    from repro.launch.train import plan_for_devices, train

    cfg = configs.get("gpt-1.1b").reduced(n_heads=4, n_kv_heads=4)
    quiet = lambda s: None
    kw = dict(steps=2, global_batch=8, seq_len=32, log=quiet)
    out = {"one": train(cfg, n_micro=2, **kw).losses}
    rng = np.random.default_rng(0)
    for conf in CONFS:
        mapping = rng.permutation(4).reshape(conf.pp, conf.tp, conf.dp)
        plan = types.SimpleNamespace(conf=conf, mapping=mapping)
        out[str(conf)] = train(cfg, plan=plan, **kw).losses
    plan, spec, bw = plan_for_devices(cfg, 32, 8)
    out["searched"] = [str(plan.conf), spec.n_gpus, list(bw.shape)]
    print(json.dumps(out))
"""


@pytest.mark.parametrize("confs", [
    "[Conf(1, 2, 2, 2, 8), Conf(1, 1, 4, 1, 8)]",
    "[Conf(2, 2, 1, 2, 8), Conf(4, 1, 1, 1, 8)]",
], ids=["gspmd", "pipelined"])
def test_planned_steps_match_one_device(confs):
    """Plans lowered by ``mesh_from_plan`` (GSPMD over model and data axes,
    or stages over the pipe axis) give the one-device loss."""
    out = json.loads(run_py(_PLANNED.replace("CONFS", confs),
                            devices=4).splitlines()[-1])
    one = out.pop("one")
    name, n_gpus, shape = out.pop("searched")
    assert n_gpus == 4 and shape == [4, 4] and "pp" in name
    assert len(out) == 2
    for conf, losses in out.items():
        np.testing.assert_allclose(losses, one, rtol=1e-4, err_msg=conf)
