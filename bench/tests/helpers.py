"""Small CPU-sized versions of the benchmark's cells for its tests."""
import time

import jax

from bench import run as R


def small_plan(name="plan.m40-13b.mixed2048"):
    bm, cell, config, traffic = R.cell_files(name)
    fleet = dict(config["fleet"], n_nodes=4)
    model = dict(config["model"], n_layers=8, d_model=512, n_heads=8,
                 n_kv_heads=8, head_dim=64, d_ff=2048, vocab_size=1000)
    config = dict(config, fleet=fleet, model=model,
                  job=dict(config["job"], bs_global=64))
    return bm, cell, config, dict(traffic, sa_iters=50, n_chains=2)


def small_train(name="train.qwen2-1.5b-l8"):
    bm, cell, config, traffic = R.cell_files(name)
    # the vocabulary is no multiple of 256, as the published one is not
    config = dict(config, num_hidden_layers=2, hidden_size=64,
                  num_attention_heads=4, num_key_value_heads=2,
                  intermediate_size=128, vocab_size=300, reference_rows=2)
    return bm, cell, config, dict(traffic, seq=32, global_batch=4, n_micro=2)


def run(files, *, seed=2**31 + 7, seconds=0.5, control=False, driver=None):
    bm, cell, config, traffic = files
    return R.run_cell(bm, cell, config, traffic, seed=seed, seconds=seconds,
                      trace=False, devices=jax.devices(), control=control,
                      t_start=time.perf_counter(), driver=driver)
