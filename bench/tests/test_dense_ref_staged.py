"""The staged reference against :func:`dense_ref.follow` at a small size
on four CPU devices (a subprocess: JAX fixes its device count at its first
use).  Both compute the same float32 arithmetic at HIGHEST over the same
row blocks, so they agree to rounding: the losses, the first gradient's
leaf norms and the update's leaf norms within 1e-5 relative.

RoPE's base is 100 here.  At Qwen2's 1e6 and 16-wide heads the slowest
pairs turn by under 1e-3 rad over 32 positions; a key bias that RoPE does
not turn shifts every score of a query alike, and softmax's gradient sums
to zero over the keys, so those coordinates of the bias's gradient are
rounding alone, and Adam's normalisation lifts rounding to the size of an
update (bk's update norms then differ by up to 1e-4)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("seed", [5, 2**31 + 7])
def test_staged_reference_matches_follow(seed):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    code = textwrap.dedent(f"""
        import jax
        from bench.reference import dense_ref, dense_ref_staged
        from bench.traffic.tokens import batch

        m = dict(n_layers=6, d_model=64, n_heads=4, n_kv_heads=2,
                 head_dim=16, d_ff=128, vocab_size=300, qkv_bias=True,
                 tie_embeddings=True, rope_theta=100.0, norm_eps=1e-6)
        opt = dict(lr=2e-4, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1,
                   grad_clip=1.0)
        bs = [batch({seed}, k, 4, 32, 300) for k in (1, 2, 3)]
        devs = jax.devices()
        assert len(devs) == 4
        ref = dense_ref.follow(m, opt, {seed}, bs, 4)
        got = dense_ref_staged.follow(m, opt, {seed}, bs, 4, devs)
        assert got[3] is None
        for a, b in zip(ref[0], got[0]):
            assert abs(a - b) <= 1e-5 * a, (a, b)
        for r, g in zip(ref[1:], got[1:3]):
            assert sorted(r) == sorted(g)
            for k in r:
                assert abs(r[k] - g[k]) <= 1e-5 * r[k], (k, r[k], g[k])
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=ROOT)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK" in r.stdout


def test_staged_reference_measures_a_gradient_distance():
    """Handed :func:`dense_ref.loss_and_grad`'s first clipped gradient,
    the staged reference finds it within 1e-5 of its own, leaf by leaf;
    handed that gradient with one layer's ``wq`` doubled, it finds that
    leaf's distance to be the layer's ``wq`` norm and the others' as
    before."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    code = textwrap.dedent("""
        import jax, numpy as np
        from bench.reference import dense_ref, dense_ref_staged
        from bench.traffic.tokens import batch

        m = dict(n_layers=6, d_model=64, n_heads=4, n_kv_heads=2,
                 head_dim=16, d_ff=128, vocab_size=300, qkv_bias=True,
                 tie_embeddings=True, rope_theta=100.0, norm_eps=1e-6)
        opt = dict(lr=2e-4, b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1,
                   grad_clip=0.5)
        bs = [batch(11, k, 4, 32, 300) for k in (1, 2)]
        with jax.default_matmul_precision("highest"):
            params = jax.jit(lambda k: dense_ref.init(m, k))(dense_ref.key(11))
            _, g = dense_ref.loss_and_grad(m, params, bs[0], 4)
            norm = float(dense_ref._global_norm(g))
        assert norm > opt["grad_clip"], norm          # the clip takes part
        grad = jax.tree.map(
            lambda x: np.asarray(x) * np.float32(opt["grad_clip"] / norm), g)
        devs = jax.devices()
        _, g1, _, dist = dense_ref_staged.follow(m, opt, 11, bs, 4, devs,
                                                 grad=grad)
        assert sorted(dist) == sorted(g1)
        for k in g1:
            assert dist[k] <= 1e-5 * g1[k], (k, dist[k], g1[k])
        wq = grad["layers"]["wq"]
        grad["layers"]["wq"] = np.concatenate([wq[:2], 2 * wq[2:3], wq[3:]])
        _, _, _, moved = dense_ref_staged.follow(m, opt, 11, bs, 4, devs,
                                                 grad=grad)
        want = float(np.linalg.norm(wq[2]))
        assert abs(moved["wq"] - want) <= 1e-4 * want, (moved["wq"], want)
        for k in g1:
            if k != "wq":
                assert moved[k] <= 1e-5 * g1[k], (k, moved[k])
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=ROOT)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK" in r.stdout
