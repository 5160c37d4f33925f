"""Record the small TPU trace that ``test_trace.py`` reduces.

    python3 bench/tests/record_trace.py <out.xplane.pb>     # on a TPU host

Inside a ``bench.window`` span: three ``train.step`` spans, each a jitted
2048^3 bf16 matmul, with a 20 ms host-only ``train.batch`` span after
each, then one call of each group-reduce kernel.  The device is idle
during the ``train.batch`` spans, so the reduction must find gaps of
about 20 ms named by them.
"""
import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels.group_reduce import group_max, group_min_scale

    assert jax.devices()[0].platform == "tpu", "needs a TPU"
    f = jax.jit(lambda x: (x @ x.T).sum())
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    sub = jnp.full((1280, 8, 8), 2.0, jnp.float32)
    vals = jnp.ones((8, 256), jnp.float32)
    for _ in range(2):                      # compile and warm up
        f(x).block_until_ready()
        group_min_scale(sub, 3.0).block_until_ready()
        group_max(vals).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    d = tempfile.mkdtemp(dir=ROOT)
    try:
        with jax.profiler.trace(d, profiler_options=opts):
            with jax.profiler.TraceAnnotation("bench.window"):
                for _ in range(3):
                    with jax.profiler.TraceAnnotation("train.step"):
                        f(x).block_until_ready()
                    with jax.profiler.TraceAnnotation("train.batch"):
                        time.sleep(0.02)
                group_min_scale(sub, 3.0).block_until_ready()
                group_max(vals).block_until_ready()
        path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
        shutil.copy(path[0], out)
    finally:
        shutil.rmtree(d)


if __name__ == "__main__":
    main(sys.argv[1])
