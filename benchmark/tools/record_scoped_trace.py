"""Record the small trace that ``benchmark/tests/test_scope_reducers.py``
checks the scope reducers against: a few runs of one small jitted
program with two named scopes and a named Pallas kernel, on the chip,
each under a ``bench:scoped`` annotation.

    chiprun -- python -m benchmark.tools.record_scoped_trace chiprun_out/tiny-scopes

Copy ``tiny-scopes.xplane.pb`` from there to ``benchmark/tests/data/``.
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

N = 512


def _double(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def scoped(a):
    with jax.named_scope("alpha"):
        h = jnp.tanh(a @ a)
    with jax.named_scope("beta"):
        h = pl.pallas_call(
            _double, out_shape=jax.ShapeDtypeStruct(h.shape, h.dtype),
            name="hvd_tiny_double")(h)
        return (h @ a).sum()


def main(out_dir: str) -> None:
    assert jax.devices()[0].platform == "tpu", "needs the chip"
    x = jnp.ones((N, N), jnp.float32)
    f = jax.jit(scoped)
    f(x).block_until_ready()
    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(out_dir)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench:scoped"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench:sleep"):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                        recursive=True)
    shutil.copy(path, os.path.join(out_dir, "tiny-scopes.xplane.pb"))
    print(os.path.getsize(path))


if __name__ == "__main__":
    main(sys.argv[1])
