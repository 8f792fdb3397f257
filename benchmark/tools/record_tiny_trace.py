"""Record the small trace that ``benchmark/tests`` checks
``trace_reduce.py`` against: a few runs of one small jitted program on
the chip, with idle time between them, under ``bench:`` annotations.

    chiprun -- python -m benchmark.tools.record_tiny_trace chiprun_out/tiny
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    assert jax.devices()[0].platform == "tpu", "needs the chip"
    x = jnp.ones((512, 512), jnp.bfloat16)
    f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    f(x).block_until_ready()
    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(out_dir)
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench:tiny"):
            f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench:sleep"):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                        recursive=True)
    shutil.copy(path, os.path.join(out_dir, "tiny.xplane.pb"))
    print(os.path.getsize(path))


if __name__ == "__main__":
    main(sys.argv[1])
