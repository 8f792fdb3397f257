"""One rank of the four-chip eager-plane bring-up check (ISSUE 21, 6b).

  python -m horovod_tpu.runner --tpu -np 4 -- \
      python -W error::RuntimeWarning tools/chip_hvd_worker.py

One process per chip: each must see exactly one local device, a TPU,
with ``jax.distributed`` up, and reduce device arrays over the XLA plane.
``-W error::RuntimeWarning`` turns the runtime's host-staging fallback
warning into a failure, so a run cannot pass on the host TCP plane.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

import horovod_tpu.jax as hvd  # noqa: E402

hvd.init()
rank, size = hvd.rank(), hvd.size()
assert jax.local_device_count() == 1, jax.local_devices()
assert jax.process_count() == size, (jax.process_count(), size)

layout = [(d.id, d.process_index) for d in jax.devices()]
me = jax.local_devices()[0]
assert me.platform == "tpu", f"rank {rank} runs on {me.platform}, not a chip"
print(f"CHIP_HVD_WORKER rank={rank} jax.process_index={jax.process_index()} "
      f"local device id={me.id} coords={getattr(me, 'coords', None)} "
      f"TPU_VISIBLE_DEVICES={os.environ.get('TPU_VISIBLE_DEVICES')} "
      f"CLOUD_TPU_TASK_ID={os.environ.get('CLOUD_TPU_TASK_ID')} "
      f"devices(id,process)={layout}", flush=True)
# Rank order on the "rank" axis, which a mean cannot see.
rows = hvd.allgather(jnp.full((1, 2), float(rank), jnp.float32), name="rows")
print(f"CHIP_HVD_WORKER rank={rank} allgather rows={np.asarray(rows)[:, 0]}",
      flush=True)
np.testing.assert_array_equal(np.asarray(rows)[:, 0], np.arange(size))
params = hvd.broadcast_parameters(
    {"w": jnp.full((4096,), 1.0 + rank, jnp.float32)}, root_rank=0)
assert float(params["w"][0]) == 1.0, (
    f"broadcast from root 0 delivered {float(params['w'][0])}")
opt = hvd.distributed_optimizer(optax.sgd(0.5))
state = opt.init(params)
for step in range(5):
    grads = {"w": jnp.full((4096,), float(rank + step), jnp.float32)}
    updates, state = opt.update(grads, state, params)
    params = optax.apply_updates(params, updates)
# w = 1 - 0.5 * sum_step mean_rank(rank + step)
want = 1.0 - 0.5 * sum((size - 1) / 2 + step for step in range(5))
np.testing.assert_allclose(np.asarray(params["w"]), want, rtol=1e-6)
print(f"CHIP_HVD_WORKER_OK rank={rank}/{size} device={me.platform}:"
      f"{me.device_kind} w={float(params['w'][0])}", flush=True)
hvd.shutdown()
