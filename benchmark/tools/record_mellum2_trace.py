"""Record the small trace that ``benchmark/tests/test_mellum2_cell.py``
checks the ``.mellum`` metrics' readers against: three train steps of a
two-layer stack (one sliding layer, one full) with a chip's share of the
experts, at sizes the v5e's kernels take (heads of 128, rows of 512), on
the chip.

    chiprun -- python -m benchmark.tools.record_mellum2_trace chiprun_out/tiny-mellum2

Copy ``tiny-mellum2.xplane.pb`` from there to ``benchmark/tests/data/``.
``MODEL`` is the ``model`` group the test hands the readers.
"""

import glob
import os
import shutil
import sys

MODEL = {"vocab_size": 512, "d_model": 256, "n_layers": 2, "n_heads": 2,
         "n_kv_heads": 1, "d_head": 128, "d_ff": 128, "max_seq": 512,
         "rope_theta": 10000.0, "norm_eps": 1e-06,
         "layer_types": ["sliding", "full"], "attn_window": 128,
         "layer_rotary": {
             "sliding": {"theta": 10000.0},
             "full": {"theta": 10000.0, "factor": 4.0,
                      "original_max_seq": 128, "beta_fast": 4.0,
                      "beta_slow": 1.0, "attention_factor": 1.25}},
         "n_experts": 8, "moe_top_k": 2, "moe_capacity_factor": None,
         "moe_norm_topk_prob": True, "moe_aux_loss_coef": 0.001,
         "moe_z_loss_coef": 0.0, "moe_experts_held": 4,
         "moe_expert_offset": 2}
RUN = {"dtype": "bfloat16", "sp_attention": "flash", "remat": True,
       "remat_policy": "full"}
SEQ = 512


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from benchmark import harness
    from horovod_tpu.models import make_train_step
    from horovod_tpu.parallel import build_mesh

    devices = harness.require_tpu(1)
    cfg = harness.model_config({"model": MODEL, "run": RUN})
    init_state, step, _ = make_train_step(
        cfg, build_mesh(devices=devices, dp=-1))
    state = init_state(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (1, SEQ + 1), 0, cfg.vocab_size, jnp.int32)}
    state, loss = step(state, batch)
    loss.block_until_ready()
    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(out_dir)
    for i in range(3):
        with jax.profiler.StepTraceAnnotation("train:step", step_num=i):
            state, loss = step(state, batch)
            loss.block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                        recursive=True)
    kept = os.path.join(out_dir, "tiny-mellum2.xplane.pb")
    with open(kept, "wb") as f:
        f.write(without_programs(path))
    shutil.rmtree(os.path.join(out_dir, "plugins"), ignore_errors=True)
    print(os.path.getsize(kept))


def without_programs(path: str) -> bytes:
    """The trace without the ``/host:metadata`` plane, which holds every
    program's HLO (1.8 of the 2.5 MB) and which no reader reads."""
    from benchmark.reducers import hvd_xplane_pb2

    space = hvd_xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    planes = [p for p in space.planes if p.name != "/host:metadata"]
    del space.planes[:]
    space.planes.extend(planes)
    return space.SerializeToString()


if __name__ == "__main__":
    main(sys.argv[1])
