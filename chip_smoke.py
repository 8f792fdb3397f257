#!/usr/bin/env python3
"""Smoke run of the main path on the chip: does the system still start?

One process, over whatever ``jax.devices()`` offers, through the entry
points a user calls (``build_mesh``, ``make_train_step``,
``ServeEngine``, ``hvd.init``), at the full width of ``transformer_std``
(d=2048, 8 layers, 16/8 heads, d_ff 8192, vocab 8192, seq 1024, bf16,
flash attention) with random weights from a seed. Four phases, none
optional; an exception in any of them ends the run non-zero and prints
no result:

* trainer — ``make_train_step`` on ``build_mesh(dp=-1)`` (one chip) or
  ``build_mesh(dp=2, fsdp=2)`` (four), 8 rows per chip: loss finite and
  falling, the compiled step holds a Mosaic ``tpu_custom_call``, the
  state is sharded as ``param_specs`` says;
* kernel — ``flash_attention`` forward and gradient against a float32
  ``local_attention`` on the same inputs, at each of ``KERNEL_SHAPES``;
* server — a ``ServeEngine`` over the same widths answers eight
  requests; first token checked against ``transformer_forward``;
* eager — ``hvd.init()``, one bf16 device-array ``hvd.allreduce``
  through the XLA exec callback, ``hvd.shutdown()`` (which also proves
  ``native/`` builds from a clean tree on this machine).

It neither sets nor trusts ``JAX_PLATFORMS``: no TPU, no run. Seconds
printed per phase are observations to read, not metrics to gate on. The
last line of stdout is ``{"ok": true, "device": {...}}``.

``tests/test_chip_smoke.py`` runs the same phases at tiny widths on
four virtual CPU devices.
"""

from __future__ import annotations

import json
import math
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# Stated tolerances, each relative to the reference tensor's largest
# magnitude. bf16 keeps 8 significant bits, so one rounding of an output
# is up to 2^-9 of it. On the v5e the kernel measured 2.0e-3 (out) and
# 2.8e-3..4.6e-3 (dq/dk/dv) at the shape below (my chip run, PR 21).
KERNEL_FWD_TOL = 2 ** -7      # flash output vs f32 reference
KERNEL_GRAD_TOL = 2 ** -6     # dq/dk/dv: the backward's XLA einsums run
#                               at the TPU's default (bf16-pass) precision
FIRST_TOKEN_TOL = 2 ** -5     # served token's reference logit vs the max

# The kernel phase's shapes: the trainer's, and a cold prompt of the
# benchmark's batch cell as `serve/decode.py::_attend_prompt` hands it
# to the kernel (one row, GQA 4, a length that pads inside the kernel).
KERNEL_SHAPES = ({}, dict(batch=1, seq=1536, heads=32, kv_heads=8))


def _obs(phase: str, **kv) -> dict:
    """Print one phase's observations as a JSON line and return them."""
    print(json.dumps({"phase": phase, **kv}), flush=True)
    return kv


def _timed_compile(jitted, *args):
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    return compiled, round(time.perf_counter() - t0, 2)


def check_state_sharded(state, cfg, mesh) -> int:
    """Every state leaf that is a param or mirrors one — its key path
    ends with the param's and it has the param's shape (Adam's moments;
    not Adafactor's factored statistics) — and whose ``param_specs``
    entry names ``fsdp`` must hold exactly its share of the elements on
    each device. Returns how many leaves were checked.

    Reads only ``param_specs`` and the arrays' shards, not the
    factories' own bookkeeping, so it checks them from outside."""
    import jax
    from jax.sharding import PartitionSpec as P
    from jax.tree_util import tree_flatten_with_path

    from horovod_tpu.models import param_specs

    specs = dict(tree_flatten_with_path(
        param_specs(cfg), is_leaf=lambda x: isinstance(x, P))[0])
    shapes = {path: leaf.shape for path, leaf in
              tree_flatten_with_path(state["params"])[0]}
    checked = 0
    for path, leaf in tree_flatten_with_path(state)[0]:
        name = next((path[i:] for i in range(len(path))
                     if shapes.get(path[i:]) == leaf.shape), None)
        if name is None:
            continue
        axes = [a for entry in specs[name] if entry is not None
                for a in (entry if isinstance(entry, tuple) else (entry,))]
        if "fsdp" not in axes:
            continue
        share = math.prod(mesh.shape[a] for a in axes)
        got = {s.data.size for s in leaf.addressable_shards}
        if got != {leaf.size // share}:
            raise AssertionError(
                f"{jax.tree_util.keystr(path)}{leaf.shape} with spec "
                f"{specs[name]} holds {sorted(got)} elements per device, "
                f"want {leaf.size // share} (1/{share}): the state is not "
                "sharded")
        checked += 1
    return checked


def transformer_std_config():
    """``transformer_std``: a standard-proportioned 8-layer d=2048 GQA
    decoder, flash attention with sequence-spanning tiles, remat off,
    layer scan unrolled."""
    import jax.numpy as jnp

    from horovod_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab_size=8192, d_model=2048, n_layers=8, n_heads=16,
        n_kv_heads=8, d_ff=8192, max_seq=1024, dtype=jnp.bfloat16,
        sp_attention="flash", remat=False, scan_unroll=8)


def phase_trainer(cfg, mesh, *, rows_per_chip: int = 8, seq: int = 1024,
                  steps: int = 6, on_chip: bool = True) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import make_train_step

    n = mesh.devices.size
    init_state, step, _ = make_train_step(cfg, mesh)
    # examples/lm_pretrain.py's spelling: an outer jit used to drop the
    # init's in-trace device_put and replicate the state; the factory
    # now pins the layout whatever the spelling.
    state = jax.jit(init_state)(jax.random.PRNGKey(0))
    n_sharded = check_state_sharded(state, cfg, mesh)

    # The same seeded rows on every data shard: the global mean loss and
    # gradient then equal the one-chip run's, so the loss trajectory is
    # comparable across chip counts while each chip does full work.
    rows = jax.random.randint(jax.random.PRNGKey(1), (rows_per_chip, seq + 1),
                              0, cfg.vocab_size)
    batch = {"tokens": jax.device_put(
        jnp.tile(rows, (n, 1)),
        NamedSharding(mesh, P(("dp", "fsdp"), None)))}

    compiled, compile_s = _timed_compile(step, state, batch)
    mosaic = "tpu_custom_call" in compiled.as_text()
    if on_chip and cfg.sp_attention == "flash" and not mosaic:
        raise AssertionError(
            "compiled train step holds no Mosaic tpu_custom_call: the "
            "flash kernel ran interpreted or gave way to local_attention")

    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, loss = compiled(state, batch)
        loss.block_until_ready()
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))

    # Does block_until_ready wait on this machine? Chain three steps
    # with no sync, block, then fetch the scalar: if the block returned
    # early the fetch pays for the steps.
    t0 = time.perf_counter()
    for _ in range(3):
        state, loss = compiled(state, batch)
    loss.block_until_ready()
    t_block = time.perf_counter() - t0
    losses.append(float(loss))
    t_fetch = time.perf_counter() - t0 - t_block
    check_state_sharded(state, cfg, mesh)

    if not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    mem = [(d.memory_stats() or {}).get("bytes_in_use")
           for d in jax.local_devices()]
    return _obs(
        "trainer", mesh={k: v for k, v in mesh.shape.items() if v > 1},
        compile_s=compile_s, first_step_s=round(step_s[0], 4),
        steady_step_s=round(statistics.median(step_s[1:]), 4),
        losses=[round(v, 4) for v in losses], mosaic_custom_call=mosaic,
        sharded_leaves_checked=n_sharded, bytes_in_use_per_device=mem,
        block_until_ready_waits=bool(t_fetch < 0.1 * (t_block + t_fetch)),
        three_steps_block_s=round(t_block, 4),
        then_fetch_s=round(t_fetch, 5))


def phase_kernel(*, batch: int = 2, seq: int = 1024, heads: int = 16,
                 kv_heads: int = 8, head_dim: int = 128) -> dict:
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.flash_attention import flash_attention
    from horovod_tpu.parallel.ring_attention import local_attention

    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(2), 4)
    q = (jax.random.normal(kq, (batch, seq, heads, head_dim)) * 0.5
         ).astype(jnp.bfloat16)
    k = (jax.random.normal(kk, (batch, seq, kv_heads, head_dim)) * 0.5
         ).astype(jnp.bfloat16)
    v = (jax.random.normal(kv, (batch, seq, kv_heads, head_dim)) * 0.5
         ).astype(jnp.bfloat16)
    w = jax.random.normal(kw, q.shape, jnp.float32)  # makes d(out) dense

    def flash(q, k, v):
        out = flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32) * w), out

    def reference(q, k, v):
        rep = heads // kv_heads
        out = local_attention(q, jnp.repeat(k, rep, axis=2),
                              jnp.repeat(v, rep, axis=2), causal=True)
        return jnp.sum(out * w), out

    jitted = jax.jit(jax.value_and_grad(flash, argnums=(0, 1, 2),
                                        has_aux=True))
    compiled, compile_s = _timed_compile(jitted, q, k, v)
    t0 = time.perf_counter()
    (_, out), grads = jax.block_until_ready(compiled(q, k, v))
    run_s = round(time.perf_counter() - t0, 4)
    with jax.default_matmul_precision("highest"):
        (_, ref), ref_grads = jax.jit(jax.value_and_grad(
            reference, argnums=(0, 1, 2), has_aux=True))(
                *(x.astype(jnp.float32) for x in (q, k, v)))

    def rel_err(got, want):
        return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                     / jnp.max(jnp.abs(want)))

    errs = {"out": rel_err(out, ref)}
    errs.update({f"d{name}": rel_err(g, r)
                 for name, g, r in zip("qkv", grads, ref_grads)})
    for name, e in errs.items():
        tol = KERNEL_FWD_TOL if name == "out" else KERNEL_GRAD_TOL
        if not e <= tol:
            raise AssertionError(
                f"flash_attention {name} differs from the f32 reference "
                f"by {e:.3g} of its max magnitude (tolerance {tol:.3g})")
    return _obs("kernel", shape=[batch, seq, heads, kv_heads, head_dim],
                compile_s=compile_s, fwd_bwd_s=run_s,
                rel_err={k: round(e, 5) for k, e in errs.items()},
                tol={"out": KERNEL_FWD_TOL, "grads": KERNEL_GRAD_TOL})


def phase_server(cfg, *, n_requests: int = 8, max_prompt: int = 512,
                 new_tokens: int = 32) -> dict:
    import jax
    import jax.numpy as jnp

    from horovod_tpu import serve
    from horovod_tpu.models import init_transformer, transformer_forward

    params = jax.jit(lambda key: init_transformer(cfg, key))(
        jax.random.PRNGKey(0))
    serve_cfg = serve.ServeConfig(max_batch=n_requests, max_prompt=max_prompt,
                                  max_new_tokens=new_tokens)
    trace = serve.make_trace(n_requests, seed=0, max_prompt=max_prompt,
                             min_new=new_tokens, max_new=new_tokens,
                             vocab=cfg.vocab_size)

    def serve_trace():
        # A fresh engine per pass shares the jitted programs (memoized
        # on config and block geometry) but not the prefix cache, so the
        # second pass runs the same programs with nothing to compile.
        engine = serve.ServeEngine(cfg, params, serve_cfg)
        t0 = time.perf_counter()
        rids = [engine.submit(p, n) for p, n in trace]
        engine.run_until_idle()
        return ([engine.result(r) for r in rids],
                round(time.perf_counter() - t0, 3))

    results, cold_s = serve_trace()
    results, warm_s = serve_trace()
    for (prompt, n), res in zip(trace, results):
        if res.status != "ok" or len(res.tokens) != n:
            raise AssertionError(
                f"request with prompt {len(prompt)}: status {res.status}, "
                f"{len(res.tokens)} tokens, want ok and {n}")

    # The longest prompt's first served token against the training
    # forward (flash kernel, no cache): its logit must be the maximum up
    # to bf16 rounding of the logits.
    i = max(range(n_requests), key=lambda j: len(trace[j][0]))
    logits = transformer_forward(
        params, jnp.asarray([trace[i][0]], jnp.int32), cfg)[0, -1]
    logits = logits.astype(jnp.float32)
    gap = float(logits.max() - logits[results[i].tokens[0]])
    tol = FIRST_TOKEN_TOL * float(jnp.max(jnp.abs(logits)))
    if not gap <= tol:
        raise AssertionError(
            f"first served token {results[i].tokens[0]} sits {gap:.4g} "
            f"below transformer_forward's max logit (tolerance {tol:.4g})")
    return _obs("server", requests=n_requests,
                prompt_lens=[len(p) for p, _ in trace],
                new_tokens=new_tokens, cold_pass_s=cold_s,
                warm_pass_s=warm_s,
                warm_tokens_per_s=round(n_requests * new_tokens / warm_s, 1),
                first_token_logit_gap=round(gap, 5))


def phase_eager() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd

    t0 = time.perf_counter()
    hvd.init()
    init_s = round(time.perf_counter() - t0, 2)
    try:
        x = (jnp.arange(4096, dtype=jnp.float32) / 64).astype(jnp.bfloat16)
        t0 = time.perf_counter()
        y = hvd.allreduce(x, op=hvd.Average, name="chip_smoke")
        y = jax.block_until_ready(y)
        first_s = round(time.perf_counter() - t0, 4)
        if not isinstance(y, jax.Array) or y.dtype != jnp.bfloat16:
            raise AssertionError(
                f"allreduce of a bf16 device array returned {type(y)} "
                f"{getattr(y, 'dtype', None)}: it left the device plane")
        np.testing.assert_array_equal(np.asarray(y.astype(jnp.float32)),
                                      np.asarray(x.astype(jnp.float32)))
        size = hvd.size()
    finally:
        hvd.shutdown()
    return _obs("eager", size=size, init_s=init_s,
                first_allreduce_s=first_s,
                result_platform=next(iter(y.devices())).platform)


def run(cfg, mesh, *, on_chip: bool, trainer=None, kernel=None,
        server=None) -> None:
    """All four phases in order. Any exception propagates: no phase is
    optional. The keyword dicts resize the phases (the CPU test)."""
    phase_trainer(cfg, mesh, on_chip=on_chip, **(trainer or {}))
    for shape in ([kernel] if kernel else KERNEL_SHAPES):
        phase_kernel(**shape)
    phase_server(cfg, **(server or {}))
    phase_eager()


def main() -> int:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: jax.devices()[0] is {dev.platform!r} "
              f"({dev.device_kind}); this run proves nothing",
              file=sys.stderr)
        return 2

    sys.path.insert(0, HERE)
    from horovod_tpu.common.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()

    import importlib.metadata

    import jaxlib
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    _obs("device", platform=dev.platform, device_kind=dev.device_kind,
         count=len(devices), jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu, compile_cache=cache_dir,
         cache_entries_at_start=(len(os.listdir(cache_dir))
                                 if os.path.isdir(cache_dir) else 0))

    from horovod_tpu.parallel import build_mesh

    if len(devices) == 1:
        mesh = build_mesh(dp=-1)
    elif len(devices) == 4:
        mesh = build_mesh(dp=2, fsdp=2)
    else:
        raise SystemExit(f"chip_smoke: {len(devices)} devices; the smoke "
                         "knows one chip and one four-chip host")
    t0 = time.perf_counter()
    run(transformer_std_config(), mesh, on_chip=True)
    _obs("total", seconds=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
