"""The names inside the two hot programs (PR 24): every scope the
benchmark's per-layer metrics look for is in the lowered train step and
in the four serve programs, and a scope changes no instruction — the
optimised HLO is the same, metadata aside, with ``jax.named_scope``
patched to do nothing. (What the four serve programs compute is held
bitwise, tokens and pool, by ``tests/test_serve_pool.py``; the digests
of PR 26's programs that stood here went with the form they held,
PR 30.) Also the compile log of ``common/compile_cache.py``."""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

import horovod_tpu as hvd
from horovod_tpu.common import compile_cache
from horovod_tpu.models import TransformerConfig, make_train_step
from horovod_tpu.parallel import build_mesh
from horovod_tpu.serve import decode as decode_lib

TRAIN_SCOPES = ("embed", "attn", "mlp", "head", "loss", "optimizer",
                "hvd_flash_fwd", "flash_bwd", "hvd_flash_bwd_dkv",
                "hvd_flash_bwd_dq")
BS, WIDTH = 8, 3
CFG = TransformerConfig.tiny(dtype=jnp.float32, sp_attention="flash",
                             remat=True, remat_policy="full")


def _lower_step(mesh, compression=None):
    init, step, _ = make_train_step(CFG, mesh, compression=compression)
    state = jax.eval_shape(init, jax.random.PRNGKey(0))
    rows = 2 * mesh.devices.size
    return step.lower(state, {"tokens": jax.ShapeDtypeStruct(
        (rows, 33), jnp.int32)})


def _lower_serve(program, **fields):
    """One of the four serve programs of a tiny model (GQA, unless
    ``fields`` say otherwise), lowered on abstract arguments."""
    cfg = TransformerConfig.tiny(dtype=jnp.float32, remat=False, **fields)
    decode_lib._cached_serve_fns.cache_clear()
    fns = dict(zip(("prefill", "prefill_resume", "decode", "inject",
                    "verify"),
                   decode_lib.make_serve_fns(cfg, None, block_size=BS,
                                             table_width=WIDTH)))
    from horovod_tpu.models import init_transformer
    params = jax.eval_shape(lambda: init_transformer(
        cfg, jax.random.PRNGKey(0)))
    cache = jax.ShapeDtypeStruct(
        (cfg.n_layers, 9, BS, cfg.n_kv_heads, cfg.head_dim), jnp.float32)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    args = {
        "prefill": (i32(16), i32(), i32(WIDTH)),
        "prefill_resume": (i32(8), i32(), i32(), i32(WIDTH)),
        "decode": (i32(4), i32(4), i32(4, WIDTH)),
        "verify": (i32(4, 2), i32(4), i32(4, WIDTH)),
    }[program]
    return fns[program].lower(params, cache, cache, *args)


def _scope_words(lowered):
    text = lowered.as_text(debug_info=True)
    return set(re.findall(r"[A-Za-z_]\w*", " ".join(
        re.findall(r'loc\("([^"]*)"', text))))


@pytest.mark.parametrize("mesh_axes,compression", [
    ({"dp": 1}, None),
    ({"dp": 2}, "int8"),
    ({"fsdp": 2}, "int8"),
])
def test_train_step_carries_every_scope_name(devices, mesh_axes,
                                             compression):
    n = 1
    for size in mesh_axes.values():
        n *= size
    mesh = build_mesh(devices=devices[:n], **mesh_axes)
    comp = getattr(hvd.Compression, compression) if compression else None
    words = _scope_words(_lower_step(mesh, comp))
    assert set(TRAIN_SCOPES) <= words, set(TRAIN_SCOPES) - words


@pytest.mark.parametrize("program", ["prefill", "prefill_resume", "decode",
                                     "verify"])
def test_serve_programs_carry_every_scope_name(program):
    lowered = _lower_serve(program)
    words = _scope_words(lowered)
    want = {"embed", "attn", "kv_write", "mlp", "head"}
    if program != "prefill":          # prefill attends over the prompt
        want.add("kv_gather")
    assert want <= words, want - words
    # the jitted functions keep their names: `jit(decode)/...` is how a
    # trace tells the programs apart
    assert f"jit({program})" in lowered.as_text(debug_info=True)


@pytest.mark.parametrize("program", ["prefill_resume", "decode"])
def test_sparse_and_lightning_programs_carry_every_scope_name(program):
    """ISSUE 50: the names the sparse and lightning kinds' per-layer
    metrics read, under ``attn``, in a chunk and in a decode step."""
    from horovod_tpu.models import init_transformer
    from horovod_tpu.serve.kv_cache import init_kv_cache

    cfg = TransformerConfig.tiny(
        dtype=jnp.float32, remat=False, n_layers=2, d_head=16,
        layer_types=("sparse", "lightning"), qk_norm_per_head=True,
        attn_gate=True, sparse_kernel=4, sparse_stride=2, sparse_block=BS,
        sparse_topk=4, sparse_window=16, sparse_dense_len=16)
    fns = dict(zip(("prefill", "prefill_resume", "decode"),
                   decode_lib.make_serve_fns(cfg, None, block_size=BS,
                                             table_width=6)))
    params = jax.eval_shape(lambda: init_transformer(
        cfg, jax.random.PRNGKey(0)))
    kc, vc = jax.eval_shape(lambda: (lambda c: (c.k, c.v))(
        init_kv_cache(cfg, 13, BS, n_slots=2)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    args = {"prefill_resume": (i32(8), i32(), i32(), (i32(6), i32())),
            "decode": (i32(2), i32(2), (i32(2, 6), i32(2)))}[program]
    lowered = fns[program].lower(params, kc, vc, *args)
    words = _scope_words(lowered)
    want = {"embed", "attn", "mlp", "head", "attn_sparse", "kv_write",
            "sparse_compress", "sparse_select", "sparse_attend", "kv_gather",
            "attn_lightning", "state_write",
            "lightning_step" if program == "decode" else "lightning_scan"}
    assert want <= words, want - words
    assert f"jit({program})" in lowered.as_text(debug_info=True)


def _instructions(lowered):
    """The optimised HLO with everything that only names things taken
    out: metadata, and the tables of files and stack frames."""
    text = lowered.compile().as_text()
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    return "\n".join(line for line in text.splitlines()
                     if not re.match(r"^(FileNames|FunctionNames|"
                                     r"FileLocations|StackFrames|\d+ )",
                                     line.strip()))


@pytest.mark.parametrize("what", ["train_step", "decode"])
def test_a_scope_changes_no_instruction(devices, monkeypatch, what):
    def lower():
        if what == "decode":
            return _lower_serve("decode")
        return _lower_step(build_mesh(devices=devices[:1], dp=-1))

    with_scopes = lower()
    assert "attn" in _scope_words(with_scopes)
    named = _instructions(with_scopes)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = lower()
    assert not {"attn", "mlp", "head"} & _scope_words(without)
    assert _instructions(without) == named
    assert named.count("\n") > 50


def test_compile_stats_counts_a_new_program_once(monkeypatch, tmp_path):
    # With the variable set nothing is configured in code, so no test
    # after this one writes a persistent cache.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    compile_cache.use_compile_cache()    # one listener however often

    def hvd_fresh_program(x):
        return jnp.tanh(x) * 3.0 + 1.0

    x = jnp.ones((7, 5))                 # a program of its own
    before = compile_cache.compile_stats()
    f = jax.jit(hvd_fresh_program)
    f(x).block_until_ready()
    once = compile_cache.compile_stats()
    for key in ("programs_lowered", "programs_compiled"):
        assert once[key] == before[key] + 1, key
    # the jitted numpy functions inside are traced too (and counted)
    assert once["programs_traced"] > before["programs_traced"]
    assert "hvd_fresh_program" in {
        e["fun_name"] for e in once["recent"] if e["kind"] == "trace"}
    assert once["backend_compile_s"] > before["backend_compile_s"]
    assert once["cache_hits"] == before["cache_hits"]
    assert once["cache_misses"] == before["cache_misses"] + 1
    last = once["recent"][-1]
    assert (last["kind"], last["fun_name"]) == (
        "compile", "jit(hvd_fresh_program)")
    f(x).block_until_ready()
    again = compile_cache.compile_stats()
    assert {k: v for k, v in again.items() if k != "recent"} == {
        k: v for k, v in once.items() if k != "recent"}
