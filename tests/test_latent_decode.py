"""A decode step's latent attention through ``hvd_latent_decode``
(``ops/paged_decode.py::latent_decode``, interpret mode here) against
the XLA form it replaced in ``mla_step``:
``reference_mla.mla_attend_absorbed`` over ``mla_pages``, the same pool
and the same tables (ISSUE 45); and against the kernel of its own it had
before it shared ``hvd_paged_decode``'s (ISSUE 58)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.models import TransformerConfig
from horovod_tpu.ops import paged_decode as latent_lib
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.kv_cache import latent_row
from horovod_tpu.serve.metrics import ServeMetrics
from reference_mla import mla_attend_absorbed

PAGE, WIDTH, WAVE = 16, 8, 2     # a key block of 32: up to four a row
N_PAGES = 64


def gap(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


def shuffled(rng, rows):
    """A table a row, no page twice, in no order."""
    return 1 + rng.permutation(N_PAGES - 1)[:rows * WIDTH].reshape(
        rows, WIDTH)


def ragged(rng):
    """Row 0 is a padded row (position 0 behind an all-null table);
    rows that end on a page's last position and on the next one's
    first; a row at the table's full width beside rows of one block."""
    lengths = [1, 2 * PAGE, 2 * PAGE + 1, WIDTH * PAGE, 5, WAVE * PAGE]
    tables = shuffled(rng, len(lengths))
    tables[0] = 0
    return lengths, tables


def in_order(rng):
    lengths = [100, 17, 64]
    return lengths, 1 + np.arange(len(lengths) * WIDTH).reshape(-1, WIDTH)


def a_prefix_hit(rng):
    """Rows 0 and 1 map the same first three pages (a prefix both hit)
    and go on in pages of their own; row 2 maps all of row 0's."""
    tables = shuffled(rng, 3)
    tables[1, :3] = tables[0, :3]
    tables[2] = tables[0]
    return [70, 120, 70], tables


CASES = {"ragged": ragged, "shuffled": lambda rng: ([128, 31, 77, 48],
                                                     shuffled(rng, 4)),
         "in_order": in_order, "a_prefix_hit": a_prefix_hit}


def both_forms(heads, lengths, tables, layer, dtype, page=PAGE,
               n_pages=N_PAGES):
    """(``_mla_decode`` through the kernel, the absorbed form in XLA) of
    one query a row at the rows' last positions, out of the same pool of
    two layers read at ``layer``, a key block of :func:`key_block` of
    the module as it stands (the caller's to patch)."""
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=heads,
        n_kv_heads=heads, d_head=16, d_ff=32, layer_types=("mla", "mla"),
        mla_kv_rank=32, mla_rope_dim=8, dtype=jnp.dtype(dtype))
    rank, rope, row = cfg.mla_kv_rank, cfg.mla_rope_dim, latent_row(cfg)
    B = len(lengths)
    ks = jax.random.split(jax.random.PRNGKey(B), 4)
    lp = {"w_ukv": (jax.random.normal(ks[0], (rank, heads * 2 * 16))
                    * rank ** -0.5).astype(cfg.dtype)}
    # every page holds numbers, the ones no row maps and the places past
    # a row's length too (what a pool holds there is whatever an earlier
    # sequence left); the row's last lanes are zeros, as they are written
    pool = jnp.pad(jax.random.normal(ks[1], (2, n_pages, page, rank + rope)),
                   ((0, 0),) * 3 + ((0, row - rank - rope),)).astype(cfg.dtype)
    qn = jax.random.normal(ks[2], (B, 1, heads, 16)).astype(cfg.dtype)
    qr = jax.random.normal(ks[3], (B, 1, heads, rope)).astype(cfg.dtype)
    tables = jnp.asarray(tables, jnp.int32)
    positions = jnp.asarray(lengths, jnp.int32) - 1

    keys_of, blocks_to = decode_lib.mla_pages(
        pool, layer, tables, latent_lib.key_block(page, tables.shape[1]))
    want = mla_attend_absorbed(
        cfg, lp, qn, qr, keys_of, blocks_to(positions.max()),
        positions[:, None])
    got = jax.jit(lambda *a: decode_lib._mla_decode(cfg, lp, *a))(
        qn, qr, pool, jnp.int32(layer), tables, positions)
    assert got.shape == want.shape == (B, 1, heads, 16)
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("heads", [32, 64])
def test_the_kernel_is_the_absorbed_form_over_the_same_pages(
        heads, case, layer, dtype, monkeypatch):
    """Every row's result out of the pool of two layers, read at
    ``layer``: a key block of two pages a wave, so that a row has one to
    four of them and the last is whole, a page short or one position
    long."""
    lengths, tables = CASES[case](np.random.default_rng(heads + layer))
    monkeypatch.setattr(latent_lib, "_wave_pages", lambda page: WAVE)
    got, want = both_forms(heads, lengths, tables, layer, dtype)
    assert gap(got, want) < (1e-5 if dtype == "float32" else 2e-2)


@pytest.mark.parametrize("pages", [1, 7, 8, 9, 15, 16, 17])
def test_a_row_s_last_block_of_any_page_count(pages):
    """Pages of 64 make a wave of 16 (the module's own rule), and a
    row's last key block then holds ``pages`` of them (17: a whole block
    and one page after it): on both sides of the eight pages a turn of
    ``start``'s loop and of every power of two ``wait`` waits for. The
    row ends three positions into its last page; the short row behind
    it is the one whose first block is started under this one's last."""
    page, width = 64, 17
    assert latent_lib._wave_pages(page) == 16
    tables = 1 + np.random.default_rng(pages).permutation(
        2 * width).reshape(2, width)
    got, want = both_forms(8, [(pages - 1) * page + 3, 5], tables, 1,
                           "float32", page=page, n_pages=2 * width + 1)
    assert gap(got, want) < 1e-5


def latent_decode_of_f075984(q, pool, layer, tables, lengths, *, rank, scale,
                             pages):
    """``ops/latent_decode.py::latent_decode`` of commit f075984 (PR 57,
    the last with a kernel of its own: a page started and awaited a turn
    of the loop on a row's last block), its key block given as
    ``pages``, in interpret mode: what ISSUE 58's one kernel is held to
    bit for bit."""
    def kernel(layer_ref, len_ref, first_ref, tab_ref, q_ref, pool_ref,
               o_ref, buf, sem, acc, m_scr, l_scr):
        b, rows = pl.program_id(0), pl.num_programs(0)
        kb = pages * page
        length = len_ref[b]
        n_blocks = pl.cdiv(length, kb)

        def copy(r, j, half, i):
            return pltpu.make_async_copy(
                pool_ref.at[layer_ref[0], tab_ref[r * width + j * pages + i]],
                buf.at[half, i], sem.at[half])

        def wave(r, j, half, how):
            def one(i, _):
                getattr(copy(r, j, half, i), how)()
                return _
            lax.fori_loop(0, jnp.minimum(pages, pl.cdiv(
                len_ref[r] - j * kb, page)), one, 0)

        @pl.when(b == 0)
        def _first():
            buf[...] = jnp.zeros_like(buf)
            wave(0, 0, 0, "start")

        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, latent_lib.NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

        def attend(j, half):
            kv = buf[half].reshape(kb, row)
            s = lax.dot_general(q_ref[...], kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            k_pos = j * kb + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos < length, s, latent_lib.NEG_INF)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            fade = jnp.exp(m_prev - m_new)
            l_scr[...] = fade * l_scr[...] + p.sum(axis=1, keepdims=True)
            acc[...] = acc[...] * fade + lax.dot(
                p.astype(kv.dtype), kv[:, :rank],
                preferred_element_type=jnp.float32)
            m_scr[...] = m_new

        def block(j, _):
            half = (first_ref[b] + j) % 2
            last = j == n_blocks - 1

            def ragged():
                @pl.when(jnp.logical_not(last))
                def _next():
                    wave(b, j + 1, 1 - half, "start")

                @pl.when(last & (b + 1 < rows))
                def _next_row():
                    wave(b + 1, 0, 1 - half, "start")

                wave(b, j, half, "wait")
                attend(j, half)

            def whole():
                for i in range(pages):
                    copy(b, j + 1, 1 - half, i).start()
                pltpu.make_async_copy(buf.at[1 - half], buf.at[half],
                                      sem.at[half]).wait()
                attend(j, half)

            lax.cond((j + 2) * kb <= length, whole, ragged)
            return _

        lax.fori_loop(0, n_blocks, block, 0)
        o_ref[...] = (acc[...] / l_scr[...]).astype(o_ref.dtype)

    (B, H, row), page, width = q.shape, pool.shape[2], tables.shape[1]
    lengths = jnp.maximum(lengths.astype(jnp.int32), 1)
    n_blocks = -(-lengths // (pages * page))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B,),
            in_specs=[pl.BlockSpec((None, H, row), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, H, rank), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, pages, page, row), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((H, rank), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
        interpret=True,
    )(jnp.asarray(layer, jnp.int32).reshape(1), lengths,
      (jnp.cumsum(n_blocks) - n_blocks).astype(jnp.int32),
      tables.astype(jnp.int32).reshape(-1), q, pool)


@pytest.mark.parametrize("case", ["a_prefix_hit", "ragged", "shuffled"])
def test_the_one_kernel_is_the_latent_kernel_it_replaced_bit_for_bit(
        case, monkeypatch):
    """The same bytes in the same places and the same dots: bfloat16
    operands, a pool of two layers read at layer 1, a key block of two
    pages, every row of the case."""
    lengths, tables = CASES[case](np.random.default_rng(0))
    ks = jax.random.split(jax.random.PRNGKey(len(lengths)), 2)
    q = jax.random.normal(ks[0], (len(lengths), 32, 128), jnp.bfloat16)
    pool = jax.random.normal(ks[1], (2, N_PAGES, PAGE, 128), jnp.bfloat16)
    args = (q, pool, 1, jnp.asarray(tables, jnp.int32),
            jnp.asarray(lengths, jnp.int32))
    monkeypatch.setattr(latent_lib, "_wave_pages", lambda page: WAVE)
    got = latent_lib.latent_decode(*args, rank=96, scale=0.07)
    want = latent_decode_of_f075984(*args, rank=96, scale=0.07, pages=WAVE)
    assert got.dtype == want.dtype == jnp.bfloat16
    assert float(jnp.abs(got.astype(jnp.float32)
                         - want.astype(jnp.float32)).max()) == 0.0


@pytest.mark.parametrize("lengths, read, longest", [
    ([1, 1024, 1025, 16384], 1 + 1 + 2 + 16, 4 * 16),   # one long row
    ([3000] * 4, 12, 12),                               # an even batch
    ([1, 1, 1, 1], 4, 4)])                              # padded rows alone
def test_the_counters_say_what_a_loop_to_the_longest_row_would_read(
        lengths, read, longest):
    """``latent_decode_key_blocks_total`` is every row to its own
    length, ``..._longest_total`` every row to the call's longest, both
    times the mla layers and summed over the calls."""
    m = ServeMetrics()
    for _ in range(2):
        m.record_latent_decode(np.asarray(lengths), 1024, 6)
    snap = m.snapshot()
    assert snap["latent_decode_key_blocks_total"] == 2 * 6 * read
    assert snap["latent_decode_key_blocks_longest_total"] == 2 * 6 * longest
    assert latent_lib.key_block(16, 1088) == 1024
    assert latent_lib.key_block(16, 9) == 144        # a table under a wave


def test_a_length_under_one_is_read_as_one():
    """A row with no key block would start no copy for the row after
    it (on the chip: a wait that never ends): every row reads at least
    its first position."""
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    q = jax.random.normal(ks[0], (3, 8, 128))
    pool = jax.random.normal(ks[1], (1, 8, PAGE, 128))
    tables = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32)

    def run(lengths):
        return latent_lib.latent_decode(
            q, pool, 0, tables, jnp.asarray(lengths, jnp.int32), rank=64,
            scale=0.1)
    np.testing.assert_array_equal(run([0, 20, -3]), run([1, 20, 1]))
