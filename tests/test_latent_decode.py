"""A decode step's latent attention through ``hvd_latent_decode``
(``ops/latent_decode.py``, interpret mode here) against the XLA form it
replaced in ``mla_step``: ``reference_mla.mla_attend_absorbed`` over
``mla_pages``, the same pool and the same tables (ISSUE 45)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import TransformerConfig
from horovod_tpu.ops import latent_decode as latent_lib
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
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=heads,
        n_kv_heads=heads, d_head=16, d_ff=32, layer_types=("mla", "mla"),
        mla_kv_rank=32, mla_rope_dim=8, dtype=jnp.dtype(dtype))
    rank, rope, row = cfg.mla_kv_rank, cfg.mla_rope_dim, latent_row(cfg)
    rng = np.random.default_rng(heads + layer)
    lengths, tables = CASES[case](rng)
    B = len(lengths)
    ks = jax.random.split(jax.random.PRNGKey(B), 4)
    lp = {"w_ukv": (jax.random.normal(ks[0], (rank, heads * 2 * 16))
                    * rank ** -0.5).astype(cfg.dtype)}
    # every page holds numbers, the ones no row maps and the places past
    # a row's length too (what a pool holds there is whatever an earlier
    # sequence left); the row's last lanes are zeros, as they are written
    pool = jnp.pad(jax.random.normal(ks[1], (2, N_PAGES, PAGE, rank + rope)),
                   ((0, 0),) * 3 + ((0, row - rank - rope),)).astype(cfg.dtype)
    qn = jax.random.normal(ks[2], (B, 1, heads, 16)).astype(cfg.dtype)
    qr = jax.random.normal(ks[3], (B, 1, heads, rope)).astype(cfg.dtype)
    tables = jnp.asarray(tables, jnp.int32)
    positions = jnp.asarray(lengths, jnp.int32) - 1

    keys_of, blocks_to = decode_lib.mla_pages(pool, layer, tables,
                                              WAVE * PAGE)
    want = mla_attend_absorbed(
        cfg, lp, qn, qr, keys_of, blocks_to(positions.max()),
        positions[:, None])
    monkeypatch.setattr(latent_lib, "_wave_pages", lambda page: WAVE)
    got = jax.jit(lambda *a: decode_lib._mla_decode(cfg, lp, *a))(
        qn, qr, pool, jnp.int32(layer), tables, positions)
    assert got.shape == want.shape == (B, 1, heads, 16)
    limit = 1e-5 if dtype == "float32" else 2e-2
    assert gap(np.asarray(got, np.float32),
               np.asarray(want, np.float32)) < limit


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
