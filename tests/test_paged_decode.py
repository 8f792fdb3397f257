"""A decode step's attention of a ``full`` layer through
``hvd_paged_decode`` (``ops/paged_decode.py``, interpret mode here)
against the XLA form it replaced in ``full_step``: ``_attend_keys`` over
every row's whole table, gathered out of the same pools through the
same tables (ISSUE 55); and a window layer's through the same kernel
over each slot's ring where it lies (``ring_decode``, ISSUE 59) against
what ``window_step`` ran before it: ``_attend_keys`` over whole rings
under ``ring_positions`` and the window."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import paged_decode as paged_lib
from horovod_tpu.serve import decode as decode_lib
from horovod_tpu.serve.metrics import ServeMetrics

PAGE, WIDTH, WAVE = 16, 8, 2     # a key block of 32: up to four a row
KB, FULL = WAVE * PAGE, WIDTH * PAGE
N_PAGES = 96

#: the three page shapes the cells have: H, Hkv, Dh, a position's tail
SHAPES = {"rows_of_512": (32, 8, 64, (512,)),        # LFM2: 8 heads of 64
          "one_head_of_128": (20, 1, 128, (1, 128)),     # jamba
          "heads_of_128": (48, 8, 128, (8, 128))}        # trinity

#: the rows' lengths; every batch gains a padded row in front
LENGTHS = {"one": [1, 1],
           "a_page_s_edge": [PAGE - 1, PAGE, PAGE + 1],
           "a_key_block": [KB, KB],
           "one_more_than_a_key_block": [KB + 1, 2 * KB + 1],
           "the_table_s_width": [FULL, FULL - 1],
           "mixed": [1, PAGE, PAGE + 1, KB, KB + 1, FULL, 5, 100]}


def gap(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


def xla_form(q, k_pool, v_pool, layer, tables, lengths, n_kv):
    """``full_step``'s attention before ISSUE 55: every row's whole
    table gathered, one masked softmax over its width."""
    B, H, Dh = q.shape
    keys, vals = (pool[layer, tables].reshape(B, FULL, n_kv, Dh)
                  for pool in (k_pool, v_pool))
    return decode_lib._attend_keys(
        q[:, None], keys, vals, jnp.arange(FULL, dtype=jnp.int32)[None],
        lengths[:, None] - 1, None).reshape(B, H, Dh)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(LENGTHS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_kernel_is_the_gathered_form_over_the_same_pages(
        shape, case, dtype, monkeypatch):
    """Every row's result out of pools of two layers, read at layer 1,
    behind shuffled tables. Every page that holds no position below its
    row's length is NaN in the pools the kernel reads (the gathered form
    reads them clean: it multiplies what it masks by zero), so a page
    copied past a length shows. Row 0 is a padded row of the batch
    bucket: length 0 behind a stale table (row 1's), its result
    whatever one page gives and not compared."""
    H, n_kv, Dh, tail = SHAPES[shape]
    lengths = np.asarray([0] + LENGTHS[case])
    B = len(lengths)
    rng = np.random.default_rng(B)
    tables = 1 + rng.permutation(N_PAGES - 1)[:B * WIDTH].reshape(B, WIDTH)
    tables[0] = tables[1]
    ks = jax.random.split(jax.random.PRNGKey(H), 3)
    pools = [jax.random.normal(k, (2, N_PAGES, PAGE) + tail).astype(dtype)
             for k in ks[:2]]
    q = jax.random.normal(ks[2], (B, H, Dh)).astype(dtype)
    live = np.zeros(N_PAGES, bool)
    for table, n in zip(tables[1:], lengths[1:]):
        live[table[:-(-n // PAGE)]] = True
    poisoned = [jnp.where(live[None, :, None, None] if len(tail) == 1
                          else live[None, :, None, None, None], pool, jnp.nan)
                for pool in pools]
    tables, lengths = jnp.asarray(tables, jnp.int32), jnp.asarray(
        lengths, jnp.int32)

    want = xla_form(q, *pools, 1, tables, jnp.maximum(lengths, 1), n_kv)
    monkeypatch.setattr(paged_lib, "_wave_pages", lambda page: WAVE)
    got = paged_lib.paged_decode(q, *poisoned, jnp.int32(1), tables, lengths)
    assert got.shape == want.shape == (B, H, Dh) and got.dtype == q.dtype
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    limit = 1e-5 if dtype == "float32" else 2e-2
    assert gap(got[1:], want[1:]) < limit


@pytest.mark.parametrize("lengths, read", [
    ([1, 16, 17, 2560], 1 + 1 + 2 + 160),       # one row fills its table
    ([1000] * 4, 4 * 63),                       # an even batch
    ([1, 1, 1, 1], 4)])                         # padded rows alone
def test_the_counters_say_what_whole_tables_would_have_held(lengths, read):
    """``paged_decode_pages_total`` is every row to its own length,
    ``..._table_total`` every row's whole table, both times the full
    layers and summed over the calls."""
    m = ServeMetrics()
    for _ in range(2):
        m.record_paged_decode(np.asarray(lengths), 16, 160, 3)
    snap = m.snapshot()
    assert snap["paged_decode_pages_total"] == 2 * 3 * read
    assert snap["paged_decode_pages_table_total"] == 2 * 3 * 4 * 160
    assert paged_lib.key_block(16, 160) == 1024
    assert paged_lib.key_block(16, 9) == 144         # a table under a wave


#: a ring of ten pages of 4 under a window of 24 (a chunk of 12 beside
#: it, ``kv_cache.ring_width``): tables of 7 pages, key blocks of 2
RING_PAGE, WINDOW, RING = 4, 24, 40

#: the positions ``p + 1`` written of each row's sequence; the row's slot
#: is its place in this list counted from the END (slots out of order),
#: and every batch gains a padded row in front (position 0, slot 0)
FRONTIERS = {"one": [1],
             "under_a_page": [RING_PAGE - 1],
             "exactly_the_window": [WINDOW],
             "between_window_and_ring": [WINDOW + 6, WINDOW + 5, RING],
             "just_past_the_ring": [RING + 1, RING + 3, RING + WINDOW - 1],
             "a_multiple_of_the_ring": [2 * RING, 3 * RING],
             "slots_out_of_order": [7, 2 * RING + 9, WINDOW + 1, 31, 1]}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(FRONTIERS))
def test_a_ring_is_read_where_it_lies_no_further_back_than_the_window(
        case, dtype, monkeypatch):
    """Every row's result out of rings of two layers, read at layer 1,
    against ``_attend_keys`` over each row's whole ring under
    ``ring_positions`` and the window (``window_step`` before ISSUE 59).
    Every page of a slot's ring that holds no position its row sees is
    NaN in the rings the kernel reads (the XLA form reads them clean),
    so a page read outside the window, or in another slot, shows."""
    H, n_kv, Dh = 8, 2, 128
    frontiers = np.asarray([1] + FRONTIERS[case])
    B = len(frontiers)
    slots = np.asarray([0] + list(range(B - 1, 0, -1)))
    n_slots, per = B + 1, RING // RING_PAGE       # a slot nobody holds
    ks = jax.random.split(jax.random.PRNGKey(B), 3)
    rings = [jax.random.normal(k, (2, n_slots, RING, n_kv, Dh)).astype(dtype)
             for k in ks[:2]]
    q = jax.random.normal(ks[2], (B, H, Dh)).astype(dtype)
    live = np.zeros((n_slots, per), bool)
    for slot, n in zip(slots, frontiers):
        seen = np.arange(max(0, n - WINDOW), n) % RING
        live[slot, np.unique(seen // RING_PAGE)] = True
    poisoned = [jnp.where(np.repeat(live, RING_PAGE, 1)[None, :, :, None,
                                                         None], r, jnp.nan)
                for r in rings]
    slots, positions = (jnp.asarray(a, jnp.int32)
                        for a in (slots, frontiers - 1))

    want = decode_lib._attend_keys(
        q[:, None], *(r[1, slots] for r in rings),
        decode_lib.ring_positions(positions + 1, RING), positions[:, None],
        WINDOW).reshape(B, H, Dh)
    monkeypatch.setattr(paged_lib, "_wave_pages", lambda page: WAVE)
    got = paged_lib.ring_decode(q, *poisoned, jnp.int32(1), slots, positions,
                                window=WINDOW, page=RING_PAGE)
    assert got.shape == want.shape == (B, H, Dh) and got.dtype == q.dtype
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    limit = 1e-5 if dtype == "float32" else 2e-2
    assert gap(got, want) < limit


@pytest.mark.parametrize("frontiers, read", [
    ([1, 16, 17, 4096], 1 + 1 + 2 + 256),           # all inside the window
    ([4097, 4111, 4112], 257 + 257 + 256),          # a window from mid-page
    ([5137, 5136 + 4096], 257 + 256),               # round the ring's end
    ([1, 1, 1, 1], 4)])                             # padded rows alone
def test_the_counters_say_what_whole_rings_would_have_held(frontiers, read):
    """``window_decode_pages_total`` is every row from the page of its
    window's first key to its own position, ``..._ring_total`` every
    slot's whole ring, both times the window layers and summed over the
    calls."""
    m = ServeMetrics()
    for _ in range(2):
        m.record_window_decode(np.asarray(frontiers), 4096, 5136, 16, 33, 4)
    snap = m.snapshot()
    assert snap["window_decode_pages_total"] == 2 * 4 * read
    assert snap["window_decode_pages_ring_total"] == 2 * 4 * 33 * 321
    assert paged_lib.ring_page(5136, 16) == 16
    with pytest.raises(ValueError, match="whole blocks"):
        paged_lib.ring_page(5136, 32)


def test_a_length_under_one_is_read_as_one():
    """A row with no key block would start no copy for the row after
    it (on the chip: a wait that never ends): every row reads at least
    its first position."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (3, 8, 128))
    k_pool, v_pool = (jax.random.normal(k, (1, 8, PAGE, 2, 128))
                      for k in ks[1:])
    tables = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32)

    def run(lengths):
        return paged_lib.paged_decode(q, k_pool, v_pool, 0, tables,
                                      jnp.asarray(lengths, jnp.int32))
    np.testing.assert_array_equal(run([0, 20, -3]), run([1, 20, 1]))


def test_pools_that_are_not_pages_of_these_heads_are_refused():
    q = jnp.zeros((2, 8, 64))
    tables, lengths = jnp.zeros((2, 4), jnp.int32), jnp.ones(2, jnp.int32)
    for k_shape, v_shape in (((1, 8, PAGE, 2, 128), (1, 8, PAGE, 2, 128)),
                             ((1, 8, PAGE, 192), (1, 8, PAGE, 192)),
                             ((1, 8, PAGE, 128), (1, 9, PAGE, 128))):
        with pytest.raises(ValueError, match="paged_decode"):
            paged_lib.paged_decode(q, jnp.zeros(k_shape), jnp.zeros(v_shape),
                                   0, tables, lengths)
