"""The rules that turn a shape into a tile, and a frontier into what a
ring holds, read by value.

Every tile below was chosen by a sweep on the chip that its rule's
docstring records (``flash_attention._default_blocks``, ``_keys_blocks``,
``_bwd_blocks``, ``decode._prompt_block``, ``grouped_matmul.taken``), at the lengths the
benchmark's cells run. A changed tile fails here, on the CPU: run the sweep that
justified the old one (``tools/prefill_attn_sweep.py``,
``benchmark/tools/flash_window_sweep.py``,
``tools/grouped_matmul_sweep.py``) before writing the new value
in. ``decode.ring_positions`` is what every window layer's mask stands
on; it is held to a plain loop that writes position p at ``p % ring``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import flash_attention as flash_lib
from horovod_tpu.ops import grouped_matmul as grouped_lib
from horovod_tpu.serve import decode as decode_lib


def round_up(n, m):
    return -(-n // m) * m


def whole_tiles(t, *blocks):
    """What the rules' docstrings promise of a tile: a multiple of 128,
    at most 1024, and no wider than the length rounded up to 128, so
    that padding ``t`` to the larger of them adds less than one block."""
    for b in blocks:
        assert b % 128 == 0 and 128 <= b <= 1024
        assert b <= round_up(t, 128)
    assert round_up(t, max(blocks)) - t < max(blocks)


# -- the forward's tiles: (length, window) -> (q tile, kv tile) ----------

DEFAULT_BLOCKS = {
    (64, None): (128, 128), (128, None): (128, 128),
    (256, None): (256, 256), (512, None): (512, 512),
    (640, None): (640, 640), (1024, None): (1024, 1024),
    (1280, None): (1024, 1024), (1536, None): (1024, 1024),
    (2048, None): (1024, 1024), (4096, None): (1024, 1024),
    (8192, None): (512, 1024), (16384, None): (512, 1024),
    # mellum's window at its training length, trinity's at 8192 and 16384
    (8192, 1024): (1024, 1024), (8192, 4096): (1024, 1024),
    (16384, 4096): (1024, 1024),
}


@pytest.mark.parametrize("t,window", sorted(DEFAULT_BLOCKS,
                                            key=lambda k: (k[1] or 0, k[0])))
def test_the_forward_s_tiles(t, window):
    blocks = flash_lib._default_blocks(t, window)
    whole_tiles(t, *blocks)
    assert blocks == DEFAULT_BLOCKS[t, window]


# -- a latent chunk's tiles: (chunk width, keys a call) -> (q, kv) --------

KEYS_BLOCKS = {
    (64, 2048): (128, 1024), (128, 2048): (128, 1024),
    (256, 2048): (256, 1024), (512, 2048): (512, 1024),
    (640, 2048): (640, 1024), (1024, 2048): (1024, 1024),
    # a prompt over itself (``local``): one key block of its own length
    (1280, 1280): (1024, 1024), (1536, 1536): (1024, 1024),
    (2048, 2048): (1024, 1024), (4096, 4096): (1024, 1024),
    (8192, 8192): (1024, 1024), (16384, 16384): (1024, 1024),
}


@pytest.mark.parametrize("c,k", sorted(KEYS_BLOCKS))
def test_a_latent_chunk_s_tiles(c, k):
    bq, bk = flash_lib._keys_blocks(c, k)
    whole_tiles(c, bq)
    whole_tiles(k, bk)
    assert (bq, bk) == KEYS_BLOCKS[c, k]


def test_a_chunk_s_call_is_two_key_blocks_of_1024():
    """The keys ``_mla_attend`` hands the kernel a call: what the
    ``(·, 2048)`` rows above stand for."""
    assert (decode_lib._MLA_KEY_BLOCK, decode_lib._MLA_CHUNK_BLOCKS) == (
        1024, 2)


# -- the backward's: (length, head width, item size) -> (block, sub) -----

BWD_BLOCKS = {
    (64, 128, 2): (128, 128), (128, 128, 2): (128, 128),
    (256, 128, 2): (256, 256), (512, 128, 2): (512, 512),
    (640, 128, 2): (512, 512), (1024, 128, 2): (1024, 512),
    (1280, 128, 2): (1024, 512), (1536, 128, 2): (1024, 512),
    (2048, 128, 2): (1024, 512),
    (4096, 128, 2): (1024, 512),       # the dense and OLMoE cells' steps
    (8192, 128, 2): (1024, 512),       # mellum's
    (16384, 128, 2): (1024, 512),
    # float32 (the references' dtype), and the widths the docstring names
    (4096, 128, 4): (1024, 512), (4096, 256, 2): (1024, 512),
    (4096, 256, 4): (512, 512), (4096, 512, 2): (512, 512),
}


@pytest.mark.parametrize("t,d,itemsize", sorted(BWD_BLOCKS))
def test_the_backward_s_tiles(t, d, itemsize):
    block, sub = flash_lib._bwd_blocks(t, d, itemsize)
    whole_tiles(t, block, sub)
    assert block & (block - 1) == 0 and block % sub == 0 and sub <= 512
    assert block * d * itemsize <= 512 * 1024     # a row block's bytes
    assert (block, sub) == BWD_BLOCKS[t, d, itemsize]


# -- a monolithic prompt's square tile ------------------------------------

PROMPT_BLOCK = {64: 128, 128: 128, 256: 256, 512: 512, 640: 640,
                1024: 1024, 1280: 640, 1536: 768, 2048: 1024, 4096: 1024,
                8192: 1024, 16384: 1024}


@pytest.mark.parametrize("t", sorted(PROMPT_BLOCK))
def test_a_prompt_s_tile(t):
    b = decode_lib._prompt_block(t)
    whole_tiles(t, b)
    n = -(-t // b)
    assert n == -(-t // 1024)           # the fewest blocks of at most 1024
    assert (b - 128) * n < t            # even: none could be 128 narrower
    assert b == PROMPT_BLOCK[t]


# -- what a ring holds ------------------------------------------------------

def written(frontier, ring):
    """The ring after positions ``0 .. frontier - 1`` were written, each
    at ``p % ring``: -1 where nothing was."""
    held = np.full(ring, -1, np.int64)
    for p in range(frontier):
        held[p % ring] = p
    return held


def holds(got, frontier, ring):
    want = written(frontier, ring)
    assert got.shape == (ring,)
    assert np.array_equal(got[want >= 0], want[want >= 0])
    assert (got[want < 0] < 0).all()


FRONTIERS = {"none": lambda ring: 0, "one": lambda ring: 1,
             "a_place_short": lambda ring: ring - 1,
             "full": lambda ring: ring, "wrapped_once": lambda ring: ring + 1,
             "the_fourth_lap": lambda ring: 3 * ring + 5}


@pytest.mark.parametrize("frontier", sorted(FRONTIERS))
@pytest.mark.parametrize("ring", [8, 128, 4096])
def test_ring_positions_are_the_newest_written_at_each_place(ring, frontier):
    n = FRONTIERS[frontier](ring)
    got = np.asarray(decode_lib.ring_positions(
        np.asarray([n], np.int32), ring))
    assert got.shape == (1, ring)
    holds(got[0], n, ring)


@pytest.mark.parametrize("ring", [8, 128, 4096])
def test_a_batch_s_rows_hold_their_own_rings(ring):
    frontiers = [f(ring) for _, f in sorted(FRONTIERS.items())]
    got = np.asarray(decode_lib.ring_positions(
        np.asarray(frontiers, np.int32), ring))
    for row, n in zip(got, frontiers):
        holds(row, n, ring)


# -- who runs a mixture's grouped product: (M, G, K, N) -> kernel? --

GROUPED_PRODUCTS = {
    # the LFM2 cell: a decode step's 512 pairs and the three chunk
    # buckets' over 32 experts, gate or up and down
    "lfm2_step": ((512, 32, 2048, 1792), True),
    "lfm2_step_down": ((512, 32, 1792, 2048), True),
    "lfm2_chunk_256": ((1024, 32, 2048, 1792), True),
    "lfm2_chunk_512": ((2048, 32, 2048, 1792), True),
    "lfm2_chunk_1024": ((4096, 32, 2048, 1792), True),
    # one row under the threshold of 256 a group, and on it
    "under_the_threshold": ((256 * 32 - 1, 32, 2048, 1792), True),
    "on_the_threshold": ((256 * 32, 32, 2048, 1792), False),
    # OLMoE's trainer: 65 536 pairs over 64 experts, the matrix unit's
    "olmoe_step": ((65536, 64, 2048, 1024), False),
    "olmoe_step_down": ((65536, 64, 1024, 2048), False),
    # widths that are no whole lane tiles (the tests' tiny models)
    "narrow_k": ((64, 4, 64, 128), False),
    "narrow_n": ((64, 4, 128, 192), False),
    # two whole matrices over the buffer's 48 MB
    "a_matrix_of_32_mb": ((512, 8, 4096, 4096), False),
    # a chip's share of the experts (ISSUE 61), M the N·K pairs of a
    # call of which the share's own lie in a group. Nemotron: 128 held
    # of 512, a step's 128 slots x 22 and the largest chunk's 1024 x 22
    "nemotron_step_up": ((2816, 128, 1024, 2688), True),
    "nemotron_step_down": ((2816, 128, 2688, 1024), True),
    "nemotron_chunk_1024_up": ((22528, 128, 1024, 2688), True),
    "nemotron_chunk_1024_down": ((22528, 128, 2688, 1024), True),
    # ling: 128 held of 512, 64 slots x 8 and 1024 x 8
    "ling_step": ((512, 128, 2560, 768), True),
    "ling_chunk_1024_down": ((8192, 128, 768, 2560), True),
    # trinity: 32 held of 256, 32 slots x 4 and 1024 x 4; two matrices
    # of 18.9 MB are the largest the buffer holds
    "trinity_step": ((128, 32, 3072, 3072), True),
    "trinity_chunk_1024": ((4096, 32, 3072, 3072), True),
    # Kimi: 12 held of 384, two matrices of 29.4 MB are over the buffer
    "kimi_step": ((256, 12, 7168, 2048), False),
    "kimi_chunk_1024_down": ((8192, 12, 2048, 7168), False),
    # mellum's trainer: 16 held of 64, the bound's 24 576 rows and all
    "mellum_bounded": ((24576, 16, 2304, 896), False),
    "mellum_over_the_bound": ((65536, 16, 2304, 896), False),
}


@pytest.mark.parametrize("case", sorted(GROUPED_PRODUCTS))
def test_who_runs_a_grouped_product(case):
    """``grouped_matmul.taken`` at the cells' shapes: a rule from
    shapes alone, so it is read from shapes alone."""
    (m, g, k, n), kernel = GROUPED_PRODUCTS[case]
    lhs = jax.ShapeDtypeStruct((m, k), jnp.bfloat16)
    rhs = jax.ShapeDtypeStruct((g, k, n), jnp.bfloat16)
    assert grouped_lib.taken(lhs, rhs) is kernel
    # operands of two dtypes never take it
    assert not grouped_lib.taken(
        jax.ShapeDtypeStruct((m, k), jnp.float32), rhs)
