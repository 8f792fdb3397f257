"""A chunk's block scores through the kernel (``ops/sparse_scores.py``,
ISSUE 53) against the form in XLA that it replaces for a chunk
(``serve/decode.py::sparse_block_scores``), in interpret mode."""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.ops import sparse_scores as scores_lib
from horovod_tpu.serve import decode as decode_lib

H, HKV, DH, STRIDE, KERNEL, PAGE = 4, 2, 32, 16, 32, 64
DENSE = 8192
CFG = types.SimpleNamespace(sparse_init_blocks=1, sparse_window=2048,
                            sparse_block=PAGE, sparse_topk=64)


def case(chunk, width, dtype=jnp.bfloat16, stride=STRIDE, kernel=KERNEL):
    keys = jax.random.split(jax.random.PRNGKey(chunk + width), 2)
    q = jax.random.normal(keys[0], (chunk, H, DH), dtype)
    ck = jax.random.normal(keys[1], (width, HKV, PAGE // stride, DH), dtype)
    return q, ck


def in_xla(q, ck, offset, stride=STRIDE, kernel=KERNEL):
    """``sparse_block_scores`` over the table's kernels one by one, each
    query seeing those complete at its position: what a chunk ran
    before ISSUE 53."""
    width, _, per, _ = ck.shape
    pos = offset + jnp.arange(q.shape[0], dtype=jnp.int32)
    seen = (stride * jnp.arange(width * per, dtype=jnp.int32) + kernel - 1
            <= pos[:, None])
    kernels = ck.swapaxes(1, 2).reshape(1, width * per, HKV, DH)
    return np.asarray(decode_lib.sparse_block_scores(
        q[None], kernels, seen[None], per, kernel // stride)[0])


def chosen(scores, offset):
    """[C, Hkv, W] bool of what ``sparse_choose`` takes, and each row's
    distance between the last score taken and the first left out."""
    scores = jnp.asarray(scores)
    pos = offset + jnp.arange(scores.shape[0], dtype=jnp.int32)
    blocks, ok = decode_lib.sparse_choose(scores, pos // PAGE, CFG)
    rows, heads = np.indices(blocks.shape[:2])
    picked = np.zeros(scores.shape, bool)
    picked[rows[..., None], heads[..., None], np.asarray(blocks)] = ok
    at = (pos // PAGE)[:, None, None]
    b = jnp.arange(scores.shape[-1])
    free = (b <= at) & (b >= CFG.sparse_init_blocks) & (
        b <= at - CFG.sparse_window // PAGE)
    k = CFG.sparse_topk - CFG.sparse_init_blocks - CFG.sparse_window // PAGE
    best = lax.top_k(jnp.where(free, scores, -jnp.inf), k + 1)[0]
    # every free block is taken where no more than k are
    return picked, np.asarray(jnp.where(
        best[..., k] > -jnp.inf, best[..., k - 1] - best[..., k], jnp.inf))


@pytest.mark.parametrize("chunk,offset,length,width", [
    (256, 0, 256, 140),             # its first rows see no kernel
    (256, DENSE - 128, 256, 140),   # across the dense length
    (256, DENSE, 200, 140),         # short of its bucket, ends in a page
    (512, DENSE - 512, 512, 140),   # up to the dense length
    (512, DENSE + 512, 471, 300),   # key tiles past the chunk's end
    (1024, DENSE - 512, 1024, 160),
    (1024, 2 * DENSE, 1000, 300)])
def test_a_chunk_s_scores_through_the_kernel_are_the_xla_form_s(
        chunk, offset, length, width):
    """To a few float32 ulps of the largest score (a softmax's sum in
    another order is all that differs), the real rows over every block;
    a block past the chunk's last kernel 0, and the same blocks chosen
    wherever the last taken and the first left out lie further apart
    than that."""
    q, ck = case(chunk, width)
    assert scores_lib.q_tile(chunk, width) == min(chunk, 1024)
    got = np.asarray(scores_lib.sparse_scores(
        q, ck, jnp.int32(offset), jnp.int32(length), stride=STRIDE,
        kernel=KERNEL))
    want = in_xla(q, ck, offset)
    assert got.shape == want.shape == (chunk, HKV, width)
    assert got.dtype == np.float32
    tol = 4 * np.finfo(np.float32).eps * want.max()
    assert np.abs(got - want)[:length].max() <= tol
    # a block that starts after the chunk's last position meets no
    # kernel that is complete there
    past = -(-(offset + length) // PAGE) + 1
    assert not got[:, :, past:].any() and not want[:length, :, past:].any()
    unseen = np.arange(offset, offset + length) < KERNEL - 1
    assert not got[:length][unseen].any()
    mine, _ = chosen(got, offset)
    theirs, apart = chosen(want, offset)
    clear = (apart > 2 * tol)[:length]
    assert clear.mean() > 0.9
    assert (mine[:length] == theirs[:length])[clear].all()


@pytest.mark.parametrize("stride,kernel,dtype", [
    (16, 64, jnp.float32),      # three kernels reach into a page
    (32, 96, jnp.bfloat16),     # a kernel reaches over a whole page
    (16, 16, jnp.bfloat16)])    # none does
def test_kernels_of_other_spans_meet_the_blocks_they_overlap(
        stride, kernel, dtype):
    q, ck = case(256, 140, dtype, stride, kernel)
    offset = 4096
    got = np.asarray(scores_lib.sparse_scores(
        q, ck, jnp.int32(offset), jnp.int32(256), stride=stride,
        kernel=kernel, block_q=128))
    want = in_xla(q, ck, offset, stride, kernel)
    assert np.abs(got - want).max() <= (
        4 * np.finfo(np.float32).eps * want.max())


def test_a_chunk_that_is_no_whole_tiles_is_refused():
    q, ck = case(192, 140)
    assert scores_lib.q_tile(192, 140) is None
    with pytest.raises(ValueError, match="192 queries"):
        scores_lib.sparse_scores(q, ck, jnp.int32(0), jnp.int32(192),
                                 stride=STRIDE, kernel=KERNEL)
    # a table of 128k positions: the tile shrinks to what fast memory holds
    assert scores_lib.q_tile(1024, 2048) == 256
