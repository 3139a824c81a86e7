"""The block-diffusion decoder's attention work and the reader of its
tile fill: the pairs the work function counts and the program's
``kfac/attention/tile_fill`` counter against a brute-force count of the
mask, at toy and at the cell's sizes, and ``readers/tile_fill`` on
counters set by hand."""

import numpy as np
import pytest

from distributed_kfac_pytorch_tpu.observability import tracing
from distributed_kfac_pytorch_tpu.ops import pallas_kernels
from kfac_bench.families import block_diffusion_moe_lm as family
from kfac_bench.readers import tile_fill


def _brute(length, block):
    """The mask from its definition, pair by pair, with numpy's own
    index arithmetic (stream, then block within the stream)."""
    i = np.arange(2 * length)[:, None]
    j = np.arange(2 * length)[None, :]
    clean_i, clean_j = i >= length, j >= length
    bi, bj = (i % length) // block, (j % length) // block
    return ((~clean_i & ~clean_j & (bi == bj))
            | (~clean_i & clean_j & (bj < bi))
            | (clean_i & clean_j & (bj <= bi)))


def _sizes(layers=2, heads=3):
    return {'num_attention_heads': heads, 'num_key_value_heads': 1,
            'head_dim': 128, 'num_hidden_layers': layers}


@pytest.mark.parametrize('length,block', [(16, 4), (12, 3), (8, 1),
                                          (2048, 4)])
def test_the_pairs_are_the_masks_pairs(length, block):
    pairs = int(_brute(length, block).sum())
    assert family.mask_pairs(length, block) == pairs
    work = family.attention_work(_sizes(), 2, length, block)
    assert work['flops'] == 12 * 128 * 2 * 2 * 3 * pairs


@pytest.mark.parametrize('length', [256, 512, 2048])
def test_the_tile_fill_counter_is_the_brute_force_count(length):
    """What a call on the splash route adds to the counters: its mask's
    kept pairs, and the pairs of every kernel tile (512 where they
    divide 2L, else 256) that keeps any, times batch x q heads."""
    t = 2 * length
    mask = _brute(length, 4)
    s = 512 if t % 512 == 0 else 256
    tiles = mask.reshape(t // s, s, t // s, s).any(axis=(1, 3))
    assert pallas_kernels.splash_tile_fill(t, 4) == (
        int(mask.sum()), int(tiles.sum()) * s * s)


def test_the_reader_is_the_kept_share_of_the_visited_pairs():
    prefix = 'kfac/attention/tile_fill/'
    tracing.clear_trace()
    try:
        assert tile_fill.read({}, prefix) is None
        tracing.count(prefix + 'kept_pairs', 4202496 * 32)
        tracing.count(prefix + 'visited_pairs', 6291456 * 32)
        assert tile_fill.read({}, prefix) == pytest.approx(66.796875)
    finally:
        tracing.clear_trace()
