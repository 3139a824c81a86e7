"""The windowed decoder's attention work and the reader of its roofline
share: the pairs the work function counts against a brute-force count
of the masks, and ``readers/attention_roofline`` on a trace written by
hand."""

import numpy as np
import pytest

from kfac_bench.families import window_moe_lm as family
from kfac_bench.readers import attention_roofline
from kfac_bench.references import window_moe_lm as plain

PEAKS = {'bf16_flops': 197e12, 'hbm_bytes_per_s': 819e9}


def _sizes(window, layer_types):
    return {'sliding_window': window, 'layer_types': layer_types,
            'num_attention_heads': 3, 'num_key_value_heads': 1,
            'head_dim': 128, 'num_hidden_layers': len(layer_types)}


@pytest.mark.parametrize('seq,window', [(16, 4), (16, 16), (16, 40),
                                        (37, 5), (1, 3)])
def test_the_pairs_are_the_masks_pairs(seq, window):
    i = np.arange(seq)[:, None]
    j = np.arange(seq)[None, :]
    full = int(np.sum(j <= i))
    sliding = int(np.sum((j <= i) & (j > i - window)))
    types = (plain.SLIDING, plain.SLIDING, plain.FULL)
    assert family.mask_pairs(_sizes(window, types), seq) == 3 * (
        2 * sliding + full)
    work = family.attention_work(_sizes(window, types), 2, seq)
    assert work['flops'] == 12 * 128 * 2 * 3 * (2 * sliding + full)


def _trace(*events):
    return {'device': {'/device:TPU:0': [list(e) for e in events]},
            'host': []}


def test_the_share_is_the_bound_over_the_scopes_time():
    work = {'flops': 197e9, 'bytes': 819e6 / 2}   # 1 ms of FLOPs, 0.5 of bytes
    trace = _trace(
        ['%splash.1', 0, 2_000_000, 'jit(step)/kfac_model/attention/sliding'],
        ['%splash.2', 2_000_000, 2_000_000,
         'jit(step)/transpose(jvp(kfac_model/attention/full))'],
        ['%fusion.3', 4_000_000, 6_000_000, 'jit(step)/kfac_model/moe'])
    run = {'counters': {'attention_work': work}, 'trace': trace,
           'steps': 2, 'peaks': PEAKS}
    # 4 ms under the scope over 2 steps: 2 ms a step against 1 ms
    assert attention_roofline.read(
        run, ['kfac_model/attention']) == pytest.approx(50.0)
    bytes_bound = dict(run, counters={'attention_work': {
        'flops': 0.0, 'bytes': 819e6 * 3}})
    assert attention_roofline.read(
        bytes_bound, ['kfac_model/attention']) == pytest.approx(150.0)


def test_nothing_where_there_is_nothing_to_read():
    trace = _trace(['%fusion.1', 0, 1000, 'jit(step)/kfac_model/moe'])
    run = {'counters': {'attention_work': {'flops': 1.0, 'bytes': 1.0}},
           'trace': trace, 'steps': 2, 'peaks': PEAKS}
    assert attention_roofline.read(run, ['kfac_model/attention']) is None
    for missing in ({'counters': {}}, {'trace': None}, {'steps': 0},
                    {'peaks': None}):
        assert attention_roofline.read(
            {**run, **missing}, ['kfac_model/moe']) is None
    assert attention_roofline.read(run, ['kfac_model/moe']) > 0
