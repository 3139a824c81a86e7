"""chip_smoke.py's legs at toy size on the CPU mesh.

The script itself runs on the chip or not at all; what can be held to
its conditions here is that the legs drive the real entry points, that
every step variant is traced and built once, that no fallback event is
recorded, and that the kernels' bodies agree with their XLA references
in interpret mode. Times and memory are the chip run's to report.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from distributed_kfac_pytorch_tpu.ops import pallas_kernels  # noqa: E402


def _passed(report):
    assert report['failures'] == [], report
    return report


def test_leg_cifar_cli_toy(tmp_path):
    report = _passed(chip_smoke.leg_cifar(
        str(tmp_path), model='resnet20', batch_size=16, steps=4,
        inv_freq=2, extra_argv=('--val-batch-size', '16')))
    assert report['trace_counts'] == {'(True, True, None)': 1,
                                      '(True, False, None)': 1}
    assert report['programs_built'] == 2
    assert report['classes_run'] == ['factor', 'firing']


def test_leg_lm_library_path_toy(tmp_path):
    # HYBRID 2x4 on the 8 virtual devices: the placement checks of the
    # four-chip run (params whole on every device, inverse stacks one
    # slice per mesh row) are live here too.
    report = _passed(chip_smoke.leg_lm(
        str(tmp_path), size='tiny', seq=16, per_chip_batch=1, vocab=64,
        steps=5, comm_method='hybrid-opt', grad_worker_fraction=0.5,
        d_model=32, num_heads=2, num_layers=1))
    assert report['mesh'] == {'kfac_ig': 2, 'kfac_gw': 4}
    assert set(report['trace_counts'].values()) == {1}
    assert report['programs_built'] == 3
    assert report['classes_run'] == ['factor', 'firing', 'plain']


def test_leg_lm_second_decoder_toy(tmp_path):
    # The mla_moe decoder's two variants (factors every step), its head
    # left to SGD, on the same HYBRID 2x4 mesh.
    report = _passed(chip_smoke.leg_lm(
        str(tmp_path), name='D', arch='mla_moe', size='tiny', seq=16,
        per_chip_batch=1, vocab=64, steps=5, factor_freq=1, inv_freq=2,
        bf16_state=False, comm_method='hybrid-opt',
        grad_worker_fraction=0.5))
    assert report['mesh'] == {'kfac_ig': 2, 'kfac_gw': 4}
    assert report['trace_counts'] == {'(True, True, None)': 1,
                                      '(True, False, None)': 1}
    assert report['programs_built'] == 2
    assert report['classes_run'] == ['factor', 'firing']


def test_leg_lm_looped_decoder_toy(tmp_path):
    # The looped decoder's two variants (every matrix called once a
    # pass), its head left to SGD, on the same HYBRID 2x4 mesh. Not a
    # leg of the chip run: two more programs to compile there.
    report = _passed(chip_smoke.leg_lm(
        str(tmp_path), name='L', arch='looped', size='tiny', seq=16,
        per_chip_batch=1, vocab=64, steps=5, factor_freq=1, inv_freq=2,
        bf16_state=False, comm_method='hybrid-opt',
        grad_worker_fraction=0.5))
    assert report['mesh'] == {'kfac_ig': 2, 'kfac_gw': 4}
    assert report['trace_counts'] == {'(True, True, None)': 1,
                                      '(True, False, None)': 1}
    assert report['programs_built'] == 2
    assert report['classes_run'] == ['factor', 'firing']


def test_leg_kernels_interpret():
    report = _passed(chip_smoke.leg_kernels(
        interpret=True, stack=2, inverse_dims=(32, 17)))
    assert len(report['kernels']) == 2


def test_leg_kernels_reports_a_refused_kernel(monkeypatch):
    compiled = pallas_kernels._pallas_batched_ns_inverse

    def refuse_dim_7(mats, *args, **kwargs):
        if mats.shape[-1] == 7:
            raise ValueError('Mosaic: unaligned sublane offset')
        return compiled(mats, *args, **kwargs)

    monkeypatch.setattr(pallas_kernels, '_pallas_batched_ns_inverse',
                        refuse_dim_7)
    report = chip_smoke.leg_kernels(
        interpret=True, inverse_dims=(7, 9), stack=1)
    assert len(report['failures']) == 1
    assert 'batched_inverse[1x7]' in report['failures'][0]
    assert 'unaligned sublane offset' in report['failures'][0]
    assert report['kernels']['batched_inverse[1x9]'].startswith('rel_err')


def test_main_refuses_the_cpu_backend(capsys):
    assert chip_smoke.main() != 0
    out = capsys.readouterr()
    assert out.out == ''          # no result line without a chip
    assert 'no TPU' in out.err


def test_fused_probe_failure_raises_on_tpu(monkeypatch):
    """On a TPU a kernel that fails its probe stops the run with its
    name and the compiler's words."""
    import jax

    def refuse(*args, **kwargs):
        raise ValueError('Mosaic failed to compile TPU kernel')

    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    monkeypatch.setattr(pallas_kernels, '_pallas_patch_cov', refuse)
    pallas_kernels.fused_patch_cov_supported.cache_clear()
    try:
        with pytest.raises(RuntimeError) as err:
            pallas_kernels.fused_patch_cov_supported()
    finally:
        pallas_kernels.fused_patch_cov_supported.cache_clear()
    assert "'patch_cov'" in str(err.value)
    assert 'Mosaic failed to compile' in str(err.value)
    assert pallas_kernels.drain_pallas_events() == []
