"""Knob-surface drift regression (r15 satellite): the SEMANTIC
cross-check that ``TUNABLE_FIELDS`` / ``OptimConfig`` / the three
example CLIs / the autotune space / ``kfac_overrides`` / the event
registry all agree — as a plain pytest over the *imported* modules,
independent of the linter, so tier-1 catches drift even when
``analysis.lint`` (whose ``surface`` family checks the same
invariants statically) is skipped.
"""

import ast
import dataclasses
import inspect
import pathlib

from distributed_kfac_pytorch_tpu.autotune import driver as at_driver
from distributed_kfac_pytorch_tpu.autotune import space as at_space
from distributed_kfac_pytorch_tpu.observability import sink as obs_sink
from distributed_kfac_pytorch_tpu.preconditioner import KFAC
from distributed_kfac_pytorch_tpu.training.optimizers import (
    TUNABLE_FIELDS,
    OptimConfig,
)

EXAMPLES = pathlib.Path(__file__).parent.parent / 'examples'
EXAMPLE_CLIS = ('train_cifar10_resnet.py', 'train_imagenet_resnet.py',
                'train_language_model.py')

# field -> flag, where underscores->dashes does not hold (kept in
# sync with analysis.surface.FLAG_ALIASES by
# test_alias_map_matches_linter below).
FLAG_ALIASES = {
    'kfac_inv_update_freq': '--kfac-update-freq',
    'factor_decay': '--stat-decay',
    'weight_decay': '--wd',
}

#: a truthy/representative sample value per tunable, for replace()
#: and kfac_overrides() exercises.
SAMPLE_VALUES = {
    'bf16_precond': True,
    'bf16_factors': True,
    'bf16_inverses': True,
    'inv_pipeline_chunks': 2,
    'deferred_factor_reduction': True,
    'inv_staleness': 1,
    'factor_batch_fraction': 0.5,
    'kfac_cov_update_freq': 2,
    'kfac_inv_update_freq': 4,
    'eigh_polish_iters': 4,
    'kfac_approx': 'reduce',
    'inv_lowrank_rank': 64,
    'inv_lowrank_dim_threshold': 256,
    'hierarchical_reduce': True,
}


def cli_flags(path: pathlib.Path) -> set:
    """add_argument('--flag', ...) literals (AST; importing an
    example module would execute its jax-touching module level)."""
    flags = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == 'add_argument' and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            flags.add(node.args[0].value)
    return flags


class TestTunableSurface:
    def test_tunables_are_optim_config_fields(self):
        fields = {f.name for f in dataclasses.fields(OptimConfig)}
        missing = set(TUNABLE_FIELDS) - fields
        assert not missing, (
            f'TUNABLE_FIELDS entries without an OptimConfig field: '
            f'{sorted(missing)}')

    def test_no_duplicate_tunables(self):
        assert len(set(TUNABLE_FIELDS)) == len(TUNABLE_FIELDS)

    def test_sample_values_cover_every_tunable(self):
        # keeps THIS test honest: a new tunable must add its sample
        # here so the replace/overrides exercises keep covering it
        assert set(SAMPLE_VALUES) == set(TUNABLE_FIELDS)

    def test_tunables_replace_cleanly(self):
        cfg = dataclasses.replace(OptimConfig(), **SAMPLE_VALUES)
        for k, v in SAMPLE_VALUES.items():
            assert getattr(cfg, k) == v

    def test_every_tunable_has_flag_in_all_three_clis(self):
        for cli in EXAMPLE_CLIS:
            flags = cli_flags(EXAMPLES / cli)
            for field in TUNABLE_FIELDS:
                want = FLAG_ALIASES.get(
                    field, '--' + field.replace('_', '-'))
                assert want in flags, (
                    f'{cli} is missing {want} for tunable {field!r} '
                    '(the knob surface must stay consistent across '
                    'the three example CLIs)')

    def test_alias_map_matches_linter(self):
        # one alias table, two consumers: the static surface checker
        # and this semantic test must not drift from each other
        from distributed_kfac_pytorch_tpu.analysis import surface
        assert surface.FLAG_ALIASES == FLAG_ALIASES


class TestAutotuneSurface:
    def test_space_knobs_are_tunable_fields(self):
        knobs = {k.name for k in at_space.default_space().knobs}
        assert knobs <= set(TUNABLE_FIELDS), (
            f'autotune space knobs outside TUNABLE_FIELDS: '
            f'{sorted(knobs - set(TUNABLE_FIELDS))}')

    def test_space_knob_values_apply(self):
        # every candidate value of every knob must overlay onto
        # OptimConfig without a constraint/type surprise
        base = dataclasses.asdict(OptimConfig(kfac_inv_update_freq=4))
        space = at_space.default_space()
        for knob in space.knobs:
            for value in knob.values:
                cfg = dataclasses.replace(OptimConfig(),
                                          **{knob.name: value})
                assert getattr(cfg, knob.name) == value
        assert space.enumerate(base), 'constraints prune everything'

    def test_apply_tuned_accepts_every_tunable(self):
        cfg, err = at_driver.apply_tuned(
            OptimConfig(kfac_inv_update_freq=4), dict(SAMPLE_VALUES))
        assert err is None, err
        for k, v in SAMPLE_VALUES.items():
            assert getattr(cfg, k) == v

    def test_kfac_overrides_accounts_for_every_tunable(self):
        kwargs, inv_freq, ignored = at_driver.kfac_overrides(
            dict(SAMPLE_VALUES))
        # every knob lands in exactly one of: KFAC kwargs, the inv
        # frequency, or the surfaced-as-ignored list — none silently
        # dropped, none invented
        assert inv_freq == SAMPLE_VALUES['kfac_inv_update_freq']
        kfac_params = set(
            inspect.signature(KFAC.__init__).parameters)
        unknown = set(kwargs) - kfac_params
        assert not unknown, (
            f'kfac_overrides produced kwargs KFAC does not accept: '
            f'{sorted(unknown)}')
        assert set(ignored) <= set(TUNABLE_FIELDS)
        assert set(ignored) == {'deferred_factor_reduction',
                                'inv_staleness',
                                'hierarchical_reduce',
                                'kfac_cov_update_freq',
                                'inv_pipeline_chunks'}


class TestEventRegistry:
    def test_known_emitters_are_registered(self):
        required = {'compile', 'retrace', 'preemption',
                    'checkpoint_save', 'restore', 'topology_change',
                    'autotune_apply', 'autotune_fallback',
                    'autotune_backoff'}
        assert required <= set(obs_sink.EVENT_KINDS)

    def test_registry_well_formed(self):
        kinds = obs_sink.EVENT_KINDS
        assert len(set(kinds)) == len(kinds)
        assert all(k and k == k.strip() for k in kinds)

    def test_every_literal_emission_is_registered(self):
        # semantic twin of the linter's event check: scan the package
        # source for literal event names and pin them to the registry
        pkg = pathlib.Path(obs_sink.__file__).parent.parent
        literals = set()
        for py in pkg.rglob('*.py'):
            if '__pycache__' in py.parts:
                continue
            for node in ast.walk(ast.parse(py.read_text())):
                if isinstance(node, ast.Call):
                    attr = (node.func.attr if isinstance(
                        node.func, ast.Attribute) else None)
                    if (attr in ('event_record', '_event')
                            and node.args
                            and isinstance(node.args[0], ast.Constant)
                            and isinstance(node.args[0].value, str)):
                        literals.add(node.args[0].value)
                elif isinstance(node, ast.Dict):
                    for k, v in zip(node.keys, node.values):
                        if (isinstance(k, ast.Constant)
                                and k.value == 'event'
                                and isinstance(v, ast.Constant)
                                and isinstance(v.value, str)):
                            literals.add(v.value)
        assert literals <= set(obs_sink.EVENT_KINDS), (
            f'unregistered event name(s): '
            f'{sorted(literals - set(obs_sink.EVENT_KINDS))}')
