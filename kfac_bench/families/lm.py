"""Family ``lm``: a decoder LM trained by the program's K-FAC step.

The program's side is ``chip_smoke.leg_lm`` (PR 21) with the loop taken
out: the same calls ``examples/train_language_model.py:main`` makes, in
its order — ``transformer_lm.get_model``, ``optimizers.get_optimizer``,
``multislice.make_multislice_mesh``, ``DistributedKFAC``,
``build_train_step`` — driven by ``engine.train_epoch``. No eval step
and no orbax save. What the benchmark makes itself: the weights (one
jitted call from the seed, GPT-2's N(0, 0.02) with zero biases, in the
tree the model declares), the tokens (a seeded host generator), the
model FLOPs of a step and the plain reference.

A family module gives the harness ``build(config, traffic, seed,
chips) -> cell`` with the attributes :class:`Cell` documents.
"""

from __future__ import annotations

import dataclasses
import gc
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from distributed_kfac_pytorch_tpu import launch, multislice
from distributed_kfac_pytorch_tpu.models import transformer_lm
from distributed_kfac_pytorch_tpu.observability import sink as obs_sink
from distributed_kfac_pytorch_tpu.parallel import distributed as D
from distributed_kfac_pytorch_tpu.training import engine, optimizers

from kfac_bench import reference
from kfac_bench.references import lm as lm_reference

DTYPES = {'bfloat16': jnp.bfloat16, 'float32': jnp.float32}
INIT_STD = 0.02


def sizes_of(config: dict) -> dict:
    """The model's sizes under the family's own names."""
    if config['d_inner'] != 4 * config['d_model'] or \
            config['d_model'] != config['num_heads'] * config['d_head']:
        raise ValueError(
            "the program's block has d_inner = 4 x d_model and d_model = "
            f'heads x d_head; the configuration states {config}')
    return {'d_model': config['d_model'],
            'num_layers': config['num_layers'],
            'num_heads': config['num_heads'],
            'mlp_ratio': config['d_inner'] // config['d_model'],
            'vocab_size': config['vocab_size']}


def flops_per_step(sizes: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step, forward and backward, from the
    sizes (copied from ``benchmarks/flagship_lm.py:lm_flops_per_step``):
    four d x d projections, causal attention counted whole (QK^T and PV,
    2 x 2 x seq^2 x d a row), the two MLP matmuls and the output
    projection; backward twice the forward. K-FAC's own work and
    recomputation do not count."""
    d, depth = sizes['d_model'], sizes['num_layers']
    tok = batch * seq
    per_layer = (2 * tok * 4 * d * d + 4 * batch * seq * seq * d
                 + 2 * tok * 2 * d * (sizes['mlp_ratio'] * d))
    head = 2 * tok * d * sizes['vocab_size']
    return 3.0 * (depth * per_layer + head)


def key_of(seed: int):
    """A PRNG key from any whole number (seeds pass 2**31)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def init_params(seed: int, sizes: dict, seq: int):
    """The weights, made on the device in one jitted call."""
    d, hidden = sizes['d_model'], sizes['mlp_ratio'] * sizes['d_model']

    def make(key):
        def normal(i, shape):
            return INIT_STD * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)

        def dense(i, d_in, d_out):
            return {'kernel': normal(i, (d_in, d_out)),
                    'bias': jnp.zeros((d_out,), jnp.float32)}

        def norm():
            return {'scale': jnp.ones((d,), jnp.float32),
                    'bias': jnp.zeros((d,), jnp.float32)}

        params = {'embed': {'embedding': normal(0, (sizes['vocab_size'],
                                                    d))},
                  'pos_embed': normal(1, (seq, d)), 'ln_f': norm()}
        for i in range(sizes['num_layers']):
            base = 10 * (i + 1)
            params[f'block{i}'] = {
                'ln1': norm(), 'ln2': norm(),
                'attn': {n: dense(base + j, d, d) for j, n in enumerate(
                    lm_reference.ATTN)},
                'mlp_in': dense(base + 4, d, hidden),
                'mlp_out': dense(base + 5, hidden, d)}
        return params

    return jax.jit(make)(key_of(seed))


def token_batches(seed: int, vocab: int, batch: int, seq: int):
    """The traffic: for ever, ``(ids, targets)`` of uniform random
    tokens, one fresh ``(batch, seq + 1)`` draw a step (every row
    differs, every step differs)."""
    rng = np.random.default_rng(seed)
    while True:
        ids = rng.integers(0, vocab, (batch, seq + 1), dtype=np.int32)
        yield ids[:, :-1], ids[:, 1:]


class Cell:
    """What the harness drives.

    ``step_fn``: the function ``build_train_step`` returned (the harness
    wraps it with its clock and hands the wrapper to :meth:`drive`).
    ``period``: steps in one cadence period. ``samples_per_step`` and
    ``flops_per_step``: tokens and model FLOPs of a step, all chips.
    ``check_steps``: how many first steps the reference follows."""

    def __init__(self, config: dict, traffic: dict, seed: int, chips: int,
                 out_dir: str):
        self.sizes = sizes_of(config)
        self.seq = traffic['seq']
        self.batch = traffic['per_chip_batch'] * chips
        self.period = traffic['inv_freq']
        self.check_steps = traffic['check_steps']
        self.samples_per_step = self.batch * self.seq
        self.flops_per_step = flops_per_step(self.sizes, self.batch,
                                             self.seq)
        vocab = self.sizes['vocab_size']
        dtype = DTYPES[config['compute_dtype']]
        bf16_state = config['kfac_state_dtype'] == 'bfloat16'
        self.config, self.traffic, self.chips = config, traffic, chips

        def build_model():
            return transformer_lm.get_model(
                vocab, config['program_size'], max_len=self.seq,
                tie_weights=True, dtype=dtype,
                d_model=config['d_model'],
                num_layers=config['num_layers'],
                num_heads=config['num_heads'],
                dropout=config['dropout'])

        model = build_model()
        cfg = optimizers.OptimConfig(
            base_lr=traffic['lr'], momentum=traffic['momentum'],
            weight_decay=0.0, warmup_epochs=1, lr_decay=[20, 30],
            workers=1, kfac_inv_update_freq=traffic['inv_freq'],
            kfac_cov_update_freq=traffic['factor_freq'],
            damping=traffic['damping'],
            factor_decay=traffic['factor_decay'],
            kl_clip=traffic['kl_clip'],
            inverse_method=config.get('inverse_method', 'auto'),
            skip_layers=[], comm_method=traffic['comm_method'],
            grad_worker_fraction=traffic['grad_worker_fraction'],
            bf16_factors=bf16_state, bf16_inverses=bf16_state,
            kfac_metrics=True)
        tx, lr_schedule, kfac, kfac_sched = optimizers.get_optimizer(
            model, cfg)
        self.stream = os.path.join(out_dir, 'metrics.jsonl')
        self.sink = obs_sink.JsonlMetricsSink(
            self.stream, interval=1, process_index=jax.process_index(),
            meta={'cli': 'kfac_bench', 'bptt': self.seq,
                  'batch_size': self.batch, 'devices': chips})
        tx = optax.chain(optax.clip_by_global_norm(traffic['grad_clip']),
                         tx)

        # Registration traces the model; its own weights are never made
        # (eval_shape), the benchmark's take their place.
        ids0 = jnp.zeros((2, self.seq), jnp.int32)
        declared = jax.eval_shape(
            lambda: kfac.init(jax.random.PRNGKey(0), ids0,
                              train=False)[0]['params'])
        params = init_params(seed, self.sizes, self.seq)
        want = jax.tree.map(lambda x: (x.shape, x.dtype), declared)
        have = jax.tree.map(lambda x: (x.shape, x.dtype), params)
        if want != have:
            raise ValueError(
                'the weights the benchmark makes do not match the tree '
                f'the model declares:\n{want}\nvs\n{have}')

        mesh = multislice.make_multislice_mesh(
            num_slices=1,
            comm_method=optimizers.COMM_METHODS[traffic['comm_method']],
            grad_worker_fraction=traffic['grad_worker_fraction'],
            seq_parallel=1)
        self.mesh = mesh
        params = launch.replicate_on_mesh(mesh, params)
        self.dkfac = dkfac = D.DistributedKFAC(kfac, mesh, params)
        self.kfac_layers = len(kfac.specs)
        self.tx = tx

        def loss_fn(out, batch):
            return optax.softmax_cross_entropy_with_integer_labels(
                out, batch[1]).mean()

        data_axes = dkfac.data_axes

        def model_kwargs_fn(batch):
            idx = jax.lax.axis_index(data_axes[0])
            for ax in data_axes[1:]:
                idx = idx * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)
            return {'train': True, 'rngs': {
                'dropout': jax.random.fold_in(batch[2], idx)}}

        data_spec = P(multislice.batch_axes(mesh))
        self.batch_spec = (data_spec, data_spec, P())
        self.step_fn = dkfac.build_train_step(
            loss_fn, tx, model_kwargs_fn=model_kwargs_fn,
            batch_spec=self.batch_spec, loss_scale=None)
        self.lr = lr_schedule(0)
        self.hyper = {'lr': self.lr, **kfac_sched.params()}
        self.restart(seed, params)

    def restart(self, seed: int, params=None) -> None:
        """Fresh weights, optimizer and K-FAC state, and traffic from
        ``seed``, for the step already built (``kfac_bench/control.py``
        reads several seeds off one compiled step; a run calls this
        once)."""
        if params is None:
            params = launch.replicate_on_mesh(
                self.mesh, init_params(seed, self.sizes, self.seq))
        self.seed = seed
        self.state = engine.TrainState(
            params=params, opt_state=self.tx.init(params),
            kfac_state=self.dkfac.init_state(params), extra_vars={})
        self.state.opt_state = optimizers.set_lr(self.state.opt_state,
                                                 self.lr)
        self._tokens = token_batches(seed, self.sizes['vocab_size'],
                                     self.batch, self.seq)
        self._root = key_of(seed + 1)
        self._fed = 0
        self.checked: list = []       # the first batches, for the reference
        self._start = None            # host copy of the first weights
        self.observed = {'losses': [], 'grad1': None, 'dparam': None,
                         'factors': None}

    # -- the feed -------------------------------------------------------

    def next_batch(self):
        """One step's batch, as the step function takes it."""
        ids, targets = next(self._tokens)
        if self._fed < self.check_steps:
            self.checked.append((ids, targets))
        key = jax.random.fold_in(self._root, self._fed)
        self._fed += 1
        return ids, targets, key

    def drive(self, step_fn, batches) -> None:
        """Run ``step_fn`` over ``batches`` as the program's entry
        point runs an epoch."""
        engine.train_epoch(
            step_fn, self.state,
            launch.global_batches(self.mesh, batches,
                                  batch_spec=self.batch_spec),
            self.hyper, verbose=False, metrics_sink=self.sink)

    def stage_of(self, flags: dict) -> str:
        fired = engine.fired_stage(flags)
        if fired is None:
            return 'plain'
        return ('firing' if fired.startswith(('inverse', 'chunk'))
                else 'factor')

    # -- what the comparison reads off the program -----------------------

    def before_first_step(self) -> None:
        self._start = jax.device_get(self.state.params)

    def after_step(self, index: int, out) -> None:
        """Called with the step function's outputs after each of the
        first ``check_steps`` steps, before the next is dispatched."""
        params, opt_state, kstate, _, metrics = out
        self.observed['losses'].append(metrics['loss'])
        if index == 0:
            trace = optax.tree_utils.tree_get(opt_state, 'trace')
            self.observed['grad1'] = reference.leaf_norms(
                jax.device_get(trace))
        if index == self.check_steps - 1:
            self.observed['dparam'] = reference.diff_norms(
                jax.device_get(params), self._start)
            self._start = None
            self.observed['factors'] = reference.leaf_arrays(
                reference.sketch_factors(kstate['factors']))

    def counters(self) -> dict:
        """The program's own counters after the run."""
        self.sink.close()
        records = obs_sink.read_jsonl(self.stream)
        steps = [r for r in records if r['kind'] == 'step']
        events = [r for r in records if r['kind'] == 'event']
        return {
            'trace_counts': {str(k): v for k, v in
                             self.step_fn.trace_counts.items()},
            'retrace_events': sum(e['event'] == 'retrace'
                                  for e in events),
            'first_call_ms': {e['data']['variant']:
                              e['data']['first_call_ms'] for e in events
                              if e['event'] == 'compile'},
            'nonfinite_skips': (steps[-1]['metrics'].get(
                'kfac/nonfinite_skips') if steps else None),
            'host_step_ms': [r['host_step_ms'] for r in steps],
            'kfac_layers': self.kfac_layers}

    # -- after the window -------------------------------------------------

    def free(self) -> None:
        """Drop the program's state and its loaded programs."""
        self.observed['losses'] = [float(x)
                                   for x in self.observed['losses']]
        self.state = self.step_fn = self.dkfac = None
        gc.collect()
        jax.clear_caches()

    def reference_run(self, **planted) -> dict:
        """The plain reference over the same first steps, from the
        same seed: its own weights, nothing of the program's."""
        return reference_observe(self.config, self.traffic, self.seed,
                                 self.chips, self.checked, **planted)


def reference_observe(config: dict, traffic: dict, seed: int, chips: int,
                      batches=None, *, rounding=reference.Rounding(),
                      half_batch=False, unchanged_state=False) -> dict:
    """What :func:`kfac_bench.reference.follow` reads for a cell's
    first steps. Needs nothing of the program: ``batches`` default to
    the first ``check_steps`` draws of the cell's own traffic.
    ``rounding`` is the control; ``half_batch`` and ``unchanged_state``
    plant a step's faults."""
    sizes = sizes_of(config)
    if batches is None:
        feed = token_batches(seed, sizes['vocab_size'],
                             traffic['per_chip_batch'] * chips,
                             traffic['seq'])
        batches = [next(feed) for _ in range(traffic['check_steps'])]
    hyper = reference.Hyper(**{
        f.name: traffic[f.name]
        for f in dataclasses.fields(reference.Hyper)})
    params = init_params(seed, sizes, traffic['seq'])
    step = lm_reference.model_step(
        sizes, config.get('reference_rows_per_block', 2),
        half_batch=half_batch)
    with jax.default_matmul_precision('highest'):
        return reference.follow(
            step, lm_reference.layers(sizes), hyper, params, batches,
            rounding=rounding, unchanged_state=unchanged_state)


def build(config: dict, traffic: dict, seed: int, chips: int,
          out_dir: str) -> Cell:
    return Cell(config, traffic, seed, chips, out_dir)
