"""Family ``looped_lm``: the looped decoder (``models/looped_lm.py``:
one stack of layers run ``total_ut_steps`` times a forward, an exit
after every pass) trained by the program's K-FAC step.

The program's side makes the calls ``examples/train_language_model.py``
makes for ``--arch looped``, in its order, as the other two families do
for their decoders: ``looped_lm.get_model``, ``optimizers.get_optimizer``
(the untied head in ``skip_layers``), ``make_multislice_mesh``,
``DistributedKFAC``, ``build_train_step``, driven by
``engine.train_epoch``. The model computes its own objective (it needs
the head at every exit): the step hands it the targets and takes the
mean of what it returns. The feed, the clock's hooks and the counters
are family ``lm``'s own (:class:`lm.Cell`); what differs is here: the
sizes from the configuration's published keys, the weights, the model
FLOPs of a step, and the plain reference (``references/looped_lm.py``)
with its own ``follow``.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from distributed_kfac_pytorch_tpu import launch, multislice
from distributed_kfac_pytorch_tpu.models import looped_lm
from distributed_kfac_pytorch_tpu.observability import sink as obs_sink
from distributed_kfac_pytorch_tpu.parallel import distributed as D
from distributed_kfac_pytorch_tpu.training import optimizers

from kfac_bench import reference
from kfac_bench.families import lm
from kfac_bench.references import looped_lm as looped_reference

INIT_STD = 0.02
SKIP_LAYERS = ['head']     # the untied head is left to SGD


def flops_per_step(sizes: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step, forward and backward:
    ``total_ut_steps`` passes, each the four attention projections,
    causal attention counted whole as family ``lm`` counts it (QK^T and
    PV, ``2 x 2 x seq x heads x head_dim`` a token), the SwiGLU's three
    matmuls in every layer, and after the pass the head over the whole
    vocabulary and the gate; backward twice the forward. K-FAC's own
    work and recomputation do not count."""
    d = sizes['hidden_size']
    width = sizes['num_attention_heads'] * sizes['head_dim']
    layer = (2 * 4 * d * width + 4 * seq * width
             + 2 * 3 * d * sizes['intermediate_size'])
    one_pass = (sizes['num_hidden_layers'] * layer
                + 2 * d * sizes['vocab_size'] + 2 * d)
    return 3.0 * sizes['total_ut_steps'] * one_pass * batch * seq


def init_params(seed: int, sizes: dict):
    """The weights, made on the device in one jitted call, in the tree
    the model declares and the reference takes: N(0, 0.02) matrices and
    embedding, unit norm scales, a zero gate bias."""
    d = sizes['hidden_size']
    width = sizes['num_attention_heads'] * sizes['head_dim']
    ff = sizes['intermediate_size']

    def make(key):
        def normal(i, *shape):
            return {'kernel': INIT_STD * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)}

        def norm():
            return {'scale': jnp.ones((d,), jnp.float32)}

        params = {
            'embed': {'embedding': normal(0, sizes['vocab_size'],
                                          d)['kernel']},
            'exit': {'norm': norm(),
                     'head': normal(1, d, sizes['vocab_size']),
                     'early_exit_gate': {
                         **normal(2, d, 1),
                         'bias': jnp.zeros((1,), jnp.float32)}}}
        for i in range(sizes['num_hidden_layers']):
            base = 10 * (i + 1)
            params[f'layer{i}'] = {
                **{n: norm() for n in (
                    'input_layernorm', 'input_layernorm_2',
                    'post_attention_layernorm',
                    'post_attention_layernorm_2')},
                'self_attn': {'q_proj': normal(base, d, width),
                              'k_proj': normal(base + 1, d, width),
                              'v_proj': normal(base + 2, d, width),
                              'o_proj': normal(base + 3, width, d)},
                'mlp': {'gate_proj': normal(base + 4, d, ff),
                        'up_proj': normal(base + 5, d, ff),
                        'down_proj': normal(base + 6, ff, d)}}
        return params

    return jax.jit(make)(lm.key_of(seed))


def build_model(config: dict, sizes: dict):
    return looped_lm.get_model(
        sizes['vocab_size'], config['program_size'],
        dtype=lm.DTYPES[config['compute_dtype']],
        d_model=sizes['hidden_size'],
        num_layers=sizes['num_hidden_layers'],
        num_heads=sizes['num_attention_heads'],
        head_dim=sizes['head_dim'],
        intermediate_size=sizes['intermediate_size'],
        total_ut_steps=sizes['total_ut_steps'],
        rope_theta=float(sizes['rope_theta']),
        exit_entropy_beta=sizes['exit_entropy_beta'])


class Cell(lm.Cell):
    """What the harness drives: :class:`lm.Cell`'s attributes, feed and
    hooks, round this family's model, weights and reference."""

    def __init__(self, config: dict, traffic: dict, seed: int, chips: int,
                 out_dir: str):
        self.sizes = looped_reference.sizes_of(config)
        self.seq = traffic['seq']
        self.batch = traffic['per_chip_batch'] * chips
        self.period = traffic['inv_freq']
        self.check_steps = traffic['check_steps']
        self.samples_per_step = self.batch * self.seq
        self.flops_per_step = flops_per_step(self.sizes, self.batch,
                                             self.seq)
        bf16_state = config['kfac_state_dtype'] == 'bfloat16'
        self.config, self.traffic, self.chips = config, traffic, chips

        model = build_model(config, self.sizes)
        cfg = optimizers.OptimConfig(
            base_lr=traffic['lr'], momentum=traffic['momentum'],
            weight_decay=0.0, warmup_epochs=1, lr_decay=[20, 30],
            workers=1, kfac_inv_update_freq=traffic['inv_freq'],
            kfac_cov_update_freq=traffic['factor_freq'],
            damping=traffic['damping'],
            factor_decay=traffic['factor_decay'],
            kl_clip=traffic['kl_clip'],
            inverse_method=config.get('inverse_method', 'auto'),
            skip_layers=SKIP_LAYERS, comm_method=traffic['comm_method'],
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
        params = init_params(seed, self.sizes)
        want = jax.tree.map(lambda x: (x.shape, x.dtype), declared)
        have = jax.tree.map(lambda x: (x.shape, x.dtype), params)
        if want != have:
            raise ValueError(
                'the weights the benchmark makes do not match the tree '
                f'the model declares:\n{want}\nvs\n{have}')
        self.left_to_sgd = sorted(
            n for n, v in kfac.approx_summary(left_to_sgd=True).items()
            if v.startswith('sgd'))
        self.calls = {name: spec.num_calls
                      for name, spec in kfac.specs.items()}
        self.a_followers = dict(kfac.a_followers())

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

        data_spec = P(multislice.batch_axes(mesh))
        self.batch_spec = (data_spec, data_spec, P())
        self.step_fn = dkfac.build_train_step(
            lambda out, batch: out.mean(), tx,
            model_kwargs_fn=lambda batch: {'train': True,
                                           'targets': batch[1]},
            batch_spec=self.batch_spec, loss_scale=None)
        self.lr = lr_schedule(0)
        self.hyper = {'lr': self.lr, **kfac_sched.params()}
        self.restart(seed, params)

    def restart(self, seed: int, params=None) -> None:
        if params is None:
            params = launch.replicate_on_mesh(
                self.mesh, init_params(seed, self.sizes))
        # lm.Cell.restart makes its own family's weights when given
        # none; given these it only resets state, feed and readings.
        super().restart(seed, params)

    def counters(self) -> dict:
        """:meth:`lm.Cell.counters`, and what registration saw: the
        calls of every registered layer, which layers follow another's
        A, and what is left to SGD."""
        out = super().counters()
        out['calls'] = self.calls
        out['a_followers'] = self.a_followers
        out['left_to_sgd'] = self.left_to_sgd
        return out

    def reference_run(self, **planted) -> dict:
        return reference_observe(self.config, self.traffic, self.seed,
                                 self.chips, self.checked, **planted)


def reference_observe(config: dict, traffic: dict, seed: int, chips: int,
                      batches=None, *, rounding=reference.Rounding(),
                      half_batch=False, unchanged_state=False,
                      dropped_pass=None) -> dict:
    """What the reference's :func:`follow` reads for a cell's first
    steps, from nothing of the program (see ``lm.reference_observe``).
    ``dropped_pass`` plants the fault of a capture that loses one
    pass's statistics."""
    sizes = looped_reference.sizes_of(config)
    if batches is None:
        feed = lm.token_batches(seed, sizes['vocab_size'],
                                traffic['per_chip_batch'] * chips,
                                traffic['seq'])
        batches = [next(feed) for _ in range(traffic['check_steps'])]
    hyper = reference.Hyper(**{
        f.name: traffic[f.name]
        for f in dataclasses.fields(reference.Hyper)})
    step = looped_reference.model_step(
        sizes, config.get('reference_rows_per_block', 2),
        half_batch=half_batch, dropped_pass=dropped_pass)
    with jax.default_matmul_precision('highest'):
        return looped_reference.follow(
            step, sizes, hyper, init_params(seed, sizes), batches,
            rounding=rounding, unchanged_state=unchanged_state)


def build(config: dict, traffic: dict, seed: int, chips: int,
          out_dir: str) -> Cell:
    return Cell(config, traffic, seed, chips, out_dir)
