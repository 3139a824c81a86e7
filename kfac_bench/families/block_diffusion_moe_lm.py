"""Family ``block_diffusion_moe_lm``: the softmax-routed expert decoder
trained by diffusion over blocks (``models/block_diffusion_lm.py``)
through the program's K-FAC step.

The program's side makes the calls ``examples/train_language_model.py``
makes for ``--arch block_diffusion_moe``, in its order, as the other
families do for their decoders: ``block_diffusion_lm.get_model``,
``optimizers.get_optimizer`` (the untied head in ``skip_layers``),
``make_multislice_mesh``, ``DistributedKFAC``, ``build_train_step``
(handed ``(x_t, x_0, weights)``, the objective's mean as its loss),
driven by ``engine.train_epoch``. The clock's hooks, the expert stacks'
readings and the MoE counters are family ``mla_moe_lm``'s
(:class:`mla_moe_lm.Cell`); what differs is here: the sizes and the
share from the configuration's published keys, the feed that draws the
noise (:func:`diffusion_batches`), the weights, the model FLOPs of a
step, the attention's own work (:func:`attention_work`, which
``readers/attention_roofline.py`` reads), and the plain reference
(``references/block_diffusion_moe_lm.py``).

The configuration is one chip's share of a deployment (its file says
which): token ids are drawn from the held vocabulary rows but the last,
which is the mask token, and loss and logits are over those rows.
``samples_per_step`` counts the training tokens (``batch x L``), not
the ``2L`` positions a row takes.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from distributed_kfac_pytorch_tpu import launch, multislice
from distributed_kfac_pytorch_tpu.models import block_diffusion_lm
from distributed_kfac_pytorch_tpu.observability import sink as obs_sink
from distributed_kfac_pytorch_tpu.parallel import distributed as D
from distributed_kfac_pytorch_tpu.training import optimizers

from kfac_bench import reference
from kfac_bench.families import lm, mla_moe_lm
from kfac_bench.families.window_moe_lm import EMBED_STD, INIT_STD
from kfac_bench.references import block_diffusion_moe_lm as bd_reference
from kfac_bench.references import window_moe_lm as window_reference

SKIP_LAYERS = ['head']     # the untied head is left to SGD


def mask_pairs(length: int, block: int) -> int:
    """(query, key) pairs of one head the block-diffusion mask keeps over
    two streams of ``length``: ``B`` a noised query in its own block,
    ``B b`` before it in the clean stream, ``B (b + 1)`` a clean query;
    ``L^2 + L B`` in all."""
    return length * length + length * block


def attention_work(sizes: dict, batch: int, length: int,
                   block: int) -> dict:
    """What the attention calls of one step must do at least, forward
    and backward: ``flops`` ``12 d`` a kept pair (as
    ``window_moe_lm.attention_work`` counts them), and ``bytes`` of q,
    k, v, o, dO, dq, dk and dv once each in bfloat16 and one float32 row
    statistic a (query, q head) over the ``2L`` positions."""
    d, heads = sizes['head_dim'], sizes['num_attention_heads']
    kv = sizes['num_key_value_heads']
    layers = sizes['num_hidden_layers']
    positions = batch * 2 * length
    per_layer = (2 * positions * d * 4 * (heads + kv)
                 + 2 * 4 * positions * heads)
    pairs = batch * layers * heads * mask_pairs(length, block)
    return {'flops': 12.0 * d * pairs, 'bytes': float(layers * per_layer)}


def flops_per_step(sizes: dict, batch: int, length: int,
                   block: int) -> float:
    """Model FLOPs of one training step, forward and backward, of the
    share this program holds: the four attention projections, the router
    at all its outputs and the held experts at the rows expected under a
    uniform router (``top_k x held / total`` a position) over all ``2L``
    positions; attention over the pairs the mask keeps
    (:func:`mask_pairs`, QK^T and P.V, ``4 d`` a pair); the head over the
    ``L`` noised positions; backward twice the forward. K-FAC's own work
    does not count."""
    d, hd = sizes['hidden_size'], sizes['head_dim']
    width = (2 * sizes['num_attention_heads']
             + 2 * sizes['num_key_value_heads']) * hd
    lo, hi = sizes['experts_held']
    rows_here = (sizes['num_experts_per_tok'] * (hi - lo)
                 / sizes['num_experts'])
    per_position = sizes['num_hidden_layers'] * (
        2 * d * width + 2 * d * sizes['num_experts']
        + rows_here * 3 * 2 * d * sizes['moe_intermediate_size'])
    attention = (4 * hd * sizes['num_hidden_layers']
                 * sizes['num_attention_heads'] * mask_pairs(length, block))
    head = 2 * d * sizes['vocab_size'] * length
    return 3.0 * batch * (per_position * 2 * length + attention + head)


def init_params(seed: int, sizes: dict):
    """The weights, made on the device in one jitted call, in the tree
    the model declares and the reference takes: N(0, 0.02) matrices, an
    N(0, 1) embedding (``window_moe_lm.EMBED_STD``) whose last row, the
    mask token's, is the mean of the others (an added token's common
    init: a drawn row would send every masked position, about half of
    the noised stream, to one seed's choice of experts), unit norm
    scales."""
    d, hd = sizes['hidden_size'], sizes['head_dim']
    lo, hi = sizes['experts_held']
    width = sizes['moe_intermediate_size']

    def make(key):
        def normal(i, *shape):
            return {'kernel': INIT_STD * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)}

        def norm(n=d):
            return {'scale': jnp.ones((n,), jnp.float32)}

        table = EMBED_STD / INIT_STD * normal(0, sizes['vocab_size'],
                                              d)['kernel']
        table = table.at[-1].set(jnp.mean(table[:-1], axis=0))
        params = {'embed': {'embedding': table}, 'norm': norm(),
                  'head': normal(1, d, sizes['vocab_size'])}
        for i in range(sizes['num_hidden_layers']):
            base = 20 * (i + 1)
            q = sizes['num_attention_heads'] * hd
            kv = sizes['num_key_value_heads'] * hd
            params[f'layer{i}'] = {
                'input_layernorm': norm(),
                'post_attention_layernorm': norm(),
                'self_attn': {'q_proj': normal(base, d, q),
                              'k_proj': normal(base + 1, d, kv),
                              'v_proj': normal(base + 2, d, kv),
                              'o_proj': normal(base + 3, q, d),
                              'q_norm': norm(hd), 'k_norm': norm(hd)},
                'mlp': {'router': normal(base + 4, d, sizes['num_experts']),
                        'experts': {
                            'gate_proj': normal(base + 5, hi - lo, d, width),
                            'up_proj': normal(base + 6, hi - lo, d, width),
                            'down_proj': normal(base + 7, hi - lo, width,
                                                d)}}}
        return params

    return jax.jit(make)(lm.key_of(seed))


def diffusion_batches(seed: int, vocab: int, batch: int, length: int,
                      block: int, levels: tuple[float, float]):
    """The traffic: for ever, ``(x_t, x_0, weights)``, one fresh draw a
    step from ``seed``: ``x_0`` uniform over the ids ``0 .. vocab - 2``;
    a noise level ``t ~ U[levels]`` a (row, block); each position of
    the block masked with probability ``t``, its id then the mask token
    ``vocab - 1`` and its weight ``1 / t``; 0 elsewhere."""
    rng = np.random.default_rng(seed)
    lo, hi = levels
    while True:
        x_0 = rng.integers(0, vocab - 1, (batch, length), dtype=np.int32)
        t = np.repeat(rng.uniform(lo, hi, (batch, length // block)),
                      block, axis=1)
        masked = rng.random((batch, length)) < t
        x_t = np.where(masked, np.int32(vocab - 1), x_0)
        yield x_t, x_0, np.where(masked, 1.0 / t, 0.0).astype(np.float32)


def build_model(config: dict, sizes: dict, block: int):
    return block_diffusion_lm.get_model(
        sizes['vocab_size'], config['program_size'],
        dtype=lm.DTYPES[config['compute_dtype']],
        d_model=sizes['hidden_size'],
        num_layers=sizes['num_hidden_layers'],
        num_heads=sizes['num_attention_heads'],
        num_kv_heads=sizes['num_key_value_heads'],
        head_dim=sizes['head_dim'],
        rope_theta=float(sizes['rope_theta']),
        num_experts=sizes['num_experts'],
        experts_held=sizes['experts_held'],
        num_experts_per_tok=sizes['num_experts_per_tok'],
        moe_intermediate_size=sizes['moe_intermediate_size'],
        block_size=block)


class Cell(mla_moe_lm.Cell):
    """What the harness drives: :class:`mla_moe_lm.Cell`'s attributes,
    hooks and counters, round this family's model, feed, weights and
    reference. A batch is ``(x_t, x_0, weights, key)``."""

    def __init__(self, config: dict, traffic: dict, seed: int, chips: int,
                 out_dir: str):
        self.sizes = bd_reference.sizes_of(config)
        self.seq = traffic['seq']
        self.block = traffic['block']
        self.levels = tuple(traffic['noise_levels'])
        self.batch = traffic['per_chip_batch'] * chips
        self.period = traffic['inv_freq']
        self.check_steps = traffic['check_steps']
        self.samples_per_step = self.batch * self.seq
        self.flops_per_step = flops_per_step(self.sizes, self.batch,
                                             self.seq, self.block)
        bf16_state = config['kfac_state_dtype'] == 'bfloat16'
        self.config, self.traffic, self.chips = config, traffic, chips

        model = build_model(config, self.sizes, self.block)
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
            lambda: kfac.init(jax.random.PRNGKey(0), ids0, ids0,
                              train=False)[0]['params'])
        params = self._weights(seed)
        want = jax.tree.map(lambda x: (x.shape, x.dtype), declared)
        have = jax.tree.map(lambda x: (x.shape, x.dtype), params)
        if want != have:
            raise ValueError(
                'the weights the benchmark makes do not match the tree '
                f'the model declares:\n{want}\nvs\n{have}')
        self.left_to_sgd = sorted(
            n for n, v in kfac.approx_summary(left_to_sgd=True).items()
            if v.startswith('sgd'))
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
        self.batch_spec = (data_spec, data_spec, data_spec, P())
        self.step_fn = dkfac.build_train_step(
            lambda out, batch: out.mean(), tx,
            model_args_fn=lambda batch: batch[:3],
            model_kwargs_fn=lambda batch: {'train': True},
            metrics_fn=masked_tokens, batch_spec=self.batch_spec,
            loss_scale=None)
        self.lr = lr_schedule(0)
        self.hyper = {'lr': self.lr, **kfac_sched.params()}
        self.restart(seed, params)

    def _weights(self, seed: int):
        return init_params(seed, self.sizes)

    def restart(self, seed: int, params=None) -> None:
        super().restart(seed, params)
        self._tokens = diffusion_batches(
            seed, self.sizes['vocab_size'], self.batch, self.seq,
            self.block, self.levels)

    def next_batch(self):
        """One step's ``(x_t, x_0, weights, key)``."""
        batch = next(self._tokens)
        if self._fed < self.check_steps:
            self.checked.append(batch)
        key = jax.random.fold_in(self._root, self._fed)
        self._fed += 1
        return (*batch, key)

    def counters(self) -> dict:
        """:meth:`mla_moe_lm.Cell.counters`, which layers follow
        another's A, the attention's work a step (:func:`attention_work`)
        and the feed's masked positions a step (``diffusion``: the step
        metric's mean over the records that carry it)."""
        out = super().counters()
        out['a_followers'] = self.a_followers
        out['attention_work'] = attention_work(self.sizes, self.batch,
                                               self.seq, self.block)
        masked = [r['metrics']['diffusion/masked_tokens']
                  for r in obs_sink.read_jsonl(self.stream)
                  if r['kind'] == 'step'
                  and 'diffusion/masked_tokens' in r['metrics']]
        out['diffusion'] = {'masked_tokens': (sum(masked) / len(masked)
                                              if masked else None),
                            'tokens': self.samples_per_step}
        return out

    def reference_run(self, **planted) -> dict:
        return reference_observe(self.config, self.traffic, self.seed,
                                 self.chips, self.checked, **planted)


def masked_tokens(out, batch) -> dict:
    """The step metric ``diffusion/masked_tokens``: the positions the
    feed masked in this chip's rows."""
    del out
    return {'diffusion/masked_tokens':
            jnp.sum(batch[2] > 0).astype(jnp.float32)}


def reference_observe(config: dict, traffic: dict, seed: int, chips: int,
                      batches=None, *, rounding=reference.Rounding(),
                      half_batch=False, unchanged_state=False) -> dict:
    """What the reference's ``follow`` reads for a cell's first steps,
    from nothing of the program (see ``lm.reference_observe``)."""
    sizes = bd_reference.sizes_of(config)
    if batches is None:
        feed = diffusion_batches(seed, sizes['vocab_size'],
                                 traffic['per_chip_batch'] * chips,
                                 traffic['seq'], traffic['block'],
                                 tuple(traffic['noise_levels']))
        batches = [next(feed) for _ in range(traffic['check_steps'])]
    hyper = reference.Hyper(**{
        f.name: traffic[f.name]
        for f in dataclasses.fields(reference.Hyper)})
    step = bd_reference.model_step(
        sizes, traffic['block'], config.get('reference_rows_per_block', 1),
        half_batch=half_batch)
    with jax.default_matmul_precision('highest'):
        return window_reference.follow(
            step, sizes, hyper, init_params(seed, sizes), batches,
            rounding=rounding, unchanged_state=unchanged_state)


def build(config: dict, traffic: dict, seed: int, chips: int,
          out_dir: str) -> Cell:
    return Cell(config, traffic, seed, chips, out_dir)
