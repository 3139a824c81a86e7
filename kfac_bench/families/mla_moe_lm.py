"""Family ``mla_moe_lm``: the latent-attention, routed-expert decoder
(``models/mla_moe_lm.py``) trained by the program's K-FAC step.

The program's side makes the calls ``examples/train_language_model.py``
makes for ``--arch mla_moe``, in its order, as family ``lm`` does for
the first decoder: ``mla_moe_lm.get_model``, ``optimizers.get_optimizer``
(the untied head in ``skip_layers``), ``make_multislice_mesh``,
``DistributedKFAC``, ``build_train_step``, driven by
``engine.train_epoch``. The feed, the clock's hooks and the counters are
family ``lm``'s own (:class:`lm.Cell`); what differs is here: the sizes
and the share from the configuration's published keys, the weights, the
model FLOPs of a step, and the plain reference
(``references/mla_moe_lm.py``) with its own ``follow``.

The configuration is one chip's share of a deployment (its file says
which): token ids are drawn from the held vocabulary rows, and loss and
logits are over those rows.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from distributed_kfac_pytorch_tpu import launch, multislice
from distributed_kfac_pytorch_tpu.models import mla_moe_lm
from distributed_kfac_pytorch_tpu.observability import sink as obs_sink
from distributed_kfac_pytorch_tpu.parallel import distributed as D
from distributed_kfac_pytorch_tpu.training import optimizers

from kfac_bench import reference
from kfac_bench.families import lm
from kfac_bench.references import mla_moe_lm as moe_reference

INIT_STD = 0.02
SKIP_LAYERS = ['head']     # the untied head is left to SGD


def flops_per_step(sizes: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step, forward and backward, of the
    share this program holds: the attention projections at the held
    heads, causal attention counted whole (QK^T at nope + rope, PV at
    the value width), the dense or the shared SwiGLU, the router at all
    its outputs, the held experts at the rows expected under a uniform
    router (``top_k x held / total`` a token), and the head over the
    held vocabulary rows; backward twice the forward. K-FAC's own work
    does not count."""
    d, heads = sizes['hidden_size'], sizes['heads_held']
    qk = sizes['qk_nope_head_dim'] + sizes['qk_rope_head_dim']
    kv = sizes['qk_nope_head_dim'] + sizes['v_head_dim']
    lo, hi = sizes['experts_held']
    attention = 2 * (d * heads * qk
                     + d * (sizes['kv_lora_rank']
                            + sizes['qk_rope_head_dim'])
                     + sizes['kv_lora_rank'] * heads * kv
                     + heads * sizes['v_head_dim'] * d
                     + seq * heads * (qk + sizes['v_head_dim']))
    swiglu = lambda width: 3 * 2 * d * width  # noqa: E731
    rows_here = (sizes['num_experts_per_tok'] * (hi - lo)
                 / sizes['n_routed_experts'])
    moe = (2 * d * sizes['n_routed_experts']
           + swiglu(sizes['n_shared_experts']
                    * sizes['moe_intermediate_size'])
           + rows_here * swiglu(sizes['moe_intermediate_size']))
    dense_layers = sizes['first_k_dense_replace']
    per_token = (sizes['num_hidden_layers'] * attention
                 + dense_layers * swiglu(sizes['intermediate_size'])
                 + (sizes['num_hidden_layers'] - dense_layers) * moe
                 + 2 * d * sizes['vocab_size'])
    return 3.0 * per_token * batch * seq


def init_params(seed: int, sizes: dict):
    """The weights, made on the device in one jitted call, in the tree
    the reference takes: N(0, 0.02) matrices, unit norm scales. (The
    program's tree has one more leaf a MoE layer, the zero correction
    bias: :func:`with_correction_bias`.)"""
    d, heads = sizes['hidden_size'], sizes['heads_held']
    qk = sizes['qk_nope_head_dim'] + sizes['qk_rope_head_dim']
    rank, lo_hi = sizes['kv_lora_rank'], sizes['experts_held']
    held = lo_hi[1] - lo_hi[0]

    def make(key):
        def normal(i, *shape):
            return {'kernel': INIT_STD * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)}

        def norm(width=d):
            return {'scale': jnp.ones((width,), jnp.float32)}

        def swiglu(base, width, *stack):
            return {'gate_proj': normal(base, *stack, d, width),
                    'up_proj': normal(base + 1, *stack, d, width),
                    'down_proj': normal(base + 2, *stack, width, d)}

        params = {
            'embed': {'embedding': normal(0, sizes['vocab_size'],
                                          d)['kernel']},
            'norm': norm(), 'head': normal(1, d, sizes['vocab_size'])}
        for i in range(sizes['num_hidden_layers']):
            base = 20 * (i + 1)
            if moe_reference.is_moe(sizes, i):
                mlp = {'router': normal(base + 4, d,
                                        sizes['n_routed_experts']),
                       'experts': swiglu(
                           base + 5, sizes['moe_intermediate_size'], held),
                       'shared_experts': swiglu(
                           base + 8, sizes['n_shared_experts']
                           * sizes['moe_intermediate_size'])}
            else:
                mlp = swiglu(base + 5, sizes['intermediate_size'])
            params[f'layer{i}'] = {
                'input_layernorm': norm(),
                'post_attention_layernorm': norm(),
                'self_attn': {
                    'q_proj': normal(base, d, heads * qk),
                    'kv_a_proj_with_mqa': normal(
                        base + 1, d, rank + sizes['qk_rope_head_dim']),
                    'kv_a_layernorm': norm(rank),
                    'kv_b_proj': normal(
                        base + 2, rank, heads * (sizes['qk_nope_head_dim']
                                                 + sizes['v_head_dim'])),
                    'o_proj': normal(base + 3,
                                     heads * sizes['v_head_dim'], d)},
                'mlp': mlp}
        return params

    return jax.jit(make)(lm.key_of(seed))


def with_correction_bias(params: dict, sizes: dict) -> dict:
    """``params`` with each MoE layer's zero ``e_score_correction_bias``
    (the program's buffer; no gradient reaches it)."""
    out = dict(params)
    for i in range(sizes['num_hidden_layers']):
        if moe_reference.is_moe(sizes, i):
            layer = dict(out[f'layer{i}'])
            layer['mlp'] = {**layer['mlp'],
                            'e_score_correction_bias': jnp.zeros(
                                (sizes['n_routed_experts'],), jnp.float32)}
            out[f'layer{i}'] = layer
    return out


def build_model(config: dict, sizes: dict):
    return mla_moe_lm.get_model(
        sizes['vocab_size'], config['program_size'],
        dtype=lm.DTYPES[config['compute_dtype']],
        d_model=sizes['hidden_size'],
        num_layers=sizes['num_hidden_layers'],
        first_k_dense=sizes['first_k_dense_replace'],
        heads_held=sizes['heads_held'],
        intermediate_size=sizes['intermediate_size'],
        n_routed_experts=sizes['n_routed_experts'],
        experts_held=sizes['experts_held'],
        num_experts_per_tok=sizes['num_experts_per_tok'],
        moe_intermediate_size=sizes['moe_intermediate_size'],
        n_shared_experts=sizes['n_shared_experts'],
        routed_scaling_factor=sizes['routed_scaling_factor'],
        qk_nope_head_dim=sizes['qk_nope_head_dim'],
        qk_rope_head_dim=sizes['qk_rope_head_dim'],
        v_head_dim=sizes['v_head_dim'],
        kv_lora_rank=sizes['kv_lora_rank'],
        rope_theta=float(sizes['rope_theta']))


class Cell(lm.Cell):
    """What the harness drives: :class:`lm.Cell`'s attributes, feed and
    hooks, round this family's model, weights and reference."""

    def __init__(self, config: dict, traffic: dict, seed: int, chips: int,
                 out_dir: str):
        self.sizes = moe_reference.sizes_of(config)
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
                out.astype(jnp.float32), batch[1]).mean()

        data_spec = P(multislice.batch_axes(mesh))
        self.batch_spec = (data_spec, data_spec, P())
        self.step_fn = dkfac.build_train_step(
            loss_fn, tx, model_kwargs_fn=lambda batch: {'train': True},
            batch_spec=self.batch_spec, loss_scale=None)
        self.lr = lr_schedule(0)
        self.hyper = {'lr': self.lr, **kfac_sched.params()}
        self.restart(seed, params)

    def _weights(self, seed: int):
        return with_correction_bias(init_params(seed, self.sizes),
                                    self.sizes)

    def restart(self, seed: int, params=None) -> None:
        if params is None:
            params = launch.replicate_on_mesh(self.mesh,
                                              self._weights(seed))
        # lm.Cell.restart makes its own family's weights when given
        # none; given these it only resets state, feed and readings.
        super().restart(seed, params)

    def after_step(self, index: int, out) -> None:
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
                reference.sketch_factors(
                    moe_reference.split_stacks(kstate['factors'])))

    def counters(self) -> dict:
        """:meth:`lm.Cell.counters`, and how the window's tokens fell on
        the held experts (``moe``: the step metrics' means over the
        records that carry them)."""
        out = super().counters()
        steps = [r['metrics'] for r in obs_sink.read_jsonl(self.stream)
                 if r['kind'] == 'step' and 'moe/rows_here' in r['metrics']]
        lo, hi = self.sizes['experts_held']
        out['moe'] = {'experts_held': hi - lo, 'steps': len(steps)}
        for key in ('rows_here', 'rows_max_expert', 'empty_experts'):
            values = [m[f'moe/{key}'] for m in steps]
            out['moe'][key] = (sum(values) / len(values) if values
                               else None)
        out['left_to_sgd'] = self.left_to_sgd
        return out

    def reference_run(self, **planted) -> dict:
        return reference_observe(self.config, self.traffic, self.seed,
                                 self.chips, self.checked, **planted)


def reference_observe(config: dict, traffic: dict, seed: int, chips: int,
                      batches=None, *, rounding=reference.Rounding(),
                      half_batch=False, unchanged_state=False) -> dict:
    """What the reference's :func:`follow` reads for a cell's first
    steps, from nothing of the program (see ``lm.reference_observe``)."""
    sizes = moe_reference.sizes_of(config)
    if batches is None:
        feed = lm.token_batches(seed, sizes['vocab_size'],
                                traffic['per_chip_batch'] * chips,
                                traffic['seq'])
        batches = [next(feed) for _ in range(traffic['check_steps'])]
    hyper = reference.Hyper(**{
        f.name: traffic[f.name]
        for f in dataclasses.fields(reference.Hyper)})
    step = moe_reference.model_step(
        sizes, config.get('reference_rows_per_block', 2),
        half_batch=half_batch)
    with jax.default_matmul_precision('highest'):
        return moe_reference.follow(
            step, sizes, hyper, init_params(seed, sizes), batches,
            rounding=rounding, unchanged_state=unchanged_state)


def build(config: dict, traffic: dict, seed: int, chips: int,
          out_dir: str) -> Cell:
    return Cell(config, traffic, seed, chips, out_dir)
