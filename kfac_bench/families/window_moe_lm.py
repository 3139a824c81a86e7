"""Family ``window_moe_lm``: the windowed/full-attention, grouped-head,
softmax-routed expert decoder (``models/window_moe_lm.py``) trained by
the program's K-FAC step.

The program's side makes the calls ``examples/train_language_model.py``
makes for ``--arch window_moe``, in its order, as the other families do
for their decoders: ``window_moe_lm.get_model``,
``optimizers.get_optimizer`` (the untied head in ``skip_layers``),
``make_multislice_mesh``, ``DistributedKFAC``, ``build_train_step``,
driven by ``engine.train_epoch``. The feed, the clock's hooks, the
expert stacks' readings and the MoE counters are family
``mla_moe_lm``'s (:class:`mla_moe_lm.Cell`); what differs is here: the
sizes and the share from the configuration's published keys, the
weights, the model FLOPs of a step, the attention's own work
(:func:`attention_work`, which ``readers/attention_roofline.py`` reads),
and the plain reference (``references/window_moe_lm.py``).

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
from distributed_kfac_pytorch_tpu.models import window_moe_lm
from distributed_kfac_pytorch_tpu.observability import sink as obs_sink
from distributed_kfac_pytorch_tpu.parallel import distributed as D
from distributed_kfac_pytorch_tpu.training import optimizers

from kfac_bench import reference
from kfac_bench.families import lm, mla_moe_lm
from kfac_bench.references import window_moe_lm as window_reference

INIT_STD = 0.02
#: The embedding's init. At N(0, 0.02) attention over thousands of keys
#: is a third of every token's normed stream, the same vector for all
#: of them, so the router's random columns send a seed's tokens to the
#: held experts 0.96 to 1.10 times as often as to the others (the cell's
#: full layer at seq 4096): each seed would time another load. N(0, 1)
#: (T5's and PaLM's scale) keeps the stream each token's own; what is
#: left of the seed in the held load is its router's columns against its
#: embedding table, about 1 % (PERF.md, section 7).
EMBED_STD = 1.0
SKIP_LAYERS = ['head']     # the untied head is left to SGD


def mask_pairs(sizes: dict, seq: int) -> int:
    """(query, key, q head) pairs the layers' masks keep for one row of
    ``seq`` tokens: ``n(i) = i + 1`` keys for query ``i`` on a full
    layer, ``min(i + 1, window)`` on a sliding one."""
    w = sizes['sliding_window']
    full = seq * (seq + 1) // 2
    sliding = (min(w, seq) * (min(w, seq) + 1) // 2
               + max(seq - w, 0) * w)
    per_head = sum(sliding if kind == window_reference.SLIDING else full
                   for kind in sizes['layer_types'])
    return sizes['num_attention_heads'] * per_head


def attention_work(sizes: dict, batch: int, seq: int) -> dict:
    """What the attention calls of one step must do at least, forward
    and backward: ``flops`` ``12 d`` a kept pair (QK^T and P.V forward,
    ``4 d``; dS, dP, dQ and dK/dV backward, ``8 d``; no recomputation),
    and ``bytes`` of q, k, v, o, dO, dq, dk and dv once each in
    bfloat16 and one float32 row statistic a (query, q head) written
    forward and read backward."""
    d, heads = sizes['head_dim'], sizes['num_attention_heads']
    kv = sizes['num_key_value_heads']
    tokens = batch * seq
    layers = sizes['num_hidden_layers']
    per_layer = 2 * tokens * d * 4 * (heads + kv) + 2 * 4 * tokens * heads
    return {'flops': 12.0 * d * batch * mask_pairs(sizes, seq),
            'bytes': float(layers * per_layer)}


def flops_per_step(sizes: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step, forward and backward, of the
    share this program holds: the four attention projections, the
    attention over the pairs the masks keep (:func:`mask_pairs`, QK^T
    and P.V, ``4 d`` a pair), the router at all its outputs, the held
    experts at the rows expected under a uniform router (``top_k x held
    / total`` a token), and the head over the held vocabulary rows;
    backward twice the forward. K-FAC's own work does not count."""
    d, hd = sizes['hidden_size'], sizes['head_dim']
    width = (2 * sizes['num_attention_heads']
             + 2 * sizes['num_key_value_heads']) * hd
    lo, hi = sizes['experts_held']
    rows_here = (sizes['num_experts_per_tok'] * (hi - lo)
                 / sizes['num_experts'])
    per_token = sizes['num_hidden_layers'] * (
        2 * d * width + 2 * d * sizes['num_experts']
        + rows_here * 3 * 2 * d * sizes['moe_intermediate_size'])
    per_token += 2 * d * sizes['vocab_size']
    attention = 4 * hd * batch * mask_pairs(sizes, seq)
    return 3.0 * (per_token * batch * seq + attention)


def init_params(seed: int, sizes: dict):
    """The weights, made on the device in one jitted call, in the tree
    the model declares and the reference takes: N(0, 0.02) matrices,
    an N(0, 1) embedding (:data:`EMBED_STD`), unit norm scales."""
    d, hd = sizes['hidden_size'], sizes['head_dim']
    lo, hi = sizes['experts_held']
    width = sizes['moe_intermediate_size']

    def make(key):
        def normal(i, *shape):
            return {'kernel': INIT_STD * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)}

        def norm():
            return {'scale': jnp.ones((d,), jnp.float32)}

        params = {
            'embed': {'embedding': EMBED_STD / INIT_STD * normal(
                0, sizes['vocab_size'], d)['kernel']},
            'norm': norm(), 'head': normal(1, d, sizes['vocab_size'])}
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
                              'o_proj': normal(base + 3, q, d)},
                'mlp': {'router': normal(base + 4, d, sizes['num_experts']),
                        'experts': {
                            'gate_proj': normal(base + 5, hi - lo, d, width),
                            'up_proj': normal(base + 6, hi - lo, d, width),
                            'down_proj': normal(base + 7, hi - lo, width,
                                                d)}}}
        return params

    return jax.jit(make)(lm.key_of(seed))


def build_model(config: dict, sizes: dict):
    theta, factor, length, fast, slow, attention = sizes['full_rope']
    if theta != sizes['sliding_rope_theta']:
        raise ValueError('the program gives both layer types one theta')
    return window_moe_lm.get_model(
        sizes['vocab_size'], config['program_size'],
        dtype=lm.DTYPES[config['compute_dtype']],
        d_model=sizes['hidden_size'],
        num_layers=sizes['num_hidden_layers'],
        layer_types=sizes['layer_types'],
        num_heads=sizes['num_attention_heads'],
        num_kv_heads=sizes['num_key_value_heads'],
        head_dim=sizes['head_dim'],
        sliding_window=sizes['sliding_window'],
        rope_theta=float(theta),
        yarn={'factor': float(factor),
              'original_max_position_embeddings': int(length),
              'beta_fast': float(fast), 'beta_slow': float(slow),
              'attention_factor': float(attention)},
        num_experts=sizes['num_experts'],
        experts_held=sizes['experts_held'],
        num_experts_per_tok=sizes['num_experts_per_tok'],
        moe_intermediate_size=sizes['moe_intermediate_size'])


class Cell(mla_moe_lm.Cell):
    """What the harness drives: :class:`mla_moe_lm.Cell`'s attributes,
    feed, hooks and counters, round this family's model, weights and
    reference."""

    def __init__(self, config: dict, traffic: dict, seed: int, chips: int,
                 out_dir: str):
        self.sizes = window_reference.sizes_of(config)
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
        return init_params(seed, self.sizes)

    def counters(self) -> dict:
        """:meth:`mla_moe_lm.Cell.counters`, which layers follow
        another's A, and the attention's work a step
        (:func:`attention_work`)."""
        out = super().counters()
        out['a_followers'] = self.a_followers
        out['attention_work'] = attention_work(self.sizes, self.batch,
                                               self.seq)
        return out

    def reference_run(self, **planted) -> dict:
        return reference_observe(self.config, self.traffic, self.seed,
                                 self.chips, self.checked, **planted)


def reference_observe(config: dict, traffic: dict, seed: int, chips: int,
                      batches=None, *, rounding=reference.Rounding(),
                      half_batch=False, unchanged_state=False) -> dict:
    """What the reference's :func:`follow` reads for a cell's first
    steps, from nothing of the program (see ``lm.reference_observe``)."""
    sizes = window_reference.sizes_of(config)
    if batches is None:
        feed = lm.token_batches(seed, sizes['vocab_size'],
                                traffic['per_chip_batch'] * chips,
                                traffic['seq'])
        batches = [next(feed) for _ in range(traffic['check_steps'])]
    hyper = reference.Hyper(**{
        f.name: traffic[f.name]
        for f in dataclasses.fields(reference.Hyper)})
    step = window_reference.model_step(
        sizes, config.get('reference_rows_per_block', 1),
        half_batch=half_batch)
    with jax.default_matmul_precision('highest'):
        return window_reference.follow(
            step, sizes, hyper, init_params(seed, sizes), batches,
            rounding=rounding, unchanged_state=unchanged_state)


def build(config: dict, traffic: dict, seed: int, chips: int,
          out_dir: str) -> Cell:
    return Cell(config, traffic, seed, chips, out_dir)
