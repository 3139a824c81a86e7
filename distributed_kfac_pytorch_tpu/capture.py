"""Activation / output-gradient capture for K-FAC, without hooks.

The reference relies on torch forward/backward hooks to snapshot each
module's inputs and output-gradients (kfac/preconditioner.py:701-727,
kfac/layers/base.py:364-379) because autograd hides intermediates. In JAX
nothing is hidden: this module captures both quantities *functionally* from
any flax model, unmodified:

  - activations ``a``: a method interceptor (``nn.intercept_methods``) wraps
    every registered module call and ``sow``s its input into the
    ``kfac_in`` collection;
  - output gradients ``g``: the interceptor adds a zero-valued probe to the
    module output (``Module.perturb``); differentiating the loss wrt the
    ``kfac_probes`` collection yields exactly dL/dy per module call.

Both arrive as pure outputs of one ``value_and_grad`` — no mutation, no
graph introspection, jit/vmap/shard_map-safe. Modules called multiple times
per step (e.g. LSTM cells unrolled over time) get one capture and one probe
per call, the analogue of the reference's ``accumulate_data`` path
(kfac/layers/base.py:364-379).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distributed_kfac_pytorch_tpu.modules.experts import ExpertsDense

CAPTURE_COL = 'kfac_in'
PROBE_COL = 'kfac_probes'


def extra_vars_of(variables) -> dict:
    """The collections a caller should carry as train-state extra_vars:
    everything except 'params' and the capture-internal collections
    (``KFAC.init`` returns ``kfac_probes`` shaped for the *init* batch —
    stale for any other batch, and dead weight in checkpoints). The one
    place the internal-collection names are spelled outside this module.
    """
    return {k: v for k, v in variables.items()
            if k not in ('params', PROBE_COL, CAPTURE_COL)}

# Module kinds, mirroring the reference's KNOWN_MODULES
# (kfac/layers/__init__.py:11) plus the embedding layer the reference
# disabled (kfac/layers/embedding.py:20).
LINEAR = 'linear'
CONV2D = 'conv2d'
EMBEDDING = 'embedding'
# Grouped/depthwise conv: per-group block-diagonal Fisher (round 5 —
# BEYOND the reference, whose registry has no conv variant at all for
# feature_group_count != 1, kfac/layers/__init__.py:13-36; this
# framework preconditions MobileNet/EfficientNet-class models).
CONV2D_GROUPED = 'conv2d_grouped'
# Stacked experts (``modules.experts.ExpertsDense``): one weight per
# expert, each seeing only the rows routed to it, so the Fisher block is
# block-diagonal over experts and the layer carries ``num_experts``
# stacked (da, da)/(dg, dg) factor blocks, as a grouped conv carries one
# per group. No reference analogue.
EXPERTS = 'experts'
#: Kinds whose factors and inverses are ``(blocks, d, d)`` stacks.
BLOCK_STACK_KINDS = (CONV2D_GROUPED, EXPERTS)

# Weight-sharing Kronecker approximations (arXiv:2311.00636, "K-FAC for
# Modern Neural Network Architectures"). A layer whose weight is shared
# across a sequence/patch axis (every Dense in a transformer block, the
# ViT patch-embed conv) admits two principled factorizations:
#   - KFAC_EXPAND: per-position independence — flatten (batch, T, d)
#     into B*T covariance rows (the historical default of this repo's
#     collapse_batch_dims path; bit-identical to pre-sharing behavior);
#   - KFAC_REDUCE: reduce over the shared axis BEFORE the covariance —
#     activations are averaged and output-grads summed over T (the
#     paper's Eq. 22 convention keeps the bias column exactly 1), so
#     the factor contraction sees B rows instead of B*T: a factor-T
#     cheaper statistic that is exact whenever activations are constant
#     across the shared axis and empirically matches expand on
#     transformer/ViT workloads.
# The per-layer choice is carried here, in the registry
# (LayerSpec.kfac_approx), resolved by sharing.approx.
KFAC_EXPAND = 'expand'
KFAC_REDUCE = 'reduce'
KFAC_APPROXES = (KFAC_EXPAND, KFAC_REDUCE)


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """Static description of one registered layer (hashable, trace-safe).

    The functional analogue of a registered ``KFACLayer``'s identity/config
    (reference kfac/layers/base.py:10-45): everything the factor math needs
    to interpret this layer's captures and map its gradient to/from the
    2-D ``(out_dim, in_dim[+1])`` matrix form.
    """
    path: tuple[str, ...]          # module path == params subtree path
    kind: str        # LINEAR | CONV2D | CONV2D_GROUPED | EMBEDDING | EXPERTS
    has_bias: bool
    num_calls: int = 1             # calls per training step (e.g. timesteps)
    # conv2d / conv2d_grouped only:
    kernel_size: tuple[int, ...] | None = None
    strides: tuple[int, ...] | None = None
    padding: Any = None
    feature_group_count: int = 1   # conv2d_grouped: number of groups
    # embedding only:
    vocab_size: int | None = None
    # experts only: the stacked experts, and the routed rows one token
    # of the step makes (the router's top-k; statistics are normalised
    # by tokens, as every other layer's are).
    num_experts: int = 0
    rows_per_token: int = 1
    # Weight-sharing approximation for this layer's factor statistics
    # (KFAC_EXPAND | KFAC_REDUCE). Registration records 'expand' (the
    # exact-parity default); sharing.annotate_specs resolves the
    # per-layer setting from KFAC(kfac_approx=...). Static program
    # structure: the choice is baked into the trace (zero retraces).
    kfac_approx: str = KFAC_EXPAND
    # Shared-axis positions seen at registration (prod of the input
    # dims between batch and features for a Dense; 1 when the input is
    # 2-D). The sharing policy's "is this Dense sequence/patch-shared"
    # signal; informational for other kinds.
    shared_positions: int = 1
    # Tied-embedding support: number of ``Embed.attend`` call sites
    # captured for this embedding (0 = lookup-only registration). The
    # in/out-tied pair contributes BOTH call sites' statistics to one
    # factor pair with one inverse entry (the reference's
    # register_shared_module intent, kfac/preconditioner.py:404-470 —
    # which it then disabled wholesale, embedding.py:20).
    tied_calls: int = 0
    # The earlier layer whose A statistic is this layer's too, because
    # both read the SAME traced value and make the same statistic of it
    # (:func:`share_a_owners`); None: the layer owns its A. A follower's
    # A is contracted and inverted once, by its owner; its factor slot
    # stays its own.
    a_owner: str | None = None

    @property
    def name(self) -> str:
        return '/'.join(self.path) if self.path else '<root>'

    @property
    def num_blocks(self) -> int:
        """Stacked factor blocks of a ``BLOCK_STACK_KINDS`` layer."""
        return (self.num_experts if self.kind == EXPERTS
                else self.feature_group_count)


def _same_a_statistic(spec: LayerSpec, other: LayerSpec) -> bool:
    """Do two layers make the same A statistic of one input? Every
    field but the layer's own place says how ``a`` is read (kind, bias
    column, calls, conv geometry, expert count, sharing approximation).
    An embedding's A is a diagonal that a tied decoder adds to: it is
    never shared."""
    return spec.kind != EMBEDDING and dataclasses.replace(
        spec, path=(), a_owner=None) == dataclasses.replace(
            other, path=(), a_owner=None)


def share_a_owners(specs: dict[str, LayerSpec], same_input
                   ) -> dict[str, LayerSpec]:
    """``specs`` with each layer's ``a_owner`` set: the first earlier
    layer that owns its A, for which ``same_input(owner, layer)`` holds
    and which makes the same statistic of that input; None otherwise.
    Registration calls it with the identity of the traced inputs;
    ``KFAC.init`` again once ``kfac_approx`` is resolved a layer (a
    follower whose approximation differs from its owner's leaves the
    group, and may own the A of later layers that went with it)."""
    out: dict[str, LayerSpec] = {}
    for name, spec in specs.items():
        owner = next(
            (n for n, s in out.items()
             if s.a_owner is None and same_input(n, name)
             and _same_a_statistic(s, spec)), None)
        out[name] = (spec if spec.a_owner == owner
                     else dataclasses.replace(spec, a_owner=owner))
    return out


def _canonical_padding(padding, n_spatial: int):
    if isinstance(padding, str):
        return padding
    if isinstance(padding, int):
        return [(padding, padding)] * n_spatial
    out = []
    for p in padding:
        out.append((p, p) if isinstance(p, int) else tuple(p))
    return out


def _conv_decline_reason(mod: nn.Conv) -> str | None:
    """Why a conv-family module cannot be K-FAC-preconditioned, or None.

    These are the configurations the factor math does not model (the
    reference's registry simply has no layer class for them either,
    kfac/layers/__init__.py:13-36 — but it *errors* on the module kinds
    it refuses, :31-33, where silence here would hide a partially
    preconditioned model). Grouped/depthwise convs are SUPPORTED since
    round 5 (per-group block-diagonal factors, kind CONV2D_GROUPED).
    """
    dilation = mod.kernel_dilation
    if dilation is not None and any(
            d != 1 for d in (dilation if isinstance(dilation, Sequence)
                             else (dilation,))):
        return f'dilated conv (kernel_dilation={dilation})'
    if len(tuple(mod.kernel_size)) != 2:
        return f'non-2D conv (kernel_size={tuple(mod.kernel_size)})'
    return None


def _decline_reason(mod: nn.Module) -> str | None:
    """Why a capturable-family module is NOT preconditioned, or None.

    One policy for every registered family (round 4 — the round-3
    review found Conv declined subclasses loudly while a Dense subclass
    with overridden call semantics was silently captured as plain
    Dense, so its factor math could mis-model it): the exact type and
    flax's own lifted-transform wrappers (nn.remat / nn.scan — base
    call semantics, wrapped execution) are accepted; any USER subclass
    is declined loudly, plus the conv-configuration checks. A user
    subclass that genuinely behaves like its base can be registered by
    converting it to composition over the exact type.
    """
    for base in (nn.Dense, nn.Conv, nn.Embed, ExpertsDense):
        if isinstance(mod, base) and type(mod) is not base:
            # flax's lifted transforms (nn.remat / nn.scan / ...)
            # generate subclasses in flax.linen.transforms whose call
            # SEMANTICS are the base's (only execution is wrapped) —
            # capture them like the base; decline user subclasses.
            if type(mod).__module__.startswith('flax.linen.'):
                break
            return (f'{base.__name__} subclass {type(mod).__name__} '
                    f'(capture only matches exact {base.__name__}; its '
                    'call semantics may differ from the factor math)')
    if isinstance(mod, nn.Conv):
        return _conv_decline_reason(mod)
    return None


def _spec_for_module(mod: nn.Module, path: tuple[str, ...],
                     num_calls: int, a_in=None) -> LayerSpec | None:
    """Build a LayerSpec for a supported flax module, else None.

    Mirrors the registry dispatch in reference kfac/layers/__init__.py:13-36
    (module type -> KFACLayer class), with unsupported configurations
    (grouped/dilated convs, subclasses of the registered families)
    skipped rather than mis-modelled (declines are recorded and
    reported — see KFACCapture.skipped_modules).

    ``a_in`` is the module input at registration time — only its static
    SHAPE is read (the Dense shared-axis position count for the
    sharing policy); None leaves the default.
    """
    if _decline_reason(mod) is not None:
        return None
    # isinstance AFTER the decline gate: what reaches here is the exact
    # type or a flax lifted-transform wrapper (accepted above).
    if isinstance(mod, nn.Dense):
        shared = (int(np.prod(a_in.shape[1:-1]))
                  if a_in is not None and a_in.ndim > 2 else 1)
        return LayerSpec(path=path, kind=LINEAR, has_bias=mod.use_bias,
                         num_calls=num_calls, shared_positions=shared)
    if isinstance(mod, nn.Conv):
        strides = mod.strides
        if strides is None:
            strides = (1, 1)
        elif isinstance(strides, int):
            strides = (strides, strides)
        else:
            strides = tuple(strides)
        groups = mod.feature_group_count
        return LayerSpec(path=path,
                         kind=CONV2D if groups == 1 else CONV2D_GROUPED,
                         has_bias=mod.use_bias,
                         num_calls=num_calls,
                         kernel_size=tuple(mod.kernel_size),
                         strides=strides,
                         padding=_canonical_padding(mod.padding, 2),
                         feature_group_count=groups)
    if isinstance(mod, nn.Embed):
        return LayerSpec(path=path, kind=EMBEDDING, has_bias=False,
                         num_calls=num_calls, vocab_size=mod.num_embeddings)
    if isinstance(mod, ExpertsDense):
        return LayerSpec(path=path, kind=EXPERTS, has_bias=False,
                         num_calls=num_calls, num_experts=mod.num_experts,
                         rows_per_token=mod.rows_per_token)
    return None


class KFACCapture:
    """Registers supported modules of a flax model and captures (a, g).

    The functional counterpart of ``KFAC.register_model``
    (reference kfac/preconditioner.py:355-402): walks the model by
    *intercepting* calls rather than attaching hooks, prunes subtrees whose
    path component or class name matches ``skip_layers`` (case-insensitive,
    like reference preconditioner.py:191-200), and exposes

      ``loss_and_grads(loss_fn, params, *args)``
        -> (loss, aux, param_grads, captures, updated_vars)

    where ``captures`` maps layer name -> {'a': tuple, 'g': tuple} with one
    entry per module call.
    """

    def __init__(self, model: nn.Module,
                 skip_layers: str | Sequence[str] | None = None,
                 capture_dtype: Any = 'auto',
                 trainable: Callable[[str], bool] | None = None,
                 tied_embeddings: bool = False):
        self.model = model
        # Capture ``Embed.attend`` call sites (the tied in/out decoder,
        # flax's form of the reference register_shared_module pair) so
        # both uses of a tied embedding weight feed one factor pair.
        # Off by default: the lookup-only capture is the historical
        # bit-identical path (KFAC resolves the default from its
        # sharing setting).
        self.tied_embeddings = tied_embeddings
        self._tied_counts: dict[tuple[str, ...], int] = {}
        if skip_layers is None:
            skip_layers = []
        elif isinstance(skip_layers, str):
            skip_layers = [skip_layers]
        self.skip_layers = frozenset(s.lower() for s in skip_layers)
        # Frozen-parameter support (reference module_requires_grad,
        # kfac/layers/__init__.py:38-40: modules whose params don't
        # require grad are never registered). JAX has no requires_grad;
        # fine-tuning freezes params via the optimizer (optax.masked /
        # zero updates), so the caller states the same intent here:
        # ``trainable('/'.join(module_path)) -> bool``. Frozen layers
        # get no capture, no factor statistics, and no preconditioning
        # — their (unused) gradients pass through untouched.
        self.trainable = trainable
        # Dtype for captured activations ('a'). The captures feed ONLY
        # the factor statistics, whose covariance matmuls round fp32
        # inputs to bf16 on the TPU MXU anyway (ops.factors.get_cov
        # precision contract) — so storing them bf16 loses nothing the
        # matmul keeps, while halving the capture write and (for convs)
        # the im2col patch materialization traffic that dominates the
        # factor phase (PERF.md round 3). This is also production
        # reference behavior: under --fp16/AMP its hooks capture the
        # autocast half-precision activations (kfac/layers/base.py:385,
        # README.md:150-160). 'auto' = bf16 on TPU for float inputs,
        # passthrough elsewhere; None = always passthrough (strict-fp32
        # parity); an explicit dtype forces the cast. Output-grad
        # captures ('g') are never cast here — they are read once, so a
        # cast would add traffic, not save it.
        self.capture_dtype = capture_dtype
        self._specs: dict[str, LayerSpec] | None = None
        self._skipped: dict[str, str] = {}

    def _cast_capture(self, x):
        cd = self.capture_dtype
        if cd is None:
            return x
        if cd == 'auto':
            if (jax.default_backend() == 'tpu'
                    and x.dtype == jnp.float32):
                cd = jnp.bfloat16
            else:
                return x
        if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != cd:
            return x.astype(cd)
        return x

    # -- registration ------------------------------------------------------

    def _module_path(self, mod: nn.Module) -> tuple[str, ...]:
        return tuple(mod.path)

    def _is_skipped(self, mod: nn.Module, path: tuple[str, ...]) -> bool:
        if type(mod).__name__.lower() in self.skip_layers:
            return True
        return any(part.lower() in self.skip_layers for part in path)

    def _make_interceptor(self, record_specs: bool):
        call_counts: dict[tuple[str, ...], int] = {}
        tied_counts: dict[tuple[str, ...], int] = {}
        self._tied_counts = tied_counts
        # Registration only: what each call of each layer was handed
        # (the input and, for stacked experts, the group sizes), kept
        # as the objects themselves so that :meth:`init` can tell which
        # layers read one traced value.
        call_inputs: dict[str, list[tuple]] = {}
        self._call_inputs = call_inputs

        def tied_attend(mod, path, args, kwargs, next_fun):
            """Capture an ``Embed.attend`` call site (the output-tied
            use of a tied in/out embedding weight: ``logits = x E^T``).
            The attend input rides in the ``a_tied`` capture slot and
            the output probe in ``tied_probe<i>`` — paired by
            :meth:`collect` into the same layer's captures so both call
            sites' statistics feed ONE factor pair (the reference's
            register_shared_module intent, preconditioner.py:404-470).
            """
            if args:
                x_in = args[0]
            elif 'query' in kwargs:
                x_in = kwargs['query']
            else:
                return next_fun(*args, **kwargs)
            idx = tied_counts.get(path, 0)
            tied_counts[path] = idx + 1
            mod.sow(CAPTURE_COL, 'a_tied', self._cast_capture(x_in),
                    init_fn=tuple, reduce_fn=lambda p, x: p + (x,))
            y = next_fun(*args, **kwargs)
            return mod.perturb(f'tied_probe{idx}', y,
                               collection=PROBE_COL)

        def interceptor(next_fun, args, kwargs, context):
            mod = context.module
            if mod is None:
                return next_fun(*args, **kwargs)
            is_attend = (self.tied_embeddings
                         and context.method_name == 'attend'
                         and isinstance(mod, nn.Embed))
            if context.method_name != '__call__' and not is_attend:
                return next_fun(*args, **kwargs)
            path = self._module_path(mod)
            if self._is_skipped(mod, path):
                if record_specs and path:
                    self._skipped['/'.join(path)] = 'skip_layers match'
                return next_fun(*args, **kwargs)
            if self.trainable is not None and \
                    not self.trainable('/'.join(path)):
                if record_specs and path:
                    self._skipped['/'.join(path)] = (
                        'frozen (trainable predicate): plain gradients, '
                        'no factor work')
                return next_fun(*args, **kwargs)
            reason = _decline_reason(mod)
            if reason or _spec_for_module(mod, path, 1) is None:
                if record_specs and reason:
                    self._skipped['/'.join(path)] = reason
                return next_fun(*args, **kwargs)
            if is_attend:
                return tied_attend(mod, path, args, kwargs, next_fun)
            # Dense/Conv/Embed all name their input 'inputs'; support both
            # positional and keyword call styles.
            if args:
                a_in = args[0]
            elif 'inputs' in kwargs:
                a_in = kwargs['inputs']
            else:
                return next_fun(*args, **kwargs)

            idx = call_counts.get(path, 0)
            call_counts[path] = idx + 1
            mod.sow(CAPTURE_COL, 'a', self._cast_capture(a_in),
                    init_fn=tuple, reduce_fn=lambda p, x: p + (x,))
            rows = None
            if isinstance(mod, ExpertsDense):
                # The rows of each expert (group_sizes): the statistics
                # are contracted expert by expert over them.
                rows = args[1] if len(args) > 1 else kwargs['group_sizes']
                mod.sow(CAPTURE_COL, 'rows', rows,
                        init_fn=tuple, reduce_fn=lambda p, x: p + (x,))
            y = next_fun(*args, **kwargs)
            y = mod.perturb(f'probe{idx}', y, collection=PROBE_COL)
            if record_specs:
                spec = _spec_for_module(mod, path, call_counts[path],
                                        a_in)
                self._specs['/'.join(path)] = spec
                call_inputs.setdefault('/'.join(path), []).append(
                    (a_in, rows))
            return y

        return interceptor

    def init(self, rng, *args, init_model: nn.Module | None = None,
             **kwargs) -> tuple[dict, dict]:
        """Init model variables under interception; records layer specs.

        Returns ``(variables, specs)`` (plain dicts). ``variables`` contains 'params' and
        'kfac_probes' (zeros, shaped for the init batch).

        ``init_model`` optionally substitutes a structurally-identical
        single-device twin for the trace — needed when ``self.model``
        contains collectives that only trace inside ``shard_map`` (e.g. a
        ring-attention sequence-parallel model): params and layer specs
        depend only on structure, so the twin's registration is exact.
        """
        self._specs = {}
        self._skipped = {}
        model = self.model if init_model is None else init_model
        with nn.intercept_methods(self._make_interceptor(record_specs=True)):
            variables = model.init(rng, *args, **kwargs)
        variables = dict(variables)
        variables.pop(CAPTURE_COL, None)
        # Tied attend call sites seen during the trace: merge the count
        # into the owning embedding's spec (the attend branch never
        # records specs itself — registration is the lookup's job; an
        # attend on a NEVER-looked-up Embed stays unregistered, like
        # any other un-called module).
        for path, n in self._tied_counts.items():
            name = '/'.join(path)
            if name in self._specs:
                self._specs[name] = dataclasses.replace(
                    self._specs[name], tied_calls=n)
        # Layers that were handed one traced value, call for call, share
        # an A. Identity, not equality: a copy or a cast made before the
        # call is a value of its own.
        seen, self._call_inputs = self._call_inputs, {}
        self._specs = share_a_owners(
            self._specs, lambda owner, name: (
                len(seen[owner]) == len(seen[name]) and all(
                    x is y for mine, theirs in zip(seen[owner], seen[name])
                    for x, y in zip(mine, theirs))))
        self._record_unregistered_params(variables.get('params', {}))
        declined = {n: r for n, r in self._skipped.items()
                    if 'conv' in r.lower() or 'subclass' in r}
        if declined:
            # The reference hard-errors on module kinds it refuses
            # (kfac/layers/__init__.py:31-33); silence here would hide a
            # partially preconditioned model, so be loud about the convs
            # K-FAC *should* cover but cannot.
            import warnings
            lines = ', '.join(f'{n} ({r})' for n, r in declined.items())
            warnings.warn(
                f'K-FAC cannot precondition {len(declined)} '
                f'module(s); their params get plain gradients: {lines}. '
                'See KFACCapture.skipped_modules for the full report.')
        return variables, dict(self._specs)

    def _record_unregistered_params(self, params) -> None:
        """Record parameterized modules that registration never covered.

        Walks the params tree for leaf-parent paths (modules holding
        arrays directly). Anything not a registered layer and not already
        recorded gets a generic 'unsupported module type' entry — e.g.
        BatchNorm scale/bias (benign: the reference never preconditions
        normalization layers either) or custom modules with params.
        """
        def walk(node, path):
            if not isinstance(node, dict):
                return
            if any(not isinstance(v, dict) for v in node.values()):
                # Direct array leaves: this path is a parameterized
                # module. Do NOT return — a module may hold its own
                # params AND nested parameterized submodules.
                name = '/'.join(path)
                if name not in self._specs and name not in self._skipped:
                    self._skipped[name] = (
                        'unsupported module type (params receive plain '
                        'gradients)')
            for key, child in node.items():
                walk(child, path + (key,))

        walk(params, ())

    @property
    def skipped_modules(self) -> dict[str, str]:
        """{module path: reason} for every parameterized module K-FAC does
        not precondition — skip_layers matches, declined conv configs
        (grouped/dilated/non-2D/subclass), and unsupported kinds. The
        loud-report answer to the reference's silent partial coverage
        (it errors only on RNNCellBase, kfac/layers/__init__.py:31-33).
        """
        return dict(self._skipped)

    @property
    def specs(self) -> dict[str, LayerSpec]:
        if self._specs is None:
            raise ValueError('no layers registered: call init() first')
        return dict(self._specs)

    # -- capture-time application -----------------------------------------

    @staticmethod
    def _clean_extra(extra_vars) -> dict:
        """Caller-supplied collections minus capture internals.

        ``KFAC.init`` returns a ``kfac_probes`` collection shaped for the
        *init* batch; a caller that forwards every non-param collection
        (the natural spelling — bench.py, the CLIs) must not pre-seat
        those stale shapes here, where fresh probes are built per batch.
        """
        extra_vars = dict(extra_vars or {})
        extra_vars.pop(PROBE_COL, None)
        extra_vars.pop(CAPTURE_COL, None)
        return extra_vars

    def zero_probes(self, params, *args, extra_vars=None, mutable_cols=(),
                    **kwargs):
        """Zero probe pytree shaped for the given batch (via eval_shape).

        Everything is closed over rather than passed through ``eval_shape``
        so non-array arguments (e.g. ``train=True`` flags) stay Python
        values instead of becoming tracers; ``eval_shape`` never executes
        compute either way.
        """
        extra_vars = self._clean_extra(extra_vars)

        def shapes():
            with nn.intercept_methods(
                    self._make_interceptor(record_specs=False)):
                _, state = self.model.apply(
                    {'params': params, **extra_vars}, *args,
                    mutable=[CAPTURE_COL, PROBE_COL, *mutable_cols],
                    **kwargs)
            return state.get(PROBE_COL, {})
        tree = jax.eval_shape(shapes)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), tree)

    def apply(self, params, probes, *args, extra_vars=None,
              mutable_cols=(), **kwargs):
        """Forward pass with capture.

        ``extra_vars`` supplies additional variable collections (e.g.
        ``{'batch_stats': ...}``); ``mutable_cols`` names the ones the
        model updates in-pass. Returns
        ``(out, activations_tree, updated_vars)``.
        """
        extra_vars = self._clean_extra(extra_vars)
        with nn.intercept_methods(self._make_interceptor(record_specs=False)):
            out, state = self.model.apply(
                {'params': params, PROBE_COL: probes, **extra_vars}, *args,
                mutable=[CAPTURE_COL, *mutable_cols], **kwargs)
        updated = {c: state[c] for c in mutable_cols if c in state}
        return out, state.get(CAPTURE_COL, {}), updated

    def loss_and_grads(self, loss_fn: Callable, params, *args,
                       probes=None, extra_vars=None, mutable_cols=(),
                       has_aux=False, loss_scale=None, intercept=True,
                       **kwargs):
        """One backward pass yielding param grads AND per-layer captures.

        ``loss_fn`` receives the model output only — close over labels and
        any other data: ``lambda out: cross_entropy(out, labels)``. With
        ``has_aux=True`` it returns ``(loss, aux)``.

        ``loss_scale`` multiplies the loss before differentiation and
        divides the gradients and output-grad captures after — the fp16
        loss-scaling hook (the analogue of the reference's GradScaler
        unscaling at hook time, kfac/layers/base.py:374-375,397-407).
        Identity in fp32/bf16; on TPU bf16 needs no scaling, so the
        default is None.

        ``extra_vars`` are non-differentiated collections passed to apply
        (e.g. ``{'batch_stats': ...}``); collections listed in
        ``mutable_cols`` are updated during the pass and returned.

        ``intercept=False`` skips the capture machinery entirely — a plain
        ``value_and_grad`` over ``model.apply``, returning ``captures={}``.
        This is the static-cadence fast path for non-factor-update steps:
        the reference's hooks are gated off exactly the same way on those
        steps (``_periodic_hook``, kfac/preconditioner.py:684-699), and
        measurement shows XLA does NOT dead-code-eliminate the probe/sow
        machinery when captures go unused (+2.7 ms/iter on ResNet-50
        @224px b64 — PERF.md round 4).

        Returns ``(loss, aux, grads, captures, updated_vars)`` where
        ``captures`` maps layer name -> {'a': (per-call activations...),
        'g': (per-call output grads...)} and ``updated_vars`` holds the
        new values of ``mutable_cols`` ({} if none).
        """
        # Loss-scaling is shared by both paths: scale the loss before
        # differentiation, unscale loss/grad outputs after (the
        # reference's GradScaler hook semantics) — one definition so the
        # intercepting and plain paths cannot drift.
        def scale_loss(loss):
            return loss if loss_scale is None else loss * loss_scale

        def unscale(*trees):
            if loss_scale is None:
                return trees
            inv = 1.0 / loss_scale
            return tuple(jax.tree.map(lambda g: g * inv, t) for t in trees)

        if not intercept:
            if probes is not None:
                raise ValueError(
                    'probes were passed with intercept=False — the capture '
                    'machinery is skipped entirely on non-intercepting '
                    'steps, so precomputed probes indicate caller '
                    'confusion; drop probes or set intercept=True')
            extra = self._clean_extra(extra_vars)

            def plain(params):
                out, state = self.model.apply(
                    {'params': params, **extra}, *args,
                    mutable=list(mutable_cols), **kwargs)
                res = loss_fn(out)
                loss, aux = res if has_aux else (res, None)
                updated = {c: state[c] for c in mutable_cols if c in state}
                return scale_loss(loss), (aux, updated)

            (loss, (aux, updated)), grads = jax.value_and_grad(
                plain, has_aux=True)(params)
            loss, grads = unscale(loss, grads)
            return loss, aux, grads, {}, updated

        if probes is None:
            probes = self.zero_probes(params, *args, extra_vars=extra_vars,
                                      mutable_cols=mutable_cols, **kwargs)

        def wrapped(params, probes):
            out, acts, updated = self.apply(
                params, probes, *args, extra_vars=extra_vars,
                mutable_cols=mutable_cols, **kwargs)
            res = loss_fn(out)
            loss, aux = res if has_aux else (res, None)
            return scale_loss(loss), (aux, acts, updated)

        (loss, (aux, acts, updated)), (grads, probe_grads) = (
            jax.value_and_grad(wrapped, argnums=(0, 1), has_aux=True)(
                params, probes))
        loss, grads, probe_grads = unscale(loss, grads, probe_grads)
        captures = self.collect(acts, probe_grads)
        return loss, aux, grads, captures, updated

    def collect(self, acts_tree, probe_grads_tree) -> dict[str, dict]:
        """Pair sown activations with probe gradients, per layer name.

        Call counts are derived from the trees themselves, not the
        init-time ``spec.num_calls`` — a weight-shared module may be called
        a different number of times at step time (e.g. a cell unrolled to a
        different sequence length) and a/g must stay paired per call.
        """
        captures = {}
        for name, spec in self.specs.items():
            acts_node = _get_path(acts_tree, spec.path)
            a_node = tuple(acts_node['a'])
            g_node = _get_path(probe_grads_tree, spec.path)
            n_tied = len(acts_node.get('a_tied', ()))
            gs = tuple(g_node[f'probe{i}']
                       for i in range(len(g_node) - n_tied))
            if len(a_node) != len(gs):
                raise ValueError(
                    f'layer {name}: {len(a_node)} captured activations vs '
                    f'{len(gs)} probe gradients — activation and probe '
                    'call counts must match')
            captures[name] = {'a': a_node, 'g': gs}
            if 'rows' in acts_node:
                captures[name]['rows'] = tuple(acts_node['rows'])
            if n_tied:
                # Tied-embedding attend sites: inputs + output-grad
                # probes, paired per call like the primary stream.
                captures[name]['a_tied'] = tuple(acts_node['a_tied'])
                captures[name]['g_tied'] = tuple(
                    g_node[f'tied_probe{i}'] for i in range(n_tied))
        return captures


def _get_path(tree, path: tuple[str, ...]):
    node = tree
    for part in path:
        node = node[part]
    return node


def subsample_captures(captures: dict, fraction: float) -> dict:
    """Keep ``ceil(B * fraction)`` evenly-strided batch rows per capture.

    Within-step thinning of the factor statistics: every covariance in
    this package normalizes by its own row count (ops.factors.get_cov),
    so a leading-dim subsample estimates the same expectations — the
    same statistical axis as the reference's production cadence
    (factors from one batch in 50, launch_node_torch_imagenet.sh:73-87),
    applied within the batch instead of across steps. Rows are taken
    *strided* across the whole batch (not a head slice) so pipelines
    that order rows within a batch (class-grouped samplers,
    length-bucketed LM batches) still contribute across the batch; the
    estimator is unbiased when batch composition doesn't correlate with
    position, which strided sampling preserves far more robustly than a
    prefix. The factor phase's cost (patch materialization + covariance
    contraction) scales with the kept rows. Slices are static (shapes
    are Python ints under jit).

    Not applied to gradients or preconditioning — only the A/G factor
    statistics see the subset.
    """
    if fraction >= 1.0:
        return captures

    def keep(t):
        b = t.shape[0]
        k = max(1, int(math.ceil(b * fraction)))
        if k >= b:
            return t
        # Evenly spread positions (i * b) // k cover the whole batch at
        # every fraction; a `[::b//k][:k]` stride degenerates to a head
        # slice whenever b // k == 1 (any fraction > 0.5) and always
        # orphans the tail when b % k != 0. Static numpy index -> one
        # constant gather under jit.
        return t[np.arange(k) * b // k]

    # All capture streams thin identically — including the tied
    # 'a_tied'/'g_tied' attend-site streams, which feed the same factor
    # statistics (dropping them here would silently bias the tied
    # factor pair toward the lookup site at fraction < 1).
    # A stacked-expert layer's rows are sorted by expert, not by batch
    # position: a leading-dim stride would thin the experts unevenly,
    # so its streams pass whole.
    return {name: (c if 'rows' in c
                   else {key: tuple(keep(t) for t in calls)
                         for key, calls in c.items()})
            for name, c in captures.items()}
