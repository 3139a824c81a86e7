"""AST rule families for kfaclint (single-file checks).

Five rule families; the first four live here (pure AST, one file at a
time), the fifth (``surface``) is cross-file and lives in
:mod:`analysis.surface`:

==================  =====================================================
family              rules
==================  =====================================================
``host-sync``       ``host-item``, ``host-device-get``,
                    ``host-scalar-cast``, ``host-implicit-bool``,
                    ``host-np-asarray`` — device->host transfers on the
                    hot-path modules. Static under-approximation by
                    design: only *syntactically certain* device values
                    (a ``jnp.*``/``jax.lax.*`` call in the expression)
                    are flagged; ``KFAC_SANITIZE=transfer`` is the
                    dynamic oracle for what the AST cannot see.
``retrace``         ``retrace-jit-in-loop``,
                    ``retrace-traced-mutation``,
                    ``retrace-variant-flag`` — hazards to the
                    one-compile-per-variant contract (PERF.md
                    pitfalls 2-3; the ``trace_counts`` guard and
                    ``KFAC_SANITIZE=retrace`` are the runtime form).
``axis``            ``axis-literal`` — collectives must name axes via
                    the canonical constants
                    (``parallel.distributed.INV_GROUP_AXIS``,
                    ``GRAD_WORKER_AXIS``, ``KFAC_AXES``,
                    ``parallel.sequence.SEQ_AXIS``), never string
                    literals.
``dtype``           ``dtype-matmul-accum`` — a matmul whose operands
                    are syntactically bf16-flavored (``bfloat16`` /
                    ``*compute_dtype*`` / ``*bf16*`` names) or part
                    of the r19 randomized low-rank sketch pipeline
                    (``*sketch*`` / ``*lowrank*`` names — the basis
                    products that must not silently accumulate in a
                    reduced-precision backend default) must pin fp32
                    accumulation via ``preferred_element_type``
                    (the r6 bf16-pipeline contract).
                    ``dtype-pallas-matmul-accum`` — EVERY matmul
                    inside a Pallas kernel body (a function passed to
                    ``pl.pallas_call``, directly or through
                    ``functools.partial``, or whose signature takes
                    two or more ``*_ref`` parameters) must pin
                    ``preferred_element_type=jnp.float32``: Mosaic
                    lowers an unpinned MXU matmul at the operand
                    dtype, so a bf16 block accumulates in bf16 with
                    no backend-default safety net.
==================  =====================================================

Waiver syntax (for the documented blocking points — the barrier
probe, metric drains, checkpoint-restore paths):

    kstep = int(jax.device_get(s['step']))  # kfaclint: waive[host-sync] one sync per epoch, documented

A waiver names a rule id or a family, must carry a non-empty reason,
and covers its own line plus the following line (so it can sit on its
own line above a multi-line call). A malformed waiver is itself a
finding (``waiver-unknown-rule`` / ``waiver-missing-reason``) so a
typo cannot silently disable a rule.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import re
import tokenize

# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

#: rule id -> (family, one-line doc). The single point of truth the
#: CLI's --list-rules, the waiver validator and the tests read.
RULES = {
    'host-item': (
        'host-sync', '.item() is a device->host sync'),
    'host-device-get': (
        'host-sync', 'jax.device_get blocks on device values'),
    'host-scalar-cast': (
        'host-sync', 'float()/int()/bool() of a traced expression '
        'forces a host sync'),
    'host-implicit-bool': (
        'host-sync', 'branching on a jnp/lax expression calls '
        '__bool__ -> host sync'),
    'host-np-asarray': (
        'host-sync', 'np.asarray/np.array of a jnp/lax expression '
        'pulls it to host'),
    'retrace-jit-in-loop': (
        'retrace', 'jax.jit/shard_map built inside a loop body '
        'retraces per iteration'),
    'retrace-traced-mutation': (
        'retrace', 'assigning self.<attr> inside a jitted function '
        'mutates module state at trace time'),
    'retrace-variant-flag': (
        'retrace', 'variant-key cadence flag given a non-canonical '
        '(unhashable or float/str) value'),
    'axis-literal': (
        'axis', 'collective names an axis with a string literal '
        'instead of the canonical axis constants'),
    'dtype-matmul-accum': (
        'dtype', 'bf16-flavored matmul without fp32 '
        'preferred_element_type accumulation'),
    'dtype-pallas-matmul-accum': (
        'dtype', 'matmul inside a Pallas kernel body without fp32 '
        'preferred_element_type accumulation'),
    'surface-drift': (
        'surface', 'cross-file knob/event surface drift '
        '(see analysis.surface)'),
    # meta rules (waiver hygiene; never waivable themselves)
    'waiver-unknown-rule': (
        'waiver', 'waiver names a rule id/family that does not exist'),
    'waiver-missing-reason': (
        'waiver', 'waiver carries no reason'),
}

FAMILIES = ('host-sync', 'retrace', 'axis', 'dtype', 'surface')

#: the variant-key cadence flags build_train_step statically keys on.
VARIANT_FLAGS = ('factor_update', 'inv_update', 'inv_chunk',
                 'factor_reduce', 'factor_snapshot')

#: jax.lax collectives whose axis argument the axis rule inspects,
#: mapped to the positional index of that argument.
COLLECTIVE_AXIS_ARG = {
    'psum': 1, 'pmean': 1, 'pmax': 1, 'pmin': 1,
    'all_gather': 1, 'all_to_all': 1, 'ppermute': 1,
    'psum_scatter': 1, 'pshuffle': 1,
    'axis_index': 0, 'axis_size': 0,
}

#: jnp/lax functions that LOOK like device calls but return host
#: values (static dtype predicates) — exempt from host-implicit-bool.
_STATIC_PREDICATES = frozenset({
    'issubdtype', 'isdtype', 'dtype', 'result_type', 'can_cast',
    'shape', 'ndim', 'size'})

_MATMUL_FUNCS = frozenset({
    'matmul', 'dot', 'einsum', 'tensordot', 'dot_general'})

_BF16_NAME = re.compile(r'bfloat16|bf16|compute_dtype|sketch|lowrank')

#: hot-path module patterns (package-relative posix paths) the
#: host-sync and dtype families are scoped to.
HOT_PATH_PATTERNS = (
    'preconditioner.py',
    'parallel/distributed.py',
    'parallel/sequence.py',
    'training/engine.py',
    'ops/',
    'layers/',
)


def is_hot_path(package_rel_path: str) -> bool:
    """True when ``package_rel_path`` (posix, relative to the package
    root) is one of the hot-path modules."""
    p = package_rel_path.replace('\\', '/')
    return any(p == pat or (pat.endswith('/') and p.startswith(pat))
               for pat in HOT_PATH_PATTERNS)


@dataclasses.dataclass
class Finding:
    """One rule violation (or waiver-hygiene problem)."""
    path: str
    line: int
    col: int
    rule: str
    family: str
    message: str
    waived: bool = False

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# Waivers
# ---------------------------------------------------------------------------

_WAIVER_RE = re.compile(
    r'#\s*kfaclint:\s*waive\[([^\]]*)\]\s*(.*)$')


@dataclasses.dataclass
class Waiver:
    line: int
    rules: tuple          # rule ids and/or family names
    reason: str
    used: bool = False

    def covers(self, rule: str, family: str, line: int) -> bool:
        if line not in (self.line, self.line + 1):
            return False
        return rule in self.rules or family in self.rules


def parse_waivers(source: str, path: str
                  ) -> tuple[list[Waiver], list[Finding]]:
    """Scan ``source`` for waiver comments; malformed ones become
    findings (a typo must not silently disable a rule).

    Real COMMENT tokens only (via ``tokenize``) — waiver syntax
    quoted in a docstring or string literal is documentation, not a
    waiver."""
    waivers, findings = [], []
    comments: list[tuple[int, str]] = []
    try:
        for tok in tokenize.generate_tokens(
                io.StringIO(source).readline):
            if tok.type == tokenize.COMMENT:
                comments.append((tok.start[0], tok.string))
    except (tokenize.TokenError, IndentationError, SyntaxError):
        pass  # unparseable files already get a syntax-error finding
    for lineno, text in comments:
        m = _WAIVER_RE.search(text)
        if not m:
            continue
        names = tuple(s.strip() for s in m.group(1).split(',')
                      if s.strip())
        reason = m.group(2).strip()
        bad = [n for n in names
               if n not in RULES and n not in FAMILIES]
        if bad or not names:
            findings.append(Finding(
                path, lineno, 0, 'waiver-unknown-rule', 'waiver',
                f'waiver names unknown rule(s)/family(ies) '
                f'{bad or ["<empty>"]} — one of {sorted(RULES)} or '
                f'{list(FAMILIES)}'))
            continue
        if not reason:
            findings.append(Finding(
                path, lineno, 0, 'waiver-missing-reason', 'waiver',
                'waiver must carry a reason '
                '(# kfaclint: waive[rule] why this blocking point '
                'is legitimate)'))
            continue
        waivers.append(Waiver(lineno, names, reason))
    return waivers, findings


# ---------------------------------------------------------------------------
# AST helpers
# ---------------------------------------------------------------------------

def _chain(node) -> list[str] | None:
    """`jax.lax.psum` -> ['jax', 'lax', 'psum']; None if not a plain
    dotted name."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return None


class _Aliases:
    """Import aliases for the jax / jax.numpy / jax.lax / numpy roots."""

    def __init__(self, tree: ast.AST):
        self.jnp = {'jnp'}      # jax.numpy aliases
        self.lax = {'lax'}      # jax.lax aliases
        self.jax = {'jax'}
        self.np = {'np', 'onp', 'numpy'}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    name = a.asname or a.name
                    if a.name == 'jax.numpy':
                        self.jnp.add(name)
                    elif a.name == 'jax.lax':
                        self.lax.add(name)
                    elif a.name == 'jax':
                        self.jax.add(name)
                    elif a.name == 'numpy':
                        self.np.add(name)
            elif isinstance(node, ast.ImportFrom):
                if node.module == 'jax':
                    for a in node.names:
                        if a.name == 'numpy':
                            self.jnp.add(a.asname or 'numpy')
                        elif a.name == 'lax':
                            self.lax.add(a.asname or 'lax')

    def is_device_chain(self, chain: list[str] | None) -> bool:
        """True when the dotted chain roots in jnp / lax / jax.lax —
        an expression that produces (or is) a traced/device value."""
        if not chain or len(chain) < 2:
            return False
        if chain[0] in self.jnp or chain[0] in self.lax:
            return True
        return (chain[0] in self.jax and len(chain) >= 3
                and chain[1] in ('lax', 'numpy'))

    def device_func_name(self, chain: list[str] | None) -> str | None:
        """Final attribute of a device-rooted chain (else None)."""
        return chain[-1] if self.is_device_chain(chain) else None


def _contains_device_expr(node: ast.AST, aliases: _Aliases) -> bool:
    """True when the expression syntactically CONTAINS a device value:
    a jnp/lax call, an ``.item()`` call, or ``jax.device_get``."""
    for sub in ast.walk(node):
        if not isinstance(sub, ast.Call):
            continue
        chain = _chain(sub.func)
        if aliases.is_device_chain(chain):
            return True
        if chain and chain[-1] == 'device_get':
            return True
        if (isinstance(sub.func, ast.Attribute)
                and sub.func.attr == 'item' and not sub.args):
            return True
    return False


def _has_string_literal(node: ast.AST) -> bool:
    """Str constant, or a tuple/list containing one."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return True
    if isinstance(node, (ast.Tuple, ast.List)):
        return any(_has_string_literal(e) for e in node.elts)
    return False


# ---------------------------------------------------------------------------
# The visitor
# ---------------------------------------------------------------------------

class _RuleVisitor(ast.NodeVisitor):
    def __init__(self, path: str, aliases: _Aliases, *, hot: bool,
                 jit_wrapped_names: frozenset,
                 pallas_kernel_names: frozenset = frozenset()):
        self.path = path
        self.aliases = aliases
        self.hot = hot
        self.jit_wrapped_names = jit_wrapped_names
        self.pallas_kernel_names = pallas_kernel_names
        self.findings: list[Finding] = []
        self._loop_depth = 0
        self._jitted_depth = 0
        self._pallas_depth = 0

    def _emit(self, node, rule: str, message: str):
        family = RULES[rule][0]
        self.findings.append(Finding(
            self.path, node.lineno, node.col_offset, rule, family,
            message))

    # -- loops (for retrace-jit-in-loop scope) --------------------------
    def visit_For(self, node):
        # target/iter evaluate ONCE, before the loop — only the body
        # re-executes per iteration (orelse runs once, after)
        self.visit(node.target)
        self.visit(node.iter)
        self._loop_body(node)

    def visit_While(self, node):
        if self.hot:
            self._check_bool_context(node.test)
        # the test DOES re-evaluate per iteration
        self._loop_depth += 1
        self.visit(node.test)
        self._loop_depth -= 1
        self._loop_body(node)

    def _loop_body(self, node):
        self._loop_depth += 1
        for stmt in node.body:
            self.visit(stmt)
        self._loop_depth -= 1
        for stmt in node.orelse:
            self.visit(stmt)

    # -- function defs (traced-mutation scope) --------------------------
    def visit_FunctionDef(self, node):
        self._function(node)

    def visit_AsyncFunctionDef(self, node):
        self._function(node)

    def _is_jit_decorator(self, dec) -> bool:
        chain = _chain(dec) or (
            _chain(dec.func) if isinstance(dec, ast.Call) else None)
        if chain and chain[-1] == 'jit':
            return True
        # functools.partial(jax.jit, ...)
        if isinstance(dec, ast.Call) and dec.args:
            inner = _chain(dec.args[0])
            if inner and inner[-1] == 'jit':
                return True
        return False

    def _is_pallas_kernel(self, node) -> bool:
        """A def is a Pallas kernel body when it is passed to
        ``pallas_call`` somewhere in the module, or (structural
        fallback for kernels handed over through wrappers the name
        scan cannot see) when two or more of its parameters follow
        the ``*_ref`` Ref-argument naming convention."""
        if node.name in self.pallas_kernel_names:
            return True
        params = node.args.posonlyargs + node.args.args
        return sum(p.arg.endswith('_ref') for p in params) >= 2

    def _function(self, node):
        jitted = (any(self._is_jit_decorator(d)
                      for d in node.decorator_list)
                  or node.name in self.jit_wrapped_names)
        in_pallas = self._is_pallas_kernel(node)
        if jitted:
            self._jitted_depth += 1
        if in_pallas:
            self._pallas_depth += 1
        # a nested def is a fresh loop scope: jit built once inside a
        # helper that a loop merely CALLS is not a per-iteration build
        saved_loops, self._loop_depth = self._loop_depth, 0
        self.generic_visit(node)
        self._loop_depth = saved_loops
        if in_pallas:
            self._pallas_depth -= 1
        if jitted:
            self._jitted_depth -= 1

    def _check_self_mutation(self, node, targets):
        if self._jitted_depth == 0:
            return
        for t in targets:
            if (isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == 'self'):
                self._emit(
                    node, 'retrace-traced-mutation',
                    f'self.{t.attr} assigned inside a jitted '
                    'function: module state mutated at trace time '
                    'is frozen into the compiled program and '
                    'desyncs on retrace — thread it through the '
                    'state pytree instead')

    def visit_Assign(self, node):
        self._check_self_mutation(node, node.targets)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        self._check_self_mutation(node, [node.target])
        self.generic_visit(node)

    # -- branch tests (implicit __bool__) -------------------------------
    def _check_bool_context(self, test):
        """A jnp/lax call ANYWHERE in a boolean test means the test
        value is traced: ``if jnp.any(x)``, ``if jnp.max(x) > t``,
        ``while jnp.linalg.norm(g) > eps and i < n`` all force
        ``__bool__`` on a device value. Static dtype/shape predicates
        (``jnp.issubdtype`` & co) are exempt."""
        def outermost(node):
            """Outermost device calls only (one finding per traced
            subexpression, not one per nested jnp call)."""
            if isinstance(node, ast.Call):
                name = self.aliases.device_func_name(
                    _chain(node.func))
                if name and name not in _STATIC_PREDICATES:
                    yield node
                    return
            for child in ast.iter_child_nodes(node):
                yield from outermost(child)

        for e in outermost(test):
            self._emit(
                e, 'host-implicit-bool',
                f'branching on {ast.unparse(e)[:60]!r} calls '
                '__bool__ on a traced value (host sync; '
                'ConcretizationTypeError under jit) — use '
                'jnp.where/lax.cond or hoist the decision to '
                'the host')

    def visit_If(self, node):
        if self.hot:
            self._check_bool_context(node.test)
        self.generic_visit(node)

    def visit_Assert(self, node):
        if self.hot:
            self._check_bool_context(node.test)
        self.generic_visit(node)

    def visit_IfExp(self, node):
        if self.hot:
            self._check_bool_context(node.test)
        self.generic_visit(node)

    # -- calls ----------------------------------------------------------
    def visit_Call(self, node):
        chain = _chain(node.func)
        tail = chain[-1] if chain else None

        # retrace-jit-in-loop: applies everywhere (not just hot files)
        if (self._loop_depth > 0
                and tail in ('jit', 'shard_map', 'pmap')
                and (self.aliases.is_device_chain(chain)
                     or (chain and chain[0] in self.aliases.jax)
                     or chain == ['jit'] or chain == ['shard_map'])):
            self._emit(
                node, 'retrace-jit-in-loop',
                f'{".".join(chain)} constructed inside a loop body: '
                'each iteration builds a fresh traced callable '
                '(compile per iteration) — hoist the jit/shard_map '
                'out of the loop and reuse it')

        # retrace-variant-flag: canonical variant-key values only
        for kw in node.keywords:
            if kw.arg in VARIANT_FLAGS:
                bad = None
                if isinstance(kw.value, (ast.List, ast.Dict, ast.Set)):
                    bad = 'an unhashable literal'
                elif (isinstance(kw.value, ast.Constant)
                      and not isinstance(kw.value.value,
                                         (bool, int, type(None)))):
                    bad = f'a {type(kw.value.value).__name__} literal'
                if bad:
                    self._emit(
                        node, 'retrace-variant-flag',
                        f'cadence flag {kw.arg}={ast.unparse(kw.value)}'
                        f' is {bad}: variant-cache keys must be '
                        'bool/int/None (hashable, canonical) or every '
                        'step compiles its own program variant')

        # axis-literal: canonical axis constants only
        axis_idx = COLLECTIVE_AXIS_ARG.get(tail)
        if axis_idx is not None and (
                self.aliases.is_device_chain(chain)
                or chain == [tail]):
            exprs = [kw.value for kw in node.keywords
                     if kw.arg in ('axis_name', 'axis', 'axis_names')]
            if not exprs and len(node.args) > axis_idx:
                exprs = [node.args[axis_idx]]
            for e in exprs:
                if _has_string_literal(e):
                    self._emit(
                        node, 'axis-literal',
                        f'{tail} names axis {ast.unparse(e)} as a '
                        'string literal — use the canonical axis '
                        'constants (parallel.distributed.'
                        'INV_GROUP_AXIS / GRAD_WORKER_AXIS / '
                        'KFAC_AXES / SLICE_AXIS, '
                        'parallel.sequence.SEQ_AXIS) so '
                        'a mesh rename cannot split the collective '
                        'surface')

        if self.hot:
            self._hot_call_rules(node, chain, tail)
        self.generic_visit(node)

    def _hot_call_rules(self, node, chain, tail):
        aliases = self.aliases
        # host-item
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr == 'item' and not node.args):
            self._emit(
                node, 'host-item',
                f'{ast.unparse(node)[:60]!r}: .item() blocks the '
                'host on device completion — keep the value on '
                'device (metrics pytree) or drain it at the epoch '
                'boundary')
        # host-device-get
        if tail == 'device_get' and chain and (
                chain[0] in aliases.jax or chain == ['device_get']):
            self._emit(
                node, 'host-device-get',
                'jax.device_get on the hot path blocks the host — '
                'drain asynchronously (sink) or waive the '
                'documented blocking point')
        # host-scalar-cast
        if (isinstance(node.func, ast.Name)
                and node.func.id in ('float', 'int', 'bool')
                and len(node.args) == 1
                and _contains_device_expr(node.args[0], aliases)):
            self._emit(
                node, 'host-scalar-cast',
                f'{node.func.id}() of a device expression forces a '
                'host sync — keep it traced or drain it off the '
                'step path')
        # host-np-asarray
        if (tail in ('asarray', 'array') and chain
                and chain[0] in aliases.np and node.args
                and _contains_device_expr(node.args[0], aliases)):
            self._emit(
                node, 'host-np-asarray',
                f'np.{tail}() of a jnp/lax expression pulls it to '
                'host — keep the computation in jnp or waive the '
                'documented blocking point')
        # dtype-matmul-accum / dtype-pallas-matmul-accum
        if (tail in _MATMUL_FUNCS
                and aliases.is_device_chain(chain)
                and not any(kw.arg == 'preferred_element_type'
                            for kw in node.keywords)):
            if self._pallas_depth > 0:
                # Inside a Pallas kernel body the requirement is
                # unconditional — Mosaic accumulates an unpinned MXU
                # matmul at the operand dtype, so even an fp32-looking
                # Ref load can be a bf16 block under a compute_dtype
                # knob. The generic bf16-flavor rule is subsumed.
                self._emit(
                    node, 'dtype-pallas-matmul-accum',
                    f'{tail} inside a Pallas kernel body must pin '
                    'fp32 accumulation: pass preferred_element_type='
                    'jnp.float32 (Mosaic lowers the MXU accumulate '
                    'at the operand dtype with no backend-default '
                    'safety net)')
            else:
                flavored = any(
                    isinstance(sub, (ast.Name, ast.Attribute))
                    and _BF16_NAME.search(
                        sub.id if isinstance(sub, ast.Name)
                        else sub.attr)
                    for a in node.args for sub in ast.walk(a))
                if flavored:
                    self._emit(
                        node, 'dtype-matmul-accum',
                        f'{tail} with bf16-flavored operands must '
                        'pin fp32 accumulation: pass '
                        'preferred_element_type=jnp.float32 (the r6 '
                        'bf16-pipeline contract — bf16 operands, '
                        'fp32 accumulate)')


def _jit_wrapped_names(tree: ast.AST) -> frozenset:
    """Names of functions passed (by name) to jax.jit in this module —
    their defs count as jitted for retrace-traced-mutation."""
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            chain = _chain(node.func)
            if chain and chain[-1] == 'jit' and node.args:
                inner = node.args[0]
                if isinstance(inner, ast.Name):
                    names.add(inner.id)
    return frozenset(names)


def _pallas_kernel_names(tree: ast.AST) -> frozenset:
    """Names of functions handed to ``pallas_call`` in this module —
    their defs count as Pallas kernel bodies for
    dtype-pallas-matmul-accum. Covers the bare form
    (``pl.pallas_call(kernel, ...)``) and the partial-bound form
    (``pl.pallas_call(functools.partial(kernel, decay=d), ...)``)
    the in-tree kernels use to close over scalars."""
    names = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _chain(node.func)
        if not (chain and chain[-1] == 'pallas_call' and node.args):
            continue
        inner = node.args[0]
        if isinstance(inner, ast.Name):
            names.add(inner.id)
        elif isinstance(inner, ast.Call) and inner.args:
            head = _chain(inner.func)
            if head and head[-1] == 'partial':
                bound = inner.args[0]
                if isinstance(bound, ast.Name):
                    names.add(bound.id)
    return frozenset(names)


def lint_file(path: str, source: str, *, hot: bool | None = None,
              package_rel: str | None = None
              ) -> tuple[list[Finding], list[Waiver]]:
    """Lint one file's source; returns ``(findings, waivers)``.

    ``hot`` forces hot-path scoping (None: derived from
    ``package_rel`` via :func:`is_hot_path`). Waived findings are
    returned with ``waived=True`` (the CLI reports but does not fail
    on them); each returned waiver carries its authoritative
    ``used`` flag — the single coverage predicate is
    :meth:`Waiver.covers`.
    """
    if hot is None:
        hot = bool(package_rel) and is_hot_path(package_rel)
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding(path, e.lineno or 0, e.offset or 0,
                        'syntax-error', 'waiver',
                        f'file does not parse: {e.msg}')], []
    waivers, findings = parse_waivers(source, path)
    aliases = _Aliases(tree)
    visitor = _RuleVisitor(
        path, aliases, hot=hot,
        jit_wrapped_names=_jit_wrapped_names(tree),
        pallas_kernel_names=_pallas_kernel_names(tree))
    visitor.visit(tree)
    for f in visitor.findings:
        for w in waivers:
            if w.covers(f.rule, f.family, f.line):
                f.waived = True
                w.used = True
                break
    findings.extend(visitor.findings)
    findings.sort(key=lambda f: (f.line, f.col, f.rule))
    return findings, waivers


def lint_source(path: str, source: str, *, hot: bool | None = None,
                package_rel: str | None = None) -> list[Finding]:
    """:func:`lint_file`, findings only (the single-file API)."""
    return lint_file(path, source, hot=hot,
                     package_rel=package_rel)[0]
