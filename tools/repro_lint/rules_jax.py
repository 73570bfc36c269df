"""JAX purity & performance rules (JAX0xx).

All five rules share one per-module :class:`JitIndex` that resolves which
functions are traced: defs decorated with ``@jax.jit`` (directly or via
``functools.partial``), defs wrapped by a ``jax.jit(...)`` / ``shard_map``
call anywhere in the module (including ``self._x = jax.jit(self._x_impl)``
method binding), and the names such wrapped programs are assigned to (the
timing rule needs to know that ``jstep = jax.jit(step)`` makes ``jstep(...)``
an *asynchronous* dispatch).

The hazards:

* Python side effects inside traced code run once at trace time, then never
  again — mutation of nonlocal state and host I/O are silent correctness
  bugs (JAX001/JAX002).
* timing a jitted call with the host clock but without
  ``block_until_ready`` measures dispatch latency, not compute (JAX003).
* array-valued / non-literal ``static_argnums`` either crash (unhashable)
  or silently recompile per value (JAX004).
* a jitted function that closes over a module-level concrete array
  constant-folds it into the executable and recompiles when it is swapped
  (JAX005).
"""
from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from tools.repro_lint.engine import (Finding, ModuleContext, Rule, qualname,
                                     register)

_JIT_WRAPPERS = {"jax.jit", "jit", "pjit", "jax.pjit"}
_SHARD_WRAPPERS = {"shard_map", "jax.shard_map"}
_WRAPPERS = _JIT_WRAPPERS | _SHARD_WRAPPERS
_PARTIALS = {"partial", "functools.partial"}


class JitIndex:
    """Which defs are traced, which names are jit-bound, and every jit call
    spec — computed once per module and shared by the JAX rules."""

    def __init__(self, ctx: ModuleContext):
        tree = ctx.tree
        self.parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(tree):
            for ch in ast.iter_child_nodes(node):
                self.parents[ch] = node

        self.defs_by_name: Dict[str, List[ast.FunctionDef]] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs_by_name.setdefault(node.name, []).append(node)

        self.jitted_defs: Set[ast.AST] = set()
        self.jit_bound_names: Set[str] = set()
        # (jit-call node, wrapped def or None) for the static-args rule
        self.jit_specs: List[Tuple[ast.Call, Optional[ast.AST]]] = []

        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and qualname(node.func) in _WRAPPERS:
                target = self._resolve_target(node)
                if target is not None:
                    self.jitted_defs.add(target)
                if qualname(node.func) in _JIT_WRAPPERS:
                    self.jit_specs.append((node, target))
                par = self.parents.get(node)
                if isinstance(par, ast.Assign):
                    for t in par.targets:
                        if isinstance(t, ast.Name):
                            self.jit_bound_names.add(t.id)
                        elif isinstance(t, ast.Attribute):
                            self.jit_bound_names.add(t.attr)

        for defs in self.defs_by_name.values():
            for fn in defs:
                spec = self._decorator_spec(fn)
                if spec is not None:
                    self.jitted_defs.add(fn)
                    self.jit_bound_names.add(fn.name)
                    if isinstance(spec, ast.Call):
                        self.jit_specs.append((spec, fn))

        # names of defs known traced: calling them directly is also an
        # async dispatch
        self.jit_bound_names |= {fn.name for fn in self.jitted_defs
                                 if hasattr(fn, "name")}

    def _resolve_target(self, call: ast.Call) -> Optional[ast.AST]:
        """The def a jit/shard_map call wraps, when visible in-module."""
        if not call.args:
            return None
        a0 = call.args[0]
        if isinstance(a0, ast.Call) and qualname(a0.func) in _WRAPPERS:
            return self._resolve_target(a0)          # jax.jit(shard_map(f))
        name = None
        if isinstance(a0, ast.Name):
            name = a0.id
        elif isinstance(a0, ast.Attribute) and \
                isinstance(a0.value, ast.Name) and a0.value.id == "self":
            name = a0.attr                           # jax.jit(self._impl)
        defs = self.defs_by_name.get(name or "", [])
        return defs[0] if len(defs) == 1 else None

    @staticmethod
    def _decorator_spec(fn) -> Optional[ast.AST]:
        """Truthy when ``fn`` is jit-decorated; the returned Call node (for
        ``@partial(jax.jit, ...)`` / ``@jax.jit(...)`` forms) carries the
        static-arg keywords."""
        for d in fn.decorator_list:
            if qualname(d) in _WRAPPERS:
                return d
            if isinstance(d, ast.Call):
                fq = qualname(d.func)
                if fq in _WRAPPERS:
                    return d
                if fq in _PARTIALS and d.args and \
                        qualname(d.args[0]) in _WRAPPERS:
                    return d
        return None


def _jit_index(ctx: ModuleContext) -> JitIndex:
    idx = ctx._cache.get("jit_index")
    if idx is None:
        idx = JitIndex(ctx)
        ctx._cache["jit_index"] = idx
    return idx


def _walk_body(fn, *, into_nested: bool = False) -> Iterator[ast.AST]:
    """Walk a def's body; by default stops at nested def/lambda/class
    boundaries (their locals and side effects belong to their own scope)."""
    stack = list(fn.body)
    while stack:
        node = stack.pop()
        yield node
        if not into_nested and isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda,
                       ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _target_names(t: ast.AST) -> Iterator[str]:
    if isinstance(t, ast.Name):
        yield t.id
    elif isinstance(t, (ast.Tuple, ast.List)):
        for e in t.elts:
            yield from _target_names(e)
    elif isinstance(t, ast.Starred):
        yield from _target_names(t.value)


def _local_names(fn) -> Set[str]:
    a = fn.args
    names = {p.arg for p in
             list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)}
    if a.vararg:
        names.add(a.vararg.arg)
    if a.kwarg:
        names.add(a.kwarg.arg)
    for node in _walk_body(fn):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                names.update(_target_names(t))
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign, ast.For,
                               ast.AsyncFor)):
            names.update(_target_names(node.target))
        elif isinstance(node, ast.NamedExpr):
            names.update(_target_names(node.target))
        elif isinstance(node, ast.comprehension):
            names.update(_target_names(node.target))
        elif isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                if item.optional_vars is not None:
                    names.update(_target_names(item.optional_vars))
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
            names.add(node.name)
    return names


def _root_name(node: ast.AST) -> Optional[str]:
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


_MUTATORS = {"append", "extend", "add", "update", "insert", "remove",
             "discard", "pop", "popitem", "clear", "setdefault", "write"}


@register
class JitNonlocalMutationRule(Rule):
    id = "JAX001"
    name = "jit-nonlocal-mutation"
    family = "jax-purity"
    description = ("mutation of captured/global state inside a traced "
                   "function happens once at trace time, then never again")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        idx = _jit_index(ctx)
        for fn in idx.jitted_defs:
            locs = _local_names(fn)
            for node in _walk_body(fn):
                if isinstance(node, (ast.Global, ast.Nonlocal)):
                    yield self.finding(
                        ctx, node,
                        f"{'global' if isinstance(node, ast.Global) else 'nonlocal'} "
                        f"declaration inside traced '{fn.name}': traced "
                        "functions must be pure — thread state through "
                        "arguments and return values")
                    continue
                targets = []
                if isinstance(node, ast.Assign):
                    targets = node.targets
                elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                    targets = [node.target]
                for t in targets:
                    if isinstance(t, (ast.Attribute, ast.Subscript)):
                        root = _root_name(t)
                        if root == "self" or (root is not None
                                              and root not in locs):
                            yield self.finding(
                                ctx, t,
                                f"write to '{root}' (captured/shared "
                                f"object) inside traced '{fn.name}' runs "
                                "at trace time only")
                if isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Attribute) and \
                        node.func.attr in _MUTATORS:
                    root = _root_name(node.func.value)
                    if root == "self" or (root is not None
                                          and root not in locs):
                        yield self.finding(
                            ctx, node,
                            f"'{root}.{node.func.attr}(...)' mutates "
                            f"captured state inside traced '{fn.name}' — "
                            "it runs at trace time only")


_IO_NAMES = {"print", "input", "breakpoint", "open"}
_IO_PREFIXES = ("logging.", "sys.stdout.", "sys.stderr.", "warnings.warn")


@register
class JitPythonIoRule(Rule):
    id = "JAX002"
    name = "jit-python-io"
    family = "jax-purity"
    description = ("host I/O inside a traced function executes at trace "
                   "time only; use jax.debug.print / jax.debug.callback")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        idx = _jit_index(ctx)
        for fn in idx.jitted_defs:
            for node in _walk_body(fn, into_nested=True):
                if not isinstance(node, ast.Call):
                    continue
                qn = qualname(node.func)
                if qn in _IO_NAMES or (qn is not None and any(
                        qn.startswith(p) or qn == p.rstrip(".")
                        for p in _IO_PREFIXES)):
                    yield self.finding(
                        ctx, node,
                        f"'{qn}(...)' inside traced '{fn.name}': host I/O "
                        "runs at trace time only — use jax.debug.print / "
                        "jax.debug.callback for runtime effects")


_TIME_FNS = {"time.time", "time.perf_counter", "time.monotonic"}


@register
class JitTimingNoSyncRule(Rule):
    id = "JAX003"
    name = "jit-timing-no-sync"
    family = "jax-perf"
    description = ("a wall-clock span around an async jitted dispatch "
                   "without block_until_ready measures dispatch latency, "
                   "not compute")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        idx = _jit_index(ctx)
        if not idx.jit_bound_names:
            return
        scopes = [ctx.tree] + [fn for defs in idx.defs_by_name.values()
                               for fn in defs]
        for scope in scopes:
            yield from self._check_scope(ctx, idx, scope)

    def _check_scope(self, ctx, idx, scope) -> Iterator[Finding]:
        walker = (_walk_body(scope) if not isinstance(scope, ast.Module)
                  else self._walk_module(scope))
        starts: List[Tuple[int, str]] = []     # (line, clock var)
        elapsed: List[Tuple[int, str, ast.AST]] = []
        jit_calls: List[int] = []
        syncs: List[int] = []
        for node in walker:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name) and \
                    isinstance(node.value, ast.Call) and \
                    qualname(node.value.func) in _TIME_FNS:
                starts.append((node.lineno, node.targets[0].id))
            elif isinstance(node, ast.BinOp) and \
                    isinstance(node.op, ast.Sub) and \
                    isinstance(node.left, ast.Call) and \
                    qualname(node.left.func) in _TIME_FNS and \
                    isinstance(node.right, ast.Name):
                elapsed.append((node.lineno, node.right.id, node))
            elif isinstance(node, ast.Call):
                qn = qualname(node.func)
                if isinstance(node.func, ast.Name) and \
                        node.func.id in idx.jit_bound_names:
                    jit_calls.append(node.lineno)
                elif qn is not None and \
                        qn.split(".")[-1] == "block_until_ready":
                    syncs.append(node.lineno)
        for eline, tvar, enode in elapsed:
            span_starts = [ln for ln, v in starts if v == tvar and ln < eline]
            if not span_starts:
                continue
            sline = max(span_starts)
            dispatched = [ln for ln in jit_calls if sline < ln < eline]
            synced = [ln for ln in syncs if sline < ln <= eline]
            if dispatched and not synced:
                yield self.finding(
                    ctx, enode,
                    f"span started at line {sline} times a jitted call "
                    f"(line {dispatched[0]}) without jax.block_until_ready"
                    " — async dispatch returns before the work finishes")

    @staticmethod
    def _walk_module(mod: ast.Module) -> Iterator[ast.AST]:
        stack = list(mod.body)
        while stack:
            node = stack.pop()
            yield node
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda, ast.ClassDef)):
                continue
            stack.extend(ast.iter_child_nodes(node))


# parameter names that (in this codebase's vocabulary) always carry arrays
_ARRAYISH_PARAMS = {"params", "batch", "x", "y", "xs", "ys", "tokens",
                    "grads", "state", "opt_state", "caches", "weights",
                    "arr", "inputs", "key", "keys", "data"}


def _literal_static_spec(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant):
        return isinstance(node.value, (int, str))
    if isinstance(node, (ast.Tuple, ast.List)):
        return all(isinstance(e, ast.Constant) and
                   isinstance(e.value, (int, str)) for e in node.elts)
    return False


@register
class StaticArgsRule(Rule):
    id = "JAX004"
    name = "suspicious-static-args"
    family = "jax-perf"
    description = ("non-literal static_argnums specs, and static args that "
                   "carry arrays (unhashable, recompile per value)")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        idx = _jit_index(ctx)
        for call, target in idx.jit_specs:
            for kw in call.keywords:
                if kw.arg not in ("static_argnums", "static_argnames"):
                    continue
                if not _literal_static_spec(kw.value):
                    yield self.finding(
                        ctx, kw.value,
                        f"{kw.arg} is not a literal int/str (tuple): a "
                        "computed static-arg spec hides which arguments "
                        "trigger recompilation")
                    continue
                if target is None:
                    continue
                yield from self._check_params(ctx, kw, target)

    def _check_params(self, ctx, kw, fn) -> Iterator[Finding]:
        params = [p.arg for p in fn.args.posonlyargs + fn.args.args]
        vals = ([kw.value] if isinstance(kw.value, ast.Constant)
                else list(kw.value.elts))
        for v in vals:
            pname = None
            if kw.arg == "static_argnums":
                i = v.value
                if not (0 <= i < len(params)):
                    yield self.finding(
                        ctx, v, f"static_argnums index {i} is out of range "
                        f"for '{fn.name}' ({len(params)} positional "
                        "parameters)")
                    continue
                pname = params[i]
            else:
                if v.value not in params:
                    yield self.finding(
                        ctx, v, f"static_argnames '{v.value}' is not a "
                        f"parameter of '{fn.name}'")
                    continue
                pname = v.value
            if pname in _ARRAYISH_PARAMS:
                yield self.finding(
                    ctx, v,
                    f"parameter '{pname}' of '{fn.name}' is marked static "
                    "but carries array data: arrays are unhashable under "
                    "static hashing and force a recompile per value")


_ARRAY_CTOR_BASES = {"np", "numpy", "jnp", "jax.numpy"}
_ARRAY_CTOR_FNS = {"array", "asarray", "zeros", "ones", "empty", "full",
                   "arange", "linspace", "eye", "identity"}


@register
class JitConstantClosureRule(Rule):
    id = "JAX005"
    name = "jit-constant-closure"
    family = "jax-perf"
    description = ("a traced function closing over a module-level concrete "
                   "array constant-folds it into the executable")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        idx = _jit_index(ctx)
        consts: Dict[str, int] = {}
        for node in ctx.tree.body:
            if not (isinstance(node, ast.Assign) and
                    isinstance(node.value, ast.Call)):
                continue
            qn = qualname(node.value.func) or ""
            base, _, attr = qn.rpartition(".")
            if base in _ARRAY_CTOR_BASES and attr in _ARRAY_CTOR_FNS:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        consts[t.id] = node.lineno
        if not consts:
            return
        for fn in idx.jitted_defs:
            locs = _local_names(fn)
            for node in _walk_body(fn, into_nested=True):
                if isinstance(node, ast.Name) and \
                        isinstance(node.ctx, ast.Load) and \
                        node.id in consts and node.id not in locs:
                    yield self.finding(
                        ctx, node,
                        f"traced '{fn.name}' captures module-level array "
                        f"'{node.id}' (built at line {consts[node.id]}): "
                        "it constant-folds into the compiled executable — "
                        "pass it as an argument instead")
