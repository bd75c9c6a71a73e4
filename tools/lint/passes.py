"""The five reprolint passes.  Catalog + rationale in DESIGN_LINT.md.

Every pass is a lexical approximation of a dynamic invariant; each class
docstring states the approximation so a reader knows what a clean run
does and does not prove.
"""

from __future__ import annotations

import ast

from .core import FileContext, Finding, LintPass

__all__ = ["ALL_PASSES", "pass_ids", "CompatSeamPass", "LockDisciplinePass",
           "WireSafetyPass", "TracerHygienePass", "OverflowGuardPass"]


# --------------------------------------------------------------------------
# 1. compat-seam
# --------------------------------------------------------------------------

class CompatSeamPass(LintPass):
    """shard_map spellings only inside ``parallel/compat.py``.

    jax renamed its SPMD surface across the versions this repo supports;
    ``repro.parallel.compat`` is the single translation seam.  This pass
    flags *references* — imports (plain, aliased, ``from``-form),
    resolved attribute chains (``import jax as j; j.shard_map``), and
    ``getattr(jax, "shard_map")`` spellings.  Strings and docstrings are
    never flagged (this is an AST pass, not a grep).
    """

    id = "compat-seam"
    description = "jax.shard_map references outside parallel/compat.py"

    EXEMPT_SUFFIX = "repro/parallel/compat.py"

    @staticmethod
    def _forbidden(dotted: str) -> bool:
        return (dotted == "jax.shard_map"
                or dotted == "jax.experimental.shard_map"
                or dotted.startswith("jax.shard_map.")
                or dotted.startswith("jax.experimental.shard_map."))

    def applies(self, path: str) -> bool:
        return not path.endswith(self.EXEMPT_SUFFIX)

    def run(self, ctx: FileContext) -> list[Finding]:
        findings: list[Finding] = []

        def flag(node: ast.AST, what: str) -> None:
            findings.append(self.finding(
                ctx, node, f"{what} — all shard_map access must go "
                           f"through repro.parallel.compat"))

        class V(ast.NodeVisitor):
            def visit_Import(self, node: ast.Import) -> None:
                for alias in node.names:
                    if CompatSeamPass._forbidden(alias.name):
                        flag(node, f"import of '{alias.name}'")

            def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
                if node.module and node.level == 0:
                    if CompatSeamPass._forbidden(node.module):
                        flag(node, f"import from '{node.module}'")
                        return
                    for alias in node.names:
                        full = f"{node.module}.{alias.name}"
                        if CompatSeamPass._forbidden(full):
                            flag(node, f"import of '{full}'")

            def visit_Attribute(self, node: ast.Attribute) -> None:
                dotted = ctx.dotted(node)
                if dotted and CompatSeamPass._forbidden(dotted):
                    flag(node, f"attribute reference '{dotted}'")
                    return  # don't re-flag the inner chain
                self.generic_visit(node)

            def visit_Call(self, node: ast.Call) -> None:
                if (isinstance(node.func, ast.Name)
                        and node.func.id == "getattr"
                        and len(node.args) >= 2
                        and isinstance(node.args[1], ast.Constant)
                        and isinstance(node.args[1].value, str)
                        and "shard_map" in node.args[1].value):
                    base = ctx.dotted(node.args[0])
                    if base in ("jax", "jax.experimental") or (
                            base and CompatSeamPass._forbidden(base)):
                        flag(node, f"getattr({base}, "
                                   f"{node.args[1].value!r})")
                self.generic_visit(node)

        V().visit(ctx.tree)
        return findings


# --------------------------------------------------------------------------
# 2. lock-discipline
# --------------------------------------------------------------------------

class LockDisciplinePass(LintPass):
    """Guarded-by checker for classes that declare ``_GUARDED_BY``.

    A class opts in with a registry mapping attribute name -> lock
    attribute name (or tuple of acceptable lock names, for a Condition
    sharing its lock):

        _GUARDED_BY = {"_pending": "_lock", "_responses": ("_resp_cv",)}

    Every ``self.<attr>`` read or write of a registered attribute must
    be **lexically** inside ``with self.<lock>:`` for one of its locks,
    or inside ``__init__``.  Lexical containment is the approximation:
    a helper documented as "caller holds the lock" does not pass — take
    the (re-entrant) lock in the helper or suppress with a justification.
    """

    id = "lock-discipline"
    description = "_GUARDED_BY attributes accessed outside their lock"

    INIT_METHODS = {"__init__", "__post_init__", "__new__", "__del__"}

    def applies(self, path: str) -> bool:
        # scoped to the concurrent serving tier — which since the
        # autoscaler includes the runtime health modules (Watchdog
        # beats cross threads) and since the plan store includes the
        # checkpoint package (background writer thread) — plus lint
        # fixtures/tests
        return ("repro/launch/" in path or "repro/core/engine" in path
                or "repro/runtime/" in path or "repro/checkpoint/" in path
                or "test" in path or "fixture" in path)

    @staticmethod
    def _registry(cls: ast.ClassDef) -> dict[str, tuple[str, ...]]:
        reg: dict[str, tuple[str, ...]] = {}
        for stmt in cls.body:
            targets: list[ast.expr] = []
            value: ast.expr | None = None
            if isinstance(stmt, ast.Assign):
                targets, value = stmt.targets, stmt.value
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                targets, value = [stmt.target], stmt.value
            if not any(isinstance(t, ast.Name) and t.id == "_GUARDED_BY"
                       for t in targets):
                continue
            if not isinstance(value, ast.Dict):
                continue
            for k, v in zip(value.keys, value.values):
                if not (isinstance(k, ast.Constant)
                        and isinstance(k.value, str)):
                    continue
                if isinstance(v, ast.Constant) and isinstance(v.value, str):
                    reg[k.value] = (v.value,)
                elif isinstance(v, (ast.Tuple, ast.List)):
                    locks = tuple(e.value for e in v.elts
                                  if isinstance(e, ast.Constant)
                                  and isinstance(e.value, str))
                    if locks:
                        reg[k.value] = locks
        return reg

    def run(self, ctx: FileContext) -> list[Finding]:
        findings: list[Finding] = []
        for cls in [n for n in ast.walk(ctx.tree)
                    if isinstance(n, ast.ClassDef)]:
            reg = self._registry(cls)
            if not reg:
                continue
            for item in cls.body:
                if not isinstance(item, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                if item.name in self.INIT_METHODS:
                    continue
                self._walk_method(ctx, item, reg, findings)
        return findings

    def _walk_method(self, ctx: FileContext, func: ast.AST,
                     reg: dict[str, tuple[str, ...]],
                     findings: list[Finding]) -> None:
        def held_locks(node: ast.With | ast.AsyncWith) -> set[str]:
            out: set[str] = set()
            for it in node.items:
                e = it.context_expr
                # with self._lock:  /  with self._cv:  (bare attribute)
                if (isinstance(e, ast.Attribute)
                        and isinstance(e.value, ast.Name)
                        and e.value.id == "self"):
                    out.add(e.attr)
            return out

        def walk(node: ast.AST, locks: frozenset[str]) -> None:
            if isinstance(node, (ast.With, ast.AsyncWith)):
                inner = locks | held_locks(node)
                for it in node.items:
                    walk(it, locks)
                for child in node.body:
                    walk(child, inner)
                return
            if (isinstance(node, ast.Attribute)
                    and isinstance(node.value, ast.Name)
                    and node.value.id == "self"
                    and node.attr in reg
                    and not (set(reg[node.attr]) & locks)):
                kind = ("write" if isinstance(node.ctx,
                                              (ast.Store, ast.Del))
                        else "read")
                want = " or ".join(f"self.{lk}" for lk in reg[node.attr])
                findings.append(self.finding(
                    ctx, node,
                    f"{kind} of guarded attribute 'self.{node.attr}' "
                    f"outside 'with {want}:'"))
            for child in ast.iter_child_nodes(node):
                walk(child, locks)

        for stmt in ast.iter_child_nodes(func):
            walk(stmt, frozenset())


# --------------------------------------------------------------------------
# 3. wire-safety
# --------------------------------------------------------------------------

class WireSafetyPass(LintPass):
    """Payloads of ``link.send(...)`` / ``send_raw(...)`` must be built
    from the plain-type wire grammar.

    Allowed: literals, f-strings, containers of allowed values,
    conversion builtins (``int``/``float``/``str``/...), registered
    NamedTuple constructors, and trusted producer methods
    (``.snapshot()``, ``.to_wire()``).  Flagged: lambdas, generator
    expressions, numpy/jax-rooted calls or attributes, bare references
    to locally-defined functions, and unvetted call results inline in
    a message (bind to a name first, or register the producer).

    Plain variable references are opaque-allowed — the pass checks how
    a message is *built* at the send site, not dataflow into it.  That
    is exactly the shape of the PR-5 regression it exists to prevent
    (``np.int64`` built inline into a stats dict).

    Registered *descriptor builders* (``shm_descriptor``: the shm
    ring's ``(offset, shape, dtype)`` payload descriptor) are vetted at
    every build site, not just inside sends — their result goes onto
    the wire verbatim, usually bound to a name first, which the
    send-site grammar deliberately treats as opaque.  Their arguments
    must satisfy the same plain grammar.
    """

    id = "wire-safety"
    description = "non-plain values built into wire messages"

    SEND_NAMES = {"send", "send_raw"}
    DESCRIPTOR_BUILDERS = {"shm_descriptor"}
    SAFE_BUILTINS = {"str", "int", "float", "bool", "bytes", "list",
                     "tuple", "dict", "set", "sorted", "len", "repr",
                     "min", "max", "abs", "round", "sum", "format", "ord"}
    SAFE_METHODS = {"to_wire", "snapshot", "tolist", "item", "copy",
                    "decode", "encode", "strip", "format", "get", "items",
                    "keys", "values"}
    REGISTERED_NAMEDTUPLES = {"PlanKey"}
    NUMERIC_MODULE_ROOTS = {"numpy", "jax"}

    def applies(self, path: str) -> bool:
        return ("repro/launch/" in path or "test" in path
                or "fixture" in path)

    def run(self, ctx: FileContext) -> list[Finding]:
        findings: list[Finding] = []
        local_funcs = {n.name for n in ast.walk(ctx.tree)
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))}

        def rooted_numeric(node: ast.AST) -> str | None:
            dotted = ctx.dotted(node)
            if dotted and dotted.split(".", 1)[0] in \
                    self.NUMERIC_MODULE_ROOTS:
                return dotted
            return None

        def check(node: ast.AST) -> None:
            if isinstance(node, ast.Constant) or node is None:
                return
            if isinstance(node, ast.JoinedStr):
                return
            if isinstance(node, ast.Lambda):
                findings.append(self.finding(
                    ctx, node, "lambda in wire message (unpicklable "
                               "closure)"))
                return
            if isinstance(node, ast.GeneratorExp):
                findings.append(self.finding(
                    ctx, node, "generator expression in wire message "
                               "(unpicklable); materialize a list"))
                return
            if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
                for e in node.elts:
                    check(e)
                return
            if isinstance(node, ast.Dict):
                for k in node.keys:
                    if k is not None:
                        check(k)
                for v in node.values:
                    check(v)
                return
            if isinstance(node, ast.Starred):
                check(node.value)
                return
            if isinstance(node, (ast.ListComp, ast.SetComp)):
                check(node.elt)
                return
            if isinstance(node, ast.DictComp):
                check(node.key)
                check(node.value)
                return
            if isinstance(node, ast.IfExp):
                check(node.body)
                check(node.orelse)
                return
            if isinstance(node, ast.BinOp):
                check(node.left)
                check(node.right)
                return
            if isinstance(node, ast.UnaryOp):
                check(node.operand)
                return
            if isinstance(node, ast.BoolOp):
                for v in node.values:
                    check(v)
                return
            if isinstance(node, ast.Compare):
                check(node.left)
                for c in node.comparators:
                    check(c)
                return
            if isinstance(node, ast.Call):
                dotted = rooted_numeric(node.func)
                if dotted:
                    findings.append(self.finding(
                        ctx, node, f"'{dotted}(...)' builds a numpy/jax "
                                   f"object into a wire message; convert "
                                   f"with float()/int()/.tolist() first"))
                    return
                if isinstance(node.func, ast.Name):
                    if node.func.id in self.SAFE_BUILTINS:
                        return  # terminal converter: result is plain
                    if node.func.id in (self.REGISTERED_NAMEDTUPLES
                                        | self.DESCRIPTOR_BUILDERS):
                        for a in node.args:
                            check(a)
                        for kw in node.keywords:
                            check(kw.value)
                        return
                elif isinstance(node.func, ast.Attribute):
                    if node.func.attr in self.SAFE_METHODS:
                        return  # trusted producer
                findings.append(self.finding(
                    ctx, node, "unvetted call result built into a wire "
                               "message; bind it to a variable or add "
                               "the producer to the wire allowlist"))
                return
            if isinstance(node, ast.Name):
                if node.id in local_funcs:
                    findings.append(self.finding(
                        ctx, node, f"function object '{node.id}' in wire "
                                   f"message (unpicklable across "
                                   f"transports)"))
                return  # opaque variable: allowed (see docstring)
            if isinstance(node, ast.Attribute):
                dotted = rooted_numeric(node)
                if dotted:
                    findings.append(self.finding(
                        ctx, node, f"numpy/jax attribute '{dotted}' in "
                                   f"wire message"))
                return  # opaque attribute: allowed
            if isinstance(node, ast.Subscript):
                check(node.value)
                return
            # anything else (await, walrus, ...) is out of grammar scope

        for call in [n for n in ast.walk(ctx.tree)
                     if isinstance(n, ast.Call)]:
            fn = call.func
            name = fn.id if isinstance(fn, ast.Name) else (
                fn.attr if isinstance(fn, ast.Attribute) else None)
            if name in self.DESCRIPTOR_BUILDERS:
                # a descriptor build site is a send site by proxy: the
                # tuple it returns crosses the wire verbatim
                for a in call.args:
                    check(a)
                for kw in call.keywords:
                    check(kw.value)
                continue
            if name not in self.SEND_NAMES:
                continue
            for a in call.args:
                check(a)
            for kw in call.keywords:
                check(kw.value)
        return findings


# --------------------------------------------------------------------------
# 4. tracer-hygiene
# --------------------------------------------------------------------------

class TracerHygienePass(LintPass):
    """No Python control flow or host escapes on traced values.

    Analyzed functions: ``@jax.jit`` / ``@functools.partial(jax.jit,
    ...)`` decorated defs, defs lowered via a ``jax.jit(f, ...)`` call
    form in the same file, and Pallas kernel bodies (first argument of
    ``pl.pallas_call`` — bare name or ``functools.partial(name, ...)``
    with the partial-bound leading params treated as static).

    Tainted = non-static parameters (``static_argnums``/``argnames``
    honored) plus direct ``x = param`` aliases.  Flagged on tainted
    values: ``if``/``while``/``assert`` tests, ``float()``/``int()``/
    ``bool()``, ``.item()``/``.tolist()``, and ``np.*(...)`` calls.
    ``x is None``, ``isinstance``, ``len()``, and ``.shape``/``.ndim``/
    ``.dtype`` uses are trace-time static and exempt.
    """

    id = "tracer-hygiene"
    description = "Python control flow / host escapes on traced values"

    STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "weak_type",
                    "sharding", "itemsize"}
    STATIC_CALLS = {"len", "isinstance", "type", "hasattr", "getattr"}
    HOST_CASTS = {"float", "int", "bool", "complex"}
    HOST_METHODS = {"item", "tolist"}

    def run(self, ctx: FileContext) -> list[Finding]:
        findings: list[Finding] = []
        funcs_by_name: dict[str, list[ast.FunctionDef]] = {}
        for n in ast.walk(ctx.tree):
            if isinstance(n, ast.FunctionDef):
                funcs_by_name.setdefault(n.name, []).append(n)

        analyzed: set[tuple[int, frozenset]] = set()
        targets: list[tuple[ast.FunctionDef, set[str]]] = []

        def is_jit(node: ast.AST) -> bool:
            d = ctx.dotted(node)
            return d in ("jax.jit", "jit") or (
                d is not None and d.endswith(".jit"))

        def static_names(fn: ast.FunctionDef,
                         kwargs: list[ast.keyword]) -> set[str]:
            params = [a.arg for a in
                      fn.args.posonlyargs + fn.args.args]
            statics: set[str] = set()
            for kw in kwargs:
                if kw.arg == "static_argnames":
                    v = kw.value
                    vals = v.elts if isinstance(
                        v, (ast.Tuple, ast.List)) else [v]
                    statics |= {e.value for e in vals
                                if isinstance(e, ast.Constant)
                                and isinstance(e.value, str)}
                elif kw.arg == "static_argnums":
                    v = kw.value
                    vals = v.elts if isinstance(
                        v, (ast.Tuple, ast.List)) else [v]
                    for e in vals:
                        if isinstance(e, ast.Constant) \
                                and isinstance(e.value, int) \
                                and 0 <= e.value < len(params):
                            statics.add(params[e.value])
            return statics

        def add_target(fn: ast.FunctionDef, statics: set[str],
                       n_bound: int = 0) -> None:
            params = [a.arg for a in
                      fn.args.posonlyargs + fn.args.args]
            params = params[n_bound:]
            traced = {p for p in params
                      if p not in statics and p != "self"}
            key = (id(fn), frozenset(traced))
            if traced and key not in analyzed:
                analyzed.add(key)
                targets.append((fn, traced))

        # decorated defs
        for fn in [n for n in ast.walk(ctx.tree)
                   if isinstance(n, ast.FunctionDef)]:
            for dec in fn.decorator_list:
                if is_jit(dec):
                    add_target(fn, set())
                elif isinstance(dec, ast.Call):
                    d = ctx.dotted(dec.func)
                    if d in ("functools.partial", "partial") \
                            and dec.args and is_jit(dec.args[0]):
                        add_target(fn, static_names(fn, dec.keywords))
                    elif is_jit(dec.func):
                        add_target(fn, static_names(fn, dec.keywords))

        # call forms: jax.jit(f, ...) and pl.pallas_call(kernel, ...)
        for call in [n for n in ast.walk(ctx.tree)
                     if isinstance(n, ast.Call)]:
            d = ctx.dotted(call.func)
            if d is None:
                continue
            if is_jit(call.func) and call.args \
                    and isinstance(call.args[0], ast.Name):
                for fn in funcs_by_name.get(call.args[0].id, []):
                    add_target(fn, static_names(fn, call.keywords))
            elif d.endswith("pallas_call") and call.args:
                kern = call.args[0]
                if isinstance(kern, ast.Name):
                    for fn in funcs_by_name.get(kern.id, []):
                        add_target(fn, set())
                elif isinstance(kern, ast.Call):
                    kd = ctx.dotted(kern.func)
                    if kd in ("functools.partial", "partial") \
                            and kern.args \
                            and isinstance(kern.args[0], ast.Name):
                        for fn in funcs_by_name.get(kern.args[0].id, []):
                            add_target(fn, set(),
                                       n_bound=len(kern.args) - 1)

        for fn, traced in targets:
            findings.extend(self._check_body(ctx, fn, traced))
        return findings

    def _tainted_use(self, node: ast.AST, taint: set[str]) -> str | None:
        """Name of a tainted value *used as a value* in ``node``, after
        pruning trace-time-static subexpressions; None if clean."""
        def scan(n: ast.AST) -> str | None:
            if isinstance(n, ast.Attribute) \
                    and n.attr in self.STATIC_ATTRS:
                return None  # x.shape etc: static at trace time
            if isinstance(n, ast.Call):
                d = n.func
                if isinstance(d, ast.Name) \
                        and d.id in self.STATIC_CALLS:
                    return None  # len(x), isinstance(x, ...)
            if isinstance(n, ast.Compare) and all(
                    isinstance(op, (ast.Is, ast.IsNot))
                    for op in n.ops) and all(
                    isinstance(c, ast.Constant) and c.value is None
                    for c in n.comparators):
                return None  # x is None: tracers are never None
            if isinstance(n, ast.Name) and n.id in taint:
                return n.id
            for child in ast.iter_child_nodes(n):
                hit = scan(child)
                if hit:
                    return hit
            return None
        return scan(node)

    def _check_body(self, ctx: FileContext, fn: ast.FunctionDef,
                    traced: set[str]) -> list[Finding]:
        findings: list[Finding] = []

        def walk(node: ast.AST, taint: set[str]) -> None:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not fn:
                # nested def: its params shadow outer traced names
                inner_params = {a.arg for a in
                                node.args.posonlyargs + node.args.args}
                sub = taint - inner_params
                for child in node.body:
                    walk(child, sub)
                return
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id in taint:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        taint.add(t.id)
            if isinstance(node, (ast.If, ast.While)):
                hit = self._tainted_use(node.test, taint)
                if hit:
                    kw = "while" if isinstance(node, ast.While) else "if"
                    findings.append(self.finding(
                        ctx, node.test,
                        f"Python '{kw}' on traced value '{hit}' — use "
                        f"jnp.where / lax.cond, or mark it static"))
            elif isinstance(node, ast.Assert):
                hit = self._tainted_use(node.test, taint)
                if hit:
                    findings.append(self.finding(
                        ctx, node,
                        f"'assert' on traced value '{hit}' — use "
                        f"checkify or a plan-time guard"))
            elif isinstance(node, ast.Call):
                f = node.func
                if isinstance(f, ast.Name) and f.id in self.HOST_CASTS:
                    hit = next((self._tainted_use(a, taint)
                                for a in node.args
                                if self._tainted_use(a, taint)), None)
                    if hit:
                        findings.append(self.finding(
                            ctx, node,
                            f"host cast '{f.id}()' on traced value "
                            f"'{hit}' forces device sync inside jit"))
                elif isinstance(f, ast.Attribute):
                    if f.attr in self.HOST_METHODS \
                            and self._tainted_use(f.value, taint):
                        findings.append(self.finding(
                            ctx, node,
                            f"host escape '.{f.attr}()' on traced value "
                            f"inside jit"))
                    else:
                        d = ctx.dotted(f)
                        if d and d.split(".", 1)[0] == "numpy":
                            hit = next((self._tainted_use(a, taint)
                                        for a in node.args
                                        if self._tainted_use(a, taint)),
                                       None)
                            if hit:
                                findings.append(self.finding(
                                    ctx, node,
                                    f"'{d}(...)' on traced value "
                                    f"'{hit}' — numpy calls escape the "
                                    f"trace; use jnp"))
            for child in ast.iter_child_nodes(node):
                walk(child, taint)

        taint = set(traced)
        for stmt in fn.body:
            walk(stmt, taint)
        return findings


# --------------------------------------------------------------------------
# 5. overflow-guard
# --------------------------------------------------------------------------

class OverflowGuardPass(LintPass):
    """``binom_table`` / ``unrank_lanes`` / ``unrank_window_lanes`` call
    sites must be dominated by a plan-time rank-space guard.

    The Radic walk enumerates C(n, m) minors; the int32 rank arithmetic
    in the kernels silently wraps past 2**31-1, so every table build or
    unranking outside the engine's own plan construction must be
    lexically preceded — in the same or an enclosing scope — by
    ``validate_rank_space(...)`` or ``plan_statics(...)``.  Exempt: the
    guard's home (``core/engine.py``), the table builder itself
    (``core/pascal.py``), and the kernel-helper def site
    (``kernels/common.py``).
    """

    id = "overflow-guard"
    description = "unguarded binom_table / unrank_* call sites"

    TARGETS = {"binom_table", "unrank_lanes", "unrank_window_lanes"}
    GUARDS = {"validate_rank_space", "plan_statics"}
    EXEMPT_SUFFIXES = ("repro/core/engine.py", "repro/core/pascal.py",
                       "repro/kernels/common.py")

    def applies(self, path: str) -> bool:
        return not path.endswith(self.EXEMPT_SUFFIXES)

    @staticmethod
    def _callee_name(call: ast.Call) -> str | None:
        f = call.func
        if isinstance(f, ast.Name):
            return f.id
        if isinstance(f, ast.Attribute):
            return f.attr
        return None

    def run(self, ctx: FileContext) -> list[Finding]:
        findings: list[Finding] = []

        # scope chain per node: module + enclosing function defs
        parents: dict[ast.AST, ast.AST] = {}
        for node in ast.walk(ctx.tree):
            for child in ast.iter_child_nodes(node):
                parents[child] = node

        def scope_chain(node: ast.AST) -> list[ast.AST]:
            chain: list[ast.AST] = []
            cur: ast.AST | None = parents.get(node)
            while cur is not None:
                if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                    ast.Module)):
                    chain.append(cur)
                cur = parents.get(cur)
            return chain

        guard_lines_by_scope: dict[ast.AST, list[int]] = {}
        target_calls: list[ast.Call] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = self._callee_name(node)
            if name in self.GUARDS:
                for scope in scope_chain(node):
                    guard_lines_by_scope.setdefault(scope, []) \
                        .append(node.lineno)
            elif name in self.TARGETS:
                target_calls.append(node)

        for call in target_calls:
            name = self._callee_name(call)
            guarded = any(
                g < call.lineno
                for scope in scope_chain(call)
                for g in guard_lines_by_scope.get(scope, ()))
            if not guarded:
                findings.append(self.finding(
                    ctx, call,
                    f"'{name}(...)' not dominated by "
                    f"validate_rank_space()/plan_statics() — int32 rank "
                    f"arithmetic can overflow unguarded"))
        return findings


ALL_PASSES: list[LintPass] = [
    CompatSeamPass(),
    LockDisciplinePass(),
    WireSafetyPass(),
    TracerHygienePass(),
    OverflowGuardPass(),
]


def pass_ids() -> list[str]:
    return [p.id for p in ALL_PASSES]
