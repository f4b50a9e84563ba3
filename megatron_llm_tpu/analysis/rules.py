"""tpulint rule families R1-R8, tuned to this codebase's idioms.

The module model (``ModuleContext``) understands the repo's jit
conventions before any rule runs:

* decorated jit functions — ``@jax.jit`` /
  ``@functools.partial(jax.jit, static_argnames=..., donate_argnums=...)``;
* module-level wrapper pairs —
  ``_f_donated = functools.partial(jax.jit, ..., donate_argnums=(2, 3))(_f_impl)``
  next to a ``_f_plain`` twin, selected at runtime by backend;
* donor aliases — ``self._decode = (_decode_plain if cpu else
  _decode_donated)`` and local ``fn = (...)`` ternaries, resolved to the
  *donating* branch so call sites through the alias are checked against
  the worst case (the TPU path).

Every rule is a pure function ``ModuleContext -> [Finding]``; known
limitations (linear statement order inside a function, method-call
mutations invisible to lock-discipline) are documented in
docs/analysis.md rather than papered over with guesses.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Set, Tuple

from .core import AnalysisConfig, Finding, Suppressions

Path_ = Tuple[str, ...]


# -- AST helpers ------------------------------------------------------------

def dotted_path(node: ast.AST) -> Optional[Path_]:
    """("self", "slots", "k_pool") for self.slots.k_pool; None for
    anything that isn't a pure Name/Attribute chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return None


def _is_path(node: ast.AST, *paths: Path_) -> bool:
    p = dotted_path(node)
    return p is not None and any(p == q or p[-len(q):] == q for q in paths)


def _const_names(node: ast.AST) -> Set[str]:
    """String constants out of "x" / ("x", "y") / ["x", "y"]."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out: Set[str] = set()
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.add(e.value)
        return out
    return set()


def _const_ints(node: ast.AST) -> Tuple[int, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(e.value for e in node.elts
                     if isinstance(e, ast.Constant)
                     and isinstance(e.value, int))
    return ()


def _fn_params(fn: ast.FunctionDef) -> List[str]:
    a = fn.args
    return ([p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
            + [p.arg for p in a.kwonlyargs])


@dataclasses.dataclass
class JitFn:
    """One jitted callable the module knows about."""

    name: str
    params: List[str]
    static: Set[str]
    donate: Tuple[int, ...]
    node: Optional[ast.FunctionDef]  # the wrapped def, when module-local


def _is_jax_jit(node: ast.AST) -> bool:
    return _is_path(node, ("jax", "jit")) or _is_path(node, ("jit",))


def _is_partial(node: ast.AST) -> bool:
    return (_is_path(node, ("functools", "partial"))
            or _is_path(node, ("partial",)))


def _jit_wrapper_call(node: ast.AST) -> Optional[ast.Call]:
    """The Call that *creates* a jitted callable, if ``node`` is one:
    ``jax.jit(...)`` or ``functools.partial(jax.jit, ...)``."""
    if not isinstance(node, ast.Call):
        return None
    if _is_jax_jit(node.func):
        return node
    if (_is_partial(node.func) and node.args
            and _is_jax_jit(node.args[0])):
        return node
    return None


def _extract_jit_opts(call: ast.Call, params: Sequence[str],
                      ) -> Tuple[Set[str], Tuple[int, ...]]:
    """(static param names, donated positional indices) from the
    keywords of a jax.jit / partial(jax.jit, ...) call."""
    static: Set[str] = set()
    donate: Tuple[int, ...] = ()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            static |= _const_names(kw.value)
        elif kw.arg == "static_argnums":
            static |= {params[i] for i in _const_ints(kw.value)
                       if i < len(params)}
        elif kw.arg == "donate_argnums":
            donate = _const_ints(kw.value)
        elif kw.arg == "donate_argnames":
            names = _const_names(kw.value)
            donate = tuple(i for i, p in enumerate(params) if p in names)
    return static, donate


class ModuleContext:
    """Parsed module + the jit/donor registries the rules share."""

    def __init__(self, path: str, tree: ast.Module, config: AnalysisConfig,
                 suppressions: Suppressions):
        self.path = path
        self.tree = tree
        self.config = config
        self.suppressions = suppressions
        self.parents: Dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(tree):
            for child in ast.iter_child_nodes(parent):
                self.parents[child] = parent
        self.qualnames: Dict[ast.AST, str] = {}
        self._assign_qualnames(tree, "")
        self.module_defs: Dict[str, ast.FunctionDef] = {
            n.name: n for n in tree.body
            if isinstance(n, ast.FunctionDef)}
        self.jit_fns: Dict[str, JitFn] = {}
        self._collect_decorated()
        self._collect_wrappers()
        self.donor_paths: Dict[Path_, JitFn] = {}
        self._collect_donor_aliases()

    # qualified names ("ServingEngine._step") for findings
    def _assign_qualnames(self, node: ast.AST, prefix: str) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                q = f"{prefix}.{child.name}" if prefix else child.name
                self.qualnames[child] = q
                self._assign_qualnames(child, q)
            else:
                self._assign_qualnames(child, prefix)

    def qualname_of(self, node: ast.AST) -> str:
        cur: Optional[ast.AST] = node
        while cur is not None:
            if cur in self.qualnames:
                return self.qualnames[cur]
            cur = self.parents.get(cur)
        return ""

    def _collect_decorated(self) -> None:
        for fn in self.module_defs.values():
            for dec in fn.decorator_list:
                if _is_jax_jit(dec):
                    self.jit_fns[fn.name] = JitFn(
                        fn.name, _fn_params(fn), set(), (), fn)
                    break
                call = _jit_wrapper_call(dec)
                if call is not None:
                    params = _fn_params(fn)
                    static, donate = _extract_jit_opts(call, params)
                    self.jit_fns[fn.name] = JitFn(
                        fn.name, params, static, donate, fn)
                    break

    def _collect_wrappers(self) -> None:
        """``name = functools.partial(jax.jit, ...)(impl)`` and
        ``name = jax.jit(impl, ...)`` at module level."""
        for stmt in self.tree.body:
            if not (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)
                    and isinstance(stmt.value, ast.Call)):
                continue
            name = stmt.targets[0].id
            call = stmt.value
            impl: Optional[ast.expr] = None
            opts_call: Optional[ast.Call] = None
            if (isinstance(call.func, ast.Call)
                    and _jit_wrapper_call(call.func) is not None
                    and len(call.args) == 1):
                impl, opts_call = call.args[0], call.func
            elif _is_jax_jit(call.func) and call.args:
                impl, opts_call = call.args[0], call
            if impl is None or not isinstance(impl, ast.Name):
                continue
            fn = self.module_defs.get(impl.id)
            params = _fn_params(fn) if fn is not None else []
            static, donate = _extract_jit_opts(opts_call, params)
            self.jit_fns[name] = JitFn(name, params, static, donate, fn)

    def resolve_jit(self, expr: ast.AST) -> Optional[JitFn]:
        """A Name/Attribute/IfExp expression -> the JitFn it denotes
        (ternaries resolve to the donating branch — the TPU path)."""
        if isinstance(expr, ast.IfExp):
            a = self.resolve_jit(expr.body)
            b = self.resolve_jit(expr.orelse)
            if a is not None and b is not None:
                return a if a.donate else b
            return a or b
        p = dotted_path(expr)
        if p is None:
            return None
        if len(p) == 1 and p[0] in self.jit_fns:
            return self.jit_fns[p[0]]
        return self.donor_paths.get(p)

    def _collect_donor_aliases(self) -> None:
        """``self._decode = (_plain if ... else _donated)`` style
        attribute aliases, to fixpoint (aliases of aliases)."""
        for _ in range(4):
            changed = False
            for node in ast.walk(self.tree):
                if not (isinstance(node, ast.Assign)
                        and len(node.targets) == 1):
                    continue
                tgt = dotted_path(node.targets[0])
                if tgt is None or len(tgt) < 2:  # only self.X / obj.X
                    continue
                jf = self.resolve_jit(node.value)
                if jf is not None and self.donor_paths.get(tgt) is not jf:
                    self.donor_paths[tgt] = jf
                    changed = True
            if not changed:
                break

    # hot-path scope for the host-sync rule
    def is_hot_function(self, fn: ast.FunctionDef) -> bool:
        if fn.lineno in self.suppressions.hot_path_lines:
            return True
        in_kernels = f"/{self.config.kernel_dir}/" in f"/{self.path}"
        return in_kernels and fn.name.endswith(self.config.kernel_fn_suffix)


def _functions(tree: ast.AST) -> Iterator[ast.FunctionDef]:
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            yield node


def _statements_in_order(body: Sequence[ast.stmt],
                         ) -> Iterator[Tuple[ast.stmt, bool]]:
    """(statement, is_header_only) in source order.  Compound statements
    yield themselves header-only (their test/iter expressions), then
    their nested bodies — a linear approximation of control flow."""
    for stmt in body:
        if isinstance(stmt, (ast.If, ast.While, ast.For, ast.With,
                             ast.Try)):
            yield stmt, True
            for blk in ("body", "orelse", "finalbody"):
                yield from _statements_in_order(getattr(stmt, blk, []) or [])
            for h in getattr(stmt, "handlers", []) or []:
                yield from _statements_in_order(h.body)
        else:
            yield stmt, False


def _header_exprs(stmt: ast.stmt) -> List[ast.expr]:
    if isinstance(stmt, (ast.If, ast.While)):
        return [stmt.test]
    if isinstance(stmt, ast.For):
        return [stmt.iter]
    if isinstance(stmt, ast.With):
        return [i.context_expr for i in stmt.items]
    return []


def _store_paths(stmt: ast.stmt) -> List[Path_]:
    """Paths (re)bound by this statement — kills donation state."""
    targets: List[ast.expr] = []
    if isinstance(stmt, ast.Assign):
        targets = list(stmt.targets)
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        targets = [stmt.target]
    elif isinstance(stmt, ast.For):
        targets = [stmt.target]
    elif isinstance(stmt, ast.With):
        targets = [i.optional_vars for i in stmt.items
                   if i.optional_vars is not None]
    out: List[Path_] = []
    stack = targets[:]
    while stack:
        t = stack.pop()
        if isinstance(t, (ast.Tuple, ast.List)):
            stack.extend(t.elts)
            continue
        if isinstance(t, ast.Subscript):   # self.x[i] = ... writes self.x
            t = t.value
        p = dotted_path(t)
        if p is not None:
            out.append(p)
    return out


# -- R1: recompile hazards --------------------------------------------------

def rule_recompile(ctx: ModuleContext) -> List[Finding]:
    findings: List[Finding] = []
    # (a) jit wrapper construction inside function bodies
    for fn in _functions(ctx.tree):
        for node in ast.walk(fn):
            call = _jit_wrapper_call(node)
            if call is None:
                continue
            parent = ctx.parents.get(node)
            invoked_inline = (isinstance(parent, ast.Call)
                              and parent.func is node)
            in_loop = False
            cur = ctx.parents.get(node)
            while cur is not None and cur is not fn:
                if isinstance(cur, (ast.For, ast.While)):
                    in_loop = True
                    break
                cur = ctx.parents.get(cur)
            if invoked_inline:
                findings.append(Finding(
                    ctx.path, node.lineno, node.col_offset, "recompile",
                    "jax.jit(...) built and invoked inline: every call "
                    "creates a fresh wrapper whose cache is thrown away",
                    ctx.qualname_of(node)))
            elif in_loop:
                findings.append(Finding(
                    ctx.path, node.lineno, node.col_offset, "recompile",
                    "jax.jit wrapper constructed inside a loop: hoist it "
                    "to module level so the compile cache is shared",
                    ctx.qualname_of(node)))
    # (b) unbounded expressions flowing into static arguments
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        jf = ctx.resolve_jit(node.func)
        if jf is None or not jf.static:
            continue
        bound: List[Tuple[str, ast.expr]] = []
        for i, arg in enumerate(node.args):
            if i < len(jf.params):
                bound.append((jf.params[i], arg))
        for kw in node.keywords:
            if kw.arg is not None:
                bound.append((kw.arg, kw.value))
        for pname, expr in bound:
            if pname not in jf.static:
                continue
            if _unbounded_static(expr, ctx.config):
                findings.append(Finding(
                    ctx.path, expr.lineno, expr.col_offset, "recompile",
                    f"static argument '{pname}' of jit'd '{jf.name}' "
                    "derives from a per-request quantity: every distinct "
                    "value compiles a new executable (bucket or pad it)",
                    ctx.qualname_of(node)))
    return findings


def _unbounded_static(expr: ast.expr, config: AnalysisConfig) -> bool:
    """True when a static-arg expression can take unboundedly many
    values: it calls len(), or does arithmetic on request-state
    attributes.  Bounded bools (comparisons, flags) are fine."""
    for node in ast.walk(expr):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "len"):
            return True
        if isinstance(node, ast.BinOp):
            for sub in ast.walk(node):
                if (isinstance(sub, ast.Attribute)
                        and sub.attr in config.request_state_attrs):
                    return True
    return False


# -- R2: host-sync hazards --------------------------------------------------

_SYNC_METHODS = {"item", "block_until_ready"}


def rule_host_sync(ctx: ModuleContext) -> List[Finding]:
    findings: List[Finding] = []
    for fn in _functions(ctx.tree):
        if not ctx.is_hot_function(fn):
            continue
        qual = ctx.qualname_of(fn)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            msg = None
            f = node.func
            if (isinstance(f, ast.Attribute) and f.attr in _SYNC_METHODS
                    and not node.args):
                msg = (f".{f.attr}() blocks on the device inside a "
                       "hot-path function")
            elif _is_path(f, ("jax", "device_get")):
                msg = "jax.device_get syncs inside a hot-path function"
            else:
                p = dotted_path(f)
                if (p is not None and len(p) == 2
                        and p[0] in ctx.config.numpy_names
                        and p[1] in ("asarray", "array")):
                    msg = (f"{p[0]}.{p[1]} on a device array forces a "
                           "host transfer inside a hot-path function")
                elif (isinstance(f, ast.Name)
                        and f.id in ("float", "int", "bool")
                        and len(node.args) == 1
                        and not isinstance(node.args[0], ast.Constant)):
                    msg = (f"{f.id}() on a non-constant inside a hot-path "
                           "function syncs if the value is a device array")
            if msg is not None:
                findings.append(Finding(ctx.path, node.lineno,
                                        node.col_offset, "host-sync", msg,
                                        qual))
    return findings


# -- R3: donation misuse ----------------------------------------------------

def rule_donation(ctx: ModuleContext) -> List[Finding]:
    findings: List[Finding] = []
    for fn in _functions(ctx.tree):
        findings.extend(_check_donation_in(ctx, fn))
    return findings


def _check_donation_in(ctx: ModuleContext, fn: ast.FunctionDef,
                       ) -> List[Finding]:
    findings: List[Finding] = []
    donated: Dict[Path_, str] = {}  # path -> donor fn name
    local_aliases: Dict[Path_, JitFn] = {}

    def resolve(callee: ast.expr) -> Optional[JitFn]:
        p = dotted_path(callee)
        if p is not None and p in local_aliases:
            return local_aliases[p]
        return ctx.resolve_jit(callee)

    def loads_in(nodes: Iterable[ast.AST]) -> List[Tuple[Path_, ast.AST]]:
        out = []
        for root in nodes:
            for sub in ast.walk(root):
                if isinstance(sub, (ast.Name, ast.Attribute)) and \
                        isinstance(getattr(sub, "ctx", None), ast.Load):
                    p = dotted_path(sub)
                    if p is not None:
                        out.append((p, sub))
        return out

    for stmt, header_only in _statements_in_order(fn.body):
        exprs: List[ast.AST] = (_header_exprs(stmt) if header_only
                                else [stmt])
        # 1) reads of already-donated buffers
        if donated:
            reported: Set[Path_] = set()
            for lp, node in loads_in(exprs):
                for dp, donor in donated.items():
                    if lp[:len(dp)] == dp and dp not in reported:
                        reported.add(dp)
                        findings.append(Finding(
                            ctx.path, node.lineno, node.col_offset,
                            "donation",
                            f"'{'.'.join(dp)}' was donated to jit'd "
                            f"'{donor}' (donate_argnums) and read again "
                            "without being rebound — invalid on TPU",
                            ctx.qualname_of(stmt)))
        # 2) new donations from calls in this statement
        for root in exprs:
            for sub in ast.walk(root):
                if not isinstance(sub, ast.Call):
                    continue
                jf = resolve(sub.func)
                if jf is None or not jf.donate:
                    continue
                for idx in jf.donate:
                    arg: Optional[ast.expr] = None
                    if idx < len(sub.args):
                        arg = sub.args[idx]
                    elif idx < len(jf.params):
                        for kw in sub.keywords:
                            if kw.arg == jf.params[idx]:
                                arg = kw.value
                    if arg is None:
                        continue
                    p = dotted_path(arg)
                    if p is not None:
                        donated[p] = jf.name
        # 3) stores kill donations and may create local donor aliases
        if not header_only and isinstance(stmt, ast.Assign) \
                and len(stmt.targets) == 1:
            tgt = dotted_path(stmt.targets[0])
            jf = ctx.resolve_jit(stmt.value)
            if tgt is not None and jf is not None:
                local_aliases[tgt] = jf
        for sp in _store_paths(stmt):
            for dp in list(donated):
                if dp[:len(sp)] == sp:
                    del donated[dp]
    return findings


# -- R4: tracer leaks -------------------------------------------------------

_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "aval", "sharding"}
_STATIC_CALLS = {"len", "isinstance", "getattr", "hasattr", "type"}


def _expr_traced(expr: ast.AST, tainted: Set[str]) -> bool:
    """Does ``expr`` depend on a traced value?  ``.shape``/``.dtype``/
    ``len()`` access is static under tracing and exempt."""
    def visit(node: ast.AST) -> bool:
        if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
            return False
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id in _STATIC_CALLS):
            return False
        if isinstance(node, ast.Name) and node.id in tainted:
            return True
        return any(visit(c) for c in ast.iter_child_nodes(node))
    return visit(expr)


def rule_tracer_leak(ctx: ModuleContext) -> List[Finding]:
    findings: List[Finding] = []
    seen: Set[ast.FunctionDef] = set()
    for jf in ctx.jit_fns.values():
        if jf.node is None or jf.node in seen:
            continue
        seen.add(jf.node)
        traced = set(jf.params) - jf.static - {"cfg", "config"}
        findings.extend(_check_tracer_leak(ctx, jf.node, traced))
    in_kernels = f"/{ctx.config.kernel_dir}/" in f"/{ctx.path}"
    if in_kernels:
        for fn in _functions(ctx.tree):
            if fn in seen or not fn.name.endswith(
                    ctx.config.kernel_fn_suffix):
                continue
            traced = {p for p in _fn_params(fn) if p.endswith("_ref")}
            if fn.args.vararg is not None:
                traced.add(fn.args.vararg.arg)
            findings.extend(_check_tracer_leak(ctx, fn, traced))
    return findings


def _check_tracer_leak(ctx: ModuleContext, fn: ast.FunctionDef,
                       traced: Set[str]) -> List[Finding]:
    findings: List[Finding] = []
    tainted = set(traced)
    qual = ctx.qualname_of(fn)

    def flag(test: ast.expr, what: str) -> None:
        if _expr_traced(test, tainted):
            findings.append(Finding(
                ctx.path, test.lineno, test.col_offset, "tracer-leak",
                f"Python {what} on a traced value inside a jit'd/kernel "
                "function — use jnp.where/lax.cond/pl.when",
                qual))

    for stmt, header_only in _statements_in_order(fn.body):
        if isinstance(stmt, (ast.If, ast.While)):
            flag(stmt.test, "if" if isinstance(stmt, ast.If) else "while")
        if header_only:
            continue
        if isinstance(stmt, ast.Assert):
            flag(stmt.test, "assert")
        for sub in ast.walk(stmt):
            if isinstance(sub, ast.IfExp):
                flag(sub.test, "conditional expression")
            elif isinstance(sub, ast.comprehension):
                for cond in sub.ifs:
                    flag(cond, "comprehension filter")
        # taint propagation through straight-line assignments
        if isinstance(stmt, ast.Assign):
            is_tr = _expr_traced(stmt.value, tainted)
            for sp in _store_paths(stmt):
                if len(sp) == 1:
                    (tainted.add if is_tr else tainted.discard)(sp[0])
    return findings


# -- R5: lock discipline ----------------------------------------------------

_LOCK_FACTORIES = (("threading", "Lock"), ("threading", "RLock"),
                   ("threading", "Condition"), ("make_lock",),
                   ("make_condition",))


def _is_lock_ctor(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and _is_path(node.func,
                                                  *_LOCK_FACTORIES)


def rule_lock_discipline(ctx: ModuleContext) -> List[Finding]:
    findings: List[Finding] = []
    for cls in ast.walk(ctx.tree):
        if isinstance(cls, ast.ClassDef):
            findings.extend(_check_class_locks(ctx, cls))
    return findings


def _check_class_locks(ctx: ModuleContext, cls: ast.ClassDef,
                       ) -> List[Finding]:
    methods = [n for n in cls.body if isinstance(n, ast.FunctionDef)]
    lock_attrs: Set[str] = set()
    for m in methods:
        for node in ast.walk(m):
            if (isinstance(node, ast.Assign) and _is_lock_ctor(node.value)):
                for t in node.targets:
                    p = dotted_path(t)
                    if p is not None and len(p) == 2 and p[0] == "self":
                        lock_attrs.add(p[1])
    if not lock_attrs:
        return []

    def with_lock_depth(node: ast.AST, fn: ast.FunctionDef) -> bool:
        """Is ``node`` lexically inside a ``with self.<lock>:`` in fn?"""
        cur = ctx.parents.get(node)
        while cur is not None and cur is not cls:
            if isinstance(cur, ast.With):
                for item in cur.items:
                    p = dotted_path(item.context_expr)
                    if (p is not None and len(p) == 2 and p[0] == "self"
                            and p[1] in lock_attrs):
                        return True
            cur = ctx.parents.get(cur)
        return False

    # pass 1: attributes written under any of the class's locks
    guarded: Set[str] = set()
    writes: List[Tuple[str, ast.AST, ast.FunctionDef, bool]] = []
    for m in methods:
        for node in ast.walk(m):
            if not isinstance(node, (ast.Assign, ast.AugAssign,
                                     ast.AnnAssign)):
                continue
            for sp in _store_paths(node):
                if len(sp) >= 2 and sp[0] == "self":
                    attr = sp[1]
                    if attr in lock_attrs:
                        continue
                    under = with_lock_depth(node, m)
                    writes.append((attr, node, m, under))
                    if under and m.name != "__init__":
                        guarded.add(attr)
    findings: List[Finding] = []
    for attr, node, m, under in writes:
        if under or m.name == "__init__" or attr not in guarded:
            continue
        findings.append(Finding(
            ctx.path, node.lineno, node.col_offset, "lock-discipline",
            f"'self.{attr}' is written under a {cls.name} lock elsewhere "
            f"but written here without holding it",
            ctx.qualname_of(node)))
    return findings


# -- R6: whole-tensor dequantization on the hot path ------------------------

_DEQUANT_FNS = ("dequantize_weight", "dequantize_cache")


def rule_dequant_hot_path(ctx: ModuleContext) -> List[Finding]:
    """The quantized-residency bytes win exists only while the packed
    form is what streams from HBM: ``ops/quant.py:mm`` dequantizes
    into the matmul (XLA fuses the convert and the scale into the dot's
    read) and the paged attention kernel dequantizes int8 KV *tiles* at
    the tile load (kernels/flash_decode.py), never the whole tensor.  A
    ``dequantize_weight`` / ``dequantize_cache`` call in a kernels/
    file or a ``tpulint: hot-path`` function re-materializes the full
    fp tensor every step — the exact traffic quantization was bought
    to eliminate.  Cold paths (checkpoint export, tests, debugging)
    are exempt."""
    findings: List[Finding] = []
    in_kernels = f"/{ctx.config.kernel_dir}/" in f"/{ctx.path}"
    seen: Set[Tuple[int, int]] = set()
    for fn in _functions(ctx.tree):
        if not (in_kernels or ctx.is_hot_function(fn)):
            continue
        qual = ctx.qualname_of(fn)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            p = dotted_path(node.func)
            if (p is None or p[-1] not in _DEQUANT_FNS
                    or (node.lineno, node.col_offset) in seen):
                continue
            seen.add((node.lineno, node.col_offset))
            where = ("a kernels/ file" if in_kernels
                     else "a hot-path function")
            findings.append(Finding(
                ctx.path, node.lineno, node.col_offset,
                "dequant-hot-path",
                f"{p[-1]} materializes the full-precision tensor inside "
                f"{where} — dequantize per tile in the kernel instead",
                qual))
    return findings


# -- R7: data-dependent operand shapes into jitted calls --------------------

_SHAPE_CTORS = {"zeros", "ones", "empty", "full"}


def _shape_expr_dynamic(expr: ast.expr, config: AnalysisConfig) -> bool:
    """True when a shape expression varies per iteration: it calls
    ``len()`` or reads request/slot state.  Config/module constants
    (``S``, ``self.config.max_batch_size``) are bounded and fine."""
    for node in ast.walk(expr):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "len"):
            return True
        if (isinstance(node, ast.Attribute)
                and node.attr in config.request_state_attrs):
            return True
    return False


def _dyn_shape_ctor(node: ast.AST, config: AnalysisConfig,
                    ) -> Optional[ast.expr]:
    """The offending shape expression, if ``node`` constructs an array
    whose SHAPE is data-dependent: ``np.zeros((len(plans), W))`` etc."""
    if not isinstance(node, ast.Call):
        return None
    p = dotted_path(node.func)
    if (p is None or p[-1] not in _SHAPE_CTORS
            or p[0] not in config.numpy_names + ("jnp", "jax")):
        return None
    shape: Optional[ast.expr] = node.args[0] if node.args else None
    for kw in node.keywords:
        if kw.arg == "shape":
            shape = kw.value
    if shape is not None and _shape_expr_dynamic(shape, config):
        return shape
    return None


def rule_dynamic_operand_shape(ctx: ModuleContext) -> List[Finding]:
    """Per-iteration operands handed to a jitted callable must have
    FIXED shapes — the candidate-tree topology operands (depths,
    ancestor tables, windows) are the canonical case: pack them at
    fixed arity (pad to the node budget, mask in-kernel,
    serving/engine.py:_spec_step_tree) rather than sizing them by
    ``len(chains)`` or per-request node counts, because every distinct
    operand shape compiles a fresh executable and the compile storm
    lands mid-decode."""
    findings: List[Finding] = []
    for fn in _functions(ctx.tree):
        dyn: Dict[str, ast.Call] = {}
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)):
                continue
            if _dyn_shape_ctor(node.value, ctx.config) is None:
                continue
            for t in node.targets:
                p = dotted_path(t)
                if p is not None and len(p) == 1:
                    dyn[p[0]] = node.value
        seen: Set[Tuple[int, int]] = set()

        def flag(ctor: ast.Call, jf_name: str, at: ast.AST) -> None:
            key = (ctor.lineno, ctor.col_offset)
            if key in seen:
                return
            seen.add(key)
            findings.append(Finding(
                ctx.path, ctor.lineno, ctor.col_offset, "dyn-shape",
                f"operand of jit'd '{jf_name}' is built with a data-"
                "dependent shape (len()/per-request state in the shape "
                "tuple): every distinct shape compiles a new executable "
                "— pack it at fixed arity (pad to the budget, mask "
                "in-kernel)", ctx.qualname_of(at)))

        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            jf = ctx.resolve_jit(node.func)
            if jf is None:
                continue
            operands: List[Tuple[str, ast.expr]] = []
            for i, arg in enumerate(node.args):
                pname = jf.params[i] if i < len(jf.params) else ""
                operands.append((pname, arg))
            for kw in node.keywords:
                if kw.arg is not None:
                    operands.append((kw.arg, kw.value))
            for pname, arg in operands:
                if pname in jf.static:
                    continue
                for sub in ast.walk(arg):
                    if (isinstance(sub, ast.Name)
                            and isinstance(getattr(sub, "ctx", None),
                                           ast.Load)
                            and sub.id in dyn):
                        flag(dyn[sub.id], jf.name, node)
                    else:
                        ctor_shape = _dyn_shape_ctor(sub, ctx.config)
                        if ctor_shape is not None:
                            flag(sub, jf.name, node)
    return findings


# -- R8: per-request adapter-factor materialization in hot paths -------------

_ADAPTER_FNS = ("merge_adapter", "install_adapter")


def rule_adapter_materialize(ctx: ModuleContext) -> List[Finding]:
    """The multi-tenant LoRA bytes math works only while adapter
    factors are *resident*: ``AdapterRegistry.acquire`` installs them
    into the device slot arena once per cache miss (at admission) and
    the decode epilogue indexes the arena by slot id — O(rank · hidden)
    extra reads, zero per-request uploads.  Re-materializing factor
    tensors inside a kernels/ file or a ``tpulint: hot-path`` function
    — reading an adapter's host-side ``.factors`` tree, re-running
    ``install_adapter``, or ``merge_adapter``-folding ΔW into the base
    — re-uploads per-request tensors every step (and, for merge, clones
    the full weight tree per tenant).  Cold paths (admission, training,
    checkpoint export) are exempt."""
    findings: List[Finding] = []
    in_kernels = f"/{ctx.config.kernel_dir}/" in f"/{ctx.path}"
    seen: Set[Tuple[int, int]] = set()
    for fn in _functions(ctx.tree):
        if not (in_kernels or ctx.is_hot_function(fn)):
            continue
        qual = ctx.qualname_of(fn)
        where = "a kernels/ file" if in_kernels else "a hot-path function"
        for node in ast.walk(fn):
            msg = None
            if isinstance(node, ast.Call):
                p = dotted_path(node.func)
                if p is not None and p[-1] in _ADAPTER_FNS:
                    what = ("folds ΔW into a fresh copy of the base "
                            "weights" if p[-1] == "merge_adapter"
                            else "re-uploads the factor tensors")
                    msg = (f"{p[-1]} {what} on every call inside {where} "
                           "— install once at admission "
                           "(AdapterRegistry.acquire) and index the "
                           "resident arena by slot id instead")
            elif (isinstance(node, ast.Attribute)
                    and node.attr == "factors"
                    and isinstance(node.ctx, ast.Load)):
                msg = (f".factors reads the host-side per-adapter factor "
                       f"tree inside {where} — serve the delta from the "
                       "resident slot arena (lora_arenas + slot ids), "
                       "never per-request host tensors")
            if msg is None or (node.lineno, node.col_offset) in seen:
                continue
            seen.add((node.lineno, node.col_offset))
            findings.append(Finding(
                ctx.path, node.lineno, node.col_offset,
                "adapter-materialize", msg, qual))
    return findings


ALL_RULES = (rule_recompile, rule_host_sync, rule_donation,
             rule_tracer_leak, rule_lock_discipline,
             rule_dequant_hot_path, rule_dynamic_operand_shape,
             rule_adapter_materialize)


def run_all(ctx: ModuleContext) -> List[Finding]:
    findings: List[Finding] = []
    for rule in ALL_RULES:
        findings.extend(rule(ctx))
    return findings
