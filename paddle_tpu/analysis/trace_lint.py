"""Trace-hazard linter: AST pass over the jitted hot paths.

Retrace and host-sync hazards are the bug class every review pass of
PR 1-4 hunted by hand: a `float()`/`.item()`/`np.asarray` on a traced
value forces a device sync (or a tracer error) inside a compiled step,
a `time.time()`/`random.*` call bakes one trace-time value into the
compiled artifact forever, and a Python `if` on a tracer-typed argument
either crashes or silently recompiles per branch. This pass finds the
*traced* functions of a module and flags those patterns inside them:

  T001 host-sync-in-trace   float()/int()/bool() on non-literals,
                            .item()/.tolist()/.block_until_ready(),
                            np.asarray/np.array on traced values
  T002 impure-call-in-trace time.*/random.*/np.random.*/os.environ —
                            evaluated once at trace time, frozen into
                            the compiled step
  T003 tracer-branch        `if`/`while` on a parameter of a traced
                            function (static accessors like `.ndim`,
                            `.shape`, `len()`, `isinstance()`,
                            `is None` are exempt — they are shape-level
                            and legitimately branch at trace time)
  T004 unhashable-static-arg jit static_argnums/static_argnames naming
                            a parameter whose default is a mutable
                            (unhashable) literal — every call misses
                            the jit cache
  T005 device-dispatch-in-scheduler  a `jnp.`/`jax.*` call reachable
                            from a host-side scheduler loop — a method
                            annotated `# thread: <domain>` (the fleet's
                            replica/monitor control threads) or any
                            same-class method those reach. A control
                            thread that dispatches device work per
                            step serializes the fleet behind one
                            accelerator queue; device math belongs in
                            the engine's traced bodies (nested traced
                            defs are exempt — they are the fix, not
                            the hazard)

A function is *traced* when it is (a) passed to / decorated with a jit
or lax control-flow marker (`jax.jit`, `jax.vmap`, `jax.pmap`,
`lax.scan`, `lax.while_loop`, `lax.fori_loop`, `lax.cond`,
`lax.map`, `jax.checkpoint`), (b) defined inside a traced function, or
(c) called by name from a traced function in the same module (local
call-graph propagation — `decode_step` is traced because `generate`'s
scan body calls it). Cross-module calls are not resolved; each hot-path
file is linted on its own.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from .diagnostics import Diagnostic, make, rel_path, walk_python_files

__all__ = ["lint_file", "lint_paths", "HOT_PATHS"]

# the jitted hot paths; `--all` lints exactly these. executor.py's jit
# sites wrap functions BUILT in core/lowering.py (cross-module), so the
# lowering module — where the traced step bodies actually live — is a
# hot path in its own right.
HOT_PATHS = [
    "paddle_tpu/models/transformer.py",
    # the parts every serving model family's compiled steps share
    # (the paged write rows, the merged pools' scatters and views, the
    # chunk attention, the expert layer)
    "paddle_tpu/models/parts.py",
    # the fused paged-attention kernels (ISSUE 13): everything in the
    # module body runs at trace time inside the compiled serving steps
    "paddle_tpu/parallel/paged_attention.py",
    "paddle_tpu/serving/engine.py",
    "paddle_tpu/serving/fleet.py",
    # multi-tenant front door + adapter paging (ISSUE 12): host-side
    # admission/residency today, but both sit ON the scheduler hot
    # path next to the compiled steps — linted from day one
    "paddle_tpu/serving/tenancy.py",
    "paddle_tpu/serving/adapters.py",
    # serving integrity (ISSUE 15): the trap/fingerprint/sentinel
    # helpers run inside (or right next to) the compiled serving steps
    "paddle_tpu/serving/integrity.py",
    # durable KV (ISSUE 16): serialization/import/spill run on the
    # admission and retire paths right next to the compiled steps
    "paddle_tpu/serving/kv_store.py",
    # KV/weight quantization (ISSUE 14): the quant/dequant helpers are
    # traced inside the compiled serving steps — a host sync here runs
    # per block per step
    "paddle_tpu/serving/quantization.py",
    # wire front door + load harness (ISSUE 18): pure host-side
    # threading, but the pump/stream paths feed the compiled steps'
    # journal flushes — a stray trace-time construct here would stall
    # every stream, so they're linted with the rest of the hot set
    "paddle_tpu/serving/frontdoor.py",
    "paddle_tpu/serving/loadgen.py",
    "paddle_tpu/fluid/executor.py",
    "paddle_tpu/fluid/core/lowering.py",
    # the training sentinel sits ON the step loop next to the jitted
    # step — registered so any traced helper that grows inside it is
    # linted from day one (today it is pure host control flow)
    "paddle_tpu/distributed/sentinel.py",
]

_TRACE_MARKERS = {
    "jax.jit", "jax.vmap", "jax.pmap", "jax.checkpoint", "jax.remat",
    "jax.lax.scan", "jax.lax.while_loop", "jax.lax.fori_loop",
    "jax.lax.cond", "jax.lax.map", "jax.lax.associative_scan",
}
_JIT_MARKERS = {"jax.jit"}

_HOST_CAST_BUILTINS = {"float", "int", "bool", "complex"}
_HOST_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_HOST_SYNC_CALLS = {"numpy.asarray", "numpy.array", "numpy.copy"}
_IMPURE_PREFIXES = ("time.", "random.", "numpy.random.", "secrets.")
_IMPURE_EXACT = {"os.environ", "os.urandom", "os.getenv"}
_STATIC_ATTRS = {"ndim", "shape", "dtype", "size", "axis_names",
                 "sharding", "weak_type"}
_SAFE_TEST_CALLS = {"len", "isinstance", "issubclass", "getattr",
                    "hasattr", "callable", "type", "jax.numpy.ndim",
                    "numpy.ndim"}


class _Fn(object):
    """One function/lambda scope."""

    def __init__(self, node, qualname: str, parent: Optional["_Fn"]):
        self.node = node
        self.qualname = qualname
        self.parent = parent
        self.children: Dict[str, "_Fn"] = {}  # name -> direct child def
        self.child_list: List["_Fn"] = []
        args = node.args
        self.params: Set[str] = {
            a.arg for a in (args.posonlyargs + args.args + args.kwonlyargs)
        }
        if args.vararg:
            self.params.add(args.vararg.arg)
        if args.kwarg:
            self.params.add(args.kwarg.arg)
        self.arg_order: List[str] = [
            a.arg for a in (args.posonlyargs + args.args)
        ]
        self.defaults = args.defaults  # align to tail of arg_order
        self.kw_defaults: Dict[str, ast.AST] = {
            a.arg: d for a, d in zip(args.kwonlyargs, args.kw_defaults)
            if d is not None
        }


class _ModuleIndex(ast.NodeVisitor):
    """Collect function scopes, the import alias table, and every call
    site paired with the scope it occurs in."""

    def __init__(self, tree):
        self.aliases: Dict[str, str] = {}
        self.module_fns: Dict[str, _Fn] = {}
        self.all_fns: List[_Fn] = []
        self.calls: List[Tuple[ast.Call, Optional[_Fn]]] = []
        self.decorated: List[_Fn] = []
        self._stack: List[Optional[_Fn]] = [None]
        self.visit(tree)

    # imports ----------------------------------------------------------
    def visit_Import(self, node):
        for a in node.names:
            if a.asname:
                self.aliases[a.asname] = a.name
            else:
                self.aliases[a.name.split(".")[0]] = a.name.split(".")[0]

    def visit_ImportFrom(self, node):
        mod = node.module or ""
        for a in node.names:
            self.aliases[a.asname or a.name] = (
                "%s.%s" % (mod, a.name) if mod else a.name
            )

    # scopes -----------------------------------------------------------
    def _enter(self, node, name):
        parent = self._stack[-1]
        qual = name if parent is None else "%s.%s" % (parent.qualname, name)
        fn = _Fn(node, qual, parent)
        if parent is None:
            self.module_fns.setdefault(name, fn)
        else:
            parent.children.setdefault(name, fn)
            parent.child_list.append(fn)
        self.all_fns.append(fn)
        self._stack.append(fn)
        return fn

    def visit_FunctionDef(self, node):
        fn = self._enter(node, node.name)
        if node.decorator_list:
            self.decorated.append(fn)
        self.generic_visit(node)
        self._stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        self._enter(node, "<lambda>")
        self.generic_visit(node)
        self._stack.pop()

    def visit_ClassDef(self, node):
        # class bodies do not create a call-resolution scope for our
        # purposes; methods register under the enclosing scope chain
        # with the class name folded into the qualname
        parent = self._stack[-1]
        shim = _Fn(ast.Lambda(args=ast.arguments(
            posonlyargs=[], args=[], kwonlyargs=[], kw_defaults=[],
            defaults=[]), body=ast.Constant(value=None)),
            node.name if parent is None
            else "%s.%s" % (parent.qualname, node.name), parent)
        shim.is_class = True  # class bodies are NOT enclosing scopes
        self._stack.append(shim)
        self.generic_visit(node)
        self._stack.pop()
        # methods are reachable for marker calls via self.* only, which
        # we do not resolve; jit(_local) INSIDE a method resolves
        # through the shim's scope chain

    def visit_Call(self, node):
        self.calls.append((node, self._stack[-1]))
        self.generic_visit(node)


def _dotted(node, aliases) -> Tuple[Optional[str], bool]:
    """Resolve an expression to a dotted name with import aliases
    expanded. Returns (dotted, base_is_import): base_is_import is True
    only when the leftmost name is a known import alias — checks that
    must not fire on same-named locals require it."""
    parts = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if not isinstance(cur, ast.Name):
        return None, False
    base = cur.id
    known = base in aliases
    parts.append(aliases.get(base, base))
    return ".".join(reversed(parts)), known


def _resolve(name: str, scope: Optional[_Fn], index: _ModuleIndex):
    """Find the function def `name` visible from `scope` under real
    Python scoping: class bodies (shim scopes) are NOT enclosing
    scopes — a bare name inside a method never resolves to a sibling
    method, it skips straight to the outer function/module scope."""
    s = scope
    while s is not None:
        if not getattr(s, "is_class", False) and name in s.children:
            return s.children[name]
        s = s.parent
    return index.module_fns.get(name)


def _marker_name(call_func, aliases) -> Optional[str]:
    dotted, _ = _dotted(call_func, aliases)
    if dotted in _TRACE_MARKERS:
        return dotted
    return None


def _traced_set(index: _ModuleIndex) -> Set[_Fn]:
    traced: Set[_Fn] = set()
    roots: List[_Fn] = []

    def add(fn):
        if fn is not None and fn not in traced:
            traced.add(fn)
            roots.append(fn)

    # (a) marker call sites: jit(f), lax.scan(body, ...), vmap(lambda ..)
    for call, scope in index.calls:
        if _marker_name(call.func, index.aliases) is None:
            continue
        # positional AND keyword forms: lax.while_loop(cond_fun=c,
        # body_fun=b) traces its operands just the same
        operands = list(call.args) + [kw.value for kw in call.keywords]
        for arg in operands:
            if isinstance(arg, ast.Name):
                add(_resolve(arg.id, scope, index))
            elif isinstance(arg, ast.Lambda):
                for fn in index.all_fns:
                    if fn.node is arg:
                        add(fn)

    # (a') decorators: @jax.jit / @partial(jax.jit, ...)
    for fn in index.decorated:
        for dec in fn.node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            dotted, _ = _dotted(target, index.aliases)
            if dotted in _TRACE_MARKERS:
                add(fn)
            elif (isinstance(dec, ast.Call)
                  and dotted in ("functools.partial", "partial")
                  and dec.args
                  and _marker_name(dec.args[0], index.aliases)):
                add(fn)

    # (b) nested defs + (c) local call-graph propagation, to fixpoint.
    # Only fn's OWN body is walked: calls inside nested defs resolve
    # from the nested def's scope when IT is processed — resolving them
    # from here would misattribute same-named outer functions.
    while roots:
        fn = roots.pop()
        for child in fn.child_list:
            add(child)
        for node in _own_nodes(fn):
            if isinstance(node, ast.Call) and isinstance(node.func,
                                                         ast.Name):
                callee = _resolve(node.func.id, fn, index)
                if callee is not None and not _is_marker_alias(
                        node.func.id, index):
                    add(callee)
    return traced


def _is_marker_alias(name, index):
    return index.aliases.get(name, name) in _TRACE_MARKERS


# --- per-function checks ----------------------------------------------

def _own_nodes(fn: _Fn):
    """Walk fn's body, NOT descending into nested function/lambda
    bodies (they are linted as their own traced functions)."""
    stack = list(ast.iter_child_nodes(fn.node))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _parent_map(root):
    parents = {}
    for node in ast.walk(root):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


def _test_param_hazard(test, fn: _Fn, aliases) -> Optional[str]:
    """Name of a traced-fn parameter branched on unsafely, or None."""
    parents = _parent_map(test)
    for node in ast.walk(test):
        if not (isinstance(node, ast.Name) and node.id in fn.params):
            continue
        if _safe_usage(node, parents, aliases):
            continue
        return node.id
    return None


def _is_static_expr(node, aliases) -> bool:
    """True when `node` is shape-level data that is concrete at trace
    time — `x.shape[1]`, `q.ndim`, `len(xs)` — so `int()`/`float()`
    over it is a legitimate idiom, not a host sync."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Attribute):
        return node.attr in _STATIC_ATTRS
    if isinstance(node, ast.Subscript):
        return _is_static_expr(node.value, aliases)
    if isinstance(node, ast.Call):
        dotted, _ = _dotted(node.func, aliases)
        return dotted in _SAFE_TEST_CALLS
    if isinstance(node, ast.BinOp):
        return (_is_static_expr(node.left, aliases)
                and _is_static_expr(node.right, aliases))
    if isinstance(node, ast.UnaryOp):
        return _is_static_expr(node.operand, aliases)
    return False


def _safe_usage(name_node, parents, aliases) -> bool:
    cur = name_node
    while cur in parents:
        parent = parents[cur]
        if isinstance(parent, ast.Attribute) and parent.value is cur:
            if parent.attr in _STATIC_ATTRS:
                return True
        if isinstance(parent, ast.Call):
            dotted, _ = _dotted(parent.func, aliases)
            if dotted in _SAFE_TEST_CALLS:
                return True
        if isinstance(parent, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot))
                   for op in parent.ops):
                return True
        cur = parent
    return False


def _check_traced_fn(fn: _Fn, index: _ModuleIndex, path: str,
                     diags: List[Diagnostic]):
    aliases = index.aliases
    for node in _own_nodes(fn):
        if isinstance(node, ast.Call):
            func = node.func
            if isinstance(func, ast.Name):
                name = func.id
                if (name in _HOST_CAST_BUILTINS
                        and name not in aliases
                        and node.args
                        and not all(_is_static_expr(a, aliases)
                                    for a in node.args)):
                    diags.append(make(
                        "T001", path, node.lineno, fn.qualname, name,
                        "%s() on a traced value forces a host sync "
                        "(or a ConcretizationTypeError)" % name))
            dotted, known = _dotted(func, aliases)
            if isinstance(func, ast.Attribute):
                if (func.attr in _HOST_SYNC_METHODS
                        and (dotted is None or not _is_module_ref(
                            dotted, known))):
                    diags.append(make(
                        "T001", path, node.lineno, fn.qualname,
                        ".%s" % func.attr,
                        ".%s() inside a traced function blocks on the "
                        "device" % func.attr))
            if dotted and known:
                if dotted in _HOST_SYNC_CALLS:
                    diags.append(make(
                        "T001", path, node.lineno, fn.qualname, dotted,
                        "%s materializes a traced value on the host"
                        % dotted))
                elif (dotted in _IMPURE_EXACT
                      or dotted.startswith(_IMPURE_PREFIXES)):
                    diags.append(make(
                        "T002", path, node.lineno, fn.qualname, dotted,
                        "%s evaluates ONCE at trace time; the compiled "
                        "step replays that frozen value" % dotted))
        elif isinstance(node, (ast.If, ast.While)):
            hazard = _test_param_hazard(node.test, fn, aliases)
            if hazard is not None:
                diags.append(make(
                    "T003", path, node.lineno, fn.qualname, hazard,
                    "branching on parameter %r of a traced function: "
                    "a tracer here raises, a python value recompiles "
                    "per branch" % hazard))
        elif isinstance(node, ast.Attribute):
            # os.environ reads (subscript or .get): the inner Attribute
            # node itself reports, exactly once
            dotted, known = _dotted(node, aliases)
            if dotted == "os.environ" and known:
                diags.append(make(
                    "T002", path, node.lineno, fn.qualname, dotted,
                    "%s read inside a traced function is frozen at "
                    "trace time" % dotted))


def _is_module_ref(dotted: str, known: bool) -> bool:
    # `np.copy` style module calls are handled by _HOST_SYNC_CALLS;
    # without this, `time.sleep` would double-report as a method call
    return known and "." in dotted


# --- T004 -------------------------------------------------------------

def _static_arg_sites(index: _ModuleIndex):
    """(jit-call node, target _Fn) pairs for BOTH forms: the call form
    `jax.jit(f, static_argnums=...)` and the decorator form
    `@partial(jax.jit, static_argnames=...)`."""
    for call, scope in index.calls:
        dotted, _ = _dotted(call.func, index.aliases)
        if dotted not in _JIT_MARKERS:
            continue
        if call.args and isinstance(call.args[0], ast.Name):
            target = _resolve(call.args[0].id, scope, index)
            if target is not None:
                yield call, target
    for fn in index.decorated:
        for dec in fn.node.decorator_list:
            if not isinstance(dec, ast.Call):
                continue
            dotted, _ = _dotted(dec.func, index.aliases)
            if dotted in _JIT_MARKERS:
                yield dec, fn
            elif (dotted in ("functools.partial", "partial") and dec.args
                  and _marker_name(dec.args[0], index.aliases)
                  in _JIT_MARKERS):
                yield dec, fn


def _check_static_args(index: _ModuleIndex, path: str,
                       diags: List[Diagnostic]):
    for call, target in _static_arg_sites(index):
        static_params: List[str] = []
        for kw in call.keywords:
            vals = _literal_seq(kw.value)
            if kw.arg == "static_argnums":
                for v in vals:
                    if isinstance(v, int) and 0 <= v < len(
                            target.arg_order):
                        static_params.append(target.arg_order[v])
            elif kw.arg == "static_argnames":
                for v in vals:
                    if isinstance(v, str) and v in target.params:
                        static_params.append(v)
        if not static_params:
            continue
        n_def = len(target.defaults)
        defaulted = dict(zip(target.arg_order[-n_def:], target.defaults)) \
            if n_def else {}
        defaulted.update(target.kw_defaults)  # keyword-only defaults
        for p in static_params:
            d = defaulted.get(p)
            if isinstance(d, (ast.List, ast.Dict, ast.Set)):
                diags.append(make(
                    "T004", path, call.lineno,
                    target.qualname, p,
                    "static arg %r defaults to an unhashable %s — "
                    "every call with the default misses the jit cache "
                    "(TypeError at best, retrace storm at worst)"
                    % (p, type(d).__name__.lower())))


def _literal_seq(node) -> list:
    if isinstance(node, ast.Constant):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        return [e.value for e in node.elts if isinstance(e, ast.Constant)]
    return []


# --- T005 -------------------------------------------------------------

# the same annotation lock_lint's thread-domain check learns from:
# `def _loop(self):  # thread: replica`
_THREAD_ANNOT_RE = re.compile(r"#\s*thread\s*:\s*(\w[\w\-]*)")


def _sched_roots(cls_node: ast.ClassDef, src_lines) -> Dict[str, str]:
    """method name -> thread domain, from `# thread:` annotations on
    the def line(s) — the declared host-side scheduler loops."""
    roots: Dict[str, str] = {}
    for item in cls_node.body:
        if not isinstance(item, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
            continue
        body_start = item.body[0].lineno if item.body else item.lineno
        for ln in range(item.lineno, body_start + 1):
            if ln - 1 < len(src_lines):
                m = _THREAD_ANNOT_RE.search(src_lines[ln - 1])
                if m:
                    roots[item.name] = m.group(1)
                    break
    return roots


def _own_stmt_nodes(fn_node):
    """Walk a def body without descending into nested defs/lambdas:
    a nested def on a scheduler path is either a traced body (the
    sanctioned home for device math) or deferred work — neither runs
    on the scheduler thread at this call site."""
    stack = list(ast.iter_child_nodes(fn_node))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _check_sched_dispatch(tree, src: str, index: _ModuleIndex,
                          traced: Set[_Fn], path: str,
                          diags: List[Diagnostic]):
    """T005: `jax.*` (so `jnp.*`) calls reachable from a `# thread:`
    annotated method through the same-class call graph. Traced
    functions are exempt wherever they appear — the check hunts
    dispatch FROM the control thread, not inside compiled steps."""
    src_lines = src.splitlines()
    traced_nodes = {id(fn.node) for fn in traced}
    for cls_node in tree.body:
        if not isinstance(cls_node, ast.ClassDef):
            continue
        roots = _sched_roots(cls_node, src_lines)
        if not roots:
            continue
        methods = {
            item.name: item for item in cls_node.body
            if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        # same-class reachability: self.m() closure from the roots
        calls: Dict[str, Set[str]] = {}
        for name, node in methods.items():
            out: Set[str] = set()
            for sub in _own_stmt_nodes(node):
                if (isinstance(sub, ast.Call)
                        and isinstance(sub.func, ast.Attribute)
                        and isinstance(sub.func.value, ast.Name)
                        and sub.func.value.id == "self"
                        and sub.func.attr in methods):
                    out.add(sub.func.attr)
            calls[name] = out
        via: Dict[str, Tuple[str, str]] = {}  # name -> (root, domain)
        frontier = [(name, name, dom) for name, dom in roots.items()]
        while frontier:
            name, root, dom = frontier.pop()
            if name in via:
                continue
            via[name] = (root, dom)
            for callee in sorted(calls.get(name, ())):
                if callee not in via:
                    frontier.append((callee, root, dom))
        for name in sorted(via):
            node = methods[name]
            if id(node) in traced_nodes:
                continue  # a traced method body is compiled, not host
            root, dom = via[name]
            qual = "%s.%s" % (cls_node.name, name)
            for sub in _own_stmt_nodes(node):
                if not isinstance(sub, ast.Call):
                    continue
                dotted, known = _dotted(sub.func, index.aliases)
                if not (known and dotted
                        and (dotted == "jax"
                             or dotted.startswith("jax."))):
                    continue
                if dotted in _TRACE_MARKERS:
                    # building a compiled step (jax.jit(body)) from
                    # the control thread is the sanctioned pattern —
                    # the hazard is dispatching work, not wrapping it
                    continue
                reach = ("a '# thread: %s' scheduler loop" % dom
                         if name == root else
                         "'%s.%s' (# thread: %s)"
                         % (cls_node.name, root, dom))
                diags.append(make(
                    "T005", path, sub.lineno, qual, dotted,
                    "%s dispatches device work from %s: a control "
                    "thread that calls into jax per step serializes "
                    "the fleet behind one accelerator queue — move it "
                    "into the engine's traced body or precompute on "
                    "the host" % (dotted, reach)))


# --- entry points ------------------------------------------------------

def lint_file(path: str) -> List[Diagnostic]:
    with open(path) as f:
        src = f.read()
    tree = ast.parse(src, filename=path)
    index = _ModuleIndex(tree)
    rel = rel_path(path)
    diags: List[Diagnostic] = []
    traced = _traced_set(index)
    for fn in sorted(traced, key=lambda f: f.node.lineno):
        _check_traced_fn(fn, index, rel, diags)
    _check_static_args(index, rel, diags)
    _check_sched_dispatch(tree, src, index, traced, rel, diags)
    diags.sort(key=lambda d: (d.path, d.line, d.code))
    return diags


def lint_paths(paths=None) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    for f in walk_python_files(paths, HOT_PATHS):
        diags.extend(lint_file(f))
    return diags
