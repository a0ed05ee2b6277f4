"""quakecheck engine: registry pre-pass + the five rule families.

The checker is two passes over plain ``ast``:

  1. **Registry pass** over every linted file: collect jitted functions
     (decorated ``@jax.jit`` / ``functools.partial(jax.jit, ...)`` or
     module-level ``name = jax.jit(...)`` aliases) with their static and
     donated arguments — QK101 auto-registers them as device-resident,
     QK102 checks their call sites' static args, QK104 checks their call
     sites' donated operands.
  2. **Rule pass** per file: a lightweight forward taint analysis inside
     device-resident functions (QK101), structural checks for jit-cache
     discipline (QK102), the Pallas kernel contract (QK103),
     donation-after-use (QK104) and serving shared-state mutation
     (QK105).

No third-party dependencies: the linter must run in CI before anything
else is importable.
"""
from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from . import config
from .pragmas import FilePragmas, parse_pragmas

RULES = {
    "QK100": "malformed pragma (allow-sync/holds require an argument)",
    "QK101": "host sync in device path",
    "QK102": "jit cache fragmentation",
    "QK103": "Pallas kernel contract",
    "QK104": "donation after use",
    "QK105": "serving shared state mutated outside write barrier",
    "QK201": "guarded field accessed without its declared lock held",
    "QK202": "lock acquired against the declared lock order",
    "QK203": "blocking call while holding an admission lock",
    "QK204": "guarded mutable state escapes its lock scope",
    "QK301": "swallowed exception in runtime path",
    "QK302": "durability write without fsync / atomic-rename discipline",
    "QK401": "wall-clock read or print() in core runtime path",
}


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    col: int
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"


# ---------------------------------------------------------------------------
# small AST helpers
# ---------------------------------------------------------------------------

def dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def leaf_name(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def root_name(node: ast.AST) -> Optional[str]:
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def const_int_tuple(node: ast.AST) -> Optional[Tuple[int, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.append(e.value)
            else:
                return None
        return tuple(out)
    return None


def const_str_tuple(node: ast.AST) -> Optional[Tuple[str, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.append(e.value)
            else:
                return None
        return tuple(out)
    return None


def _is_jax_jit(node: ast.AST) -> bool:
    d = dotted(node)
    return d in ("jax.jit", "jit", "pjit", "jax.pjit")


# ---------------------------------------------------------------------------
# registry pass
# ---------------------------------------------------------------------------

@dataclass
class JitInfo:
    name: str
    path: str
    line: int
    params: Tuple[str, ...] = ()
    static_names: Set[str] = field(default_factory=set)
    static_nums: Set[int] = field(default_factory=set)
    donate_nums: Set[int] = field(default_factory=set)
    donate_names: Set[str] = field(default_factory=set)
    donate_unknown: bool = False   # dynamic donate expr — skip QK104

    def static_params(self) -> Set[str]:
        out = set(self.static_names)
        for i in self.static_nums:
            if i < len(self.params):
                out.add(self.params[i])
        return out

    def donated_positions(self) -> Set[int]:
        out = set(self.donate_nums)
        for n in self.donate_names:
            if n in self.params:
                out.add(self.params.index(n))
        return out


def _jit_kwargs(call: ast.Call, info: JitInfo) -> None:
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            names = const_str_tuple(kw.value)
            if names:
                info.static_names |= set(names)
        elif kw.arg == "static_argnums":
            nums = const_int_tuple(kw.value)
            if nums:
                info.static_nums |= set(nums)
        elif kw.arg == "donate_argnums":
            nums = const_int_tuple(kw.value)
            if nums is not None:
                info.donate_nums |= set(nums)
            else:
                info.donate_unknown = True
        elif kw.arg == "donate_argnames":
            names = const_str_tuple(kw.value)
            if names is not None:
                info.donate_names |= set(names)
            else:
                info.donate_unknown = True


def _fn_params(fn) -> Tuple[str, ...]:
    a = fn.args
    return tuple(p.arg for p in
                 list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs))


def collect_registry(trees: Dict[str, ast.AST]) -> Dict[str, JitInfo]:
    """name -> JitInfo over all linted files (bare-name matching: the
    stack imports these under their def names)."""
    reg: Dict[str, JitInfo] = {}
    for path, tree in trees.items():
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    info = None
                    if _is_jax_jit(dec):
                        info = JitInfo(node.name, path, node.lineno,
                                       _fn_params(node))
                    elif (isinstance(dec, ast.Call)
                          and leaf_name(dec.func) == "partial"
                          and dec.args and _is_jax_jit(dec.args[0])):
                        info = JitInfo(node.name, path, node.lineno,
                                       _fn_params(node))
                        _jit_kwargs(dec, info)
                    elif isinstance(dec, ast.Call) and _is_jax_jit(dec.func):
                        info = JitInfo(node.name, path, node.lineno,
                                       _fn_params(node))
                        _jit_kwargs(dec, info)
                    if info is not None:
                        reg[info.name] = info
            elif isinstance(node, ast.Assign) and len(node.targets) == 1:
                tgt = node.targets[0]
                if (isinstance(tgt, ast.Name)
                        and isinstance(node.value, ast.Call)
                        and _is_jax_jit(node.value.func)):
                    call = node.value
                    params: Tuple[str, ...] = ()
                    if call.args and isinstance(call.args[0], ast.Lambda):
                        params = tuple(
                            p.arg for p in call.args[0].args.args)
                    elif call.args:
                        inner = leaf_name(call.args[0])
                        if inner and inner in reg:
                            params = reg[inner].params
                    info = JitInfo(tgt.id, path, node.lineno, params)
                    _jit_kwargs(call, info)
                    reg[info.name] = info
    return reg


# ---------------------------------------------------------------------------
# QK101 — host sync in device path (forward taint pass)
# ---------------------------------------------------------------------------

class _Taint:
    """Forward may-be-on-device taint over one function body."""

    def __init__(self, fn, path: str, pragmas: FilePragmas,
                 findings: List[Finding], mode: str,
                 initial: Iterable[str] = ()):
        self.fn = fn
        self.path = path
        self.pragmas = pragmas
        self.findings = findings
        self.mode = mode              # "host" (registered) | "jit"
        self.tainted: Set[str] = set(initial)

    # -- expression taint (also emits findings for sync calls) ----------

    def taint_of(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Call):
            return self._call(node)
        if isinstance(node, (ast.Name, ast.Attribute)):
            d = dotted(node)
            if d is not None:
                if d in self.tainted:
                    return True
                head = d.split(".")[0]
                return head in self.tainted
            return isinstance(node, ast.Attribute) and \
                self.taint_of(node.value)
        if isinstance(node, ast.Subscript):
            return self.taint_of(node.value)
        if isinstance(node, ast.BinOp):
            return self.taint_of(node.left) or self.taint_of(node.right)
        if isinstance(node, ast.BoolOp):
            return any(self.taint_of(v) for v in node.values)
        if isinstance(node, ast.Compare):
            return (self.taint_of(node.left)
                    or any(self.taint_of(c) for c in node.comparators))
        if isinstance(node, ast.UnaryOp):
            return self.taint_of(node.operand)
        if isinstance(node, ast.IfExp):
            return self.taint_of(node.body) or self.taint_of(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.taint_of(e) for e in node.elts)
        if isinstance(node, ast.Starred):
            return self.taint_of(node.value)
        return False

    def _args_tainted(self, call: ast.Call) -> bool:
        return (any(self.taint_of(a) for a in call.args)
                or any(self.taint_of(k.value) for k in call.keywords))

    def _flag(self, node: ast.AST, what: str) -> None:
        line = node.lineno
        if self.pragmas.allows_sync(line) \
                or self.pragmas.disabled(line, "QK101"):
            return
        where = self.fn.name
        self.findings.append(Finding(
            "QK101", self.path, line, node.col_offset,
            f"{what} inside device-resident '{where}' — document with "
            f"'# quakecheck: allow-sync(<reason>)' if intentional"))

    def _call(self, call: ast.Call) -> bool:
        fn_dotted = dotted(call.func) or ""
        fn_leaf = leaf_name(call.func) or ""
        fn_root = fn_dotted.split(".")[0] if fn_dotted else ""

        # recurse args first: nested producing calls taint, nested syncs flag
        arg_taint = self._args_tainted(call)

        # explicit sync entry points
        if fn_dotted in config.HOST_SYNC_CALLS or fn_leaf == "device_get":
            if arg_taint or self.mode == "jit":
                self._flag(call, f"host sync ({fn_dotted or fn_leaf}) on a "
                                 f"device value")
            return False
        if isinstance(call.func, ast.Name) \
                and call.func.id in config.HOST_SYNC_BUILTINS:
            if arg_taint:
                self._flag(call, f"host sync ({call.func.id}() "
                                 f"concretizes a device value)")
            return False
        if isinstance(call.func, ast.Attribute) \
                and call.func.attr in config.HOST_SYNC_METHODS:
            if self.taint_of(call.func.value):
                self._flag(call, f".{call.func.attr}() on a device value")
            return False

        # generic numpy call on a device operand = implicit conversion
        if fn_root in ("np", "numpy") and arg_taint:
            self._flag(call, f"implicit device->host conversion "
                             f"({fn_dotted})")
            return False

        # device-producing calls
        if fn_root in ("jnp", "lax"):
            return True
        if fn_root == "jax" and fn_leaf != "device_get":
            return True
        if fn_leaf in config.DEVICE_PRODUCING_CALLS:
            return True
        # unknown call: propagate operand taint conservatively
        return arg_taint

    # -- statements -----------------------------------------------------

    def _bind(self, target: ast.AST, value_taint: bool) -> None:
        if isinstance(target, ast.Name):
            (self.tainted.add if value_taint
             else self.tainted.discard)(target.id)
        elif isinstance(target, ast.Attribute):
            d = dotted(target)
            if d:
                (self.tainted.add if value_taint
                 else self.tainted.discard)(d)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._bind(e, value_taint)
        elif isinstance(target, ast.Starred):
            self._bind(target.value, value_taint)
        # subscript stores don't rebind the base

    def run(self, body: Sequence[ast.stmt]) -> None:
        for stmt in body:
            self._stmt(stmt)

    def _stmt(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, ast.Assign):
            t = self.taint_of(stmt.value)
            if (isinstance(stmt.value, ast.Tuple)
                    and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], (ast.Tuple, ast.List))
                    and len(stmt.targets[0].elts)
                    == len(stmt.value.elts)):
                for tgt, val in zip(stmt.targets[0].elts,
                                    stmt.value.elts):
                    self._bind(tgt, self.taint_of(val))
            else:
                for tgt in stmt.targets:
                    self._bind(tgt, t)
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            self._bind(stmt.target, self.taint_of(stmt.value))
        elif isinstance(stmt, ast.AugAssign):
            t = self.taint_of(stmt.value) or self.taint_of(stmt.target)
            self._bind(stmt.target, t)
        elif isinstance(stmt, (ast.Expr, ast.Return)):
            if stmt.value is not None:
                self.taint_of(stmt.value)
        elif isinstance(stmt, (ast.For, ast.AsyncFor)):
            self.taint_of(stmt.iter)
            self._bind(stmt.target, self.taint_of(stmt.iter))
            # two passes: taints introduced late in the body reach uses
            # earlier in the next iteration
            self.run(stmt.body)
            self.run(stmt.body)
            self.run(stmt.orelse)
        elif isinstance(stmt, ast.While):
            self.taint_of(stmt.test)
            self.run(stmt.body)
            self.run(stmt.body)
            self.run(stmt.orelse)
        elif isinstance(stmt, ast.If):
            self.taint_of(stmt.test)
            self.run(stmt.body)
            self.run(stmt.orelse)
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                self.taint_of(item.context_expr)
            self.run(stmt.body)
        elif isinstance(stmt, ast.Try):
            self.run(stmt.body)
            for h in stmt.handlers:
                self.run(h.body)
            self.run(stmt.orelse)
            self.run(stmt.finalbody)
        elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # nested defs (e.g. scan_round closures) inherit current taint
            inner = _Taint(stmt, self.path, self.pragmas, self.findings,
                           self.mode, initial=set(self.tainted))
            inner.fn = stmt
            inner.run(stmt.body)
        # other statements carry no taint


def _qualname(fn, class_stack: Tuple[str, ...]) -> str:
    return (".".join(class_stack + (fn.name,))
            if class_stack else fn.name)


def check_qk101(tree: ast.AST, path: str, pragmas: FilePragmas,
                registry: Dict[str, JitInfo],
                findings: List[Finding]) -> None:
    def visit(node, class_stack: Tuple[str, ...]):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                visit(child, class_stack + (child.name,))
            elif isinstance(child, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                qual = _qualname(child, class_stack)
                short = class_stack[-1] + "." + child.name \
                    if class_stack else child.name
                registered = (
                    child.name in config.DEVICE_RESIDENT_FUNCS
                    or qual in config.DEVICE_RESIDENT_FUNCS
                    or short in config.DEVICE_RESIDENT_FUNCS
                    or pragmas.device_path(child.lineno))
                jit = registry.get(child.name)
                jitted = jit is not None and jit.path == path \
                    and jit.line == child.lineno
                if jitted:
                    statics = jit.static_params()
                    initial = [p for p in _fn_params(child)
                               if p not in statics and p != "self"]
                    t = _Taint(child, path, pragmas, findings, "jit",
                               initial)
                    t.run(child.body)
                elif registered:
                    t = _Taint(child, path, pragmas, findings, "host")
                    t.run(child.body)
                else:
                    visit(child, class_stack)   # look for nested defs
            else:
                visit(child, class_stack)

    visit(tree, ())


# ---------------------------------------------------------------------------
# QK102 — jit cache fragmentation
# ---------------------------------------------------------------------------

def _expr_mentions(node: ast.AST, pred) -> bool:
    return any(pred(n) for n in ast.walk(node))


def _is_bucket_hint(node: ast.AST) -> bool:
    name = None
    if isinstance(node, ast.Name):
        name = node.id
    elif isinstance(node, ast.Attribute):
        name = node.attr
    elif isinstance(node, ast.Call):
        name = leaf_name(node.func)
        if name in config.BUCKET_CALLS:
            return True
        name = None
    if name is None:
        return False
    low = name.lower()
    return any(h in low for h in config.BUCKET_HINT_NAMES)


def _is_data_reducer(node: ast.AST) -> bool:
    # Only method/np-style reducers (counts.max(), np.unique(x)) count:
    # builtin min(k, x.shape[0]) is shape math, not data-dependent.
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in config.DATA_DEPENDENT_REDUCERS)


class _AssignIndex(ast.NodeVisitor):
    """name -> last assigned expression, per enclosing function."""

    def __init__(self):
        self.assigns: Dict[str, ast.AST] = {}

    def visit_Assign(self, node: ast.Assign):
        for tgt in node.targets:
            if isinstance(tgt, ast.Name):
                self.assigns[tgt.id] = node.value
        self.generic_visit(node)


def _resolve_props(expr: ast.AST, assigns: Dict[str, ast.AST],
                   depth: int = 0, seen: Optional[Set[str]] = None
                   ) -> Tuple[bool, bool]:
    """(data_dependent, bucketed) for an expression, chasing local
    assignments a few levels deep."""
    seen = seen or set()
    dd = _expr_mentions(expr, _is_data_reducer)
    bk = _expr_mentions(expr, _is_bucket_hint)
    if depth >= 5:
        return dd, bk
    for n in ast.walk(expr):
        if isinstance(n, ast.Name) and n.id in assigns \
                and n.id not in seen:
            seen.add(n.id)
            d2, b2 = _resolve_props(assigns[n.id], assigns,
                                    depth + 1, seen)
            dd = dd or d2
            bk = bk or b2
    return dd, bk


def check_qk102(tree: ast.AST, path: str, pragmas: FilePragmas,
                registry: Dict[str, JitInfo],
                findings: List[Finding]) -> None:
    def flag(node, msg):
        if not pragmas.disabled(node.lineno, "QK102"):
            findings.append(Finding("QK102", path, node.lineno,
                                    node.col_offset, msg))

    # (a) per-call jit construction
    loop_stack: List[ast.AST] = []

    def walk(node, in_loop: bool):
        for child in ast.iter_child_nodes(node):
            child_in_loop = in_loop or isinstance(
                child, (ast.For, ast.While, ast.AsyncFor))
            if isinstance(child, ast.Call):
                if _is_jax_jit(child.func):
                    if in_loop:
                        flag(child, "jax.jit constructed inside a loop — "
                                    "a fresh compile cache every "
                                    "iteration; hoist it out")
                elif isinstance(child.func, ast.Call) \
                        and _is_jax_jit(child.func.func):
                    flag(child, "jax.jit(...)(...) immediately invoked — "
                                "the cache is discarded after one call; "
                                "bind the jitted callable once")
            walk(child, child_in_loop)

    walk(tree, False)

    # (b)+(c) static-argument discipline at call sites of known-jitted fns
    for fn in [n for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]:
        idx = _AssignIndex()
        idx.visit(fn)
        for call in [n for n in ast.walk(fn) if isinstance(n, ast.Call)]:
            name = leaf_name(call.func)
            info = registry.get(name or "")
            if info is None:
                continue
            statics = info.static_params()
            static_exprs: List[Tuple[str, ast.AST]] = []
            for kw in call.keywords:
                if kw.arg in statics:
                    static_exprs.append((kw.arg, kw.value))
            for i, arg in enumerate(call.args):
                if i in info.static_nums or (
                        i < len(info.params)
                        and info.params[i] in statics):
                    static_exprs.append((info.params[i]
                                         if i < len(info.params)
                                         else f"arg{i}", arg))
            for pname, expr in static_exprs:
                if isinstance(expr, (ast.List, ast.Dict, ast.Set)) or (
                        isinstance(expr, ast.Call)
                        and dotted(expr.func) in ("np.array",
                                                  "np.asarray")):
                    flag(expr, f"unhashable static argument "
                               f"'{pname}' to jitted '{name}' — every "
                               f"call re-traces")
                    continue
                dd, bk = _resolve_props(expr, idx.assigns)
                if dd and not bk:
                    flag(expr,
                         f"data-dependent static argument '{pname}' to "
                         f"jitted '{name}' without a padding bucket — "
                         f"every distinct value compiles a new "
                         f"executable; round it through a bucket "
                         f"(u_bucket/_next_pow2/_pad_to)")


# ---------------------------------------------------------------------------
# QK103 — Pallas kernel contract
# ---------------------------------------------------------------------------

def _has_f32_cast(call: ast.Call) -> bool:
    for n in ast.walk(call):
        if isinstance(n, ast.Attribute) and n.attr == "astype":
            return True
        if isinstance(n, ast.Attribute) and n.attr in ("float32",):
            return True
    return False


def check_qk103(tree: ast.AST, path: str, pragmas: FilePragmas,
                findings: List[Finding]) -> None:
    parts = path.replace(os.sep, "/").split("/")
    if config.KERNELS_DIR_FRAGMENT not in parts:
        return
    is_compat = os.path.basename(path) == config.PALLAS_COMPAT_FILE

    def flag(node, msg):
        if not pragmas.disabled(node.lineno, "QK103"):
            findings.append(Finding("QK103", path, node.lineno,
                                    node.col_offset, msg))

    # (a) the Mosaic params / grid API only through pallas_compat
    if not is_compat:
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) \
                    and node.attr in config.PLTPU_COMPAT_ONLY \
                    and root_name(node.value or node) in ("pltpu",):
                flag(node, f"direct pltpu.{node.attr} — dispatch through "
                           f"kernels/pallas_compat.py (the one file "
                           f"that names that API)")
            if isinstance(node, (ast.ImportFrom,)) and node.module \
                    and "pallas" in node.module:
                for alias in node.names:
                    if alias.name in config.PLTPU_COMPAT_ONLY:
                        flag(node, f"importing {alias.name} directly — "
                                   f"use kernels/pallas_compat.py")

    for fn in [n for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]:
        src_calls = [n for n in ast.walk(fn) if isinstance(n, ast.Call)]
        is_launcher = any(
            leaf_name(c.func) in ("pallas_call",
                                  "prefetch_scalar_grid_spec")
            for c in src_calls)
        is_kernel_body = fn.name.endswith("_kernel")
        q8 = "q8" in fn.name or "int8" in fn.name

        # (b) launchers must carry a divisibility / padding guard
        if is_launcher and not is_compat:
            has_guard = False
            for n in ast.walk(fn):
                if isinstance(n, ast.Assert) and any(
                        isinstance(m, ast.Mod)
                        for m in ast.walk(n.test)
                        if isinstance(m, ast.operator) or
                        isinstance(m, ast.Mod)):
                    has_guard = True
                if isinstance(n, ast.Assert):
                    for m in ast.walk(n.test):
                        if isinstance(m, ast.BinOp) \
                                and isinstance(m.op, ast.Mod):
                            has_guard = True
                if isinstance(n, ast.Call) \
                        and leaf_name(n.func) in config.BUCKET_CALLS:
                    has_guard = True
                if isinstance(n, (ast.While, ast.If)):
                    for m in ast.walk(n.test if hasattr(n, "test")
                                      else n):
                        if isinstance(m, ast.BinOp) \
                                and isinstance(m.op, ast.Mod):
                            has_guard = True
            if not has_guard:
                flag(fn, f"'{fn.name}' launches a Pallas kernel without "
                         f"a tile-divisibility guard (assert X % block "
                         f"== 0, or pad via _pad_to/_next_pow2) — "
                         f"non-dividing grids truncate silently")

        # (c) int8 paths accumulate in int32
        if q8:
            for c in src_calls:
                if leaf_name(c.func) in ("dot_general", "dot", "matmul",
                                         "einsum"):
                    pet = None
                    for kw in c.keywords:
                        if kw.arg == "preferred_element_type":
                            pet = leaf_name(kw.value)
                    if pet is None and _has_f32_cast(c):
                        continue    # explicit dequant-to-f32 operand
                    if pet != "int32":
                        flag(c, f"int8 kernel '{fn.name}' runs a dot "
                                f"without preferred_element_type="
                                f"jnp.int32 — int8 accumulation "
                                f"overflows at d>=128")

        # (d) no f64 inside kernel bodies
        if is_kernel_body:
            for n in ast.walk(fn):
                bad = (isinstance(n, ast.Attribute)
                       and n.attr == "float64") or (
                    isinstance(n, ast.Constant)
                    and n.value == "float64")
                if bad:
                    flag(n, f"float64 inside kernel body '{fn.name}' — "
                            f"TPUs have no f64; use f32 accumulation")


# ---------------------------------------------------------------------------
# QK104 — donation after use
# ---------------------------------------------------------------------------

def check_qk104(tree: ast.AST, path: str, pragmas: FilePragmas,
                registry: Dict[str, JitInfo],
                findings: List[Finding]) -> None:
    donators = {n: i for n, i in registry.items()
                if (i.donate_nums or i.donate_names)
                and not i.donate_unknown}
    if not donators:
        return

    for fn in [n for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]:
        # collect (call, donated dotted names, store-lines, load-lines)
        stores: Dict[str, List[int]] = {}
        loads: Dict[str, List[int]] = {}
        calls: List[Tuple[ast.Call, List[str]]] = []
        for node in ast.walk(fn):
            if isinstance(node, (ast.Name, ast.Attribute)):
                d = dotted(node)
                if d is None:
                    continue
                ctx = getattr(node, "ctx", None)
                if isinstance(ctx, ast.Store):
                    stores.setdefault(d, []).append(node.lineno)
                elif isinstance(ctx, ast.Load):
                    loads.setdefault(d, []).append(node.lineno)
            if isinstance(node, ast.Call):
                info = donators.get(leaf_name(node.func) or "")
                if info is None:
                    continue
                donated: List[str] = []
                for pos in info.donated_positions():
                    if pos < len(node.args):
                        d = dotted(node.args[pos])
                        if d:
                            donated.append(d)
                for kw in node.keywords:
                    if kw.arg in info.donate_names:
                        d = dotted(kw.value)
                        if d:
                            donated.append(d)
                if donated:
                    calls.append((node, donated))
        for call, donated in calls:
            if pragmas.disabled(call.lineno, "QK104"):
                continue
            for name in donated:
                # attribute loads of the *donated buffer's fields* count
                use_lines = [ln for d, lns in loads.items()
                             if d == name or d.startswith(name + ".")
                             for ln in lns if ln > call.lineno]
                if not use_lines:
                    continue
                first_use = min(use_lines)
                rebinds = [ln for ln in stores.get(name, ())
                           if call.lineno <= ln <= first_use]
                if not rebinds:
                    findings.append(Finding(
                        "QK104", path, first_use, 0,
                        f"'{name}' donated to jitted "
                        f"'{leaf_name(call.func)}' at line "
                        f"{call.lineno} is read again here — the "
                        f"buffer is invalidated by donation; copy "
                        f"first or drop donate_argnums"))


# ---------------------------------------------------------------------------
# QK105 — serving shared state outside the write barrier
# ---------------------------------------------------------------------------

def _owners_of(attr: str) -> List[str]:
    return [cls for cls, attrs in config.GUARDED_STATE.items()
            if attr in attrs]


def check_qk105(tree: ast.AST, path: str, pragmas: FilePragmas,
                findings: List[Finding]) -> None:
    def flag(node, attr, how):
        if pragmas.disabled(node.lineno, "QK105"):
            return
        owners = " / ".join(_owners_of(attr))
        findings.append(Finding(
            "QK105", path, node.lineno, node.col_offset,
            f"{how} of write-barrier-guarded field '.{attr}' "
            f"(owned by {owners}) outside the owning class — route "
            f"through the owner's API (docs/serving.md write-barrier "
            f"discipline)"))

    def guarded_attr_node(node) -> Optional[ast.Attribute]:
        """The guarded Attribute being mutated, unwrapping subscripts."""
        while isinstance(node, ast.Subscript):
            node = node.value
        if isinstance(node, ast.Attribute) \
                and node.attr in config.GUARDED_ATTRS:
            return node
        return None

    def allowed(attr_node: ast.Attribute,
                class_stack: Tuple[str, ...]) -> bool:
        # A class mutating its own ``self.X`` is the owner's prerogative
        # (the linter cannot infer types; guarded-state violations are
        # cross-object, e.g. ``self.scheduler.done.clear()``).
        base = attr_node.value
        return isinstance(base, ast.Name) and base.id == "self"

    def visit(node, class_stack: Tuple[str, ...]):
        for child in ast.iter_child_nodes(node):
            stack = class_stack
            if isinstance(child, ast.ClassDef):
                stack = class_stack + (child.name,)
            if isinstance(child, (ast.Assign, ast.AugAssign,
                                  ast.AnnAssign, ast.Delete)):
                targets = (child.targets
                           if isinstance(child, (ast.Assign, ast.Delete))
                           else [child.target])
                for tgt in targets:
                    g = guarded_attr_node(tgt)
                    if g is not None and not allowed(g, class_stack):
                        flag(child, g.attr,
                             "augmented write" if isinstance(
                                 child, ast.AugAssign) else "write")
            elif isinstance(child, ast.Call) \
                    and isinstance(child.func, ast.Attribute) \
                    and child.func.attr in config.MUTATING_METHODS:
                g = guarded_attr_node(child.func.value)
                if g is not None and not allowed(g, class_stack):
                    flag(child, g.attr,
                         f"mutating call .{child.func.attr}()")
            visit(child, stack)

    visit(tree, ())


# ---------------------------------------------------------------------------
# QK2xx — lock discipline & happens-before (concurrency rule family)
# ---------------------------------------------------------------------------
#
# Intra-procedural lock-set analysis over the methods of every class that
# owns ``config.GUARDED_BY`` state (the concurrency layer on top of
# QK105's *who-writes* check):
#
#   QK201  access to a guarded ``self.<field>`` while the field's
#          declared lock is not in the lock-set
#   QK202  acquiring a lock while holding one that is *later* in
#          ``config.LOCK_ORDER``
#   QK203  a ``config.BLOCKING_CALLS`` call while an admission lock
#          (``config.ADMISSION_LOCKS``) is held
#   QK204  a guarded mutable field returned raw or stored into another
#          object (the alias outlives the lock scope)
#
# The lock-set is seeded from ``@guarded_by("<lock>")`` decorators and
# def-line ``# quakecheck: holds(<lock>)`` pragmas, grows through
# ``with self._lock:`` blocks and linear ``acquire()``/``release()``
# pairs, and propagates into ``_``-private helpers as the intersection
# of the lock-sets at their intra-class call sites (fixpoint).

_ORDER_INDEX = {name: i for i, name in enumerate(config.LOCK_ORDER)}


def _qualify_lock(name: str, cls: str) -> str:
    return name if "." in name else f"{cls}.{name}"


def _resolve_lock(node: ast.AST, cls: str) -> Optional[str]:
    """Qualified lock name for an acquisition expression, or None.

    ``self._lock`` -> ``<cls>._lock``; ``self.cache._lock`` resolves the
    intermediate attribute through ``config.INSTANCE_ATTRS``.
    """
    if not isinstance(node, ast.Attribute):
        return None
    attr = node.attr
    base = node.value
    if isinstance(base, ast.Name) and base.id == "self":
        return f"{cls}.{attr}"
    if (isinstance(base, ast.Attribute)
            and isinstance(base.value, ast.Name)
            and base.value.id == "self"
            and base.attr in config.INSTANCE_ATTRS):
        return f"{config.INSTANCE_ATTRS[base.attr]}.{attr}"
    return None


def _is_lockish(name: Optional[str]) -> bool:
    return name is not None and ("lock" in name.rsplit(".", 1)[-1].lower())


def _guarded_by_decorator_locks(fn, cls: str) -> Set[str]:
    out: Set[str] = set()
    for dec in fn.decorator_list:
        if (isinstance(dec, ast.Call)
                and leaf_name(dec.func) == "guarded_by"
                and dec.args
                and isinstance(dec.args[0], ast.Constant)
                and isinstance(dec.args[0].value, str)):
            out.add(_qualify_lock(dec.args[0].value, cls))
    return out


def _copy_wrapped(node: ast.AST) -> bool:
    """True when ``node`` is a copy-producing call (``list(...)``,
    ``x.copy()``, ``np.asarray(...)`` ...)."""
    if isinstance(node, ast.Call):
        name = leaf_name(node.func)
        return name in config.COPYING_CALLS
    return False


class _ClassLockAnalysis:
    """QK201-QK204 over one class body."""

    def __init__(self, cls: ast.ClassDef, path: str, pragmas: FilePragmas,
                 findings: List[Finding]):
        self.cls = cls
        self.name = cls.name
        self.path = path
        self.pragmas = pragmas
        self.findings = findings
        self.guarded: Dict[str, str] = {
            f: _qualify_lock(l, self.name)
            for f, l in config.GUARDED_BY.get(self.name, {}).items()}
        self.methods: Dict[str, ast.FunctionDef] = {
            n.name: n for n in cls.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        # helper name -> lock-sets observed at intra-class call sites
        self.callsites: Dict[str, List[frozenset]] = {}
        self.seeds: Dict[str, Set[str]] = {}
        self.emit = False

    # -- seeds ---------------------------------------------------------

    def _explicit_seed(self, fn) -> Set[str]:
        seed = _guarded_by_decorator_locks(fn, self.name)
        seed |= {_qualify_lock(l, self.name)
                 for l in self.pragmas.holds(fn.lineno)}
        return seed

    def run(self) -> None:
        for name, fn in self.methods.items():
            self.seeds[name] = self._explicit_seed(fn)
        # fixpoint: helper seeds grow from call-site intersections; each
        # round re-records call sites under the latest seeds
        for _ in range(10):
            self.callsites = {}
            for fn in self.methods.values():
                self._walk_fn(fn)
            changed = False
            for name, sites in self.callsites.items():
                if name not in self.methods or not name.startswith("_") \
                        or name.startswith("__"):
                    continue
                inter = frozenset.intersection(*sites) if sites \
                    else frozenset()
                new = self._explicit_seed(self.methods[name]) | set(inter)
                if new != self.seeds.get(name):
                    self.seeds[name] = new
                    changed = True
            if not changed:
                break
        self.emit = True
        for fn in self.methods.values():
            self._walk_fn(fn)

    # -- traversal -----------------------------------------------------

    def _walk_fn(self, fn) -> None:
        self._fn = fn
        self._walk_block(fn.body, set(self.seeds.get(fn.name, ())))

    def _held_at(self, line: int, held: Set[str]) -> Set[str]:
        extra = {_qualify_lock(l, self.name)
                 for l in self.pragmas.holds(line)}
        return held | extra

    def _flag(self, rule: str, node: ast.AST, msg: str) -> None:
        if not self.emit:
            return
        if self.pragmas.disabled(node.lineno, rule):
            return
        self.findings.append(Finding(rule, self.path, node.lineno,
                                     node.col_offset, msg))

    def _acquire(self, lock: str, node: ast.AST, held: Set[str]) -> None:
        if lock in held:          # RLock re-entry
            return
        ni = _ORDER_INDEX.get(lock)
        if ni is not None:
            for h in self._held_at(node.lineno, held):
                hi = _ORDER_INDEX.get(h)
                if hi is not None and hi > ni:
                    self._flag(
                        "QK202", node,
                        f"acquiring '{lock}' while holding '{h}' "
                        f"inverts the declared lock order "
                        f"({' -> '.join(config.LOCK_ORDER)}); take "
                        f"'{lock}' first or release '{h}'")

    def _walk_block(self, stmts: Sequence[ast.stmt],
                    held: Set[str]) -> None:
        held = set(held)
        for stmt in stmts:
            # linear acquire()/release() tracking at block level
            if isinstance(stmt, ast.Expr) and isinstance(stmt.value,
                                                         ast.Call):
                call = stmt.value
                if isinstance(call.func, ast.Attribute):
                    lk = _resolve_lock(call.func.value, self.name)
                    if call.func.attr == "acquire" and _is_lockish(lk):
                        self._acquire(lk, stmt, held)
                        self._scan_exprs(stmt, held)
                        held.add(lk)
                        continue
                    if call.func.attr == "release" and _is_lockish(lk):
                        held.discard(lk)
                        continue
            self._stmt(stmt, held)

    def _stmt(self, stmt: ast.stmt, held: Set[str]) -> None:
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            inner = set(held)
            for item in stmt.items:
                lk = _resolve_lock(item.context_expr, self.name)
                if _is_lockish(lk):
                    self._acquire(lk, item.context_expr, inner)
                    inner.add(lk)
                else:
                    self._scan_expr(item.context_expr, held)
            self._walk_block(stmt.body, inner)
        elif isinstance(stmt, ast.If):
            self._scan_expr(stmt.test, held)
            self._walk_block(stmt.body, held)
            self._walk_block(stmt.orelse, held)
        elif isinstance(stmt, ast.While):
            self._scan_expr(stmt.test, held)
            self._walk_block(stmt.body, held)
            self._walk_block(stmt.orelse, held)
        elif isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._scan_expr(stmt.iter, held)
            self._walk_block(stmt.body, held)
            self._walk_block(stmt.orelse, held)
        elif isinstance(stmt, ast.Try):
            self._walk_block(stmt.body, held)
            for h in stmt.handlers:
                self._walk_block(h.body, held)
            self._walk_block(stmt.orelse, held)
            self._walk_block(stmt.finalbody, held)
        elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # nested def (closure): deferred execution — it runs under
            # whatever locks its *caller* holds, so analyze with its own
            # explicit seeds only (annotate with holds()/guarded_by)
            self._walk_block(stmt.body, self._explicit_seed(stmt))
        elif isinstance(stmt, ast.ClassDef):
            pass
        else:
            # simple statement: scan every expression node it contains
            for node in ast.walk(stmt):
                self._scan_node(node, held)
            self._qk204(stmt, held)

    def _scan_expr(self, expr: ast.AST, held: Set[str]) -> None:
        for node in ast.walk(expr):
            self._scan_node(node, held)

    def _scan_node(self, n: ast.AST, held: Set[str]) -> None:
        # QK201 — guarded self.<field> access outside the lock
        if (isinstance(n, ast.Attribute)
                and isinstance(n.value, ast.Name)
                and n.value.id == "self"
                and n.attr in self.guarded
                and self._fn.name not in ("__init__", "__new__")):
            lock = self.guarded[n.attr]
            eff = self._held_at(n.lineno, held)
            if lock not in eff:
                self._flag(
                    "QK201", n,
                    f"'self.{n.attr}' is guarded by '{lock}' "
                    f"(config.GUARDED_BY) but the lock-set here is "
                    f"{sorted(eff) if eff else '{}'} — wrap the access "
                    f"in 'with self.{lock.rsplit('.', 1)[-1]}:' or "
                    f"document the carrier with "
                    f"'# quakecheck: holds({lock})'")
        # QK203 — blocking call under an admission lock; helper call
        # sites recorded for seed propagation
        if isinstance(n, ast.Call):
            cname = leaf_name(n.func)
            if cname in config.BLOCKING_CALLS:
                eff = self._held_at(n.lineno, held)
                adm = eff & config.ADMISSION_LOCKS
                if adm:
                    self._flag(
                        "QK203", n,
                        f"blocking call '{cname}()' while holding "
                        f"admission lock '{sorted(adm)[0]}' — every "
                        f"concurrent submit_* caller stalls behind it; "
                        f"move the blocking work outside the lock "
                        f"(engine-lock scope)")
            if (isinstance(n.func, ast.Attribute)
                    and isinstance(n.func.value, ast.Name)
                    and n.func.value.id == "self"
                    and n.func.attr in self.methods):
                self.callsites.setdefault(n.func.attr, []).append(
                    frozenset(self._held_at(n.lineno, held)))

    def _guarded_mutable_attr(self, node: ast.AST) -> Optional[str]:
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "self"
                and node.attr in self.guarded
                and node.attr not in config.SCALAR_GUARDED):
            return node.attr
        return None

    def _qk204(self, stmt: ast.stmt, held: Set[str]) -> None:
        if self._fn.name in ("__init__", "__new__"):
            return
        if isinstance(stmt, ast.Return) and stmt.value is not None:
            attr = self._guarded_mutable_attr(stmt.value)
            if attr is not None and not _copy_wrapped(stmt.value):
                self._flag(
                    "QK204", stmt,
                    f"returning guarded mutable 'self.{attr}' hands the "
                    f"caller an alias that outlives "
                    f"'{self.guarded[attr]}' — return a copy "
                    f"(list/dict/.copy()) or transfer ownership by "
                    f"rebinding the field first")
        elif isinstance(stmt, ast.Assign):
            attr = self._guarded_mutable_attr(stmt.value)
            if attr is None:
                return
            for tgt in stmt.targets:
                if (isinstance(tgt, ast.Attribute)
                        and not (isinstance(tgt.value, ast.Name)
                                 and tgt.value.id == "self")):
                    self._flag(
                        "QK204", stmt,
                        f"storing guarded mutable 'self.{attr}' into "
                        f"'{dotted(tgt) or 'another object'}' escapes "
                        f"'{self.guarded[attr]}' — store a copy")


def check_qk2xx(tree: ast.AST, path: str, pragmas: FilePragmas,
                findings: List[Finding]) -> None:
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef):
            _ClassLockAnalysis(node, path, pragmas, findings).run()


# ---------------------------------------------------------------------------
# QK301 — swallowed exceptions in runtime paths (docs/serving.md failure
# semantics: every failure is terminal-status-counted, degraded-to, or
# retried — never silently dropped).  Scoped to config.SWALLOW_DIR_FRAGMENT
# paths; an intentional drop carries # quakecheck: allow-swallow(<why>).
# ---------------------------------------------------------------------------

_BROAD_EXC_NAMES = {"Exception", "BaseException"}


def _handler_only_drops(handler: ast.ExceptHandler) -> bool:
    """True when the handler body does nothing but discard the error:
    ``pass`` / ``...`` / ``continue`` statements only."""
    for stmt in handler.body:
        if isinstance(stmt, (ast.Pass, ast.Continue)):
            continue
        if (isinstance(stmt, ast.Expr)
                and isinstance(stmt.value, ast.Constant)
                and stmt.value.value is Ellipsis):
            continue
        return False
    return True


def _handler_reraises(handler: ast.ExceptHandler) -> bool:
    return any(isinstance(n, ast.Raise) for n in ast.walk(handler))


def _broad_exc_caught(type_node: ast.AST) -> bool:
    nodes = (type_node.elts if isinstance(type_node, ast.Tuple)
             else [type_node])
    return any(leaf_name(n) in _BROAD_EXC_NAMES for n in nodes)


def check_qk301(tree: ast.AST, path: str, pragmas: FilePragmas,
                findings: List[Finding]) -> None:
    parts = path.replace(os.sep, "/").split("/")
    if config.SWALLOW_DIR_FRAGMENT not in parts:
        return

    def flag(node, msg):
        if pragmas.disabled(node.lineno, "QK301"):
            return
        if pragmas.allows_swallow(node.lineno):
            return
        findings.append(Finding("QK301", path, node.lineno,
                                node.col_offset, msg))

    for node in ast.walk(tree):
        if not isinstance(node, ast.Try):
            continue
        for h in node.handlers:
            if h.type is None:
                if not _handler_reraises(h):
                    flag(h, "bare 'except:' swallows everything "
                            "(including KeyboardInterrupt) — catch a "
                            "concrete exception, re-raise, or document "
                            "with # quakecheck: allow-swallow(<why>)")
            elif _broad_exc_caught(h.type) and _handler_only_drops(h):
                flag(h, "broad exception handler silently drops the "
                        "error — count it, log it, degrade, or document "
                        "with # quakecheck: allow-swallow(<why>)")


# ---------------------------------------------------------------------------
# QK302 — durability I/O discipline (docs/durability.md).  Scoped to
# config.DURABILITY_PATH_FRAGMENT paths; in scope, a write-mode open()
# must be paired with an fsync in the same function (a write the OS may
# still be buffering is not durable), and manifest/checkpoint files must
# be published via temp + rename, never written in place.  An intentional
# unsynced write carries # quakecheck: allow-nosync(<why>).
# ---------------------------------------------------------------------------

_WRITE_MODE_CHARS = set("wax+")


def _in_durability_path(path: str) -> bool:
    parts = path.replace(os.sep, "/").split("/")
    frag = config.DURABILITY_PATH_FRAGMENT
    return any(p == frag or p.startswith(frag + ".") for p in parts)


def _open_write_mode(call: ast.Call) -> bool:
    """True for ``open(..., mode)`` calls whose mode literal writes."""
    if leaf_name(call.func) != "open":
        return False
    mode: Optional[ast.AST] = None
    if len(call.args) >= 2:
        mode = call.args[1]
    for kw in call.keywords:
        if kw.arg == "mode":
            mode = kw.value
    if not (isinstance(mode, ast.Constant) and isinstance(mode.value, str)):
        return False   # default "r", or dynamic — not provably a write
    return bool(_WRITE_MODE_CHARS & set(mode.value))


def _path_arg_hints_manifest(call: ast.Call) -> bool:
    """True when the path operand of ``open`` contains a string literal
    naming a manifest/checkpoint (config.MANIFEST_HINTS)."""
    target: Optional[ast.AST] = call.args[0] if call.args else None
    for kw in call.keywords:
        if kw.arg == "file":
            target = kw.value
    if target is None:
        return False
    for n in ast.walk(target):
        if isinstance(n, ast.Constant) and isinstance(n.value, str):
            low = n.value.lower()
            if any(h in low for h in config.MANIFEST_HINTS):
                return True
    return False


def _shallow_nodes(func: ast.AST) -> Iterable[ast.AST]:
    """Walk a function body without descending into nested defs/classes —
    the pairing contract is per-function."""
    stack = list(func.body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def check_qk302(tree: ast.AST, path: str, pragmas: FilePragmas,
                findings: List[Finding]) -> None:
    if not _in_durability_path(path):
        return

    for func in ast.walk(tree):
        if not isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        write_opens: List[ast.Call] = []
        has_fsync = False
        has_rename = False
        for node in _shallow_nodes(func):
            if not isinstance(node, ast.Call):
                continue
            if _open_write_mode(node):
                write_opens.append(node)
            name = leaf_name(node.func)
            if name in config.FSYNC_CALLS:
                has_fsync = True
            if name in config.RENAME_CALLS:
                has_rename = True
        for call in write_opens:
            if pragmas.disabled(call.lineno, "QK302"):
                continue
            if not has_fsync and not pragmas.allows_nosync(call.lineno):
                findings.append(Finding(
                    "QK302", path, call.lineno, call.col_offset,
                    f"write-mode open in '{func.name}' with no fsync in "
                    "the same function — an unsynced write is not "
                    "durable: fsync before closing, or document with "
                    "# quakecheck: allow-nosync(<why>)"))
            if _path_arg_hints_manifest(call) and not has_rename:
                findings.append(Finding(
                    "QK302", path, call.lineno, call.col_offset,
                    f"manifest/checkpoint written in place in "
                    f"'{func.name}' — a crash mid-write leaves a torn "
                    "file that validates as the newest state: write to "
                    "a temp name and publish with os.rename/os.replace"))


# ---------------------------------------------------------------------------
# QK401 — wall-clock / stdout discipline (docs/observability.md).  Scoped
# to core runtime paths (a "repro" and a "core" path component): latency
# accounting must come from the injectable monotonic clock so fake-clock
# tests stay deterministic, and the serving hot path reports through the
# metrics registry / trace emitter, never stdout.  Documented exceptions
# carry # quakecheck: allow-wallclock(<why>).
# ---------------------------------------------------------------------------

def check_qk401(tree: ast.AST, path: str, pragmas: FilePragmas,
                findings: List[Finding]) -> None:
    parts = path.replace(os.sep, "/").split("/")
    if (config.SWALLOW_DIR_FRAGMENT not in parts
            or config.RUNTIME_CORE_FRAGMENT not in parts):
        return

    def flag(node, msg):
        if pragmas.disabled(node.lineno, "QK401"):
            return
        if pragmas.allows_wallclock(node.lineno):
            return
        findings.append(Finding("QK401", path, node.lineno,
                                node.col_offset, msg))

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = dotted(node.func)
        if name in config.WALLCLOCK_CALLS or (
                isinstance(node.func, ast.Name) and node.func.id == "time"):
            flag(node, "wall-clock read in a core runtime path — take the "
                       "injectable monotonic clock (the `clock` parameter, "
                       "default time.perf_counter) so fake-clock tests and "
                       "latency accounting stay deterministic, or document "
                       "with # quakecheck: allow-wallclock(<why>)")
        elif (isinstance(node.func, ast.Name)
                and node.func.id in config.STDOUT_CALLS):
            flag(node, "print() in a core runtime path — report through "
                       "the metrics registry / trace emitter "
                       "(docs/observability.md), or document with "
                       "# quakecheck: allow-wallclock(<why>)")


# ---------------------------------------------------------------------------
# QK100 — malformed pragmas
# ---------------------------------------------------------------------------

def check_qk100(path: str, pragmas: FilePragmas,
                findings: List[Finding]) -> None:
    for line, p in pragmas.by_line.items():
        if p.allow_sync and not p.allow_sync_reason.strip():
            findings.append(Finding(
                "QK100", path, line, 0,
                "allow-sync pragma without a reason — intentional syncs "
                "must be documented: # quakecheck: allow-sync(<why>)"))
        if p.allow_swallow and not p.allow_swallow_reason.strip():
            findings.append(Finding(
                "QK100", path, line, 0,
                "allow-swallow pragma without a reason — intentional "
                "swallows must be documented: "
                "# quakecheck: allow-swallow(<why>)"))
        if p.allow_nosync and not p.allow_nosync_reason.strip():
            findings.append(Finding(
                "QK100", path, line, 0,
                "allow-nosync pragma without a reason — intentional "
                "unsynced writes must be documented: "
                "# quakecheck: allow-nosync(<why>)"))
        if p.allow_wallclock and not p.allow_wallclock_reason.strip():
            findings.append(Finding(
                "QK100", path, line, 0,
                "allow-wallclock pragma without a reason — intentional "
                "wall-clock reads must be documented: "
                "# quakecheck: allow-wallclock(<why>)"))
        if p.bad_holds:
            findings.append(Finding(
                "QK100", path, line, 0,
                "holds() pragma names no lock — declare the carrier: "
                "# quakecheck: holds(<lock>)"))


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def lint_source(source: str, path: str,
                registry: Optional[Dict[str, JitInfo]] = None,
                select: Optional[Set[str]] = None) -> List[Finding]:
    tree = ast.parse(source, filename=path)
    pragmas = parse_pragmas(source)
    if registry is None:
        registry = collect_registry({path: tree})
    findings: List[Finding] = []
    check_qk100(path, pragmas, findings)
    check_qk101(tree, path, pragmas, registry, findings)
    check_qk102(tree, path, pragmas, registry, findings)
    check_qk103(tree, path, pragmas, findings)
    check_qk104(tree, path, pragmas, registry, findings)
    check_qk105(tree, path, pragmas, findings)
    check_qk2xx(tree, path, pragmas, findings)
    check_qk301(tree, path, pragmas, findings)
    check_qk302(tree, path, pragmas, findings)
    check_qk401(tree, path, pragmas, findings)
    if select:
        # prefix match: --select QK2 picks the whole QK2xx family
        findings = [f for f in findings
                    if any(f.rule.startswith(s) for s in select)]
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


def iter_py_files(paths: Sequence[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isfile(p) and p.endswith(".py"):
            out.append(p)
        elif os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = [d for d in dirs
                           if d not in ("__pycache__", ".git")]
                out.extend(os.path.join(root, f) for f in sorted(files)
                           if f.endswith(".py"))
    return sorted(set(out))


def lint_paths(paths: Sequence[str],
               select: Optional[Set[str]] = None) -> List[Finding]:
    files = iter_py_files(paths)
    trees: Dict[str, ast.AST] = {}
    sources: Dict[str, str] = {}
    findings: List[Finding] = []
    for f in files:
        try:
            with open(f, "r", encoding="utf-8") as fh:
                src = fh.read()
            trees[f] = ast.parse(src, filename=f)
            sources[f] = src
        except SyntaxError as e:
            findings.append(Finding("QK100", f, e.lineno or 0, 0,
                                    f"syntax error: {e.msg}"))
    registry = collect_registry(trees)
    for f in sorted(trees):
        findings.extend(lint_source(sources[f], f, registry=registry,
                                    select=select))
    # lint_source re-parses; dedupe syntax-error doubles
    return sorted(set(findings), key=lambda x: (x.path, x.line, x.rule))
