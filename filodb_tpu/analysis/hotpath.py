"""JAX hot-path hygiene pass for ``query/engine``.

Inside a jitted kernel a host sync (``.item()``, ``float(arr)``,
``np.asarray`` on a traced value) either fails under tracing or —
worse — silently forces a device round-trip per call, which is exactly
the per-step transfer cost the paper's batched design exists to avoid.
Python-side ``time``/``random`` calls are traced once at compile time
and frozen into the kernel, an outright correctness bug.

- **HP301 host-sync-in-kernel**: ``.item()``, ``.tolist()``,
  ``.block_until_ready()``, ``np.asarray``/``np.array``/
  ``np.frombuffer``, and ``float()``/``int()``/``bool()`` applied to an
  attribute/subscript expression (plain-``Name`` casts are skipped:
  they are usually static args, and flagging them would drown the pass
  in false positives).
- **HP302 wallclock-in-kernel**: ``time.*``, ``random.*``,
  ``np.random.*`` calls.

A function counts as a kernel when decorated ``@jax.jit`` / ``@jit`` /
``@partial(jax.jit, ...)``, when passed to ``pl.pallas_call``, when
wrapped in call form (``jit(fn)`` / ``jax.jit(fn)`` or
``jax.shard_map(fn, ...)`` — the factory idiom
``parallel/dist_query.py`` builds its SPMD programs with), or when
lexically nested inside a kernel.

The pass covers ``query/engine/`` and ``parallel/`` — the two places
jitted kernels live.
"""

from __future__ import annotations

import ast

from filodb_tpu.analysis.model import Finding
from filodb_tpu.analysis.runner import AnalysisContext

ENGINE_PREFIXES = ("filodb_tpu/query/engine/", "filodb_tpu/parallel/")

_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_NP_SYNC_FUNCS = {"asarray", "array", "frombuffer"}
_CAST_FUNCS = {"float", "int", "bool"}
_CLOCK_MODULES = {"time", "random"}


def _src(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:
        return "<expr>"


def _is_jit_decorator(dec: ast.AST) -> bool:
    # @jax.jit / @jit
    if isinstance(dec, ast.Attribute) and dec.attr == "jit":
        return True
    if isinstance(dec, ast.Name) and dec.id == "jit":
        return True
    # @partial(jax.jit, ...) / @functools.partial(jit, ...) / @jit(...)
    if isinstance(dec, ast.Call):
        fn = dec.func
        fname = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None)
        if fname == "jit":
            return True
        if fname == "partial" and dec.args:
            return _is_jit_decorator(dec.args[0])
    return False


def _pallas_kernel_names(tree: ast.Module) -> set[str]:
    """Function names passed (positionally or as ``kernel=``) to
    ``pl.pallas_call``."""
    names: set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        fname = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None)
        if fname != "pallas_call":
            continue
        cands = list(node.args[:1]) + [kw.value for kw in node.keywords
                                       if kw.arg == "kernel"]
        for c in cands:
            if isinstance(c, ast.Name):
                names.add(c.id)
            elif isinstance(c, ast.Call):  # partial(kernel_fn, ...)
                for a in c.args:
                    if isinstance(a, ast.Name):
                        names.add(a.id)
    return names


def _wrapped_kernel_names(tree: ast.Module) -> set[str]:
    """Function names made kernels by call-form wrapping: the callee of
    ``shard_map(f, ...)`` / ``jax.shard_map(f, ...)`` and call-form
    ``jit(f)`` / ``jax.jit(f)`` (the ``parallel/dist_query.py`` factory
    idiom, which the decorator check cannot see)."""
    names: set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        fname = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None)
        if fname == "shard_map":
            cands = list(node.args[:1]) + [kw.value for kw in node.keywords
                                           if kw.arg == "f"]
        elif fname == "jit":
            cands = list(node.args[:1])
        else:
            continue
        for c in cands:
            if isinstance(c, ast.Name):
                names.add(c.id)
            elif isinstance(c, ast.Call):  # partial(kernel_fn, ...)
                for a in c.args:
                    if isinstance(a, ast.Name):
                        names.add(a.id)
    return names


class _KernelWalker(ast.NodeVisitor):
    def __init__(self, path: str, symbol: str, out: list[Finding]):
        self.path = path
        self.symbol = symbol
        self.out = out

    def _finding(self, code: str, node: ast.AST, detail: str,
                 message: str) -> None:
        self.out.append(Finding(code, self.path, node.lineno,
                                self.symbol, detail, message))

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        if isinstance(fn, ast.Attribute):
            recv = fn.value
            recv_root = recv
            while isinstance(recv_root, ast.Attribute):
                recv_root = recv_root.value
            root_name = recv_root.id if isinstance(recv_root, ast.Name) \
                else None
            if fn.attr in _SYNC_METHODS:
                self._finding(
                    "HP301", node, f"{fn.attr}:{_src(recv)}",
                    f"host sync .{fn.attr}() on {_src(recv)} inside a "
                    f"jitted kernel")
            elif root_name == "np" and fn.attr in _NP_SYNC_FUNCS:
                self._finding(
                    "HP301", node, f"np.{fn.attr}:{_src(node.args[0]) if node.args else ''}",
                    f"np.{fn.attr}(...) materializes on host inside a "
                    f"jitted kernel; use jnp or hoist out of the kernel")
            elif root_name in _CLOCK_MODULES or (
                    root_name == "np" and isinstance(recv, ast.Attribute)
                    and recv.attr == "random"):
                self._finding(
                    "HP302", node, f"{_src(fn)}",
                    f"{_src(fn)}() is traced once at compile time and "
                    f"frozen into the kernel; pass values in as "
                    f"arguments instead")
        elif isinstance(fn, ast.Name) and fn.id in _CAST_FUNCS and \
                node.args and isinstance(node.args[0],
                                         (ast.Attribute, ast.Subscript)):
            self._finding(
                "HP301", node, f"{fn.id}:{_src(node.args[0])}",
                f"{fn.id}({_src(node.args[0])}) forces a host sync "
                f"inside a jitted kernel")
        self.generic_visit(node)

    # nested defs are scanned separately (with their own symbol) by the
    # scope walk in run(); don't double-report them here
    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        pass

    visit_AsyncFunctionDef = visit_FunctionDef


def run(ctx: AnalysisContext) -> list[Finding]:
    out: list[Finding] = []
    for mi in ctx.modules:
        if not mi.path.startswith(ENGINE_PREFIXES):
            continue
        pallas = _pallas_kernel_names(mi.tree) | _wrapped_kernel_names(
            mi.tree)

        def scan(fdef: ast.FunctionDef, symbol: str) -> None:
            w = _KernelWalker(mi.path, symbol, out)
            for stmt in fdef.body:
                w.visit(stmt)

        def visit_scope(body, prefix: str, inside_kernel: bool) -> None:
            for node in body:
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    sym = f"{prefix}{node.name}"
                    is_kernel = (inside_kernel
                                 or node.name in pallas
                                 or any(_is_jit_decorator(d)
                                        for d in node.decorator_list))
                    if is_kernel:
                        scan(node, sym)
                    # nested defs inherit kernel-ness lexically
                    visit_scope(node.body, f"{sym}.", is_kernel)
                elif isinstance(node, ast.ClassDef):
                    visit_scope(node.body, f"{node.name}.",
                                inside_kernel)

        visit_scope(mi.tree.body, "", False)
    return out
