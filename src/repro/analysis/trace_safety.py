"""COOPT004 — trace-safety of jitted step functions.

Lineage: two recorded incidents. (1) PR 6's AOT warmup promises ZERO
retraces at serve time (``warmup()`` pre-compiles every bucketed shape);
that guarantee only holds if jitted impls never read state that mutates
between traces — a closed-over mutable ``self`` attribute or a module
global silently bakes its TRACE-TIME value into the cached executable
(the retired ``ops.INTERPRET`` flag was the canonical hazard: it was
flipped by a launcher AFTER import, so a jitted body that read it directly
froze whichever value import-time happened to see). (2) PR 4
replaced the ``jnp.take`` full-pool gather in the MLA decode path with
paged Pallas kernels precisely because a full-pool gather materialises
the ENTIRE KV pool per step — re-introducing one inside ``kernels/``
would quietly undo that PR.

Contracts enforced:

  * A jitted function (``@jax.jit`` / ``@partial(jax.jit, ...)`` decorated,
    or the impl behind ``self.X = jax.jit(self.X_impl, ...)``) must not
    read a module global that is reassigned through ``global X`` anywhere
    in its module, and must not read a ``self`` attribute that is stored
    outside ``__init__`` (mutable engine state like ``self.cache`` must
    flow through the function's arguments instead).
  * No ``jnp.take`` full-pool gathers inside ``kernels/`` modules —
    except ``kernels/ref.py``, the interpret-mode parity oracle whose
    whole point is the naive gather formulation.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from repro.analysis.core import FileCtx, Finding, dotted_name, iter_scopes

CODE = "COOPT004"

_GATHER_FUNCS = {"jnp.take", "jax.numpy.take", "numpy.take"}
_INIT_SCOPES = {"__init__", "__post_init__", "setup"}


def _jit_decorated(fn) -> bool:
    for dec in fn.decorator_list:
        name = dotted_name(dec)
        if name in ("jax.jit", "jit"):
            return True
        if isinstance(dec, ast.Call):
            cname = dotted_name(dec.func)
            if cname in ("jax.jit", "jit"):
                return True
            if cname in ("partial", "functools.partial") and dec.args and \
                    dotted_name(dec.args[0]) in ("jax.jit", "jit"):
                return True
    return False


def _jitted_impl_names(tree: ast.Module) -> Set[str]:
    """Method/function names passed positionally into ``jax.jit(...)``
    (the ``self._prefill_fn = jax.jit(self._prefill_impl, ...)`` idiom)."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and \
                dotted_name(node.func) in ("jax.jit", "jit") and node.args:
            target = dotted_name(node.args[0])
            if target:
                out.add(target.split(".")[-1])
    return out


def _mutable_globals(tree: ast.Module) -> Set[str]:
    """Module globals reassigned via ``global X`` inside some function."""
    out: Set[str] = set()
    for _q, fn, _c in iter_scopes(tree):
        declared: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Global):
                declared.update(node.names)
        if not declared:
            continue
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id in declared:
                        out.add(t.id)
            elif isinstance(node, ast.AugAssign) and \
                    isinstance(node.target, ast.Name) and \
                    node.target.id in declared:
                out.add(node.target.id)
    return out


def _self_attr_stores(fn) -> Set[str]:
    """Attribute names stored on ``self`` inside ``fn`` — plain stores,
    AugAssign, and item-stores (``self.x[...] = ...`` mutates the object
    ``self.x`` refers to, which is just as trace-hostile)."""
    out: Set[str] = set()

    def base_attr(target) -> Optional[str]:
        while isinstance(target, ast.Subscript):
            target = target.value
        if isinstance(target, ast.Attribute) and \
                isinstance(target.value, ast.Name) and \
                target.value.id == "self":
            return target.attr
        return None

    for node in ast.walk(fn):
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            targets = [node.target]
        for t in targets:
            for el in (t.elts if isinstance(t, (ast.Tuple, ast.List))
                       else [t]):
                a = base_attr(el)
                if a:
                    out.add(a)
    return out


def _mutable_attrs_by_class(tree: ast.Module) -> Dict[str, Set[str]]:
    """class name -> attrs stored on ``self`` outside __init__-like
    scopes (these are per-step mutable state, not frozen config)."""
    out: Dict[str, Set[str]] = {}
    for q, fn, cls in iter_scopes(tree):
        if cls is None or q.split(".")[-1] in _INIT_SCOPES:
            continue
        out.setdefault(cls.name, set()).update(_self_attr_stores(fn))
    return out


def _param_names(fn) -> Set[str]:
    a = fn.args
    names = [p.arg for p in
             list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return set(names)


def _check_jitted_body(f: FileCtx, qual: str, fn, cls,
                       mutable_globals: Set[str],
                       mutable_attrs: Dict[str, Set[str]],
                       out: List[Finding]) -> None:
    params = _param_names(fn)
    locals_stored: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                for el in (t.elts if isinstance(t, (ast.Tuple, ast.List))
                           else [t]):
                    if isinstance(el, ast.Name):
                        locals_stored.add(el.id)
    cls_attrs = mutable_attrs.get(cls.name, set()) if cls else set()
    seen: Set[Tuple[str, str]] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            nm = node.id
            if nm in mutable_globals and nm not in params and \
                    nm not in locals_stored and ("g", nm) not in seen:
                seen.add(("g", nm))
                out.append(Finding(
                    code=CODE, path=f.path, line=node.lineno, symbol=qual,
                    message=(f"jitted function reads mutable module global "
                             f"'{nm}' (reassigned via `global {nm}`): its "
                             "trace-time value is baked into the cached "
                             "executable — pass it as a static argument "
                             "instead")))
        elif isinstance(node, ast.Attribute) and \
                isinstance(node.ctx, ast.Load) and \
                isinstance(node.value, ast.Name) and \
                node.value.id == "self":
            if node.attr in cls_attrs and ("a", node.attr) not in seen:
                seen.add(("a", node.attr))
                out.append(Finding(
                    code=CODE, path=f.path, line=node.lineno, symbol=qual,
                    message=(f"jitted method reads 'self.{node.attr}', "
                             "which is stored outside __init__ (per-step "
                             "mutable state): the closure bakes its "
                             "trace-time value into the cached trace — "
                             "thread it through the arguments")))


def run(files: List[FileCtx]) -> List[Finding]:
    out: List[Finding] = []
    for f in files:
        mg = _mutable_globals(f.tree)
        ma = _mutable_attrs_by_class(f.tree)
        impl_names = _jitted_impl_names(f.tree)
        for qual, fn, cls in iter_scopes(f.tree):
            if _jit_decorated(fn) or fn.name in impl_names:
                _check_jitted_body(f, qual, fn, cls, mg, ma, out)

        # full-pool gathers in kernel-hot-path modules
        if "kernels/" in f.path and not f.path.endswith("/ref.py"):
            from repro.analysis.core import enclosing_index, scope_of
            index = enclosing_index(f.tree)
            for node in ast.walk(f.tree):
                if isinstance(node, ast.Call) and \
                        dotted_name(node.func) in _GATHER_FUNCS:
                    out.append(Finding(
                        code=CODE, path=f.path, line=node.lineno,
                        symbol=scope_of(index, node.lineno),
                        message=("jnp.take full-pool gather inside a "
                                 "kernel hot-path module: materialises "
                                 "the whole pool per step (the pattern "
                                 "PR 4's paged kernels removed); use the "
                                 "scalar-prefetch index_map path")))
    return out
