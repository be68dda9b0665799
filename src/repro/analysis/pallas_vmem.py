"""COOPT005 — Pallas kernel contracts: index_map discipline, the ``-1``
page sentinel, and a static VMEM budget.

Lineage: the paged kernels (PRs 3-5) share three load-bearing conventions:

  * BlockSpec ``index_map`` functions run on the TPU scalar core BEFORE the
    block DMA — they may only dereference SCALAR-PREFETCHED refs (the
    trailing params injected by ``PrefetchScalarGridSpec``). Touching a
    grid index as an array, or a closed-over tensor, is not a type error —
    it miscompiles or silently reads garbage.
  * Page tables use ``-1`` for never-allocated slots. An index_map that
    dereferences a table without clamping (``jnp.maximum(phys[b, s], 0)``)
    turns ``-1`` into a wrap-around DMA of the pool's LAST page — exactly
    the PR 5 slot-wrap incident class, where an unhandled sentinel let a
    write land on a live pool line. (The write kernel instead clamps its
    page indices before the call; its index_maps carry inline allows
    citing that.) A read whose every index is a literal (``lyr[0]``)
    takes a scalar argument — the layer of the pool — not a table entry
    keyed by the grid, and holds no sentinel.
  * Every block named by the specs is resident in VMEM (~16 MiB/core),
    double-buffered, alongside the scratch accumulators. The estimator
    below computes worst-case residency from the BlockSpec shapes and
    fails the build when a kernel's working set crosses the budget
    (default half of VMEM, leaving headroom for the compiler's own
    allocations) — so a block-size bump that would OOM on hardware fails
    in CI on the CPU container instead.

Shape symbols are resolved against documented repo defaults (page size 64
from ``core.coopt``, head dim 128, block_q/block_k 256, ...); unresolvable
dims fall back to 128 and are listed in the report so a human can audit
the estimate.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Tuple

from repro.analysis.core import (FileCtx, Finding, dotted_name,
                                 enclosing_index, scope_of)

CODE = "COOPT005"

DEFAULT_BUDGET = 8 * 1024 * 1024    # bytes: half of ~16 MiB VMEM/core

# documented repo defaults for symbolic block dims (see module docstring)
ASSUMPTIONS: Dict[str, int] = {
    "ps": 64,       # CoOptConfig.page_size
    "D": 128,       # attention head dim
    "bq": 256, "bk": 256, "block_q": 256, "block_k": 256,
    "G": 8,         # GQA group size upper bound
    "Hkv": 8, "H": 128, "Hq": 64,
    "R": 512,       # MLA latent rank
    "W": 576,       # packed latent width R + d_rope
    "dr": 64,       # rope sub-dim
    # cross-lane visit grids (kernels.visits): flattened row counts at the
    # MAX_VISIT_LANES=32 dispatch ceiling — BG = B*G, BH = B*H_q(mla=8)
    "BG": 128, "BH": 256,
    # tile-resident chunk streaming: resident row-block caps
    # (flash_chunk_prefill.RESIDENT_ROWS / latent_chunk_prefill's)
    "rq": 1024, "rl": 512,
}
_UNKNOWN_DEFAULT = 128

_CLAMP_FUNCS = {"jnp.maximum", "jnp.clip", "jax.lax.max", "lax.max",
                "jax.numpy.maximum", "jax.numpy.clip"}
_DTYPE_BYTES = {"float32": 4, "int32": 4, "uint32": 4, "bfloat16": 2,
                "float16": 2, "int16": 2, "int8": 1, "uint8": 1,
                "float8_e4m3fn": 1, "float8_e5m2": 1, "bool_": 1}


def _unparse(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:
        return "<expr>"


# --------------------------------------------------------- dim evaluation --
def _eval_dim(node: ast.AST, used: Dict[str, int],
              unknown: List[str]) -> int:
    if isinstance(node, ast.Constant) and node.value is None:
        return 1                    # a squeezed block dim holds one element
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return node.value
    if isinstance(node, ast.Name):
        if node.id in ASSUMPTIONS:
            used[node.id] = ASSUMPTIONS[node.id]
            return ASSUMPTIONS[node.id]
        unknown.append(node.id)
        return _UNKNOWN_DEFAULT
    if isinstance(node, ast.BinOp):
        lhs = _eval_dim(node.left, used, unknown)
        rhs = _eval_dim(node.right, used, unknown)
        if isinstance(node.op, ast.Add):
            return lhs + rhs
        if isinstance(node.op, ast.Sub):
            return max(lhs - rhs, 1)
        if isinstance(node.op, ast.Mult):
            return lhs * rhs
        if isinstance(node.op, ast.FloorDiv):
            return max(lhs // max(rhs, 1), 1)
    if isinstance(node, ast.Call):
        fname = dotted_name(node.func)
        vals = [_eval_dim(a, used, unknown) for a in node.args]
        if fname == "min" and vals:
            return min(vals)
        if fname == "max" and vals:
            return max(vals)
    unknown.append(_unparse(node))
    return _UNKNOWN_DEFAULT


def _dtype_bytes(node: ast.AST) -> int:
    name = dotted_name(node)
    if name:
        return _DTYPE_BYTES.get(name.split(".")[-1], 4)
    return 4


# ------------------------------------------------------------- resolution --
def _local_assigns(fn: ast.AST) -> Dict[str, List[ast.AST]]:
    """name -> every value ever assigned/augmented onto it in ``fn``."""
    out: Dict[str, List[ast.AST]] = {}
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    out.setdefault(t.id, []).append(node.value)
        elif isinstance(node, ast.AugAssign) and \
                isinstance(node.target, ast.Name):
            out.setdefault(node.target.id, []).append(node.value)
    return out


def _is_blockspec(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and \
        (dotted_name(node.func) or "").split(".")[-1] == "BlockSpec"


def _resolve_specs(node: Optional[ast.AST],
                   assigns: Dict[str, List[ast.AST]]) -> List[ast.Call]:
    """Flatten a spec expression (list literal / single BlockSpec / local
    name built via ``x = [a]; x += [b, c]``) into BlockSpec calls. The
    union over every assignment is taken — a conservative upper bound for
    conditionally-appended specs (the ``return_state`` idiom)."""
    if node is None:
        return []
    if _is_blockspec(node):
        return [node]
    if isinstance(node, (ast.List, ast.Tuple)):
        out = []
        for el in node.elts:
            out.extend(_resolve_specs(el, assigns))
        return out
    if isinstance(node, ast.Name) and node.id in assigns:
        out = []
        for val in assigns[node.id]:
            out.extend(_resolve_specs(val, assigns))
        return out
    return []


def _resolve_index_map(node: Optional[ast.AST], fn: ast.AST):
    """The index_map callable behind a BlockSpec's second arg: an inline
    Lambda, a local ``def``, or a name bound to a lambda."""
    if node is None:
        return None
    if isinstance(node, ast.Lambda):
        return node
    if isinstance(node, ast.Name):
        for n in ast.walk(fn):
            if isinstance(n, ast.FunctionDef) and n.name == node.id:
                return n
            if isinstance(n, ast.Assign) and \
                    isinstance(n.value, ast.Lambda):
                for t in n.targets:
                    if isinstance(t, ast.Name) and t.id == node.id:
                        return n.value
    return None


def _params_of(im) -> List[str]:
    args = im.args
    return [a.arg for a in list(args.posonlyargs) + list(args.args)]


def _parent_map(root: ast.AST) -> Dict[int, ast.AST]:
    out = {}
    for node in ast.walk(root):
        for child in ast.iter_child_nodes(node):
            out[id(child)] = node
    return out


def _scalar_read(sub: ast.Subscript) -> bool:
    """Every index a literal (``lyr[0]``): a scalar argument, not a table
    lookup keyed by the grid."""
    idx = sub.slice
    elts = idx.elts if isinstance(idx, ast.Tuple) else [idx]
    return all(isinstance(e, ast.Constant) for e in elts)


def _clamped(sub: ast.Subscript, parents: Dict[int, ast.AST]) -> bool:
    node: ast.AST = sub
    while id(node) in parents:
        node = parents[id(node)]
        if isinstance(node, ast.Call) and \
                dotted_name(node.func) in _CLAMP_FUNCS:
            return True
    return False


# ------------------------------------------------------------ the checks --
def _check_index_map(f: FileCtx, qual: str, im, grid_len: int,
                     num_prefetch: int, out: List[Finding]) -> None:
    params = _params_of(im)
    prefetch = set(params[grid_len:]) if num_prefetch else set()
    parents = _parent_map(im)
    for node in ast.walk(im):
        if not isinstance(node, ast.Subscript):
            continue
        base = node.value
        while isinstance(base, ast.Subscript):
            base = base.value
        if not isinstance(base, ast.Name):
            continue
        if base.id in prefetch:
            if not (_scalar_read(node) or _clamped(node, parents)):
                out.append(Finding(
                    code=CODE, path=f.path, line=node.lineno, symbol=qual,
                    message=(f"index_map dereferences page table "
                             f"'{base.id}' without clamping the -1 "
                             "sentinel: wrap in jnp.maximum(..., 0) (or "
                             "pre-map -1 to a reserved line before the "
                             "call) so unallocated pages cannot DMA a "
                             "wrapped pool line")))
        elif base.id in params:
            out.append(Finding(
                code=CODE, path=f.path, line=node.lineno, symbol=qual,
                message=(f"index_map subscripts grid index '{base.id}': "
                         "only scalar-prefetch refs (the trailing "
                         f"{num_prefetch} params) may be dereferenced "
                         "inside an index_map")))
        else:
            out.append(Finding(
                code=CODE, path=f.path, line=node.lineno, symbol=qual,
                message=(f"index_map subscripts closed-over value "
                         f"'{base.id}': index_maps run on the scalar core "
                         "before the DMA and may only touch their params "
                         "(scalar-prefetch refs); pass the table through "
                         "PrefetchScalarGridSpec instead")))


def _kw(call: ast.Call, name: str) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _analyze_site(f: FileCtx, qual: str, fn: ast.AST, call: ast.Call,
                  budget: int, out: List[Finding],
                  report: List[Dict[str, object]]) -> None:
    assigns = _local_assigns(fn)
    grid_spec = _kw(call, "grid_spec")
    num_prefetch = 0
    if isinstance(grid_spec, ast.Call):
        src = grid_spec
        npf = _kw(grid_spec, "num_scalar_prefetch")
        if isinstance(npf, ast.Constant) and isinstance(npf.value, int):
            num_prefetch = npf.value
    else:
        src = call
    grid = _kw(src, "grid")
    grid_len = len(grid.elts) if isinstance(grid, (ast.Tuple, ast.List)) \
        else 0
    in_specs = _resolve_specs(_kw(src, "in_specs"), assigns)
    out_specs = _resolve_specs(_kw(src, "out_specs"), assigns)
    scratch = _kw(call, "scratch_shapes") or _kw(src, "scratch_shapes")

    used: Dict[str, int] = {}
    unknown: List[str] = []
    block_bytes = 0
    for spec in in_specs + out_specs:
        shape = spec.args[0] if spec.args else None
        dims = 1
        if isinstance(shape, (ast.Tuple, ast.List)):
            for d in shape.elts:
                dims *= _eval_dim(d, used, unknown)
        block_bytes += dims * 4           # f32 upper bound per element
        im = _resolve_index_map(spec.args[1] if len(spec.args) > 1 else None,
                                fn)
        if im is not None:
            _check_index_map(f, qual, im, grid_len, num_prefetch, out)
    scratch_bytes = 0
    if isinstance(scratch, (ast.List, ast.Tuple)):
        for s in scratch.elts:
            if isinstance(s, ast.Call) and s.args:
                dims = 1
                if isinstance(s.args[0], (ast.Tuple, ast.List)):
                    for d in s.args[0].elts:
                        dims *= _eval_dim(d, used, unknown)
                nbytes = _dtype_bytes(s.args[1]) if len(s.args) > 1 else 4
                scratch_bytes += dims * nbytes
    total = block_bytes * 2 + scratch_bytes   # x2: double-buffered DMA
    entry = {
        "kernel": qual or "<module>", "path": f.path, "line": call.lineno,
        "grid": _unparse(grid) if grid is not None else None,
        "num_scalar_prefetch": num_prefetch,
        "num_block_specs": len(in_specs) + len(out_specs),
        "block_bytes": block_bytes, "scratch_bytes": scratch_bytes,
        "est_vmem_bytes": total, "budget_bytes": budget,
        "under_budget": total <= budget,
        "assumed_dims": dict(sorted(used.items())),
        "unresolved_dims": sorted(set(unknown)),
    }
    report.append(entry)
    if total > budget:
        out.append(Finding(
            code=CODE, path=f.path, line=call.lineno, symbol=qual,
            message=(f"estimated VMEM working set {total} bytes exceeds "
                     f"the {budget}-byte budget (blocks {block_bytes} x2 "
                     f"double-buffered + scratch {scratch_bytes}): shrink "
                     "the BlockSpec block shapes or raise --vmem-budget "
                     "with a hardware justification")))


def run(files: Sequence[FileCtx], *, vmem_budget: Optional[int] = None
        ) -> Tuple[List[Finding], List[Dict[str, object]]]:
    budget = vmem_budget if vmem_budget else DEFAULT_BUDGET
    out: List[Finding] = []
    report: List[Dict[str, object]] = []
    for f in files:
        if "kernels/" not in f.path:
            continue
        index = enclosing_index(f.tree)
        scope_nodes = {}
        from repro.analysis.core import iter_scopes
        for q, fn, _c in iter_scopes(f.tree):
            scope_nodes[q] = fn
        for node in ast.walk(f.tree):
            if isinstance(node, ast.Call) and \
                    (dotted_name(node.func) or "").split(".")[-1] == \
                    "pallas_call":
                qual = scope_of(index, node.lineno)
                fn = scope_nodes.get(qual, f.tree)
                _analyze_site(f, qual, fn, node, budget, out, report)
    report.sort(key=lambda e: (e["path"], e["line"]))
    return out, report
