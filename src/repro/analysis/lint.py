"""Retrace/dtype linter: AST rules + a static no-retrace shape model.

AST rules over ``src/`` (the bug classes this repo actually shipped):

  per-call-jit        a ``jax.jit`` (bare, called, or via ``partial``)
                      created INSIDE a function body. Every call of the
                      enclosing function builds a fresh jitted callable
                      whose trace cache starts empty -- the PR 2
                      ``range_query`` bug (~245 ms/request until fixed).
                      Module-level jits and decorators are fine.
  host-sync-in-jit    ``.item()`` / ``np.asarray`` (errors) and
                      ``float()``/``int()`` of a non-literal (warnings)
                      inside a jit-decorated function or its nested
                      defs: on traced values these force a blocking
                      device sync (or a tracer error at runtime).
  int64-key-literal   hardcoded int64 sentinels -- ``PAD_KEY`` reads,
                      ``iinfo(int64)`` probes, or the bare 2^63-1
                      literal. On the ``REPRO_NO_X64`` int32 key path
                      these overflow or silently never match (the PR 3
                      key-aliasing class); key code must go through
                      ``grid.pad_key_for``/``grid.key_dtype_for``.
                      Legitimate declaration sites live in the committed
                      baseline; any NEW site fails CI.
  eps-squared-predicate  a hardcoded eps-squared comparison (the radius
                      multiplied by itself, or raised to the power 2)
                      outside ``core/metric.py``. Since the
                      metric trait (DESIGN.md S12) the refine predicate
                      is owned by ``core.metric`` alone -- an inlined
                      eps-squared comparison silently reverts that site
                      to L2 for every metric (a cosine or jaccard join
                      routed through it returns L2 answers). Use
                      ``metric_lib.eps_squared`` / ``l2_sq_hits`` /
                      ``tile_refine_hits`` instead.

Static no-retrace check (``check_no_retrace``): enumerates, by pure
``bucket_rows``/capacity-class arithmetic, every fused-launch executable
a canned request mix can demand and proves it a subset of what
``PreparedJoin.warm`` compiles for the warmed size ladder -- the
compile-time complement of ``serve.assert_no_retrace`` (which can only
catch a retrace after it already happened in production).
"""
from __future__ import annotations

import ast
import os
import re
from typing import Iterable, Optional

from repro.analysis.findings import SEV_WARNING, Finding

_AN = "lint"
RULE_JIT = "per-call-jit"
RULE_SYNC = "host-sync-in-jit"
RULE_I64 = "int64-key-literal"
RULE_EPS = "eps-squared-predicate"

# the one module allowed to spell the squared-threshold arithmetic: the
# metric trait that owns every refine predicate (DESIGN.md S12)
_EPS_OWNER = "core/metric.py"

_I64_MAX = (1 << 63) - 1          # spelled as a shift so we don't self-flag
_NP_NAMES = ("np", "numpy", "jnp")


def _is_jit_ref(node) -> bool:
    if isinstance(node, ast.Attribute) and node.attr == "jit":
        return True
    return isinstance(node, ast.Name) and node.id == "jit"


def _is_partial_ref(node) -> bool:
    if isinstance(node, ast.Attribute) and node.attr == "partial":
        return True
    return isinstance(node, ast.Name) and node.id == "partial"


def _is_jit_maker(node) -> bool:
    """A Call expression that creates a jitted callable."""
    if not isinstance(node, ast.Call):
        return False
    if _is_jit_ref(node.func):
        return True
    return (_is_partial_ref(node.func)
            and any(_is_jit_ref(a) for a in node.args))


def _decorator_is_jit(dec) -> bool:
    return _is_jit_ref(dec) or _is_jit_maker(dec)


def _is_int64_ref(node) -> bool:
    if isinstance(node, ast.Attribute) and node.attr == "int64":
        return True
    return isinstance(node, ast.Name) and node.id == "int64"


_EPS_IDENT = re.compile(r"(?:^|_)eps")   # eps, eps_geom, metric_eps; NOT steps


def _is_eps_ref(node) -> bool:
    """A Name/Attribute whose terminal identifier is an epsilon: 'eps',
    'eps_geom', 'self.eps', 'index.metric_eps', ... The 'eps' token must
    start the identifier or a ``_``-separated word of it, so 'steps' and
    'depth_steps' do not flag."""
    if isinstance(node, ast.Attribute):
        return bool(_EPS_IDENT.search(node.attr.lower()))
    return isinstance(node, ast.Name) and bool(_EPS_IDENT.search(node.id.lower()))


def _is_eps_square(node) -> bool:
    """The banned squaring shapes: an eps reference multiplied by the
    SAME eps reference, or an eps reference raised to the power 2."""
    if not isinstance(node, ast.BinOp):
        return False
    if isinstance(node.op, ast.Mult):
        return (_is_eps_ref(node.left) and _is_eps_ref(node.right)
                and ast.dump(node.left) == ast.dump(node.right))
    if isinstance(node.op, ast.Pow):
        return (_is_eps_ref(node.left)
                and isinstance(node.right, ast.Constant)
                and node.right.value == 2)
    return False


class _Linter(ast.NodeVisitor):
    def __init__(self, relpath: str):
        self.relpath = relpath
        self.stack: list = []        # enclosing class/function names
        self.func_depth = 0
        self.jit_depth = 0           # > 0: inside a jit-decorated def
        self.skip: set = set()       # decorator node ids (not per-call jits)
        self.findings: list = []

    def _qual(self) -> str:
        return ".".join(self.stack) if self.stack else "<module>"

    def _site(self) -> str:
        return f"{self.relpath}::{self._qual()}"

    def _add(self, rule: str, message: str, node, severity: str = "error"):
        self.findings.append(Finding(
            _AN, rule, self._site(), message, severity=severity,
            line=getattr(node, "lineno", None)))

    # -- scopes -------------------------------------------------------------

    def visit_ClassDef(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    def _visit_func(self, node):
        jitted = any(_decorator_is_jit(d) for d in node.decorator_list)
        for d in node.decorator_list:
            for sub in ast.walk(d):
                self.skip.add(id(sub))
        if self.func_depth > 0 and jitted:
            self._add(RULE_JIT,
                      f"per-call @jax.jit: '{node.name}' is traced and "
                      f"compiled fresh on every call of "
                      f"'{self._qual()}' (hoist to module level or cache "
                      f"the jitted callable)", node)
        self.stack.append(node.name)
        self.func_depth += 1
        self.jit_depth += 1 if (jitted or self.jit_depth) else 0
        # decorators were evaluated in the ENCLOSING scope; still walk them
        # for int64 literals etc.
        for d in node.decorator_list:
            self.visit(d)
        for item in node.body:
            self.visit(item)
        if jitted or self.jit_depth:
            self.jit_depth -= 1 if self.jit_depth else 0
        self.func_depth -= 1
        self.stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    # -- rules --------------------------------------------------------------

    def visit_Call(self, node):
        if (self.func_depth > 0 and id(node) not in self.skip
                and _is_jit_maker(node)):
            self._add(RULE_JIT,
                      "jax.jit called inside a function body: the "
                      "resulting callable's trace cache is rebuilt per "
                      "call (hoist to module level or cache it)", node)
        if self.jit_depth > 0:
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr == "item":
                self._add(RULE_SYNC,
                          ".item() inside a jitted function blocks on the "
                          "device (or fails on a tracer)", node)
            elif (isinstance(f, ast.Attribute) and f.attr == "asarray"
                  and isinstance(f.value, ast.Name)
                  and f.value.id in ("np", "numpy")):
                self._add(RULE_SYNC,
                          "np.asarray inside a jitted function forces a "
                          "host sync of a traced value", node)
            elif (isinstance(f, ast.Name) and f.id in ("float", "int")
                  and node.args
                  and not isinstance(node.args[0], ast.Constant)):
                self._add(RULE_SYNC,
                          f"{f.id}() of a non-literal inside a jitted "
                          f"function syncs if the value is traced",
                          node, severity=SEV_WARNING)
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr == "iinfo"
                and any(_is_int64_ref(a) for a in node.args)):
            self._add(RULE_I64,
                      "iinfo(int64) sentinel: breaks the int32 key fast "
                      "path (REPRO_NO_X64); derive sentinels via "
                      "grid.pad_key_for(index.key_dtype)", node)
        self.generic_visit(node)

    def visit_Name(self, node):
        if node.id == "PAD_KEY" and isinstance(node.ctx, ast.Load):
            self._add(RULE_I64,
                      "PAD_KEY is the int64-max sentinel: on int32-keyed "
                      "grids it overflows/never matches; use "
                      "grid.pad_key_for(index.key_dtype)", node)
        self.generic_visit(node)

    def visit_Constant(self, node):
        if node.value == _I64_MAX and isinstance(node.value, int):
            self._add(RULE_I64,
                      "bare 2^63-1 literal used as a key sentinel", node)
        self.generic_visit(node)

    def visit_BinOp(self, node):
        if (_is_eps_square(node)
                and not self.relpath.endswith(_EPS_OWNER)):
            self._add(RULE_EPS,
                      "hardcoded eps-squared predicate outside "
                      "core/metric.py: the refine threshold is owned by "
                      "the metric trait (metric_lib.eps_squared / "
                      "l2_sq_hits / tile_refine_hits); an inlined square "
                      "silently evaluates L2 for every metric", node)
        self.generic_visit(node)


def lint_source(text: str, relpath: str) -> list:
    """Lint one module's source text; findings carry ``relpath`` sites."""
    tree = ast.parse(text, filename=relpath)
    linter = _Linter(relpath)
    linter.visit(tree)
    return linter.findings


def lint_paths(paths: Iterable[str], root: Optional[str] = None) -> list:
    out = []
    for path in paths:
        rel = os.path.relpath(path, root) if root else path
        with open(path) as fh:
            out.extend(lint_source(fh.read(), rel.replace(os.sep, "/")))
    return out


def lint_tree(root: str = "src") -> list:
    """Lint every ``.py`` under ``root`` (sites relative to its parent)."""
    paths = []
    for dirpath, _, files in os.walk(root):
        for name in sorted(files):
            if name.endswith(".py"):
                paths.append(os.path.join(dirpath, name))
    base = os.path.dirname(os.path.abspath(root))
    return lint_paths(sorted(paths), root=base)


# ---------------------------------------------------------------------------
# static no-retrace check (shape-space model of PreparedJoin.warm)
# ---------------------------------------------------------------------------

def fused_launch_keys(pj, size: int, keep: bool) -> set:
    """Every fused-sweep executable key a request of ``size`` queries can
    demand from ``pj``: (capacity, tile, padded rows, keep_hits). On a
    bucketed index the per-class row split is data-dependent, but its
    SHAPE space is the pow2 tile ladder bounded by the request bucket --
    the same enumeration ``PreparedJoin.warm``'s ladder loop compiles."""
    from repro.core.query_join import bucket_rows
    from repro.kernels.fused_join import TQ_DEFAULT as tile

    qp = bucket_rows(size)
    keys = set()
    if not pj.bucketed:
        keys.add((pj.c, tile, qp, keep))
        return keys
    for cb in pj.classes:
        s = tile
        while s <= qp:
            keys.add((cb, tile, s, keep))
            s *= 2
    return keys


def warmed_launch_keys(pj, warm_sizes: Iterable[int],
                       keep_variants=(True, False)) -> set:
    """The executable set ``PreparedJoin.warm(n)`` compiles for each
    warmed size: the request-bucket launch (single-class indexes) plus
    the full (class, pow2-size) ladder (bucketed indexes)."""
    keys = set()
    for n in warm_sizes:
        for keep in keep_variants:
            keys |= fused_launch_keys(pj, int(n), keep)
    return keys


def check_no_retrace(pj, *, max_batch: int, request_sizes: Iterable[int],
                     warm_sizes: Optional[Iterable[int]] = None,
                     keep_variants=(True, False),
                     tag: str = "prepared") -> list:
    """Prove a canned request mix cannot out-trace the warm set.

    ``warm_sizes=None`` models the batching service's full pow2 ladder up
    to ``max_batch`` (launch/serve.py ``BatchingJoinService.warmup``); an
    explicit list models a fixed-size ``JoinService.warmup``. Findings
    name every executable the mix demands that warm never compiled --
    each one is a steady-state trace+compile on the request path."""
    from repro.core.query_join import bucket_rows

    if warm_sizes is None:
        warm_sizes, s = [], bucket_rows(1)
        while s <= bucket_rows(max_batch):
            warm_sizes.append(s)
            s *= 2
    warmed = warmed_launch_keys(pj, warm_sizes, keep_variants)
    out = []
    for m in request_sizes:
        for keep in keep_variants:
            missing = sorted(fused_launch_keys(pj, int(m), keep) - warmed)
            if missing:
                out.append(Finding(
                    _AN, "static-retrace", f"{tag}:q{int(m)}:keep={keep}",
                    f"request of {int(m)} queries demands un-warmed "
                    f"executables {missing}: each is a steady-state "
                    f"trace+compile (warm sizes {sorted(warm_sizes)})"))
    return out


def count_distinct_lowerings(pj, sizes: Iterable[int],
                             keep_variants=(True, False)) -> int:
    """Distinct fused-sweep lowerings a request mix compiles in total --
    the number ``executable_cache_stats`` would report for the sweep."""
    keys = set()
    for m in sizes:
        for keep in keep_variants:
            keys |= fused_launch_keys(pj, int(m), keep)
    return len(keys)
