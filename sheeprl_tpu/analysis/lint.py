"""graft-lint static pass: AST rules for JAX/TPU trace hygiene.

The rules encode invariants the fused hot paths rely on and no generic linter
checks. Each is cheap to state and expensive to violate:

GL001  RNG key consumed more than once. A key passed to a ``jax.random``
       sampler (or ``split``) is spent; using the same name again without an
       intervening reassignment silently correlates samples (``fold_in`` is
       the sanctioned multi-derive and is exempt).
GL002  Host sync inside jit-reachable code: ``.item()``, ``.tolist()``,
       ``.block_until_ready()``, ``float()``/``int()``/``bool()``,
       ``np.asarray``/``np.array`` on a traced value — each one is a
       device->host round trip (or a trace error) in the steady state.
GL003  Other ``np.`` calls on traced values in jit-reachable code: the op
       runs on host per trace and constant-folds, or fails outright — use
       ``jnp``.
GL004  Python ``if``/``while``/``for`` on a traced value: data-dependent
       control flow must go through ``lax.cond``/``lax.scan`` et al.
GL005  Read-after-donate: an argument passed at a ``donate_argnums`` position
       is dead after the call; reading it again is use-after-free (XLA may
       have aliased the buffer into the output).
GL006  Dict-ordering-sensitive pytree construction (dict comprehension over a
       ``set``, ``dict(zip(a.keys(), b.values()))`` across two objects):
       pytree structure follows insertion order, and per-process hash seeds
       make set order nondeterministic — structure drift means retraces on
       one host and desync across hosts.
GL007  ``jax.random.PRNGKey``/``jax.random.key`` created inside a loop body:
       fresh keys from a (usually constant) seed per iteration either repeat
       the stream or hide a host->device transfer per step; derive from a
       carried key with ``split``/``fold_in`` instead.
GL008  ``jax.jit`` that BOTH donates buffers AND returns mesh-axis-sharded
       ``shard_map`` outputs WITHOUT pinned ``out_shardings`` — the exact
       PR 8 bug shape: jit canonicalizes the sharded output placement to an
       EQUIVALENT layout with a different C++ jit-cache key, so the next call
       (fed by this call's donated outputs) silently recompiles the whole
       program — one abstract signature, two compiles, no tracing-cache miss
       to warn anyone. Pin ``out_shardings`` on every fed-back output.

Jit-reachability is computed per module by walking (a) ``@jax.jit`` /
``@partial(jax.jit, ...)`` decorators, (b) function names passed to
``jax.jit`` / ``shard_map`` / ``pmap`` / ``vmap`` / ``grad`` /
``lax.scan``-family combinators, (c) the module-local call graph from those
roots, and (d) bodies that use axis collectives (``lax.pmean`` et al. are
only legal under a mapped trace, so such bodies are trace context by
construction). Traced-value tracking is a per-function taint pass seeded from
the function's parameters.

Suppression: append ``# graft-lint: disable=GL001[,GL002]`` (or a bare
``disable`` for all rules) to the offending line, or put
``# graft-lint: disable-next-line=GLxxx`` on the line above. Pre-existing
findings live in a checked-in baseline (``.graft-lint-baseline.json``);
see :mod:`sheeprl_tpu.analysis.__main__` for the CLI contract.
"""

from __future__ import annotations

import ast
import io
import json
import os
import re
import tokenize
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

__all__ = [
    "RULES",
    "Finding",
    "analyze_source",
    "analyze_paths",
    "load_baseline",
    "write_baseline",
    "apply_baseline",
    "fingerprint",
    "iter_python_files",
    "collect_suppressions",
]

RULES: Dict[str, str] = {
    "GL001": "RNG key consumed more than once without reassignment",
    "GL002": "host synchronization on a traced value inside jit-reachable code",
    "GL003": "numpy (host) op on a traced value inside jit-reachable code — use jnp",
    "GL004": "Python control flow on a traced value inside jit-reachable code",
    "GL005": "read of a donated buffer after the donating call",
    "GL006": "dict-ordering-sensitive pytree construction",
    "GL007": "PRNGKey created inside a loop body",
    "GL008": "donating jit over sharded shard_map outputs without pinned out_shardings",
}

# jax.random callables that SPEND the key passed as their first argument.
# ``fold_in`` is deliberately absent: deriving many child keys from one base
# via fold_in(key, i) is the documented idiom (and how the Anakin/Sebulba
# paths stream per-step keys without a host round trip).
_KEY_CONSUMERS = {
    "ball", "bernoulli", "beta", "binomial", "bits", "categorical", "cauchy",
    "chisquare", "choice", "dirichlet", "double_sided_maxwell", "exponential",
    "f", "gamma", "generalized_normal", "geometric", "gumbel", "laplace",
    "loggamma", "logistic", "lognormal", "maxwell", "multivariate_normal",
    "normal", "orthogonal", "pareto", "permutation", "poisson", "rademacher",
    "randint", "rayleigh", "shuffle", "split", "t", "triangular",
    "truncated_normal", "uniform", "wald", "weibull_min",
}

# Axis collectives: calling one requires a mapped trace (shard_map / pmap),
# so any function body containing one is trace context by construction.
_COLLECTIVES = {
    "pmean", "psum", "pmin", "pmax", "all_gather", "all_to_all", "ppermute",
    "axis_index", "pshuffle", "psum_scatter",
}

# Higher-order jax entry points: a module-local function name passed as an
# argument to any of these is traced.
_TRACE_WRAPPERS = {
    "jit", "pmap", "vmap", "shard_map", "grad", "value_and_grad", "checkpoint",
    "remat", "custom_jvp", "custom_vjp", "scan", "cond", "while_loop",
    "fori_loop", "switch", "associative_scan", "named_call",
}
# ``lax.map``/``jax.tree.map`` deliberately excluded: ``tree.map`` callbacks
# run eagerly on host in host code, and bare ``map`` is the builtin.

def _suppress_re(tool: str) -> "re.Pattern[str]":
    return re.compile(rf"#\s*{tool}:\s*(disable(?:-next-line)?)\s*(?:=\s*([A-Z0-9,\s]+))?")


_SUPPRESS_RE = _suppress_re("graft-lint")


def collect_suppressions(src: str, tool: str = "graft-lint") -> Dict[int, Optional[Set[str]]]:
    """``line -> suppressed rules`` (``None`` = all) for ``# <tool>: disable``
    comments. ONE implementation for every AST tier (graft-lint, graft-sync)
    so the directive semantics cannot drift: ``disable-next-line`` skips over
    continuation COMMENT lines to the next code line, because suppressions
    are required to carry a justification comment and justifications wrap."""
    pattern = _suppress_re(tool)
    lines: Dict[int, Optional[Set[str]]] = {}
    code_lines: Set[int] = set()
    pending: List[Tuple[int, Optional[Set[str]]]] = []

    def merge(line: int, rules: Optional[Set[str]]) -> None:
        prev = lines.get(line)
        if prev is None and line in lines:
            return  # already suppress-all
        if rules is None:
            lines[line] = None
        else:
            lines[line] = (prev or set()) | rules

    try:
        for tok in tokenize.generate_tokens(io.StringIO(src).readline):
            if tok.type not in (
                tokenize.COMMENT,
                tokenize.NL,
                tokenize.NEWLINE,
                tokenize.INDENT,
                tokenize.DEDENT,
                tokenize.ENDMARKER,
            ):
                code_lines.add(tok.start[0])
            if tok.type != tokenize.COMMENT:
                continue
            m = pattern.search(tok.string)
            if not m:
                continue
            rules = None
            if m.group(2):
                rules = {r.strip() for r in m.group(2).split(",") if r.strip()}
            if m.group(1) == "disable-next-line":
                pending.append((tok.start[0], rules))
            else:
                merge(tok.start[0], rules)
    except tokenize.TokenError:  # pragma: no cover - half-written files
        pass
    max_line = max(code_lines, default=0)
    for start, rules in pending:
        line = start + 1
        while line <= max_line and line not in code_lines:
            line += 1
        merge(line, rules)
    return lines


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    col: int
    message: str
    function: str = "<module>"

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message} [in {self.function}]"


def fingerprint(f: Finding) -> str:
    """Line-insensitive identity used by the baseline: a finding keeps its
    baseline slot across unrelated edits that only shift line numbers (line
    references inside messages are normalized away too)."""
    msg = re.sub(r"\bline \d+\b", "line *", f.message)
    return f"{f.path}::{f.rule}::{f.function}::{msg}"


#: The stale-suppression pseudo-rule, shared by every AST tier: a
#: ``# graft-*: disable=...`` directive that absorbed nothing this run is a
#: dead justification riding fixed code. Reported warn-level by default;
#: ``--strict-suppressions`` promotes it into the findings stream (exit 1).
SUPPRESSION_RULE = "SUP001"


def stale_suppression_findings(
    tool: str,
    catalog: Dict[str, str],
    declared: Dict[int, Optional[Set[str]]],
    used: Dict[int, Set[str]],
    path: str,
    select: Optional[Set[str]] = None,
    ignore: Optional[Set[str]] = None,
) -> List[Finding]:
    """Compare a file's declared suppressions against the rules that actually
    hit them. ONE implementation for every tier (graft-lint/sync/jit) so
    staleness semantics cannot drift. A directive naming a rule that this run
    did not execute (``--select``/``--ignore`` filtered it out) is NOT stale —
    the rule might fire on a full run. A directive naming a rule outside the
    tier's catalog can never fire and is always stale."""
    rules_run = set(catalog) if select is None else (select & set(catalog))
    if ignore:
        rules_run -= set(ignore)
    out: List[Finding] = []
    for line in sorted(declared):
        rules = declared[line]
        absorbed = used.get(line, set())
        if rules is None:
            if not absorbed:
                out.append(
                    Finding(
                        SUPPRESSION_RULE, path, line, 1,
                        f"stale suppression: `# {tool}: disable` absorbs nothing on this "
                        "line (remove the dead directive)",
                    )
                )
            continue
        for rule in sorted(rules):
            if rule in absorbed:
                continue
            if rule in catalog and rule not in rules_run:
                continue  # rule filtered out this run: can't judge staleness
            hint = "" if rule in catalog else f" ({rule} is not a {tool} rule and can never fire)"
            out.append(
                Finding(
                    SUPPRESSION_RULE, path, line, 1,
                    f"stale suppression: `# {tool}: disable={rule}` — {rule} does not fire "
                    f"on this line{hint} (remove the dead directive)",
                )
            )
    return out


# --------------------------------------------------------------------------- #
# module context: imports, aliases, suppressions
# --------------------------------------------------------------------------- #


class _ModuleContext:
    def __init__(self, src: str, path: str) -> None:
        self.src = src
        self.path = path
        self.aliases: Dict[str, str] = {}  # local name -> canonical dotted prefix
        self.suppressed: Dict[int, Optional[Set[str]]] = {}  # line -> rules (None = all)
        self.sup_used: Dict[int, Set[str]] = {}  # line -> rules a directive absorbed
        self._collect_suppressions()

    def _collect_suppressions(self) -> None:
        self.suppressed = collect_suppressions(self.src, tool="graft-lint")

    def is_suppressed(self, rule: str, line: int) -> bool:
        if line not in self.suppressed:
            return False
        rules = self.suppressed[line]
        if rules is None or rule in rules:
            self.sup_used.setdefault(line, set()).add(rule)
            return True
        return False

    def add_import(self, node: ast.AST) -> None:
        if isinstance(node, ast.Import):
            for a in node.names:
                self.aliases[a.asname or a.name.split(".")[0]] = a.name if a.asname else a.name.split(".")[0]
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                self.aliases[a.asname or a.name] = f"{node.module}.{a.name}"

    def resolve(self, node: ast.AST) -> Optional[str]:
        """Dotted name of ``node`` with the root import alias expanded, e.g.
        ``np.asarray`` -> ``numpy.asarray``; returns None for non-name exprs."""
        parts: List[str] = []
        cur = node
        while isinstance(cur, ast.Attribute):
            parts.append(cur.attr)
            cur = cur.value
        if not isinstance(cur, ast.Name):
            return None
        root = self.aliases.get(cur.id, cur.id)
        parts.append(root)
        return ".".join(reversed(parts))


def _tail(resolved: Optional[str]) -> Optional[str]:
    return resolved.rsplit(".", 1)[-1] if resolved else None


def _is_numpy(resolved: Optional[str]) -> bool:
    return bool(resolved) and (resolved == "numpy" or resolved.startswith("numpy."))


def _is_jax_random(resolved: Optional[str]) -> bool:
    return bool(resolved) and resolved.startswith("jax.random.")


def _is_trace_wrapper(resolved: Optional[str]) -> bool:
    tail = _tail(resolved)
    if tail not in _TRACE_WRAPPERS:
        return False
    if resolved == tail:  # bare name that never came from an import
        return tail in ("shard_map", "jit")  # local defs named e.g. `map` don't count
    # anything imported from jax/lax qualifies
    return True


# --------------------------------------------------------------------------- #
# reachability
# --------------------------------------------------------------------------- #


class _FunctionInfo:
    def __init__(self, node: ast.AST, qualname: str) -> None:
        self.node = node
        self.qualname = qualname
        self.reachable = False
        self.calls: Set[str] = set()  # bare names called in the body (own frame only)
        self.static_argnums: Set[int] = set()  # from jax.jit(..., static_argnums=...)
        self.static_argnames: Set[str] = set()


def _collect_functions(tree: ast.Module) -> Dict[int, _FunctionInfo]:
    """Map id(node) -> info for every (async) function def, with qualnames."""
    out: Dict[int, _FunctionInfo] = {}

    def walk(node: ast.AST, prefix: str) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{child.name}"
                out[id(child)] = _FunctionInfo(child, qual)
                walk(child, qual + ".")
            elif isinstance(child, ast.ClassDef):
                walk(child, f"{prefix}{child.name}.")
            elif isinstance(child, ast.Lambda):
                walk(child, prefix)
            else:
                walk(child, prefix)

    walk(tree, "")
    return out


def _own_frame_nodes(fn: ast.AST) -> Iterable[ast.AST]:
    """Yield nodes of ``fn``'s body excluding nested function/class frames
    (their hazards are judged in their own analysis pass)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _mark_reachable(ctx: _ModuleContext, tree: ast.Module, funcs: Dict[int, _FunctionInfo]) -> None:
    by_name: Dict[str, List[_FunctionInfo]] = {}
    for info in funcs.values():
        by_name.setdefault(info.node.name, []).append(info)

    roots: List[_FunctionInfo] = []

    def _record_static_args(info: _FunctionInfo, call: Optional[ast.Call]) -> None:
        if call is None:
            return
        for kw in call.keywords:
            if kw.arg not in ("static_argnums", "static_argnames"):
                continue
            try:
                val = ast.literal_eval(kw.value)
            except (ValueError, SyntaxError):
                continue
            vals = val if isinstance(val, (tuple, list)) else (val,)
            for v in vals:
                if isinstance(v, int):
                    info.static_argnums.add(v)
                elif isinstance(v, str):
                    info.static_argnames.add(v)

    # (a) decorator roots: @jax.jit, @jit, @partial(jax.jit, ...), @shard_map
    for info in funcs.values():
        for dec in getattr(info.node, "decorator_list", []):
            target = dec.func if isinstance(dec, ast.Call) else dec
            resolved = ctx.resolve(target)
            if _is_trace_wrapper(resolved):
                roots.append(info)
                _record_static_args(info, dec if isinstance(dec, ast.Call) else None)
            elif isinstance(dec, ast.Call) and _tail(ctx.resolve(dec.func)) == "partial":
                inner = dec.args[0] if dec.args else None
                if inner is not None and _is_trace_wrapper(ctx.resolve(inner)):
                    roots.append(info)
                    _record_static_args(info, dec)

    # (b) call-argument roots: f passed to jit/shard_map/scan/cond/...; also
    # partial(f, ...) passed to the same.
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        resolved = ctx.resolve(node.func)
        if not _is_trace_wrapper(resolved):
            continue
        cand: List[ast.expr] = list(node.args) + [kw.value for kw in node.keywords]
        for arg in cand:
            if isinstance(arg, ast.Call) and _tail(ctx.resolve(arg.func)) == "partial" and arg.args:
                arg = arg.args[0]
            if isinstance(arg, ast.Name):
                matches = by_name.get(arg.id, [])
                roots.extend(matches)
                if _tail(resolved) == "jit":
                    for m in matches:
                        _record_static_args(m, node)

    # (c) intrinsic trace context: bodies using axis collectives
    for info in funcs.values():
        for node in _own_frame_nodes(info.node):
            if isinstance(node, ast.Call):
                tail = _tail(ctx.resolve(node.func))
                if tail in _COLLECTIVES:
                    roots.append(info)
                    break

    # local call graph: bare-name calls made from each function's own frame
    for info in funcs.values():
        for node in _own_frame_nodes(info.node):
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
                info.calls.add(node.func.id)

    # propagate
    work = list(roots)
    while work:
        info = work.pop()
        if info.reachable:
            continue
        info.reachable = True
        for name in info.calls:
            for callee in by_name.get(name, []):
                if not callee.reachable:
                    work.append(callee)


# --------------------------------------------------------------------------- #
# per-function linear analysis
# --------------------------------------------------------------------------- #


class _FnAnalysis:
    """One pass over a single function frame: taint from parameters, RNG-key
    consumption, donated-buffer liveness, loop-scoped PRNGKey creation."""

    def __init__(
        self,
        ctx: _ModuleContext,
        info: _FunctionInfo,
        findings: Set[Finding],
        donate_sites: Dict[str, Tuple[Tuple[int, ...], Tuple[str, ...]]],
    ) -> None:
        self.ctx = ctx
        self.info = info
        self.findings = findings
        self.donate_sites = donate_sites
        self.reachable = info.reachable
        self.tainted: Set[str] = set()
        self.param_names: Set[str] = set()
        self.reassigned: Set[str] = set()
        self.consumed: Dict[str, int] = {}  # key name -> line of first consumption
        self.donated: Dict[str, int] = {}  # name -> line of donating call
        self.loop_depth = 0
        node = info.node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            args = node.args
            positional = list(args.posonlyargs) + list(args.args)
            for i, a in enumerate(positional + list(args.kwonlyargs)):
                # method receivers, jit-static params, and conventionally-
                # static metadata names are never traced values here
                if a.arg in (
                    "self", "cls", "shape", "shapes", "dtype", "dtypes", "axis", "axes",
                    "cfg", "config", "path", "paths", "name", "names", "layout", "mesh",
                    "spec", "specs", "treedef",
                ):
                    continue
                if i < len(positional) and i in info.static_argnums:
                    continue
                if a.arg in info.static_argnames:
                    continue
                self.tainted.add(a.arg)
                self.param_names.add(a.arg)
            if args.vararg:
                self.tainted.add(args.vararg.arg)
                self.param_names.add(args.vararg.arg)
            if args.kwarg:
                self.tainted.add(args.kwarg.arg)
                self.param_names.add(args.kwarg.arg)

    # -- helpers ----------------------------------------------------------- #

    def report(self, rule: str, node: ast.AST, message: str) -> None:
        line = getattr(node, "lineno", 0)
        if self.ctx.is_suppressed(rule, line):
            return
        self.findings.add(
            Finding(rule, self.ctx.path, line, getattr(node, "col_offset", 0) + 1, message, self.info.qualname)
        )

    def is_tainted(self, node: ast.AST) -> bool:
        """Structural taint: does evaluating ``node`` plausibly yield a traced
        value? Attribute access is the load-bearing precision rule — config
        and metadata reads (``actor.is_continuous``, ``leaf.shape``,
        ``layout.segments``) are static even on tracers, so attributes do NOT
        propagate taint except the handful of array views that do."""
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Constant):
            return False
        if isinstance(node, (ast.Lambda, ast.FunctionDef, ast.AsyncFunctionDef)):
            return False
        if isinstance(node, ast.Attribute):
            if node.attr in ("T", "mT", "at", "real", "imag"):
                return self.is_tainted(node.value)
            return False
        if isinstance(node, ast.Call):
            recv = isinstance(node.func, ast.Attribute) and self.is_tainted(node.func.value)
            return (
                recv
                or any(self.is_tainted(a) for a in node.args)
                or any(self.is_tainted(kw.value) for kw in node.keywords)
            )
        if isinstance(node, ast.Subscript):
            return self.is_tainted(node.value) or self.is_tainted(node.slice)
        return any(self.is_tainted(c) for c in ast.iter_child_nodes(node))

    def _is_bare_param(self, node: ast.AST) -> bool:
        """An unmodified parameter used bare: `if greedy:` / `for x in obs:`.
        These are overwhelmingly static flags / python containers at trace
        time; a traced bare test would have raised at trace time already."""
        return (
            isinstance(node, ast.Name)
            and node.id in self.param_names
            and node.id not in self.reassigned
        )

    _LOOP_EXEMPT_CALLS = {"zip", "enumerate", "range", "reversed", "sorted", "filter", "map", "list", "tuple"}

    def _iter_hazard(self, it: ast.AST) -> bool:
        """Is iterating ``it`` plausibly tracer iteration (the GL004 hazard)?
        Iterating a python container OF traced arrays is static unrolling and
        idiomatic; the hazard is iterating an array itself — which in this
        codebase surfaces as a Subscript (``batch["obs"]``) or a direct
        jnp/lax/random call result. Bare names stay quiet (a traced bare-name
        iteration raises at trace time anyway)."""
        if isinstance(it, (ast.List, ast.Tuple, ast.Set, ast.Dict)):
            return False
        if isinstance(it, ast.Call):
            f = it.func
            if isinstance(f, ast.Name) and f.id in self._LOOP_EXEMPT_CALLS:
                return False
            if isinstance(f, ast.Attribute) and f.attr in ("items", "keys", "values", "split"):
                return False
            resolved = self.ctx.resolve(f)
            if resolved and resolved.startswith(("jax.numpy.", "jax.lax.", "jax.random.", "jax.nn.")):
                return self.is_tainted(it)
            return False
        if isinstance(it, ast.Subscript):
            return self.is_tainted(it)
        return False

    def _dynamic_test(self, test: ast.expr) -> bool:
        """Is ``test`` a genuinely data-dependent condition on a traced
        value? (The GL004 if/while trigger.)"""
        if isinstance(test, ast.BoolOp):
            # `isinstance(x, float) and x <= 0` — the guard makes the whole
            # conjunction trace-time static
            if any(
                isinstance(v, ast.Call) and isinstance(v.func, ast.Name) and v.func.id == "isinstance"
                for v in test.values
            ):
                return False
            return any(self._dynamic_test(v) for v in test.values)
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            return self._dynamic_test(test.operand)
        if self._static_test(test) or self._is_bare_param(test):
            return False
        return self.is_tainted(test)

    def _assign_names(self, target: ast.expr) -> List[str]:
        names: List[str] = []
        for sub in ast.walk(target):
            if isinstance(sub, ast.Name) and isinstance(sub.ctx, (ast.Store,)):
                names.append(sub.id)
        return names

    def _reset(self, name: str) -> None:
        self.consumed.pop(name, None)
        self.donated.pop(name, None)

    # -- statement walk ---------------------------------------------------- #

    def run(self) -> None:
        body = getattr(self.info.node, "body", [])
        self.walk_block(body)

    def walk_block(self, stmts: Sequence[ast.stmt]) -> None:
        for stmt in stmts:
            self.walk_stmt(stmt)

    def walk_stmt(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            return  # separate frame, analyzed on its own
        if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            value = stmt.value
            if value is not None:
                self.visit_expr(value)
            targets = stmt.targets if isinstance(stmt, ast.Assign) else [stmt.target]
            rhs_tainted = value is not None and self.is_tainted(value)
            if isinstance(stmt, ast.AugAssign):
                # `x += 1` keeps x's existing taint
                rhs_tainted = rhs_tainted or self.is_tainted(stmt.target)
            for t in targets:
                for name in self._assign_names(t):
                    self._reset(name)
                    self.reassigned.add(name)
                    if rhs_tainted:
                        self.tainted.add(name)
                    else:
                        self.tainted.discard(name)
                # subscript/attribute stores still read their base expr
                self.visit_expr_reads_only(t)
        elif isinstance(stmt, ast.If):
            self.visit_expr(stmt.test)
            if self.reachable and self._dynamic_test(stmt.test):
                self.report("GL004", stmt, "Python `if` on a traced value — use lax.cond/jnp.where")
            self._walk_branches([stmt.body, stmt.orelse])
        elif isinstance(stmt, (ast.For, ast.AsyncFor)):
            self.visit_expr(stmt.iter)
            iter_tainted = self.is_tainted(stmt.iter)
            if self.reachable and self._iter_hazard(stmt.iter):
                self.report("GL004", stmt, "Python `for` over a traced value — use lax.scan/fori_loop")
            target_names = self._assign_names(stmt.target)
            # enumerate: the counter (first tuple element) is a python int
            untainted_targets: Set[str] = set()
            if (
                isinstance(stmt.iter, ast.Call)
                and isinstance(stmt.iter.func, ast.Name)
                and stmt.iter.func.id in ("enumerate", "range")
            ):
                if stmt.iter.func.id == "range":
                    untainted_targets.update(target_names)
                elif isinstance(stmt.target, ast.Tuple) and stmt.target.elts:
                    untainted_targets.update(self._assign_names(stmt.target.elts[0]))
            self.loop_depth += 1
            # two passes catch state that survives an iteration boundary (key
            # consumed in iteration i, consumed again in i+1); loop targets
            # are reassigned every iteration, so reset them per pass
            for _pass in range(2):
                for name in target_names:
                    self._reset(name)
                    if iter_tainted and name not in untainted_targets:
                        self.tainted.add(name)
                    else:
                        self.tainted.discard(name)
                    self.reassigned.add(name)
                self.walk_block(stmt.body)
            self.loop_depth -= 1
            self.walk_block(stmt.orelse)
        elif isinstance(stmt, ast.While):
            self.visit_expr(stmt.test)
            if self.reachable and self._dynamic_test(stmt.test):
                self.report("GL004", stmt, "Python `while` on a traced value — use lax.while_loop")
            self.loop_depth += 1
            self.walk_block(stmt.body)
            self.walk_block(stmt.body)
            self.loop_depth -= 1
            self.walk_block(stmt.orelse)
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                self.visit_expr(item.context_expr)
                if item.optional_vars is not None:
                    for name in self._assign_names(item.optional_vars):
                        self._reset(name)
                        if self.is_tainted(item.context_expr):
                            self.tainted.add(name)
            self.walk_block(stmt.body)
        elif isinstance(stmt, ast.Try):
            self.walk_block(stmt.body)
            for h in stmt.handlers:
                self.walk_block(h.body)
            self.walk_block(stmt.orelse)
            self.walk_block(stmt.finalbody)
        elif isinstance(stmt, (ast.Return, ast.Expr)):
            if stmt.value is not None:
                self.visit_expr(stmt.value)
        elif isinstance(stmt, ast.Delete):
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    self._reset(t.id)
                    self.tainted.discard(t.id)
        elif isinstance(stmt, (ast.Assert, ast.Raise)):
            for sub in ast.iter_child_nodes(stmt):
                if isinstance(sub, ast.expr):
                    self.visit_expr(sub)
        # Import/Pass/Break/Continue/Global/Nonlocal: nothing to do

    @staticmethod
    def _terminates(block: Sequence[ast.stmt]) -> bool:
        return bool(block) and isinstance(block[-1], (ast.Return, ast.Raise, ast.Break, ast.Continue))

    def _walk_branches(self, blocks: Sequence[Sequence[ast.stmt]]) -> None:
        merged_consumed: Dict[str, int] = dict(self.consumed)
        merged_donated: Dict[str, int] = dict(self.donated)
        merged_tainted: Set[str] = set(self.tainted)
        base = (dict(self.consumed), dict(self.donated), set(self.tainted))
        for block in blocks:
            self.consumed, self.donated, self.tainted = dict(base[0]), dict(base[1]), set(base[2])
            self.walk_block(block)
            if self._terminates(block):
                continue  # a returning/raising branch can't leak state past the If
            merged_consumed.update(self.consumed)
            merged_donated.update(self.donated)
            merged_tainted |= self.tainted
        self.consumed, self.donated, self.tainted = merged_consumed, merged_donated, merged_tainted

    @staticmethod
    def _static_test(test: ast.expr) -> bool:
        """Tests that are static even when a traced name appears in them:
        `x is None`, `isinstance(x, T)`, `len(x) == k` (shape is static)."""
        if isinstance(test, ast.Compare):
            if any(isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
                return True
            operands = [test.left] + list(test.comparators)
            if any(
                isinstance(o, ast.Call) and isinstance(o.func, ast.Name) and o.func.id == "len" for o in operands
            ):
                return True
        if isinstance(test, ast.Call) and isinstance(test.func, ast.Name) and test.func.id in ("isinstance", "hasattr", "len", "callable"):
            return True
        if isinstance(test, ast.BoolOp):
            return all(_FnAnalysis._static_test(v) for v in test.values)
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            return _FnAnalysis._static_test(test.operand)
        return False

    # -- expression walk ---------------------------------------------------- #

    def visit_expr_reads_only(self, node: ast.AST) -> None:
        """Check donated-buffer reads inside a store target's value exprs."""
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load):
                self._check_donated_read(sub)

    def _check_donated_read(self, name_node: ast.Name) -> None:
        line = self.donated.get(name_node.id)
        if line is not None:
            self.report(
                "GL005",
                name_node,
                f"`{name_node.id}` was donated to a jitted call on line {line} and must not be read again",
            )
            self.donated.pop(name_node.id, None)  # one report per donation

    def visit_expr(self, node: ast.AST) -> None:
        """Recursive expression visit in (approximate) evaluation order."""
        if isinstance(node, (ast.Lambda, ast.FunctionDef, ast.AsyncFunctionDef)):
            return
        if isinstance(node, ast.Call):
            self._visit_call(node)
            return
        if isinstance(node, ast.Name):
            if isinstance(node.ctx, ast.Load):
                self._check_donated_read(node)
            return
        if isinstance(node, ast.DictComp):
            self._check_dictcomp(node)
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp, ast.DictComp)):
            for gen in node.generators:
                self.visit_expr(gen.iter)
                if self.reachable and self._iter_hazard(gen.iter):
                    self.report(
                        "GL004",
                        node,
                        "Python comprehension over a traced value — use lax.scan/vmap",
                    )
            # visit element exprs for nested calls (names bound by the
            # comprehension shadow outer state only locally; close enough)
            if isinstance(node, ast.DictComp):
                self.visit_expr(node.key)
                self.visit_expr(node.value)
            else:
                self.visit_expr(node.elt if hasattr(node, "elt") else node)
            return
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.expr) or isinstance(child, (ast.keyword, ast.comprehension)):
                self.visit_expr(child if isinstance(child, ast.expr) else getattr(child, "value", child))

    def _visit_call(self, node: ast.Call) -> None:
        resolved = self.ctx.resolve(node.func)
        tail = _tail(resolved)

        # recurse into arguments FIRST (inner calls evaluate before the outer)
        for arg in node.args:
            self.visit_expr(arg)
        for kw in node.keywords:
            self.visit_expr(kw.value)
        if isinstance(node.func, ast.Attribute):
            self.visit_expr(node.func.value)

        # GL007: fresh PRNGKey inside a loop
        if self.loop_depth > 0 and resolved in ("jax.random.PRNGKey", "jax.random.key"):
            self.report(
                "GL007",
                node,
                "jax.random.PRNGKey created inside a loop — split/fold_in from a carried key instead",
            )

        # GL001: key consumption
        if _is_jax_random(resolved) and tail in _KEY_CONSUMERS:
            key_arg: Optional[ast.expr] = None
            if node.args:
                key_arg = node.args[0]
            else:
                for kw in node.keywords:
                    if kw.arg == "key":
                        key_arg = kw.value
            if isinstance(key_arg, ast.Name):
                prev = self.consumed.get(key_arg.id)
                if prev is not None:
                    self.report(
                        "GL001",
                        node,
                        f"RNG key `{key_arg.id}` already consumed on line {prev} — "
                        "split it (or fold_in) instead of reusing",
                    )
                else:
                    self.consumed[key_arg.id] = node.lineno

        # GL002/GL003: host syncs and numpy on traced values (jit-reachable only)
        if self.reachable:
            self._check_host_sync(node, resolved, tail)

        # GL005: donating call — mark donated argument names AFTER evaluating
        # the call (the call itself may legally read them)
        if isinstance(node.func, ast.Name) and node.func.id in self.donate_sites:
            positions, argnames = self.donate_sites[node.func.id]
            for pos in positions:
                if pos < len(node.args) and isinstance(node.args[pos], ast.Name):
                    self.donated[node.args[pos].id] = node.lineno
            for kw in node.keywords:
                if kw.arg in argnames and isinstance(kw.value, ast.Name):
                    self.donated[kw.value.id] = node.lineno

        # GL006: dict(zip(a.keys(), b.values()))
        if tail == "dict" and resolved in ("dict", "builtins.dict") and node.args:
            inner = node.args[0]
            if isinstance(inner, ast.Call) and _tail(self.ctx.resolve(inner.func)) == "zip" and len(inner.args) >= 2:
                srcs = []
                for z in inner.args[:2]:
                    if (
                        isinstance(z, ast.Call)
                        and isinstance(z.func, ast.Attribute)
                        and z.func.attr in ("keys", "values", "items")
                    ):
                        srcs.append(ast.dump(z.func.value))
                    else:
                        srcs.append(None)
                if all(s is not None for s in srcs) and srcs[0] != srcs[1]:
                    self.report(
                        "GL006",
                        node,
                        "dict(zip(a.keys(), b.values())) pairs keys and values from different objects — "
                        "dict order is insertion order, not a shared contract",
                    )

    def _check_host_sync(self, node: ast.Call, resolved: Optional[str], tail: Optional[str]) -> None:
        # method-style syncs: x.item(), x.tolist(), x.block_until_ready()
        if isinstance(node.func, ast.Attribute) and node.func.attr in ("item", "tolist", "block_until_ready"):
            if self.is_tainted(node.func.value):
                self.report(
                    "GL002",
                    node,
                    f"`.{node.func.attr}()` on a traced value forces a device->host sync inside a jitted body",
                )
            return
        # builtin casts on traced values
        if isinstance(node.func, ast.Name) and node.func.id in ("float", "int", "bool") and node.args:
            if self.is_tainted(node.args[0]):
                self.report(
                    "GL002",
                    node,
                    f"`{node.func.id}()` on a traced value concretizes it (host sync / trace error) — "
                    "keep it as a jnp scalar",
                )
            return
        if not _is_numpy(resolved):
            return
        arg_tainted = any(self.is_tainted(a) for a in node.args) or any(
            self.is_tainted(kw.value) for kw in node.keywords
        )
        if not arg_tainted:
            return
        if tail in ("asarray", "array", "copyto", "ascontiguousarray", "save", "savez"):
            self.report(
                "GL002",
                node,
                f"`np.{tail}` on a traced value pulls it to host inside a jitted body — "
                "stage explicitly outside the trace or use jnp",
            )
        else:
            self.report(
                "GL003",
                node,
                f"`np.{tail}` on a traced value runs on host per trace — use the jnp equivalent",
            )

    def _check_dictcomp(self, node: ast.DictComp) -> None:
        for gen in node.generators:
            it = gen.iter
            is_set = isinstance(it, ast.Set) or (
                isinstance(it, ast.Call) and _tail(self.ctx.resolve(it.func)) == "set"
            ) or (
                isinstance(it, ast.BinOp)
                and isinstance(it.op, (ast.BitAnd, ast.BitOr, ast.Sub))
                and any(
                    isinstance(s, ast.Call) and _tail(self.ctx.resolve(s.func)) == "set"
                    for s in (it.left, it.right)
                )
            )
            if is_set:
                self.report(
                    "GL006",
                    node,
                    "dict built by iterating a set: insertion order (= pytree structure) is "
                    "nondeterministic across processes — sort the keys",
                )


# --------------------------------------------------------------------------- #
# donation sites (module-wide pre-pass)
# --------------------------------------------------------------------------- #


def _collect_donate_sites(
    ctx: _ModuleContext, tree: ast.Module
) -> Dict[str, Tuple[Tuple[int, ...], Tuple[str, ...]]]:
    """Names bound to ``jax.jit(..., donate_argnums=/donate_argnames=...)``
    results, mapped to (donated positional indices, donated keyword names).
    Module-local, name-based — factories that return donating jits are out of
    scope (documented limitation)."""
    sites: Dict[str, Tuple[Tuple[int, ...], Tuple[str, ...]]] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign) or not isinstance(node.value, ast.Call):
            continue
        call = node.value
        if _tail(ctx.resolve(call.func)) != "jit":
            continue
        positions: Tuple[int, ...] = ()
        names: Tuple[str, ...] = ()
        for kw in call.keywords:
            if kw.arg not in ("donate_argnums", "donate_argnames"):
                continue
            try:
                val = ast.literal_eval(kw.value)
            except (ValueError, SyntaxError):
                continue
            vals = val if isinstance(val, (tuple, list)) else (val,)
            positions += tuple(v for v in vals if isinstance(v, int))
            names += tuple(v for v in vals if isinstance(v, str))
        if not positions and not names:
            continue
        for t in node.targets:
            if isinstance(t, ast.Name):
                sites[t.id] = (positions, names)
    return sites


# --------------------------------------------------------------------------- #
# GL008: donating jit over sharded shard_map outputs without pinned
# out_shardings (module-wide pre-pass, like the donation-site collection)
# --------------------------------------------------------------------------- #


def _contains_sharded_p(ctx: _ModuleContext, expr: ast.AST, sharded_names: Set[str]) -> bool:
    """Does ``expr`` plausibly denote a MESH-AXIS-SHARDED PartitionSpec —
    a ``P(...)``/``PartitionSpec(...)`` call with a string axis argument, or
    a name bound to one anywhere in the module (covers the
    ``spec = P(None, "dp") if cond else P()`` conditional idiom)?"""
    for node in ast.walk(expr):
        if isinstance(node, ast.Call) and _tail(ctx.resolve(node.func)) in ("P", "PartitionSpec"):
            if any(isinstance(a, ast.Constant) and isinstance(a.value, str) for a in node.args):
                return True
        if isinstance(node, ast.Name) and node.id in sharded_names:
            return True
    return False


def _iter_ordered_assigns(fn: ast.AST) -> Iterable[ast.Assign]:
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            yield node


def _gl008_donates(call: ast.Call) -> bool:
    for kw in call.keywords:
        if kw.arg in ("donate_argnums", "donate_argnames"):
            try:
                val = ast.literal_eval(kw.value)
            except (ValueError, SyntaxError):
                return True  # conditional donation (`(0, 1) if donate else ()`)
            if val not in ((), [], None):
                return True
    return False


def _check_gl008(
    ctx: _ModuleContext,
    tree: ast.Module,
    funcs: Dict[int, "_FunctionInfo"],
    findings: Set[Finding],
) -> None:
    """Per-FRAME analysis: shard_map bindings, spec names, and wrapper
    functions are all factory-local by idiom (every ``make_*`` builds its own
    ``shard_train``), so name maps must not leak across frames — a sharded
    ``shard_train`` in one factory must not indict the replicated one next
    door."""
    frames: List[Tuple[str, ast.AST]] = [("<module>", tree)]
    for info in funcs.values():
        if isinstance(info.node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            frames.append((info.qualname, info.node))

    for qualname, frame in frames:
        own = list(_own_frame_nodes(frame))
        # (1) frame-local names bound to sharded P specs
        sharded_names: Set[str] = set()
        for node in own:
            if isinstance(node, ast.Assign) and _contains_sharded_p(ctx, node.value, set()):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        sharded_names.add(t.id)
        # (2) frame-local shard_map bindings with out_specs shardedness
        shardmaps: Dict[str, bool] = {}
        for node in own:
            if (
                isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)
                and _tail(ctx.resolve(node.value.func)) == "shard_map"
            ):
                sharded = False
                for kw in node.value.keywords:
                    if kw.arg == "out_specs":
                        sharded = _contains_sharded_p(ctx, kw.value, sharded_names)
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        shardmaps[t.id] = sharded
        if not shardmaps:
            continue
        # (3) child wrapper functions whose return values data-flow from a
        # frame-local shard_map call (the `packed(...)` idiom: unpack the
        # tuple, restructure into dicts, return) — a two-pass propagation
        # over the child's assignments covers rebuilt containers
        wrappers: Dict[str, bool] = {}
        for child in own:
            if not isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            assigned_from: Dict[str, bool] = {}
            for _ in range(2):
                for node in _iter_ordered_assigns(child):
                    value = node.value
                    sharded2: Optional[bool] = None
                    if (
                        isinstance(value, ast.Call)
                        and isinstance(value.func, ast.Name)
                        and value.func.id in shardmaps
                    ):
                        sharded2 = shardmaps[value.func.id]
                    else:
                        hits = [
                            assigned_from[x.id]
                            for x in ast.walk(value)
                            if isinstance(x, ast.Name) and x.id in assigned_from
                        ]
                        if hits:
                            sharded2 = any(hits)
                    if sharded2 is None:
                        continue
                    for t in node.targets:
                        for x in ast.walk(t):
                            if isinstance(x, ast.Name):
                                assigned_from[x.id] = assigned_from.get(x.id, False) or sharded2
            for node in ast.walk(child):
                if isinstance(node, ast.Return) and node.value is not None:
                    for x in ast.walk(node.value):
                        if isinstance(x, ast.Name) and x.id in assigned_from:
                            wrappers[child.name] = wrappers.get(child.name, False) or assigned_from[x.id]
                        if (
                            isinstance(x, ast.Call)
                            and isinstance(x.func, ast.Name)
                            and x.func.id in shardmaps
                        ):
                            wrappers[child.name] = wrappers.get(child.name, False) or shardmaps[x.func.id]
        # (4) the hazard: a frame-local jit(target, donate_argnums=...,
        # <no out_shardings>) whose target returns sharded shard_map outputs
        for node in own:
            if not isinstance(node, ast.Call) or _tail(ctx.resolve(node.func)) != "jit":
                continue
            if not _gl008_donates(node):
                continue
            if any(kw.arg == "out_shardings" for kw in node.keywords):
                continue
            target = node.args[0] if node.args else None
            sharded = False
            target_name = None
            if isinstance(target, ast.Name):
                target_name = target.id
                sharded = shardmaps.get(target.id, False) or wrappers.get(target.id, False)
            elif isinstance(target, ast.Call) and _tail(ctx.resolve(target.func)) == "shard_map":
                target_name = "<inline shard_map>"
                for kw in target.keywords:
                    if kw.arg == "out_specs":
                        sharded = _contains_sharded_p(ctx, kw.value, sharded_names)
            if not sharded:
                continue
            if ctx.is_suppressed("GL008", node.lineno):
                continue
            findings.add(
                Finding(
                    "GL008",
                    ctx.path,
                    node.lineno,
                    node.col_offset + 1,
                    f"jit donates buffers and returns `{target_name}`'s mesh-axis-sharded shard_map "
                    "outputs without pinned out_shardings — a canonicalized (equivalent) output "
                    "placement keys a fresh C++ jit cache entry and silently recompiles the program "
                    "when the outputs are fed back; pin out_shardings on every fed-back output",
                    qualname,
                )
            )


# --------------------------------------------------------------------------- #
# public API
# --------------------------------------------------------------------------- #


def analyze_source(
    src: str,
    path: str = "<string>",
    select: Optional[Set[str]] = None,
    ignore: Optional[Set[str]] = None,
    stale_out: Optional[List[Finding]] = None,
) -> List[Finding]:
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding("GL000", path, e.lineno or 0, 1, f"syntax error: {e.msg}", "<module>")]
    ctx = _ModuleContext(src, path)
    for node in ast.walk(tree):
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            ctx.add_import(node)
    funcs = _collect_functions(tree)
    _mark_reachable(ctx, tree, funcs)
    donate_sites = _collect_donate_sites(ctx, tree)

    findings: Set[Finding] = set()
    # module level rides a synthetic frame (reachable=False: module body is
    # host code; GL001/GL005/GL006/GL007 still apply there)
    module_body_only = ast.Module(
        body=[s for s in tree.body if not isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef))],
        type_ignores=[],
    )
    module_info_frame = _FunctionInfo(module_body_only, "<module>")
    _FnAnalysis(ctx, module_info_frame, findings, donate_sites).run()
    for info in funcs.values():
        _FnAnalysis(ctx, info, findings, donate_sites).run()
    _check_gl008(ctx, tree, funcs, findings)

    if stale_out is not None:
        stale_out.extend(
            stale_suppression_findings(
                "graft-lint", RULES, ctx.suppressed, ctx.sup_used, path,
                select=select, ignore=ignore,
            )
        )

    out = [
        f
        for f in findings
        # GL000 (syntax error = file entirely unanalyzed) always surfaces:
        # a selective run must not report a broken file as clean
        if f.rule == "GL000"
        or ((select is None or f.rule in select) and (ignore is None or f.rule not in ignore))
    ]
    out.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return out


def iter_python_files(paths: Sequence[str]) -> List[str]:
    files: List[str] = []
    for p in paths:
        if os.path.isfile(p) and p.endswith(".py"):
            files.append(p)
        elif os.path.isdir(p):
            for root, dirs, names in os.walk(p):
                dirs[:] = [d for d in dirs if d not in ("__pycache__", ".git", ".hypothesis")]
                for n in sorted(names):
                    if n.endswith(".py"):
                        files.append(os.path.join(root, n))
    return sorted(set(files))


def analyze_paths(
    paths: Sequence[str],
    select: Optional[Set[str]] = None,
    ignore: Optional[Set[str]] = None,
    stale_out: Optional[List[Finding]] = None,
) -> List[Finding]:
    findings: List[Finding] = []
    for path in iter_python_files(paths):
        try:
            with open(path, "r", encoding="utf-8") as fh:
                src = fh.read()
        except (OSError, UnicodeDecodeError) as e:  # pragma: no cover
            findings.append(Finding("GL000", path, 0, 1, f"unreadable: {e}", "<module>"))
            continue
        rel = os.path.relpath(path)
        findings.extend(
            analyze_source(src, rel, select=select, ignore=ignore, stale_out=stale_out)
        )
    return findings


# --------------------------------------------------------------------------- #
# baseline
# --------------------------------------------------------------------------- #


def load_baseline(path: str) -> Dict[str, int]:
    with open(path, "r", encoding="utf-8") as fh:
        data = json.load(fh)
    if not isinstance(data, dict) or "findings" not in data:
        raise ValueError(f"malformed baseline file: {path}")
    return {str(k): int(v) for k, v in data["findings"].items()}


def write_baseline(path: str, findings: Sequence[Finding]) -> None:
    counts: Dict[str, int] = {}
    for f in findings:
        counts[fingerprint(f)] = counts.get(fingerprint(f), 0) + 1
    payload = {
        "comment": (
            "graft-lint baseline: pre-existing findings exempted from CI. "
            "Refresh with `python -m sheeprl_tpu.analysis <paths> --write-baseline`; "
            "NEW code should use inline `# graft-lint: disable=GLxxx` with a reason instead."
        ),
        "version": 1,
        "findings": dict(sorted(counts.items())),
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")


def apply_baseline(findings: Sequence[Finding], baseline: Dict[str, int]) -> List[Finding]:
    """Drop up to baseline[fingerprint] occurrences of each known finding;
    anything beyond its baselined count is reported."""
    budget = dict(baseline)
    out: List[Finding] = []
    for f in findings:
        fp = fingerprint(f)
        if budget.get(fp, 0) > 0:
            budget[fp] -= 1
        else:
            out.append(f)
    return out
