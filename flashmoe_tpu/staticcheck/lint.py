"""AST lint pass over ``flashmoe_tpu/`` and ``tests/``.

Five rule families, all pure AST — no imports of the heavy modules, no
pytest-in-pytest:

* **in-graph hygiene** — functions that end up inside a trace (bodies
  handed to ``shard_map`` / ``jit`` / ``lax.scan`` / ``pallas_call`` /
  ..., transitively through calls and ``functools.partial``) must not
  call host-time APIs (``time.time``, ``np.random``, ``random.*``, ...)
  whose results would be frozen into the compiled graph, and must not
  branch Python-``if``/``while`` on ``jnp.*`` expressions (tracer
  leakage — the branch would specialize on one traced value).  A line
  may opt out with a ``# staticcheck: ok`` comment plus a reason.
* **decision-name registry** — every literal passed to
  ``metrics.decision("x.y", ...)`` / ``last_decision("x.y")`` must be
  declared in ``utils/telemetry.py:DECISION_NAMES``; a typo'd name used
  to vanish silently into JSONL.  Non-literal names are flagged too:
  the registry cannot vouch for a name it cannot see.
* **span-name registry** — the same contract for phase spans: every
  literal handed to ``trace_span(...)`` / a profiler ``section(...)``
  must be declared in ``utils/telemetry.py:SPAN_NAMES`` (chunked
  pipeline f-strings must start with a registered base + ``.``) — a
  typo'd span silently forks the phase timeline the cost ledger joins.
* **doc sync** — every registered decision name must appear in
  docs/OBSERVABILITY.md, and every name in that doc's decision table
  must be registered (the table is generated from the registry:
  ``telemetry.decision_table_markdown``); span names likewise
  (``telemetry.span_table_markdown``).
* **slow-mark budget guard** — migrated from tests/test_collection.py
  (which now thinly wraps this engine): tests that run chaos drills
  (any test file) or execute shard_map MoE layers (files listed in
  ``SHARD_MAP_EXEC_FILES``; ``jax.make_jaxpr`` tracing is exempt — it
  is exactly what this package does) must carry ``@pytest.mark.slow``.
  ``slow`` means multi-process tests, drills and any case over 40 s;
  the gate itself executes the mesh paths in the other test files.
"""

from __future__ import annotations

import ast
import os
import re

from flashmoe_tpu.staticcheck.registry import Violation

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PKG_DIR = os.path.join(REPO_ROOT, "flashmoe_tpu")
TESTS_DIR = os.path.join(REPO_ROOT, "tests")
OBS_DOC = os.path.join(REPO_ROOT, "docs", "OBSERVABILITY.md")

#: suppression marker: a line carrying this comment (with a reason) is
#: exempt from the in-graph rules
WAIVER = "# staticcheck: ok"

# ---------------------------------------------------------------------
# slow-mark rule (migrated from tests/test_collection.py)
# ---------------------------------------------------------------------

#: calls that make a test a chaos DRILL (a full resilient training job)
DRILL_CALLS = frozenset({"run_drill", "run_matrix"})

#: calls that EXECUTE a shard_map'd MoE layer on the virtual mesh
#: (jax.make_jaxpr over the same layer is trace-only and stays fast)
SHARD_MAP_CALLS = frozenset({"ep_moe_layer", "ragged_ep_moe_layer",
                             "fused_ep_moe_layer"})

#: files the shard_map-execution rule applies to (drills apply
#: everywhere).  Other test files budget their executions individually;
#: add a file here to opt it into the strict rule.
SHARD_MAP_EXEC_FILES = ("test_chaos.py",)

#: wrappers whose function arguments end up inside a trace
_TRACE_WRAPPERS = frozenset({
    "shard_map", "jit", "pallas_call", "scan", "cond", "switch",
    "while_loop", "fori_loop", "vmap", "pmap", "grad",
    "value_and_grad", "checkpoint", "remat", "custom_vjp",
    "custom_jvp", "make_jaxpr", "eval_shape",
})

#: dotted call names whose values must never be baked into a traced
#: graph (host wall-clock / host randomness)
_FORBIDDEN_CALLS = {
    "time.time", "time.perf_counter", "time.monotonic",
    "time.process_time",
    "np.random", "numpy.random",
    "random.random", "random.randint", "random.uniform",
    "random.choice", "random.sample", "random.shuffle",
    "random.gauss",
    "datetime.now", "datetime.utcnow", "datetime.datetime.now",
    "os.urandom", "secrets.token_bytes", "secrets.randbits",
    "uuid.uuid4",
}

#: roots whose calls inside an ``if``/``while`` test mean Python is
#: branching on a tracer
_TRACER_ROOTS = ("jnp.", "jax.numpy.")


def _dotted(node) -> str | None:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _called_names(node: ast.AST) -> set:
    names = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            f = sub.func
            if isinstance(f, ast.Name):
                names.add(f.id)
            elif isinstance(f, ast.Attribute):
                names.add(f.attr)
    return names


def _is_slow_marked(fn) -> bool:
    return any("mark.slow" in ast.unparse(dec)
               for dec in fn.decorator_list)


def _test_functions(tree):
    for node in tree.body:
        if isinstance(node, ast.FunctionDef) and \
                node.name.startswith("test_"):
            yield node


def _parse(path: str):
    with open(path) as f:
        src = f.read()
    return ast.parse(src, filename=path), src.splitlines()


def _iter_py(root: str):
    for dirpath, dirnames, filenames in os.walk(root):
        # sort the traversal in place: os.walk yields subdirectories
        # in FILESYSTEM order, and the in-graph rule's bare-name index
        # resolves duplicate function names to the first file seen —
        # an unsorted walk made the lint verdict depend on checkout
        # inode order (found when a fresh container flagged a chain a
        # dev tree never built)
        dirnames.sort()
        if "__pycache__" in dirpath:
            continue
        for name in sorted(filenames):
            if name.endswith(".py"):
                yield os.path.join(dirpath, name)


def check_slow_marks(test_files=None) -> list[Violation]:
    """The tier-1 budget guard, generalized: drills anywhere, shard_map
    executions in the strict files."""
    out = []
    if test_files is None:
        test_files = [os.path.join(TESTS_DIR, n)
                      for n in sorted(os.listdir(TESTS_DIR))
                      if n.startswith("test_") and n.endswith(".py")]
    for path in test_files:
        name = os.path.basename(path)
        tree, _src = _parse(path)
        strict = name in SHARD_MAP_EXEC_FILES
        for fn in _test_functions(tree):
            called = _called_names(fn)
            if called & DRILL_CALLS and not _is_slow_marked(fn):
                out.append(Violation(
                    "lint", "slow-mark", f"{name}::{fn.name}",
                    "runs a chaos drill (a full resilient training "
                    "job) without @pytest.mark.slow — drills belong "
                    "outside the tier-1 gate (ROADMAP.md)"))
            if strict and called & SHARD_MAP_CALLS \
                    and "make_jaxpr" not in called \
                    and not _is_slow_marked(fn):
                out.append(Violation(
                    "lint", "slow-mark", f"{name}::{fn.name}",
                    "executes a shard_map MoE layer without "
                    "@pytest.mark.slow (jax.make_jaxpr tracing is the "
                    "fast-lane alternative)"))
    return out


def slow_mark_selfcheck() -> list[Violation]:
    """The scan must actually FIND the known drill/execution tests —
    an empty scan would make the guard vacuously green."""
    path = os.path.join(TESTS_DIR, "test_chaos.py")
    if not os.path.exists(path):
        return [Violation("lint", "slow-mark-selfcheck", "test_chaos.py",
                          "known drill file is missing")]
    tree, _src = _parse(path)
    drills, execs = [], []
    for fn in _test_functions(tree):
        called = _called_names(fn)
        if called & DRILL_CALLS:
            drills.append(fn.name)
        if called & SHARD_MAP_CALLS and "make_jaxpr" not in called:
            execs.append(fn.name)
    out = []
    if "test_drill_matrix" not in drills:
        out.append(Violation(
            "lint", "slow-mark-selfcheck", "test_chaos.py",
            f"drill scan no longer sees test_drill_matrix ({drills})"))
    if not execs:
        out.append(Violation(
            "lint", "slow-mark-selfcheck", "test_chaos.py",
            "shard_map-execution scan found nothing — rule is vacuous"))
    return out


# ---------------------------------------------------------------------
# decision-name registry rule
# ---------------------------------------------------------------------

def check_decision_names(files=None) -> list[Violation]:
    from flashmoe_tpu.utils.telemetry import DECISION_NAMES

    out = []
    if files is None:
        # tests included: a typo'd name in `last_decision("preempt.drian")`
        # makes the test silently assert against None — the same
        # vanish-into-JSONL failure this rule closes in the package
        files = list(_iter_py(PKG_DIR)) + list(_iter_py(TESTS_DIR))
    for path in files:
        rel = os.path.relpath(path, REPO_ROOT)
        tree, lines = _parse(path)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            attr = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else None)
            if attr not in ("decision", "last_decision"):
                continue
            if not node.args:
                continue
            # skip the registry's own definition site and methods on
            # unrelated objects taking non-name first args
            arg = node.args[0]
            line = lines[node.lineno - 1] if node.lineno <= len(
                lines) else ""
            if WAIVER in line:
                continue
            if isinstance(arg, ast.Constant) and isinstance(arg.value,
                                                            str):
                if arg.value not in DECISION_NAMES:
                    out.append(Violation(
                        "lint", "decision-name",
                        f"{rel}:{node.lineno}",
                        f"decision name {arg.value!r} is not declared "
                        f"in utils/telemetry.py:DECISION_NAMES — typo'd "
                        f"names vanish silently into JSONL; register "
                        f"it (with a one-line meaning), fix the "
                        f"spelling, or waive with "
                        f"'{WAIVER} <reason>'"))
            elif attr == "decision" and not (
                    isinstance(arg, ast.Name) and arg.id == "self"):
                out.append(Violation(
                    "lint", "decision-name", f"{rel}:{node.lineno}",
                    "non-literal decision name: the registry "
                    "cannot vouch for a computed name — pass a "
                    "registered literal (or waive with "
                    "'# staticcheck: ok <reason>')"))
    return out


# ---------------------------------------------------------------------
# span-name registry rule
# ---------------------------------------------------------------------

def _span_base(name: str) -> str:
    """Chunked pipeline spans carry a numeric suffix
    (``moe.expert.3``) — merge onto the registered base.  Delegates to
    :func:`flashmoe_tpu.profiler.spans.merged_phase` so the lint and
    the timeline can never disagree on the suffix convention."""
    from flashmoe_tpu.profiler.spans import merged_phase

    return merged_phase(name)


def check_span_names(files=None) -> list[Violation]:
    """Every literal handed to ``trace_span(...)`` or a profiler
    ``section(...)`` must be declared in
    ``utils/telemetry.py:SPAN_NAMES`` — a misspelled span silently
    forks the phase timeline the cost ledger joins on.  F-string spans
    (the chunked pipeline's ``f"moe.expert.{ck}"``) must start with a
    registered base followed by ``.``; a wholly computed name on
    ``trace_span`` is flagged (waivable) because the registry cannot
    vouch for it.  Non-literal ``section`` calls — plain variables and
    f-strings without a registered literal base — are skipped: the
    name is too generic to attribute (the profiler's own dispatcher
    forwards a variable)."""
    from flashmoe_tpu.utils.telemetry import SPAN_NAMES

    out = []
    if files is None:
        files = list(_iter_py(PKG_DIR)) + list(_iter_py(TESTS_DIR))

    def unregistered(rel, lineno, name):
        out.append(Violation(
            "lint", "span-name", f"{rel}:{lineno}",
            f"span name {name!r} is not declared in "
            f"utils/telemetry.py:SPAN_NAMES — a typo'd span forks the "
            f"phase timeline; register it (with a one-line meaning), "
            f"fix the spelling, or waive with '{WAIVER} <reason>'"))

    for path in files:
        rel = os.path.relpath(path, REPO_ROOT)
        tree, lines = _parse(path)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            f = node.func
            attr = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else None)
            # _phase: the serving engine's phase boundary, which opens
            # a trace_span under the name it is handed
            if attr not in ("trace_span", "section", "_phase"):
                continue
            line = lines[node.lineno - 1] if node.lineno <= len(
                lines) else ""
            if WAIVER in line:
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value,
                                                            str):
                if _span_base(arg.value) not in SPAN_NAMES:
                    unregistered(rel, node.lineno, arg.value)
            elif isinstance(arg, ast.JoinedStr):
                head = arg.values[0] if arg.values else None
                if isinstance(head, ast.Constant) and isinstance(
                        head.value, str) and head.value.endswith("."):
                    if head.value[:-1] not in SPAN_NAMES:
                        unregistered(rel, node.lineno, head.value + "*")
                elif attr == "trace_span":
                    # section() f-strings without a literal base are
                    # skipped like other non-literal section names —
                    # the documented contract only binds trace_span
                    out.append(Violation(
                        "lint", "span-name", f"{rel}:{node.lineno}",
                        "f-string span must start with a registered "
                        "base name followed by '.' (chunk-suffix "
                        "convention) — the registry cannot vouch for "
                        "a computed prefix"))
            elif attr == "trace_span" or (
                    attr == "_phase" and not (
                        isinstance(arg, ast.Constant)
                        and arg.value is None)):
                out.append(Violation(
                    "lint", "span-name", f"{rel}:{node.lineno}",
                    "non-literal span name: the registry cannot vouch "
                    "for a computed name — pass a registered literal "
                    f"(or waive with '{WAIVER} <reason>')"))
    return out


def check_span_doc_sync() -> list[Violation]:
    """Every registered span name must appear in docs/OBSERVABILITY.md
    (the span table is generated from the registry:
    ``telemetry.span_table_markdown``)."""
    from flashmoe_tpu.utils.telemetry import SPAN_NAMES

    if not os.path.exists(OBS_DOC):
        return [Violation("lint", "span-doc", "docs/OBSERVABILITY.md",
                          "document is missing")]
    with open(OBS_DOC) as f:
        doc = f.read()
    out = []
    for name in sorted(SPAN_NAMES):
        if f"`{name}`" not in doc:
            out.append(Violation(
                "lint", "span-doc", name,
                "registered span name is absent from "
                "docs/OBSERVABILITY.md — regenerate the table with "
                "telemetry.span_table_markdown()"))
    return out


def check_decision_doc_sync() -> list[Violation]:
    from flashmoe_tpu.utils.telemetry import DECISION_NAMES, SPAN_NAMES

    out = []
    if not os.path.exists(OBS_DOC):
        return [Violation("lint", "decision-doc", "docs/OBSERVABILITY.md",
                          "document is missing")]
    with open(OBS_DOC) as f:
        doc = f.read()
    for name in sorted(DECISION_NAMES):
        if f"`{name}`" not in doc:
            out.append(Violation(
                "lint", "decision-doc", name,
                "registered decision name is absent from "
                "docs/OBSERVABILITY.md — regenerate the table with "
                "telemetry.decision_table_markdown()"))
    for name in re.findall(r"^\| `([a-z_]+\.[a-z_.]+)` \|", doc,
                           re.MULTILINE):
        # dotted table rows are either decisions or spans (the span
        # table of the phase profiler shares the doc)
        if name not in DECISION_NAMES and name not in SPAN_NAMES:
            out.append(Violation(
                "lint", "decision-doc", name,
                "documented dotted name is registered neither in "
                "DECISION_NAMES nor SPAN_NAMES (stale doc row?)"))
    return out


# ---------------------------------------------------------------------
# in-graph hygiene rule
# ---------------------------------------------------------------------

def _module_functions(tree) -> dict:
    """name -> FunctionDef for module-level and one-level-nested defs."""
    fns = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fns.setdefault(node.name, node)
    return fns


def _seed_traced(tree, fns) -> set:
    """Names of functions this module hands to trace wrappers —
    directly, or through a ``functools.partial`` binding."""
    partial_of: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and isinstance(node.value,
                                                       ast.Call):
            callee = _dotted(node.value.func) or ""
            if callee.endswith("partial") and node.value.args and \
                    isinstance(node.value.args[0], ast.Name):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        partial_of[tgt.id] = node.value.args[0].id
    seeds = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        callee = _dotted(node.func) or ""
        if callee.split(".")[-1] not in _TRACE_WRAPPERS:
            continue
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            if isinstance(arg, ast.Name):
                seeds.add(partial_of.get(arg.id, arg.id))
            elif isinstance(arg, ast.Call):
                inner = _dotted(arg.func) or ""
                if inner.endswith("partial") and arg.args and \
                        isinstance(arg.args[0], ast.Name):
                    seeds.add(arg.args[0].id)
    return {s for s in seeds if s in fns}


def check_in_graph(files=None) -> list[Violation]:
    """Forbidden host-side patterns inside (transitively) traced
    functions."""
    out = []
    if files is None:
        files = list(_iter_py(PKG_DIR))
    # global index: function name -> (rel, FunctionDef, lines), for
    # cross-module transitive closure (unique last-segment resolution —
    # a lint, not a type checker)
    index: dict[str, tuple[str, ast.AST, list]] = {}
    per_file = []
    for path in files:
        rel = os.path.relpath(path, REPO_ROOT)
        tree, lines = _parse(path)
        fns = _module_functions(tree)
        per_file.append((rel, tree, fns, lines))
        for name, fn in fns.items():
            index.setdefault(name, (rel, fn, lines))

    # BFS from every module's seeds through the call graph
    queue = []
    visited = set()
    for rel, tree, fns, lines in per_file:
        for s in _seed_traced(tree, fns):
            key = (rel, s)
            if key not in visited:
                visited.add(key)
                queue.append((rel, fns[s], lines))
    while queue:
        rel, fn, lines = queue.pop()
        out.extend(_scan_traced_fn(rel, fn, lines))
        for called in sorted(_called_names(fn)):
            if called in index:
                crel, cfn, clines = index[called]
                key = (crel, cfn.name)
                if key not in visited:
                    visited.add(key)
                    queue.append((crel, cfn, clines))
    return out


def _scan_traced_fn(rel, fn, lines) -> list[Violation]:
    out = []

    def waived(node) -> bool:
        i = node.lineno - 1
        return i < len(lines) and WAIVER in lines[i]

    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            callee = _dotted(node.func)
            if callee in _FORBIDDEN_CALLS or (
                    callee and (callee.startswith("np.random.")
                                or callee.startswith("numpy.random."))):
                if not waived(node):
                    out.append(Violation(
                        "lint", "in-graph-host-call",
                        f"{rel}:{node.lineno} ({fn.name})",
                        f"{callee}() inside traced code: the host "
                        f"value would be frozen into the compiled "
                        f"graph (and differ across ranks/restarts) — "
                        f"pass it in as an argument, or waive with "
                        f"'{WAIVER} <reason>'"))
        elif isinstance(node, (ast.If, ast.While)):
            for sub in ast.walk(node.test):
                if isinstance(sub, ast.Call):
                    callee = _dotted(sub.func) or ""
                    if any(callee.startswith(r) for r in _TRACER_ROOTS):
                        if not waived(node):
                            out.append(Violation(
                                "lint", "tracer-branch",
                                f"{rel}:{node.lineno} ({fn.name})",
                                f"Python {type(node).__name__.lower()} "
                                f"on {callee}(...): branching on a "
                                f"tracer value freezes one branch into "
                                f"the graph (or raises a "
                                f"ConcretizationError) — use jnp.where "
                                f"/ lax.cond"))
                        break
    return out


# ---------------------------------------------------------------------
# engine entry
# ---------------------------------------------------------------------

def run_lint(paths=None) -> list[Violation]:
    """Run every lint rule.  ``paths`` restricts the decision-name and
    in-graph rules to an explicit file list (tests plant violations in
    tmp files); the slow-mark and doc-sync rules always run on the
    repo unless ``paths`` is given."""
    out: list[Violation] = []
    if paths is not None:
        files = [os.path.abspath(p) for p in paths]
        out.extend(check_decision_names(files))
        out.extend(check_span_names(files))
        out.extend(check_in_graph(files))
        return out
    out.extend(check_slow_marks())
    out.extend(slow_mark_selfcheck())
    out.extend(check_decision_names())
    out.extend(check_decision_doc_sync())
    out.extend(check_span_names())
    out.extend(check_span_doc_sync())
    out.extend(check_in_graph())
    return out
