"""``hvdt-lint`` — AST-based project lint engine with a rule registry.

Every correctness contract this codebase relies on is (was) enforced by
convention and hand-written tests: knobs must be declared in
``common/config.py``, env-gated subsystems must keep a ``None``-when-unset zero-overhead path, nothing
feeding collective issue order may iterate a ``set``, and transient-
failure polls must ride ``resilience.retry.Backoff`` instead of bare
``time.sleep`` loops.  This module turns each convention into a checked
rule.

Ratcheting baseline: pre-existing violations are suppressed in a
baseline file (``.hvdt-lint-baseline.json`` at the repo root) **with a
written reason each**; anything not in the baseline fails the gate, so
the violation count can only go down.  Baseline keys hash the offending
source line (not its line number), so unrelated edits never churn the
file.

Pure stdlib (``ast``) — no jax import, safe to run anywhere, fast
enough to gate every CI run (``python -m horovod_tpu.analysis``).
"""

from __future__ import annotations

import ast
import dataclasses
import hashlib
import json
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

__all__ = [
    "Finding", "Rule", "RULES", "register", "lint_source", "lint_paths",
    "default_paths", "load_baseline", "save_baseline", "apply_baseline",
    "run_lint", "knob_table_markdown", "write_knob_table",
    "check_knob_docs", "declared_knobs", "metric_table_markdown",
    "write_metric_table", "check_metric_docs",
]

_KNOB_RE = re.compile(r"^HVDT_[A-Z0-9]+(?:_[A-Z0-9]+)*$")
_DOC_TOKEN_RE = re.compile(r"HVDT_[A-Z0-9_]*[A-Z0-9]")


@dataclasses.dataclass
class Finding:
    """One lint violation.  ``key`` identifies it across edits: rule +
    path + a hash of the stripped source line + an occurrence index (for
    identical lines in one file) — line numbers are display-only."""

    rule: str
    path: str
    line: int
    message: str
    snippet: str = ""
    occurrence: int = 0

    @property
    def key(self) -> str:
        h = hashlib.sha1(self.snippet.strip().encode()).hexdigest()[:12]
        return f"{self.rule}:{self.path}:{h}:{self.occurrence}"

    def format(self) -> str:
        return (f"{self.path}:{self.line}: [{self.rule}] {self.message}")


class Rule:
    """Base lint rule: subclass, set ``name``/``doc``, implement
    :meth:`check` yielding :class:`Finding`."""

    name = "base"
    doc = ""

    def check(self, tree: ast.Module, src: str, path: str,
              ctx: "LintContext") -> Iterable[Finding]:
        raise NotImplementedError


RULES: List[Rule] = []


def register(cls):
    RULES.append(cls())
    return cls


@dataclasses.dataclass
class LintContext:
    """Shared facts rules consult (knob registry, repo root)."""

    declared: Set[str]
    contract: Set[str]
    root: str = ""


def _line_of(src_lines: List[str], lineno: int) -> str:
    if 1 <= lineno <= len(src_lines):
        return src_lines[lineno - 1]
    return ""


def _parent_map(tree: ast.AST) -> Dict[ast.AST, ast.AST]:
    parents: Dict[ast.AST, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


def _ancestors(node: ast.AST,
               parents: Dict[ast.AST, ast.AST]) -> Iterable[ast.AST]:
    cur = parents.get(node)
    while cur is not None:
        yield cur
        cur = parents.get(cur)


def _attr_chain(node: ast.AST) -> Tuple[str, ...]:
    """('jax', 'lax', 'pcast') for nested Attribute/Name access."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return tuple(reversed(parts))


# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------


@register
class KnobDriftRule(Rule):
    """Every ``HVDT_*`` name read anywhere in the tree must be declared
    in ``common/config.py`` — as a :class:`Knob` (operator-facing, doc'd
    in the knob table) or a ``CONTRACT_VARS`` entry (launcher/driver
    internal wiring).  An undeclared read is a knob that silently does
    nothing when the operator typos it and never shows up in docs."""

    name = "knob-drift"
    doc = ("HVDT_* env reads must be declared in common/config.py "
           "(Knob or CONTRACT_VARS)")

    def check(self, tree, src, path, ctx):
        if path.endswith(os.path.join("common", "config.py")):
            return
        lines = src.splitlines()
        parents = _parent_map(tree)
        seen: Dict[str, int] = {}
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)
                    and _KNOB_RE.match(node.value)):
                continue
            # Skip docstrings / bare string statements.
            parent = parents.get(node)
            if isinstance(parent, ast.Expr):
                continue
            name = node.value
            if name in ctx.declared or name in ctx.contract:
                continue
            snippet = _line_of(lines, node.lineno)
            occ = seen.get(name, 0)
            seen[name] = occ + 1
            yield Finding(
                self.name, path, node.lineno,
                f"env var {name!r} is read but not declared in "
                f"common/config.py (add a Knob, or a CONTRACT_VARS "
                f"entry if it is launcher-internal wiring)",
                snippet=snippet, occurrence=occ)


@register
class ZeroOverheadGateRule(Rule):
    """An env-gated singleton accessor (module-level ``get_*`` that
    reads ``os.environ``) must carry a ``None``-when-unset path — the
    zero-overhead identity contract every optional subsystem
    (overlap/transport/faults/flight-recorder/telemetry) pins: feed
    sites branch on ``is None`` and the off path stays the exact
    pre-existing code objects."""

    name = "zero-overhead-gate"
    doc = ("env-gated get_*() accessors must have a None-when-unset "
           "path (zero-overhead identity contract)")

    def check(self, tree, src, path, ctx):
        lines = src.splitlines()
        for node in tree.body:
            if not (isinstance(node, ast.FunctionDef)
                    and re.match(r"^get_\w+$", node.name)):
                continue
            reads_env = any(
                _attr_chain(n)[-2:] == ("os", "environ")
                for n in ast.walk(node))
            if not reads_env:
                continue
            has_none = any(
                isinstance(n, ast.Constant) and n.value is None
                for n in ast.walk(node))
            if not has_none:
                yield Finding(
                    self.name, path, node.lineno,
                    f"{node.name}() reads os.environ but has no "
                    f"None-when-unset path — the disabled state must "
                    f"cost one env read and return None so feed sites "
                    f"can branch on `is None`",
                    snippet=_line_of(lines, node.lineno))


@register
class NondeterministicIterationRule(Rule):
    """Iterating a ``set``/``frozenset`` yields a hash-seed-dependent
    order.  Anything order-sensitive downstream — bucket plans,
    collective issue order, broadcast payloads — then differs across
    ranks, which IS the mismatched-collective desync.  Wrap in
    ``sorted(...)``."""

    name = "nondet-iteration"
    doc = ("no bare set/frozenset iteration (hash-order differs "
           "across ranks) — wrap in sorted()")

    def _is_set_expr(self, node: ast.AST) -> bool:
        if isinstance(node, (ast.Set, ast.SetComp)):
            return True
        return (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id in ("set", "frozenset"))

    def check(self, tree, src, path, ctx):
        lines = src.splitlines()
        seen: Dict[str, int] = {}
        for node in ast.walk(tree):
            target = None
            if isinstance(node, ast.For) and self._is_set_expr(node.iter):
                target = node.iter
            elif isinstance(node, (ast.ListComp, ast.SetComp,
                                   ast.GeneratorExp, ast.DictComp)):
                for gen in node.generators:
                    if self._is_set_expr(gen.iter):
                        target = gen.iter
                        break
            if target is None:
                continue
            snippet = _line_of(lines, target.lineno)
            occ = seen.get(snippet, 0)
            seen[snippet] = occ + 1
            yield Finding(
                self.name, path, target.lineno,
                "iterating a set/frozenset: hash order is per-process "
                "— if this order feeds collective issue order or any "
                "cross-rank payload it desyncs; wrap in sorted(...)",
                snippet=snippet, occurrence=occ)


@register
class MagicPeakFlopsRule(Rule):
    """Hardware peak-rate literals (device FLOP/s, HBM/link byte/s —
    anything >= 1e11) have exactly two homes: the
    ``telemetry/step_stats.py`` device-peak table (the MFU gauge) and
    the ``analysis/topology.py`` link-constants module (the cost
    model).  A peak literal anywhere else is a second source of truth
    that silently drifts when a new TPU generation lands — the fitter
    and the MFU gauge must read the same numbers."""

    name = "magic-peak-flops"
    doc = ("no hardware peak-rate literals (the topology.py "
           "PEAK_LITERAL window) outside telemetry/step_stats.py and "
           "analysis/topology.py")

    _ALLOWED = (os.path.join("telemetry", "step_stats.py"),
                os.path.join("analysis", "topology.py"))

    def check(self, tree, src, path, ctx):
        if any(path.endswith(a) for a in self._ALLOWED):
            return
        # The classification window itself lives in the constants
        # module this rule enforces — no literal here either.
        from .topology import PEAK_LITERAL_CEIL, PEAK_LITERAL_FLOOR

        lines = src.splitlines()
        seen: Dict[str, int] = {}
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Constant)
                    and isinstance(node.value, (int, float))
                    and not isinstance(node.value, bool)):
                continue
            try:
                v = abs(float(node.value))
            except OverflowError:
                v = float("inf")
            if not (PEAK_LITERAL_FLOOR <= v <= PEAK_LITERAL_CEIL):
                continue
            snippet = _line_of(lines, node.lineno)
            occ = seen.get(snippet, 0)
            seen[snippet] = occ + 1
            yield Finding(
                self.name, path, node.lineno,
                f"hardware-rate-sized literal {node.value!r}: peak "
                f"FLOP/s / bandwidth numbers live in telemetry/"
                f"step_stats.PEAK_BY_DEVICE_KIND or analysis/topology "
                f"constants — import them so the MFU gauge and the "
                f"cost model can never disagree",
                snippet=snippet, occurrence=occ)


@register
class MetricDriftRule(Rule):
    """Every metric the package constructs by literal name
    (``registry.counter("hvdt_...")`` / ``Counter("hvdt_...")`` /
    ``.gauge`` / ``.summary``) must be declared in the
    ``telemetry/metrics.py`` CATALOG — the registry ``docs/metrics.md``
    is generated from.  An undeclared construction is a metric that
    never reaches the docs and silently forks the naming scheme
    (the knob-drift contract applied to metrics)."""

    name = "metric-drift"
    doc = ("hvdt_*/serve_* metric constructions must be declared in "
           "telemetry/metrics.py CATALOG")

    _METHODS = ("counter", "gauge", "summary")
    _CLASSES = ("Counter", "Gauge", "Summary")
    _PREFIXES = ("hvdt_", "serve_")

    def check(self, tree, src, path, ctx):
        # The catalog module itself declares, it doesn't construct.
        if path.endswith(os.path.join("telemetry", "metrics.py")):
            return
        from ..telemetry.metrics import declared_metric

        lines = src.splitlines()
        seen: Dict[str, int] = {}
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            fn = node.func
            is_metric_call = (
                (isinstance(fn, ast.Attribute)
                 and fn.attr in self._METHODS)
                or (isinstance(fn, ast.Name) and fn.id in self._CLASSES))
            if not is_metric_call:
                continue
            arg = node.args[0]
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)):
                continue   # dynamic names ride catalog wildcards
            name = arg.value
            if not name.startswith(self._PREFIXES):
                continue   # collections.Counter & friends
            if declared_metric(name):
                continue
            snippet = _line_of(lines, node.lineno)
            occ = seen.get(name, 0)
            seen[name] = occ + 1
            yield Finding(
                self.name, path, node.lineno,
                f"metric {name!r} is constructed but not declared in "
                f"telemetry/metrics.py CATALOG — add a MetricSpec "
                f"(name/kind/labels/doc) and regenerate docs/metrics.md "
                f"(python -m horovod_tpu.analysis --metric-table "
                f"--write docs/metrics.md)",
                snippet=snippet, occurrence=occ)


@register
class SleepPollRule(Rule):
    """A ``time.sleep`` inside a ``while`` loop is a hand-rolled poll:
    fixed-interval retries synchronize into thundering herds and have
    no deadline.  ``resilience.retry.Backoff`` (exponential, jittered,
    deadline-bounded) is the mandated primitive."""

    name = "sleep-poll"
    doc = ("no bare time.sleep polling loops — use "
           "resilience.retry.Backoff")

    _EXEMPT = (os.path.join("resilience", "retry.py"),)

    def check(self, tree, src, path, ctx):
        if any(path.endswith(e) for e in self._EXEMPT):
            return
        lines = src.splitlines()
        parents = _parent_map(tree)
        from_time_sleep = any(
            isinstance(n, ast.ImportFrom) and n.module == "time"
            and any(a.name == "sleep" for a in n.names)
            for n in ast.walk(tree))
        seen: Dict[str, int] = {}
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            is_sleep = (_attr_chain(node.func)[-2:] == ("time", "sleep")
                        or (from_time_sleep
                            and isinstance(node.func, ast.Name)
                            and node.func.id == "sleep"))
            if not is_sleep:
                continue
            if not any(isinstance(a, (ast.While, ast.For))
                       for a in _ancestors(node, parents)):
                continue
            snippet = _line_of(lines, node.lineno)
            occ = seen.get(snippet, 0)
            seen[snippet] = occ + 1
            yield Finding(
                self.name, path, node.lineno,
                "bare time.sleep inside a loop — polling must ride "
                "resilience.retry.Backoff (exponential + full jitter "
                "+ deadline) so concurrent retriers decorrelate and "
                "dead dependencies cannot hang the caller",
                snippet=snippet, occurrence=occ)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


def declared_knobs() -> Tuple[Set[str], Set[str]]:
    """(knob names, contract var names) from the live registry."""
    from ..common import config

    contract = set(getattr(config, "CONTRACT_VARS", ()))
    return set(config.KNOBS), contract


def _make_context(root: str) -> LintContext:
    declared, contract = declared_knobs()
    return LintContext(declared=declared, contract=contract, root=root)


def lint_source(src: str, path: str,
                ctx: Optional[LintContext] = None,
                rules: Optional[Sequence[Rule]] = None) -> List[Finding]:
    """Run the rule registry over one source string (the unit-test
    entry point — fixtures feed crafted sources through here)."""
    ctx = ctx or _make_context("")
    tree = ast.parse(src)
    out: List[Finding] = []
    for rule in (rules if rules is not None else RULES):
        out.extend(rule.check(tree, src, path, ctx))
    return sorted(out, key=lambda f: (f.path, f.line, f.rule))


def default_paths(root: str) -> List[str]:
    """The lint scan set: every .py under horovod_tpu/ (the package
    lints itself, analysis/ included)."""
    pkg = os.path.join(root, "horovod_tpu")
    out: List[str] = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for f in sorted(filenames):
            if f.endswith(".py"):
                out.append(os.path.join(dirpath, f))
    return out


def lint_paths(paths: Sequence[str], root: str = "",
               rules: Optional[Sequence[Rule]] = None) -> List[Finding]:
    ctx = _make_context(root)
    out: List[Finding] = []
    for p in paths:
        try:
            with open(p, encoding="utf-8") as fh:
                src = fh.read()
        except OSError:
            continue
        rel = os.path.relpath(p, root) if root else p
        try:
            tree = ast.parse(src)
        except SyntaxError as e:
            out.append(Finding("syntax", rel, e.lineno or 0,
                               f"unparseable: {e.msg}"))
            continue
        for rule in (rules if rules is not None else RULES):
            out.extend(rule.check(tree, src, rel, ctx))
    return sorted(out, key=lambda f: (f.path, f.line, f.rule))


# ---------------------------------------------------------------------------
# Ratcheting baseline
# ---------------------------------------------------------------------------

BASELINE_NAME = ".hvdt-lint-baseline.json"


def load_baseline(path: str) -> Dict[str, str]:
    """key -> reason map; missing file = empty baseline."""
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except OSError:
        return {}
    return {s["key"]: s.get("reason", "")
            for s in doc.get("suppressions", [])}


def save_baseline(path: str, findings: Sequence[Finding],
                  reasons: Optional[Dict[str, str]] = None,
                  keep: Optional[Dict[str, str]] = None) -> None:
    """Write the ratchet file: current findings (with any reasons
    already on record) plus ``keep`` — non-lint suppressions (lock
    cycles) carried through an update."""
    reasons = reasons or {}
    doc = {
        "version": 1,
        "comment": ("hvdt-lint ratchet baseline: pre-existing "
                    "violations, each with a written reason.  New "
                    "findings FAIL the gate — fix them or add a "
                    "reasoned entry here.  Regenerate keys with "
                    "`python -m horovod_tpu.analysis --lint "
                    "--update-baseline`."),
        "suppressions": [
            {"key": f.key, "rule": f.rule, "path": f.path,
             "line": f.line,
             "reason": reasons.get(f.key, "baselined pre-existing "
                                   "violation — needs a written reason")}
            for f in findings] + [
            {"key": k, "rule": k.split(":", 1)[0], "reason": r}
            for k, r in sorted((keep or {}).items())],
    }
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=2)
        fh.write("\n")


def apply_baseline(findings: Sequence[Finding], baseline: Dict[str, str]
                   ) -> Tuple[List[Finding], List[Finding], List[str]]:
    """(new, suppressed, stale_keys): findings not in the baseline fail
    the gate; baseline keys matching nothing are stale (the violation
    was fixed — prune them to ratchet down)."""
    new, suppressed = [], []
    live_keys = set()
    for f in findings:
        if f.key in baseline:
            suppressed.append(f)
            live_keys.add(f.key)
        else:
            new.append(f)
    stale = sorted(k for k in baseline if k not in live_keys)
    return new, suppressed, stale


def run_lint(root: str, baseline_path: Optional[str] = None,
             update_baseline: bool = False,
             paths: Optional[Sequence[str]] = None
             ) -> Tuple[List[Finding], List[Finding], List[str]]:
    """Lint the repo against the ratchet baseline.  Returns
    (new, suppressed, stale_keys); the CI gate fails on any new."""
    bp = baseline_path or os.path.join(root, BASELINE_NAME)
    findings = lint_paths(paths or default_paths(root), root=root)
    baseline = load_baseline(bp)
    if update_baseline:
        save_baseline(bp, findings, reasons=baseline)
        return [], findings, []
    return apply_baseline(findings, baseline)


# ---------------------------------------------------------------------------
# Knob table: generated docs + drift check (the knob-table satellite)
# ---------------------------------------------------------------------------

_GENERATED_MARK = ("<!-- generated by `python -m horovod_tpu.analysis "
                   "--knob-table --write docs/knobs.md` — do not edit "
                   "by hand -->")


def _squash(doc: str) -> str:
    return re.sub(r"\s+", " ", doc).strip().replace("|", "\\|")


def knob_table_markdown() -> str:
    """The full knob registry as one markdown table (the docs rows the
    knob-drift killer generates instead of letting humans chase 125+
    knobs by hand)."""
    from ..common import config

    lines = ["| Knob | Default | Description |", "|---|---|---|"]
    for name in sorted(config.KNOBS):
        k = config.KNOBS[name]
        lines.append(f"| `{name}` | `{k.default!r}` | {_squash(k.doc)} |")
    contract = getattr(config, "CONTRACT_VARS", {})
    if contract:
        lines += ["", "### Internal env contract (not operator knobs)",
                  "",
                  "| Var | Set by / meaning |", "|---|---|"]
        for name in sorted(contract):
            lines.append(f"| `{name}` | {_squash(contract[name])} |")
    return "\n".join(lines)


def render_knob_doc() -> str:
    return "\n".join([
        "# Runtime knob registry",
        "",
        _GENERATED_MARK,
        "",
        "Single source of truth: `horovod_tpu/common/config.py`.  "
        "Precedence: CLI > env > config file > built-in default "
        "(docs/launcher.md).  `python -m horovod_tpu.analysis "
        "--knob-table --check` gates drift between this table, the "
        "registry, and every `HVDT_*` mention across docs/.",
        "",
        knob_table_markdown(),
        "",
    ])


def write_knob_table(path: str) -> str:
    with open(path, "w") as fh:
        fh.write(render_knob_doc())
    return path


_METRIC_MARK = ("<!-- generated by `python -m horovod_tpu.analysis "
                "--metric-table --write docs/metrics.md` — do not edit "
                "by hand -->")


def metric_table_markdown() -> str:
    """The metric CATALOG as markdown tables grouped by kind (the
    docs/knobs.md pattern applied to metrics)."""
    from ..telemetry.metrics import CATALOG

    lines = ["| Metric | Type | Labels | Description |",
             "|---|---|---|---|"]
    for name in sorted(CATALOG):
        s = CATALOG[name]
        labels = ", ".join(f"`{lb}`" for lb in s.labels) or "—"
        lines.append(f"| `{name}` | {s.kind} | {labels} | "
                     f"{_squash(s.doc)} |")
    return "\n".join(lines)


def render_metric_doc() -> str:
    return "\n".join([
        "# Metric registry",
        "",
        _METRIC_MARK,
        "",
        "Single source of truth: the CATALOG in "
        "`horovod_tpu/telemetry/metrics.py`.  Every "
        "Counter/Gauge/Summary the package constructs must be declared "
        "there — the `metric-drift` lint rule fails CI on any literal "
        "metric name missing from the catalog, and `python -m "
        "horovod_tpu.analysis --metric-table --check` gates drift "
        "between the catalog and this table.  Names ending in `*` are "
        "prefix wildcards for dynamically-formatted families.  See "
        "docs/observability.md for semantics and scrape examples.",
        "",
        metric_table_markdown(),
        "",
    ])


def write_metric_table(path: str) -> str:
    with open(path, "w") as fh:
        fh.write(render_metric_doc())
    return path


def check_metric_docs(root: str) -> List[str]:
    """Freshness check: docs/metrics.md must match the generated
    catalog table."""
    problems: List[str] = []
    metrics_md = os.path.join(root, "docs", "metrics.md")
    try:
        current = open(metrics_md).read()
    except OSError:
        problems.append("docs/metrics.md missing — generate it with "
                        "`python -m horovod_tpu.analysis --metric-table "
                        "--write docs/metrics.md`")
        current = ""
    if current and current.strip() != render_metric_doc().strip():
        problems.append("docs/metrics.md is stale vs telemetry/metrics."
                        "py CATALOG — regenerate with `python -m "
                        "horovod_tpu.analysis --metric-table --write "
                        "docs/metrics.md`")
    return problems


def check_knob_docs(root: str) -> List[str]:
    """Drift check between the registry and the docs tree.  Failures:

    * ``docs/knobs.md`` missing or stale vs the generated table (every
      declared knob therefore appears in a docs knob table);
    * any ``HVDT_*`` token anywhere in ``docs/*.md`` that names neither
      a declared knob, a contract var, nor a declared-name prefix
      (wildcard mentions like ``HVDT_SERVE_*``).
    """
    problems: List[str] = []
    declared, contract = declared_knobs()
    known = declared | set(contract)

    knobs_md = os.path.join(root, "docs", "knobs.md")
    try:
        current = open(knobs_md).read()
    except OSError:
        problems.append("docs/knobs.md missing — generate it with "
                        "`python -m horovod_tpu.analysis --knob-table "
                        "--write docs/knobs.md`")
        current = ""
    if current and current.strip() != render_knob_doc().strip():
        problems.append("docs/knobs.md is stale vs common/config.py — "
                        "regenerate with `python -m horovod_tpu."
                        "analysis --knob-table --write docs/knobs.md`")

    docs_dir = os.path.join(root, "docs")
    try:
        md_files = sorted(f for f in os.listdir(docs_dir)
                          if f.endswith(".md"))
    except OSError:
        md_files = []
    for f in md_files:
        text = open(os.path.join(docs_dir, f)).read()
        for tok in sorted(set(_DOC_TOKEN_RE.findall(text))):
            if tok in known:
                continue
            if any(name.startswith(tok + "_") for name in known):
                continue   # prefix/wildcard mention (HVDT_SERVE_*)
            problems.append(
                f"docs/{f}: mentions {tok!r} which is neither a "
                f"declared knob nor a CONTRACT_VARS entry")
    return problems
