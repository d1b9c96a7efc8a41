"""fedlint framework: file loader, scope/call-graph builder, rule
registry, suppressions, baseline ratchet, JSON + human output.

The analyzer is deliberately self-contained (stdlib ``ast`` only — no
jax import, no runtime import of the code under analysis): rules see a
:class:`Project` of parsed modules plus two derived indexes,

- a **call graph** resolving three call shapes — bare names to
  same-module (or from-imported) functions, ``self.m(...)`` /
  ``cls.m(...)`` to methods of the enclosing class, and
  ``mod.f(...)`` through the module's import aliases — precise enough
  to follow real code, conservative enough to never crash on dynamic
  dispatch (unresolvable calls simply add no edge);
- the **jit-reachable set**: every function transitively callable from
  a compile site — a call to ``jax.jit`` / ``jit`` / ``pjit``,
  ``memscope.ProgramSite``, ``shard_map``, or
  ``elastic.CompiledRoundCache`` (first positional argument is the
  traced callable; lambdas count, and their bodies are walked in the
  enclosing module scope). Rules like jit-purity and traced-branch key
  off this set, so "is this function allowed to touch the host?" is
  answered by the graph, not by convention.

Findings are identified by a line-number-free fingerprint
``sha1(rule|path|scope|message)`` so the ``--baseline`` ratchet file
survives unrelated edits: pre-existing findings stay frozen, anything
new fails the run (docs/STATIC_ANALYSIS.md "Baseline policy").
"""

from __future__ import annotations

import ast
import dataclasses
import fnmatch
import hashlib
import json
import os
import re
from typing import Any, Callable, Iterable, Iterator

__all__ = [
    "AnalysisConfig", "Finding", "FunctionInfo", "ModuleInfo", "Project",
    "RULES", "Rule", "load_baseline", "register_rule", "run_analysis",
    "write_baseline",
]

#: names whose call mints a jit compile site; the first positional
#: argument is the traced callable (fedavg.py `ProgramSite(self._round,
#: ...)`, elastic.py `CompiledRoundCache(fn, ...)`, jax.shard_map)
JIT_ENTRY_NAMES = frozenset(
    {"jit", "pjit", "ProgramSite", "shard_map", "CompiledRoundCache"}
)

_SUPPRESS_RE = re.compile(
    r"#\s*fedlint:\s*disable=([A-Za-z0-9_,\- ]+)"
)
_SUPPRESS_FILE_RE = re.compile(
    r"#\s*fedlint:\s*disable-file=([A-Za-z0-9_,\- ]+)"
)


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation.

    ``scope`` is the enclosing function qualname (or ``<module>``) and
    feeds the fingerprint together with rule, path, and message — NOT
    the line number, so baselined findings survive unrelated edits that
    shift lines."""

    rule: str
    path: str  # repo-root-relative, '/'-separated
    line: int
    message: str
    scope: str = "<module>"

    @property
    def fingerprint(self) -> str:
        key = f"{self.rule}|{self.path}|{self.scope}|{self.message}"
        return hashlib.sha1(key.encode()).hexdigest()[:12]

    def to_dict(self) -> dict[str, Any]:
        return {
            "rule": self.rule, "path": self.path, "line": self.line,
            "scope": self.scope, "message": self.message,
            "fingerprint": self.fingerprint,
        }

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


@dataclasses.dataclass
class FunctionInfo:
    """One function/method definition plus its outgoing call edges."""

    qualname: str  # "pkg.mod:Class.method" | "pkg.mod:func"
    module: "ModuleInfo"
    node: ast.AST  # FunctionDef | AsyncFunctionDef | Lambda
    cls: str | None = None
    #: resolved callee qualnames (filled by Project._link_calls)
    callees: set[str] = dataclasses.field(default_factory=set)

    @property
    def name(self) -> str:
        return self.qualname.rsplit(".", 1)[-1].rsplit(":", 1)[-1]


class ModuleInfo:
    """One parsed source file: AST, import aliases, suppressions,
    function defs keyed by qualname."""

    def __init__(self, path: str, relpath: str, source: str):
        self.path = path
        self.relpath = relpath.replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.modname = self.relpath[:-3].replace("/", ".") \
            if self.relpath.endswith(".py") else self.relpath
        # alias -> imported module ("np" -> "numpy"); from-imports map
        # the bound name to "module.attr" ("sleep" -> "time.sleep")
        self.import_aliases: dict[str, str] = {}
        self.from_imports: dict[str, str] = {}
        self.functions: dict[str, FunctionInfo] = {}
        self._suppressed_lines: dict[int, set[str]] = {}
        self._suppressed_file: set[str] = set()
        self._collect_imports()
        self._collect_suppressions()
        self._collect_functions()

    # -- construction --------------------------------------------------

    def _collect_imports(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.import_aliases[a.asname or a.name.split(".")[0]] \
                        = a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.from_imports[a.asname or a.name] = \
                        f"{node.module}.{a.name}"

    def _collect_suppressions(self) -> None:
        for i, line in enumerate(self.lines, start=1):
            m = _SUPPRESS_RE.search(line)
            if m:
                rules = {r.strip() for r in
                         m.group(1).split(",") if r.strip()}
                # drop trailing free-text reason words ("rule  reason")
                rules = {r.split()[0] for r in rules}
                self._suppressed_lines.setdefault(i, set()).update(rules)
            m = _SUPPRESS_FILE_RE.search(line)
            if m:
                self._suppressed_file.update(
                    r.strip().split()[0] for r in m.group(1).split(",")
                    if r.strip()
                )

    def _collect_functions(self) -> None:
        mod = self

        class V(ast.NodeVisitor):
            def __init__(self) -> None:
                self.stack: list[str] = []
                self.cls: list[str] = []

            def visit_ClassDef(self, node: ast.ClassDef) -> None:
                self.cls.append(node.name)
                self.stack.append(node.name)
                self.generic_visit(node)
                self.stack.pop()
                self.cls.pop()

            def _def(self, node) -> None:
                self.stack.append(node.name)
                qual = f"{mod.modname}:" + ".".join(self.stack)
                mod.functions[qual] = FunctionInfo(
                    qual, mod, node, cls=self.cls[-1] if self.cls else None
                )
                self.generic_visit(node)
                self.stack.pop()

            visit_FunctionDef = _def
            visit_AsyncFunctionDef = _def

        V().visit(self.tree)

    # -- queries -------------------------------------------------------

    def suppressed(self, rule: str, line: int) -> bool:
        """True when ``# fedlint: disable=<rule>`` covers this line —
        on the line itself, anywhere in the contiguous comment block
        directly above it (so the disable can carry a multi-line
        reason, which the policy requires), or file-wide via
        ``disable-file``."""
        if rule in self._suppressed_file:
            return True
        if rule in self._suppressed_lines.get(line, ()):
            return True
        i = line - 1
        while i >= 1 and self.lines[i - 1].lstrip().startswith("#"):
            if rule in self._suppressed_lines.get(i, ()):
                return True
            i -= 1
        return False

    def enclosing_function(self, line: int) -> str:
        """Qualname suffix of the innermost def containing ``line``
        (fingerprint scope)."""
        best, best_span = "<module>", None
        for qual, fi in self.functions.items():
            node = fi.node
            end = getattr(node, "end_lineno", node.lineno)
            if node.lineno <= line <= end:
                span = end - node.lineno
                if best_span is None or span < best_span:
                    best, best_span = qual.split(":", 1)[1], span
        return best


@dataclasses.dataclass
class AnalysisConfig:
    """Repo-level analyzer config (``fedlint.json``).

    ``exempt`` maps rule name -> list of relpath glob patterns the rule
    skips entirely (policy exemptions live HERE, visible in one file —
    e.g. ``scripts/*.py`` is exempt from jit-purity because the smoke
    scripts intentionally time host work; inline ``# fedlint: disable``
    comments are for single intentional sites, with a reason).
    """

    exempt: dict[str, list[str]] = dataclasses.field(default_factory=dict)
    #: vocabulary source for the metric-vocabulary rule
    vocabulary_doc: str = "docs/OBSERVABILITY.md"
    #: extra rule knobs, keyed by rule name
    options: dict[str, Any] = dataclasses.field(default_factory=dict)

    @staticmethod
    def load(path: str | None, root: str) -> "AnalysisConfig":
        if path is None:
            cand = os.path.join(root, "fedlint.json")
            path = cand if os.path.exists(cand) else None
        if path is None:
            return AnalysisConfig()
        with open(path) as f:
            raw = json.load(f)
        return AnalysisConfig(
            exempt={k: list(v) for k, v in raw.get("exempt", {}).items()},
            vocabulary_doc=raw.get("vocabulary_doc",
                                   "docs/OBSERVABILITY.md"),
            options=raw.get("options", {}),
        )

    def exempted(self, rule: str, relpath: str) -> bool:
        return any(fnmatch.fnmatch(relpath, pat)
                   for pat in self.exempt.get(rule, ()))


class Project:
    """Every parsed module under the target paths, plus the call graph
    and the jit-reachable set rules key off."""

    def __init__(self, root: str, config: AnalysisConfig):
        self.root = os.path.abspath(root)
        self.config = config
        self.modules: dict[str, ModuleInfo] = {}  # relpath -> module
        self.functions: dict[str, FunctionInfo] = {}
        #: functions handed directly to a compile site, with the jit
        #: call's static_argnames resolved to parameter names
        self.jit_roots: dict[str, set[str]] = {}
        self.jit_reachable: set[str] = set()

    # -- loading -------------------------------------------------------

    @staticmethod
    def load(paths: Iterable[str], root: str,
             config: AnalysisConfig | None = None) -> "Project":
        config = config or AnalysisConfig()
        proj = Project(root, config)
        for p in paths:
            ap = os.path.abspath(p)
            # a mistyped/renamed target must FAIL, not lint an empty
            # set: exiting 0 'clean' would silently disable the CI gate
            if not os.path.exists(ap):
                raise SystemExit(f"fedlint: no such target: {p}")
            if os.path.isfile(ap) and not ap.endswith(".py"):
                raise SystemExit(
                    f"fedlint: not a python file: {p}"
                )
            if os.path.isdir(ap):
                for dirpath, dirnames, filenames in os.walk(ap):
                    dirnames[:] = [d for d in dirnames
                                   if d != "__pycache__"
                                   and not d.startswith(".")]
                    for fn in sorted(filenames):
                        if fn.endswith(".py"):
                            proj._add_file(os.path.join(dirpath, fn))
            elif ap.endswith(".py"):
                proj._add_file(ap)
        proj._link()
        return proj

    def _add_file(self, path: str) -> None:
        relpath = os.path.relpath(path, self.root)
        if relpath in self.modules:
            return
        with open(path, encoding="utf-8") as f:
            source = f.read()
        try:
            mod = ModuleInfo(path, relpath, source)
        except SyntaxError as err:  # a broken file is its own finding
            raise SystemExit(f"fedlint: cannot parse {relpath}: {err}")
        self.modules[mod.relpath] = mod
        self.functions.update(mod.functions)

    # -- call graph ----------------------------------------------------

    def _link(self) -> None:
        # index: simple function name -> qualnames, per module and per
        # (module, class)
        by_module: dict[tuple[str, str], str] = {}
        by_class: dict[tuple[str, str, str], str] = {}
        for qual, fi in self.functions.items():
            modname, local = qual.split(":", 1)
            simple = local.rsplit(".", 1)[-1]
            by_module.setdefault((modname, simple), qual)
            if fi.cls is not None:
                by_class[(modname, fi.cls, simple)] = qual

        # factory-returned closures — the repo's build_* idiom:
        # `self.local_update = build_local_update(...)` binds a nested
        # def the round body later calls (or hands to vmap/scan).
        # returns_of[F] = nested defs F returns; the use-site edges are
        # added in _resolve_calls.
        self._returns_of = {
            qual: self._returned_nested(fi)
            for qual, fi in self.functions.items()
        }
        self._attr_results: dict[tuple[str, str, str], set[str]] = {}
        for qual, fi in self.functions.items():
            if fi.cls is None:
                continue
            mod = fi.module
            for node in ast.walk(fi.node):
                if not (isinstance(node, ast.Assign)
                        and isinstance(node.value, ast.Call)):
                    continue
                targets = self._factory_targets(node.value, fi,
                                                by_module, by_class)
                if not targets:
                    continue
                for t in node.targets:
                    if isinstance(t, ast.Attribute) \
                            and isinstance(t.value, ast.Name) \
                            and t.value.id == "self":
                        self._attr_results.setdefault(
                            (mod.modname, fi.cls, t.attr), set()
                        ).update(targets)

        for qual, fi in self.functions.items():
            fi.callees = self._resolve_calls(fi, by_module, by_class)
        self._find_jit_roots(by_module, by_class)
        self._close_reachability()

    def _returned_nested(self, fi: FunctionInfo) -> set[str]:
        """Qualnames of nested defs ``fi`` returns (directly, or one
        wrapper-call deep: ``return jax.jit(inner)``)."""
        node = fi.node
        if isinstance(node, ast.Lambda):
            return set()
        nested = {n.name for n in ast.walk(node)
                  if isinstance(n, (ast.FunctionDef,
                                    ast.AsyncFunctionDef))
                  and n is not node}
        if not nested:
            return set()
        out: set[str] = set()
        for r in ast.walk(node):
            if isinstance(r, ast.Return) and r.value is not None:
                for sub in ast.walk(r.value):
                    if isinstance(sub, ast.Name) and sub.id in nested:
                        cand = f"{fi.qualname}.{sub.id}"
                        if cand in self.functions:
                            out.add(cand)
        return out

    def _factory_targets(self, call: ast.Call, fi, by_module, by_class
                         ) -> set[str]:
        """Nested defs the factory ``call`` returns, or empty."""
        f = call.func
        mod = fi.module
        target = None
        if isinstance(f, ast.Name):
            target = self._resolve_name(f.id, mod, by_module)
        elif isinstance(f, ast.Attribute):
            if isinstance(f.value, ast.Name):
                if f.value.id in ("self", "cls") and fi.cls:
                    target = by_class.get((mod.modname, fi.cls, f.attr))
                else:
                    tm = mod.import_aliases.get(f.value.id)
                    if tm is not None:
                        target = self._module_function(tm, f.attr,
                                                       by_module)
        if target is None:
            return set()
        return self._returns_of.get(target, set())

    def _resolve_calls(self, fi: FunctionInfo, by_module, by_class
                       ) -> set[str]:
        mod = fi.module
        out: set[str] = set()
        # function-local bindings of factory results:
        # `lu = build_local_update(...)` -> calling/handing-off `lu`
        # reaches the nested def the factory returned
        local_results: dict[str, set[str]] = {}
        for node in ast.walk(fi.node):
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call):
                targets = self._factory_targets(node.value, fi,
                                                by_module, by_class)
                if targets:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            local_results.setdefault(t.id, set()) \
                                .update(targets)
        for node in ast.walk(fi.node):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Name):
                target = self._resolve_name(f.id, mod, by_module)
                if target:
                    out.add(target)
                out.update(local_results.get(f.id, ()))
            elif isinstance(f, ast.Attribute):
                base = f.value
                if isinstance(base, ast.Name) and base.id in ("self",
                                                              "cls"):
                    if fi.cls is not None:
                        t = by_class.get((mod.modname, fi.cls, f.attr))
                        if t:
                            out.add(t)
                        out.update(self._attr_results.get(
                            (mod.modname, fi.cls, f.attr), ()))
                elif isinstance(base, ast.Name):
                    target_mod = mod.import_aliases.get(base.id)
                    if target_mod is not None:
                        t = self._module_function(target_mod, f.attr,
                                                  by_module)
                        if t:
                            out.add(t)
            # callables escaping into combinators (`jax.vmap(lu)`,
            # `jax.vmap(self.local_update)`, `lax.scan(step, ...)`)
            # count as calls of what they wrap
            for arg in node.args:
                if isinstance(arg, ast.Name):
                    out.update(local_results.get(arg.id, ()))
                elif isinstance(arg, ast.Attribute) \
                        and isinstance(arg.value, ast.Name) \
                        and arg.value.id in ("self", "cls") \
                        and fi.cls is not None:
                    out.update(self._attr_results.get(
                        (mod.modname, fi.cls, arg.attr), ()))
        return out

    def _resolve_name(self, name: str, mod: ModuleInfo, by_module
                      ) -> str | None:
        t = by_module.get((mod.modname, name))
        if t:
            return t
        dotted = mod.from_imports.get(name)
        if dotted:
            target_mod, _, attr = dotted.rpartition(".")
            return self._module_function(target_mod, attr, by_module)
        return None

    def _module_function(self, target_mod: str, attr: str, by_module
                         ) -> str | None:
        # imported module names rarely match our relpath-derived
        # modnames exactly (package vs file path); match by suffix
        for (modname, simple), qual in by_module.items():
            if simple == attr and (
                modname == target_mod
                or modname.endswith("." + target_mod.rsplit(".", 1)[-1])
                or target_mod.endswith(modname.rsplit(".", 1)[-1])
            ):
                return qual
        return None

    # -- jit roots + reachability -------------------------------------

    def _find_jit_roots(self, by_module, by_class) -> None:
        for relpath, mod in self.modules.items():
            for node in ast.walk(mod.tree):
                # decorator form: @jax.jit / @partial(jax.jit, ...)
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    for dec in node.decorator_list:
                        if self._is_jit_expr(dec):
                            qual = self._qual_for_node(mod, node)
                            if qual:
                                self._add_root(
                                    qual,
                                    self._static_names(
                                        dec,
                                        self.functions.get(qual)))
                if not isinstance(node, ast.Call):
                    continue
                name = _terminal_name(node.func)
                if name not in JIT_ENTRY_NAMES or not node.args:
                    continue
                fn_arg = node.args[0]
                target = self._callable_target(fn_arg, mod, by_module,
                                               by_class, node)
                if target:
                    self._add_root(target,
                                   self._static_names(node,
                                                      self.functions
                                                      .get(target)))

        # lambdas handed to jit: their body's resolved calls are roots
        # too (handled by _callable_target returning a synthetic entry)

    def _qual_for_node(self, mod: ModuleInfo, node) -> str | None:
        for qual, fi in mod.functions.items():
            if fi.node is node:
                return qual
        return None

    def _callable_target(self, fn_arg, mod, by_module, by_class,
                         call) -> str | None:
        if isinstance(fn_arg, ast.Name):
            return self._resolve_name(fn_arg.id, mod, by_module)
        if isinstance(fn_arg, ast.Attribute) \
                and isinstance(fn_arg.value, ast.Name) \
                and fn_arg.value.id in ("self", "cls"):
            # ProgramSite(self._round, ...) inside a method: resolve in
            # the enclosing class
            encl = mod.enclosing_function(call.lineno)
            cls = encl.split(".", 1)[0] if "." in encl else None
            if cls:
                return by_class.get((mod.modname, cls, fn_arg.attr))
        if isinstance(fn_arg, ast.Lambda):
            # mark every function the lambda body calls as a root
            for sub in ast.walk(fn_arg.body):
                if isinstance(sub, ast.Call):
                    n = sub.func
                    if isinstance(n, ast.Name):
                        t = self._resolve_name(n.id, mod, by_module)
                        if t:
                            self._add_root(t, set())
        return None

    def _add_root(self, qual: str, static_names: set[str]) -> None:
        self.jit_roots.setdefault(qual, set()).update(static_names)

    def _static_names(self, call_or_dec, fn_info) -> set[str]:
        """Parameter names a jit site marks static (static_argnames
        literals, plus static_argnums resolved against the callee's
        positional parameters when it is known)."""
        out: set[str] = set()
        call = call_or_dec if isinstance(call_or_dec, ast.Call) else None
        if call is None:
            return out
        node = getattr(fn_info, "node", None) if fn_info else None
        params: list[str] = []
        if node is not None and not isinstance(node, ast.Lambda):
            params = [a.arg for a in node.args.args]
            if params and params[0] in ("self", "cls"):
                params = params[1:]
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                for sub in ast.walk(kw.value):
                    if isinstance(sub, ast.Constant) \
                            and isinstance(sub.value, str):
                        out.add(sub.value)
            elif kw.arg == "static_argnums" and params:
                for sub in ast.walk(kw.value):
                    if isinstance(sub, ast.Constant) \
                            and isinstance(sub.value, int) \
                            and 0 <= sub.value < len(params):
                        out.add(params[sub.value])
        return out

    def _is_jit_expr(self, expr) -> bool:
        if _terminal_name(expr) in JIT_ENTRY_NAMES:
            return True
        if isinstance(expr, ast.Call):  # @partial(jax.jit, ...)
            if _terminal_name(expr.func) == "partial" and expr.args:
                return _terminal_name(expr.args[0]) in JIT_ENTRY_NAMES
            return self._is_jit_expr(expr.func)
        return False

    def _close_reachability(self) -> None:
        seen = set(self.jit_roots)
        frontier = list(self.jit_roots)
        while frontier:
            qual = frontier.pop()
            fi = self.functions.get(qual)
            if fi is None:
                continue
            for callee in fi.callees:
                if callee not in seen:
                    seen.add(callee)
                    frontier.append(callee)
        self.jit_reachable = seen


def _terminal_name(expr) -> str | None:
    """`jax.jit` -> "jit", `M.ProgramSite` -> "ProgramSite",
    `jit` -> "jit"."""
    if isinstance(expr, ast.Attribute):
        return expr.attr
    if isinstance(expr, ast.Name):
        return expr.id
    return None


# ---------------------------------------------------------------------
# rule registry
# ---------------------------------------------------------------------

@dataclasses.dataclass
class Rule:
    name: str
    doc: str
    check: Callable[[Project], Iterator[Finding]]


RULES: dict[str, Rule] = {}


def register_rule(name: str, doc: str):
    """Decorator: ``@register_rule("jit-purity", "...")`` over a
    ``check(project) -> Iterator[Finding]`` generator."""
    def deco(fn):
        RULES[name] = Rule(name=name, doc=doc, check=fn)
        return fn
    return deco


def _ensure_rules_loaded() -> None:
    from fedml_tpu.analysis import rules  # noqa: F401  (registers all)


# ---------------------------------------------------------------------
# baseline ratchet
# ---------------------------------------------------------------------

def load_baseline(path: str) -> set[str]:
    """Fingerprints frozen by a previous ``--write-baseline`` run."""
    with open(path) as f:
        raw = json.load(f)
    return {e["fingerprint"] for e in raw.get("findings", [])}


def write_baseline(path: str, findings: list[Finding]) -> None:
    """Freeze the CURRENT findings. Entries carry the human fields next
    to the fingerprint so a baseline diff reviews like code."""
    payload = {
        "version": 1,
        "note": "frozen fedlint findings — new findings fail CI; see "
                "docs/STATIC_ANALYSIS.md for the ratchet policy",
        "findings": sorted(
            (f.to_dict() for f in findings),
            key=lambda d: (d["rule"], d["path"], d["scope"],
                           d["message"]),
        ),
    }
    for e in payload["findings"]:
        e.pop("line", None)  # lines drift; fingerprints do not
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")


# ---------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------

def run_analysis(paths: Iterable[str], root: str,
                 config: AnalysisConfig | None = None,
                 rules: Iterable[str] | None = None,
                 ) -> list[Finding]:
    """Parse ``paths``, run every registered rule, return findings with
    suppression comments and config exemptions already applied."""
    _ensure_rules_loaded()
    config = config or AnalysisConfig()
    project = Project.load(paths, root, config)
    selected = list(rules) if rules else sorted(RULES)
    unknown = [r for r in selected if r not in RULES]
    if unknown:
        raise SystemExit(f"fedlint: unknown rule(s): {unknown} "
                         f"(have: {sorted(RULES)})")
    findings: list[Finding] = []
    for rname in selected:
        for f in RULES[rname].check(project):
            if config.exempted(rname, f.path):
                continue
            mod = project.modules.get(f.path)
            if mod is not None and mod.suppressed(rname, f.line):
                continue
            findings.append(f)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings
