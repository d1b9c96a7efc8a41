"""parse-time-validation: the config<->CLI contract, checked statically.

The historical bug class: the fednova+defense crash-loop — a config
combination rejected only at first round close, where a supervised
server burns its restart budget crash-looping, instead of at
``parse_args`` where the operator sees one clear error (PR 4's second
review round moved it; this rule keeps it moved). Three checks:

- **field->flag**: every ``FedConfig``/``DeployConfig`` field that is
  READ anywhere in the run paths must have a registered CLI flag
  (``--<field>`` or a declared alias in ``fedlint.json``
  ``options.parse-time-validation.flag_aliases``) — a field reachable
  only by hand-editing a config JSON is validated nowhere;
- **duplicate registration**: the same option string registered twice
  in one parser build;
- **reserved flags**: option strings owned by the run CLI
  (``options.parse-time-validation.reserved_flags``, the runtime twin
  is ``fedml_tpu.analysis.flags.check_flag_registry``) registered by
  any other module — a script minting its own ``--slo`` would shadow
  the SloSpec semantics operators rely on.
"""

from __future__ import annotations

import ast
from typing import Iterator

from fedml_tpu.analysis.core import Finding, Project, register_rule

_RULE = "parse-time-validation"
_DEFAULT_CLASSES = ("FedConfig", "DeployConfig")


@register_rule(
    _RULE,
    "config fields read in run paths need a registered CLI flag; "
    "duplicate and reserved-flag registrations fail at lint time",
)
def check(project: Project) -> Iterator[Finding]:
    opts = project.config.options.get(_RULE, {})
    classes = tuple(opts.get("config_classes", _DEFAULT_CLASSES))
    aliases: dict[str, str] = dict(opts.get("flag_aliases", {}))
    reserved = set(opts.get("reserved_flags", ()))
    owner = opts.get("reserved_owner", "")

    # --- collect dataclass fields ------------------------------------
    fields: list[tuple[str, str, str, int]] = []  # (cls, field, path, ln)
    for relpath, mod in sorted(project.modules.items()):
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ClassDef) and node.name in classes:
                for stmt in node.body:
                    if isinstance(stmt, ast.AnnAssign) \
                            and isinstance(stmt.target, ast.Name):
                        fields.append((node.name, stmt.target.id,
                                       mod.relpath, stmt.lineno))

    # --- collect flags + duplicates + reserved misuse ----------------
    flags: set[str] = set()
    for relpath, mod in sorted(project.modules.items()):
        per_scope: dict[str, dict[str, int]] = {}
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "add_argument"
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                    and node.args[0].value.startswith("--")):
                continue
            flag = node.args[0].value
            flags.add(flag)
            scope = mod.enclosing_function(node.lineno)
            seen = per_scope.setdefault(scope, {})
            if flag in seen:
                # no line numbers in the message: it feeds the
                # baseline fingerprint, which must survive line drift
                yield Finding(
                    rule=_RULE, path=mod.relpath, line=node.lineno,
                    scope=scope,
                    message=(
                        f"flag `{flag}` registered twice in one "
                        f"parser"
                    ),
                )
            else:
                seen[flag] = node.lineno
            if flag in reserved and mod.relpath != owner:
                yield Finding(
                    rule=_RULE, path=mod.relpath, line=node.lineno,
                    scope=scope,
                    message=(
                        f"reserved flag `{flag}` belongs to {owner} "
                        f"(the run CLI's SLO/export plane) — rename "
                        f"this flag"
                    ),
                )

    if not flags:
        return  # no CLI in the analyzed tree: field->flag is vacuous

    # --- field reads -------------------------------------------------
    read_attrs: set[str] = set()
    for relpath, mod in sorted(project.modules.items()):
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Attribute) \
                    and isinstance(node.ctx, ast.Load):
                read_attrs.add(node.attr)

    for cls, field, relpath, lineno in fields:
        if field not in read_attrs:
            continue  # never read: not this rule's concern
        flag = aliases.get(field, field)
        if flag == "":  # alias to "" = explicitly flagless by policy
            continue
        candidates = {f"--{flag}", f"--no_{flag}", f"--no-{flag}"}
        if not candidates & flags:
            yield Finding(
                rule=_RULE, path=relpath, line=lineno, scope=cls,
                message=(
                    f"{cls}.{field} is read in run paths but has no "
                    f"registered CLI flag (--{flag}) — it can only be "
                    f"set by hand-editing config JSON, bypassing "
                    f"parse-time validation"
                ),
            )
