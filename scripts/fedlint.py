#!/usr/bin/env python
"""fedlint CLI: project-invariant static analysis with a CI ratchet.

Usage:
    python scripts/fedlint.py fedml_tpu/ [scripts/ ...]
        [--baseline fedlint_baseline.json] [--write-baseline]
        [--json out.json] [--rules jit-purity,lock-hygiene]
        [--config fedlint.json] [--root .] [--list-rules]

Exit codes: 0 = clean (or every finding baselined / suppressed),
1 = NEW findings (the ratchet: pre-existing findings are frozen in the
baseline file; anything new fails), 2 = usage error.

docs/STATIC_ANALYSIS.md has the rule catalog and the suppression /
baseline policy.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from fedml_tpu.analysis import core  # noqa: E402


def _discover_root(paths: list[str]) -> str:
    """The documented --root default: walk up from the first target
    looking for a ``fedlint.json``; its directory anchors relpaths (so
    baseline fingerprints match the committed ones regardless of CWD)
    and supplies the repo config. Falls back to CWD."""
    start = os.path.abspath(paths[0]) if paths else os.getcwd()
    cur = start if os.path.isdir(start) else os.path.dirname(start)
    while True:
        if os.path.exists(os.path.join(cur, "fedlint.json")):
            return cur
        parent = os.path.dirname(cur)
        if parent == cur:
            return os.getcwd()
        cur = parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="fedlint: AST-level project-invariant checks "
        "(docs/STATIC_ANALYSIS.md)")
    ap.add_argument("paths", nargs="*",
                    help="files / directories to analyze")
    ap.add_argument("--root", default=None,
                    help="repo root paths + baseline fingerprints are "
                    "relative to (default: the nearest directory at or "
                    "above the first target that holds a fedlint.json, "
                    "else CWD — so invocations from outside the repo "
                    "still load the repo config and produce "
                    "baseline-stable paths)")
    ap.add_argument("--config", default=None,
                    help="fedlint.json (default: <root>/fedlint.json "
                    "when present)")
    ap.add_argument("--baseline", default=None,
                    help="ratchet file: findings fingerprinted here "
                    "pass; new ones fail")
    ap.add_argument("--write-baseline", action="store_true",
                    help="freeze the CURRENT findings into --baseline "
                    "and exit 0")
    ap.add_argument("--json", dest="json_out", default=None,
                    help="write the full finding list as JSON "
                    "('-' = stdout)")
    ap.add_argument("--rules", default=None,
                    help="comma-separated subset of rules to run")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="summary line only")
    args = ap.parse_args(argv)

    core._ensure_rules_loaded()
    if args.list_rules:
        for name in sorted(core.RULES):
            print(f"{name:24s} {core.RULES[name].doc}")
        return 0
    if not args.paths:
        ap.error("paths are required (except with --list-rules)")

    root = os.path.abspath(args.root) if args.root \
        else _discover_root(args.paths)
    try:
        config = core.AnalysisConfig.load(args.config, root)
        rules = [r.strip() for r in args.rules.split(",")] \
            if args.rules else None
        findings = core.run_analysis(args.paths, root, config, rules)
    except SystemExit as err:
        # core raises SystemExit(message) for usage-class errors
        # (unknown rule, unparseable target, broken config) — exit 2
        # per the documented contract, never 1 ('new findings')
        if isinstance(err.code, str):
            print(err.code, file=sys.stderr)
            return 2
        raise
    except (OSError, json.JSONDecodeError) as err:
        # unreadable --config / malformed json: same usage class
        print(f"fedlint: {err}", file=sys.stderr)
        return 2

    def emit_json(new, old):
        payload = {
            "new": [f.to_dict() for f in new],
            "baselined": [f.to_dict() for f in old],
            "rules": sorted(rules or core.RULES),
            "paths": args.paths,
        }
        text = json.dumps(payload, indent=2)
        if args.json_out == "-":
            print(text)
        else:
            with open(args.json_out, "w") as f:
                f.write(text + "\n")

    if args.write_baseline:
        if not args.baseline:
            print("fedlint: --write-baseline requires --baseline",
                  file=sys.stderr)
            return 2
        core.write_baseline(args.baseline, findings)
        if args.json_out:  # everything just frozen = baselined
            emit_json([], findings)
        print(f"fedlint: froze {len(findings)} finding(s) into "
              f"{args.baseline}",
              file=sys.stderr if args.json_out == "-" else sys.stdout)
        return 0

    baseline: set[str] = set()
    if args.baseline and os.path.exists(args.baseline):
        try:
            baseline = core.load_baseline(args.baseline)
        except (OSError, json.JSONDecodeError, KeyError,
                TypeError) as err:
            print(f"fedlint: corrupt baseline {args.baseline}: {err}",
                  file=sys.stderr)
            return 2
    new = [f for f in findings if f.fingerprint not in baseline]
    old = [f for f in findings if f.fingerprint in baseline]

    if args.json_out:
        emit_json(new, old)

    # with --json - the JSON document owns stdout; human output moves
    # to stderr so `fedlint --json - | jq` stays parseable
    human = sys.stderr if args.json_out == "-" else sys.stdout
    if not args.quiet:
        for f in new:
            print(f.render(), file=human)
    print(f"fedlint: {len(new)} new finding(s), {len(old)} baselined, "
          f"{len(findings)} total "
          f"({'FAIL' if new else 'ok'})", file=human)
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
