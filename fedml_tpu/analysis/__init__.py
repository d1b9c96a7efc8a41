"""fedlint: project-invariant static analysis (docs/STATIC_ANALYSIS.md).

Twelve PRs of review hardening fixed the same defect classes over and
over — host impurity inside compiled rounds, donated buffers reused
after the call, blocking work under locks, metric names missing from
the OBSERVABILITY.md vocabulary, config validation deferred past parse
time. FedJAX gets its safety from a narrow functional API; this repo
chose a wide one, so the invariants are machine-checked instead:
AST-level rules (:mod:`fedml_tpu.analysis.rules`) over a small scope /
call-graph framework (:mod:`fedml_tpu.analysis.core`), ratcheted in CI
via a frozen baseline (``scripts/fedlint.py --baseline``).

The analyzer (:mod:`.core` + :mod:`.rules`) imports NOTHING from the
code it lints — it parses it — so linting cannot perturb what it
lints; stdlib ``ast`` only, no jax. One module here IS runtime-shared
by design: :mod:`.flags`, the flag-registration checker run.py and
the deploy supervisor call at startup (the runtime twin of
the parse-time-validation rule). This ``__init__`` stays import-free
so that runtime path pulls in none of the analyzer.
"""
