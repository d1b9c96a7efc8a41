"""fedlint: the project-invariant static analyzer
(fedml_tpu/analysis/, docs/STATIC_ANALYSIS.md).

Tiers:

1. per-rule fixture pins — one FLAGGED and one CLEAN snippet per rule
   (the rule catalog's contract, stated as code);
2. framework pins — suppression comments, config exemptions, the
   baseline ratchet (a baselined finding passes, a new finding fails),
   fingerprint stability under line drift;
3. pre-fix regression pins — fixture copies of the ACTUAL pre-existing
   violations this PR fixed (undocumented metric names, unnamed
   split-actor message types, flagless FedConfig server-opt fields,
   the dead S2C_INIT edge, the mutable pipeline closure), each proven
   caught by the linter;
4. the end-to-end pin — fedlint over the real tree exits 0 with the
   shipped baseline;
5. the shared flag-registration checker (fedml_tpu/analysis/flags.py).

The analyzer is stdlib-only (ast), so this suite imports no jax and
runs in milliseconds.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap

import pytest

from fedml_tpu.analysis import core as A
from fedml_tpu.analysis.flags import (
    RESERVED_RUN_FLAGS,
    check_flag_registry,
    check_rank_argv,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint(tmp_path, files: dict[str, str], rules=None, config=None):
    """Write ``files`` under ``tmp_path`` and run the analyzer over it
    (root = tmp_path, so finding paths are fixture-relative)."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return A.run_analysis([str(tmp_path)], root=str(tmp_path),
                          config=config, rules=rules)


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# tier 1: one flagged + one clean fixture per rule
# ---------------------------------------------------------------------------

class TestJitPurity:
    def test_flagged_time_in_jit_reachable(self, tmp_path):
        fs = lint(tmp_path, {"m.py": """
            import time
            import jax

            def helper(s):
                t = time.time()  # impure, reachable through round_fn
                return s

            def round_fn(state):
                return helper(state)

            compiled = jax.jit(round_fn)
        """}, rules=["jit-purity"])
        assert len(fs) == 1, fs
        assert "time.time" in fs[0].message
        assert fs[0].scope == "helper"

    def test_flagged_coercion_of_kwonly_param(self, tmp_path):
        """Keyword-only (and positional-only) params are traced too —
        the taint seed must cover the full parameter list."""
        fs = lint(tmp_path, {"m.py": """
            import jax

            def step(x, *, loss):
                return x, float(loss)

            compiled = jax.jit(step)
        """}, rules=["jit-purity"])
        assert len(fs) == 1 and "`float(...)`" in fs[0].message

    def test_flagged_item_and_float_on_traced(self, tmp_path):
        fs = lint(tmp_path, {"m.py": """
            import jax

            def round_fn(state):
                loss = state * 2
                host = float(loss)
                also = loss.item()
                return state

            compiled = jax.jit(round_fn)
        """}, rules=["jit-purity"])
        msgs = " | ".join(f.message for f in fs)
        assert "`float(...)`" in msgs and "`.item()`" in msgs

    def test_factory_closure_is_reachable(self, tmp_path):
        """The repo's build_* idiom: a factory returns a nested def
        that is bound to an attribute and handed to vmap inside the
        jitted round — the purity rules must see through it."""
        fs = lint(tmp_path, {"m.py": """
            import time
            import jax

            def build_local_update(cfg):
                def local_update(vars, x):
                    time.time()  # impure inside the traced closure
                    return vars

                return local_update

            class Sim:
                def __init__(self, cfg):
                    self.local_update = build_local_update(cfg)
                    self._round_fn = jax.jit(self._round)

                def _round(self, state, xs):
                    return jax.vmap(self.local_update)(state, xs)
        """}, rules=["jit-purity"])
        assert len(fs) == 1, fs
        assert "time.time" in fs[0].message
        assert "local_update" in fs[0].scope

    def test_clean_host_code_and_shape_math(self, tmp_path):
        fs = lint(tmp_path, {"m.py": """
            import time
            import jax

            def round_fn(x):
                # shape-derived ints are static under trace, not syncs
                n = int(x.shape[0] * 0.5)
                return x[:n]

            compiled = jax.jit(round_fn)

            def host_loop():  # NOT jit-reachable: impurity is fine
                t = time.time()
                print(t)
        """}, rules=["jit-purity"])
        assert fs == []


class TestTracedBranch:
    def test_flagged_branch_on_traced_param(self, tmp_path):
        fs = lint(tmp_path, {"m.py": """
            import jax

            def round_fn(x, n):
                y = x + 1
                if y > 0:
                    return y
                return x

            compiled = jax.jit(round_fn, static_argnames=("n",))
        """}, rules=["traced-branch"])
        assert len(fs) == 1 and "y" in fs[0].message

    def test_decorator_static_argnums_resolved(self, tmp_path):
        """@partial(jax.jit, static_argnums=...) marks those params
        static too — decorator-form sites must not false-positive on
        legal static-arg control flow."""
        fs = lint(tmp_path, {"m.py": """
            from functools import partial
            import jax

            @partial(jax.jit, static_argnums=(1,))
            def round_fn(x, n):
                if n > 0:
                    return x * 2
                return x
        """}, rules=["traced-branch"])
        assert fs == []

    def test_clean_static_and_shape_branches(self, tmp_path):
        fs = lint(tmp_path, {"m.py": """
            import jax

            def round_fn(x, n):
                if n > 3:            # static_argnames
                    x = x * 2
                if x.shape[0] > 1:   # shape is static under trace
                    x = x + 1
                if x is None:        # identity test
                    return 0
                assert len(x.shape) == 2
                return x

            compiled = jax.jit(round_fn, static_argnames=("n",))
        """}, rules=["traced-branch"])
        assert fs == []


class TestDonationDiscipline:
    def test_flagged_read_after_donation(self, tmp_path):
        fs = lint(tmp_path, {"m.py": """
            import jax

            def step(s):
                return s

            g = jax.jit(step, donate_argnums=(0,))

            def run(state):
                out = g(state)
                return state  # donated buffers already deleted
        """}, rules=["donation-discipline"])
        assert len(fs) == 1 and "`state`" in fs[0].message

    def test_flagged_self_attr_donor_cross_method(self, tmp_path):
        fs = lint(tmp_path, {"m.py": """
            import jax

            class Sim:
                def __init__(self, fn):
                    self._round = jax.jit(fn, donate_argnums=(0,))

                def run(self, state):
                    new = self._round(state)
                    norm = state + 1  # stale read of donated state
                    return new, norm
        """}, rules=["donation-discipline"])
        assert len(fs) == 1 and "`state`" in fs[0].message

    def test_clean_rebind_and_branches(self, tmp_path):
        fs = lint(tmp_path, {"m.py": """
            import jax

            def step(s):
                return s

            g = jax.jit(step, donate_argnums=(0,))

            def run(state, flag):
                for _ in range(3):
                    state = g(state)  # the donation idiom: rebind
                return state

            def branches(state, flag):
                if flag:
                    return g(state)   # exclusive branch may donate
                return state          # ... while this one reads
        """}, rules=["donation-discipline"])
        assert fs == []


class TestLockHygiene:
    def test_flagged_sleep_under_lock(self, tmp_path):
        fs = lint(tmp_path, {"m.py": """
            import threading
            import time

            class Actor:
                def __init__(self):
                    self._lock = threading.Lock()

                def close(self, sock, t):
                    with self._lock:
                        time.sleep(0.1)
                        sock.sendall(b"bye")
                        t.join()
        """}, rules=["lock-hygiene"])
        msgs = " | ".join(f.message for f in fs)
        assert "time.sleep" in msgs
        assert "sendall" in msgs
        assert ".join" in msgs

    def test_clean_cv_wait_under_its_lock(self, tmp_path):
        """The canonical Condition(lock) pattern: cv.wait() under
        `with self._lock:` RELEASES the lock — never a finding."""
        fs = lint(tmp_path, {"m.py": """
            import threading

            class Actor:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._cond = threading.Condition(self._lock)

                def park(self):
                    with self._lock:
                        self._cond.wait()
        """}, rules=["lock-hygiene"])
        assert fs == []

    def test_clean_outside_lock_cv_and_str_join(self, tmp_path):
        fs = lint(tmp_path, {"m.py": """
            import threading
            import time

            class Actor:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._cv = threading.Condition()

                def ok(self, parts):
                    with self._lock:
                        label = ", ".join(parts)  # str.join: not a block
                    time.sleep(0.1)  # after release
                    with self._cv:
                        self._cv.wait()  # releases the lock: its contract
                    return label
        """}, rules=["lock-hygiene"])
        assert fs == []

    def test_lock_order_cycle_flagged(self, tmp_path):
        fs = lint(tmp_path, {"m.py": """
            import threading

            class Pair:
                def __init__(self):
                    self._a_lock = threading.Lock()
                    self._b_lock = threading.Lock()

                def fwd(self):
                    with self._a_lock:
                        with self._b_lock:
                            pass

                def rev(self):
                    with self._b_lock:
                        with self._a_lock:
                            pass
        """}, rules=["lock-hygiene"])
        assert len(fs) == 1 and "cycle" in fs[0].message
        assert "Pair._a_lock" in fs[0].message

    def test_consistent_order_clean(self, tmp_path):
        fs = lint(tmp_path, {"m.py": """
            import threading

            class Pair:
                def __init__(self):
                    self._a_lock = threading.Lock()
                    self._b_lock = threading.Lock()

                def one(self):
                    with self._a_lock:
                        with self._b_lock:
                            pass

                def two(self):
                    with self._a_lock:
                        with self._b_lock:
                            pass
        """}, rules=["lock-hygiene"])
        assert fs == []


VOCAB_DOC = """
# Vocabulary

| name | kind | meaning |
|---|---|---|
| `round.wall_s` | histogram | per-round wall time |
| `wire.bytes_by_kind.<kind>` | counter | per-kind bytes |
| `fx.{alpha,beta}_frac` | gauge | fraction pair |
| `ghost.metric` | counter | documented but never written |
"""


class TestMetricVocabulary:
    def test_flagged_both_directions(self, tmp_path):
        fs = lint(tmp_path, {
            "docs/VOCAB.md": VOCAB_DOC,
            "m.py": """
                from fedml_tpu.core import telemetry

                def close(wall):
                    telemetry.METRICS.observe("round.wall_s", wall)
                    telemetry.METRICS.inc("round.mystery")  # undocumented
            """,
        }, rules=["metric-vocabulary"],
            config=A.AnalysisConfig(
                vocabulary_doc="docs/VOCAB.md",
                options={"metric-vocabulary": {"reverse": "always"}}))
        undocumented = [f for f in fs if "round.mystery" in f.message]
        stale = [f for f in fs if "ghost.metric" in f.message]
        assert len(undocumented) == 1
        assert undocumented[0].path == "m.py"
        assert len(stale) == 1
        assert stale[0].path == "docs/VOCAB.md"

    def test_clean_wildcards_braces_prefixes(self, tmp_path):
        fs = lint(tmp_path, {
            "docs/VOCAB.md": VOCAB_DOC,
            "m.py": """
                from fedml_tpu.core import telemetry

                def close(wall, kind, k):
                    m = telemetry.METRICS
                    m.observe("round.wall_s", wall)
                    m.inc(f"wire.bytes_by_kind.{kind}", 1)  # wildcard row
                    m.gauge(f"fx.{k}_frac", 0.5)            # brace row
                    m.inc("ghost.metric")                   # satisfies reverse
            """,
        }, rules=["metric-vocabulary"],
            config=A.AnalysisConfig(vocabulary_doc="docs/VOCAB.md"))
        assert fs == []

    def test_prefix_must_end_at_family_boundary(self, tmp_path):
        """A dynamic name's literal head only matches at a '.' family
        boundary: f"rec{kind}" must not satisfy `recovery.*`-style
        rows in either direction."""
        fs = lint(tmp_path, {
            "docs/VOCAB.md": VOCAB_DOC,
            "m.py": """
                from fedml_tpu.core import telemetry

                def close(kind, wall):
                    m = telemetry.METRICS
                    m.observe("round.wall_s", wall)
                    m.inc(f"rou{kind}")   # not a boundary: flagged
                    m.inc(f"ghost.{kind}")
            """,
        }, rules=["metric-vocabulary"],
            config=A.AnalysisConfig(
                vocabulary_doc="docs/VOCAB.md",
                options={"metric-vocabulary": {"reverse": "always"}}))
        msgs = " | ".join(f.message for f in fs)
        assert "`rou*`" in msgs  # the sloppy head is itself a finding
        # ...and it did NOT mark `round.wall_s`-adjacent rows written:
        # ghost.metric is satisfied only by the proper boundary write
        assert "ghost.metric" not in msgs

    def test_assume_written_covers_infra_rows(self, tmp_path):
        cfg = A.AnalysisConfig(
            vocabulary_doc="docs/VOCAB.md",
            options={"metric-vocabulary": {
                "reverse": "always",
                "assume_written": ["ghost.metric"]}},
        )
        fs = lint(tmp_path, {
            "docs/VOCAB.md": VOCAB_DOC,
            "m.py": """
                from fedml_tpu.core import telemetry

                def close(wall, kind, k):
                    m = telemetry.METRICS
                    m.observe("round.wall_s", wall)
                    m.inc(f"wire.bytes_by_kind.{kind}", 1)
                    m.gauge(f"fx.{k}_frac", 0.5)
            """,
        }, rules=["metric-vocabulary"], config=cfg)
        assert fs == []


class TestParseTimeValidation:
    def test_flagged_field_without_flag(self, tmp_path):
        fs = lint(tmp_path, {
            "config.py": """
                import dataclasses

                @dataclasses.dataclass(frozen=True)
                class FedConfig:
                    num_rounds: int = 10
                    secret_knob: float = 0.0
            """,
            "run.py": """
                import argparse

                def parse_args():
                    p = argparse.ArgumentParser()
                    p.add_argument("--num_rounds", type=int)
                    return p.parse_args()

                def main(cfg):
                    return cfg.secret_knob * cfg.num_rounds
            """,
        }, rules=["parse-time-validation"])
        assert len(fs) == 1
        assert "secret_knob" in fs[0].message
        assert fs[0].path == "config.py"

    def test_duplicate_finding_fingerprint_survives_line_drift(
            self, tmp_path):
        """The duplicate-registration message must not embed line
        numbers: it feeds the baseline fingerprint, which the ratchet
        contract requires to survive unrelated edits."""
        src = """
            import argparse

            def parse_args():
                p = argparse.ArgumentParser()
                p.add_argument("--rounds", type=int)
                p.add_argument("--rounds", type=int)
                return p
        """
        fs1 = lint(tmp_path, {"b.py": src},
                   rules=["parse-time-validation"])
        (tmp_path / "b.py").write_text(
            "# drift\n# drift\n" + textwrap.dedent(src))
        fs2 = A.run_analysis([str(tmp_path)], root=str(tmp_path),
                             rules=["parse-time-validation"])
        assert len(fs1) == len(fs2) == 1
        assert fs1[0].line != fs2[0].line
        assert fs1[0].fingerprint == fs2[0].fingerprint

    def test_flagged_duplicate_and_reserved(self, tmp_path):
        cfg = A.AnalysisConfig(options={"parse-time-validation": {
            "reserved_flags": ["--slo"],
            "reserved_owner": "owner.py",
        }})
        fs = lint(tmp_path, {
            "owner.py": """
                import argparse

                def parse_args():
                    p = argparse.ArgumentParser()
                    p.add_argument("--slo", action="append")
                    return p
            """,
            "bench.py": """
                import argparse

                def parse_args():
                    p = argparse.ArgumentParser()
                    p.add_argument("--slo", type=str)   # reserved!
                    p.add_argument("--rounds", type=int)
                    p.add_argument("--rounds", type=int)  # duplicate
                    return p
            """,
        }, rules=["parse-time-validation"], config=cfg)
        msgs = " | ".join(f.message for f in fs)
        assert "reserved flag `--slo`" in msgs
        assert "registered twice" in msgs
        assert all(f.path == "bench.py" for f in fs)

    def test_clean_aliased_field(self, tmp_path):
        cfg = A.AnalysisConfig(options={"parse-time-validation": {
            "flag_aliases": {"num_rounds": "comm_round"}}})
        fs = lint(tmp_path, {
            "config.py": """
                import dataclasses

                @dataclasses.dataclass(frozen=True)
                class FedConfig:
                    num_rounds: int = 10
            """,
            "run.py": """
                import argparse

                def parse_args():
                    p = argparse.ArgumentParser()
                    p.add_argument("--comm_round", type=int)
                    return p.parse_args()

                def main(cfg):
                    return cfg.num_rounds
            """,
        }, rules=["parse-time-validation"], config=cfg)
        assert fs == []


class TestMessageEdge:
    def test_flagged_unnamed_unhandled_and_raw_subscript(self, tmp_path):
        fs = lint(tmp_path, {"actors.py": """
            MSG_FOO_PING = 200   # registered but unnamed
            MSG_FOO_DEAD = 201   # neither registered nor named

            class Actor:
                def __init__(self):
                    self.register_message_receive_handler(
                        MSG_FOO_PING, self._on_ping)

                def _on_ping(self, msg):
                    return msg.payload["x"]  # raw subscript
        """}, rules=["message-edge"])
        msgs = " | ".join(f.message for f in fs)
        assert "MSG_FOO_PING has no MSG_TYPE_NAMES" in msgs
        assert "MSG_FOO_DEAD has no register_message_receive_handler" \
            in msgs
        assert "MSG_FOO_DEAD has no MSG_TYPE_NAMES" in msgs
        assert "raw payload subscript" in msgs
        assert len(fs) == 4

    def test_clean_complete_edge(self, tmp_path):
        fs = lint(tmp_path, {"actors.py": """
            from fedml_tpu.core.message import MSG_TYPE_NAMES

            MSG_FOO_PING = 200

            MSG_TYPE_NAMES.update({MSG_FOO_PING: "foo_ping"})

            class Actor:
                def __init__(self):
                    self.register_message_receive_handler(
                        MSG_FOO_PING, self._on_ping)

                def _on_ping(self, msg):
                    x = msg.get("x")
                    if x is None:
                        return None
                    return x
        """}, rules=["message-edge"])
        assert fs == []


class TestRecompileHazard:
    def test_flagged_jit_invoked_in_loop(self, tmp_path):
        fs = lint(tmp_path, {"m.py": """
            import jax

            def f(x):
                return x

            def run(xs):
                out = []
                for x in xs:
                    out.append(jax.jit(f)(x))  # recompiles per iter
                return out
        """}, rules=["recompile-hazard"])
        assert len(fs) == 1 and "inside a loop" in fs[0].message

    def test_flagged_mutable_closure(self, tmp_path):
        fs = lint(tmp_path, {"m.py": """
            import jax

            def build(p):
                perm = [(i, (i + 1) % p) for i in range(p)]

                def run(x):
                    return x, perm

                return jax.jit(run)
        """}, rules=["recompile-hazard"])
        assert len(fs) == 1 and "`perm`" in fs[0].message

    def test_clean_deferred_compile_in_loop_body_def(self, tmp_path):
        """A def (or lambda) INSIDE the loop body defers the invocation
        to call time — building stored runners per bucket is the
        elastic idiom, not the per-iteration retrace hazard."""
        fs = lint(tmp_path, {"m.py": """
            import jax

            def f(x):
                return x

            def build(buckets, x):
                runners = []
                for b in buckets:
                    def runner(b=b):
                        return jax.jit(f)(x)  # runs at call, not here
                    runners.append(runner)
                    runners.append(lambda: jax.jit(f)(x))
                return runners
        """}, rules=["recompile-hazard"])
        assert fs == []

    def test_clean_stored_callables_and_frozen_closure(self, tmp_path):
        fs = lint(tmp_path, {"m.py": """
            import jax

            def f(x):
                return x

            def build_per_bucket(buckets, p):
                perm = tuple((i, (i + 1) % p) for i in range(p))
                compiled = []
                for b in buckets:
                    compiled.append(jax.jit(f))  # stored, lazy: fine

                def run(x):
                    return x, perm  # tuple closure: hashable

                return compiled, jax.jit(run)
        """}, rules=["recompile-hazard"])
        assert fs == []


# ---------------------------------------------------------------------------
# tier 2: framework — suppressions, exemptions, ratchet, fingerprints
# ---------------------------------------------------------------------------

IMPURE = """
    import time
    import jax

    def round_fn(state):
        t = time.time()
        return state

    compiled = jax.jit(round_fn)
"""


class TestFramework:
    def test_inline_suppression_with_reason(self, tmp_path):
        fs = lint(tmp_path, {"m.py": """
            import time
            import jax

            def round_fn(state):
                # fedlint: disable=jit-purity  trace-time stamp is the
                # point here: it labels the executable build, not a
                # per-round value
                t = time.time()
                return state

            compiled = jax.jit(round_fn)
        """}, rules=["jit-purity"])
        assert fs == []

    def test_suppression_is_rule_scoped(self, tmp_path):
        fs = lint(tmp_path, {"m.py": """
            import time
            import jax

            def round_fn(state):
                # fedlint: disable=lock-hygiene  wrong rule on purpose
                t = time.time()
                return state

            compiled = jax.jit(round_fn)
        """}, rules=["jit-purity"])
        assert len(fs) == 1  # a disable for another rule does nothing

    def test_file_level_suppression(self, tmp_path):
        fs = lint(tmp_path, {"m.py": """
            # fedlint: disable-file=jit-purity
            import time
            import jax

            def round_fn(state):
                return time.time(), state

            compiled = jax.jit(round_fn)
        """}, rules=["jit-purity"])
        assert fs == []

    def test_config_exemption_by_glob(self, tmp_path):
        cfg = A.AnalysisConfig(exempt={"jit-purity": ["bench*.py"]})
        fs = lint(tmp_path, {"bench_x.py": IMPURE},
                  rules=["jit-purity"], config=cfg)
        assert fs == []

    def test_fingerprint_stable_under_line_drift(self, tmp_path):
        fs1 = lint(tmp_path, {"m.py": IMPURE}, rules=["jit-purity"])
        (tmp_path / "m.py").write_text(
            "# a new leading comment\n# another\n"
            + textwrap.dedent(IMPURE))
        fs2 = A.run_analysis([str(tmp_path)], root=str(tmp_path),
                             rules=["jit-purity"])
        assert len(fs1) == len(fs2) == 1
        assert fs1[0].line != fs2[0].line  # lines drifted...
        assert fs1[0].fingerprint == fs2[0].fingerprint  # ...id did not

    def test_baseline_ratchet(self, tmp_path):
        """The CI contract: a baselined finding passes, a NEW finding
        fails, and --write-baseline freezes the current state."""
        proj = tmp_path / "proj"
        proj.mkdir()
        (proj / "m.py").write_text(textwrap.dedent(IMPURE))
        baseline = str(tmp_path / "baseline.json")
        cli = [sys.executable, os.path.join(REPO, "scripts",
                                            "fedlint.py")]
        env = dict(os.environ, PYTHONPATH=REPO)

        def run(*extra):
            return subprocess.run(
                [*cli, str(proj), "--root", str(proj),
                 "--rules", "jit-purity", "--baseline", baseline,
                 *extra],
                capture_output=True, text=True, env=env, cwd=REPO)

        r = run()
        assert r.returncode == 1, r.stdout + r.stderr  # unbaselined
        r = run("--write-baseline")
        assert r.returncode == 0, r.stdout + r.stderr
        r = run()
        assert r.returncode == 0, r.stdout + r.stderr  # frozen now
        assert "1 baselined" in r.stdout
        # a NEW finding rides in: the ratchet fails on it only
        (proj / "n.py").write_text(textwrap.dedent("""
            import random
            import jax

            def other_round(state):
                return random.random(), state

            compiled2 = jax.jit(other_round)
        """))
        r = run()
        assert r.returncode == 1
        assert "n.py" in r.stdout and "m.py" not in r.stdout

    def test_json_artifact_shape(self, tmp_path):
        proj = tmp_path / "proj"
        proj.mkdir()
        (proj / "m.py").write_text(textwrap.dedent(IMPURE))
        out = tmp_path / "fedlint.json"
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "fedlint.py"),
             str(proj), "--root", str(proj), "--rules", "jit-purity",
             "--json", str(out)],
            capture_output=True, text=True,
            env=dict(os.environ, PYTHONPATH=REPO), cwd=REPO)
        assert r.returncode == 1
        payload = json.loads(out.read_text())
        assert payload["baselined"] == []
        [f] = payload["new"]
        assert f["rule"] == "jit-purity" and f["path"] == "m.py"
        assert f["fingerprint"] and f["line"] > 0

    def test_unknown_rule_is_a_usage_error(self, tmp_path):
        (tmp_path / "m.py").write_text("x = 1\n")
        with pytest.raises(SystemExit):
            A.run_analysis([str(tmp_path)], root=str(tmp_path),
                           rules=["no-such-rule"])
        # ...and the CLI maps it to exit 2 (usage error), NEVER 1
        # ('new findings') — wrappers branch on the code
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "fedlint.py"),
             str(tmp_path), "--root", str(tmp_path),
             "--rules", "no-such-rule"],
            capture_output=True, text=True, cwd=REPO,
            env=dict(os.environ, PYTHONPATH=REPO))
        assert r.returncode == 2 and "unknown rule" in r.stderr

    def test_missing_target_is_a_usage_error(self, tmp_path):
        """A mistyped target must exit 2, not lint nothing and pass:
        exit 0 on a renamed directory would silently disable CI."""
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "fedlint.py"),
             "no_such_dir_xyz", "--root", str(tmp_path)],
            capture_output=True, text=True, cwd=str(tmp_path),
            env=dict(os.environ, PYTHONPATH=REPO))
        assert r.returncode == 2, r.stdout + r.stderr
        assert "no such target" in r.stderr

    def test_write_baseline_still_emits_json(self, tmp_path):
        proj = tmp_path / "proj"
        proj.mkdir()
        (proj / "m.py").write_text(textwrap.dedent(IMPURE))
        out = tmp_path / "artifact.json"
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "fedlint.py"),
             str(proj), "--root", str(proj), "--rules", "jit-purity",
             "--baseline", str(tmp_path / "b.json"),
             "--write-baseline", "--json", str(out)],
            capture_output=True, text=True, cwd=REPO,
            env=dict(os.environ, PYTHONPATH=REPO))
        assert r.returncode == 0, r.stdout + r.stderr
        payload = json.loads(out.read_text())
        assert payload["new"] == [] and len(payload["baselined"]) == 1


# ---------------------------------------------------------------------------
# tier 3: pre-fix regression pins — the violations this PR fixed, each
# demonstrated caught by the linter on a fixture copy of the OLD code
# ---------------------------------------------------------------------------

#: excerpt of docs/OBSERVABILITY.md's vocabulary as it stood BEFORE this
#: PR added the perf.profile.window_s / recovery.rejoins_reconciled rows
PREFIX_VOCAB = """
| name | kind | meaning |
|---|---|---|
| `perf.profile.{compute,collective,host,idle}_frac` | gauge | breakdown |
| `perf.profiled_rounds` | counter | capture windows taken |
| `recovery.rejoins` | counter | mid-run JOINs re-added |
"""


class TestPreFixViolations:
    def test_prefix_undocumented_metrics_caught(self, tmp_path):
        """Pre-fix core/perf.py and distributed_fedavg.py wrote two
        metric names missing from the vocabulary tables."""
        fs = lint(tmp_path, {
            "docs/OBSERVABILITY.md": PREFIX_VOCAB,
            "perf.py": """
                from fedml_tpu.core import telemetry

                def record(bd):
                    m = telemetry.METRICS
                    m.inc("perf.profiled_rounds")
                    for k in ("compute_frac", "idle_frac"):
                        m.gauge(f"perf.profile.{k}", bd[k])
                    m.gauge("perf.profile.window_s", bd["window_s"])
            """,
            "actor.py": """
                from fedml_tpu.core import telemetry

                def start_round(stranded):
                    if stranded:
                        telemetry.METRICS.inc(
                            "recovery.rejoins_reconciled",
                            len(stranded))
            """,
        }, rules=["metric-vocabulary"],
            config=A.AnalysisConfig(
                vocabulary_doc="docs/OBSERVABILITY.md"))
        msgs = " | ".join(f.message for f in fs)
        assert "perf.profile.window_s" in msgs
        assert "recovery.rejoins_reconciled" in msgs

    def test_postfix_vocabulary_covers_them(self):
        """...and against the REAL (fixed) vocabulary doc the same
        writes are clean."""
        doc = open(os.path.join(REPO, "docs",
                                "OBSERVABILITY.md")).read()
        assert "`perf.profile.window_s`" in doc
        assert "`recovery.rejoins_reconciled`" in doc

    def test_prefix_unnamed_split_actor_types_caught(self, tmp_path):
        """Pre-fix split_actors.py minted 9 MSG_* constants with no
        MSG_TYPE_NAMES entries — per-type byte counters fell back to
        bare integers."""
        fs = lint(tmp_path, {"split_actors.py": """
            MSG_SNN_TURN = 100
            MSG_SNN_ACTS = 101

            class SplitNNServerActor:
                def __init__(self):
                    self.register_message_receive_handler(
                        MSG_SNN_TURN, self._on_turn)
                    self.register_message_receive_handler(
                        MSG_SNN_ACTS, self._on_acts)

                def _on_turn(self, msg):
                    return msg.get("turn")

                def _on_acts(self, msg):
                    return msg.get("acts")
        """}, rules=["message-edge"])
        assert len(fs) == 2
        assert all("no MSG_TYPE_NAMES entry" in f.message for f in fs)

    def test_postfix_split_actor_types_named(self):
        from fedml_tpu.algorithms import split_actors as SA
        from fedml_tpu.core.message import MSG_TYPE_NAMES, msg_type_name

        for const in (SA.MSG_SNN_TURN, SA.MSG_SNN_ACTS,
                      SA.MSG_SNN_GRADS, SA.MSG_SNN_EPOCH_DONE,
                      SA.MSG_GKT_START, SA.MSG_GKT_FEATURES,
                      SA.MSG_VFL_STEP, SA.MSG_VFL_COMPONENT,
                      SA.MSG_VFL_GRAD):
            assert const in MSG_TYPE_NAMES
            assert not msg_type_name(const).isdigit()

    def test_prefix_flagless_server_opt_fields_caught(self, tmp_path):
        """Pre-fix FedConfig.server_optimizer/server_lr/
        server_momentum/gmf were read by server_update but registered
        no CLI flag — settable only by hand-editing config JSON,
        bypassing parse-time validation."""
        fs = lint(tmp_path, {
            "config.py": """
                import dataclasses

                @dataclasses.dataclass(frozen=True)
                class FedConfig:
                    num_rounds: int = 10
                    server_optimizer: str = "sgd"
                    server_lr: float = 1.0
                    server_momentum: float = 0.0
                    gmf: float = 0.0
            """,
            "run.py": """
                import argparse

                def parse_args():
                    p = argparse.ArgumentParser()
                    p.add_argument("--num_rounds", type=int)
                    return p.parse_args()
            """,
            "fedavg.py": """
                def server_update(fed, state, delta):
                    opt = make_server_optimizer(
                        fed.server_optimizer, fed.server_lr,
                        fed.server_momentum)
                    if fed.gmf > 0:
                        delta = delta * fed.gmf
                    return opt, state, delta
            """,
        }, rules=["parse-time-validation"])
        flagged = {f.message.split()[0] for f in fs}
        assert flagged == {
            "FedConfig.server_optimizer", "FedConfig.server_lr",
            "FedConfig.server_momentum", "FedConfig.gmf",
        }

    def test_postfix_run_cli_registers_server_opt_flags(self):
        import fedml_tpu.experiments.run as run

        src = open(run.__file__.replace(".pyc", ".py")).read()
        for flag in ("--server_optimizer", "--server_lr",
                     "--server_momentum", "--gmf"):
            assert f'"{flag}"' in src, flag

    def test_postfix_server_opt_validated_at_parse_time(self):
        from fedml_tpu.experiments.run import parse_args

        base = ["--algorithm", "fedavg"]
        with pytest.raises(SystemExit, match="server_lr"):
            parse_args([*base, "--server_lr", "-0.5"])
        with pytest.raises(SystemExit, match="server_momentum"):
            parse_args([*base, "--server_momentum", "1.5"])
        with pytest.raises(SystemExit, match="gmf"):
            parse_args([*base, "--gmf", "2.0"])

    def test_prefix_dead_message_edge_caught(self, tmp_path):
        """Pre-fix MSG_TYPE_S2C_INIT existed since the seed, named in
        MSG_TYPE_NAMES but never sent nor handled anywhere."""
        fs = lint(tmp_path, {"message.py": """
            MSG_TYPE_S2C_INIT = 1
            MSG_TYPE_FINISH = 4

            MSG_TYPE_NAMES = {
                MSG_TYPE_S2C_INIT: "s2c_init",
                MSG_TYPE_FINISH: "finish",
            }

            class Manager:
                def __init__(self):
                    self.register_message_receive_handler(
                        MSG_TYPE_FINISH, self._on_finish)

                def _on_finish(self, msg):
                    return msg.get("reason")
        """}, rules=["message-edge"])
        assert len(fs) == 1
        assert "MSG_TYPE_S2C_INIT has no " \
               "register_message_receive_handler" in fs[0].message

    def test_postfix_s2c_init_removed(self):
        from fedml_tpu.core import message as M

        assert not hasattr(M, "MSG_TYPE_S2C_INIT")
        assert 1 not in M.MSG_TYPE_NAMES  # the int stays reserved

    def test_prefix_mutable_pipeline_closure_caught(self, tmp_path):
        """Pre-fix ops/pipeline.py built `perm` as a list and closed
        over it in the shard_map'd `run`."""
        fs = lint(tmp_path, {"pipeline.py": """
            from jax import shard_map

            def make_pipeline(stage_fn, mesh, p):
                perm = [(i, (i + 1) % p) for i in range(p)]

                def run(params, x):
                    return stage_fn(params, x), perm

                return shard_map(run, mesh=mesh)
        """}, rules=["recompile-hazard"])
        assert len(fs) == 1 and "`perm`" in fs[0].message

    def test_scan_from_outside_repo_root(self, tmp_path):
        """--root defaults to the nearest fedlint.json directory above
        the first target, so an invocation from ANY cwd loads the repo
        config and produces baseline-stable relative paths."""
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "fedlint.py"),
             os.path.join(REPO, "fedml_tpu"), "--baseline",
             os.path.join(REPO, "fedlint_baseline.json")],
            capture_output=True, text=True, cwd=str(tmp_path),
            env=dict(os.environ, PYTHONPATH=REPO))
        assert r.returncode == 0, r.stdout + r.stderr

    def test_subset_scan_skips_stale_row_direction(self):
        """Linting a subtree must not indict every vocabulary row
        whose writer lives elsewhere: the doc->code direction is gated
        on the scan covering the metrics-registry implementation."""
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "fedlint.py"), "scripts"],
            capture_output=True, text=True, cwd=REPO,
            env=dict(os.environ, PYTHONPATH=REPO))
        assert r.returncode == 0, r.stdout + r.stderr
        assert "no write site" not in r.stdout

    def test_json_stdout_is_pure_json(self):
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "fedlint.py"), "scripts",
             "--json", "-"],
            capture_output=True, text=True, cwd=REPO,
            env=dict(os.environ, PYTHONPATH=REPO))
        payload = json.loads(r.stdout)  # no trailing human summary
        assert "new" in payload and "baselined" in payload
        assert "fedlint:" in r.stderr  # the summary moved to stderr

    def test_whole_tree_scan_is_clean(self):
        """The e2e acceptance pin: fedlint over the real fedml_tpu/ +
        scripts/ (what ci.sh scans) exits 0 with the SHIPPED baseline (and the
        shipped baseline is genuinely empty: every pre-existing
        violation was fixed, not frozen)."""
        r = subprocess.run(
            [sys.executable, "scripts/fedlint.py", "fedml_tpu",
             "scripts", "--baseline", "fedlint_baseline.json"],
            capture_output=True, text=True, cwd=REPO,
            env=dict(os.environ, PYTHONPATH=REPO))
        assert r.returncode == 0, r.stdout + r.stderr
        assert "0 new finding(s)" in r.stdout
        shipped = json.load(open(os.path.join(
            REPO, "fedlint_baseline.json")))
        assert shipped["findings"] == []


# ---------------------------------------------------------------------------
# tier 5: the shared flag-registration checker
# ---------------------------------------------------------------------------

class TestFlagRegistry:
    def _parser(self, *flags):
        p = argparse.ArgumentParser()
        for f in flags:
            p.add_argument(f)
        return p

    def test_non_owner_clean(self):
        check_flag_registry(self._parser("--rounds", "--family"),
                            entrypoint="chip_smoke.py")

    def test_non_owner_reserved_rejected(self):
        with pytest.raises(SystemExit, match="--slo"):
            check_flag_registry(self._parser("--rounds", "--slo"),
                                entrypoint="chip_smoke.py")

    def test_owner_must_register_reserved(self):
        p = self._parser("--slo", "--metrics_port")
        check_flag_registry(p, owner=True, entrypoint="run")
        with pytest.raises(SystemExit, match="metrics_port"):
            check_flag_registry(self._parser("--slo"), owner=True,
                                entrypoint="run")

    def test_rank_argv_check(self):
        check_rank_argv(["run", "--metrics_port", "0"], rank=0)
        check_rank_argv(["run", "--rounds", "3"], rank=2)
        with pytest.raises(SystemExit, match="rank-0-only"):
            check_rank_argv(["run", "--metrics_port", "0"], rank=2)
        # the `--flag=value` form argparse also accepts must be caught
        with pytest.raises(SystemExit, match="rank-0-only"):
            check_rank_argv(["run", "--metrics_port=9000"], rank=2)

    def test_run_parser_passes_owner_check(self):
        from fedml_tpu.experiments.run import parse_args

        cfg, a = parse_args(["--algorithm", "fedavg"])
        assert cfg.fed.algorithm == "fedavg"
