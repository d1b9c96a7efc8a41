"""Shared CLI flag-registration checker — the RUNTIME twin of the
parse-time-validation lint rule (docs/STATIC_ANALYSIS.md).

One registration contract for every entrypoint (run.py, the
deploy/supervisor argv builders):

- :data:`RESERVED_RUN_FLAGS` names the option strings owned by the run
  CLI's SLO/export plane. ``--slo`` means an SloSpec and
  ``--metrics_port`` means the OpenMetrics listener on EVERY
  entrypoint — another program minting its own ``--slo`` would shadow
  those semantics, so registering a collision fails loudly at parser
  build, not at first confused use. (Duplicate option strings need no
  runtime check: argparse already raises at ``add_argument`` time —
  the STATIC side of this contract, including literal duplicates, is
  the fedlint parse-time-validation rule.)

``check_flag_registry(parser)`` is the check for a non-owning
entrypoint; the owner (run.py) calls it with ``owner=True``, which
additionally asserts the reserved flags are actually registered — the
reservation must never outlive the plane it protects.
"""

from __future__ import annotations

#: option strings owned by the run CLI's live-observability plane
#: (fedml_tpu/experiments/run.py: the SLO engine + OpenMetrics
#: exporter). The supervisor also strips these from client argv —
#: clients would collide on one bind (run.py keeps --metrics_port on
#: rank 0 only).
RESERVED_RUN_FLAGS = ("--slo", "--metrics_port")


def registered_option_strings(parser) -> list[str]:
    """Every option string the parser knows, in registration order."""
    return [s for act in parser._actions for s in act.option_strings]


def check_flag_registry(parser, *, reserved=RESERVED_RUN_FLAGS,
                        owner: bool = False,
                        entrypoint: str = "this entrypoint") -> None:
    """Validate a built parser's registrations. Raises ``SystemExit``
    (a config error the operator must fix, not a crash to swallow) on
    a reserved flag registered by a non-owner, or on a reserved flag
    MISSING from the owner. (Duplicates cannot survive to this point —
    argparse raises at ``add_argument`` time.)"""
    taken = registered_option_strings(parser)
    clash = sorted(set(taken).intersection(reserved))
    if owner:
        missing = sorted(set(reserved) - set(taken))
        if missing:
            raise SystemExit(
                f"{entrypoint} owns reserved flag(s) {missing} but "
                f"does not register them — the reservation must not "
                f"outlive the plane it protects "
                f"(fedml_tpu/analysis/flags.py)"
            )
        return
    if clash:
        raise SystemExit(
            f"{entrypoint} registered reserved flag(s) {clash}: these "
            f"names belong to the run CLI's SLO/export plane "
            f"(fedml_tpu/experiments/run.py) — rename the flag "
            f"(fedml_tpu/analysis/flags.py)"
        )


#: flags that name ONE listener bind (or one deep-profiling session)
#: per world and therefore belong to rank 0 only — every client of a
#: supervised world inheriting ``--metrics_port`` would collide on the
#: same bind, and every client inheriting ``--profile_on_breach``
#: would arm its own jax.profiler against a per-rank SLO view when
#: the breach the operator cares about is the round the SERVER closes
#: (run.py strips them from client argv; the Supervisor re-checks at
#: spawn)
RANK0_ONLY_FLAGS = ("--metrics_port", "--profile_on_breach")


def check_rank_argv(argv, rank: int) -> None:
    """Spawn-time safety net for supervised worlds: a client rank's
    argv must not carry a rank-0-exclusive bind flag. run.py's
    ``--supervise`` path strips them when BUILDING the argv; this
    re-check catches hand-built :class:`RankSpec` lists taking the
    same shortcut without the strip."""
    if rank == 0:
        return
    # match both argv forms argparse accepts: `--flag value` and
    # `--flag=value`
    present = {str(tok).split("=", 1)[0] for tok in argv}
    clash = sorted(present & set(RANK0_ONLY_FLAGS))
    if clash:
        raise SystemExit(
            f"client rank {rank} argv carries rank-0-only flag(s) "
            f"{clash} — every client would collide on the same bind; "
            f"strip them from client argv "
            f"(fedml_tpu/analysis/flags.py RANK0_ONLY_FLAGS)"
        )
