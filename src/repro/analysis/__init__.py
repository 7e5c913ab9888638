"""repro.analysis — static verification of engine, concurrency, and
dataflow-spec invariants.

Three analyzers share one :class:`Finding`/:class:`Waiver` schema and
one CLI (``python -m repro.launch.lint``):

* :mod:`repro.analysis.concurrency` — AST linter over ``src/repro/``
  for unlocked shared-state mutation in the threaded modules;
* :mod:`repro.analysis.speclint` — static legality of dataflow programs
  and ``Query`` specs before any compile;
* :mod:`repro.analysis.jaxpr_audit` — jaxpr-level invariants of every
  universal executable family (f64, callbacks, const-folded operands,
  reduce shrink, primitive budget).

``run_repo_lint`` is the cheap, jax-free pass (concurrency + shipped
dataflow corpus); ``run_full`` adds the jaxpr audit.  Both return raw
findings — apply ``load_waivers``/``apply_waivers`` to honour the
checked-in ``waivers.toml``.
"""
from __future__ import annotations

from typing import Any

from .findings import (CODES, DEFAULT_WAIVERS, Finding, Waiver,
                       apply_waivers, load_waivers, sort_findings)

__all__ = ["CODES", "DEFAULT_WAIVERS", "Finding", "Waiver",
           "apply_waivers", "load_waivers", "run_full", "run_repo_lint",
           "sort_findings"]


def run_repo_lint() -> list[Finding]:
    """The jax-free analyzers: concurrency lint over the source tree +
    legality lint over the shipped dataflow corpus."""
    from . import concurrency, speclint
    return sort_findings(concurrency.lint_tree() + speclint.lint_corpus())


def run_full(device_counts: tuple[int, ...] = (1,)
             ) -> tuple[list[Finding], dict[str, Any]]:
    """Everything: repo lint + the jaxpr audit.  Returns the findings
    and the audit's primitive-count report."""
    from . import jaxpr_audit
    findings = run_repo_lint()
    audit_findings, report = jaxpr_audit.audit(device_counts)
    return sort_findings(findings + audit_findings), report
