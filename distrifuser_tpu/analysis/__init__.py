"""distrilint: repo-native static analysis for the invariants PRs re-prove.

The system's correctness under load rests on cross-cutting contracts that
no single module owns — every trace-affecting serve knob mirrored into
`ExecKey` (serve/cache.py), every collective routed through the
WIRE_REGISTRY-accounted helpers so the comm_plan/StepTimeline exact
reconciliation stays exhaustive (parallel/context.py), serve-layer
mutations respecting the scheduler-thread/lock ownership rules
(serve/resilience.py), typed outcomes on every serve failure path
(serve/errors.py), and the stale-exchange collectives staying deferred to
the carry (utils/overlap.py; the PipeFusion/FastUSP overlap contracts).
Until now these were enforced by comments, reviewer memory, and
`slow`-marked 8-device HLO tests that never run on the 2-core tier-1
runner.  This package machine-checks them:

* each **checker** (analysis/checkers/) emits structured `Finding`s with
  ``file:line``, severity, and a stable fingerprint;
* **suppressions** live in a checked-in baseline (analysis/baseline.txt)
  where every entry requires a ``# provenance:`` reason line — the same
  contract the attention route table enforces on its rows (each names the
  origin of its verdict; the `route-tables` checker);
* ``python -m distrifuser_tpu.analysis --strict`` is the one entry point,
  wired into tier-1 CI as a hard gate before pytest.

See docs/ANALYSIS.md for the checker catalog and the baseline workflow.
"""

from __future__ import annotations

from .core import (  # noqa: F401
    Baseline,
    BaselineError,
    CheckContext,
    Finding,
    apply_baseline,
    render_baseline,
)
from .registry import all_checkers, get_checker, run_checkers  # noqa: F401
