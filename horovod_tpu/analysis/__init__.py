"""hvdlint: static collective-consistency and lock-order analysis.

The runtime controller (``ops/controller.py``) diagnoses rank divergence
only after a job has stalled for ``HOROVOD_STALL_CHECK_TIME`` seconds on
real hardware.  The classic Horovod failure classes — collectives under
rank-conditional branches, missing initial-state broadcast, mismatched
submission order — are statically detectable in user scripts, so this
package catches them in CI instead of on a TPU reservation.

Six engines:

* **user-script rules** (``user_rules.py``): HVD001–HVD006, AST checks
  over training scripts for the deadlock/divergence hazard classes —
  rank/except/jit hazards see through one level of helper functions.
* **lock-order self-check** (``lock_order.py``): HVD101–HVD103, a
  lock-acquisition-graph deadlock detector over our own threaded modules
  (engine, controller, elastic driver, stall inspector).
* **guarded-by self-check** (``guarded_by.py`` over ``callgraph.py``):
  HVD110–HVD115, Eraser-style lock-set race detection — each shared
  attribute's guard is inferred from the lock held at the majority of
  its access sites, and unguarded writes / read-modify-writes / torn
  reads / init-time publication races are reported.  A findings
  baseline (``tools/hvdlint_baseline.json``, ``--baseline`` /
  ``--update-baseline``) lets CI fail only on NEW findings.
* **SPMD divergence dataflow** (``divergence.py``): HVD200–HVD211,
  rank-divergent control flow / operand shapes / collective parameters,
  plus the committed collective-schedule snapshot checks.
* **cross-artifact contracts** (``contracts.py``): HVD300–HVD307, the
  repo-wide pass keeping config rows, docs tables, metric families,
  RPC handler tables, chaos sites and the negotiation token schema in
  lockstep.
* **concurrency lifecycle** (``lifecycle.py``): HVD400–HVD407,
  blocking-under-lock (interprocedural over the call graph), unbounded
  job-lifetime growth, wall/monotonic clock mixing, and shutdown
  hygiene (unjoined threads, unwakeable stop loops, stuck
  edge-triggers).

CLI::

    python -m horovod_tpu.analysis horovod_tpu/ examples/
    tools/hvdlint --format=json path/to/train.py

Suppress a finding with ``# hvdlint: disable=HVD001`` on (or directly
above) the flagged line, or skip a whole file with
``# hvdlint: skip-file``.  See docs/analysis.md for the rule catalog.

The analysis modules themselves import only the standard library (no
jax, no numpy), so a lint run costs AST parsing, nothing more.  (The
``horovod_tpu`` parent package still imports its runtime deps on entry,
so the CLI needs the normal install — as in CI.)
"""

from .report import Finding, RULES, iter_suppressions  # noqa: F401
from .cli import analyze_paths, analyze_source, main  # noqa: F401

__all__ = [
    "Finding", "RULES", "analyze_paths", "analyze_source", "main",
    "iter_suppressions",
]
