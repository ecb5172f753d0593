"""Graceful degradation, fault injection, and the strict-balance gate.

Three pieces (see docs/robustness.md for the operator view):

  * the **degradation contract** — structured exception types
    (errors.py) plus :func:`with_fallback`, the policy wrapper with
    bounded retry and a per-site circuit breaker (policy.py), wired
    through every optional fast path so a failure degrades visibly (a
    ``degraded`` telemetry event) instead of aborting the run or going
    silent;
  * the **fault-injection harness** — ``KAMINPAR_TPU_FAULTS`` site plans
    (faults.py), deterministic by seed, driving the chaos suite
    (tests/test_resilience.py) and the check_all.sh chaos smoke stage;
  * the **strict-balance output gate** — end-of-pipeline host validation
    of partition invariants with a greedy repair pass (gate.py), so
    ``KaMinPar.compute_partition``'s postcondition holds no matter which
    paths degraded;
  * **preemption-safe checkpoint/resume** — atomic barrier snapshots of
    the multilevel state under ``--checkpoint-dir`` with a versioned,
    checksummed manifest, and ``--resume`` re-entry at the recorded
    stage (checkpoint.py);
  * the **deadline budget / anytime contract** — ``--time-budget`` plus
    SIGTERM/SIGINT routing: cooperative wind-down at the same barriers,
    returning a gate-valid partition annotated ``anytime: true`` instead
    of a stack trace (deadline.py).
"""

from .errors import (  # noqa: F401
    AdmissionRejected,
    CacheDegraded,
    CheckpointCorrupt,
    CheckpointMismatch,
    CheckpointWriteFailed,
    CollectiveTimeout,
    DegradationError,
    DeltaApplyFailed,
    DeviceOOM,
    IntegrityViolation,
    NativeUnavailable,
    RankDivergence,
    RefinerRefused,
    StageHang,
    WorkerCrash,
    classify,
)
from .faults import (  # noqa: F401
    ENV_VAR as FAULTS_ENV_VAR,
    FaultPlanError,
    SITES,
    injected_log,
    maybe_inject,
    parse_plan,
    plan_summary,
    site_spec,
)
from .policy import (  # noqa: F401
    BREAKER_THRESHOLD,
    breaker_state,
    reset_breakers,
    with_fallback,
)
from . import gate  # noqa: F401
from . import integrity  # noqa: F401
from . import checkpoint  # noqa: F401
from . import deadline  # noqa: F401
from . import agreement  # noqa: F401
from . import supervisor  # noqa: F401


def reset() -> None:
    """Reset injection counters, circuit breakers, the active checkpoint
    manager, any armed deadline, the dist agreement/sentinel state, and
    the supervision watchdog/heartbeat counters (test isolation)."""
    from . import faults as _faults

    _faults.reset()
    reset_breakers()
    integrity.reset()
    checkpoint.deactivate()
    deadline.clear()
    agreement.disarm()
    agreement.set_gather_override(None)
    supervisor.reset()
