"""Structured degradation exceptions — the failure vocabulary of the
pipeline's optional fast paths.

Every optional accelerator path (native FM/IP via the C-API,
compressed-graph streaming, device balancers, distributed collectives)
can refuse, crash, or time out.  Instead of a
bare ``except Exception`` at each call site (a tpulint-documented hazard,
docs/static_analysis.md), failures are raised as one of these types and
routed through :func:`kaminpar_tpu.resilience.with_fallback`, which pairs
each registered *site* with its documented fallback and emits a
``degraded`` telemetry event (docs/robustness.md has the full matrix).

The hierarchy is deliberately flat: callers either handle
:class:`DegradationError` (the policy wrapper) or a specific subtype
(tests, site-local handling).  ``injected=True`` marks exceptions raised
by the fault-injection harness (``KAMINPAR_TPU_FAULTS``) so chaos tests
can tell simulated failures from real ones in the telemetry stream.
"""

from __future__ import annotations

from typing import Optional


class DegradationError(RuntimeError):
    """Base of all structured fast-path failures.

    Attributes:
      site      registered fault-site name ("" until the policy wrapper
                stamps it)
      injected  True when raised by the fault-injection harness

    Class attribute ``breaker_relevant``: whether failures of this type
    advance the site's circuit breaker.  Crash-shaped failures (missing
    native lib, OOM, timeout) do; deterministic data-dependent REFUSALS
    (plan blowup on a skewed level, FM refusing a too-large k) do not —
    a legitimate refusal on one input must not disable the fast path
    for the next input.
    """

    breaker_relevant = True

    def __init__(
        self,
        message: str = "",
        *,
        site: Optional[str] = None,
        injected: bool = False,
    ) -> None:
        super().__init__(message or type(self).__name__)
        self.site = site or ""
        self.injected = bool(injected)


class NativeUnavailable(DegradationError):
    """The native (C++/ctypes) component could not be built, loaded, or
    run — missing toolchain, build timeout, or a corrupted build cache.
    Fallback: the pure-numpy/ctypes-free twin of the same entry point."""


class RefinerRefused(DegradationError):
    """A refiner declined to run at the current (n, k) — e.g. native FM's
    INT64_MIN sentinel when k exceeds the sparse engine's 16-bit packed
    tags and the dense (n, k) table is unaffordable.  Fallback: return
    the partition unchanged (refusal, not failure: no moves were made).
    Does not advance the circuit breaker."""

    breaker_relevant = False


class CollectiveTimeout(DegradationError):
    """A cross-process collective (timer aggregation, metric allgather)
    timed out or failed.  Fallback: continue with local-only data."""


class CheckpointWriteFailed(DegradationError):
    """A checkpoint snapshot or manifest could not be written (disk full,
    permissions, injected fault).  Fallback: the run continues with
    in-memory-only checkpoints — losing durability, never the run."""


class CheckpointCorrupt(DegradationError):
    """A checkpoint snapshot failed its content checksum (truncated or
    bit-rotted file) or the manifest would not parse.  Fallback: the
    previous manifest generation.  A property of stored data, not of the
    process: does not advance the circuit breaker."""

    breaker_relevant = False


class CheckpointMismatch(DegradationError):
    """A checkpoint exists but belongs to a different run: the graph
    fingerprint or the context fingerprint recorded in the manifest does
    not match the current invocation.  Policy: clean restart (ignore the
    checkpoint), never a crash and never a silent resume of foreign
    state.  A refusal, not a fault: does not advance the breaker."""

    breaker_relevant = False


class AdmissionRejected(DegradationError):
    """The serving layer's admission controller refused a request —
    queue depth or estimated-cost cap exceeded, a draining service, an
    open per-request-class breaker, or an injected `serving-admit`
    fault.  Fallback: a structured `rejected` verdict for that request;
    the service keeps serving.  A policy decision, not a fault: does
    not advance the circuit breaker."""

    breaker_relevant = False


class CacheDegraded(DegradationError):
    """A bounded-cache lookup was forced to miss (or an entry forcibly
    evicted) — today only via the `serving-cache` injection site; a
    future persistent cache backend would surface real read failures
    the same way.  Fallback: recompute the request.  Correctness is
    untouched (caches are an optimization), so the breaker ignores it.
    """

    breaker_relevant = False


class DeltaApplyFailed(DegradationError):
    """The in-place CSR delta-apply of a dynamic graph session failed —
    today only via the `dynamic-apply` injection site; a real failure
    class would be a patched bucket disagreeing with the device arrays.
    Fallback: the session rebuilds the CSR and re-uploads into a fresh
    bucket (the bucket-crossing path) — strictly more work, never a
    wrong graph, so the breaker ignores it."""

    breaker_relevant = False


class RankDivergence(DegradationError):
    """The cross-rank divergence sentinel fired: at a dist pipeline
    barrier the ranks disagreed on the stage id, the memory-ladder rung,
    or the run fingerprint (graph/ctx/sharding plan) — one rank silently
    skipped a barrier, took a different recovery path, or is running a
    different problem.  There is no safe local fallback (continuing
    would deadlock a collective or return a wrong answer), so this is a
    structured ABORT carrying ``ranks``, the per-rank state dump the
    sentinel gathered (also annotated into the run report's
    ``dist_resilience`` section before the raise).  Crash-shaped: it
    advances the circuit breaker."""

    def __init__(
        self,
        message: str = "",
        *,
        ranks=None,
        site: Optional[str] = None,
        injected: bool = False,
    ) -> None:
        super().__init__(message, site=site, injected=injected)
        self.ranks = list(ranks or [])


class StageHang(DegradationError):
    """A pipeline stage exceeded its HARD wall-clock ceiling
    (resilience/supervisor.py): a hung backend init, a hung device
    launch, or a supervised worker that stopped answering.  The
    cooperative deadline budget cannot interrupt these — it is checked
    between launches — so the watchdog converts them into this
    structured, breaker-relevant failure instead of an eternal block.

    ``stage`` is the armed stage name, ``scope_path`` the (best-effort)
    dotted timer-scope path that was open when the ceiling expired —
    i.e. where the run was stuck — and ``ceiling_s`` the ceiling that
    was exceeded.  Raised with site ``worker-hang`` by the worker
    supervisor's SIGKILL path; async-delivered (no site) by the
    in-process watchdog.  Crash-shaped: it advances the breaker."""

    def __init__(
        self,
        message: str = "",
        *,
        stage: str = "",
        scope_path: str = "",
        ceiling_s: Optional[float] = None,
        site: Optional[str] = None,
        injected: bool = False,
    ) -> None:
        super().__init__(message, site=site, injected=injected)
        self.stage = stage
        self.scope_path = scope_path
        self.ceiling_s = ceiling_s


class IntegrityViolation(DegradationError):
    """An integrity sentinel or exchange digest detected silent data
    corruption (resilience/integrity.py): a conservation invariant
    broken across a contraction, a partition vector out of range, an
    accepted refinement pass that *increased* the cut, a content digest
    that no longer matches its bytes (spill re-read, worker reply,
    cached result), or a sampled re-execution audit that disagreed with
    the device bitwise.

    ``invariant`` names the violated check (the degradation-matrix row),
    ``level`` the hierarchy level it fired at (None outside the
    multilevel drivers), ``scope_path`` the phase boundary.  NEVER
    absorbed by ``policy.with_fallback`` — a corrupted value has no
    documented fallback twin; the only safe responses are the bounded
    retry-from-last-good-barrier ladder (integrity.run_with_retry) or,
    for exchange digests, a re-fetch from the source of truth.
    Crash-shaped: it advances the circuit breaker."""

    def __init__(
        self,
        message: str = "",
        *,
        invariant: str = "",
        level: Optional[int] = None,
        scope_path: str = "",
        site: Optional[str] = None,
        injected: bool = False,
    ) -> None:
        super().__init__(message, site=site, injected=injected)
        self.invariant = invariant
        self.level = level
        self.scope_path = scope_path


class WorkerCrash(DegradationError):
    """A supervised worker subprocess died — segfault in the native
    library, allocator kill, or an injected SIGKILL (the
    ``worker-crash`` chaos site).  The supervisor detects the death,
    surfaces it as this structured failure for that request alone, and
    keeps draining the queue with a fresh worker.  ``exit_code`` is the
    subprocess's exit code (negative = killed by that signal).
    Crash-shaped: it advances the breaker."""

    #: Exit code of the dead worker (None when it could not be read).
    exit_code: Optional[int] = None


class DeviceOOM(DegradationError):
    """The accelerator (or host, for MemoryError) ran out of memory in an
    optional fast path.  Fallback: the path's smaller-footprint twin
    (host balancer, uncompressed CSR, XLA gather) — and, anywhere under
    ``compute_partition``, the memory governor's recovery ladder
    (resilience/memory.py): the run retries at the next rung instead of
    surfacing RESOURCE_EXHAUSTED.

    ``rungs_exhausted`` is stamped True by the ladder only when every
    rung (including the host-only path) failed — THAT is the
    crash-shaped verdict the serving per-class breaker may latch on; a
    ladder-retryable OOM never escapes the facade, so it can never latch
    anything (the serving boundary additionally refuses to count a
    ``rungs_exhausted=False`` OOM as a crash — the belt-and-braces for a
    governor-disabled process)."""

    #: True only when the recovery ladder ran out of rungs (set by
    #: resilience/memory.py); a plain DeviceOOM is ladder-retryable.
    rungs_exhausted = False


#: Raw-exception markers that classify as DeviceOOM.  XLA surfaces
#: allocator failure as XlaRuntimeError("RESOURCE_EXHAUSTED: ...").
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")


def classify(exc: BaseException, site: str) -> Optional[DegradationError]:
    """Map a raw exception to a structured degradation, or None.

    * DegradationError passes through (site stamped if missing);
    * MemoryError and XLA RESOURCE_EXHAUSTED become :class:`DeviceOOM`;
    * anything else returns None — the caller must re-raise, NOT swallow
      (an unclassified exception is a bug, not a degradation).
    """
    if isinstance(exc, DegradationError):
        if not exc.site:
            exc.site = site
        return exc
    if isinstance(exc, MemoryError):
        err = DeviceOOM(f"host allocation failed: {exc}", site=site)
        err.__cause__ = exc
        return err
    text = f"{type(exc).__name__}: {exc}"
    if any(marker in text for marker in _OOM_MARKERS):
        err = DeviceOOM(text, site=site)
        err.__cause__ = exc
        return err
    return None
