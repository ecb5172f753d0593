"""tpulint — codebase-specific AST static analysis for the JAX pipeline.

The round-5 verdict and advisor findings were all *statically visible*
in the Python source before they cost a round: the C-ABI driver eagerly
initialized a TPU backend despite ``JAX_PLATFORMS=cpu`` and hung the
suite 600 s; trace-time comm accounting silently under/over-counted;
int32 tags and accumulators capped scale.  tpulint encodes each incident
class as a rule so future perf PRs cannot silently reintroduce them:

  R1  host-sync primitives (``.item()``, ``int()/float()/bool()`` of jax
      values, ``np.asarray`` of device values, Python ``if`` on traced
      expressions) inside functions reachable from ``jax.jit``-decorated
      code or inside telemetry span scopes;
  R2  eager/ungated device or backend queries — ``jax.devices()`` et al.
      must go through ``kaminpar_tpu.utils.platform`` (the lazy,
      ``JAX_PLATFORMS``-respecting gate), and must never run at import
      time;
  R3  32-bit accumulation (``dtype=...int32`` on cumsum/sum/segment_sum
      class reductions, int32 astype of reduction results) in ``ops/``,
      ``graphs/``, ``parallel/`` — the ``dtypes.py`` 64-bit policy owns
      accumulator widths;
  R4  retrace hygiene — jit wrappers constructed inside loops or around
      fresh lambdas retrace/recompile per evaluation;
  R6  eager device-memory/cost introspection must stay behind the gated
      perf helpers (``telemetry.perf`` / ``utils.heap_profiler``);
  R7  SPMD collective symmetry — rank-dependent control flow
      (``agreement.rank()``, ``jax.process_index()``, ``*RANK*`` env
      reads) must not guard a collective: ranks that skip a ``psum``
      deadlock the ranks that entered it;
  R8  exception hygiene — broad ``except Exception`` around the
      degradation/fault surface must route through
      ``policy.with_fallback``/``classify`` or re-raise, never swallow;
  R9  schema-pin consistency (cross-file) — the run-report
      ``SCHEMA_VERSION``, the schema enum, the checker conditional and
      the highest transition fixture must agree.

Since PR 17 the engine carries an intra-package call graph: span-scope
and rank-guard analysis follows factored helpers ONE call deep, so a
host pull hidden inside a small helper invoked under ``Timer.scope``
still fires (docs/static_analysis.md#call-graph has the semantics and
the blind spots).

Usage:  ``python -m kaminpar_tpu.lint [paths...]`` — see ``--help`` and
docs/static_analysis.md.  Findings are suppressible per line with
``# tpulint: disable=R1[,R2...]`` (or per file with ``disable-file=``)
and ratcheted via the checked-in baseline
``scripts/tpulint_baseline.json`` (empty since PR 17; the CLI refuses
``--write-baseline`` runs that would grow it).
"""

from __future__ import annotations

from .engine import (  # noqa: F401
    Finding,
    LintConfig,
    RULES,
    lint_file,
    lint_paths,
    lint_source,
)
from .baseline import (  # noqa: F401
    diff_against_baseline,
    load_baseline,
    write_baseline,
)
