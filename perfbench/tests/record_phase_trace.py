#!/usr/bin/env python3
"""Record the small annotated trace that ``test_phase_reduce.py`` checks
the phase reduction against (run once on the chip, by hand):

    python3 perfbench/tests/record_phase_trace.py [<out dir>]

One request drawn with the program's own producer (``utils/timer.py``:
``request_span`` and ``GLOBAL_TIMER`` scopes under the names the roll-up
knows) around launches of one jitted step: one under ``coarsening`` with
a host sleep after it; one enqueued under ``lp-refinement`` and not
waited for, so that it runs while ``jet`` is open; one under ``jet``; a
slice and its readback under ``extend-pull``; a host sleep and a ``jet``
under ``extend-partition``; the last readback under
``partition-download``.  Writes ``phase.xplane.pb`` and
``phase.expected.json``: what the script knows by construction
(``recorded``) and the reduction as ``harness/phase_reduce.py`` reads it,
to be checked by hand against ``tools/dump_xplane.py``'s view before it
is committed under ``tests/data``."""

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

COARSENING_SLEEP_S = 0.02
EXTEND_SLEEP_S = 0.03


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from kaminpar_tpu.utils import timer
    from perfbench.harness import phase_reduce
    from perfbench.harness.trace_reduce import newest_xplane

    if not hasattr(timer, "request_span"):
        sys.exit("record_phase_trace: this checkout's timer emits no "
                 "profiler spans")
    out = (sys.argv[1] if len(sys.argv) > 1 else
           os.path.join(ROOT, "chiprun_out", "perfbench", "phase_trace"))
    os.makedirs(out, exist_ok=True)

    @jax.jit
    def phase_step(x):
        def body(_, v):
            picked = v[jnp.argsort(v)[::-1]]
            return jnp.zeros_like(v).at[picked % 1024].add(v) + picked

        return jax.lax.fori_loop(0, 4, body, x)

    def request(x):
        scope = timer.GLOBAL_TIMER.scope
        timer.GLOBAL_TIMER.reset()
        with timer.request_span(k=2, n=int(x.shape[0]), m=0):
            with scope("partitioning"):
                with scope("coarsening"):
                    y = phase_step(x).block_until_ready()
                    time.sleep(COARSENING_SLEEP_S)
                with scope("uncoarsening"):
                    with scope("lp-refinement"):
                        y = phase_step(y)  # not waited for here
                    with scope("jet"):
                        y = phase_step(y).block_until_ready()
                    with scope("extend-pull", sync=True):
                        np.asarray(y[:8])
                    with scope("extend-partition"):
                        time.sleep(EXTEND_SLEEP_S)
                        with scope("jet"):
                            y = phase_step(y).block_until_ready()
                with scope("partition-download", sync=True):
                    return np.asarray(y)

    x = jax.device_put(jnp.arange(1 << 18, dtype=jnp.int32)[::-1] * 7919)
    request(x)  # every shape compiled before the trace

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    trace_dir = os.path.join(out, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    t0 = time.perf_counter()
    request(x)
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()

    target = os.path.join(out, "phase.xplane.pb")
    shutil.copy(newest_xplane(trace_dir), target)
    phases = phase_reduce.reduce_file(target)
    if phases is None:
        sys.exit(f"record_phase_trace: {target} reduces to nothing")
    print(phase_reduce.render(phases))
    expected = {
        "recorded": {"device": str(jax.devices()[0].device_kind),
                     "jax": jax.__version__, "wall_s": wall,
                     "coarsening_sleep_s": COARSENING_SLEEP_S,
                     "extend_sleep_s": EXTEND_SLEEP_S,
                     "timer_tree": timer.GLOBAL_TIMER.render_machine()},
        "joined": phases["joined"],
        "launches": {path: row["launches"]
                     for path, row in phases["spans"].items()},
        "layers": phases["layers"],
        "jet": phases["jet"],
        "attributed_share": phases["attributed_share"],
        "request_s": phases["request"]["seconds"],
        "gap_spans": [gap[2] for gap in phases["gaps"][:3]],
    }
    with open(os.path.join(out, "phase.expected.json"), "w") as f:
        json.dump(expected, f, indent=1)
    print(f"record_phase_trace: {target} "
          f"({os.path.getsize(target)} bytes), wall {wall:.4f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
