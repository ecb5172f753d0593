#!/usr/bin/env python3
"""Record the small trace that ``test_trace_reduce.py`` checks the
reduction against (run once on the chip, by hand):

    python3 perfbench/tests/record_small_trace.py [<out dir>]

Three launches of one jitted step whose ``while`` body holds a sort, a
gather and a scatter-add, with a host sleep between the launches so that
the trace has idle gaps of a known size.  Writes ``small.xplane.pb`` and
``small.dump.json`` (``tools/dump_xplane.py``'s view of it)."""

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

LAUNCHES = 3
SLEEP_S = 0.05


def main() -> int:
    import jax
    import jax.numpy as jnp

    from perfbench.harness.trace_reduce import newest_xplane
    from perfbench.tools.dump_xplane import dump

    out = (sys.argv[1] if len(sys.argv) > 1 else
           os.path.join(ROOT, "chiprun_out", "perfbench", "small_trace"))
    os.makedirs(out, exist_ok=True)

    @jax.jit
    def small_step(x):
        def body(_, v):
            order = jnp.argsort(v)                       # sort
            picked = v[order[::-1]]                      # gather
            return jnp.zeros_like(v).at[picked % 1024].add(v) + picked

        return jax.lax.fori_loop(0, 4, body, x)

    x = jax.device_put(jnp.arange(1 << 18, dtype=jnp.int32)[::-1] * 7919)
    small_step(x).block_until_ready()

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    trace_dir = os.path.join(out, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    t0 = time.perf_counter()
    for _ in range(LAUNCHES):
        small_step(x).block_until_ready()
        time.sleep(SLEEP_S)
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()

    target = os.path.join(out, "small.xplane.pb")
    shutil.copy(newest_xplane(trace_dir), target)
    view = dump(target)
    view["recorded"] = {"device": str(jax.devices()[0].device_kind),
                        "launches": LAUNCHES, "sleep_s": SLEEP_S,
                        "wall_s": wall, "jax": jax.__version__}
    with open(os.path.join(out, "small.dump.json"), "w") as f:
        json.dump(view, f, indent=1)
    print(f"record_small_trace: {target} "
          f"({os.path.getsize(target)} bytes), wall {wall:.4f} s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
