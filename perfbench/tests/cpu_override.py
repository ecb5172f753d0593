"""Test-only: run ``perfbench/run.py`` of a checkout on the CPU.

    python cpu_override.py <checkout> --workload ... --seed ... --seconds ... --trace ...

The benchmark itself has no CPU mode and gets none here: this file makes
the harness of ``<checkout>`` accept the CPU by patching its device
module in this process, then runs ``run.py`` as ``__main__``.  What such
a run prints says whether the control flow and the counts are right; it
is never a device number."""

import os
import runpy
import sys


def main() -> None:
    root = os.path.abspath(sys.argv[1])
    sys.path.insert(0, root)
    from perfbench.harness import device

    device.REQUIRED_PLATFORM = "cpu"
    device.PEAKS["cpu"] = {"source": "test-only row, not a peak"}
    device.memory_peak_bytes = lambda chips: 1  # the CPU reports none
    sys.argv = [os.path.join(root, "perfbench", "run.py")] + sys.argv[2:]
    runpy.run_path(sys.argv[0], run_name="__main__")


if __name__ == "__main__":
    main()
