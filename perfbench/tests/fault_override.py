"""Test-only: run ``perfbench/run.py`` of a checkout with the timed path
broken underneath, to see ``correct`` come out false.

    python fault_override.py <fault> [--cpu] <checkout> --workload ... --seed ... --seconds ... --trace ...

The fault is planted where the answer is produced, in the program's
``KaMinPar.compute_partition``, from the process's second request on (a
warm-up that fails is a non-zero exit, not a verdict):

  label       one label of every second answer is another block's: the
              replay guarantee ``bitwise`` breaks, ``feasible`` does not
  infeasible  a twentieth of the nodes are moved into block 0: the
              balance guarantee breaks, under either replay value

On the chip it is run as it stands (the control at a cell's own size);
``--cpu`` goes through ``cpu_override.py`` for the rehearsal."""

import os
import runpy
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def plant(fault: str) -> None:
    import numpy as np

    from kaminpar_tpu import KaMinPar

    sound = KaMinPar.compute_partition
    calls = {"n": 0}

    def broken(self, *args, **kwargs):
        part = np.array(sound(self, *args, **kwargs))
        calls["n"] += 1
        if calls["n"] == 1:
            return part
        k = int(part.max()) + 1
        if fault == "label" and calls["n"] % 2 == 0:
            part[0] = (part[0] + 1) % k
        elif fault == "infeasible":
            part[::20] = 0
        return part

    KaMinPar.compute_partition = broken


def main() -> None:
    fault, rest = sys.argv[1], sys.argv[2:]
    if fault not in ("label", "infeasible"):
        sys.exit(f"fault_override: unknown fault {fault!r}")
    cpu = rest[0] == "--cpu"
    root = os.path.abspath(rest[1] if cpu else rest[0])
    sys.path.insert(0, root)
    plant(fault)
    if cpu:
        sys.argv = [os.path.join(HERE, "cpu_override.py")] + rest[1:]
    else:
        sys.argv = [os.path.join(root, "perfbench", "run.py")] + rest[1:]
    runpy.run_path(sys.argv[0], run_name="__main__")


if __name__ == "__main__":
    main()
