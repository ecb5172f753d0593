"""The measured window: one caller, closed loop, the same request
replayed back to back.

A request is one call of the public facade, host graph in, host
partition out: ``KaMinPar(preset).set_graph(from_csr(arrays))
.compute_partition(k=, epsilon=, seed=)``, upload and output gate
included, program telemetry off.  The result is a numpy array, so the
device has finished when the clock stops."""

from __future__ import annotations

import os
import shutil
import time
from statistics import median

import numpy as np

from . import timer_tree
from .trace_reduce import newest_xplane
from .validate import check_partition


class Request:
    """The cell's one request, and the call that serves it."""

    def __init__(self, csr: dict, preset: str, k: int, epsilon: float,
                 seed: int) -> None:
        self.csr, self.preset = csr, preset
        self.k, self.epsilon, self.seed = int(k), float(epsilon), int(seed)

    def serve(self) -> dict:
        """One partition.  Everything after ``wall_s`` is taken is the
        benchmark's own checking and is not timed."""
        from kaminpar_tpu import KaMinPar, from_csr, telemetry
        from kaminpar_tpu.utils import timer
        from kaminpar_tpu.utils.logger import OutputLevel

        csr = self.csr
        t0 = time.perf_counter()
        solver = KaMinPar(self.preset)
        solver.set_output_level(OutputLevel.QUIET)
        graph = from_csr(csr["xadj"], csr["adjncy"], csr.get("node_weights"),
                         csr.get("edge_weights"))
        part = solver.set_graph(graph).compute_partition(
            k=self.k, epsilon=self.epsilon, seed=self.seed)
        wall = time.perf_counter() - t0

        sample = check_sample(solver, graph, csr, part, self.k, self.epsilon)
        if telemetry.enabled():
            sample["errors"].append("program telemetry is on")
        sample["wall_s"] = wall
        sample["tree"] = timer_tree.snapshot(timer.GLOBAL_TIMER.root)
        return sample


def check_sample(solver, graph, csr: dict, part, k: int,
                 epsilon: float) -> dict:
    """One returned partition against the guarantees the configuration
    states: ``{"partition", "cut", "reported_cut", "max_block_weight",
    "bound", "errors"}``."""
    errors, reported = [], None
    if solver.last_anytime is not None:
        errors.append(f"wound down early: {solver.last_anytime}")
    part = np.asarray(part)
    checked = check_partition(csr, part, k, epsilon)
    errors.extend(checked["errors"])
    if checked["cut"] is not None:
        reported = int(solver.result_metrics(graph, part)["cut"])
        if reported != checked["cut"]:
            errors.append(f"the program reports cut {reported}, the "
                          f"benchmark counts {checked['cut']}")
    return {"partition": part, "cut": checked["cut"], "reported_cut": reported,
            "max_block_weight": checked["max_block_weight"],
            "bound": checked["bound"], "errors": errors}


def serve_traced(request: Request, trace_dir: str) -> dict:
    """One partition inside ``jax.profiler.trace``.  The Python tracer
    stays off (it slows the host several times over) and so does the
    HLO dump; ``xplane`` is the file written, ``stop_s`` what writing it
    took.  ``trace_dir`` is the benchmark's own and is emptied first."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    shutil.rmtree(trace_dir, ignore_errors=True)  # keep the newest only
    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        sample = request.serve()
    finally:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        stop_s = time.perf_counter() - t0
    sample["traced"] = True
    sample["xplane"] = newest_xplane(trace_dir)
    sample["stop_s"] = stop_s
    return sample


def run_window(request: Request, seconds: float, trace_dir=None) -> dict:
    """Replay the request until the next partition is not expected to
    end inside ``seconds`` (elapsed + median so far); the first two
    always run.  With ``trace_dir`` the first partition is traced and
    the rest are not.  A partition that raises counts as failed and ends
    the window."""
    samples, raised = [], None
    start = time.perf_counter()
    while True:
        walls = [s["wall_s"] for s in samples]
        elapsed = time.perf_counter() - start
        if len(samples) >= 2 and elapsed + median(walls) > seconds:
            break
        try:
            if trace_dir is not None and not samples:
                samples.append(serve_traced(request, trace_dir))
            else:
                samples.append(request.serve())
        except Exception as exc:  # the boundary: report it, do not hide it
            import traceback

            traceback.print_exc()
            raised = f"{type(exc).__name__}: {exc}"
            break
    return {"samples": samples, "raised": raised,
            "elapsed_s": time.perf_counter() - start}
