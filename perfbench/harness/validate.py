"""The comparison that decides ``correct``, in plain numpy on the CSR
arrays the benchmark generated; independent of ``graphs/host.py``.

Two parts: ``check_partition`` holds one returned partition to the
configuration's guarantees on labels and balance and recounts its cut;
``verdict`` holds the warm-up's and the window's partitions together to
the configuration's ``guarantees.replay`` (``replay_guarantee``,
``replay_reasons``) and to what every cell promises: nothing compiled
inside the window, nothing raised, something ended."""

from __future__ import annotations

import hashlib
from statistics import median

import numpy as np

#: ``guarantees.replay`` of a configuration file: how one replay of the
#: request relates to another.  An absent field is ``bitwise``.
REPLAY_VALUES = ("bitwise", "feasible")


def edge_cut(csr: dict, part: np.ndarray) -> int:
    """Weight of the edges whose ends lie in different blocks.  The CSR
    holds every undirected edge twice, hence the halving."""
    xadj, adjncy = csr["xadj"], csr["adjncy"]
    src = np.repeat(np.arange(len(xadj) - 1), np.diff(xadj))
    crossing = part[src] != part[adjncy]
    weights = csr.get("edge_weights")
    if weights is None:
        return int(crossing.sum()) // 2
    return int(weights[crossing].sum()) // 2


def max_block_weight(total_weight: int, k: int, epsilon: float) -> float:
    """The reference's strict balance bound: (1 + eps) * ceil(W / k)."""
    return (1 + epsilon) * -(-int(total_weight) // int(k))


def check_partition(csr: dict, part, k: int, epsilon: float) -> dict:
    """``{"cut", "max_block_weight", "bound", "errors"}``; ``errors`` is
    empty for a valid, feasible partition.  Node weights are unit unless
    the CSR carries ``node_weights``."""
    def invalid(why: str) -> dict:
        return {"cut": None, "max_block_weight": None, "bound": None,
                "errors": [why]}

    n = len(csr["xadj"]) - 1
    errors = []
    part = np.asarray(part)
    if part.shape != (n,):
        return invalid(f"shape {part.shape} != ({n},)")
    if not np.issubdtype(part.dtype, np.integer):
        return invalid(f"dtype {part.dtype} is not an integer type")
    lo, hi = int(part.min()), int(part.max())
    if lo < 0 or hi >= k:
        return invalid(f"labels [{lo}, {hi}] outside [0, {k})")
    node_w = csr.get("node_weights")
    if node_w is None:
        block_w = np.bincount(part, minlength=k)
        total = n
    else:
        block_w = np.bincount(part, weights=node_w, minlength=k)
        total = int(np.sum(node_w))
    bound = max_block_weight(total, k, epsilon)
    heaviest = int(block_w.max())
    if heaviest > bound:
        errors.append(f"infeasible: block weight {heaviest} > "
                      f"(1 + {epsilon}) * ceil({total} / {k}) = {bound}")
    return {"cut": edge_cut(csr, part), "max_block_weight": heaviest,
            "bound": bound, "errors": errors}


def replay_guarantee(guarantees, cut_bound) -> dict:
    """A configuration's ``guarantees`` (or None) as the replay rule
    ``verdict`` holds it to: ``{"replay": "bitwise"}`` or ``{"replay":
    "feasible", "cut_within": fraction}``.  ``cut_bound`` is the
    manifest's bound on ``cut``: a configuration may let its replays'
    cuts lie apart by half of it at the most, so that the band can never
    hide a loss the bound would catch.  Raises ValueError for anything
    else, so that a typing error can never loosen a cell."""
    guarantees = guarantees or {}
    replay = guarantees.get("replay", "bitwise")
    if replay not in REPLAY_VALUES:
        raise ValueError(f"guarantees.replay is {replay!r}, not one of "
                         f"{', '.join(REPLAY_VALUES)}")
    if replay == "bitwise":
        if "replay_cut_within" in guarantees:
            raise ValueError("guarantees.replay_cut_within belongs to "
                             "replay 'feasible', not to 'bitwise'")
        return {"replay": replay}
    within = guarantees.get("replay_cut_within")
    if isinstance(within, bool) or not isinstance(within, (int, float)):
        raise ValueError("replay 'feasible' needs a number "
                         f"guarantees.replay_cut_within, not {within!r}")
    if cut_bound is None:
        raise ValueError("replay 'feasible' needs an end-to-end metric "
                         "'cut' with a bound in BENCHMARK.json")
    if not 0 < within <= cut_bound / 2:
        raise ValueError(
            f"guarantees.replay_cut_within is {within!r}: it has to be "
            f"positive and at most half the bound on cut ({cut_bound / 2:g})")
    return {"replay": replay, "cut_within": float(within)}


def median_cut(samples: list):
    """Median cut of the samples that have one; an int where it is one
    (always, where the replays are bitwise equal).  None without any."""
    cuts = [s["cut"] for s in samples if s.get("cut") is not None]
    if not cuts:
        return None
    mid = median(cuts)
    return int(mid) if mid == int(mid) else mid


def distinct_partitions(samples: list) -> int:
    """How many different partitions the samples hold (by digest)."""
    return len({hashlib.sha1(np.asarray(s["partition"]).astype(
        np.int64).tobytes()).hexdigest() for s in samples})


def _labels_differing(part, first) -> int:
    if part.shape != first.shape:
        return max(part.size, first.size)
    return int((part != first).sum())


def replay_readings(samples: list, guarantee: dict) -> list:
    """Per sample (the warm-up first, then the window's) the number the
    replay guarantee limits, None where the sample has none.

    ``bitwise``: the labels that differ from the first sample's (limit 0).
    ``feasible``: labels are not compared; how far the cut lies off the
    median cut of them all, as a share of it (limit ``cut_within``); an
    invalid partition has no cut and answers for itself."""
    if guarantee["replay"] == "bitwise":
        return [None] + [
            _labels_differing(s["partition"], samples[0]["partition"])
            for s in samples[1:]]
    mid = median_cut(samples)
    return [abs(s["cut"] - mid) / mid if mid and s.get("cut") is not None
            else None for s in samples]


def replay_reasons(samples: list, guarantee: dict) -> list:
    """What makes the samples break the configuration's replay
    guarantee.  (That each partition is valid and feasible on its own is
    ``check_partition``'s, under either value.)"""
    readings = replay_readings(samples, guarantee)
    if guarantee["replay"] == "bitwise":
        return [f"partition {i} differs from partition 0 in {differ} labels"
                for i, differ in enumerate(readings) if differ]
    mid, within = median_cut(samples), guarantee["cut_within"]
    return [f"partition {i}: cut {samples[i]['cut']} is {100 * off:.2f} % "
            f"off the median {mid}, the configuration allows "
            f"{100 * within:g} %"
            for i, off in enumerate(readings)
            if off is not None and off > within]


def verdict(samples: list, raised, window_compile: dict,
            guarantee: dict) -> tuple:
    """``(failed, reasons)``: partitions that failed, and everything
    that makes the run incorrect.  ``samples`` are the warm-up's and the
    window's, in that order; ``guarantee`` as ``replay_guarantee`` gives."""
    reasons, failed = [], 0
    for i, sample in enumerate(samples):
        if sample["errors"]:
            failed += 1
            reasons.extend(f"partition {i}: {e}" for e in sample["errors"])
    if raised is not None:
        failed += 1
        reasons.append(f"a partition raised {raised}")
    reasons.extend(replay_reasons(samples, guarantee))
    if window_compile["executables"]:
        reasons.append(
            f"{window_compile['executables']} executables were compiled or "
            "loaded inside the window")
    if not samples:
        reasons.append("no partition ended")
    return failed, reasons


def compared(samples: list, failed: int, window_compile: dict,
             guarantee: dict) -> dict:
    """Every number ``correct`` compared, beside its limit:
    ``{name: [number, limit]}``, each the worst over the samples; None
    where no sample has the number (an invalid partition has no cut)."""
    def worst(values):
        values = [v for v in values if v is not None]
        return max(values) if values else None

    out = {
        "partitions_failed": [failed, 0],
        "max_block_weight": [worst(s["max_block_weight"] for s in samples),
                             worst(s.get("bound") for s in samples)],
        "cut_recount_gap": [worst(
            abs(s["reported_cut"] - s["cut"]) for s in samples
            if s.get("reported_cut") is not None), 0],
        "window_executables": [window_compile["executables"], 0],
    }
    name, limit = (("labels_differing", 0)
                   if guarantee["replay"] == "bitwise"
                   else ("cut_off_median", guarantee["cut_within"]))
    out[name] = [worst(replay_readings(samples, guarantee)), limit]
    return out
