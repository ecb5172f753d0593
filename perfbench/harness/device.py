"""The device the benchmark accepts, and the table of published peaks.

A device that is not in the table is an error, not a default, and so is
any platform but the required one: no path of the benchmark reports a
number taken on a CPU under a metric's name."""

from __future__ import annotations

import sys

#: keyed by ``device_kind`` as JAX reports it.  Copied from
#: ``kaminpar_tpu/telemetry/perf.DEVICE_PEAKS`` (PR 22), with the memory.
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes": 16_000_000_000,
        "hbm_gb_per_s": 819.0,
        "bf16_tflop_per_s": 197.0,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}

REQUIRED_PLATFORM = "tpu"


def require_device(chips: int) -> dict:
    """What JAX sees, as the result line's ``device``; exits non-zero
    unless it is ``chips`` chips of a kind the peaks table knows."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"perfbench: jax {jax.__version__} platform={dev.platform} "
          f"device_kind={dev.device_kind!r} count={len(devices)}", flush=True)
    if dev.platform != REQUIRED_PLATFORM:
        sys.exit(f"perfbench: FAIL: jax.devices()[0] is {dev.platform}:"
                 f"{dev.device_kind}, not a {REQUIRED_PLATFORM}; the "
                 "benchmark has no CPU mode")
    if dev.device_kind not in PEAKS:
        sys.exit(f"perfbench: FAIL: device_kind {dev.device_kind!r} has no "
                 "row in perfbench/harness/device.PEAKS; add one with its "
                 "source")
    if len(devices) < chips:
        sys.exit(f"perfbench: FAIL: the cell asks for {chips} chips and JAX "
                 f"sees {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the first ``chips`` devices."""
    import jax

    return max(int(d.memory_stats()["peak_bytes_in_use"])
               for d in jax.devices()[:chips])
