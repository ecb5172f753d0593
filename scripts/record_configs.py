#!/usr/bin/env python
"""Record the BASELINE.json headline configs that previous rounds never
exercised, on real hardware:

  * configs[3] analog — k=32 with FM refinement enabled (strong preset).
    The Walshaw fe_ocean graph itself is unreachable offline (zero
    egress); the bench RMAT at the same scale class substitutes, and the
    substitution is recorded in the output.
  * configs[4] — compressed-graph mode, k=128, deep multilevel on the
    10M-edge graph (TeraPart v2 codec), with the compression ratio.
  * large-k — k=4096 on the 10M-edge graph (largek preset, no dense
    (n, k) structures), with wall time and peak device memory.

Each run appends one JSON line to docs/recorded_configs.jsonl.
Usage: python scripts/record_configs.py [fe_ocean|compressed128|largek4096]
"""

from __future__ import annotations

import json
import os
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT = os.path.join(os.path.dirname(__file__), "..", "docs",
                   "recorded_configs.jsonl")


def record(entry: dict) -> None:
    entry["recorded_at"] = time.strftime("%Y-%m-%d %H:%M:%S")
    with open(OUT, "a") as f:
        f.write(json.dumps(entry) + "\n")
    print(json.dumps(entry), flush=True)


def run(name: str, preset: str, n: int, m: int, gen_seed: int, k: int,
        compressed: bool = False, seed: int = 1) -> None:
    import numpy as np

    from kaminpar_tpu.graphs.factories import make_rmat
    from kaminpar_tpu.graphs.host import host_partition_metrics
    from kaminpar_tpu.kaminpar import KaMinPar
    from kaminpar_tpu.utils.logger import OutputLevel

    entry = {
        "config": name,
        "graph": f"rmat n={n} m={m} seed={gen_seed}",
        "preset": preset,
        "k": k,
        "eps": 0.03,
        "seed": seed,
    }
    if compressed:
        # TeraPart compute parity: generation + compression run in a
        # SUBPROCESS that writes only the compressed file, so THIS
        # process (whose ru_maxrss is recorded) never holds the flat
        # CSR — it loads compressed, partitions through the chunked
        # device upload, and measures the cut with chunked decodes.
        import subprocess
        import tempfile

        from kaminpar_tpu.graphs.compressed import (
            compressed_partition_metrics,
        )
        from kaminpar_tpu.io import load_compressed

        # np.savez appends .npz to extensionless-or-foreign suffixes
        path = os.path.join(tempfile.gettempdir(),
                            f"rmat_{n}_{m}_{gen_seed}.kcg.npz")
        if not os.path.exists(path):
            code = (
                "import sys; sys.path.insert(0, %r)\n"
                "from kaminpar_tpu.graphs.factories import make_rmat\n"
                "from kaminpar_tpu.graphs.compressed import compress_host_graph\n"
                "from kaminpar_tpu.io import write_compressed\n"
                "write_compressed(%r, compress_host_graph("
                "make_rmat(%d, %d, seed=%d)))\n"
            ) % (os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 path[: -len(".npz")], n, m, gen_seed)
            subprocess.run([sys.executable, "-c", code], check=True)
        cg = load_compressed(path)
        entry["codec"] = cg.codec
        entry["compression_ratio"] = round(cg.compression_ratio(), 2)
        entry["compressed_mb"] = cg.memory_bytes() // (1 << 20)
        p = KaMinPar(preset)
        p.set_output_level(OutputLevel.QUIET)
        t0 = time.perf_counter()
        part = p.set_graph(cg).compute_partition(k=k, epsilon=0.03,
                                                 seed=seed)
        entry["wall_s"] = round(time.perf_counter() - t0, 1)
        entry["decoded_on_host"] = getattr(p, "_decoded", None) is not None
        res = compressed_partition_metrics(cg, part, k)
        nw = cg.node_weight_array()
    else:
        host = make_rmat(n, m, seed=gen_seed)
        p = KaMinPar(preset)
        p.set_output_level(OutputLevel.QUIET)
        t0 = time.perf_counter()
        part = p.set_graph(host).compute_partition(k=k, epsilon=0.03,
                                                   seed=seed)
        entry["wall_s"] = round(time.perf_counter() - t0, 1)
        res = host_partition_metrics(host, part, k)
        nw = host.node_weight_array()
    cap = (1 + 0.03) * np.ceil(nw.sum() / k)
    entry["cut"] = int(res["cut"])
    entry["imbalance"] = round(float(res["imbalance"]), 5)
    entry["feasible"] = bool(res["block_weights"].max() <= cap)
    entry["peak_host_rss_mb"] = (
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024
    )
    record(entry)


def main() -> None:
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("fe_ocean", "all"):
        # configs[3] analog: FM-enabled k=32.  fe_ocean (Walshaw archive)
        # is not fetchable offline; the medium bench RMAT is the same
        # size class (fe_ocean: n=143k m=410k)
        run("configs[3]-analog fe_ocean-substitute k=32 FM (strong)",
            "strong", 1 << 17, 420_000, 77, 32)
    if which in ("compressed128", "all"):
        run("configs[4] compressed-mode k=128 deep", "terapart",
            1 << 20, 10_000_000, 7, 128, compressed=True)
    if which in ("largek4096", "all"):
        run("large-k k=4096 (largek preset)", "largek",
            1 << 20, 10_000_000, 7, 4096)


if __name__ == "__main__":
    main()
