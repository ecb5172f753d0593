"""Native host-runtime components (C++, loaded via ctypes).

The reference's host runtime — graph compression codecs and parsers — is
C++ (kaminpar-common/graph_compression/, kaminpar-io/).  This package
builds the framework's native equivalents from codec.cpp on first use with
the system toolchain and exposes them via ctypes; every entry point has a
pure-numpy fallback, so the framework works (slower) without a compiler.

Build artifacts are cached next to the source keyed by a source hash.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import tempfile
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRCS = [
    os.path.join(_DIR, "codec.cpp"),
    os.path.join(_DIR, "codec2.cpp"),
    os.path.join(_DIR, "ip.cpp"),
    os.path.join(_DIR, "fm.cpp"),
]

_lib: Optional[ctypes.CDLL] = None
_tried = False

# Build-cache directory override (tests poison a tmp cache dir to
# exercise the corrupted-cache clean-rebuild path without touching the
# package's real artifacts) and the compile timeout.
CACHE_DIR_ENV = "KAMINPAR_TPU_NATIVE_CACHE_DIR"
BUILD_TIMEOUT_ENV = "KAMINPAR_TPU_NATIVE_BUILD_TIMEOUT"
DEFAULT_BUILD_TIMEOUT_S = 300.0


def cache_dir() -> str:
    """Where built artifacts are cached (package dir unless overridden)."""
    return os.environ.get(CACHE_DIR_ENV, "") or _DIR


def build_timeout() -> float:
    """Native compile timeout in seconds (KAMINPAR_TPU_NATIVE_BUILD_TIMEOUT;
    a hung compiler must degrade to ctypes-free mode, not hang the run)."""
    raw = os.environ.get(BUILD_TIMEOUT_ENV, "")
    try:
        return float(raw) if raw else DEFAULT_BUILD_TIMEOUT_S
    except ValueError:
        return DEFAULT_BUILD_TIMEOUT_S


def sanitize_flags() -> list:
    """Extra compile flags from KMP_SANITIZE (e.g. 'address,undefined').

    The sanitizer build mode for the native layer: frame pointers and
    debug info stay in, optimization drops to -O1 so reports map to
    source lines.  scripts/run_native_sanitized.sh drives a full
    rebuild + test run under it (LD_PRELOAD of libasan included)."""
    san = os.environ.get("KMP_SANITIZE", "").strip()
    if not san:
        return []
    return [f"-fsanitize={san}", "-fno-omit-frame-pointer", "-g", "-O1"]


def _build() -> str:
    """Compile (or reuse) the cached native library; returns its path.

    Raises resilience.NativeUnavailable on a missing toolchain, a failed
    compile, or a compile exceeding build_timeout() — the structured
    error the `native-build` degradation site routes to ctypes-free
    mode."""
    from ..resilience import NativeUnavailable

    h = hashlib.sha256()
    for src in _SRCS:
        with open(src, "rb") as f:
            h.update(f.read())
    # sanitized and plain builds must not share a cache slot
    h.update(",".join(sanitize_flags()).encode())
    tag = h.hexdigest()[:16]
    cdir = cache_dir()
    out = os.path.join(cdir, f"libkmpnative-{tag}.so")
    if os.path.exists(out):
        return out
    try:
        os.makedirs(cdir, exist_ok=True)
        # stale builds from older source versions
        for name in os.listdir(cdir):
            if name.startswith("libkmpnative-") and name.endswith(".so"):
                try:
                    os.remove(os.path.join(cdir, name))
                except OSError:
                    pass
    except OSError as e:
        # an unusable cache dir (bad KAMINPAR_TPU_NATIVE_CACHE_DIR,
        # permissions) must degrade to ctypes-free mode, not crash
        raise NativeUnavailable(f"build cache dir unusable: {e}") from e
    tmp_path = None
    try:
        with tempfile.NamedTemporaryFile(
            suffix=".so", dir=cdir, delete=False
        ) as tmp:
            tmp_path = tmp.name
        subprocess.run(
            # -mssse3 (x86 only): the StreamVByte-class SIMD residual
            # decode in codec2.cpp (guarded by __SSSE3__, scalar on
            # other architectures)
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++20", "-pthread",
             *(["-mssse3"] if platform.machine() in
               ("x86_64", "AMD64", "i686") else []),
             *sanitize_flags(),
             *_SRCS, "-o", tmp_path],
            check=True,
            capture_output=True,
            timeout=build_timeout(),
        )
        os.replace(tmp_path, out)
        return out
    except subprocess.TimeoutExpired as e:
        raise NativeUnavailable(
            f"native build timed out after {build_timeout():.0f}s "
            f"(raise {BUILD_TIMEOUT_ENV} if the toolchain is just slow)"
        ) from e
    except subprocess.CalledProcessError as e:
        stderr = (e.stderr or b"").decode("utf-8", "replace")[-400:]
        raise NativeUnavailable(f"g++ failed: {stderr}") from e
    except OSError as e:
        raise NativeUnavailable(f"toolchain unavailable: {e}") from e
    finally:
        if tmp_path is not None and os.path.exists(tmp_path):
            try:
                os.remove(tmp_path)
            except OSError:
                pass


def _load_native() -> ctypes.CDLL:
    """Build + dlopen + bind signatures, with ONE automatic clean-rebuild
    retry when the cached artifact is corrupted (truncated file, wrong
    architecture, poisoned cache dir: dlopen or symbol binding fails)."""
    from ..resilience import NativeUnavailable
    from ..utils.logger import log_warning

    path = _build()
    try:
        return _bind(ctypes.CDLL(path))
    except (OSError, AttributeError) as e:
        try:
            os.remove(path)
        except OSError:
            pass
        log_warning(
            f"native build cache corrupted ({type(e).__name__}: "
            f"{str(e)[:120]}); clean rebuild"
        )
        path = _build()  # artifact removed -> full recompile
        try:
            return _bind(ctypes.CDLL(path))
        except (OSError, AttributeError) as e2:
            raise NativeUnavailable(
                f"native library unusable after clean rebuild: {e2}"
            ) from e2


def get_lib() -> Optional[ctypes.CDLL]:
    """The native library, building it on first call; None if unavailable.

    Build/load failures degrade through the `native-build` site: a
    `degraded` telemetry event is emitted once and every native entry
    point falls back to its ctypes-free numpy twin for the rest of the
    process."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    from ..resilience import with_fallback

    _lib = with_fallback(_load_native, lambda exc: None, site="native-build")
    return _lib


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    """Declare every exported symbol's signature (raises AttributeError
    on a library that is loadable but not ours — a corrupted cache)."""
    i64 = ctypes.c_int64
    p_i64 = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    p_i32 = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    p_u8 = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")

    lib.kmp_encode_gaps_size.restype = i64
    lib.kmp_encode_gaps_size.argtypes = [i64, p_i64, p_i32, p_i64]
    lib.kmp_encode_gaps.restype = None
    lib.kmp_encode_gaps.argtypes = [i64, p_i64, p_i32, p_i64, p_u8]
    lib.kmp_decode_gaps.restype = None
    lib.kmp_decode_gaps.argtypes = [i64, p_i64, p_i64, p_u8, p_i32]
    lib.kmp_decode_node.restype = i64
    lib.kmp_decode_node.argtypes = [i64, p_i64, p_i64, p_u8, p_i32]
    lib.kmp_parse_metis_body.restype = i64
    lib.kmp_parse_metis_body.argtypes = [
        ctypes.c_char_p, i64, i64, ctypes.c_int, ctypes.c_int, i64,
        p_i64, p_i32, p_i64, p_i64,
    ]
    i32 = ctypes.c_int32
    f64 = ctypes.c_double
    p_i8 = np.ctypeslib.ndpointer(np.int8, flags="C_CONTIGUOUS")
    lib.kmp_ml_bipartition.restype = i64
    lib.kmp_ml_bipartition.argtypes = [
        i64, p_i64, p_i32, p_i64, p_i64, i64, i64,       # graph + caps
        i64, f64, i64,                                   # coarsening
        i64, i64, i64, f64, i32, i32, i32, i32,          # pool
        i32, i32, i64, f64, i64,                         # pool FM
        i32, i32, i64, f64, i64,                         # per-level FM
        ctypes.c_uint64, p_i8,
    ]
    lib.kmp_fm_refine.restype = i64
    lib.kmp_fm_refine.argtypes = [
        i64, p_i64, p_i32, p_i64, p_i64, i64, p_i64,
        np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS,WRITEABLE"),
        i64, i64, f64, i64, i32, ctypes.c_uint64, i64,
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS,WRITEABLE"),
    ]
    lib.kmp_fm_refine_sparse.restype = i64
    lib.kmp_fm_refine_sparse.argtypes = lib.kmp_fm_refine.argtypes
    # v2 codec (interval + streamvbyte-class residuals + varint weights)
    lib.kmp_encode_v2_size.restype = i64
    lib.kmp_encode_v2_size.argtypes = [i64, p_i64, p_i32, p_i64]
    lib.kmp_encode_v2.restype = None
    lib.kmp_encode_v2.argtypes = [i64, p_i64, p_i32, p_i64, p_u8]
    lib.kmp_decode_v2.restype = None
    lib.kmp_decode_v2.argtypes = [i64, p_i64, p_i64, p_u8, p_i32]
    lib.kmp_decode_v2_node.restype = i64
    lib.kmp_decode_v2_node.argtypes = [i64, p_i64, p_i64, p_u8, p_i32]
    lib.kmp_encode_v2_weights_size.restype = i64
    lib.kmp_encode_v2_weights_size.argtypes = [i64, p_i64, p_i32, p_i64, p_i64]
    lib.kmp_encode_v2_weights.restype = None
    lib.kmp_encode_v2_weights.argtypes = [i64, p_i64, p_i32, p_i64, p_i64, p_u8]
    lib.kmp_decode_v2_weights.restype = None
    lib.kmp_decode_v2_weights.argtypes = [i64, p_i64, p_i64, p_u8, p_i64]
    return lib


def available() -> bool:
    return get_lib() is not None


# ---------------------------------------------------------------------------
# Varint gap codec (native with numpy fallback)
# ---------------------------------------------------------------------------


def encode_gaps(xadj: np.ndarray, adjncy: np.ndarray):
    """Encode sorted CSR neighborhoods as varint gap streams.

    Returns (bytes u8[total], offsets i64[n+1])."""
    n = len(xadj) - 1
    xadj = np.ascontiguousarray(xadj, dtype=np.int64)
    adjncy = np.ascontiguousarray(adjncy, dtype=np.int32)
    lib = get_lib()
    offsets = np.zeros(n + 1, dtype=np.int64)
    if lib is not None:
        total = lib.kmp_encode_gaps_size(n, xadj, adjncy, offsets)
        out = np.empty(total, dtype=np.uint8)
        lib.kmp_encode_gaps(n, xadj, adjncy, offsets, out)
        return out, offsets
    return _encode_gaps_np(n, xadj, adjncy)


def decode_gaps(xadj: np.ndarray, offsets: np.ndarray, data: np.ndarray):
    """Inverse of encode_gaps; returns adjncy i32[m]."""
    n = len(xadj) - 1
    xadj = np.ascontiguousarray(xadj, dtype=np.int64)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    out = np.empty(int(xadj[-1]), dtype=np.int32)
    lib = get_lib()
    if lib is not None:
        lib.kmp_decode_gaps(n, xadj, offsets, data, out)
        return out
    return _decode_gaps_np(n, xadj, offsets, data, out)


def decode_node(u: int, xadj, offsets, data) -> np.ndarray:
    """Decode a single node's neighborhood."""
    deg = int(xadj[u + 1] - xadj[u])
    out = np.empty(deg, dtype=np.int32)
    lib = get_lib()
    if lib is not None and deg:
        lib.kmp_decode_node(
            int(u),
            np.ascontiguousarray(xadj, dtype=np.int64),
            np.ascontiguousarray(offsets, dtype=np.int64),
            np.ascontiguousarray(data, dtype=np.uint8),
            out,
        )
        return out
    if deg == 0:
        return out
    sub_x = np.array([0, deg], dtype=np.int64)
    sub_off = np.array([0, 0], dtype=np.int64)
    piece = np.asarray(data[int(offsets[u]) : int(offsets[u + 1])], np.uint8)
    return _decode_gaps_np(1, sub_x, sub_off, piece, out)


def _varint_sizes_np(vals: np.ndarray) -> np.ndarray:
    v = vals.astype(np.uint64)
    sizes = np.ones(len(vals), dtype=np.int64)
    for k in range(1, 5):
        sizes += (v >= (1 << (7 * k))).astype(np.int64)
    return sizes


def _encode_gaps_np(n, xadj, adjncy):
    m = int(xadj[-1])
    first_mask = np.zeros(m, dtype=bool)
    nonempty = xadj[1:] > xadj[:-1]
    first_mask[xadj[:-1][nonempty]] = True
    gaps = np.empty(m, dtype=np.uint32)
    if m:
        gaps[1:] = np.diff(adjncy.astype(np.int64)).astype(np.uint32)
        gaps[first_mask] = adjncy[first_mask].astype(np.uint32) + 1
    sizes = _varint_sizes_np(gaps) if m else np.zeros(0, dtype=np.int64)
    csum = np.concatenate([[0], np.cumsum(sizes)])
    offsets = csum[xadj]
    total = int(csum[-1])
    out = np.zeros(total, dtype=np.uint8)
    # byte-by-byte scatter, vectorized over the byte position
    pos = csum[:-1].copy() if m else csum[:0]
    rem = gaps.copy()
    active = np.ones(m, dtype=bool)
    while m and active.any():
        idx = np.nonzero(active)[0]
        b = (rem[idx] & 0x7F).astype(np.uint8)
        more = rem[idx] >= 0x80
        out[pos[idx]] = b | (more.astype(np.uint8) << 7)
        pos[idx] += 1
        rem[idx] >>= 7
        active[idx] = more
    return out, offsets


def _decode_gaps_np(n, xadj, offsets, data, out):
    # sequential fallback decode (native path is the fast one)
    for u in range(n):
        p = int(offsets[u])
        lo, hi = int(xadj[u]), int(xadj[u + 1])
        prev = -1
        for e in range(lo, hi):
            x = 0
            shift = 0
            while True:
                byte = int(data[p])
                p += 1
                x |= (byte & 0x7F) << shift
                if not byte & 0x80:
                    break
                shift += 7
            prev = x - 1 if e == lo else prev + x
            out[e] = prev
    return out


# ---------------------------------------------------------------------------
# Native sequential multilevel bipartitioner (ip.cpp)
# ---------------------------------------------------------------------------


def ml_bipartition(graph, max_block_weights, ip_ctx, seed: int):
    """Run the native multilevel 2-way bipartitioner on a HostGraph.

    Native counterpart of initial.InitialMultilevelBipartitioner (see
    ip.cpp header); returns an int8 partition, or None when the native
    library is unavailable (caller falls back to the numpy path).
    """
    attempts = ml_bipartition_attempts(
        graph, max_block_weights, ip_ctx, [seed]
    )
    return None if attempts is None else attempts[0][0]


def ml_bipartition_attempts(graph, max_block_weights, ip_ctx, seeds):
    """One independent native multilevel bipartition per seed, as
    ``[(int8 partition, cut), ...]`` in the order of ``seeds``, or None
    when the native library is unavailable.  More than one seed runs on
    a thread each: the call holds no state outside its arguments and
    ctypes releases the GIL for its duration, so the attempts cost the
    wall time of the slowest one where the host has the cores."""
    lib = get_lib()
    if lib is None or graph.n == 0:
        return None
    from ..context import FMStoppingRule

    xadj = np.ascontiguousarray(graph.xadj, dtype=np.int64)
    adjncy = np.ascontiguousarray(graph.adjncy, dtype=np.int32)
    node_w = np.ascontiguousarray(graph.node_weight_array(), dtype=np.int64)
    edge_w = np.ascontiguousarray(graph.edge_weight_array(), dtype=np.int64)
    max_bw = np.asarray(max_block_weights, dtype=np.int64)
    ic = ip_ctx.coarsening
    pool = ip_ctx.pool
    pfm = pool.refinement
    fm = ip_ctx.refinement
    max_cluster_weight = max(
        1, int(ic.cluster_weight_multiplier * int(max_bw.max()))
    )

    def attempt(seed):
        out = np.empty(graph.n, dtype=np.int8)
        cut = lib.kmp_ml_bipartition(
            graph.n, xadj, adjncy, node_w, edge_w,
            int(max_bw[0]), int(max_bw[1]),
            int(ic.contraction_limit), float(ic.convergence_threshold),
            max_cluster_weight,
            int(pool.min_num_repetitions),
            int(pool.min_num_non_adaptive_repetitions),
            int(pool.max_num_repetitions), float(pool.repetition_multiplier),
            int(bool(pool.use_adaptive_bipartitioner_selection)),
            int(bool(pool.enable_bfs_bipartitioner)),
            int(bool(pool.enable_ggg_bipartitioner)),
            int(bool(pool.enable_random_bipartitioner)),
            int(bool(pfm.disabled)),
            int(pfm.stopping_rule == FMStoppingRule.ADAPTIVE),
            int(pfm.num_fruitless_moves), float(pfm.alpha),
            int(pfm.num_iterations),
            int(bool(fm.disabled)),
            int(fm.stopping_rule == FMStoppingRule.ADAPTIVE),
            int(fm.num_fruitless_moves), float(fm.alpha),
            int(fm.num_iterations),
            int(seed) & 0xFFFFFFFFFFFFFFFF, out,
        )
        return out, int(cut)

    if len(seeds) == 1:
        return [attempt(seeds[0])]
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=len(seeds)) as threads:
        return list(threads.map(attempt, seeds))


# ---------------------------------------------------------------------------
# Native localized batch k-way FM (fm.cpp)
# ---------------------------------------------------------------------------


# fm_refine's refusal sentinel: native FM could not run at this (n, k).
# INT64_MIN, matching fm.cpp; every other return is the exact cut
# improvement, never negative.
FM_REFUSED = -(1 << 63)

#: fm.cpp's stats out-array (`FmStat`), slot by slot: worker threads,
#: passes, batches, moves committed and kept, commits the cap refused,
#: moves a commit undid, the batches' estimated gain, the exact gain
#: (the return value).
FM_STATS = (
    "threads", "passes", "batches", "committed", "cap_refusals",
    "undone_moves", "estimated_gain", "exact_gain",
)


def fm_refine(graph, partition, k, max_block_weights, fm_ctx, seed: int,
              threads: int = 1, force_sparse: bool = False,
              stats: Optional[dict] = None):
    """Run the native localized batch FM on a HostGraph partition.

    Native counterpart of the reference's parallel localized FM scheme
    (see fm.cpp header); refines `partition` IN PLACE and returns the
    total cut improvement, or None when the native library is
    unavailable.  `threads` > 1 grows each round's regions on a worker
    pool and commits them in batch order with exact gains: the labels
    depend on the seed, not on the thread count (>= 2) or the timing.

    Above the dense-table size limit the native side automatically
    switches to the sparse compact-hashing gain cache
    (compact_hashing_gain_cache.h:34 analog, O(m) memory), so FM stays
    active at large k.  `force_sparse` exercises that path at any k
    (tests).

    Returns FM_REFUSED (INT64_MIN) when the native side REFUSED to run —
    k above the sparse engine's 16-bit packed-tag limit (0xFFFF) with the
    dense (n, k) table also unaffordable — so the caller can tell "FM
    did not run" from "FM found no improvement"; the refusal is also
    recorded as an `fm-refused` telemetry event for the run report.

    `stats`, a dict, receives the call's counters under `FM_STATS`'
    names (all 0 where the engine did not run)."""
    lib = get_lib()
    if lib is None or graph.n == 0 or k <= 1:
        return None
    xadj = np.ascontiguousarray(graph.xadj, dtype=np.int64)
    adjncy = np.ascontiguousarray(graph.adjncy, dtype=np.int32)
    node_w = np.ascontiguousarray(graph.node_weight_array(), dtype=np.int64)
    edge_w = np.ascontiguousarray(graph.edge_weight_array(), dtype=np.int64)
    max_bw = np.ascontiguousarray(max_block_weights, dtype=np.int64)
    assert partition.dtype == np.int32 and partition.flags.c_contiguous
    fn = lib.kmp_fm_refine_sparse if force_sparse else lib.kmp_fm_refine
    counters = np.zeros(len(FM_STATS), dtype=np.int64)
    ret = int(
        fn(
            graph.n, xadj, adjncy, node_w, edge_w, int(k), max_bw,
            partition,
            int(fm_ctx.num_iterations), int(fm_ctx.num_seed_nodes),
            float(fm_ctx.alpha), int(fm_ctx.num_fruitless_moves),
            1,  # adaptive stopping (the reference's default for FM)
            int(seed) & 0xFFFFFFFFFFFFFFFF,
            max(1, int(threads)),
            counters,
        )
    )
    if stats is not None:
        stats.update(zip(FM_STATS, (int(v) for v in counters)))
    if ret == FM_REFUSED:
        from .. import telemetry
        from ..utils.logger import log_warning

        # only the sparse engine refuses (16-bit packed tags); the normal
        # entry reaches it because the dense table is over the cap, the
        # test hook because the caller forced the sparse path
        reason = "k exceeds the sparse engine's 16-bit tag limit (0xFFFF)"
        reason += (
            " (sparse path forced)" if force_sparse
            else " and the dense (n, k) table is unaffordable"
        )
        telemetry.event(
            "fm-refused", n=int(graph.n), k=int(k), reason=reason
        )
        log_warning(f"native FM did not run: {reason} (n={graph.n}, k={k})")
    return ret


# ---------------------------------------------------------------------------
# v2 codec: interval + streamvbyte-class residuals + varint edge weights
# (codec2.cpp — the TeraPart compressed_neighborhoods parity codec).
# Native-only: the numpy fallback keeps the v1 gap codec.
# ---------------------------------------------------------------------------


def encode_v2(xadj, adjncy):
    """Encode sorted CSR neighborhoods with the v2 codec.
    Returns (bytes u8[total], offsets i64[n+1]) or None without the lib."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(xadj) - 1
    xadj = np.ascontiguousarray(xadj, dtype=np.int64)
    adjncy = np.ascontiguousarray(adjncy, dtype=np.int32)
    offsets = np.zeros(n + 1, dtype=np.int64)
    total = lib.kmp_encode_v2_size(n, xadj, adjncy, offsets)
    out = np.empty(total, dtype=np.uint8)
    lib.kmp_encode_v2(n, xadj, adjncy, offsets, out)
    return out, offsets


def decode_v2(xadj, offsets, data):
    """Decode a v2 stream; returns adjncy i32[m] in EMIT order
    (interval members first — pairs 1:1 with the weight stream)."""
    lib = get_lib()
    assert lib is not None, "v2 codec requires the native library"
    n = len(xadj) - 1
    xadj = np.ascontiguousarray(xadj, dtype=np.int64)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    out = np.empty(int(xadj[-1]), dtype=np.int32)
    lib.kmp_decode_v2(n, xadj, offsets, data, out)
    return out


def decode_v2_node(u, xadj, offsets, data):
    lib = get_lib()
    assert lib is not None, "v2 codec requires the native library"
    deg = int(xadj[u + 1] - xadj[u])
    out = np.empty(deg, dtype=np.int32)
    if deg:
        lib.kmp_decode_v2_node(
            int(u),
            np.ascontiguousarray(xadj, dtype=np.int64),
            np.ascontiguousarray(offsets, dtype=np.int64),
            np.ascontiguousarray(data, dtype=np.uint8),
            out,
        )
    return out


def encode_v2_weights(xadj, adjncy, edge_w):
    """Varint-encode edge weights in the v2 EMIT order.
    Returns (bytes, woffsets) or None without the lib."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(xadj) - 1
    xadj = np.ascontiguousarray(xadj, dtype=np.int64)
    adjncy = np.ascontiguousarray(adjncy, dtype=np.int32)
    edge_w = np.ascontiguousarray(edge_w, dtype=np.int64)
    woffsets = np.zeros(n + 1, dtype=np.int64)
    total = lib.kmp_encode_v2_weights_size(n, xadj, adjncy, edge_w, woffsets)
    out = np.empty(total, dtype=np.uint8)
    lib.kmp_encode_v2_weights(n, xadj, adjncy, edge_w, woffsets, out)
    return out, woffsets


def decode_v2_weights(xadj, woffsets, wdata):
    lib = get_lib()
    assert lib is not None, "v2 codec requires the native library"
    n = len(xadj) - 1
    xadj = np.ascontiguousarray(xadj, dtype=np.int64)
    woffsets = np.ascontiguousarray(woffsets, dtype=np.int64)
    wdata = np.ascontiguousarray(wdata, dtype=np.uint8)
    out = np.empty(int(xadj[-1]), dtype=np.int64)
    lib.kmp_decode_v2_weights(n, xadj, woffsets, wdata, out)
    return out
