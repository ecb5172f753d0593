#!/usr/bin/env python
"""Break-even of the CSR-order streaming helpers against XLA's gather and
scatter (ops/segments.expand_rows, csr_block_ratings), on the chip.

For each level shape (n, m, n_pad, m_pad): `values[src]` against
expand_rows, and for each k the flat segment_sum conn table against the
streaming engine at each of --columns block columns a step (default: the
library's conn_stream_columns).  With --slots, for the scatter rating's
slot table at each shape (SLOTS a pass): whether a rated cluster has room,
asked of the finished table (two gathers at table width, the plain
reference of tests/test_rating.py) against decided per edge (one more dst
gather and two owner streams, as ops/lp.lp_round does), and against the
same with the room bit-packed beside the label into the one word
labels[dst] moves.  With --conn-delta, and none of the above, for each
shape and k: Jet's conn table rebuilt (ops/jet._full_ratings) against
updated from the movers' rows (_conn_update_rows) through a buffer of
m_pad // 4 (the afterburner's row buffer past the gate), // 8, // 16
(what _conn_step uses) and // 32 slots, the movers' degrees filling the
smallest: what ops/jet.CONN_DELTA_DIVISOR rests on.  With
--jet-iteration, and none of the above, for each shape and k: ONE
ops/jet._jet_iteration on both paths, from the same graph, the same
partition (a random one settled by SETTLE edge-wide iterations), the same
table and the same salt: `jet-rows` as the program runs it from
jet.DELTA_MIN_EDGE_SLOTS slots on, `jet-edges` with that gate raised
past the shape for this process alone, and `wide`, the `jet-rows`
iteration without its narrow branch (the candidates always pruned to and
filtered through m_pad // 4, whether or not their rows fit _conn_slots);
ms, and what each iteration counted (movers, conn_delta, pruned, rows,
wide).
With --level0-pad, and none of the above, for each shape and k: the same
real graph uploaded at the shape's power-of-two widths and at the input
graph's eighth-octave widths (caching.input_pad_size over the chip's
floors, what graphs/csr.upload_input_graph gives level 0): ms of one
scatter clustering round (ops/lp.lp_round, the shape's slots, every node
active) and of one Jet iteration as the program runs it at the bucket
(the same path at both widths: the gates read the bucket), each checked
to give the same labels, partition and table at both widths.
Past the gate the row also times the filter alone from the iteration's
find step (jet._gated_rows_filter): as the program runs it
(`narrow_filter_ms`: through _conn_slots where the candidates' rows fit
it, `filter_wide` 0) against the prune and the afterburner through
m_pad // 4 (`wide_filter_ms`), both checked to give the same accepts,
table and `pruned`.  At a shape UNDER that gate
the pair is the one the iteration chooses between by itself: the row
afterburner through _conn_slots (jet._rows_filter) against the edge-wide
one followed by _conn_step (the iteration as it was before PR 35) from the
same state, all nodes locked but a random set whose rows fill half the
buffer, so the candidates fit it; their summed degree is printed.  Every
timing is the minimum
of REPS launches ending in block_until_ready; the labels[dst] gather both
engines share is timed alone so it can be subtracted.  A small program
compiles in ~25 s on the chip: name only what you need.

Usage: python scripts/microbench_csr_stream.py [--shapes coarse,fine,mesh]
    [--ks 2,4,8,16,32] [--columns 1,4,8] [--no-scatter] [--slots]
    python scripts/microbench_csr_stream.py --conn-delta
    [--shapes fine,coarse,mesh,large] [--ks 2,16]
    python scripts/microbench_csr_stream.py --jet-iteration
    [--shapes large,fine,mesh] [--ks 2,16]
    python scripts/microbench_csr_stream.py --level0-pad
    [--shapes fine,large,mesh] [--ks 2]
(TPU; a CPU run only proves the script runs.)  Writes
chiprun_out/microbench_csr_stream.json.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from kaminpar_tpu.utils.platform import configure_compile_cache

configure_compile_cache()

import jax.numpy as jnp
import numpy as np

from kaminpar_tpu import caching
from kaminpar_tpu.graphs.csr import device_graph_from_host
from kaminpar_tpu.ops import jet
from kaminpar_tpu.ops import lp
from kaminpar_tpu.ops import segments as seg

REPS = 5
# name -> (n, m, n_pad, m_pad): levels 1 and 0 of rmat-s16 at --seed 1,
# and levels 0 and 1 of delaunay-n17 (degree 6; n = 2^17 exactly, and
# the n + 1 row pointers pad to 2^18)
SHAPES = {
    "coarse": (7_759, 903_382, 1 << 13, 1 << 20),
    "fine": (41_761, 1_083_716, 1 << 16, 1 << 21),
    "mesh": (131_072, 786_374, 1 << 18, 1 << 20),
    "mesh1": (26_901, 160_468, 1 << 15, 1 << 20),
    # level 0 of rmat-s17 at --seed 1: the large side of the 1 << 22 gate,
    # where Jet prunes to and runs its afterburner over m_pad // 4 slots
    "large": (80_170, 2_207_668, 1 << 17, 1 << 22),
    # a rehearsal on the CPU, no level of any cell
    "tiny": (500, 6_000, 1 << 9, 1 << 13),
}
# slots a pass of the scatter rating at a shape: the preset's 32, doubled
# by the coarsener on rmat-s16's level 0 (average degree 26 > 16)
SLOTS = {"coarse": 32, "fine": 64, "large": 64, "mesh": 32, "tiny": 32}
# the chip's shape floors (graphs/csr.shape_floors), which the input
# graph's eighth-octave bucket rounds over
CHIP_FLOORS = (1 << 13, 1 << 20)
CONN_DIVISORS = (4, 8, 16, 32)
# iterations that settle the random start of --jet-iteration (the coarse
# budget): the timed one then moves what an iteration of a refiner call
# moves, not what the first one from a random partition does
SETTLE = 12


def skew(rng, n):
    """Node probabilities with R-MAT-like skew."""
    p = (rng.permutation(n) + 1.0) ** -0.8
    return p / p.sum()


def skewed_graph(rng, n, m):
    """A directed stand-in with RMAT-like skew on both sides (the
    timings depend on index counts and locality, not on symmetry)."""
    from kaminpar_tpu.graphs.host import HostGraph

    p = skew(rng, n)
    deg = rng.multinomial(m, p)
    xadj = np.concatenate([[0], np.cumsum(deg)]).astype(np.int64)
    adjncy = rng.choice(n, size=m, p=p).astype(np.int32)
    return HostGraph(xadj=xadj, adjncy=adjncy, node_weights=None,
                     edge_weights=rng.integers(1, 50, m).astype(np.int64))


def symmetric_skewed_graph(rng, n, m):
    """The same skew with every edge stored in both directions under one
    weight: a conn table is updated from its movers' rows only where the
    neighbour's row holds the edge too."""
    from kaminpar_tpu.graphs.host import HostGraph

    p = skew(rng, n)
    u, v = rng.choice(n, size=(2, m // 2), p=p).astype(np.int32)
    w = rng.integers(1, 50, m // 2).astype(np.int64)
    src, dst = np.concatenate([u, v]), np.concatenate([v, u])
    order = np.argsort(src, kind="stable")
    xadj = np.concatenate(
        [[0], np.cumsum(np.bincount(src, minlength=n))]).astype(np.int64)
    return HostGraph(xadj=xadj, adjncy=dst[order], node_weights=None,
                     edge_weights=np.concatenate([w, w])[order])


def best_ms(fn_j, *args):
    """Minimum of REPS launches of a function that has compiled."""
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn_j(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def timeit(fn, *args):
    fn_j = jax.jit(fn)
    jax.block_until_ready(fn_j(*args))  # compile
    return best_ms(fn_j, *args)


def slots_row(rng, graph, num_slots):
    """ms a clustering round for the feasibility of the rated clusters,
    three ways (module docstring), each checked against the first."""
    n_pad, m_pad = graph.n_pad, graph.m_pad
    label_bits = (n_pad - 1).bit_length()
    room_max = (1 << (31 - label_bits)) - 1
    labels = jnp.asarray(rng.integers(0, n_pad, n_pad).astype(np.int32))
    weights = jnp.asarray(rng.integers(0, 64, n_pad).astype(np.int32))
    cap = jnp.int32(48)
    # what the finished table holds: a label or -1 in every slot
    table = jnp.asarray(
        rng.integers(-1, n_pad, (n_pad, 2 * num_slots)).astype(np.int32))

    def table_side(g, slot_label, weights, cap):
        lab_c = jnp.clip(slot_label, 0, n_pad - 1)
        cap_b = jnp.broadcast_to(cap, (n_pad,))
        return weights[lab_c] + g.node_w[:, None] <= cap_b[lab_c]

    def of_owner(g, values):
        return seg.expand_rows(values, g.row_ptr, m_pad)

    def edge_side(g, labels, weights, cap):
        nb = labels[g.dst]
        room = (cap - weights)[labels]
        fits = of_owner(g, g.node_w) <= room[g.dst]
        return nb, fits | (nb == of_owner(g, labels))

    def packed(g, labels, weights, cap):
        # exact while no node weighs more than room_max (a guard and
        # the form above as its fallback would have to go with it)
        room = jnp.clip((cap - weights)[labels], 0, room_max)
        word = ((room << label_bits) | labels)[g.dst]
        nb = word & ((1 << label_bits) - 1)
        fits = of_owner(g, g.node_w) <= (word >> label_bits)
        return nb, fits | (nb == of_owner(g, labels))

    want = edge_side(graph, labels, weights, cap)
    got = packed(graph, labels, weights, cap)
    assert all(bool(jnp.all(a == b)) for a, b in zip(want, got))
    # the per-edge bit is the table's: slot (u, label) against any edge
    # of u into that label
    src, dst = np.asarray(graph.src), np.asarray(graph.dst)
    ref = np.asarray(
        weights[labels][dst] + graph.node_w[src] <= cap)
    own = np.asarray(labels)[dst] == np.asarray(labels)[src]
    assert (np.asarray(want[1]) == (ref | own)).all()
    return dict(
        op="slots", num_slots=num_slots, table_entries=n_pad * 2 * num_slots,
        table_side_ms=timeit(table_side, graph, table, weights, cap),
        labels_dst_ms=timeit(lambda g, lab: lab[g.dst], graph, labels),
        edge_side_ms=timeit(edge_side, graph, labels, weights, cap),
        packed_ms=timeit(packed, graph, labels, weights, cap))


def conn_delta_row(rng, graph, k):
    """ms for the conn table of a partition a few nodes away from the one
    the table in hand matches: rebuilt, and updated through each buffer
    (each checked against the rebuild)."""
    n_pad, m_pad = graph.n_pad, graph.m_pad
    before = jnp.asarray(rng.integers(0, k, n_pad).astype(np.int32))
    # movers in random order while their rows fit the smallest buffer
    degrees = np.asarray(graph.degrees)
    order = rng.permutation(n_pad)
    fits = np.cumsum(degrees[order]) <= m_pad // max(CONN_DIVISORS)
    moved = np.zeros(n_pad, bool)
    moved[order[fits]] = True
    after = jnp.where(jnp.asarray(moved), (before + 1) % k, before)
    conn = jet._full_ratings(graph, before, k)
    want = jet._full_ratings(graph, after, k)
    row = dict(op="conn_delta", k=k, movers=int(moved.sum()),
               mover_edges=int(degrees[moved].sum()),
               full_ms=timeit(lambda g, p: jet._full_ratings(g, p, k),
                              graph, after))
    for divisor in CONN_DIVISORS:
        fn = lambda g, c, b, a: jet._conn_update_rows(
            g, c, b, a, k, m_pad // divisor)
        assert bool(jnp.all(fn(graph, conn, before, after) == want))
        row[f"delta_div{divisor}_ms"] = timeit(fn, graph, conn, before,
                                               after)
    return row


def parent_filter(graph, conn, part, next_part, gain, candidate, k, slots):
    """jet._rows_filter's answer as an iteration under the gate computed
    it before PR 35: the edge-wide afterburner, then _conn_step through
    the same buffer (the rebuild _edges_filter adds is dead code here)."""
    accept, _ = jet._edges_filter(graph, part, next_part, gain, candidate, k)
    moved = jnp.where(accept, next_part, part)
    return accept, jet._conn_step(graph, conn, part, moved, k, slots)[0]


def always_wide(graph, conn, part, best, gain, candidate, k, salt, dslots,
                conn_slots, gated=jet._gated_rows_filter):
    """jet._gated_rows_filter without its narrow branch: the prune and the
    buffer of `dslots` whatever the candidates' rows (_conn_step keeps its
    own buffer)."""
    return gated(graph, conn, part, best, gain, candidate, k, salt, dslots, 0)


def jet_step(graph, k, caps, gate, **stand_ins):
    """One jitted _jet_iteration that resolves its path under `gate`, with
    the functions of ops/jet named in `stand_ins` replaced.  Both are read
    while tracing; the program's own are back after every call."""
    step = jax.jit(lambda g, part, lock, conn, salt: jet._jet_iteration(
        g, part, lock, k, caps, jnp.float32(0.25), salt, 4, conn=conn))

    def call(part, lock, conn, salt):
        shipped = {name: getattr(jet, name)
                   for name in ("DELTA_MIN_EDGE_SLOTS", *stand_ins)}
        jet.DELTA_MIN_EDGE_SLOTS = gate
        for name, fn in stand_ins.items():
            setattr(jet, name, fn)
        try:
            return step(graph, part, lock, conn, salt)
        finally:
            for name, value in shipped.items():
                setattr(jet, name, value)

    return call


def filter_row(graph, part, lock, conn, k, salt):
    """ms of jet._gated_rows_filter from one find step: as the program
    runs it (through _conn_slots where the candidates' rows fit it) and
    without its narrow branch (pruned to and through _delta_slots); the
    accepts, the table and `pruned` of both compared."""
    best, gain, _, candidate = jet._find_moves(
        graph, conn, part, lock, k, jnp.float32(0.25), salt)
    conn_slots, dslots = jet._conn_slots(graph), jet._delta_slots(graph)

    def gated(slots):
        return lambda g, conn, part, best, gain, candidate: (
            jet._gated_rows_filter(g, conn, part, best, gain, candidate, k,
                                   salt, dslots, slots))

    args = (graph, conn, part, best, gain, candidate)
    got, want = jax.jit(gated(conn_slots))(*args), jax.jit(gated(0))(*args)
    return dict(
        candidate_edges=int(jet._candidate_slots(graph, candidate)),
        filter_wide=int(got[3]),
        narrow_filter_ms=timeit(gated(conn_slots), *args),
        wide_filter_ms=timeit(gated(0), *args),
        same_filter=all(bool(jnp.all(a == b))
                        for a, b in zip(got[:3], want[:3])))


def candidate_edges(graph, part, lock, conn, k, salt):
    """Summed degree of the candidates jet_step's iteration finds."""
    *_, candidate = jet._find_moves(
        graph, conn, part, lock, k, jnp.float32(0.25), salt)
    return int(jet._candidate_slots(graph, candidate))


def jet_iteration_row(rng, graph, k):
    """ms of one level-0 Jet iteration (fine temperature, 4 balancer
    rounds, epsilon 0.03) on the rows path and on the edge-wide path."""
    n_pad, m_pad = graph.n_pad, graph.m_pad
    node_w = np.asarray(graph.node_w)
    caps = jnp.full(k, int(1.03 * np.ceil(node_w.sum() / k)), jnp.int32)
    part = jnp.asarray(np.where(
        np.arange(n_pad) < int(graph.n), rng.integers(0, k, n_pad), 0
    ).astype(np.int32))
    under_gate = m_pad < jet.DELTA_MIN_EDGE_SLOTS
    if under_gate:
        paths = {"rows": jet_step(graph, k, caps, 2 * m_pad),
                 "edges": jet_step(graph, k, caps, 2 * m_pad,
                                   _rows_filter=parent_filter)}
    else:
        gate = jet.DELTA_MIN_EDGE_SLOTS
        paths = {"rows": jet_step(graph, k, caps, gate),
                 "wide": jet_step(graph, k, caps, gate,
                                  _gated_rows_filter=always_wide),
                 "edges": jet_step(graph, k, caps, 2 * m_pad)}
    lock = jnp.zeros(n_pad, jnp.int32)
    conn = jet._full_ratings(graph, part, k)
    salts = [jnp.int32((12345 + i * 1566083941) & 0x7FFFFFFF)
             for i in range(SETTLE + 1)]
    for salt in salts[:-1]:
        part, lock, _, conn, *_ = paths["edges"](part, lock, conn, salt)
    conn_slots = jet._conn_slots(graph)
    row = dict(op="jet_iteration", k=k, settle=SETTLE, conn_slots=conn_slots,
               under_gate=under_gate)
    if under_gate:
        # unlocked: nodes in random order while their rows fill half the
        # buffer, so whatever the find step makes of them fits it
        order = rng.permutation(n_pad)
        free = np.cumsum(np.asarray(graph.degrees)[order]) <= conn_slots // 2
        locked = np.ones(n_pad, np.int32)
        locked[order[free]] = 0
        lock = jnp.asarray(locked)
        row["candidate_edges"] = candidate_edges(
            graph, part, lock, conn, k, salts[-1])
    else:
        row.update(filter_row(graph, part, lock, conn, k, salts[-1]))
    after, table = {}, {}
    for name, step in paths.items():
        args = (part, lock, conn, salts[-1])
        (after[name], new_lock, _, table[name], conn_delta, pruned,
         rows, wide) = step(*args)
        row[f"{name}_ms"] = best_ms(step, *args)
        row[name] = dict(
            accepted=int(new_lock.sum()),
            changed=int((after[name] != part).sum()),
            conn_delta=int(conn_delta), pruned=int(pruned), rows=int(rows),
            wide=int(wide))
    row["same_partition"] = all(
        bool(jnp.all(after["rows"] == p)) for p in after.values())
    row["same_table"] = all(
        bool(jnp.all(table["rows"] == t)) for t in table.values())
    return row


def level0_pad_row(rng, host, n_pad, m_pad, k, num_slots):
    """ms of one scatter clustering round and one Jet iteration on the
    same real graph at its power-of-two widths (n_pad, m_pad) and at the
    input graph's eighth-octave widths; both answers compared."""
    n, m = host.n, host.m
    # the chip's floors, or the shape's own pads where those lie under
    # them (the CPU rehearsal's tiny shape)
    n_floor, m_floor = min(CHIP_FLOORS[0], n_pad), min(CHIP_FLOORS[1], m_pad)
    widths = {"pow2": (n_pad, m_pad),
              "eighth": (caching.input_pad_size(n + 1, n_floor),
                         caching.input_pad_size(m, m_floor))}
    graphs = {name: device_graph_from_host(host, n_pad=np_, m_pad=mp)
              for name, (np_, mp) in widths.items()}
    cfg = lp.LPConfig(rating="scatter", num_slots=num_slots)
    cap = jnp.int32(max(1, int(host.node_weight_array().sum()) // 4096))
    salt = jnp.int32(0x2545F491)

    def cluster(g, labels, weights, active):
        return lp.lp_round(g, labels, weights, cap, active, salt, cfg)

    row = dict(op="level0_pad", k=k, n=n, m=m, num_slots=num_slots,
               pow2=list(widths["pow2"]), eighth=list(widths["eighth"]))
    settled = None
    answers = {}
    for name, g in graphs.items():
        width = g.n_pad
        labels = jnp.arange(width, dtype=jnp.int32)
        args = (g, labels, g.node_w.astype(jnp.int32),
                jnp.arange(width) < n)
        row[f"lp_round_{name}_ms"] = timeit(cluster, *args)
        new_labels = jax.jit(cluster)(*args)[0]

        node_w = np.asarray(g.node_w)
        caps = jnp.full(k, int(1.03 * np.ceil(node_w.sum() / k)), jnp.int32)
        step = jet_step(g, k, caps, jet.DELTA_MIN_EDGE_SLOTS)
        if settled is None:  # a partition settled on the first width
            part = np.where(np.arange(width) < n,
                            rng.integers(0, k, width), 0).astype(np.int32)
            part, lock = jnp.asarray(part), jnp.zeros(width, jnp.int32)
            conn = jet._full_ratings(g, part, k)
            for i in range(SETTLE):
                part, lock, _, conn, *_ = step(
                    part, lock, conn, jnp.int32(12345 + 7919 * i))
            settled = (np.asarray(part)[:n], np.asarray(lock)[:n])
        part = jnp.asarray(np.pad(settled[0], (0, width - n)))
        lock = jnp.asarray(np.pad(settled[1], (0, width - n)))
        conn = jet._full_ratings(g, part, k)
        jet_args = (part, lock, conn, jnp.int32(0x3C6EF372))
        row[f"jet_iteration_{name}_ms"] = best_ms(step, *jet_args)
        after, _, _, table, *_ = step(*jet_args)
        row[f"jet_path_{name}"] = jet.iteration_path(g, k)
        answers[name] = (np.asarray(new_labels)[:n], np.asarray(after)[:n],
                         np.asarray(table)[:n])
    row["same_answers"] = all(
        np.array_equal(a, b)
        for a, b in zip(answers["pow2"], answers["eighth"]))
    return row


def ints(text):
    return [int(x) for x in text.split(",") if x]


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--shapes", default="coarse,fine")
    parser.add_argument("--ks", type=ints, default=[2, 4, 8, 16, 32])
    parser.add_argument("--columns", type=ints, default=[])
    parser.add_argument("--no-scatter", action="store_true")
    parser.add_argument("--slots", action="store_true")
    parser.add_argument("--conn-delta", action="store_true")
    parser.add_argument("--jet-iteration", action="store_true")
    parser.add_argument("--level0-pad", action="store_true")
    args = parser.parse_args()
    jet_rows = [fn for flag, fn in (("conn_delta", conn_delta_row),
                                    ("jet_iteration", jet_iteration_row))
                if getattr(args, flag)]
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "reps": REPS, "rows": []}
    print(json.dumps(out["device"]), flush=True)
    rng = np.random.default_rng(0)
    for name in args.shapes.split(","):
        n, m, n_pad, m_pad = SHAPES[name]
        make = (symmetric_skewed_graph if jet_rows or args.level0_pad
                else skewed_graph)
        host = make(rng, n, m)
        if args.level0_pad:
            for k in args.ks:
                row = dict(shape=name, **level0_pad_row(
                    rng, host, n_pad, m_pad, k, SLOTS.get(name, 32)))
                out["rows"].append(row)
                print(json.dumps(row), flush=True)
            continue
        graph = device_graph_from_host(host, n_pad=n_pad, m_pad=m_pad)
        values = jnp.asarray(
            rng.integers(0, 2**31 - 1, n_pad).astype(np.int32))
        shape = {"shape": name, "n_pad": n_pad, "m_pad": m_pad, "m": m}
        if jet_rows:
            for make_row in jet_rows:
                for k in args.ks:
                    row = dict(shape, **make_row(rng, graph, k))
                    out["rows"].append(row)
                    print(json.dumps(row), flush=True)
            continue
        if not args.no_scatter:
            row = dict(
                shape, op="owner_column",
                gather_ms=timeit(lambda g, v: v[g.src], graph, values),
                expand_rows_ms=timeit(
                    lambda g, v: seg.expand_rows(v, g.row_ptr, m_pad),
                    graph, values),
                cumsum_ms=timeit(lambda g: jnp.cumsum(g.edge_w), graph),
                dst_gather_ms=timeit(lambda g, v: v[g.dst], graph, values))
            out["rows"].append(row)
            print(json.dumps(row), flush=True)
        if args.slots and name in SLOTS:
            row = dict(shape, **slots_row(rng, graph, SLOTS[name]))
            out["rows"].append(row)
            print(json.dumps(row), flush=True)
        for k in args.ks:
            labels = jnp.asarray(rng.integers(0, k, n_pad).astype(np.int32))
            dense = lambda g, lab: seg.dense_block_ratings(
                g.src, g.dst, g.edge_w, lab, n_pad, k)
            ref = dense(graph, labels)
            row = dict(shape, op="conn_table", k=k,
                       rule_streams=seg.conn_table_streams(k, n_pad, m_pad))
            if not args.no_scatter:
                row["scatter_ms"] = timeit(dense, graph, labels)
            for columns in args.columns or [seg.conn_stream_columns(m_pad)]:
                columns = min(columns, k)
                fn = lambda g, lab: seg._stream_block_ratings(
                    g, lab, k, columns)
                assert bool(jnp.all(fn(graph, labels) == ref))
                row[f"stream_c{columns}_ms"] = timeit(fn, graph, labels)
            out["rows"].append(row)
            print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/microbench_csr_stream.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
