// Localized batch k-way FM refinement (native host runtime).
//
// The native analog of the reference's parallel localized FM
// (kaminpar-shm/refinement/fm/fm_refiner.cc:48-110 FMRefiner/
// LocalizedFMRefiner, gains/delta_gain_caches.h:202): seed nodes are
// taken from the pass's shuffled border, each batch grows a localized
// region speculatively against a DELTA overlay of the partition and gain
// table, and only the best prefix of the batch's moves is committed to
// the global state; non-moved region nodes are released for later
// batches.
//
// One thread runs the batches one after another: each sees every earlier
// commit, its delta is exact and its prefix always fits.
//
// More threads run a pass in ROUNDS of kRoundBatches batches, where the
// reference's pool races.  The workers grow a round's regions in
// parallel against the state the last round left and write nothing
// shared (regions of one round may overlap); between rounds one thread
// commits the round's batches in batch order.  A commit replays the
// batch's prefix move by move against the global state: it stops at a
// node an earlier batch of the round moved, or at a block the cap
// refuses, takes each move's EXACT gain from the global table, keeps the
// best sub-prefix (none where none gains) and undoes the rest.  So each
// batch's kept gain is exact and >= 0, the return value is exactly
// cut_in - cut_out >= 0, no block ever passes its cap, and the partition
// depends on the seed alone, not on the thread count (>= 2) or on the
// threads' timing.  The caller may pass a stats array (FmStat).
//
// Dense (n, k) gain table (gains/sparse_gain_cache.h lineage), delta
// overlay of arena rows behind a flat node-indexed slot array, adaptive
// (Osipov-Sanders) or simple stopping.

#include <algorithm>
#include <atomic>
#include <barrier>
#include <cmath>
#include <cstdint>
#include <cstring>
#include <memory>
#include <queue>
#include <thread>
#include <tuple>
#include <unordered_map>
#include <vector>

namespace {

struct Rng {
  uint64_t s;
  explicit Rng(uint64_t seed) : s(seed ^ 0x9E3779B97F4A7C15ULL) {
    if (s == 0) s = 0x2545F4914F6CDD1DULL;
  }
  uint64_t next() {
    uint64_t z = (s += 0x9E3779B97F4A7C15ULL);
    z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ULL;
    z = (z ^ (z >> 27)) * 0x94D049BB133111EBULL;
    return z ^ (z >> 31);
  }
  uint32_t tie() { return (uint32_t)(next() >> 32); }
};

constexpr auto kRelaxed = std::memory_order_relaxed;

// Plain loads and stores throughout: workers only read the global state,
// and only while no batch commits (refine_rounds).
struct Ctx {
  int64_t n, k;
  const int64_t* xadj;
  const int32_t* adjncy;
  const int64_t* node_w;
  const int64_t* edge_w;
  const int64_t* max_bw;
  int32_t* part;
  std::vector<int64_t> conn;  // dense (n, k) connection table
  std::vector<int64_t> bw;    // global block weights

  int64_t conn_at(int64_t u, int64_t b) const { return conn[u * k + b]; }
  int32_t part_at(int64_t u) const { return part[u]; }
  int64_t bw_at(int64_t b) const { return bw[b]; }
};

// per-node state within a pass (NodeTracker analog): kFree = claimable,
// kMoved = committed this pass, else (one thread) the owning batch id
constexpr int32_t kFree = -1;
constexpr int32_t kMoved = -2;

// batches a round of a pass on more than one thread
constexpr size_t kRoundBatches = 32;

// kmp_fm_refine's stats out-array, one slot each (native/__init__.py's
// FM_STATS names them in this order)
enum FmStat {
  kStatThreads,        // worker threads
  kStatPasses,         // passes run
  kStatBatches,        // batches that grew a region
  kStatCommitted,      // moves committed and kept
  kStatCapRefusals,    // commits the block-weight cap refused
  kStatUndoneMoves,    // moves a commit undid (past the best sub-prefix)
  kStatEstimatedGain,  // the batches' delta gains for their prefixes
  kStatExactGain,      // cut_in - cut_out, the return value
  kNumStats
};

struct Counters {
  int64_t batches = 0, committed = 0, cap_refusals = 0, undone = 0,
          estimated = 0;
};

struct Params {
  int64_t num_seeds;
  double alpha;
  int64_t num_fruitless;
  int use_adaptive;
};

void build_conn(Ctx& c) {
  std::fill(c.conn.begin(), c.conn.end(), 0);
  std::fill(c.bw.begin(), c.bw.end(), 0);
  for (int64_t u = 0; u < c.n; ++u) {
    c.bw[c.part[u]] += c.node_w[u];
    for (int64_t e = c.xadj[u]; e < c.xadj[u + 1]; ++e)
      c.conn[u * c.k + c.part[c.adjncy[e]]] += c.edge_w[e];
  }
}

// Delta overlay (delta_gain_caches.h analog): tentative partition and
// gain-table deltas for the current batch.  Touched nodes get a dense
// ARENA row copy of their (k-wide) connection row plus a tentative
// block field.  A node finds its arena slot in a flat node-indexed array
// (-1 = untouched this batch): a row access is one array read, where a
// hash map cost a probe (~22 a tentative move, the hot path of the
// whole refiner).  The array is n wide once a worker and pass; a batch
// resets only the entries it set, by its list of the nodes it touched (there
// are hundreds of batches a call, each touching a few thousand nodes).
struct Delta {
  const Ctx* c;
  std::vector<int32_t> slot_of;  // u -> arena slot, -1 = untouched
  std::vector<int32_t> node_of;  // arena slot -> u (the reset list)
  std::vector<int64_t> rows;     // arena, k per slot
  std::vector<int32_t> blocks;   // arena slot -> tent. block
  std::vector<int64_t> bw_delta;

  explicit Delta(const Ctx& ctx)
      : c(&ctx), slot_of(ctx.n, -1), bw_delta(ctx.k, 0) {}
  void clear() {
    for (const int32_t u : node_of) slot_of[u] = -1;
    node_of.clear();
    rows.clear();
    blocks.clear();
    std::fill(bw_delta.begin(), bw_delta.end(), 0);
  }
  // arena row of u, materialized from the global table on first touch
  int64_t* row(int64_t u) {
    int32_t s = slot_of[u];
    if (s < 0) {
      s = slot_of[u] = (int32_t)node_of.size();
      node_of.push_back((int32_t)u);
      const size_t base = rows.size();
      rows.resize(base + c->k);
      for (int64_t b = 0; b < c->k; ++b)
        rows[base + b] = c->conn_at(u, b);
      blocks.push_back(c->part_at(u));
    }
    return rows.data() + (int64_t)s * c->k;
  }
  int32_t block(int64_t u) const {
    const int32_t s = slot_of[u];
    return s < 0 ? c->part_at(u) : blocks[s];
  }
  // row view: the arena row when touched, else a temp copy of the
  // global row
  const int64_t* row_view(int64_t u, int64_t* scratch) const {
    const int32_t s = slot_of[u];
    if (s >= 0) return rows.data() + (int64_t)s * c->k;
    for (int64_t b = 0; b < c->k; ++b) scratch[b] = c->conn_at(u, b);
    return scratch;
  }
  int64_t weight(int64_t b) const { return c->bw_at(b) + bw_delta[b]; }
  // tentatively move u from -> to, updating neighbor rows
  void move(int64_t u, int32_t from, int32_t to) {
    row(u);  // materialize so the block override has a slot
    blocks[slot_of[u]] = to;
    bw_delta[from] -= c->node_w[u];
    bw_delta[to] += c->node_w[u];
    for (int64_t e = c->xadj[u]; e < c->xadj[u + 1]; ++e) {
      const int32_t v = c->adjncy[e];
      int64_t* r = row(v);
      r[from] -= c->edge_w[e];
      r[to] += c->edge_w[e];
    }
  }
};

// best feasible move of u under the delta view: (gain, target) or
// (INT64_MIN, -1)
std::pair<int64_t, int32_t> best_move(const Delta& d, int64_t u, Rng& rng,
                                      int64_t* scratch) {
  const Ctx& c = *d.c;
  const int32_t b = d.block(u);
  const int64_t* r = d.row_view(u, scratch);
  const int64_t own = r[b];
  int64_t best_gain = INT64_MIN;
  int32_t best_t = -1;
  uint32_t best_tie = 0;
  for (int32_t t = 0; t < c.k; ++t) {
    if (t == b) continue;
    if (d.weight(t) + c.node_w[u] > c.max_bw[t]) continue;
    const int64_t g = r[t] - own;
    if (g > best_gain) {
      best_gain = g;
      best_t = t;
      best_tie = rng.tie();
    } else if (g == best_gain && best_t >= 0) {
      const uint32_t tb = rng.tie();
      if (tb > best_tie) {
        best_t = t;
        best_tie = tb;
      }
    }
  }
  return {best_gain, best_t};
}

bool fits(const Ctx& c, int64_t u, int32_t to) {
  return c.bw[to] + c.node_w[u] <= c.max_bw[to];
}

// move u from -> to in the global state
void apply_move(Ctx& c, int64_t u, int32_t from, int32_t to) {
  const int64_t w = c.node_w[u];
  c.bw[to] += w;
  c.bw[from] -= w;
  c.part[u] = to;
  for (int64_t e = c.xadj[u]; e < c.xadj[u + 1]; ++e) {
    const int64_t v = c.adjncy[e];
    c.conn[v * c.k + from] -= c.edge_w[e];
    c.conn[v * c.k + to] += c.edge_w[e];
  }
}

// The batch's candidate moves, popped in descending (gain, tie, node,
// target) order: exactly what one binary heap over all of them pops.
// Most entries a batch pushes are never popped (four of five on a mesh:
// the region stops or hits its cap first, and they sit below every move
// it makes), so only those at or above a gain FLOOR are kept heap-ordered;
// the rest wait unordered, and a push below the floor is an append.
// When the heap runs empty the floor drops to the gain of the waiting
// entries' best sixteenth (the top gain bucket where gains repeat, as
// on an unweighted mesh; a sixteenth of them where every gain is
// distinct, so the scan is paid for by the pops it feeds).
struct MoveQueue {
  using Entry = std::tuple<int64_t, uint32_t, int64_t, int32_t>;
  std::vector<Entry> heap;  // gain >= floor, heap-ordered
  std::vector<Entry> rest;  // gain < floor, unordered
  int64_t floor = INT64_MAX;

  bool empty() const { return heap.empty() && rest.empty(); }
  void push(const Entry& e) {
    if (std::get<0>(e) >= floor) {
      heap.push_back(e);
      std::push_heap(heap.begin(), heap.end());
    } else {
      rest.push_back(e);
    }
  }
  Entry pop() {
    if (heap.empty()) lower_floor();
    std::pop_heap(heap.begin(), heap.end());
    const Entry e = heap.back();
    heap.pop_back();
    return e;
  }
  void lower_floor() {
    const auto by_gain = [](const Entry& a, const Entry& b) {
      return std::get<0>(a) > std::get<0>(b);
    };
    const auto nth = rest.begin() + rest.size() / 16;
    std::nth_element(rest.begin(), nth, rest.end(), by_gain);
    floor = std::get<0>(*nth);
    const auto waiting = std::partition(
        rest.begin(), rest.end(),
        [&](const Entry& e) { return std::get<0>(e) < floor; });
    heap.assign(waiting, rest.end());
    rest.erase(waiting, rest.end());
    std::make_heap(heap.begin(), heap.end());
  }
};

struct Move {
  int64_t u;
  int32_t from, to;
  int64_t gain;
};

// A region on one thread: the pass's owner array, where the batch holds
// what it claims until it ends.
struct OwnedRegion {
  std::vector<int32_t>& owner;
  int32_t id;
  std::vector<int64_t> touched;  // claimed, released by release()

  bool mine(int64_t u) const { return owner[u] == id; }
  // u is in the region afterwards (claimed now or before)
  bool join(int64_t u) {
    if (owner[u] == id) return true;
    if (owner[u] != kFree) return false;
    owner[u] = id;
    touched.push_back(u);
    return true;
  }
  void release() {
    for (const int64_t u : touched)
      if (owner[u] == id) owner[u] = kFree;
  }
};

// A region of a round: the worker's own marks (stamp = the batch), so
// regions of one round may overlap; a node moved in an earlier round of
// the pass is taken.
struct StampedRegion {
  std::vector<int32_t>& mark;
  const std::vector<int32_t>& owner;
  int32_t stamp;

  bool mine(int64_t u) const { return mark[u] == stamp; }
  bool join(int64_t u) {
    if (mark[u] == stamp) return true;
    if (owner[u] == kMoved) return false;
    mark[u] = stamp;
    return true;
  }
};

// grow one localized region from its seeds against the delta
// (LocalizedFMRefiner::run_batch): its tentative moves into `moves`, and
// the length of their best prefix returned
template <class Region>
size_t search(const Ctx& c, Delta& d, Region& region,
              const std::vector<int64_t>& seeds, const Params& p, Rng& rng,
              int64_t* scratch, std::vector<Move>& moves) {
  d.clear();
  moves.clear();
  MoveQueue pq;
  auto push = [&](int64_t u) {
    auto [g, t] = best_move(d, u, rng, scratch);
    if (t >= 0) pq.push({g, rng.tie(), u, t});
  };
  for (int64_t s : seeds) {
    region.join(s);
    push(s);
  }
  if (pq.empty()) return 0;

  int64_t cur = 0, best = 0;
  size_t best_len = 0;
  int64_t fruitless = 0;
  int64_t steps = 0;
  double mean = 0.0, m2 = 0.0;
  const size_t max_moves = 4096;  // region safety cap

  while (!pq.empty() && moves.size() < max_moves) {
    auto [g, tie, u, t] = pq.pop();
    if (!region.mine(u)) continue;
    // stale check: gains shift as the region moves.  Re-queue only on a
    // GAIN change — the target may legitimately differ on ties (random
    // tie-break per query), and re-queuing on target alone could cycle
    auto [g2, t2] = best_move(d, u, rng, scratch);
    if (t2 < 0) continue;
    if (g2 != g) {
      pq.push({g2, rng.tie(), u, t2});
      continue;
    }
    t = t2;
    const int32_t b = d.block(u);
    d.move(u, b, t);
    moves.push_back({u, b, t, g2});
    cur += g2;
    if (cur > best) {
      best = cur;
      best_len = moves.size();
    }
    // expand: adjacent free nodes join the region
    for (int64_t e = c.xadj[u]; e < c.xadj[u + 1]; ++e) {
      const int32_t v = c.adjncy[e];
      if (region.join(v)) push(v);
    }
    // stopping policies (stopping_policies.h:16)
    if (p.use_adaptive) {
      ++steps;
      const double dlt = (double)g - mean;
      mean += dlt / (double)steps;
      m2 += dlt * ((double)g - mean);
      if (steps >= 2) {
        const double variance = m2 / (double)(steps - 1);
        if (mean < 0 &&
            (double)steps * mean * mean > p.alpha * variance + 10.0)
          break;
      }
    } else {
      fruitless = (g > 0) ? 0 : fruitless + 1;
      if (fruitless >= p.num_fruitless) break;
    }
  }
  return best_len;
}

// one thread: batch after batch, each committing its best prefix at once
int64_t refine_sequential(Ctx& c, const Params& p,
                          const std::vector<int64_t>& border,
                          std::vector<int32_t>& owner, Rng& rng,
                          Counters& cnt) {
  Delta d(c);
  std::vector<int64_t> scratch(c.k), seeds;
  std::vector<Move> moves;
  int64_t gain = 0;
  int32_t id = 0;
  size_t head = 0;
  for (;;) {
    seeds.clear();
    while ((int64_t)seeds.size() < p.num_seeds && head < border.size()) {
      const int64_t u = border[head++];
      if (owner[u] == kFree) seeds.push_back(u);
    }
    if (seeds.empty()) return gain;
    ++cnt.batches;
    OwnedRegion region{owner, ++id, {}};
    const size_t best_len =
        search(c, d, region, seeds, p, rng, scratch.data(), moves);
    // the delta is exact: the prefix fits and its gains are the cut's
    for (size_t i = 0; i < best_len; ++i) {
      const Move& m = moves[i];
      apply_move(c, m.u, m.from, m.to);
      owner[m.u] = kMoved;
      gain += m.gain;
    }
    cnt.committed += (int64_t)best_len;
    region.release();
  }
}

struct Batch {
  std::vector<int64_t> seeds;
  std::vector<Move> moves;
  size_t best_len = 0;
};

// commit one batch of a round to the global state (one thread, in batch
// order): the prefix up to a node an earlier batch moved or a move the
// cap refuses, with exact gains, cut back to its best sub-prefix
int64_t commit_batch(Ctx& c, std::vector<int32_t>& owner, const Batch& b,
                     Counters& cnt) {
  int64_t run = 0, best = 0;
  size_t keep = 0, i = 0;
  for (; i < b.best_len; ++i) {
    const Move& m = b.moves[i];
    if (owner[m.u] == kMoved || c.part[m.u] != m.from) break;
    if (!fits(c, m.u, m.to)) {
      ++cnt.cap_refusals;
      break;
    }
    run += c.conn_at(m.u, m.to) - c.conn_at(m.u, m.from);
    apply_move(c, m.u, m.from, m.to);
    if (run > best) {
      best = run;
      keep = i + 1;
    }
    cnt.estimated += m.gain;
  }
  for (size_t j = i; j-- > keep;)
    apply_move(c, b.moves[j].u, b.moves[j].to, b.moves[j].from);
  for (size_t j = 0; j < keep; ++j) owner[b.moves[j].u] = kMoved;
  cnt.committed += (int64_t)keep;
  cnt.undone += (int64_t)(i - keep);
  return best;
}

// T threads: the pass in rounds (the header)
int64_t refine_rounds(Ctx& c, const Params& p,
                      const std::vector<int64_t>& border,
                      std::vector<int32_t>& owner, int64_t T, uint64_t seed,
                      int64_t pass, Counters& cnt) {
  std::vector<Batch> round(kRoundBatches);
  size_t size = 0, head = 0;
  uint64_t first = 0;  // the pass's index of round[0]
  std::atomic<size_t> next{0};
  int64_t gain = 0;

  // the next round's batches: seeds in border order, skipping moved ones
  auto form = [&] {
    first += size;
    size = 0;
    while (size < round.size() && head < border.size()) {
      Batch& b = round[size];
      b.seeds.clear();
      while ((int64_t)b.seeds.size() < p.num_seeds && head < border.size()) {
        const int64_t u = border[head++];
        if (owner[u] != kMoved) b.seeds.push_back(u);
      }
      if (!b.seeds.empty()) ++size;
    }
    next.store(0, kRelaxed);
  };
  auto commit = [&]() noexcept {
    for (size_t i = 0; i < size; ++i)
      gain += commit_batch(c, owner, round[i], cnt);
    cnt.batches += (int64_t)size;
    form();
  };
  form();
  std::barrier<decltype(commit)> sync((std::ptrdiff_t)T, commit);

  auto worker = [&] {
    Delta d(c);
    std::vector<int32_t> mark(c.n, 0);
    std::vector<int64_t> scratch(c.k);
    int32_t stamp = 0;
    // `size` changes only in `commit`, which every worker waits for
    while (size > 0) {
      for (size_t i; (i = next.fetch_add(1, kRelaxed)) < size;) {
        Batch& b = round[i];
        StampedRegion region{mark, owner, ++stamp};
        // the batch's own stream: the same whichever worker runs it
        Rng rng(seed ^ (0x9E3779B97F4A7C15ULL * (uint64_t)(pass + 1)) ^
                (0xD1B54A32D192ED03ULL * (first + i + 1)));
        b.best_len =
            search(c, d, region, b.seeds, p, rng, scratch.data(), b.moves);
      }
      sync.arrive_and_wait();
    }
  };
  std::vector<std::thread> pool;
  pool.reserve(T);
  for (int64_t t = 0; t < T; ++t) pool.emplace_back(worker);
  for (auto& th : pool) th.join();
  return gain;
}

// the dense engine's passes; returns cut_in - cut_out
int64_t refine_dense(Ctx& c, const Params& p, int64_t num_iterations,
                     int64_t T, uint64_t seed, Counters& cnt,
                     int64_t& passes) {
  Rng rng(seed);
  std::vector<int32_t> owner(c.n);
  std::vector<int64_t> border;
  int64_t total = 0;
  int64_t first_pass_gain = 0;
  for (int64_t pass = 0; pass < std::max<int64_t>(1, num_iterations);
       ++pass) {
    // border nodes: nonzero external connection
    border.clear();
    for (int64_t u = 0; u < c.n; ++u) {
      const int64_t own = c.conn_at(u, c.part[u]);
      int64_t deg_w = 0;
      for (int64_t b = 0; b < c.k; ++b) deg_w += c.conn_at(u, b);
      if (deg_w > own) border.push_back(u);
    }
    if (border.empty()) break;
    for (int64_t i = (int64_t)border.size() - 1; i > 0; --i)
      std::swap(border[i], border[(int64_t)(rng.next() % (uint64_t)(i + 1))]);

    std::fill(owner.begin(), owner.end(), kFree);
    const int64_t pg =
        T == 1 ? refine_sequential(c, p, border, owner, rng, cnt)
               : refine_rounds(c, p, border, owner, T, seed, pass, cnt);
    ++passes;
    total += pg;
    if (pg <= 0) break;
    // improvement abortion (initial_fm_refiner improvement_abortion
    // lineage): later passes chase diminishing returns at full pass cost
    if (pass == 0)
      first_pass_gain = pg;
    else if (pg * 20 < first_pass_gain)
      break;
  }
  return total;
}

// ---------------------------------------------------------------------------
// Sparse compact-hashing connection table + FM path (large k).
//
// The dense (n, k) table above is O(n*k) memory — impossible at the
// reference's large-k operating point (README.MD:17 rides
// gains/compact_hashing_gain_cache.h:34 there).  This path stores, per
// node, a power-of-two open-addressing table of (block, weight) entries
// sized 2*ceil2(min(deg, k)) — distinct adjacent blocks never exceed
// deg, the 2x headroom absorbs tombstones, and a row is rebuilt exactly
// from the adjacency when probing saturates.  Entries pack
// (block + 1) << 48 | weight, so a weight update is one fetch_add and
// an insert is one CAS.  Total memory O(sum 2*ceil2(deg)) = O(m).
// ---------------------------------------------------------------------------

namespace sparse_fm {

constexpr int64_t kTagShift = 48;
constexpr int64_t kWeightMask = ((int64_t)1 << kTagShift) - 1;

inline int64_t pack(int32_t block, int64_t w) {
  return ((int64_t)(block + 1) << kTagShift) | w;
}
// unsigned shift: block+1 can reach bit 63's neighborhood at large k
// and an arithmetic shift would sign-extend into a wrong (negative) tag
inline int32_t tag_of(int64_t e) {
  return (int32_t)((uint64_t)e >> kTagShift) - 1;
}
inline int64_t weight_of(int64_t e) { return e & kWeightMask; }

inline uint64_t hash_block(int32_t b) {
  uint64_t z = (uint64_t)b * 0x9E3779B97F4A7C15ULL;
  return z ^ (z >> 29);
}

struct SparseCtx {
  int64_t n, k;
  const int64_t* xadj;
  const int32_t* adjncy;
  const int64_t* node_w;
  const int64_t* edge_w;
  const int64_t* max_bw;
  int32_t* part;
  std::vector<int64_t> off;      // slot ranges (off[u]..off[u+1]), pow2 caps
  std::vector<int64_t> entries;  // packed atomic slots
  std::vector<int64_t> wdeg;     // weighted degree (border test)
  std::vector<int64_t> bw;

  int64_t cap(int64_t u) const { return off[u + 1] - off[u]; }
  int32_t part_at(int64_t u) const {
    return std::atomic_ref(const_cast<int32_t&>(part[u])).load(kRelaxed);
  }
  int64_t bw_at(int64_t b) const {
    return std::atomic_ref(const_cast<int64_t&>(bw[b])).load(kRelaxed);
  }

  int64_t load(int64_t u, int32_t b) const {
    const int64_t base = off[u], c = cap(u);
    if (c == 0) return 0;
    const int64_t mask = c - 1;
    for (int64_t i = 0; i < c; ++i) {
      const int64_t s = base + ((hash_block(b) + (uint64_t)i) & mask);
      const int64_t e =
          std::atomic_ref(const_cast<int64_t&>(entries[s])).load(kRelaxed);
      if (e == 0) return 0;
      if (tag_of(e) == b) return weight_of(e);
    }
    return 0;  // saturated row without the tag: weight is 0
  }

  // add w (may be negative) to (u, b); returns false when the row needs
  // a rebuild (all slots probed, tag absent — only possible for w > 0)
  bool add(int64_t u, int32_t b, int64_t w) {
    const int64_t base = off[u], c = cap(u);
    if (c == 0) return true;
    const int64_t mask = c - 1;
    for (int64_t i = 0; i < c; ++i) {
      const int64_t s = base + ((hash_block(b) + (uint64_t)i) & mask);
      std::atomic_ref<int64_t> ref(entries[s]);
      int64_t e = ref.load(kRelaxed);
      while (e == 0) {
        // claim the empty slot (tag + weight in one CAS); a zero-weight
        // claim is fine — it acts as a pre-claimed tombstone
        if (ref.compare_exchange_weak(e, pack(b, w), kRelaxed)) return true;
      }
      if (tag_of(e) == b) {
        ref.fetch_add(w, kRelaxed);  // weight field only; tag untouched
        return true;
      }
    }
    return false;
  }

  // exact rebuild of u's row from the adjacency + current partition
  // (clears tombstones; single-threaded callers only)
  void rebuild_row(int64_t u) {
    std::fill(entries.begin() + off[u], entries.begin() + off[u + 1], 0);
    for (int64_t e = xadj[u]; e < xadj[u + 1]; ++e)
      (void)add(u, part_at(adjncy[e]), edge_w[e]);
  }

  template <class Fn>
  void for_entries(int64_t u, Fn&& fn) const {
    for (int64_t s = off[u]; s < off[u + 1]; ++s) {
      const int64_t e =
          std::atomic_ref(const_cast<int64_t&>(entries[s])).load(kRelaxed);
      if (e != 0 && weight_of(e) > 0) fn(tag_of(e), weight_of(e));
    }
  }
};

inline int64_t ceil2_i64(int64_t x) {
  int64_t p = 1;
  while (p < x) p <<= 1;
  return p;
}

void build_sparse(SparseCtx& c) {
  c.off.assign(c.n + 1, 0);
  for (int64_t u = 0; u < c.n; ++u) {
    const int64_t deg = c.xadj[u + 1] - c.xadj[u];
    const int64_t distinct = std::min<int64_t>(deg, c.k);
    c.off[u + 1] =
        c.off[u] + (distinct == 0 ? 0 : 2 * ceil2_i64(distinct));
  }
  c.entries.assign(c.off[c.n], 0);
  c.wdeg.assign(c.n, 0);
  c.bw.assign(c.k, 0);
  for (int64_t u = 0; u < c.n; ++u) {
    c.bw[c.part[u]] += c.node_w[u];
    for (int64_t e = c.xadj[u]; e < c.xadj[u + 1]; ++e) {
      c.wdeg[u] += c.edge_w[e];
      (void)c.add(u, c.part[c.adjncy[e]], c.edge_w[e]);
    }
  }
}

// Delta overlay: private copies of touched rows (cap-sized, same
// probing), tentative blocks, block-weight deltas.
struct SparseDelta {
  SparseCtx* c;
  std::unordered_map<int64_t, int64_t> slot;  // u -> arena offset
  std::vector<int64_t> arena;                 // cap(u) packed entries per row
  std::unordered_map<int64_t, int32_t> blocks;
  std::vector<int64_t> bw_delta;

  explicit SparseDelta(SparseCtx& ctx) : c(&ctx), bw_delta(ctx.k, 0) {
    slot.reserve(1 << 12);
  }
  void clear() {
    slot.clear();
    arena.clear();
    blocks.clear();
    std::fill(bw_delta.begin(), bw_delta.end(), 0);
  }
  int64_t* row(int64_t u) {
    auto [it, fresh] = slot.try_emplace(u, (int64_t)arena.size());
    if (fresh) {
      const size_t base = arena.size();
      arena.resize(base + c->cap(u));
      for (int64_t s = 0; s < c->cap(u); ++s)
        arena[base + s] = std::atomic_ref(c->entries[c->off[u] + s])
                              .load(kRelaxed);
    }
    return arena.data() + it->second;
  }
  int32_t block(int64_t u) const {
    auto it = blocks.find(u);
    return it == blocks.end() ? c->part_at(u) : it->second;
  }
  int64_t weight(int64_t b) const { return c->bw_at(b) + bw_delta[b]; }

  // private-row add with exact rebuild on saturation
  void row_add(int64_t u, int32_t b, int64_t w) {
    int64_t* r = row(u);
    const int64_t cp = c->cap(u);
    if (cp == 0) return;
    const int64_t mask = cp - 1;
    for (int64_t i = 0; i < cp; ++i) {
      int64_t& e = r[(hash_block(b) + (uint64_t)i) & mask];
      if (e == 0) {
        e = pack(b, w);
        return;
      }
      if (tag_of(e) == b) {
        e += w;
        return;
      }
    }
    // saturated: rebuild the private row exactly from the adjacency
    // under the delta's tentative blocks (rare; O(deg * probe))
    std::fill(r, r + cp, 0);
    for (int64_t e2 = c->xadj[u]; e2 < c->xadj[u + 1]; ++e2) {
      const int32_t bb = block(c->adjncy[e2]);
      const int64_t mask2 = cp - 1;
      for (int64_t i = 0; i < cp; ++i) {
        int64_t& e = r[(hash_block(bb) + (uint64_t)i) & mask2];
        if (e == 0) {
          e = pack(bb, c->edge_w[e2]);
          break;
        }
        if (tag_of(e) == bb) {
          e += c->edge_w[e2];
          break;
        }
      }
    }
  }

  int64_t row_load(int64_t u, int32_t b) const {
    auto it = slot.find(u);
    if (it == slot.end()) return c->load(u, b);
    const int64_t* r = arena.data() + it->second;
    const int64_t cp = c->cap(u);
    if (cp == 0) return 0;
    const int64_t mask = cp - 1;
    for (int64_t i = 0; i < cp; ++i) {
      const int64_t e = r[(hash_block(b) + (uint64_t)i) & mask];
      if (e == 0) return 0;
      if (tag_of(e) == b) return weight_of(e);
    }
    return 0;
  }

  void move(int64_t u, int32_t from, int32_t to) {
    row(u);
    blocks[u] = to;
    bw_delta[from] -= c->node_w[u];
    bw_delta[to] += c->node_w[u];
    for (int64_t e = c->xadj[u]; e < c->xadj[u + 1]; ++e) {
      const int32_t v = c->adjncy[e];
      row_add(v, from, -c->edge_w[e]);
      row_add(v, to, c->edge_w[e]);
    }
  }

  // best feasible move among u's ADJACENT blocks (the compact-hashing
  // cache iterates its entries — non-adjacent targets are the
  // balancers' job, as in the reference's large-k configuration)
  std::pair<int64_t, int32_t> best_move(int64_t u, Rng& rng) const {
    const int32_t b = block(u);
    const int64_t own = row_load(u, b);
    int64_t best_gain = INT64_MIN;
    int32_t best_t = -1;
    uint32_t best_tie = 0;
    auto consider = [&](int32_t t, int64_t w) {
      if (t == b) return;
      if (weight(t) + c->node_w[u] > c->max_bw[t]) return;
      const int64_t g = w - own;
      if (g > best_gain) {
        best_gain = g;
        best_t = t;
        best_tie = rng.tie();
      } else if (g == best_gain && best_t >= 0) {
        const uint32_t tb = rng.tie();
        if (tb > best_tie) {
          best_t = t;
          best_tie = tb;
        }
      }
    };
    auto it = slot.find(u);
    if (it == slot.end()) {
      c->for_entries(u, consider);
    } else {
      const int64_t* r = arena.data() + it->second;
      for (int64_t s = 0; s < c->cap(u); ++s)
        if (r[s] != 0 && weight_of(r[s]) > 0)
          consider(tag_of(r[s]), weight_of(r[s]));
    }
    return {best_gain, best_t};
  }
};

// commit with cap re-check; a saturated neighbor row is rebuilt exactly
// (single-threaded path — the sparse configuration runs T=1, see
// kmp_fm_refine)
bool commit_move(SparseCtx& c, int64_t u, int32_t from, int32_t to) {
  const int64_t w = c.node_w[u];
  std::atomic_ref bw_to(c.bw[to]);
  if (bw_to.fetch_add(w, kRelaxed) + w > c.max_bw[to]) {
    bw_to.fetch_sub(w, kRelaxed);
    return false;
  }
  std::atomic_ref(c.bw[from]).fetch_sub(w, kRelaxed);
  std::atomic_ref(c.part[u]).store(to, kRelaxed);
  for (int64_t e = c.xadj[u]; e < c.xadj[u + 1]; ++e) {
    const int32_t v = c.adjncy[e];
    (void)c.add(v, from, -c.edge_w[e]);
    if (!c.add(v, to, c.edge_w[e])) c.rebuild_row(v);
  }
  return true;
}

int64_t run_batch(SparseCtx& c, SparseDelta& d,
                  std::atomic<int32_t>* owner, int32_t my_id,
                  const std::vector<int64_t>& seeds, double alpha,
                  int64_t num_fruitless, int use_adaptive, Rng& rng) {
  d.clear();
  using Entry = std::tuple<int64_t, uint32_t, int64_t, int32_t>;
  std::priority_queue<Entry> pq;
  std::vector<int64_t> touched;

  auto claim = [&](int64_t u) {
    int32_t expect = kFree;
    return owner[u].compare_exchange_strong(expect, my_id, kRelaxed);
  };
  auto push = [&](int64_t u) {
    auto [g, t] = d.best_move(u, rng);
    if (t >= 0) pq.push({g, rng.tie(), u, t});
  };
  for (int64_t s : seeds) {
    touched.push_back(s);
    push(s);
  }
  if (pq.empty()) {
    for (int64_t u : touched) owner[u].store(kFree, kRelaxed);
    return 0;
  }

  std::vector<Move> moves;
  int64_t cur = 0, best = 0;
  size_t best_len = 0;
  int64_t fruitless = 0;
  int64_t steps = 0;
  double mean = 0.0, m2 = 0.0;
  const size_t max_moves = 4096;

  while (!pq.empty() && moves.size() < max_moves) {
    auto [g, tie, u, t] = pq.top();
    pq.pop();
    if (owner[u].load(kRelaxed) != my_id) continue;
    auto [g2, t2] = d.best_move(u, rng);
    if (t2 < 0) continue;
    if (g2 != g) {
      pq.push({g2, rng.tie(), u, t2});
      continue;
    }
    t = t2;
    const int32_t b = d.block(u);
    d.move(u, b, t);
    moves.push_back({u, b, t, g2});
    cur += g2;
    if (cur > best) {
      best = cur;
      best_len = moves.size();
    }
    for (int64_t e = c.xadj[u]; e < c.xadj[u + 1]; ++e) {
      const int32_t v = c.adjncy[e];
      const int32_t o = owner[v].load(kRelaxed);
      if (o == kFree) {
        if (claim(v)) {
          touched.push_back(v);
          push(v);
        }
      } else if (o == my_id) {
        push(v);
      }
    }
    if (use_adaptive) {
      ++steps;
      const double dlt = (double)g - mean;
      mean += dlt / (double)steps;
      m2 += dlt * ((double)g - mean);
      if (steps >= 2) {
        const double variance = m2 / (double)(steps - 1);
        if (mean < 0 &&
            (double)steps * mean * mean > alpha * variance + 10.0)
          break;
      }
    } else {
      fruitless = (g > 0) ? 0 : fruitless + 1;
      if (fruitless >= num_fruitless) break;
    }
  }

  int64_t committed_gain = 0;
  for (size_t i = 0; i < best_len; ++i) {
    if (!commit_move(c, moves[i].u, moves[i].from, moves[i].to)) break;
    owner[moves[i].u].store(kMoved, kRelaxed);
    committed_gain += moves[i].gain;
  }
  for (int64_t u : touched)
    if (owner[u].load(kRelaxed) == my_id) owner[u].store(kFree, kRelaxed);
  return committed_gain;
}

int64_t refine(int64_t n, const int64_t* xadj, const int32_t* adjncy,
               const int64_t* node_w, const int64_t* edge_w, int64_t k,
               const int64_t* max_bw, int32_t* part,
               int64_t num_iterations, int64_t num_seed_nodes,
               double alpha, int64_t num_fruitless_moves,
               int32_t use_adaptive, uint64_t seed, int64_t* stats) {
  // the packed tag field holds block+1 in 16 bits (max tag = k).
  // INT64_MIN is the REFUSAL sentinel — the caller must distinguish "FM
  // did not run" from "FM found no improvement" (ADVICE round 5 low #3),
  // and a small negative value would be ambiguous.
  if (k > 0xFFFF) return INT64_MIN;
  SparseCtx c{n, k, xadj, adjncy, node_w, edge_w, max_bw, part,
              {}, {}, {}, {}};
  Rng rng(seed);
  build_sparse(c);

  std::unique_ptr<std::atomic<int32_t>[]> owner(
      new std::atomic<int32_t>[n]);
  SparseDelta d(c);

  int64_t total = 0;
  int64_t first_pass_gain = 0;
  std::vector<int64_t> border;
  for (int64_t pass = 0; pass < std::max<int64_t>(1, num_iterations);
       ++pass) {
    border.clear();
    for (int64_t u = 0; u < n; ++u)
      if (c.load(u, c.part[u]) < c.wdeg[u]) border.push_back(u);
    if (border.empty()) break;
    for (int64_t i = (int64_t)border.size() - 1; i > 0; --i)
      std::swap(border[i],
                border[(int64_t)(rng.next() % (uint64_t)(i + 1))]);

    for (int64_t u = 0; u < n; ++u) owner[u].store(kFree, kRelaxed);
    const int64_t nseeds = std::max<int64_t>(1, num_seed_nodes);
    size_t head = 0;
    int64_t pass_gain = 0;
    int32_t next_batch_id = 0;

    for (;;) {
      const int32_t my_id = ++next_batch_id;
      std::vector<int64_t> seeds;
      while ((int64_t)seeds.size() < nseeds && head < border.size()) {
        const int64_t u = border[head++];
        int32_t expect = kFree;
        if (owner[u].compare_exchange_strong(expect, my_id, kRelaxed))
          seeds.push_back(u);
      }
      if (seeds.empty()) break;
      pass_gain += run_batch(c, d, owner.get(), my_id, seeds, alpha,
                             num_fruitless_moves, use_adaptive, rng);
    }

    total += pass_gain;
    if (stats) ++stats[kStatPasses];
    if (pass_gain <= 0) break;
    if (pass == 0)
      first_pass_gain = pass_gain;
    else if (pass_gain * 20 < first_pass_gain)
      break;
  }
  if (stats) {
    // one thread: the delta is exact, the estimate is the cut's change
    stats[kStatThreads] = 1;
    stats[kStatEstimatedGain] = stats[kStatExactGain] = total;
  }
  return total;
}

}  // namespace sparse_fm

}  // namespace

// test hook: force the sparse compact-hashing path at any k (the
// normal entry dispatches on table size; tests exercise both on the
// same small graph and assert both improve the cut)
extern "C" int64_t kmp_fm_refine_sparse(
    int64_t n, const int64_t* xadj, const int32_t* adjncy,
    const int64_t* node_w, const int64_t* edge_w, int64_t k,
    const int64_t* max_bw, int32_t* part, int64_t num_iterations,
    int64_t num_seed_nodes, double alpha, int64_t num_fruitless_moves,
    int32_t use_adaptive, uint64_t seed, int64_t /*num_threads*/,
    int64_t* stats) {
  if (stats) std::fill(stats, stats + kNumStats, 0);
  if (n <= 0 || k <= 1) return 0;
  return sparse_fm::refine(n, xadj, adjncy, node_w, edge_w, k, max_bw,
                           part, num_iterations, num_seed_nodes, alpha,
                           num_fruitless_moves, use_adaptive, seed, stats);
}

extern "C" int64_t kmp_fm_refine(
    int64_t n, const int64_t* xadj, const int32_t* adjncy,
    const int64_t* node_w, const int64_t* edge_w, int64_t k,
    const int64_t* max_bw, int32_t* part, int64_t num_iterations,
    int64_t num_seed_nodes, double alpha, int64_t num_fruitless_moves,
    int32_t use_adaptive, uint64_t seed, int64_t num_threads,
    int64_t* stats) {
  if (stats) std::fill(stats, stats + kNumStats, 0);
  if (n <= 0 || k <= 1) return 0;
  if (n * k > (int64_t)3e8) {
    // large k: the dense (n, k) table is unaffordable — run the sparse
    // compact-hashing path (compact_hashing_gain_cache.h:34 analog),
    // O(m) memory.  Single-threaded: its exact rebuild-on-saturation
    // is not written for concurrent writers.
    return sparse_fm::refine(n, xadj, adjncy, node_w, edge_w, k, max_bw,
                             part, num_iterations, num_seed_nodes, alpha,
                             num_fruitless_moves, use_adaptive, seed, stats);
  }
  Ctx c{n, k, xadj, adjncy, node_w, edge_w, max_bw, part, {}, {}};
  c.conn.resize(n * k);
  c.bw.resize(k);
  build_conn(c);

  const int64_t T = std::max<int64_t>(1, num_threads);
  const Params p{std::max<int64_t>(1, num_seed_nodes), alpha,
                 num_fruitless_moves, use_adaptive};
  Counters cnt;
  int64_t passes = 0;
  const int64_t total =
      refine_dense(c, p, num_iterations, T, seed, cnt, passes);
  if (stats) {
    stats[kStatThreads] = T;
    stats[kStatPasses] = passes;
    stats[kStatBatches] = cnt.batches;
    stats[kStatCommitted] = cnt.committed;
    stats[kStatCapRefusals] = cnt.cap_refusals;
    stats[kStatUndoneMoves] = cnt.undone;
    // one thread: the delta is exact, the estimate is the cut's change
    stats[kStatEstimatedGain] = T == 1 ? total : cnt.estimated;
    stats[kStatExactGain] = total;
  }
  return total;
}
