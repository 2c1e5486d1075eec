// Plain reference for the string-replay cells: a passive merge-tree
// replica that applies a totally ordered op stream, each op at its own
// (refSeq, client) perspective, in a document-ordered segment list.
//
// It follows the merge-tree rules of Fluid Framework's
// packages/dds/merge-tree (insertingWalk tie-break, markRangeRemoved,
// annotateRange, zamboni) for sequenced remote ops only: no local ops,
// no pending groups, no attribution. The benchmark keeps it apart from
// the system under test so that no change to the program can move it.
//
// Two entry points, bound by reference/string_ref.py:
//   ref_gen_lagged  the lagged-stream generator's loop (positions are
//                   drawn inside the visible length at each op's own
//                   perspective), fed with pre-drawn random columns;
//   ref_replay      replay a stream and write the final text and the
//                   per-character properties.

#include <algorithm>
#include <array>
#include <cstdint>
#include <memory>
#include <vector>

namespace {

constexpr int32_t REMOVED_NONE = INT32_MIN;
constexpr int32_t INT32_MAX_ = 2147483647;
constexpr int NKEYS = 8;
constexpr int32_t ABSENT = -1;
constexpr int OP_INSERT = 0;
constexpr int OP_REMOVE = 1;
constexpr int OP_ANNOTATE = 2;

using Props = std::array<int32_t, NKEYS>;

// Properties as runs over a segment's characters (offset, props), so
// settled neighbours merge whatever their properties: the walks stay
// O(collaboration window + document / run cap) per op.
struct Run {
  int32_t off;
  Props p;
};

struct Seg {
  std::vector<int32_t> content;
  int32_t seq = 0;
  int32_t client = -2;
  int32_t removed_seq = REMOVED_NONE;
  std::vector<int32_t> removed_clients;
  std::vector<Run> runs;
  Seg() {
    Props none;
    none.fill(ABSENT);
    runs.push_back({0, none});
  }
};

void append_runs(Seg* dst, const Seg* src, int32_t base) {
  for (const Run& r : src->runs) {
    if (!dst->runs.empty() && dst->runs.back().p == r.p) continue;
    dst->runs.push_back({base + r.off, r.p});
  }
}

enum Vis { SKIP = 0, ZERO = 1, VISIBLE = 2 };

struct Doc {
  std::vector<std::unique_ptr<Seg>> owner;
  std::vector<Seg*> segs;  // document order
  int32_t min_seq = 0;
  int32_t min_tomb = INT32_MAX_;
  size_t pack_watermark = 64;

  Seg* make() {
    owner.push_back(std::make_unique<Seg>());
    return owner.back().get();
  }

  Vis vis(const Seg* s, int32_t ref, int32_t client, int64_t* len) const {
    bool removed = s->removed_seq != REMOVED_NONE;
    *len = 0;
    if (removed && s->removed_seq <= ref) return SKIP;
    if (s->client == client || s->seq <= ref) {
      if (removed)
        for (int32_t c : s->removed_clients)
          if (c == client) return ZERO;
      *len = (int64_t)s->content.size();
      return VISIBLE;
    }
    if (removed) return SKIP;
    return ZERO;
  }

  Seg* split(Seg* s, int64_t off) {
    Seg* t = make();
    t->content.assign(s->content.begin() + off, s->content.end());
    s->content.resize(off);
    t->seq = s->seq;
    t->client = s->client;
    t->removed_seq = s->removed_seq;
    t->removed_clients = s->removed_clients;
    size_t i = 0;
    while (i < s->runs.size() && s->runs[i].off < off) i++;
    t->runs.clear();
    if (!(i < s->runs.size() && s->runs[i].off == off))
      t->runs.push_back({0, s->runs[i - 1].p});
    for (size_t k = i; k < s->runs.size(); k++)
      t->runs.push_back({(int32_t)(s->runs[k].off - off), s->runs[k].p});
    s->runs.resize(i);
    return t;
  }

  int64_t visible_length(int32_t ref, int32_t client) const {
    int64_t total = 0, len;
    for (const Seg* s : segs) {
      vis(s, ref, client, &len);
      total += len;
    }
    return total;
  }

  int insert(int64_t pos, const int32_t* items, int64_t n, int32_t ref,
             int32_t client, int32_t seq) {
    Seg* ns = make();
    ns->content.assign(items, items + n);
    ns->seq = seq;
    ns->client = client;
    int64_t remaining = pos;
    size_t at = segs.size();
    bool landed = false;
    for (size_t i = 0; i < segs.size(); i++) {
      Seg* s = segs[i];
      int64_t len;
      if (vis(s, ref, client, &len) == SKIP) continue;
      if (remaining < len) {
        if (remaining == 0) {
          at = i;
        } else {
          segs.insert(segs.begin() + i + 1, split(s, remaining));
          at = i + 1;
        }
        landed = true;
        break;
      }
      if (remaining == 0 && len == 0) {
        // A concurrent insert the op did not see: the later sequence
        // number goes first.
        if (seq > s->seq) {
          at = i;
          landed = true;
          break;
        }
        continue;
      }
      remaining -= len;
    }
    if (!landed) {
      if (remaining > 0) return -1;
      at = segs.size();
    }
    segs.insert(segs.begin() + at, ns);
    return 0;
  }

  void boundary(int64_t pos, int32_t ref, int32_t client) {
    int64_t remaining = pos;
    for (size_t i = 0; i < segs.size(); i++) {
      Seg* s = segs[i];
      int64_t len;
      if (vis(s, ref, client, &len) == SKIP) continue;
      if (remaining < len) {
        if (remaining > 0) segs.insert(segs.begin() + i + 1, split(s, remaining));
        return;
      }
      remaining -= len;
    }
  }

  int remove(int64_t start, int64_t end, int32_t ref, int32_t client,
             int32_t seq) {
    if (!(end > start && start >= 0)) return -1;
    boundary(start, ref, client);
    boundary(end, ref, client);
    int64_t pos = 0;
    for (Seg* s : segs) {
      if (pos >= end) break;
      int64_t len;
      if (vis(s, ref, client, &len) == SKIP || len == 0) continue;
      if (pos >= start) {
        if (s->removed_seq != REMOVED_NONE) {
          s->removed_clients.push_back(client);
        } else {
          s->removed_seq = seq;
          s->removed_clients.assign(1, client);
          if (seq < min_tomb) min_tomb = seq;
        }
      }
      pos += len;
    }
    return 0;
  }

  int annotate(int64_t start, int64_t end, int32_t key, int32_t val,
               int32_t ref, int32_t client) {
    if (!(end > start && start >= 0) || key < 0 || key >= NKEYS) return -1;
    boundary(start, ref, client);
    boundary(end, ref, client);
    int64_t pos = 0;
    for (Seg* s : segs) {
      if (pos >= end) break;
      int64_t len;
      if (vis(s, ref, client, &len) == SKIP || len == 0) continue;
      if (pos >= start) {
        std::vector<Run> merged;
        for (Run r : s->runs) {
          r.p[key] = val;
          if (merged.empty() || !(merged.back().p == r.p)) merged.push_back(r);
        }
        s->runs.swap(merged);
      }
      pos += len;
    }
    return 0;
  }

  // Tombstones at or below the MSN leave the list; settled neighbours
  // merge (runs capped so that later splits stay cheap).
  void update_min_seq(int32_t msn) {
    min_seq = msn;
    if (min_tomb <= msn) {
      std::vector<Seg*> kept;
      kept.reserve(segs.size());
      min_tomb = INT32_MAX_;
      for (Seg* s : segs) {
        if (s->removed_seq != REMOVED_NONE) {
          if (s->removed_seq <= msn) continue;
          if (s->removed_seq < min_tomb) min_tomb = s->removed_seq;
        }
        kept.push_back(s);
      }
      segs.swap(kept);
    }
    if (segs.size() >= pack_watermark * 2) {
      pack();
      pack_watermark = std::max<size_t>(64, segs.size());
    }
  }

  void pack() {
    std::vector<Seg*> kept;
    kept.reserve(segs.size());
    Seg* run = nullptr;
    for (Seg* s : segs) {
      bool settled = s->seq <= min_seq && s->removed_seq == REMOVED_NONE;
      if (settled && run &&
          run->content.size() + s->content.size() <= 4096) {
        int32_t base = (int32_t)run->content.size();
        run->content.insert(run->content.end(), s->content.begin(),
                            s->content.end());
        append_runs(run, s, base);
        continue;
      }
      kept.push_back(s);
      run = settled ? s : nullptr;
    }
    segs.swap(kept);
  }

  void load(const int32_t* items, int64_t n) {
    if (n <= 0) return;
    Seg* s = make();
    s->content.assign(items, items + n);
    segs.push_back(s);
  }
};

}  // namespace

extern "C" {

// The lagged generator's loop (see traffic.py, kind "lagged_log").
// Writes op_type/pos1/pos2/ref_seq/buf_start/ins_len per op; returns
// the arena length used, or -(i+1) when op i came out invalid.
int64_t ref_gen_lagged(int64_t n_ops, int32_t n_clients, int32_t window,
                       double lag_zero_frac, double t_ins, double t_rem,
                       const double* type_u, const double* pos_u,
                       const double* lag_u, const int64_t* lag_draw,
                       const int64_t* len_draw, const int64_t* range_draw,
                       const int32_t* arena, int32_t initial_len,
                       int32_t* op_type, int32_t* pos1, int32_t* pos2,
                       int32_t* ref_seq, int32_t* buf_start,
                       int32_t* ins_len) {
  Doc d;
  d.load(arena, initial_len);
  std::vector<int32_t> last_ref(n_clients + 1, 0);
  int64_t arena_off = initial_len;
  for (int64_t i = 0; i < n_ops; i++) {
    int32_t seq = (int32_t)(i + 1);
    int32_t c = (int32_t)(i % n_clients) + 1;
    int32_t msn = std::max(seq - window, 0);
    int32_t r = lag_u[i] < lag_zero_frac ? seq - 1
                                         : seq - 1 - (int32_t)lag_draw[i];
    if (r < msn) r = msn;
    if (r < last_ref[c]) r = last_ref[c];
    last_ref[c] = r;
    ref_seq[i] = r;
    int64_t L = d.visible_length(r, c);
    double u = type_u[i];
    int rc = 0;
    pos2[i] = 0;
    buf_start[i] = 0;
    ins_len[i] = 0;
    if (u < t_ins || L == 0) {
      int64_t n = len_draw[i];
      int64_t p = (int64_t)(pos_u[i] * (double)(L + 1));
      op_type[i] = OP_INSERT;
      pos1[i] = (int32_t)p;
      buf_start[i] = (int32_t)arena_off;
      ins_len[i] = (int32_t)n;
      rc = d.insert(p, arena + arena_off, n, r, c, seq);
      arena_off += n;
    } else {
      int64_t start = (int64_t)(pos_u[i] * (double)L);
      int64_t end = std::min(L, start + range_draw[i]);
      pos1[i] = (int32_t)start;
      pos2[i] = (int32_t)end;
      if (u < t_rem) {
        op_type[i] = OP_REMOVE;
        rc = d.remove(start, end, r, c, seq);
      } else {
        // Annotations never change visible lengths: the view skips them.
        op_type[i] = OP_ANNOTATE;
      }
    }
    if (rc != 0) return -(i + 1);
    if ((i & 255) == 255) {
      d.update_min_seq(msn);
      d.pack();
    }
  }
  return arena_off;
}

// Replay a stream from its columns. Returns the final visible length
// (writing up to `cap` characters and their NKEYS properties each), or
// -(i+1) when op i could not be applied. `control` 1 is the comparison's
// control: annotations resolve their range at the head (refSeq = seq -
// 1) instead of at the op's own refSeq.
int64_t ref_replay(int64_t n, const int32_t* op_type, const int32_t* pos1,
                   const int32_t* pos2, const int32_t* seq,
                   const int32_t* ref_seq, const int32_t* client,
                   const int32_t* buf_start, const int32_t* ins_len,
                   const int32_t* prop_key, const int32_t* prop_val,
                   const int32_t* min_seq, const int32_t* text,
                   int32_t initial_len, int32_t control,
                   int32_t* out_text, int32_t* out_props, int64_t cap) {
  Doc d;
  d.load(text, initial_len);
  for (int64_t i = 0; i < n; i++) {
    int rc;
    switch (op_type[i]) {
      case OP_INSERT:
        rc = d.insert(pos1[i], text + buf_start[i], ins_len[i], ref_seq[i],
                      client[i], seq[i]);
        break;
      case OP_REMOVE:
        rc = d.remove(pos1[i], pos2[i], ref_seq[i], client[i], seq[i]);
        break;
      case OP_ANNOTATE:
        rc = d.annotate(pos1[i], pos2[i], prop_key[i], prop_val[i],
                        control ? seq[i] - 1 : ref_seq[i], client[i]);
        break;
      default:
        rc = -1;
    }
    if (rc != 0) return -(i + 1);
    if ((i & 255) == 255) d.update_min_seq(min_seq[i]);
  }
  int64_t k = 0;
  for (const Seg* s : d.segs) {
    if (s->removed_seq != REMOVED_NONE) continue;
    size_t r = 0;
    for (size_t j = 0; j < s->content.size(); j++) {
      while (r + 1 < s->runs.size() && s->runs[r + 1].off <= (int32_t)j) r++;
      if (k < cap) {
        out_text[k] = s->content[j];
        for (int q = 0; q < NKEYS; q++) out_props[k * NKEYS + q] = s->runs[r].p[q];
      }
      k++;
    }
  }
  return k;
}

}  // extern "C"
