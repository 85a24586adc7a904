// Native schedule-compilation engine.
//
// C++ twin of parallel/schedules.py: per-device action-order generation for
// GPipe / 1F1B / Interleaved-1F1B / ZB-H1, ASAP tick scheduling (a forward
// and a full backward may share a device's tick) with one-hop ppermute latency,
// greedy buffer-slot allocation from activation lifetimes, and emission of
// the executor tick table [T, D, 17] (column layout
// documented in schedules.py). Semantics must match the Python implementation
// exactly — tests assert bit-identical tables — so the Python path remains
// the executable specification and this library is the fast production path
// (large D*V*M schedule compilation is O(actions * ticks) host work).
//
// This fills the native-runtime slot that the reference occupies with
// vendored C++ (c10d/gloo transport + ATen, SURVEY.md §2.3): here the
// transport/compute layers are XLA's native code, and the first-party native
// layer is this schedule engine plus the Pallas kernels.
//
// Build: make -C csrc   (produces libschedule_engine.so; loaded via ctypes)

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <map>
#include <queue>
#include <string>
#include <vector>

namespace {

enum Op { OP_F = 0, OP_B = 1, OP_W = 2 };

struct Action {
  int stage;
  int op;  // Op
  int mb;
  bool operator<(const Action& o) const {
    if (stage != o.stage) return stage < o.stage;
    if (op != o.op) return op < o.op;
    return mb < o.mb;
  }
};

using Order = std::vector<Action>;

int fail(char* err, int errlen, const std::string& msg) {
  std::strncpy(err, msg.c_str(), errlen - 1);
  err[errlen - 1] = '\0';
  return 1;
}

std::vector<Order> gpipe_order(int D, int M) {
  std::vector<Order> orders(D);
  for (int d = 0; d < D; ++d) {
    for (int m = 0; m < M; ++m) orders[d].push_back({d, OP_F, m});
    for (int m = 0; m < M; ++m) orders[d].push_back({d, OP_B, m});
  }
  return orders;
}

// Warm-up 2 * (D-1-d): a hop costs one tick each way, so that many forwards
// are in flight before B(d, 0) can run, and every steady-state F, B pair
// shares a tick (schedules.one_f_one_b_order says why).
std::vector<Order> one_f_one_b_order(int D, int M) {
  std::vector<Order> orders(D);
  for (int d = 0; d < D; ++d) {
    int warmup = std::min(M, 2 * (D - 1 - d));
    int nf = 0, nb = 0;
    for (; nf < warmup; ++nf) orders[d].push_back({d, OP_F, nf});
    while (nf < M) {
      orders[d].push_back({d, OP_F, nf++});
      orders[d].push_back({d, OP_B, nb++});
    }
    for (; nb < M; ++nb) orders[d].push_back({d, OP_B, nb});
  }
  return orders;
}

std::vector<Order> interleaved_order(int D, int V, int M) {
  if (V == 1) return one_f_one_b_order(D, M);
  int num_rounds = std::max(1, M / D);
  int mbpr = M / num_rounds;  // microbatches per round
  int total = M * V;
  std::vector<Order> orders(D);
  auto fwd_vm = [&](int i, int* v, int* m) {
    *v = (i / mbpr) % V;
    *m = (i / (mbpr * V)) * mbpr + (i % mbpr);
  };
  auto bwd_vm = [&](int j, int* v, int* m) {
    *v = V - 1 - ((j / mbpr) % V);
    *m = (j / (mbpr * V)) * mbpr + (j % mbpr);
  };
  for (int d = 0; d < D; ++d) {
    int warmup = std::min(total, (V - 1) * mbpr + 2 * (D - 1 - d));
    int nf = 0, nb = 0, v, m;
    for (; nf < warmup; ++nf) {
      fwd_vm(nf, &v, &m);
      orders[d].push_back({v * D + d, OP_F, m});
    }
    while (nf < total) {
      fwd_vm(nf++, &v, &m);
      orders[d].push_back({v * D + d, OP_F, m});
      bwd_vm(nb++, &v, &m);
      orders[d].push_back({v * D + d, OP_B, m});
    }
    while (nb < total) {
      bwd_vm(nb++, &v, &m);
      orders[d].push_back({v * D + d, OP_B, m});
    }
  }
  return orders;
}

// BFS breadth-first pipeline (arXiv:2211.05953): GPipe generalized to V
// virtual stages with wrap placement — all forwards in (v, m) lexicographic
// order, then all backwards with v reversed. Mirrors schedules.bfs_order.
std::vector<Order> bfs_order(int D, int V, int M) {
  std::vector<Order> orders(D);
  for (int d = 0; d < D; ++d) {
    for (int v = 0; v < V; ++v)
      for (int m = 0; m < M; ++m) orders[d].push_back({v * D + d, OP_F, m});
    for (int v = V - 1; v >= 0; --v)
      for (int m = 0; m < M; ++m) orders[d].push_back({v * D + d, OP_B, m});
  }
  return orders;
}

// ZB-H1 (arXiv:2401.10241): dgrad/wgrad split backward; stage 0 has no B
// (nothing upstream to send a cotangent to) — its W does the full
// parameter+embedding backward. Orders come from the same greedy priority
// simulation as schedules._zb_greedy_order (B > F > W so wgrad sinks into
// bubble ticks; in-flight forward cap 2D - d, the memory price of hitting
// the paper's 3M + D - 1 makespan with the stage-0 dgrad elided). Must stay
// bit-identical to the Python generator.
std::vector<Order> zb_h1_order(int D, int M) {
  const int S = D;
  // done[s][op][m] = completion tick, or -1
  std::vector<std::vector<std::vector<int>>> done(
      S, std::vector<std::vector<int>>(3, std::vector<int>(M, -1)));
  // per (stage, op) next-microbatch pointer: within an op, readiness is
  // monotone in m, so the minimum remaining ready m is always the pointer
  std::vector<std::vector<int>> next_m(S, std::vector<int>(3, 0));
  std::vector<int> n_f(D, 0), n_w(D, 0);
  std::vector<Order> orders(D);
  int remaining = 3 * S * M - M;  // no B on stage 0
  int t = 0;
  const int limit = 8 * remaining + 64;

  auto ready = [&](int s, int op, int m, int now) {
    if (op == OP_F) {
      if (s == 0) return true;
      int d = done[s - 1][OP_F][m];
      return d >= 0 && d + 1 <= now;
    }
    if (done[s][OP_F][m] < 0) return false;
    if (op == OP_W) {
      if (s == 0) {
        int d = done[1][OP_B][m];
        return d >= 0 && d + 1 <= now;
      }
      if (s == S - 1) return true;
      return done[s][OP_B][m] >= 0;
    }
    // dgrad B
    if (s == S - 1) return true;
    int d = done[s + 1][OP_B][m];
    return d >= 0 && d + 1 <= now;
  };

  while (remaining > 0) {
    if (t > limit) return {};  // deadlock: caller reports failure
    for (int d = 0; d < D; ++d) {
      const int s = d;  // V = 1: stage == device
      // priority: B, then F (under the in-flight cap), then W
      const int order_ops[3] = {OP_B, OP_F, OP_W};
      for (int op : order_ops) {
        if (op == OP_B && s == 0) continue;
        int m = next_m[s][op];
        if (m >= M) continue;
        if (op == OP_F && n_f[d] - n_w[d] >= 2 * D - d) continue;
        if (!ready(s, op, m, t)) continue;
        done[s][op][m] = t;
        next_m[s][op] = m + 1;
        orders[d].push_back({s, op, m});
        if (op == OP_F) ++n_f[d];
        if (op == OP_W) ++n_w[d];
        --remaining;
        break;
      }
    }
    ++t;
  }
  return orders;
}

// Greedy interval slot allocation, identical to schedules._allocate_slots:
// events sorted by (store, release); min-heap of freed slots so the
// lowest-numbered free slot is always reused first.
struct SlotAlloc {
  std::map<std::pair<int, int>, int> assign;  // (stage, mb) -> slot
  int n_slots = 0;
};

SlotAlloc allocate(std::vector<std::tuple<int, int, std::pair<int, int>>> events) {
  std::sort(events.begin(), events.end(),
            [](const auto& a, const auto& b) {
              if (std::get<0>(a) != std::get<0>(b))
                return std::get<0>(a) < std::get<0>(b);
              return std::get<1>(a) < std::get<1>(b);
            });
  std::priority_queue<int, std::vector<int>, std::greater<int>> free_slots;
  std::priority_queue<std::pair<int, int>, std::vector<std::pair<int, int>>,
                      std::greater<std::pair<int, int>>> in_use;  // (release, slot)
  SlotAlloc out;
  for (const auto& [store, release, key] : events) {
    while (!in_use.empty() && in_use.top().first < store) {
      free_slots.push(in_use.top().second);
      in_use.pop();
    }
    int slot;
    if (!free_slots.empty()) {
      slot = free_slots.top();
      free_slots.pop();
    } else {
      slot = out.n_slots++;
    }
    out.assign[key] = slot;
    in_use.push({release, slot});
  }
  return out;
}

// Tick-table column layout (schedules.py). Columns 13-16 are the vshape
// (ZB-V) reverse/local transfer routes; the wrap-placement schedules this
// engine compiles never use them, so they stay -1 — keeping wrap tables
// bit-identical to the Python compiler's.
enum Cols {
  COL_STORE_F_SLOT = 0,
  COL_FWD_V = 1, COL_FWD_M = 2, COL_FWD_SLOT = 3,
  COL_STORE_B_SLOT = 4,
  COL_BWD_V = 5, COL_BWD_M = 6,
  COL_BWD_ASLOT = 7, COL_BWD_GSLOT = 8,
  COL_W_V = 9, COL_W_M = 10,
  COL_W_ASLOT = 11, COL_W_GSLOT = 12,
  COL_FWD_LOCAL_SLOT = 13, COL_STORE_F_NEG_SLOT = 14,
  COL_BWD_LOCAL_SLOT = 15, COL_STORE_B_POS_SLOT = 16,
  N_COLS = 17,
};

}  // namespace

extern "C" {

// Compiles a schedule. Returns 0 on success. table_out must hold
// table_capacity int32s; on success *t_out ticks were written as
// [T, D, N_COLS]. Matches compile_schedule() in schedules.py bit-for-bit.
int dtpp_compile_schedule(const char* name, int D, int V, int M,
                          int32_t* table_out, int64_t table_capacity,
                          int* t_out, int* n_act_out, int* n_grad_out,
                          char* err, int errlen) {
  std::string sname(name);
  std::vector<Order> orders;
  if (sname == "GPipe") {
    if (V != 1) return fail(err, errlen, "GPipe supports a single stage per device");
    orders = gpipe_order(D, M);
  } else if (sname == "1F1B" || (sname == "Interleaved1F1B" && V == 1)) {
    if (M < D) return fail(err, errlen, "1F1B requires n_microbatches >= n_devices");
    orders = one_f_one_b_order(D, M);
  } else if (sname == "Interleaved1F1B") {
    int num_rounds = std::max(1, M / D);
    if (M % num_rounds != 0)
      return fail(err, errlen, "Interleaved1F1B requires n_microbatches % num_rounds == 0");
    orders = interleaved_order(D, V, M);
  } else if (sname == "BFS") {
    orders = bfs_order(D, V, M);
  } else if (sname == "ZBH1") {
    if (V != 1) return fail(err, errlen, "ZBH1 supports a single stage per device");
    if (D < 2) return fail(err, errlen, "ZBH1 requires n_devices >= 2");
    if (M < D) return fail(err, errlen, "ZBH1 requires n_microbatches >= n_devices");
    orders = zb_h1_order(D, M);
    if (orders.empty())
      return fail(err, errlen, "ZBH1 synthesis deadlocked");
  } else {
    return fail(err, errlen, "unknown schedule: " + sname);
  }

  const int S = D * V;
  // --- ASAP tick scheduling (schedule_ticks) ---
  std::map<Action, int> done;
  std::vector<size_t> ptr(D, 0);
  int n_actions = 0;
  // a split-backward order (any W) keeps one unit per device per tick: its
  // synthesis fills every unit tick already (schedules.schedule_ticks)
  bool split = false;
  for (const auto& o : orders) {
    n_actions += o.size();
    for (const Action& a : o) split = split || a.op == OP_W;
  }
  const int limit = 4 * n_actions + 4 * S + 16;
  int t = 0;
  auto pending = [&]() {
    for (int d = 0; d < D; ++d)
      if (ptr[d] < orders[d].size()) return true;
    return false;
  };
  while (pending()) {
    if (t > limit) return fail(err, errlen, "schedule deadlocked");
    for (int d = 0; d < D; ++d) {
      // a device takes, in one tick, its next forward and its next full
      // backward, in list order: the F and B slots of a table row (a
      // packed tick)
      bool taken[3] = {false, false, false};
      while (ptr[d] < orders[d].size()) {
        const Action& a = orders[d][ptr[d]];
        if (taken[a.op]) break;
        bool ready;
        if (a.op == OP_F) {
          if (a.stage == 0) {
            ready = true;
          } else {
            auto it = done.find({a.stage - 1, OP_F, a.mb});
            ready = it != done.end() && it->second + 1 <= t;
          }
        } else if (a.op == OP_W) {
          ready = done.count({a.stage, OP_F, a.mb}) > 0;
          if (ready) {
            if (a.stage == 0) {
              auto it = done.find({1, OP_B, a.mb});
              ready = it != done.end() && it->second + 1 <= t;
            } else if (a.stage != S - 1) {
              ready = done.count({a.stage, OP_B, a.mb}) > 0;
            }
          }
        } else {  // OP_B
          ready = done.count({a.stage, OP_F, a.mb}) > 0;
          if (ready && a.stage != S - 1) {
            auto it = done.find({a.stage + 1, OP_B, a.mb});
            ready = it != done.end() && it->second + 1 <= t;
          }
        }
        if (!ready) break;
        done[a] = t;
        taken[a.op] = true;
        ++ptr[d];
        if (split) break;
      }
    }
    ++t;
  }
  int T = t + 1;  // +1 for trailing arrivals (trimmed below)

  // --- slot allocation from lifetimes ---
  std::vector<std::vector<std::tuple<int, int, std::pair<int, int>>>>
      act_events(D), grad_events(D);
  for (const auto& [a, ta] : done) {
    if (a.op != OP_F) continue;
    int d = a.stage % D;
    int store = a.stage == 0 ? ta : done.at({a.stage - 1, OP_F, a.mb}) + 1;
    int release = -1;
    auto itb = done.find({a.stage, OP_B, a.mb});
    if (itb != done.end()) release = std::max(release, itb->second);
    auto itw = done.find({a.stage, OP_W, a.mb});
    if (itw != done.end()) release = std::max(release, itw->second);
    act_events[d].push_back({store, release, {a.stage, a.mb}});
  }
  for (int s = 0; s < S - 1; ++s) {
    int d = s % D;
    for (int m = 0; m < M; ++m) {
      int store = done.at({s + 1, OP_B, m}) + 1;
      int release = -1;
      auto itb = done.find({s, OP_B, m});
      if (itb != done.end()) release = std::max(release, itb->second);
      auto itw = done.find({s, OP_W, m});
      if (itw != done.end()) release = std::max(release, itw->second);
      grad_events[d].push_back({store, release, {s, m}});
    }
  }
  std::vector<SlotAlloc> act_alloc(D), grad_alloc(D);
  int n_act = 0, n_grad = 0;
  for (int d = 0; d < D; ++d) {
    act_alloc[d] = allocate(act_events[d]);
    grad_alloc[d] = allocate(grad_events[d]);
    n_act = std::max(n_act, act_alloc[d].n_slots);
    n_grad = std::max(n_grad, grad_alloc[d].n_slots);
  }
  n_grad = std::max(n_grad, 1);

  // --- table emission ---
  if (static_cast<int64_t>(T) * D * N_COLS > table_capacity)
    return fail(err, errlen, "table capacity too small");
  std::vector<int32_t> table(static_cast<size_t>(T) * D * N_COLS, -1);
  auto cell = [&](int tt, int d, int c) -> int32_t& {
    return table[(static_cast<size_t>(tt) * D + d) * N_COLS + c];
  };
  for (const auto& [a, ta] : done) {
    int d = a.stage % D;
    int v = a.stage / D;
    if (a.op == OP_F) {
      cell(ta, d, COL_FWD_V) = v;
      cell(ta, d, COL_FWD_M) = a.mb;
      cell(ta, d, COL_FWD_SLOT) = act_alloc[d].assign.at({a.stage, a.mb});
      if (a.stage < S - 1) {
        int nd = (a.stage + 1) % D;
        cell(ta + 1, nd, COL_STORE_F_SLOT) =
            act_alloc[nd].assign.at({a.stage + 1, a.mb});
      }
    } else if (a.op == OP_B) {
      cell(ta, d, COL_BWD_V) = v;
      cell(ta, d, COL_BWD_M) = a.mb;
      cell(ta, d, COL_BWD_ASLOT) = act_alloc[d].assign.at({a.stage, a.mb});
      if (a.stage < S - 1)
        cell(ta, d, COL_BWD_GSLOT) = grad_alloc[d].assign.at({a.stage, a.mb});
      if (a.stage > 0) {
        int pd = (a.stage - 1) % D;
        cell(ta + 1, pd, COL_STORE_B_SLOT) =
            grad_alloc[pd].assign.at({a.stage - 1, a.mb});
      }
    } else {  // OP_W
      cell(ta, d, COL_W_V) = v;
      cell(ta, d, COL_W_M) = a.mb;
      cell(ta, d, COL_W_ASLOT) = act_alloc[d].assign.at({a.stage, a.mb});
      if (a.stage < S - 1)
        cell(ta, d, COL_W_GSLOT) = grad_alloc[d].assign.at({a.stage, a.mb});
    }
  }
  // trim trailing all-empty ticks
  auto tick_empty = [&](int tt) {
    for (int d = 0; d < D; ++d)
      for (int c = 0; c < N_COLS; ++c)
        if (cell(tt, d, c) != -1) return false;
    return true;
  };
  while (T > 1 && tick_empty(T - 1)) --T;

  std::memcpy(table_out, table.data(),
              static_cast<size_t>(T) * D * N_COLS * sizeof(int32_t));
  *t_out = T;
  *n_act_out = n_act;
  *n_grad_out = n_grad;
  return 0;
}

}  // extern "C"
