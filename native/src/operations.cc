// Runtime lifecycle + C ABI.
//
// Rebuild of horovod/common/operations.cc: a per-process global state
// holding every subsystem, a background thread running the fixed-
// cadence coordination cycle (reference BackgroundThreadLoop
// operations.cc:353 / RunLoopOnce :587), the enqueue API, and the
// extern "C" surface consumed by the Python ctypes bridge (reference
// horovod_init/... operations.cc:708-910, bound by common/basics.py).
//
// Execution of device-tensor (CALLBACK) responses is delegated to a
// registered Python executor that launches jitted XLA collectives —
// see horovod_tpu/runtime.py. Host-tensor responses run natively
// (LocalOps/TcpOps).

#include <sched.h>
#include <unistd.h>

#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdlib>
#include <cstring>
#include <map>
#include <memory>
#include <mutex>
#include <thread>
#include <unordered_map>
#include <vector>

#include "hvd/codec.h"
#include "hvd/common.h"
#include "hvd/controller.h"
#include "hvd/env.h"
#include "hvd/flight.h"
#include "hvd/fusion_buffer.h"
#include "hvd/logging.h"
#include "hvd/membership.h"
#include "hvd/message.h"
#include "hvd/metrics.h"
#include "hvd/ops.h"
#include "hvd/schedule.h"
#include "hvd/bayesian.h"
#include "hvd/parameter_manager.h"
#include "hvd/response_cache.h"
#include "hvd/stall_inspector.h"
#include "hvd/steady_lock.h"
#include "hvd/tensor_queue.h"
#include "hvd/thread_pool.h"
#include "hvd/timeline.h"
#include "hvd/topology.h"

namespace hvd {
namespace {

// Bounded CV wait via a system_clock wait_until: libstdc++ 10's
// wait_for lowers to pthread_cond_clockwait (glibc >= 2.30), which
// this container's gcc-10 libtsan does NOT intercept — tsan then
// misses the unlock inside the wait and reports a bogus "double lock"
// on every subsequent acquire (verified with a 15-line repro). The
// system_clock path lowers to the intercepted pthread_cond_timedwait.
// All callers are heartbeat-style waits with predicates, so a wall
// clock jump at worst delays one tick.
template <typename Rep, typename Period, typename Pred>
bool CvWaitFor(std::condition_variable& cv,
               std::unique_lock<std::mutex>& lk,
               std::chrono::duration<Rep, Period> dur, Pred pred) {
  return cv.wait_until(
      lk,
      std::chrono::system_clock::now() +
          std::chrono::duration_cast<std::chrono::microseconds>(dur),
      pred);
}

// Persistent locked hot-wait (steady_lock.h): while the steady lock
// runs with persistent slot plans, ops arrive back-to-back by the
// lock's own definition, so the two per-op thread handoffs (enqueue ->
// background wake, fire -> synchronize wake) poll through a bounded
// sched_yield window before parking on their condition variables —
// each futex wake round trip skipped is scheduler latency off the
// locked p50. The window matches the transport's 200 us yield budget
// (no busy-spinning past it). Level 2 (TCP data plane only) lets the
// synchronize side keep polling at 100 us sleeps past the window: a
// cross-rank fire outlives the yield window, and on TCP the exchange
// threads block off-CPU in recv so the poller's quanta are free. On
// the shm plane the SAME extension is a net loss — the arena barriers
// spin/sleep on-CPU and the poller steals their timeslices — so shm
// stops at the yield window (level 1). Level 0 (off the persistent
// plane, idle rank, or HOROVOD_STEADY_PERSISTENT=off) never spins:
// the PR 15 wake path exactly.
std::atomic<int> g_persistent_hot_wait{0};

template <typename Pred>
bool HotWaitPoll(Pred&& pred) {
  if (g_persistent_hot_wait.load(std::memory_order_relaxed) < 1) return false;
  const auto until =
      std::chrono::steady_clock::now() + std::chrono::microseconds(200);
  do {
    if (pred()) return true;
    sched_yield();
  } while (std::chrono::steady_clock::now() < until);
  return pred();
}

// ---- handle manager (reference horovod/torch/handle_manager.h:31-40)
class HandleManager {
 public:
  int64_t Allocate() {
    MutexLock lock(mu_);
    int64_t h = next_++;
    results_.emplace(h, Result{});
    return h;
  }
  void MarkDone(int64_t h, const Status& s) {
    MutexLock lock(mu_);
    auto it = results_.find(h);
    if (it == results_.end()) return;
    it->second.status = s;
    it->second.done = true;
    cv_.notify_all();
  }
  bool Poll(int64_t h) {
    MutexLock lock(mu_);
    auto it = results_.find(h);
    return it == results_.end() || it->second.done;
  }
  // timeout_ms < 0: wait forever. Returns false on timeout.
  // cv wait: dynamic lock flow, opted out of static analysis (tsan
  // tier covers it).
  bool Wait(int64_t h, int timeout_ms, Status* out)
      HVD_NO_THREAD_SAFETY_ANALYSIS {
    // Hot-wait: under the persistent locked plane a fire is a few
    // scheduler quanta away (a cross-rank 4B slot runs ~300 us on the
    // bench box — past the yield window), so ride the transport's full
    // wait pattern: bounded sched_yield, then (level 2) 100 us sleep
    // polls while the plane stays hot. The level dropping (unlock,
    // knob off, loop exit) breaks to the classic futex park below,
    // whose pred passes immediately when the poll already saw the
    // completion.
    if (timeout_ms < 0) {
      HotWaitPoll([&] { return Poll(h); });
      while (g_persistent_hot_wait.load(std::memory_order_relaxed) >= 2 &&
             !Poll(h))
        usleep(100);
    }
    std::unique_lock<std::mutex> lock(mu_.native());
    auto pred = [&] {
      auto it = results_.find(h);
      return it == results_.end() || it->second.done;
    };
    if (timeout_ms < 0) {
      cv_.wait(lock, pred);
    } else {
      // The user-supplied deadline runs on the STEADY clock (a wall
      // step must not shrink or stretch a synchronize() timeout);
      // each bounded chunk rides CvWaitFor's tsan-safe wait, so a
      // step costs at most one 100ms chunk of extra wait.
      const auto deadline = std::chrono::steady_clock::now() +
                            std::chrono::milliseconds(timeout_ms);
      while (!pred()) {
        const auto left = deadline - std::chrono::steady_clock::now();
        if (left <= std::chrono::steady_clock::duration::zero())
          return false;
        CvWaitFor(cv_, lock,
                  std::min<std::chrono::steady_clock::duration>(
                      left, std::chrono::milliseconds(100)),
                  pred);
      }
    }
    auto it = results_.find(h);
    *out = it == results_.end() ? Status::OK() : it->second.status;
    return true;
  }
  void Release(int64_t h) {
    MutexLock lock(mu_);
    results_.erase(h);
  }
  void GetStatus(int64_t h, Status* out) {
    MutexLock lock(mu_);
    auto it = results_.find(h);
    *out = it == results_.end() ? Status::OK() : it->second.status;
  }

 private:
  struct Result {
    bool done = false;
    Status status;
  };
  Mutex mu_;
  // Plain condition_variable over mu_.native(): notify_all fires per
  // completed op — a hot path under small-tensor traffic.
  std::condition_variable cv_;
  int64_t next_ HVD_GUARDED_BY(mu_) = 0;
  std::unordered_map<int64_t, Result> results_ HVD_GUARDED_BY(mu_);
};

// Python-side hooks (set before hvd_init).
// Executor: runs one CALLBACK-mode response; must call hvd_exec_done.
// `this_rank_contributes` is 1 when this rank's data participates in
// the response (it announced the tensors); 0 means this rank joined and
// the executor must synthesize a zeros contribution. Fused responses
// share one contributor set (fusion requires it), so one flag suffices.
typedef void (*ExecCallback)(int64_t exec_id, int op_type, int num_tensors,
                             const char** tensor_names, int32_t dtype,
                             const int64_t* sizes, int32_t sizes_len,
                             int32_t reduce_op,
                             int32_t this_rank_contributes);
// Allocator: returns a host buffer for late-sized outputs
// (allgather/alltoall), keyed by the entry's handle.
typedef void* (*AllocCallback)(int64_t handle, const int64_t* shape,
                               int32_t ndim);

struct PendingExec {
  Response response;
  std::vector<TensorTableEntry> entries;
};

struct GlobalState {
  std::atomic<bool> initialized{false};
  std::atomic<bool> shutdown_requested{false};
  std::atomic<bool> shut_down{false};

  int rank = 0, size = 1, local_rank = 0, local_size = 1;
  int cross_rank = 0, cross_size = 1;

  TensorQueue tensor_queue;
  ResponseCache response_cache;
  StallInspector stall_inspector;
  Timeline timeline;
  FusionBufferManager fusion;
  HandleManager handles;
  ParameterManager param_manager;

  std::unique_ptr<Controller> controller;
  std::unique_ptr<OpExecutor> host_ops;
  std::thread background_thread;
  // Set by BackgroundThreadLoop at entry (cleared at exit): lets
  // membership fences tell whether they are running ON the
  // coordination loop — the std::thread object itself must not be
  // touched from other threads while init assigns it.
  std::atomic<std::thread::id> background_thread_id{};

  double cycle_time_ms = 1.0;
  ExecCallback exec_cb = nullptr;
  AllocCallback alloc_cb = nullptr;

  // Event-driven coordination: enqueues (and shutdown) signal the
  // background loop instead of it sleeping a fixed cadence. Plain
  // std::mutex (not the annotated wrapper): it exists only to pair
  // with the condition variable — the guarded predicate state lives
  // behind the tensor queue's own lock.
  std::mutex wake_mu;
  std::condition_variable wake_cv;

  // Python executor handoff: the coordinator publishes a pending exec,
  // arbitrary Python threads complete it via hvd_exec_done.
  Mutex exec_mu;
  int64_t next_exec_id HVD_GUARDED_BY(exec_mu) = 0;
  std::unordered_map<int64_t, PendingExec> pending_execs
      HVD_GUARDED_BY(exec_mu);

  // Written by the data plane at completion, read by hvd_get_recvsplits
  // from Python threads.
  Mutex recvsplits_mu;
  std::unordered_map<int64_t, std::vector<int64_t>> recvsplits
      HVD_GUARDED_BY(recvsplits_mu);  // by handle

  // Epoch fences this incarnation registered on the membership plane
  // (hvd/membership.h) — unregistered at shutdown so an elastic
  // re-init never stacks duplicates on the process-global singleton.
  std::vector<int> membership_fence_tokens;
};

GlobalState& State() {
  static GlobalState* state = new GlobalState();
  return *state;
}

void CompleteEntry(GlobalState& st, TensorTableEntry& e, const Status& s) {
  if (!e.recvsplits.empty()) {
    MutexLock lock(st.recvsplits_mu);
    st.recvsplits[e.handle] = e.recvsplits;
  }
  if (e.callback) e.callback(s);
}

// Allocate late-sized outputs (allgather/alltoall) via the Python
// allocator before the data plane runs (reference OpContext::
// AllocateOutput driven from PrepareOutputAndParams,
// collective_operations.h:206-268).
Status AllocateOutputs(GlobalState& st, const Response& resp,
                       std::vector<TensorTableEntry>& entries) {
  if (resp.response_type != ResponseType::ALLGATHER &&
      resp.response_type != ResponseType::ALLTOALL &&
      resp.response_type != ResponseType::REDUCESCATTER)
    return Status::OK();
  for (size_t t = 0; t < entries.size(); ++t) {
    auto& e = entries[t];
    if (e.output != nullptr || e.exec_mode != ExecMode::HOST) continue;
    std::vector<int64_t> shape = e.shape.dims();
    if (resp.response_type == ResponseType::ALLGATHER) {
      // Fused responses carry per-tensor blocks of `size` row counts.
      int64_t rows = 0;
      for (int k = 0; k < st.size; ++k)
        rows += resp.tensor_sizes[t * st.size + k];
      shape[0] = rows;
    } else if (resp.response_type == ResponseType::ALLTOALL) {
      int64_t rows = 0;
      for (int k = 0; k < st.size; ++k)
        rows += resp.recvsplits[static_cast<size_t>(st.rank) * st.size + k];
      shape[0] = rows;
    } else {  // REDUCESCATTER
      shape[0] = resp.tensor_sizes[st.rank];
    }
    if (st.alloc_cb == nullptr)
      return Status::PreconditionError("no output allocator registered");
    e.output = st.alloc_cb(e.handle, shape.data(),
                           static_cast<int32_t>(shape.size()));
    if (e.output == nullptr)
      return Status::PreconditionError("output allocation failed for " +
                                       e.name);
  }
  return Status::OK();
}

// Per-op-type counters for one response THIS rank executes (joined
// ranks that skip execution don't count it): response count, payload
// bytes, tensor count, and the fusion shape (batched-tensor count +
// fill ratio against the live fusion threshold).
void RecordResponseMetrics(GlobalState& st, const Response& response) {
  MetricCounter ops, bytes;
  switch (response.response_type) {
    case ResponseType::ALLREDUCE:
      ops = kCtrResponsesAllreduce;
      bytes = kCtrBytesAllreduce;
      break;
    case ResponseType::ALLGATHER:
      ops = kCtrResponsesAllgather;
      bytes = kCtrBytesAllgather;
      break;
    case ResponseType::BROADCAST:
      ops = kCtrResponsesBroadcast;
      bytes = kCtrBytesBroadcast;
      break;
    case ResponseType::ALLTOALL:
      ops = kCtrResponsesAlltoall;
      bytes = kCtrBytesAlltoall;
      break;
    case ResponseType::REDUCESCATTER:
      ops = kCtrResponsesReducescatter;
      bytes = kCtrBytesReducescatter;
      break;
    default:
      return;  // JOIN/BARRIER/ERROR carry no payload metrics
  }
  if (!MetricsRegistry::Get().enabled()) return;
  const int64_t b = response.TotalByteSize();
  const int64_t n = static_cast<int64_t>(response.tensor_names.size());
  MetricAdd(ops);
  MetricAdd(bytes, b);
  MetricAdd(kCtrTensorsTotal, n);
  if (n > 1) {
    MetricAdd(kCtrFusedBatches);
    MetricAdd(kCtrFusedTensors, n);
    MetricObserve(kHistFusedTensorsPerResponse, n);
  }
  if (response.response_type == ResponseType::ALLREDUCE && st.controller) {
    const int64_t thr = st.controller->fusion_threshold();
    if (thr > 0) MetricObserve(kHistFusionFillPct, 100 * b / thr);
  }
}

void PerformOperation(GlobalState& st, const Response& response) {
  std::vector<TensorTableEntry> entries;
  st.tensor_queue.GetTensorEntriesFromResponse(response, &entries);

  if (response.response_type == ResponseType::ERROR) {
    MetricAdd(kCtrErrorResponses);
    Status err = Status::PreconditionError(response.error_message);
    for (auto& e : entries) CompleteEntry(st, e, err);
    return;
  }
  if (response.response_type == ResponseType::JOIN) {
    // Everyone-joined flush committed. The JOIN response is broadcast-
    // ordered AFTER the flushed tensors in the same list, so every
    // rank advances the membership epoch at the identical point in the
    // response stream — no op straddles two epochs, and all ranks
    // compute the same new epoch without extra wire traffic.
    MembershipPlane::Get().Advance(kMemberJoin, -1);
  }
  if (entries.empty()) {
    // Joined rank: no local tensors. HOST mode: nothing to do — the
    // peer-mesh algorithms run entirely among the contributors (the
    // rank-0 hub role is gone). CALLBACK mode: this process must
    // STILL launch the XLA program — every process in a multi-controller
    // JAX job has to execute the same collective in the same order
    // (xla_exec synthesizes a zeros contribution from the response's
    // element counts; reference feeds zeros for joined ranks,
    // operations.cc:260).
    if (response.exec_mode == ExecMode::HOST) return;
    if (response.exec_mode != ExecMode::CALLBACK || st.exec_cb == nullptr ||
        response.response_type != ResponseType::ALLREDUCE) {
      return;
    }
    // fall through to the CALLBACK launch below with empty entries
  }

  RecordResponseMetrics(st, response);
  const std::string tname =
      entries.empty() ? response.tensor_names.front() : entries.front().name;
  st.timeline.Start(tname, ResponseTypeName(response.response_type));

  Status status = AllocateOutputs(st, response, entries);
  if (status.ok()) {
    if (response.exec_mode == ExecMode::CALLBACK) {
      // Hand off to the Python/XLA executor; completion arrives via
      // hvd_exec_done (possibly from another thread). Names come from
      // the response (not the local entries) so a joined rank with no
      // local tensors launches the identical program.
      if (st.exec_cb == nullptr) {
        status = Status::PreconditionError("no XLA executor registered");
      } else {
        int64_t exec_id;
        std::vector<const char*> names;
        {
          MutexLock lock(st.exec_mu);
          exec_id = st.next_exec_id++;
          auto& pe = st.pending_execs[exec_id];
          pe.response = response;
          pe.entries = std::move(entries);
          for (auto& n : pe.response.tensor_names)
            names.push_back(n.c_str());
        }
        st.timeline.ActivityStart(tname, ACT_XLA_EXEC);
        const std::vector<int64_t>& sizes =
            response.response_type == ResponseType::ALLTOALL
                ? response.recvsplits
                : response.tensor_sizes;
        // Empty contributor set means "everyone contributes" (same
        // convention as the host data plane, ops.cc).
        int32_t contributes = response.contributors.empty() ? 1 : 0;
        for (int32_t r : response.contributors)
          if (r == st.rank) contributes = 1;
        st.exec_cb(exec_id, static_cast<int>(response.response_type),
                   static_cast<int>(names.size()), names.data(),
                   static_cast<int32_t>(response.tensor_type), sizes.data(),
                   static_cast<int32_t>(sizes.size()),
                   static_cast<int32_t>(response.reduce_op), contributes);
        return;  // completed asynchronously
      }
    } else {
      status = st.host_ops->Execute(response, entries);
    }
  }
  st.timeline.End(tname, 0);
  for (auto& e : entries) CompleteEntry(st, e, status);
}

// Rank-0 autotune bookkeeping, shared by the negotiated cycle and the
// locked phase: record the window's reduction traffic and, on a
// parameter move, apply rank 0's new values and stage the broadcast
// (reference parameter-manager hook, operations.cc:635-642). Returns
// true when tunables were staged this call — the locked phase turns
// that into a deterministic unlock so the stage can ride the next
// negotiated broadcast.
bool MaybeAutotuneRank0(GlobalState& st, int64_t bytes, double now_secs) {
  if (st.rank != 0 || !st.param_manager.enabled()) return false;
  st.param_manager.Record(bytes);  // allreduce traffic (others size 0)
  if (!st.param_manager.Update(now_secs)) return false;
  using PM = hvd::ParameterManager;
  auto cat = [&](PM::Categorical c) {
    return st.param_manager.categorical_tunable(c)
               ? (st.param_manager.categorical(c) ? 1 : 0)
               : -1;
  };
  st.controller->SetFusionThreshold(st.param_manager.fusion_threshold());
  st.cycle_time_ms = st.param_manager.cycle_time_ms();
  st.controller->SetHierarchical(st.param_manager.hierarchical_tunable()
                                     ? st.param_manager.hierarchical()
                                     : st.controller->hierarchical());
  if (st.param_manager.categorical_tunable(PM::kCatCache))
    st.controller->SetCacheActive(st.param_manager.categorical(PM::kCatCache));
  if (st.param_manager.categorical_tunable(PM::kCatShm))
    st.controller->SetShmActive(st.param_manager.categorical(PM::kCatShm));
  // Stage host knobs only when the search owns them: an untuned knob
  // staged every window would clobber runtime overrides
  // (hvd.set_reduce_threads) with the stale init-time value.
  int tuned_threads = 0, tuned_depth = 0, tuned_wire = -1;
  int tuned_algo = -1;
  if (st.param_manager.threads_tunable()) {
    st.controller->SetReduceThreads(st.param_manager.reduce_threads());
    SetHostReduceThreads(st.controller->reduce_threads());
    tuned_threads = st.controller->reduce_threads();
  }
  if (st.param_manager.depth_tunable()) {
    st.controller->SetShmSegmentDepth(st.param_manager.seg_depth());
    tuned_depth = st.controller->shm_segment_depth();
  }
  if (st.param_manager.wire_tunable()) {
    const int prev_wire = st.controller->wire_codec();
    st.controller->SetWireCodec(st.param_manager.wire_codec());
    tuned_wire = st.controller->wire_codec();
    if (tuned_wire != prev_wire)
      FlightRecord(hvd::kFlightWireVerdict, tuned_wire, prev_wire);
  }
  if (st.param_manager.algo_tunable()) {
    const int prev_algo = st.controller->collective_algo();
    st.controller->SetCollectiveAlgo(st.param_manager.collective_algo());
    tuned_algo = st.controller->collective_algo();
    if (tuned_algo != prev_algo)
      FlightRecord(hvd::kFlightAlgoVerdict, tuned_algo, prev_algo);
  }
  st.controller->StageTunedParams(
      st.param_manager.fusion_threshold(), st.param_manager.cycle_time_ms(),
      cat(PM::kCatHier), cat(PM::kCatCache), cat(PM::kCatShm), tuned_threads,
      tuned_depth, tuned_wire, tuned_algo);
  FlightRecord(hvd::kFlightAutotuneStage, st.param_manager.fusion_threshold(),
               static_cast<int64_t>(st.param_manager.cycle_time_ms() * 1000));
  return true;
}

// Idle heartbeat: an idle rank still enters a (cheap, empty) cycle at
// this cadence so coordinator stall checks and broadcast shutdown
// verdicts stay live — 10 wakeups/s instead of the old 1000.
constexpr int kIdleHeartbeatMs = 100;
// A JOINED rank idles differently: the peers' every collective is
// gated on its empty announce frames and no local enqueue will ever
// wake it, so it keeps near the old cycle cadence instead.
constexpr int kJoinedHeartbeatMs = 2;
// Locked-phase wait tick: bounds how long a peer's UNLOCK proposal or
// the partial-slot timeout can sit unnoticed while this rank idles.
constexpr int kLockWaitTickMs = 50;

// One locked-phase iteration. Returns false when the lock ended (the
// caller falls back to negotiated cycles).
bool RunLockedIteration(GlobalState& st,
                        std::chrono::steady_clock::time_point loop_epoch) {
  int forced = -1;
  if (st.rank == 0 && st.param_manager.enabled()) {
    const double now = std::chrono::duration<double>(
                           std::chrono::steady_clock::now() - loop_epoch)
                           .count();
    if (MaybeAutotuneRank0(st, 0, now)) forced = hvd::kUnlockTunables;
  }
  Response fire;
  bool fatal = false;
  const auto step = st.controller->LockedPhaseStep(
      st.shutdown_requested.load(), forced, &st.shutdown_requested, &fire,
      &fatal);
  using LS = hvd::Controller::LockStep;
  if (step == LS::kFired) {
    if (MetricsRegistry::Get().enabled()) {
      // Bypass-path latency: oldest member enqueue -> fire (the
      // negotiation+cycle budget this path exists to delete).
      const auto now = std::chrono::steady_clock::now();
      int64_t worst = -1;
      for (const auto& name : fire.tensor_names) {
        TensorTableEntry e;
        if (st.tensor_queue.Lookup(name, &e))
          worst = std::max<int64_t>(
              worst, std::chrono::duration_cast<std::chrono::microseconds>(
                         now - e.enqueue_time)
                         .count());
      }
      if (worst >= 0) MetricObserve(kHistLockFireUs, worst);
    }
    MetricAdd(kCtrBypassedResponses);
    PerformOperation(st, fire);
    if (st.rank == 0 && st.param_manager.enabled())
      st.param_manager.Record(fire.TotalByteSize());
    return true;
  }
  if (step == LS::kWait) {
    auto ready = [&] {
      return st.tensor_queue.has_messages() || st.shutdown_requested.load();
    };
    // Hot-wait first (persistent plane only): the next enqueue usually
    // lands within a quantum of the previous synchronize, and catching
    // it in the yield window skips the enqueue->background futex wake.
    // On the TCP plane (level 2) a miss keeps sleep-polling at 100 us
    // up to the same kLockWaitTickMs bound the parked wait uses, so
    // peer proposals / partial-slot timeouts are still inspected at
    // tick cadence; the shm plane (level 1) parks after the window —
    // its arena barriers need the quanta a poller would burn.
    if (!HotWaitPoll(ready)) {
      if (g_persistent_hot_wait.load(std::memory_order_relaxed) >= 2) {
        const auto tick_end =
            std::chrono::steady_clock::now() +
            std::chrono::milliseconds(kLockWaitTickMs);
        while (!ready() && std::chrono::steady_clock::now() < tick_end &&
               g_persistent_hot_wait.load(std::memory_order_relaxed) >= 2)
          usleep(100);
      } else {
        std::unique_lock<std::mutex> lk(st.wake_mu);
        CvWaitFor(st.wake_cv, lk, std::chrono::milliseconds(kLockWaitTickMs),
                  ready);
      }
    }
    return true;
  }
  // kUnlocked: pending work was requeued; negotiated cycles resume. A
  // fatal unlock (stall-shutdown abort tore the links down) raises the
  // process shutdown flag so the next cycle ends the job.
  if (fatal) st.shutdown_requested.store(true);
  return false;
}

void BackgroundThreadLoop(GlobalState& st) {
  // Publish this loop's identity for the membership fences: purges of
  // cycle-lockstep state (response cache, staged tunables) only act
  // when the advance itself ran on this thread.
  st.background_thread_id.store(std::this_thread::get_id(),
                                std::memory_order_relaxed);
  const auto loop_epoch = std::chrono::steady_clock::now();
  while (true) {
    const bool locked = st.controller->lock_engaged();
    const bool hot =
        locked &&
        st.controller->steady_persistent() == hvd::kSteadyPersistentAuto;
    g_persistent_hot_wait.store(
        hot ? (st.controller->data_plane_shm() ? 1 : 2) : 0,
        std::memory_order_relaxed);
    if (locked) {
      RunLockedIteration(st, loop_epoch);
      continue;
    }
    // Messages pending BEFORE the cycle: a cycle that drained none and
    // fired nothing is an idle heartbeat, not coordination work.
    const bool had_msgs = st.tensor_queue.has_messages();
    auto cycle_start = std::chrono::steady_clock::now();
    st.timeline.MarkCycleStart();
    ResponseList list =
        st.controller->ComputeResponseList(st.shutdown_requested.load());
    // Workers apply staged tunables BEFORE executing this cycle's
    // responses: rank 0 already runs with the new values (it applied
    // them at the end of the previous cycle), and hierarchical is a
    // data-plane ALGORITHM choice — executing one cycle with mixed
    // values would deadlock the exchange.
    if (st.rank != 0 && list.tuned_fusion_threshold > 0) {
      st.controller->SetFusionThreshold(list.tuned_fusion_threshold);
      if (list.tuned_cycle_time_ms > 0)
        st.cycle_time_ms = list.tuned_cycle_time_ms;
      if (list.tuned_hierarchical >= 0)
        st.controller->SetHierarchical(list.tuned_hierarchical != 0);
      if (list.tuned_reduce_threads > 0) {
        st.controller->SetReduceThreads(list.tuned_reduce_threads);
        SetHostReduceThreads(st.controller->reduce_threads());
      }
      // Depth changes region indices and barrier counts — like
      // hierarchical, it must be live before this cycle's responses
      // execute or the arena desyncs.
      if (list.tuned_seg_depth > 0)
        st.controller->SetShmSegmentDepth(list.tuned_seg_depth);
      // Wire codec agreement per response is already guaranteed (the
      // coordinator resolves it into each Response); applying the
      // tuned default here keeps this rank's introspected value — and
      // any "follow the default" requests it originates as a future
      // coordinator — truthful.
      if (list.tuned_wire_codec >= 0 &&
          list.tuned_wire_codec != st.controller->wire_codec()) {
        FlightRecord(kFlightWireVerdict, list.tuned_wire_codec,
                     st.controller->wire_codec());
        st.controller->SetWireCodec(list.tuned_wire_codec);
      }
      // Algorithm agreement per response is already guaranteed (the
      // coordinator resolves it into each Response); as with the wire
      // codec, applying the tuned force here keeps this rank's
      // introspected value truthful.
      if (list.tuned_collective_algo >= 0 &&
          list.tuned_collective_algo != st.controller->collective_algo()) {
        FlightRecord(kFlightAlgoVerdict, list.tuned_collective_algo,
                     st.controller->collective_algo());
        st.controller->SetCollectiveAlgo(list.tuned_collective_algo);
      }
    }
    for (const auto& resp : list.responses) PerformOperation(st, resp);
    if (list.shutdown) break;
    // Steady-state lock engagement rides the broadcast list; switch
    // AFTER executing this cycle's responses so every rank enters the
    // locked phase at the same ring position.
    if (list.lock_engage && !list.lock_ring.empty()) {
      st.controller->EngageLock(list.lock_ring);
      continue;
    }
    // HOROVOD_STEADY_LOCK=off reverts the WHOLE feature to the PR 14
    // loop — fixed sleep-to-budget, every cycle counted in cycle_us —
    // so `off` is behaviorally byte-identical to the pre-lock runtime
    // (and an off arm measures the real baseline).
    const bool event_driven =
        st.controller->steady_lock() != hvd::kSteadyLockOff;
    const bool empty_cycle =
        event_driven && !had_msgs && list.responses.empty();
    if (!empty_cycle) {
      int64_t bytes = 0;
      for (const auto& r : list.responses) bytes += r.TotalByteSize();
      FlightRecord(kFlightCycleSummary,
                   static_cast<int64_t>(list.responses.size()), bytes);
      MaybeAutotuneRank0(st, bytes,
                         std::chrono::duration<double>(
                             std::chrono::steady_clock::now() - loop_epoch)
                             .count());
    }
    auto elapsed = std::chrono::steady_clock::now() - cycle_start;
    // Coordinator-cycle telemetry: wall time of negotiate + execute
    // (waits are idle time, not cycle cost) and the in-flight depth
    // this cycle left behind. Idle heartbeats skip the clock-derived
    // observes entirely — with event-driven wakeups they are waits,
    // and folding them in would poison the cycle_us percentiles.
    if (empty_cycle) {
      MetricAdd(kCtrCyclesIdle);
    } else if (MetricsRegistry::Get().enabled()) {
      MetricAdd(kCtrCycles);
      const int64_t cyc_us =
          std::chrono::duration_cast<std::chrono::microseconds>(elapsed)
              .count();
      MetricObserve(kHistCycleUs, cyc_us);
      MetricObserve(kHistQueueDepth,
                    static_cast<int64_t>(st.tensor_queue.size()));
      if (st.timeline.Initialized()) {
        // Counter tracks next to the spans, fed from the same numbers
        // the registry reports — traces and hvd.metrics() can't
        // disagree. busbw uses the NCCL convention 2(P-1)/P.
        int64_t cyc_bytes = 0;
        for (const auto& r : list.responses) cyc_bytes += r.TotalByteSize();
        st.timeline.Counter("queue_depth",
                            static_cast<double>(st.tensor_queue.size()));
        st.timeline.Counter("fusion_bytes", static_cast<double>(cyc_bytes));
        const double secs = std::chrono::duration<double>(elapsed).count();
        const double busbw =
            (secs > 0 && st.size > 0)
                ? cyc_bytes * 2.0 * (st.size - 1) / st.size / secs / 1e9
                : 0.0;
        st.timeline.Counter("busbw_gbps", busbw);
      }
    }
    // Event-driven wait (replaces the fixed sleep-to-budget):
    //  * fresh messages already queued -> hold the batching window out
    //    to the cycle budget (fusion and the autotuner's cycle-time
    //    dimension keep their semantics), then cycle again;
    //  * negotiation in flight -> re-enter immediately (the blocking
    //    control rendezvous IS the wait), pacing consecutive empty
    //    cycles at the budget so straggler churn stays bounded;
    //  * idle -> park until an enqueue arrives (heartbeat-capped so
    //    stall checks and shutdown verdicts stay live). An op enqueued
    //    after an idle gap starts its cycle immediately instead of
    //    paying up to a full HOROVOD_CYCLE_TIME of residual sleep.
    const auto budget =
        std::chrono::duration<double, std::milli>(st.cycle_time_ms);
    elapsed = std::chrono::steady_clock::now() - cycle_start;
    if (!event_driven) {
      if (elapsed < budget) std::this_thread::sleep_for(budget - elapsed);
      continue;
    }
    std::unique_lock<std::mutex> lk(st.wake_mu);
    auto woken = [&] {
      return st.tensor_queue.has_messages() || st.shutdown_requested.load();
    };
    if (woken()) {
      if (elapsed < budget)
        CvWaitFor(st.wake_cv, lk, budget - elapsed,
                  [&] { return st.shutdown_requested.load(); });
    } else if (st.controller->HasUnresolvedWork()) {
      if (empty_cycle && elapsed < budget)
        CvWaitFor(st.wake_cv, lk, budget - elapsed, woken);
    } else {
      CvWaitFor(st.wake_cv, lk,
                std::chrono::milliseconds(st.controller->IsJoined()
                                              ? kJoinedHeartbeatMs
                                              : kIdleHeartbeatMs),
                woken);
    }
  }
  g_persistent_hot_wait.store(0, std::memory_order_relaxed);
  st.tensor_queue.FailAll(Status::Aborted("Horovod has been shut down"));
  st.timeline.Shutdown();
  st.background_thread_id.store(std::thread::id(),
                                std::memory_order_relaxed);
  st.shut_down.store(true);
}

Status EnqueueEntries(std::vector<TensorTableEntry> entries,
                      RequestType type) {
  GlobalState& st = State();
  if (!st.initialized.load() || st.shut_down.load())
    return Status::PreconditionError("horovod_tpu core not initialized");
  std::vector<Request> requests;
  requests.reserve(entries.size());
  for (auto& e : entries) {
    Request req;
    req.request_rank = st.rank;
    req.request_type = type;
    req.tensor_type = e.dtype;
    req.tensor_name = e.name;
    req.tensor_shape = e.shape.dims();
    req.root_rank = e.root_rank;
    req.reduce_op = e.reduce_op;
    req.prescale_factor = e.prescale_factor;
    req.postscale_factor = e.postscale_factor;
    req.splits = e.splits;
    req.exec_mode = e.exec_mode;
    req.group_key = e.group_key;
    req.group_size = e.group_size;
    req.wire_codec = e.wire_codec;
    req.collective_algo = e.collective_algo;
    requests.push_back(std::move(req));
  }
  Status s = st.tensor_queue.AddToTensorQueue(std::move(entries),
                                              std::move(requests));
  if (s.ok()) {
    // Wake the event-driven background loop: an op arriving after an
    // idle gap starts negotiating (or lock-matching) immediately.
    std::lock_guard<std::mutex> g(st.wake_mu);
    st.wake_cv.notify_all();
  }
  return s;
}

}  // namespace
}  // namespace hvd

// ===========================================================================
// C ABI (consumed by horovod_tpu/common/basics.py via ctypes).
// ===========================================================================

extern "C" {

using hvd::GlobalState;

int hvd_init(int rank, int size, int local_rank, int local_size,
             int cross_rank, int cross_size) {
  auto& st = hvd::State();
  if (st.initialized.load()) return 0;
  if (st.shut_down.load()) {
    // Elastic re-init: reset the single-shot state.
    st.shut_down.store(false);
    st.shutdown_requested.store(false);
    st.response_cache.Clear();
    if (st.background_thread.joinable()) st.background_thread.join();
  }
  st.rank = rank;
  st.size = size;
  st.local_rank = local_rank;
  st.local_size = local_size;
  st.cross_rank = cross_rank;
  st.cross_size = cross_size;

  // Sanitized env parsing throughout (env.h, warn-once): atoll/atof's
  // silent 0 for garbage would set a live value on several of these.
  st.cycle_time_ms = hvd::EnvDoubleSane("HOROVOD_CYCLE_TIME", 1.0);
  // Bound is a sanity ceiling well above any real deployment, not a
  // policy: values past it fall back to the default WITH a warning,
  // so the bound must never bite a legitimate operator.
  st.response_cache.SetCapacity(static_cast<uint32_t>(
      hvd::EnvInt64Sane("HOROVOD_CACHE_CAPACITY", 1024, 0, 1 << 24)));
  // Single read of HOROVOD_FUSION_THRESHOLD: three subsystems consume
  // it (fusion buffer sizing, autotune seed, controller threshold) and
  // reading the environment three times would let them disagree if
  // anything mutated the variable between reads.
  const int64_t fusion_threshold = hvd::EnvInt64Sane(
      "HOROVOD_FUSION_THRESHOLD", 64 * 1024 * 1024, 0, int64_t(1) << 40);
  st.fusion.SetInitialSize(fusion_threshold);
  // 0 is live for both stall knobs (0 shutdown = never shut down).
  st.stall_inspector.SetWarningTime(hvd::EnvDoubleSane(
      "HOROVOD_STALL_CHECK_TIME_SECONDS", 60.0, /*allow_zero=*/true));
  st.stall_inspector.SetShutdownTime(hvd::EnvDoubleSane(
      "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS", 0.0, /*allow_zero=*/true));
  st.param_manager = hvd::ParameterManager();
  st.param_manager.Initialize(fusion_threshold, st.cycle_time_ms);
  // Any nonzero enables (historic semantics: `EnvInt64(...) != 0`) —
  // a [0,1] bound here would silently DISABLE the feature for an
  // operator launching with AUTOTUNE=2, the opposite of their intent.
  st.param_manager.SetEnabled(
      hvd::EnvInt64Sane("HOROVOD_AUTOTUNE", 0, 0, 1 << 30) != 0);
  if (const char* lp = hvd::EnvStr("HOROVOD_AUTOTUNE_LOG"))
    st.param_manager.SetLogPath(lp);

  hvd::ControllerDeps deps;
  deps.tensor_queue = &st.tensor_queue;
  deps.response_cache = &st.response_cache;
  deps.stall_inspector = &st.stall_inspector;
  deps.timeline = &st.timeline;

  const char* addr = hvd::EnvStr("HOROVOD_CONTROLLER_ADDR");
  if (size > 1 && addr == nullptr) {
    LOG_ERROR << "multi-process init requires HOROVOD_CONTROLLER_ADDR";
    return -1;
  }
  if (size > 1) {
    st.controller = std::make_unique<hvd::TcpController>(
        rank, size, addr, deps);
  } else {
    st.controller = std::make_unique<hvd::LocalController>(deps);
  }
  st.controller->SetFusionThreshold(fusion_threshold);
  // Sanitized parses (warn once + default): atoll's silent 0 for
  // garbage would route every payload onto the ring / shrink the shm
  // segment to its floor without a trace.
  // Default 256 KB: the calibration sweep (docs/perf_tuning.md,
  // host_allreduce_busbw_{ring,hd}_* arms) shows halving-doubling
  // beating the ring through the 64-512 KB latency band.
  st.controller->SetRingThreshold(hvd::EnvInt64Sane(
      "HOROVOD_RING_THRESHOLD", 256 * 1024, 0, int64_t(1) << 40));
  st.controller->SetShmSegmentBytes(hvd::EnvInt64Sane(
      "HOROVOD_SHM_SEGMENT_BYTES", 8 * 1024 * 1024, 4096,
      int64_t(1) << 34));
  st.controller->SetShmSegmentDepth(static_cast<int>(
      hvd::EnvInt64Sane("HOROVOD_SHM_SEGMENT_DEPTH", 2, 1, 8)));
  // Host-reduction worker threads: default leaves every co-located
  // rank its fair share of the machine (cores / local_size, capped at
  // 8) so the pool speeds reductions up instead of oversubscribing
  // the box the ranks already timeshare.
  {
    const int hw = static_cast<int>(std::thread::hardware_concurrency());
    const int dflt =
        std::max(1, std::min(8, hw / std::max(1, local_size)));
    st.controller->SetReduceThreads(static_cast<int>(
        hvd::EnvInt64Sane("HOROVOD_REDUCE_THREADS", dflt, 1, 64)));
  }
  // Wire codec for the TCP data plane: a choice knob, not a number —
  // garbage must not alias to "none" silently (the operator would
  // believe the wire is compressed when it isn't).
  st.controller->SetWireCodec(
      hvd::EnvChoiceSane("HOROVOD_WIRE_COMPRESSION", 0,
                         hvd::kWireCodecNames, hvd::kNumWireCodecs));
  // Collective-algorithm force for the TCP allreduce plane: a choice
  // knob over the schedule.h names ("auto" = the per-(payload, np,
  // topology) selection table decides per response). Coordinator-
  // synced and resolved into each Response, so a per-rank divergence
  // of this knob cannot split the exchange (rank 0's value wins, now
  // explicitly rather than by the old post-sync threshold accident).
  st.controller->SetCollectiveAlgo(
      hvd::EnvChoiceSane("HOROVOD_COLLECTIVE_ALGO", 0,
                         hvd::kCollectiveAlgoNames,
                         hvd::kNumCollectiveAlgos));
  // Schedule-synthesis parameters (hvd/schedule.h): stripe count for
  // the striped family, sub-chunks per ring shard, halving-doubling
  // recursion ordering. Coordinator-synced like the algorithm force —
  // every rank must generate the SAME table or the exchange deadlocks
  // — and normally written by tools/synth.py's verdict, not by hand.
  st.controller->SetCollectiveStripes(static_cast<int>(
      hvd::EnvInt64Sane("HOROVOD_COLLECTIVE_STRIPES", 2, 1, 8)));
  st.controller->SetCollectiveGranularity(static_cast<int>(
      hvd::EnvInt64Sane("HOROVOD_COLLECTIVE_GRANULARITY", 1, 1, 8)));
  st.controller->SetHdOrder(static_cast<int>(
      hvd::EnvInt64Sane("HOROVOD_HD_ORDER", 0, 0, 1)));
  // Alltoall schedule-family force (ISSUE 18): same sane-choice and
  // coordinator-sync discipline (param field 17) — "auto" lets the
  // measured topology model arbitrate pairwise vs bruck per response.
  st.controller->SetAlltoallAlgo(
      hvd::EnvChoiceSane("HOROVOD_ALLTOALL_ALGO", 0,
                         hvd::kAlltoallAlgoNames,
                         hvd::kNumAlltoallAlgos));
  st.controller->SetTopology(local_rank, local_size, cross_rank, cross_size);
  st.controller->SetHierarchical(   // any nonzero enables (see above)
      hvd::EnvInt64Sane("HOROVOD_HIERARCHICAL_ALLREDUCE", 0, 0, 1 << 30)
      != 0);
  st.controller->SetShmEnabled(
      size > 1 && !hvd::EnvFlag("HOROVOD_SHM_DISABLE"));
  // Steady-state schedule lock (hvd/steady_lock.h): a choice knob —
  // garbage must not silently disable (or enable) the bypass plane.
  // Rank 0's parse is synced in Initialize (param field 15): the LOCK
  // broadcast and its token rounds must be job-unique.
  {
    static const char* const kSteadyLockChoices[] = {"auto", "off"};
    st.controller->SetSteadyLock(
        hvd::EnvChoiceSane("HOROVOD_STEADY_LOCK", 0, kSteadyLockChoices, 2));
    // Partial-slot unlock deadline: how long a half-fed locked slot may
    // wait for its remaining members before the lock concedes the op
    // set changed and renegotiates. 0/garbage fall back to the default.
    st.controller->SetSteadyLockTimeout(hvd::EnvDoubleSane(
        "HOROVOD_STEADY_LOCK_TIMEOUT_SECONDS", 2.0));
    // Persistent locked data plane (ISSUE 17): same sane-choice + sync
    // discipline (param field 16) — the consensus-cell mapping and the
    // per-slot inline verdicts both derive from it, and either one
    // split across ranks would wedge the token rounds.
    static const char* const kSteadyPersistentChoices[] = {"auto", "off"};
    st.controller->SetSteadyPersistent(hvd::EnvChoiceSane(
        "HOROVOD_STEADY_PERSISTENT", 0, kSteadyPersistentChoices, 2));
  }
  hvd::Status s = st.controller->Initialize();
  // The pool's budget follows the controller's POST-SYNC value: rank
  // 0's knob (env or default) reaches every rank through the param
  // sync, the same discipline as the thresholds.
  hvd::SetHostReduceThreads(st.controller->reduce_threads());
  // Stagger co-located ranks' pinned crews across the allowed CPUs
  // (rank r's workers start r*threads slots in) so first-touch pages
  // and their reducers land per-rank-disjoint under `auto` affinity.
  hvd::WorkerPool::Get().ConfigureAffinity(
      local_rank * st.controller->reduce_threads());
  if (s.ok() && hvd::EnvFlag("HOROVOD_SHM_DISABLE") &&
      (st.controller->shm_enabled() ||
       st.controller->node_shm_applicable())) {
    // Deliberate (controller.h: the data-plane choice must be job-
    // wide), but silently ignoring a rank's env knob surprises people
    // debugging one rank — say so.
    LOG_WARNING << "HOROVOD_SHM_DISABLE is set on this rank but the "
                   "coordinator's synced verdict enables shm; the knob "
                   "must be set job-wide (rank 0 / --no-shm) to take "
                   "effect";
  }
  if (s.ok() && rank == 0) {
    using PM = hvd::ParameterManager;
    st.param_manager.SetHierarchicalTunable(
        st.controller->hierarchical_fit() && size > 1,
        st.controller->hierarchical());
    // Cache enablement and the shm data plane join the categorical
    // set (reference tunes the same switches,
    // parameter_manager.h:80-108). The flips ride the broadcast
    // ResponseList cycle-safely. Seed each with its EFFECTIVE state
    // (not the raw active flag, which defaults true even when the
    // feature is absent) so the CSV log reports the truth on jobs
    // where a switch is unavailable.
    st.param_manager.SetCategoricalTunable(
        PM::kCatCache, st.response_cache.capacity() > 0 && size > 1,
        st.response_cache.capacity() > 0 && size > 1 &&
            st.controller->cache_active());
    st.param_manager.SetCategoricalTunable(
        PM::kCatShm, st.controller->shm_enabled() && size > 1,
        st.controller->shm_enabled() && st.controller->shm_active());
    // Host data-plane knobs join the search: threads over [1, what
    // the machine can offer], pipeline depth only when a shm arena
    // is actually in play.
    const int hw = static_cast<int>(std::thread::hardware_concurrency());
    st.param_manager.SetHostTunables(
        st.controller->reduce_threads(),
        std::max(st.controller->reduce_threads(), std::min(16, hw)),
        st.controller->shm_segment_depth(),
        st.controller->shm_enabled() && size > 1);
    // Wire codec joins the search only when the operator already opted
    // into lossy wire via HOROVOD_WIRE_COMPRESSION (the ceiling): the
    // tuner may back off toward lossless, never add loss on its own.
    st.param_manager.SetWireTunable(
        size > 1 ? st.controller->wire_codec() : 0,
        st.controller->wire_codec());
    // The algorithm dimension joins the search only when the job runs
    // a real TCP plane and the operator left HOROVOD_COLLECTIVE_ALGO
    // on auto — the tuner explores the table's envelope, it never
    // fights an explicit force.
    st.param_manager.SetAlgoTunable(
        size > 1 && st.controller->collective_algo() == 0,
        st.controller->collective_algo());
  }
  if (!s.ok()) {
    LOG_ERROR << "controller init failed: " << s.reason();
    return -1;
  }
  // Membership plane (hvd/membership.h): install this incarnation's
  // epoch — the elastic driver's restart counter in the high bits —
  // and fence the stateful consumers on every subsequent change. The
  // fences only mutate state that is either mutex-guarded (topology
  // model) or owned by the thread the in-training advances run on
  // (the background loop detects dead peers and executes the JOIN
  // flush); an API-thread advance (serving router, tests) skips the
  // background-owned teardown it has no cycle racing against anyway.
  {
    auto& plane = hvd::MembershipPlane::Get();
    plane.Reset(hvd::EnvInt64Sane("HOROVOD_ELASTIC_EPOCH", 0, 0,
                                  (int64_t(1) << 42)),
                size);
    st.membership_fence_tokens.push_back(plane.RegisterFence(
        "topology", [&st](int reason, int64_t) {
          // A lost or shrunk world voids the measured verdicts: the
          // model priced links that may no longer exist. Drop it so
          // selection rides the hand bands until a re-probe (the
          // Join-shrunk rule; ResolveAlgoAuto's hostkey check backs
          // this up even for a model that slips through). The JOIN
          // flush restores the ORIGINAL full world, which the model
          // still describes — keep it there.
          if (reason != hvd::kMemberDeadPeer && reason != hvd::kMemberShrink)
            return;
          if (st.controller) st.controller->SetTopologyModel({});
        }));
    st.membership_fence_tokens.push_back(plane.RegisterFence(
        "response_cache", [&st](int reason, int64_t) {
          // The cache runs in coordinator lockstep; entries negotiated
          // under the old membership must not seed bits under the new
          // one. Background-thread-owned: only purge from the thread
          // the cycle runs on (dead-peer detection does).
          if (reason != hvd::kMemberDeadPeer) return;
          if (std::this_thread::get_id() !=
              st.background_thread_id.load(std::memory_order_relaxed))
            return;
          st.response_cache.Clear();
        }));
    st.membership_fence_tokens.push_back(plane.RegisterFence(
        "autotune_stage", [&st](int reason, int64_t) {
          // Staged-but-unbroadcast tunables were computed for the old
          // world; drop the stage instead of letting it cross the
          // epoch (the tuner re-stages from post-churn windows).
          if (reason != hvd::kMemberDeadPeer && reason != hvd::kMemberShrink)
            return;
          if (std::this_thread::get_id() !=
              st.background_thread_id.load(std::memory_order_relaxed))
            return;
          if (st.rank == 0 && st.controller)
            st.controller->StageTunedParams(0, 0.0);
        }));
  }
  if (size > 1) {
    st.host_ops = std::make_unique<hvd::TcpOps>(st.controller.get(),
                                                &st.fusion, &st.timeline);
  } else {
    st.host_ops = std::make_unique<hvd::LocalOps>(st.controller.get(),
                                                  &st.fusion, &st.timeline);
  }
  if (const char* tl = hvd::EnvStr("HOROVOD_TIMELINE"))
    st.timeline.Initialize(tl, rank);

  st.background_thread = std::thread([&st] { hvd::BackgroundThreadLoop(st); });
  st.initialized.store(true);
  LOG_INFO << "horovod_tpu core initialized: rank " << rank << "/" << size;
  return 0;
}

void hvd_shutdown() {
  auto& st = hvd::State();
  if (!st.initialized.load()) return;
  st.shutdown_requested.store(true);
  {
    // The background loop may be parked on the enqueue CV (idle or
    // locked-wait); wake it so the shutdown cycle runs promptly.
    std::lock_guard<std::mutex> g(st.wake_mu);
    st.wake_cv.notify_all();
  }
  if (st.background_thread.joinable()) st.background_thread.join();
  // Drop this incarnation's epoch fences: the plane outlives the core
  // (process-global), and the next hvd_init registers fresh ones bound
  // to the new controller.
  for (int tok : st.membership_fence_tokens)
    hvd::MembershipPlane::Get().UnregisterFence(tok);
  st.membership_fence_tokens.clear();
  st.initialized.store(false);
}

// v15 (wire formats unchanged): flight recorder (hvd/flight.h) — the
// hvd_flight_* surface (record / snapshot / dump / install /
// num_events / event_name / count / clear / set_enabled / enabled)
// over the always-on control-plane event ring, armed for fatal-signal
// auto-dump by HOROVOD_FLIGHT_DIR at library load.
// v14 (wire formats unchanged): alltoall schedule families — the
// HOROVOD_ALLTOALL_ALGO knob with the hvd_alltoall_* accessors and
// probes, and the Bruck table selected by the measured cost model;
// metrics v9 adds alltoall_measured_selects_total.
// v13 (wire formats unchanged): persistent locked data plane — the
// HOROVOD_STEADY_PERSISTENT knob (param field 16) with the
// hvd_steady_persistent accessor and the hvd_tcp_prepost_buffers
// gauge hook; metrics v8 adds ctrl_persistent_fires_total /
// ctrl_token_piggybacks_total and the tcp_prepost_buffers gauge.
// v12 (wire formats unchanged): membership plane — the
// hvd_membership_* accessors over hvd/membership.h's epoch / fence /
// active-rank state, the hvd_blacklist_* decay-blacklist surface, and
// the topology staleness hooks (hvd_topology_inject,
// hvd_algo_resolve_auto); metrics v7 adds membership_changes_total
// plus the membership_epoch and hosts_blacklisted gauges.
// v11: steady-state schedule lock (ResponseList wire v7 carries the
// LOCK engagement ring): hvd_steady_lock_engaged plus the
// hvd_lockdet_* period-detector test hooks; metrics v6 adds the
// ctrl_locked gauge, the ctrl_locks/_bypassed_responses/_unlocks_*
// counters, cycles_idle_total and the lock_fire_us histogram.
// v10: transport-rider surface (hvd_tcp_iouring_mode + _name,
// hvd_worker_affinity) and metrics v5 (tcp_iouring_batches_total,
// tcp_iouring_mode / worker_affinity gauges) — wire formats unchanged.
// v9: measured-topology surface (hvd_topology / hvd_topology_probe /
// hvd_algo_select_measured / hvd_algo_cost_us) + the extended
// any-collective builder hvd_build_coll_schedule — wire formats
// unchanged; the model rides the init-time param plane, not the
// per-cycle wire.
// v8: vectored-transport surface (hvd_tcp_sendv / hvd_tcp_recvv /
// hvd_tcp_send_frame / hvd_tcp_recv_frame over caller-owned fds,
// hvd_tcp_transport_mode + _name) — wire formats unchanged.
// v7: hvd_enqueue gained collective_algo; schedule-interpreter surface
// (hvd_build_schedule / hvd_algo_select / hvd_algo_name /
// hvd_collective_algo); Request/Response/ResponseList carry the
// collective-algorithm fields.
// Bump whenever the callback signatures or the wire format change; the
// Python bridge refuses to load a library whose version disagrees.
// v6: metrics registry surface (hvd_metrics_snapshot + name tables,
// layout versioned separately by kMetricsVersion), hvd_stalled_tensors,
// and hvd_start_timeline now returns an error code (restart-capable).
// v5: hvd_enqueue gained wire_codec; wire codec kernel entry points;
// Request/Response/ResponseList carry wire-compression fields. The
// authoritative constant lives in message.h next to the wire versions
// (tests/test_wire_abi.py pins all three against the Python shim).
int hvd_abi_version() { return hvd::kAbiVersion; }

int hvd_initialized() { return hvd::State().initialized.load() ? 1 : 0; }
int hvd_rank() { return hvd::State().rank; }
int hvd_size() { return hvd::State().size; }
int hvd_local_rank() { return hvd::State().local_rank; }
int hvd_local_size() { return hvd::State().local_size; }
int hvd_cross_rank() { return hvd::State().cross_rank; }
int hvd_cross_size() { return hvd::State().cross_size; }
int hvd_is_homogeneous() {
  auto& st = hvd::State();
  return st.size == st.local_size * st.cross_size ? 1 : 0;
}

void hvd_set_exec_callback(hvd::ExecCallback cb) {
  hvd::State().exec_cb = cb;
}
void hvd_set_alloc_callback(hvd::AllocCallback cb) {
  hvd::State().alloc_cb = cb;
}

// Generic enqueue. Returns handle >= 0, or -1 on immediate error (use
// hvd_last_enqueue_error for the message).
static thread_local std::string g_last_enqueue_error;

int64_t hvd_enqueue(int op_type, const char* name, int dtype,
                    const int64_t* shape, int ndim, const void* data,
                    void* output, int root_rank, int reduce_op,
                    double prescale, double postscale, const int64_t* splits,
                    int nsplits, int exec_mode, int64_t group_key,
                    int group_size, int wire_codec, int collective_algo) {
  auto& st = hvd::State();
  hvd::TensorTableEntry e;
  e.name = name;
  e.dtype = static_cast<hvd::DataType>(dtype);
  e.shape = hvd::TensorShape(std::vector<int64_t>(shape, shape + ndim));
  e.data = data;
  e.output = output;
  e.root_rank = root_rank;
  e.reduce_op = static_cast<hvd::ReduceOp>(reduce_op);
  e.prescale_factor = prescale;
  e.postscale_factor = postscale;
  if (splits && nsplits > 0)
    e.splits.assign(splits, splits + nsplits);
  e.exec_mode = static_cast<hvd::ExecMode>(exec_mode);
  e.group_key = group_key;
  e.group_size = group_size;
  e.wire_codec = static_cast<int8_t>(
      wire_codec < -1 || wire_codec > 3 ? -1 : wire_codec);
  e.collective_algo = static_cast<int8_t>(
      collective_algo < 0 || collective_algo >= hvd::kNumCollectiveAlgos
          ? 0
          : collective_algo);
  int64_t handle = st.handles.Allocate();
  e.handle = handle;
  e.callback = [&st, handle](const hvd::Status& s) {
    st.handles.MarkDone(handle, s);
  };
  hvd::Status s = hvd::EnqueueEntries({std::move(e)},
                                      static_cast<hvd::RequestType>(op_type));
  if (!s.ok()) {
    g_last_enqueue_error = s.reason();
    st.handles.Release(handle);
    return -1;
  }
  return handle;
}

const char* hvd_last_enqueue_error() { return g_last_enqueue_error.c_str(); }

int64_t hvd_join() {
  return hvd_enqueue(static_cast<int>(hvd::RequestType::JOIN), "join",
                     static_cast<int>(hvd::DataType::UINT8), nullptr, 0,
                     nullptr, nullptr, 0, 1, 1.0, 1.0, nullptr, 0, 0, -1, 0,
                     -1, 0);
}

int64_t hvd_barrier() {
  return hvd_enqueue(static_cast<int>(hvd::RequestType::BARRIER), "barrier",
                     static_cast<int>(hvd::DataType::UINT8), nullptr, 0,
                     nullptr, nullptr, 0, 1, 1.0, 1.0, nullptr, 0, 0, -1, 0,
                     -1, 0);
}

int hvd_poll(int64_t handle) {
  return hvd::State().handles.Poll(handle) ? 1 : 0;
}

// Returns: 0 ok, 1 timeout, negative = status error code.
int hvd_wait(int64_t handle, int timeout_ms, char* err_buf, int err_len) {
  hvd::Status s;
  if (!hvd::State().handles.Wait(handle, timeout_ms, &s)) return 1;
  if (s.ok()) return 0;
  if (err_buf && err_len > 0) {
    std::strncpy(err_buf, s.reason().c_str(), err_len - 1);
    err_buf[err_len - 1] = '\0';
  }
  return -static_cast<int>(s.type());
}

void hvd_release_handle(int64_t handle) {
  auto& st = hvd::State();
  st.handles.Release(handle);
  hvd::MutexLock lock(st.recvsplits_mu);
  st.recvsplits.erase(handle);
}

// Copies the alltoall recv splits recorded for `handle`; returns count.
int hvd_get_recvsplits(int64_t handle, int64_t* out, int max_n) {
  auto& st = hvd::State();
  hvd::MutexLock lock(st.recvsplits_mu);
  auto it = st.recvsplits.find(handle);
  if (it == st.recvsplits.end()) return 0;
  int n = static_cast<int>(it->second.size());
  if (out) {
    for (int i = 0; i < n && i < max_n; ++i) out[i] = it->second[i];
  }
  return n;
}

// Completion path for the Python/XLA executor.
void hvd_exec_done(int64_t exec_id, int status_code, const char* err) {
  auto& st = hvd::State();
  hvd::PendingExec pe;
  {
    hvd::MutexLock lock(st.exec_mu);
    auto it = st.pending_execs.find(exec_id);
    if (it == st.pending_execs.end()) return;
    pe = std::move(it->second);
    st.pending_execs.erase(it);
  }
  hvd::Status s = status_code == 0
                      ? hvd::Status::OK()
                      : hvd::Status::UnknownError(err ? err : "exec failed");
  // Close the timeline span opened in PerformOperation — also on a
  // joined rank whose launch had no local entries (tname came from the
  // response there too).
  if (!pe.response.tensor_names.empty()) {
    const std::string& tname = pe.response.tensor_names.front();
    st.timeline.ActivityEnd(tname);
    st.timeline.End(tname, 0);
  }
  // Alltoall recvsplits for CALLBACK entries.
  if (pe.response.response_type == hvd::ResponseType::ALLTOALL) {
    for (auto& e : pe.entries) {
      e.recvsplits.clear();
      for (int k = 0; k < st.size; ++k)
        e.recvsplits.push_back(
            pe.response
                .recvsplits[static_cast<size_t>(st.rank) * st.size + k]);
    }
  }
  for (auto& e : pe.entries) hvd::CompleteEntry(st, e, s);
}

// Starts — or RESTARTS onto a new path — the host timeline. Returns 0
// on success, -1 when the file cannot be opened (surfaced as a Python
// exception; the silent void no-op this used to be left
// start_timeline(new_path) on a running timeline doing nothing).
int hvd_start_timeline(const char* path) {
  auto& st = hvd::State();
  return st.timeline.Initialize(path, st.rank) ? 0 : -1;
}

void hvd_stop_timeline() { hvd::State().timeline.Shutdown(); }

// Test hook: number of tensors currently in flight.
int64_t hvd_pending_count() {
  return static_cast<int64_t>(hvd::State().tensor_queue.size());
}

// ---------------------------------------------------------------------------
// Metrics (hvd/metrics.h): versioned packed snapshot + name tables,
// consumed by horovod_tpu/metrics.py. Layout pinned by
// tests/test_metrics_abi.py (same discipline as the wire constants).
// ---------------------------------------------------------------------------

int64_t hvd_metrics_snapshot(int64_t* out, int64_t max_slots) {
  auto& st = hvd::State();
  auto& reg = hvd::MetricsRegistry::Get();
  // Point-in-time gauges are filled fresh per snapshot; everything
  // else in the registry is already live.
  reg.Set(hvd::kGaugePendingTensors,
          static_cast<int64_t>(st.tensor_queue.size()));
  reg.Set(hvd::kGaugeStalledTensors,
          static_cast<int64_t>(st.stall_inspector.Report(st.size).size()));
  reg.Set(hvd::kGaugeReduceThreads, hvd::HostReduceThreads());
  // Deliberate: this resolves the transport mode (one-time end-to-end
  // probe) so the gauge always reads the real verdict — the operator
  // contract is "the chosen mode is visible in hvd.metrics()". On a
  // real kernel the probe settles in microseconds (reject or deliver);
  // only this completion-less sandbox pays its ~40 ms poll bound, once
  // per metrics-reading process.
  reg.Set(hvd::kGaugeTcpZerocopyMode, hvd::ResolvedTransportMode());
  reg.Set(hvd::kGaugeTcpIouringMode, hvd::ResolvedIouringMode());
  reg.Set(hvd::kGaugeWorkerAffinity,
          hvd::WorkerPool::Get().PinnedWorkers());
  reg.Set(hvd::kGaugeTopoProbeMs,
          static_cast<int64_t>(hvd::TopologyProbeMs()));
  // Links reflect the LIVE model (a cache-loaded model measured them
  // in an earlier job), not merely this process's last probe.
  int64_t links = 0;
  if (st.controller) {
    if (auto m = st.controller->topology_model())
      links = static_cast<int64_t>(m->np) * (m->np - 1);
  }
  reg.Set(hvd::kGaugeTopoLinks, links);
  reg.Set(hvd::kGaugeCtrlLocked,
          st.controller && st.controller->lock_engaged() ? 1 : 0);
  {
    auto& plane = hvd::MembershipPlane::Get();
    reg.Set(hvd::kGaugeMembershipEpoch, plane.epoch());
    // steady_clock shares CLOCK_MONOTONIC with Python's
    // time.monotonic() (membership.h), so driver-recorded flap stamps
    // decay on the same axis this snapshot reads.
    const double now_s =
        std::chrono::duration<double>(
            std::chrono::steady_clock::now().time_since_epoch())
            .count();
    reg.Set(hvd::kGaugeHostsBlacklisted, plane.BlacklistedCount(now_s));
  }
  // Pre-posted recv buffers: only meaningful while the lock is
  // engaged — the compiled plan dies with the lock session, so the
  // gauge reads 0 the moment the job falls back to negotiation.
  reg.Set(hvd::kGaugeTcpPrepostBuffers,
          st.controller && st.controller->lock_engaged()
              ? hvd::PrepostBufferGauge()
              : 0);
  return reg.Snapshot(out, max_slots);
}

int hvd_metrics_version() { return hvd::kMetricsVersion; }
int hvd_metrics_num_counters() { return hvd::kNumMetricCounters; }
int hvd_metrics_num_hists() { return hvd::kNumMetricHistograms; }
int hvd_metrics_hist_buckets() { return hvd::kMetricsHistBuckets; }
const char* hvd_metrics_counter_name(int i) {
  return hvd::MetricCounterName(i);
}
int hvd_metrics_counter_kind(int i) { return hvd::MetricCounterKind(i); }
const char* hvd_metrics_hist_name(int i) {
  return hvd::MetricHistogramName(i);
}
void hvd_metrics_reset() { hvd::MetricsRegistry::Get().Reset(); }
// Runtime enable switch: lets the overhead guard time the identical
// workload with observations on vs off (off short-circuits even the
// timer clock reads).
void hvd_metrics_set_enabled(int on) {
  hvd::MetricsRegistry::Get().SetEnabled(on != 0);
}
int hvd_metrics_enabled() {
  return hvd::MetricsRegistry::Get().enabled() ? 1 : 0;
}
// Test hooks: drive the registry directly so bucketing and
// concurrent-increment behavior are unit-testable through ctypes.
void hvd_metrics_test_add(int counter, int64_t v) {
  if (counter >= 0 && counter < hvd::kNumMetricCounters)
    hvd::MetricAdd(static_cast<hvd::MetricCounter>(counter), v);
}
void hvd_metrics_test_observe(int hist, int64_t v) {
  if (hist >= 0 && hist < hvd::kNumMetricHistograms)
    hvd::MetricObserve(static_cast<hvd::MetricHistogram>(hist), v);
}

// StallInspector findings beyond the log: tab-separated lines
// "name\tage_secs\tmissing_rank,missing_rank,...\n" for every tensor
// past the warning age. Tensor names are arbitrary user strings, so
// backslash/tab/newline in the name are backslash-escaped — the Python
// parser (horovod_tpu/metrics.py stalled_tensors) unescapes; a name
// containing a separator must not break the very accessor used to
// diagnose its stall. Coordinator-rank data (workers have no pending
// table). Returns the byte count needed INCLUDING the NUL; copies at
// most len-1 bytes.
int hvd_stalled_tensors(char* buf, int len) {
  auto& st = hvd::State();
  auto report = st.stall_inspector.Report(st.size);
  std::string out;
  for (const auto& s : report) {
    for (char c : s.name) {
      switch (c) {
        case '\\': out += "\\\\"; break;
        case '\t': out += "\\t"; break;
        case '\n': out += "\\n"; break;
        default: out += c;
      }
    }
    out += '\t';
    out += std::to_string(s.age_secs);
    out += '\t';
    for (size_t i = 0; i < s.missing_ranks.size(); ++i) {
      if (i) out += ',';
      out += std::to_string(s.missing_ranks[i]);
    }
    out += '\n';
  }
  if (buf != nullptr && len > 0) {
    std::strncpy(buf, out.c_str(), len - 1);
    buf[len - 1] = '\0';
  }
  return static_cast<int>(out.size()) + 1;
}

// ---------------------------------------------------------------------------
// Flight recorder (hvd/flight.h): always-on control-plane event ring,
// dumped as a postmortem by fatal-signal handlers / the stall-breach
// path / HorovodInternalError. Consumed by horovod_tpu/metrics.py
// (hvd.flight_events()) and merged by bin/hvd-trace.
// ---------------------------------------------------------------------------

void hvd_flight_record(int event, long long a0, long long a1) {
  if (event < 0 || event >= hvd::kNumFlightEvents) return;
  hvd::FlightRecord(static_cast<hvd::FlightEvent>(event), a0, a1);
}

// Size-probe text protocol (hvd_stalled_tensors discipline): returns
// the byte count needed INCLUDING the NUL, copies at most len-1.
long long hvd_flight_snapshot(char* buf, long long len) {
  return hvd::FlightRecorder::Get().SnapshotText(buf, len);
}

// path == NULL/"" dumps to the HOROVOD_FLIGHT_DIR auto-dump path.
int hvd_flight_dump(const char* path) {
  return hvd::FlightRecorder::Get().DumpFile(path);
}

int hvd_flight_install(const char* dir) {
  return hvd::FlightRecorder::Get().InstallAutoDump(dir);
}

int hvd_flight_num_events() { return hvd::kNumFlightEvents; }
const char* hvd_flight_event_name(int i) { return hvd::FlightEventName(i); }
long long hvd_flight_count() {
  return hvd::FlightRecorder::Get().count();
}
void hvd_flight_clear() { hvd::FlightRecorder::Get().Clear(); }
void hvd_flight_set_enabled(int on) {
  hvd::FlightRecorder::Get().SetEnabled(on != 0);
}
int hvd_flight_enabled() {
  return hvd::FlightRecorder::Get().enabled() ? 1 : 0;
}

// Direct host-kernel entry points: the dtype/op matrix is verified
// against numpy references through ctypes (tests/test_host_kernels.py)
// — including the threaded chunked path, which must be bitwise
// identical to single-threaded at every size.
void hvd_host_accumulate(int op, int dtype, const void* src, void* dst,
                         int64_t count) {
  hvd::HostAccumulate(static_cast<hvd::ReduceOp>(op),
                      static_cast<hvd::DataType>(dtype), src, dst, count);
}

void hvd_host_scale(int dtype, void* dst, int64_t count, double factor) {
  hvd::HostScale(static_cast<hvd::DataType>(dtype), dst, count, factor);
}

void hvd_set_reduce_threads(int n) { hvd::SetHostReduceThreads(n); }
int hvd_reduce_threads() { return hvd::HostReduceThreads(); }

// Schedule-interpreter surface (hvd/schedule.h): the chunk-op tables
// and the default selection table are pure functions, exposed so the
// Python simulator tests can verify every generated schedule
// (complete, deadlock-free, chunk-conserving) without spawning ranks.

// Fills out[] with int32 quintets (step, peer, chunk, action, flags)
// for rank position `pos` of `nranks`. Returns the op count (callers
// pass out=nullptr to size the buffer); writes *nsteps/*nchunks.
int hvd_build_schedule(int algo, int nranks, int pos, int* nsteps,
                       int* nchunks, int32_t* out, int max_ops) {
  hvd::ChunkSchedule s = hvd::BuildSchedule(algo, nranks, pos);
  if (nsteps) *nsteps = s.nsteps;
  if (nchunks) *nchunks = s.nchunks;
  if (out) {
    int n = std::min<int>(max_ops, static_cast<int>(s.ops.size()));
    for (int i = 0; i < n; ++i) {
      out[i * 5 + 0] = s.ops[i].step;
      out[i * 5 + 1] = s.ops[i].peer;
      out[i * 5 + 2] = s.ops[i].chunk;
      out[i * 5 + 3] = static_cast<int32_t>(s.ops[i].action);
      out[i * 5 + 4] = s.ops[i].flags;
    }
  }
  return static_cast<int>(s.ops.size());
}

// Extended builder (ABI v9): any collective KIND (hvd/schedule.h
// CollKind) plus the synthesis parameters — the surface
// tools/synth.py's sketch-guided search and the promoted verifier
// enumerate. Same quintet layout as hvd_build_schedule.
int hvd_build_coll_schedule(int kind, int algo, int nranks, int pos,
                            int stripes, int granularity, int hd_order,
                            int* nsteps, int* nchunks, int32_t* out,
                            int max_ops) {
  hvd::ChunkSchedule s = hvd::BuildCollSchedule(
      kind, algo, nranks, pos, stripes, granularity, hd_order);
  if (nsteps) *nsteps = s.nsteps;
  if (nchunks) *nchunks = s.nchunks;
  if (out) {
    int n = std::min<int>(max_ops, static_cast<int>(s.ops.size()));
    for (int i = 0; i < n; ++i) {
      out[i * 5 + 0] = s.ops[i].step;
      out[i * 5 + 1] = s.ops[i].peer;
      out[i * 5 + 2] = s.ops[i].chunk;
      out[i * 5 + 3] = static_cast<int32_t>(s.ops[i].action);
      out[i * 5 + 4] = s.ops[i].flags;
    }
  }
  return static_cast<int>(s.ops.size());
}

// Default selection-table query (no controller state: callers pass the
// synced inputs, so tests can probe any (bytes, np, topology)
// cell).
int hvd_algo_select(int64_t bytes, int np, int hier_ok,
                    int64_t ring_threshold) {
  return hvd::ResolveAlgoDefault(bytes, np, hier_ok != 0, ring_threshold);
}

// Measured-model verdict for one (bytes, np) cell using THIS process's
// broadcast topology model (the audit comparison). Returns -1 when no
// model covers np — callers fall back to hvd_algo_select's hand bands.
int hvd_algo_select_measured(int64_t bytes, int np, int hier_ok,
                             int64_t ring_threshold) {
  auto& st = hvd::State();
  if (!st.controller) return -1;
  auto m = st.controller->topology_model();
  if (m == nullptr || m->np != np) return -1;
  return hvd::ResolveAlgoMeasured(
      bytes, np, hier_ok != 0, ring_threshold, *m,
      st.controller->collective_stripes(),
      st.controller->collective_granularity(), st.controller->hd_order());
}

// Alpha-beta cost (us) of one candidate's table family at `bytes`
// under the live model; <0 when no model. tools/synth.py uses this to
// cross-check its Python cost walk against the native one.
double hvd_algo_cost_us(int algo, int64_t bytes, int stripes,
                        int granularity, int hd_order) {
  auto& st = hvd::State();
  if (!st.controller) return -1.0;
  auto m = st.controller->topology_model();
  if (m == nullptr) return -1.0;
  const double c =
      hvd::AlgoCostUs(algo, bytes, *m, stripes, granularity, hd_order);
  return c >= 1e18 ? -1.0 : c;
}

// Measured topology accessor: fills alpha[np*np] (us) and
// beta[np*np] (us/byte) when cap >= np*np; returns the model's np (0 =
// no model). Every rank holds the identical broadcast numbers.
int hvd_topology(double* alpha, double* beta, int cap) {
  auto& st = hvd::State();
  if (!st.controller) return 0;
  auto m = st.controller->topology_model();
  if (m == nullptr) return 0;
  const int n2 = m->np * m->np;
  if (alpha != nullptr && beta != nullptr && cap >= n2) {
    for (int i = 0; i < n2; ++i) {
      alpha[i] = m->alpha_us[i];
      beta[i] = m->beta_us_per_byte[i];
    }
  }
  return m->np;
}

// On-demand re-probe. COLLECTIVE CONTRACT: every rank must call this
// with no collectives in flight — the probe ping-pongs over the data
// links the exchanges use (the same quiet-plane discipline as
// hvd_shutdown's drain). Returns the probe wall-clock in ms, or -1 on
// failure (all ranks then agree there is no model). Rank 0 rewrites
// the disk cache so subsequent jobs start from the fresh measurement.
double hvd_topology_probe() {
  auto& st = hvd::State();
  if (!st.controller || st.size <= 1) return -1.0;
  double ms = -1.0;
  hvd::TopologyModel m = hvd::ProbeTopology(st.controller.get(), &ms);
  const bool ok = m.valid();
  if (ok && st.rank == 0)
    hvd::StoreTopologyCache(
        m, hvd::TopologyHostKey(st.size, st.local_size));
  st.controller->SetTopologyModel(std::move(m));
  return ok ? ms : -1.0;
}

// ---- membership plane (ABI v12; hvd/membership.h) ----
// All usable BEFORE hvd_init: the plane is a process-global singleton
// so the elastic driver and the serving router ride the same accessor
// (hvd.membership()) from processes that never init the core.

int64_t hvd_membership_epoch() {
  return hvd::MembershipPlane::Get().epoch();
}
int64_t hvd_membership_generation() {
  return hvd::MembershipPlane::Get().generation();
}
int hvd_membership_size() { return hvd::MembershipPlane::Get().size(); }
// Fills out[] with the active rank ids (cap permitting); returns the
// active count.
int hvd_membership_ranks(int* out, int cap) {
  const auto ranks = hvd::MembershipPlane::Get().active_ranks();
  const int n = static_cast<int>(ranks.size());
  if (out != nullptr) {
    for (int i = 0; i < n && i < cap; ++i) out[i] = ranks[i];
  }
  return n;
}
// Explicit advance (serving router replica churn, tests). In-training
// advances come from the coordination loop (JOIN flush, dead peers) —
// this entry point must NOT be called mid-training on a subset of
// ranks or their epochs diverge.
int64_t hvd_membership_advance(int reason, int rank) {
  return hvd::MembershipPlane::Get().Advance(reason, rank);
}
void hvd_membership_reset(int64_t external_epoch, int size) {
  hvd::MembershipPlane::Get().Reset(external_epoch, size);
}
int hvd_membership_fence_count() {
  return hvd::MembershipPlane::Get().fence_count();
}

// Decay blacklist (per-host flap history). now_s is caller-supplied
// CLOCK_MONOTONIC seconds (time.monotonic() in Python), making the
// decay model deterministic under test-driven timestamps.
void hvd_blacklist_configure(double threshold, double half_life_s) {
  hvd::MembershipPlane::Get().BlacklistConfigure(threshold, half_life_s);
}
double hvd_blacklist_record(const char* host, double now_s) {
  return hvd::MembershipPlane::Get().BlacklistRecord(
      host ? host : "", now_s);
}
double hvd_blacklist_weight(const char* host, double now_s) {
  return hvd::MembershipPlane::Get().BlacklistWeight(
      host ? host : "", now_s);
}
int hvd_blacklist_check(const char* host, double now_s) {
  return hvd::MembershipPlane::Get().Blacklisted(host ? host : "", now_s)
             ? 1
             : 0;
}
int hvd_blacklist_count(double now_s) {
  return hvd::MembershipPlane::Get().BlacklistedCount(now_s);
}
void hvd_blacklist_clear() { hvd::MembershipPlane::Get().BlacklistClear(); }

// Topology staleness hooks (ABI v12): install a serialized model with
// NO key gate (hvd_lockdet_*-style test surface — lets a test stand in
// a model whose stored hostkey predates a membership change) and read
// the auto-resolution verdict, so ResolveAlgoAuto's refuse-stale-key
// rule is pinnable without faking a whole elastic restart.
int hvd_topology_inject(const char* blob) {
  auto& st = hvd::State();
  if (!st.controller || blob == nullptr) return 0;
  hvd::TopologyModel m = hvd::ParseTopology(blob, "");
  const int np = m.valid() ? m.np : 0;
  st.controller->SetTopologyModel(std::move(m));
  return np;
}
int hvd_algo_resolve_auto(int64_t bytes, int ncontributors, int hier_ok) {
  auto& st = hvd::State();
  if (!st.controller) return -1;
  return st.controller->ResolveAlgoAuto(bytes, ncontributors, hier_ok != 0);
}

const char* hvd_algo_name(int algo) { return hvd::CollectiveAlgoName(algo); }

// The live job-wide force (0 = auto/table) after env parse, param
// sync, and any autotuner retarget.
int hvd_collective_algo() {
  auto& st = hvd::State();
  return st.controller ? st.controller->collective_algo() : 0;
}

const char* hvd_alltoall_algo_name(int algo) {
  return hvd::AlltoallAlgoName(algo);
}

// The live job-wide alltoall family force (0 = measured verdict)
// after env parse and param sync.
int hvd_alltoall_algo() {
  auto& st = hvd::State();
  return st.controller ? st.controller->alltoall_algo() : 0;
}

// Alpha-beta cost (us) of one alltoall family's P tables at TOTAL
// exchanged bytes under the live model; <0 when no model. The
// selection tests use this to cross-check the measured verdict
// against the priced tables.
double hvd_alltoall_cost_us(int algo, int64_t bytes) {
  auto& st = hvd::State();
  if (!st.controller) return -1.0;
  auto m = st.controller->topology_model();
  if (m == nullptr) return -1.0;
  const double c = hvd::AlltoallAlgoCostUs(algo, bytes, *m);
  return c >= 1e18 ? -1.0 : c;
}

// Point-to-point migration pricing (docs/serving.md "Direct
// migration"): alpha-beta cost (us) of one span src -> dst under the
// live model, and the chunked-stream generalization the serving
// router's chunk planner sweeps. Both <0 when no model / bad args —
// the Python cost twin (horovod_tpu/serve/migrate.py) then stands
// alone, and the sanitizer tier cross-checks the pair bit-for-bit
// whenever a model exists.
double hvd_link_cost_us(int src, int dst, int64_t bytes) {
  auto& st = hvd::State();
  if (!st.controller) return -1.0;
  auto m = st.controller->topology_model();
  if (m == nullptr) return -1.0;
  const double c = hvd::LinkCostUs(*m, src, dst, bytes);
  return c >= 1e18 ? -1.0 : c;
}

double hvd_migration_cost_us(int src, int dst, int64_t bytes,
                             int64_t n_chunks) {
  auto& st = hvd::State();
  if (!st.controller) return -1.0;
  auto m = st.controller->topology_model();
  if (m == nullptr) return -1.0;
  const double c = hvd::MigrationCostUs(*m, src, dst, bytes, n_chunks);
  return c >= 1e18 ? -1.0 : c;
}

// Measured-model alltoall verdict for one (total bytes, np) cell using
// THIS process's broadcast topology model. Returns -1 when no model
// covers np — the coordinator then serves pairwise.
int hvd_alltoall_select_measured(int64_t bytes, int np) {
  auto& st = hvd::State();
  if (!st.controller) return -1;
  auto m = st.controller->topology_model();
  if (m == nullptr || m->np != np) return -1;
  return hvd::ResolveAlltoallMeasured(bytes, np, *m);
}

// Wire-codec kernel entry points (tests/test_host_kernels.py drives
// the encode/decode matrix — incl. error feedback and thread-count
// bitwise invariance — against numpy models through ctypes).
int64_t hvd_wire_encoded_bytes(int codec, int64_t elems) {
  return hvd::WireEncodedBytes(static_cast<hvd::WireCodec>(codec), elems);
}
void hvd_wire_encode(int codec, const float* src, int64_t elems,
                     uint8_t* dst, float* residual) {
  hvd::WireEncode(static_cast<hvd::WireCodec>(codec), src, elems, dst,
                  residual);
}
void hvd_wire_decode(int codec, const uint8_t* src, int64_t elems,
                     float* dst) {
  hvd::WireDecode(static_cast<hvd::WireCodec>(codec), src, elems, dst);
}
void hvd_wire_decode_add(int codec, const uint8_t* src, int64_t elems,
                         float* dst) {
  hvd::WireDecodeAdd(static_cast<hvd::WireCodec>(codec), src, elems, dst);
}

// Vectored-transport entry points (ABI v8): wrap caller-owned fds
// (socketpair halves in tests/test_transport.py) in a non-owning
// TcpConn and drive the REAL SendV/RecvV/frame paths — split reads,
// EINTR retries, iovec windowing and the metrics accounting are
// exercised exactly as the data plane runs them. The fds stay the
// caller's (Detach before the conn destructs).
int hvd_tcp_sendv(int fd, void* const* bufs, const uint64_t* lens, int n) {
  std::vector<struct iovec> iov(static_cast<size_t>(n > 0 ? n : 0));
  for (int i = 0; i < n; ++i)
    iov[i] = {bufs[i], static_cast<size_t>(lens[i])};
  hvd::TcpConn conn(fd);
  const bool ok = conn.SendV(iov.data(), n);
  conn.Detach();
  return ok ? 1 : 0;
}

int hvd_tcp_recvv(int fd, void* const* bufs, const uint64_t* lens, int n) {
  std::vector<struct iovec> iov(static_cast<size_t>(n > 0 ? n : 0));
  for (int i = 0; i < n; ++i)
    iov[i] = {bufs[i], static_cast<size_t>(lens[i])};
  hvd::TcpConn conn(fd);
  const bool ok = conn.RecvV(iov.data(), n);
  conn.Detach();
  return ok ? 1 : 0;
}

int hvd_tcp_send_frame(int fd, const void* data, uint64_t len) {
  hvd::TcpConn conn(fd);
  const bool ok = conn.SendFrame(data, len);
  conn.Detach();
  return ok ? 1 : 0;
}

// Returns the frame length (which may exceed max_len — the copied
// prefix is then truncated), or -1 on socket error/EOF.
int64_t hvd_tcp_recv_frame(int fd, void* out, uint64_t max_len) {
  hvd::TcpConn conn(fd);
  std::string s;
  const bool ok = conn.RecvFrame(&s);
  conn.Detach();
  if (!ok) return -1;
  std::memcpy(out, s.data(), std::min<uint64_t>(s.size(), max_len));
  return static_cast<int64_t>(s.size());
}

int hvd_tcp_transport_mode() { return hvd::ResolvedTransportMode(); }

const char* hvd_tcp_transport_mode_name() {
  return hvd::TransportModeName(hvd::ResolvedTransportMode());
}

int hvd_tcp_iouring_mode() { return hvd::ResolvedIouringMode(); }

const char* hvd_tcp_iouring_mode_name() {
  return hvd::IouringModeName(hvd::ResolvedIouringMode());
}

// Worker threads currently CPU-pinned (the worker_affinity gauge; 0
// under HOROVOD_REDUCE_THREAD_AFFINITY=off, and until the pool's lazy
// workers have actually spawned).
int hvd_worker_affinity() { return hvd::WorkerPool::Get().PinnedWorkers(); }

// Steady-state lock state (docs/perf_tuning.md "Steady-state schedule
// lock"): 1 while this rank runs the negotiation-bypass plane. Also a
// gauge (ctrl_locked) so dashboards see it without the ABI call.
int hvd_steady_lock_engaged() {
  auto& st = hvd::State();
  return st.controller && st.controller->lock_engaged() ? 1 : 0;
}

// Persistent locked data plane (docs/perf_tuning.md "Persistent
// locked data plane"): the resolved HOROVOD_STEADY_PERSISTENT knob
// (0 = auto, 1 = off — the coordinator-synced value, not the local
// env wish) and the live pre-posted recv buffer count.
int hvd_steady_persistent() {
  auto& st = hvd::State();
  return st.controller ? st.controller->steady_persistent() : 0;
}
int64_t hvd_tcp_prepost_buffers() { return hvd::PrepostBufferGauge(); }

// Test hooks: drive the period detector (hvd/steady_lock.h) without
// spawning ranks — tests/test_steady_lock.py pins the K/period/reset
// semantics the coordinator's engage decision is built on. Each feed
// is one cycle carrying a single synthetic response named `name`
// (NULL/empty = an empty cycle, which must neither extend nor break a
// window).
void* hvd_lockdet_create() { return new hvd::LockDetector(); }
void hvd_lockdet_feed(void* h, int pure, const char* name) {
  std::vector<hvd::Response> responses;
  if (name != nullptr && name[0] != '\0') {
    hvd::Response r;
    r.tensor_names = {name};
    responses.push_back(std::move(r));
  }
  static_cast<hvd::LockDetector*>(h)->FeedCycle(pure != 0, responses);
}
int hvd_lockdet_ready(void* h) {
  return static_cast<hvd::LockDetector*>(h)->Ready() ? 1 : 0;
}
int hvd_lockdet_period(void* h) {
  return static_cast<hvd::LockDetector*>(h)->period();
}
// Returns the detected ring's response count and resets the detector.
int hvd_lockdet_take(void* h) {
  return static_cast<int>(
      static_cast<hvd::LockDetector*>(h)->TakeRing().size());
}
void hvd_lockdet_destroy(void* h) {
  delete static_cast<hvd::LockDetector*>(h);
}

// Test hooks: drive the Bayesian autotune optimizer (hvd/bayesian.h)
// against a caller-provided objective, so tests can assert global
// convergence properties the x2 hill climb lacks.
void* hvd_bayes_create(int n_cont, int n_cat, uint64_t seed) {
  return new hvd::BayesianOptimizer(n_cont, n_cat, seed);
}
void hvd_bayes_add(void* h, const double* x, int n, double y) {
  static_cast<hvd::BayesianOptimizer*>(h)->AddSample(
      std::vector<double>(x, x + n), y);
}
void hvd_bayes_next(void* h, double* x_out, int n) {
  auto x = static_cast<hvd::BayesianOptimizer*>(h)->NextCandidate();
  for (int i = 0; i < n && i < static_cast<int>(x.size()); ++i)
    x_out[i] = x[i];
}
double hvd_bayes_best(void* h, double* x_out, int n) {
  double score = 0.0;
  auto x = static_cast<hvd::BayesianOptimizer*>(h)->Best(&score);
  for (int i = 0; i < n && i < static_cast<int>(x.size()); ++i)
    x_out[i] = x[i];
  return score;
}
void hvd_bayes_destroy(void* h) {
  delete static_cast<hvd::BayesianOptimizer*>(h);
}

}  // extern "C"
