#include "hvd/tcp.h"

#include <arpa/inet.h>
#include <netdb.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <fcntl.h>
#include <sys/mman.h>
#include <sys/socket.h>
#include <sys/syscall.h>
#include <unistd.h>

#include <algorithm>
#include <chrono>
#include <cstring>
#include <thread>

#include "hvd/env.h"
#include "hvd/logging.h"
#include "hvd/metrics.h"

// MSG_ZEROCOPY plumbing (kernel >= 4.14). The toolchain headers on
// this container predate the feature, so the constants are defined
// here when missing — the runtime probe below, not the build host,
// decides whether the path is live.
#ifndef SO_ZEROCOPY
#define SO_ZEROCOPY 60
#endif
#ifndef SO_EE_ORIGIN_ZEROCOPY
#define SO_EE_ORIGIN_ZEROCOPY 5
#endif
#ifndef MSG_ZEROCOPY
#define MSG_ZEROCOPY 0x4000000
#endif
#if defined(__linux__) && __has_include(<linux/errqueue.h>)
#include <linux/errqueue.h>
#define HVD_HAS_ERRQUEUE 1
#endif

namespace hvd {
namespace {
// Handshake ack word: proves the accepting socket is actually a peer
// of THIS framework — a NAT catch-all or stray service that accepts
// the TCP connection but never acks is rejected within the dial slice
// instead of wedging the mesh bootstrap.
constexpr int32_t kHelloAck = 0x48564441;  // "HVDA"
}  // namespace
}  // namespace hvd


namespace hvd {

namespace {

bool SplitAddr(const std::string& addr, std::string* host, int* port) {
  auto pos = addr.rfind(':');
  if (pos == std::string::npos) return false;
  *host = addr.substr(0, pos);
  *port = std::atoi(addr.c_str() + pos + 1);
  return true;
}

void SetNoDelay(int fd) {
  int one = 1;
  setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
}

}  // namespace

namespace {
// Spans per syscall window: SendV/RecvV copy the caller's (const)
// iovec table into a stack window this size and let the kernel drain
// it — far below IOV_MAX, large enough that even a many-tensor fused
// allgather block rarely needs a second window.
constexpr int kIovWindow = 64;
// MSG_ZEROCOPY floor: below this the page-pin + completion round trip
// costs more than the copy it saves (the kernel's own guidance is
// ~10 KB; we stay conservative since loopback often degrades to the
// COPIED completion anyway — see docs/perf_tuning.md).
constexpr uint64_t kZcMinBytes = 64 * 1024;

uint64_t IovBytes(const struct iovec* iov, int n) {
  uint64_t total = 0;
  for (int i = 0; i < n; ++i) total += iov[i].iov_len;
  return total;
}
}  // namespace

#ifdef HVD_HAS_ERRQUEUE
namespace {
// END-TO-END zerocopy probe: one real MSG_ZEROCOPY send over a
// loopback TCP pair whose completion must actually arrive on the
// error queue. Merely accepting SO_ZEROCOPY proves nothing — this
// container's sandboxed 4.4-era kernel ACCEPTS the option and then
// never posts a completion, which would wedge every large send in
// the reap loop. Anything short of a delivered completion within the
// deadline means "feature absent".
bool ProbeZerocopy() {
  int lfd = ::socket(AF_INET, SOCK_STREAM, 0);
  if (lfd < 0) return false;
  sockaddr_in sa{};
  sa.sin_family = AF_INET;
  sa.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
  socklen_t slen = sizeof(sa);
  bool ok = false;
  int cfd = -1, afd = -1;
  do {
    if (::bind(lfd, reinterpret_cast<sockaddr*>(&sa), sizeof(sa)) != 0 ||
        ::listen(lfd, 1) != 0 ||
        getsockname(lfd, reinterpret_cast<sockaddr*>(&sa), &slen) != 0)
      break;
    cfd = ::socket(AF_INET, SOCK_STREAM, 0);
    if (cfd < 0) break;
    int one = 1;
    if (setsockopt(cfd, SOL_SOCKET, SO_ZEROCOPY, &one, sizeof(one)) != 0)
      break;
    if (::connect(cfd, reinterpret_cast<sockaddr*>(&sa), sizeof(sa)) != 0)
      break;
    afd = ::accept(lfd, nullptr, nullptr);
    if (afd < 0) break;
    char payload[4096] = {};
    struct iovec iov{payload, sizeof(payload)};
    msghdr msg{};
    msg.msg_iov = &iov;
    msg.msg_iovlen = 1;
    if (::sendmsg(cfd, &msg, MSG_NOSIGNAL | MSG_ZEROCOPY) !=
        static_cast<ssize_t>(sizeof(payload)))
      break;
    char sink[4096];
    for (size_t got = 0; got < sizeof(payload);) {
      ssize_t k = ::recv(afd, sink, sizeof(sink), 0);
      if (k <= 0) break;
      got += static_cast<size_t>(k);
    }
    // A real kernel posts the loopback completion at skb-free time —
    // microseconds after the peer's recv above — so a tight deadline
    // suffices, and a completion-less sandbox costs every process only
    // ~40 ms once, not a long stall (tier-1 spawns hundreds of ranks).
    for (int spin = 0; spin < 2 && !ok; ++spin) {
      pollfd p{cfd, 0, 0};
      ::poll(&p, 1, 20);
      char ctrl[128];
      msghdr em{};
      em.msg_control = ctrl;
      em.msg_controllen = sizeof(ctrl);
      if (::recvmsg(cfd, &em, MSG_ERRQUEUE) < 0) continue;
      for (cmsghdr* cm = CMSG_FIRSTHDR(&em); cm != nullptr;
           cm = CMSG_NXTHDR(&em, cm)) {
        if (cm->cmsg_level != SOL_IP && cm->cmsg_level != SOL_IPV6) continue;
        auto* ee = reinterpret_cast<const sock_extended_err*>(CMSG_DATA(cm));
        if (ee->ee_origin == SO_EE_ORIGIN_ZEROCOPY) ok = true;
      }
    }
  } while (false);
  if (cfd >= 0) ::close(cfd);
  if (afd >= 0) ::close(afd);
  ::close(lfd);
  return ok;
}
}  // namespace
#endif

// ---------------------------------------------------------------------------
// io_uring submission batching (kernel >= 5.1; SENDMSG/RECVMSG opcodes
// >= 5.3). The toolchain on this container predates <linux/io_uring.h>
// entirely, so the uapi subset the batcher needs is declared here —
// exactly the MSG_ZEROCOPY discipline above: the build host proves
// nothing, only the runtime probe decides.
// ---------------------------------------------------------------------------

#if defined(__linux__)
#ifndef __NR_io_uring_setup
#define __NR_io_uring_setup 425
#endif
#ifndef __NR_io_uring_enter
#define __NR_io_uring_enter 426
#endif

namespace {

// uapi mirror of struct io_uring_params and friends (layout fixed by
// the kernel ABI; field names follow linux/io_uring.h).
struct IoSqringOffsets {
  uint32_t head, tail, ring_mask, ring_entries, flags, dropped, array,
      resv1;
  uint64_t resv2;
};
struct IoCqringOffsets {
  uint32_t head, tail, ring_mask, ring_entries, overflow, cqes, flags,
      resv1;
  uint64_t resv2;
};
struct IoUringParams {
  uint32_t sq_entries, cq_entries, flags, sq_thread_cpu, sq_thread_idle,
      features, wq_fd, resv[3];
  IoSqringOffsets sq_off;
  IoCqringOffsets cq_off;
};
struct IoUringSqe {  // 64 bytes, fields past user_data unused here
  uint8_t opcode;
  uint8_t flags;
  uint16_t ioprio;
  int32_t fd;
  uint64_t off;
  uint64_t addr;
  uint32_t len;
  uint32_t msg_flags;
  uint64_t user_data;
  uint64_t pad[3];
};
struct IoUringCqe {
  uint64_t user_data;
  int32_t res;
  uint32_t flags;
};
static_assert(sizeof(IoUringSqe) == 64, "sqe ABI layout");
static_assert(sizeof(IoUringCqe) == 16, "cqe ABI layout");

constexpr uint8_t kOpNop = 0;
constexpr uint8_t kOpSendmsg = 9;
constexpr uint8_t kOpRecvmsg = 10;
constexpr uint8_t kSqeIoLink = 1u << 2;  // IOSQE_IO_LINK
constexpr unsigned kEnterGetevents = 1u << 0;
constexpr uint32_t kFeatSingleMmap = 1u << 0;
constexpr uint64_t kOffSqRing = 0;
constexpr uint64_t kOffCqRing = 0x8000000ull;
constexpr uint64_t kOffSqes = 0x10000000ull;
// Windows submitted per io_uring_enter: bounds the msghdr/sqe stack
// tables. 8 x 64-span windows = one syscall where the classic loop
// issues eight.
constexpr int kIouringBatchWindows = 8;

int IoUringSetup(unsigned entries, IoUringParams* p) {
  return static_cast<int>(syscall(__NR_io_uring_setup, entries, p));
}
int IoUringEnter(int ring_fd, unsigned to_submit, unsigned min_complete,
                 unsigned flags) {
  return static_cast<int>(syscall(__NR_io_uring_enter, ring_fd, to_submit,
                                  min_complete, flags, nullptr, 0));
}

}  // namespace

// Minimal single-threaded submission/completion ring. One instance is
// owned per TcpConn direction (tcp.h: at most one sender plus one
// receiver thread touch a conn concurrently, so each ring has exactly
// one user and needs no locks). Head/tail words are shared with the
// kernel: release stores publish SQEs, acquire loads observe CQEs.
class IouringQueue {
 public:
  ~IouringQueue() { Close(); }

  bool Init(unsigned entries) {
    IoUringParams p{};
    ring_fd_ = IoUringSetup(entries, &p);
    if (ring_fd_ < 0) return false;
    sq_len_ = p.sq_off.array + p.sq_entries * sizeof(uint32_t);
    cq_len_ = p.cq_off.cqes + p.cq_entries * sizeof(IoUringCqe);
    if (p.features & kFeatSingleMmap) sq_len_ = cq_len_ = std::max(sq_len_, cq_len_);
    sq_ptr_ = ::mmap(nullptr, sq_len_, PROT_READ | PROT_WRITE,
                     MAP_SHARED | MAP_POPULATE, ring_fd_, kOffSqRing);
    if (sq_ptr_ == MAP_FAILED) return Fail();
    cq_ptr_ = (p.features & kFeatSingleMmap)
                  ? sq_ptr_
                  : ::mmap(nullptr, cq_len_, PROT_READ | PROT_WRITE,
                           MAP_SHARED | MAP_POPULATE, ring_fd_, kOffCqRing);
    if (cq_ptr_ == MAP_FAILED) return Fail();
    sqes_len_ = p.sq_entries * sizeof(IoUringSqe);
    sqes_ = static_cast<IoUringSqe*>(
        ::mmap(nullptr, sqes_len_, PROT_READ | PROT_WRITE,
               MAP_SHARED | MAP_POPULATE, ring_fd_, kOffSqes));
    if (sqes_ == MAP_FAILED) return Fail();
    auto sq = static_cast<uint8_t*>(sq_ptr_);
    sq_head_ = reinterpret_cast<uint32_t*>(sq + p.sq_off.head);
    sq_tail_ = reinterpret_cast<uint32_t*>(sq + p.sq_off.tail);
    sq_mask_ = *reinterpret_cast<uint32_t*>(sq + p.sq_off.ring_mask);
    sq_array_ = reinterpret_cast<uint32_t*>(sq + p.sq_off.array);
    auto cq = static_cast<uint8_t*>(cq_ptr_);
    cq_head_ = reinterpret_cast<uint32_t*>(cq + p.cq_off.head);
    cq_tail_ = reinterpret_cast<uint32_t*>(cq + p.cq_off.tail);
    cq_mask_ = *reinterpret_cast<uint32_t*>(cq + p.cq_off.ring_mask);
    cqes_ = reinterpret_cast<IoUringCqe*>(cq + p.cq_off.cqes);
    n_entries_ = p.sq_entries;
    return true;
  }

  bool valid() const { return ring_fd_ >= 0 && sqes_ != nullptr; }
  unsigned entries() const { return n_entries_; }

  // Stage the next SQE (caller fills it). The callers below never
  // stage more than sq_entries per batch, so this cannot overrun.
  IoUringSqe* NextSqe() {
    const uint32_t tail = local_tail_++;
    const uint32_t idx = tail & sq_mask_;
    sq_array_[idx] = idx;
    IoUringSqe* e = &sqes_[idx];
    *e = IoUringSqe{};
    return e;
  }

  // Publish staged SQEs, submit all `n`, and wait until all `n`
  // completions have POSTED. Returns +1 on success, 0 when the ring
  // accepted NOTHING (no op in flight — the caller may fall back to
  // the classic loop safely), -1 fatal: ops were submitted but their
  // completions cannot be confirmed — the kernel may still reference
  // the caller's msghdr/iovec stacks and the stream position is
  // unknowable, so the connection must be treated as broken.
  int SubmitAndWait(unsigned n) {
    __atomic_store_n(sq_tail_, local_tail_, __ATOMIC_RELEASE);
    unsigned submitted = 0;
    while (submitted < n) {
      int rc = IoUringEnter(ring_fd_, n - submitted, 0, 0);
      if (rc < 0) {
        if (errno == EINTR) continue;
        return submitted == 0 ? 0 : -1;
      }
      if (rc == 0) return submitted == 0 ? 0 : -1;  // no forward progress
      submitted += static_cast<unsigned>(rc);
    }
    // Wait for ALL n CQEs. io_uring_enter returns on any signal, and
    // min_complete counts ring entries, not new arrivals — so a
    // signal landing mid-wait must RETRY, never bail: returning with
    // fewer than n completions posted would let the caller's stack
    // frames die while SENDMSG/RECVMSG ops still reference them, and
    // would leave the stream position unknowable.
    while (CqReady() < n) {
      int rc = IoUringEnter(ring_fd_, 0, n, kEnterGetevents);
      if (rc < 0 && errno != EINTR) return -1;
    }
    return 1;
  }

  // Completions currently posted and unconsumed.
  unsigned CqReady() const {
    return __atomic_load_n(cq_tail_, __ATOMIC_ACQUIRE) - *cq_head_;
  }

  // Pop one completion (false when the CQ is empty).
  bool PopCqe(IoUringCqe* out) {
    const uint32_t head = *cq_head_;
    if (head == __atomic_load_n(cq_tail_, __ATOMIC_ACQUIRE)) return false;
    *out = cqes_[head & cq_mask_];
    __atomic_store_n(cq_head_, head + 1, __ATOMIC_RELEASE);
    return true;
  }

 private:
  bool Fail() {
    Close();
    return false;
  }
  void Close() {
    if (sqes_ && sqes_ != MAP_FAILED) ::munmap(sqes_, sqes_len_);
    if (cq_ptr_ && cq_ptr_ != MAP_FAILED && cq_ptr_ != sq_ptr_)
      ::munmap(cq_ptr_, cq_len_);
    if (sq_ptr_ && sq_ptr_ != MAP_FAILED) ::munmap(sq_ptr_, sq_len_);
    if (ring_fd_ >= 0) ::close(ring_fd_);
    sqes_ = nullptr;
    cq_ptr_ = sq_ptr_ = nullptr;
    ring_fd_ = -1;
  }

  int ring_fd_ = -1;
  void* sq_ptr_ = nullptr;
  void* cq_ptr_ = nullptr;
  IoUringSqe* sqes_ = nullptr;
  size_t sq_len_ = 0, cq_len_ = 0, sqes_len_ = 0;
  uint32_t* sq_head_ = nullptr;
  uint32_t* sq_tail_ = nullptr;
  uint32_t* sq_array_ = nullptr;
  uint32_t sq_mask_ = 0;
  uint32_t* cq_head_ = nullptr;
  uint32_t* cq_tail_ = nullptr;
  uint32_t cq_mask_ = 0;
  IoUringCqe* cqes_ = nullptr;
  uint32_t local_tail_ = 0;
  unsigned n_entries_ = 0;
};

namespace {

// END-TO-END io_uring probe: set up a real ring and push one SENDMSG
// and one RECVMSG through it over a loopback socketpair. Anything
// short of both completions delivering the payload — ENOSYS on 4.4,
// EINVAL from a 5.1 kernel without the msg opcodes, a sandbox that
// accepts the setup but never completes — means "feature absent".
bool ProbeIouring() {
  IouringQueue ring;
  if (!ring.Init(4)) return false;
  int sv[2];
  if (::socketpair(AF_UNIX, SOCK_STREAM, 0, sv) != 0) return false;
  bool ok = false;
  do {
    char payload[256];
    std::memset(payload, 0x5a, sizeof(payload));
    struct iovec siov{payload, sizeof(payload)};
    msghdr smsg{};
    smsg.msg_iov = &siov;
    smsg.msg_iovlen = 1;
    IoUringSqe* se = ring.NextSqe();
    se->opcode = kOpSendmsg;
    se->fd = sv[0];
    se->addr = reinterpret_cast<uint64_t>(&smsg);
    se->len = 1;
    se->msg_flags = MSG_NOSIGNAL;
    se->user_data = 1;
    char back[256] = {};
    struct iovec riov{back, sizeof(back)};
    msghdr rmsg{};
    rmsg.msg_iov = &riov;
    rmsg.msg_iovlen = 1;
    IoUringSqe* re = ring.NextSqe();
    re->opcode = kOpRecvmsg;
    re->fd = sv[1];
    re->addr = reinterpret_cast<uint64_t>(&rmsg);
    re->len = 1;
    re->user_data = 2;
    if (ring.SubmitAndWait(2) != 1) break;
    int good = 0;
    IoUringCqe cqe;
    while (ring.PopCqe(&cqe))
      if (cqe.res == static_cast<int32_t>(sizeof(payload))) ++good;
    ok = good == 2 && std::memcmp(payload, back, sizeof(back)) == 0;
  } while (false);
  ::close(sv[0]);
  ::close(sv[1]);
  return ok;
}

}  // namespace
#endif  // __linux__

int ResolvedIouringMode() {
  static const int mode = [] {
    static const char* kChoices[] = {"auto", "off"};
    const int wish = EnvChoiceSane("HOROVOD_TCP_IOURING", 0, kChoices, 2);
    if (wish == 1) return static_cast<int>(kIouringOff);
    bool ok = false;
#if defined(__linux__)
    ok = ProbeIouring();
#endif
    return static_cast<int>(ok ? kIouringBatched : kIouringOff);
  }();
  return mode;
}

const char* IouringModeName(int mode) {
  return mode == kIouringBatched ? "batched" : "syscall";
}

// tcp_prepost_buffers gauge backing store. Written by the executor
// when a persistent slot plan is compiled/torn down, read by
// hvd_metrics_snapshot — relaxed is enough for a monitoring gauge.
namespace {
std::atomic<int64_t> g_prepost_buffers{0};
}  // namespace

void SetPrepostBufferGauge(int64_t n) {
  g_prepost_buffers.store(n, std::memory_order_relaxed);
}

int64_t PrepostBufferGauge() {
  return g_prepost_buffers.load(std::memory_order_relaxed);
}

int ResolvedTransportMode() {
  // Decided once per process (the data plane asks per send): the env
  // wish sanitized like every other knob, then a live end-to-end
  // kernel probe — compile-time constants (or even an accepted
  // setsockopt) prove nothing about the running kernel.
  static const int mode = [] {
    static const char* kChoices[] = {"auto", "on", "off"};
    const int wish = EnvChoiceSane("HOROVOD_TCP_ZEROCOPY", 0, kChoices, 3);
    if (wish == 2) return static_cast<int>(kTransportVectored);
    bool ok = false;
#ifdef HVD_HAS_ERRQUEUE
    ok = ProbeZerocopy();
#endif
    if (!ok && wish == 1 && EnvWarnOnce("HOROVOD_TCP_ZEROCOPY(probe)"))
      LOG_WARNING << "HOROVOD_TCP_ZEROCOPY=on but this kernel does not "
                     "deliver MSG_ZEROCOPY completions (needs >= 4.14); "
                     "staying on the vectored path";
    return static_cast<int>(ok ? kTransportZerocopy : kTransportVectored);
  }();
  return mode;
}

const char* TransportModeName(int mode) {
  return mode == kTransportZerocopy ? "zerocopy" : "vectored";
}

TcpConn::TcpConn() = default;

TcpConn::TcpConn(int fd) : fd_(fd) {}

TcpConn::TcpConn(TcpConn&& o) noexcept
    : fd_(o.fd_),
      zc_(o.zc_),
      iou_send_(std::move(o.iou_send_)),
      iou_recv_(std::move(o.iou_recv_)),
      iou_dead_(o.iou_dead_.load(std::memory_order_relaxed)) {
  o.fd_ = -1;
}

TcpConn& TcpConn::operator=(TcpConn&& o) noexcept {
  if (this != &o) {
    Close();
    fd_ = o.fd_;
    zc_ = o.zc_;
    iou_send_ = std::move(o.iou_send_);
    iou_recv_ = std::move(o.iou_recv_);
    iou_dead_.store(o.iou_dead_.load(std::memory_order_relaxed),
                    std::memory_order_relaxed);
    o.fd_ = -1;
  }
  return *this;
}

// Drain as much of iov[0..n) as the batched rings will take: windows
// of <= kIovWindow spans become linked SENDMSG/RECVMSG SQEs (the link
// keeps the stream ordered — io_uring severs a chain on a SHORT
// transfer, so a partial window can never be followed by an
// out-of-order sibling), submitted kIouringBatchWindows at a time with
// ONE io_uring_enter. A short transfer or cancelled link stops the
// batch and the caller's classic loop finishes from *consumed; a ring
// that accepted nothing latches batching off for the conn. Returns
// false on a hard socket error OR when in-flight ops' completions
// cannot be confirmed (stream position unknowable — resuming would
// duplicate bytes, so the transfer must fail and the conn tear down).
bool TcpConn::BatchedV(bool send, const struct iovec* iov, int n,
                       uint64_t* consumed) {
  *consumed = 0;
#if !defined(__linux__)
  (void)send;
  (void)iov;
  (void)n;
  return true;
#else
  // Batching latched off for this conn.
  if (iou_dead_.load(std::memory_order_relaxed)) return true;
  auto& ring = send ? iou_send_ : iou_recv_;
  if (!ring) {
    ring.reset(new IouringQueue());
    if (!ring->Init(kIouringBatchWindows)) {
      // Per-conn latch, the zc_ = -1 discipline: never re-probe a
      // ring this conn rejected.
      iou_dead_.store(true, std::memory_order_relaxed);
      return true;
    }
  }
  if (!ring->valid()) return true;
  struct iovec wins[kIouringBatchWindows][kIovWindow];
  msghdr msgs[kIouringBatchWindows];
  uint64_t win_bytes[kIouringBatchWindows];
  int i = 0;
  for (;;) {
    // Stage up to kIouringBatchWindows full windows; the tail window
    // (and any list that fits one window) stays with the classic loop
    // — a lone window is one syscall either way.
    int k = 0;
    IoUringSqe* last = nullptr;
    while (k < kIouringBatchWindows && n - i > kIovWindow) {
      const int cnt = kIovWindow;
      std::memcpy(wins[k], iov + i, sizeof(struct iovec) * cnt);
      msgs[k] = msghdr{};
      msgs[k].msg_iov = wins[k];
      msgs[k].msg_iovlen = static_cast<size_t>(cnt);
      win_bytes[k] = IovBytes(wins[k], cnt);
      IoUringSqe* e = ring->NextSqe();
      e->opcode = send ? kOpSendmsg : kOpRecvmsg;
      e->fd = fd_;
      e->addr = reinterpret_cast<uint64_t>(&msgs[k]);
      e->len = 1;
      // MSG_WAITALL on the recv side: without it every routine short
      // read severs the link chain and cancels the batch's remaining
      // windows, degenerating recv batching to one short recvmsg per
      // enter on real networks. (Sends need nothing: blocking
      // sendmsg already writes the full window or errors.)
      e->msg_flags = send ? MSG_NOSIGNAL : MSG_WAITALL;
      e->user_data = static_cast<uint64_t>(k);
      e->flags = kSqeIoLink;
      last = e;
      ++k;
      i += cnt;
    }
    if (k == 0) return true;
    last->flags = 0;  // chain ends inside this batch, never dangles
    const int rc = ring->SubmitAndWait(static_cast<unsigned>(k));
    if (rc == 0) {
      // The ring accepted NOTHING: no op in flight, the stream is
      // untouched by this batch — latch batching off for the conn
      // (probe-should-have-caught territory; re-creating the ring
      // would just retry the same failure forever) and let the
      // classic loop drive from *consumed.
      ring.reset();
      iou_dead_.store(true, std::memory_order_relaxed);
      return true;
    }
    if (rc < 0) {
      // Ops were submitted but their completions could not be
      // confirmed: the stream position is unknowable, so resuming the
      // classic loop could duplicate bytes mid-stream. Same contract
      // as a hard sendmsg error — fail the transfer, the caller tears
      // the connection down.
      ring.reset();
      iou_dead_.store(true, std::memory_order_relaxed);
      errno = EIO;
      return false;
    }
    MetricAdd(kCtrTcpIouringBatches);
    int32_t res[kIouringBatchWindows];
    int got = 0;
    IoUringCqe cqe;
    while (ring->PopCqe(&cqe))
      if (cqe.user_data < static_cast<uint64_t>(k)) {
        res[cqe.user_data] = cqe.res;
        ++got;
      }
    if (got != k) {
      // All k completions POSTED (SubmitAndWait guarantees it) but the
      // CQ handed back something else — a protocol bug, not a runtime
      // hiccup. Stream position unknowable: fail hard, same as above.
      ring.reset();
      iou_dead_.store(true, std::memory_order_relaxed);
      errno = EIO;
      return false;
    }
    MetricAdd(send ? kCtrTcpSendvCalls : kCtrTcpRecvvCalls);
    // Windows execute in link order; consume results in that order and
    // stop at the first short/failed one (everything after it was
    // cancelled by the severed link or never touched the stream).
    for (int w = 0; w < k; ++w) {
      if (res[w] < 0) {
        if (res[w] == -ECANCELED || res[w] == -EINTR || res[w] == -EAGAIN)
          return true;  // classic loop resumes from *consumed
        errno = -res[w];
        return false;  // hard socket error, same contract as sendmsg
      }
      *consumed += static_cast<uint64_t>(res[w]);
      if (static_cast<uint64_t>(res[w]) < win_bytes[w]) return true;
    }
    if (n - i <= kIovWindow) return true;  // classic loop takes the tail
  }
#endif
}

TcpConn::~TcpConn() { Close(); }

void TcpConn::Close() {
  iou_send_.reset();
  iou_recv_.reset();
  if (fd_ >= 0) {
    ::close(fd_);
    fd_ = -1;
    zc_ = 0;
  }
}

// Drain one mutable iovec window through sendmsg. Large windows ride
// MSG_ZEROCOPY when the resolved mode allows and this fd accepts
// SO_ZEROCOPY; every zerocopy completion is reaped from the error
// queue BEFORE returning, so callers may immediately reuse or mutate
// the spans (the in-place exchanges and the grow-only pool depend on
// exactly that).
bool TcpConn::SendWindow(struct iovec* win, int cnt, uint64_t bytes) {
  bool use_zc = false;
#ifdef HVD_HAS_ERRQUEUE
  // Size gate FIRST: ResolvedTransportMode()'s one-time probe costs
  // ~40 ms on a completion-less kernel, and most processes (tier-1
  // spawns hundreds) never send a zerocopy-eligible span — they must
  // never pay it. Only large sends, or an explicit mode query
  // (metrics gauge), resolve the mode.
  if (bytes >= kZcMinBytes && zc_ >= 0 &&
      ResolvedTransportMode() == kTransportZerocopy) {
    if (zc_ == 0) {
      int one = 1;
      zc_ = setsockopt(fd_, SOL_SOCKET, SO_ZEROCOPY, &one, sizeof(one)) == 0
                ? 1
                : -1;
    }
    use_zc = zc_ == 1;
  }
#endif
  (void)bytes;
  uint32_t zc_pending = 0;
  int j = 0;
  while (j < cnt) {
    while (j < cnt && win[j].iov_len == 0) ++j;  // recvmsg-EOF ambiguity
    if (j == cnt) break;
    msghdr msg{};
    msg.msg_iov = win + j;
    msg.msg_iovlen = static_cast<size_t>(cnt - j);
    ssize_t n =
        ::sendmsg(fd_, &msg, MSG_NOSIGNAL | (use_zc ? MSG_ZEROCOPY : 0));
    if (n < 0) {
      if (errno == EINTR) continue;
#ifdef HVD_HAS_ERRQUEUE
      if (use_zc && errno == ENOBUFS) {
        if (zc_pending > 0) {
          // optmem exhausted by un-reaped notifications: reap, retry.
          if (!ReapZerocopy(&zc_pending, /*wait=*/true)) return false;
        } else {
          // Nothing left to reap: the socket's optmem budget or the
          // process memlock limit cannot cover this send at all. The
          // MSG_ZEROCOPY contract's documented fallback is a plain
          // (copied) send — a healthy connection must not die over a
          // pinning budget.
          use_zc = false;
        }
        continue;
      }
#endif
      return false;
    }
    MetricAdd(kCtrTcpSendvCalls);
    if (use_zc) {
      MetricAdd(kCtrTcpZerocopySends);
      ++zc_pending;
    }
    uint64_t left = static_cast<uint64_t>(n);
    while (j < cnt && left >= win[j].iov_len) {
      left -= win[j].iov_len;
      ++j;
    }
    if (j < cnt && left > 0) {
      win[j].iov_base = static_cast<char*>(win[j].iov_base) + left;
      win[j].iov_len -= left;
    }
  }
#ifdef HVD_HAS_ERRQUEUE
  while (zc_pending > 0)
    if (!ReapZerocopy(&zc_pending, /*wait=*/true)) return false;
#endif
  return true;
}

#ifdef HVD_HAS_ERRQUEUE
bool TcpConn::ReapZerocopy(uint32_t* pending, bool wait) {
  // Each error-queue record acknowledges a RANGE of MSG_ZEROCOPY sends
  // ([ee_info, ee_data]); block on POLLERR (level-triggered while the
  // queue is non-empty) up to a generous bound so a dead peer surfaces
  // as an error instead of a wedge.
  while (*pending > 0) {
    char ctrl[128];
    msghdr msg{};
    msg.msg_control = ctrl;
    msg.msg_controllen = sizeof(ctrl);
    ssize_t n = ::recvmsg(fd_, &msg, MSG_ERRQUEUE);
    if (n < 0) {
      if (errno == EINTR) continue;
      if (errno == EAGAIN || errno == EWOULDBLOCK) {
        if (!wait) return true;
        pollfd p{fd_, 0, 0};
        int rc = ::poll(&p, 1, 60 * 1000);
        if (rc < 0 && errno == EINTR) continue;  // same retry as the IO
        if (rc <= 0) return false;
        continue;
      }
      return false;
    }
    for (cmsghdr* cm = CMSG_FIRSTHDR(&msg); cm != nullptr;
         cm = CMSG_NXTHDR(&msg, cm)) {
      if (cm->cmsg_level != SOL_IP && cm->cmsg_level != SOL_IPV6) continue;
      auto* ee = reinterpret_cast<const sock_extended_err*>(CMSG_DATA(cm));
      if (ee->ee_origin != SO_EE_ORIGIN_ZEROCOPY) continue;
      const uint32_t acked = ee->ee_data - ee->ee_info + 1;
      *pending -= std::min(*pending, acked);
    }
  }
  return true;
}
#endif

bool TcpConn::SendV(const struct iovec* iov, int n) {
  // Ground-truth on-the-wire accounting (one relaxed atomic add per
  // call): with a wire codec active this counts the ENCODED bytes, so
  // it is the denominator-of-record for effective-bandwidth math.
  MetricAdd(kCtrTcpSendBytes, static_cast<int64_t>(IovBytes(iov, n)));
  uint64_t skip = 0;
  // Multi-window lists may batch their windows through io_uring (one
  // enter for up to kIouringBatchWindows sendmsg calls). Mode order
  // matters: the io_uring probe is checked FIRST so a box without the
  // feature (this 4.4 kernel) never pays the zerocopy probe here; the
  // batched path yields to MSG_ZEROCOPY when that resolved live (the
  // reap loop owns those sends).
  if (n > kIovWindow && ResolvedIouringMode() == kIouringBatched &&
      ResolvedTransportMode() != kTransportZerocopy) {
    if (!BatchedV(/*send=*/true, iov, n, &skip)) return false;
  }
  struct iovec win[kIovWindow];
  int i = 0;
  while (i < n && skip >= iov[i].iov_len) skip -= iov[i].iov_len, ++i;
  while (i < n) {
    const int cnt = std::min(n - i, kIovWindow);
    std::memcpy(win, iov + i, sizeof(struct iovec) * cnt);
    if (skip) {  // partial span left behind by the batched path
      win[0].iov_base = static_cast<char*>(win[0].iov_base) + skip;
      win[0].iov_len -= skip;
      skip = 0;
    }
    if (!SendWindow(win, cnt, IovBytes(win, cnt))) return false;
    i += cnt;
  }
  return true;
}

bool TcpConn::RecvV(const struct iovec* iov, int n) {
  MetricAdd(kCtrTcpRecvBytes, static_cast<int64_t>(IovBytes(iov, n)));
  uint64_t skip = 0;
  // Same batching as SendV (short reads sever the link chain, which
  // just hands the remainder back to the classic drain below).
  if (n > kIovWindow && ResolvedIouringMode() == kIouringBatched) {
    if (!BatchedV(/*send=*/false, iov, n, &skip)) return false;
  }
  struct iovec win[kIovWindow];
  int i = 0;
  while (i < n && skip >= iov[i].iov_len) skip -= iov[i].iov_len, ++i;
  while (i < n) {
    const int cnt = std::min(n - i, kIovWindow);
    std::memcpy(win, iov + i, sizeof(struct iovec) * cnt);
    if (skip) {
      win[0].iov_base = static_cast<char*>(win[0].iov_base) + skip;
      win[0].iov_len -= skip;
      skip = 0;
    }
    int j = 0;
    while (j < cnt) {
      // Skip empty spans BEFORE the syscall: recvmsg over a zero-byte
      // window returns 0, which is indistinguishable from peer EOF.
      while (j < cnt && win[j].iov_len == 0) ++j;
      if (j == cnt) break;
      msghdr msg{};
      msg.msg_iov = win + j;
      msg.msg_iovlen = static_cast<size_t>(cnt - j);
      ssize_t got = ::recvmsg(fd_, &msg, 0);
      if (got <= 0) {
        if (got < 0 && errno == EINTR) continue;
        return false;
      }
      MetricAdd(kCtrTcpRecvvCalls);
      uint64_t left = static_cast<uint64_t>(got);
      while (j < cnt && left >= win[j].iov_len) {
        left -= win[j].iov_len;
        ++j;
      }
      if (j < cnt && left > 0) {
        win[j].iov_base = static_cast<char*>(win[j].iov_base) + left;
        win[j].iov_len -= left;
      }
    }
    i += cnt;
  }
  return true;
}

bool TcpConn::SendAll(const void* data, uint64_t len) {
  struct iovec iov{const_cast<void*>(data), static_cast<size_t>(len)};
  return SendV(&iov, 1);
}

bool TcpConn::RecvAll(void* data, uint64_t len) {
  struct iovec iov{data, static_cast<size_t>(len)};
  return RecvV(&iov, 1);
}

void TcpConn::SetRecvTimeout(int ms) {
  if (fd_ < 0) return;
  timeval tv{};
  tv.tv_sec = ms / 1000;
  tv.tv_usec = (ms % 1000) * 1000;
  setsockopt(fd_, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof(tv));
}

std::string TcpConn::LocalIp() const {
  sockaddr_in sa{};
  socklen_t slen = sizeof(sa);
  if (fd_ < 0 ||
      getsockname(fd_, reinterpret_cast<sockaddr*>(&sa), &slen) != 0)
    return "";
  char buf[INET_ADDRSTRLEN];
  if (inet_ntop(AF_INET, &sa.sin_addr, buf, sizeof(buf)) == nullptr) return "";
  return buf;
}

bool SendRecv(TcpConn* to, const void* sbuf, uint64_t sbytes, TcpConn* from,
              void* rbuf, uint64_t rbytes) {
  // Payloads comfortably below the kernel's minimum socket send buffer
  // (SO_SNDBUF floor is 4 KB; defaults are ≥ 16 KB) cannot block in
  // send(), so the latency-sensitive small-tensor path skips the
  // concurrent-sender thread entirely.
  constexpr uint64_t kNoBlockBytes = 8 * 1024;
  if (sbytes <= kNoBlockBytes)
    return (sbytes == 0 || to->SendAll(sbuf, sbytes)) &&
           (rbytes == 0 || from->RecvAll(rbuf, rbytes));
  bool send_ok = true;
  std::thread sender(
      [&] { send_ok = to->SendAll(sbuf, sbytes); });
  bool recv_ok = rbytes == 0 || from->RecvAll(rbuf, rbytes);
  sender.join();
  return send_ok && recv_ok;
}

bool TcpConn::SendFrame(const void* data, uint64_t len) {
  // Header and payload in ONE vectored syscall: the old two-send
  // framing under TCP_NODELAY pushed an 8-byte segment per frame and
  // doubled the syscall count of every control-plane message.
  uint64_t hdr = len;
  struct iovec iov[2] = {{&hdr, sizeof(hdr)},
                         {const_cast<void*>(data), static_cast<size_t>(len)}};
  return SendV(iov, len == 0 ? 1 : 2);
}

bool TcpConn::SendTokenFrame(const void* token, const void* payload,
                             uint64_t payload_len) {
  // The 8-byte consensus token leads the slot's payload in ONE
  // vectored send — the SendFrame header-fold applied to the lock
  // token, so a persistent locked firing costs no packet (and no
  // syscall) beyond the bare payload it had to push anyway.
  struct iovec iov[2] = {
      {const_cast<void*>(token), 8},
      {const_cast<void*>(payload), static_cast<size_t>(payload_len)}};
  return SendV(iov, payload_len == 0 ? 1 : 2);
}

bool TcpConn::RecvFrame(std::string* out) {
  uint64_t len;
  if (!RecvAll(&len, sizeof(len))) return false;
  if (len > (1ull << 40)) return false;  // sanity
  out->resize(len);
  return len == 0 || RecvAll(&(*out)[0], len);
}

int TcpServer::Listen(const std::string& addr) {
  std::string host;
  int port;
  if (!SplitAddr(addr, &host, &port)) return -1;
  listen_fd_ = ::socket(AF_INET, SOCK_STREAM, 0);
  if (listen_fd_ < 0) return -1;
  int one = 1;
  setsockopt(listen_fd_, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  sockaddr_in sa{};
  sa.sin_family = AF_INET;
  sa.sin_port = htons(static_cast<uint16_t>(port));
  sa.sin_addr.s_addr =
      host == "0.0.0.0" || host.empty() ? INADDR_ANY : inet_addr(host.c_str());
  if (::bind(listen_fd_, reinterpret_cast<sockaddr*>(&sa), sizeof(sa)) < 0 ||
      ::listen(listen_fd_, 128) < 0) {
    ::close(listen_fd_);
    listen_fd_ = -1;
    return -1;
  }
  socklen_t slen = sizeof(sa);
  getsockname(listen_fd_, reinterpret_cast<sockaddr*>(&sa), &slen);
  return ntohs(sa.sin_port);
}

// Accept one connection with a shared deadline and read its (rank,
// channel) handshake. Returns false on timeout/socket error.
bool TcpServer::AcceptOne(std::chrono::steady_clock::time_point deadline,
                          int my_rank, int32_t hello[2], TcpConn* out) {
  timeval tv{};
  auto remain = std::chrono::duration_cast<std::chrono::microseconds>(
                    deadline - std::chrono::steady_clock::now())
                    .count();
  if (remain <= 0) return false;
  tv.tv_sec = remain / 1000000;
  tv.tv_usec = remain % 1000000;
  fd_set fds;
  FD_ZERO(&fds);
  FD_SET(listen_fd_, &fds);
  if (::select(listen_fd_ + 1, &fds, nullptr, nullptr, &tv) <= 0) return false;
  int fd = ::accept(listen_fd_, nullptr, nullptr);
  if (fd < 0) return false;
  SetNoDelay(fd);
  TcpConn conn(fd);
  if (!conn.RecvAll(hello, sizeof(int32_t) * 2)) return false;
  // Ack echoes OUR rank: candidate IPs (e.g. identical bridge
  // addresses on several hosts) can reach the wrong host's listener;
  // the dialer verifies it reached the rank it meant to.
  const int32_t ack[2] = {kHelloAck, my_rank};
  if (!conn.SendAll(ack, sizeof(ack))) return false;
  *out = std::move(conn);
  return true;
}

bool TcpServer::AcceptPeers(int n, std::vector<TcpConn>* control_by_rank,
                            std::vector<TcpConn>* data_by_rank,
                            int timeout_ms) {
  control_by_rank->clear();
  control_by_rank->resize(n + 1);
  data_by_rank->clear();
  data_by_rank->resize(n + 1);
  auto deadline = std::chrono::steady_clock::now() +
                  std::chrono::milliseconds(timeout_ms);
  // Count UNIQUE (rank, channel) arrivals, replacing duplicates with
  // the newest connection: a dialer whose ack wait timed out abandons
  // its connection and redials, and the stale one must not consume an
  // accept slot (the peer closed it — the latest is the live one).
  int filled = 0;
  while (filled < 2 * n) {
    int32_t hello[2];
    TcpConn conn;
    if (!AcceptOne(deadline, 0, hello, &conn)) return false;
    if (hello[0] < 1 || hello[0] > n || (hello[1] != 0 && hello[1] != 1)) {
      LOG_ERROR << "controller handshake: bad (rank, channel) = (" << hello[0]
                << ", " << hello[1] << ")";
      return false;
    }
    auto* vec = hello[1] == 0 ? control_by_rank : data_by_rank;
    if (!(*vec)[hello[0]].valid()) filled++;
    (*vec)[hello[0]] = std::move(conn);
  }
  return true;
}

bool TcpServer::AcceptMesh(int n, int my_rank, std::vector<TcpConn>* out_by_rank,
                           int timeout_ms) {
  auto deadline = std::chrono::steady_clock::now() +
                  std::chrono::milliseconds(timeout_ms);
  int filled = 0;
  while (filled < n) {  // unique ranks; duplicates replace (see AcceptPeers)
    int32_t hello[2];
    TcpConn conn;
    if (!AcceptOne(deadline, my_rank, hello, &conn)) return false;
    if (hello[1] != 2 || hello[0] <= my_rank ||
        hello[0] >= static_cast<int32_t>(out_by_rank->size())) {
      LOG_ERROR << "mesh handshake: bad (rank, channel) = (" << hello[0]
                << ", " << hello[1] << ") at rank " << my_rank;
      return false;
    }
    if (!(*out_by_rank)[hello[0]].valid()) filled++;
    (*out_by_rank)[hello[0]] = std::move(conn);
  }
  return true;
}

void TcpServer::Close() {
  if (listen_fd_ >= 0) {
    ::close(listen_fd_);
    listen_fd_ = -1;
  }
}

namespace {
// connect() bounded by `timeout_ms` (non-blocking + poll): a candidate
// address on an unreachable NIC must cost its slice, not the kernel's
// multi-minute SYN retry budget.
int ConnectWithTimeout(const sockaddr_in& sa, int timeout_ms) {
  int fd = ::socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return -1;
  int flags = fcntl(fd, F_GETFL, 0);
  fcntl(fd, F_SETFL, flags | O_NONBLOCK);
  int rc = ::connect(fd, reinterpret_cast<const sockaddr*>(&sa), sizeof(sa));
  if (rc != 0) {
    if (errno != EINPROGRESS) {
      ::close(fd);
      return -1;
    }
    pollfd p{fd, POLLOUT, 0};
    if (::poll(&p, 1, timeout_ms) <= 0) {
      ::close(fd);
      return -1;
    }
    int err = 0;
    socklen_t len = sizeof(err);
    if (getsockopt(fd, SOL_SOCKET, SO_ERROR, &err, &len) != 0 || err != 0) {
      ::close(fd);
      return -1;
    }
  }
  fcntl(fd, F_SETFL, flags);
  return fd;
}

bool DialOnce(const std::string& host, int port, int my_rank, int channel,
              int expect_rank, int timeout_ms, TcpConn* out) {
  sockaddr_in sa{};
  sa.sin_family = AF_INET;
  sa.sin_port = htons(static_cast<uint16_t>(port));
  // getaddrinfo, not gethostbyname: the dial path runs concurrently
  // with elastic rebootstrap threads, and gethostbyname's static
  // result buffer is a data race the tsan tier would (rightly) flag.
  addrinfo hints{};
  hints.ai_family = AF_INET;
  hints.ai_socktype = SOCK_STREAM;
  addrinfo* res = nullptr;
  if (getaddrinfo(host.c_str(), nullptr, &hints, &res) == 0 &&
      res != nullptr) {
    sa.sin_addr = reinterpret_cast<sockaddr_in*>(res->ai_addr)->sin_addr;
    freeaddrinfo(res);
  } else {
    // Numeric fallback, preserving the old path's acceptance of the
    // legacy inet_addr spellings (hex/octal quads).
    sa.sin_addr.s_addr = inet_addr(host.c_str());
  }
  int fd = ConnectWithTimeout(sa, timeout_ms);
  if (fd < 0) return false;
  SetNoDelay(fd);
  TcpConn conn(fd);
  int32_t hello[2] = {my_rank, channel};
  if (!conn.SendAll(hello, sizeof(hello))) return false;
  conn.SetRecvTimeout(std::max(1, timeout_ms));
  int32_t ack[2] = {0, -1};
  bool acked = conn.RecvAll(ack, sizeof(ack)) && ack[0] == kHelloAck &&
               ack[1] == expect_rank;
  conn.SetRecvTimeout(0);
  if (!acked) return false;
  *out = std::move(conn);
  return true;
}
}  // namespace

bool TcpConnect(const std::string& addr, int my_rank, int channel,
                int expect_rank, int timeout_ms, TcpConn* out) {
  std::string host;
  int port;
  if (!SplitAddr(addr, &host, &port)) return false;
  auto deadline = std::chrono::steady_clock::now() +
                  std::chrono::milliseconds(timeout_ms);
  while (std::chrono::steady_clock::now() < deadline) {
    int left = static_cast<int>(
        std::chrono::duration_cast<std::chrono::milliseconds>(
            deadline - std::chrono::steady_clock::now())
            .count());
    if (DialOnce(host, port, my_rank, channel, expect_rank,
                 std::max(1, left), out))
      return true;
    std::this_thread::sleep_for(std::chrono::milliseconds(50));
  }
  return false;
}

bool TcpConnectAny(const std::vector<std::string>& addrs, int my_rank,
                   int channel, int expect_rank, int timeout_ms,
                   TcpConn* out) {
  // Multi-NIC peers advertise every candidate address; dial them round
  // robin with bounded per-candidate slices until one answers (the
  // reachability ELECTION happens here, per peer pair — the analog of
  // the reference driver's cross-host NIC intersection,
  // runner/driver/driver_service.py:266).
  if (addrs.empty()) return false;
  auto deadline = std::chrono::steady_clock::now() +
                  std::chrono::milliseconds(timeout_ms);
  const int slice = std::max(
      250, std::min(3000, timeout_ms / (2 * static_cast<int>(addrs.size()))));
  while (std::chrono::steady_clock::now() < deadline) {
    for (const auto& addr : addrs) {
      std::string host;
      int port;
      if (!SplitAddr(addr, &host, &port)) continue;
      if (DialOnce(host, port, my_rank, channel, expect_rank, slice,
                   out)) {
        LOG_DEBUG << "mesh dial: rank " << my_rank << " reached peer via "
                  << addr;
        return true;
      }
      LOG_DEBUG << "mesh dial: candidate " << addr << " not reachable";
    }
    std::this_thread::sleep_for(std::chrono::milliseconds(50));
  }
  return false;
}

}  // namespace hvd
