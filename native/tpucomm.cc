/* tpucomm — TCP-mesh communication runtime (see tpucomm.h).
 *
 * Design notes:
 * - Connection setup: rank r listens on base_port + r; for each pair
 *   (i, j) with i < j, j dials i and identifies itself with its rank.
 * - Messages are framed (tag, nbytes) and matched strictly in order — the
 *   Python layer serializes communicating ops per process with JAX ordered
 *   effects, so out-of-order arrival on one socket is a program error
 *   (matching the reference's token-ordering contract, not a message
 *   re-ordering layer).
 * - Collectives are deterministic schedules over the point-to-point layer.
 *   allreduce/allgather carry SELECTABLE algorithms (ring / recursive
 *   doubling / binomial tree, plus the quantized-wire qring/qrd
 *   allreduce twins — the collective algorithm engine, owned by
 *   mpi4jax_tpu/tune): AUTO consults the decision table installed via
 *   tpucomm_set_coll_table, per-call forcing goes through the *_algo
 *   entry points.
 * - Algorithm wire-protocol invariant: every algorithm is built from the
 *   same framed point-to-point messages (tag kCollectiveTag, comm_id in
 *   every header), so the transport's divergence checks fire identically
 *   under every algorithm — ranks that disagree on the schedule (or on
 *   the algorithm itself) hit the tag/size/comm-id mismatch diagnostics
 *   and abort instead of corrupting data.  The same-host shm arena keeps
 *   its own opword cross-check, and always wins over the selector when a
 *   communicator has an arena (the engine governs the TCP path).
 * - Debug tracing mirrors the reference bridge's format
 *   ("r<rank> | <id> | Op ..."): entry + exit line with wall time.
 * - Fail-fast: any socket/protocol error prints to stderr and returns
 *   nonzero; the Python layer aborts the process group.
 */

#include "tpucomm.h"

#include <arpa/inet.h>
#include <emmintrin.h>
#include <fcntl.h>
#include <immintrin.h>
#include <linux/futex.h>
#include <linux/io_uring.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/mman.h>
#include <sys/socket.h>
#include <sys/stat.h>
#include <sys/syscall.h>
#include <sys/uio.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <chrono>
#include <cmath>
#include <condition_variable>
#include <cstdarg>
#include <cstddef>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <limits>
#include <map>
#include <mutex>
#include <random>
#include <string>
#include <thread>
#include <memory>
#include <vector>

namespace {

int g_logging = 0;

double now_s() {
  using clock = std::chrono::steady_clock;
  return std::chrono::duration<double>(clock::now().time_since_epoch())
      .count();
}

std::string call_id() {
  static thread_local std::mt19937_64 rng{std::random_device{}()};
  char buf[12];
  std::snprintf(buf, sizeof(buf), "%08llx",
                (unsigned long long)(rng() & 0xffffffffull));
  return buf;
}

struct LogScope {
  int rank;
  std::string id;
  const char* op;
  double t0 = 0;
  bool active;
  /* detail is a callable returning std::string so the formatting (and
   * the call-id rng) costs nothing when logging is off — the hot path
   * pays one branch (allreduce at 1 KB np8 is ~16 us end to end; a
   * handful of std::to_string allocations were measurable) */
  template <typename DetailFn>
  LogScope(int rank, const char* op, DetailFn&& detail)
      : rank(rank), op(op), active(g_logging != 0) {
    if (active) {
      id = call_id();
      std::fprintf(stderr, "r%d | %s | %s %s\n", rank, id.c_str(), op,
                   detail().c_str());
      t0 = now_s();
    }
  }
  ~LogScope() {
    if (active) {
      std::fprintf(stderr, "r%d | %s | %s done with code 0 (%.6f s)\n", rank,
                   id.c_str(), op, now_s() - t0);
    }
  }
};

/* Last-error text, readable from Python via tpucomm_last_error() so the
 * abort path can print a human-readable reason next to the error code
 * (the analog of the reference's ierr -> MPI_Error_string conversion,
 * mpi_xla_bridge.pyx:67-91 there). */
char g_last_error[512] = {0};
std::mutex g_last_error_mu;

void set_last_error(int rank, const char* fmt, ...) {
  char body[448];
  va_list ap;
  va_start(ap, fmt);
  std::vsnprintf(body, sizeof(body), fmt, ap);
  va_end(ap);
  std::lock_guard<std::mutex> lock(g_last_error_mu);
  std::snprintf(g_last_error, sizeof(g_last_error), "r%d: %s", rank, body);
}

#define FAIL(comm, ...)                                              \
  do {                                                               \
    std::fprintf(stderr, "tpucomm r%d: ", (comm)->rank);             \
    std::fprintf(stderr, __VA_ARGS__);                               \
    std::fprintf(stderr, "\n");                                      \
    set_last_error((comm)->rank, __VA_ARGS__);                       \
    return 1;                                                        \
  } while (0)

struct MsgHeader {
  int64_t nbytes;
  int32_t tag;
  int32_t comm_id;  // communicator the message belongs to (world = 0)
};

/* ============== self-healing link layer: wire format ==============
 *
 * With MPI4JAX_TPU_RETRY > 0 ("armed"), every wire frame header grows
 * to MsgHeaderX: the plain header as a PREFIX (so MSG_PEEK-based
 * poison/liveness probes that look at the first 16 bytes keep working
 * unchanged), then a per-link sequence number, the link's connection
 * epoch, and a CRC32C over the extended header.  Sequence numbers are
 * per (link, direction) and count DATA frames only; control frames
 * (heartbeat ping/pong, poison) carry seq 0 and are out-of-band — they
 * are never retained, never replayed, and never advance the receiver's
 * delivery cursor.  All ranks read the same environment, so the wire
 * format agrees job-wide; MPI4JAX_TPU_RETRY unset/0 keeps the 16-byte
 * header and the historic byte stream bit-for-bit. */
struct MsgHeaderX {
  MsgHeader h;
  uint32_t seq_lo;  // low/high halves of the 64-bit link sequence
  uint32_t seq_hi;
  uint32_t epoch;   // link connection epoch at stamp time
  uint32_t crc;     // CRC32C of this struct with crc = 0 (when enabled)
};

/* heartbeat control frames (never visible to user receives) */
constexpr int32_t kPingTag = -7711;
constexpr int32_t kPongTag = -7712;

bool retry_armed();          // MPI4JAX_TPU_RETRY > 0
int64_t wire_hdr_bytes();    // sizeof(MsgHeaderX) when armed, else MsgHeader

/* Reconnect handshake, exchanged raw (not framed) on a fresh socket:
 * each side identifies itself and reports the last data seq it fully
 * delivered, so the peer replays exactly the gap.  Always sealed with
 * CRC32C (control scope — independent of MPI4JAX_TPU_WIRE_CRC). */
struct ReconnectHello {
  uint32_t magic;        // kReconnectMagic
  int32_t rank;          // sender's ROOT (socket-owner) rank
  int32_t comm_id;       // root comm id, as a cross-job sanity check
  uint32_t epoch;        // sender's current link epoch
  uint64_t rx_delivered; // last inbound data seq fully delivered
  uint32_t crc;
};
constexpr uint32_t kReconnectMagic = 0x4d344a52u;  // "M4JR"

struct Comm;
int link_recover(Comm* c, int peer, int fd_seen, const char* what);
int link_send_frame(Comm* c, int dest, int tag, const void* p1, int64_t n1,
                    const void* p2, int64_t n2);
int link_fd(Comm* c, int peer);

/* One retained (replayable) outbound frame: the complete stamped wire
 * bytes, header included, so replay is a verbatim rewrite. */
struct ReplayFrame {
  uint64_t seq = 0;
  std::vector<char> bytes;
};

enum LinkPhase { LINK_UP = 0, LINK_SUSPECT, LINK_RECONNECTING, LINK_DEAD };

/* Per-peer link state, owned by the socket-owning root comm and indexed
 * by ROOT rank.  `mu` serializes recovery per link (the first thread to
 * hit a failure reconnects; threads arriving later block on it, then see
 * the fresh fd and simply retry their frame).  `wmu` serializes whole
 * FRAMES onto the socket when armed, so a heartbeat pong injected from
 * the receive path can never interleave with another thread's
 * header/payload write pair. */
struct LinkState {
  std::mutex mu;
  std::mutex wmu;
  /* receive-side frame mutex: held across the armed header read and by
   * a recovery while it rewires the fd, so fd loads on the read side
   * are synchronized (lock order: mu -> rmu -> wmu) */
  std::mutex rmu;
  uint32_t epoch = 1;
  std::atomic<uint64_t> tx_seq{0};  // last stamped outbound data seq
  std::atomic<uint64_t> rx_seq{0};  // last fully delivered inbound data seq
  /* newest outbound data seq with NO retained copy (too large, or
   * evicted from the ring): a reconnect whose replay gap crosses this
   * cannot restore the stream and must escalate */
  std::atomic<uint64_t> hole_seq{0};
  std::deque<ReplayFrame> ring;  // guarded by wmu (and mu during recovery)
  int64_t ring_bytes = 0;
  std::atomic<int> phase{LINK_UP};
  std::atomic<double> last_rx{0};    // stamp of last inbound bytes seen
  std::atomic<double> last_ping{0};  // stamp of last heartbeat ping sent
};

/* One queued outbound message.  The enqueuing op always waits for
 * completion before returning, so `buf` stays valid (zero-copy). */
struct SendJob {
  int fd = -1;
  int rank = -1;  // enqueuer's rank, for error text
  int dest = -1;
  Comm* comm = nullptr;  // enqueuing comm (self-healing frame path)
  MsgHeader hdr{};
  const void* buf = nullptr;
  int rc = 0;
  bool done = false;
};

struct ShmArena;  // same-host shared-memory fast path, defined below
void arena_destroy(ShmArena* a);
struct Engine;    // async progress engine (per socket-owning comm)
void engine_shutdown(Engine* e);

/* A user message staged off the socket: a coalesced wire frame carries
 * several adjacent small sends from one peer; the receiver lands the
 * one a posted receive is waiting for directly in the user buffer and
 * stages the rest here, consumed strictly in arrival order (the same
 * in-order-channel contract as the wire). */
struct PendingMsg {
  MsgHeader hdr;
  std::vector<char> data;
};

struct Comm;

/* Locality map installed by tpucomm_set_topology (mpi4jax_tpu/topo is
 * the discovering owner): which member ranks share an island (a host /
 * shm domain), each island's leader (its lowest member rank), and the
 * intra-island + leaders sub-communicators the hierarchical collective
 * schedules compose over.  The sub-comms are tpucomm_split children of
 * this comm (they borrow its sockets); the Python bridge creates them
 * and tears them down before the world. */
struct TopoInfo {
  std::vector<int32_t> island_of;             // member rank -> island id
  std::vector<int32_t> leaders;               // island id -> leader rank
  std::vector<std::vector<int32_t>> members;  // island id -> sorted ranks
  Comm* intra = nullptr;   // my island's sub-comm (null: singleton island)
  Comm* leader = nullptr;  // leaders' sub-comm (null: not a leader)
  int n_islands = 0;
  int my_island = -1;
  int my_leader = -1;      // member rank of my island's leader
};

/* shm p2p rings (defined in the arena section below) */
bool ring_p2p_on(const Comm* c);
int shm_try_send(Comm* c, int dest, int tag, const void* buf,
                 int64_t nbytes, bool* inlined);
int shm_recv_status(Comm* c, int source, int tag, void* buf,
                    int64_t nbytes, int32_t* out_src, int32_t* out_tag,
                    int64_t* out_count);
int ring_poll_any(Comm* c, int tag, int* out_source);

struct Comm {
  int rank = -1;
  int size = 0;
  std::vector<int> socks;  // per-peer fd, -1 for self
  ShmArena* arena = nullptr;  // non-null when every member shares this host
  std::string shm_prefix;     // job-unique shm name prefix (inherited)
  std::mutex mu;           // one op at a time (ordered effects upstream)
  /* self-delivery queue: send-to-self enqueues here, recv-from-self pops
   * (MPI allows self-messaging; the reference's exit-flush regression is
   * a sendrecv-to-self, test_common.py:91-114 there).  Guarded by mu. */
  std::deque<std::pair<MsgHeader, std::vector<char>>> self_q;
  /* coalesced sub-messages staged off the wire, keyed by source rank.
   * Touched only by the thread executing this comm's ops (the same
   * single-executor discipline as self_q: either the calling thread
   * running inline, or the progress thread — never both at once). */
  std::map<int, std::deque<PendingMsg>> pending;
  int32_t comm_id = 0;     // deterministic across ranks (world = 0)
  /* effective host of every member — the real host table with the
   * MPI4JAX_TPU_FAKE_HOSTS virtual partition applied; arena eligibility
   * (bootstrap AND split subsets) is decided on THIS view, so a
   * virtually partitioned loopback job behaves like the multi-host
   * shape it models.  Inherited (subsetted) by split/dup children. */
  std::vector<std::string> member_hosts;
  /* discovered locality map (tpucomm_set_topology); null = flat */
  TopoInfo* topo = nullptr;
  bool owns_socks = true;  // split/dup comms borrow the parent's sockets
  int32_t next_split_seq = 1;  // collective-call counter, agrees rank-wide
  Comm* lock_root = this;  // sub-comms serialize on the socket owner's mu:
                           // two comms sharing fds must never interleave
                           // header/payload writes on one socket

  /* Persistent writer thread: the send half of sendrecv/collective
   * rounds is queued here instead of spawning a std::thread per message
   * (round 2 paid thread creation — tens of microseconds — on every
   * round of every collective; VERDICT.md weak #6).  Lives on the
   * socket-owning root comm; lazily started on first use. */
  std::thread writer;
  std::mutex wmu;
  std::condition_variable wcv;       // writer wakeup
  std::condition_variable wdone_cv;  // completion notification
  std::deque<SendJob*> wq;
  bool writer_started = false;
  bool wstop = false;

  /* Async progress engine (lives on the socket-owning root comm, like
   * the writer thread): a dedicated progress thread draining a
   * lock-free submission queue of op descriptors.  Created lazily on
   * the first queued post; null while every op has run inline. */
  Engine* engine = nullptr;

  /* ---- self-healing link layer (populated only when armed) ---- */
  /* member rank -> socket-owning root rank, so sub-comms resolve the
   * one LinkState per physical socket (world: identity; children
   * compose through the parent's map at split time) */
  std::vector<int> root_rank;
  /* per-ROOT-rank link state; lives on the socket owner only */
  std::vector<std::unique_ptr<LinkState>> links;
  /* bootstrap listener, kept open for the comm's lifetime when armed so
   * reconnect dials have somewhere to land (closed at finalize) */
  int listen_fd = -1;
  int base_port = 0;
  std::vector<std::string> real_hosts;  // dialing addresses (not FAKE_HOSTS)
  /* reconnect dials accepted while the expected acceptor was busy
   * elsewhere: root rank -> (connected fd, its hello, already read) */
  std::mutex rcmu;
  std::map<int, std::pair<int, ReconnectHello>> pending_rc;
  /* replaced fds parked (shutdown but not closed) until finalize:
   * closing immediately could hand the fd number to an unrelated open
   * while another thread is still blocked on it */
  std::vector<int> dead_fds;
  /* child comms borrowing these sockets (registered at split, removed
   * at finalize) so a reconnect can rewire every view of a link */
  std::mutex kids_mu;
  std::vector<Comm*> kids;

  ~Comm() {
    if (engine) engine_shutdown(engine);  // drains, joins, frees
    if (writer_started) {
      {
        std::lock_guard<std::mutex> lock(wmu);
        wstop = true;
      }
      wcv.notify_all();
      writer.join();
    }
    if (arena) arena_destroy(arena);
    if (listen_fd >= 0) ::close(listen_fd);
    for (int fd : dead_fds)
      if (fd >= 0) ::close(fd);
    delete topo;
  }
};

/* every op entry point locks the socket-owning ancestor */
std::mutex& comm_mu(Comm* c) { return c->lock_root->mu; }

std::mutex g_comms_mu;
std::map<int64_t, Comm*> g_comms;
int64_t g_next_handle = 1;

Comm* get_comm(int64_t h) {
  std::lock_guard<std::mutex> lock(g_comms_mu);
  auto it = g_comms.find(h);
  return it == g_comms.end() ? nullptr : it->second;
}

void count_sys_fwd();  // transport syscall counter (obs section below)

/* EAGAIN here is reachable only when the uring backend put the mesh on
 * non-blocking fds (a ring-creation failure then lands a direct caller
 * on these loops); park in poll() instead of spinning.  On the URING=0
 * path the fds are blocking unless a deadline is armed — and then the
 * _dl variants serve — so this branch is dead there and the historic
 * byte-for-byte behavior is untouched. */
int io_wait_ready(int fd, bool wr) {
  pollfd pf{fd, (short)(wr ? POLLOUT : POLLIN), 0};
  count_sys_fwd();
  return ::poll(&pf, 1, 60000);
}

int write_all(int fd, const void* buf, int64_t n) {
  const char* p = static_cast<const char*>(buf);
  while (n > 0) {
    count_sys_fwd();
    ssize_t w = ::write(fd, p, (size_t)n);
    if (w <= 0) {
      if (w < 0 && (errno == EINTR)) continue;
      if (w < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
        if (io_wait_ready(fd, true) < 0 && errno != EINTR) return 1;
        continue;
      }
      return 1;
    }
    p += w;
    n -= w;
  }
  return 0;
}

int read_all(int fd, void* buf, int64_t n) {
  char* p = static_cast<char*>(buf);
  while (n > 0) {
    count_sys_fwd();
    ssize_t r = ::read(fd, p, (size_t)n);
    if (r <= 0) {
      if (r < 0 && (errno == EINTR)) continue;
      if (r < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
        if (io_wait_ready(fd, false) < 0 && errno != EINTR) return 1;
        continue;
      }
      if (r == 0) errno = ECONNRESET;  // EOF: don't report stale "Success"
      return 1;
    }
    p += r;
    n -= r;
  }
  return 0;
}

/* ============== observability event ring ==============
 *
 * One fixed-size ring of TpuObsEvent per process (see tpucomm.h).  All
 * entry points append through ObsScope; the blocking sub-phases add
 * their blocked time to a thread-local accumulator through ObsWaitTimer
 * so every event carries a wait/transfer split.  Disabled (default):
 * one relaxed atomic load per op, no clock reads, no ring writes —
 * g_obs_on is the ONLY thing the hot path touches. */

std::atomic<int> g_obs_on{0};
std::mutex g_obs_mu;
std::vector<TpuObsEvent> g_obs_ring;  // fixed capacity once enabled
int64_t g_obs_total = 0;              // appended since enable (kept + dropped)
int64_t g_obs_dropped = 0;            // overwritten by overflow
int64_t g_obs_seq = 0;                // appended since enable, NEVER reset by
                                      // drain — the absolute sequence space
                                      // tpucomm_obs_peek cursors live in
thread_local double g_obs_wait_acc = 0.0;

/* Self-healing link counters (process totals; see tpucomm_link_counters
 * in tpucomm.h).  The thread-local accumulator mirrors g_obs_wait_acc:
 * successful recoveries bump it so ObsScope can stamp the per-op
 * retries delta on the event that absorbed them. */
std::atomic<int64_t> g_lc_retries{0};      // recovery events entered
std::atomic<int64_t> g_lc_reconnects{0};   // successful reconnect handshakes
std::atomic<int64_t> g_lc_dup_dropped{0};  // duplicate data frames discarded
std::atomic<int64_t> g_lc_crc_errors{0};   // header/control CRC mismatches
std::atomic<int64_t> g_lc_replayed{0};     // retained frames retransmitted
std::atomic<int64_t> g_lc_heartbeats{0};   // idle-link pings sent
thread_local int64_t g_heal_acc = 0;

/* Transport syscall counter: every socket-moving syscall (write/read/
 * writev/send/recv/poll and io_uring_enter; futex parks excluded — they
 * are scheduling, not wire) bumps it, so events carry a per-op syscall
 * count and benchmarks read the process total (tpucomm_syscall_count).
 * Process-global (relaxed) rather than thread-local: the writer/
 * progress threads issue syscalls on BEHALF of the op executing on
 * another thread, and a per-op window over the global counter
 * attributes them to that op — exact for the serialized-op case the
 * benchmarks measure, conserved in total always. */
std::atomic<int64_t> g_syscalls{0};

inline void count_sys() { g_syscalls.fetch_add(1, std::memory_order_relaxed); }

void count_sys_fwd() { count_sys(); }  // callable from above-definition code

void obs_append(const TpuObsEvent& ev) {
  std::lock_guard<std::mutex> lock(g_obs_mu);
  if (g_obs_ring.empty()) return;  // disabled raced with the op's scope
  const int64_t cap = (int64_t)g_obs_ring.size();
  g_obs_ring[(size_t)(g_obs_total % cap)] = ev;
  if (g_obs_total >= cap) g_obs_dropped++;
  g_obs_total++;
  g_obs_seq++;
}

/* RAII event record for one transport op.  Constructed where the op
 * starts EXECUTING (inline on the calling thread, or on the progress
 * thread for queued descriptors); the destructor stamps duration and
 * the wait share accumulated by ObsWaitTimer scopes inside the op.
 * `t_post` (>= 0) is the submission time of an engine-queued op: the
 * event's t_start becomes the post time and queue_s the dispatch
 * delay (post -> execution start), so dur = queue + wait + wire. */
struct ObsScope {
  bool on;
  double t0 = 0, wait0 = 0, post = -1;
  int64_t sys0 = 0, heal0 = 0;
  TpuObsEvent ev{};
  ObsScope(int op, int peer, int tag, int64_t nbytes, int algo = -1,
           double t_post = -1) {
    on = g_obs_on.load(std::memory_order_relaxed) != 0;
    if (!on) return;
    ev.op = op;
    ev.peer = peer;
    ev.tag = tag;
    ev.nbytes = nbytes;
    ev.wire_bytes = nbytes;  // exact ops: the wire carries the payload
    ev.algo = algo;
    wait0 = g_obs_wait_acc;
    heal0 = g_heal_acc;
    sys0 = g_syscalls.load(std::memory_order_relaxed);
    post = t_post;
    t0 = now_s();
  }
  void set_algo(int algo) { ev.algo = algo; }
  /* quantized collectives: the payload's on-wire representation is the
   * packed codec size, not the logical bytes */
  void set_wire(int64_t wb) { ev.wire_bytes = wb; }
  /* hierarchical collectives: label a per-leg event with its transport
   * tier (intra-island vs inter-island) so stats split the bytes */
  void set_tier(int tier) { ev.tier = tier; }
  ~ObsScope() {
    if (!on) return;
    double t1 = now_s();
    double start = post >= 0 && post <= t0 ? post : t0;
    ev.t_start = start;
    ev.dur_s = t1 - start;
    ev.queue_s = t0 - start;
    ev.wait_s = g_obs_wait_acc - wait0;
    if (ev.wait_s > ev.dur_s - ev.queue_s) ev.wait_s = ev.dur_s - ev.queue_s;
    int64_t ds = g_syscalls.load(std::memory_order_relaxed) - sys0;
    ev.syscalls = ds > INT32_MAX ? INT32_MAX : (int32_t)(ds < 0 ? 0 : ds);
    int64_t dh = g_heal_acc - heal0;
    ev.retries = dh > INT32_MAX ? INT32_MAX : (int32_t)(dh < 0 ? 0 : dh);
    obs_append(ev);
  }
};

/* Accumulates blocked time (header arrival, barrier rendezvous) into
 * the wait share of the enclosing ObsScope.  Scoped tightly around the
 * blocking call itself. */
struct ObsWaitTimer {
  bool on;
  double t0 = 0;
  ObsWaitTimer() {
    on = g_obs_on.load(std::memory_order_relaxed) != 0;
    if (on) t0 = now_s();
  }
  ~ObsWaitTimer() {
    if (on) g_obs_wait_acc += now_s() - t0;
  }
};

/* ============== failure detection: transport deadlines ==============
 *
 * MPI4JAX_TPU_TIMEOUT_S bounds every blocking wait on the TCP mesh
 * with a PROGRESS-based deadline: the clock resets whenever any byte
 * moves, so a slow-but-live bulk transfer survives while a wedged peer
 * (hung process, dead NIC, lost frame) trips the deadline instead of
 * hanging the whole job forever.  0 (the default) keeps the historic
 * infinite blocking loops bit-for-bit.  The same knob caps the shm
 * arena's barrier/ring waits (see shm_timeout_s) so one deadline
 * bounds the job regardless of which path a message rides. */

/* Strict seconds parser: a typo'd deadline knob must stop the job, not
 * silently arm NO deadline while the operator believes one is set (the
 * same loud-failure contract as the fault spec and COLL_ALGO parsers).
 * Returns the parsed value; callers clamp non-positive to their "off" /
 * default semantics. */
double parse_env_seconds(const char* name, double dflt) {
  const char* e = std::getenv(name);
  if (!e || !e[0]) return dflt;
  char* end = nullptr;
  double v = std::strtod(e, &end);
  const bool converted = end != e;
  while (end && (*end == ' ' || *end == '\t')) end++;
  if (!converted || (end && *end)) {
    std::fprintf(stderr, "tpucomm: cannot parse %s=%s as seconds\n", name,
                 e);
    std::exit(2);
  }
  return v;
}

double transport_timeout_s() {
  static double v = [] {
    double t = parse_env_seconds("MPI4JAX_TPU_TIMEOUT_S", 0.0);
    return t > 0 ? t : 0.0;  // 0 = no deadline (historic behavior)
  }();
  return v;
}

/* 0 means OFF (same convention as TIMEOUT_S): dial retries forever and
 * accept blocks forever.  Unset = the 30 s default the old fixed
 * 600 x 50 ms retry spin gave the dial side. */
double connect_timeout_s() {
  static double v = [] {
    double t = parse_env_seconds("MPI4JAX_TPU_CONNECT_TIMEOUT_S", 30.0);
    return t > 0 ? t : 0.0;
  }();
  return v;
}

/* ============== self-healing link knobs ==============
 *
 * MPI4JAX_TPU_RETRY arms the link layer: wire headers grow to
 * MsgHeaderX, retained small sends double as a retransmit buffer, and a
 * failing socket gets up to RETRY reconnect attempts (exponential
 * backoff from MPI4JAX_TPU_RETRY_BACKOFF_MS, with jitter) before the
 * failure escalates through the historic poison -> abort -> elastic
 * path.  0 (the default) keeps today's fail-fast path bit-for-bit.
 * Strict parsing, same loud contract as every other knob. */

int64_t parse_env_int(const char* name, int64_t dflt) {
  const char* e = std::getenv(name);
  if (!e || !e[0]) return dflt;
  char* end = nullptr;
  long long v = std::strtoll(e, &end, 10);
  while (end && (*end == ' ' || *end == '\t')) end++;
  if (end == e || (end && *end)) {
    std::fprintf(stderr, "tpucomm: cannot parse %s=%s as an integer\n",
                 name, e);
    std::exit(2);
  }
  return (int64_t)v;
}

int64_t retry_budget() {
  static int64_t v = [] {
    int64_t n = parse_env_int("MPI4JAX_TPU_RETRY", 0);
    return n > 0 ? n : 0;
  }();
  return v;
}

bool retry_armed() { return retry_budget() > 0; }

/* Bytes each frame header occupies on the wire under the current arming
 * (diag/tests cross-check the overhead claim against this). */
[[maybe_unused]] int64_t wire_hdr_bytes() {
  return retry_armed() ? (int64_t)sizeof(MsgHeaderX)
                       : (int64_t)sizeof(MsgHeader);
}

double retry_backoff_ms() {
  static double v = [] {
    double t = parse_env_seconds("MPI4JAX_TPU_RETRY_BACKOFF_MS", 100.0);
    return t > 0 ? t : 100.0;
  }();
  return v;
}

double heartbeat_s() {
  static double v = [] {
    double t = parse_env_seconds("MPI4JAX_TPU_HEARTBEAT_S", 0.0);
    return t > 0 ? t : 0.0;
  }();
  return v;
}

/* Test-only protocol exerciser: replay N extra already-delivered frames
 * on every reconnect, so the receiver's dedup layer provably fires
 * (dup_dropped > 0) while digests stay bit-identical. */
int64_t replay_slack() {
  static int64_t v = [] {
    int64_t n = parse_env_int("MPI4JAX_TPU_RETRY_REPLAY_SLACK", 0);
    return n > 0 ? n : 0;
  }();
  return v;
}

/* MPI4JAX_TPU_WIRE_CRC = auto (default: on iff the link layer is
 * armed — the CRC field only exists in the extended header) | 0 | 1.
 * 1 with RETRY=0 is a spec error: there is no header field to carry
 * the checksum, so honoring it silently would protect nothing. */
bool wire_crc_on() {
  static bool v = [] {
    const char* e = std::getenv("MPI4JAX_TPU_WIRE_CRC");
    if (!e || !e[0] || std::strcmp(e, "auto") == 0) return retry_armed();
    if (std::strcmp(e, "0") == 0) return false;
    if (std::strcmp(e, "1") == 0) {
      if (!retry_armed()) {
        std::fprintf(stderr,
                     "tpucomm: MPI4JAX_TPU_WIRE_CRC=1 requires "
                     "MPI4JAX_TPU_RETRY > 0 (the 16-byte legacy header "
                     "has no checksum field)\n");
        std::exit(2);
      }
      return true;
    }
    std::fprintf(stderr,
                 "tpucomm: cannot parse MPI4JAX_TPU_WIRE_CRC=%s "
                 "(expected auto|0|1)\n", e);
    std::exit(2);
  }();
  return v;
}

/* CRC32C (Castagnoli), software table — headers are 32 bytes, so the
 * table lookup is noise next to the syscall that carries them. */
uint32_t crc32c(const void* data, size_t n) {
  static const uint32_t* table = [] {
    static uint32_t t[256];
    for (uint32_t i = 0; i < 256; i++) {
      uint32_t c = i;
      for (int k = 0; k < 8; k++)
        c = (c & 1) ? (0x82f63b78u ^ (c >> 1)) : (c >> 1);
      t[i] = c;
    }
    return t;
  }();
  uint32_t c = 0xffffffffu;
  const uint8_t* p = static_cast<const uint8_t*>(data);
  for (size_t i = 0; i < n; i++) c = table[(c ^ p[i]) & 0xff] ^ (c >> 8);
  return c ^ 0xffffffffu;
}

/* Stamp an extended header's CRC field (zeroed during the computation). */
void hx_seal(MsgHeaderX* hx) {
  hx->crc = 0;
  if (wire_crc_on())
    hx->crc = crc32c(hx, offsetof(MsgHeaderX, crc));
}

/* Verify a received extended header.  Control payloads are covered by
 * their own seals; data payloads are NOT covered (documented scope:
 * large-payload CRC would tax the hot path; header integrity is what
 * protects stream framing). */
bool hx_check(const MsgHeaderX* hx) {
  if (!wire_crc_on()) return true;
  MsgHeaderX tmp = *hx;
  tmp.crc = 0;
  return crc32c(&tmp, offsetof(MsgHeaderX, crc)) == hx->crc;
}

/* MPI4JAX_TPU_FAKE_HOSTS=r0,r1|r2,r3 — virtual host partition for
 * topology testing on one machine: ranks in one '|'-separated group are
 * treated as sharing a (virtual) host for arena eligibility, ranks in
 * different groups as host-separated even over loopback.  Tokens are
 * `rN` or bare `N`, indexing CURRENT world ranks (an elastic rebuild
 * re-applies the spec against the dense new ranks).  Ranks not listed
 * keep their real host.  Malformed specs exit loudly (same contract as
 * MPI4JAX_TPU_FAULT: a typo'd partition must not silently test the
 * wrong shape).  Out-of-range ranks are ignored (a spec written for
 * np=4 stays valid on a shrunk np=2 world). */
void apply_fake_hosts(std::vector<std::string>& hosts, int size) {
  const char* e = std::getenv("MPI4JAX_TPU_FAKE_HOSTS");
  if (!e || !e[0]) return;
  std::vector<int> seen(hosts.size(), 0);
  int group = 0;
  const char* p = e;
  std::string tok;
  auto flush_tok = [&]() {
    /* trim whitespace */
    size_t b = tok.find_first_not_of(" \t");
    if (b == std::string::npos) {
      tok.clear();
      return;
    }
    tok = tok.substr(b, tok.find_last_not_of(" \t") - b + 1);
    const char* t = tok.c_str();
    if (*t == 'r' || *t == 'R') t++;
    char* end = nullptr;
    long r = std::strtol(t, &end, 10);
    /* digits only ('+5' / ' 5' inside a token would diverge from the
     * Python mirror, which accepts bare digits) */
    if (end == t || *end || r < 0 || !(*t >= '0' && *t <= '9')) {
      std::fprintf(stderr,
                   "tpucomm: cannot parse MPI4JAX_TPU_FAKE_HOSTS token "
                   "%s (expected rN or N, groups separated by |)\n",
                   tok.c_str());
      std::exit(2);
    }
    if (r < size) {
      if (seen[(size_t)r]) {
        std::fprintf(stderr,
                     "tpucomm: MPI4JAX_TPU_FAKE_HOSTS lists rank %ld "
                     "twice\n", r);
        std::exit(2);
      }
      seen[(size_t)r] = 1;
      hosts[(size_t)r] = "fake-host-" + std::to_string(group);
    }
    tok.clear();
  };
  for (;; p++) {
    if (*p == ',' || *p == '|' || *p == '\0') {
      flush_tok();
      if (*p == '|') group++;
      if (*p == '\0') break;
    } else {
      tok.push_back(*p);
    }
  }
}

/* progress detail for the caller's diagnostic when a deadline fires */
thread_local int64_t g_io_done = 0;
thread_local int64_t g_io_want = 0;

/* Deadline anchor for engine-queued ops: deadlines are measured from
 * POST time, not execution start — time an op spends behind others in
 * the submission queue is zero-progress time and must count against
 * the job deadline.  The progress-thread executor sets this to the
 * descriptor's post timestamp; the first deadline-bounded transfer of
 * the op consumes it (anchoring its initial window at the post time),
 * after which the usual any-progress-resets-the-clock rule applies. */
thread_local double g_dl_post_anchor = 0;

/* io_uring submission backend (defined after the fault section; probed
 * once per process, one ring per thread).  uring_io_all implements the
 * exact deadline/progress/anchor semantics of the poll loop below over
 * submitted SQEs instead of poll+read/write pairs. */
bool uring_ready();
int uring_io_all(int fd, void* buf, int64_t n, bool wr, double t);

/* Deadline-bounded read/write of exactly n bytes.  Returns 0 on
 * success, 1 on a socket error (errno describes it), 2 when the
 * deadline passed with zero bytes of progress (g_io_done / g_io_want
 * hold the transfer state).  `t` defaults to the job-wide knob; with
 * that unset this IS read_all/write_all.  With MPI4JAX_TPU_URING
 * active the transfer is submitted to the thread's io_uring instead
 * (same deadline semantics, fewer syscalls). */
template <bool kWrite>
int io_all_deadline(int fd, void* buf, int64_t n, double t = -1.0) {
  if (t < 0) t = transport_timeout_s();
  if (uring_ready()) return uring_io_all(fd, buf, n, kWrite, t);
  if (t <= 0)
    return kWrite ? write_all(fd, buf, n) : read_all(fd, buf, n);
  char* p = static_cast<char*>(buf);
  int64_t left = n;
  double deadline = now_s() + t;
  if (g_dl_post_anchor > 0) {
    /* queued op: the first window is anchored at post time (consumed
     * once; progress below re-anchors at now as usual) */
    double anchored = g_dl_post_anchor + t;
    if (anchored < deadline) deadline = anchored;
    g_dl_post_anchor = 0;
  }
  while (left > 0) {
    double remain = deadline - now_s();
    if (remain <= 0) {
      g_io_done = n - left;
      g_io_want = n;
      return 2;
    }
    pollfd pf{fd, (short)(kWrite ? POLLOUT : POLLIN), 0};
    count_sys();
    int pr = ::poll(&pf, 1, (int)std::min(remain * 1000.0 + 1, 60000.0));
    if (pr < 0) {
      if (errno == EINTR) continue;
      return 1;
    }
    if (pr == 0) continue;  // loop re-checks the deadline
    count_sys();
    ssize_t m = kWrite ? ::write(fd, p, (size_t)left)
                       : ::read(fd, p, (size_t)left);
    if (m <= 0) {
      if (m < 0 && (errno == EINTR || errno == EAGAIN ||
                    errno == EWOULDBLOCK))
        continue;
      if (m == 0 && !kWrite) errno = ECONNRESET;  // EOF, not "Success"
      return 1;
    }
    p += m;
    left -= m;
    deadline = now_s() + t;  // any progress resets the clock
  }
  return 0;
}

int read_all_dl(int fd, void* buf, int64_t n) {
  return io_all_deadline<false>(fd, buf, n);
}

int write_all_dl(int fd, const void* buf, int64_t n) {
  return io_all_deadline<true>(fd, const_cast<void*>(buf), n);
}

/* Caller-side diagnostic for a *_dl result: rc 2 = deadline (op, peer,
 * comm, and bytes-progressed detail), rc 1 = the historic errno text.
 * dir_fmt is a printf format with one %d for the peer rank, e.g.
 * "send to %d" — the rc 1 message matches the pre-deadline wording. */
#define FAIL_IO(comm, rc, dir_fmt, peer)                                    \
  do {                                                                      \
    if ((rc) == 2)                                                          \
      FAIL(comm,                                                            \
           dir_fmt " timed out after %.0f s on comm %d with %lld/%lld "     \
                   "bytes moved — the peer is hung or unreachable "         \
                   "(MPI4JAX_TPU_TIMEOUT_S)",                               \
           peer, transport_timeout_s(), (comm)->comm_id,                    \
           (long long)g_io_done, (long long)g_io_want);                     \
    FAIL(comm, dir_fmt " failed: %s", peer, std::strerror(errno));          \
  } while (0)

/* ============== deterministic fault injection ==============
 *
 * MPI4JAX_TPU_FAULT=rank=R,point=send|recv|connect,after=N,action=hang|exit|close
 * arms exactly one fault in the native layer: on rank R, the (N+1)-th
 * operation at the given point (N defaults to 0) either hangs forever,
 * exits the process (code 17, simulating a crash), or shuts down every
 * mesh socket (simulating a network partition).  This is how the
 * timeout / abort-propagation / watchdog paths are exercised by real
 * multi-process tests — a typo'd spec fails the job loudly instead of
 * silently injecting nothing. */

enum FaultPoint { FP_NONE = 0, FP_SEND, FP_RECV, FP_CONNECT };
enum FaultAction {
  FA_NONE = 0, FA_HANG, FA_EXIT, FA_CLOSE,
  /* transient link faults (one-shot; the self-healing layer is
   * expected to absorb them when armed, or the job to abort loudly) */
  FA_RESET,    // SO_LINGER(0) + close: RST both directions
  FA_DROP,     // kill the connection mid-frame after `param` bytes
  FA_DELAY,    // stall the op for `param` milliseconds, then proceed
  FA_CORRUPT,  // flip a byte in the next wire header after CRC stamping
};

struct FaultSpec {
  bool armed = false;
  int rank = -1;
  int point = FP_NONE;
  long long after = 0;
  int action = FA_NONE;
  long long param = 0;  // drop: bytes before the RST; delay: milliseconds
  std::atomic<long long> hits{0};
};
FaultSpec g_fault;

/* A fired drop/corrupt fault arms this thread-local order for the NEXT
 * wire frame this thread writes; link_send_frame consumes it.  (The
 * fire site and the frame writer are the same thread: inline sends,
 * the writer thread, and the engine drain loop all fire the injector
 * immediately before building their frame.) */
struct WireFault {
  int action = FA_NONE;
  long long param = 0;
};
thread_local WireFault g_wire_fault;
std::once_flag g_fault_once;
/* the spec's rank=R is a JOB rank: comm-local ranks diverge on split
 * sub-comms, so injection keys on the rank this process was born with */
int g_job_rank = -1;

void fault_parse() {
  const char* e = std::getenv("MPI4JAX_TPU_FAULT");
  if (!e || !e[0]) return;
  int rank = -1, point = FP_NONE, action = FA_NONE;
  long long after = 0, param = 0;
  int has_param = 0;
  bool ok = true;
  std::string s(e);
  size_t pos = 0;
  while (pos < s.size() && ok) {
    size_t comma = s.find(',', pos);
    std::string kv = s.substr(pos, comma == std::string::npos
                                       ? std::string::npos
                                       : comma - pos);
    pos = comma == std::string::npos ? s.size() : comma + 1;
    size_t eq = kv.find('=');
    if (eq == std::string::npos) {
      ok = false;
      break;
    }
    std::string k = kv.substr(0, eq), v = kv.substr(eq + 1);
    /* numeric fields parse strictly: atoi("x") == 0 would silently arm
     * the fault on rank 0 — the fake-green failure mode this parser's
     * loud-exit contract exists to prevent */
    auto parse_ll = [&ok](const std::string& s, long long* out) {
      char* end = nullptr;
      long long n = std::strtoll(s.c_str(), &end, 10);
      if (end == s.c_str() || *end) ok = false;
      *out = n;
    };
    if (k == "rank") {
      long long r = -1;
      parse_ll(v, &r);
      rank = (int)r;
    } else if (k == "after") {
      parse_ll(v, &after);
    } else if (k == "bytes" || k == "ms") {
      /* drop=N bytes before the RST / delay=N milliseconds; validated
       * against the action below */
      parse_ll(v, &param);
      has_param = k == "bytes" ? 1 : 2;
    } else if (k == "point") {
      point = v == "send" ? FP_SEND
              : v == "recv" ? FP_RECV
              : v == "connect" ? FP_CONNECT
                               : FP_NONE;
      ok = point != FP_NONE;
    } else if (k == "action") {
      action = v == "hang" ? FA_HANG
               : v == "exit" ? FA_EXIT
               : v == "close" ? FA_CLOSE
               : v == "reset" ? FA_RESET
               : v == "drop" ? FA_DROP
               : v == "delay" ? FA_DELAY
               : v == "corrupt" ? FA_CORRUPT
                                : FA_NONE;
      ok = action != FA_NONE;
    } else {
      ok = false;
    }
  }
  /* bytes= only modifies drop, ms= only delay (an ignored parameter
   * would silently test a different fault than the spec says) */
  if (has_param == 1 && action != FA_DROP) ok = false;
  if (has_param == 2 && action != FA_DELAY) ok = false;
  if (has_param && param < 0) ok = false;
  if (!ok || rank < 0 || point == FP_NONE || action == FA_NONE) {
    std::fprintf(stderr,
                 "tpucomm: malformed MPI4JAX_TPU_FAULT spec %s (expected "
                 "rank=R,point=send|recv|connect[,after=N],"
                 "action=hang|exit|close|reset|drop|delay|corrupt"
                 "[,bytes=N][,ms=N])\n",
                 e);
    std::exit(2);  // silently injecting nothing would fake a green test
  }
  if (!has_param) param = action == FA_DROP ? 20 : 100;
  g_fault.rank = rank;
  g_fault.point = point;
  g_fault.after = after;
  g_fault.action = action;
  g_fault.param = param;
  g_fault.armed = true;
}

void fault_init() { std::call_once(g_fault_once, fault_parse); }

/* RST the connection: SO_LINGER{on, 0} + close sends a reset instead
 * of a FIN, so both ends see ECONNRESET — the transient-fault shape
 * the self-healing layer is built to absorb.  Test-only (the closed fd
 * number may be reused; real traffic never calls this). */
void linger_rst(int fd) {
  struct linger lg{1, 0};
  ::setsockopt(fd, SOL_SOCKET, SO_LINGER, &lg, sizeof(lg));
  ::close(fd);
}

/* Fire the armed fault if (rank, point) match and `after` ops have
 * already passed this point.  `c` may be null at the connect point.
 * `fd` is the socket the firing op is about to use (-1 when unknown):
 * reset kills exactly that connection; drop/corrupt arm a thread-local
 * order the frame writer consumes. */
void fault_fire(Comm* c, int rank, int point, const char* what,
                int fd = -1) {
  if (!g_fault.armed || g_fault.rank != rank || g_fault.point != point)
    return;
  if (g_fault.hits.fetch_add(1, std::memory_order_relaxed) < g_fault.after)
    return;
  const char* action = g_fault.action == FA_HANG ? "hang"
                       : g_fault.action == FA_EXIT ? "exit"
                       : g_fault.action == FA_CLOSE ? "close"
                       : g_fault.action == FA_RESET ? "reset"
                       : g_fault.action == FA_DROP ? "drop"
                       : g_fault.action == FA_DELAY ? "delay"
                                                    : "corrupt";
  std::fprintf(stderr,
               "tpucomm r%d: fault injection: %s at point=%s "
               "(MPI4JAX_TPU_FAULT)\n",
               rank, action, what);
  std::fflush(stderr);
  switch (g_fault.action) {
    case FA_HANG:
      for (;;) std::this_thread::sleep_for(std::chrono::seconds(3600));
    case FA_EXIT:
      std::_Exit(17);
    case FA_CLOSE:
      /* shutdown (not close): other threads may hold the fds; all
       * their I/O now fails/EOFs, exactly like a yanked cable */
      if (c)
        for (int fd2 : c->lock_root->socks)
          if (fd2 >= 0) ::shutdown(fd2, SHUT_RDWR);
      g_fault.armed = false;  // a partition happens once
      break;
    case FA_RESET:
      if (fd >= 0)
        linger_rst(fd);
      else if (c)
        /* no specific socket at this point: reset the whole mesh (the
         * self-healing layer reconnects each link it touches next) */
        for (int fd2 : c->lock_root->socks)
          if (fd2 >= 0) linger_rst(fd2);
      g_fault.armed = false;  // a transient happens once
      break;
    case FA_DROP:
    case FA_CORRUPT:
      /* armed for the next frame THIS thread writes; when the link
       * layer is off there is no frame writer to consume the order, so
       * degrade to a reset at the fire point — the fault still lands
       * and the job still fails loudly instead of testing nothing */
      if (retry_armed()) {
        g_wire_fault.action = g_fault.action;
        g_wire_fault.param = g_fault.param;
      } else if (fd >= 0) {
        linger_rst(fd);
      } else if (c) {
        for (int fd2 : c->lock_root->socks)
          if (fd2 >= 0) linger_rst(fd2);
      }
      g_fault.armed = false;
      break;
    case FA_DELAY:
      std::this_thread::sleep_for(
          std::chrono::milliseconds(g_fault.param > 0 ? g_fault.param : 100));
      g_fault.armed = false;
      break;
    default:
      break;
  }
}

/* ============== zero-copy submission backend (UringEngine) ==============
 *
 * The transport floor below the progress engine: when MPI4JAX_TPU_URING
 * resolves to on, every deadline-bounded transfer routes through a
 * per-thread io_uring instead of the poll+read/write pairs — one
 * io_uring_enter both submits and waits, so a small send (header and
 * payload staged into one registered-buffer frame) or a small receive
 * (header + payload speculatively read in one submission) costs ONE
 * syscall where the poll path pays four; the drain loop's descriptor
 * bursts ride single vectored submissions; and oversized sends go out
 * as MSG_ZEROCOPY (IORING_OP_SEND_ZC) with the kernel's buffer-release
 * notification consumed as a CQE before the op returns, so large
 * payloads skip the kernel copy while the caller keeps the historic
 * buffer-ownership contract.
 *
 * Everything layered above the byte movers is untouched: deadlines are
 * progress-based and anchored at post time (the same g_dl_post_anchor
 * handoff), poison frames and fault injection fire at the same logical
 * points, the coalesced-frame wire format is byte-identical, and
 * MPI4JAX_TPU_URING=0 keeps the poll-driven path for sanitizer builds
 * and old kernels.  One ring per thread (rings are not thread-safe;
 * the calling thread, the progress thread, and the writer thread each
 * lazily own one), torn down at thread exit; a ring that loses track
 * of an in-flight completion is marked broken and rebuilt. */

/* ABI constants newer than the build host's kernel headers (the
 * io_uring ABI is append-only; values from include/uapi/linux) */
constexpr uint8_t kOpSendZc = 47;           /* IORING_OP_SEND_ZC (6.0) */
#ifndef IORING_CQE_F_NOTIF
#define IORING_CQE_F_NOTIF (1U << 3)
#endif
/* sqe->ioprio flag (6.2+): the buffer-release NOTIF cqe reports in its
 * res whether the kernel actually avoided the copy */
constexpr uint16_t kSendZcReportUsage = 1U << 3;
constexpr uint32_t kNotifZcCopied = 1U << 31; /* IORING_NOTIF_USAGE_ZC_COPIED */

constexpr int64_t kZcBytes = 64 * 1024;     /* MSG_ZEROCOPY chunk floor
                                             * (op gate: zc_min_bytes) */
constexpr int64_t kUringSmall = 32 * 1024;  /* staged single-frame ceiling
                                             * (mirrors kEagerBytes) */
constexpr size_t kUringStageBytes =
    (size_t)kUringSmall + 4096;             /* frame staging + recv stash */

struct KernelTimespec {  /* __kernel_timespec (s64/s64) */
  int64_t tv_sec;
  int64_t tv_nsec;
};

int g_uring_avail = 0;          /* resolved by uring_probe() */
bool g_uring_zc = false;        /* kernel supports IORING_OP_SEND_ZC */
char g_uring_reason[160] = "not probed";

/* Adaptive MSG_ZEROCOPY: the kernel pins the pages but may still COPY
 * at delivery (loopback and NIC-without-SG paths go through
 * skb_orphan_frags_rx) and then the zero-copy send is all overhead —
 * pinning plus a notification per send for nothing.  The NOTIF cqe
 * reports which happened (kSendZcReportUsage); a streak of copied
 * notifications with no true zero-copy turns SEND_ZC off process-wide
 * and large sends ride plain submitted sends instead.  Kernels older
 * than 6.2 reject the report flag (-EINVAL, retried once without), and
 * then there is no signal — ZC stays on as probed. */
std::atomic<bool> g_zc_report_ok{true};
std::atomic<int> g_zc_copied_streak{0};
std::atomic<bool> g_zc_fallback{false};
constexpr int kZcCopiedStreakOff = 4;

void zc_note_usage(int32_t res) {
  if (!g_zc_report_ok.load(std::memory_order_relaxed)) return;
  if ((uint32_t)res & kNotifZcCopied) {
    int s = g_zc_copied_streak.fetch_add(1, std::memory_order_relaxed) + 1;
    if (s >= kZcCopiedStreakOff)
      /* visible through tpucomm_uring_status() as
       * "on(zerocopy-fallback)" — diag and the bench rows stamp it */
      g_zc_fallback.store(true, std::memory_order_relaxed);
  } else {
    g_zc_copied_streak.store(0, std::memory_order_relaxed);
  }
}

bool zc_enabled() {
  return g_uring_zc && !g_zc_fallback.load(std::memory_order_relaxed);
}

/* Completion-envelope equivalence: a plain send completes once the
 * kernel ACCEPTS the bytes (sndbuf plus whatever the receiver's kernel
 * absorbs in flight), but a SEND_ZC's buffer release waits for the
 * skbs to be FREED — past that window, for the receiving APPLICATION
 * to consume.  Engaging zero-copy for a payload the kernel could have
 * buffered would turn a buffered send into a rendezvous and deadlock
 * cyclic schedules that the poll path (and the analysis match model)
 * accept.  So ZC is gated to sends that exceed the kernel's maximum
 * possible buffering — the TCP autotune ceilings tcp_wmem[2] +
 * tcp_rmem[2] — where the poll path would also have waited on the
 * receiver and the completion envelopes coincide. */
int64_t proc_tcp_ceiling(const char* path, int64_t dflt) {
  FILE* f = std::fopen(path, "re");
  if (!f) return dflt;
  long long lo = 0, mid = 0, hi = 0;
  int n = std::fscanf(f, "%lld %lld %lld", &lo, &mid, &hi);
  std::fclose(f);
  return n == 3 && hi > 0 ? (int64_t)hi : dflt;
}

int64_t zc_min_bytes() {
  static int64_t v = [] {
    int64_t w = proc_tcp_ceiling("/proc/sys/net/ipv4/tcp_wmem", 4 << 20);
    int64_t r = proc_tcp_ceiling("/proc/sys/net/ipv4/tcp_rmem", 6 << 20);
    return std::max(kZcBytes, w + r);
  }();
  return v;
}

/* MPI4JAX_TPU_URING: auto (-1, probe) | 0 (off) | 1 (on, loud when the
 * kernel cannot).  Strict: a typo'd knob must not silently change the
 * submission path under a sanitizer build or a benchmark. */
int uring_mode() {
  static int m = [] {
    const char* e = std::getenv("MPI4JAX_TPU_URING");
    if (!e) return -1;
    /* whitespace-trimmed, like config.uring_mode() (the Python mirror
     * pins byte-for-byte parity) and the sibling knob parsers */
    const char* b = e;
    while (*b && std::isspace((unsigned char)*b)) ++b;
    const char* t = b + std::strlen(b);
    while (t > b && std::isspace((unsigned char)t[-1])) --t;
    std::string v(b, t);
    if (v.empty() || v == "auto") return -1;
    if (v == "0") return 0;
    if (v == "1") return 1;
    std::fprintf(stderr,
                 "tpucomm: cannot parse MPI4JAX_TPU_URING=%s (expected "
                 "auto, 0, or 1)\n", e);
    std::exit(2);
    return 0;
  }();
  return m;
}

struct Uring {
  int fd = -1;
  void* ring_mem = MAP_FAILED;
  size_t ring_bytes = 0;
  void* sqe_mem = MAP_FAILED;
  size_t sqe_bytes = 0;
  unsigned* sq_head = nullptr;
  unsigned* sq_tail = nullptr;
  unsigned sq_mask = 0;
  unsigned* sq_array = nullptr;
  unsigned* cq_head = nullptr;
  unsigned* cq_tail = nullptr;
  unsigned cq_mask = 0;
  io_uring_sqe* sqes = nullptr;
  io_uring_cqe* cqes = nullptr;
  uint64_t seq = 0;          /* user_data generator */
  bool broken = false;       /* lost an in-flight CQE: rebuild the ring */
  bool fixed_ok = true;      /* READ/WRITE_FIXED accepted on sockets */
  bool registered = false;   /* stage is an IORING_REGISTER_BUFFERS pool */
  std::vector<char> stage;   /* hot payload pool: staged small frames,
                              * speculative receive stash */
  std::vector<uint64_t> notifs; /* SEND_ZC buffer-release notifications
                                 * still in flight (deferred: collected
                                 * opportunistically by every CQE scan,
                                 * forced by u_flush_notifs before a
                                 * zero-copy send returns) */
  ~Uring() {
    if (sqe_mem != MAP_FAILED) ::munmap(sqe_mem, sqe_bytes);
    if (ring_mem != MAP_FAILED) ::munmap(ring_mem, ring_bytes);
    if (fd >= 0) ::close(fd);
  }
};

/* `why` (optional) receives the failure reason.  Only the call_once
 * probe passes the g_uring_reason global — per-thread ring creation in
 * uring_acquire runs concurrently and must not race writers/readers of
 * the process-wide status string. */
Uring* uring_make(unsigned entries, char* why = nullptr,
                  size_t why_len = 0) {
  io_uring_params p{};
  count_sys();
  int fd = (int)::syscall(__NR_io_uring_setup, entries, &p);
  if (fd < 0) {
    if (why)
      std::snprintf(why, why_len, "io_uring_setup: %s",
                    std::strerror(errno));
    return nullptr;
  }
  if (!(p.features & IORING_FEAT_SINGLE_MMAP) ||
      !(p.features & IORING_FEAT_EXT_ARG) ||
      !(p.features & IORING_FEAT_NODROP)) {
    if (why)
      std::snprintf(why, why_len,
                    "kernel io_uring lacks SINGLE_MMAP/EXT_ARG/NODROP "
                    "(features 0x%x; needs >= 5.11)", p.features);
    ::close(fd);
    return nullptr;
  }
  auto u = std::make_unique<Uring>();
  u->fd = fd;
  u->ring_bytes = std::max<size_t>(
      p.sq_off.array + p.sq_entries * sizeof(unsigned),
      p.cq_off.cqes + p.cq_entries * sizeof(io_uring_cqe));
  u->ring_mem = ::mmap(nullptr, u->ring_bytes, PROT_READ | PROT_WRITE,
                       MAP_SHARED | MAP_POPULATE, fd, IORING_OFF_SQ_RING);
  u->sqe_bytes = p.sq_entries * sizeof(io_uring_sqe);
  u->sqe_mem = ::mmap(nullptr, u->sqe_bytes, PROT_READ | PROT_WRITE,
                      MAP_SHARED | MAP_POPULATE, fd, IORING_OFF_SQES);
  if (u->ring_mem == MAP_FAILED || u->sqe_mem == MAP_FAILED) {
    if (why)
      std::snprintf(why, why_len, "io_uring ring mmap: %s",
                    std::strerror(errno));
    return nullptr;
  }
  char* r = static_cast<char*>(u->ring_mem);
  u->sq_head = reinterpret_cast<unsigned*>(r + p.sq_off.head);
  u->sq_tail = reinterpret_cast<unsigned*>(r + p.sq_off.tail);
  u->sq_mask = *reinterpret_cast<unsigned*>(r + p.sq_off.ring_mask);
  u->sq_array = reinterpret_cast<unsigned*>(r + p.sq_off.array);
  u->cq_head = reinterpret_cast<unsigned*>(r + p.cq_off.head);
  u->cq_tail = reinterpret_cast<unsigned*>(r + p.cq_off.tail);
  u->cq_mask = *reinterpret_cast<unsigned*>(r + p.cq_off.ring_mask);
  u->cqes = reinterpret_cast<io_uring_cqe*>(r + p.cq_off.cqes);
  u->sqes = static_cast<io_uring_sqe*>(u->sqe_mem);
  u->stage.resize(kUringStageBytes);
  struct iovec iov {u->stage.data(), u->stage.size()};
  count_sys();
  if (::syscall(__NR_io_uring_register, fd, IORING_REGISTER_BUFFERS, &iov,
                1) == 0)
    u->registered = true;  /* pinned pool: READ/WRITE_FIXED skip per-op
                            * page pinning; soft — plain ops serve */
  return u.release();
}

/* One-time probe: resolves availability + SEND_ZC support.  mode 1 on
 * an incapable kernel warns loudly and serves the poll path — the CI
 * legs probe tpucomm_uring_status first and SKIP visibly instead. */
void uring_probe() {
  static std::once_flag once;
  std::call_once(once, [] {
    if (uring_mode() == 0) {
      std::snprintf(g_uring_reason, sizeof(g_uring_reason),
                    "disabled (MPI4JAX_TPU_URING=0)");
      return;
    }
    std::unique_ptr<Uring> probe(
        uring_make(8, g_uring_reason, sizeof(g_uring_reason)));
    if (!probe) {
      if (uring_mode() == 1)
        std::fprintf(stderr,
                     "tpucomm: MPI4JAX_TPU_URING=1 but io_uring is "
                     "unavailable (%s); serving the poll path\n",
                     g_uring_reason);
      return;
    }
    /* io_uring_probe ends in the flexible array ops[]: probe into an
     * aligned buffer with room for 64 entries behind the header */
    alignas(io_uring_probe) unsigned char
        buf[sizeof(io_uring_probe) + 64 * sizeof(io_uring_probe_op)] = {};
    auto* pr = reinterpret_cast<io_uring_probe*>(buf);
    count_sys();
    if (::syscall(__NR_io_uring_register, probe->fd, IORING_REGISTER_PROBE,
                  pr, 64) == 0 &&
        pr->ops_len > kOpSendZc &&
        (pr->ops[kOpSendZc].flags & IO_URING_OP_SUPPORTED))
      g_uring_zc = true;
    g_uring_avail = 1;
  });
}

/* The calling thread's ring, or null (knob off, kernel can't, or this
 * thread's ring creation failed).  A broken ring (lost CQE after a
 * failed cancel) is torn down — the kernel reaps its in-flight state
 * at fd close — and rebuilt once per breakage. */
Uring* uring_acquire() {
  uring_probe();
  if (g_uring_avail != 1) return nullptr;
  static thread_local std::unique_ptr<Uring> tl;
  static thread_local bool tried = false;
  if (tl && tl->broken) {
    tl.reset();
    tried = false;
  }
  if (!tried) {
    tried = true;
    tl.reset(uring_make(64));
  }
  return tl.get();
}

bool uring_ready() { return uring_acquire() != nullptr; }

io_uring_sqe* u_sqe(Uring* u, uint8_t opcode, int fd, const void* addr,
                    uint32_t len) {
  unsigned tail = __atomic_load_n(u->sq_tail, __ATOMIC_RELAXED);
  io_uring_sqe* s = &u->sqes[tail & u->sq_mask];
  std::memset(s, 0, sizeof(*s));
  s->opcode = opcode;
  s->fd = fd;
  s->addr = (uint64_t)(uintptr_t)addr;
  s->len = len;
  s->user_data = ++u->seq;
  u->sq_array[tail & u->sq_mask] = tail & u->sq_mask;
  __atomic_store_n(u->sq_tail, tail + 1, __ATOMIC_RELEASE);
  return s;
}

bool u_cqe(Uring* u, io_uring_cqe* out) {
  unsigned head = __atomic_load_n(u->cq_head, __ATOMIC_RELAXED);
  if (head == __atomic_load_n(u->cq_tail, __ATOMIC_ACQUIRE)) return false;
  *out = u->cqes[head & u->cq_mask];
  __atomic_store_n(u->cq_head, head + 1, __ATOMIC_RELEASE);
  return true;
}

/* submit + wait in one syscall.  wait_s < 0 waits unbounded; >= 0 uses
 * the EXT_ARG timeout.  Returns 0 (caller re-checks CQEs/deadline) or
 * -1 on a hard enter failure. */
int u_enter(Uring* u, unsigned to_submit, unsigned min_complete,
            double wait_s) {
  io_uring_getevents_arg arg{};
  KernelTimespec ts{};
  unsigned flags = IORING_ENTER_GETEVENTS;
  void* argp = nullptr;
  size_t argsz = 0;
  if (min_complete > 0 && wait_s >= 0) {
    double w = std::min(std::max(wait_s, 0.0), 60.0);
    ts.tv_sec = (int64_t)w;
    ts.tv_nsec = (int64_t)((w - (double)ts.tv_sec) * 1e9);
    arg.ts = (uint64_t)(uintptr_t)&ts;
    flags |= IORING_ENTER_EXT_ARG;
    argp = &arg;
    argsz = sizeof(arg);
  }
  count_sys();
  long r = ::syscall(__NR_io_uring_enter, u->fd, to_submit, min_complete,
                     flags, argp, argsz);
  if (r < 0 && errno != ETIME && errno != EINTR) return -1;
  return 0;
}

/* A CQE that is not the op currently waited on: either a stale CQE of
 * a cancelled earlier op (dropped) or a DEFERRED SEND_ZC buffer-release
 * notification (consumed — and its usage report feeds the adaptive
 * zero-copy fallback).  Every CQE scan routes misses through here so a
 * deferred notification can never be mistaken for garbage. */
void u_note_stale(Uring* u, const io_uring_cqe& c) {
  if (!(c.flags & IORING_CQE_F_NOTIF)) return;
  auto it = std::find(u->notifs.begin(), u->notifs.end(), c.user_data);
  if (it == u->notifs.end()) return;
  u->notifs.erase(it);
  zc_note_usage(c.res);
}

/* Collect every deferred SEND_ZC notification — called before a
 * zero-copy send_msg returns so the caller's buffer-ownership contract
 * holds (the kernel has released the pinned pages), WITHOUT having
 * serialized each chunk against the receiver mid-stream.  Bounded: a
 * notification that never arrives marks the ring broken (rebuilt on
 * next acquire; fd close releases the kernel-side state). */
int u_flush_notifs(Uring* u, double budget_s) {
  if (u->notifs.empty()) return 0;
  double limit = now_s() + (budget_s > 0 ? budget_s : 60.0);
  for (;;) {
    io_uring_cqe c;
    bool any = false;
    while (u_cqe(u, &c)) {
      any = true;
      u_note_stale(u, c);
    }
    if (u->notifs.empty()) return 0;
    if (!any && now_s() > limit) break;
    if (u_enter(u, 0, 1, 0.2) < 0) break;
  }
  u->broken = true;
  u->notifs.clear();
  return 1;
}

/* Submit one I/O SQE and wait for its completion — and, for SEND_ZC,
 * for the kernel's buffer-release notification CQE (the MSG_ZEROCOPY
 * errqueue event surfaced through the ring), so the caller's buffer-
 * ownership contract survives the zero-copy send.  With `defer_notif`
 * the notification is NOT waited for here: it is parked on u->notifs
 * (collected opportunistically by later CQE scans, forced by
 * u_flush_notifs before the enclosing send returns) so back-to-back
 * zero-copy chunks pipeline instead of serializing on the receiver.
 * Returns the op's res (> 0 bytes; 0 = EOF on a receive; < 0 =
 * -errno).  When the progress deadline expires first the in-flight SQE
 * is cancelled (and its CQE drained) and *timed_out is set; a drain
 * that fails marks the ring broken, so a recycled user_data can never
 * be mis-matched. */
int64_t u_do(Uring* u, uint8_t opcode, int fd, const void* p, int64_t len,
             uint16_t buf_index, double deadline, bool* timed_out,
             bool defer_notif = false) {
  *timed_out = false;
  io_uring_sqe* s = u_sqe(u, opcode, fd, p,
                          (uint32_t)std::min<int64_t>(len, 1 << 30));
  if (opcode == IORING_OP_READ_FIXED || opcode == IORING_OP_WRITE_FIXED)
    s->buf_index = buf_index;
  if (opcode == kOpSendZc &&
      g_zc_report_ok.load(std::memory_order_relaxed))
    s->ioprio = kSendZcReportUsage;  /* NOTIF res reports copied vs zc */
  const uint64_t ud = s->user_data;
  unsigned to_submit = 1;
  bool got_main = false, need_notif = false;
  int64_t res = 0;
  for (;;) {
    io_uring_cqe c;
    while (u_cqe(u, &c)) {
      if (c.user_data != ud) {
        u_note_stale(u, c);  /* deferred notif or cancelled-op residue */
        continue;
      }
      if (c.flags & IORING_CQE_F_NOTIF) {
        need_notif = false;
        zc_note_usage(c.res);
        continue;
      }
      got_main = true;
      res = c.res;
      if (c.flags & IORING_CQE_F_MORE) need_notif = true;
    }
    if (got_main && need_notif && defer_notif && res > 0) {
      u->notifs.push_back(ud);
      return res;
    }
    if (got_main && !need_notif) return res;
    double wait_s = -1.0;
    if (deadline > 0) {
      double remain = deadline - now_s();
      if (remain <= 0) {
        if (!got_main) {
          /* cancel the in-flight SQE and drain its CQE so the ring
           * stays coherent for the next op */
          io_uring_sqe* cs = u_sqe(u, IORING_OP_ASYNC_CANCEL, -1, nullptr, 0);
          cs->addr = ud;
          const uint64_t cud = cs->user_data;
          bool seen_cancel = false;
          double limit = now_s() + 5.0;
          unsigned sub = 1;
          while (!(got_main && !need_notif) || !seen_cancel) {
            io_uring_cqe d;
            bool any = false;
            while (u_cqe(u, &d)) {
              any = true;
              if (d.user_data == cud) {
                seen_cancel = true;
              } else if (d.user_data == ud) {
                if (d.flags & IORING_CQE_F_NOTIF) need_notif = false;
                else {
                  got_main = true;
                  if (d.flags & IORING_CQE_F_MORE) need_notif = true;
                }
              } else {
                u_note_stale(u, d);
              }
            }
            if ((got_main && !need_notif) && seen_cancel) break;
            if (!any && now_s() > limit) {
              u->broken = true;
              break;
            }
            if (u_enter(u, sub, 1, 0.2) < 0) {
              u->broken = true;
              break;
            }
            sub = 0;
          }
        }
        *timed_out = true;
        return 0;
      }
      wait_s = std::min(remain + 0.001, 60.0);
    }
    if (u_enter(u, to_submit, 1, wait_s) < 0) {
      u->broken = true;
      return -EIO;
    }
    to_submit = 0;
  }
}

/* The poll loop's exact deadline/progress/anchor semantics over
 * submitted SQEs.  `stage_fixed` marks transfers whose buffer lives in
 * the registered staging pool (READ/WRITE_FIXED, no per-op pinning);
 * writes past the buffering ceiling (zc_min_bytes) go out as SEND_ZC
 * when the kernel supports it. */
int u_io_all(Uring* u, int fd, char* p, int64_t n, bool wr, double t,
             bool stage_fixed = false) {
  int64_t left = n;
  double deadline = 0;
  if (t > 0) {
    deadline = now_s() + t;
    if (g_dl_post_anchor > 0) {
      double anchored = g_dl_post_anchor + t;
      if (anchored < deadline) deadline = anchored;
      g_dl_post_anchor = 0;
    }
  }
  /* zero-copy only past the kernel's autotune buffering ceiling (see
   * zc_min_bytes): below it a plain send completes without the
   * receiver, a ZC buffer release cannot, and the mismatch deadlocks
   * cyclic schedules the poll path accepts */
  const bool zc_ok = wr && !stage_fixed && zc_enabled() && n > zc_min_bytes();
  while (left > 0) {
    uint8_t op;
    uint16_t bidx = 0;
    if (wr) {
      if (zc_ok && left >= kZcBytes)
        op = kOpSendZc;
      else if (stage_fixed && u->registered && u->fixed_ok)
        op = IORING_OP_WRITE_FIXED;
      else
        op = IORING_OP_SEND;
    } else {
      op = (stage_fixed && u->registered && u->fixed_ok)
               ? IORING_OP_READ_FIXED
               : IORING_OP_RECV;
    }
    bool timed_out = false;
    /* zero-copy chunks defer their buffer-release notification (the
     * flush below collects them) — waiting per chunk would serialize
     * the whole payload against the receiver's consumption */
    int64_t m = u_do(u, op, fd, p, left, bidx, deadline, &timed_out,
                     op == kOpSendZc);
    if (timed_out) {
      g_io_done = n - left;
      g_io_want = n;
      u_flush_notifs(u, 0.5);  /* best effort: the job is tearing down */
      return 2;
    }
    if (m <= 0) {
      if (m == -EINTR || m == -EAGAIN) continue;
      if ((m == -EINVAL || m == -EOPNOTSUPP) &&
          (op == IORING_OP_WRITE_FIXED || op == IORING_OP_READ_FIXED)) {
        u->fixed_ok = false;  /* kernel rejects fixed ops here: fall back */
        continue;
      }
      if (m == -EINVAL && op == kOpSendZc &&
          g_zc_report_ok.load(std::memory_order_relaxed)) {
        /* kernel < 6.2: no REPORT_USAGE ioprio flag — retry without
         * (and without the adaptive copied signal, see zc_note_usage) */
        g_zc_report_ok.store(false, std::memory_order_relaxed);
        continue;
      }
      if (m == 0 && !wr) {
        errno = ECONNRESET;  /* EOF, not "Success" */
        return 1;
      }
      errno = m < 0 ? (int)-m : EIO;
      u_flush_notifs(u, 0.5);
      return 1;
    }
    p += m;
    left -= m;
    if (t > 0) deadline = now_s() + t;  /* any progress resets the clock */
  }
  /* the zero-copy ownership contract: every deferred notification must
   * land before the caller's buffer is considered released */
  if (wr && !u->notifs.empty() &&
      u_flush_notifs(u, t > 0 ? t : 0) != 0) {
    errno = EIO;
    return 1;
  }
  return 0;
}

int uring_io_all(int fd, void* buf, int64_t n, bool wr, double t) {
  Uring* u = uring_acquire();
  if (!u) return 1;  /* unreachable: callers gate on uring_ready() */
  return u_io_all(u, fd, static_cast<char*>(buf), n, wr, t);
}

/* One speculative receive: up to `len` bytes in a single submission
 * (blocks until at least one byte, exactly like the poll path's header
 * read).  Returns 0 and the byte count, 1 on error, 2 on deadline. */
int u_recv_some(Uring* u, int fd, char* p, int64_t len, int64_t* got,
                double t, bool stage_fixed) {
  double deadline = 0;
  if (t > 0) {
    deadline = now_s() + t;
    if (g_dl_post_anchor > 0) {
      double anchored = g_dl_post_anchor + t;
      if (anchored < deadline) deadline = anchored;
      g_dl_post_anchor = 0;
    }
  }
  for (;;) {
    uint8_t op = (stage_fixed && u->registered && u->fixed_ok)
                     ? IORING_OP_READ_FIXED
                     : IORING_OP_RECV;
    bool timed_out = false;
    int64_t m = u_do(u, op, fd, p, len, 0, deadline, &timed_out);
    if (timed_out) {
      g_io_done = 0;
      g_io_want = len;
      return 2;
    }
    if (m <= 0) {
      if (m == -EINTR || m == -EAGAIN) continue;
      if ((m == -EINVAL || m == -EOPNOTSUPP) && op == IORING_OP_READ_FIXED) {
        u->fixed_ok = false;
        continue;
      }
      if (m == 0) {
        errno = ECONNRESET;
        return 1;
      }
      errno = (int)-m;
      return 1;
    }
    *got = m;
    return 0;
  }
}

/* Vectored deadline-bounded write: the drain loop's descriptor-burst
 * twin of io_all_deadline (iovecs are advanced in place on partial
 * writes; wire bytes are EXACTLY the concatenated frames).  Routes to
 * one OP_WRITEV submission per attempt under uring, poll+writev pairs
 * otherwise. */
void iov_consume(struct iovec** piov, int* pcnt, size_t done) {
  struct iovec* iov = *piov;
  int cnt = *pcnt;
  while (done > 0 && cnt > 0) {
    if (done >= iov->iov_len) {
      done -= iov->iov_len;
      iov++;
      cnt--;
    } else {
      iov->iov_base = static_cast<char*>(iov->iov_base) + done;
      iov->iov_len -= done;
      done = 0;
    }
  }
  *piov = iov;
  *pcnt = cnt;
}

int writev_all_dl(int fd, struct iovec* iov, int iovcnt, int64_t total) {
  const double t = transport_timeout_s();
  int64_t left = total;
  Uring* u = uring_acquire();
  if (u) {
    double deadline = 0;
    if (t > 0) {
      deadline = now_s() + t;
      if (g_dl_post_anchor > 0) {
        double anchored = g_dl_post_anchor + t;
        if (anchored < deadline) deadline = anchored;
        g_dl_post_anchor = 0;
      }
    }
    while (left > 0) {
      bool timed_out = false;
      int64_t m = u_do(u, IORING_OP_WRITEV, fd, iov, iovcnt, 0, deadline,
                       &timed_out);
      if (timed_out) {
        g_io_done = total - left;
        g_io_want = total;
        return 2;
      }
      if (m <= 0) {
        if (m == -EINTR || m == -EAGAIN) continue;
        errno = m < 0 ? (int)-m : EIO;
        return 1;
      }
      left -= m;
      iov_consume(&iov, &iovcnt, (size_t)m);
      if (t > 0) deadline = now_s() + t;
    }
    return 0;
  }
  double deadline = t > 0 ? now_s() + t : 0;
  if (t > 0 && g_dl_post_anchor > 0) {
    double anchored = g_dl_post_anchor + t;
    if (anchored < deadline) deadline = anchored;
    g_dl_post_anchor = 0;
  }
  while (left > 0) {
    if (t > 0) {
      double remain = deadline - now_s();
      if (remain <= 0) {
        g_io_done = total - left;
        g_io_want = total;
        return 2;
      }
      pollfd pf{fd, POLLOUT, 0};
      count_sys();
      int pr = ::poll(&pf, 1, (int)std::min(remain * 1000.0 + 1, 60000.0));
      if (pr < 0) {
        if (errno == EINTR) continue;
        return 1;
      }
      if (pr == 0) continue;
    }
    count_sys();
    ssize_t w = ::writev(fd, iov, iovcnt);
    if (w < 0) {
      if (errno == EINTR) continue;
      if (errno == EAGAIN || errno == EWOULDBLOCK) {
        /* no deadline armed means no poll() above paces this loop; on
         * the uring-made-nonblocking fds an EAGAIN must park like
         * write_all does, not spin */
        if (t <= 0 && io_wait_ready(fd, true) < 0 && errno != EINTR)
          return 1;
        continue;
      }
      return 1;
    }
    left -= w;
    iov_consume(&iov, &iovcnt, (size_t)w);
    if (t > 0) deadline = now_s() + t;
  }
  return 0;
}

/* ============== self-healing link layer ==============
 *
 * Armed by MPI4JAX_TPU_RETRY > 0.  Every wire frame carries a per-link
 * sequence number and connection epoch (MsgHeaderX); small frames are
 * retained in a bounded per-link ring so that when a socket dies
 * (ECONNRESET / EPIPE / deadline / CRC mismatch) the link reconnects —
 * the HIGHER root rank dials the LOWER's bootstrap listener, both sides
 * exchange ReconnectHello{epoch, last_seq_delivered}, the sender
 * replays exactly the gap, and the receiver drops duplicates by seq —
 * exactly-once delivery, bit-identical to a fault-free run.  Frames
 * with no retained copy (rendezvous-large, or evicted) make a replay
 * infeasible: the link goes DEAD and the failure escalates through the
 * historic poison -> abort -> elastic path unchanged. */

/* retention caps: a frame above kRetainMaxFrame is never retained
 * (rendezvous-large: its loss escalates); the per-link ring holds at
 * most kRetainRing bytes, evicting oldest-first */
constexpr int64_t kRetainMaxFrame = 256 * 1024;
constexpr int64_t kRetainRing = 4 * 1024 * 1024;

void link_idle_service(Comm* root);

/* Resolve the LinkState for `peer` of `c` (nullptr when the link layer
 * is off, for self, or before bootstrap populated the maps).  Sub-comms
 * resolve through root_rank to the one LinkState per physical socket. */
LinkState* link_state(Comm* c, int peer, int* out_rp = nullptr) {
  if (!retry_armed()) return nullptr;
  if (peer < 0 || peer >= c->size || c->root_rank.empty()) return nullptr;
  Comm* root = c->lock_root;
  int rp = c->root_rank[(size_t)peer];
  if (rp < 0 || rp >= (int)root->links.size() || !root->links[(size_t)rp])
    return nullptr;
  if (out_rp) *out_rp = rp;
  return root->links[(size_t)rp].get();
}

/* Snapshot the live fd for `peer` (synchronized against a concurrent
 * reconnect's rewiring via rmu).  -1 while a recovery is mid-flight. */
int link_fd(Comm* c, int peer) {
  int rp = -1;
  LinkState* L = link_state(c, peer, &rp);
  if (!L) return c->socks[(size_t)peer];
  std::lock_guard<std::mutex> rl(L->rmu);
  return c->lock_root->socks[(size_t)rp];
}

/* Is this I/O failure the transient-link shape a reconnect can absorb?
 * rc 2 = deadline, 3 = CRC mismatch, 4 = sequence gap (a reconnect
 * replays from the receiver's cursor, healing the gap or proving it
 * unhealable), 1 = errno-described socket death. */
bool io_rc_retryable(int rc) {
  if (!retry_armed()) return false;
  if (rc == 2 || rc == 3 || rc == 4) return true;
  if (rc != 1) return false;
  switch (errno) {
    case ECONNRESET:
    case EPIPE:
    case ECONNABORTED:
    case ETIMEDOUT:
    case EBADF:      // fd parked by a concurrent recovery
    case ENOTCONN:
    case EIO:
      return true;
    default:
      return false;
  }
}

/* Mark an inbound data frame fully delivered: the dedup cursor the next
 * ReconnectHello reports.  MUST be called after the payload is entirely
 * consumed (never before: a replay of a half-read frame would then be
 * dropped as a duplicate and its bytes lost). */
void wire_mark_delivered(Comm* c, int source, uint64_t seq) {
  if (seq == 0) return;
  LinkState* L = link_state(c, source);
  if (L) L->rx_seq.store(seq, std::memory_order_relaxed);
}

/* Write one control frame (ping/pong: seq 0, no payload, no retention).
 * Bounded at 5 s regardless of the job deadline knob — 32 bytes into a
 * socket buffer never legitimately blocks longer. */
int link_send_control(Comm* root, int rp, int tag) {
  LinkState* L = root->links[(size_t)rp].get();
  std::lock_guard<std::mutex> wl(L->wmu);
  int fd = root->socks[(size_t)rp];
  if (fd < 0) return 1;
  MsgHeaderX hx{};
  hx.h = MsgHeader{0, tag, root->comm_id};
  hx.epoch = L->epoch;
  hx_seal(&hx);
  return io_all_deadline<true>(fd, &hx, sizeof(hx), 5.0) == 0 ? 0 : 1;
}

/* Read one DATA frame header from `source`, transparently servicing
 * control frames (ping -> pong reply, pong -> liveness stamp) and
 * dropping replay duplicates (seq <= delivered cursor: payload drained
 * to scratch, counter bumped).  Legacy (unarmed) callers get the plain
 * 16-byte read.  Returns 0 with *h / *seq_out / *fd_out filled (payload
 * reads MUST use *fd_out — the captured fd — not a fresh socks[] load);
 * 1 errno, 2 deadline, 3 CRC mismatch (errno EBADMSG), 4 sequence gap
 * (errno EIO).  Poison frames pass through as data (seq 0). */
int wire_read_hdr(Comm* c, int source, MsgHeader* h, uint64_t* seq_out,
                  int* fd_out) {
  int rp = -1;
  LinkState* L = link_state(c, source, &rp);
  if (!L) {
    if (seq_out) *seq_out = 0;
    if (fd_out) *fd_out = c->socks[(size_t)source];
    return read_all_dl(c->socks[(size_t)source], h, sizeof(*h));
  }
  Comm* root = c->lock_root;
  thread_local std::vector<char> drain;
  for (;;) {
    MsgHeaderX hx{};
    int fd;
    int rc;
    {
      std::unique_lock<std::mutex> rl(L->rmu);
      fd = root->socks[(size_t)rp];
      if (fd < 0) {
        /* a recovery parked the fd mid-rewire; fail retryably so the
         * caller joins (blocks on) that recovery and retries */
        if (fd_out) *fd_out = -1;
        errno = EBADF;
        return 1;
      }
      rc = read_all_dl(fd, &hx, sizeof(hx));
      if (rc == 0 && !hx_check(&hx)) {
        g_lc_crc_errors.fetch_add(1, std::memory_order_relaxed);
        std::fprintf(stderr,
                     "tpucomm r%d: self-heal: header CRC mismatch from r%d "
                     "(wire corruption) — forcing reconnect\n",
                     root->rank, rp);
        errno = EBADMSG;
        rc = 3;
      }
      if (rc == 0) {
        L->last_rx.store(now_s(), std::memory_order_relaxed);
        if (hx.h.tag == kPingTag && hx.h.nbytes == 0) {
          rl.unlock();
          link_send_control(root, rp, kPongTag);  // best-effort
          continue;
        }
        if (hx.h.tag == kPongTag && hx.h.nbytes == 0) continue;
      }
    }
    if (rc != 0) {
      if (fd_out) *fd_out = fd;
      return rc;
    }
    uint64_t seq = (uint64_t)hx.seq_lo | ((uint64_t)hx.seq_hi << 32);
    if (seq != 0) {
      uint64_t rx = L->rx_seq.load(std::memory_order_relaxed);
      if (seq <= rx) {
        /* replay overlap: already delivered — drain and drop */
        g_lc_dup_dropped.fetch_add(1, std::memory_order_relaxed);
        int64_t left = hx.h.nbytes;
        if (left > 0 && (int64_t)drain.size() < std::min<int64_t>(left, 1 << 16))
          drain.resize((size_t)std::min<int64_t>(left, 1 << 16));
        while (left > 0) {
          int64_t take = std::min<int64_t>(left, (int64_t)drain.size());
          int drc = read_all_dl(fd, drain.data(), take);
          if (drc != 0) {
            if (fd_out) *fd_out = fd;
            return drc;
          }
          left -= take;
        }
        continue;
      }
      if (seq != rx + 1) {
        std::fprintf(stderr,
                     "tpucomm r%d: self-heal: sequence gap from r%d "
                     "(expected %llu, got %llu) — forcing reconnect\n",
                     root->rank, rp, (unsigned long long)(rx + 1),
                     (unsigned long long)seq);
        if (fd_out) *fd_out = fd;
        errno = EIO;
        return 4;
      }
    }
    *h = hx.h;
    if (seq_out) *seq_out = seq;
    if (fd_out) *fd_out = fd;
    return 0;
  }
}

/* Rewire every view of root's link to `rp`: the root's own socks slot
 * plus each registered child borrowing it.  Called with the link's rmu
 * AND wmu held (readers/writers load under those), kids_mu taken here. */
void root_update_fd(Comm* root, int rp, int fd) {
  root->socks[(size_t)rp] = fd;
  std::lock_guard<std::mutex> g(root->kids_mu);
  for (Comm* ch : root->kids) {
    if (ch->root_rank.empty()) continue;
    for (int m = 0; m < ch->size; m++)
      if (m != ch->rank && ch->root_rank[(size_t)m] == rp)
        ch->socks[(size_t)m] = fd;
  }
}

void hello_fill(ReconnectHello* h, Comm* root, LinkState* L) {
  std::memset(h, 0, sizeof(*h));
  h->magic = kReconnectMagic;
  h->rank = root->rank;
  h->comm_id = root->comm_id;
  h->epoch = L->epoch;
  h->rx_delivered = L->rx_seq.load(std::memory_order_relaxed);
  h->crc = crc32c(h, offsetof(ReconnectHello, crc));
}

bool hello_ok(const ReconnectHello* h, int expect_rank, int32_t comm_id) {
  ReconnectHello tmp = *h;
  tmp.crc = 0;
  if (crc32c(&tmp, offsetof(ReconnectHello, crc)) != h->crc) return false;
  if (h->magic != kReconnectMagic || h->comm_id != comm_id) return false;
  return expect_rank < 0 || h->rank == expect_rank;
}

/* Nonblocking dial of root rank `rp`'s bootstrap listener with a
 * deadline; returns a connected blocking-mode fd or -1 (errno set). */
int link_dial(Comm* root, int rp, double deadline_s) {
  int fd = ::socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return -1;
  int one = 1;
  ::setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons((uint16_t)(root->base_port + rp));
  const char* host = root->real_hosts.empty()
                         ? "127.0.0.1"
                         : root->real_hosts[(size_t)rp].c_str();
  ::inet_pton(AF_INET, host, &addr.sin_addr);  // same resolver as bootstrap
  int fl = ::fcntl(fd, F_GETFL, 0);
  ::fcntl(fd, F_SETFL, fl | O_NONBLOCK);
  int cr = ::connect(fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr));
  if (cr != 0 && errno == EINPROGRESS) {
    pollfd pf{fd, POLLOUT, 0};
    count_sys();
    int pr = ::poll(&pf, 1, (int)std::max(deadline_s * 1000.0, 1.0));
    if (pr > 0) {
      int soerr = 0;
      socklen_t sl = sizeof(soerr);
      ::getsockopt(fd, SOL_SOCKET, SO_ERROR, &soerr, &sl);
      if (soerr == 0) {
        cr = 0;
      } else {
        errno = soerr;
        cr = -1;
      }
    } else {
      errno = ETIMEDOUT;
      cr = -1;
    }
  }
  if (cr != 0) {
    int e = errno;
    ::close(fd);
    errno = e;
    return -1;
  }
  ::fcntl(fd, F_SETFL, fl);  // handshake runs blocking-mode reads/writes
  return fd;
}

/* Reconnect the link `c` <-> `peer` after an I/O failure on fd_seen.
 * Returns 0 when the link is healed (the caller retries its frame: a
 * retained send was already replayed, a receive restarts at frame
 * granularity) and 1 when it could not be (link DEAD — the caller
 * escalates through the historic failure path).  Serialized per link on
 * L->mu; latecomers seeing a fresher fd than the one they failed on
 * return healed immediately. */
int link_recover(Comm* c, int peer, int fd_seen, const char* what) {
  int rp = -1;
  LinkState* L = link_state(c, peer, &rp);
  if (!L) return 1;
  Comm* root = c->lock_root;
  std::lock_guard<std::mutex> lk(L->mu);
  if (L->phase.load(std::memory_order_relaxed) == LINK_DEAD) return 1;
  {
    std::lock_guard<std::mutex> rl(L->rmu);
    int cur = root->socks[(size_t)rp];
    if (cur >= 0 && cur != fd_seen) {
      g_heal_acc++;  // healed by the thread that got here first
      return 0;
    }
  }
  g_lc_retries.fetch_add(1, std::memory_order_relaxed);
  g_heal_acc++;
  L->phase.store(LINK_RECONNECTING, std::memory_order_relaxed);
  const int64_t budget = retry_budget();
  std::fprintf(stderr,
               "tpucomm r%d: self-heal: link to r%d failed (%s) — "
               "reconnecting with replay (epoch %u, budget %lld, "
               "MPI4JAX_TPU_RETRY)\n",
               root->rank, rp, what, L->epoch, (long long)budget);
  std::fflush(stderr);
  /* retire the old socket: shutdown wakes any thread still blocked on
   * it; the fd number is parked (closed only at finalize) so a reused
   * number can never alias a blocked thread's view */
  {
    std::lock_guard<std::mutex> rl(L->rmu);
    std::lock_guard<std::mutex> wl(L->wmu);
    int old_fd = root->socks[(size_t)rp];
    if (old_fd >= 0) {
      ::shutdown(old_fd, SHUT_RDWR);
      std::lock_guard<std::mutex> g(root->rcmu);
      root->dead_fds.push_back(old_fd);
    }
    root_update_fd(root, rp, -1);
  }
  /* hold both frame locks for the whole handshake: in-flight readers
   * and writers have failed out of them by now (the shutdown above
   * guarantees progress), and no new frame may touch the wire until
   * the replay is complete */
  std::lock_guard<std::mutex> rl(L->rmu);
  std::lock_guard<std::mutex> wl(L->wmu);
  const bool dialer = root->rank > rp;  // acceptor = lower rank: it owns
                                        // the listener (bootstrap topology)
  /* deterministic per-(rank, link, epoch) jitter: reproducible runs,
   * decorrelated dial storms */
  uint32_t jstate =
      ((uint32_t)root->rank * 2654435761u) ^ ((uint32_t)rp << 16) ^ L->epoch;
  char reason[160];
  std::snprintf(reason, sizeof(reason), "budget exhausted");
  int64_t attempt = 0;
  for (; attempt < budget; attempt++) {
    if (attempt > 0) {
      jstate = jstate * 1664525u + 1013904223u;
      double base = retry_backoff_ms() * (double)(1 << std::min<int64_t>(attempt - 1, 6));
      double jit = base * 0.25 * ((jstate >> 8) & 0xff) / 255.0;
      double ms = std::min(base + jit, 5000.0);
      std::this_thread::sleep_for(
          std::chrono::microseconds((long long)(ms * 1000.0)));
    }
    const double hs_t =
        std::min(5.0, std::max(0.25, retry_backoff_ms() / 1000.0 * 4));
    int nfd = -1;
    ReconnectHello mine{}, theirs{};
    hello_fill(&mine, root, L);
    if (dialer) {
      nfd = link_dial(root, rp, hs_t);
      if (nfd < 0) {
        std::snprintf(reason, sizeof(reason), "dial failed: %s",
                      std::strerror(errno));
        continue;
      }
      if (io_all_deadline<true>(nfd, &mine, sizeof(mine), hs_t) != 0 ||
          io_all_deadline<false>(nfd, &theirs, sizeof(theirs), hs_t) != 0 ||
          !hello_ok(&theirs, rp, root->comm_id)) {
        std::snprintf(reason, sizeof(reason), "handshake failed");
        ::close(nfd);
        nfd = -1;
        continue;
      }
    } else {
      /* acceptor: a dial may already be stashed by the idle service */
      {
        std::lock_guard<std::mutex> g(root->rcmu);
        auto it = root->pending_rc.find(rp);
        if (it != root->pending_rc.end()) {
          nfd = it->second.first;
          theirs = it->second.second;
          root->pending_rc.erase(it);
        }
      }
      if (nfd < 0 && root->listen_fd >= 0) {
        pollfd pf{root->listen_fd, POLLIN, 0};
        count_sys();
        int pr = ::poll(&pf, 1, (int)(hs_t * 1000.0));
        if (pr > 0) {
          int afd = ::accept(root->listen_fd, nullptr, nullptr);
          if (afd >= 0) {
            ReconnectHello hello{};
            if (io_all_deadline<false>(afd, &hello, sizeof(hello), hs_t) ==
                    0 &&
                hello_ok(&hello, -1, root->comm_id) && hello.rank >= 0 &&
                hello.rank < root->size) {
              if (hello.rank == rp) {
                nfd = afd;
                theirs = hello;
              } else {
                /* a DIFFERENT link's dialer: stash for its recovery */
                std::lock_guard<std::mutex> g(root->rcmu);
                auto it = root->pending_rc.find(hello.rank);
                if (it != root->pending_rc.end()) {
                  ::close(it->second.first);
                  it->second = {afd, hello};
                } else {
                  root->pending_rc[hello.rank] = {afd, hello};
                }
              }
            } else {
              ::close(afd);
            }
          }
        }
      }
      if (nfd < 0) {
        std::snprintf(reason, sizeof(reason),
                      "no reconnect dial from peer within the window");
        continue;
      }
      if (io_all_deadline<true>(nfd, &mine, sizeof(mine), hs_t) != 0) {
        std::snprintf(reason, sizeof(reason), "handshake reply failed");
        ::close(nfd);
        nfd = -1;
        continue;
      }
    }
    /* handshake complete: agree on the epoch, check replay feasibility */
    uint32_t new_epoch = std::max(L->epoch, theirs.epoch) + 1;
    uint64_t prx = theirs.rx_delivered;
    if (L->hole_seq.load(std::memory_order_relaxed) > prx) {
      std::snprintf(reason, sizeof(reason),
                    "replay infeasible: peer delivered through seq %llu but "
                    "the oldest retained frame starts after %llu "
                    "(rendezvous-large or evicted sends cannot replay)",
                    (unsigned long long)prx,
                    (unsigned long long)
                        L->hole_seq.load(std::memory_order_relaxed));
      ::close(nfd);
      break;  // a reconnect cannot fix this: escalate now
    }
    /* trim acknowledged frames (keeping replay_slack() extras so the
     * dedup path is exercisable on demand), then replay the gap */
    uint64_t from = prx;
    int64_t slack = replay_slack();
    while (slack > 0 && from > 0) {
      from--;
      slack--;
    }
    while (!L->ring.empty() && L->ring.front().seq <= from) {
      L->ring_bytes -= (int64_t)L->ring.front().bytes.size();
      L->ring.pop_front();
    }
    int64_t replayed = 0;
    int rrc = 0;
    for (const ReplayFrame& rf : L->ring) {
      if (rf.seq <= from) continue;
      rrc = io_all_deadline<true>(nfd, const_cast<char*>(rf.bytes.data()),
                                  (int64_t)rf.bytes.size(),
                                  std::max(hs_t, 5.0));
      if (rrc != 0) break;
      replayed++;
    }
    if (rrc != 0) {
      std::snprintf(reason, sizeof(reason), "replay write failed: %s",
                    std::strerror(errno));
      ::close(nfd);
      continue;
    }
    /* install: TCP options to match bootstrap, rewire every view */
    int one = 1;
    ::setsockopt(nfd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    if (transport_timeout_s() > 0 || uring_ready()) {
      int fl = ::fcntl(nfd, F_GETFL, 0);
      ::fcntl(nfd, F_SETFL, fl | O_NONBLOCK);
    }
    root_update_fd(root, rp, nfd);
    L->epoch = new_epoch;
    L->phase.store(LINK_UP, std::memory_order_relaxed);
    L->last_rx.store(now_s(), std::memory_order_relaxed);
    L->last_ping.store(0, std::memory_order_relaxed);
    g_lc_reconnects.fetch_add(1, std::memory_order_relaxed);
    g_lc_replayed.fetch_add(replayed, std::memory_order_relaxed);
    std::fprintf(stderr,
                 "tpucomm r%d: self-heal: link to r%d recovered (epoch %u, "
                 "replayed %lld frames, %lld dups dropped) [attempt "
                 "%lld/%lld]\n",
                 root->rank, rp, new_epoch, (long long)replayed,
                 (long long)g_lc_dup_dropped.load(std::memory_order_relaxed),
                 (long long)(attempt + 1), (long long)budget);
    std::fflush(stderr);
    return 0;
  }
  L->phase.store(LINK_DEAD, std::memory_order_relaxed);
  std::fprintf(stderr,
               "tpucomm r%d: self-heal: link to r%d DEAD after %lld "
               "attempt(s): %s — escalating (poison -> abort -> elastic)\n",
               root->rank, rp, (long long)std::max<int64_t>(attempt, 1),
               reason);
  std::fflush(stderr);
  return 1;
}

/* Write one DATA frame (header + up to two payload spans) to `dest`,
 * stamping seq/epoch/CRC, retaining small frames for replay, consuming
 * a pending wire-fault order, and healing retryable failures in place.
 * This is THE armed send path: every frame writer routes here so seq
 * assignment and socket writes stay atomic per link (wmu).  Unarmed
 * comms get the historic header+payload writes, byte-identical. */
int link_send_frame(Comm* c, int dest, int tag, const void* p1, int64_t n1,
                    const void* p2, int64_t n2) {
  const int64_t payload = n1 + n2;
  int rp = -1;
  LinkState* L = link_state(c, dest, &rp);
  if (!L) {
    MsgHeader h{payload, tag, c->comm_id};
    int fd = c->socks[(size_t)dest];
    int rc = write_all_dl(fd, &h, sizeof(h));
    if (rc == 0 && n1 > 0) rc = write_all_dl(fd, p1, n1);
    if (rc == 0 && n2 > 0) rc = write_all_dl(fd, p2, n2);
    return rc;
  }
  Comm* root = c->lock_root;
  for (;;) {
    int rc;
    int fd;
    bool retained = false;
    {
      std::unique_lock<std::mutex> wl(L->wmu);
      fd = root->socks[(size_t)rp];
      if (fd < 0) {
        /* a recovery is rewiring the link: join it and retry */
        wl.unlock();
        if (link_recover(c, dest, -1, "send (link down)") == 0) continue;
        errno = ECONNRESET;
        return 1;
      }
      MsgHeaderX hx{};
      hx.h = MsgHeader{payload, tag, c->comm_id};
      hx.epoch = L->epoch;
      uint64_t seq = L->tx_seq.load(std::memory_order_relaxed) + 1;
      L->tx_seq.store(seq, std::memory_order_relaxed);
      hx.seq_lo = (uint32_t)(seq & 0xffffffffu);
      hx.seq_hi = (uint32_t)(seq >> 32);
      hx_seal(&hx);
      WireFault wf = g_wire_fault;
      g_wire_fault = WireFault{};
      const int64_t frame_bytes = (int64_t)sizeof(hx) + payload;
      if (frame_bytes <= kRetainMaxFrame) {
        /* retain the GOOD frame (a corrupt order flips only the wire
         * copy below, so replay restores the true bytes) */
        ReplayFrame rf;
        rf.seq = seq;
        rf.bytes.resize((size_t)frame_bytes);
        std::memcpy(rf.bytes.data(), &hx, sizeof(hx));
        if (n1 > 0) std::memcpy(rf.bytes.data() + sizeof(hx), p1, (size_t)n1);
        if (n2 > 0)
          std::memcpy(rf.bytes.data() + sizeof(hx) + n1, p2, (size_t)n2);
        L->ring.push_back(std::move(rf));
        L->ring_bytes += frame_bytes;
        while (L->ring_bytes > kRetainRing && !L->ring.empty()) {
          uint64_t ev = L->ring.front().seq;
          uint64_t hole = L->hole_seq.load(std::memory_order_relaxed);
          if (ev > hole) L->hole_seq.store(ev, std::memory_order_relaxed);
          L->ring_bytes -= (int64_t)L->ring.front().bytes.size();
          L->ring.pop_front();
        }
        retained = true;
      } else {
        uint64_t hole = L->hole_seq.load(std::memory_order_relaxed);
        if (seq > hole) L->hole_seq.store(seq, std::memory_order_relaxed);
      }
      if (wf.action == FA_CORRUPT) {
        /* flip a header byte AFTER sealing: the receiver's CRC check
         * must catch it (that is the injected failure) */
        MsgHeaderX bad = hx;
        reinterpret_cast<char*>(&bad)[5] ^= 0x40;
        rc = write_all_dl(fd, &bad, sizeof(bad));
        if (rc == 0 && n1 > 0) rc = write_all_dl(fd, p1, n1);
        if (rc == 0 && n2 > 0) rc = write_all_dl(fd, p2, n2);
        /* the bytes landed but the peer will reject them; force our own
         * side into recovery so both ends converge on a fresh epoch */
        if (rc == 0) {
          errno = EBADMSG;
          rc = 3;
        }
      } else if (wf.action == FA_DROP) {
        int64_t keep = std::min<int64_t>(
            wf.param, retained ? (int64_t)L->ring.back().bytes.size()
                               : (int64_t)sizeof(hx));
        const char* src = retained
                              ? L->ring.back().bytes.data()
                              : reinterpret_cast<const char*>(&hx);
        if (keep > 0) (void)write_all_dl(fd, src, keep);
        linger_rst(fd);  // mid-frame kill: the heal below replays it
        errno = ECONNRESET;
        rc = 1;
      } else if (retained) {
        const ReplayFrame& rf = L->ring.back();
        rc = write_all_dl(fd, rf.bytes.data(), (int64_t)rf.bytes.size());
      } else {
        struct iovec iov[3];
        int cnt = 0;
        iov[cnt++] = {&hx, sizeof(hx)};
        if (n1 > 0) iov[cnt++] = {const_cast<void*>(p1), (size_t)n1};
        if (n2 > 0) iov[cnt++] = {const_cast<void*>(p2), (size_t)n2};
        rc = writev_all_dl(fd, iov, cnt, (int64_t)sizeof(hx) + payload);
      }
    }
    if (rc == 0) return 0;
    if (!io_rc_retryable(rc)) return rc;
    int erc = rc;
    int esave = errno;
    if (link_recover(c, dest, fd, "send") == 0) {
      /* healed.  A retained frame was replayed (or confirmed delivered)
       * by the handshake; an unretained frame only reaches here when
       * the peer confirmed full delivery (otherwise the replay gap
       * crossed its hole and recovery escalated). */
      return 0;
    }
    errno = esave;
    return erc;
  }
}

/* Idle-time service, run from the engine's drain loop when the queue is
 * empty (~10 Hz): accepts and stashes reconnect dials so a busy
 * acceptor never strands a dialer, and drives heartbeats over idle
 * links (MPI4JAX_TPU_HEARTBEAT_S > 0).  All lock acquisition is
 * try-only — this must never stall the progress thread. */
void link_idle_service(Comm* root) {
  if (!retry_armed() || root->links.empty()) return;
  /* (a) accept + stash reconnect dials (no comm lock needed: only the
   * rcmu-guarded stash is touched) */
  if (root->listen_fd >= 0) {
    for (;;) {
      pollfd pf{root->listen_fd, POLLIN, 0};
      if (::poll(&pf, 1, 0) <= 0) break;
      int afd = ::accept(root->listen_fd, nullptr, nullptr);
      if (afd < 0) break;
      ReconnectHello hello{};
      if (io_all_deadline<false>(afd, &hello, sizeof(hello), 2.0) != 0 ||
          !hello_ok(&hello, -1, root->comm_id) || hello.rank < 0 ||
          hello.rank >= root->size) {
        ::close(afd);
        continue;
      }
      std::lock_guard<std::mutex> g(root->rcmu);
      auto it = root->pending_rc.find(hello.rank);
      if (it != root->pending_rc.end()) {
        ::close(it->second.first);
        it->second = {afd, hello};
      } else {
        root->pending_rc[hello.rank] = {afd, hello};
      }
    }
  }
  /* (b) heartbeats: ping links idle past the knob, recover links silent
   * past two windows after a ping */
  const double hb = heartbeat_s();
  if (hb <= 0) return;
  std::unique_lock<std::mutex> cl(root->mu, std::try_to_lock);
  if (!cl.owns_lock()) return;  // an op is running: the wire is live
  const double now = now_s();
  for (int rp = 0; rp < (int)root->links.size(); rp++) {
    LinkState* L = root->links[(size_t)rp].get();
    if (!L || L->phase.load(std::memory_order_relaxed) != LINK_UP) continue;
    int fd;
    {
      std::unique_lock<std::mutex> rl(L->rmu, std::try_to_lock);
      if (!rl.owns_lock()) continue;
      fd = root->socks[(size_t)rp];
      if (fd < 0) continue;
      /* consume control replies queued on the idle socket (peek first:
       * data frames must stay for the op path) */
      for (;;) {
        MsgHeaderX hx{};
        ssize_t p = ::recv(fd, &hx, sizeof(hx), MSG_PEEK | MSG_DONTWAIT);
        if (p < (ssize_t)sizeof(hx)) {
          if (p > 0) L->last_rx.store(now, std::memory_order_relaxed);
          break;
        }
        L->last_rx.store(now, std::memory_order_relaxed);
        if (!hx_check(&hx)) break;  // op path owns CRC failures
        if ((hx.h.tag != kPingTag && hx.h.tag != kPongTag) ||
            hx.h.nbytes != 0)
          break;  // data frame: leave it for the op path
        ::recv(fd, &hx, sizeof(hx), MSG_DONTWAIT);  // consume control
        if (hx.h.tag == kPingTag) {
          rl.unlock();
          link_send_control(root, rp, kPongTag);
          rl.lock();
        }
      }
    }
    const double last_rx = L->last_rx.load(std::memory_order_relaxed);
    const double last_ping = L->last_ping.load(std::memory_order_relaxed);
    if (now - last_rx > hb && now - last_ping > hb) {
      if (link_send_control(root, rp, kPingTag) == 0) {
        L->last_ping.store(now, std::memory_order_relaxed);
        g_lc_heartbeats.fetch_add(1, std::memory_order_relaxed);
      } else {
        (void)link_recover(root, rp, fd, "heartbeat send failed");
        continue;
      }
    }
    if (last_ping > last_rx && now - last_ping > 2 * hb)
      (void)link_recover(root, rp, fd, "heartbeat timeout (no pong)");
  }
}

/* ============== job-wide abort propagation (poison frames) ==============
 *
 * When this process aborts (any FAIL surfacing to the Python bridge),
 * tpucomm_abort_all best-effort writes one poison control frame —
 * kPoisonTag header + this process's last-error text — to every peer
 * socket and shuts the sockets down.  A peer blocked in any recv path
 * consumes the poison and fails immediately naming the aborting rank,
 * so the group tears down within one deadline instead of waiting for
 * timeouts to cascade rank by rank. */
constexpr int32_t kPoisonTag = -7707;

/* Consume a poison frame whose header is already read; always fails.
 * `pre`/`pre_len` hand over payload bytes a speculative uring receive
 * already pulled off the socket. */
int poison_fail_pre(Comm* c, int source, const MsgHeader& h,
                    const char* pre, int64_t pre_len) {
  char text[448] = {0};
  int64_t nb = std::min<int64_t>(h.nbytes, (int64_t)sizeof(text) - 1);
  int64_t take = std::min(nb, pre_len);
  if (take > 0) std::memcpy(text, pre, (size_t)take);
  /* best effort: the aborter shuts the socket down right after the
   * frame, so a partial payload ends in EOF, not a hang */
  if (nb > take) {
    int pfd = retry_armed() ? link_fd(c, source) : c->socks[source];
    if (pfd >= 0) read_all_dl(pfd, text + take, nb - take);
  }
  text[sizeof(text) - 1] = 0;
  FAIL(c, "rank %d aborted the job: %s", source,
       text[0] ? text : "(no detail)");
}

int poison_fail(Comm* c, int source, const MsgHeader& h) {
  return poison_fail_pre(c, source, h, nullptr, 0);
}

void self_deliver(Comm* c, int tag, const void* buf, int64_t nbytes) {
  MsgHeader h{nbytes, tag, c->comm_id};
  const char* p = static_cast<const char*>(buf);
  c->self_q.emplace_back(h, std::vector<char>(p, p + nbytes));
}

int send_msg_tcp(Comm* c, int dest, int tag, const void* buf,
                 int64_t nbytes) {
  if (retry_armed()) {
    /* armed path: every frame goes through the link layer (seq/epoch
     * stamp, retention, heal-in-place).  The uring staged-small fast
     * path is bypassed — classic writes still ride uring inside
     * io_all_deadline, but frame assembly must be the link layer's. */
    fault_fire(c, g_job_rank, FP_SEND, "send", link_fd(c, dest));
    int arc = link_send_frame(c, dest, tag, buf, nbytes, nullptr, 0);
    if (arc) FAIL_IO(c, arc, "send to %d", dest);
    return 0;
  }
  fault_fire(c, g_job_rank, FP_SEND, "send");
  MsgHeader h{nbytes, tag, c->comm_id};
  int rc;
  Uring* u;
  if (nbytes <= kUringSmall && (u = uring_acquire()) != nullptr) {
    /* one staged frame, one submission: header + payload go out in a
     * single io_uring_enter from the registered staging pool (the poll
     * path pays two writes, four syscalls with a deadline armed) */
    char* st = u->stage.data();
    std::memcpy(st, &h, sizeof(h));
    if (nbytes > 0) std::memcpy(st + sizeof(h), buf, (size_t)nbytes);
    rc = u_io_all(u, c->socks[dest], st, (int64_t)sizeof(h) + nbytes, true,
                  transport_timeout_s(), /*stage_fixed=*/true);
  } else {
    rc = write_all_dl(c->socks[dest], &h, sizeof(h));
    if (!rc) rc = write_all_dl(c->socks[dest], buf, nbytes);
  }
  if (rc) FAIL_IO(c, rc, "send to %d", dest);
  return 0;
}

int send_msg(Comm* c, int dest, int tag, const void* buf, int64_t nbytes) {
  if (dest < 0 || dest >= c->size) FAIL(c, "send to invalid rank %d", dest);
  if (dest == c->rank) {
    self_deliver(c, tag, buf, nbytes);
    return 0;
  }
  return send_msg_tcp(c, dest, tag, buf, nbytes);
}

/* ---------------- persistent writer (async send half) ---------------- */

void writer_loop(Comm* root) {
  std::unique_lock<std::mutex> lock(root->wmu);
  for (;;) {
    root->wcv.wait(lock, [&] { return root->wstop || !root->wq.empty(); });
    if (root->wstop && root->wq.empty()) return;
    SendJob* j = root->wq.front();
    root->wq.pop_front();
    lock.unlock();
    /* large frames never reach send_msg_tcp's injector hook — a
     * point=send fault must be able to wedge/kill big transfers too
     * (hang here hangs the whole rank: wait_send then never returns,
     * which is exactly the wedged-peer shape the deadlines detect) */
    fault_fire(nullptr, g_job_rank, FP_SEND, "send", j->fd);
    int rc = 0;
    int io;
    if (retry_armed() && j->comm) {
      /* armed: the link layer stamps, (maybe) retains, and heals */
      io = link_send_frame(j->comm, j->dest, j->hdr.tag, j->buf,
                           j->hdr.nbytes, nullptr, 0);
    } else {
      io = write_all_dl(j->fd, &j->hdr, sizeof(j->hdr));
      if (!io) io = write_all_dl(j->fd, j->buf, j->hdr.nbytes);
    }
    if (io) {
      /* wait_send is an unbounded cv wait — this deadline is what keeps
       * it bounded when the peer stops draining the socket */
      char why[160];
      if (io == 2)
        std::snprintf(why, sizeof(why),
                      "timed out after %.0f s with %lld/%lld bytes moved "
                      "(MPI4JAX_TPU_TIMEOUT_S)",
                      transport_timeout_s(), (long long)g_io_done,
                      (long long)g_io_want);
      else
        std::snprintf(why, sizeof(why), "%s", std::strerror(errno));
      std::fprintf(stderr, "tpucomm r%d: async send to %d failed: %s\n",
                   j->rank, j->dest, why);
      set_last_error(j->rank, "async send to %d failed: %s", j->dest, why);
      rc = 1;
    }
    lock.lock();
    j->rc = rc;
    j->done = true;
    root->wdone_cv.notify_all();
  }
}

/* Eager threshold: a frame this small fits far inside the kernel socket
 * buffer (>= 208KB default), so writing it inline cannot block even
 * before the matching receive posts — the writer thread (two context
 * switches on a busy host) is only needed to guarantee progress for
 * payloads that could fill the pipe. */
constexpr int64_t kEagerBytes = 32 * 1024;

/* Queue the send half of a concurrent send+recv round.  Returns 0 and
 * fills `job` on success; nonzero on validation failure (nothing queued).
 * Callers MUST wait_send() before letting `buf` or `job` die. */
int async_send(Comm* c, SendJob* job, int dest, int tag, const void* buf,
               int64_t nbytes) {
  if (dest < 0 || dest >= c->size) FAIL(c, "send to invalid rank %d", dest);
  if (dest == c->rank) {
    /* deliver synchronously so a following recv-from-self (e.g. the
     * sendrecv self case) finds the frame already queued */
    self_deliver(c, tag, buf, nbytes);
    job->rc = 0;
    job->done = true;
    return 0;
  }
  if (ring_p2p_on(c)) {
    bool inlined = false;
    if (shm_try_send(c, dest, tag, buf, nbytes, &inlined)) {
      job->rc = 1;
      job->done = true;
      return 1;
    }
    if (inlined) {
      job->rc = 0;
      job->done = true;
      return 0;
    }
    /* stub in the ring: the payload follows on TCP (eager inline below,
     * or the writer thread for large frames) */
  }
  if (nbytes <= kEagerBytes) {
    job->rc = send_msg(c, dest, tag, buf, nbytes);
    job->done = true;
    return 0;
  }
  job->fd = retry_armed() ? link_fd(c, dest) : c->socks[dest];
  job->rank = c->rank;
  job->dest = dest;
  job->comm = retry_armed() ? c : nullptr;
  job->hdr = MsgHeader{nbytes, tag, c->comm_id};
  job->buf = buf;
  job->rc = 0;
  job->done = false;
  Comm* root = c->lock_root;
  {
    std::lock_guard<std::mutex> lock(root->wmu);
    if (!root->writer_started) {
      root->writer = std::thread(writer_loop, root);
      root->writer_started = true;
    }
    root->wq.push_back(job);
  }
  root->wcv.notify_one();
  return 0;
}

int wait_send(Comm* c, SendJob* job) {
  Comm* root = c->lock_root;
  std::unique_lock<std::mutex> lock(root->wmu);
  root->wdone_cv.wait(lock, [&] { return job->done; });
  return job->rc;
}

/* MPI_ANY_TAG / MPI_ANY_SOURCE analogs (match utils/status.py). */
constexpr int kAnyTag = -1;
constexpr int kAnySource = -2;

/* collective-protocol frames (never visible to user receives) */
constexpr int kCollectiveTag = -7701;

/* Coalesced container frame: several adjacent small sends to one peer
 * packed into one wire frame by the progress engine (sender side).
 * Payload = repeated [MsgHeader | payload] sub-messages, each with its
 * original user tag; the receive side splits them back apart (first
 * matching sub-message lands directly in the posted user buffer, the
 * rest stage in Comm::pending), so tags, sizes, and per-channel order
 * are bit-for-bit what N separate frames would have delivered. */
constexpr int kCoalescedTag = -7703;

/* True when a frame header is eligible for a wildcard receive on comm
 * `c` with tag filter `tag`: right communicator, and either the exact
 * tag or (under ANY_TAG) any *user* tag — collective-protocol frames
 * mean the peer raced ahead into a collective we will run later, and
 * must never be consumed as user data. */
bool header_matches(const Comm* c, const MsgHeader& h, int tag) {
  if (h.tag == kPoisonTag) return false;  // never user data: a peer abort
  if (h.comm_id != c->comm_id) return false;
  if (tag == kAnyTag)
    return h.tag != kCollectiveTag && h.tag != kCoalescedTag;
  return h.tag == tag;
}

/* Read one coalesced container frame (outer header already consumed)
 * from `source` and split it back into user messages.  When `buf` is a
 * posted receive (non-null) whose tag filter matches the FIRST
 * sub-message, that payload lands directly in the user buffer (no
 * staging copy) and *consumed is set; every other sub-message stages
 * in c->pending[source] in arrival order. */
/* `pre`/`pre_len` hand over container bytes a speculative uring receive
 * already pulled off the socket (consumed before any further socket
 * reads — arrival order is preserved exactly). */
/* Armed callers pass the captured frame fd (`frame_fd` >= 0): an I/O
 * failure mid-container then returns the soft sentinel 5 with the real
 * rc stashed in g_stage_soft_rc, so the caller can roll back the staged
 * sub-messages and heal the link — the whole container was retained by
 * the sender and replays verbatim. */
thread_local int g_stage_soft_rc = 0;
int stage_coalesced_pre(Comm* c, int source, const MsgHeader& outer, int tag,
                        void* buf, int64_t nbytes, int32_t* out_tag,
                        int64_t* out_count, bool* consumed,
                        const char* pre, int64_t pre_len,
                        int frame_fd = -1) {
  if (consumed) *consumed = false;
  int64_t pre_off = 0;
  auto rd = [&](void* dst, int64_t n) -> int {
    char* d = static_cast<char*>(dst);
    int64_t take = std::min(n, pre_len - pre_off);
    if (take > 0) {
      std::memcpy(d, pre + pre_off, (size_t)take);
      pre_off += take;
      d += take;
      n -= take;
    }
    if (n <= 0) return 0;
    return read_all_dl(frame_fd >= 0 ? frame_fd : c->socks[source], d, n);
  };
  int64_t remaining = outer.nbytes;
  bool first = true;
  while (remaining > 0) {
    MsgHeader sh{};
    if (remaining < (int64_t)sizeof(sh))
      FAIL(c, "corrupt coalesced frame from rank %d (%lld trailing bytes)",
           source, (long long)remaining);
    int rc = rd(&sh, sizeof(sh));
    if (rc) {
      if (frame_fd >= 0 && io_rc_retryable(rc)) {
        g_stage_soft_rc = rc;
        return 5;
      }
      FAIL_IO(c, rc, "recv coalesced header from %d", source);
    }
    remaining -= sizeof(sh);
    if (sh.comm_id != c->comm_id || sh.nbytes < 0 || sh.nbytes > remaining)
      FAIL(c, "corrupt coalesced sub-message from rank %d (comm %d, %lld "
           "bytes of %lld left)", source, sh.comm_id, (long long)sh.nbytes,
           (long long)remaining);
    if (first && consumed && buf && (tag == kAnyTag || sh.tag == tag) &&
        sh.nbytes <= nbytes) {
      /* pre-posted receive: land the head message straight in the user
       * buffer instead of staging it */
      rc = rd(buf, sh.nbytes);
      if (rc) {
        if (frame_fd >= 0 && io_rc_retryable(rc)) {
          g_stage_soft_rc = rc;
          return 5;
        }
        FAIL_IO(c, rc, "recv coalesced payload from %d", source);
      }
      if (out_tag) *out_tag = sh.tag;
      if (out_count) *out_count = sh.nbytes;
      *consumed = true;
    } else {
      PendingMsg m;
      m.hdr = sh;
      m.data.resize((size_t)sh.nbytes);
      if (sh.nbytes > 0) {
        rc = rd(m.data.data(), sh.nbytes);
        if (rc) {
          if (frame_fd >= 0 && io_rc_retryable(rc)) {
            g_stage_soft_rc = rc;
            return 5;
          }
          FAIL_IO(c, rc, "recv coalesced payload from %d", source);
        }
      }
      c->pending[source].push_back(std::move(m));
    }
    remaining -= sh.nbytes;
    first = false;
  }
  if (pre_off < pre_len)
    /* the speculative read ran past the whole container — only possible
     * when the awaited message is shorter than posted, which the strict
     * caller is about to abort on; fail with its wording here so the
     * over-read can never silently desynchronize the stream */
    FAIL(c, "size mismatch from rank %d: expected %lld bytes, got %lld",
         source, (long long)nbytes,
         (long long)(outer.nbytes - (int64_t)sizeof(MsgHeader)));
  return 0;
}

int stage_coalesced(Comm* c, int source, const MsgHeader& outer, int tag,
                    void* buf, int64_t nbytes, int32_t* out_tag,
                    int64_t* out_count, bool* consumed) {
  return stage_coalesced_pre(c, source, outer, tag, buf, nbytes, out_tag,
                             out_count, consumed, nullptr, 0);
}

/* Consume the head of c->pending[source] into a posted receive, with
 * exactly the checks the wire path applies (order violation on a tag
 * mismatch, truncation on a short buffer). */
int consume_pending(Comm* c, int source, int tag, void* buf, int64_t nbytes,
                    int32_t* out_src, int32_t* out_tag, int64_t* out_count) {
  auto& q = c->pending[source];
  if (q.empty())
    FAIL(c, "internal: empty pending queue for rank %d", source);
  PendingMsg m = std::move(q.front());
  q.pop_front();
  if (q.empty()) c->pending.erase(source);
  if (tag != kAnyTag && m.hdr.tag != tag)
    FAIL(c, "message order violation: expected tag %d from rank %d, got %d",
         tag, source, m.hdr.tag);
  if (m.hdr.nbytes > nbytes)
    FAIL(c, "message truncated: rank %d sent %lld bytes into a %lld-byte "
         "buffer", source, (long long)m.hdr.nbytes, (long long)nbytes);
  std::memcpy(buf, m.data.data(), (size_t)m.hdr.nbytes);
  if (out_src) *out_src = source;
  if (out_tag) *out_tag = m.hdr.tag;
  if (out_count) *out_count = m.hdr.nbytes;
  return 0;
}

/* Head of the pending queue for `source`, or null. */
const MsgHeader* pending_head(Comm* c, int source) {
  auto it = c->pending.find(source);
  if (it == c->pending.end() || it->second.empty()) return nullptr;
  return &it->second.front().hdr;
}

/* ANY_SOURCE resolution: poll every peer socket until one holds a
 * complete frame HEADER that matches (comm_id, tag), return its rank.
 * Per-socket order is still strict, so a wildcard receive composes with
 * the ordered-transport contract (the reference's default — its libmpi
 * matches MPI_ANY_SOURCE natively, reference recv.py:45).  A socket
 * whose next frame does NOT match can never satisfy this wildcard (its
 * head cannot be consumed while we hold the comm lock) and is dropped
 * from the candidate set, as are peers that exited cleanly. */
int poll_any_source_once(Comm* c, int tag, int* out_source) {
  const bool armed = retry_armed() && !c->root_rank.empty();
  std::vector<pollfd> fds;
  std::vector<int> ranks;
  for (int r = 0; r < c->size; r++) {
    int fd = armed ? link_fd(c, r) : c->socks[r];
    if (fd < 0) continue;
    fds.push_back({fd, POLLIN, 0});
    ranks.push_back(r);
  }
  if (fds.empty()) FAIL(c, "ANY_SOURCE recv with no peers");
  const double t = transport_timeout_s();
  double deadline = t > 0 ? now_s() + t : 0;
  /* per-candidate peeked-header byte counts: the deadline must reset on
   * actual byte PROGRESS, not on poll readiness — a peer stalled
   * mid-header keeps POLLIN asserted forever, which would both defeat
   * the timeout and busy-spin the level-triggered poll */
  std::vector<int64_t> peeked(ranks.size(), 0);
  for (;;) {
    count_sys();
    int n = ::poll(fds.data(), fds.size(), t > 0 ? 100 : -1);
    if (n < 0) {
      if (errno == EINTR) continue;
      FAIL(c, "ANY_SOURCE poll failed: %s", std::strerror(errno));
    }
    if (n == 0) {
      if (t > 0 && now_s() > deadline)
        FAIL(c,
             "ANY_SOURCE recv timed out after %.0f s — no peer delivered "
             "a matching message (MPI4JAX_TPU_TIMEOUT_S)",
             t);
      continue;
    }
    bool progress = false;
    std::vector<size_t> dead;
    for (size_t i = 0; i < fds.size(); i++) {
      if (!(fds[i].revents & POLLIN)) {
        if (fds[i].revents & (POLLHUP | POLLERR)) {
          if (armed &&
              link_recover(c, ranks[i], fds[i].fd, "ANY_SOURCE poll") == 0)
            return -2;  // healed: rebuild the candidate set
          dead.push_back(i);
        }
        continue;
      }
      if (armed) {
        /* armed wire format: peek the 32-byte extended header, service
         * control frames and replay duplicates in place, and heal a
         * failing candidate instead of writing it off */
        MsgHeaderX hx{};
        count_sys();
        ssize_t p = ::recv(fds[i].fd, &hx, sizeof(hx),
                           MSG_PEEK | MSG_DONTWAIT);
        if (p == (ssize_t)sizeof(hx)) {
          LinkState* L = link_state(c, ranks[i]);
          if (L) L->last_rx.store(now_s(), std::memory_order_relaxed);
          if (!hx_check(&hx)) {
            g_lc_crc_errors.fetch_add(1, std::memory_order_relaxed);
            errno = EBADMSG;
            if (link_recover(c, ranks[i], fds[i].fd,
                             "ANY_SOURCE header CRC") == 0)
              return -2;
            FAIL(c, "header CRC mismatch from rank %d (wire corruption)",
                 ranks[i]);
          }
          uint64_t seq = (uint64_t)hx.seq_lo | ((uint64_t)hx.seq_hi << 32);
          if ((hx.h.tag == kPingTag || hx.h.tag == kPongTag) &&
              hx.h.nbytes == 0) {
            ::recv(fds[i].fd, &hx, sizeof(hx), MSG_DONTWAIT);
            if (hx.h.tag == kPingTag && L)
              link_send_control(c->lock_root, c->root_rank[(size_t)ranks[i]],
                                kPongTag);
            progress = true;
            continue;
          }
          if (hx.h.tag == kPoisonTag) {
            ::recv(fds[i].fd, &hx, sizeof(hx), MSG_DONTWAIT);  // consume
            return poison_fail(c, ranks[i], hx.h);
          }
          if (seq != 0 && L &&
              seq <= L->rx_seq.load(std::memory_order_relaxed)) {
            /* replay duplicate at the head: consume and drop it */
            ::recv(fds[i].fd, &hx, sizeof(hx), MSG_DONTWAIT);
            g_lc_dup_dropped.fetch_add(1, std::memory_order_relaxed);
            thread_local std::vector<char> drain;
            int64_t left = hx.h.nbytes;
            if (left > 0 && (int64_t)drain.size() <
                                std::min<int64_t>(left, 1 << 16))
              drain.resize((size_t)std::min<int64_t>(left, 1 << 16));
            int drc = 0;
            while (left > 0 && drc == 0) {
              int64_t take = std::min<int64_t>(left, (int64_t)drain.size());
              drc = read_all_dl(fds[i].fd, drain.data(), take);
              left -= take;
            }
            if (drc) {
              if (io_rc_retryable(drc) &&
                  link_recover(c, ranks[i], fds[i].fd,
                               "ANY_SOURCE dup drain") == 0)
                return -2;
              FAIL_IO(c, drc, "recv payload from %d", ranks[i]);
            }
            progress = true;
            continue;
          }
          if (seq != 0 && L &&
              seq != L->rx_seq.load(std::memory_order_relaxed) + 1) {
            errno = EIO;
            if (link_recover(c, ranks[i], fds[i].fd,
                             "ANY_SOURCE sequence gap") == 0)
              return -2;
            FAIL(c, "sequence gap from rank %d", ranks[i]);
          }
          if (hx.h.tag == kCoalescedTag && hx.h.comm_id == c->comm_id) {
            MsgHeaderX outer{};
            int orc = read_all_dl(fds[i].fd, &outer, sizeof(outer));
            if (orc) {
              if (io_rc_retryable(orc) &&
                  link_recover(c, ranks[i], fds[i].fd,
                               "ANY_SOURCE coalesced header") == 0)
                return -2;
              FAIL(c, "recv coalesced header from %d failed: %s", ranks[i],
                   std::strerror(errno));
            }
            size_t staged0 = 0;
            {
              auto it = c->pending.find(ranks[i]);
              if (it != c->pending.end()) staged0 = it->second.size();
            }
            int src = stage_coalesced_pre(c, ranks[i], outer.h, kAnyTag,
                                          nullptr, 0, nullptr, nullptr,
                                          nullptr, nullptr, 0, fds[i].fd);
            if (src == 5) {
              auto it = c->pending.find(ranks[i]);
              if (it != c->pending.end()) {
                while (it->second.size() > staged0) it->second.pop_back();
                if (it->second.empty()) c->pending.erase(it);
              }
              if (link_recover(c, ranks[i], fds[i].fd,
                               "ANY_SOURCE coalesced") == 0)
                return -2;
              FAIL_IO(c, g_stage_soft_rc, "recv coalesced payload from %d",
                      ranks[i]);
            }
            if (src) return 1;
            wire_mark_delivered(c, ranks[i], seq);
            const MsgHeader* ph = pending_head(c, ranks[i]);
            if (ph && (tag == kAnyTag || ph->tag == tag)) {
              *out_source = ranks[i];
              return 0;
            }
            dead.push_back(i);  // staged head can never match
            continue;
          }
          if (header_matches(c, hx.h, tag)) {
            *out_source = ranks[i];
            return 0;
          }
          dead.push_back(i);  // head frame can never match this wildcard
        } else if (p == 0 || (p < 0 && errno != EAGAIN &&
                              errno != EWOULDBLOCK && errno != EINTR)) {
          if (p == 0) errno = ECONNRESET;
          if (io_rc_retryable(1) &&
              link_recover(c, ranks[i], fds[i].fd, "ANY_SOURCE peek") == 0)
            return -2;
          dead.push_back(i);
        } else if (p > 0 && (int64_t)p > peeked[i]) {
          peeked[i] = p;
          progress = true;
        }
        continue;
      }
      {
        /* POLLIN also fires for EOF; peek the header to tell a real
         * matching frame from a mismatch or a peer that exited */
        MsgHeader h{};
        count_sys();
        ssize_t p = ::recv(fds[i].fd, &h, sizeof(h),
                           MSG_PEEK | MSG_DONTWAIT);
        if (p == (ssize_t)sizeof(h)) {
          if (h.tag == kPoisonTag) {
            ::recv(fds[i].fd, &h, sizeof(h), MSG_DONTWAIT);  // consume hdr
            return poison_fail(c, ranks[i], h);
          }
          if (h.tag == kCoalescedTag && h.comm_id == c->comm_id) {
            /* a coalesced container at the head: split it into pending
             * (consuming the frame preserves per-channel order), then
             * judge the wildcard on the FIRST sub-message's tag */
            MsgHeader outer{};
            if (read_all_dl(c->socks[ranks[i]], &outer, sizeof(outer)))
              FAIL(c, "recv coalesced header from %d failed: %s", ranks[i],
                   std::strerror(errno));
            if (stage_coalesced(c, ranks[i], outer, kAnyTag, nullptr, 0,
                                nullptr, nullptr, nullptr))
              return 1;
            const MsgHeader* ph = pending_head(c, ranks[i]);
            if (ph && (tag == kAnyTag || ph->tag == tag)) {
              *out_source = ranks[i];
              return 0;
            }
            dead.push_back(i);  // staged head can never match
            continue;
          }
          if (header_matches(c, h, tag)) {
            *out_source = ranks[i];
            return 0;
          }
          dead.push_back(i);  // head frame can never match this wildcard
        } else if (p == 0 || (p < 0 && errno != EAGAIN &&
                              errno != EWOULDBLOCK && errno != EINTR)) {
          dead.push_back(i);
        } else if (p > 0 && (int64_t)p > peeked[i]) {
          peeked[i] = p;  // header still arriving: real byte progress
          progress = true;
        }
      }
    }
    if (t > 0) {
      if (progress || !dead.empty()) {
        deadline = now_s() + t;
      } else {
        /* only stalled partial headers keep POLLIN raised with nothing
         * to do: the deadline must be checked HERE too (poll keeps
         * returning ready, so the n == 0 check above never runs), and
         * the loop paced so it can fire without burning a core */
        if (now_s() > deadline)
          FAIL(c,
               "ANY_SOURCE recv timed out after %.0f s — a peer stalled "
               "mid-frame (MPI4JAX_TPU_TIMEOUT_S)",
               t);
        std::this_thread::sleep_for(std::chrono::milliseconds(2));
      }
    }
    for (size_t k = dead.size(); k-- > 0;) {
      fds.erase(fds.begin() + dead[k]);
      ranks.erase(ranks.begin() + dead[k]);
      peeked.erase(peeked.begin() + dead[k]);
    }
    if (fds.empty())
      FAIL(c, "ANY_SOURCE recv: no peer can deliver a matching message "
           "(all disconnected, mismatched, or on other communicators)");
  }
}

int poll_any_source(Comm* c, int tag, int* out_source) {
  for (;;) {
    int rc = poll_any_source_once(c, tag, out_source);
    if (rc != -2) return rc;  // -2: a link healed mid-poll — restart with
                              // fresh fds (the candidate set was rewired)
  }
}

/* Strict-receive fast path over the uring backend: header AND payload
 * speculatively pulled in ONE submission into the registered stash
 * (the sender wrote them contiguously, so they almost always arrive
 * together) — one syscall where the poll path pays four.  Safe because
 * the channel is strictly ordered: the frame at the head IS the one
 * this receive awaits, and every divergence is recoverable —
 *   - a coalesced container's over-pulled content is handed to the
 *     splitter as a prefix (arrival order preserved bit-for-bit),
 *   - a poison frame's text rides the prefix into the abort message,
 *   - any mismatched header fails exactly like the classic path (the
 *     job is aborting; stream position no longer matters).
 * Callers: strict exact-size receives only (recv_msg) — wildcard and
 * status receives keep the classic two-stage path. */
int uring_recv_frame(Comm* c, Uring* u, int source, int tag, void* buf,
                     int64_t nbytes, int64_t* out_count) {
  const int fd = c->socks[source];
  const int64_t want = (int64_t)sizeof(MsgHeader) + nbytes;
  char* st = u->stage.data();
  int64_t got = 0;
  {
    /* first bytes = header arrival = the blocked share (the sender has
     * not reached the matching send until they appear) */
    ObsWaitTimer wt;
    int rc = u_recv_some(u, fd, st, want, &got, transport_timeout_s(),
                         /*stage_fixed=*/true);
    if (rc) FAIL_IO(c, rc, "recv header from %d", source);
    while (got < (int64_t)sizeof(MsgHeader)) {
      int64_t more = 0;
      rc = u_recv_some(u, fd, st + got, want - got, &more,
                       transport_timeout_s(), /*stage_fixed=*/true);
      if (rc) FAIL_IO(c, rc, "recv header from %d", source);
      got += more;
    }
  }
  MsgHeader h;
  std::memcpy(&h, st, sizeof(h));
  const char* body = st + sizeof(h);
  const int64_t body_got = got - (int64_t)sizeof(h);
  if (h.tag == kPoisonTag)
    return poison_fail_pre(c, source, h, body, body_got);
  if (h.comm_id != c->comm_id)
    FAIL(c, "communicator mismatch: rank %d's message is for comm %d, this "
         "is comm %d — ops on sibling communicators must run in a "
         "consistent order on both endpoints", source, h.comm_id,
         c->comm_id);
  if (h.tag == kCoalescedTag) {
    bool consumed = false;
    if (stage_coalesced_pre(c, source, h, tag, buf, nbytes, nullptr,
                            out_count, &consumed, body, body_got))
      return 1;
    if (consumed) return 0;
    return consume_pending(c, source, tag, buf, nbytes, nullptr, nullptr,
                           out_count);
  }
  if (h.tag != tag)
    FAIL(c, "message order violation: expected tag %d from rank %d, got %d",
         tag, source, h.tag);
  if (h.nbytes > nbytes)
    FAIL(c, "message truncated: rank %d sent %lld bytes into a %lld-byte "
         "buffer", source, (long long)h.nbytes, (long long)nbytes);
  int64_t take = std::min(body_got, h.nbytes);
  if (take > 0) std::memcpy(buf, body, (size_t)take);
  if (h.nbytes > take) {
    int rc = u_io_all(u, fd, static_cast<char*>(buf) + take,
                      h.nbytes - take, false, transport_timeout_s());
    if (rc) FAIL_IO(c, rc, "recv payload from %d", source);
  } else if (body_got > h.nbytes) {
    /* over-pulled past a SHORT frame: only reachable when the strict
     * caller is about to abort on the size check — abort with its
     * wording here, never leave the stream desynchronized */
    FAIL(c, "size mismatch from rank %d: expected %lld bytes, got %lld",
         source, (long long)nbytes, (long long)h.nbytes);
  }
  if (out_count) *out_count = h.nbytes;
  return 0;
}

/* Full-featured receive: ANY_TAG / ANY_SOURCE wildcards and short
 * messages allowed (buffer larger than the payload — MPI receive
 * semantics), with the actual source/tag/byte-count reported for status
 * introspection.  The strict recv_msg below keeps the exact-match
 * contract collectives rely on. */
int recv_msg_status(Comm* c, int source, int tag, void* buf, int64_t nbytes,
                    int32_t* out_src, int32_t* out_tag, int64_t* out_count,
                    bool strict_exact = false) {
  fault_fire(c, g_job_rank, FP_RECV, "recv");
  if (source == kAnySource) {
    /* a queued self-message is already complete — it wins immediately,
     * but only when its header actually matches the tag filter (a
     * mismatched self head cannot satisfy this wildcard; a peer might).
     * Staged coalesced sub-messages are equally complete and win next. */
    int pending_src = -1;
    for (const auto& kv : c->pending)
      if (!kv.second.empty() &&
          (tag == kAnyTag || kv.second.front().hdr.tag == tag)) {
        pending_src = kv.first;
        break;
      }
    if (!c->self_q.empty() &&
        header_matches(c, c->self_q.front().first, tag)) {
      source = c->rank;
    } else if (pending_src >= 0) {
      source = pending_src;
    } else if (ring_p2p_on(c)) {
      ObsWaitTimer wt;  // wildcard resolution is pure arrival wait
      if (ring_poll_any(c, tag, &source)) return 1;
    } else {
      ObsWaitTimer wt;
      if (poll_any_source(c, tag, &source)) return 1;
    }
  }
  if (source < 0 || source >= c->size)
    FAIL(c, "recv from invalid rank %d", source);
  if (source == c->rank) {
    /* self-delivery: the ordered op stream means the matching send must
     * already have run (a blocking self-recv first would deadlock —
     * program error, same as MPI) */
    if (c->self_q.empty())
      FAIL(c, "recv from self with no pending self-message");
    auto [h, payload] = std::move(c->self_q.front());
    c->self_q.pop_front();
    if (tag != kAnyTag && h.tag != tag)
      FAIL(c, "message order violation: expected tag %d from self, got %d",
           tag, h.tag);
    if (h.nbytes > nbytes)
      FAIL(c, "message truncated: self-message of %lld bytes into a "
           "%lld-byte buffer", (long long)h.nbytes, (long long)nbytes);
    std::memcpy(buf, payload.data(), h.nbytes);
    if (out_src) *out_src = c->rank;
    if (out_tag) *out_tag = h.tag;
    if (out_count) *out_count = h.nbytes;
    return 0;
  }
  if (pending_head(c, source))
    /* a previously split coalesced frame already delivered this
     * channel's next message: consume it in order, same checks as the
     * wire path */
    return consume_pending(c, source, tag, buf, nbytes, out_src, out_tag,
                           out_count);
  if (ring_p2p_on(c))
    return shm_recv_status(c, source, tag, buf, nbytes, out_src, out_tag,
                           out_count);
  Uring* u;
  if (strict_exact && !retry_armed() && tag != kAnyTag && nbytes > 0 &&
      nbytes <= kUringSmall && (u = uring_acquire()) != nullptr)
    /* strict exact-size receive (recv_msg says so EXPLICITLY — a
     * status caller passing null src/tag still keeps legal
     * short-message semantics): one speculative submission pulls the
     * whole frame (see uring_recv_frame).  Gated off when the link
     * layer is armed: speculative over-pulls cannot be rolled back at
     * frame granularity, which replay-after-reconnect requires (classic
     * reads still ride uring inside io_all_deadline). */
    return uring_recv_frame(c, u, source, tag, buf, nbytes, out_count);
  if (out_src) *out_src = source;
  MsgHeader h{};
  uint64_t seq = 0;
  int ffd = -1;
  int rc;
  for (;;) {
    {
      /* header arrival is the wait phase: the sender hasn't reached (or
       * hasn't finished) the matching send until these bytes appear */
      ObsWaitTimer wt;
      rc = wire_read_hdr(c, source, &h, &seq, &ffd);
    }
    if (rc) {
      /* transient link death with the layer armed: reconnect + replay,
       * then restart this receive at frame granularity (nothing of the
       * failed frame was delivered — delivery marks only run below) */
      if (io_rc_retryable(rc) &&
          link_recover(c, source, ffd, "recv header") == 0)
        continue;
      FAIL_IO(c, rc, "recv header from %d", source);
    }
    if (h.tag == kPoisonTag) return poison_fail(c, source, h);
    if (h.comm_id != c->comm_id)
      FAIL(c, "communicator mismatch: rank %d's message is for comm %d, this "
           "is comm %d — ops on sibling communicators must run in a "
           "consistent order on both endpoints", source, h.comm_id,
           c->comm_id);
    if (h.tag == kCoalescedTag) {
      /* split the container: the first sub-message lands directly in this
       * posted receive when it matches; the rest stage for later recvs */
      size_t staged0 = 0;
      {
        auto it = c->pending.find(source);
        if (it != c->pending.end()) staged0 = it->second.size();
      }
      bool consumed = false;
      int src = stage_coalesced_pre(c, source, h, tag, buf, nbytes, out_tag,
                                    out_count, &consumed, nullptr, 0,
                                    retry_armed() ? ffd : -1);
      if (src == 5) {
        /* mid-container link death: roll the partially staged split
         * back (the sender retained the whole container — the replay
         * redelivers it verbatim from its first byte) */
        auto it = c->pending.find(source);
        if (it != c->pending.end()) {
          while (it->second.size() > staged0) it->second.pop_back();
          if (it->second.empty()) c->pending.erase(it);
        }
        if (link_recover(c, source, ffd, "recv coalesced") == 0) continue;
        FAIL_IO(c, g_stage_soft_rc, "recv coalesced payload from %d",
                source);
      }
      if (src) return 1;
      wire_mark_delivered(c, source, seq);
      if (consumed) return 0;
      return consume_pending(c, source, tag, buf, nbytes, out_src, out_tag,
                             out_count);
    }
    if (tag != kAnyTag && h.tag != tag)
      FAIL(c, "message order violation: expected tag %d from rank %d, got %d",
           tag, source, h.tag);
    if (h.nbytes > nbytes)
      FAIL(c, "message truncated: rank %d sent %lld bytes into a %lld-byte "
           "buffer", source, (long long)h.nbytes, (long long)nbytes);
    rc = read_all_dl(ffd, buf, h.nbytes);
    if (rc) {
      if (io_rc_retryable(rc) &&
          link_recover(c, source, ffd, "recv payload") == 0)
        continue;  // the replay redelivers this frame from its header
      FAIL_IO(c, rc, "recv payload from %d", source);
    }
    wire_mark_delivered(c, source, seq);
    break;
  }
  if (out_tag) *out_tag = h.tag;
  if (out_count) *out_count = h.nbytes;
  return 0;
}

int recv_msg(Comm* c, int source, int tag, void* buf, int64_t nbytes) {
  int64_t count = 0;
  if (recv_msg_status(c, source, tag, buf, nbytes, nullptr, nullptr, &count,
                      /*strict_exact=*/true))
    return 1;
  if (count != nbytes)
    FAIL(c, "size mismatch from rank %d: expected %lld bytes, got %lld",
         source, (long long)nbytes, (long long)count);
  return 0;
}

/* ---------------- element-wise reduction kernels ---------------- */

float bf16_to_f32(uint16_t v) {
  uint32_t bits = (uint32_t)v << 16;
  float out;
  std::memcpy(&out, &bits, 4);
  return out;
}

uint16_t f32_to_bf16(float f) {
  uint32_t bits;
  std::memcpy(&bits, &f, 4);
  /* round to nearest even */
  uint32_t rounded = bits + 0x7fff + ((bits >> 16) & 1);
  return (uint16_t)(rounded >> 16);
}

float f16_to_f32(uint16_t v) {
  uint32_t sign = (v & 0x8000u) << 16;
  uint32_t exp = (v >> 10) & 0x1f;
  uint32_t mant = v & 0x3ff;
  uint32_t bits;
  if (exp == 0) {
    if (mant == 0) {
      bits = sign;
    } else {
      exp = 127 - 15 + 1;
      while (!(mant & 0x400)) {
        mant <<= 1;
        exp--;
      }
      mant &= 0x3ff;
      bits = sign | (exp << 23) | (mant << 13);
    }
  } else if (exp == 31) {
    bits = sign | 0x7f800000u | (mant << 13);
  } else {
    bits = sign | ((exp - 15 + 127) << 23) | (mant << 13);
  }
  float out;
  std::memcpy(&out, &bits, 4);
  return out;
}

uint16_t f32_to_f16(float f) {
  uint32_t bits;
  std::memcpy(&bits, &f, 4);
  uint32_t sign = (bits >> 16) & 0x8000u;
  int32_t exp = (int32_t)((bits >> 23) & 0xff) - 127 + 15;
  uint32_t mant = bits & 0x7fffffu;
  if (exp >= 31) return (uint16_t)(sign | 0x7c00u); /* inf/overflow */
  if (exp <= 0) return (uint16_t)sign;              /* flush denormals */
  return (uint16_t)(sign | (exp << 10) | (mant >> 13));
}

template <typename T>
void combine_typed(T* acc, const T* in, int64_t n, int op) {
  switch (op) {
    case TPU_SUM:
      for (int64_t i = 0; i < n; i++) acc[i] = acc[i] + in[i];
      break;
    case TPU_PROD:
      for (int64_t i = 0; i < n; i++) acc[i] = acc[i] * in[i];
      break;
    case TPU_MAX:
      for (int64_t i = 0; i < n; i++)
        acc[i] = acc[i] < in[i] ? in[i] : acc[i];
      break;
    case TPU_MIN:
      for (int64_t i = 0; i < n; i++)
        acc[i] = in[i] < acc[i] ? in[i] : acc[i];
      break;
    default:
      break;
  }
}

template <typename T>
void combine_integer(T* acc, const T* in, int64_t n, int op) {
  switch (op) {
    case TPU_LAND:
      for (int64_t i = 0; i < n; i++) acc[i] = (T)((acc[i] != 0) && (in[i] != 0));
      break;
    case TPU_LOR:
      for (int64_t i = 0; i < n; i++) acc[i] = (T)((acc[i] != 0) || (in[i] != 0));
      break;
    case TPU_LXOR:
      for (int64_t i = 0; i < n; i++) acc[i] = (T)((acc[i] != 0) ^ (in[i] != 0));
      break;
    case TPU_BAND:
      for (int64_t i = 0; i < n; i++) acc[i] = acc[i] & in[i];
      break;
    case TPU_BOR:
      for (int64_t i = 0; i < n; i++) acc[i] = acc[i] | in[i];
      break;
    case TPU_BXOR:
      for (int64_t i = 0; i < n; i++) acc[i] = acc[i] ^ in[i];
      break;
    default:
      combine_typed(acc, in, n, op);
      break;
  }
}

template <typename T, typename ToF, typename FromF>
void combine_via_float(T* acc, const T* in, int64_t n, int op, ToF to_f,
                       FromF from_f) {
  for (int64_t i = 0; i < n; i++) {
    float a = to_f(acc[i]), b = to_f(in[i]);
    float r;
    switch (op) {
      case TPU_SUM: r = a + b; break;
      case TPU_PROD: r = a * b; break;
      case TPU_MAX: r = a < b ? b : a; break;
      case TPU_MIN: r = b < a ? b : a; break;
      default: r = a; break;
    }
    acc[i] = from_f(r);
  }
}

void combine_complex(float* acc, const float* in, int64_t n, int op) {
  /* n complex elements, interleaved re/im */
  for (int64_t i = 0; i < n; i++) {
    float ar = acc[2 * i], ai = acc[2 * i + 1];
    float br = in[2 * i], bi = in[2 * i + 1];
    if (op == TPU_SUM) {
      acc[2 * i] = ar + br;
      acc[2 * i + 1] = ai + bi;
    } else { /* PROD */
      acc[2 * i] = ar * br - ai * bi;
      acc[2 * i + 1] = ar * bi + ai * br;
    }
  }
}

void combine_complex_d(double* acc, const double* in, int64_t n, int op) {
  for (int64_t i = 0; i < n; i++) {
    double ar = acc[2 * i], ai = acc[2 * i + 1];
    double br = in[2 * i], bi = in[2 * i + 1];
    if (op == TPU_SUM) {
      acc[2 * i] = ar + br;
      acc[2 * i + 1] = ai + bi;
    } else {
      acc[2 * i] = ar * br - ai * bi;
      acc[2 * i + 1] = ar * bi + ai * br;
    }
  }
}

int combine(void* acc, const void* in, int64_t count, int dtype, int op,
            Comm* c) {
  switch (dtype) {
    case TPU_BOOL:
    case TPU_U8:
      combine_integer((uint8_t*)acc, (const uint8_t*)in, count, op);
      return 0;
    case TPU_I8:
      combine_integer((int8_t*)acc, (const int8_t*)in, count, op);
      return 0;
    case TPU_I16:
      combine_integer((int16_t*)acc, (const int16_t*)in, count, op);
      return 0;
    case TPU_I32:
      combine_integer((int32_t*)acc, (const int32_t*)in, count, op);
      return 0;
    case TPU_I64:
      combine_integer((int64_t*)acc, (const int64_t*)in, count, op);
      return 0;
    case TPU_U16:
      combine_integer((uint16_t*)acc, (const uint16_t*)in, count, op);
      return 0;
    case TPU_U32:
      combine_integer((uint32_t*)acc, (const uint32_t*)in, count, op);
      return 0;
    case TPU_U64:
      combine_integer((uint64_t*)acc, (const uint64_t*)in, count, op);
      return 0;
    case TPU_F16:
      combine_via_float((uint16_t*)acc, (const uint16_t*)in, count, op,
                        f16_to_f32, f32_to_f16);
      return 0;
    case TPU_BF16:
      combine_via_float((uint16_t*)acc, (const uint16_t*)in, count, op,
                        bf16_to_f32, f32_to_bf16);
      return 0;
    case TPU_F32:
      combine_typed((float*)acc, (const float*)in, count, op);
      return 0;
    case TPU_F64:
      combine_typed((double*)acc, (const double*)in, count, op);
      return 0;
    case TPU_C64:
      if (op != TPU_SUM && op != TPU_PROD)
        FAIL(c, "op %d not defined for complex dtype", op);
      combine_complex((float*)acc, (const float*)in, count, op);
      return 0;
    case TPU_C128:
      if (op != TPU_SUM && op != TPU_PROD)
        FAIL(c, "op %d not defined for complex dtype", op);
      combine_complex_d((double*)acc, (const double*)in, count, op);
      return 0;
    default:
      FAIL(c, "unknown dtype code %d", dtype);
  }
}

int64_t dtype_size(int dtype) {
  switch (dtype) {
    case TPU_BOOL: case TPU_I8: case TPU_U8: return 1;
    case TPU_I16: case TPU_U16: case TPU_F16: case TPU_BF16: return 2;
    case TPU_I32: case TPU_U32: case TPU_F32: return 4;
    case TPU_I64: case TPU_U64: case TPU_F64: case TPU_C64: return 8;
    case TPU_C128: return 16;
    default: return 0;
  }
}

/* ================= same-host shared-memory arena =================
 *
 * When every member of a communicator lives on one host (the common
 * case for the np=N loopback jobs this replaces libmpi's sm BTL for),
 * collectives run through a POSIX shared-memory arena instead of the
 * TCP loopback stack: one slot per rank plus a result region, fenced
 * by a sense-reversing futex barrier (~14 us for 8 ranks on this
 * host's single core, measured).  Point-to-point stays on TCP — its
 * ordered-stream matching semantics are the product contract, and the
 * collectives are where the serial-hop latency and double-copy cost
 * lived (VERDICT r3 weak #3: 1 KB allreduce 6.4 ms, 16 MB at
 * 0.137 GB/s/rank over TCP loopback).
 *
 * Protocol per collective (all ops use exactly two barriers):
 *   write phase  -> publish opword + B1 -> verify -> read/reduce
 *   phase -> B2 -> (allreduce/reduce: copy result out, protected from
 *   overwrite by the *next* op's B1, which no rank can pass before
 *   every rank finished its copy-out and re-entered).
 * Region discipline behind that protection: slot reads all happen
 *   between B1 and B2, so a rank may write its OWN slot before B1; but
 *   result() reads extend PAST B2 (the large-allreduce copy-out), so
 *   nothing may write result() before B1 — every op that publishes
 *   data pre-B1 (bcast, scatter) stages it through slot(root), and
 *   result() is written only between B1 and B2 (the cooperative
 *   reduce).  A pre-B1 result() write can silently corrupt a slower
 *   rank's allreduce copy-out (ADVICE r4 high).
 * The opword (opcode | root | dtype | reduce-op | byte-count per
 * rank, one cacheline each) turns cross-rank collective-order — or
 * type/op — divergence into a fail-fast diagnostic instead of silent
 * corruption: the shm analog of the TCP frames' comm-id/tag order
 * checking.  Equal byte counts with different dtypes (f32 vs i32) or
 * different reduce ops (SUM vs MAX) are caught too.
 *
 * Large allreduce is cooperative: after B1 each rank reduces its
 * 64-byte-aligned chunk of the message across all slots (AVX2 8-wide
 * vertical sum for the hot f32/SUM case, generic combine() otherwise)
 * into the result region, so every byte is reduced exactly once and
 * every rank reads back bitwise-identical results.  Small messages
 * (<= 64 KB) skip the result indirection: each rank redundantly
 * reduces all slots straight into its private output (same slot
 * order, so still bitwise-identical across ranks).
 *
 * Stale-segment safety: the creator (comm rank 0) writes a random
 * nonce into the header and broadcasts it over the already-connected
 * TCP mesh; attachers reject any segment whose nonce mismatches, so a
 * crashed job's leftover /dev/shm file with the same name can never
 * be adopted.  The creator unlinks the name once every rank has
 * attached.  Env knobs: MPI4JAX_TPU_DISABLE_SHM=1 forces TCP-only
 * (CI exercises both paths), MPI4JAX_TPU_SHM_MB sizes the slots
 * (default 32; bigger messages are processed in slot-sized pieces),
 * MPI4JAX_TPU_SHM_TIMEOUT_S bounds barrier waits (default 180),
 * MPI4JAX_TPU_JOBID uniquifies segment names (the launcher sets a
 * uuid; bare env-var jobs fall back to the coord port). */

struct ShmHdr {
  uint64_t magic;  // set LAST by the creator
  uint64_t nonce;  // fresh per creation; attachers verify via TCP bcast
  int32_t nranks;
  int64_t slot_bytes;
  std::atomic<int32_t> attached;
  std::atomic<int32_t> bar_count;
  std::atomic<int32_t> bar_sense;  // futex word
};

constexpr uint64_t kShmMagic = 0x6d34416a73686d31ull;
constexpr int64_t kOpwordStride = 64;  // one cacheline per rank
constexpr int64_t kShmSmallBytes = 64 * 1024;

/* Per-directed-pair SPSC ring for same-host point-to-point (r5).  One
 * producer (src rank) and one consumer (dst rank); head/tail are byte
 * cursors that only ever grow.  The futex seq words let either side
 * park when the ring is full/empty without burning the shared core. */
struct RingHdr {
  /* producer-written and consumer-written fields live on separate
   * cachelines: both sides store on every op, and sharing a line would
   * ping-pong it between cores on exactly the latency path the rings
   * exist to shorten */
  alignas(64) std::atomic<uint64_t> head;  // bytes produced (src writes)
  std::atomic<int32_t> hseq;               // bumped per publish (futex)
  alignas(64) std::atomic<uint64_t> tail;  // bytes consumed (dst writes)
  std::atomic<int32_t> tseq;               // bumped per consume (futex)
};
static_assert(sizeof(RingHdr) <= 128, "RingHdr must fit kRingHdrBytes");

/* Frame inside a ring: header then payload, padded to 16 bytes.  A
 * kRingStub frame carries no ring payload — the message body follows on
 * the TCP socket (large sends keep the writer-thread progress
 * guarantee); the ring remains the (comm, src->dst) ordering spine. */
struct RingFrame {
  int32_t tag;
  int32_t flags;    // kRingStub
  int64_t nbytes;   // payload size (actual, even for stubs)
};
constexpr int32_t kRingStub = 1;
constexpr int64_t kRingHdrBytes = 128;  // RingHdr, cacheline-padded

int64_t ring_round(int64_t n) { return (n + 15) & ~int64_t(15); }

/* Peer-death detection for the shm wait loops: the TCP recv path gets
 * EOF for free when a peer dies; a futex wait on a shared ring does
 * not.  The mesh socket to the peer doubles as a liveness probe (clean
 * exit -> EOF, crash -> RST), checked only on the slow (parked) path.
 * A socket holding undelivered data is alive, not dead.  With the link
 * layer armed, a dead SOCKET is not a dead PEER until the link state
 * machine says so (a transient reset heals on the next op): only
 * LINK_DEAD — budget exhausted or replay infeasible — reports death. */
bool peer_socket_dead(Comm* c, int r) {
  const bool armed = retry_armed() && !c->root_rank.empty();
  int fd = r >= 0 && r < (int)c->socks.size()
               ? (armed ? link_fd(c, r) : c->socks[r])
               : -1;
  if (armed) {
    LinkState* L = link_state(c, r);
    if (L && L->phase.load(std::memory_order_relaxed) == LINK_DEAD)
      return true;
  }
  if (fd < 0) return false;  // self or never-connected: no evidence
  char b[sizeof(MsgHeader)];
  ssize_t p = ::recv(fd, b, sizeof(b), MSG_PEEK | MSG_DONTWAIT);
  if (p == 0) return !armed;
  if (p < 0 && errno != EAGAIN && errno != EWOULDBLOCK && errno != EINTR)
    return !armed;
  if (p == (ssize_t)sizeof(MsgHeader)) {
    /* a poison control frame means the peer is aborting the job: treat
     * it as dead so shm waiters tear down within one probe interval.
     * (The armed 32-byte header embeds MsgHeader as a prefix, so this
     * 16-byte peek parses the same frame either way.) */
    MsgHeader h{};
    std::memcpy(&h, b, sizeof(h));
    if (h.tag == kPoisonTag) return true;
  }
  return false;
}

struct ShmArena {
  char* base = nullptr;
  size_t map_len = 0;
  int64_t slot_bytes = 0;
  int64_t ring_bytes = 0;
  int nranks = 0;

  ShmHdr* hdr() { return reinterpret_cast<ShmHdr*>(base); }
  std::atomic<uint64_t>* opword(int r) {
    return reinterpret_cast<std::atomic<uint64_t>*>(
        base + 4096 + (int64_t)r * kOpwordStride);
  }
  char* result() { return base + 4096 + (int64_t)nranks * kOpwordStride; }
  char* slot(int r) {
    return result() + slot_bytes + (int64_t)r * slot_bytes;
  }
  /* ring region sits after the slots; one block per directed pair
   * (src, dst), diagonal unused (self goes through self_q) */
  char* ring_base() {
    return result() + (int64_t)(nranks + 1) * slot_bytes;
  }
  RingHdr* ring_hdr(int src, int dst) {
    return reinterpret_cast<RingHdr*>(
        ring_base() +
        ((int64_t)src * nranks + dst) * (kRingHdrBytes + ring_bytes));
  }
  char* ring_data(int src, int dst) {
    return reinterpret_cast<char*>(ring_hdr(src, dst)) + kRingHdrBytes;
  }
  static size_t total_bytes(int nranks, int64_t slot_bytes,
                            int64_t ring_bytes) {
    return 4096 + (size_t)nranks * kOpwordStride +
           (size_t)(nranks + 1) * slot_bytes +
           (size_t)nranks * nranks * (kRingHdrBytes + ring_bytes);
  }
};

void arena_destroy(ShmArena* a) {
  if (a->base) ::munmap(a->base, a->map_len);
  delete a;
}

double shm_timeout_s() {
  const char* e = std::getenv("MPI4JAX_TPU_SHM_TIMEOUT_S");
  double v = e && e[0] ? std::atof(e) : 180.0;
  if (v <= 0) v = 180.0;
  /* the job-wide transport deadline caps shm waits too, so one knob
   * bounds every blocking wait regardless of the path a message rides */
  double t = transport_timeout_s();
  if (t > 0 && t < v) v = t;
  return v;
}

/* Non-temporal streaming copy: bypasses the cache and skips the
 * read-for-ownership a normal store pays, ~3x memcpy for the big
 * arena transfers on this host (9.1 vs 3.1 GB/s measured).  SSE2 is
 * baseline on x86_64.  Ends with sfence so the weakly-ordered stores
 * are globally visible before any following barrier arithmetic. */
void nt_memcpy(void* dst, const void* src, int64_t n) {
  char* d = static_cast<char*>(dst);
  const char* s = static_cast<const char*>(src);
  int64_t head = (16 - ((uintptr_t)d & 15)) & 15;
  if (head > n) head = n;
  if (head) {
    std::memcpy(d, s, head);
    d += head;
    s += head;
    n -= head;
  }
  int64_t i = 0;
  for (; i + 64 <= n; i += 64) {
    __m128i a = _mm_loadu_si128(reinterpret_cast<const __m128i*>(s + i));
    __m128i b = _mm_loadu_si128(reinterpret_cast<const __m128i*>(s + i + 16));
    __m128i e = _mm_loadu_si128(reinterpret_cast<const __m128i*>(s + i + 32));
    __m128i f = _mm_loadu_si128(reinterpret_cast<const __m128i*>(s + i + 48));
    _mm_stream_si128(reinterpret_cast<__m128i*>(d + i), a);
    _mm_stream_si128(reinterpret_cast<__m128i*>(d + i + 16), b);
    _mm_stream_si128(reinterpret_cast<__m128i*>(d + i + 32), e);
    _mm_stream_si128(reinterpret_cast<__m128i*>(d + i + 48), f);
  }
  if (i < n) std::memcpy(d + i, s + i, n - i);
  _mm_sfence();
}

__attribute__((target("avx2"))) void sum_f32_avx2(float* out,
                                                  const float* const* src,
                                                  int ns, int64_t n) {
  bool aligned = ((uintptr_t)out & 31) == 0;
  int64_t i = 0;
  for (; i + 8 <= n; i += 8) {
    __m256 acc = _mm256_loadu_ps(src[0] + i);
    for (int s = 1; s < ns; s++)
      acc = _mm256_add_ps(acc, _mm256_loadu_ps(src[s] + i));
    if (aligned)
      _mm256_stream_ps(out + i, acc);
    else
      _mm256_storeu_ps(out + i, acc);
  }
  for (; i < n; i++) {
    float acc = src[0][i];
    for (int s = 1; s < ns; s++) acc += src[s][i];
    out[i] = acc;
  }
  _mm_sfence();
}

bool have_avx2() {
  static bool v = __builtin_cpu_supports("avx2");
  return v;
}

/* Reduce the same [0, count) element range of ns source buffers into
 * out, combining in source order (deterministic; identical on every
 * rank that runs it with the same sources). */
int vertical_reduce(Comm* c, void* out, const char* const* srcs, int ns,
                    int64_t count, int dtype, int op) {
  if (dtype == TPU_F32 && op == TPU_SUM && have_avx2()) {
    sum_f32_avx2(static_cast<float*>(out),
                 reinterpret_cast<const float* const*>(srcs), ns, count);
    return 0;
  }
  int64_t nb = count * dtype_size(dtype);
  std::memcpy(out, srcs[0], nb);
  for (int s = 1; s < ns; s++)
    if (combine(out, srcs[s], count, dtype, op, c)) return 1;
  return 0;
}

int shm_futex_wait(std::atomic<int32_t>* addr, int32_t expected,
                   int timeout_ms) {
  timespec ts{timeout_ms / 1000, (timeout_ms % 1000) * 1000000L};
  return syscall(SYS_futex, reinterpret_cast<int32_t*>(addr), FUTEX_WAIT,
                 expected, &ts, nullptr, 0);
}

void shm_futex_wake_all(std::atomic<int32_t>* addr) {
  syscall(SYS_futex, reinterpret_cast<int32_t*>(addr), FUTEX_WAKE, INT32_MAX,
          nullptr, nullptr, 0);
}

int shm_barrier(Comm* c) {
  ObsWaitTimer wt;  // barrier rendezvous is pure wait (straggler skew)
  ShmHdr* h = c->arena->hdr();
  _mm_sfence();  // drain NT stores before signaling arrival
  int32_t sense = h->bar_sense.load(std::memory_order_acquire);
  if (h->bar_count.fetch_add(1, std::memory_order_acq_rel) ==
      c->arena->nranks - 1) {
    h->bar_count.store(0, std::memory_order_relaxed);
    h->bar_sense.store(1 - sense, std::memory_order_release);
    shm_futex_wake_all(&h->bar_sense);
    return 0;
  }
  double deadline = now_s() + shm_timeout_s();
  int spins = 0;
  while (h->bar_sense.load(std::memory_order_acquire) == sense) {
    /* few yields, then futex: on hosts where ranks share cores (this
     * one exposes a single core for 8 ranks) long yield loops just
     * churn the run queue — 4 was the measured sweet spot */
    if (spins < 4) {
      spins++;
      ::sched_yield();
      continue;
    }
    shm_futex_wait(&h->bar_sense, sense, 100);
    if (h->bar_sense.load(std::memory_order_acquire) != sense) break;
    for (int r = 0; r < c->size; r++)
      if (r != c->rank && peer_socket_dead(c, r)) {
        /* TOCTOU: the last arriver may have flipped the sense and
         * exited between our sense load and the death probe */
        if (h->bar_sense.load(std::memory_order_acquire) != sense) break;
        FAIL(c, "shm barrier: rank %d exited while this rank waits — "
             "the ranks disagree on the collective schedule", r);
      }
    if (now_s() > deadline)
      FAIL(c,
           "shm barrier timed out after %.0f s — a peer died or the ranks "
           "disagree on the collective schedule (set "
           "MPI4JAX_TPU_SHM_TIMEOUT_S to adjust)",
           shm_timeout_s());
  }
  return 0;
}

/* ================= shm point-to-point rings =================
 *
 * Same-host send/recv/sendrecv (and shift2, which rides them) go
 * through per-directed-pair SPSC rings in the arena instead of the TCP
 * loopback stack (VERDICT r4 #3: np2 sendrecv 1 KB was 27.5 us over
 * TCP while the arena showed ~16 us two-barrier round trips).
 *
 * Contract preserved exactly:
 * - ordered-stream matching per (comm, src->dst): the ring IS the
 *   stream; the head frame must match the expected tag or fail fast
 *   (same "message order violation" diagnostic as the TCP frames);
 * - sends never block on a missing receiver: a frame that doesn't fit
 *   the ring's free space degrades to a kRingStub in the ring (the
 *   ordering spine) with the payload riding the existing TCP
 *   eager/writer-thread path — the progress guarantee the writer
 *   thread gives TCP large sends carries over unchanged;
 * - ANY_SOURCE polls every inbound ring head (self-queue first), and a
 *   head that cannot match is dropped from the candidate set, exactly
 *   like the TCP poll;
 * - collective-protocol traffic never enters the rings (arena comms
 *   run collectives through the barrier protocol above).
 *
 * Knobs: MPI4JAX_TPU_SHM_RING_KB sizes each ring (default 1024;
 * inline cutoff is ring/4), MPI4JAX_TPU_DISABLE_SHM_P2P=1 keeps p2p
 * on TCP while collectives stay on the arena (CI axis; must agree
 * across ranks, like the other shm knobs). */

int ring_wait_space(Comm* c, int dest, RingHdr* rh, int64_t ring_bytes,
                    int64_t need) {
  ObsWaitTimer wt;  // blocked on the consumer draining the ring
  double deadline = now_s() + shm_timeout_s();
  int spins = 0;
  for (;;) {
    uint64_t used = rh->head.load(std::memory_order_relaxed) -
                    rh->tail.load(std::memory_order_acquire);
    if ((int64_t)(ring_bytes - used) >= need) return 0;
    if (spins < 4) {
      spins++;
      ::sched_yield();
      continue;
    }
    int32_t seq = rh->tseq.load(std::memory_order_acquire);
    uint64_t used2 = rh->head.load(std::memory_order_relaxed) -
                     rh->tail.load(std::memory_order_acquire);
    if ((int64_t)(ring_bytes - used2) >= need) return 0;
    shm_futex_wait(&rh->tseq, seq, 50);
    if (peer_socket_dead(c, dest))
      FAIL(c, "send to rank %d failed: peer exited with its inbound "
           "ring full", dest);
    if (now_s() > deadline)
      FAIL(c,
           "shm p2p ring full for %.0f s — the peer stopped receiving "
           "(died, or the ranks disagree on the message schedule)",
           shm_timeout_s());
  }
}

void ring_copy_in(char* data, int64_t ring_bytes, uint64_t at,
                  const void* src, int64_t n) {
  int64_t off = (int64_t)(at % (uint64_t)ring_bytes);
  int64_t first = std::min(n, ring_bytes - off);
  std::memcpy(data + off, src, first);
  if (n > first) std::memcpy(data, (const char*)src + first, n - first);
}

void ring_copy_out(const char* data, int64_t ring_bytes, uint64_t at,
                   void* dst, int64_t n) {
  int64_t off = (int64_t)(at % (uint64_t)ring_bytes);
  int64_t first = std::min(n, ring_bytes - off);
  std::memcpy(dst, data + off, first);
  if (n > first) std::memcpy((char*)dst + first, data, n - first);
}

/* Push one frame (inline payload or stub).  Space for the 16-byte
 * header is always waited for (a full ring of stubs means 64Ki
 * outstanding unreceived messages — schedule bug, surfaced by the
 * timeout); inline callers check free space first and degrade to a
 * stub instead of waiting. */
int ring_push(Comm* c, int dst, int32_t tag, int32_t flags,
              const void* buf, int64_t nbytes) {
  ShmArena* a = c->arena;
  RingHdr* rh = a->ring_hdr(c->rank, dst);
  char* data = a->ring_data(c->rank, dst);
  int64_t payload = (flags & kRingStub) ? 0 : ring_round(nbytes);
  int64_t need = (int64_t)sizeof(RingFrame) + payload;
  if (ring_wait_space(c, dst, rh, a->ring_bytes, need)) return 1;
  uint64_t head = rh->head.load(std::memory_order_relaxed);
  RingFrame f{tag, flags, nbytes};
  ring_copy_in(data, a->ring_bytes, head, &f, sizeof(f));
  if (payload)
    ring_copy_in(data, a->ring_bytes, head + sizeof(RingFrame), buf, nbytes);
  rh->head.store(head + need, std::memory_order_release);
  rh->hseq.fetch_add(1, std::memory_order_release);
  shm_futex_wake_all(&rh->hseq);
  return 0;
}

/* Block until the (src -> me) ring holds a frame; peek it into *out. */
int ring_wait_frame(Comm* c, int src, RingFrame* out) {
  ObsWaitTimer wt;  // frame arrival = wait phase (shm twin of the
                    // TCP header read)
  ShmArena* a = c->arena;
  RingHdr* rh = a->ring_hdr(src, c->rank);
  double deadline = now_s() + shm_timeout_s();
  int spins = 0;
  for (;;) {
    uint64_t tail = rh->tail.load(std::memory_order_relaxed);
    if (rh->head.load(std::memory_order_acquire) != tail) {
      ring_copy_out(a->ring_data(src, c->rank), a->ring_bytes, tail, out,
                    sizeof(*out));
      return 0;
    }
    if (spins < 4) {
      spins++;
      ::sched_yield();
      continue;
    }
    int32_t seq = rh->hseq.load(std::memory_order_acquire);
    if (rh->head.load(std::memory_order_acquire) !=
        rh->tail.load(std::memory_order_relaxed))
      continue;
    shm_futex_wait(&rh->hseq, seq, 50);
    if (rh->head.load(std::memory_order_acquire) !=
        rh->tail.load(std::memory_order_relaxed))
      continue;  // drain whatever arrived, even from a now-dead peer
    if (peer_socket_dead(c, src)) {
      /* TOCTOU: the peer's last act may have been push-then-exit
       * between our emptiness load and the death probe — recheck */
      if (rh->head.load(std::memory_order_acquire) !=
          rh->tail.load(std::memory_order_relaxed))
        continue;
      FAIL(c, "recv from rank %d failed: peer exited with no matching "
           "send pending", src);
    }
    if (now_s() > deadline)
      FAIL(c,
           "shm p2p recv from rank %d timed out after %.0f s — no "
           "matching send arrived (peer died or schedule mismatch)",
           src, shm_timeout_s());
  }
}

/* Consume the head frame after its payload (if inline) is copied out. */
void ring_consume(Comm* c, int src, const RingFrame& f) {
  ShmArena* a = c->arena;
  RingHdr* rh = a->ring_hdr(src, c->rank);
  int64_t payload = (f.flags & kRingStub) ? 0 : ring_round(f.nbytes);
  rh->tail.fetch_add((int64_t)sizeof(RingFrame) + payload,
                     std::memory_order_release);
  rh->tseq.fetch_add(1, std::memory_order_release);
  shm_futex_wake_all(&rh->tseq);
}

bool ring_p2p_on(const Comm* c) {
  return c->arena != nullptr && c->arena->ring_bytes > 0;
}

/* ANY_SOURCE over the rings: first peer whose HEAD frame matches the
 * tag filter wins; a non-matching head disqualifies that peer (its
 * stream can never satisfy this wildcard), mirroring poll_any_source. */
int ring_poll_any(Comm* c, int tag, int* out_source) {
  std::vector<int> cands;
  for (int r = 0; r < c->size; r++)
    if (r != c->rank) cands.push_back(r);
  double deadline = now_s() + shm_timeout_s();
  for (;;) {
    for (size_t i = 0; i < cands.size();) {
      int r = cands[i];
      RingHdr* rh = c->arena->ring_hdr(r, c->rank);
      uint64_t tail = rh->tail.load(std::memory_order_relaxed);
      if (rh->head.load(std::memory_order_acquire) != tail) {
        RingFrame f{};
        ring_copy_out(c->arena->ring_data(r, c->rank), c->arena->ring_bytes,
                      tail, &f, sizeof(f));
        if (tag == kAnyTag || f.tag == tag) {
          *out_source = r;
          return 0;
        }
        cands.erase(cands.begin() + i);  // head can never match
        continue;
      }
      i++;
    }
    if (cands.empty())
      FAIL(c, "ANY_SOURCE recv: no peer can deliver a matching message "
           "(all ring heads mismatched or peers exited)");
    ::sched_yield();
    for (size_t i = 0; i < cands.size();) {
      RingHdr* rh = c->arena->ring_hdr(cands[i], c->rank);
      bool empty = rh->head.load(std::memory_order_acquire) ==
                   rh->tail.load(std::memory_order_relaxed);
      if (empty && peer_socket_dead(c, cands[i]) &&
          /* TOCTOU: push-then-exit between the loads — recheck */
          rh->head.load(std::memory_order_acquire) ==
              rh->tail.load(std::memory_order_relaxed))
        cands.erase(cands.begin() + i);
      else
        i++;
    }
    if (now_s() > deadline)
      FAIL(c, "ANY_SOURCE recv timed out after %.0f s on the shm rings",
           shm_timeout_s());
  }
}

int shm_try_send(Comm* c, int dest, int tag, const void* buf,
                 int64_t nbytes, bool* inlined) {
  /* a send that rides the shm rings never reaches send_msg_tcp, so the
   * injector needs its own hook here (point=send counts transmissions:
   * a stub-degraded send also pays the TCP-payload count).  When the
   * link layer is armed, target the peer's TCP link precisely — shm
   * traffic itself cannot be reset, so the fault lands on the idle
   * socket underneath and heartbeats (or the next stub payload) find
   * it */
  fault_fire(c, g_job_rank, FP_SEND, "send",
             retry_armed() ? link_fd(c, dest) : -1);
  ShmArena* a = c->arena;
  RingHdr* rh = a->ring_hdr(c->rank, dest);
  int64_t need = (int64_t)sizeof(RingFrame) + ring_round(nbytes);
  uint64_t used = rh->head.load(std::memory_order_relaxed) -
                  rh->tail.load(std::memory_order_acquire);
  if (nbytes <= a->ring_bytes / 4 &&
      (int64_t)(a->ring_bytes - used) >= need) {
    *inlined = true;
    return ring_push(c, dest, tag, 0, buf, nbytes);
  }
  /* too big, or no room right now: order rides a stub; payload rides
   * the TCP eager/writer path so the send still cannot block on a
   * missing receiver */
  *inlined = false;
  return ring_push(c, dest, tag, kRingStub, nullptr, nbytes);
}

int shm_recv_status(Comm* c, int source, int tag, void* buf,
                    int64_t nbytes, int32_t* out_src, int32_t* out_tag,
                    int64_t* out_count) {
  ShmArena* a = c->arena;
  RingFrame f{};
  if (ring_wait_frame(c, source, &f)) return 1;
  if (tag != kAnyTag && f.tag != tag)
    FAIL(c, "message order violation: expected tag %d from rank %d, got %d",
         tag, source, f.tag);
  if (f.nbytes > nbytes)
    FAIL(c, "message truncated: rank %d sent %lld bytes into a %lld-byte "
         "buffer", source, (long long)f.nbytes, (long long)nbytes);
  if (f.flags & kRingStub) {
    /* payload is the next TCP frame from this peer; the usual header
       checks keep cross-communicator socket order honest */
    for (;;) {
      MsgHeader h{};
      uint64_t seq = 0;
      int ffd = -1;
      int rc = wire_read_hdr(c, source, &h, &seq, &ffd);
      if (rc) {
        if (io_rc_retryable(rc) &&
            link_recover(c, source, ffd, "recv stub payload header") == 0)
          continue;
        FAIL_IO(c, rc, "recv header from %d", source);
      }
      if (h.tag == kPoisonTag) return poison_fail(c, source, h);
      if (h.comm_id != c->comm_id)
        FAIL(c, "communicator mismatch: rank %d's message is for comm %d, "
             "this is comm %d — ops on sibling communicators must run in a "
             "consistent order on both endpoints", source, h.comm_id,
             c->comm_id);
      if (h.tag != f.tag || h.nbytes != f.nbytes)
        FAIL(c, "shm stub/TCP frame mismatch from rank %d (tag %d/%d, "
             "bytes %lld/%lld)", source, f.tag, h.tag, (long long)f.nbytes,
             (long long)h.nbytes);
      rc = read_all_dl(ffd, buf, h.nbytes);
      if (rc) {
        if (io_rc_retryable(rc) &&
            link_recover(c, source, ffd, "recv stub payload") == 0)
          continue;
        FAIL_IO(c, rc, "recv payload from %d", source);
      }
      wire_mark_delivered(c, source, seq);
      break;
    }
  } else {
    RingHdr* rh = a->ring_hdr(source, c->rank);
    uint64_t tail = rh->tail.load(std::memory_order_relaxed);
    ring_copy_out(a->ring_data(source, c->rank), a->ring_bytes,
                  tail + sizeof(RingFrame), buf, f.nbytes);
  }
  ring_consume(c, source, f);
  if (out_src) *out_src = source;
  if (out_tag) *out_tag = f.tag;
  if (out_count) *out_count = f.nbytes;
  return 0;
}

/* opword layout: opcode byte | root byte | dtype byte | reduce-op byte
 * | 32 bits of per-rank piece bytes (pieces are <= slot_bytes, far
 * below 4 GB).  dtype/op are 0 for ops they don't apply to. */
uint64_t shm_opword(int opcode, int root, int dtype, int op,
                    int64_t nbytes) {
  return ((uint64_t)(uint8_t)opcode << 56) | ((uint64_t)(uint8_t)root << 48) |
         ((uint64_t)(uint8_t)dtype << 40) | ((uint64_t)(uint8_t)op << 32) |
         ((uint64_t)nbytes & 0xffffffffull);
}

enum ShmOpcode {
  SHM_ALLREDUCE = 1, SHM_REDUCE, SHM_SCAN, SHM_BCAST, SHM_BARRIER,
  SHM_ALLGATHER, SHM_GATHER, SHM_SCATTER, SHM_ALLTOALL,
};

/* B1 with the cross-rank schedule check (see section comment). */
int shm_publish_and_check(Comm* c, uint64_t word) {
  ShmArena* a = c->arena;
  a->opword(c->rank)->store(word, std::memory_order_release);
  if (shm_barrier(c)) return 1;
  for (int r = 0; r < a->nranks; r++) {
    uint64_t w = a->opword(r)->load(std::memory_order_acquire);
    if (w != word)
      FAIL(c,
           "collective schedule mismatch: rank %d published op 0x%llx, this "
           "rank op 0x%llx — every member must issue collectives on a "
           "communicator in the same order",
           r, (unsigned long long)w, (unsigned long long)word);
  }
  return 0;
}

int shm_allreduce_like(Comm* c, const void* sendbuf, void* recvbuf,
                       int64_t count, int dtype, int op, int root,
                       bool all_ranks_out) {
  ShmArena* a = c->arena;
  const int64_t esize = dtype_size(dtype);
  const int64_t total = count * esize;
  const char* in = static_cast<const char*>(sendbuf);
  char* out = static_cast<char*>(recvbuf);
  const int opcode = all_ranks_out ? SHM_ALLREDUCE : SHM_REDUCE;
  std::vector<const char*> srcs(a->nranks);
  int64_t off = 0;
  do {
    int64_t nb = std::min(total - off, a->slot_bytes);
    int64_t cnt = nb / esize;
    nt_memcpy(a->slot(c->rank), in + off, nb);
    if (shm_publish_and_check(c, shm_opword(opcode, root, dtype, op, nb)))
      return 1;
    for (int r = 0; r < a->nranks; r++) srcs[r] = a->slot(r);
    if (nb <= kShmSmallBytes) {
      /* every interested rank reduces all slots straight into its out */
      if (all_ranks_out || c->rank == root) {
        if (vertical_reduce(c, out + off, srcs.data(), a->nranks, cnt, dtype,
                            op))
          return 1;
      }
      if (shm_barrier(c)) return 1;
    } else {
      /* cooperative: this rank owns a 64-byte-aligned chunk */
      int64_t per = (((nb + a->nranks - 1) / a->nranks) + 63) & ~int64_t(63);
      int64_t lo = std::min(per * c->rank, nb);
      int64_t hi = std::min(lo + per, nb);
      if (hi > lo) {
        std::vector<const char*> chunk(a->nranks);
        for (int r = 0; r < a->nranks; r++) chunk[r] = srcs[r] + lo;
        if (vertical_reduce(c, a->result() + lo, chunk.data(), a->nranks,
                            (hi - lo) / esize, dtype, op))
          return 1;
      }
      if (shm_barrier(c)) return 1;
      if (all_ranks_out || c->rank == root)
        nt_memcpy(out + off, a->result(), nb);
    }
    off += nb;
  } while (off < total);
  return 0;
}

int shm_scan(Comm* c, const void* sendbuf, void* recvbuf, int64_t count,
             int dtype, int op) {
  ShmArena* a = c->arena;
  const int64_t esize = dtype_size(dtype);
  const int64_t total = count * esize;
  const char* in = static_cast<const char*>(sendbuf);
  char* out = static_cast<char*>(recvbuf);
  std::vector<const char*> srcs(a->nranks);
  int64_t off = 0;
  do {
    int64_t nb = std::min(total - off, a->slot_bytes);
    nt_memcpy(a->slot(c->rank), in + off, nb);
    if (shm_publish_and_check(c, shm_opword(SHM_SCAN, 0, dtype, op, nb)))
      return 1;
    for (int r = 0; r <= c->rank; r++) srcs[r] = a->slot(r);
    if (vertical_reduce(c, out + off, srcs.data(), c->rank + 1, nb / esize,
                        dtype, op))
      return 1;
    if (shm_barrier(c)) return 1;
    off += nb;
  } while (off < total);
  return 0;
}

int shm_bcast(Comm* c, void* buf, int64_t nbytes, int root) {
  ShmArena* a = c->arena;
  char* p = static_cast<char*>(buf);
  int64_t off = 0;
  do {
    int64_t nb = std::min(nbytes - off, a->slot_bytes);
    /* pre-B1 writes must target the writer's own slot, never result()
     * (a slow rank may still be copying a previous large allreduce out
     * of result() after its B2 — ADVICE r4 high) */
    if (c->rank == root) nt_memcpy(a->slot(root), p + off, nb);
    if (shm_publish_and_check(c, shm_opword(SHM_BCAST, root, 0, 0, nb)))
      return 1;
    if (c->rank != root) std::memcpy(p + off, a->slot(root), nb);
    if (shm_barrier(c)) return 1;
    off += nb;
  } while (off < nbytes);
  return 0;
}

int shm_allgather(Comm* c, const void* sendbuf, int64_t nbytes,
                  void* recvbuf, int root, bool all_ranks_out) {
  ShmArena* a = c->arena;
  const char* in = static_cast<const char*>(sendbuf);
  char* out = static_cast<char*>(recvbuf);
  const int opcode = all_ranks_out ? SHM_ALLGATHER : SHM_GATHER;
  int64_t off = 0;
  do {
    int64_t nb = std::min(nbytes - off, a->slot_bytes);
    nt_memcpy(a->slot(c->rank), in + off, nb);
    if (shm_publish_and_check(c, shm_opword(opcode, root, 0, 0, nb)))
      return 1;
    if (all_ranks_out || c->rank == root)
      for (int r = 0; r < a->nranks; r++)
        std::memcpy(out + (int64_t)r * nbytes + off, a->slot(r), nb);
    if (shm_barrier(c)) return 1;
    off += nb;
  } while (off < nbytes);
  return 0;
}

int shm_scatter(Comm* c, const void* sendbuf, void* recvbuf, int64_t nbytes,
                int root) {
  ShmArena* a = c->arena;
  const char* in = static_cast<const char*>(sendbuf);
  char* out = static_cast<char*>(recvbuf);
  /* per-piece budget: all nranks pieces must fit one slot */
  int64_t piece = std::max<int64_t>(
      64, (a->slot_bytes / a->nranks) & ~int64_t(63));
  int64_t off = 0;
  do {
    int64_t nb = std::min(nbytes - off, piece);
    /* staged through slot(root), not result(): see bcast note */
    if (c->rank == root)
      for (int r = 0; r < a->nranks; r++)
        nt_memcpy(a->slot(root) + (int64_t)r * nb,
                  in + (int64_t)r * nbytes + off, nb);
    if (shm_publish_and_check(c, shm_opword(SHM_SCATTER, root, 0, 0, nb)))
      return 1;
    std::memcpy(out + off, a->slot(root) + (int64_t)c->rank * nb, nb);
    if (shm_barrier(c)) return 1;
    off += nb;
  } while (off < nbytes);
  return 0;
}

int shm_alltoall(Comm* c, const void* sendbuf, void* recvbuf,
                 int64_t chunk) {
  ShmArena* a = c->arena;
  const char* in = static_cast<const char*>(sendbuf);
  char* out = static_cast<char*>(recvbuf);
  int64_t piece = std::max<int64_t>(
      64, (a->slot_bytes / a->nranks) & ~int64_t(63));
  int64_t off = 0;
  do {
    int64_t nb = std::min(chunk - off, piece);
    for (int d = 0; d < a->nranks; d++)
      nt_memcpy(a->slot(c->rank) + (int64_t)d * nb,
                in + (int64_t)d * chunk + off, nb);
    if (shm_publish_and_check(c, shm_opword(SHM_ALLTOALL, 0, 0, 0, nb)))
      return 1;
    for (int s = 0; s < a->nranks; s++)
      std::memcpy(out + (int64_t)s * chunk + off,
                  a->slot(s) + (int64_t)c->rank * nb, nb);
    if (shm_barrier(c)) return 1;
    off += nb;
  } while (off < chunk);
  return 0;
}

int shm_barrier_op(Comm* c) {
  if (shm_publish_and_check(c, shm_opword(SHM_BARRIER, 0, 0, 0, 0))) return 1;
  return shm_barrier(c);
}

int bcast_internal(Comm* c, void* buf, int64_t nbytes, int root);

/* Create/attach the arena for comm c (all members must share this
 * host; collective over c's TCP mesh).  Failure is soft: the comm
 * simply stays on the TCP path.  Called before c is published. */
void arena_init(Comm* c) {
  if (c->size < 2) return;
  const char* dis = std::getenv("MPI4JAX_TPU_DISABLE_SHM");
  if (dis && dis[0] && dis[0] != '0') return;
  int64_t slot_mb = 32;
  if (const char* e = std::getenv("MPI4JAX_TPU_SHM_MB"))
    if (std::atoll(e) > 0) slot_mb = std::atoll(e);
  int64_t slot_bytes = ((slot_mb << 20) + 4095) & ~int64_t(4095);
  int64_t ring_kb = 1024;
  if (const char* e = std::getenv("MPI4JAX_TPU_SHM_RING_KB"))
    if (std::atoll(e) > 0) ring_kb = std::atoll(e);
  const char* p2p_dis = std::getenv("MPI4JAX_TPU_DISABLE_SHM_P2P");
  if (p2p_dis && p2p_dis[0] && p2p_dis[0] != '0') ring_kb = 0;
  int64_t ring_bytes = ring_kb << 10;
  size_t total = ShmArena::total_bytes(c->size, slot_bytes, ring_bytes);
  char name[128];
  std::snprintf(name, sizeof(name), "/%s_c%d", c->shm_prefix.c_str(),
                (int)c->comm_id);

  ShmArena* a = new ShmArena;
  a->slot_bytes = slot_bytes;
  a->ring_bytes = ring_bytes;
  a->nranks = c->size;
  uint64_t nonce = 0;
  if (c->rank == 0) {
    static thread_local std::mt19937_64 rng{std::random_device{}()};
    ::shm_unlink(name);
    int fd = ::shm_open(name, O_CREAT | O_EXCL | O_RDWR, 0600);
    if (fd >= 0 && ::ftruncate(fd, (off_t)total) == 0) {
      void* base = ::mmap(nullptr, total, PROT_READ | PROT_WRITE, MAP_SHARED,
                          fd, 0);
      if (base != MAP_FAILED) {
#ifdef MADV_HUGEPAGE
        ::madvise(base, total, MADV_HUGEPAGE);  // fewer TLB misses on the
                                                // multi-MB streaming copies
#endif
        a->base = static_cast<char*>(base);
        a->map_len = total;
        ShmHdr* h = a->hdr();
        nonce = rng() | 1;  // nonzero
        h->nonce = nonce;
        h->nranks = c->size;
        h->slot_bytes = slot_bytes;
        h->attached.store(1, std::memory_order_relaxed);
        h->bar_count.store(0, std::memory_order_relaxed);
        h->bar_sense.store(0, std::memory_order_relaxed);
        std::atomic_thread_fence(std::memory_order_release);
        __atomic_store_n(&h->magic, kShmMagic, __ATOMIC_RELEASE);
      }
    }
    int saved_errno = errno;
    if (fd >= 0) ::close(fd);
    if (!a->base) {
      if (fd >= 0) ::shm_unlink(name);  // don't leak a half-created name
      std::fprintf(stderr,
                   "tpucomm r%d: shm arena creation failed (%s); collectives "
                   "stay on TCP\n",
                   c->rank, std::strerror(saved_errno));
      nonce = 0;
    }
  }
  /* creator tells everyone the nonce (0 = no arena, stay on TCP) */
  uint64_t wire = nonce;
  if (bcast_internal(c, &wire, sizeof(wire), 0) != 0) wire = 0;
  if (wire == 0) {
    if (a->base) {
      ::shm_unlink(name);
      ::munmap(a->base, a->map_len);
    }
    delete a;
    return;
  }
  nonce = wire;
  /* attach waits are bounded by 30 s, tightened by the job deadline */
  const double attach_wait_s = std::min(30.0, shm_timeout_s());
  if (c->rank != 0) {
    double deadline = now_s() + attach_wait_s;
    for (;;) {
      int fd = ::shm_open(name, O_RDWR, 0600);
      if (fd >= 0) {
        struct stat st{};
        if (::fstat(fd, &st) == 0 && (size_t)st.st_size == total) {
          void* base = ::mmap(nullptr, total, PROT_READ | PROT_WRITE,
                              MAP_SHARED, fd, 0);
          ::close(fd);
          if (base != MAP_FAILED) {
            ShmHdr* h = reinterpret_cast<ShmHdr*>(base);
            if (__atomic_load_n(&h->magic, __ATOMIC_ACQUIRE) == kShmMagic &&
                h->nonce == nonce) {
              a->base = static_cast<char*>(base);
              a->map_len = total;
              break;
            }
            ::munmap(base, total);
          }
        } else {
          ::close(fd);
        }
      }
      if (now_s() > deadline) {
        std::fprintf(stderr,
                     "tpucomm r%d: shm arena attach timed out; aborting "
                     "(creator succeeded, so this host is misconfigured)\n",
                     c->rank);
        delete a;
        std::exit(1);  // mixed shm/TCP members would deadlock: fail fast
      }
      std::this_thread::sleep_for(std::chrono::milliseconds(2));
    }
    a->hdr()->attached.fetch_add(1, std::memory_order_acq_rel);
  }
  /* everyone waits for full attachment, then the name disappears */
  double deadline = now_s() + attach_wait_s;
  while (a->hdr()->attached.load(std::memory_order_acquire) < c->size) {
    if (now_s() > deadline) {
      std::fprintf(stderr, "tpucomm r%d: shm arena attach wait timed out\n",
                   c->rank);
      std::exit(1);
    }
    ::sched_yield();
  }
  if (c->rank == 0) ::shm_unlink(name);
  c->arena = a;
}

int bcast_internal(Comm* c, void* buf, int64_t nbytes, int root) {
  /* binomial tree rooted at `root` (relative ranks) */
  int vrank = (c->rank - root + c->size) % c->size;
  int dist = 1;
  while (dist < c->size) dist *= 2;
  if (vrank != 0) {
    int lowbit = vrank & (-vrank);
    int parent = (vrank - lowbit + root) % c->size;
    if (recv_msg(c, parent, kCollectiveTag, buf, nbytes)) return 1;
  }
  int lowbit = vrank == 0 ? dist : (vrank & (-vrank));
  for (int step = lowbit / 2; step >= 1; step /= 2) {
    int vchild = vrank + step;
    if (vchild < c->size) {
      int child = (vchild + root) % c->size;
      if (send_msg(c, child, kCollectiveTag, buf, nbytes)) return 1;
    }
  }
  return 0;
}

/* ================= collective algorithm engine (TCP path) =================
 *
 * allreduce/allgather carry selectable schedules; selection is owned by
 * the Python tune package (mpi4jax_tpu/tune), which installs a per-op
 * (min_bytes -> algorithm) decision table here at communicator creation.
 * Per-call forcing rides the *_algo entry points.  All algorithms use
 * the same kCollectiveTag frames as the fixed schedules they replace,
 * so the ordered transport's divergence checks (tag/size/comm-id) keep
 * firing identically under every algorithm — a cross-rank disagreement
 * on the algorithm aborts at the first mismatched frame. */

struct CollTable {
  /* (min_bytes ascending, TpuCollAlgo); empty = built-in heuristic */
  std::vector<std::pair<int64_t, int32_t>> entries;
};
CollTable g_coll_table[3];  // indexed by TpuCollOpKind
std::mutex g_coll_table_mu;

/* Live re-tuning staging area (mpi4jax_tpu/live): candidate tables park
 * here without touching dispatch until every rank commits them at an
 * agreed collective boundary.  g_coll_epoch stamps the live table's
 * generation (0 = the offline-installed table). */
CollTable g_coll_staged[3];
bool g_coll_staged_set[3] = {false, false, false};
int64_t g_coll_epoch = 0;

int coll_table_lookup(int op_kind, int64_t nbytes) {
  std::lock_guard<std::mutex> lock(g_coll_table_mu);
  int algo = TPU_COLL_AUTO;
  for (const auto& e : g_coll_table[op_kind].entries) {
    if (nbytes >= e.first) algo = e.second;
  }
  return algo;
}

const char* coll_algo_name(int algo) {
  switch (algo) {
    case TPU_COLL_RING: return "ring";
    case TPU_COLL_RD: return "rd";
    case TPU_COLL_TREE: return "tree";
    case TPU_COLL_SHM: return "shm";
    case TPU_COLL_QRING: return "qring";
    case TPU_COLL_QRD: return "qrd";
    case TPU_COLL_HRING: return "hring";
    case TPU_COLL_HTREE: return "htree";
    case TPU_COLL_QA2A: return "qalltoall";
    case TPU_COLL_HA2A: return "halltoall";
    case TPU_COLL_HQA2A: return "hqalltoall";
    default: return "auto";
  }
}

/* MPI4JAX_TPU_HIER: process-wide gate over the hierarchical schedules.
 * allow (default) = table/env/API selection may pick hring/htree (and
 * large bcast/reduce route hierarchically) on a multi-island comm;
 * deny = every hierarchical pick degrades to its flat twin (a routing
 * kill-switch; frames still match because the degradation keys on the
 * installed topology, which agrees across ranks); force = every
 * eligible allreduce/allgather upgrades to a hierarchical twin and
 * bcast/reduce route hierarchically at any size.  Must agree across
 * ranks (like COLL_ALGO/COLL_QUANT: the schedules exchange different
 * frames). */
enum { HIER_ALLOW = 0, HIER_DENY = 1, HIER_FORCE = 2 };

int hier_mode() {
  static int v = [] {
    const char* e = std::getenv("MPI4JAX_TPU_HIER");
    if (!e) return HIER_ALLOW;
    std::string s(e);
    const size_t b = s.find_first_not_of(" \t\r\n");
    if (b == std::string::npos) return HIER_ALLOW;
    s = s.substr(b, s.find_last_not_of(" \t\r\n") - b + 1);
    if (s == "allow") return HIER_ALLOW;
    if (s == "deny") return HIER_DENY;
    if (s == "force") return HIER_FORCE;
    std::fprintf(stderr,
                 "tpucomm: cannot parse MPI4JAX_TPU_HIER=%s "
                 "(expected allow, deny, or force)\n", e);
    std::exit(2);  // a typo'd gate must not silently change routing
  }();
  return v;
}

/* bcast/reduce route hierarchically above this payload under
 * hier=allow (below it the flat binomial tree's log2(n) hops win on
 * latency); force removes the floor, deny the routing */
constexpr int64_t kHierMinBytes = 64 * 1024;

/* hierarchical schedules need a discovered multi-island topology */
bool hier_eligible(const Comm* c) {
  return c->topo != nullptr && c->topo->n_islands > 1;
}

bool hier_routable(const Comm* c, int64_t nbytes) {
  if (!hier_eligible(c) || hier_mode() == HIER_DENY) return false;
  return hier_mode() == HIER_FORCE || nbytes >= kHierMinBytes;
}

/* quantized wire formats (codec + schedules defined below) */
bool quant_dtype_ok(int dtype);
int64_t quant_packed_bytes(int64_t count);

/* MPI4JAX_TPU_COLL_QUANT: process-wide gate over the quantized wire
 * formats.  allow (default) = table/env/API selection may pick them;
 * deny = quantized picks degrade to their exact counterparts (a safety
 * kill-switch that never changes which frames match, only their
 * contents); force = every quant-eligible allreduce upgrades to the
 * quantized twin of its selected schedule.  Must agree across ranks
 * (like COLL_ALGO: a divergent gate fails fast on frame-size checks). */
enum { QUANT_ALLOW = 0, QUANT_DENY = 1, QUANT_FORCE = 2 };

int quant_mode() {
  static int v = [] {
    const char* e = std::getenv("MPI4JAX_TPU_COLL_QUANT");
    if (!e) return QUANT_ALLOW;
    /* trim surrounding whitespace (shell exports / YAML trailing
     * newlines) so this agrees byte-for-byte with the Python layers'
     * read of the same knob (utils/config.quant_mode) */
    std::string s(e);
    const size_t b = s.find_first_not_of(" \t\r\n");
    if (b == std::string::npos) return QUANT_ALLOW;
    s = s.substr(b, s.find_last_not_of(" \t\r\n") - b + 1);
    if (s == "allow") return QUANT_ALLOW;
    if (s == "deny") return QUANT_DENY;
    if (s == "force") return QUANT_FORCE;
    std::fprintf(stderr,
                 "tpucomm: cannot parse MPI4JAX_TPU_COLL_QUANT=%s "
                 "(expected allow, deny, or force)\n", e);
    std::exit(2);  // a typo'd gate must not silently change numerics
  }();
  return v;
}

/* The algorithm that will serve (op_kind, nbytes, count) on comm `c`.
 * `requested` = per-call force (AUTO -> table -> built-in heuristic).
 * Also applies legality fixups (allgather has no recursive-doubling
 * schedule for non-power-of-two sizes: falls back to ring; quantized
 * codes degrade to their exact counterparts unless the call is a
 * float SUM allreduce and MPI4JAX_TPU_COLL_QUANT permits), so callers
 * log the algorithm that actually runs.  `dtype`/`rop` carry the
 * reduction context for the quantized-eligibility gate; callers
 * without one (allgather, the byte-only probe) pass the defaults. */
int resolve_coll_algo(Comm* c, int op_kind, int64_t nbytes, int64_t count,
                      int requested, int dtype = -1, int rop = -1) {
  if (c->arena && c->size > 1) return TPU_COLL_SHM;
  int algo = requested;
  if (algo == TPU_COLL_AUTO) algo = coll_table_lookup(op_kind, nbytes);
  if (algo == TPU_COLL_AUTO) {
    /* built-in heuristic, identical to the pre-engine behavior */
    if (op_kind == TPU_OPKIND_ALLREDUCE)
      algo = (nbytes >= 64 * 1024 && count >= c->size) ? TPU_COLL_RING
                                                       : TPU_COLL_TREE;
    else
      algo = TPU_COLL_RING;
  }
  /* per-op canonicalization: the alltoall family (qalltoall/halltoall/
   * hqalltoall) exists only for alltoall, and alltoall has only the
   * pairwise exchange outside that family (rd/tree/qring/... have no
   * alltoall schedule).  Map strays to RING — the exact flat exchange —
   * BEFORE the gates, so deny/force act on canonical codes. */
  if (op_kind == TPU_OPKIND_ALLTOALL) {
    if (algo != TPU_COLL_RING && algo != TPU_COLL_QA2A &&
        algo != TPU_COLL_HA2A && algo != TPU_COLL_HQA2A)
      algo = TPU_COLL_RING;
  } else if (algo == TPU_COLL_QA2A || algo == TPU_COLL_HA2A ||
             algo == TPU_COLL_HQA2A) {
    algo = TPU_COLL_RING;
  }
  /* hierarchical eligibility: needs a discovered multi-island topology
   * on this comm.  A hierarchical pick on a flat comm (or under
   * MPI4JAX_TPU_HIER=deny) degrades to its flat twin; =force upgrades
   * every eligible flat pick.  The topology agrees across ranks (every
   * member installed the same map), so the degradation is consistent
   * and the schedules still match.  BEFORE the quant block: the
   * quantized wire format applies to a hierarchical schedule's
   * inter-island LEG (inside hier_allreduce), never to the whole-op
   * code. */
  {
    const bool h_ok = hier_eligible(c);
    if (algo == TPU_COLL_HRING || algo == TPU_COLL_HTREE) {
      if (!h_ok || hier_mode() == HIER_DENY)
        algo = algo == TPU_COLL_HRING ? TPU_COLL_RING : TPU_COLL_TREE;
    } else if (algo == TPU_COLL_HA2A || algo == TPU_COLL_HQA2A) {
      /* the hierarchical alltoall degrades one axis at a time: HQA2A
       * keeps its quantized wire (QA2A) on a flat comm — the quant
       * block below settles the other axis */
      if (!h_ok || hier_mode() == HIER_DENY)
        algo = algo == TPU_COLL_HA2A ? TPU_COLL_RING : TPU_COLL_QA2A;
    } else if (hier_mode() == HIER_FORCE && h_ok &&
               op_kind == TPU_OPKIND_ALLTOALL) {
      /* same non-upgrade rule as qring below: an explicitly quantized
       * flat exchange only gains the hierarchical route when the quant
       * force gate re-quantizes the leader leg anyway */
      if (algo == TPU_COLL_RING) algo = TPU_COLL_HA2A;
    } else if (hier_mode() == HIER_FORCE && h_ok &&
               algo != TPU_COLL_SHM && algo != TPU_COLL_QRING &&
               algo != TPU_COLL_QRD) {
      /* an explicitly selected quantized wire format is NOT upgraded:
       * the hierarchical leader leg only re-quantizes under
       * COLL_QUANT=force, so rewriting qring -> hring here would
       * silently move ~4x the bytes on the slow tier */
      algo = algo == TPU_COLL_RING ? TPU_COLL_HRING : TPU_COLL_HTREE;
    }
  }
  /* quantized eligibility: allreduce, real floating dtype, SUM.  An
   * ineligible (dtype, op) or the deny gate degrades the quantized
   * code to its exact counterpart — dtype agrees across ranks, so the
   * degradation is consistent and the schedules still match.  BEFORE
   * the allgather fixups, so a (nonsensical) quantized table row for
   * allgather degrades and then takes the normal rd/ring legality
   * path. */
  {
    /* alltoall is pure data movement — no reduction op to gate on, the
     * wire format just needs a codec-legal dtype */
    const bool q_ok =
        op_kind == TPU_OPKIND_ALLTOALL
            ? quant_dtype_ok(dtype)
            : op_kind == TPU_OPKIND_ALLREDUCE && quant_dtype_ok(dtype) &&
                  rop == TPU_SUM;
    if (algo == TPU_COLL_QRING || algo == TPU_COLL_QRD) {
      if (!q_ok || quant_mode() == QUANT_DENY)
        algo = algo == TPU_COLL_QRING ? TPU_COLL_RING : TPU_COLL_RD;
    } else if (algo == TPU_COLL_QA2A || algo == TPU_COLL_HQA2A) {
      if (!q_ok || quant_mode() == QUANT_DENY)
        algo = algo == TPU_COLL_QA2A ? TPU_COLL_RING : TPU_COLL_HA2A;
    } else if (quant_mode() == QUANT_FORCE && q_ok &&
               op_kind == TPU_OPKIND_ALLTOALL) {
      if (algo == TPU_COLL_RING) algo = TPU_COLL_QA2A;
      else if (algo == TPU_COLL_HA2A) algo = TPU_COLL_HQA2A;
    } else if (quant_mode() == QUANT_FORCE && q_ok &&
               algo != TPU_COLL_HRING && algo != TPU_COLL_HTREE) {
      algo = algo == TPU_COLL_RING ? TPU_COLL_QRING : TPU_COLL_QRD;
    }
  }
  if (op_kind == TPU_OPKIND_ALLGATHER && algo == TPU_COLL_RD &&
      (c->size & (c->size - 1)) != 0)
    algo = TPU_COLL_RING;
  if (op_kind == TPU_OPKIND_ALLGATHER && algo == TPU_COLL_TREE &&
      c->size > 200)
    /* the gather half addresses ranks serially; keep the root's recv
     * loop bounded on very wide worlds */
    algo = TPU_COLL_RING;
  return algo;
}

/* chunk [i] covers elements [i*per, min((i+1)*per, count)) */
int64_t chunk_lo(int64_t count, int size, int i) {
  int64_t per = (count + size - 1) / size;
  int64_t lo = per * i;
  return lo < count ? lo : count;
}

/* Receive one exact-size collective frame from `source` and fold it
 * into `dst` in cache-sized blocks AS THE BYTES ARRIVE: the payload
 * goes socket -> small hot scratch -> combine, instead of
 * socket -> multi-MB tmp (a full RAM round trip) -> combine.  Wire
 * format identical to recv_msg (one frame, same header checks); only
 * the landing buffer is blocked.  TCP path only — arena comms never
 * reach the ring schedules. */
constexpr int64_t kCombineBlockBytes = 128 * 1024;

int recv_combine_msg(Comm* c, int source, char* dst, std::vector<char>& tmp,
                     int64_t count, int dtype, int op) {
  fault_fire(c, g_job_rank, FP_RECV, "recv");
  if (pending_head(c, source))
    /* staged user messages precede this collective on the channel: the
     * ranks disagree on the schedule (the wire path would read a user
     * frame here and fail the same way) */
    FAIL(c, "message order violation: collective frame expected from rank "
         "%d but user message (tag %d) is pending", source,
         pending_head(c, source)->tag);
  const int64_t esize = dtype_size(dtype);
  const int64_t nbytes = count * esize;
  MsgHeader h{};
  uint64_t seq = 0;
  int ffd = -1;
  int rc;
  for (;;) {
    {
      ObsWaitTimer wt;  // header arrival = wait phase (see recv_msg_status)
      rc = wire_read_hdr(c, source, &h, &seq, &ffd);
    }
    if (rc == 0) break;
    /* heal-at-header only: the header wait is where a transient reset
     * lands in practice.  Once blocks start folding into dst the frame
     * is partially combined and cannot replay — a mid-payload failure
     * below escalates (documented scope: sharp-bits "Self-healing"). */
    if (io_rc_retryable(rc) &&
        link_recover(c, source, ffd, "recv collective header") == 0)
      continue;
    FAIL_IO(c, rc, "recv header from %d", source);
  }
  if (h.tag == kPoisonTag) return poison_fail(c, source, h);
  if (h.comm_id != c->comm_id)
    FAIL(c, "communicator mismatch: rank %d's message is for comm %d, this "
         "is comm %d — ops on sibling communicators must run in a "
         "consistent order on both endpoints", source, h.comm_id,
         c->comm_id);
  if (h.tag != kCollectiveTag)
    FAIL(c, "message order violation: expected tag %d from rank %d, got %d",
         kCollectiveTag, source, h.tag);
  if (h.nbytes != nbytes)
    FAIL(c, "size mismatch from rank %d: expected %lld bytes, got %lld",
         source, (long long)nbytes, (long long)h.nbytes);
  for (int64_t off = 0; off < nbytes; off += kCombineBlockBytes) {
    int64_t nb = std::min(nbytes - off, kCombineBlockBytes);
    rc = read_all_dl(ffd, tmp.data(), nb);
    if (rc) FAIL_IO(c, rc, "recv payload from %d", source);
    if (combine(dst + off, tmp.data(), nb / esize, dtype, op, c)) return 1;
  }
  wire_mark_delivered(c, source, seq);
  return 0;
}

/* Chunked ring: reduce-scatter then allgather, 2*(n-1)/n of the payload
 * on the wire per rank — the bandwidth-optimal schedule for large
 * messages.  Handles count < size via empty chunks (zero-byte frames).
 * The reduce-scatter receive folds blockwise while the frame streams in
 * (recv_combine_msg) — bitwise identical to landing the whole chunk
 * first, since elementwise combine is independent per block. */
int ring_allreduce(Comm* c, void* recvbuf, int64_t count, int dtype,
                   int op) {
  const int size = c->size, rank = c->rank;
  const int64_t esize = dtype_size(dtype);
  char* buf = static_cast<char*>(recvbuf);
  int next = (rank + 1) % size, prev = (rank - 1 + size) % size;
  std::vector<char> tmp(std::min<int64_t>(
      kCombineBlockBytes, ((count + size - 1) / size) * esize));

  /* phase 1: ring reduce-scatter — after size-1 rounds, chunk (rank+1)%size
   * holds the full reduction */
  for (int step = 0; step < size - 1; step++) {
    int sc = (rank - step + size) % size;
    int rc = (rank - step - 1 + size) % size;
    int64_t slo = chunk_lo(count, size, sc), shi = chunk_lo(count, size, sc + 1);
    int64_t rlo = chunk_lo(count, size, rc), rhi = chunk_lo(count, size, rc + 1);
    SendJob job;
    if (async_send(c, &job, next, kCollectiveTag, buf + slo * esize,
                   (shi - slo) * esize))
      return 1;
    int recv_rc = recv_combine_msg(c, prev, buf + rlo * esize, tmp,
                                   rhi - rlo, dtype, op);
    if (wait_send(c, &job) || recv_rc) return 1;
  }
  /* phase 2: ring allgather of the reduced chunks */
  for (int step = 0; step < size - 1; step++) {
    int sc = (rank + 1 - step + size) % size;
    int rc = (rank - step + size) % size;
    int64_t slo = chunk_lo(count, size, sc), shi = chunk_lo(count, size, sc + 1);
    int64_t rlo = chunk_lo(count, size, rc), rhi = chunk_lo(count, size, rc + 1);
    SendJob job;
    if (async_send(c, &job, next, kCollectiveTag, buf + slo * esize,
                   (shi - slo) * esize))
      return 1;
    int recv_rc = recv_msg(c, prev, kCollectiveTag, buf + rlo * esize,
                           (rhi - rlo) * esize);
    if (wait_send(c, &job) || recv_rc) return 1;
  }
  return 0;
}

/* Binomial-tree reduce to rank 0 + tree bcast: 2*log2(n) serial hops —
 * the latency-favoring schedule for small payloads (the pre-engine
 * small-message default). */
int tree_allreduce(Comm* c, void* recvbuf, int64_t count, int dtype,
                   int op) {
  const int64_t nbytes = count * dtype_size(dtype);
  std::vector<char> tmp(nbytes);
  for (int mask = 1; mask < c->size; mask <<= 1) {
    if (c->rank & mask) {
      if (send_msg(c, c->rank - mask, kCollectiveTag, recvbuf, nbytes))
        return 1;
      break;
    }
    if (c->rank + mask < c->size) {
      if (recv_msg(c, c->rank + mask, kCollectiveTag, tmp.data(), nbytes))
        return 1;
      if (combine(recvbuf, tmp.data(), count, dtype, op, c)) return 1;
    }
  }
  return bcast_internal(c, recvbuf, nbytes, 0);
}

/* Recursive doubling: log2(n) rounds of pairwise full-buffer exchange —
 * every rank holds the result with no bcast phase.  Non-power-of-two
 * sizes use the standard fold: the first 2*rem ranks pair up (evens
 * lend their data to odds and sit out the butterfly), the remaining
 * power-of-two group doubles, then the evens get the result back. */
int rd_allreduce(Comm* c, void* recvbuf, int64_t count, int dtype, int op) {
  const int size = c->size, rank = c->rank;
  const int64_t nbytes = count * dtype_size(dtype);
  int pof2 = 1;
  while (pof2 * 2 <= size) pof2 *= 2;
  const int rem = size - pof2;
  std::vector<char> tmp(nbytes);
  int newrank;
  if (rank < 2 * rem) {
    if ((rank & 1) == 0) {
      if (send_msg(c, rank + 1, kCollectiveTag, recvbuf, nbytes)) return 1;
      newrank = -1;  // sits out the butterfly
    } else {
      if (recv_msg(c, rank - 1, kCollectiveTag, tmp.data(), nbytes))
        return 1;
      if (combine(recvbuf, tmp.data(), count, dtype, op, c)) return 1;
      newrank = rank / 2;
    }
  } else {
    newrank = rank - rem;
  }
  if (newrank >= 0) {
    for (int mask = 1; mask < pof2; mask <<= 1) {
      int newpeer = newrank ^ mask;
      int peer = newpeer < rem ? newpeer * 2 + 1 : newpeer + rem;
      SendJob job;
      if (async_send(c, &job, peer, kCollectiveTag, recvbuf, nbytes))
        return 1;
      int rc = recv_msg(c, peer, kCollectiveTag, tmp.data(), nbytes);
      if (wait_send(c, &job) || rc) return 1;
      if (combine(recvbuf, tmp.data(), count, dtype, op, c)) return 1;
    }
  }
  if (rank < 2 * rem) {
    if (rank & 1) {
      if (send_msg(c, rank - 1, kCollectiveTag, recvbuf, nbytes)) return 1;
    } else {
      if (recv_msg(c, rank + 1, kCollectiveTag, recvbuf, nbytes)) return 1;
    }
  }
  return 0;
}

/* Ring allgather: size-1 rounds, each forwarding the block received
 * last round (the pre-engine default). */
int ring_allgather(Comm* c, const void* sendbuf, int64_t nbytes,
                   void* recvbuf) {
  char* out = static_cast<char*>(recvbuf);
  std::memcpy(out + (int64_t)c->rank * nbytes, sendbuf, nbytes);
  int next = (c->rank + 1) % c->size;
  int prev = (c->rank - 1 + c->size) % c->size;
  if (c->size == 1) return 0;
  for (int round = 0; round < c->size - 1; round++) {
    int send_block = (c->rank - round + c->size) % c->size;
    int recv_block = (c->rank - round - 1 + c->size) % c->size;
    SendJob job;
    if (async_send(c, &job, next, kCollectiveTag,
                   out + (int64_t)send_block * nbytes, nbytes))
      return 1;
    int recv_rc = recv_msg(c, prev, kCollectiveTag,
                           out + (int64_t)recv_block * nbytes, nbytes);
    if (wait_send(c, &job) || recv_rc) return 1;
  }
  return 0;
}

/* Gather to rank 0 + binomial bcast of the stacked result: trades the
 * ring's n-1 serial rounds for a serial gather + log2(n) bcast hops —
 * wins at small payloads where per-hop latency dominates. */
int tree_allgather(Comm* c, const void* sendbuf, int64_t nbytes,
                   void* recvbuf) {
  char* out = static_cast<char*>(recvbuf);
  const int root = 0;
  if (c->rank == root) {
    std::memcpy(out + (int64_t)root * nbytes, sendbuf, nbytes);
    for (int r = 0; r < c->size; r++) {
      if (r == root) continue;
      if (recv_msg(c, r, kCollectiveTag, out + (int64_t)r * nbytes, nbytes))
        return 1;
    }
  } else {
    if (send_msg(c, root, kCollectiveTag, sendbuf, nbytes)) return 1;
  }
  return bcast_internal(c, out, (int64_t)c->size * nbytes, root);
}

/* Recursive-doubling allgather (power-of-two sizes only; resolve_coll_algo
 * degrades to ring otherwise): at step k each rank swaps its current
 * 2^k-block group with partner rank^2^k — log2(n) rounds, same total
 * bytes as the ring. */
int rd_allgather(Comm* c, const void* sendbuf, int64_t nbytes,
                 void* recvbuf) {
  char* out = static_cast<char*>(recvbuf);
  std::memcpy(out + (int64_t)c->rank * nbytes, sendbuf, nbytes);
  for (int mask = 1; mask < c->size; mask <<= 1) {
    int peer = c->rank ^ mask;
    int64_t my_off = (int64_t)(c->rank & ~(mask - 1)) * nbytes;
    int64_t peer_off = (int64_t)(peer & ~(mask - 1)) * nbytes;
    int64_t len = (int64_t)mask * nbytes;
    SendJob job;
    if (async_send(c, &job, peer, kCollectiveTag, out + my_off, len))
      return 1;
    int rc = recv_msg(c, peer, kCollectiveTag, out + peer_off, len);
    if (wait_send(c, &job) || rc) return 1;
  }
  return 0;
}

/* ============ quantized wire formats (qring / qrd) ============
 *
 * EQuARX-style in-collective block quantization (arXiv:2506.17615):
 * every collective frame carries int8 codes plus per-block f32 absmax
 * scales instead of full-precision elements — ~4x fewer payload bytes
 * for f32, ~2x for bf16/f16 — and the receive side dequantizes and
 * reduces streaming in f32.  The codec below IS the wire format; it is
 * also exported (tpucomm_quant_pack/unpack) so diag and the Python
 * accuracy harness can round-trip the exact native bits.
 *
 * Determinism contract: quantization is pure per-block f32 arithmetic
 * (absmax, divide, round-to-nearest-even), so identical inputs pack to
 * identical bytes on every rank, and both algorithms are built so that
 * every rank reconstructs bit-identical RESULTS (see each schedule's
 * comment) — a quantized gradient sync cannot make DP replicas drift
 * apart. */

constexpr int64_t kQuantBlock = 256;  // elements per f32 absmax scale

int64_t quant_blocks(int64_t count) {
  return count > 0 ? (count + kQuantBlock - 1) / kQuantBlock : 0;
}

/* packed layout: ceil(count/256) f32 scales, then count int8 codes */
int64_t quant_packed_bytes(int64_t count) {
  return count > 0 ? 4 * quant_blocks(count) + count : 0;
}

bool quant_dtype_ok(int dtype) {
  return dtype == TPU_F16 || dtype == TPU_BF16 || dtype == TPU_F32 ||
         dtype == TPU_F64;
}

/* dtype buffer -> f32 working values (codec and reduction run in f32) */
void quant_load_f32(const void* src, int dtype, int64_t count, float* dst) {
  switch (dtype) {
    case TPU_F32:
      std::memcpy(dst, src, (size_t)count * 4);
      break;
    case TPU_F64: {
      const double* s = static_cast<const double*>(src);
      for (int64_t i = 0; i < count; i++) dst[i] = (float)s[i];
      break;
    }
    case TPU_BF16: {
      const uint16_t* s = static_cast<const uint16_t*>(src);
      for (int64_t i = 0; i < count; i++) dst[i] = bf16_to_f32(s[i]);
      break;
    }
    default: {  // TPU_F16 (quant_dtype_ok gates everything else out)
      const uint16_t* s = static_cast<const uint16_t*>(src);
      for (int64_t i = 0; i < count; i++) dst[i] = f16_to_f32(s[i]);
      break;
    }
  }
}

void quant_store_f32(const float* src, int dtype, int64_t count, void* dst) {
  switch (dtype) {
    case TPU_F32:
      std::memcpy(dst, src, (size_t)count * 4);
      break;
    case TPU_F64: {
      double* d = static_cast<double*>(dst);
      for (int64_t i = 0; i < count; i++) d[i] = (double)src[i];
      break;
    }
    case TPU_BF16: {
      uint16_t* d = static_cast<uint16_t*>(dst);
      for (int64_t i = 0; i < count; i++) d[i] = f32_to_bf16(src[i]);
      break;
    }
    default: {  // TPU_F16
      uint16_t* d = static_cast<uint16_t*>(dst);
      for (int64_t i = 0; i < count; i++) d[i] = f32_to_f16(src[i]);
      break;
    }
  }
}

/* Block kernels.  The AVX2 variants follow the vertical_reduce pattern
 * (target attribute + have_avx2() runtime dispatch) and are BIT-
 * IDENTICAL to the scalar fallbacks: both compute value*(1/scale) in
 * f32, clip to ±127, and round to nearest EVEN (cvtps_epi32 under the
 * default MXCSR mode ≡ the scalar add-2^23-magic-number trick), so a
 * mixed-CPU job cannot diverge on quantized bits.  The pack loop at
 * 16 MiB measures ~9 GB/s with AVX2 vs ~1 GB/s scalar on the CI host —
 * without it the codec, not the wire, would be the bottleneck. */

inline float quant_amax_scalar(const float* x, int64_t n) {
  float amax = 0.0f;
  for (int64_t i = 0; i < n; i++) amax = std::max(amax, std::fabs(x[i]));
  return amax;
}

inline void quant_codes_scalar(const float* x, int64_t n, float inv,
                               int8_t* codes) {
  for (int64_t i = 0; i < n; i++) {
    float v = x[i] * inv;
    v = std::min(127.0f, std::max(-127.0f, v));
    v = (v + 12582912.0f) - 12582912.0f;  // round to nearest even
    codes[i] = (int8_t)(int32_t)v;
  }
}

__attribute__((target("avx2"))) float quant_amax_avx2(const float* x,
                                                      int64_t n) {
  const __m256 mask = _mm256_castsi256_ps(_mm256_set1_epi32(0x7fffffff));
  __m256 am = _mm256_setzero_ps();
  int64_t i = 0;
  for (; i + 8 <= n; i += 8)
    am = _mm256_max_ps(am, _mm256_and_ps(mask, _mm256_loadu_ps(x + i)));
  float tmp[8];
  _mm256_storeu_ps(tmp, am);
  float amax = 0.0f;
  for (int k = 0; k < 8; k++) amax = std::max(amax, tmp[k]);
  for (; i < n; i++) amax = std::max(amax, std::fabs(x[i]));
  return amax;
}

__attribute__((target("avx2"))) void quant_codes_avx2(const float* x,
                                                      int64_t n, float inv,
                                                      int8_t* codes) {
  const __m256 vinv = _mm256_set1_ps(inv);
  const __m256 vlo = _mm256_set1_ps(-127.0f);
  const __m256 vhi = _mm256_set1_ps(127.0f);
  int64_t i = 0;
  for (; i + 8 <= n; i += 8) {
    __m256 v = _mm256_mul_ps(_mm256_loadu_ps(x + i), vinv);
    v = _mm256_min_ps(vhi, _mm256_max_ps(vlo, v));
    __m256i q = _mm256_cvtps_epi32(v);  // rounds to nearest even
    __m128i p16 = _mm_packs_epi32(_mm256_castsi256_si128(q),
                                  _mm256_extracti128_si256(q, 1));
    __m128i p8 = _mm_packs_epi16(p16, p16);
    _mm_storel_epi64(reinterpret_cast<__m128i*>(codes + i), p8);
  }
  if (i < n) quant_codes_scalar(x + i, n - i, inv, codes + i);
}

__attribute__((target("avx2"))) void quant_dq_avx2(const int8_t* codes,
                                                   int64_t n, float scale,
                                                   float* dst, bool add) {
  const __m256 vs = _mm256_set1_ps(scale);
  int64_t i = 0;
  for (; i + 8 <= n; i += 8) {
    __m256i q = _mm256_cvtepi8_epi32(
        _mm_loadl_epi64(reinterpret_cast<const __m128i*>(codes + i)));
    __m256 v = _mm256_mul_ps(vs, _mm256_cvtepi32_ps(q));
    if (add) v = _mm256_add_ps(_mm256_loadu_ps(dst + i), v);
    _mm256_storeu_ps(dst + i, v);
  }
  for (; i < n; i++) {
    const float v = scale * (float)codes[i];
    dst[i] = add ? dst[i] + v : v;
  }
}

__attribute__((target("avx512f,avx512bw,avx512dq"))) float
quant_amax_avx512(const float* x, int64_t n) {
  const __m512 mask = _mm512_castsi512_ps(_mm512_set1_epi32(0x7fffffff));
  __m512 am = _mm512_setzero_ps();
  int64_t i = 0;
  for (; i + 16 <= n; i += 16)
    am = _mm512_max_ps(am, _mm512_and_ps(mask, _mm512_loadu_ps(x + i)));
  float amax = _mm512_reduce_max_ps(am);
  for (; i < n; i++) amax = std::max(amax, std::fabs(x[i]));
  return amax;
}

__attribute__((target("avx512f,avx512bw,avx512dq"))) void
quant_codes_avx512(const float* x, int64_t n, float inv, int8_t* codes) {
  const __m512 vinv = _mm512_set1_ps(inv);
  const __m512 vlo = _mm512_set1_ps(-127.0f);
  const __m512 vhi = _mm512_set1_ps(127.0f);
  int64_t i = 0;
  for (; i + 16 <= n; i += 16) {
    __m512 v = _mm512_mul_ps(_mm512_loadu_ps(x + i), vinv);
    v = _mm512_min_ps(vhi, _mm512_max_ps(vlo, v));
    __m512i q = _mm512_cvtps_epi32(v);  // rounds to nearest even
    /* saturating narrow is exact here: q is pre-clipped to ±127 */
    _mm_storeu_si128(reinterpret_cast<__m128i*>(codes + i),
                     _mm512_cvtsepi32_epi8(q));
  }
  if (i < n) quant_codes_scalar(x + i, n - i, inv, codes + i);
}

__attribute__((target("avx512f,avx512bw,avx512dq"))) void
quant_dq_avx512(const int8_t* codes, int64_t n, float scale, float* dst,
                bool add) {
  const __m512 vs = _mm512_set1_ps(scale);
  int64_t i = 0;
  /* NB: non-temporal stores were tried for the write-only (!add) path
   * and measured SLOWER on the virtualized CI hosts (WC flushes under
   * KVM), besides needing fence discipline around the progress
   * thread — plain stores keep the kernel simple and bit-obvious. */
  for (; i + 16 <= n; i += 16) {
    __m512i q = _mm512_cvtepi8_epi32(
        _mm_loadu_si128(reinterpret_cast<const __m128i*>(codes + i)));
    __m512 v = _mm512_mul_ps(vs, _mm512_cvtepi32_ps(q));
    if (add) v = _mm512_add_ps(_mm512_loadu_ps(dst + i), v);
    _mm512_storeu_ps(dst + i, v);
  }
  for (; i < n; i++) {
    const float v = scale * (float)codes[i];
    dst[i] = add ? dst[i] + v : v;
  }
}

/* 0 = scalar, 1 = avx2, 2 = avx512 — one probe, pack/unpack dispatch */
int quant_isa() {
  static int v = [] {
    if (__builtin_cpu_supports("avx512f") &&
        __builtin_cpu_supports("avx512bw") &&
        __builtin_cpu_supports("avx512dq"))
      return 2;
    return have_avx2() ? 1 : 0;
  }();
  return v;
}

/* pack: per-block absmax scale (absmax/127; 1.0 for an all-zero
 * block), codes = round-to-nearest-even of value/scale clipped ±127 */
void quant_pack_f32(const float* x, int64_t count, char* out) {
  const int64_t nb = quant_blocks(count);
  char* scales = out;
  int8_t* codes = reinterpret_cast<int8_t*>(out + 4 * nb);
  const int isa = quant_isa();
  for (int64_t b = 0; b < nb; b++) {
    const int64_t lo = b * kQuantBlock;
    const int64_t n = std::min(count - lo, kQuantBlock);
    const float amax = isa == 2   ? quant_amax_avx512(x + lo, n)
                       : isa == 1 ? quant_amax_avx2(x + lo, n)
                                  : quant_amax_scalar(x + lo, n);
    const float scale = amax > 0.0f ? amax / 127.0f : 1.0f;
    const float inv = 1.0f / scale;
    std::memcpy(scales + 4 * b, &scale, 4);
    if (isa == 2)
      quant_codes_avx512(x + lo, n, inv, codes + lo);
    else if (isa == 1)
      quant_codes_avx2(x + lo, n, inv, codes + lo);
    else
      quant_codes_scalar(x + lo, n, inv, codes + lo);
  }
}

/* dst = scale * code (exact in f32: |code| <= 127 is exact, scale is a
 * stored f32 — every rank dequantizing the same bytes gets the same
 * bits).  `scales`/`codes` may point into one packed buffer (the
 * contiguous wire layout) or at separate staging runs (the streaming
 * receive path): `count` elements starting at dst, whole leading
 * blocks. */
void quant_dq_run(const char* scales, const int8_t* codes, int64_t count,
                  float* dst, bool add) {
  const int64_t nb = quant_blocks(count);
  const int isa = quant_isa();
  for (int64_t b = 0; b < nb; b++) {
    const int64_t lo = b * kQuantBlock;
    const int64_t n = std::min(count - lo, kQuantBlock);
    float scale;
    std::memcpy(&scale, scales + 4 * b, 4);
    if (isa == 2) {
      quant_dq_avx512(codes + lo, n, scale, dst + lo, add);
    } else if (isa == 1) {
      quant_dq_avx2(codes + lo, n, scale, dst + lo, add);
    } else {
      for (int64_t i = 0; i < n; i++) {
        const float v = scale * (float)codes[lo + i];
        dst[lo + i] = add ? dst[lo + i] + v : v;
      }
    }
  }
}

void quant_unpack_f32(const char* in, int64_t count, float* dst) {
  quant_dq_run(in,
               reinterpret_cast<const int8_t*>(in + 4 * quant_blocks(count)),
               count, dst, false);
}

/* Reusable per-thread scratch for the quantized schedules: fresh
 * multi-MB allocations per call cost first-touch page faults that are
 * pure CPU on the loopback critical path (the same reasoning as the
 * bridge's reusable output buffers).  One op executes at a time per
 * thread, so fixed slots cannot alias; a slot grows to the largest
 * payload seen and stays.  Slots: 0 = send packs, 1 = own-chunk pack,
 * 2 = frame scales, 3 = codes run, 4 = received contributions. */
std::vector<char>& quant_tls_buf(int slot, int64_t n) {
  static thread_local std::vector<char> bufs[5];
  auto& b = bufs[slot];
  if ((int64_t)b.size() < n) b.resize((size_t)std::max<int64_t>(n, 1));
  return b;
}

/* Fold the peers' packed contributions into the chunk, quantize the
 * reduced chunk, and dequantize the packed bytes back into the working
 * buffer — ONE L1-blocked pass per 256-element block instead of three
 * whole-chunk passes (fold, pack, unpack).  The per-element arithmetic
 * sequence is exactly the sequential version's, so the packed bytes
 * and the final values are bit-identical to quant_dq_multi_add
 * followed by quant_pack_f32 + quant_unpack_f32. */
void quant_fold_pack(const char* const* packs, int nsrc, int64_t count,
                     float* acc, char* out) {
  const int64_t nb = quant_blocks(count);
  char* scales_out = out;
  int8_t* codes_out = reinterpret_cast<int8_t*>(out + 4 * nb);
  const int isa = quant_isa();
  for (int64_t b = 0; b < nb; b++) {
    const int64_t lo = b * kQuantBlock;
    const int64_t n = std::min(count - lo, kQuantBlock);
    for (int k = 0; k < nsrc; k++) {
      const char* in = packs[k];
      const int8_t* codes =
          reinterpret_cast<const int8_t*>(in + 4 * nb) + lo;
      float scale;
      std::memcpy(&scale, in + 4 * b, 4);
      if (isa == 2) {
        quant_dq_avx512(codes, n, scale, acc + lo, true);
      } else if (isa == 1) {
        quant_dq_avx2(codes, n, scale, acc + lo, true);
      } else {
        for (int64_t i = 0; i < n; i++)
          acc[lo + i] += scale * (float)codes[i];
      }
    }
    const float amax = isa == 2   ? quant_amax_avx512(acc + lo, n)
                       : isa == 1 ? quant_amax_avx2(acc + lo, n)
                                  : quant_amax_scalar(acc + lo, n);
    const float scale = amax > 0.0f ? amax / 127.0f : 1.0f;
    const float inv = 1.0f / scale;
    std::memcpy(scales_out + 4 * b, &scale, 4);
    if (isa == 2)
      quant_codes_avx512(acc + lo, n, inv, codes_out + lo);
    else if (isa == 1)
      quant_codes_avx2(acc + lo, n, inv, codes_out + lo);
    else
      quant_codes_scalar(acc + lo, n, inv, codes_out + lo);
    if (isa == 2) {
      quant_dq_avx512(codes_out + lo, n, scale, acc + lo, false);
    } else if (isa == 1) {
      quant_dq_avx2(codes_out + lo, n, scale, acc + lo, false);
    } else {
      for (int64_t i = 0; i < n; i++)
        acc[lo + i] = scale * (float)codes_out[lo + i];
    }
  }
}

/* Receive one packed-codec collective frame from `source` and
 * dequantize it into `dst` (accumulating when `add`) AS THE BYTES
 * ARRIVE: the scales land in one small read, then the codes stream
 * through a cache-sized scratch run — the packed payload never
 * occupies a full-size intermediate buffer (recv_combine_msg's
 * streaming-fold pattern, specialized to the quantized wire).  Frame
 * checks are identical to recv_msg (one frame, same header
 * diagnostics).  TCP path only — arena comms never reach the
 * quantized schedules. */
int recv_quant_msg(Comm* c, int source, int64_t count, float* dst,
                   bool add) {
  fault_fire(c, g_job_rank, FP_RECV, "recv");
  if (pending_head(c, source))
    FAIL(c, "message order violation: collective frame expected from rank "
         "%d but user message (tag %d) is pending", source,
         pending_head(c, source)->tag);
  const int64_t nbytes = quant_packed_bytes(count);
  MsgHeader h{};
  uint64_t seq = 0;
  int ffd = -1;
  int rc;
  /* Heal-at-header only (same scope as recv_combine_msg): once codes
   * start folding into dst the frame is partially dequantized and
   * cannot replay — a mid-payload failure escalates. */
  for (;;) {
    {
      ObsWaitTimer wt;  // header arrival = wait phase (see recv_msg_status)
      rc = wire_read_hdr(c, source, &h, &seq, &ffd);
    }
    if (rc && io_rc_retryable(rc) &&
        link_recover(c, source, ffd, "recv collective header") == 0)
      continue;
    break;
  }
  if (ffd < 0) ffd = c->socks[source];
  if (rc) FAIL_IO(c, rc, "recv header from %d", source);
  if (h.tag == kPoisonTag) return poison_fail(c, source, h);
  if (h.comm_id != c->comm_id)
    FAIL(c, "communicator mismatch: rank %d's message is for comm %d, this "
         "is comm %d — ops on sibling communicators must run in a "
         "consistent order on both endpoints", source, h.comm_id,
         c->comm_id);
  if (h.tag != kCollectiveTag)
    FAIL(c, "message order violation: expected tag %d from rank %d, got %d",
         kCollectiveTag, source, h.tag);
  if (h.nbytes != nbytes)
    FAIL(c, "size mismatch from rank %d: expected %lld bytes, got %lld",
         source, (long long)nbytes, (long long)h.nbytes);
  if (count <= 0) {
    wire_mark_delivered(c, source, seq);
    return 0;
  }
  const int64_t nb = quant_blocks(count);
  std::vector<char>& scales = quant_tls_buf(2, 4 * nb);
  rc = read_all_dl(ffd, scales.data(), 4 * nb);
  if (rc) FAIL_IO(c, rc, "recv payload from %d", source);
  /* codes in runs of whole blocks (kCombineBlockBytes is a multiple of
   * kQuantBlock, so every run starts on a block boundary) */
  static_assert(kCombineBlockBytes % kQuantBlock == 0,
                "codes runs must stay block-aligned");
  std::vector<char>& run =
      quant_tls_buf(3, std::min<int64_t>(count, kCombineBlockBytes));
  for (int64_t e0 = 0; e0 < count; e0 += kCombineBlockBytes) {
    const int64_t e1 = std::min(count, e0 + kCombineBlockBytes);
    rc = read_all_dl(ffd, run.data(), e1 - e0);
    if (rc) FAIL_IO(c, rc, "recv payload from %d", source);
    quant_dq_run(scales.data() + 4 * (e0 / kQuantBlock),
                 reinterpret_cast<const int8_t*>(run.data()), e1 - e0,
                 dst + e0, add);
  }
  wire_mark_delivered(c, source, seq);
  return 0;
}

/* Quantized ring-family allreduce (the EQuARX decomposition): a DIRECT
 * pairwise quantized reduce-scatter — round r exchanges packed chunks
 * with ranks ±r, so each rank's inputs are quantized exactly ONCE —
 * followed by the ring allgather of the once-quantized reduced chunks.
 * Same total wire bytes as the exact ring (2*(n-1)/n of the payload,
 * at ~1/4 the bytes for f32), but only TWO quantization steps touch
 * any element (input + reduced chunk) instead of one per hop: less
 * codec CPU on the critical path AND a tighter error bound.  Each
 * rank's own contribution to its chunk stays full-precision; the
 * allgather forwards packed bytes verbatim and the owner dequantizes
 * its own packed chunk too, so every rank reconstructs bit-identical
 * results.  SUM only (resolve_coll_algo gates). */
int qring_allreduce(Comm* c, void* recvbuf, int64_t count, int dtype,
                    int op) {
  (void)op;  // gated to TPU_SUM before dispatch
  const int size = c->size, rank = c->rank;
  /* f32 payloads run IN PLACE on the caller's buffer — a 16 MiB call
   * must not pay a 16 MiB zero-fill + two 16 MiB copies of staging
   * (measured: the staging traffic alone cost more than the wire
   * saving on a loopback host).  Other dtypes stage through an
   * uninitialized f32 scratch. */
  float* acc;
  std::unique_ptr<float[]> staged;
  if (dtype == TPU_F32) {
    acc = static_cast<float*>(recvbuf);
  } else {
    staged.reset(new float[(size_t)count]);
    quant_load_f32(recvbuf, dtype, count, staged.get());
    acc = staged.get();
  }
  const int64_t per = chunk_lo(count, size, 1) - chunk_lo(count, size, 0);
  const int64_t ppc = quant_packed_bytes(per);  // per-chunk pack ceiling
  const int64_t mlo = chunk_lo(count, size, rank);
  const int64_t mhi = chunk_lo(count, size, rank + 1);
  /* phase 1: direct quantized reduce-scatter.  Pack EVERY destination
   * chunk up front (dest chunks are never accumulated into, so they
   * still hold the original values — each input element is quantized
   * exactly once) and post all sends before the first receive: the
   * writer thread streams them while this thread drains incoming
   * contributions, instead of a per-round pack -> wire -> fold convoy.
   * Send k goes to rank+k and is the k-th frame receiver rank+k reads
   * from this channel, so every posted frame is at most one deep in a
   * socket buffer — deadlock-free for any buffer size. */
  std::vector<char>& spacks = quant_tls_buf(0, ppc * size);
  std::vector<SendJob> jobs((size_t)size);
  for (int round = 1; round < size; round++) {
    const int dest = (rank + round) % size;
    const int64_t dlo = chunk_lo(count, size, dest);
    const int64_t dhi = chunk_lo(count, size, dest + 1);
    char* p = spacks.data() + (int64_t)dest * ppc;
    quant_pack_f32(acc + dlo, dhi - dlo, p);
    if (async_send(c, &jobs[dest], dest, kCollectiveTag, p,
                   quant_packed_bytes(dhi - dlo))) {
      for (int r2 = 1; r2 < round; r2++)
        wait_send(c, &jobs[(rank + r2) % size]);
      return 1;
    }
  }
  int rc = 0;
  /* land every peer's contribution (one frame per channel, reusable
   * scratch), then fold them in ONE L1-blocked pass over my chunk —
   * the fixed arrival order rank-1, rank-2, ... is preserved per
   * element by the fused fold, so the f32 accumulation is
   * deterministic and bit-identical to sequential folding */
  const int64_t mpb = quant_packed_bytes(mhi - mlo);
  std::vector<char>& contrib =
      quant_tls_buf(4, mpb * std::max(size - 1, 1));
  std::vector<const char*> cptrs((size_t)std::max(size - 1, 1));
  for (int round = 1; round < size && !rc; round++) {
    const int src = (rank - round + size) % size;
    char* slot = contrib.data() + (int64_t)(round - 1) * mpb;
    cptrs[(size_t)(round - 1)] = slot;
    rc = recv_msg(c, src, kCollectiveTag, slot, mpb);
  }
  std::vector<char>& own = quant_tls_buf(1, quant_packed_bytes(mhi - mlo));
  if (!rc && size > 1)
    /* fold + quantize + owner-requantize in one cache-blocked pass:
     * `own` then holds the once-quantized reduced chunk phase 2 ships */
    quant_fold_pack(cptrs.data(), size - 1, mhi - mlo, acc + mlo,
                    own.data());
  /* phase-1 sends keep draining on the writer thread while phase 2
   * packs and posts — both sets are waited together at the end */
  if (rc) {
    for (int round = 1; round < size; round++)
      wait_send(c, &jobs[(rank + round) % size]);
    return 1;
  }
  /* phase 2: direct allgather of the once-quantized reduced chunks —
   * pack the own chunk ONCE, dequantize the same bytes back (owner and
   * receivers hold identical bits), stream the identical frame to
   * every peer off the writer thread, then drain the peers' chunks.
   * Wire bytes per rank are the same as the ring pipeline
   * ((n-1)/n of the packed payload each way) without its step-by-step
   * serialization; per-channel depth stays one frame, so this is
   * deadlock-free for any socket buffer size. */
  {
    std::vector<SendJob> jobs2((size_t)size);
    bool posted_fail = false;
    for (int round = 1; round < size && !posted_fail; round++) {
      const int dest = (rank + round) % size;
      posted_fail = async_send(c, &jobs2[dest], dest, kCollectiveTag,
                               own.data(),
                               quant_packed_bytes(mhi - mlo)) != 0;
      if (posted_fail) rc = 1;
    }
    for (int round = 1; round < size && !rc; round++) {
      const int src = (rank - round + size) % size;
      const int64_t slo = chunk_lo(count, size, src);
      const int64_t shi = chunk_lo(count, size, src + 1);
      rc = recv_quant_msg(c, src, shi - slo, acc + slo, false);
    }
    /* both phases' sends reference spacks/own until here */
    for (int round = 1; round < size; round++) {
      rc |= wait_send(c, &jobs[(rank + round) % size]);
      if (jobs2[(rank + round) % size].fd >= 0 ||
          jobs2[(rank + round) % size].done)
        rc |= wait_send(c, &jobs2[(rank + round) % size]);
    }
    if (rc) return 1;
  }
  if (dtype != TPU_F32) quant_store_f32(acc, dtype, count, recvbuf);
  return 0;
}

/* Quantized recursive doubling: log2(n) pairwise exchanges of the
 * whole packed buffer.  Rank consistency: each side combines
 * dequant(own packed) + dequant(peer packed) — the pair exchanges the
 * same two byte strings and f32 addition is commutative, so merged
 * groups hold identical bits after every round and all ranks finish
 * identical.  Non-power-of-two fold matches rd_allreduce (the odd
 * member of each leading pair also requantizes the final result it
 * returns, keeping the sidelined even member bit-identical). */
int qrd_allreduce(Comm* c, void* recvbuf, int64_t count, int dtype, int op) {
  (void)op;  // gated to TPU_SUM before dispatch
  const int size = c->size, rank = c->rank;
  /* in-place for f32, staged otherwise — see qring_allreduce */
  float* acc;
  std::unique_ptr<float[]> staged;
  if (dtype == TPU_F32) {
    acc = static_cast<float*>(recvbuf);
  } else {
    staged.reset(new float[(size_t)count]);
    quant_load_f32(recvbuf, dtype, count, staged.get());
    acc = staged.get();
  }
  const int64_t pb = quant_packed_bytes(count);
  std::vector<char>& self = quant_tls_buf(0, pb);
  int pof2 = 1;
  while (pof2 * 2 <= size) pof2 *= 2;
  const int rem = size - pof2;
  int newrank;
  if (rank < 2 * rem) {
    quant_pack_f32(acc, count, self.data());
    if ((rank & 1) == 0) {
      if (send_msg(c, rank + 1, kCollectiveTag, self.data(), pb)) return 1;
      newrank = -1;  // sits out the butterfly
    } else {
      quant_unpack_f32(self.data(), count, acc);
      if (recv_quant_msg(c, rank - 1, count, acc, true)) return 1;
      newrank = rank / 2;
    }
  } else {
    newrank = rank - rem;
  }
  if (newrank >= 0) {
    for (int mask = 1; mask < pof2; mask <<= 1) {
      int newpeer = newrank ^ mask;
      int peer = newpeer < rem ? newpeer * 2 + 1 : newpeer + rem;
      quant_pack_f32(acc, count, self.data());
      SendJob job;
      if (async_send(c, &job, peer, kCollectiveTag, self.data(), pb))
        return 1;
      /* requantize the local half while the peer's frame is in
       * flight, then dequantize-and-add the arriving bytes streaming */
      quant_unpack_f32(self.data(), count, acc);
      int rc = recv_quant_msg(c, peer, count, acc, true);
      if (wait_send(c, &job) || rc) return 1;
    }
  }
  if (rem > 0) {
    /* non-power-of-two return phase: the sidelined evens receive the
     * result QUANTIZED, so every other rank must hold the same
     * quantize-dequantize image of it — the odd fold members pack the
     * bytes they send anyway, and the out-of-fold ranks requantize
     * locally (the butterfly left all participants bit-identical, so
     * everyone packs the same bytes and lands on the same result). */
    if (rank < 2 * rem && (rank & 1) == 0) {
      if (recv_quant_msg(c, rank + 1, count, acc, false)) return 1;
    } else {
      quant_pack_f32(acc, count, self.data());
      if (rank < 2 * rem &&
          send_msg(c, rank - 1, kCollectiveTag, self.data(), pb))
        return 1;
      quant_unpack_f32(self.data(), count, acc);
    }
  }
  if (dtype != TPU_F32) quant_store_f32(acc, dtype, count, recvbuf);
  return 0;
}

/* ============ alltoall schedules ============ */

/* The exact pairwise exchange (the historic tpucomm_alltoall body):
 * round r trades chunks with ranks ±r, one in-flight send overlapping
 * the matching receive.  Shared by the flat dispatch path and the
 * intra-island leg of the hierarchical alltoall. */
int flat_alltoall(Comm* c, const void* sendbuf, void* recvbuf,
                  int64_t chunk) {
  const char* in = static_cast<const char*>(sendbuf);
  char* out = static_cast<char*>(recvbuf);
  std::memcpy(out + (int64_t)c->rank * chunk,
              in + (int64_t)c->rank * chunk, chunk);
  for (int round = 1; round < c->size; round++) {
    int dest = (c->rank + round) % c->size;
    int src = (c->rank - round + c->size) % c->size;
    SendJob job;
    if (async_send(c, &job, dest, kCollectiveTag,
                   in + (int64_t)dest * chunk, chunk))
      return 1;
    int recv_rc = recv_msg(c, src, kCollectiveTag,
                           out + (int64_t)src * chunk, chunk);
    if (wait_send(c, &job) || recv_rc) return 1;
  }
  return 0;
}

/* Quantized pairwise alltoall (TPU_COLL_QA2A): the same round schedule
 * with every off-rank chunk on the int8+scales codec wire —
 * quant_packed_bytes(count) per chunk instead of count*esize (~4x
 * fewer payload bytes for f32, ~2x for bf16/f16).  Every outgoing
 * chunk is packed up-front (one codec pass per chunk; the own-rank
 * chunk never crosses the wire and is copied EXACT), then the rounds
 * move only packed frames.  Rank-consistent by construction: each
 * destination dequantizes the bytes its source packed — there is no
 * cross-rank reduction to disagree on.  resolve_coll_algo gates
 * dtypes (F16/BF16/F32/F64). */
int q_alltoall(Comm* c, const void* sendbuf, void* recvbuf, int64_t count,
               int dtype) {
  const int size = c->size, rank = c->rank;
  const int64_t chunk = count * dtype_size(dtype);
  const int64_t ppc = quant_packed_bytes(count);
  const char* in = static_cast<const char*>(sendbuf);
  char* out = static_cast<char*>(recvbuf);
  std::vector<char>& spacks = quant_tls_buf(0, ppc * size);
  if (dtype == TPU_F32) {
    for (int d = 0; d < size; d++) {
      if (d == rank) continue;
      quant_pack_f32(reinterpret_cast<const float*>(in + d * chunk), count,
                     spacks.data() + d * ppc);
    }
  } else {
    std::vector<char>& staged = quant_tls_buf(1, 4 * count);
    float* st = reinterpret_cast<float*>(staged.data());
    for (int d = 0; d < size; d++) {
      if (d == rank) continue;
      quant_load_f32(in + d * chunk, dtype, count, st);
      quant_pack_f32(st, count, spacks.data() + d * ppc);
    }
  }
  std::memcpy(out + (int64_t)rank * chunk, in + (int64_t)rank * chunk,
              chunk);
  for (int round = 1; round < size; round++) {
    int dest = (rank + round) % size;
    int src = (rank - round + size) % size;
    SendJob job;
    if (async_send(c, &job, dest, kCollectiveTag, spacks.data() + dest * ppc,
                   ppc))
      return 1;
    int rc;
    if (dtype == TPU_F32) {
      rc = recv_quant_msg(c, src, count,
                          reinterpret_cast<float*>(out + src * chunk),
                          false);
    } else {
      std::vector<char>& staged = quant_tls_buf(1, 4 * count);
      float* st = reinterpret_cast<float*>(staged.data());
      rc = recv_quant_msg(c, src, count, st, false);
      if (!rc) quant_store_f32(st, dtype, count, out + src * chunk);
    }
    if (wait_send(c, &job) || rc) return 1;
  }
  return 0;
}

/* ============ hierarchical (topology-aware) schedules ============
 *
 * hring / htree compose the flat kernels above over the sub-groups a
 * discovered topology provides (tpucomm_set_topology): an intra-island
 * reduce to the island leader (the shm arena when the island shares a
 * host, a serial member-order reduce over TCP otherwise), a
 * leader-tier allreduce across the slow inter-island links (ring for
 * hring, recursive doubling for htree; upgraded to the qring/qrd
 * quantized twin on that leg only under MPI4JAX_TPU_COLL_QUANT=force),
 * and an intra-island bcast of the result.  At np8 split 2x4 the flat
 * ring crosses the inter-host boundary on every hop; here only the
 * leader leg does — 2*(L-1)/L of the payload per LEADER instead of
 * 2*(n-1)/n per RANK on the slow tier.
 *
 * Determinism: both intra reduce paths combine in island member order
 * (the serial TCP reduce mirrors vertical_reduce's source order), so
 * shm-on and shm-off runs produce identical bits and ONE numpy
 * schedule simulator (topo.simulate_hring_sum) models both.  Every
 * rank of an island receives the leader's bytes verbatim in phase 3,
 * so ranks are always bit-consistent.
 *
 * Every leg additionally records one observability event labeled with
 * its transport tier (TPU_TIER_INTRA / TPU_TIER_INTER) inside the
 * whole-op record, so obs.stats() splits intra- from inter-island
 * bytes. */

/* Serial reduce to sub-comm rank 0 in member order: root starts from
 * its own buffer and folds rank 1, 2, ... sequentially — the same
 * association as the shm arena's vertical_reduce, which is what makes
 * the two intra paths bit-identical.  Islands are host-sized (a few
 * ranks), so the serial fan-in is not the bottleneck leg. */
int serial_reduce0(Comm* c, void* buf, int64_t count, int dtype, int op) {
  const int64_t nbytes = count * dtype_size(dtype);
  if (c->rank == 0) {
    std::vector<char> tmp((size_t)std::min<int64_t>(nbytes,
                                                    kCombineBlockBytes));
    for (int r = 1; r < c->size; r++)
      if (recv_combine_msg(c, r, static_cast<char*>(buf), tmp, count,
                           dtype, op))
        return 1;
    return 0;
  }
  return send_msg(c, 0, kCollectiveTag, buf, nbytes);
}

/* The leader-tier leg of a hierarchical allreduce: `leg` is ring or
 * rd, upgraded to its quantized twin when the force gate and the
 * (dtype, op) eligibility allow.  Returns the algorithm that ran via
 * *ran (for tracing). */
int leader_allreduce_leg(Comm* lead, void* buf, int64_t count, int dtype,
                         int op, int leg, int* ran) {
  const bool q_ok = quant_dtype_ok(dtype) && op == TPU_SUM;
  if (quant_mode() == QUANT_FORCE && q_ok)
    leg = leg == TPU_COLL_RING ? TPU_COLL_QRING : TPU_COLL_QRD;
  *ran = leg;
  switch (leg) {
    case TPU_COLL_QRING: return qring_allreduce(lead, buf, count, dtype, op);
    case TPU_COLL_QRD: return qrd_allreduce(lead, buf, count, dtype, op);
    case TPU_COLL_RD: return rd_allreduce(lead, buf, count, dtype, op);
    default: return ring_allreduce(lead, buf, count, dtype, op);
  }
}

int intra_bcast(Comm* intra, void* buf, int64_t nbytes, int root) {
  if (intra->arena) return shm_bcast(intra, buf, nbytes, root);
  return bcast_internal(intra, buf, nbytes, root);
}

/* Ring-aware point-to-point send for the hierarchical hops: a comm
 * with shm p2p rings delivers user messages through them (recv_msg
 * waits on the ring), so a bare send_msg_tcp would never match —
 * mirror the engine's SEND routing. */
int p2p_send(Comm* c, int dest, int tag, const void* buf, int64_t nbytes) {
  if (ring_p2p_on(c) && dest != c->rank && dest >= 0 && dest < c->size) {
    bool inlined = false;
    if (shm_try_send(c, dest, tag, buf, nbytes, &inlined)) return 1;
    if (inlined) return 0;
    return send_msg_tcp(c, dest, tag, buf, nbytes);
  }
  return send_msg(c, dest, tag, buf, nbytes);
}

/* Hierarchical allreduce (TPU_COLL_HRING / HTREE): recvbuf already
 * holds this rank's contribution (the dispatch site memcpy'd sendbuf
 * in, like every flat algorithm). */
int hier_allreduce(Comm* c, void* recvbuf, int64_t count, int dtype,
                   int op, int leg_algo) {
  TopoInfo* t = c->topo;
  Comm* intra = t->intra;
  Comm* lead = t->leader;
  const int64_t nbytes = count * dtype_size(dtype);
  /* phase 1: intra-island reduce to the island leader (intra rank 0 —
   * split keyed on rank, leader = lowest member) */
  if (intra && intra->size > 1) {
    ObsScope obs(TPU_OBS_REDUCE, t->my_leader, 0, nbytes,
                 intra->arena ? TPU_COLL_SHM : TPU_COLL_TREE);
    obs.set_tier(TPU_TIER_INTRA);
    int rc = intra->arena
                 ? shm_allreduce_like(intra, recvbuf, recvbuf, count,
                                      dtype, op, 0, false)
                 : serial_reduce0(intra, recvbuf, count, dtype, op);
    if (rc) return 1;
  }
  /* phase 2: leaders allreduce the island sums over the slow tier */
  if (lead && lead->size > 1) {
    int ran = leg_algo;
    ObsScope obs(TPU_OBS_ALLREDUCE, -1, 0, nbytes, leg_algo);
    obs.set_tier(TPU_TIER_INTER);
    int rc = leader_allreduce_leg(lead, recvbuf, count, dtype, op,
                                  leg_algo, &ran);
    obs.set_algo(ran);
    if (ran == TPU_COLL_QRING || ran == TPU_COLL_QRD)
      obs.set_wire(quant_packed_bytes(count));
    if (rc) return 1;
  }
  /* phase 3: the leader broadcasts the result within its island */
  if (intra && intra->size > 1) {
    ObsScope obs(TPU_OBS_BCAST, t->my_leader, 0, nbytes,
                 intra->arena ? TPU_COLL_SHM : TPU_COLL_TREE);
    obs.set_tier(TPU_TIER_INTRA);
    if (intra_bcast(intra, recvbuf, nbytes, 0)) return 1;
  }
  return 0;
}

/* Hierarchical allgather: intra gather to the leader (member order),
 * leader-tier ring allgatherv of the variable-size island blocks
 * (uneven islands are first-class: block sizes come from the member
 * map), intra bcast of the assembled payload, then a local scatter
 * from island-block order into world-rank order. */
int hier_allgather(Comm* c, const void* sendbuf, int64_t nbytes,
                   void* recvbuf) {
  TopoInfo* t = c->topo;
  Comm* intra = t->intra;
  Comm* lead = t->leader;
  const int L = t->n_islands;
  char* out = static_cast<char*>(recvbuf);
  /* island-block staging: island i's members are contiguous at ioff[i] */
  std::vector<int64_t> ioff((size_t)L + 1, 0);
  for (int i = 0; i < L; i++)
    ioff[(size_t)i + 1] =
        ioff[(size_t)i] + (int64_t)t->members[(size_t)i].size() * nbytes;
  std::vector<char> stage((size_t)ioff[(size_t)L]);
  char* myblock = stage.data() + ioff[(size_t)t->my_island];
  /* phase 1: intra gather to the leader, member order */
  if (intra && intra->size > 1) {
    ObsScope obs(TPU_OBS_GATHER, t->my_leader, 0, nbytes,
                 intra->arena ? TPU_COLL_SHM : TPU_COLL_TREE);
    obs.set_tier(TPU_TIER_INTRA);
    if (intra->arena) {
      if (shm_allgather(intra, sendbuf, nbytes, myblock, 0, false))
        return 1;
    } else if (intra->rank == 0) {
      std::memcpy(myblock, sendbuf, (size_t)nbytes);
      for (int r = 1; r < intra->size; r++)
        if (recv_msg(intra, r, kCollectiveTag,
                     myblock + (int64_t)r * nbytes, nbytes))
          return 1;
    } else {
      if (send_msg(intra, 0, kCollectiveTag, sendbuf, nbytes)) return 1;
    }
  } else {
    std::memcpy(myblock, sendbuf, (size_t)nbytes);
  }
  /* phase 2: leader ring allgatherv of the island blocks (the ring
   * allgather schedule with per-island block sizes) */
  if (lead && lead->size > 1) {
    ObsScope obs(TPU_OBS_ALLGATHER, -1, 0,
                 ioff[(size_t)L] - (ioff[(size_t)t->my_island + 1] -
                                    ioff[(size_t)t->my_island]),
                 TPU_COLL_RING);
    obs.set_tier(TPU_TIER_INTER);
    const int lr = lead->rank;  // == island id (leaders sorted by rank)
    const int next = (lr + 1) % L, prev = (lr - 1 + L) % L;
    for (int round = 0; round < L - 1; round++) {
      int sb = (lr - round + L) % L;
      int rb = (lr - round - 1 + L) % L;
      SendJob job;
      if (async_send(lead, &job, next, kCollectiveTag,
                     stage.data() + ioff[(size_t)sb],
                     ioff[(size_t)sb + 1] - ioff[(size_t)sb]))
        return 1;
      int rc = recv_msg(lead, prev, kCollectiveTag,
                        stage.data() + ioff[(size_t)rb],
                        ioff[(size_t)rb + 1] - ioff[(size_t)rb]);
      if (wait_send(lead, &job) || rc) return 1;
    }
  }
  /* phase 3: the leader broadcasts the whole assembled payload */
  if (intra && intra->size > 1) {
    ObsScope obs(TPU_OBS_BCAST, t->my_leader, 0, ioff[(size_t)L],
                 intra->arena ? TPU_COLL_SHM : TPU_COLL_TREE);
    obs.set_tier(TPU_TIER_INTRA);
    if (intra_bcast(intra, stage.data(), ioff[(size_t)L], 0)) return 1;
  }
  /* island-block order -> world-rank order (islands need not be
   * contiguous rank ranges: FAKE_HOSTS partitions are arbitrary) */
  for (int i = 0; i < L; i++)
    for (size_t m = 0; m < t->members[(size_t)i].size(); m++)
      std::memcpy(out + (int64_t)t->members[(size_t)i][m] * nbytes,
                  stage.data() + ioff[(size_t)i] + (int64_t)m * nbytes,
                  (size_t)nbytes);
  return 0;
}

/* Hierarchical alltoall (TPU_COLL_HA2A / HQA2A) — hier_allgather's
 * uneven-island block machinery generalized to the all-pairs exchange:
 *
 *   A. intra-island alltoall of the local chunks (shm arena when the
 *      island shares a host, the pairwise exchange otherwise);
 *   B. intra gather of every member's CROSS-island chunks to the
 *      leader (member order);
 *   C. leader-tier pairwise exchange of the cross-island blocks —
 *      block li->k carries p_li*p_k chunks laid out src-member-major,
 *      variable-size per island pair (uneven islands are first-class);
 *      under `quant_leg` each block rides the int8+scales codec wire
 *      as ONE packed frame (256-element codec blocks span chunk
 *      boundaries inside the frame — the numpy simulator replays the
 *      exact concatenation);
 *   D. intra scatter of the received blocks to their destination
 *      members, then a local reorder into world-rank positions.
 *
 * Only phase C touches the slow tier: (n-p_i)*p_i chunks per LEADER
 * instead of (n-1) chunks per RANK crossing islands.  The exact
 * variant is a pure permutation — output bit-identical to the flat
 * pairwise exchange; quant_leg quantizes exactly the chunks that
 * cross islands (intra chunks stay exact).  Every leg records one obs
 * event labeled with its transport tier inside the whole-op record,
 * like the allreduce twins. */
int h_alltoall(Comm* c, const void* sendbuf, void* recvbuf, int64_t chunk,
               int64_t count, int dtype, bool quant_leg) {
  TopoInfo* t = c->topo;
  Comm* intra = t->intra;
  Comm* lead = t->leader;
  const int L = t->n_islands;
  const int li = t->my_island;
  const std::vector<int32_t>& mine = t->members[(size_t)li];
  const int pi = (int)mine.size();
  const int n = c->size;
  const char* in = static_cast<const char*>(sendbuf);
  char* out = static_cast<char*>(recvbuf);

  /* phase A: intra-island exchange (compact to member order, exchange,
   * scatter back to world positions) */
  if (intra && intra->size > 1) {
    ObsScope obs(TPU_OBS_ALLTOALL, -1, 0, chunk * pi,
                 intra->arena ? TPU_COLL_SHM : TPU_COLL_RING);
    obs.set_tier(TPU_TIER_INTRA);
    std::vector<char> sa((size_t)(chunk * pi)), ra((size_t)(chunk * pi));
    for (int m = 0; m < pi; m++)
      std::memcpy(sa.data() + (int64_t)m * chunk,
                  in + (int64_t)mine[(size_t)m] * chunk, (size_t)chunk);
    int rc = intra->arena
                 ? shm_alltoall(intra, sa.data(), ra.data(), chunk)
                 : flat_alltoall(intra, sa.data(), ra.data(), chunk);
    if (rc) return 1;
    for (int m = 0; m < pi; m++)
      std::memcpy(out + (int64_t)mine[(size_t)m] * chunk,
                  ra.data() + (int64_t)m * chunk, (size_t)chunk);
  } else {
    std::memcpy(out + (int64_t)c->rank * chunk,
                in + (int64_t)c->rank * chunk, (size_t)chunk);
  }
  if (n == pi) return 0;  // single island: resolve degrades before here

  const bool is_leader = c->rank == t->leaders[(size_t)li];
  const int rloc = intra ? intra->rank : 0;  // my island member index
  const int64_t cross_bytes = (int64_t)(n - pi) * chunk;
  /* xoff[k]: byte offset of island k's run inside any (island-order,
   * skipping li; member-order within) cross buffer */
  std::vector<int64_t> xoff((size_t)L, 0);
  {
    int64_t o = 0;
    for (int k = 0; k < L; k++) {
      if (k == li) continue;
      xoff[(size_t)k] = o;
      o += (int64_t)t->members[(size_t)k].size() * chunk;
    }
  }
  /* my cross-island chunks, (island k != li, dst member t_) order */
  std::vector<char> cross((size_t)cross_bytes);
  {
    int64_t off = 0;
    for (int k = 0; k < L; k++) {
      if (k == li) continue;
      for (int32_t w : t->members[(size_t)k]) {
        std::memcpy(cross.data() + off, in + (int64_t)w * chunk,
                    (size_t)chunk);
        off += chunk;
      }
    }
  }

  /* phase B: gather the members' cross buffers at the leader, member
   * order (G[m] = member m's cross buffer) */
  std::vector<char> G;
  if (is_leader) G.resize((size_t)(cross_bytes * pi));
  if (intra && intra->size > 1) {
    ObsScope obs(TPU_OBS_GATHER, t->my_leader, 0, cross_bytes,
                 intra->arena ? TPU_COLL_SHM : TPU_COLL_TREE);
    obs.set_tier(TPU_TIER_INTRA);
    if (intra->arena) {
      if (shm_allgather(intra, cross.data(), cross_bytes, G.data(), 0,
                        false))
        return 1;
    } else if (rloc == 0) {
      std::memcpy(G.data(), cross.data(), (size_t)cross_bytes);
      for (int r = 1; r < intra->size; r++)
        if (recv_msg(intra, r, kCollectiveTag,
                     G.data() + (int64_t)r * cross_bytes, cross_bytes))
          return 1;
    } else {
      if (send_msg(intra, 0, kCollectiveTag, cross.data(), cross_bytes))
        return 1;
    }
  } else if (is_leader) {
    std::memcpy(G.data(), cross.data(), (size_t)cross_bytes);
  }

  /* phases C on the leaders: reorder G into per-dest-island blocks,
   * trade blocks pairwise, reorder into per-member scatter payloads */
  std::vector<char> D;  // phase D payload: pi members x cross_bytes
  if (is_leader) {
    /* boff[k]: byte offset of the island-k block in the send (and,
     * p_i*p_k being symmetric in the pair, receive) staging buffer */
    std::vector<int64_t> boff((size_t)L + 1, 0);
    for (int k = 0; k < L; k++)
      boff[(size_t)k + 1] =
          boff[(size_t)k] +
          (k == li ? 0
                   : (int64_t)pi * t->members[(size_t)k].size() * chunk);
    std::vector<char> sblk((size_t)boff[(size_t)L]);
    std::vector<char> rblk((size_t)boff[(size_t)L]);
    for (int k = 0; k < L; k++) {
      if (k == li) continue;
      const int pk = (int)t->members[(size_t)k].size();
      for (int m = 0; m < pi; m++)
        std::memcpy(sblk.data() + boff[(size_t)k] +
                        (int64_t)m * pk * chunk,
                    G.data() + (int64_t)m * cross_bytes + xoff[(size_t)k],
                    (size_t)((int64_t)pk * chunk));
    }
    {
      ObsScope obs(TPU_OBS_ALLTOALL, -1, 0, boff[(size_t)L],
                   quant_leg ? TPU_COLL_QA2A : TPU_COLL_RING);
      obs.set_tier(TPU_TIER_INTER);
      if (quant_leg) {
        int64_t wire = 0;
        for (int k = 0; k < L; k++)
          if (k != li)
            wire += quant_packed_bytes((boff[(size_t)k + 1] -
                                        boff[(size_t)k]) /
                                       dtype_size(dtype));
        obs.set_wire(wire);
      }
      for (int round = 1; round < L; round++) {
        const int kd = (li + round) % L;
        const int ks = (li - round + L) % L;
        const int64_t snb = boff[(size_t)kd + 1] - boff[(size_t)kd];
        const int64_t rnb = boff[(size_t)ks + 1] - boff[(size_t)ks];
        SendJob job;
        int rc;
        if (!quant_leg) {
          if (async_send(lead, &job, kd, kCollectiveTag,
                         sblk.data() + boff[(size_t)kd], snb))
            return 1;
          rc = recv_msg(lead, ks, kCollectiveTag,
                        rblk.data() + boff[(size_t)ks], rnb);
        } else {
          /* one codec frame per block: load the whole block to f32,
           * pack (codec 256-blocks span chunk boundaries), ship */
          const int64_t scount = snb / dtype_size(dtype);
          const int64_t rcount = rnb / dtype_size(dtype);
          std::vector<char>& qs =
              quant_tls_buf(0, quant_packed_bytes(scount));
          if (dtype == TPU_F32) {
            quant_pack_f32(reinterpret_cast<const float*>(
                               sblk.data() + boff[(size_t)kd]),
                           scount, qs.data());
          } else {
            std::vector<char>& st = quant_tls_buf(1, 4 * scount);
            quant_load_f32(sblk.data() + boff[(size_t)kd], dtype, scount,
                           reinterpret_cast<float*>(st.data()));
            quant_pack_f32(reinterpret_cast<const float*>(st.data()),
                           scount, qs.data());
          }
          if (async_send(lead, &job, kd, kCollectiveTag, qs.data(),
                         quant_packed_bytes(scount)))
            return 1;
          if (dtype == TPU_F32) {
            rc = recv_quant_msg(lead, ks, rcount,
                                reinterpret_cast<float*>(
                                    rblk.data() + boff[(size_t)ks]),
                                false);
          } else {
            std::vector<char>& st = quant_tls_buf(1, 4 * rcount);
            float* stf = reinterpret_cast<float*>(st.data());
            rc = recv_quant_msg(lead, ks, rcount, stf, false);
            if (!rc)
              quant_store_f32(stf, dtype, rcount,
                              rblk.data() + boff[(size_t)ks]);
          }
        }
        if (wait_send(lead, &job) || rc) return 1;
      }
    }
    /* per-member scatter payloads: member t_ gets (island k != li, src
     * member m) order — the same run layout as `cross`, so xoff
     * addresses both */
    D.resize((size_t)(cross_bytes * pi));
    for (int k = 0; k < L; k++) {
      if (k == li) continue;
      const int pk = (int)t->members[(size_t)k].size();
      for (int m = 0; m < pk; m++)
        for (int t_ = 0; t_ < pi; t_++)
          std::memcpy(D.data() + (int64_t)t_ * cross_bytes +
                          xoff[(size_t)k] + (int64_t)m * chunk,
                      rblk.data() + boff[(size_t)k] +
                          ((int64_t)m * pi + t_) * chunk,
                      (size_t)chunk);
    }
  }

  /* phase D: scatter each member its cross chunks */
  std::vector<char> stage((size_t)cross_bytes);
  if (intra && intra->size > 1) {
    ObsScope obs(TPU_OBS_SCATTER, t->my_leader, 0, cross_bytes,
                 intra->arena ? TPU_COLL_SHM : TPU_COLL_TREE);
    obs.set_tier(TPU_TIER_INTRA);
    if (intra->arena) {
      if (shm_scatter(intra, D.data(), stage.data(), cross_bytes, 0))
        return 1;
    } else if (rloc == 0) {
      std::memcpy(stage.data(), D.data(), (size_t)cross_bytes);
      for (int r = 1; r < intra->size; r++)
        if (p2p_send(intra, r, kCollectiveTag,
                     D.data() + (int64_t)r * cross_bytes, cross_bytes))
          return 1;
    } else {
      if (recv_msg(intra, 0, kCollectiveTag, stage.data(), cross_bytes))
        return 1;
    }
  } else {
    std::memcpy(stage.data(), D.data(), (size_t)cross_bytes);
  }
  /* (island, src member) order -> world-rank positions */
  {
    int64_t off = 0;
    for (int k = 0; k < L; k++) {
      if (k == li) continue;
      for (int32_t w : t->members[(size_t)k]) {
        std::memcpy(out + (int64_t)w * chunk, stage.data() + off,
                    (size_t)chunk);
        off += chunk;
      }
    }
  }
  return 0;
}

/* Hierarchical bcast: root's island first (so its leader holds the
 * payload), then the leader tier, then the remaining islands. */
int hier_bcast(Comm* c, void* buf, int64_t nbytes, int root) {
  TopoInfo* t = c->topo;
  Comm* intra = t->intra;
  Comm* lead = t->leader;
  const int ri = t->island_of[(size_t)root];
  if (t->my_island == ri && intra && intra->size > 1) {
    const auto& mem = t->members[(size_t)ri];
    int rloc = 0;
    for (size_t m = 0; m < mem.size(); m++)
      if (mem[m] == root) rloc = (int)m;
    ObsScope obs(TPU_OBS_BCAST, root, 0, nbytes,
                 intra->arena ? TPU_COLL_SHM : TPU_COLL_TREE);
    obs.set_tier(TPU_TIER_INTRA);
    if (intra_bcast(intra, buf, nbytes, rloc)) return 1;
  }
  if (lead && lead->size > 1) {
    ObsScope obs(TPU_OBS_BCAST, ri, 0, nbytes, TPU_COLL_TREE);
    obs.set_tier(TPU_TIER_INTER);
    if (bcast_internal(lead, buf, nbytes, ri)) return 1;
  }
  if (t->my_island != ri && intra && intra->size > 1) {
    ObsScope obs(TPU_OBS_BCAST, t->my_leader, 0, nbytes,
                 intra->arena ? TPU_COLL_SHM : TPU_COLL_TREE);
    obs.set_tier(TPU_TIER_INTRA);
    if (intra_bcast(intra, buf, nbytes, 0)) return 1;
  }
  return 0;
}

/* Hierarchical reduce: intra reduce to the leaders, leader-tier serial
 * reduce to the root island's leader, then a final intra hop to the
 * root when it is not its island's leader.  The flat contract is
 * preserved: only the root's recvbuf holds the reduction; every other
 * rank's recvbuf keeps its input copy (leaders fold into a scratch
 * accumulator, never into the caller's buffer). */
int hier_reduce(Comm* c, const void* sendbuf, void* recvbuf, int64_t count,
                int dtype, int op, int root) {
  TopoInfo* t = c->topo;
  Comm* intra = t->intra;
  Comm* lead = t->leader;
  const int64_t nbytes = count * dtype_size(dtype);
  const int ri = t->island_of[(size_t)root];
  const bool am_leader = t->my_leader == c->rank;
  /* leaders accumulate island (then global) sums in scratch */
  std::vector<char> acc;
  if (am_leader) {
    acc.resize((size_t)nbytes);
    std::memcpy(acc.data(), sendbuf, (size_t)nbytes);
  }
  if (recvbuf != sendbuf) std::memcpy(recvbuf, sendbuf, (size_t)nbytes);
  /* phase 1: intra reduce to the leader (member-order association) */
  if (intra && intra->size > 1) {
    ObsScope obs(TPU_OBS_REDUCE, t->my_leader, 0, nbytes,
                 intra->arena ? TPU_COLL_SHM : TPU_COLL_TREE);
    obs.set_tier(TPU_TIER_INTRA);
    int rc;
    if (intra->arena) {
      rc = shm_allreduce_like(intra, sendbuf, am_leader ? acc.data()
                                                        : recvbuf,
                              count, dtype, op, 0, false);
    } else if (am_leader) {
      rc = serial_reduce0(intra, acc.data(), count, dtype, op);
    } else {
      rc = send_msg(intra, 0, kCollectiveTag, sendbuf, nbytes);
    }
    if (rc) return 1;
  }
  /* phase 2: leaders reduce to the root island's leader (leader-rank
   * order, root island's own sum first) */
  if (lead && lead->size > 1) {
    ObsScope obs(TPU_OBS_REDUCE, ri, 0, nbytes, TPU_COLL_TREE);
    obs.set_tier(TPU_TIER_INTER);
    if (lead->rank == ri) {
      std::vector<char> tmp((size_t)nbytes);
      for (int r = 0; r < lead->size; r++) {
        if (r == ri) continue;
        if (recv_msg(lead, r, kCollectiveTag, tmp.data(), nbytes))
          return 1;
        if (combine(acc.data(), tmp.data(), count, dtype, op, c)) return 1;
      }
    } else {
      if (send_msg(lead, ri, kCollectiveTag, acc.data(), nbytes)) return 1;
    }
  }
  /* phase 3: land the result in the root's recvbuf */
  const int root_leader = t->leaders[(size_t)ri];
  if (root == root_leader) {
    if (c->rank == root) std::memcpy(recvbuf, acc.data(), (size_t)nbytes);
    return 0;
  }
  if (c->rank == root_leader || c->rank == root) {
    ObsScope obs(TPU_OBS_SEND, root, 0, nbytes,
                 intra && intra->arena ? TPU_COLL_SHM : TPU_COLL_TREE);
    obs.set_tier(TPU_TIER_INTRA);
    const auto& mem = t->members[(size_t)ri];
    int rloc = 0;
    for (size_t m = 0; m < mem.size(); m++)
      if (mem[m] == root) rloc = (int)m;
    if (c->rank == root_leader) {
      if (p2p_send(intra, rloc, kCollectiveTag, acc.data(), nbytes))
        return 1;
    } else {
      if (recv_msg(intra, 0, kCollectiveTag, recvbuf, nbytes)) return 1;
    }
  }
  return 0;
}

/* ================= async progress engine =================
 *
 * One dedicated progress thread per socket-owning communicator drives
 * a bounded lock-free (SPSC: posts are serialized by the comm lock,
 * the progress thread is the only consumer) submission queue of op
 * descriptors and a per-descriptor completion futex:
 *
 * - small sends DETACH: the payload is copied into the descriptor and
 *   the caller returns immediately — the buffered-send semantics the
 *   static verifier's match model (analysis/_match.py) already
 *   assumes.  Ordering is preserved because the queue drains strictly
 *   in posted order, exactly the serialization the comm lock gave the
 *   inline path;
 * - every other op posts and PARKS on its completion futex when the
 *   queue is non-empty (an earlier op is still in flight — running it
 *   inline would reorder the channel), and runs INLINE on the calling
 *   thread when the engine is idle (no context-switch tax on the
 *   latency path; bit-for-bit the historic behavior);
 * - adjacent detached sends to the same peer coalesce into one
 *   kCoalescedTag wire frame (threshold MPI4JAX_TPU_COALESCE_BYTES;
 *   the receive side splits transparently, tags preserved);
 * - deadlines are measured from POST time (g_dl_post_anchor): time
 *   spent queued behind a wedged op counts against the job deadline,
 *   and abort poison is consumed on the progress thread exactly as it
 *   was inline (the bodies are the same code).
 *
 * MPI4JAX_TPU_PROGRESS_THREAD=0 disables the engine entirely: every
 * op executes inline under the comm lock, the pre-engine behavior. */

bool progress_thread_on() {
  static bool v = [] {
    const char* e = std::getenv("MPI4JAX_TPU_PROGRESS_THREAD");
    if (!e || !e[0]) return true;
    if (!std::strcmp(e, "0") || !std::strcmp(e, "false") ||
        !std::strcmp(e, "off") || !std::strcmp(e, "no"))
      return false;
    if (!std::strcmp(e, "1") || !std::strcmp(e, "true") ||
        !std::strcmp(e, "on") || !std::strcmp(e, "yes"))
      return true;
    std::fprintf(stderr,
                 "tpucomm: cannot parse MPI4JAX_TPU_PROGRESS_THREAD=%s\n", e);
    std::exit(2);
  }();
  return v;
}

int64_t parse_env_bytes(const char* name, int64_t dflt, int64_t lo,
                        int64_t hi) {
  const char* e = std::getenv(name);
  if (!e || !e[0]) return dflt;
  char* end = nullptr;
  long long v = std::strtoll(e, &end, 10);
  if (end == e || *end) {
    std::fprintf(stderr, "tpucomm: cannot parse %s=%s\n", name, e);
    std::exit(2);  // a typo'd knob must not silently change behavior
  }
  if (v < lo) v = lo;
  if (v > hi) v = hi;
  return (int64_t)v;
}

/* sends <= this coalesce when adjacent in posted order (0 = off) */
int64_t coalesce_bytes() {
  static int64_t v =
      parse_env_bytes("MPI4JAX_TPU_COALESCE_BYTES", 4096, 0, 64 * 1024);
  return v;
}

/* submission-queue capacity in descriptors */
int64_t queue_depth() {
  static int64_t v = [] {
    int64_t d = parse_env_bytes("MPI4JAX_TPU_QUEUE_DEPTH", 1024, 16,
                                1 << 16);
    int64_t p = 16;
    while (p < d) p <<= 1;
    return p;
  }();
  return v;
}

/* sends up to this size are copied into the descriptor and detached */
int64_t detach_threshold() {
  static int64_t v = std::max<int64_t>(kEagerBytes, coalesce_bytes());
  return v;
}

constexpr int kCoalesceMaxRun = 32;   // sends merged into one frame, max
constexpr uint32_t kOpStatus = 1;     // flags: status-reporting variant

struct EngineOp {
  int32_t kind = 0;            // TpuObsOp code
  uint32_t flags = 0;
  Comm* comm = nullptr;
  const void* sbuf = nullptr;
  void* rbuf = nullptr;
  int64_t snb = 0, rnb = 0;    // payload bytes (send / recv side)
  int64_t count = 0;           // elements (reductions)
  int dtype = 0, rop = 0;
  int peer = -1, peer2 = -1;   // dest/root/lo , source/hi
  int tag = 0, tag2 = 0;
  int algo = TPU_COLL_AUTO;
  int32_t* out_src = nullptr;  // status out-params (parked ops only)
  int32_t* out_tag = nullptr;
  int64_t* out_count = nullptr;
  double t_post = -1;
  bool detached = false;
  std::vector<char> owned;     // copied payload of a detached send
  std::atomic<int32_t> state{0};  // 0 = queued, 1 = done (futex word)
  int rc = 0;
};

struct Engine {
  std::vector<EngineOp*> slots;
  uint64_t cap = 0;
  std::atomic<uint64_t> head{0};   // produced (posting side)
  std::atomic<uint64_t> tail{0};   // consumed (progress thread)
  std::atomic<int32_t> hseq{0};    // futex: progress thread parks here
  std::atomic<int32_t> tseq{0};    // futex: full-queue posters park here
  std::atomic<int64_t> inflight{0};
  std::atomic<int32_t> stop{0};
  std::atomic<int32_t> sticky{0};  // a detached op failed
  std::thread thr;
  std::vector<char> scratch;       // coalesced frame assembly
};

/* Execute one descriptor: the op bodies, verbatim from the pre-engine
 * public entry points, wrapped in the same ObsScope/LogScope (now fed
 * the post timestamp so events carry the dispatch split). */
int engine_run_body(EngineOp* o) {
  Comm* c = o->comm;
  const double tp = o->t_post;
  switch (o->kind) {
    case TPU_OBS_SEND: {
      ObsScope obs(TPU_OBS_SEND, o->peer, o->tag, o->snb, -1, tp);
      LogScope log(c->rank, "Send", [&] {
        return "to " + std::to_string(o->peer) + " (" +
               std::to_string(o->snb) + " bytes, tag " +
               std::to_string(o->tag) + ")";
      });
      if (ring_p2p_on(c) && o->peer != c->rank && o->peer >= 0 &&
          o->peer < c->size) {
        bool inlined = false;
        if (shm_try_send(c, o->peer, o->tag, o->sbuf, o->snb, &inlined))
          return 1;
        if (inlined) return 0;
        return send_msg_tcp(c, o->peer, o->tag, o->sbuf, o->snb);
      }
      return send_msg(c, o->peer, o->tag, o->sbuf, o->snb);
    }
    case TPU_OBS_RECV: {
      ObsScope obs(TPU_OBS_RECV, o->peer2, o->tag, o->rnb, -1, tp);
      LogScope log(c->rank, "Recv", [&] {
        return "from " + std::to_string(o->peer2) + " (" +
               std::to_string(o->rnb) + " bytes, tag " +
               std::to_string(o->tag) +
               ((o->flags & kOpStatus) ? ", status)" : ")");
      });
      if (o->flags & kOpStatus)
        return recv_msg_status(c, o->peer2, o->tag, o->rbuf, o->rnb,
                               o->out_src, o->out_tag, o->out_count);
      return recv_msg(c, o->peer2, o->tag, o->rbuf, o->rnb);
    }
    case TPU_OBS_SENDRECV: {
      ObsScope obs(TPU_OBS_SENDRECV, o->peer, o->tag, o->snb + o->rnb, -1,
                   tp);
      LogScope log(c->rank, "Sendrecv", [&] {
        return "to " + std::to_string(o->peer) + " from " +
               std::to_string(o->peer2) +
               ((o->flags & kOpStatus) ? " (status)" : "");
      });
      SendJob job;
      if (async_send(c, &job, o->peer, o->tag, o->sbuf, o->snb)) return 1;
      int recv_rc =
          (o->flags & kOpStatus)
              ? recv_msg_status(c, o->peer2, o->tag2, o->rbuf, o->rnb,
                                o->out_src, o->out_tag, o->out_count)
              : recv_msg(c, o->peer2, o->tag2, o->rbuf, o->rnb);
      return wait_send(c, &job) || recv_rc;
    }
    case TPU_OBS_SHIFT2: {
      ObsScope obs(TPU_OBS_SHIFT2, o->peer2, o->tag, 2 * o->snb, -1, tp);
      LogScope log(c->rank, "Shift2", [&] {
        return std::to_string(o->snb) + " bytes, lo " +
               std::to_string(o->peer) + " hi " + std::to_string(o->peer2);
      });
      const int lo = o->peer, hi = o->peer2;
      const int64_t strip_nbytes = o->snb;
      const int tag = o->tag;
      const char* in = static_cast<const char*>(o->sbuf);
      char* out = static_cast<char*>(o->rbuf);
      const char* to_lo = in;
      const char* to_hi = in + strip_nbytes;
      char* from_lo = out;
      char* from_hi = out + strip_nbytes;
      if (lo == c->rank && hi == c->rank) {
        std::memcpy(from_lo, to_hi, strip_nbytes);
        std::memcpy(from_hi, to_lo, strip_nbytes);
        return 0;
      }
      SendJob jlo, jhi;
      bool sent_lo = false, sent_hi = false;
      if (lo >= 0) {
        if (async_send(c, &jlo, lo, tag, to_lo, strip_nbytes)) return 1;
        sent_lo = true;
      } else {
        std::memcpy(from_lo, to_hi, strip_nbytes);  // wall: passthrough
      }
      if (hi >= 0) {
        if (async_send(c, &jhi, hi, tag + 1, to_hi, strip_nbytes)) {
          if (sent_lo) wait_send(c, &jlo);
          return 1;
        }
        sent_hi = true;
      } else {
        std::memcpy(from_hi, to_lo, strip_nbytes);
      }
      int rc = 0;
      if (hi >= 0) rc |= recv_msg(c, hi, tag, from_hi, strip_nbytes);
      if (lo >= 0) rc |= recv_msg(c, lo, tag + 1, from_lo, strip_nbytes);
      if (sent_lo) rc |= wait_send(c, &jlo);
      if (sent_hi) rc |= wait_send(c, &jhi);
      return rc;
    }
    case TPU_OBS_BARRIER: {
      ObsScope obs(TPU_OBS_BARRIER, -1, 0, 0, c->arena ? TPU_COLL_SHM : -1,
                   tp);
      LogScope log(c->rank, "Barrier", [&] { return std::string(); });
      if (c->arena) return shm_barrier_op(c);
      uint8_t token = 1;
      for (int dist = 1; dist < c->size; dist *= 2) {
        int dest = (c->rank + dist) % c->size;
        int src = (c->rank - dist + c->size) % c->size;
        uint8_t got = 0;
        SendJob job;
        if (async_send(c, &job, dest, kCollectiveTag, &token, 1)) return 1;
        int recv_rc = recv_msg(c, src, kCollectiveTag, &got, 1);
        if (wait_send(c, &job) || recv_rc) return 1;
      }
      return 0;
    }
    case TPU_OBS_BCAST: {
      ObsScope obs(TPU_OBS_BCAST, o->peer, 0, o->rnb,
                   c->arena ? TPU_COLL_SHM : -1, tp);
      LogScope log(c->rank, "Bcast", [&] {
        return std::to_string(o->rnb) + " bytes, root " +
               std::to_string(o->peer);
      });
      if (c->arena) return shm_bcast(c, o->rbuf, o->rnb, o->peer);
      /* multi-island worlds route large bcasts through the island
       * leaders (MPI4JAX_TPU_HIER; force drops the size floor, deny
       * the routing) — only the inter-island leg rides the slow tier */
      if (hier_routable(c, o->rnb))
        return hier_bcast(c, o->rbuf, o->rnb, o->peer);
      return bcast_internal(c, o->rbuf, o->rnb, o->peer);
    }
    case TPU_OBS_GATHER: {
      ObsScope obs(TPU_OBS_GATHER, o->peer, 0, o->snb,
                   c->arena ? TPU_COLL_SHM : -1, tp);
      LogScope log(c->rank, "Gather", [&] {
        return std::to_string(o->snb) + " bytes, root " +
               std::to_string(o->peer);
      });
      const int root = o->peer;
      if (c->arena)
        return shm_allgather(c, o->sbuf, o->snb, o->rbuf, root, false);
      if (c->rank == root) {
        char* out = static_cast<char*>(o->rbuf);
        std::memcpy(out + (int64_t)root * o->snb, o->sbuf, o->snb);
        for (int r = 0; r < c->size; r++) {
          if (r == root) continue;
          if (recv_msg(c, r, kCollectiveTag, out + (int64_t)r * o->snb,
                       o->snb))
            return 1;
        }
        return 0;
      }
      return send_msg(c, root, kCollectiveTag, o->sbuf, o->snb);
    }
    case TPU_OBS_SCATTER: {
      ObsScope obs(TPU_OBS_SCATTER, o->peer, 0, o->rnb,
                   c->arena ? TPU_COLL_SHM : -1, tp);
      LogScope log(c->rank, "Scatter", [&] {
        return std::to_string(o->rnb) + " bytes, root " +
               std::to_string(o->peer);
      });
      const int root = o->peer;
      if (c->arena) return shm_scatter(c, o->sbuf, o->rbuf, o->rnb, root);
      if (c->rank == root) {
        const char* in = static_cast<const char*>(o->sbuf);
        std::memcpy(o->rbuf, in + (int64_t)root * o->rnb, o->rnb);
        for (int r = 0; r < c->size; r++) {
          if (r == root) continue;
          if (send_msg(c, r, kCollectiveTag, in + (int64_t)r * o->rnb,
                       o->rnb))
            return 1;
        }
        return 0;
      }
      return recv_msg(c, root, kCollectiveTag, o->rbuf, o->rnb);
    }
    case TPU_OBS_ALLGATHER: {
      int chosen =
          resolve_coll_algo(c, TPU_OPKIND_ALLGATHER, o->snb, 0, o->algo);
      ObsScope obs(TPU_OBS_ALLGATHER, -1, 0, o->snb, chosen, tp);
      LogScope log(c->rank, "Allgather", [&] {
        return std::to_string(o->snb) + " bytes algo " +
               coll_algo_name(chosen);
      });
      if (chosen == TPU_COLL_SHM)
        return shm_allgather(c, o->sbuf, o->snb, o->rbuf, 0, true);
      switch (chosen) {
        case TPU_COLL_TREE:
          return tree_allgather(c, o->sbuf, o->snb, o->rbuf);
        case TPU_COLL_RD:
          return rd_allgather(c, o->sbuf, o->snb, o->rbuf);
        case TPU_COLL_HRING:
        case TPU_COLL_HTREE:
          return hier_allgather(c, o->sbuf, o->snb, o->rbuf);
        default:
          return ring_allgather(c, o->sbuf, o->snb, o->rbuf);
      }
    }
    case TPU_OBS_ALLTOALL: {
      /* count > 0 marks the typed entry (tpucomm_alltoall_algo); the
       * legacy byte-chunk tpucomm_alltoall has no dtype context and
       * always resolves to the exact schedules. */
      const bool typed = o->count > 0;
      int64_t chunk = o->snb;
      if (typed) {
        int64_t esize = dtype_size(o->dtype);
        if (esize == 0) FAIL(c, "bad dtype %d", o->dtype);
        chunk = o->count * esize;
      }
      int chosen =
          resolve_coll_algo(c, TPU_OPKIND_ALLTOALL, chunk * c->size,
                            o->count, o->algo, typed ? o->dtype : -1);
      ObsScope obs(TPU_OBS_ALLTOALL, -1, 0, chunk * c->size, chosen, tp);
      if (chosen == TPU_COLL_QA2A)
        obs.set_wire(quant_packed_bytes(o->count) * c->size);
      LogScope log(c->rank, "Alltoall", [&] {
        return std::to_string(chunk) + " bytes/chunk " +
               coll_algo_name(chosen);
      });
      switch (chosen) {
        case TPU_COLL_SHM:
          return shm_alltoall(c, o->sbuf, o->rbuf, chunk);
        case TPU_COLL_QA2A:
          return q_alltoall(c, o->sbuf, o->rbuf, o->count, o->dtype);
        case TPU_COLL_HA2A:
          return h_alltoall(c, o->sbuf, o->rbuf, chunk, o->count,
                            o->dtype, false);
        case TPU_COLL_HQA2A:
          return h_alltoall(c, o->sbuf, o->rbuf, chunk, o->count,
                            o->dtype, true);
        default:
          return flat_alltoall(c, o->sbuf, o->rbuf, chunk);
      }
    }
    case TPU_OBS_ALLREDUCE: {
      int64_t esize = dtype_size(o->dtype);
      if (esize == 0) FAIL(c, "bad dtype %d", o->dtype);
      int64_t nbytes = o->count * esize;
      int chosen = resolve_coll_algo(c, TPU_OPKIND_ALLREDUCE, nbytes,
                                     o->count, o->algo, o->dtype, o->rop);
      ObsScope obs(TPU_OBS_ALLREDUCE, -1, 0, nbytes, chosen, tp);
      if (chosen == TPU_COLL_QRING || chosen == TPU_COLL_QRD)
        obs.set_wire(quant_packed_bytes(o->count));
      LogScope log(c->rank, "Allreduce", [&] {
        return std::to_string(o->count) + " elems dtype " +
               std::to_string(o->dtype) + " op " + std::to_string(o->rop) +
               " algo " + coll_algo_name(chosen);
      });
      if (c->size == 1) {
        if (o->rbuf != o->sbuf) std::memcpy(o->rbuf, o->sbuf, nbytes);
        return 0;
      }
      if (chosen == TPU_COLL_SHM)
        return shm_allreduce_like(c, o->sbuf, o->rbuf, o->count, o->dtype,
                                  o->rop, 0, true);
      if (o->rbuf != o->sbuf) std::memcpy(o->rbuf, o->sbuf, nbytes);
      switch (chosen) {
        case TPU_COLL_RING:
          return ring_allreduce(c, o->rbuf, o->count, o->dtype, o->rop);
        case TPU_COLL_RD:
          return rd_allreduce(c, o->rbuf, o->count, o->dtype, o->rop);
        case TPU_COLL_QRING:
          return qring_allreduce(c, o->rbuf, o->count, o->dtype, o->rop);
        case TPU_COLL_QRD:
          return qrd_allreduce(c, o->rbuf, o->count, o->dtype, o->rop);
        case TPU_COLL_HRING:
          return hier_allreduce(c, o->rbuf, o->count, o->dtype, o->rop,
                                TPU_COLL_RING);
        case TPU_COLL_HTREE:
          return hier_allreduce(c, o->rbuf, o->count, o->dtype, o->rop,
                                TPU_COLL_RD);
        default:
          return tree_allreduce(c, o->rbuf, o->count, o->dtype, o->rop);
      }
    }
    case TPU_OBS_REDUCE: {
      int64_t esize = dtype_size(o->dtype);
      ObsScope obs(TPU_OBS_REDUCE, o->peer, 0, o->count * esize,
                   c->arena && c->size > 1 ? TPU_COLL_SHM : -1, tp);
      LogScope log(c->rank, "Reduce", [&] {
        return std::to_string(o->count) + " elems, root " +
               std::to_string(o->peer);
      });
      if (esize == 0) FAIL(c, "bad dtype %d", o->dtype);
      const int root = o->peer;
      if (c->arena && c->size > 1) {
        if (c->rank != root && o->rbuf != o->sbuf)
          std::memcpy(o->rbuf, o->sbuf, o->count * esize);
        return shm_allreduce_like(c, o->sbuf, o->rbuf, o->count, o->dtype,
                                  o->rop, root, false);
      }
      int64_t nbytes = o->count * esize;
      /* multi-island worlds fold within each island first, then across
       * the leaders (same gate as bcast; float association changes
       * like any algorithm switch — docs/usage.md) */
      if (hier_routable(c, nbytes))
        return hier_reduce(c, o->sbuf, o->rbuf, o->count, o->dtype,
                           o->rop, root);
      if (c->rank == root) {
        if (o->rbuf != o->sbuf) std::memcpy(o->rbuf, o->sbuf, nbytes);
        std::vector<char> tmp(nbytes);
        for (int r = 0; r < c->size; r++) {
          if (r == root) continue;
          if (recv_msg(c, r, kCollectiveTag, tmp.data(), nbytes)) return 1;
          if (combine(o->rbuf, tmp.data(), o->count, o->dtype, o->rop, c))
            return 1;
        }
        return 0;
      }
      if (o->rbuf != o->sbuf) std::memcpy(o->rbuf, o->sbuf, nbytes);
      return send_msg(c, root, kCollectiveTag, o->rbuf, nbytes);
    }
    case TPU_OBS_SCAN: {
      int64_t esize = dtype_size(o->dtype);
      ObsScope obs(TPU_OBS_SCAN, -1, 0, o->count * esize,
                   c->arena && c->size > 1 ? TPU_COLL_SHM : -1, tp);
      LogScope log(c->rank, "Scan",
                   [&] { return std::to_string(o->count) + " elems"; });
      if (esize == 0) FAIL(c, "bad dtype %d", o->dtype);
      if (c->arena && c->size > 1)
        return shm_scan(c, o->sbuf, o->rbuf, o->count, o->dtype, o->rop);
      int64_t nbytes = o->count * esize;
      if (o->rbuf != o->sbuf) std::memcpy(o->rbuf, o->sbuf, nbytes);
      if (c->rank > 0) {
        std::vector<char> tmp(nbytes);
        if (recv_msg(c, c->rank - 1, kCollectiveTag, tmp.data(), nbytes))
          return 1;
        std::vector<char> mine(nbytes);
        std::memcpy(mine.data(), o->rbuf, nbytes);
        std::memcpy(o->rbuf, tmp.data(), nbytes);
        if (combine(o->rbuf, mine.data(), o->count, o->dtype, o->rop, c))
          return 1;
      }
      if (c->rank < c->size - 1) {
        if (send_msg(c, c->rank + 1, kCollectiveTag, o->rbuf, nbytes))
          return 1;
      }
      return 0;
    }
    default:
      FAIL(c, "unknown engine op kind %d", o->kind);
  }
}

/* Socket-liveness check for the drain-loop merge predicates.  Armed
 * links must snapshot through link_fd (the recovery thread rewires
 * socks under the link locks); an fd of -1 mid-recovery just demotes
 * the op to the single-descriptor path, whose link_send_frame joins
 * the recovery instead of racing it. */
static inline int engine_peer_fd(const EngineOp* o) {
  return retry_armed() ? link_fd(o->comm, o->peer)
                       : o->comm->socks[o->peer];
}

/* True when this descriptor may merge into a coalesced frame. */
bool coalescible(const EngineOp* o) {
  return o->kind == TPU_OBS_SEND && o->detached && coalesce_bytes() > 0 &&
         o->snb <= coalesce_bytes() && o->peer != o->comm->rank &&
         o->peer >= 0 && o->peer < o->comm->size &&
         !ring_p2p_on(o->comm) && engine_peer_fd(o) >= 0;
}

/* One obs event per logical send of a batched drain-loop write (the
 * whole burst's syscalls are attributed to the FIRST event so per-op
 * sums stay exact). */
void engine_obs_burst(EngineOp** ops, int n, int dest, double tw0,
                      int64_t sys0) {
  if (!g_obs_on.load(std::memory_order_relaxed)) return;
  double tw1 = now_s();
  int64_t ds = g_syscalls.load(std::memory_order_relaxed) - sys0;
  for (int i = 0; i < n; i++) {
    TpuObsEvent ev{};
    ev.op = TPU_OBS_SEND;
    ev.peer = dest;
    ev.tag = ops[i]->tag;
    ev.nbytes = ops[i]->snb;
    ev.wire_bytes = ops[i]->snb;
    ev.algo = -1;
    ev.t_start = ops[i]->t_post;
    ev.dur_s = tw1 - ops[i]->t_post;
    ev.queue_s = tw0 - ops[i]->t_post;
    if (ev.queue_s < 0) ev.queue_s = 0;
    if (ev.queue_s > ev.dur_s) ev.queue_s = ev.dur_s;
    ev.syscalls =
        i == 0 ? (int32_t)std::min<int64_t>(ds, INT32_MAX) : 0;
    obs_append(ev);
  }
}

/* Write a run of adjacent detached sends (same comm, same peer) as ONE
 * kCoalescedTag frame.  Tags and sizes ride as per-message sub-headers;
 * the receive side splits them back apart.  Returns the shared rc.
 * The outer header is assembled INTO the scratch buffer, so the whole
 * container leaves in one write (one SQE under uring) — byte-identical
 * wire to the historic header-then-body write pair. */
int engine_write_coalesced(Engine* e, EngineOp** ops, int n) {
  Comm* c = ops[0]->comm;
  const int dest = ops[0]->peer;
  const bool armed = retry_armed();
  int64_t total = 0;
  for (int i = 0; i < n; i++) total += (int64_t)sizeof(MsgHeader) + ops[i]->snb;
  /* armed: the outer header is stamped (seq + epoch + CRC) inside
   * link_send_frame, so only the sub-frames are assembled here; the
   * whole container is then one retained, replayable wire frame */
  e->scratch.resize((size_t)(total + (armed ? 0 : (int64_t)sizeof(MsgHeader))));
  char* p = e->scratch.data();
  if (!armed) {
    MsgHeader outer{total, kCoalescedTag, c->comm_id};
    std::memcpy(p, &outer, sizeof(outer));
    p += sizeof(outer);
  }
  for (int i = 0; i < n; i++) {
    /* one injector hit per LOGICAL send: MPI4JAX_TPU_FAULT's after=N
     * counts user sends, not wire frames, so a fault lands at the same
     * op index with coalescing on or off */
    fault_fire(c, g_job_rank, FP_SEND, "send",
               armed ? link_fd(c, dest) : -1);
    MsgHeader sh{ops[i]->snb, ops[i]->tag, c->comm_id};
    std::memcpy(p, &sh, sizeof(sh));
    p += sizeof(sh);
    std::memcpy(p, ops[i]->sbuf, (size_t)ops[i]->snb);
    p += ops[i]->snb;
  }
  LogScope log(c->rank, "SendCoalesced", [&] {
    return "to " + std::to_string(dest) + " (" + std::to_string(n) +
           " msgs, " + std::to_string(total) + " bytes)";
  });
  g_dl_post_anchor = ops[0]->t_post;
  double tw0 = now_s();
  int64_t sys0 = g_syscalls.load(std::memory_order_relaxed);
  int io = armed
               ? link_send_frame(c, dest, kCoalescedTag, e->scratch.data(),
                                 total, nullptr, 0)
               : write_all_dl(c->socks[dest], e->scratch.data(),
                              total + (int64_t)sizeof(MsgHeader));
  g_dl_post_anchor = 0;
  int rc = 0;
  if (io) {
    char why[160];
    if (io == 2)
      std::snprintf(why, sizeof(why),
                    "timed out after %.0f s with %lld/%lld bytes moved "
                    "(MPI4JAX_TPU_TIMEOUT_S)",
                    transport_timeout_s(), (long long)g_io_done,
                    (long long)g_io_want);
    else
      std::snprintf(why, sizeof(why), "%s", std::strerror(errno));
    std::fprintf(stderr,
                 "tpucomm r%d: coalesced send to %d (%d msgs) failed: %s\n",
                 c->rank, dest, n, why);
    set_last_error(c->rank, "coalesced send to %d failed: %s", dest, why);
    rc = 1;
  }
  engine_obs_burst(ops, n, dest, tw0, sys0);
  return rc;
}

/* True for a detached TCP send the drain loop may merge into a
 * vectored write (no container framing — the wire bytes are EXACTLY
 * the N individual frames). */
bool batchable(const EngineOp* o) {
  return o->kind == TPU_OBS_SEND && o->detached &&
         o->peer != o->comm->rank && o->peer >= 0 &&
         o->peer < o->comm->size && !ring_p2p_on(o->comm) &&
         engine_peer_fd(o) >= 0;
}

/* Write a run of adjacent detached sends that are NOT coalescible
 * (above the threshold, or coalescing off) as one vectored write: the
 * historic drain loop issued one header+payload write pair per
 * descriptor even when several completed descriptors targeted the same
 * socket back-to-back — batching them into a single writev keeps the
 * wire bytes bit-identical while the URING=0 escape hatch also sheds
 * the per-descriptor syscalls. */
int engine_write_batch(Engine* e, EngineOp** ops, int n) {
  (void)e;
  Comm* c = ops[0]->comm;
  const int dest = ops[0]->peer;
  const bool armed = retry_armed();
  std::vector<MsgHeader> hdrs((size_t)n);
  std::vector<struct iovec> iov((size_t)n * 2);
  int64_t total = 0;
  for (int i = 0; i < n; i++) {
    if (!armed) fault_fire(c, g_job_rank, FP_SEND, "send");
    hdrs[(size_t)i] = MsgHeader{ops[i]->snb, ops[i]->tag, c->comm_id};
    iov[(size_t)(2 * i)] = {&hdrs[(size_t)i], sizeof(MsgHeader)};
    iov[(size_t)(2 * i + 1)] = {const_cast<void*>(ops[i]->sbuf),
                                (size_t)ops[i]->snb};
    total += (int64_t)sizeof(MsgHeader) + ops[i]->snb;
  }
  LogScope log(c->rank, "SendBatch", [&] {
    return "to " + std::to_string(dest) + " (" + std::to_string(n) +
           " frames, " + std::to_string(total) + " bytes)";
  });
  g_dl_post_anchor = ops[0]->t_post;
  double tw0 = now_s();
  int64_t sys0 = g_syscalls.load(std::memory_order_relaxed);
  int io = 0;
  if (armed) {
    /* armed: each frame needs its own seq stamp + retained copy, so
     * the run leaves as N sequential link_send_frame writes instead of
     * one shared writev (the merge still saves per-descriptor queue
     * round-trips; only the vectored-syscall saving is conceded) */
    for (int i = 0; i < n && !io; i++) {
      fault_fire(c, g_job_rank, FP_SEND, "send", link_fd(c, dest));
      io = link_send_frame(c, dest, ops[i]->tag, ops[i]->sbuf,
                           ops[i]->snb, nullptr, 0);
    }
  } else {
    io = writev_all_dl(c->socks[dest], iov.data(), 2 * n, total);
  }
  g_dl_post_anchor = 0;
  int rc = 0;
  if (io) {
    char why[160];
    if (io == 2)
      std::snprintf(why, sizeof(why),
                    "timed out after %.0f s with %lld/%lld bytes moved "
                    "(MPI4JAX_TPU_TIMEOUT_S)",
                    transport_timeout_s(), (long long)g_io_done,
                    (long long)g_io_want);
    else
      std::snprintf(why, sizeof(why), "%s", std::strerror(errno));
    std::fprintf(stderr,
                 "tpucomm r%d: batched send to %d (%d frames) failed: %s\n",
                 c->rank, dest, n, why);
    set_last_error(c->rank, "batched send to %d failed: %s", dest, why);
    rc = 1;
  }
  engine_obs_burst(ops, n, dest, tw0, sys0);
  return rc;
}

void engine_loop(Comm* root) {
  Engine* e = root->engine;
  for (;;) {
    uint64_t t = e->tail.load(std::memory_order_relaxed);
    uint64_t h = e->head.load(std::memory_order_acquire);
    if (h == t) {
      if (e->stop.load(std::memory_order_acquire)) return;
      /* idle tick: heartbeat quiet links + drain stray reconnect dials
       * (the ISSUE's uring-timeout-slot role — the 100 ms futex park
       * below already bounds the tick period) */
      if (retry_armed()) link_idle_service(root);
      int32_t seq = e->hseq.load(std::memory_order_acquire);
      if (e->head.load(std::memory_order_acquire) != t) continue;
      shm_futex_wait(&e->hseq, seq, 100);
      continue;
    }
    EngineOp* op = e->slots[t % e->cap];
    int run = 1;
    bool as_container = false;
    if (coalescible(op)) {
      /* small adjacent sends merge into ONE container frame (the
       * historic coalescing wire format, unchanged) */
      as_container = true;
      while (t + run < h && run < kCoalesceMaxRun) {
        EngineOp* nxt = e->slots[(t + run) % e->cap];
        if (!coalescible(nxt) || nxt->comm != op->comm ||
            nxt->peer != op->peer)
          break;
        run++;
      }
      if (run == 1) as_container = false;
    } else if (batchable(op)) {
      /* larger detached sends to one socket back-to-back: one vectored
       * write of the individual frames (bit-identical wire bytes) */
      while (t + run < h && run < kCoalesceMaxRun) {
        EngineOp* nxt = e->slots[(t + run) % e->cap];
        if (!batchable(nxt) || coalescible(nxt) || nxt->comm != op->comm ||
            nxt->peer != op->peer)
          break;
        run++;
      }
    }
    if (run > 1) {
      EngineOp* batch[kCoalesceMaxRun];
      for (int i = 0; i < run; i++) batch[i] = e->slots[(t + i) % e->cap];
      int rc = as_container ? engine_write_coalesced(e, batch, run)
                            : engine_write_batch(e, batch, run);
      e->tail.store(t + run, std::memory_order_release);
      e->tseq.fetch_add(1, std::memory_order_release);
      shm_futex_wake_all(&e->tseq);
      e->inflight.fetch_sub(run, std::memory_order_release);
      if (rc) e->sticky.store(1, std::memory_order_release);
      for (int i = 0; i < run; i++) delete batch[i];
      continue;
    }
    g_dl_post_anchor = op->t_post;
    op->rc = engine_run_body(op);
    g_dl_post_anchor = 0;
    e->tail.store(t + 1, std::memory_order_release);
    e->tseq.fetch_add(1, std::memory_order_release);
    shm_futex_wake_all(&e->tseq);
    e->inflight.fetch_sub(1, std::memory_order_release);
    if (op->detached) {
      if (op->rc) e->sticky.store(1, std::memory_order_release);
      delete op;
    } else {
      /* the waiter owns the descriptor and may destroy it the moment
       * it observes state == 1.  The wake AFTER the store is still
       * safe: FUTEX_WAKE keys on the address only (never dereferences
       * it) — the standard condvar-internal idiom — and the waiter's
       * futex wait is timed (100 ms), so even a wake landing on a
       * recycled stack address costs at most one spurious wakeup. */
      op->state.store(1, std::memory_order_release);
      shm_futex_wake_all(&op->state);
    }
  }
}

/* Lazy engine creation, factored out of engine_post so an armed
 * bootstrap can spin the progress thread up eagerly: heartbeats must
 * tick on a link that never posts an op.  Callers serialize (comm lock
 * or single-threaded bootstrap). */
Engine* engine_ensure(Comm* root) {
  Engine* e = root->engine;
  if (e == nullptr) {
    e = new Engine;
    e->cap = (uint64_t)queue_depth();
    e->slots.assign((size_t)e->cap, nullptr);
    root->engine = e;  // published before the thread starts
    e->thr = std::thread(engine_loop, root);
  }
  return e;
}

/* Post under the comm lock; the queue itself is lock-free SPSC. */
void engine_post(Comm* root, EngineOp* op) {
  Engine* e = engine_ensure(root);
  uint64_t h = e->head.load(std::memory_order_relaxed);
  while (h - e->tail.load(std::memory_order_acquire) >= e->cap) {
    /* bounded queue: park for space (backpressure, not allocation) */
    int32_t seq = e->tseq.load(std::memory_order_acquire);
    if (h - e->tail.load(std::memory_order_acquire) < e->cap) break;
    shm_futex_wait(&e->tseq, seq, 100);
  }
  e->slots[h % e->cap] = op;
  e->inflight.fetch_add(1, std::memory_order_release);
  e->head.store(h + 1, std::memory_order_release);
  e->hseq.fetch_add(1, std::memory_order_release);
  shm_futex_wake_all(&e->hseq);
}

/* Wait (under the comm lock) until the progress thread has drained and
 * completed everything posted so far.  Required before any direct
 * socket I/O outside the engine (split's arena bootstrap). */
void engine_quiesce(Comm* root) {
  Engine* e = root->engine;
  if (!e) return;
  while (e->inflight.load(std::memory_order_acquire) > 0) {
    int32_t seq = e->tseq.load(std::memory_order_acquire);
    if (e->inflight.load(std::memory_order_acquire) <= 0) break;
    shm_futex_wait(&e->tseq, seq, 50);
  }
}

/* The single entry point every public op goes through.  Holds the comm
 * lock for the duration of an INLINE op (the historic exclusivity), or
 * only for the post + park of a queued one (the progress thread never
 * takes the lock — queue order is the serialization). */
int engine_submit(Comm* c, EngineOp* op) {
  op->comm = c;
  Comm* root = c->lock_root;
  std::lock_guard<std::mutex> lock(comm_mu(c));
  Engine* e = root->engine;
  if (e && e->sticky.load(std::memory_order_acquire))
    FAIL(c, "an earlier asynchronously posted send failed — see the "
         "diagnostic above (async progress engine)");
  const bool engine_on = progress_thread_on();
  const bool detach = engine_on && op->kind == TPU_OBS_SEND &&
                      op->snb <= detach_threshold() && op->peer >= 0 &&
                      op->peer < c->size;
  const bool busy =
      e && e->inflight.load(std::memory_order_acquire) > 0;
  if (!engine_on || (!detach && !busy)) {
    /* idle engine (or engine off): run inline on this thread — no
     * context switch on the latency path, bit-for-bit the historic
     * behavior */
    op->t_post = g_obs_on.load(std::memory_order_relaxed) ? now_s() : -1;
    return engine_run_body(op);
  }
  op->t_post = now_s();
  if (detach) {
    auto* hop = new EngineOp;
    hop->kind = op->kind;
    hop->flags = op->flags;
    hop->comm = c;
    hop->snb = op->snb;
    hop->peer = op->peer;
    hop->tag = op->tag;
    hop->t_post = op->t_post;
    hop->detached = true;
    const char* src = static_cast<const char*>(op->sbuf);
    hop->owned.assign(src, src + op->snb);
    hop->sbuf = hop->owned.data();
    engine_post(root, hop);
    return 0;  // buffered-send semantics: completion is asynchronous
  }
  engine_post(root, op);
  while (op->state.load(std::memory_order_acquire) == 0)
    shm_futex_wait(&op->state, 0, 100);
  return op->rc;
}

/* Drain the queue (the loop finishes everything posted before stop is
 * observed with an empty queue), join the thread, free the engine.
 * Declared near the top: Comm's destructor and finalize call it. */
void engine_shutdown(Engine* e) {
  e->stop.store(1, std::memory_order_release);
  e->hseq.fetch_add(1, std::memory_order_release);
  shm_futex_wake_all(&e->hseq);
  if (e->thr.joinable()) e->thr.join();
  if (e->sticky.load(std::memory_order_acquire))
    /* a detached send failed and no later op surfaced it (each failure
     * already printed its own diagnostic at the moment it happened):
     * say so once more at teardown so a job whose LAST op was the
     * failing buffered send cannot drain silently */
    std::fprintf(stderr,
                 "tpucomm: asynchronously posted send(s) failed before "
                 "finalize; data may be undelivered (see diagnostics "
                 "above)\n");
  delete e;
}

}  // namespace

extern "C" {

void tpucomm_set_logging(int enabled) { g_logging = enabled; }

/* The TCP-mesh bootstrap shared by tpucomm_init and tpucomm_shrink:
 * listen for higher ranks, dial lower ranks (deadline-bounded with
 * exponential backoff), exchange rank handshakes, arm non-blocking
 * mode when a transport deadline is set, and attach the same-host shm
 * arena.  Returns a registered handle, 0 on failure. */
static int64_t comm_bootstrap(int rank, int size, int base_port,
                              const char* hosts) {
  auto* c = new Comm;
  c->rank = rank;
  c->size = size;
  c->socks.assign(size, -1);

  std::vector<std::string> host_list(size, "127.0.0.1");
  if (hosts && hosts[0]) {
    std::string s(hosts);
    size_t pos = 0;
    for (int i = 0; i < size; i++) {
      size_t comma = s.find(',', pos);
      host_list[i] = s.substr(pos, comma == std::string::npos
                                       ? std::string::npos
                                       : comma - pos);
      if (comma == std::string::npos) break;
      pos = comma + 1;
    }
  }

  /* listener for ranks > me */
  int listen_fd = -1;
  if (rank < size - 1) {
    listen_fd = ::socket(AF_INET, SOCK_STREAM, 0);
    int one = 1;
    ::setsockopt(listen_fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_addr.s_addr = INADDR_ANY;
    addr.sin_port = htons((uint16_t)(base_port + rank));
    if (::bind(listen_fd, (sockaddr*)&addr, sizeof(addr)) != 0 ||
        ::listen(listen_fd, size) != 0) {
      std::fprintf(stderr, "tpucomm r%d: cannot listen on port %d: %s\n",
                   rank, base_port + rank, std::strerror(errno));
      delete c;
      return 0;
    }
  }

  /* dial every lower rank (retrying while they come up): deadline-bounded
   * with exponential backoff instead of the old fixed 600 x 50 ms spin;
   * the failure names the last errno so a refused port reads differently
   * from an unroutable host */
  const double connect_dl = connect_timeout_s();  // 0 = unbounded
  for (int peer = 0; peer < rank; peer++) {
    int fd = -1;
    int last_errno = 0;
    double deadline = connect_dl > 0
                          ? now_s() + connect_dl
                          : std::numeric_limits<double>::infinity();
    double backoff_ms = 1.0;
    for (;;) {
      fd = ::socket(AF_INET, SOCK_STREAM, 0);
      sockaddr_in addr{};
      addr.sin_family = AF_INET;
      addr.sin_port = htons((uint16_t)(base_port + peer));
      ::inet_pton(AF_INET, host_list[peer].c_str(), &addr.sin_addr);
      /* non-blocking connect + poll: a blackholed host must consume at
       * most the remaining deadline, not the kernel's ~2 min SYN
       * retransmit cycle (the deadline is the contract, and the error
       * text reports the elapsed budget) */
      int fl = ::fcntl(fd, F_GETFL, 0);
      ::fcntl(fd, F_SETFL, fl | O_NONBLOCK);
      int cr = ::connect(fd, (sockaddr*)&addr, sizeof(addr));
      if (cr != 0 && errno == EINPROGRESS) {
        double remain = deadline - now_s();
        pollfd pf{fd, POLLOUT, 0};
        int pr = remain > 0
                     ? ::poll(&pf, 1, (int)std::min(remain * 1000.0 + 1,
                                                    60000.0))
                     : 0;
        if (pr > 0) {
          int soerr = 0;
          socklen_t sl = sizeof(soerr);
          ::getsockopt(fd, SOL_SOCKET, SO_ERROR, &soerr, &sl);
          if (soerr == 0) {
            cr = 0;
          } else {
            errno = soerr;
            cr = -1;
          }
        } else {
          errno = ETIMEDOUT;
          cr = -1;
        }
      }
      if (cr == 0) {
        ::fcntl(fd, F_SETFL, fl);  // back to blocking for the handshake
        break;
      }
      last_errno = errno;
      ::close(fd);
      fd = -1;
      if (now_s() + backoff_ms / 1000.0 > deadline) break;
      std::this_thread::sleep_for(
          std::chrono::microseconds((int64_t)(backoff_ms * 1000)));
      backoff_ms = std::min(backoff_ms * 2.0, 200.0);
    }
    if (fd < 0) {
      std::fprintf(stderr,
                   "tpucomm r%d: cannot reach rank %d (%s:%d) within "
                   "%.0f s: %s (MPI4JAX_TPU_CONNECT_TIMEOUT_S)\n",
                   rank, peer, host_list[peer].c_str(), base_port + peer,
                   connect_dl, std::strerror(last_errno));
      set_last_error(rank,
                     "bootstrap connect to rank %d (%s:%d) timed out after "
                     "%.0f s: %s", peer, host_list[peer].c_str(),
                     base_port + peer, connect_dl,
                     std::strerror(last_errno));
      delete c;
      return 0;
    }
    int one = 1;
    ::setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    int32_t my_rank = rank;
    if (write_all(fd, &my_rank, sizeof(my_rank))) {
      delete c;
      return 0;
    }
    c->socks[peer] = fd;
  }

  /* accept every higher rank, bounded by the connect deadline BY
   * DEFAULT: the dial side has been deadline-bounded since the knob
   * landed, but accept used to block forever unless the operator set
   * MPI4JAX_TPU_CONNECT_TIMEOUT_S explicitly — an accept-side hang
   * (one higher rank never scheduled) outlived every other deadline in
   * the stack.  A missing higher rank now hangs accept exactly as long
   * as a missing lower rank hangs connect; 0 opts back into unbounded
   * waits on both sides. */
  const bool bounded_accept = connect_dl > 0;
  for (int expected = rank + 1; expected < size; expected++) {
    if (bounded_accept) {
      double deadline = now_s() + connect_dl;
      int pr = 0;
      do {
        pollfd pf{listen_fd, POLLIN, 0};
        pr = ::poll(&pf, 1, 100);
      } while (pr <= 0 && now_s() < deadline);
      if (pr <= 0) {
        std::fprintf(stderr,
                     "tpucomm r%d: no higher rank dialed within %.0f s "
                     "(%d of %d peers still missing; "
                     "MPI4JAX_TPU_CONNECT_TIMEOUT_S)\n",
                     rank, connect_dl, size - expected, size - rank - 1);
        set_last_error(rank,
                       "bootstrap accept timed out after %.0f s with %d "
                       "higher rank(s) missing", connect_dl,
                       size - expected);
        delete c;
        return 0;
      }
    }
    int fd = ::accept(listen_fd, nullptr, nullptr);
    if (fd < 0) {
      std::fprintf(stderr, "tpucomm r%d: accept failed: %s\n", rank,
                   std::strerror(errno));
      delete c;
      return 0;
    }
    int one = 1;
    ::setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    int32_t peer_rank = -1;
    /* bounded handshake (when the accept side is bounded at all): a
     * peer that connects but wedges before identifying itself must not
     * hold bootstrap hostage past the deadline.  The fd is blocking
     * here, which is fine for the read side: io_all_deadline polls
     * before every read, so it only ever reads available bytes. */
    int hs_rc = bounded_accept
                    ? io_all_deadline<false>(fd, &peer_rank,
                                             sizeof(peer_rank), connect_dl)
                    : read_all(fd, &peer_rank, sizeof(peer_rank));
    if (hs_rc || peer_rank <= rank ||
        peer_rank >= size || c->socks[peer_rank] != -1) {
      std::fprintf(stderr, "tpucomm r%d: bad handshake (peer said %d)\n",
                   rank, peer_rank);
      delete c;
      return 0;
    }
    c->socks[peer_rank] = fd;
  }
  if (listen_fd >= 0) {
    if (retry_armed())
      /* self-healing: reconnect dials from higher ranks land on the
       * bootstrap listener, so it stays open for the comm's lifetime */
      c->listen_fd = listen_fd;
    else
      ::close(listen_fd);
  }

  /* With a transport deadline armed, the mesh runs on non-blocking fds:
   * the deadline paths poll() before every transfer and handle EAGAIN,
   * and a blocking socket write of a large payload would otherwise park
   * in the kernel until ALL bytes are queued — unwakeable past any
   * deadline when the peer stops draining.  Without the knob the fds
   * stay blocking and the historic loops serve untouched.  The uring
   * backend ALSO wants non-blocking fds: a blocking submitted send is
   * punted to an io-wq kernel worker (a context switch per op, and a
   * parked worker past any deadline), where a non-blocking one
   * completes through the ring's internal poll — so an active uring
   * resolves the same fd mode the deadline does. */
  if (transport_timeout_s() > 0 || uring_ready()) {
    for (int fd : c->socks)
      if (fd >= 0) {
        int fl = ::fcntl(fd, F_GETFL, 0);
        if (fl >= 0) ::fcntl(fd, F_SETFL, fl | O_NONBLOCK);
      }
  }

  /* self-healing link layer: one LinkState per peer socket, the
   * identity root_rank map (sub-comms compose through it at split),
   * and the REAL dialing addresses — reconnect must dial the wire
   * host even when FAKE_HOSTS virtually partitions locality */
  if (retry_armed()) {
    c->base_port = base_port;
    c->real_hosts = host_list;
    c->root_rank.resize((size_t)size);
    c->links.resize((size_t)size);
    const double t0 = now_s();
    for (int r = 0; r < size; r++) {
      c->root_rank[(size_t)r] = r;
      if (r == rank) continue;
      c->links[(size_t)r].reset(new LinkState);
      c->links[(size_t)r]->last_rx.store(t0, std::memory_order_relaxed);
    }
  }

  /* same-host groups get the shared-memory collective arena */
  const char* jobid = std::getenv("MPI4JAX_TPU_JOBID");
  char prefix[96];
  /* the base port is part of the prefix even with a job id: elastic
   * recovery re-bootstraps a new world GENERATION at a re-derived port
   * under the same job id, and its arena segments must never collide
   * with (or attach to) the previous generation's */
  if (jobid && jobid[0])
    std::snprintf(prefix, sizeof(prefix), "m4jshm_%.48s_p%d", jobid,
                  base_port);
  else
    std::snprintf(prefix, sizeof(prefix), "m4jshm_p%d", base_port);
  c->shm_prefix = prefix;
  /* arena eligibility keys on the EFFECTIVE host view: the real host
   * table with the MPI4JAX_TPU_FAKE_HOSTS virtual partition applied —
   * a partitioned loopback job loses the world arena exactly like the
   * multi-host shape it models (its intra-island sub-comms get their
   * own arenas through the same check in tpucomm_split).  Sockets
   * always dial the REAL hosts; only locality decisions change. */
  std::vector<std::string> eff_hosts = host_list;
  apply_fake_hosts(eff_hosts, size);
  c->member_hosts = eff_hosts;
  bool same_host = true;
  for (int i = 1; i < size; i++)
    if (eff_hosts[i] != eff_hosts[0]) same_host = false;
  if (same_host) arena_init(c);

  /* armed + engine on: start the progress thread eagerly — heartbeats
   * must tick on a link that never posts an op (half-open detection on
   * idle links is the point) */
  if (retry_armed() && progress_thread_on()) engine_ensure(c);

  std::lock_guard<std::mutex> lock(g_comms_mu);
  int64_t h = g_next_handle++;
  g_comms[h] = c;
  return h;
}

int64_t tpucomm_init(int rank, int size, int base_port, const char* hosts) {
  fault_init();
  g_job_rank = rank;
  fault_fire(nullptr, rank, FP_CONNECT, "connect");
  return comm_bootstrap(rank, size, base_port, hosts);
}

int64_t tpucomm_shrink(int64_t old_h, int new_rank, int new_size,
                       int base_port, const char* hosts) {
  fault_init();
  /* tear the dead world down first: drain/stop its progress engine and
   * close its sockets so the rebuilt mesh starts from a clean fd table.
   * The caller already abandoned the old comm (elastic recovery runs
   * after abort_all poisoned and shut every socket down, so the drain
   * fails fast instead of blocking on dead peers).  Sub-communicators
   * of the old world must be gone before this call — they borrow its
   * sockets. */
  if (old_h != 0) tpucomm_finalize(old_h);
  /* connect-point fault injection keys on the rank this process was
   * BORN with (g_job_rank), exactly like the send/recv points: a fault
   * spec must address the same process before and after renumbering */
  fault_fire(nullptr, g_job_rank, FP_CONNECT, "connect");
  return comm_bootstrap(new_rank, new_size, base_port, hosts);
}

void tpucomm_finalize(int64_t h) {
  std::lock_guard<std::mutex> lock(g_comms_mu);
  auto it = g_comms.find(h);
  if (it == g_comms.end()) return;
  Comm* c = it->second;
  /* drain the progress engine BEFORE closing sockets or freeing the
   * comm: detached sends still in the queue must reach the wire (the
   * buffered-send flush MPI_Finalize performs).  A split/dup comm's
   * descriptors live on the socket owner's engine — quiesce it, or a
   * queued send would dereference this comm after the delete below. */
  if (c->engine) {
    engine_shutdown(c->engine);
    c->engine = nullptr;
  } else if (c->lock_root != c) {
    /* the parent may itself have been finalized already (legal call
     * order before the engine existed): only touch lock_root while it
     * is still registered — we hold g_comms_mu, so this is race-free */
    for (const auto& kv : g_comms)
      if (kv.second == c->lock_root) {
        if (kv.second->engine) engine_quiesce(kv.second);
        break;
      }
  }
  /* a finalized comm may be referenced as another comm's topology
   * sub-communicator (intra-island / leaders): drop that topology
   * entirely — every rank of the owning comm tears its sub-comms down
   * at the same point (the Python bridge owns them), so the map
   * disappears consistently and hierarchical picks degrade to their
   * flat twins everywhere instead of on a subset of ranks */
  for (auto& kv : g_comms) {
    Comm* w = kv.second;
    if (w->topo && (w->topo->intra == c || w->topo->leader == c)) {
      delete w->topo;
      w->topo = nullptr;
    }
  }
  if (c->lock_root != c) {
    /* unregister from the socket owner's reconnect-rewire list — but
     * only while the owner is still registered (it may legally have
     * been finalized first; g_comms_mu makes the check race-free) */
    for (const auto& kv : g_comms)
      if (kv.second == c->lock_root) {
        std::lock_guard<std::mutex> kl(c->lock_root->kids_mu);
        auto& ks = c->lock_root->kids;
        ks.erase(std::remove(ks.begin(), ks.end(), c), ks.end());
        break;
      }
  }
  if (c->owns_socks)
    for (int fd : c->socks)
      if (fd >= 0) ::close(fd);
  delete c;
  g_comms.erase(it);
}

/* Sub-communicators (the analog of MPI_Comm_split / MPI_Comm_dup —
 * the reference accepts any mpi4py comm, users Split()/Clone() freely,
 * comm.py:4-11 + docs/sharp-bits.rst:82-143 there).
 *
 * Collective over the parent: every member must call in the same program
 * position.  Ranks sharing a `color` form a new communicator ordered by
 * (key, parent rank); color < 0 opts out (returns the null handle -1).
 * The child borrows the parent's sockets with ranks remapped; message
 * isolation between sibling comms is enforced by the comm_id carried in
 * every frame header (mismatch = fail-fast, consistent with the ordered
 * transport's no-reordering contract). */
int64_t tpucomm_split(int64_t h, int color, int key) {
  Comm* c = get_comm(h);
  if (!c) return 0;
  std::vector<int32_t> mine{(int32_t)color, (int32_t)key};
  std::vector<int32_t> all(2 * (size_t)c->size);
  if (tpucomm_allgather(h, mine.data(), 2 * sizeof(int32_t), all.data()))
    return 0;
  int32_t seq;
  {
    std::lock_guard<std::mutex> lock(comm_mu(c));
    seq = c->next_split_seq++;
  }
  if (color < 0) return -1;  // null comm: this rank opted out

  std::vector<std::pair<int, int>> members;  // (key, parent rank)
  for (int r = 0; r < c->size; r++)
    if (all[2 * r] == color) members.push_back({all[2 * r + 1], r});
  std::stable_sort(members.begin(), members.end());

  auto* nc = new Comm;
  nc->size = (int)members.size();
  nc->socks.assign(nc->size, -1);
  nc->owns_socks = false;
  /* serialize on (and queue async sends through) the socket owner: two
   * comms sharing fds must never interleave writes on one socket */
  nc->lock_root = c->lock_root;
  for (int nr = 0; nr < nc->size; nr++) {
    int old = members[nr].second;
    if (old == c->rank)
      nc->rank = nr;
    else
      nc->socks[nr] = c->socks[old];
  }
  if (retry_armed() && !c->root_rank.empty()) {
    /* compose the root_rank map through the parent so this child
     * resolves the same per-socket LinkState, and register it with the
     * socket owner so a reconnect rewires this comm's socks view too */
    nc->root_rank.resize((size_t)nc->size);
    for (int nr = 0; nr < nc->size; nr++)
      nc->root_rank[(size_t)nr] =
          c->root_rank[(size_t)members[(size_t)nr].second];
    Comm* rt = nc->lock_root;
    std::lock_guard<std::mutex> kl(rt->kids_mu);
    rt->kids.push_back(nc);
  }
  /* FNV mix of (parent id, call seq, color): identical on every member,
   * distinct across sibling groups and successive splits */
  uint32_t id = 2166136261u;
  for (uint32_t v : {(uint32_t)c->comm_id, (uint32_t)seq, (uint32_t)color}) {
    id ^= v;
    id *= 16777619u;
  }
  nc->comm_id = (int32_t)(id & 0x7fffffff);
  if (nc->comm_id == 0) nc->comm_id = 1;  // 0 is reserved for the world

  /* a subset of a same-(effective-)host group is same-host: a child
   * whose members all share one entry of the parent's member_hosts view
   * gets its own arena even when the PARENT spans hosts — this is what
   * gives an intra-island sub-comm of a multi-host (or FAKE_HOSTS-
   * partitioned) world the shm fast path the hierarchical collectives
   * ride.  arena_init's nonce bcast writes the shared sockets, so it
   * must hold the socket owner's lock like every other op on borrowed
   * fds. */
  nc->shm_prefix = c->shm_prefix;
  if (!c->member_hosts.empty()) {
    nc->member_hosts.resize((size_t)nc->size);
    for (int nr = 0; nr < nc->size; nr++)
      nc->member_hosts[(size_t)nr] =
          c->member_hosts[(size_t)members[(size_t)nr].second];
  }
  bool sub_same_host = nc->size > 1 && !nc->member_hosts.empty();
  for (int nr = 1; sub_same_host && nr < nc->size; nr++)
    if (nc->member_hosts[(size_t)nr] != nc->member_hosts[0])
      sub_same_host = false;
  if (c->arena || sub_same_host) {
    std::lock_guard<std::mutex> lock(comm_mu(nc));
    /* arena bootstrap writes the shared sockets directly (nonce bcast):
     * the progress thread must be idle first — two writers on one
     * socket would interleave frames */
    engine_quiesce(nc->lock_root);
    arena_init(nc);
  }

  std::lock_guard<std::mutex> lock(g_comms_mu);
  int64_t nh = g_next_handle++;
  g_comms[nh] = nc;
  return nh;
}

int64_t tpucomm_dup(int64_t h) {
  Comm* c = get_comm(h);
  if (!c) return 0;
  /* split with one shared color, keyed by rank: same membership and
   * ordering, fresh comm_id (isolated message space) */
  return tpucomm_split(h, 0, c->rank);
}

/* ---- topology installation (mpi4jax_tpu/topo is the owner) ---- */

int tpucomm_set_topology(int64_t h, const int32_t* island_of, int n,
                         int64_t intra_h, int64_t leader_h) {
  Comm* c = get_comm(h);
  if (!c || !island_of || n != c->size) return 1;
  std::unique_ptr<TopoInfo> t(new TopoInfo);
  t->island_of.assign(island_of, island_of + n);
  int max_id = -1;
  for (int r = 0; r < n; r++) {
    if (island_of[r] < 0 || island_of[r] >= n) return 1;
    if (island_of[r] > max_id) max_id = island_of[r];
  }
  t->n_islands = max_id + 1;
  t->members.assign((size_t)t->n_islands, {});
  for (int r = 0; r < n; r++)
    t->members[(size_t)island_of[r]].push_back(r);
  t->leaders.resize((size_t)t->n_islands);
  for (int i = 0; i < t->n_islands; i++) {
    if (t->members[(size_t)i].empty()) return 1;  // ids must be dense
    t->leaders[(size_t)i] = t->members[(size_t)i][0];
    /* island ids ordered by leader rank: the leaders' sub-comm (split
     * keyed on rank) then has leader-comm rank == island id, which the
     * hierarchical schedules rely on */
    if (i > 0 && t->leaders[(size_t)i] <= t->leaders[(size_t)i - 1])
      return 1;
  }
  t->my_island = island_of[c->rank];
  t->my_leader = t->leaders[(size_t)t->my_island];
  const auto& mine = t->members[(size_t)t->my_island];
  Comm* intra = intra_h > 0 ? get_comm(intra_h) : nullptr;
  Comm* lead = leader_h > 0 ? get_comm(leader_h) : nullptr;
  /* a single-island (flat) topology installs for the probes only — no
   * sub-comms needed, the hierarchical schedules never become eligible */
  if (mine.size() > 1 && t->n_islands > 1) {
    int idx = -1;
    for (size_t m = 0; m < mine.size(); m++)
      if (mine[m] == c->rank) idx = (int)m;
    if (!intra || intra->size != (int)mine.size() || intra->rank != idx)
      return 1;
    t->intra = intra;
  }
  if (t->my_leader == c->rank && t->n_islands > 1) {
    if (!lead || lead->size != t->n_islands ||
        lead->rank != t->my_island)
      return 1;
    t->leader = lead;
  }
  /* swap in under the op lock with the engine quiesced: dispatch reads
   * c->topo without a lock, so no op may be mid-flight */
  std::lock_guard<std::mutex> lock(comm_mu(c));
  engine_quiesce(c->lock_root);
  delete c->topo;
  c->topo = t.release();
  return 0;
}

int tpucomm_topo_info(int64_t h, int32_t* out_island_of,
                      int32_t* out_n_islands) {
  Comm* c = get_comm(h);
  if (!c) return -1;
  if (!c->topo) return 1;
  if (out_island_of)
    for (int r = 0; r < c->size; r++)
      out_island_of[r] = c->topo->island_of[(size_t)r];
  if (out_n_islands) *out_n_islands = c->topo->n_islands;
  return 0;
}

int tpucomm_rank(int64_t h) {
  Comm* c = get_comm(h);
  return c ? c->rank : -1;
}

int tpucomm_size(int64_t h) {
  Comm* c = get_comm(h);
  return c ? c->size : -1;
}

/* Observability: did the same-host fast paths engage for this comm?
 * Returns 1 with the arena's sizes, 0 when the comm runs on TCP only,
 * -1 for a bad handle.  (diag CLI / docs §5.5.) */
int tpucomm_shm_info(int64_t h, int64_t* slot_bytes, int64_t* ring_bytes) {
  Comm* c = get_comm(h);
  if (!c) return -1;
  if (!c->arena) {
    *slot_bytes = 0;
    *ring_bytes = 0;
    return 0;
  }
  *slot_bytes = c->arena->slot_bytes;
  *ring_bytes = c->arena->ring_bytes;
  return 1;
}

int tpucomm_send(int64_t h, const void* buf, int64_t nbytes, int dest,
                 int tag) {
  Comm* c = get_comm(h);
  if (!c) return 1;
  EngineOp op;
  op.kind = TPU_OBS_SEND;
  op.sbuf = buf;
  op.snb = nbytes;
  op.peer = dest;
  op.tag = tag;
  return engine_submit(c, &op);
}

int tpucomm_recv(int64_t h, void* buf, int64_t nbytes, int source, int tag) {
  Comm* c = get_comm(h);
  if (!c) return 1;
  EngineOp op;
  op.kind = TPU_OBS_RECV;
  op.rbuf = buf;
  op.rnb = nbytes;
  op.peer2 = source;
  op.tag = tag;
  return engine_submit(c, &op);
}

const char* tpucomm_uring_status(void) {
  uring_probe();
  if (uring_mode() == 0) return "off";
  if (g_uring_avail == 1) {
    if (!g_uring_zc) return "on(no-zerocopy)";
    /* adaptive: the kernel reported it copies zero-copy sends on this
     * path (loopback) — large sends ride plain submitted sends now */
    if (g_zc_fallback.load(std::memory_order_relaxed))
      return "on(zerocopy-fallback)";
    return "on";
  }
  /* g_uring_reason is frozen once the probe resolves; the format
   * buffer is per-thread so concurrent status calls cannot race */
  static thread_local char buf[200];
  std::snprintf(buf, sizeof(buf), "unavailable(%s)", g_uring_reason);
  return buf;
}

int64_t tpucomm_syscall_count(void) {
  return g_syscalls.load(std::memory_order_relaxed);
}

const char* tpucomm_last_error(void) {
  std::lock_guard<std::mutex> lock(g_last_error_mu);
  return g_last_error;
}

void tpucomm_abort_all(void) {
  /* Best-effort job-wide abort propagation, called by the Python layer
   * on its way into os._exit: one poison frame (kPoisonTag header +
   * last-error text) to every peer of every socket-owning comm, then
   * shutdown — peers blocked in a recv consume the poison and fail
   * naming this rank; peers parked in shm waits see the socket die on
   * their next liveness probe.  Everything here is non-blocking: an
   * abort must never hang behind a full socket buffer. */
  char text[sizeof(g_last_error)] = {0};
  {
    std::lock_guard<std::mutex> lock(g_last_error_mu);
    std::memcpy(text, g_last_error, sizeof(text));
  }
  text[sizeof(text) - 1] = 0;
  const int64_t len = (int64_t)std::strlen(text);
  std::lock_guard<std::mutex> lock(g_comms_mu);
  for (auto& kv : g_comms) {
    Comm* c = kv.second;
    if (!c->owns_socks) continue;  // sub-comms borrow these same fds
    for (int r = 0; r < c->size; r++) {
      int fd = c->socks[r];
      if (fd < 0) continue;
      ssize_t w;
      if (retry_armed()) {
        /* armed peers parse MsgHeaderX frames: send a sealed extended
         * header (seq 0 = control, never dedup'd or replayed) so the
         * poison isn't rejected as a CRC mismatch */
        MsgHeaderX hx{};
        hx.h = MsgHeader{len, kPoisonTag, c->comm_id};
        hx_seal(&hx);
        w = ::send(fd, &hx, sizeof(hx), MSG_NOSIGNAL | MSG_DONTWAIT);
        w = (w == (ssize_t)sizeof(hx)) ? (ssize_t)sizeof(MsgHeader) : -1;
      } else {
        MsgHeader h{len, kPoisonTag, c->comm_id};
        w = ::send(fd, &h, sizeof(h), MSG_NOSIGNAL | MSG_DONTWAIT);
      }
      /* payload only behind a COMPLETE header: a partial header send
       * (nearly-full buffer — the typical abort scenario) followed by
       * text bytes would be parsed as a garbage frame header on the
       * peer; partial header + EOF degrades to the historic dead-socket
       * diagnostic instead */
      if (w == (ssize_t)sizeof(MsgHeader) && len > 0)
        ::send(fd, text, (size_t)len, MSG_NOSIGNAL | MSG_DONTWAIT);
      ::shutdown(fd, SHUT_RDWR);
    }
  }
}

int tpucomm_recv_status(int64_t h, void* buf, int64_t nbytes, int source,
                        int tag, int32_t* out_src, int32_t* out_tag,
                        int64_t* out_count) {
  Comm* c = get_comm(h);
  if (!c) return 1;
  EngineOp op;
  op.kind = TPU_OBS_RECV;
  op.flags = kOpStatus;
  op.rbuf = buf;
  op.rnb = nbytes;
  op.peer2 = source;
  op.tag = tag;
  op.out_src = out_src;
  op.out_tag = out_tag;
  op.out_count = out_count;
  return engine_submit(c, &op);
}

int tpucomm_sendrecv_status(int64_t h, const void* sendbuf,
                            int64_t send_nbytes, int dest, void* recvbuf,
                            int64_t recv_nbytes, int source, int sendtag,
                            int recvtag, int32_t* out_src, int32_t* out_tag,
                            int64_t* out_count) {
  Comm* c = get_comm(h);
  if (!c) return 1;
  EngineOp op;
  op.kind = TPU_OBS_SENDRECV;
  op.flags = kOpStatus;
  op.sbuf = sendbuf;
  op.snb = send_nbytes;
  op.peer = dest;
  op.rbuf = recvbuf;
  op.rnb = recv_nbytes;
  op.peer2 = source;
  op.tag = sendtag;
  op.tag2 = recvtag;
  op.out_src = out_src;
  op.out_tag = out_tag;
  op.out_count = out_count;
  return engine_submit(c, &op);
}

int tpucomm_sendrecv(int64_t h, const void* sendbuf, int64_t send_nbytes,
                     int dest, void* recvbuf, int64_t recv_nbytes, int source,
                     int tag) {
  Comm* c = get_comm(h);
  if (!c) return 1;
  EngineOp op;
  op.kind = TPU_OBS_SENDRECV;
  op.sbuf = sendbuf;
  op.snb = send_nbytes;
  op.peer = dest;
  op.rbuf = recvbuf;
  op.rnb = recv_nbytes;
  op.peer2 = source;
  op.tag = tag;
  op.tag2 = tag;
  return engine_submit(c, &op);
}

int tpucomm_shift2(int64_t h, const void* sendbuf, void* recvbuf,
                   int64_t strip_nbytes, int lo, int hi, int tag) {
  /* Bidirectional 1-D neighbor exchange in ONE op (the
   * MPI_Neighbor_alltoall analog on a ring segment): sendbuf holds
   * [to_lo | to_hi] strips, recvbuf receives [from_lo | from_hi].
   * Both sends go out asynchronously before either receive, so any
   * topology (chain, ring of any length, ring of 2, self-wrap) is
   * deadlock-free within the op when every member calls it at the same
   * program position.  A -1 neighbor is a wall (MPI_PROC_NULL): that
   * side's output strip is the corresponding input passthrough.
   * Frames to the LOW side use `tag`, to the HIGH side `tag+1` —
   * unambiguous even when both neighbors are one peer (ring of 2). */
  Comm* c = get_comm(h);
  if (!c) return 1;
  EngineOp op;
  op.kind = TPU_OBS_SHIFT2;
  op.sbuf = sendbuf;
  op.rbuf = recvbuf;
  op.snb = strip_nbytes;
  op.peer = lo;
  op.peer2 = hi;
  op.tag = tag;
  return engine_submit(c, &op);
}

int tpucomm_barrier(int64_t h) {
  Comm* c = get_comm(h);
  if (!c) return 1;
  EngineOp op;
  op.kind = TPU_OBS_BARRIER;
  return engine_submit(c, &op);
}

int tpucomm_bcast(int64_t h, void* buf, int64_t nbytes, int root) {
  Comm* c = get_comm(h);
  if (!c) return 1;
  EngineOp op;
  op.kind = TPU_OBS_BCAST;
  op.rbuf = buf;
  op.rnb = nbytes;
  op.peer = root;
  return engine_submit(c, &op);
}

int tpucomm_gather(int64_t h, const void* sendbuf, int64_t nbytes,
                   void* recvbuf, int root) {
  Comm* c = get_comm(h);
  if (!c) return 1;
  EngineOp op;
  op.kind = TPU_OBS_GATHER;
  op.sbuf = sendbuf;
  op.snb = nbytes;
  op.rbuf = recvbuf;
  op.peer = root;
  return engine_submit(c, &op);
}

int tpucomm_scatter(int64_t h, const void* sendbuf, void* recvbuf,
                    int64_t nbytes, int root) {
  Comm* c = get_comm(h);
  if (!c) return 1;
  EngineOp op;
  op.kind = TPU_OBS_SCATTER;
  op.sbuf = sendbuf;
  op.rbuf = recvbuf;
  op.rnb = nbytes;
  op.peer = root;
  return engine_submit(c, &op);
}

int tpucomm_allgather_algo(int64_t h, const void* sendbuf, int64_t nbytes,
                           void* recvbuf, int algo) {
  Comm* c = get_comm(h);
  if (!c) return 1;
  EngineOp op;
  op.kind = TPU_OBS_ALLGATHER;
  op.sbuf = sendbuf;
  op.snb = nbytes;
  op.rbuf = recvbuf;
  op.algo = algo;
  return engine_submit(c, &op);
}

int tpucomm_allgather(int64_t h, const void* sendbuf, int64_t nbytes,
                      void* recvbuf) {
  return tpucomm_allgather_algo(h, sendbuf, nbytes, recvbuf, TPU_COLL_AUTO);
}

int tpucomm_alltoall(int64_t h, const void* sendbuf, void* recvbuf,
                     int64_t chunk) {
  Comm* c = get_comm(h);
  if (!c) return 1;
  EngineOp op;
  op.kind = TPU_OBS_ALLTOALL;
  op.sbuf = sendbuf;
  op.rbuf = recvbuf;
  op.snb = chunk;
  return engine_submit(c, &op);
}

int tpucomm_alltoall_algo(int64_t h, const void* sendbuf, void* recvbuf,
                          int64_t count, int dtype, int algo) {
  Comm* c = get_comm(h);
  if (!c) return 1;
  EngineOp op;
  op.kind = TPU_OBS_ALLTOALL;
  op.sbuf = sendbuf;
  op.rbuf = recvbuf;
  op.count = count;
  op.dtype = dtype;
  op.algo = algo;
  return engine_submit(c, &op);
}

int tpucomm_allreduce_algo(int64_t h, const void* sendbuf, void* recvbuf,
                           int64_t count, int dtype, int op, int algo) {
  Comm* c = get_comm(h);
  if (!c) return 1;
  EngineOp eop;
  eop.kind = TPU_OBS_ALLREDUCE;
  eop.sbuf = sendbuf;
  eop.rbuf = recvbuf;
  eop.count = count;
  eop.dtype = dtype;
  eop.rop = op;
  eop.algo = algo;
  return engine_submit(c, &eop);
}

int tpucomm_allreduce(int64_t h, const void* sendbuf, void* recvbuf,
                      int64_t count, int dtype, int op) {
  return tpucomm_allreduce_algo(h, sendbuf, recvbuf, count, dtype, op,
                                TPU_COLL_AUTO);
}

void tpucomm_set_coll_table(int op_kind, const int64_t* min_bytes,
                            const int32_t* algos, int n) {
  if (op_kind < 0 || op_kind > 2) return;
  std::vector<std::pair<int64_t, int32_t>> entries;
  for (int i = 0; i < n; i++) {
    int32_t a = algos[i];
    if (a < TPU_COLL_AUTO || a > TPU_COLL_HQA2A || a == TPU_COLL_SHM)
      continue;  // SHM not forcible; unknown codes dropped
    entries.emplace_back(min_bytes[i], a);
  }
  std::sort(entries.begin(), entries.end());
  std::lock_guard<std::mutex> lock(g_coll_table_mu);
  g_coll_table[op_kind].entries = std::move(entries);
}

void tpucomm_stage_coll_table(int op_kind, const int64_t* min_bytes,
                              const int32_t* algos, int n) {
  if (op_kind < 0 || op_kind > 2) return;
  std::vector<std::pair<int64_t, int32_t>> entries;
  for (int i = 0; i < n; i++) {
    int32_t a = algos[i];
    if (a < TPU_COLL_AUTO || a > TPU_COLL_HQA2A || a == TPU_COLL_SHM)
      continue;  // same validation as the direct install
    entries.emplace_back(min_bytes[i], a);
  }
  std::sort(entries.begin(), entries.end());
  std::lock_guard<std::mutex> lock(g_coll_table_mu);
  g_coll_staged[op_kind].entries = std::move(entries);
  g_coll_staged_set[op_kind] = true;
}

int tpucomm_commit_coll_tables(int64_t h, int64_t epoch) {
  Comm* c = get_comm(h);
  if (!c) return 1;
  /* the tpucomm_set_topology swap discipline: comm lock + engine
   * quiesced, so no op resolved against the old table is mid-flight
   * when the table changes under it */
  std::lock_guard<std::mutex> lock(comm_mu(c));
  engine_quiesce(c->lock_root);
  std::lock_guard<std::mutex> tlock(g_coll_table_mu);
  for (int k = 0; k < 3; k++) {
    if (!g_coll_staged_set[k]) continue;  // never-staged kinds keep theirs
    g_coll_table[k].entries = g_coll_staged[k].entries;
    g_coll_staged[k].entries.clear();
    g_coll_staged_set[k] = false;
  }
  g_coll_epoch = epoch;
  return 0;
}

int64_t tpucomm_coll_epoch(void) {
  std::lock_guard<std::mutex> lock(g_coll_table_mu);
  return g_coll_epoch;
}

int tpucomm_coll_algo_for(int64_t h, int op_kind, int64_t nbytes) {
  Comm* c = get_comm(h);
  if (!c || op_kind < 0 || op_kind > 2) return -1;
  /* count only gates the built-in allreduce heuristic's ring cutoff;
   * approximate with 4-byte elements (the table path ignores it).
   * The probe has no dtype/op context: assume the quant-eligible case
   * (f32 SUM) so it reports qring/qrd where the table picks them — an
   * actual ineligible call degrades to the exact twin at dispatch. */
  return resolve_coll_algo(c, op_kind, nbytes, nbytes / 4, TPU_COLL_AUTO,
                           TPU_F32, TPU_SUM);
}

/* ---- quantized wire codec (diag / tests / accuracy-harness probe) ---- */

int64_t tpucomm_quant_packed_bytes(int64_t count) {
  return quant_packed_bytes(count);
}

int tpucomm_quant_pack(const void* in, int64_t count, int dtype, void* out) {
  if (!quant_dtype_ok(dtype)) return 1;
  if (count <= 0) return 0;
  std::vector<float> tmp((size_t)count);
  quant_load_f32(in, dtype, count, tmp.data());
  quant_pack_f32(tmp.data(), count, static_cast<char*>(out));
  return 0;
}

int tpucomm_quant_unpack(const void* in, int64_t count, int dtype,
                         void* out) {
  if (!quant_dtype_ok(dtype)) return 1;
  if (count <= 0) return 0;
  std::vector<float> tmp((size_t)count);
  quant_unpack_f32(static_cast<const char*>(in), count, tmp.data());
  quant_store_f32(tmp.data(), dtype, count, out);
  return 0;
}

void tpucomm_obs_enable(int enabled, int64_t capacity) {
  std::lock_guard<std::mutex> lock(g_obs_mu);
  if (enabled) {
    if (capacity < 16) capacity = 16;
    g_obs_ring.assign((size_t)capacity, TpuObsEvent{});
  } else {
    g_obs_ring.clear();
    g_obs_ring.shrink_to_fit();
  }
  g_obs_total = 0;
  g_obs_dropped = 0;
  g_obs_seq = 0;
  /* flip the hot-path flag LAST on enable (an op racing this call may
   * observe on=1 with the ring already sized, never a stale ring) */
  g_obs_on.store(enabled ? 1 : 0, std::memory_order_release);
}

void tpucomm_obs_counts(int64_t* out_recorded, int64_t* out_dropped) {
  std::lock_guard<std::mutex> lock(g_obs_mu);
  const int64_t cap = (int64_t)g_obs_ring.size();
  if (out_recorded)
    *out_recorded = g_obs_total < cap ? g_obs_total : cap;
  if (out_dropped) *out_dropped = g_obs_dropped;
}

int64_t tpucomm_obs_drain(TpuObsEvent* out, int64_t max_n) {
  std::lock_guard<std::mutex> lock(g_obs_mu);
  const int64_t cap = (int64_t)g_obs_ring.size();
  if (cap == 0 || max_n <= 0) return 0;
  int64_t held = g_obs_total < cap ? g_obs_total : cap;
  int64_t n = held < max_n ? held : max_n;
  /* oldest-first: when the ring wrapped, the oldest held event sits at
   * g_obs_total % cap; copy the NEWEST n of the held events in order */
  int64_t first = g_obs_total - n;  // index of the oldest copied event
  for (int64_t i = 0; i < n; i++)
    out[i] = g_obs_ring[(size_t)((first + i) % cap)];
  /* held events the caller's buffer could not take (e.g. appended
   * between its count probe and this drain) are COUNTED, never lost
   * silently — the exact-drop-accounting contract */
  g_obs_dropped += held - n;
  g_obs_total = 0;  // drain clears held events; dropped survives
  return n;
}

int64_t tpucomm_obs_peek(TpuObsEvent* out, int64_t max_n, int64_t* cursor,
                         int64_t* out_skipped) {
  std::lock_guard<std::mutex> lock(g_obs_mu);
  if (out_skipped) *out_skipped = 0;
  if (!cursor) return 0;
  const int64_t cap = (int64_t)g_obs_ring.size();
  if (cap == 0 || max_n <= 0) return 0;
  int64_t held = g_obs_total < cap ? g_obs_total : cap;
  /* the held events occupy the absolute sequence range
   * [g_obs_seq - held, g_obs_seq); anything older was overwritten by
   * overflow or cleared by a destructive drain */
  int64_t oldest = g_obs_seq - held;
  int64_t cur = *cursor;
  if (cur < 0) cur = 0;
  if (cur > g_obs_seq) cur = oldest;  // cursor from before a re-enable
  if (cur < oldest) {
    if (out_skipped) *out_skipped = oldest - cur;
    cur = oldest;
  }
  int64_t avail = g_obs_seq - cur;
  int64_t n = avail < max_n ? avail : max_n;
  for (int64_t i = 0; i < n; i++) {
    /* slot of sequence number s: the newest held event (s = seq-1)
     * sits at (g_obs_total - 1) % cap and slots run backwards from
     * there — valid for every s >= oldest because drain resets
     * g_obs_total and g_obs_seq never moves backwards */
    int64_t s = cur + i;
    out[i] = g_obs_ring[(size_t)((g_obs_total - (g_obs_seq - s)) % cap)];
  }
  *cursor = cur + n;
  return n;
}

double tpucomm_obs_clock(void) { return now_s(); }

void tpucomm_link_counters(int64_t* retries, int64_t* reconnects,
                           int64_t* dup_dropped, int64_t* crc_errors,
                           int64_t* replayed, int64_t* heartbeats) {
  /* process totals, monotone since load; all zero unless armed (the
   * counters only increment on armed paths).  The symbol itself doubles
   * as the bridge's layout probe for the 80-byte TpuObsEvent. */
  if (retries) *retries = g_lc_retries.load(std::memory_order_relaxed);
  if (reconnects)
    *reconnects = g_lc_reconnects.load(std::memory_order_relaxed);
  if (dup_dropped)
    *dup_dropped = g_lc_dup_dropped.load(std::memory_order_relaxed);
  if (crc_errors)
    *crc_errors = g_lc_crc_errors.load(std::memory_order_relaxed);
  if (replayed) *replayed = g_lc_replayed.load(std::memory_order_relaxed);
  if (heartbeats)
    *heartbeats = g_lc_heartbeats.load(std::memory_order_relaxed);
}

int tpucomm_reduce(int64_t h, const void* sendbuf, void* recvbuf,
                   int64_t count, int dtype, int op, int root) {
  Comm* c = get_comm(h);
  if (!c) return 1;
  EngineOp eop;
  eop.kind = TPU_OBS_REDUCE;
  eop.sbuf = sendbuf;
  eop.rbuf = recvbuf;
  eop.count = count;
  eop.dtype = dtype;
  eop.rop = op;
  eop.peer = root;
  return engine_submit(c, &eop);
}

int tpucomm_scan(int64_t h, const void* sendbuf, void* recvbuf,
                 int64_t count, int dtype, int op) {
  Comm* c = get_comm(h);
  if (!c) return 1;
  EngineOp eop;
  eop.kind = TPU_OBS_SCAN;
  eop.sbuf = sendbuf;
  eop.rbuf = recvbuf;
  eop.count = count;
  eop.dtype = dtype;
  eop.rop = op;
  return engine_submit(c, &eop);
}

/* ---- batched dispatch entry (the Python bridge's descriptor hop) ---- */

int tpucomm_execute(int64_t h, const struct TpuOpExec* d) {
  Comm* c = get_comm(h);
  if (!c || !d) return 1;
  EngineOp op;
  op.kind = d->kind;
  op.sbuf = d->sbuf;
  op.rbuf = d->rbuf;
  op.snb = d->snbytes;
  op.rnb = d->rnbytes;
  op.count = d->count;
  op.dtype = d->dtype;
  op.rop = d->rop;
  op.peer = d->peer;
  op.peer2 = d->peer2;
  op.tag = d->tag;
  op.tag2 = d->tag2;  // sendrecv: the bridge sets tag2 == tag
  op.algo = d->algo;
  return engine_submit(c, &op);
}

/* ---- ticketed non-blocking posting (schedule-plan execution) ----
 *
 * The descriptor is heap-allocated and FORCE-QUEUED (never run inline,
 * even on an idle engine): the whole point is returning to the caller
 * before the op completes, so the progress thread can read/write the
 * wire while the host computes.  The queue drains FIFO, so post order
 * is wire order — the exact model the schedule compiler's equivalence
 * prover verified before any plan reaches this entry point. */

int64_t tpucomm_post(int64_t h, const struct TpuOpExec* d) {
  Comm* c = get_comm(h);
  if (!c || !d) return 0;
  auto* op = new EngineOp;
  op->kind = d->kind;
  op->comm = c;
  op->sbuf = d->sbuf;
  op->rbuf = d->rbuf;
  op->snb = d->snbytes;
  op->rnb = d->rnbytes;
  op->count = d->count;
  op->dtype = d->dtype;
  op->rop = d->rop;
  op->peer = d->peer;
  op->peer2 = d->peer2;
  op->tag = d->tag;
  op->tag2 = d->tag2;
  op->algo = d->algo;
  Comm* root = c->lock_root;
  std::lock_guard<std::mutex> lock(comm_mu(c));
  Engine* e = root->engine;
  if (e && e->sticky.load(std::memory_order_acquire)) {
    delete op;
    std::fprintf(stderr,
                 "tpucomm r%d: post rejected — an earlier asynchronously "
                 "posted send failed (see the diagnostic above)\n",
                 c->rank);
    return 0;
  }
  op->t_post = now_s();
  if (!progress_thread_on()) {
    /* engine off: execute inline now; the ticket is already complete,
     * so plan execution degrades to the historic serialized order
     * bit-for-bit (MPI4JAX_TPU_PLAN composes with PROGRESS_THREAD=0) */
    op->rc = engine_run_body(op);
    op->state.store(1, std::memory_order_release);
    return reinterpret_cast<int64_t>(op);
  }
  engine_post(root, op);
  return reinterpret_cast<int64_t>(op);
}

int tpucomm_wait_ticket(int64_t h, int64_t ticket) {
  (void)h;  // the ticket IS the descriptor; the handle is for symmetry
  if (!ticket) return 1;
  auto* op = reinterpret_cast<EngineOp*>(ticket);
  while (op->state.load(std::memory_order_acquire) == 0)
    shm_futex_wait(&op->state, 0, 100);
  int rc = op->rc;
  delete op;
  return rc;
}

}  /* extern "C" */
