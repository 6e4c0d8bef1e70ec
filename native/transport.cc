// geomx_tpu native transport core.
//
// The C++ counterpart of the Python van's socket layer — the role ZMQVan
// plays for ps-lite in the reference (3rdparty/ps-lite/src/zmq_van.h:41-516:
// Bind/Connect/SendMsg/RecvMsg over persistent per-peer connections), built
// on raw POSIX TCP sockets instead of ZeroMQ.
//
// Scope: frame transport only. It owns
//   - the listener socket + accept thread,
//   - one reader thread per inbound connection, each parsing frame
//     boundaries (17-byte preheader | meta | u32 ndata | {u32 len|part}*)
//     and enqueueing complete frames,
//   - an inbound frame queue drained by the host (Python) through
//     gx_recv,
//   - outbound connections dialed lazily per destination id and cached
//     (reference: zmq_van.h:160-196 Connect caches per-id sockets),
//   - eviction + single redial on send failure (peer restart recovery).
//
// Routing, rendezvous, barriers, and message semantics stay in the host —
// this layer never inspects the JSON meta, only the fixed preheader.
//
// Wire format (must match geomx_tpu/ps/message.py):
//   u32 magic "GEOM" | i32 recver | u8 flags | i32 priority | u32 meta_len
//   | meta bytes | u32 ndata | { u32 len | bytes } * ndata
// all little-endian, no padding (preheader is 17 bytes).
//
// Who owns a frame's memory:
//   - outbound, gx_sendv writes the caller's buffers as they lie (the
//     prefix, then a 4-byte length and the part's own memory for each
//     part) in a sendmsg loop; they are the caller's before, during and
//     after the call, and no joined frame exists. gx_send takes one joined
//     buffer (control messages, the one-shot registration send).
//   - inbound, a reader thread reads a frame ONCE into one malloc'ed
//     block, placed so that the first part's data is 16-byte aligned
//     (never zero-filled; grown with realloc as the parts' lengths arrive
//     unless a block the host has released is large enough already, which
//     after a first round it is), and queues it. gx_recv hands that very
//     memory to the host: no copy in this file. From then on it is the
//     host's, to be released with gx_free exactly once (ps/native.py does
//     so when the last view of the frame is dropped); gx_free keeps the
//     largest few released blocks for the frames to come (BlockPool). A
//     frame still queued at Stop is freed here.

#include <arpa/inet.h>
#include <netdb.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <sys/socket.h>
#include <sys/uio.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <condition_variable>
#include <climits>
#include <cstddef>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <functional>
#include <map>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

namespace {

constexpr uint32_t kMagic = 0x47454F4D;  // "GEOM"
constexpr size_t kPrehdrSize = 4 + 4 + 1 + 4 + 4;
constexpr size_t kMaxFrame = size_t(1) << 31;  // 2 GiB sanity bound
constexpr size_t kMaxParts = 1 << 20;

std::atomic<uint64_t> g_frames_freed{0};

int SetNoDelay(int fd) {
  int one = 1;
  return setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
}

bool SendAll(int fd, const uint8_t* buf, size_t len) {
  size_t off = 0;
  while (off < len) {
    ssize_t n = ::send(fd, buf + off, len - off, MSG_NOSIGNAL);
    if (n <= 0) {
      if (n < 0 && (errno == EINTR)) continue;
      return false;
    }
    off += size_t(n);
  }
  return true;
}

// Gathered write of n buffers, in order, whole: sendmsg in batches of
// at most IOV_MAX entries, resumed inside a buffer after a short write.
bool SendAllV(int fd, const uint8_t* const* bufs, const uint64_t* lens,
              size_t n) {
  constexpr size_t kBatch = IOV_MAX < 1024 ? IOV_MAX : 1024;
  struct iovec iov[kBatch];
  size_t i = 0;      // first buffer not yet fully written
  size_t done = 0;   // bytes of buffer i already written
  while (i < n) {
    size_t cnt = 0;
    for (size_t j = i; j < n && cnt < kBatch; ++j) {
      size_t skip = j == i ? done : 0;
      if (lens[j] == skip) continue;
      iov[cnt].iov_base = const_cast<uint8_t*>(bufs[j]) + skip;
      iov[cnt].iov_len = size_t(lens[j]) - skip;
      ++cnt;
    }
    if (cnt == 0) break;  // only empty buffers were left
    struct msghdr mh{};
    mh.msg_iov = iov;
    mh.msg_iovlen = cnt;
    ssize_t w = ::sendmsg(fd, &mh, MSG_NOSIGNAL);
    if (w <= 0) {
      if (w < 0 && errno == EINTR) continue;
      return false;
    }
    size_t left = size_t(w);
    while (i < n && left >= size_t(lens[i]) - done) {
      left -= size_t(lens[i]) - done;
      done = 0;
      ++i;
    }
    done += left;
  }
  return true;
}

bool RecvExact(int fd, uint8_t* buf, size_t len) {
  size_t off = 0;
  while (off < len) {
    ssize_t n = ::recv(fd, buf + off, len - off, 0);
    if (n <= 0) {
      if (n < 0 && errno == EINTR) continue;
      return false;
    }
    off += size_t(n);
  }
  return true;
}

// Resolve host (IPv4 literal or DNS name) into addr. The Python backend
// resolves via getaddrinfo inside socket.connect; the native path must
// accept the same host strings.
bool ResolveIpv4(const char* host, int port, sockaddr_in* addr) {
  std::memset(addr, 0, sizeof(*addr));
  addr->sin_family = AF_INET;
  addr->sin_port = htons(uint16_t(port));
  if (inet_pton(AF_INET, host, &addr->sin_addr) == 1) return true;
  struct addrinfo hints{};
  hints.ai_family = AF_INET;
  hints.ai_socktype = SOCK_STREAM;
  struct addrinfo* res = nullptr;
  if (getaddrinfo(host, nullptr, &hints, &res) != 0 || res == nullptr)
    return false;
  addr->sin_addr = reinterpret_cast<sockaddr_in*>(res->ai_addr)->sin_addr;
  freeaddrinfo(res);
  return true;
}

int DialTcp(const char* host, int port, double timeout_s) {
  sockaddr_in addr{};
  if (!ResolveIpv4(host, port, &addr)) return -1;
  int fd = ::socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return -1;
  if (timeout_s > 0) {
    struct timeval tv;
    tv.tv_sec = long(timeout_s);
    tv.tv_usec = long((timeout_s - double(tv.tv_sec)) * 1e6);
    setsockopt(fd, SOL_SOCKET, SO_SNDTIMEO, &tv, sizeof(tv));
  }
  if (::connect(fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) != 0) {
    ::close(fd);
    return -1;
  }
  SetNoDelay(fd);
  return fd;
}

// One inbound frame: `len` bytes at `buf`, which lies a few bytes (under
// kAlign) into a block's room; gx_free finds the block again by rounding
// down (see the header comment for who calls it).
struct Frame {
  uint8_t* buf = nullptr;
  size_t len = 0;
};

// malloc's own guarantee on this platform: what a frame's first part is
// aligned to, and what lets RoomOf undo a frame's shift.
constexpr size_t kAlign = 16;
static_assert(alignof(std::max_align_t) >= kAlign, "malloc alignment");

// A block: kAlign bytes that hold its capacity, then that many bytes of
// room, malloc'ed as one.
struct Block {
  uint8_t* room = nullptr;
  size_t cap = 0;
};

uint8_t* RoomOf(uint8_t* frame) {
  return reinterpret_cast<uint8_t*>(reinterpret_cast<uintptr_t>(frame) &
                                    ~uintptr_t(kAlign - 1));
}

void FreeBlock(uint8_t* room) {
  if (room) ::free(room - kAlign);
}

// Blocks whose frames the host has released, kept for the frames to come:
// a round's frames are about as large as the last round's, and a block
// that has held one has the room, with its pages already touched, so the
// next one is read without growing (a copy of what was read so far, below
// malloc's mmap threshold) and without page faults (above it). The kSlots
// largest are kept, process-wide; the rest go back to malloc.
class BlockPool {
 public:
  static BlockPool& Get() {
    static BlockPool* pool = new BlockPool;  // never destroyed: the host's
    return *pool;                            // finalisers may outlive main
  }

  // The largest block kept, if it has room for `need`; else none.
  Block Take(size_t need) {
    std::lock_guard<std::mutex> lk(mu_);
    auto it = std::max_element(
        kept_.begin(), kept_.end(),
        [](const Block& a, const Block& b) { return a.cap < b.cap; });
    if (it == kept_.end() || it->cap < need) return Block{};
    Block got = *it;
    kept_.erase(it);
    return got;
  }

  void Give(uint8_t* room) {
    Block b{room, 0};
    std::memcpy(&b.cap, room - kAlign, sizeof(b.cap));
    {
      std::lock_guard<std::mutex> lk(mu_);
      kept_.push_back(b);
      if (kept_.size() <= kSlots) return;
      auto it = std::min_element(
          kept_.begin(), kept_.end(),
          [](const Block& a, const Block& b) { return a.cap < b.cap; });
      b = *it;
      kept_.erase(it);
    }
    FreeBlock(b.room);
  }

 private:
  static constexpr size_t kSlots = 8;
  std::mutex mu_;
  std::vector<Block> kept_;
};

// Make room for `need` bytes in a block of which `used` are written. A
// block that has to grow (a frame with parts: the frame's header carries
// no total) first looks for a released block that is large enough; else
// it doubles, so that a frame of many parts reallocates a logarithmic
// number of times. Nothing is zero-filled; RecvExact overwrites it.
bool Reserve(Block* b, size_t used, size_t need) {
  if (need <= b->cap) return true;
  if (b->room) {
    Block got = BlockPool::Get().Take(need);
    if (got.room) {
      std::memcpy(got.room, b->room, used);
      FreeBlock(b->room);
      *b = got;
      return true;
    }
  }
  size_t want = std::max(need, b->cap * 2);
  auto* p = static_cast<uint8_t*>(
      ::realloc(b->room ? b->room - kAlign : nullptr, want + kAlign));
  if (!p) return false;
  std::memcpy(p, &want, sizeof(want));
  b->room = p + kAlign;
  b->cap = want;
  return true;
}

// Read one complete frame from fd, calling `begun` once its pre-header is
// in. Returns false on EOF/error (nothing is left allocated then). The
// frame is the wire's bytes, unpadded; it
// starts `shift` bytes into its block's room so that the FIRST part's
// data is kAlign-aligned. Parts whose sizes are multiples of 4 (float32
// values, int32 positions, the int64 header arrays) then all start
// 4-aligned, and numpy reads arrays over them on its aligned paths.
bool ReadFrame(int fd, Frame* out, const std::function<void()>& begun) {
  uint8_t hdr[kPrehdrSize];
  if (!RecvExact(fd, hdr, kPrehdrSize)) return false;
  uint32_t magic, meta_len;
  std::memcpy(&magic, hdr, 4);
  std::memcpy(&meta_len, hdr + 13, 4);
  if (magic != kMagic) return false;
  if (meta_len > kMaxFrame) return false;
  begun();  // a frame is arriving: the host may start its clock
  size_t len = kPrehdrSize + meta_len + 4;  // the prefix
  const size_t shift = (kAlign - (len + 4) % kAlign) % kAlign;
  Block b;
  len += shift;  // from here on, offsets into the room
  bool ok = Reserve(&b, 0, len);
  if (ok) {
    std::memcpy(b.room + shift, hdr, kPrehdrSize);
    ok = RecvExact(fd, b.room + shift + kPrehdrSize, meta_len + 4);
  }
  uint32_t ndata = 0;
  if (ok) {
    std::memcpy(&ndata, b.room + len - 4, 4);
    ok = ndata <= kMaxParts;
  }
  for (uint32_t i = 0; ok && i < ndata; ++i) {
    // the part's length lands where it belongs in the frame
    ok = Reserve(&b, len, len + 4) && RecvExact(fd, b.room + len, 4);
    if (!ok) break;
    uint32_t n;
    std::memcpy(&n, b.room + len, 4);
    len += 4;
    ok = n <= kMaxFrame && len + n <= kMaxFrame &&
         Reserve(&b, len, len + n) &&
         (n == 0 || RecvExact(fd, b.room + len, n));
    len += n;
  }
  if (!ok) {
    FreeBlock(b.room);
    return false;
  }
  if (b.cap / 4 > len) {
    // a small frame that took a large released block: move it out, so
    // that whoever keeps a view of it pins its own size and not a large
    // frame's (doubling alone stays under 2x; this keeps it under 4x)
    Block exact;
    if (Reserve(&exact, 0, len)) {
      std::memcpy(exact.room, b.room, len);
      BlockPool::Get().Give(b.room);
      b = exact;
    }
  }
  out->buf = b.room + shift;
  out->len = len - shift;
  return true;
}

struct Route {
  std::string host;
  int port = 0;
  int fd = -1;
  std::mutex send_mu;
};

class Transport {
 public:
  Transport(const char* bind_host, int port)
      : bind_host_(bind_host ? bind_host : "127.0.0.1") {
    listener_ = ::socket(AF_INET, SOCK_STREAM, 0);
    int one = 1;
    setsockopt(listener_, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
    sockaddr_in addr{};
    if (!ResolveIpv4(bind_host_.c_str(), port, &addr) ||
        ::bind(listener_, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) != 0 ||
        ::listen(listener_, 128) != 0) {
      ::close(listener_);
      listener_ = -1;
      return;
    }
    sockaddr_in got{};
    socklen_t gl = sizeof(got);
    getsockname(listener_, reinterpret_cast<sockaddr*>(&got), &gl);
    port_ = ntohs(got.sin_port);
    accept_thread_ = std::thread([this] { AcceptLoop(); });
  }

  ~Transport() { Stop(); }

  bool ok() const { return listener_ >= 0; }
  int port() const { return port_; }

  // fd discipline (one process hosts many transports, so a stale close()
  // on a reused fd NUMBER can kill an unrelated van's socket):
  //  - a route's fd is closed only under its send_mu (Send also closes
  //    there on failure);
  //  - a reader's fd is closed exactly once, by its own reader thread,
  //    under readers_mu_; Stop only shutdown()s fds still listed there;
  //  - reader threads are joined outside readers_mu_ (they need it to
  //    deregister their fd on exit).
  void Stop() {
    bool was = stopped_.exchange(true);
    if (was) return;
    {
      std::lock_guard<std::mutex> lk(queue_mu_);
      queue_cv_.notify_all();
    }
    if (listener_ >= 0) ::shutdown(listener_, SHUT_RDWR);
    if (accept_thread_.joinable()) accept_thread_.join();
    // close only after the join: closing first frees the fd number for
    // reuse while the accept thread may still be entering ::accept on it
    if (listener_ >= 0) ::close(listener_);
    // no new readers can appear past this point
    {
      std::lock_guard<std::mutex> lk(readers_mu_);
      for (int fd : reader_fds_) ::shutdown(fd, SHUT_RDWR);
    }
    std::vector<std::thread> readers;
    {
      std::lock_guard<std::mutex> lk(readers_mu_);
      readers.swap(reader_threads_);
    }
    for (auto& t : readers)
      if (t.joinable()) t.join();
    {
      // frames nobody took: theirs was never handed over
      std::lock_guard<std::mutex> lk(queue_mu_);
      for (auto& f : queue_) FreeBlock(RoomOf(f.buf));
      queue_.clear();
    }
    std::vector<std::shared_ptr<Route>> routes;
    {
      std::lock_guard<std::mutex> lk(routes_mu_);
      for (auto& kv : routes_) routes.push_back(kv.second);
      routes_.clear();
    }
    for (auto& r : routes) {
      std::lock_guard<std::mutex> lk(r->send_mu);
      if (r->fd >= 0) {
        ::close(r->fd);
        r->fd = -1;
      }
    }
  }

  // Register/refresh the route for a node id; evicts a cached connection
  // if the address changed (peer recovered elsewhere — reference:
  // van.cc:176-193 + the Python van's _evict_conn on table update).
  void SetRoute(int id, const char* host, int port) {
    std::shared_ptr<Route> stale;
    {
      std::lock_guard<std::mutex> lk(routes_mu_);
      auto it = routes_.find(id);
      if (it != routes_.end()) {
        if (it->second->host == host && it->second->port == port) return;
        stale = it->second;
        routes_.erase(it);
      }
      auto r = std::make_shared<Route>();
      r->host = host;
      r->port = port;
      routes_[id] = std::move(r);
    }
    if (stale) {
      std::lock_guard<std::mutex> lk(stale->send_mu);
      if (stale->fd >= 0) {
        ::close(stale->fd);
        stale->fd = -1;
      }
    }
  }

  // Framed send with connection reuse and one redial on failure: the
  // n buffers, in order, are one frame. A write that fails part-way
  // drops the connection, and the redial sends the frame whole.
  int64_t Send(int id, const uint8_t* const* bufs, const uint64_t* lens,
               size_t n) {
    std::shared_ptr<Route> r;
    {
      std::lock_guard<std::mutex> lk(routes_mu_);
      auto it = routes_.find(id);
      if (it == routes_.end()) return -2;  // no route
      r = it->second;
    }
    uint64_t len = 0;
    for (size_t i = 0; i < n; ++i) len += lens[i];
    std::lock_guard<std::mutex> lk(r->send_mu);
    for (int attempt = 0; attempt < 2; ++attempt) {
      if (r->fd >= 0) {
        // probe for a half-closed peer: connections are unidirectional
        // (dialer writes, acceptor reads), so any readable byte/EOF on
        // our outbound socket means the peer went away — redial instead
        // of losing the frame in a dead send buffer
        char probe;
        ssize_t p = ::recv(r->fd, &probe, 1, MSG_PEEK | MSG_DONTWAIT);
        if (p == 0 || (p < 0 && errno != EAGAIN && errno != EWOULDBLOCK)) {
          ::close(r->fd);
          r->fd = -1;
        }
      }
      if (r->fd < 0) {
        r->fd = DialTcp(r->host.c_str(), r->port, 10.0);
        if (r->fd < 0) {
          if (debug()) {
            fprintf(stderr, "gx_send: dial %s:%d for node %d failed: %s\n",
                    r->host.c_str(), r->port, id, strerror(errno));
          }
          continue;
        }
      }
      if (SendAllV(r->fd, bufs, lens, n)) {
        send_bytes_ += len;
        return int64_t(len);
      }
      if (debug()) {
        fprintf(stderr, "gx_send: write to node %d (%s:%d) failed: %s\n", id,
                r->host.c_str(), r->port, strerror(errno));
      }
      ::close(r->fd);
      r->fd = -1;
    }
    return -1;
  }

  static bool debug() {
    static const bool on = [] {
      const char* v = getenv("GEOMX_NATIVE_DEBUG");
      return v && v[0] == '1';
    }();
    return on;
  }

  // One-shot connect+send+close (pre-rendezvous registration).
  int64_t SendToAddr(const char* host, int port, const uint8_t* buf,
                     size_t len) {
    int fd = DialTcp(host, port, 10.0);
    if (fd < 0) return -1;
    bool ok = SendAll(fd, buf, len);
    ::close(fd);
    if (!ok) return -1;
    send_bytes_ += len;
    return int64_t(len);
  }

  // Pop one complete inbound frame. Returns:
  //   >=0 frame length; *out is the buffer the reader filled, the
  //       caller's from here on (gx_free)
  //   -1 timeout, -2 stopped.
  int64_t Recv(uint8_t** out, double timeout_s) {
    std::unique_lock<std::mutex> lk(queue_mu_);
    auto pred = [this] { return !queue_.empty() || stopped_.load(); };
    if (timeout_s < 0) {
      queue_cv_.wait(lk, pred);
    } else {
      if (!queue_cv_.wait_for(
              lk, std::chrono::duration<double>(timeout_s), pred))
        return -1;
    }
    if (queue_.empty()) return stopped_.load() ? -2 : -1;
    Frame frame = queue_.front();
    queue_.pop_front();
    --arriving_;
    lk.unlock();
    *out = frame.buf;
    return int64_t(frame.len);
  }

  // Block until a frame has BEGUN to arrive (its pre-header is in; it may
  // be complete and queued) and nobody has taken it yet: 1; 0 on timeout,
  // -2 stopped. The host opens its receive span here, so that the span
  // covers the rest of the read, which Recv then waits for.
  int Wait(double timeout_s) {
    std::unique_lock<std::mutex> lk(queue_mu_);
    queue_cv_.wait_for(lk, std::chrono::duration<double>(timeout_s),
                       [this] { return arriving_ > 0 || stopped_.load(); });
    if (arriving_ > 0) return 1;
    return stopped_.load() ? -2 : 0;
  }

  uint64_t send_bytes() const { return send_bytes_.load(); }
  uint64_t recv_bytes() const { return recv_bytes_.load(); }

 private:
  void AcceptLoop() {
    while (!stopped_.load()) {
      sockaddr_in peer{};
      socklen_t pl = sizeof(peer);
      int fd = ::accept(listener_, reinterpret_cast<sockaddr*>(&peer), &pl);
      if (fd < 0) {
        if (stopped_.load()) return;
        if (errno == EINTR) continue;
        return;
      }
      SetNoDelay(fd);
      std::lock_guard<std::mutex> lk(readers_mu_);
      reader_fds_.push_back(fd);
      reader_threads_.emplace_back([this, fd] { ReaderLoop(fd); });
    }
  }

  void ReaderLoop(int fd) {
    Frame frame;
    while (!stopped_.load()) {
      bool counted = false;
      auto begun = [&] {
        std::lock_guard<std::mutex> lk(queue_mu_);
        ++arriving_;
        counted = true;
        queue_cv_.notify_all();
      };
      if (!ReadFrame(fd, &frame, begun)) {
        if (counted) {
          std::lock_guard<std::mutex> lk(queue_mu_);
          --arriving_;  // the frame that began will never be whole
        }
        break;
      }
      recv_bytes_ += frame.len;
      std::lock_guard<std::mutex> lk(queue_mu_);
      queue_.push_back(frame);
      queue_cv_.notify_all();
    }
    // close + deregister atomically so Stop never shutdown()s a reused
    // fd number
    std::lock_guard<std::mutex> lk(readers_mu_);
    ::close(fd);
    reader_fds_.erase(
        std::find(reader_fds_.begin(), reader_fds_.end(), fd));
  }

  std::string bind_host_;
  int listener_ = -1;
  int port_ = 0;
  std::atomic<bool> stopped_{false};

  std::thread accept_thread_;
  std::mutex readers_mu_;
  std::vector<std::thread> reader_threads_;
  std::vector<int> reader_fds_;

  std::mutex routes_mu_;
  std::map<int, std::shared_ptr<Route>> routes_;

  std::mutex queue_mu_;
  std::condition_variable queue_cv_;
  std::deque<Frame> queue_;
  int arriving_ = 0;  // frames begun or queued, not yet taken (queue_mu_)

  std::atomic<uint64_t> send_bytes_{0};
  std::atomic<uint64_t> recv_bytes_{0};
};

}  // namespace

extern "C" {

void* gx_create(const char* bind_host, int port) {
  auto* t = new Transport(bind_host, port);
  if (!t->ok()) {
    delete t;
    return nullptr;
  }
  return t;
}

int gx_port(void* h) { return static_cast<Transport*>(h)->port(); }

void gx_set_route(void* h, int id, const char* host, int port) {
  static_cast<Transport*>(h)->SetRoute(id, host, port);
}

int64_t gx_send(void* h, int id, const uint8_t* buf, uint64_t len) {
  return static_cast<Transport*>(h)->Send(id, &buf, &len, 1);
}

// One frame from n buffers of the caller's, written as they lie.
int64_t gx_sendv(void* h, int id, const uint8_t* const* bufs,
                 const uint64_t* lens, uint64_t n) {
  return static_cast<Transport*>(h)->Send(id, bufs, lens, size_t(n));
}

int64_t gx_send_addr(void* h, const char* host, int port, const uint8_t* buf,
                     uint64_t len) {
  return static_cast<Transport*>(h)->SendToAddr(host, port, buf, size_t(len));
}

int64_t gx_recv(void* h, uint8_t** out, double timeout_s) {
  return static_cast<Transport*>(h)->Recv(out, timeout_s);
}

int gx_wait(void* h, double timeout_s) {
  return static_cast<Transport*>(h)->Wait(timeout_s);
}

void gx_free(uint8_t* buf) {
  BlockPool::Get().Give(RoomOf(buf));
  ++g_frames_freed;
}

// Frames released through gx_free, process-wide (tests count them).
uint64_t gx_frames_freed() { return g_frames_freed.load(); }

uint64_t gx_send_bytes(void* h) {
  return static_cast<Transport*>(h)->send_bytes();
}

uint64_t gx_recv_bytes(void* h) {
  return static_cast<Transport*>(h)->recv_bytes();
}

void gx_stop(void* h) { static_cast<Transport*>(h)->Stop(); }

void gx_destroy(void* h) { delete static_cast<Transport*>(h); }

}  // extern "C"
