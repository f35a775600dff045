// paddle_tpu native runtime — C ABI, loaded via ctypes.
//
// Capability parity with the reference's native runtime pieces that remain
// host-side on TPU (the device path is XLA's):
//   * TCPStore — rendezvous key/value store for multi-host bootstrap
//     (reference: paddle/phi/core/distributed/store/tcp_store.h:121 +
//     socket.cpp; used by init_parallel_env — parallel.py:1134).
//     Protocol here: length-prefixed cmd frames over TCP; commands
//     SET/GET/WAIT/ADD with blocking WAIT, matching the reference's
//     semantics (set/get/wait/add — tcp_store.h).
//   * Batch collation engine — GIL-free parallel gather of sample rows into
//     contiguous batch buffers with a prefetch thread pool (the role of the
//     reference's shared-memory DataLoader worker transport —
//     python/paddle/io/dataloader/worker.py + fluid/framework/data_feed.h).
//
// Build: paddle_tpu/lib does it on first use (g++ -O2 -shared -fPIC -pthread
// ptpu_runtime.cpp -o libptpu_runtime.<source hash>.so); never tracked.
#include <arpa/inet.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <sys/socket.h>
#include <unistd.h>

#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <functional>
#include <map>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

// ---------------------------------------------------------------------------
// TCPStore
// ---------------------------------------------------------------------------

namespace {

enum Cmd : uint8_t { kSet = 0, kGet = 1, kWait = 2, kAdd = 3, kStop = 4 };

bool send_all(int fd, const void* buf, size_t n) {
  const char* p = static_cast<const char*>(buf);
  while (n) {
    ssize_t w = ::send(fd, p, n, MSG_NOSIGNAL);
    if (w <= 0) return false;
    p += w;
    n -= static_cast<size_t>(w);
  }
  return true;
}

bool recv_all(int fd, void* buf, size_t n) {
  char* p = static_cast<char*>(buf);
  while (n) {
    ssize_t r = ::recv(fd, p, n, 0);
    if (r <= 0) return false;
    p += r;
    n -= static_cast<size_t>(r);
  }
  return true;
}

bool send_bytes(int fd, const std::string& s) {
  uint32_t len = static_cast<uint32_t>(s.size());
  return send_all(fd, &len, 4) && (len == 0 || send_all(fd, s.data(), len));
}

// Cap accepted frame length: a malformed/hostile length prefix must not
// trigger a multi-GiB allocation (keys and rendezvous blobs are small).
constexpr uint32_t kMaxFrameBytes = 64u << 20;

bool recv_bytes(int fd, std::string* out) {
  uint32_t len = 0;
  if (!recv_all(fd, &len, 4)) return false;
  if (len > kMaxFrameBytes) return false;
  out->resize(len);
  return len == 0 || recv_all(fd, &(*out)[0], len);
}

struct StoreServer {
  int listen_fd = -1;
  int port = 0;
  std::thread accept_thread;
  std::vector<std::thread> client_threads;
  std::vector<int> client_fds;
  std::mutex fds_mu;
  std::mutex mu;
  std::condition_variable cv;
  std::map<std::string, std::string> kv;
  std::atomic<bool> stop{false};

  void handle_client(int fd) {
    for (;;) {
      uint8_t cmd;
      if (!recv_all(fd, &cmd, 1)) break;
      if (cmd == kStop) break;
      std::string key;
      if (!recv_bytes(fd, &key)) break;
      if (cmd == kSet) {
        std::string val;
        if (!recv_bytes(fd, &val)) break;
        {
          std::lock_guard<std::mutex> g(mu);
          kv[key] = val;
        }
        cv.notify_all();
        // Ack after the store is applied: without it, set() returning on the
        // sender does not order before a get() on another connection.
        uint8_t ok = 1;
        if (!send_all(fd, &ok, 1)) break;
      } else if (cmd == kGet) {
        std::string val;
        uint8_t found = 0;
        {
          std::lock_guard<std::mutex> g(mu);
          auto it = kv.find(key);
          if (it != kv.end()) {
            val = it->second;
            found = 1;
          }
        }
        if (!send_all(fd, &found, 1)) break;
        if (found && !send_bytes(fd, val)) break;
        if (!found && !send_bytes(fd, std::string())) break;
      } else if (cmd == kWait) {
        std::string val;
        {
          std::unique_lock<std::mutex> g(mu);
          cv.wait(g, [&] { return stop.load() || kv.count(key) > 0; });
          if (stop.load()) break;
          val = kv[key];
        }
        if (!send_bytes(fd, val)) break;
      } else if (cmd == kAdd) {
        std::string delta_s;
        if (!recv_bytes(fd, &delta_s)) break;
        if (delta_s.size() != sizeof(int64_t)) break;
        int64_t delta = 0, cur = 0;
        std::memcpy(&delta, delta_s.data(), sizeof(int64_t));
        {
          std::lock_guard<std::mutex> g(mu);
          std::string& v = kv[key];
          if (v.size() == sizeof(int64_t)) std::memcpy(&cur, v.data(), 8);
          cur += delta;
          v.assign(reinterpret_cast<const char*>(&cur), sizeof(int64_t));
        }
        cv.notify_all();
        if (!send_all(fd, &cur, 8)) break;
      }
    }
    ::close(fd);
  }

  bool start(int want_port, const char* bind_addr) {
    listen_fd = ::socket(AF_INET, SOCK_STREAM, 0);
    if (listen_fd < 0) return false;
    int one = 1;
    ::setsockopt(listen_fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    // Default: all interfaces, so other hosts can rendezvous (reference
    // TCPStore listens on INADDR_ANY — tcp_utils.cc tcpListen).
    addr.sin_addr.s_addr = htonl(INADDR_ANY);
    if (bind_addr && bind_addr[0] &&
        ::inet_pton(AF_INET, bind_addr, &addr.sin_addr) != 1)
      return false;
    addr.sin_port = htons(static_cast<uint16_t>(want_port));
    if (::bind(listen_fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) != 0)
      return false;
    socklen_t alen = sizeof(addr);
    ::getsockname(listen_fd, reinterpret_cast<sockaddr*>(&addr), &alen);
    port = ntohs(addr.sin_port);
    if (::listen(listen_fd, 64) != 0) return false;
    accept_thread = std::thread([this] {
      for (;;) {
        int fd = ::accept(listen_fd, nullptr, nullptr);
        if (fd < 0) break;
        if (stop.load()) {
          ::close(fd);
          break;
        }
        {
          std::lock_guard<std::mutex> g(fds_mu);
          client_fds.push_back(fd);
        }
        client_threads.emplace_back(&StoreServer::handle_client, this, fd);
      }
    });
    return true;
  }

  void shutdown() {
    stop.store(true);
    cv.notify_all();
    ::shutdown(listen_fd, SHUT_RDWR);
    ::close(listen_fd);
    {
      // unblock handler threads parked in recv on live connections
      std::lock_guard<std::mutex> g(fds_mu);
      for (int fd : client_fds) ::shutdown(fd, SHUT_RDWR);
    }
    if (accept_thread.joinable()) accept_thread.join();
    for (auto& t : client_threads)
      if (t.joinable()) t.join();
  }
};

struct StoreClient {
  int fd = -1;
  std::mutex mu;  // one request in flight per client

  bool connect_to(const char* host, int port, double timeout_s) {
    fd = ::socket(AF_INET, SOCK_STREAM, 0);
    if (fd < 0) return false;
    sockaddr_in addr{};
    addr.sin_family = AF_INET;
    addr.sin_port = htons(static_cast<uint16_t>(port));
    ::inet_pton(AF_INET, host, &addr.sin_addr);
    double waited = 0;
    while (::connect(fd, reinterpret_cast<sockaddr*>(&addr), sizeof(addr)) != 0) {
      if (waited >= timeout_s) return false;
      ::usleep(100000);
      waited += 0.1;
      ::close(fd);
      fd = ::socket(AF_INET, SOCK_STREAM, 0);
    }
    int one = 1;
    ::setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    return true;
  }
};

}  // namespace

extern "C" {

void* ptpu_store_server_start2(int port, const char* bind_addr);

void* ptpu_store_server_start(int port) {
  return ptpu_store_server_start2(port, nullptr);
}

// bind_addr: dotted-quad interface to bind, NULL/"" = all interfaces.
void* ptpu_store_server_start2(int port, const char* bind_addr) {
  auto* s = new StoreServer();
  if (!s->start(port, bind_addr)) {
    delete s;
    return nullptr;
  }
  return s;
}

int ptpu_store_server_port(void* h) { return static_cast<StoreServer*>(h)->port; }

void ptpu_store_server_stop(void* h) {
  auto* s = static_cast<StoreServer*>(h);
  s->shutdown();
  delete s;
}

void* ptpu_store_client_connect(const char* host, int port, double timeout_s) {
  auto* c = new StoreClient();
  if (!c->connect_to(host, port, timeout_s)) {
    delete c;
    return nullptr;
  }
  return c;
}

void ptpu_store_client_close(void* h) {
  auto* c = static_cast<StoreClient*>(h);
  uint8_t cmd = kStop;
  send_all(c->fd, &cmd, 1);
  ::close(c->fd);
  delete c;
}

int ptpu_store_set(void* h, const char* key, const char* val, int len) {
  auto* c = static_cast<StoreClient*>(h);
  std::lock_guard<std::mutex> g(c->mu);
  uint8_t cmd = kSet;
  if (!send_all(c->fd, &cmd, 1) || !send_bytes(c->fd, key) ||
      !send_bytes(c->fd, std::string(val, val + len)))
    return -1;
  uint8_t ok = 0;
  return recv_all(c->fd, &ok, 1) && ok == 1 ? 0 : -1;
}

// returns length, -1 if missing, -2 on error; caller buffer must be big enough
int ptpu_store_get(void* h, const char* key, char* out, int cap) {
  auto* c = static_cast<StoreClient*>(h);
  std::lock_guard<std::mutex> g(c->mu);
  uint8_t cmd = kGet;
  if (!send_all(c->fd, &cmd, 1) || !send_bytes(c->fd, key)) return -2;
  uint8_t found = 0;
  if (!recv_all(c->fd, &found, 1)) return -2;
  std::string val;
  if (!recv_bytes(c->fd, &val)) return -2;
  if (!found) return -1;
  int n = static_cast<int>(val.size());
  if (n > cap) return -2;
  std::memcpy(out, val.data(), val.size());
  return n;
}

int ptpu_store_wait(void* h, const char* key, char* out, int cap) {
  auto* c = static_cast<StoreClient*>(h);
  std::lock_guard<std::mutex> g(c->mu);
  uint8_t cmd = kWait;
  if (!send_all(c->fd, &cmd, 1) || !send_bytes(c->fd, key)) return -2;
  std::string val;
  if (!recv_bytes(c->fd, &val)) return -2;
  int n = static_cast<int>(val.size());
  if (n > cap) return -2;
  std::memcpy(out, val.data(), val.size());
  return n;
}

long long ptpu_store_add(void* h, const char* key, long long delta) {
  auto* c = static_cast<StoreClient*>(h);
  std::lock_guard<std::mutex> g(c->mu);
  uint8_t cmd = kAdd;
  int64_t d = delta;
  if (!send_all(c->fd, &cmd, 1) || !send_bytes(c->fd, key) ||
      !send_bytes(c->fd, std::string(reinterpret_cast<char*>(&d), 8)))
    return INT64_MIN;
  int64_t cur = 0;
  if (!recv_all(c->fd, &cur, 8)) return INT64_MIN;
  return cur;
}

// ---------------------------------------------------------------------------
// Batch collation engine: parallel row gather without the GIL.
// Gathers rows src[idx[i]] (row_bytes each) into dst[i] using nthreads.
// ---------------------------------------------------------------------------

void ptpu_gather_rows(const char* src, const long long* idx, int n_idx,
                      long long row_bytes, char* dst, int nthreads) {
  if (nthreads <= 1 || n_idx < 4 * nthreads) {
    for (int i = 0; i < n_idx; ++i)
      std::memcpy(dst + i * row_bytes, src + idx[i] * row_bytes,
                  static_cast<size_t>(row_bytes));
    return;
  }
  std::vector<std::thread> ts;
  int chunk = (n_idx + nthreads - 1) / nthreads;
  for (int t = 0; t < nthreads; ++t) {
    int lo = t * chunk, hi = std::min(n_idx, lo + chunk);
    if (lo >= hi) break;
    ts.emplace_back([=] {
      for (int i = lo; i < hi; ++i)
        std::memcpy(dst + i * row_bytes, src + idx[i] * row_bytes,
                    static_cast<size_t>(row_bytes));
    });
  }
  for (auto& t : ts) t.join();
}

}  // extern "C"
