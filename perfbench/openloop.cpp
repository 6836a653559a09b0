// Open-loop HTTP/1.1 load generator for the benchmark.
//
// Sends each request of a precomputed schedule at its due time, whatever
// the state of earlier requests, over `conns` keep-alive connections
// (pipelined; a request goes to the connection with the fewest in
// flight). Latency is the caller's to compute: done time minus due time,
// so a stall delays every request behind it on the clock.
//
// Derived from the program's closed-loop `pt_http_blast`
// (patrol_tpu/native/patrol_http.cpp): the same epoll loop and
// Content-Length framing, with the pipeline window replaced by a
// timerfd-driven schedule.
//
//   openloop HOST PORT CONNS SCHEDULE TARGETS OUT DRAIN_MS
//
// SCHEDULE: records {int64 due_ns since T0, uint32 target, uint32 pad}.
// TARGETS: one request path per line. OUT: records {int64 sent_ns,
// int64 done_ns (both since T0, -1 if never), int32 status (0 if none),
// int32 body as an integer (-1 if not one)}. Prints "T0 <CLOCK_MONOTONIC
// ns>" once every connection is up, "DONE" when OUT is written.

#include <arpa/inet.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <sys/epoll.h>
#include <sys/socket.h>
#include <sys/timerfd.h>
#include <time.h>
#include <unistd.h>

#include <cerrno>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <string>
#include <vector>

namespace {

struct Rec {
  int64_t due;
  uint32_t target;
  uint32_t pad;
};

struct Res {
  int64_t sent;
  int64_t done;
  int32_t status;
  int32_t value;
};

struct Conn {
  int fd = -1;
  std::string rbuf;
  std::string wbuf;
  size_t woff = 0;
  std::deque<uint32_t> inflight;  // request ids, in send order
};

int64_t mono_ns() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return int64_t(ts.tv_sec) * 1000000000LL + ts.tv_nsec;
}

int connect_to(const sockaddr_in& addr) {
  int fd = socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return -1;
  if (connect(fd, (const sockaddr*)&addr, sizeof(addr)) < 0) {
    close(fd);
    return -1;
  }
  int one = 1;
  setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
  fcntl(fd, F_SETFL, fcntl(fd, F_GETFL) | O_NONBLOCK);
  return fd;
}

bool flush(Conn& c) {
  while (c.woff < c.wbuf.size()) {
    ssize_t n = send(c.fd, c.wbuf.data() + c.woff, c.wbuf.size() - c.woff,
                     MSG_NOSIGNAL);
    if (n < 0) return errno == EAGAIN || errno == EWOULDBLOCK;
    c.woff += size_t(n);
  }
  c.wbuf.clear();
  c.woff = 0;
  return true;
}

// Parses complete responses off the front of c.rbuf.
void parse(Conn& c, std::vector<Res>& res, int64_t t0) {
  while (!c.inflight.empty()) {
    size_t he = c.rbuf.find("\r\n\r\n");
    if (he == std::string::npos) return;
    size_t clen = 0;
    for (const char* key : {"Content-Length:", "content-length:"}) {
      size_t p = c.rbuf.find(key);
      if (p != std::string::npos && p < he) clen = strtoul(c.rbuf.c_str() + p + 15, nullptr, 10);
    }
    if (c.rbuf.size() < he + 4 + clen) return;
    uint32_t id = c.inflight.front();
    c.inflight.pop_front();
    Res& r = res[id];
    r.done = mono_ns() - t0;
    r.status = c.rbuf.size() > 12 ? atoi(c.rbuf.c_str() + 9) : 0;
    std::string body = c.rbuf.substr(he + 4, clen);
    char* end = nullptr;
    long v = strtol(body.c_str(), &end, 10);
    r.value = (end != body.c_str()) ? int32_t(v) : -1;
    c.rbuf.erase(0, he + 4 + clen);
  }
}

}  // namespace

int main(int argc, char** argv) {
  if (argc != 8) {
    fprintf(stderr, "usage: openloop HOST PORT CONNS SCHEDULE TARGETS OUT DRAIN_MS\n");
    return 2;
  }
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons(uint16_t(atoi(argv[2])));
  if (inet_pton(AF_INET, argv[1], &addr.sin_addr) != 1) return 2;
  int nconn = atoi(argv[3]);
  int64_t drain_ns = atoll(argv[7]) * 1000000LL;

  std::vector<Rec> sched;
  {
    FILE* f = fopen(argv[4], "rb");
    if (!f) { perror("schedule"); return 2; }
    Rec r;
    while (fread(&r, sizeof(r), 1, f) == 1) sched.push_back(r);
    fclose(f);
  }
  std::vector<std::string> reqs;
  {
    FILE* f = fopen(argv[5], "r");
    if (!f) { perror("targets"); return 2; }
    char line[1024];
    while (fgets(line, sizeof(line), f)) {
      size_t n = strcspn(line, "\r\n");
      line[n] = 0;
      reqs.push_back(std::string("POST ") + line + " HTTP/1.1\r\nHost: x\r\n\r\n");
    }
    fclose(f);
  }
  for (const Rec& r : sched)
    if (r.target >= reqs.size()) { fprintf(stderr, "target out of range\n"); return 2; }

  std::vector<Res> res(sched.size(), Res{-1, -1, 0, -1});
  std::vector<Conn> cs(nconn);
  int ep = epoll_create1(0);
  for (int i = 0; i < nconn; i++) {
    cs[i].fd = connect_to(addr);
    if (cs[i].fd < 0) { perror("connect"); return 1; }
    epoll_event ev{};
    ev.events = EPOLLIN;
    ev.data.u32 = uint32_t(i);
    epoll_ctl(ep, EPOLL_CTL_ADD, cs[i].fd, &ev);
  }
  int tfd = timerfd_create(CLOCK_MONOTONIC, TFD_NONBLOCK);
  {
    epoll_event ev{};
    ev.events = EPOLLIN;
    ev.data.u32 = 0xFFFFFFFFu;
    epoll_ctl(ep, EPOLL_CTL_ADD, tfd, &ev);
  }
  const int64_t t0 = mono_ns() + 100000000LL;
  printf("T0 %lld\n", (long long)t0);
  fflush(stdout);

  size_t next = 0, outstanding = 0;
  int64_t armed = -1;
  const int64_t last_due = sched.empty() ? 0 : sched.back().due;
  std::vector<epoll_event> evs(256);
  char buf[65536];
  while (true) {
    int64_t now = mono_ns();
    while (next < sched.size() && t0 + sched[next].due <= now) {
      size_t best = 0;
      for (size_t i = 1; i < cs.size(); i++)
        if (cs[i].fd >= 0 && (cs[best].fd < 0 || cs[i].inflight.size() < cs[best].inflight.size()))
          best = i;
      Conn& c = cs[best];
      uint32_t id = uint32_t(next++);
      if (c.fd < 0) continue;  // every connection lost: never sent
      res[id].sent = mono_ns() - t0;
      c.inflight.push_back(id);
      c.wbuf += reqs[sched[id].target];
      outstanding++;
      if (!flush(c)) {
        epoll_ctl(ep, EPOLL_CTL_DEL, c.fd, nullptr);
        close(c.fd);
        c.fd = -1;
      }
    }
    if (next >= sched.size() && (outstanding == 0 || now > t0 + last_due + drain_ns)) break;
    if (next < sched.size() && armed != sched[next].due) {
      itimerspec its{};
      int64_t at = t0 + sched[next].due;
      its.it_value.tv_sec = at / 1000000000LL;
      its.it_value.tv_nsec = at % 1000000000LL;
      timerfd_settime(tfd, TFD_TIMER_ABSTIME, &its, nullptr);
      armed = sched[next].due;
    }
    int n = epoll_wait(ep, evs.data(), int(evs.size()), next < sched.size() ? -1 : 100);
    for (int i = 0; i < n; i++) {
      uint32_t k = evs[i].data.u32;
      if (k == 0xFFFFFFFFu) {
        uint64_t x;
        while (read(tfd, &x, sizeof(x)) > 0) {
        }
        continue;
      }
      Conn& c = cs[k];
      if (c.fd < 0) continue;
      bool closed = false;
      while (true) {
        ssize_t rd = recv(c.fd, buf, sizeof(buf), 0);
        if (rd > 0) {
          c.rbuf.append(buf, size_t(rd));
          continue;
        }
        if (rd == 0 || (errno != EAGAIN && errno != EWOULDBLOCK)) closed = true;
        break;
      }
      size_t before = c.inflight.size();
      parse(c, res, t0);
      outstanding -= before - c.inflight.size();
      if (!closed && !c.wbuf.empty() && !flush(c)) closed = true;
      if (closed) {
        epoll_ctl(ep, EPOLL_CTL_DEL, c.fd, nullptr);
        close(c.fd);
        c.fd = -1;
        outstanding -= c.inflight.size();
        c.inflight.clear();
        c.fd = connect_to(addr);
        if (c.fd >= 0) {
          epoll_event ev{};
          ev.events = EPOLLIN;
          ev.data.u32 = k;
          epoll_ctl(ep, EPOLL_CTL_ADD, c.fd, &ev);
        }
      }
    }
  }
  for (auto& c : cs)
    if (c.fd >= 0) close(c.fd);
  close(tfd);
  close(ep);
  FILE* f = fopen(argv[6], "wb");
  if (!f) { perror("out"); return 1; }
  fwrite(res.data(), sizeof(Res), res.size(), f);
  fclose(f);
  printf("DONE\n");
  fflush(stdout);
  return 0;
}
