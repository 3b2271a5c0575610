// The reply sender: one thread an engine that takes every served
// connection's reply bytes off the event loop.
//
// A `send` on a loopback socket carries the segment through the stack and
// wakes the receiver INSIDE the call; on the event-loop thread that was
// the largest single piece of a command's cost. Here the loop's part of a
// reply is a copy and a queue push (jy_snd_send), and this thread makes
// the system call. It never touches the interpreter and holds `mu` across
// no system call but the ones that cannot block (a stop-time flush with
// MSG_DONTWAIT, close, an eventfd write).
//
// Invariants a later change must keep (CHANGES.md, PR 40):
//   * one door a connection: every reply byte of a connection that was
//     opened here goes through here, in hand-off order (a FIFO a
//     connection, one job in flight at a time);
//   * a connection is (id, fd): `fd` is the sender's OWN duplicate of the
//     socket, closed by the sender alone, so its number cannot be handed
//     to another connection while jobs for it exist, whatever the
//     transport does with its own descriptor; `id` is the generation, never
//     reused, and a job goes to its own id's descriptor and no other;
//   * a CLOSED connection's bytes are still delivered: jy_snd_close marks
//     it closing, the thread finishes its FIFO (POLLOUT among the others)
//     and only then lets go of the descriptor, as a closing transport
//     flushes its buffer before it closes the socket; bytes are dropped
//     only when the peer reset the connection or at jy_snd_stop;
//   * a socket that does not take a job whole keeps the rest at the head
//     of ITS FIFO and waits for POLLOUT among the others: no connection
//     waits behind another's consumer.

#include "engine.h"

#include <atomic>
#include <cerrno>
#include <chrono>
#include <deque>
#include <mutex>
#include <thread>

#include <fcntl.h>
#include <poll.h>
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <unistd.h>

namespace jy {

namespace {

// how long the thread goes on looking at its empty queues before it
// sleeps: of the order of two gaps between replies at a loaded node's
// rate (7-10k a second), so that under load a hand-off finds it awake and
// costs the loop no wake-up, and an idle node's sender sleeps
constexpr int64_t IDLE_SPIN_NS = 200 * 1000;
// with connections waiting for POLLOUT and others being served, how often
// the waiting ones are looked at
constexpr int64_t POLL_EVERY_NS = 500 * 1000;

inline int64_t now_ns() {
    return std::chrono::duration_cast<std::chrono::nanoseconds>(
               std::chrono::steady_clock::now().time_since_epoch())
        .count();
}

inline void cpu_relax() {
#if defined(__x86_64__) || defined(__i386__)
    __builtin_ia32_pause();
#else
    std::this_thread::yield();
#endif
}

// a freed job's buffer is kept for its connection's next reply up to this
// size: a reply's allocation (past malloc's mmap threshold a mapping and
// its page faults, every time) is the dearest part of a large hand-off
constexpr int64_t SPARE_MAX = 1 << 20;
constexpr int64_t SPARE_MIN = 4096;

struct Job {
    uint8_t* data;
    int64_t len;
    int64_t cap;
};

struct Conn {
    int fd = -1;  // the sender's own duplicate of the socket
    std::deque<Job> jobs;
    int64_t head_off = 0;  // bytes of jobs.front() already sent
    int64_t pending = 0;   // bytes queued and not yet sent
    int64_t low = 0, high = 0;  // the loop's water marks for this socket
    uint8_t* spare = nullptr;   // the largest buffer a sent job left
    int64_t spare_cap = 0;
    bool queued = false;   // its id is in `ready`
    bool blocked = false;  // the socket refused bytes: waits for POLLOUT
    bool busy = false;     // the thread is inside send() for it
    bool dead = false;     // EPIPE / ECONNRESET: jobs dropped, none taken
    bool notify = false;   // a handler sleeps until pending <= low
    bool closing = false;  // jy_snd_close: goes once its FIFO is through

    // the bytes its consumer is behind by: what is pending once the
    // socket has refused some, or once jobs pile up past the high-water
    // mark behind one the thread has not got through (a reply of any size
    // that was just handed over and not yet tried is NOT behind: the
    // loop's own writer tried its send before it answered), else 0
    int64_t behind() const {
        return (blocked || (jobs.size() > 1 && pending > high)) ? pending : 0;
    }

    // a job is through (or dropped): keep its buffer if it is the larger
    void retire(Job& j) {
        if (j.cap <= SPARE_MAX && j.cap > spare_cap) {
            delete[] spare;
            spare = j.data;
            spare_cap = j.cap;
        } else {
            delete[] j.data;
        }
    }
};

}  // namespace

struct Sender {
    std::mutex mu;
    std::unordered_map<int64_t, Conn> conns;
    std::deque<int64_t> ready;    // ids with jobs, not blocked, not busy
    std::atomic<int64_t> n_ready{0};  // ready.size(), read without `mu`
    int64_t n_blocked = 0;
    int64_t next_id = 1;
    int64_t pending_all = 0;
    bool asleep = false;  // the thread is in poll() with no timeout
    bool stop = false;
    bool started = false;
    std::thread th;
    int wake_fd = -1;    // hand-off -> sleeping thread
    int notify_fd = -1;  // thread -> event loop (a handler's write wait)
    // counters (relaxed: read at scrape time)
    std::atomic<uint64_t> sends{0}, partial{0}, wakes{0}, dropped{0},
        pending_max{0}, busy_ns{0};

    Sender() {
        wake_fd = eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
        notify_fd = eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
    }

    ~Sender() {
        halt();
        for (auto& kv : conns) drop(kv.second);
        if (wake_fd >= 0) close(wake_fd);
        if (notify_fd >= 0) close(notify_fd);
    }

    // join the thread; what is left of the CLOSING connections' bytes is
    // written as far as their sockets take it at once and dropped beyond
    // (nobody will send it now), open connections keep theirs
    void halt() {
        {
            std::lock_guard<std::mutex> l(mu);
            if (!started) return;
            stop = true;
            asleep = false;
        }
        kick(wake_fd);
        th.join();
        std::lock_guard<std::mutex> l(mu);
        started = false;
        stop = false;
        for (auto it = conns.begin(); it != conns.end();) {
            Conn& c = it->second;
            if (!c.closing) {
                ++it;
                continue;
            }
            while (!c.jobs.empty()) {
                Job& j = c.jobs.front();
                ssize_t n = send(c.fd, j.data + c.head_off,
                                 static_cast<size_t>(j.len - c.head_off),
                                 MSG_DONTWAIT | MSG_NOSIGNAL);
                if (n <= 0) break;
                c.head_off += n;
                c.pending -= n;
                pending_all -= n;
                if (c.head_off < j.len) break;
                delete[] j.data;
                c.jobs.pop_front();
                c.head_off = 0;
            }
            drop(c);
            it = conns.erase(it);
        }
    }

    static void kick(int efd) {
        uint64_t one = 1;
        ssize_t r = write(efd, &one, sizeof one);
        (void)r;  // EAGAIN: the counter is full, the reader is signalled
    }

    // forget a connection's jobs and its descriptor (`mu` held)
    void drop(Conn& c) {
        for (Job& j : c.jobs) delete[] j.data;
        c.jobs.clear();
        delete[] c.spare;
        c.spare = nullptr;
        c.spare_cap = 0;
        dropped.fetch_add(static_cast<uint64_t>(c.pending),
                          std::memory_order_relaxed);
        pending_all -= c.pending;
        c.pending = 0;
        c.head_off = 0;
        if (c.blocked) {
            c.blocked = false;
            n_blocked--;
        }
        if (c.fd >= 0) close(c.fd);
        c.fd = -1;
    }

    // the socket's answer to a send of the head job of a connection that
    // was not blocked (`mu` held)
    void settle(int64_t id, Conn& c, ssize_t sent, int err) {
        if (sent < 0 && err != EAGAIN && err != EWOULDBLOCK && err != EINTR) {
            c.dead = true;  // the reader side ends the handler
            drop(c);
        } else {
            if (sent > 0) {
                c.head_off += sent;
                c.pending -= sent;
                pending_all -= sent;
            }
            Job& j = c.jobs.front();
            if (c.head_off == j.len) {
                c.retire(j);
                c.jobs.pop_front();
                c.head_off = 0;
            } else if (sent >= 0 || err != EINTR) {
                // a short count or EAGAIN: the rest waits for POLLOUT
                partial.fetch_add(1, std::memory_order_relaxed);
                c.blocked = true;
                n_blocked++;
            }
            if (!c.jobs.empty() && !c.blocked && !c.queued) {
                c.queued = true;  // at the back: the others go first
                ready.push_back(id);
                n_ready.store(static_cast<int64_t>(ready.size()),
                              std::memory_order_release);
            }
        }
        if (c.notify && (c.dead || c.pending <= c.low)) {
            c.notify = false;
            kick(notify_fd);
        }
        // a closed connection whose last byte is through (or whose peer
        // is gone) goes now, and its descriptor with it: `c` ends here
        if (c.closing && (c.dead || c.jobs.empty())) {
            drop(c);
            conns.erase(id);
        }
    }

    // one send of one connection's head job; false: nothing was ready
    bool serve_one() {
        std::unique_lock<std::mutex> l(mu);
        while (!ready.empty()) {
            int64_t id = ready.front();
            ready.pop_front();
            n_ready.store(static_cast<int64_t>(ready.size()),
                          std::memory_order_release);
            auto it = conns.find(id);
            if (it == conns.end()) continue;  // closed meanwhile
            Conn& c = it->second;
            c.queued = false;
            if (c.jobs.empty() || c.blocked) continue;
            const Job j = c.jobs.front();
            const int64_t off = c.head_off;
            const int fd = c.fd;
            c.busy = true;
            l.unlock();
            int64_t t0 = now_ns();
            ssize_t n = send(fd, j.data + off, static_cast<size_t>(j.len - off),
                             MSG_DONTWAIT | MSG_NOSIGNAL);
            int err = n < 0 ? errno : 0;
            busy_ns.fetch_add(static_cast<uint64_t>(now_ns() - t0),
                              std::memory_order_relaxed);
            l.lock();
            // `c` stands: while busy only settle() erases it, and
            // rehashing keeps references to elements
            c.busy = false;
            settle(id, c, n, err);
            return true;
        }
        return false;
    }

    // look at the connections that wait for POLLOUT, and with `sleep`
    // wait for one of them or for a hand-off
    void poll_blocked(bool sleep) {
        std::vector<pollfd> pfds;
        std::vector<int64_t> ids;
        {
            std::lock_guard<std::mutex> l(mu);
            if (sleep) {
                if (!ready.empty() || stop) return;
                asleep = true;
            }
            pfds.push_back({wake_fd, POLLIN, 0});
            ids.push_back(0);
            for (auto& kv : conns)
                if (kv.second.blocked) {
                    pfds.push_back({kv.second.fd, POLLOUT, 0});
                    ids.push_back(kv.first);
                }
        }
        int64_t t0 = sleep ? 0 : now_ns();
        int n = poll(pfds.data(), pfds.size(), sleep ? -1 : 0);
        if (!sleep)
            busy_ns.fetch_add(static_cast<uint64_t>(now_ns() - t0),
                              std::memory_order_relaxed);
        std::lock_guard<std::mutex> l(mu);
        asleep = false;
        if (n <= 0) return;
        if (pfds[0].revents) {
            uint64_t v;
            ssize_t r = read(wake_fd, &v, sizeof v);
            (void)r;
        }
        for (size_t i = 1; i < pfds.size(); i++) {
            if (!pfds[i].revents) continue;
            // POLLOUT, or POLLERR / POLLHUP: the next send says which
            auto it = conns.find(ids[i]);
            if (it == conns.end() || !it->second.blocked) continue;
            Conn& c = it->second;
            c.blocked = false;
            n_blocked--;
            if (!c.queued && !c.jobs.empty()) {
                c.queued = true;
                ready.push_back(ids[i]);
            }
        }
        n_ready.store(static_cast<int64_t>(ready.size()),
                      std::memory_order_release);
    }

    void run() {
        int64_t last_poll = now_ns();
        while (true) {
            bool any_blocked;
            {
                std::lock_guard<std::mutex> l(mu);
                if (stop) return;
                any_blocked = n_blocked > 0;
            }
            if (any_blocked && now_ns() - last_poll > POLL_EVERY_NS) {
                poll_blocked(false);
                last_poll = now_ns();
            }
            if (serve_one()) continue;
            // idle: stay awake for a bounded time, then sleep
            int64_t until = now_ns() + IDLE_SPIN_NS;
            bool found = false;
            while (!found) {
                for (int i = 0; i < 64 && !found; i++) {
                    found = n_ready.load(std::memory_order_acquire) > 0;
                    if (!found) cpu_relax();
                }
                if (!found && now_ns() >= until) break;
            }
            if (!found) {
                poll_blocked(true);
                last_poll = now_ns();
            }
        }
    }
};

void sender_destroy(Sender* s) { delete s; }

}  // namespace jy

using namespace jy;

namespace {

Sender* sender_of(void* ev, bool create) {
    Engine* eng = static_cast<Engine*>(ev);
    Sender* s = eng->sender.load(std::memory_order_acquire);
    if (s == nullptr && create) {
        Sender* made = new Sender();
        if (eng->sender.compare_exchange_strong(s, made,
                                                std::memory_order_acq_rel))
            return made;
        delete made;  // another thread made it first: `s` is theirs
    }
    return s;
}

}  // namespace

extern "C" {

// A connection's door: the sender duplicates `fd` (the duplicate is its
// own until jy_snd_close) and answers with the connection's id, > 0 and
// never reused; -1 when no descriptor could be had (the caller then keeps
// the loop's own writer). `low` / `high` are the loop's water marks.
int64_t jy_snd_open(void* ev, int32_t fd, int64_t low, int64_t high) {
    Sender* s = sender_of(ev, true);
    if (s->wake_fd < 0 || s->notify_fd < 0) return -1;
    int own = fcntl(fd, F_DUPFD_CLOEXEC, 0);
    if (own < 0) return -1;
    std::lock_guard<std::mutex> l(s->mu);
    int64_t id = s->next_id++;
    Conn& c = s->conns[id];
    c.fd = own;
    c.low = low;
    c.high = high;
    return id;
}

// Hand `n` bytes at `data` to connection `id`: copied here, sent by the
// thread, in hand-off order. Answers with the bytes the connection's
// consumer is behind by (Conn::behind), or -1 for a connection that is
// closed (gone or closing) or dead (the bytes are dropped and counted).
int64_t jy_snd_send(void* ev, int64_t id, const uint8_t* data, int64_t n) {
    Sender* s = sender_of(ev, false);
    if (s == nullptr || n < 0) return -1;
    if (n == 0) return 0;
    Job job{nullptr, n, 0};
    {   // the connection's spare buffer, if it holds the reply
        std::lock_guard<std::mutex> l(s->mu);
        auto it = s->conns.find(id);
        if (it != s->conns.end() && it->second.spare_cap >= n) {
            job.data = it->second.spare;
            job.cap = it->second.spare_cap;
            it->second.spare = nullptr;
            it->second.spare_cap = 0;
        }
    }
    if (job.data == nullptr) {
        job.cap = SPARE_MIN;
        while (job.cap < n && job.cap < SPARE_MAX) job.cap *= 2;
        if (job.cap < n) job.cap = n;
        job.data = new uint8_t[static_cast<size_t>(job.cap)];
    }
    memcpy(job.data, data, static_cast<size_t>(n));  // outside the lock
    bool wake;
    int64_t behind;
    {
        std::lock_guard<std::mutex> l(s->mu);
        auto it = s->conns.find(id);
        if (it == s->conns.end() || it->second.dead || it->second.closing) {
            delete[] job.data;
            s->dropped.fetch_add(static_cast<uint64_t>(n),
                                 std::memory_order_relaxed);
            return -1;
        }
        Conn& c = it->second;
        c.jobs.push_back(job);
        c.pending += n;
        s->pending_all += n;
        if (static_cast<uint64_t>(s->pending_all) >
            s->pending_max.load(std::memory_order_relaxed))
            s->pending_max.store(static_cast<uint64_t>(s->pending_all),
                                 std::memory_order_relaxed);
        if (!c.queued && !c.blocked && !c.busy) {
            c.queued = true;
            s->ready.push_back(id);
            s->n_ready.store(static_cast<int64_t>(s->ready.size()),
                             std::memory_order_release);
        }
        behind = c.behind();
        if (!s->started) {
            s->started = true;
            s->th = std::thread([s] { s->run(); });
        }
        wake = s->asleep;
        s->asleep = false;
    }
    s->sends.fetch_add(1, std::memory_order_relaxed);
    if (wake) {
        s->wakes.fetch_add(1, std::memory_order_relaxed);
        Sender::kick(s->wake_fd);
    }
    return behind;
}

// The bytes `id`'s consumer is behind by now, as jy_snd_send answers
// (0 for a closed or dead one).
int64_t jy_snd_behind(void* ev, int64_t id) {
    Sender* s = sender_of(ev, false);
    if (s == nullptr) return 0;
    std::lock_guard<std::mutex> l(s->mu);
    auto it = s->conns.find(id);
    return it == s->conns.end() ? 0 : it->second.behind();
}

// A handler wants to sleep until `id` is written down to its low-water
// mark: 1 = armed (now or before, and not yet fired), the thread will
// signal jy_snd_notify_fd; 0 = no need (not behind by more than the
// high-water mark, closed, or dead).
int32_t jy_snd_wait(void* ev, int64_t id) {
    Sender* s = sender_of(ev, false);
    if (s == nullptr) return 0;
    std::lock_guard<std::mutex> l(s->mu);
    auto it = s->conns.find(id);
    if (it == s->conns.end() || it->second.dead || it->second.closing)
        return 0;
    Conn& c = it->second;
    if (!c.notify && c.behind() > c.high) c.notify = true;
    return c.notify ? 1 : 0;
}

// The eventfd the thread signals the loop through (-1: no sender yet).
int32_t jy_snd_notify_fd(void* ev) {
    Sender* s = sender_of(ev, false);
    return s == nullptr ? -1 : s->notify_fd;
}

// Close `id` before its socket is closed: no job is taken for it from
// now on, and the thread goes on writing the ones it has (POLLOUT among
// the others) and closes the sender's descriptor after the last, so the
// peer reads every reply and then the end of the stream, as it does
// behind a closing transport. With nothing pending it goes at once. A
// peer reset drops what is left (counted), and so does jy_snd_stop.
// Answers with the bytes still to be written. Idempotent.
int64_t jy_snd_close(void* ev, int64_t id) {
    Sender* s = sender_of(ev, false);
    if (s == nullptr) return 0;
    std::lock_guard<std::mutex> l(s->mu);
    auto found = s->conns.find(id);
    if (found == s->conns.end()) return 0;
    Conn& c = found->second;
    c.closing = true;
    c.notify = false;  // its handler is gone
    // settle() ends it; with no thread (stopped, and no hand-off since)
    // nobody would: what it holds is dropped here
    if (c.busy || (!c.jobs.empty() && s->started)) return c.pending;
    s->drop(c);
    s->conns.erase(found);
    return 0;
}

// The bytes the sender holds for all connections now, closing ones too.
int64_t jy_snd_pending(void* ev) {
    Sender* s = sender_of(ev, false);
    if (s == nullptr) return 0;
    std::lock_guard<std::mutex> l(s->mu);
    return s->pending_all;
}

// sends, partial, wakes, dropped bytes, pending max bytes, busy ns,
// pending bytes now, 1 while the thread runs
void jy_snd_stats(void* ev, uint64_t* out) {
    for (int i = 0; i < 8; i++) out[i] = 0;
    Sender* s = sender_of(ev, false);
    if (s == nullptr) return;
    out[0] = s->sends.load(std::memory_order_relaxed);
    out[1] = s->partial.load(std::memory_order_relaxed);
    out[2] = s->wakes.load(std::memory_order_relaxed);
    out[3] = s->dropped.load(std::memory_order_relaxed);
    out[4] = s->pending_max.load(std::memory_order_relaxed);
    out[5] = s->busy_ns.load(std::memory_order_relaxed);
    std::lock_guard<std::mutex> l(s->mu);
    out[6] = static_cast<uint64_t>(s->pending_all);
    out[7] = s->started ? 1 : 0;
}

// Join the thread (the next hand-off starts another). Closing connections
// end here, with what their sockets take at once; open ones stay until
// they are closed.
void jy_snd_stop(void* ev) {
    Sender* s = sender_of(ev, false);
    if (s != nullptr) s->halt();
}

}  // extern "C"
