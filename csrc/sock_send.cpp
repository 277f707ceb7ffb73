// One piece of a response body written to a non-blocking socket without
// the interpreter lock (minio_tpu/ops/host.py sock_send, called through
// ctypes, which lets go of the lock for the whole call): send() until
// the piece is out, poll(POLLOUT) where the socket is full.  In Python
// each partial send and each poll is one more wait for the lock, behind
// every other thread of the server.

#include <cerrno>
#include <cstddef>
#include <poll.h>
#include <sys/socket.h>
#include <sys/types.h>

extern "C" {

// Bytes of buf[0, n) the socket took.  Fewer than n: the socket took
// nothing for stall_ms (*err 0: the caller gives the rest to someone
// who may wait), or send/poll failed (*err the errno, after what had
// been sent).  MSG_NOSIGNAL: a reset connection is EPIPE, never SIGPIPE.
size_t sock_send(int fd, const char* buf, size_t n, int stall_ms, int* err) {
    size_t sent = 0;
    *err = 0;
    while (sent < n) {
        ssize_t took = send(fd, buf + sent, n - sent, MSG_NOSIGNAL);
        if (took >= 0) {
            sent += static_cast<size_t>(took);
            continue;
        }
        if (errno == EINTR) continue;
        if (errno != EAGAIN && errno != EWOULDBLOCK) {
            *err = errno;
            break;
        }
        struct pollfd full = {fd, POLLOUT, 0};
        int ready = poll(&full, 1, stall_ms);
        if (ready == 0) break;
        if (ready < 0 && errno != EINTR) {
            *err = errno;
            break;
        }
    }
    return sent;
}

}  // extern "C"
