// A small file (a drive's xl.meta) read whole in one call without the
// interpreter lock (minio_tpu/ops/host.py read_file, called through ctypes,
// which lets go of the lock for the whole call).  In Python the same read is
// open, fstat, lseek, a size probe, two reads and close, each a wait for the
// lock on its way back.

#include <fcntl.h>
#include <sys/stat.h>
#include <unistd.h>

#include <cerrno>
#include <cstdint>
#include <ctime>

namespace {

int64_t now_ns() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return static_cast<int64_t>(ts.tv_sec) * 1000000000 + ts.tv_nsec;
}

}  // namespace

extern "C" {

// Status of file_read; info[0] says more.
enum { FILE_OK = 0, FILE_ERRNO = 1 };

// open(O_RDONLY | O_CLOEXEC), fstat, read up to the size fstat gives (or to
// an earlier end of the file) into buf[0, cap), close.  Returns FILE_OK
// with info[0] the bytes read, or, where the size is more than cap, the
// size and nothing read (call again with a buffer that holds it); or
// FILE_ERRNO with info[0] the errno (EISDIR for a directory).  info[1]: the
// call's own nanoseconds, open to close.
int file_read(const char* path, uint8_t* buf, size_t cap, int64_t* info) {
  const int64_t t0 = now_ns();
  info[0] = info[1] = 0;
  int fd;
  do {
    fd = open(path, O_RDONLY | O_CLOEXEC);
  } while (fd < 0 && errno == EINTR);
  if (fd < 0) {
    info[0] = errno;
    info[1] = now_ns() - t0;
    return FILE_ERRNO;
  }
  int status = FILE_OK;
  struct stat st;
  if (fstat(fd, &st) != 0) {
    info[0] = errno;
    status = FILE_ERRNO;
  } else if (S_ISDIR(st.st_mode)) {
    info[0] = EISDIR;
    status = FILE_ERRNO;
  } else if (static_cast<uint64_t>(st.st_size) > cap) {
    info[0] = st.st_size;
  } else {
    const size_t size = static_cast<size_t>(st.st_size);
    size_t got = 0;
    while (got < size) {
      ssize_t n = read(fd, buf + got, size - got);
      if (n < 0) {
        if (errno == EINTR) continue;
        info[0] = errno;
        status = FILE_ERRNO;
        break;
      }
      if (n == 0) break;  // the file ends before its size: what is there
      got += static_cast<size_t>(n);
    }
    if (status == FILE_OK) info[0] = static_cast<int64_t>(got);
  }
  close(fd);
  info[1] = now_ns() - t0;
  return status;
}

}  // extern "C"
