// One group of a shard file's bitrot frames, [hash | block] x nblocks, read,
// placed and checked in one call without the interpreter lock
// (minio_tpu/ops/host.py read_frames, called through ctypes, which lets go
// of the lock for the whole call).  In Python the same group is two
// readinto calls a frame and a hash call, each a wait for the lock.
//
// The frames' hashes go to consecutive 32-byte rows of `hashes`, their
// blocks to rows of `out` spaced `stride` bytes apart (one shard's column
// of a dispatch's batch).  Every block is then hashed with the bitrot key
// (highwayhash.cpp) and compared with the hash stored before it.

#include <cerrno>
#include <cstdint>
#include <cstring>
#include <ctime>
#include <sys/types.h>
#include <sys/uio.h>
#include <unistd.h>

extern "C" void hh256_sum(const uint8_t key[32], const uint8_t* data,
                          size_t len, uint8_t out[32]);

namespace {

constexpr size_t kHash = 32;
constexpr int kMaxIov = 1024;  // IOV_MAX on Linux

// Where byte r of the group goes: a hash row or a block row.
struct Layout {
  uint8_t* hashes;
  uint8_t* out;
  size_t block_len;
  size_t stride;

  size_t frame() const { return kHash + block_len; }

  // The destination of group bytes [r, r + n) that lie in one frame's
  // hash or block, and how many of the n do.
  uint8_t* at(size_t r, size_t n, size_t* take) const {
    size_t i = r / frame(), j = r % frame();
    if (j < kHash) {
      *take = n < kHash - j ? n : kHash - j;
      return hashes + i * kHash + j;
    }
    j -= kHash;
    *take = n < block_len - j ? n : block_len - j;
    return out + i * stride + j;
  }

  // Copy `n` bytes that belong at group offset r to their places.
  void place(const uint8_t* src, size_t r, size_t n) const {
    while (n) {
      size_t take;
      uint8_t* dst = at(r, n, &take);
      memcpy(dst, src, take);
      src += take;
      r += take;
      n -= take;
    }
  }
};

int64_t now_ns() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return static_cast<int64_t>(ts.tv_sec) * 1000000000 + ts.tv_nsec;
}

}  // namespace

extern "C" {

// Status of frame_read; info[0] says more.
enum { FRAMES_OK = 0, FRAMES_SHORT = 1, FRAMES_MISMATCH = 2, FRAMES_ERRNO = 3 };

// Read nblocks frames of (32 + block_len) bytes from file offset `off` of
// `fd`.  With bounce == nullptr the file is read straight into the rows
// (preadv, the kernel scatters); otherwise (an O_DIRECT descriptor) in
// reads of at most bounce_len bytes at multiples of `align`, from the
// aligned offset below `off` to the aligned offset above the group's end,
// through `bounce` (aligned, bounce_len a multiple of align), and copied
// to the rows from there.
//
// Returns FRAMES_OK; FRAMES_SHORT where the file ended inside the group
// (info[0] the group bytes read); FRAMES_MISMATCH where a block's hash
// differs from its stored one (info[0] the first such frame); FRAMES_ERRNO
// where a read failed (info[0] the errno).  info[1]: nanoseconds spent
// hashing.  info[2], info[3]: the file offset of bounce's first byte and
// the bytes there that hold the file's (0 where no bounce, or where a read
// into it failed).  With a bounce, the descriptor's own offset is left at
// info[2] + info[3]: the O_DIRECT reader's sequential reads go on there.
int frame_read(int fd, int64_t off, size_t nblocks, size_t block_len,
               uint8_t* hashes, uint8_t* out, size_t stride,
               uint8_t* bounce, size_t bounce_len, size_t align,
               const uint8_t* key, int64_t* info) {
  const Layout lay{hashes, out, block_len, stride};
  const size_t want = nblocks * lay.frame();
  info[0] = info[1] = info[2] = info[3] = 0;
  size_t got = 0;
  if (bounce == nullptr) {
    struct iovec iov[kMaxIov];
    while (got < want) {
      int cnt = 0;
      size_t r = got;
      while (r < want && cnt < kMaxIov) {
        size_t take;
        iov[cnt].iov_base = lay.at(r, want - r, &take);
        iov[cnt].iov_len = take;
        r += take;
        cnt++;
      }
      ssize_t n = preadv(fd, iov, cnt, off + static_cast<int64_t>(got));
      if (n < 0) {
        if (errno == EINTR) continue;
        info[0] = errno;
        return FRAMES_ERRNO;
      }
      if (n == 0) break;
      got += static_cast<size_t>(n);
    }
  } else {
    const int64_t end = off + static_cast<int64_t>(want);
    const int64_t a_end =
        (end + static_cast<int64_t>(align) - 1) / align * align;
    int64_t pos = off / static_cast<int64_t>(align) * align;
    while (pos < a_end) {
      size_t len = static_cast<size_t>(a_end - pos);
      if (len > bounce_len) len = bounce_len;
      ssize_t n = pread(fd, bounce, len, pos);
      if (n < 0) {
        if (errno == EINTR) continue;
        info[0] = errno;
        info[2] = pos;
        info[3] = 0;
        lseek(fd, pos, SEEK_SET);
        return FRAMES_ERRNO;
      }
      info[2] = pos;
      info[3] = n;
      // the part of [pos, pos + n) inside the group
      int64_t lo = pos > off ? pos : off;
      int64_t hi = pos + n < end ? pos + n : end;
      if (hi > lo) {
        lay.place(bounce + (lo - pos), static_cast<size_t>(lo - off),
                  static_cast<size_t>(hi - lo));
        got = static_cast<size_t>(hi - off);
      }
      if (static_cast<size_t>(n) < len) break;  // the file ends here
      pos += n;
    }
    lseek(fd, info[2] + info[3], SEEK_SET);
  }
  if (got < want) {
    info[0] = static_cast<int64_t>(got);
    return FRAMES_SHORT;
  }
  const int64_t t0 = now_ns();
  int status = FRAMES_OK;
  uint8_t sum[kHash];
  for (size_t i = 0; i < nblocks; i++) {
    hh256_sum(key, out + i * stride, block_len, sum);
    if (memcmp(sum, hashes + i * kHash, kHash) != 0) {
      info[0] = static_cast<int64_t>(i);
      status = FRAMES_MISMATCH;
      break;
    }
  }
  info[1] = now_ns() - t0;
  return status;
}

}  // extern "C"
