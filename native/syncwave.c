// The syscalls of a drive's writer thread (storage/commit.py) and of a
// read's local drives (storage/xl_storage.py), each group of them ONE
// call that never holds the interpreter lock:
//
//   * a wave of a group-commit flush (GroupCollector.flush): the fsyncs
//     of a round's files, or of its directories, issued together;
//   * the landing of a drive op's body (land_part / land_file, further
//     down): a file created, written, dup'd or fsynced, and closed, for
//     the part file behind its two mkdirs;
//   * a read wave (mt_read_files, further down): the xl.meta file of
//     every local drive of a set read into a slot each, for one quorum
//     metadata read (read_version_wave);
//   * a shard read wave (mt_read_verify_ranges, at the end of this
//     file): one framed window of a shard per local drive, read, its
//     frames' digests checked and its payload gathered, for one round of
//     a GET's shard read (read_shard_wave).
//
// Why native: under a loaded interpreter every blocking call a Python
// thread makes ends with a wait for the GIL, so a drive's writer thread
// that fsyncs a batch's ~40 files and directories one os.* call at a
// time spends its wall waiting for the interpreter, not for the drive
// (PERF.md, commit_flush_ms).  A read that hands each drive's open /
// read / close to a pool thread pays a hand-over to start each child and
// one more per syscall (PERF.md, meta_queue_ms, get_io_ms).  Here the
// whole wave costs the calling thread one release and one
// re-acquisition.
//
// The calls are the ones the Python code made, for the same objects:
//   files:  fsync(fd); close(fd)              errs[i] = errno of the fsync
//   dirs:   open(O_RDONLY|O_DIRECTORY); fsync; close     errors tolerated
//   reads:  open(O_RDONLY); fstat; read until EOF; close
//   shards: open(O_RDONLY); pread until the window or EOF; close
// A wave is cut into at most MT_SYNC_SLICES slices, each on a thread of
// its own that is joined before the call returns: nothing of the wave is
// in flight when the caller goes on.

#define _GNU_SOURCE
#include <errno.h>
#include <fcntl.h>
#include <pthread.h>
#include <stddef.h>
#include <stdint.h>
#include <string.h>
#include <sys/stat.h>
#include <sys/types.h>
#include <time.h>
#include <unistd.h>

#define MT_SYNC_SLICES 8

// one item of a wave: ctx is the wave's own description, errs its
// per-item result where it has one
typedef void (*item_fn)(const void *ctx, int *errs, int i);

typedef struct {
    item_fn item;
    const void *ctx;
    int *errs;
    int n, first, step;
} slice_t;

static void file_item(const void *ctx, int *errs, int i) {
    int fd = ((const int *)ctx)[i];
    errs[i] = fsync(fd) == 0 ? 0 : errno;
    close(fd);
}

static void dir_item(const void *ctx, int *errs, int i) {
    (void)errs;
    int dfd = open(((const char *const *)ctx)[i],
                   O_RDONLY | O_DIRECTORY | O_CLOEXEC);
    if (dfd < 0) return;     // same tolerance as _fsync_dir
    fsync(dfd);
    close(dfd);
}

static void *run_slice(void *arg) {
    const slice_t *s = (const slice_t *)arg;
    for (int i = s->first; i < s->n; i += s->step)
        s->item(s->ctx, s->errs, i);
    return 0;
}

static void wave(item_fn item, const void *ctx, int n, int *errs) {
    if (n <= 0) return;
    int k = n < MT_SYNC_SLICES ? n : MT_SYNC_SLICES;
    slice_t sl[MT_SYNC_SLICES];
    pthread_t th[MT_SYNC_SLICES];
    int started[MT_SYNC_SLICES];
    for (int j = 0; j < k; j++) {
        sl[j] = (slice_t){item, ctx, errs, n, j, k};
        // slice 0 runs here; a thread that cannot start runs here too
        started[j] = j > 0
            && pthread_create(&th[j], 0, run_slice, &sl[j]) == 0;
    }
    for (int j = 0; j < k; j++)
        if (!started[j]) run_slice(&sl[j]);
    for (int j = 1; j < k; j++)
        if (started[j]) pthread_join(th[j], 0);
}

// fsync + close every fd; errs[i] is 0 or the errno of fds[i]'s fsync.
void mt_sync_files(const int *fds, int n, int *errs) {
    wave(file_item, fds, n, errs);
}

// open + fsync + close every directory; errors are tolerated, as
// _fsync_dir tolerates them.
void mt_sync_dirs(const char *const *dirs, int n) {
    wave(dir_item, dirs, n, 0);
}

// -- a drive op's body: one file landed per call ---------------------------
//
// Under the same load the body of a drive op (xl_storage.py
// write_data_commit: 2 mkdir, then open / write / dup / close for the
// part file and again for the xl.meta tmp file) paid the interpreter
// once per syscall (PERF.md section 6, PR 32).  The calls, their order
// and their objects are the Python sequence's; what differs is that the
// calling thread gives the interpreter lock up once per file.

// the step a landing failed at (mt_land_t.step); 0 = it did not fail
enum { MT_LAND_MKDIR_OBJ = 1, MT_LAND_MKDIR_DDIR, MT_LAND_OPEN,
       MT_LAND_WRITE, MT_LAND_SYNC, MT_LAND_CLOSE };

// what follows the write: nothing (MT_FSYNC=0), a dup whose fsync the
// armed collector issues at its flush, or the fsync itself (eager path)
enum { MT_SYNC_NONE = 0, MT_SYNC_DUP = 1, MT_SYNC_NOW = 2 };

typedef struct {
    int step;   // 0, or the MT_LAND_* step that failed
    int err;    // that step's errno
    int fresh;  // land_part: mkdir(obj) created it (EEXIST is no error)
    int fd;     // MT_SYNC_DUP: the dup'd descriptor, the caller's to
                // fsync and close; else -1
} mt_land_t;

static int land_fail(mt_land_t *out, int step, int err) {
    out->step = step;
    out->err = err;
    return -1;
}

// open(O_WRONLY|O_CREAT|O_TRUNC, 0644), write until drained, dup or
// fsync, close: _write_file_atomic's body up to, not including, its
// os.replace.  No descriptor is left open on any failing step.
int mt_land_file(const char *path, const void *buf, size_t len, int sync,
                 mt_land_t *out) {
    int fd;
    out->step = out->err = 0;
    out->fd = -1;
    do {
        fd = open(path, O_WRONLY | O_CREAT | O_TRUNC | O_CLOEXEC, 0644);
    } while (fd < 0 && errno == EINTR);
    if (fd < 0) return land_fail(out, MT_LAND_OPEN, errno);
    const char *p = (const char *)buf;
    while (len > 0) {           // short writes are legal, EINTR too
        ssize_t w = write(fd, p, len);
        if (w < 0) {
            if (errno == EINTR) continue;
            int e = errno;
            close(fd);
            return land_fail(out, MT_LAND_WRITE, e);
        }
        p += w;
        len -= (size_t)w;
    }
    int dfd = -1, rc = 0;
    if (sync == MT_SYNC_DUP) {
        // os.dup's descriptor is not inheritable: the same, in one call
        dfd = fcntl(fd, F_DUPFD_CLOEXEC, 0);
        rc = dfd < 0 ? -1 : 0;
    } else if (sync == MT_SYNC_NOW) {
        do { rc = fsync(fd); } while (rc < 0 && errno == EINTR);
    }
    if (rc < 0) {
        int e = errno;
        close(fd);
        return land_fail(out, MT_LAND_SYNC, e);
    }
    // EINTR from close: Linux has released the descriptor already and
    // os.close reports none (PEP 475); any other errno is the landing's
    if (close(fd) < 0 && errno != EINTR) {
        int e = errno;
        if (dfd >= 0) close(dfd);
        return land_fail(out, MT_LAND_CLOSE, e);
    }
    out->fd = dfd;
    return 0;
}

// mkdir(obj) (EEXIST: not fresh, no error), mkdir(ddir), then the part
// file as mt_land_file lands it: write_data_commit's one-shot branch.
int mt_land_part(const char *obj, const char *ddir, const char *part,
                 const void *buf, size_t len, int sync, mt_land_t *out) {
    out->fd = -1;
    out->fresh = 1;
    if (mkdir(obj, 0777) < 0) {
        if (errno != EEXIST) return land_fail(out, MT_LAND_MKDIR_OBJ, errno);
        out->fresh = 0;
    }
    if (mkdir(ddir, 0777) < 0)
        return land_fail(out, MT_LAND_MKDIR_DDIR, errno);
    return mt_land_file(part, buf, len, sync, out);
}

// -- a quorum metadata read: one xl.meta per local drive, read together -----
//
// read_version on every drive of a set was 16 pool children, each of
// which waited for a thread and the interpreter to start, then paid it
// again after open, read and close (PERF.md, meta_queue_ms).  Here the
// local drives' files are read by one call: item i lands in its own slot
// of the caller's arena, arena + i * cap, and comes back with its length,
// 0 or the errno of the step that failed, and CLOCK_MONOTONIC stamps
// around it (the clock Python's time.monotonic_ns() reads).

// errs[i] of a file larger than its slot: lens[i] is the size seen, and
// the caller reads that one file again without a limit
#define MT_READ_TOOBIG (-1)

typedef struct {
    const char *const *paths;
    char *arena;
    size_t cap;              // bytes per slot
    long long *lens, *t0, *t1;
} read_t;

static long long mono_ns(void) {
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return (long long)ts.tv_sec * 1000000000LL + ts.tv_nsec;
}

// open, fstat, read until EOF, close: what Python's
// open(path, "rb").read() issues, with the directory check its fstat
// makes (EISDIR).  Returns 0, an errno or MT_READ_TOOBIG.
static int read_file(const char *path, char *buf, size_t cap,
                     long long *len) {
    int fd, err = 0;
    size_t got = 0;
    do {
        fd = open(path, O_RDONLY | O_CLOEXEC);
    } while (fd < 0 && errno == EINTR);
    if (fd < 0) return errno;
    struct stat st;
    if (fstat(fd, &st) < 0) {
        err = errno;
    } else if (S_ISDIR(st.st_mode)) {
        err = EISDIR;
    } else if ((unsigned long long)st.st_size > cap) {
        got = (size_t)st.st_size;
        err = MT_READ_TOOBIG;
    } else {
        for (;;) {
            // past a full slot, one byte more tells EOF from a file that
            // grew since its fstat
            char probe;
            ssize_t k = got < cap ? read(fd, buf + got, cap - got)
                                  : read(fd, &probe, 1);
            if (k < 0) {
                if (errno == EINTR) continue;
                err = errno;
                break;
            }
            if (k == 0) break;
            if (got >= cap) err = MT_READ_TOOBIG;
            got += (size_t)k;
            if (err) break;
        }
    }
    close(fd);
    *len = (long long)got;
    return err;
}

static void read_item(const void *ctx, int *errs, int i) {
    const read_t *r = (const read_t *)ctx;
    r->t0[i] = mono_ns();
    errs[i] = read_file(r->paths[i], r->arena + (size_t)i * r->cap, r->cap,
                        &r->lens[i]);
    r->t1[i] = mono_ns();
}

// Read every path into its slot; per item lens[i], errs[i] and
// t0[i] / t1[i].
void mt_read_files(const char *const *paths, int n, char *arena, size_t cap,
                   long long *lens, int *errs, long long *t0, long long *t1) {
    read_t rd = {paths, arena, cap, lens, t0, t1};
    wave(read_item, &rd, n, errs);
}

// -- a GET's shard read: one framed window per local drive, verified -------
//
// A round of a GET's shard read was k pool children, each of which
// waited for a thread and the interpreter to start, then paid it again
// after open, seek, read and close, and once more around its digest
// pass (PERF.md, get_io_ms / get_verify_ms).  Here the local drives'
// windows are read by one call.  Item i is the window [offs[i],
// offs[i] + len) of paths[i], read into its row out + i * len; its
// frames are checked by the caller's `verify` (highwayhash.c
// mt_hh256_verify_framed, handed over as a pointer: the digest code is
// that library's alone), and its frames' payloads are then moved to the
// front of the row, where the caller finds `seg` bytes.

// the check of a framed buffer: 0, or the 1-based index of the first
// frame whose digest does not match
typedef int (*mt_verify_fn)(const uint64_t *key, const uint8_t *framed,
                            size_t size, size_t block_size);

// res[0] of an item that read but did not verify, or read short
#define MT_SHARD_SHORT (-2)   // res[1]: the bytes read
#define MT_SHARD_BITROT (-3)  // res[1]: the frame that did not match
#define MT_SHARD_TRUNC (-4)   // res[1]: the payload bytes present
// res[1] of an errno: the step that failed
enum { MT_SHARD_OPEN = 1, MT_SHARD_READ = 2 };
// int64s per item in res
#define MT_SHARD_RES 6

typedef struct {
    const char *const *paths;
    const long long *offs;
    size_t len, ssize, seg;
    mt_verify_fn verify;
    const void *key;
    uint8_t *out;
    long long *res;
} shard_t;

static long long cpu_ns(void) {
    struct timespec ts;
    clock_gettime(CLOCK_THREAD_CPUTIME_ID, &ts);
    return (long long)ts.tv_sec * 1000000000LL + ts.tv_nsec;
}

// open, pread until len bytes or EOF, close: what read_file_stream's
// open / seek / read / close issue.  Returns 0 or an errno (*step says
// which call failed); *got is the bytes read.
static int pread_window(const char *path, long long off, uint8_t *buf,
                        size_t len, long long *got, long long *step) {
    int fd;
    do {
        fd = open(path, O_RDONLY | O_CLOEXEC);
    } while (fd < 0 && errno == EINTR);
    if (fd < 0) {
        *step = MT_SHARD_OPEN;
        return errno;
    }
    int err = 0;
    size_t n = 0;
    while (n < len) {
        ssize_t k = pread(fd, buf + n, len - n, (off_t)(off + (long long)n));
        if (k < 0) {
            if (errno == EINTR) continue;
            err = errno;
            *step = MT_SHARD_READ;
            break;
        }
        if (k == 0) break;
        n += (size_t)k;
    }
    close(fd);
    *got = (long long)n;
    return err;
}

// Move each frame's payload to the front of buf, frame by frame as the
// verify walked them ([32-byte digest][<= ssize bytes]); returns the
// payload bytes there.
static size_t gather_payload(uint8_t *buf, size_t len, size_t ssize) {
    size_t off = 0, dst = 0;
    while (off + 32 < len) {
        size_t n = len - off - 32 < ssize ? len - off - 32 : ssize;
        memmove(buf + dst, buf + off + 32, n);
        dst += n;
        off += 32 + n;
    }
    return dst;
}

static void shard_item(const void *ctx, int *errs, int i) {
    (void)errs;
    const shard_t *r = (const shard_t *)ctx;
    long long *res = r->res + (size_t)i * MT_SHARD_RES;
    uint8_t *row = r->out + (size_t)i * r->len;
    int sample = res[5] != 0;
    long long got = 0;
    res[1] = 0;
    res[5] = -1;
    res[2] = mono_ns();
    int err = pread_window(r->paths[i], r->offs[i], row, r->len, &got,
                           &res[1]);
    res[3] = res[4] = mono_ns();
    if (err) {
        res[0] = err;
        return;
    }
    if ((size_t)got < r->len) {
        res[0] = MT_SHARD_SHORT;
        res[1] = got;
        return;
    }
    long long c0 = sample ? cpu_ns() : 0;
    int bad = r->verify((const uint64_t *)r->key, row, r->len, r->ssize);
    size_t present = bad ? 0 : gather_payload(row, r->len, r->ssize);
    res[0] = bad ? MT_SHARD_BITROT : present < r->seg ? MT_SHARD_TRUNC : 0;
    res[1] = bad ? bad : res[0] ? (long long)present : got;
    if (sample) res[5] = cpu_ns() - c0;
    res[4] = mono_ns();
}

// Read, verify and gather every item; per item six int64s of res:
//   in:  res[5] != 0 to time the verify on its thread's CPU clock
//   out: res[0] 0, an errno or MT_SHARD_*; res[1] the failed step, the
//        MT_SHARD_* detail, or the bytes read; res[2] / res[3] / res[4]
//        CLOCK_MONOTONIC at the start, once read, once verified (the
//        read's end where nothing was verified); res[5] the verify's
//        thread CPU ns, or -1
void mt_read_verify_ranges(const char *const *paths, const long long *offs,
                           int n, size_t len, size_t ssize, size_t seg,
                           mt_verify_fn verify, const void *key,
                           uint8_t *out, long long *res) {
    shard_t sh = {paths, offs, len, ssize, seg, verify, key, out, res};
    wave(shard_item, &sh, n, 0);
}
