// The syscalls of a drive's writer thread (storage/commit.py) and of a
// quorum metadata read (storage/xl_storage.py), each group of them ONE
// call that never holds the interpreter lock:
//
//   * a wave of a group-commit flush (GroupCollector.flush): the fsyncs
//     of a round's files, or of its directories, issued together;
//   * the landing of a drive op's body (land_part / land_file, further
//     down): a file created, written, dup'd or fsynced, and closed, for
//     the part file behind its two mkdirs;
//   * a read wave (mt_read_files, at the end of this file): the xl.meta
//     file of every local drive of a set read into a slot each, for one
//     quorum metadata read (read_version_wave).
//
// Why native: under a loaded interpreter every blocking call a Python
// thread makes ends with a wait for the GIL, so a drive's writer thread
// that fsyncs a batch's ~40 files and directories one os.* call at a
// time spends its wall waiting for the interpreter, not for the drive
// (PERF.md, commit_flush_ms).  A metadata read that hands each drive's
// open / read / close to a pool thread pays a hand-over to start each
// child and one more per syscall (PERF.md, meta_queue_ms).  Here the whole
// wave costs the calling thread one release and one re-acquisition.
//
// The calls are the ones the Python code made, for the same objects:
//   files:  fsync(fd); close(fd)              errs[i] = errno of the fsync
//   dirs:   open(O_RDONLY|O_DIRECTORY); fsync; close     errors tolerated
//   reads:  open(O_RDONLY); fstat; read until EOF; close
// A wave is cut into at most MT_SYNC_SLICES slices, each on a thread of
// its own that is joined before the call returns: nothing of the wave is
// in flight when the caller goes on.

#define _GNU_SOURCE
#include <errno.h>
#include <fcntl.h>
#include <pthread.h>
#include <stddef.h>
#include <sys/stat.h>
#include <sys/types.h>
#include <time.h>
#include <unistd.h>

#define MT_SYNC_SLICES 8

typedef struct read_s read_t;    // a read wave's buffers (below)

typedef struct {
    const int *fds;          // files wave (or NULL)
    const char *const *dirs; // directories wave (or NULL)
    const read_t *rd;        // read wave (or NULL)
    int *errs;               // files and read waves: per item 0 or errno
    int n, first, step;
} slice_t;

static void read_item(const read_t *r, int *errs, int i);

static void sync_item(const slice_t *s, int i) {
    if (s->rd) {
        read_item(s->rd, s->errs, i);
        return;
    }
    if (s->fds) {
        int fd = s->fds[i];
        s->errs[i] = fsync(fd) == 0 ? 0 : errno;
        close(fd);
        return;
    }
    int dfd = open(s->dirs[i], O_RDONLY | O_DIRECTORY | O_CLOEXEC);
    if (dfd < 0) return;     // same tolerance as _fsync_dir
    fsync(dfd);
    close(dfd);
}

static void *run_slice(void *arg) {
    const slice_t *s = (const slice_t *)arg;
    for (int i = s->first; i < s->n; i += s->step) sync_item(s, i);
    return 0;
}

static void wave(const int *fds, const char *const *dirs, const read_t *rd,
                 int n, int *errs) {
    if (n <= 0) return;
    int k = n < MT_SYNC_SLICES ? n : MT_SYNC_SLICES;
    slice_t sl[MT_SYNC_SLICES];
    pthread_t th[MT_SYNC_SLICES];
    int started[MT_SYNC_SLICES];
    for (int j = 0; j < k; j++) {
        sl[j] = (slice_t){fds, dirs, rd, errs, n, j, k};
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
    wave(fds, 0, 0, n, errs);
}

// open + fsync + close every directory; errors are tolerated, as
// _fsync_dir tolerates them.
void mt_sync_dirs(const char *const *dirs, int n) {
    wave(0, dirs, 0, n, 0);
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

struct read_s {
    const char *const *paths;
    char *arena;
    size_t cap;              // bytes per slot
    long long *lens, *t0, *t1;
};

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

static void read_item(const read_t *r, int *errs, int i) {
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
    wave(0, 0, &rd, n, errs);
}
