// The syscalls of a drive's writer thread (storage/commit.py), each
// group of them ONE call that never holds the interpreter lock:
//
//   * a wave of a group-commit flush (GroupCollector.flush): the fsyncs
//     of a round's files, or of its directories, issued together;
//   * the landing of a drive op's body (land_part / land_file, at the
//     end of this file): a file created, written, dup'd or fsynced, and
//     closed, for the part file behind its two mkdirs.
//
// Why native: under a loaded interpreter every blocking call a Python
// thread makes ends with a wait for the GIL, so a drive's writer thread
// that fsyncs a batch's ~40 files and directories one os.* call at a
// time spends its wall waiting for the interpreter, not for the drive
// (PERF.md section 6, PR 30).  Here the whole wave costs the calling
// thread one release and one re-acquisition.
//
// The calls are the ones the Python loop made, for the same objects:
//   files:  fsync(fd); close(fd)              errs[i] = errno of the fsync
//   dirs:   open(O_RDONLY|O_DIRECTORY); fsync; close     errors tolerated
// A wave is cut into at most MT_SYNC_SLICES slices, each on a thread of
// its own that is joined before the call returns: nothing of the wave is
// in flight when the caller goes on to the round's continuations.

#define _GNU_SOURCE
#include <errno.h>
#include <fcntl.h>
#include <pthread.h>
#include <stddef.h>
#include <sys/stat.h>
#include <sys/types.h>
#include <unistd.h>

#define MT_SYNC_SLICES 8

typedef struct {
    const int *fds;          // files wave (or NULL)
    const char *const *dirs; // directories wave (or NULL)
    int *errs;               // files wave: per fd 0 or its fsync's errno
    int n, first, step;
} slice_t;

static void sync_item(const slice_t *s, int i) {
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

static void wave(const int *fds, const char *const *dirs, int n, int *errs) {
    if (n <= 0) return;
    int k = n < MT_SYNC_SLICES ? n : MT_SYNC_SLICES;
    slice_t sl[MT_SYNC_SLICES];
    pthread_t th[MT_SYNC_SLICES];
    int started[MT_SYNC_SLICES];
    for (int j = 0; j < k; j++) {
        sl[j] = (slice_t){fds, dirs, errs, n, j, k};
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
    wave(fds, 0, n, errs);
}

// open + fsync + close every directory; errors are tolerated, as
// _fsync_dir tolerates them.
void mt_sync_dirs(const char *const *dirs, int n) {
    wave(0, dirs, n, 0);
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
