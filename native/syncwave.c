// One wave of a drive's group-commit flush (storage/commit.py
// GroupCollector.flush): the fsyncs of a round's files, or of its
// directories, issued together from ONE call that never holds the
// interpreter lock.
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
