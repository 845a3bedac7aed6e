/* Counts the 16 KiB mmap / munmap pairs of a process: CPython 3.12's frame
 * stack grows in 16 KiB chunks, maps a new chunk when a call does not fit in
 * the current one and unmaps it when that call returns. A hot call that
 * straddles a chunk boundary therefore pays an mmap and a munmap every time.
 * Tracing and lowering the scanned train step recurse deeply through flax and
 * jax, so where the boundary falls there depends on every frame above the
 * jitted call: on the chip's host (sandboxed kernel, slow mmap) the same
 * program's `setup_trace_s` read 19.6 s or 24.7 s for a shift of 88 bytes
 * (PERF.md section 6, PR 27). The count is the same on any machine with the
 * same Python, so it can be read here, without the chip:
 *
 *   gcc -shared -fPIC -O2 -o /tmp/chunk_shim.so perfbench/tools/chunk_shim.c -ldl
 *   MMAP_REPORT=/tmp/chunks.txt LD_PRELOAD=/tmp/chunk_shim.so JAX_PLATFORMS=cpu \
 *     python3 perfbench/run.py --workload bert_base.finetune --seed 5 \
 *     --seconds 1 --trace 0 --rehearse-cpu; cat /tmp/chunks.txt
 *
 * Readings at the rehearsal's sizes (my runs, PR 27): parent 20,836; PR 27 as
 * committed 21,129; PR 27 with the dispatch in a helper method 28,386.
 */
#define _GNU_SOURCE
#include <dlfcn.h>
#include <stdatomic.h>
#include <stdio.h>
#include <stdlib.h>
#include <sys/mman.h>
#include <unistd.h>

#define CHUNK 16384

static atomic_long n_map, n_unmap, n_all;
static void *(*real_mmap)(void *, size_t, int, int, int, off_t);
static void *(*real_mmap64)(void *, size_t, int, int, int, off_t);
static int (*real_munmap)(void *, size_t);

void *mmap(void *addr, size_t len, int prot, int flags, int fd, off_t off) {
    if (!real_mmap) real_mmap = dlsym(RTLD_NEXT, "mmap");
    atomic_fetch_add(&n_all, 1);
    if (len == CHUNK) atomic_fetch_add(&n_map, 1);
    return real_mmap(addr, len, prot, flags, fd, off);
}

void *mmap64(void *addr, size_t len, int prot, int flags, int fd, off_t off) {
    if (!real_mmap64) real_mmap64 = dlsym(RTLD_NEXT, "mmap64");
    atomic_fetch_add(&n_all, 1);
    if (len == CHUNK) atomic_fetch_add(&n_map, 1);
    return real_mmap64(addr, len, prot, flags, fd, off);
}

int munmap(void *addr, size_t len) {
    if (!real_munmap) real_munmap = dlsym(RTLD_NEXT, "munmap");
    if (len == CHUNK) atomic_fetch_add(&n_unmap, 1);
    return real_munmap(addr, len);
}

__attribute__((destructor)) static void report(void) {
    const char *path = getenv("MMAP_REPORT");
    FILE *f = path ? fopen(path, "a") : NULL;
    if (f) {
        fprintf(f, "pid %d mmap16k %ld munmap16k %ld all %ld\n", getpid(),
                (long)n_map, (long)n_unmap, (long)n_all);
        fclose(f);
    }
}
