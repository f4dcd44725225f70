/*
 * libmxtpu — native runtime for the TPU framework.
 *
 * TPU-native counterpart of the reference's C++ runtime core:
 *  - dependency engine (parity: src/engine/threaded_engine.{h,cc},
 *    include/mxnet/engine.h:75-229): device compute is scheduled by
 *    PjRt/XLA, so this engine schedules the *host-side* async work the
 *    reference also ran through its engine — IO prefetch, checkpoint
 *    writes, kvstore staging — with the same const/mutable var-ordering
 *    contract (writers serialized, readers parallel, per-var FIFO).
 *  - RecordIO (parity: dmlc-core recordio framing + InputSplit sharding):
 *    native frame scanner/writer so the data pipeline's record handling
 *    is not bottlenecked on Python.
 *  - pooled storage arena (parity: src/storage/pooled_storage_manager.h):
 *    size-class recycling for host staging buffers.
 */
#ifndef MXTPU_H_
#define MXTPU_H_

#include <stddef.h>
#include <stdint.h>

#ifdef __cplusplus
extern "C" {
#endif

/* ------------------------------------------------------------------ engine */
typedef void (*mxe_fn_t)(void *ctx);

/* Create an engine with n worker threads (0 = hardware_concurrency). */
void *mxe_create(int num_threads);
void mxe_destroy(void *engine);

/* New variable handle; freed with the engine. */
int64_t mxe_new_var(void *engine);

/* Push an async op: fn(ctx) runs once all deps resolve.  const_vars are
 * read deps (parallel), mutable_vars write deps (serialized, FIFO per
 * var).  Duplicate or overlapping var lists are rejected with -1
 * (parity: ThreadedEngine::CheckDuplicate); unknown/freed var ids with
 * -2.  priority: higher runs first among ready ops. */
int mxe_push(void *engine, mxe_fn_t fn, void *ctx,
             const int64_t *const_vars, int num_const,
             const int64_t *mutable_vars, int num_mutable,
             int priority);

/* Like mxe_push, plus a retirement hook: done_fn(done_ctx) is invoked on
 * the worker thread strictly AFTER fn has returned.  Callers managing
 * closure lifetimes (ctypes trampolines) use it as the release point —
 * once done_fn fires, fn's stack frame and trampoline have fully
 * unwound, so freeing fn is safe. */
int mxe_push_ex(void *engine, mxe_fn_t fn, void *ctx,
                mxe_fn_t done_fn, void *done_ctx,
                const int64_t *const_vars, int num_const,
                const int64_t *mutable_vars, int num_mutable,
                int priority);

/* Block until all ops touching var have completed. */
int mxe_wait_for_var(void *engine, int64_t var);
/* Block until every pushed op has completed. */
void mxe_wait_all(void *engine);
/* Number of ops pushed but not yet completed. */
int64_t mxe_pending(void *engine);

/* ---------------------------------------------------------------- recordio */
/* Reader over one shard of a RecordIO file (part_index/num_parts as in
 * dmlc::InputSplit): byte-range split, then aligned to record magic. */
void *mxr_open(const char *path, int part_index, int num_parts);
void mxr_close(void *reader);
/* Next record: returns pointer valid until the following call, or NULL at
 * end of shard; *len receives the payload length. */
const uint8_t *mxr_next(void *reader, uint64_t *len);
void mxr_reset(void *reader);
/* Batched read: fill buf (capacity buf_cap bytes) with up to max_records
 * concatenated payloads; lens[i] receives each payload's length.  Returns
 * the number of records read (0 at end of shard).  One FFI crossing per
 * batch instead of per record. */
int64_t mxr_next_batch(void *reader, uint8_t *buf, uint64_t buf_cap,
                       uint64_t *lens, int64_t max_records);
/* Scan the whole file, filling offsets[] (at most cap); returns count. */
int64_t mxr_index(const char *path, uint64_t *offsets, int64_t cap);

void *mxr_writer_open(const char *path);
int mxr_write(void *writer, const uint8_t *buf, uint64_t len);
void mxr_writer_close(void *writer);

/* ------------------------------------------------------------- jpeg decode */
/* Header-only parse: fills w/h/c (c always 3: decode converts to RGB). */
int mxj_dims(const uint8_t *src, uint64_t len, uint32_t *w, uint32_t *h,
             uint32_t *c);
/* Full RGB8 decode into dst (capacity cap bytes, needs w*h*3).  Both
 * return 0 on success, -1 on malformed input.  Thread-safe, GIL-free. */
int mxj_decode(const uint8_t *src, uint64_t len, uint8_t *dst,
               uint64_t cap);

/* ----------------------------------------------------------------- storage */
/* Pooled aligned host allocator.  Freed blocks are recycled by
 * round-up-to-pow2 size class. */
void *mxs_alloc(uint64_t size);
void mxs_free(void *ptr);
void mxs_direct_free(void *ptr);   /* bypass pool */
uint64_t mxs_pool_bytes(void);      /* bytes held in free lists */
void mxs_release_all(void);         /* drop pooled blocks */

/* ---- predict-only C ABI (libmxtpu_predict.so; parity:
 * include/mxnet/c_predict_api.h).  Embeds CPython; XLA does the math.
 * dev_type: 1 = cpu, 2 = accelerator.  All return 0/-1; error text via
 * MXPredGetLastError(). */
const char *MXPredGetLastError(void);
int MXPredCreate(const char *symbol_json, const void *param_bytes,
                 int param_size, int dev_type, int dev_id,
                 uint32_t num_input_nodes, const char **input_keys,
                 const uint32_t *input_shape_indptr,
                 const uint32_t *input_shape_data, void **out);
int MXPredSetInput(void *handle, const char *key, const float *data,
                   uint32_t size);
int MXPredForward(void *handle);
/* Pipelined inference: ForwardAsync dispatches without joining and hands
 * back a ticket; GetOutputAsync joins that ticket.  Keeping 2+ tickets in
 * flight overlaps input upload, compute, and output fetch across calls. */
int MXPredForwardAsync(void *handle, int64_t *out_ticket);
int MXPredGetOutputAsync(void *handle, int64_t ticket, uint32_t index,
                         float *data, uint32_t size);
int MXPredGetOutputShape(void *handle, uint32_t index, uint32_t **shape_data,
                         uint32_t *shape_ndim);
int MXPredGetOutput(void *handle, uint32_t index, float *data, uint32_t size);
int MXPredFree(void *handle);

#ifdef __cplusplus
}
#endif

#endif /* MXTPU_H_ */
