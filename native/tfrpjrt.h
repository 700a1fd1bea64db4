/* tfrpjrt: C interface of the native PJRT execution core.
 *
 * The TPU-native analogue of the reference's libtensorflow C++ session
 * layer (TensorFlowOps.scala:46-64 readGraph/withSession + session.Run):
 * a serialized StableHLO computation is loaded, compiled and executed
 * entirely in C++, with host buffers exposed to the caller for zero-copy
 * reads (results are written straight into caller-provided memory).
 *
 * Two backends behind one interface:
 *   - "cpu" / "cpu:<n>"  — XLA:CPU hosted in-process via the PJRT C++ API
 *     (linked from libtensorflow_cc; the local-test backend);
 *   - "plugin:<path>"    — any PJRT C API plugin loaded with dlopen;
 *     on TPU hosts, libtpu.so (the production backend).
 *
 * All functions are thread-compatible; a client may be shared across
 * threads (PJRT clients are thread-safe; no tfLock analogue is needed,
 * unlike the reference's global lock, DebugRowOps.scala:718-719).
 */
#ifndef TFRPJRT_H_
#define TFRPJRT_H_

#include <stddef.h>

#ifdef __cplusplus
extern "C" {
#endif

typedef struct tfr_pjrt_client tfr_pjrt_client;
typedef struct tfr_pjrt_exe tfr_pjrt_exe;
typedef struct tfr_pjrt_results tfr_pjrt_results;
/* A device-resident buffer detached from a results set: lets a caller
 * chain executions without a host round-trip per dispatch (the
 * device-resident loop the jax path gets for free). */
typedef struct tfr_pjrt_buffer tfr_pjrt_buffer;

/* dtype codes (stable across backends; mapped internally) */
enum tfr_dtype {
  TFR_F32 = 1,
  TFR_F64 = 2,
  TFR_I32 = 3,
  TFR_I64 = 4,
  TFR_BF16 = 5,
  TFR_PRED = 6,
};

/* Create a client. spec: "cpu", "cpu:<ndevices>", or "plugin:<path.so>".
 * Either form may carry URL-style options: "plugin:<path>?k=v&k2=v2".
 * Values that parse as integers are passed to the plugin as int64
 * NamedValues, everything else as strings (PJRT_Client_Create
 * create_options). The reserved key "tfr_device"
 * selects the addressable-device ordinal this client executes on
 * (default 0) and is not forwarded to the plugin.
 * Returns NULL on failure with a message in err. */
tfr_pjrt_client* tfr_pjrt_client_create(const char* spec, char* err,
                                        int errlen);
void tfr_pjrt_client_destroy(tfr_pjrt_client* c);
int tfr_pjrt_client_device_count(tfr_pjrt_client* c);
/* Writes the platform name into out; returns its length. */
int tfr_pjrt_client_platform(tfr_pjrt_client* c, char* out, int outlen);

/* Compile a StableHLO module (text or MLIR bytecode). */
tfr_pjrt_exe* tfr_pjrt_compile(tfr_pjrt_client* c, const char* module_bytes,
                               long module_len, char* err, int errlen);

/* Compile a DYNAMIC-shape serialized StableHLO module (the jax.export
 * wire format with symbolic dims) at the given concrete argument shapes:
 * shape refinement + lowering to HLO happen natively, so the executing
 * host needs no jax. cc_version is the module's calling-convention
 * version; platforms_csv lists the platforms it was lowered for (comma
 * separated, in order) and select_platform picks this host's entry when
 * there is more than one. dtypes/ndims/dims describe the argument shapes
 * exactly as in tfr_pjrt_execute. */
tfr_pjrt_exe* tfr_pjrt_compile_dynamic(
    tfr_pjrt_client* c, const char* module_bytes, long module_len,
    int cc_version, const char* platforms_csv, const char* select_platform,
    int nargs, const int* dtypes, const int* ndims, const long long* dims,
    char* err, int errlen);

/* As tfr_pjrt_compile_dynamic, replicated n_replicas times (SPMD). */
tfr_pjrt_exe* tfr_pjrt_compile_dynamic_n(
    tfr_pjrt_client* c, const char* module_bytes, long module_len,
    int cc_version, const char* platforms_csv, const char* select_platform,
    int nargs, const int* dtypes, const int* ndims, const long long* dims,
    int n_replicas, char* err, int errlen);

/* SPMD-replicated compile: one program instance per device,
 * n_replicas <= device count (and < 128). */
tfr_pjrt_exe* tfr_pjrt_compile_n(tfr_pjrt_client* c,
                                 const char* module_bytes, long module_len,
                                 int n_replicas, char* err, int errlen);

/* GSPMD-partitioned compile: num_replicas = 1, num_partitions =
 * n_partitions, SPMD partitioning ON. The module is a jax mesh lowering
 * (GSPMD flavor): GLOBAL-shaped parameters/results annotated with
 * mhlo.sharding attributes; XLA's SPMD partitioner splits it into the
 * per-device program, inserting the ICI/host collectives the shardings
 * imply. Execute with tfr_pjrt_execute_replicated, n = n_partitions; each
 * device's argument is its SHARD of the global array (dims describe the
 * shard — all shards equal-shaped, row-axis padding is the caller's job),
 * and results come back device-major as shards (replicated outputs: one
 * full copy per device). */
tfr_pjrt_exe* tfr_pjrt_compile_spmd(tfr_pjrt_client* c,
                                    const char* module_bytes,
                                    long module_len, int n_partitions,
                                    char* err, int errlen);

/* Execute a replicated executable across its devices in ONE call.
 * data holds n_replicas * nargs host pointers, replica-major; every
 * replica shares the same shapes (dtypes/ndims/dims as in
 * tfr_pjrt_execute). Results are replica-major: n_replicas * n_outputs
 * entries. */
tfr_pjrt_results* tfr_pjrt_execute_replicated(
    tfr_pjrt_client* c, tfr_pjrt_exe* e, int n_replicas, int nargs,
    const int* dtypes, const int* ndims, const long long* dims,
    const void* const* data, char* err, int errlen);

void tfr_pjrt_exe_destroy(tfr_pjrt_exe* e);

/* Execute on the client's device (ordinal "tfr_device" from the spec;
 * default 0). Inputs are dense row-major host buffers.
 * dims is one flat array; ndims[i] gives each argument's rank and the
 * dims of argument i follow those of i-1. Returns NULL on failure. */
tfr_pjrt_results* tfr_pjrt_execute(tfr_pjrt_client* c, tfr_pjrt_exe* e,
                                   int nargs, const int* dtypes,
                                   const int* ndims, const long long* dims,
                                   const void* const* data, char* err,
                                   int errlen);

int tfr_pjrt_results_count(tfr_pjrt_results* r);
/* dims must have room for 8 entries; returns 0 on success. */
int tfr_pjrt_result_meta(tfr_pjrt_results* r, int i, int* dtype, int* ndim,
                         long long* dims);
/* Copy result i (dense row-major) into dst; nbytes must match exactly.
 * Returns 0 on success. */
int tfr_pjrt_result_read(tfr_pjrt_results* r, int i, void* dst,
                         long long nbytes, char* err, int errlen);
void tfr_pjrt_results_destroy(tfr_pjrt_results* r);

/* Detach result i as a standalone DEVICE-RESIDENT buffer handle. The
 * buffer stays in device memory (HBM on TPU); the results slot is
 * emptied (meta/read on it fail afterwards). The caller owns the handle
 * and may pass it back as an input to
 * tfr_pjrt_execute_replicated_mixed — the residency contract that turns
 * per-call host marshalling into a device loop. Returns NULL on
 * out-of-range or already-released slots. */
tfr_pjrt_buffer* tfr_pjrt_result_release_buffer(tfr_pjrt_results* r, int i);
/* dims must have room for 8 entries; returns 0 on success. */
int tfr_pjrt_buffer_meta(tfr_pjrt_buffer* b, int* dtype, int* ndim,
                         long long* dims);
void tfr_pjrt_buffer_destroy(tfr_pjrt_buffer* b);

/* As tfr_pjrt_execute_replicated, but each (replica, arg) slot may be a
 * device-resident buffer instead of host memory: dev_bufs holds
 * n_replicas * nargs entries, replica-major; a non-NULL entry is used
 * directly (it must live on that replica's device — true for buffers
 * released from a result slot of the same (replica, executable-family)
 * position) and the corresponding data entry is ignored. dev_bufs NULL
 * means all-host (identical to tfr_pjrt_execute_replicated). dtypes/
 * ndims/dims still describe every argument (device entries included —
 * they are part of the program signature). Buffers are NOT consumed:
 * the same handle may be passed to many executions and must still be
 * destroyed by the caller. */
tfr_pjrt_results* tfr_pjrt_execute_replicated_mixed(
    tfr_pjrt_client* c, tfr_pjrt_exe* e, int n_replicas, int nargs,
    const int* dtypes, const int* ndims, const long long* dims,
    const void* const* data, tfr_pjrt_buffer* const* dev_bufs, char* err,
    int errlen);

#ifdef __cplusplus
}
#endif

#endif /* TFRPJRT_H_ */
