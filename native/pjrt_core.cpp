// Native PJRT execution core (see tfrpjrt.h for the interface contract).
//
// The reference executes every graph in C++ through libtensorflow sessions
// (TensorFlowOps.scala:46-64, DebugRowOps.scala:776-788); this is the
// TPU-native equivalent: serialized StableHLO in, XLA compile + execute in
// C++, results written straight into caller-owned host memory.
//
//   backend "cpu"           — XLA:CPU via the PJRT C++ API, linked from
//                             libtensorflow_cc (local tests; same compiler
//                             stack XLA uses everywhere);
//   backend "plugin:<path>" — any PJRT C API plugin via dlopen, e.g.
//                             /...//libtpu.so on TPU hosts. Pure C ABI.
//
// LLVM/MLIR headers are not shipped in this environment, so mlir-typed
// PJRT entry points are declared through a one-pointer stub (mlir_stub/)
// and the module parse goes through the exported
// ParseMlirModuleStringAndConvertToXlaComputation symbol instead of
// mlir_to_hlo.h. NDEBUG is required: tsl AsyncValue type-ids are assigned
// per-DSO, so its DCHECK-only accessor checks cannot pass across the
// library boundary (the data accesses themselves are layout-stable).

#include "tfrpjrt.h"

#include <dlfcn.h>

#include <cerrno>
#include <cstdlib>
#include <cstring>
#include <memory>
#include <string>
#include <string_view>
#include <vector>

#include <mutex>

#include "xla/pjrt/pjrt_client.h"
#include "xla/pjrt/pjrt_executable.h"
#include "xla/pjrt/plugin/xla_cpu/xla_cpu_pjrt_client.h"
#include "xla/hlo/builder/xla_computation.h"
#include "xla/pjrt/c/pjrt_c_api.h"
#include "xla/shape.h"
#include "xla/shape_util.h"
#include "xla/service/hlo.pb.h"

namespace xla {
// Declared here to avoid mlir_to_hlo.h's LLVM header dependency; resolved
// against the exported symbol in libtensorflow_cc.
absl::Status ParseMlirModuleStringAndConvertToXlaComputation(
    std::string_view mlir_module_str, XlaComputation& xla_computation,
    bool use_tuple_args, bool return_tuple);
}  // namespace xla

// ---------------------------------------------------------------------------
// ABI declarations for tensorflow::XlaCallModuleLoader (the jax.export /
// XlaCallModule dynamic-shape loader in libtensorflow_cc) without the
// LLVM/MLIR headers this environment does not ship. Only layout-stable
// value types cross the boundary: llvm::StringRef and llvm::ArrayRef are
// {pointer, size} pairs; mlir::MLIRContext is a single-unique_ptr pimpl
// constructed through its exported out-of-line constructor.
// ---------------------------------------------------------------------------

namespace mlir {
class MLIRContext {
 public:
  enum class Threading { DISABLED, ENABLED };
  explicit MLIRContext(Threading t);
  ~MLIRContext();

 private:
  void* impl_;  // stands in for std::unique_ptr<MLIRContextImpl>
};
}  // namespace mlir

namespace llvm {
class StringRef {
 public:
  StringRef(const char* d, size_t l) : data_(d), len_(l) {}
  const char* data_;
  size_t len_;
};
template <typename T>
class ArrayRef {
 public:
  ArrayRef(const T* d, size_t l) : data_(d), len_(l) {}
  const T* data_;
  size_t len_;
};
}  // namespace llvm

namespace tensorflow {
class XlaCallModuleLoader {
 public:
  static absl::StatusOr<std::unique_ptr<XlaCallModuleLoader>> Create(
      mlir::MLIRContext* context, int version, llvm::StringRef module_str,
      std::vector<std::string> disabled_checks,
      std::vector<std::string> platforms, int num_invocation_args,
      bool main_has_token_input_output, bool use_shardy_partitioner);
  absl::Status SetPlatformIndex(std::string_view compilation_platform);
  absl::Status RefineDynamicShapes(llvm::ArrayRef<xla::Shape> input_shapes);
  absl::Status ValidateStaticShapes();
  absl::StatusOr<xla::XlaComputation> ToXlaComputation();
};
}  // namespace tensorflow

namespace {

void set_err(char* err, int errlen, const std::string& msg) {
  if (err && errlen > 0) {
    std::snprintf(err, static_cast<size_t>(errlen), "%s", msg.c_str());
  }
}

// "plugin:/x.so?topology=v5e:1x1x1&n_slices=1" -> base "plugin:/x.so" +
// ordered (key, value) pairs. Only the LAST '?' before the first '&'
// region is honored as the option separator so .so paths containing '?'
// (never in practice) don't need escaping.
struct SpecOption {
  std::string key;
  std::string value;
  bool is_int = false;
  long long int_value = 0;
};

std::vector<SpecOption> parse_spec_options(std::string* spec) {
  std::vector<SpecOption> out;
  auto q = spec->find('?');
  if (q == std::string::npos) return out;
  std::string opts = spec->substr(q + 1);
  spec->resize(q);
  size_t pos = 0;
  while (pos <= opts.size()) {
    auto amp = opts.find('&', pos);
    std::string pair = opts.substr(
        pos, amp == std::string::npos ? std::string::npos : amp - pos);
    if (!pair.empty()) {
      SpecOption o;
      auto eq = pair.find('=');
      if (eq == std::string::npos) {
        o.key = pair;
      } else {
        o.key = pair.substr(0, eq);
        o.value = pair.substr(eq + 1);
      }
      if (!o.value.empty()) {
        char* end = nullptr;
        errno = 0;
        long long v = std::strtoll(o.value.c_str(), &end, 10);
        if (errno == 0 && end && *end == '\0') {
          o.is_int = true;
          o.int_value = v;
        }
      }
      out.push_back(std::move(o));
    }
    if (amp == std::string::npos) break;
    pos = amp + 1;
  }
  return out;
}

// Pops the reserved "tfr_device" option; returns the ordinal (default 0).
int take_device_ordinal(std::vector<SpecOption>* opts) {
  int ordinal = 0;
  for (auto it = opts->begin(); it != opts->end();) {
    if (it->key == "tfr_device") {
      if (it->is_int) ordinal = static_cast<int>(it->int_value);
      it = opts->erase(it);
    } else {
      ++it;
    }
  }
  return ordinal;
}

// ---------------------------------------------------------------------------
// Backend interface
// ---------------------------------------------------------------------------

// A device-resident buffer detached from a results set; passed back as
// an execution input to keep loop state in device memory across
// dispatches (no host round-trip per call).
struct BufIface {
  virtual ~BufIface() = default;
  virtual int meta(int* dtype, int* ndim, long long* dims) const = 0;
};

struct ResultsIface {
  virtual ~ResultsIface() = default;
  virtual int count() const = 0;
  virtual int meta(int i, int* dtype, int* ndim, long long* dims) const = 0;
  virtual int read(int i, void* dst, long long nbytes, std::string* err) = 0;
  // Detach slot i as a standalone device buffer (slot becomes empty);
  // nullptr on out-of-range / already-released slots.
  virtual BufIface* release(int i) = 0;
};

struct ExeIface {
  virtual ~ExeIface() = default;
};

struct ClientIface {
  virtual ~ClientIface() = default;
  virtual int device_count() const = 0;
  virtual std::string platform() const = 0;
  virtual ExeIface* compile(std::string_view module, std::string* err) = 0;
  // Compile a serialized xla.HloModuleProto (the output of the dynamic-
  // shape refinement below), replicated n_replicas times (1 = single).
  virtual ExeIface* compile_hlo(const std::string& hlo_proto,
                                std::string* err, int n_replicas = 1) = 0;
  virtual ResultsIface* execute(ExeIface* exe, int nargs, const int* dtypes,
                                const int* ndims, const long long* dims,
                                const void* const* data,
                                std::string* err) = 0;
  // SPMD-replicated: compile for n_replicas devices and run one program
  // instance per device in a single call (the per-executor parallel
  // dispatch of the reference's executor fleet, in-process).
  virtual ExeIface* compile_n(std::string_view module, int n_replicas,
                              std::string* err) = 0;
  // GSPMD-partitioned: ONE logical program over n_partitions devices
  // (num_replicas=1, use_spmd_partitioning on); the module carries
  // mhlo.sharding annotations from a jax mesh lowering and XLA's SPMD
  // partitioner emits the per-device program + collectives. This is the
  // mesh layer's executor: the distributed half of the framework running
  // in C++, not just the per-partition half.
  virtual ExeIface* compile_spmd(std::string_view module, int n_partitions,
                                 std::string* err) = 0;
  // data: n_replicas * nargs host pointers, replica-major; every replica
  // shares the same shapes. Results are replica-major too
  // (n_replicas * n_outputs entries).
  virtual ResultsIface* execute_replicated(
      ExeIface* exe, int n_replicas, int nargs, const int* dtypes,
      const int* ndims, const long long* dims, const void* const* data,
      std::string* err) {
    return execute_replicated_mixed(exe, n_replicas, nargs, dtypes, ndims,
                                    dims, data, nullptr, err);
  }
  // As execute_replicated, but a non-null dev_bufs[r*nargs + a] entry is
  // used as that slot's input directly (device-resident, not consumed —
  // the caller still owns it); the matching data entry is ignored.
  virtual ResultsIface* execute_replicated_mixed(
      ExeIface* exe, int n_replicas, int nargs, const int* dtypes,
      const int* ndims, const long long* dims, const void* const* data,
      BufIface* const* dev_bufs, std::string* err) = 0;
};

long long dense_elems(int ndim, const long long* dims) {
  long long n = 1;
  for (int i = 0; i < ndim; ++i) n *= dims[i];
  return n;
}

int dtype_size(int dt) {
  switch (dt) {
    case TFR_F32: case TFR_I32: return 4;
    case TFR_F64: case TFR_I64: return 8;
    case TFR_BF16: return 2;
    case TFR_PRED: return 1;
  }
  return 0;
}

xla::PrimitiveType to_xla_type(int dt);  // defined below

// Refine a serialized jax.export StableHLO module (symbolic/dynamic dims)
// at concrete argument shapes and lower it to a serialized HloModuleProto —
// entirely in C++, no jax on the executing host. This is the executor-side
// step the reference performed by parsing GraphDef bytes in libtensorflow
// (TensorFlowOps.scala:46-52); here the shipped program is StableHLO and
// the shape specialization runs TF's XlaCallModuleLoader refinement.
absl::StatusOr<std::string> refine_to_hlo_proto(
    std::string_view module_bytes, int cc_version,
    const std::vector<std::string>& platforms,
    const std::string& select_platform, int nargs, const int* dtypes,
    const int* ndims, const long long* dims) {
  // one context + one refinement at a time: the loader mutates the module
  // and MLIR contexts are not cheap; serialize access behind a mutex
  static std::mutex mu;
  static mlir::MLIRContext* ctx = new mlir::MLIRContext(
      mlir::MLIRContext::Threading::DISABLED);
  std::lock_guard<std::mutex> lock(mu);

  auto loader_or = tensorflow::XlaCallModuleLoader::Create(
      ctx, cc_version,
      llvm::StringRef(module_bytes.data(), module_bytes.size()),
      /*disabled_checks=*/{}, platforms, /*num_invocation_args=*/nargs,
      /*main_has_token_input_output=*/false,
      /*use_shardy_partitioner=*/false);
  if (!loader_or.ok()) return loader_or.status();
  // Intentionally released, never deleted: the stub declaration above has
  // no destructor knowledge, and callers cache the compiled executable per
  // signature, so the leak is one module-sized object per native compile.
  tensorflow::XlaCallModuleLoader* loader = loader_or.value().release();
  if (platforms.size() > 1) {
    auto st = loader->SetPlatformIndex(select_platform);
    if (!st.ok()) return st;
  }
  std::vector<xla::Shape> shapes;
  const long long* d = dims;
  for (int a = 0; a < nargs; ++a) {
    std::vector<int64_t> shp(d, d + ndims[a]);
    d += ndims[a];
    shapes.push_back(xla::ShapeUtil::MakeShape(
        to_xla_type(dtypes[a]),
        absl::Span<const int64_t>(shp.data(), shp.size())));
  }
  auto st = loader->RefineDynamicShapes(
      llvm::ArrayRef<xla::Shape>(shapes.data(), shapes.size()));
  if (!st.ok()) return st;
  st = loader->ValidateStaticShapes();
  if (!st.ok()) return st;
  auto xc_or = loader->ToXlaComputation();
  if (!xc_or.ok()) return xc_or.status();
  return xc_or.value().proto().SerializeAsString();
}

// ---------------------------------------------------------------------------
// C++-API backend (XLA:CPU from libtensorflow_cc)
// ---------------------------------------------------------------------------

xla::PrimitiveType to_xla_type(int dt) {
  switch (dt) {
    case TFR_F32: return xla::PrimitiveType::F32;
    case TFR_F64: return xla::PrimitiveType::F64;
    case TFR_I32: return xla::PrimitiveType::S32;
    case TFR_I64: return xla::PrimitiveType::S64;
    case TFR_BF16: return xla::PrimitiveType::BF16;
    case TFR_PRED: return xla::PrimitiveType::PRED;
  }
  return xla::PrimitiveType::PRIMITIVE_TYPE_INVALID;
}

int from_xla_type(xla::PrimitiveType t) {
  switch (t) {
    case xla::PrimitiveType::F32: return TFR_F32;
    case xla::PrimitiveType::F64: return TFR_F64;
    case xla::PrimitiveType::S32: return TFR_I32;
    case xla::PrimitiveType::S64: return TFR_I64;
    case xla::PrimitiveType::BF16: return TFR_BF16;
    case xla::PrimitiveType::PRED: return TFR_PRED;
    default: return 0;
  }
}

struct CppExe : ExeIface {
  std::unique_ptr<xla::PjRtLoadedExecutable> exe;
};

struct CppBuf : BufIface {
  std::unique_ptr<xla::PjRtBuffer> buf;

  int meta(int* dtype, int* ndim, long long* dims) const override {
    *dtype = from_xla_type(buf->element_type());
    auto d = buf->dimensions();
    if (d.size() > 8) return 2;
    *ndim = static_cast<int>(d.size());
    for (size_t k = 0; k < d.size(); ++k) dims[k] = d[k];
    return 0;
  }
};

struct CppResults : ResultsIface {
  std::vector<std::unique_ptr<xla::PjRtBuffer>> bufs;

  int count() const override { return static_cast<int>(bufs.size()); }

  BufIface* release(int i) override {
    if (i < 0 || i >= count() || !bufs[i]) return nullptr;
    auto* b = new CppBuf();
    b->buf = std::move(bufs[i]);  // slot left empty; meta/read now fail
    return b;
  }

  int meta(int i, int* dtype, int* ndim, long long* dims) const override {
    if (i < 0 || i >= count() || !bufs[i]) return 1;
    const auto& b = bufs[i];
    *dtype = from_xla_type(b->element_type());
    auto d = b->dimensions();
    if (d.size() > 8) return 2;
    *ndim = static_cast<int>(d.size());
    for (size_t k = 0; k < d.size(); ++k) dims[k] = d[k];
    return 0;
  }

  int read(int i, void* dst, long long nbytes, std::string* err) override {
    if (i < 0 || i >= count() || !bufs[i]) {
      *err = "result index out of range or buffer released";
      return 1;
    }
    auto& b = bufs[i];
    auto sz = b->GetOnDeviceSizeInBytes();
    if (!sz.ok()) { *err = sz.status().ToString(); return 1; }
    if (static_cast<long long>(*sz) != nbytes) {
      *err = "size mismatch: device has " + std::to_string(*sz) +
             " bytes, caller expects " + std::to_string(nbytes) +
             " (non-dense layout?)";
      return 1;
    }
    auto st = b->CopyRawToHost(dst, 0, *sz).Await();
    if (!st.ok()) { *err = st.ToString(); return 1; }
    return 0;
  }
};

struct CppClient : ClientIface {
  std::unique_ptr<xla::PjRtClient> client;
  int device_ordinal = 0;

  int device_count() const override { return client->device_count(); }

  std::string platform() const override {
    return std::string(client->platform_name());
  }

  ExeIface* compile(std::string_view module, std::string* err) override {
    xla::XlaComputation xc;
    auto st = xla::ParseMlirModuleStringAndConvertToXlaComputation(
        module, xc, /*use_tuple_args=*/false, /*return_tuple=*/false);
    if (!st.ok()) { *err = st.ToString(); return nullptr; }
    return compile_xla(std::move(xc), err);
  }

  ExeIface* compile_hlo(const std::string& hlo_proto, std::string* err,
                        int n_replicas = 1) override {
    xla::HloModuleProto proto;
    if (!proto.ParseFromString(hlo_proto)) {
      *err = "HloModuleProto parse failed";
      return nullptr;
    }
    return compile_xla(xla::XlaComputation(std::move(proto)), err,
                       n_replicas);
  }

  ExeIface* compile_xla(xla::XlaComputation xc, std::string* err,
                        int n_replicas = 1, int n_partitions = 1) {
    xla::CompileOptions opts;
    if (n_replicas > 1) {
      opts.executable_build_options.set_num_replicas(n_replicas);
    }
    if (n_partitions > 1) {
      opts.executable_build_options.set_num_partitions(n_partitions);
      opts.executable_build_options.set_use_spmd_partitioning(true);
    }
    auto exe_or = client->CompileAndLoad(xc, opts);
    if (!exe_or.ok()) { *err = exe_or.status().ToString(); return nullptr; }
    auto* e = new CppExe();
    e->exe = std::move(exe_or).value();
    return e;
  }

  ExeIface* compile_n(std::string_view module, int n_replicas,
                      std::string* err) override {
    if (n_replicas < 1 || n_replicas > device_count()) {
      *err = "n_replicas " + std::to_string(n_replicas) +
             " out of range (1.." + std::to_string(device_count()) + ")";
      return nullptr;
    }
    xla::XlaComputation xc;
    auto st = xla::ParseMlirModuleStringAndConvertToXlaComputation(
        module, xc, /*use_tuple_args=*/false, /*return_tuple=*/false);
    if (!st.ok()) { *err = st.ToString(); return nullptr; }
    return compile_xla(std::move(xc), err, n_replicas);
  }

  ExeIface* compile_spmd(std::string_view module, int n_partitions,
                         std::string* err) override {
    if (n_partitions < 1 || n_partitions > device_count()) {
      *err = "n_partitions " + std::to_string(n_partitions) +
             " out of range (1.." + std::to_string(device_count()) + ")";
      return nullptr;
    }
    xla::XlaComputation xc;
    auto st = xla::ParseMlirModuleStringAndConvertToXlaComputation(
        module, xc, /*use_tuple_args=*/false, /*return_tuple=*/false);
    if (!st.ok()) { *err = st.ToString(); return nullptr; }
    return compile_xla(std::move(xc), err, /*n_replicas=*/1, n_partitions);
  }

  ResultsIface* execute_replicated_mixed(ExeIface* exe_i, int n_replicas,
                                         int nargs, const int* dtypes,
                                         const int* ndims,
                                         const long long* dims,
                                         const void* const* data,
                                         BufIface* const* dev_bufs,
                                         std::string* err) override {
    auto* exe = static_cast<CppExe*>(exe_i);
    // the executable's own devices, in execution order — covers both
    // replicated (n replicas x 1 partition) and GSPMD-partitioned
    // (1 replica x n partitions) executables; Execute's argument lists
    // are positional over this same sequence
    auto exe_devices = exe->exe->addressable_devices();
    if (n_replicas < 1 ||
        n_replicas != static_cast<int>(exe_devices.size())) {
      *err = "n devices " + std::to_string(n_replicas) +
             " does not match the executable's device count " +
             std::to_string(exe_devices.size());
      return nullptr;
    }
    std::vector<std::vector<std::unique_ptr<xla::PjRtBuffer>>> in_bufs(
        n_replicas);
    std::vector<std::vector<xla::PjRtBuffer*>> arg_lists(n_replicas);
    for (int r = 0; r < n_replicas; ++r) {
      xla::PjRtDevice* device = exe_devices[r];
      auto ms_or = device->default_memory_space();
      if (!ms_or.ok()) { *err = ms_or.status().ToString(); return nullptr; }
      const long long* d = dims;
      for (int a = 0; a < nargs; ++a) {
        std::vector<int64_t> shape(d, d + ndims[a]);
        d += ndims[a];
        if (dev_bufs && dev_bufs[r * nargs + a]) {
          // device-resident input: borrowed, not consumed (the caller
          // keeps ownership; default-compiled programs donate nothing)
          arg_lists[r].push_back(
              static_cast<CppBuf*>(dev_bufs[r * nargs + a])->buf.get());
          continue;
        }
        auto buf_or = client->BufferFromHostBuffer(
            data[r * nargs + a], to_xla_type(dtypes[a]), shape,
            std::nullopt,
            xla::PjRtClient::HostBufferSemantics::kImmutableOnlyDuringCall,
            nullptr, ms_or.value(), nullptr);
        if (!buf_or.ok()) {
          *err = buf_or.status().ToString();
          return nullptr;
        }
        in_bufs[r].push_back(std::move(buf_or).value());
        arg_lists[r].push_back(in_bufs[r].back().get());
      }
    }
    // multi-output programs come back as one tuple buffer unless asked
    // to untuple; CppResults expects one buffer per output
    xla::ExecuteOptions exec_opts;
    exec_opts.untuple_result = true;
    auto out_or = exe->exe->Execute(absl::MakeSpan(arg_lists), exec_opts);
    if (!out_or.ok()) { *err = out_or.status().ToString(); return nullptr; }
    auto* res = new CppResults();
    for (auto& per_replica : out_or.value()) {
      for (auto& b : per_replica) res->bufs.push_back(std::move(b));
    }
    return res;
  }

  ResultsIface* execute(ExeIface* exe_i, int nargs, const int* dtypes,
                        const int* ndims, const long long* dims,
                        const void* const* data, std::string* err) override {
    auto* exe = static_cast<CppExe*>(exe_i);
    auto devices = client->addressable_devices();
    if (device_ordinal < 0 ||
        device_ordinal >= static_cast<int>(devices.size())) {
      *err = "device ordinal " + std::to_string(device_ordinal) +
             " out of range (" + std::to_string(devices.size()) +
             " addressable devices)";
      return nullptr;
    }
    auto* device = devices[device_ordinal];
    auto ms_or = device->default_memory_space();
    if (!ms_or.ok()) { *err = ms_or.status().ToString(); return nullptr; }

    std::vector<std::unique_ptr<xla::PjRtBuffer>> in_bufs;
    std::vector<xla::PjRtBuffer*> in_ptrs;
    const long long* d = dims;
    for (int a = 0; a < nargs; ++a) {
      std::vector<int64_t> shape(d, d + ndims[a]);
      d += ndims[a];
      auto buf_or = client->BufferFromHostBuffer(
          data[a], to_xla_type(dtypes[a]), shape, std::nullopt,
          xla::PjRtClient::HostBufferSemantics::kImmutableOnlyDuringCall,
          nullptr, ms_or.value(), nullptr);
      if (!buf_or.ok()) { *err = buf_or.status().ToString(); return nullptr; }
      in_bufs.push_back(std::move(buf_or).value());
      in_ptrs.push_back(in_bufs.back().get());
    }
    std::vector<std::vector<xla::PjRtBuffer*>> arg_lists = {in_ptrs};
    xla::ExecuteOptions exec_opts;
    exec_opts.untuple_result = true;
    auto out_or = exe->exe->Execute(absl::MakeSpan(arg_lists), exec_opts);
    if (!out_or.ok()) { *err = out_or.status().ToString(); return nullptr; }
    auto* r = new CppResults();
    r->bufs = std::move(out_or.value()[0]);
    return r;
  }
};

// ---------------------------------------------------------------------------
// PJRT C API backend (dlopen'd plugin, e.g. libtpu.so)
// ---------------------------------------------------------------------------

std::string capi_err(const PJRT_Api* api, PJRT_Error* e) {
  if (!e) return "";
  PJRT_Error_Message_Args m;
  std::memset(&m, 0, sizeof(m));
  m.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  m.error = e;
  api->PJRT_Error_Message(&m);
  std::string msg(m.message, m.message_size);
  PJRT_Error_Destroy_Args dd;
  std::memset(&dd, 0, sizeof(dd));
  dd.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dd.error = e;
  api->PJRT_Error_Destroy(&dd);
  return msg;
}

// Awaits and destroys the event; returns error message or "".
std::string capi_await(const PJRT_Api* api, PJRT_Event* ev) {
  if (!ev) return "";
  PJRT_Event_Await_Args aw;
  std::memset(&aw, 0, sizeof(aw));
  aw.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  aw.event = ev;
  std::string msg = capi_err(api, api->PJRT_Event_Await(&aw));
  PJRT_Event_Destroy_Args dd;
  std::memset(&dd, 0, sizeof(dd));
  dd.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  dd.event = ev;
  api->PJRT_Event_Destroy(&dd);
  return msg;
}

PJRT_Buffer_Type to_capi_type(int dt) {
  switch (dt) {
    case TFR_F32: return PJRT_Buffer_Type_F32;
    case TFR_F64: return PJRT_Buffer_Type_F64;
    case TFR_I32: return PJRT_Buffer_Type_S32;
    case TFR_I64: return PJRT_Buffer_Type_S64;
    case TFR_BF16: return PJRT_Buffer_Type_BF16;
    case TFR_PRED: return PJRT_Buffer_Type_PRED;
  }
  return PJRT_Buffer_Type_INVALID;
}

int from_capi_type(PJRT_Buffer_Type t) {
  switch (t) {
    case PJRT_Buffer_Type_F32: return TFR_F32;
    case PJRT_Buffer_Type_F64: return TFR_F64;
    case PJRT_Buffer_Type_S32: return TFR_I32;
    case PJRT_Buffer_Type_S64: return TFR_I64;
    case PJRT_Buffer_Type_BF16: return TFR_BF16;
    case PJRT_Buffer_Type_PRED: return TFR_PRED;
    default: return 0;
  }
}

// Minimal serialized xla.CompileOptionsProto:
//   executable_build_options (field 3) {
//     num_replicas (field 4) = 1; num_partitions (field 5) = 1; }
const char kCompileOptionsProto[] = {0x1a, 0x04, 0x20, 0x01, 0x28, 0x01};

// Same proto with num_replicas = n (single-byte varint, n < 128).
std::string compile_options_proto(int n_replicas) {
  std::string p(kCompileOptionsProto, sizeof(kCompileOptionsProto));
  p[3] = static_cast<char>(n_replicas);
  return p;
}

// executable_build_options { num_replicas (4) = 1; num_partitions (5) = n;
// use_spmd_partitioning (6) = true } — the GSPMD compile request
// (n < 128 keeps every varint single-byte).
std::string compile_options_proto_spmd(int n_partitions) {
  std::string ebo;
  ebo += '\x20'; ebo += '\x01';                           // num_replicas=1
  ebo += '\x28'; ebo += static_cast<char>(n_partitions);  // num_partitions
  ebo += '\x30'; ebo += '\x01';                           // use_spmd=true
  std::string p;
  p += '\x1a';                                            // field 3, LEN
  p += static_cast<char>(ebo.size());
  p += ebo;
  return p;
}

struct CApiExe : ExeIface {
  const PJRT_Api* api = nullptr;
  PJRT_LoadedExecutable* exe = nullptr;
  ~CApiExe() override {
    if (exe) {
      PJRT_LoadedExecutable_Destroy_Args dd;
      std::memset(&dd, 0, sizeof(dd));
      dd.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
      dd.executable = exe;
      capi_err(api, api->PJRT_LoadedExecutable_Destroy(&dd));
    }
  }
};

// Shared meta query for a single PJRT_Buffer (results + detached bufs).
int capi_buffer_meta(const PJRT_Api* api, PJRT_Buffer* buf, int* dtype,
                     int* ndim, long long* dims) {
  PJRT_Buffer_ElementType_Args et;
  std::memset(&et, 0, sizeof(et));
  et.struct_size = PJRT_Buffer_ElementType_Args_STRUCT_SIZE;
  et.buffer = buf;
  if (api->PJRT_Buffer_ElementType(&et)) return 2;
  *dtype = from_capi_type(et.type);
  PJRT_Buffer_Dimensions_Args dm;
  std::memset(&dm, 0, sizeof(dm));
  dm.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
  dm.buffer = buf;
  if (api->PJRT_Buffer_Dimensions(&dm)) return 2;
  if (dm.num_dims > 8) return 2;
  *ndim = static_cast<int>(dm.num_dims);
  for (size_t k = 0; k < dm.num_dims; ++k) dims[k] = dm.dims[k];
  return 0;
}

struct CApiBuf : BufIface {
  const PJRT_Api* api = nullptr;
  PJRT_Buffer* buf = nullptr;

  ~CApiBuf() override {
    if (buf) {
      PJRT_Buffer_Destroy_Args dd;
      std::memset(&dd, 0, sizeof(dd));
      dd.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
      dd.buffer = buf;
      capi_err(api, api->PJRT_Buffer_Destroy(&dd));
    }
  }

  int meta(int* dtype, int* ndim, long long* dims) const override {
    return capi_buffer_meta(api, buf, dtype, ndim, dims);
  }
};

struct CApiResults : ResultsIface {
  const PJRT_Api* api = nullptr;
  std::vector<PJRT_Buffer*> bufs;

  ~CApiResults() override {
    for (auto* b : bufs) {
      if (!b) continue;  // released slots
      PJRT_Buffer_Destroy_Args dd;
      std::memset(&dd, 0, sizeof(dd));
      dd.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
      dd.buffer = b;
      capi_err(api, api->PJRT_Buffer_Destroy(&dd));
    }
  }

  int count() const override { return static_cast<int>(bufs.size()); }

  BufIface* release(int i) override {
    if (i < 0 || i >= count() || !bufs[i]) return nullptr;
    auto* b = new CApiBuf();
    b->api = api;
    b->buf = bufs[i];
    bufs[i] = nullptr;  // slot emptied; meta/read now fail
    return b;
  }

  int meta(int i, int* dtype, int* ndim, long long* dims) const override {
    if (i < 0 || i >= count() || !bufs[i]) return 1;
    return capi_buffer_meta(api, bufs[i], dtype, ndim, dims);
  }

  int read(int i, void* dst, long long nbytes, std::string* err) override {
    if (i < 0 || i >= count() || !bufs[i]) {
      *err = "result index out of range or buffer released";
      return 1;
    }
    PJRT_Buffer_ToHostBuffer_Args th;
    std::memset(&th, 0, sizeof(th));
    th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    th.src = bufs[i];
    th.dst = nullptr;  // query size
    if (auto* e = api->PJRT_Buffer_ToHostBuffer(&th)) {
      *err = capi_err(api, e);
      return 1;
    }
    if (static_cast<long long>(th.dst_size) != nbytes) {
      *err = "size mismatch: host needs " + std::to_string(th.dst_size) +
             " bytes, caller expects " + std::to_string(nbytes);
      return 1;
    }
    th.dst = dst;
    if (auto* e = api->PJRT_Buffer_ToHostBuffer(&th)) {
      *err = capi_err(api, e);
      return 1;
    }
    std::string msg = capi_await(api, th.event);
    if (!msg.empty()) { *err = msg; return 1; }
    return 0;
  }
};

struct CApiClient : ClientIface {
  void* dl = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  int device_ordinal = 0;

  ~CApiClient() override {
    if (client) {
      PJRT_Client_Destroy_Args dd;
      std::memset(&dd, 0, sizeof(dd));
      dd.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
      dd.client = client;
      capi_err(api, api->PJRT_Client_Destroy(&dd));
    }
    // The plugin stays loaded (dlclose of live XLA runtimes is unsafe).
  }

  std::string init(const std::string& path,
                   const std::vector<SpecOption>& options) {
    dl = dlopen(path.c_str(), RTLD_NOW | RTLD_LOCAL);
    if (!dl) return std::string("dlopen failed: ") + dlerror();
    using GetApiFn = const PJRT_Api* (*)();
    auto get_api = reinterpret_cast<GetApiFn>(dlsym(dl, "GetPjrtApi"));
    if (!get_api) return "plugin has no GetPjrtApi symbol";
    api = get_api();
    if (!api) return "GetPjrtApi returned null";
    PJRT_Plugin_Initialize_Args pi;
    std::memset(&pi, 0, sizeof(pi));
    pi.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    if (auto* e = api->PJRT_Plugin_Initialize(&pi)) {
      return "plugin init failed: " + capi_err(api, e);
    }
    // Spec options become PJRT NamedValues (int64 when numeric, string
    // otherwise).
    std::vector<PJRT_NamedValue> nvs(options.size());
    for (size_t i = 0; i < options.size(); ++i) {
      auto& nv = nvs[i];
      std::memset(&nv, 0, sizeof(nv));
      nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
      nv.name = options[i].key.c_str();
      nv.name_size = options[i].key.size();
      if (options[i].is_int) {
        nv.type = PJRT_NamedValue_kInt64;
        nv.int64_value = options[i].int_value;
        nv.value_size = 1;
      } else {
        nv.type = PJRT_NamedValue_kString;
        nv.string_value = options[i].value.c_str();
        nv.value_size = options[i].value.size();
      }
    }
    PJRT_Client_Create_Args cc;
    std::memset(&cc, 0, sizeof(cc));
    cc.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
    cc.create_options = nvs.data();
    cc.num_options = nvs.size();
    if (auto* e = api->PJRT_Client_Create(&cc)) {
      return "client create failed: " + capi_err(api, e);
    }
    client = cc.client;
    return "";
  }

  int device_count() const override {
    PJRT_Client_AddressableDevices_Args ad;
    std::memset(&ad, 0, sizeof(ad));
    ad.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
    ad.client = client;
    if (api->PJRT_Client_AddressableDevices(&ad)) return -1;
    return static_cast<int>(ad.num_addressable_devices);
  }

  std::string platform() const override {
    PJRT_Client_PlatformName_Args pn;
    std::memset(&pn, 0, sizeof(pn));
    pn.struct_size = PJRT_Client_PlatformName_Args_STRUCT_SIZE;
    pn.client = client;
    if (api->PJRT_Client_PlatformName(&pn)) return "?";
    return std::string(pn.platform_name, pn.platform_name_size);
  }

  ExeIface* compile(std::string_view module, std::string* err) override {
    return compile_fmt(module, "mlir", err);
  }

  ExeIface* compile_hlo(const std::string& hlo_proto, std::string* err,
                        int n_replicas = 1) override {
    return compile_fmt(hlo_proto, "hlo", err, n_replicas);
  }

  ExeIface* compile_fmt(std::string_view module, const char* format,
                        std::string* err, int n_replicas = 1,
                        int n_partitions = 1) {
    PJRT_Program prog;
    std::memset(&prog, 0, sizeof(prog));
    prog.struct_size = PJRT_Program_STRUCT_SIZE;
    prog.code = const_cast<char*>(module.data());
    prog.code_size = module.size();
    prog.format = format;
    prog.format_size = std::strlen(format);

    std::string opts = n_partitions > 1
        ? compile_options_proto_spmd(n_partitions)
        : compile_options_proto(n_replicas);
    PJRT_Client_Compile_Args ca;
    std::memset(&ca, 0, sizeof(ca));
    ca.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
    ca.client = client;
    ca.program = &prog;
    ca.compile_options = opts.data();
    ca.compile_options_size = opts.size();
    if (auto* e = api->PJRT_Client_Compile(&ca)) {
      *err = capi_err(api, e);
      return nullptr;
    }
    auto* ex = new CApiExe();
    ex->api = api;
    ex->exe = ca.executable;
    return ex;
  }

  ExeIface* compile_n(std::string_view module, int n_replicas,
                      std::string* err) override {
    if (n_replicas < 1 || n_replicas > 127 ||
        n_replicas > device_count()) {
      *err = "n_replicas " + std::to_string(n_replicas) +
             " out of range (1.." + std::to_string(device_count()) + ")";
      return nullptr;
    }
    return compile_fmt(module, "mlir", err, n_replicas);
  }

  ExeIface* compile_spmd(std::string_view module, int n_partitions,
                         std::string* err) override {
    if (n_partitions < 1 || n_partitions > 127 ||
        n_partitions > device_count()) {
      *err = "n_partitions " + std::to_string(n_partitions) +
             " out of range (1.." + std::to_string(device_count()) + ")";
      return nullptr;
    }
    return compile_fmt(module, "mlir", err, /*n_replicas=*/1, n_partitions);
  }

  ResultsIface* execute_replicated_mixed(ExeIface* exe_i, int n_replicas,
                                         int nargs, const int* dtypes,
                                         const int* ndims,
                                         const long long* dims,
                                         const void* const* data,
                                         BufIface* const* dev_bufs,
                                         std::string* err) override {
    auto* exe = static_cast<CApiExe*>(exe_i);
    // the executable's addressable devices, in replica order
    PJRT_LoadedExecutable_AddressableDevices_Args ad;
    std::memset(&ad, 0, sizeof(ad));
    ad.struct_size =
        PJRT_LoadedExecutable_AddressableDevices_Args_STRUCT_SIZE;
    ad.executable = exe->exe;
    if (auto* e = api->PJRT_LoadedExecutable_AddressableDevices(&ad)) {
      *err = capi_err(api, e);
      return nullptr;
    }
    if (static_cast<int>(ad.num_addressable_devices) < n_replicas) {
      *err = "executable has " + std::to_string(ad.num_addressable_devices)
             + " addressable devices, need " + std::to_string(n_replicas);
      return nullptr;
    }

    std::vector<PJRT_Buffer*> in_bufs;  // only buffers we created here
    auto destroy_inputs = [&]() {
      for (auto* b : in_bufs) {
        PJRT_Buffer_Destroy_Args dd;
        std::memset(&dd, 0, sizeof(dd));
        dd.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
        dd.buffer = b;
        capi_err(api, api->PJRT_Buffer_Destroy(&dd));
      }
    };
    std::vector<std::vector<PJRT_Buffer*>> arg_lists(n_replicas);
    for (int r = 0; r < n_replicas; ++r) {
      PJRT_Device* device = ad.addressable_devices[r];
      const long long* d = dims;
      for (int a = 0; a < nargs; ++a) {
        std::vector<int64_t> shape(d, d + ndims[a]);
        d += ndims[a];
        if (dev_bufs && dev_bufs[r * nargs + a]) {
          // device-resident input: borrowed (caller keeps ownership;
          // not added to in_bufs, so never destroyed here)
          arg_lists[r].push_back(
              static_cast<CApiBuf*>(dev_bufs[r * nargs + a])->buf);
          continue;
        }
        PJRT_Client_BufferFromHostBuffer_Args bh;
        std::memset(&bh, 0, sizeof(bh));
        bh.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
        bh.client = client;
        bh.data = data[r * nargs + a];
        bh.type = to_capi_type(dtypes[a]);
        bh.dims = shape.data();
        bh.num_dims = shape.size();
        bh.host_buffer_semantics =
            PJRT_HostBufferSemantics_kImmutableOnlyDuringCall;
        bh.device = device;
        if (auto* e = api->PJRT_Client_BufferFromHostBuffer(&bh)) {
          *err = capi_err(api, e);
          destroy_inputs();
          return nullptr;
        }
        std::string msg = capi_await(api, bh.done_with_host_buffer);
        in_bufs.push_back(bh.buffer);
        arg_lists[r].push_back(bh.buffer);
        if (!msg.empty()) {
          *err = msg;
          destroy_inputs();
          return nullptr;
        }
      }
    }

    PJRT_LoadedExecutable_GetExecutable_Args ge;
    std::memset(&ge, 0, sizeof(ge));
    ge.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
    ge.loaded_executable = exe->exe;
    if (auto* e = api->PJRT_LoadedExecutable_GetExecutable(&ge)) {
      *err = capi_err(api, e);
      destroy_inputs();
      return nullptr;
    }
    PJRT_Executable_NumOutputs_Args no;
    std::memset(&no, 0, sizeof(no));
    no.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
    no.executable = ge.executable;
    if (auto* e = api->PJRT_Executable_NumOutputs(&no)) {
      *err = capi_err(api, e);
      destroy_inputs();
      return nullptr;
    }

    PJRT_ExecuteOptions opts;
    std::memset(&opts, 0, sizeof(opts));
    opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

    std::vector<std::vector<PJRT_Buffer*>> outs(
        n_replicas, std::vector<PJRT_Buffer*>(no.num_outputs, nullptr));
    std::vector<PJRT_Buffer* const*> arg_ptrs(n_replicas);
    std::vector<PJRT_Buffer**> out_ptrs(n_replicas);
    for (int r = 0; r < n_replicas; ++r) {
      arg_ptrs[r] = arg_lists[r].data();
      out_ptrs[r] = outs[r].data();
    }
    std::vector<PJRT_Event*> done(n_replicas, nullptr);

    PJRT_LoadedExecutable_Execute_Args ex;
    std::memset(&ex, 0, sizeof(ex));
    ex.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
    ex.executable = exe->exe;
    ex.options = &opts;
    ex.argument_lists = arg_ptrs.data();
    ex.num_devices = static_cast<size_t>(n_replicas);
    ex.num_args = static_cast<size_t>(nargs);
    ex.output_lists = out_ptrs.data();
    ex.device_complete_events = done.data();
    ex.execute_device = nullptr;  // multi-device launch
    if (auto* e = api->PJRT_LoadedExecutable_Execute(&ex)) {
      *err = capi_err(api, e);
      destroy_inputs();
      return nullptr;
    }
    std::string msg;
    for (int r = 0; r < n_replicas; ++r) {
      std::string m = capi_await(api, done[r]);
      if (!m.empty() && msg.empty()) msg = m;
    }
    destroy_inputs();
    auto* res = new CApiResults();
    res->api = api;
    for (int r = 0; r < n_replicas; ++r) {
      for (auto* b : outs[r]) res->bufs.push_back(b);
    }
    if (!msg.empty()) {
      *err = msg;
      delete res;
      return nullptr;
    }
    return res;
  }

  ResultsIface* execute(ExeIface* exe_i, int nargs, const int* dtypes,
                        const int* ndims, const long long* dims,
                        const void* const* data, std::string* err) override {
    auto* exe = static_cast<CApiExe*>(exe_i);

    PJRT_Client_AddressableDevices_Args ad;
    std::memset(&ad, 0, sizeof(ad));
    ad.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
    ad.client = client;
    if (auto* e = api->PJRT_Client_AddressableDevices(&ad)) {
      *err = capi_err(api, e);
      return nullptr;
    }
    if (device_ordinal < 0 ||
        static_cast<size_t>(device_ordinal) >= ad.num_addressable_devices) {
      *err = "device ordinal " + std::to_string(device_ordinal) +
             " out of range (" + std::to_string(ad.num_addressable_devices) +
             " addressable devices)";
      return nullptr;
    }
    PJRT_Device* device = ad.addressable_devices[device_ordinal];

    std::vector<PJRT_Buffer*> in_bufs;
    auto destroy_inputs = [&]() {
      for (auto* b : in_bufs) {
        PJRT_Buffer_Destroy_Args dd;
        std::memset(&dd, 0, sizeof(dd));
        dd.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
        dd.buffer = b;
        capi_err(api, api->PJRT_Buffer_Destroy(&dd));
      }
    };
    const long long* d = dims;
    for (int a = 0; a < nargs; ++a) {
      std::vector<int64_t> shape(d, d + ndims[a]);
      d += ndims[a];
      PJRT_Client_BufferFromHostBuffer_Args bh;
      std::memset(&bh, 0, sizeof(bh));
      bh.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
      bh.client = client;
      bh.data = data[a];
      bh.type = to_capi_type(dtypes[a]);
      bh.dims = shape.data();
      bh.num_dims = shape.size();
      bh.host_buffer_semantics =
          PJRT_HostBufferSemantics_kImmutableOnlyDuringCall;
      bh.device = device;
      if (auto* e = api->PJRT_Client_BufferFromHostBuffer(&bh)) {
        *err = capi_err(api, e);
        destroy_inputs();
        return nullptr;
      }
      std::string msg = capi_await(api, bh.done_with_host_buffer);
      in_bufs.push_back(bh.buffer);
      if (!msg.empty()) {
        *err = msg;
        destroy_inputs();
        return nullptr;
      }
    }

    // number of outputs
    PJRT_LoadedExecutable_GetExecutable_Args ge;
    std::memset(&ge, 0, sizeof(ge));
    ge.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
    ge.loaded_executable = exe->exe;
    if (auto* e = api->PJRT_LoadedExecutable_GetExecutable(&ge)) {
      *err = capi_err(api, e);
      destroy_inputs();
      return nullptr;
    }
    PJRT_Executable_NumOutputs_Args no;
    std::memset(&no, 0, sizeof(no));
    no.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
    no.executable = ge.executable;
    if (auto* e = api->PJRT_Executable_NumOutputs(&no)) {
      *err = capi_err(api, e);
      destroy_inputs();
      return nullptr;
    }

    PJRT_ExecuteOptions opts;
    std::memset(&opts, 0, sizeof(opts));
    opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

    std::vector<PJRT_Buffer*> outs(no.num_outputs, nullptr);
    PJRT_Buffer* const* arg_list = in_bufs.data();
    PJRT_Buffer** out_list = outs.data();
    PJRT_Event* done = nullptr;

    PJRT_LoadedExecutable_Execute_Args ex;
    std::memset(&ex, 0, sizeof(ex));
    ex.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
    ex.executable = exe->exe;
    ex.options = &opts;
    ex.argument_lists = &arg_list;
    ex.num_devices = 1;
    ex.num_args = static_cast<size_t>(nargs);
    ex.output_lists = &out_list;
    ex.device_complete_events = &done;
    ex.execute_device = device;
    if (auto* e = api->PJRT_LoadedExecutable_Execute(&ex)) {
      *err = capi_err(api, e);
      destroy_inputs();
      return nullptr;
    }
    std::string msg = capi_await(api, done);
    destroy_inputs();
    auto* r = new CApiResults();
    r->api = api;
    r->bufs = std::move(outs);
    if (!msg.empty()) {
      *err = msg;
      delete r;  // destroys any produced output buffers
      return nullptr;
    }
    return r;
  }
};

}  // namespace

// ---------------------------------------------------------------------------
// C interface
// ---------------------------------------------------------------------------

struct tfr_pjrt_client {
  std::unique_ptr<ClientIface> impl;
};
struct tfr_pjrt_exe {
  std::unique_ptr<ExeIface> impl;
};
struct tfr_pjrt_results {
  std::unique_ptr<ResultsIface> impl;
};
struct tfr_pjrt_buffer {
  std::unique_ptr<BufIface> impl;
};

extern "C" {

tfr_pjrt_client* tfr_pjrt_client_create(const char* spec, char* err,
                                        int errlen) {
  std::string s(spec ? spec : "");
  try {
    std::vector<SpecOption> options = parse_spec_options(&s);
    int ordinal = take_device_ordinal(&options);
    if (s == "cpu" || s.rfind("cpu:", 0) == 0) {
      xla::CpuClientOptions opts;
      opts.cpu_device_count = 1;
      if (s.size() > 4) opts.cpu_device_count = std::stoi(s.substr(4));
      auto c_or = xla::GetXlaPjrtCpuClient(opts);
      if (!c_or.ok()) {
        set_err(err, errlen, c_or.status().ToString());
        return nullptr;
      }
      auto* c = new CppClient();
      c->client = std::move(c_or).value();
      c->device_ordinal = ordinal;
      auto* out = new tfr_pjrt_client();
      out->impl.reset(c);
      return out;
    }
    if (s.rfind("plugin:", 0) == 0) {
      auto* c = new CApiClient();
      c->device_ordinal = ordinal;
      std::string msg = c->init(s.substr(7), options);
      if (!msg.empty()) {
        set_err(err, errlen, msg);
        delete c;
        return nullptr;
      }
      auto* out = new tfr_pjrt_client();
      out->impl.reset(c);
      return out;
    }
  } catch (const std::exception& e) {
    set_err(err, errlen, e.what());
    return nullptr;
  }
  set_err(err, errlen, "unknown backend spec: " + s +
                       " (expected cpu[:n] or plugin:<path>)");
  return nullptr;
}

void tfr_pjrt_client_destroy(tfr_pjrt_client* c) { delete c; }

int tfr_pjrt_client_device_count(tfr_pjrt_client* c) {
  return c->impl->device_count();
}

int tfr_pjrt_client_platform(tfr_pjrt_client* c, char* out, int outlen) {
  std::string p = c->impl->platform();
  int n = static_cast<int>(p.size());
  if (out && outlen > 0) {
    std::snprintf(out, static_cast<size_t>(outlen), "%s", p.c_str());
  }
  return n;
}

tfr_pjrt_exe* tfr_pjrt_compile(tfr_pjrt_client* c, const char* module_bytes,
                               long module_len, char* err, int errlen) {
  std::string errmsg;
  ExeIface* e = c->impl->compile(
      std::string_view(module_bytes, static_cast<size_t>(module_len)),
      &errmsg);
  if (!e) {
    set_err(err, errlen, errmsg);
    return nullptr;
  }
  auto* out = new tfr_pjrt_exe();
  out->impl.reset(e);
  return out;
}

tfr_pjrt_exe* tfr_pjrt_compile_dynamic(
    tfr_pjrt_client* c, const char* module_bytes, long module_len,
    int cc_version, const char* platforms_csv, const char* select_platform,
    int nargs, const int* dtypes, const int* ndims, const long long* dims,
    char* err, int errlen) {
  return tfr_pjrt_compile_dynamic_n(
      c, module_bytes, module_len, cc_version, platforms_csv,
      select_platform, nargs, dtypes, ndims, dims, 1, err, errlen);
}

tfr_pjrt_exe* tfr_pjrt_compile_dynamic_n(
    tfr_pjrt_client* c, const char* module_bytes, long module_len,
    int cc_version, const char* platforms_csv, const char* select_platform,
    int nargs, const int* dtypes, const int* ndims, const long long* dims,
    int n_replicas, char* err, int errlen) {
  std::vector<std::string> platforms;
  std::string csv(platforms_csv ? platforms_csv : "");
  size_t pos = 0;
  while (pos <= csv.size() && !csv.empty()) {
    auto comma = csv.find(',', pos);
    platforms.push_back(csv.substr(
        pos, comma == std::string::npos ? std::string::npos : comma - pos));
    if (comma == std::string::npos) break;
    pos = comma + 1;
  }
  auto hlo_or = refine_to_hlo_proto(
      std::string_view(module_bytes, static_cast<size_t>(module_len)),
      cc_version, platforms,
      std::string(select_platform ? select_platform : ""), nargs, dtypes,
      ndims, dims);
  if (!hlo_or.ok()) {
    set_err(err, errlen, hlo_or.status().ToString());
    return nullptr;
  }
  std::string errmsg;
  ExeIface* e = c->impl->compile_hlo(hlo_or.value(), &errmsg, n_replicas);
  if (!e) {
    set_err(err, errlen, errmsg);
    return nullptr;
  }
  auto* out = new tfr_pjrt_exe();
  out->impl.reset(e);
  return out;
}

tfr_pjrt_exe* tfr_pjrt_compile_n(tfr_pjrt_client* c,
                                 const char* module_bytes, long module_len,
                                 int n_replicas, char* err, int errlen) {
  std::string errmsg;
  ExeIface* e = c->impl->compile_n(
      std::string_view(module_bytes, static_cast<size_t>(module_len)),
      n_replicas, &errmsg);
  if (!e) {
    set_err(err, errlen, errmsg);
    return nullptr;
  }
  auto* out = new tfr_pjrt_exe();
  out->impl.reset(e);
  return out;
}

tfr_pjrt_exe* tfr_pjrt_compile_spmd(tfr_pjrt_client* c,
                                    const char* module_bytes,
                                    long module_len, int n_partitions,
                                    char* err, int errlen) {
  std::string errmsg;
  ExeIface* e = c->impl->compile_spmd(
      std::string_view(module_bytes, static_cast<size_t>(module_len)),
      n_partitions, &errmsg);
  if (!e) {
    set_err(err, errlen, errmsg);
    return nullptr;
  }
  auto* out = new tfr_pjrt_exe();
  out->impl.reset(e);
  return out;
}

tfr_pjrt_results* tfr_pjrt_execute_replicated(
    tfr_pjrt_client* c, tfr_pjrt_exe* e, int n_replicas, int nargs,
    const int* dtypes, const int* ndims, const long long* dims,
    const void* const* data, char* err, int errlen) {
  for (int a = 0; a < nargs; ++a) {
    if (dtype_size(dtypes[a]) == 0) {
      set_err(err, errlen,
              "unsupported dtype code " + std::to_string(dtypes[a]));
      return nullptr;
    }
  }
  std::string errmsg;
  ResultsIface* r = c->impl->execute_replicated(
      e->impl.get(), n_replicas, nargs, dtypes, ndims, dims, data, &errmsg);
  if (!r) {
    set_err(err, errlen, errmsg);
    return nullptr;
  }
  auto* out = new tfr_pjrt_results();
  out->impl.reset(r);
  return out;
}

void tfr_pjrt_exe_destroy(tfr_pjrt_exe* e) { delete e; }

tfr_pjrt_results* tfr_pjrt_execute(tfr_pjrt_client* c, tfr_pjrt_exe* e,
                                   int nargs, const int* dtypes,
                                   const int* ndims, const long long* dims,
                                   const void* const* data, char* err,
                                   int errlen) {
  for (int a = 0; a < nargs; ++a) {
    if (dtype_size(dtypes[a]) == 0) {
      set_err(err, errlen,
              "unsupported dtype code " + std::to_string(dtypes[a]));
      return nullptr;
    }
  }
  std::string errmsg;
  ResultsIface* r =
      c->impl->execute(e->impl.get(), nargs, dtypes, ndims, dims, data,
                       &errmsg);
  if (!r) {
    set_err(err, errlen, errmsg);
    return nullptr;
  }
  auto* out = new tfr_pjrt_results();
  out->impl.reset(r);
  return out;
}

int tfr_pjrt_results_count(tfr_pjrt_results* r) { return r->impl->count(); }

int tfr_pjrt_result_meta(tfr_pjrt_results* r, int i, int* dtype, int* ndim,
                         long long* dims) {
  return r->impl->meta(i, dtype, ndim, dims);
}

int tfr_pjrt_result_read(tfr_pjrt_results* r, int i, void* dst,
                         long long nbytes, char* err, int errlen) {
  std::string errmsg;
  int rc = r->impl->read(i, dst, nbytes, &errmsg);
  if (rc) set_err(err, errlen, errmsg);
  return rc;
}

void tfr_pjrt_results_destroy(tfr_pjrt_results* r) { delete r; }

tfr_pjrt_buffer* tfr_pjrt_result_release_buffer(tfr_pjrt_results* r,
                                                int i) {
  BufIface* b = r->impl->release(i);
  if (!b) return nullptr;
  auto* out = new tfr_pjrt_buffer();
  out->impl.reset(b);
  return out;
}

int tfr_pjrt_buffer_meta(tfr_pjrt_buffer* b, int* dtype, int* ndim,
                         long long* dims) {
  return b->impl->meta(dtype, ndim, dims);
}

void tfr_pjrt_buffer_destroy(tfr_pjrt_buffer* b) { delete b; }

tfr_pjrt_results* tfr_pjrt_execute_replicated_mixed(
    tfr_pjrt_client* c, tfr_pjrt_exe* e, int n_replicas, int nargs,
    const int* dtypes, const int* ndims, const long long* dims,
    const void* const* data, tfr_pjrt_buffer* const* dev_bufs, char* err,
    int errlen) {
  for (int a = 0; a < nargs; ++a) {
    if (dtype_size(dtypes[a]) == 0) {
      set_err(err, errlen,
              "unsupported dtype code " + std::to_string(dtypes[a]));
      return nullptr;
    }
  }
  std::vector<BufIface*> devs;
  if (dev_bufs) {
    devs.resize(static_cast<size_t>(n_replicas) * nargs, nullptr);
    for (size_t i = 0; i < devs.size(); ++i) {
      if (dev_bufs[i]) devs[i] = dev_bufs[i]->impl.get();
    }
  }
  std::string errmsg;
  ResultsIface* r = c->impl->execute_replicated_mixed(
      e->impl.get(), n_replicas, nargs, dtypes, ndims, dims, data,
      dev_bufs ? devs.data() : nullptr, &errmsg);
  if (!r) {
    set_err(err, errlen, errmsg);
    return nullptr;
  }
  auto* out = new tfr_pjrt_results();
  out->impl.reset(r);
  return out;
}

}  // extern "C"
