// Python-free serving: replay an AOT artifact through the PJRT C API.
//
// Reference analog: paddle/fluid/inference/api/paddle_api.h:199 — the
// genuinely Python-free C++ deployment engine. The embedded-CPython shim
// (serving.cc) keeps that API shape but still requires a Python runtime
// in-process; THIS library removes it: the serving computation was
// AOT-lowered to StableHLO by jax.export (inference/export_serving.py),
// and here we dlopen any PJRT plugin (libtpu.so),
// compile the bytecode via PJRT_Client_Compile, and execute — no
// libpython linked, no interpreter started (the e2e test asserts the
// .so's dependency closure is Python-free).
//
//   int   pds_probe(const char* plugin_path, int* major, int* minor);
//            dlopen + GetPjrtApi + version handshake only (CI-testable
//            against a stub plugin; no client is created).
//   void* pds_load(const char* artifact_dir, const char* plugin_path);
//            full init: plugin, client (a chip belongs to one process
//            — one pds_load per process), compile every bucket, upload
//            weights once.
//   int   pds_run(void* h, int batch_size, const void** in_data,
//                 const float** out_data, const long long** out_shapes,
//                 int* out_ndims, int max_outputs);
//            inputs in manifest feed order at the manifest dtypes;
//            outputs marshaled to float32 (S32 outputs cast), owned by
//            the handle until the next run/destroy.
//   void  pds_destroy(void* h);
//   const char* pds_last_error(void);
//
// Build (native/__init__.py): g++ pjrt_serving.cc tensor_store.cc
//   -I<tensorflow>/include -ldl        (no python flags!)

#include "xla/pjrt/c/pjrt_c_api.h"

#include <dlfcn.h>

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <string>
#include <vector>

// tensor_store.cc's C API (ts_read_*): the weights reader
extern "C" {
void* ts_read_open(const char* path);
int ts_read_count(void* h);
const char* ts_read_name(void* h, int i);
int ts_read_dtype(void* h, int i);
int ts_read_ndim(void* h, int i);
void ts_read_dims(void* h, int i, int64_t* out);
const void* ts_read_data(void* h, int i);
int64_t ts_read_nbytes(void* h, int i);
void ts_read_close(void* h);
}

namespace {

std::string g_error;

void set_error(const std::string& msg) { g_error = msg; }

// tensor_store dtype codes (native/dtypes.py CODE_OF_DTYPE — the one
// authoritative table: 0=f32 1=i64 2=f64 3=i32 4=u8 5=bf16 6=bool
// 7=f16 8=i8 9=u32 10=i16) -> PJRT_Buffer_Type
PJRT_Buffer_Type ts_to_pjrt(int ts_dtype) {
  switch (ts_dtype) {
    case 0: return PJRT_Buffer_Type_F32;
    case 1: return PJRT_Buffer_Type_S64;
    case 2: return PJRT_Buffer_Type_F64;
    case 3: return PJRT_Buffer_Type_S32;
    case 4: return PJRT_Buffer_Type_U8;
    case 5: return PJRT_Buffer_Type_BF16;
    case 6: return PJRT_Buffer_Type_PRED;
    case 7: return PJRT_Buffer_Type_F16;
    case 8: return PJRT_Buffer_Type_S8;
    case 9: return PJRT_Buffer_Type_U32;
    case 10: return PJRT_Buffer_Type_S16;
    default: return PJRT_Buffer_Type_INVALID;
  }
}

struct TensorMeta {
  std::string name;
  int pjrt_type = 0;
  std::vector<int64_t> dims;
  int64_t elems() const {
    int64_t n = 1;
    for (auto d : dims) n *= d;
    return n;
  }
};

struct Bucket {
  int batch_size = 0;
  std::string module_file;
  std::vector<TensorMeta> feeds;
  std::vector<TensorMeta> outs;
  PJRT_LoadedExecutable* exec = nullptr;
};

struct Handle {
  void* dl = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  PJRT_Device* device = nullptr;
  std::vector<std::string> platforms;   // manifest order
  int platform_index = -1;              // of the opened plugin
  std::vector<std::string> param_names;
  std::vector<PJRT_Buffer*> param_bufs;  // uploaded once
  std::vector<Bucket> buckets;
  std::vector<std::vector<float>> out_bufs;
  std::vector<std::vector<long long>> out_shapes;
};

// returns false (with g_error set) when err != nullptr
bool check(const PJRT_Api* api, PJRT_Error* err, const char* what) {
  if (err == nullptr) return true;
  PJRT_Error_Message_Args m;
  std::memset(&m, 0, sizeof(m));
  m.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  m.error = err;
  api->PJRT_Error_Message(&m);
  set_error(std::string(what) + ": " + std::string(m.message, m.message_size));
  PJRT_Error_Destroy_Args d;
  std::memset(&d, 0, sizeof(d));
  d.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  d.error = err;
  api->PJRT_Error_Destroy(&d);
  return false;
}

bool await_event(const PJRT_Api* api, PJRT_Event* ev, const char* what) {
  if (ev == nullptr) return true;
  PJRT_Event_Await_Args a;
  std::memset(&a, 0, sizeof(a));
  a.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  a.event = ev;
  bool ok = check(api, api->PJRT_Event_Await(&a), what);
  PJRT_Event_Destroy_Args d;
  std::memset(&d, 0, sizeof(d));
  d.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  d.event = ev;
  api->PJRT_Event_Destroy(&d);
  return ok;
}

const PJRT_Api* open_plugin(const char* plugin_path, void** dl_out) {
  void* dl = dlopen(plugin_path, RTLD_NOW | RTLD_LOCAL);
  if (dl == nullptr) {
    set_error(std::string("dlopen failed: ") + dlerror());
    return nullptr;
  }
  using GetApiFn = const PJRT_Api* (*)();
  auto get_api = reinterpret_cast<GetApiFn>(dlsym(dl, "GetPjrtApi"));
  if (get_api == nullptr) {
    set_error("plugin exports no GetPjrtApi symbol");
    dlclose(dl);
    return nullptr;
  }
  const PJRT_Api* api = get_api();
  if (api == nullptr) {
    set_error("GetPjrtApi returned null");
    dlclose(dl);
    return nullptr;
  }
  if (dl_out != nullptr) *dl_out = dl;
  return api;
}

bool read_file(const std::string& path, std::string* out) {
  FILE* f = std::fopen(path.c_str(), "rb");
  if (f == nullptr) {
    set_error("cannot open " + path);
    return false;
  }
  std::fseek(f, 0, SEEK_END);
  long n = std::ftell(f);
  std::fseek(f, 0, SEEK_SET);
  out->resize(n);
  bool ok = n == 0 || std::fread(&(*out)[0], 1, n, f) == (size_t)n;
  std::fclose(f);
  if (!ok) set_error("short read on " + path);
  return ok;
}

bool parse_meta(FILE* f, int n, std::vector<TensorMeta>* out) {
  for (int i = 0; i < n; ++i) {
    TensorMeta t;
    char name[512];
    int ndim = 0;
    if (std::fscanf(f, "%511s %d %d", name, &t.pjrt_type, &ndim) != 3)
      return false;
    t.name = name;
    t.dims.resize(ndim);
    for (int d = 0; d < ndim; ++d) {
      long long v;
      if (std::fscanf(f, "%lld", &v) != 1) return false;
      t.dims[d] = v;
    }
    out->push_back(std::move(t));
  }
  return true;
}

bool parse_manifest(const std::string& dir, Handle* h) {
  FILE* f = std::fopen((dir + "/manifest.txt").c_str(), "r");
  if (f == nullptr) {
    set_error("cannot open " + dir + "/manifest.txt");
    return false;
  }
  bool ok = false;
  do {
    char tag[64];
    int version = 0, n = 0;
    if (std::fscanf(f, "%63s %d", tag, &version) != 2 ||
        std::strcmp(tag, "pds-manifest") != 0 || version != 1) {
      set_error("bad manifest header");
      break;
    }
    if (std::fscanf(f, "%63s %d", tag, &n) != 2 ||
        std::strcmp(tag, "platforms") != 0) break;
    for (int i = 0; i < n; ++i) {
      char p[64];
      if (std::fscanf(f, "%63s", p) != 1) break;
      h->platforms.push_back(p);
    }
    if (std::fscanf(f, "%63s %d", tag, &n) != 2 ||
        std::strcmp(tag, "params") != 0) break;
    for (int i = 0; i < n; ++i) {
      char p[512];
      if (std::fscanf(f, "%511s", p) != 1) break;
      h->param_names.push_back(p);
    }
    int nbuckets = 0;
    if (std::fscanf(f, "%63s %d", tag, &nbuckets) != 2 ||
        std::strcmp(tag, "buckets") != 0) break;
    bool bad = false;
    for (int b = 0; b < nbuckets && !bad; ++b) {
      Bucket bk;
      char file[512];
      if (std::fscanf(f, "%63s %d %511s", tag, &bk.batch_size, file) != 3 ||
          std::strcmp(tag, "bucket") != 0) { bad = true; break; }
      bk.module_file = file;
      int nf = 0;
      if (std::fscanf(f, "%63s %d", tag, &nf) != 2 ||
          std::strcmp(tag, "feeds") != 0 ||
          !parse_meta(f, nf, &bk.feeds)) { bad = true; break; }
      int no = 0;
      if (std::fscanf(f, "%63s %d", tag, &no) != 2 ||
          std::strcmp(tag, "outs") != 0 ||
          !parse_meta(f, no, &bk.outs)) { bad = true; break; }
      h->buckets.push_back(std::move(bk));
    }
    if (bad) break;
    ok = true;
  } while (false);
  if (!ok && g_error.empty()) set_error("malformed manifest.txt");
  std::fclose(f);
  return ok;
}

PJRT_Buffer* upload(Handle* h, const void* data, PJRT_Buffer_Type type,
                    const int64_t* dims, size_t ndims) {
  PJRT_Client_BufferFromHostBuffer_Args a;
  std::memset(&a, 0, sizeof(a));
  a.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  a.client = h->client;
  a.data = data;
  a.type = type;
  a.dims = dims;
  a.num_dims = ndims;
  a.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  a.device = h->device;
  if (!check(h->api, h->api->PJRT_Client_BufferFromHostBuffer(&a),
             "BufferFromHostBuffer"))
    return nullptr;
  if (!await_event(h->api, a.done_with_host_buffer,
                   "host buffer transfer")) {
    // the device buffer was allocated before the transfer failed; don't
    // strand it on flaky-plugin retries
    PJRT_Buffer_Destroy_Args d;
    std::memset(&d, 0, sizeof(d));
    d.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    d.buffer = a.buffer;
    PJRT_Error* derr = h->api->PJRT_Buffer_Destroy(&d);
    if (derr != nullptr) {
      PJRT_Error_Destroy_Args dd;
      std::memset(&dd, 0, sizeof(dd));
      dd.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
      dd.error = derr;
      h->api->PJRT_Error_Destroy(&dd);  // keep the transfer error
    }
    return nullptr;
  }
  return a.buffer;
}

void destroy_buffer(Handle* h, PJRT_Buffer* b) {
  if (b == nullptr) return;
  PJRT_Buffer_Destroy_Args d;
  std::memset(&d, 0, sizeof(d));
  d.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
  d.buffer = b;
  check(h->api, h->api->PJRT_Buffer_Destroy(&d), "Buffer_Destroy");
}

}  // namespace

extern "C" {

void pds_destroy(void* handle);  // forward: pds_load error path

const char* pds_last_error(void) { return g_error.c_str(); }

int pds_probe(const char* plugin_path, int* major, int* minor) {
  void* dl = nullptr;
  const PJRT_Api* api = open_plugin(plugin_path, &dl);
  if (api == nullptr) return -1;
  if (major != nullptr) *major = api->pjrt_api_version.major_version;
  if (minor != nullptr) *minor = api->pjrt_api_version.minor_version;
  // leave the plugin loaded: PJRT plugins are not re-entrant through
  // dlclose, and the probe is used before a real pds_load
  return 0;
}

void* pds_load(const char* artifact_dir, const char* plugin_path) {
  g_error.clear();
  auto* h = new Handle();
  std::string dir(artifact_dir);
  do {
    h->api = open_plugin(plugin_path, &h->dl);
    if (h->api == nullptr) break;
    if (!parse_manifest(dir, h)) break;

    PJRT_Plugin_Initialize_Args ia;
    std::memset(&ia, 0, sizeof(ia));
    ia.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    if (!check(h->api, h->api->PJRT_Plugin_Initialize(&ia),
               "Plugin_Initialize"))
      break;

    PJRT_Client_Create_Args ca;
    std::memset(&ca, 0, sizeof(ca));
    ca.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
    if (!check(h->api, h->api->PJRT_Client_Create(&ca), "Client_Create"))
      break;
    h->client = ca.client;

    PJRT_Client_PlatformName_Args pa;
    std::memset(&pa, 0, sizeof(pa));
    pa.struct_size = PJRT_Client_PlatformName_Args_STRUCT_SIZE;
    pa.client = h->client;
    if (!check(h->api, h->api->PJRT_Client_PlatformName(&pa),
               "PlatformName"))
      break;
    std::string plat(pa.platform_name, pa.platform_name_size);
    for (size_t i = 0; i < h->platforms.size(); ++i) {
      if (plat.find(h->platforms[i]) != std::string::npos)
        h->platform_index = static_cast<int>(i);
    }
    if (h->platform_index < 0) {
      set_error("plugin platform '" + plat + "' not in artifact platforms");
      break;
    }

    PJRT_Client_AddressableDevices_Args da;
    std::memset(&da, 0, sizeof(da));
    da.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
    da.client = h->client;
    if (!check(h->api, h->api->PJRT_Client_AddressableDevices(&da),
               "AddressableDevices"))
      break;
    if (da.num_addressable_devices == 0) {
      set_error("no addressable devices");
      break;
    }
    h->device = da.addressable_devices[0];

    std::string copts;
    if (!read_file(dir + "/compile_options.pb", &copts)) break;

    bool bad = false;
    for (auto& bk : h->buckets) {
      std::string code;
      if (!read_file(dir + "/" + bk.module_file, &code)) { bad = true; break; }
      PJRT_Program prog;
      std::memset(&prog, 0, sizeof(prog));
      prog.struct_size = PJRT_Program_STRUCT_SIZE;
      prog.code = &code[0];
      prog.code_size = code.size();
      prog.format = "mlir";
      prog.format_size = 4;
      PJRT_Client_Compile_Args cc;
      std::memset(&cc, 0, sizeof(cc));
      cc.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
      cc.client = h->client;
      cc.program = &prog;
      cc.compile_options = copts.data();
      cc.compile_options_size = copts.size();
      if (!check(h->api, h->api->PJRT_Client_Compile(&cc),
                 ("compile " + bk.module_file).c_str())) {
        bad = true;
        break;
      }
      bk.exec = cc.executable;
    }
    if (bad) break;

    // weights: upload once, reused by every run
    void* ts = ts_read_open((dir + "/params.ptck").c_str());
    if (ts == nullptr) {
      set_error("cannot read params.ptck");
      break;
    }
    int count = ts_read_count(ts);
    for (auto& want : h->param_names) {
      int found = -1;
      for (int i = 0; i < count; ++i)
        if (want == ts_read_name(ts, i)) found = i;
      if (found < 0) {
        set_error("params.ptck is missing " + want);
        bad = true;
        break;
      }
      std::vector<int64_t> dims(ts_read_ndim(ts, found));
      if (!dims.empty()) ts_read_dims(ts, found, dims.data());
      PJRT_Buffer* b =
          upload(h, ts_read_data(ts, found),
                 ts_to_pjrt(ts_read_dtype(ts, found)), dims.data(),
                 dims.size());
      if (b == nullptr) { bad = true; break; }
      h->param_bufs.push_back(b);
    }
    ts_read_close(ts);
    if (bad) break;

    return h;
  } while (false);
  // cleanup must not mask the root cause in pds_last_error
  std::string cause = g_error;
  pds_destroy(h);
  g_error = cause;
  return nullptr;
}

int pds_run(void* handle, int batch_size, const void** in_data,
            const float** out_data, const long long** out_shapes,
            int* out_ndims, int max_outputs) {
  auto* h = static_cast<Handle*>(handle);
  if (h == nullptr) {
    set_error("null handle");
    return -1;
  }
  Bucket* bk = nullptr;
  for (auto& b : h->buckets)
    if (b.batch_size == batch_size) bk = &b;
  if (bk == nullptr) {
    set_error("no bucket for batch size " + std::to_string(batch_size));
    return -1;
  }
  if (static_cast<int>(bk->outs.size()) > max_outputs) {
    set_error("more outputs than max_outputs");
    return -1;
  }

  std::vector<PJRT_Buffer*> args;
  bool ok = true;
  int32_t pindex = h->platform_index;
  if (h->platforms.size() > 1) {
    // multi-platform module: leading _platform_index scalar argument
    PJRT_Buffer* b = upload(h, &pindex, PJRT_Buffer_Type_S32, nullptr, 0);
    ok = b != nullptr;
    if (ok) args.push_back(b);
  }
  for (size_t i = 0; i < bk->feeds.size() && ok; ++i) {
    const TensorMeta& t = bk->feeds[i];
    PJRT_Buffer* b =
        upload(h, in_data[i], static_cast<PJRT_Buffer_Type>(t.pjrt_type),
               t.dims.data(), t.dims.size());
    ok = b != nullptr;
    if (ok) args.push_back(b);
  }
  size_t n_feed_args = args.size();
  for (auto* p : h->param_bufs) args.push_back(p);

  size_t n_out = bk->outs.size();
  std::vector<PJRT_Buffer*> outs(n_out, nullptr);
  if (ok) {
    PJRT_ExecuteOptions opts;
    std::memset(&opts, 0, sizeof(opts));
    opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;
    PJRT_Buffer* const* arg_list = args.data();
    PJRT_Buffer** out_list = outs.data();
    PJRT_Event* done = nullptr;
    PJRT_LoadedExecutable_Execute_Args ea;
    std::memset(&ea, 0, sizeof(ea));
    ea.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
    ea.executable = bk->exec;
    ea.options = &opts;
    ea.argument_lists = &arg_list;
    ea.num_devices = 1;
    ea.num_args = args.size();
    ea.output_lists = &out_list;
    ea.device_complete_events = &done;
    ok = check(h->api, h->api->PJRT_LoadedExecutable_Execute(&ea),
               "Execute") &&
         await_event(h->api, done, "execute completion");
  }

  if (ok) {
    h->out_bufs.assign(n_out, {});
    h->out_shapes.assign(n_out, {});
    for (size_t i = 0; i < n_out && ok; ++i) {
      const TensorMeta& t = bk->outs[i];
      PJRT_Buffer_ToHostBuffer_Args ta;
      std::memset(&ta, 0, sizeof(ta));
      ta.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
      ta.src = outs[i];
      ok = check(h->api, h->api->PJRT_Buffer_ToHostBuffer(&ta),
                 "ToHostBuffer size query");
      if (!ok) break;
      std::vector<char> raw(ta.dst_size);
      ta.dst = raw.data();
      ok = check(h->api, h->api->PJRT_Buffer_ToHostBuffer(&ta),
                 "ToHostBuffer") &&
           await_event(h->api, ta.event, "host transfer");
      if (!ok) break;
      int64_t n = t.elems();
      h->out_bufs[i].resize(n);
      if (t.pjrt_type == PJRT_Buffer_Type_F32) {
        std::memcpy(h->out_bufs[i].data(), raw.data(), n * 4);
      } else if (t.pjrt_type == PJRT_Buffer_Type_S32) {
        const int32_t* s = reinterpret_cast<const int32_t*>(raw.data());
        for (int64_t k = 0; k < n; ++k)
          h->out_bufs[i][k] = static_cast<float>(s[k]);
      } else {
        set_error("unsupported output dtype code " +
                  std::to_string(t.pjrt_type));
        ok = false;
        break;
      }
      for (auto d : t.dims) h->out_shapes[i].push_back(d);
      out_data[i] = h->out_bufs[i].data();
      out_shapes[i] = h->out_shapes[i].data();
      out_ndims[i] = static_cast<int>(t.dims.size());
    }
  }

  // feed (and platform-index) buffers die with the run; outputs +
  // params persist on device until destroy
  for (size_t i = 0; i < n_feed_args; ++i) destroy_buffer(h, args[i]);
  for (auto* b : outs) destroy_buffer(h, b);
  return ok ? static_cast<int>(n_out) : -1;
}

void pds_destroy(void* handle) {
  auto* h = static_cast<Handle*>(handle);
  if (h == nullptr) return;
  if (h->client != nullptr && h->api != nullptr) {
    for (auto* b : h->param_bufs) destroy_buffer(h, b);
    for (auto& bk : h->buckets) {
      if (bk.exec != nullptr) {
        PJRT_LoadedExecutable_Destroy_Args d;
        std::memset(&d, 0, sizeof(d));
        d.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
        d.executable = bk.exec;
        check(h->api, h->api->PJRT_LoadedExecutable_Destroy(&d),
              "LoadedExecutable_Destroy");
      }
    }
    PJRT_Client_Destroy_Args d;
    std::memset(&d, 0, sizeof(d));
    d.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
    d.client = h->client;
    check(h->api, h->api->PJRT_Client_Destroy(&d), "Client_Destroy");
  }
  // deliberately no dlclose: PJRT plugins don't support unloading
  delete h;
}

}  // extern "C"
