// Python-free predict runtime over the PJRT C API.
//
// Reference: src/c_api/c_predict_api.cc + amalgamation/README.md:1-13 — the
// reference's amalgamation exists to run models where the framework doesn't
// (single-file build, JNI/mobile). This library is the TPU-native analog:
// it implements the same c_predict_api.h surface from a `.mxa` artifact
// (export_artifact.py: manifest JSON + StableHLO bytecode + .params blob)
// by dlopen-ing a PJRT plugin (`libtpu.so` on TPU hosts, any GetPjrtApi
// exporter elsewhere) and driving compile/execute through the stable C ABI.
// No Python.h anywhere in this translation unit; `ldd` on the built .so
// shows no libpython (asserted by tests/test_predict_native.py).
//
// Environment:
//   MXTPU_PJRT_PLUGIN   path to the plugin .so (default: libtpu.so on the
//                       loader path, else the copy in the `libtpu` Python
//                       package the build found — MXTPU_LIBTPU_PATH, baked
//                       in by the Makefile — else an error).
//   MXTPU_PJRT_OPTIONS  client create options "k=v;k=v". Integers become
//                       kInt64, anything else kString; "session_id=auto"
//                       generates a random UUID.
#include <dlfcn.h>

#include <cctype>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <map>
#include <memory>
#include <mutex>
#include <string>
#include <vector>

#include "include/ndarray_wire.h"
#include "include/vendor/pjrt_c_api.h"

#define MXNET_DLL extern "C" __attribute__((visibility("default")))

extern "C" {
#include "include/c_predict_api.h"
int MXPredCreateFromFile(const char* artifact_path, int dev_type, int dev_id,
                         PredictorHandle* out);
}

namespace {

thread_local std::string g_err;

int fail(const std::string& msg) {
  g_err = msg;
  return -1;
}

// ---- tiny JSON reader (the manifest is machine-generated by
// export_artifact.py; this parses the full JSON grammar minus \u escapes
// beyond BMP pass-through) -------------------------------------------------

struct JVal {
  enum Kind { OBJ, ARR, STR, NUM, BOOL, NUL } kind = NUL;
  std::map<std::string, JVal> obj;
  std::vector<JVal> arr;
  std::string str;
  double num = 0;
  bool b = false;

  const JVal* get(const std::string& k) const {
    auto it = obj.find(k);
    return it == obj.end() ? nullptr : &it->second;
  }
};

struct JParser {
  const char* p;
  const char* end;
  bool ok = true;

  void ws() {
    while (p < end && (*p == ' ' || *p == '\t' || *p == '\n' || *p == '\r'))
      ++p;
  }
  bool lit(const char* s) {
    size_t n = strlen(s);
    if (static_cast<size_t>(end - p) < n || strncmp(p, s, n) != 0) return false;
    p += n;
    return true;
  }
  JVal parse() {
    ws();
    JVal v;
    if (p >= end) { ok = false; return v; }
    switch (*p) {
      case '{': {
        v.kind = JVal::OBJ;
        ++p; ws();
        if (p < end && *p == '}') { ++p; return v; }
        while (ok) {
          ws();
          if (p >= end || *p != '"') { ok = false; break; }
          std::string key = pstr();
          ws();
          if (p >= end || *p++ != ':') { ok = false; break; }
          v.obj[key] = parse();
          ws();
          if (p < end && *p == ',') { ++p; continue; }
          if (p < end && *p == '}') { ++p; break; }
          ok = false;
        }
        return v;
      }
      case '[': {
        v.kind = JVal::ARR;
        ++p; ws();
        if (p < end && *p == ']') { ++p; return v; }
        while (ok) {
          v.arr.push_back(parse());
          ws();
          if (p < end && *p == ',') { ++p; continue; }
          if (p < end && *p == ']') { ++p; break; }
          ok = false;
        }
        return v;
      }
      case '"':
        v.kind = JVal::STR;
        v.str = pstr();
        return v;
      case 't': v.kind = JVal::BOOL; v.b = true; ok = lit("true"); return v;
      case 'f': v.kind = JVal::BOOL; v.b = false; ok = lit("false"); return v;
      case 'n': v.kind = JVal::NUL; ok = lit("null"); return v;
      default: {
        v.kind = JVal::NUM;
        // bound the scan: strtod on the raw pointer could read past `end`
        // (the buffer is a slice of the artifact, not NUL-terminated)
        std::string numbuf;
        const char* q = p;
        while (q < end && (isdigit(static_cast<unsigned char>(*q)) ||
                           *q == '-' || *q == '+' || *q == '.' ||
                           *q == 'e' || *q == 'E'))
          numbuf += *q++;
        char* parsed_end = nullptr;
        v.num = strtod(numbuf.c_str(), &parsed_end);
        if (numbuf.empty() || parsed_end == numbuf.c_str()) {
          ok = false;
          return v;
        }
        p += parsed_end - numbuf.c_str();
        return v;
      }
    }
  }
  std::string pstr() {
    std::string s;
    if (p >= end || *p != '"') { ok = false; return s; }
    ++p;
    while (p < end && *p != '"') {
      if (*p == '\\' && p + 1 < end) {
        ++p;
        switch (*p) {
          case 'n': s += '\n'; break;
          case 't': s += '\t'; break;
          case 'r': s += '\r'; break;
          case 'b': s += '\b'; break;
          case 'f': s += '\f'; break;
          case 'u': {
            // manifest strings are ASCII op/arg names; decode BMP escapes
            // to UTF-8 so a unicode layer name still round-trips
            if (end - p < 5) { ok = false; return s; }
            unsigned cp = 0;
            for (int i = 1; i <= 4; ++i) {
              char c = p[i];
              cp <<= 4;
              if (c >= '0' && c <= '9') cp |= c - '0';
              else if (c >= 'a' && c <= 'f') cp |= c - 'a' + 10;
              else if (c >= 'A' && c <= 'F') cp |= c - 'A' + 10;
              else { ok = false; return s; }
            }
            p += 4;
            if (cp < 0x80) {
              s += static_cast<char>(cp);
            } else if (cp < 0x800) {
              s += static_cast<char>(0xC0 | (cp >> 6));
              s += static_cast<char>(0x80 | (cp & 0x3F));
            } else {
              s += static_cast<char>(0xE0 | (cp >> 12));
              s += static_cast<char>(0x80 | ((cp >> 6) & 0x3F));
              s += static_cast<char>(0x80 | (cp & 0x3F));
            }
            break;
          }
          default: s += *p;
        }
      } else {
        s += *p;
      }
      ++p;
    }
    if (p >= end) { ok = false; return s; }
    ++p;  // closing quote
    return s;
  }
};

bool json_parse(const char* data, size_t n, JVal* out) {
  JParser jp{data, data + n};
  *out = jp.parse();
  return jp.ok;
}

// ---- dtype tables ---------------------------------------------------------

struct DType {
  PJRT_Buffer_Type pjrt;
  size_t size;
};

bool dtype_by_name(const std::string& name, DType* out) {
  if (name == "float32") { *out = {PJRT_Buffer_Type_F32, 4}; return true; }
  if (name == "float64") { *out = {PJRT_Buffer_Type_F64, 8}; return true; }
  if (name == "float16") { *out = {PJRT_Buffer_Type_F16, 2}; return true; }
  if (name == "bfloat16") { *out = {PJRT_Buffer_Type_BF16, 2}; return true; }
  if (name == "uint8") { *out = {PJRT_Buffer_Type_U8, 1}; return true; }
  if (name == "int32") { *out = {PJRT_Buffer_Type_S32, 4}; return true; }
  if (name == "int8") { *out = {PJRT_Buffer_Type_S8, 1}; return true; }
  if (name == "int64") { *out = {PJRT_Buffer_Type_S64, 8}; return true; }
  if (name == "bool") { *out = {PJRT_Buffer_Type_PRED, 1}; return true; }
  return false;
}

// reference NDArray dtype flags (ndarray.cc TYPE_FLAG; mirrored in
// ndarray.py _DTYPE_MX_TO_NP) + TPU-build extensions 7..10
bool dtype_by_flag(int flag, DType* out) {
  switch (flag) {
    case 0: *out = {PJRT_Buffer_Type_F32, 4}; return true;
    case 1: *out = {PJRT_Buffer_Type_F64, 8}; return true;
    case 2: *out = {PJRT_Buffer_Type_F16, 2}; return true;
    case 3: *out = {PJRT_Buffer_Type_U8, 1}; return true;
    case 4: *out = {PJRT_Buffer_Type_S32, 4}; return true;
    case 5: *out = {PJRT_Buffer_Type_S8, 1}; return true;
    case 6: *out = {PJRT_Buffer_Type_S64, 8}; return true;
    case 7: *out = {PJRT_Buffer_Type_BF16, 2}; return true;
    case 8: *out = {PJRT_Buffer_Type_PRED, 1}; return true;
    default: return false;
  }
}

// ---- .params blob reader: the wire format lives in the shared header
// (include/ndarray_wire.h), same decoder the C API's Load uses ------------

struct HostArray {
  std::string name;
  mxt_ndwire::NdRecord rec;
  std::vector<int64_t> shape;
  // MXNDListGet contract: shapes as mx_uint
  std::vector<mx_uint> shape_u32;
};

struct Cursor {
  const char* p;
  const char* end;
  bool read(void* dst, size_t n) {
    if (static_cast<size_t>(end - p) < n) return false;
    memcpy(dst, p, n);
    p += n;
    return true;
  }
};

bool parse_params(const char* data, size_t n, std::vector<HostArray>* out,
                  std::string* err) {
  Cursor c{data, data + n};
  uint64_t magic = 0, reserved = 0, count = 0;
  if (!c.read(&magic, 8) || magic != mxt_ndwire::kListMagic) {
    *err = "params blob: bad list magic";
    return false;
  }
  if (!c.read(&reserved, 8) || !c.read(&count, 8)) {
    *err = "params blob: truncated header";
    return false;
  }
  if (count > (1u << 24)) {
    *err = "params blob: implausible array count";
    return false;
  }
  out->resize(count);
  auto rd = [&c](void* dst, size_t m) { return c.read(dst, m); };
  for (uint64_t i = 0; i < count; ++i) {
    HostArray& a = (*out)[i];
    if (!mxt_ndwire::read_ndarray_record(rd, &a.rec, err)) {
      *err = "params blob: " + *err;
      return false;
    }
    for (uint32_t s : a.rec.shape) {
      a.shape.push_back(s);
      a.shape_u32.push_back(s);
    }
  }
  uint64_t n_names = 0;
  if (c.read(&n_names, 8) && n_names == count) {
    for (uint64_t i = 0; i < n_names; ++i) {
      uint64_t len = 0;
      if (!c.read(&len, 8) || len > (1u << 20)) break;
      std::string s(len, '\0');
      if (!c.read(&s[0], len)) break;
      (*out)[i].name = s;
    }
  }
  return true;
}

// ---- PJRT runtime (process-wide plugin + client) --------------------------

typedef const PJRT_Api* (*GetPjrtApiFn)();

struct NamedOpt {
  std::string name;
  bool is_int = false;
  int64_t i = 0;
  std::string s;
};

std::string gen_uuid() {
  unsigned char b[16];
  FILE* f = fopen("/dev/urandom", "rb");
  if (!f || fread(b, 1, 16, f) != 16)
    for (int i = 0; i < 16; ++i) b[i] = static_cast<unsigned char>(rand());
  if (f) fclose(f);
  b[6] = (b[6] & 0x0F) | 0x40;
  b[8] = (b[8] & 0x3F) | 0x80;
  char buf[40];
  snprintf(buf, sizeof buf,
           "%02x%02x%02x%02x-%02x%02x-%02x%02x-%02x%02x-"
           "%02x%02x%02x%02x%02x%02x",
           b[0], b[1], b[2], b[3], b[4], b[5], b[6], b[7], b[8], b[9], b[10],
           b[11], b[12], b[13], b[14], b[15]);
  return buf;
}

struct Runtime {
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  PJRT_Device* device = nullptr;
  std::vector<PJRT_Device*> devices;  // all addressable, [0] == device
  std::string error;

  std::string err_of(PJRT_Error* e, const char* where) {
    PJRT_Error_Message_Args m;
    memset(&m, 0, sizeof m);
    m.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
    m.error = e;
    api->PJRT_Error_Message(&m);
    std::string msg = std::string(where) + ": " +
                      std::string(m.message, m.message_size);
    PJRT_Error_Destroy_Args d;
    memset(&d, 0, sizeof d);
    d.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
    d.error = e;
    api->PJRT_Error_Destroy(&d);
    return msg;
  }

  bool init() {
    const char* plugin = getenv("MXTPU_PJRT_PLUGIN");
    std::string path = plugin ? plugin : "";
    if (path.empty()) {
      // default: libtpu on the loader path, else the installed libtpu
      // package's copy the build found
      void* probe = dlopen("libtpu.so", RTLD_NOW | RTLD_LOCAL);
      if (probe) {
        path = "libtpu.so";
        dlclose(probe);
      } else if (FILE* f = fopen(MXTPU_LIBTPU_PATH, "rb")) {
        fclose(f);
        path = MXTPU_LIBTPU_PATH;
      } else {
        error = "no PJRT plugin found; set MXTPU_PJRT_PLUGIN";
        return false;
      }
    }
    void* h = dlopen(path.c_str(), RTLD_NOW | RTLD_LOCAL);
    if (!h) {
      error = std::string("dlopen ") + path + ": " + dlerror();
      return false;
    }
    auto get = reinterpret_cast<GetPjrtApiFn>(dlsym(h, "GetPjrtApi"));
    if (!get) {
      error = path + " does not export GetPjrtApi";
      return false;
    }
    api = get();
    PJRT_Plugin_Initialize_Args init_args;
    memset(&init_args, 0, sizeof init_args);
    init_args.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    if (PJRT_Error* e = api->PJRT_Plugin_Initialize(&init_args)) {
      error = err_of(e, "PJRT_Plugin_Initialize");
      return false;
    }

    std::vector<NamedOpt> opts;
    const char* env_opts = getenv("MXTPU_PJRT_OPTIONS");
    if (env_opts) {
      std::string s = env_opts;
      size_t pos = 0;
      while (pos < s.size()) {
        size_t semi = s.find(';', pos);
        if (semi == std::string::npos) semi = s.size();
        std::string kv = s.substr(pos, semi - pos);
        pos = semi + 1;
        size_t eq = kv.find('=');
        if (eq == std::string::npos) continue;
        NamedOpt o;
        o.name = kv.substr(0, eq);
        o.s = kv.substr(eq + 1);
        if (o.name == "session_id" && o.s == "auto") o.s = gen_uuid();
        char* q = nullptr;
        long long v = strtoll(o.s.c_str(), &q, 10);
        if (q && *q == '\0' && !o.s.empty()) {
          o.is_int = true;
          o.i = v;
        }
        opts.push_back(o);
      }
    }

    std::vector<PJRT_NamedValue> nvs(opts.size());
    for (size_t i = 0; i < opts.size(); ++i) {
      PJRT_NamedValue& n = nvs[i];
      memset(&n, 0, sizeof n);
      n.struct_size = PJRT_NamedValue_STRUCT_SIZE;
      n.name = opts[i].name.c_str();
      n.name_size = opts[i].name.size();
      if (opts[i].is_int) {
        n.type = PJRT_NamedValue_kInt64;
        n.int64_value = opts[i].i;
        n.value_size = 1;
      } else {
        n.type = PJRT_NamedValue_kString;
        n.string_value = opts[i].s.c_str();
        n.value_size = opts[i].s.size();
      }
    }

    PJRT_Client_Create_Args cc;
    memset(&cc, 0, sizeof cc);
    cc.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
    cc.create_options = nvs.data();
    cc.num_options = nvs.size();
    if (PJRT_Error* e = api->PJRT_Client_Create(&cc)) {
      error = err_of(e, "PJRT_Client_Create");
      return false;
    }
    client = cc.client;
    PJRT_Client_AddressableDevices_Args dv;
    memset(&dv, 0, sizeof dv);
    dv.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
    dv.client = client;
    if (PJRT_Error* e = api->PJRT_Client_AddressableDevices(&dv)) {
      error = err_of(e, "PJRT_Client_AddressableDevices");
      return false;
    }
    if (dv.num_addressable_devices == 0) {
      error = "PJRT client has no addressable devices";
      return false;
    }
    devices.assign(dv.addressable_devices,
                   dv.addressable_devices + dv.num_addressable_devices);
    device = devices[0];
    return true;
  }

  bool await_ev(PJRT_Event* ev, const char* where, std::string* err) {
    PJRT_Event_Await_Args a;
    memset(&a, 0, sizeof a);
    a.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
    a.event = ev;
    PJRT_Error* e = api->PJRT_Event_Await(&a);
    PJRT_Event_Destroy_Args d;
    memset(&d, 0, sizeof d);
    d.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
    d.event = ev;
    api->PJRT_Event_Destroy(&d);
    if (e) {
      *err = err_of(e, where);
      return false;
    }
    return true;
  }

  PJRT_Buffer* upload(const void* data, PJRT_Buffer_Type type,
                      const std::vector<int64_t>& dims, std::string* err,
                      PJRT_Device* dev = nullptr) {
    PJRT_Client_BufferFromHostBuffer_Args bh;
    memset(&bh, 0, sizeof bh);
    bh.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    bh.client = client;
    bh.data = data;
    bh.type = type;
    bh.dims = dims.data();
    bh.num_dims = dims.size();
    bh.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    bh.device = dev ? dev : device;
    if (PJRT_Error* e = api->PJRT_Client_BufferFromHostBuffer(&bh)) {
      *err = err_of(e, "BufferFromHostBuffer");
      return nullptr;
    }
    if (!await_ev(bh.done_with_host_buffer, "h2d", err)) return nullptr;
    return bh.buffer;
  }

  void destroy_buffer(PJRT_Buffer* b) {
    if (!b) return;
    PJRT_Buffer_Destroy_Args d;
    memset(&d, 0, sizeof d);
    d.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    d.buffer = b;
    api->PJRT_Buffer_Destroy(&d);
  }
};

Runtime* runtime() {
  static Runtime* rt = nullptr;
  static std::once_flag once;
  std::call_once(once, [] {
    auto* r = new Runtime();
    if (!r->init()) {
      // keep the instance for its error string; client stays null
    }
    rt = r;
  });
  return rt;
}

// ---- predictor ------------------------------------------------------------

struct IoSpec {
  std::string name;
  std::vector<int64_t> shape;
  std::vector<mx_uint> shape_u32;
  DType dtype;
  bool is_label = false;
  // jax.export DCE drops module args whose value the graph never reads
  // (fix_gamma gammas, inference labels); non-kept inputs are settable but
  // not passed to the executable
  bool kept = true;
  int64_t n_elem = 1;
};

struct Predictor {
  const PJRT_Api* api = nullptr;
  PJRT_LoadedExecutable* exec = nullptr;
  std::vector<IoSpec> inputs;
  std::vector<IoSpec> outputs;
  std::vector<PJRT_Buffer*> param_bufs;         // resident across forwards
  std::vector<std::vector<char>> input_host;    // staged by SetInput
  std::vector<PJRT_Buffer*> out_bufs;           // owned; freed on re-forward
  bool has_run = false;

  ~Predictor() {
    Runtime* rt = runtime();
    for (auto* b : param_bufs) rt->destroy_buffer(b);
    for (auto* b : out_bufs) rt->destroy_buffer(b);
    if (exec) {
      PJRT_LoadedExecutable_Destroy_Args d;
      memset(&d, 0, sizeof d);
      d.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
      d.executable = exec;
      rt->api->PJRT_LoadedExecutable_Destroy(&d);
    }
  }
};

// xla.CompileOptionsProto for 1 replica / 1 partition, serialized from
// jax's own get_compile_options() (jaxlib 0.9): the full default
// DebugOptions block matters — a minimal proto (just device_ordinal/
// num_replicas/num_partitions) made the TPU compiler schedule the
// 400+-buffer donated training step into a "Ran out of memory in memory
// space sflag" permanent error, while these defaults compile it fine
// (measured on v5e; predict-sized programs compiled either way).
// Override per deployment with MXTPU_COMPILE_OPTIONS_FILE = path to a
// serialized xla.CompileOptionsProto (e.g. from
// jax._src.compiler.get_compile_options(...).SerializeAsString()).
const unsigned char kCompileOptions[] = {
    0x1A, 0xD5, 0x06, 0x08, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF,
    0xFF, 0x01, 0x1A, 0xB1, 0x06, 0xF8, 0x01, 0x03, 0x98, 0x02, 0x01, 0xE0,
    0x03, 0x01, 0xEA, 0x03, 0x04, 0x4E, 0x6F, 0x6E, 0x65, 0xB0, 0x04, 0x01,
    0xB8, 0x04, 0x01, 0xC0, 0x04, 0x01, 0xC8, 0x04, 0x00, 0x88, 0x06, 0x00,
    0x98, 0x06, 0x00, 0xA0, 0x06, 0x00, 0xB0, 0x06, 0x01, 0xC8, 0x06, 0x00,
    0xA0, 0x07, 0x00, 0xC0, 0x07, 0x01, 0xC8, 0x07, 0x01, 0xD0, 0x07, 0x01,
    0xD8, 0x07, 0x04, 0xF0, 0x07, 0x01, 0x88, 0x08, 0x01, 0x98, 0x08, 0x00,
    0xA0, 0x08, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0x01,
    0xC8, 0x08, 0x00, 0xD0, 0x08, 0x00, 0xE0, 0x08, 0x00, 0xF0, 0x08, 0xFF,
    0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0x01, 0x80, 0x09, 0x00,
    0xA8, 0x09, 0x00, 0xE8, 0x09, 0x87, 0x80, 0x80, 0x0F, 0x98, 0x0A, 0xFF,
    0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0x01, 0xA0, 0x0A, 0x01,
    0xA8, 0x0A, 0x01, 0xB0, 0x0A, 0x00, 0xD0, 0x0A, 0x01, 0xA8, 0x0B, 0x00,
    0xB0, 0x0B, 0x00, 0xC8, 0x0B, 0x01, 0xD0, 0x0B, 0x00, 0xD8, 0x0B, 0x00,
    0xE0, 0x0B, 0x01, 0xE8, 0x0B, 0x01, 0xF0, 0x0B, 0x01, 0xD8, 0x0C, 0x00,
    0xE8, 0x0C, 0x01, 0x80, 0x0D, 0x05, 0x88, 0x0D, 0x01, 0x92, 0x0D, 0x00,
    0xA0, 0x0D, 0x87, 0x80, 0x80, 0x0F, 0xA8, 0x0D, 0x87, 0x80, 0x80, 0x0F,
    0xC0, 0x0D, 0x01, 0xC8, 0x0D, 0x00, 0xD8, 0x0D, 0x00, 0x80, 0x0E, 0x00,
    0x8D, 0x0E, 0xCD, 0xCC, 0x8C, 0x3F, 0x98, 0x0E, 0x00, 0xA0, 0x0E, 0x80,
    0x80, 0x80, 0x04, 0xB8, 0x0E, 0x01, 0xD8, 0x0E, 0x00, 0xE0, 0x0E, 0x00,
    0xE8, 0x0E, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0x7F, 0x80,
    0x0F, 0x00, 0x88, 0x0F, 0x01, 0x98, 0x0F, 0x01, 0xB0, 0x0F, 0x00, 0xB8,
    0x0F, 0x01, 0xC0, 0x0F, 0x00, 0xD0, 0x0F, 0x01, 0xD8, 0x0F, 0x0F, 0xE0,
    0x0F, 0x01, 0xE8, 0x0F, 0x01, 0xF0, 0x0F, 0x00, 0xF8, 0x0F, 0x00, 0x80,
    0x10, 0x00, 0x88, 0x10, 0x00, 0x92, 0x10, 0x06, 0x01, 0x02, 0x08, 0x07,
    0x03, 0x09, 0x98, 0x10, 0x01, 0xA0, 0x10, 0x5F, 0xAA, 0x10, 0x00, 0xB0,
    0x10, 0x00, 0xC8, 0x10, 0xA0, 0x8D, 0x06, 0xD8, 0x10, 0x00, 0xE0, 0x10,
    0x00, 0xE8, 0x10, 0x00, 0x80, 0x11, 0x01, 0x88, 0x11, 0x00, 0x90, 0x11,
    0x00, 0xA8, 0x11, 0x00, 0xC0, 0x11, 0x01, 0xD8, 0x11, 0x64, 0xE0, 0x11,
    0x00, 0xE8, 0x11, 0x00, 0xF8, 0x11, 0x00, 0x80, 0x12, 0x00, 0x90, 0x12,
    0x00, 0x98, 0x12, 0x00, 0xA8, 0x12, 0x10, 0xB0, 0x12, 0x03, 0xC0, 0x12,
    0x00, 0xE0, 0x12, 0x01, 0xE8, 0x12, 0x00, 0x80, 0x13, 0x01, 0x88, 0x13,
    0x00, 0x98, 0x13, 0x01, 0xA0, 0x13, 0x80, 0x02, 0xB2, 0x13, 0x00, 0xB8,
    0x13, 0x10, 0xC0, 0x13, 0x00, 0xD8, 0x13, 0x00, 0xE5, 0x13, 0xCD, 0xCC,
    0xCC, 0x3D, 0xE8, 0x13, 0x00, 0xF0, 0x13, 0x05, 0x98, 0x14, 0x20, 0xA0,
    0x14, 0x01, 0xB8, 0x14, 0x0A, 0xC0, 0x14, 0x1E, 0xC8, 0x14, 0x00, 0xD0,
    0x14, 0x00, 0xD8, 0x14, 0x20, 0xEA, 0x14, 0x00, 0xF0, 0x14, 0x00, 0xF8,
    0x14, 0x00, 0x80, 0x15, 0x01, 0x88, 0x15, 0x00, 0x98, 0x15, 0xFF, 0xFF,
    0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0x01, 0xA0, 0x15, 0x00, 0xA8,
    0x15, 0x01, 0xB0, 0x15, 0x01, 0xB8, 0x15, 0x00, 0xC0, 0x15, 0x00, 0xC8,
    0x15, 0x00, 0xD8, 0x15, 0x00, 0xE0, 0x15, 0x00, 0xE8, 0x15, 0x00, 0xF0,
    0x15, 0x00, 0xF8, 0x15, 0x00, 0x88, 0x16, 0x00, 0x90, 0x16, 0x00, 0xA0,
    0x16, 0x01, 0xAA, 0x16, 0x13, 0x0A, 0x0D, 0x63, 0x68, 0x75, 0x6E, 0x6B,
    0x5F, 0x70, 0x72, 0x65, 0x70, 0x5F, 0x75, 0x73, 0x12, 0x02, 0x2D, 0x31,
    0xAA, 0x16, 0x16, 0x0A, 0x10, 0x63, 0x68, 0x75, 0x6E, 0x6B, 0x5F, 0x73,
    0x69, 0x7A, 0x65, 0x5F, 0x62, 0x79, 0x74, 0x65, 0x73, 0x12, 0x02, 0x2D,
    0x31, 0xAA, 0x16, 0x13, 0x0A, 0x0D, 0x67, 0x70, 0x75, 0x73, 0x5F, 0x70,
    0x65, 0x72, 0x5F, 0x6E, 0x6F, 0x64, 0x65, 0x12, 0x02, 0x2D, 0x31, 0xAA,
    0x16, 0x17, 0x0A, 0x11, 0x6E, 0x63, 0x63, 0x6C, 0x5F, 0x6F, 0x70, 0x5F,
    0x6C, 0x61, 0x75, 0x6E, 0x63, 0x68, 0x5F, 0x75, 0x73, 0x12, 0x02, 0x2D,
    0x31, 0xAA, 0x16, 0x14, 0x0A, 0x0E, 0x6E, 0x69, 0x63, 0x5F, 0x73, 0x70,
    0x65, 0x65, 0x64, 0x5F, 0x67, 0x62, 0x70, 0x73, 0x12, 0x02, 0x2D, 0x31,
    0xAA, 0x16, 0x0C, 0x0A, 0x06, 0x72, 0x74, 0x74, 0x5F, 0x75, 0x73, 0x12,
    0x02, 0x2D, 0x31, 0xB8, 0x16, 0x01, 0xD0, 0x16, 0x01, 0xD8, 0x16, 0x00,
    0xE8, 0x16, 0x00, 0xF0, 0x16, 0x00, 0x80, 0x17, 0x00, 0x90, 0x17, 0x00,
    0xA0, 0x17, 0x00, 0xB0, 0x17, 0x00, 0xB8, 0x17, 0x01, 0xC0, 0x17, 0x01,
    0xCA, 0x17, 0x00, 0xD0, 0x17, 0x87, 0x80, 0x80, 0x0F, 0xD8, 0x17, 0x00,
    0xE0, 0x17, 0x00, 0xE8, 0x17, 0x01, 0xF0, 0x17, 0x01, 0xFA, 0x17, 0x00,
    0x80, 0x18, 0x00, 0x90, 0x18, 0x00, 0x98, 0x18, 0x00, 0xA0, 0x18, 0x00,
    0xA8, 0x18, 0x01, 0xB0, 0x18, 0x01, 0xB8, 0x18, 0x14, 0xC0, 0x18, 0x28,
    0xC8, 0x18, 0x00, 0xD8, 0x18, 0x00, 0xE0, 0x18, 0x00, 0x98, 0x19, 0x00,
    0xA0, 0x19, 0x02, 0xB0, 0x19, 0x00, 0xBA, 0x19, 0x00, 0xC0, 0x19, 0x00,
    0xC8, 0x19, 0x01, 0xD0, 0x19, 0x00, 0xD8, 0x19, 0x00, 0xE0, 0x19, 0x00,
    0xE8, 0x19, 0x00, 0xF0, 0x19, 0x00, 0x88, 0x1A, 0x28, 0x90, 0x1A, 0x14,
    0x98, 0x1A, 0x00, 0xA0, 0x1A, 0x01, 0xA8, 0x1A, 0x00, 0xB2, 0x1A, 0x01,
    0x04, 0xC8, 0x1A, 0x01, 0xD0, 0x1A, 0x00, 0xE0, 0x1A, 0x00, 0xE8, 0x1A,
    0x00, 0xF0, 0x1A, 0x01, 0xF8, 0x1A, 0x00, 0x80, 0x1B, 0x00, 0xA0, 0x1B,
    0x01, 0xA8, 0x1B, 0x01, 0xB0, 0x1B, 0x88, 0x0E, 0xB8, 0x1B, 0x00, 0xC0,
    0x1B, 0x00, 0xC8, 0x1B, 0x00, 0xD2, 0x1B, 0x07, 0x02, 0x03, 0x04, 0x01,
    0x05, 0x06, 0x07, 0xD8, 0x1B, 0x00, 0x20, 0x01, 0x28, 0x01, 0x30, 0x01,
    0x62, 0x01, 0x00, 0x92, 0x01, 0x01, 0x00, 0x98, 0x01, 0x01, 0xB8, 0x01,
    0x01, 0xC8, 0x01, 0x1D, 0x28, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF,
    0xFF, 0xFF, 0x01,
};

// optional per-deployment override of the compile-options proto
const std::string& compile_options_blob() {
  static const std::string blob = [] {
    const char* path = getenv("MXTPU_COMPILE_OPTIONS_FILE");
    if (path && *path) {
      FILE* f = fopen(path, "rb");
      if (f) {
        std::string s;
        char buf[4096];
        size_t n;
        while ((n = fread(buf, 1, sizeof buf, f)) > 0) s.append(buf, n);
        bool bad = ferror(f) != 0;  // a partial proto must not reach PJRT
        fclose(f);
        if (!bad && !s.empty()) return s;
      }
      fprintf(stderr,
              "mxtpu: MXTPU_COMPILE_OPTIONS_FILE=%s unreadable/empty; "
              "using built-in compile options\n", path);
    }
    return std::string(reinterpret_cast<const char*>(kCompileOptions),
                       sizeof kCompileOptions);
  }();
  return blob;
}

int create_from_artifact(const char* bytes, size_t size,
                         mx_uint num_input_nodes, const char** input_keys,
                         const mx_uint* input_shape_indptr,
                         const mx_uint* input_shape_data,
                         PredictorHandle* out) {
  Runtime* rt = runtime();
  if (!rt->client) return fail(rt->error);

  if (size < 8 + 8 || memcmp(bytes, "MXTPUAR1", 8) != 0)
    return fail("not an .mxa artifact (bad magic); build one with "
                "mxnet_tpu.export_predict_artifact");
  Cursor c{bytes + 8, bytes + size};
  uint64_t mlen = 0;
  if (!c.read(&mlen, 8) || static_cast<size_t>(c.end - c.p) < mlen)
    return fail(".mxa: truncated manifest");
  JVal manifest;
  if (!json_parse(c.p, mlen, &manifest) || manifest.kind != JVal::OBJ)
    return fail(".mxa: manifest is not valid JSON");
  c.p += mlen;
  uint64_t plen = 0;
  if (!c.read(&plen, 8) || static_cast<size_t>(c.end - c.p) < plen)
    return fail(".mxa: truncated program");
  const char* program = c.p;
  c.p += plen;
  uint64_t qlen = 0;
  if (!c.read(&qlen, 8) || static_cast<size_t>(c.end - c.p) < qlen)
    return fail(".mxa: truncated params");
  std::vector<HostArray> params;
  std::string perr;
  if (!parse_params(c.p, qlen, &params, &perr)) return fail(".mxa: " + perr);
  std::map<std::string, const HostArray*> by_name;
  for (const auto& a : params) by_name[a.name] = &a;

  auto pred = std::make_unique<Predictor>();
  pred->api = rt->api;

  const JVal* jin = manifest.get("inputs");
  const JVal* jpar = manifest.get("params");
  const JVal* jout = manifest.get("outputs");
  if (!jin || !jpar || !jout || jin->kind != JVal::ARR ||
      jpar->kind != JVal::ARR || jout->kind != JVal::ARR)
    return fail(".mxa: manifest missing inputs/params/outputs");

  auto parse_iospec = [](const JVal& j, IoSpec* s) -> bool {
    const JVal* n = j.get("name");
    const JVal* shp = j.get("shape");
    const JVal* dt = j.get("dtype");
    if (!n || !shp || !dt || shp->kind != JVal::ARR) return false;
    s->name = n->str;
    // same manifest bounds as parse_train_arg: a crafted shape must fail
    // here, not overflow n_elem into the staging-buffer allocation
    if (shp->arr.size() > 64) return false;
    for (const auto& d : shp->arr) {
      int64_t v = static_cast<int64_t>(d.num);
      if (v < 0 || v > (int64_t{1} << 31)) return false;
      if (v != 0 && s->n_elem > (int64_t{1} << 40) / v) return false;
      s->shape.push_back(v);
      s->shape_u32.push_back(static_cast<mx_uint>(v));
      s->n_elem *= v;
    }
    const JVal* kind = j.get("kind");
    s->is_label = kind && kind->str == "label";
    const JVal* kept = j.get("kept");
    s->kept = !kept || kept->kind != JVal::BOOL || kept->b;
    return dtype_by_name(dt->str, &s->dtype);
  };
  for (const auto& j : jin->arr) {
    IoSpec s;
    if (!parse_iospec(j, &s)) return fail(".mxa: bad input spec");
    pred->inputs.push_back(std::move(s));
  }
  for (const auto& j : jout->arr) {
    IoSpec s;
    if (!parse_iospec(j, &s)) return fail(".mxa: bad output spec");
    pred->outputs.push_back(std::move(s));
  }

  // validate caller-specified shapes against the artifact (the executable
  // is AOT-compiled: shapes are fixed at export time)
  for (mx_uint i = 0; i < num_input_nodes; ++i) {
    const char* key = input_keys[i];
    const IoSpec* spec = nullptr;
    for (const auto& s : pred->inputs)
      if (s.name == key) { spec = &s; break; }
    if (!spec)
      return fail(std::string("input '") + key + "' not in artifact");
    std::vector<mx_uint> want(input_shape_data + input_shape_indptr[i],
                              input_shape_data + input_shape_indptr[i + 1]);
    if (want != spec->shape_u32)
      return fail(std::string("input '") + key +
                  "' shape differs from the exported artifact (AOT shapes "
                  "are fixed; re-export for new shapes)");
  }

  // compile
  PJRT_Program prog;
  memset(&prog, 0, sizeof prog);
  prog.struct_size = PJRT_Program_STRUCT_SIZE;
  prog.code = const_cast<char*>(program);
  prog.code_size = plen;
  static const char kFmt[] = "mlir";
  prog.format = kFmt;
  prog.format_size = strlen(kFmt);
  PJRT_Client_Compile_Args comp;
  memset(&comp, 0, sizeof comp);
  comp.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  comp.client = rt->client;
  comp.program = &prog;
  comp.compile_options = compile_options_blob().data();
  comp.compile_options_size = compile_options_blob().size();
  if (PJRT_Error* e = rt->api->PJRT_Client_Compile(&comp))
    return fail(rt->err_of(e, "compile"));
  pred->exec = comp.executable;

  // upload params in manifest order
  std::string err;
  for (const auto& j : jpar->arr) {
    auto it = by_name.find(j.str);
    if (it == by_name.end())
      return fail(".mxa: manifest param '" + j.str +
                  "' missing from params blob");
    const HostArray& a = *it->second;
    if (a.rec.none)
      return fail(".mxa: param '" + j.str + "' is a none record");
    DType dt;
    if (!dtype_by_flag(a.rec.dtype, &dt))
      return fail(".mxa: param '" + j.str + "' has an unsupported dtype");
    PJRT_Buffer* b = rt->upload(a.rec.data.data(), dt.pjrt, a.shape, &err);
    if (!b) return fail(err);
    pred->param_bufs.push_back(b);
  }

  // zero-init input staging (labels stay zero unless the client sets them —
  // reference predict also never feeds labels)
  pred->input_host.resize(pred->inputs.size());
  for (size_t i = 0; i < pred->inputs.size(); ++i)
    pred->input_host[i].assign(
        pred->inputs[i].n_elem * pred->inputs[i].dtype.size, 0);

  *out = pred.release();
  return 0;
}

// ---- native trainer (kind="train" artifacts) ------------------------------
// The deployment story the reference never had: its amalgamation/predict
// stack ran INFERENCE without the framework; here the exported artifact is
// the whole fused training step (export_artifact.export_train_artifact), so
// a C process trains through the PJRT C API with no Python loaded. Carried
// state (params, optimizer slots, auxs) lives in device buffers that each
// step's outputs replace in place (the program declares input-output
// aliasing via jax donation, so PJRT reuses the memory).

struct TrainArg {
  std::string name;
  std::string role;  // param | state | aux | data | label | lr | t
  std::vector<int64_t> shape;
  std::vector<mx_uint> shape_u32;
  DType dtype;
  bool kept = true;
  int64_t n_elem = 1;
  // SPMD placement (manifest "sharding"): 0 = replicated across the mesh,
  // 1 = sharded on axis 0 (the dp batch split)
  int shard = 0;
};

struct Trainer {
  PJRT_LoadedExecutable* exec = nullptr;
  std::vector<TrainArg> args;      // flat exported arg order (pre-DCE)
  std::vector<TrainArg> outputs;   // flat output order
  size_t n_carry = 0;              // args[0..n_carry): param/state/aux
  size_t n_dev = 1;                // SPMD width (manifest num_devices)
  // the executable's OWN addressable-device order (PJRT defines the
  // argument/output lists by it, which may differ from the client's)
  std::vector<PJRT_Device*> exec_devices;
  // device state: carry[i] holds one buffer per device (replicated carry)
  std::vector<std::vector<PJRT_Buffer*>> carry;
  std::vector<std::vector<char>> input_host;  // staged data/label inputs
  std::vector<int> input_idx;      // arg index per input_host entry
  std::vector<char> loss_flags;    // per "out"-role output
  size_t n_state_outs = 0;         // outputs[0..n_state_outs) carry back
  int32_t t = 1;
  bool has_run = false;
  // last step's "out"-role buffers, one per device
  std::vector<std::vector<PJRT_Buffer*>> out_keep;

  ~Trainer() {
    Runtime* rt = runtime();
    for (auto& per_dev : carry)
      for (auto* b : per_dev) rt->destroy_buffer(b);
    for (auto& per_dev : out_keep)
      for (auto* b : per_dev) rt->destroy_buffer(b);
    if (exec) {
      PJRT_LoadedExecutable_Destroy_Args d;
      memset(&d, 0, sizeof d);
      d.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
      d.executable = exec;
      rt->api->PJRT_LoadedExecutable_Destroy(&d);
    }
  }
};

bool parse_train_arg(const JVal& j, TrainArg* s) {
  const JVal* n = j.get("name");
  const JVal* role = j.get("role");
  const JVal* shp = j.get("shape");
  const JVal* dt = j.get("dtype");
  if (!n || !role || !shp || !dt || shp->kind != JVal::ARR) return false;
  s->name = n->str;
  s->role = role->str;
  // same bounds read_ndarray_record enforces on the blob side: without them
  // a crafted manifest can overflow n_elem*size to a small value and defeat
  // the blob-vs-manifest size validation in train_create
  if (shp->arr.size() > 64) return false;
  for (const auto& d : shp->arr) {
    int64_t v = static_cast<int64_t>(d.num);
    if (v < 0 || v > (int64_t{1} << 31)) return false;
    // divide-before-multiply, as ndarray_wire.h does: the product itself
    // could overflow int64 and wrap past this guard
    if (v != 0 && s->n_elem > (int64_t{1} << 40) / v) return false;
    s->shape.push_back(v);
    s->shape_u32.push_back(static_cast<mx_uint>(v));
    s->n_elem *= v;
  }
  const JVal* kept = j.get("kept");
  if (kept && kept->kind == JVal::BOOL) s->kept = kept->b;
  const JVal* shard = j.get("sharding");
  if (shard && shard->kind == JVal::STR) {
    if (shard->str == "batch")
      s->shard = 1;
    else if (shard->str == "rep")
      s->shard = 0;
    else
      return false;  // unknown placement must fail, not run misplaced
  }
  return dtype_by_name(dt->str, &s->dtype);
}

// base64 decode (manifest "compile_options"); returns false on bad input
bool b64_decode(const std::string& in, std::string* out) {
  auto val = [](char c) -> int {
    if (c >= 'A' && c <= 'Z') return c - 'A';
    if (c >= 'a' && c <= 'z') return c - 'a' + 26;
    if (c >= '0' && c <= '9') return c - '0' + 52;
    if (c == '+') return 62;
    if (c == '/') return 63;
    return -1;
  };
  out->clear();
  int buf = 0, bits = 0;
  for (char c : in) {
    if (c == '=' || c == '\n' || c == '\r') continue;
    int v = val(c);
    if (v < 0) return false;
    buf = (buf << 6) | v;
    bits += 6;
    if (bits >= 8) {
      bits -= 8;
      out->push_back(static_cast<char>((buf >> bits) & 0xFF));
    }
  }
  return true;
}

int train_create(const char* bytes, size_t size, TrainNativeHandle* out) {
  Runtime* rt = runtime();
  if (!rt->client) return fail(rt->error);
  if (size < 16 || memcmp(bytes, "MXTPUAR1", 8) != 0)
    return fail("not an .mxa artifact; build one with "
                "mxnet_tpu.export_train_artifact");
  Cursor c{bytes + 8, bytes + size};
  uint64_t mlen = 0;
  if (!c.read(&mlen, 8) || static_cast<size_t>(c.end - c.p) < mlen)
    return fail(".mxa: truncated manifest");
  JVal manifest;
  if (!json_parse(c.p, mlen, &manifest) || manifest.kind != JVal::OBJ)
    return fail(".mxa: manifest is not valid JSON");
  c.p += mlen;
  const JVal* jkind = manifest.get("kind");
  if (!jkind || jkind->str != "train")
    return fail(".mxa: not a train artifact (kind != \"train\"); use "
                "MXPredCreateFromFile for predict artifacts");
  uint64_t plen = 0;
  if (!c.read(&plen, 8) || static_cast<size_t>(c.end - c.p) < plen)
    return fail(".mxa: truncated program");
  const char* program = c.p;
  c.p += plen;
  uint64_t qlen = 0;
  if (!c.read(&qlen, 8) || static_cast<size_t>(c.end - c.p) < qlen)
    return fail(".mxa: truncated params");
  std::vector<HostArray> params;
  std::string perr;
  if (!parse_params(c.p, qlen, &params, &perr)) return fail(".mxa: " + perr);
  std::map<std::string, const HostArray*> by_name;
  for (const auto& a : params) by_name[a.name] = &a;

  auto tr = std::make_unique<Trainer>();
  const JVal* jargs = manifest.get("args");
  const JVal* jouts = manifest.get("outputs");
  if (!jargs || !jouts || jargs->kind != JVal::ARR || jouts->kind != JVal::ARR)
    return fail(".mxa: train manifest missing args/outputs");
  for (const auto& j : jargs->arr) {
    TrainArg a;
    if (!parse_train_arg(j, &a)) return fail(".mxa: bad train arg spec");
    tr->args.push_back(std::move(a));
  }
  for (const auto& j : jouts->arr) {
    TrainArg a;
    if (!parse_train_arg(j, &a)) return fail(".mxa: bad train output spec");
    tr->outputs.push_back(std::move(a));
  }
  const JVal* jt0 = manifest.get("t0");
  tr->t = jt0 && jt0->kind == JVal::NUM ? static_cast<int32_t>(jt0->num) : 1;
  const JVal* jnd = manifest.get("num_devices");
  tr->n_dev = jnd && jnd->kind == JVal::NUM && jnd->num >= 1
                  ? static_cast<size_t>(jnd->num)
                  : 1;
  if (tr->n_dev > rt->devices.size())
    return fail(".mxa: artifact wants " + std::to_string(tr->n_dev) +
                " devices but the PJRT client has " +
                std::to_string(rt->devices.size()) + " addressable");
  const JVal* jloss = manifest.get("loss_outputs");
  if (jloss && jloss->kind == JVal::ARR)
    for (const auto& f : jloss->arr)
      tr->loss_flags.push_back(f.kind == JVal::BOOL && f.b);

  // carry prefix: param/state/aux args, in order, then inputs, then lr/t
  for (const auto& a : tr->args) {
    if (a.role == "param" || a.role == "state" || a.role == "aux")
      ++tr->n_carry;
    else
      break;
  }
  for (const auto& o : tr->outputs)
    if (o.role == "param" || o.role == "state" || o.role == "aux")
      ++tr->n_state_outs;
  if (tr->n_state_outs != tr->n_carry)
    return fail(".mxa: carry mismatch (outputs carry " +
                std::to_string(tr->n_state_outs) + " args " +
                std::to_string(tr->n_carry) + ")");
  for (size_t i = 0; i < tr->n_carry; ++i)
    if (tr->outputs[i].name != tr->args[i].name)
      return fail(".mxa: carry order mismatch at slot " + std::to_string(i));

  // validate the params blob against the manifest BEFORE compiling: a
  // crafted manifest whose shape exceeds a blob record must fail cleanly
  // here, not read past the record's bytes at upload time (the upload loop
  // below relies on these checks having run)
  std::vector<const HostArray*> carry_init(tr->n_carry, nullptr);
  for (size_t i = 0; i < tr->n_carry; ++i) {
    const TrainArg& a = tr->args[i];
    std::string key = (a.role == "param" ? "arg:" + a.name
                       : a.role == "aux" ? "aux:" + a.name
                                         : "state:" + a.name);
    auto it = by_name.find(key);
    if (it == by_name.end())
      return fail(".mxa: initial value '" + key + "' missing from blob");
    const HostArray& h = *it->second;
    if (h.rec.none) return fail(".mxa: '" + key + "' is a none record");
    DType dt;
    if (!dtype_by_flag(h.rec.dtype, &dt) || dt.pjrt != a.dtype.pjrt)
      return fail(".mxa: dtype mismatch for '" + key + "'");
    if (h.rec.data.size() !=
        static_cast<size_t>(a.n_elem) * a.dtype.size)
      return fail(".mxa: shape mismatch for '" + key + "' (blob record holds " +
                  std::to_string(h.rec.data.size()) + " bytes, manifest wants " +
                  std::to_string(static_cast<size_t>(a.n_elem) * a.dtype.size) +
                  ")");
    if (a.shard != 0)
      return fail(".mxa: carried arg '" + a.name +
                  "' is sharded; only replicated carry is supported");
    carry_init[i] = &h;
  }
  // sharded inputs/outputs must split evenly over the mesh
  for (const auto& a : tr->args)
    if (a.shard == 1 &&
        (a.shape.empty() ||
         a.shape[0] % static_cast<int64_t>(tr->n_dev) != 0))
      return fail(".mxa: sharded arg '" + a.name +
                  "' batch dim does not divide num_devices");
  for (const auto& o : tr->outputs)
    if (o.shard == 1 &&
        (o.shape.empty() ||
         o.shape[0] % static_cast<int64_t>(tr->n_dev) != 0))
      return fail(".mxa: sharded output '" + o.name +
                  "' batch dim does not divide num_devices");

  // compile
  PJRT_Program prog;
  memset(&prog, 0, sizeof prog);
  prog.struct_size = PJRT_Program_STRUCT_SIZE;
  prog.code = const_cast<char*>(program);
  prog.code_size = plen;
  static const char kFmt[] = "mlir";
  prog.format = kFmt;
  prog.format_size = strlen(kFmt);
  PJRT_Client_Compile_Args comp;
  memset(&comp, 0, sizeof comp);
  comp.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  comp.client = rt->client;
  comp.program = &prog;
  // an SPMD artifact ships its own compile options (num_partitions=N,
  // spmd partitioning, device assignment) from the export side
  std::string artifact_opts;
  const JVal* jco = manifest.get("compile_options");
  if (jco && jco->kind == JVal::STR) {
    if (!b64_decode(jco->str, &artifact_opts))
      return fail(".mxa: compile_options is not valid base64");
  } else if (tr->n_dev > 1) {
    return fail(".mxa: num_devices > 1 but no compile_options in manifest");
  }
  const std::string& co =
      artifact_opts.empty() ? compile_options_blob() : artifact_opts;
  comp.compile_options = co.data();
  comp.compile_options_size = co.size();
  if (PJRT_Error* e = rt->api->PJRT_Client_Compile(&comp))
    return fail(rt->err_of(e, "compile"));
  tr->exec = comp.executable;

  // slot d of the execute argument/output lists belongs to the
  // EXECUTABLE's addressable-device order, not the client's — query it
  {
    PJRT_LoadedExecutable_AddressableDevices_Args ad;
    memset(&ad, 0, sizeof ad);
    ad.struct_size = PJRT_LoadedExecutable_AddressableDevices_Args_STRUCT_SIZE;
    ad.executable = tr->exec;
    if (PJRT_Error* e =
            rt->api->PJRT_LoadedExecutable_AddressableDevices(&ad))
      return fail(rt->err_of(e, "executable addressable devices"));
    tr->exec_devices.assign(ad.addressable_devices,
                            ad.addressable_devices + ad.num_addressable_devices);
    if (tr->exec_devices.size() != tr->n_dev)
      return fail(".mxa: executable spans " +
                  std::to_string(tr->exec_devices.size()) +
                  " devices, manifest says " + std::to_string(tr->n_dev));
  }

  // upload initial carried state (validated against the manifest above);
  // carry is replicated: every device gets the same bytes
  std::string err;
  tr->carry.assign(tr->n_carry, {});
  for (size_t i = 0; i < tr->n_carry; ++i) {
    const TrainArg& a = tr->args[i];
    tr->carry[i].assign(tr->n_dev, nullptr);
    for (size_t d = 0; d < tr->n_dev; ++d) {
      PJRT_Buffer* b =
          rt->upload(carry_init[i]->rec.data.data(), a.dtype.pjrt, a.shape,
                     &err, tr->exec_devices[d]);
      if (!b) return fail(err);
      tr->carry[i][d] = b;
    }
  }

  // stage zero-filled input hosts for data/label args
  for (size_t i = tr->n_carry; i < tr->args.size(); ++i) {
    const TrainArg& a = tr->args[i];
    if (a.role != "data" && a.role != "label") continue;
    tr->input_idx.push_back(static_cast<int>(i));
    tr->input_host.emplace_back(a.n_elem * a.dtype.size, 0);
  }
  *out = tr.release();
  return 0;
}

// d2h of an f32 buffer with an explicit row-major host layout (the
// compiler may pick a column-major device layout; see MXPredGetOutput)
int fetch_f32(Runtime* rt, PJRT_Buffer* src,
              const std::vector<int64_t>& shape, float* dst, size_t n) {
  std::vector<int64_t> m2m(shape.size());
  for (size_t d = 0; d < m2m.size(); ++d)
    m2m[d] = static_cast<int64_t>(m2m.size() - 1 - d);
  PJRT_Buffer_MemoryLayout layout;
  memset(&layout, 0, sizeof layout);
  layout.struct_size = PJRT_Buffer_MemoryLayout_STRUCT_SIZE;
  layout.type = PJRT_Buffer_MemoryLayout_Type_Tiled;
  layout.tiled.struct_size = PJRT_Buffer_MemoryLayout_Tiled_STRUCT_SIZE;
  layout.tiled.minor_to_major = m2m.data();
  layout.tiled.minor_to_major_size = m2m.size();
  PJRT_Buffer_ToHostBuffer_Args th;
  memset(&th, 0, sizeof th);
  th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
  th.src = src;
  th.host_layout = &layout;
  if (PJRT_Error* e = rt->api->PJRT_Buffer_ToHostBuffer(&th))
    return fail(rt->err_of(e, "d2h size"));
  if (th.dst_size != n * sizeof(float))
    return fail("device buffer size mismatch");
  th.dst = dst;
  if (PJRT_Error* e = rt->api->PJRT_Buffer_ToHostBuffer(&th))
    return fail(rt->err_of(e, "d2h"));
  std::string err;
  if (!rt->await_ev(th.event, "d2h", &err)) return fail(err);
  return 0;
}

}  // namespace

// ---- C API ---------------------------------------------------------------

extern "C" {

const char* MXGetLastError() { return g_err.c_str(); }

MXNET_DLL int MXPredCreate(const char* symbol_json_str,
                           const void* param_bytes, int param_size,
                           int dev_type, int dev_id, mx_uint num_input_nodes,
                           const char** input_keys,
                           const mx_uint* input_shape_indptr,
                           const mx_uint* input_shape_data,
                           PredictorHandle* out) {
  (void)dev_type;
  (void)dev_id;
  if (param_bytes && param_size > 8 &&
      memcmp(param_bytes, "MXTPUAR1", 8) == 0)
    return create_from_artifact(static_cast<const char*>(param_bytes),
                                param_size, num_input_nodes, input_keys,
                                input_shape_indptr, input_shape_data, out);
  // reference-style (symbol JSON + raw params) is the embedded-Python
  // library's job; this native library consumes AOT artifacts only
  if (symbol_json_str && strstr(symbol_json_str, ".mxa"))
    return fail("pass the artifact bytes as param_bytes, or use "
                "MXPredCreateFromFile");
  return fail(
      "libmxtpu_predict_native consumes .mxa artifacts "
      "(export_predict_artifact); for symbol-JSON + params use "
      "libmxtpu_predict.so");
}

MXNET_DLL int MXPredCreateFromFile(const char* artifact_path, int dev_type,
                                   int dev_id, PredictorHandle* out) {
  (void)dev_type;
  (void)dev_id;
  FILE* f = fopen(artifact_path, "rb");
  if (!f) return fail(std::string("cannot open ") + artifact_path);
  fseek(f, 0, SEEK_END);
  long sz = ftell(f);
  fseek(f, 0, SEEK_SET);
  if (sz <= 0) {  // non-seekable stream (FIFO) or empty file
    fclose(f);
    return fail(std::string("cannot size ") + artifact_path +
                " (need a regular file)");
  }
  std::vector<char> bytes(sz);
  size_t got = fread(bytes.data(), 1, sz, f);
  fclose(f);
  if (got != static_cast<size_t>(sz))
    return fail(std::string("short read on ") + artifact_path);
  return create_from_artifact(bytes.data(), bytes.size(), 0, nullptr, nullptr,
                              nullptr, out);
}

MXNET_DLL int MXPredCreatePartialOut(
    const char* symbol_json_str, const void* param_bytes, int param_size,
    int dev_type, int dev_id, mx_uint num_input_nodes,
    const char** input_keys, const mx_uint* input_shape_indptr,
    const mx_uint* input_shape_data, mx_uint num_output_nodes,
    const char** output_keys, PredictorHandle* out) {
  (void)output_keys;
  // with no extra output nodes this is exactly MXPredCreate — accept it so
  // FFI frontends (e.g. the MATLAB binding) can drive both runtimes through
  // one call sequence
  if (num_output_nodes == 0)
    return MXPredCreate(symbol_json_str, param_bytes, param_size, dev_type,
                        dev_id, num_input_nodes, input_keys,
                        input_shape_indptr, input_shape_data, out);
  // an AOT artifact is a sealed executable; internal outputs are chosen at
  // export time (export_predict_artifact on sym.get_internals())
  return fail("partial-out is an export-time choice for AOT artifacts: "
              "re-export the internal outputs you need");
}

MXNET_DLL int MXPredSetInput(PredictorHandle handle, const char* key,
                             const mx_float* data, mx_uint size) {
  auto* p = static_cast<Predictor*>(handle);
  if (!p) return fail("null predictor");
  for (size_t i = 0; i < p->inputs.size(); ++i) {
    if (p->inputs[i].name != key) continue;
    const IoSpec& s = p->inputs[i];
    if (s.dtype.pjrt != PJRT_Buffer_Type_F32)
      return fail("MXPredSetInput is float32; input '" + s.name +
                  "' is not float32 in the artifact");
    if (size != static_cast<mx_uint>(s.n_elem))
      return fail("MXPredSetInput: size mismatch for '" + s.name + "'");
    memcpy(p->input_host[i].data(), data, size * sizeof(float));
    return 0;
  }
  return fail(std::string("unknown input '") + key + "'");
}

MXNET_DLL int MXPredForward(PredictorHandle handle) {
  auto* p = static_cast<Predictor*>(handle);
  if (!p) return fail("null predictor");
  Runtime* rt = runtime();
  std::string err;

  std::vector<PJRT_Buffer*> in_bufs;
  in_bufs.reserve(p->inputs.size() + p->param_bufs.size());
  for (size_t i = 0; i < p->inputs.size(); ++i) {
    if (!p->inputs[i].kept) continue;
    PJRT_Buffer* b = rt->upload(p->input_host[i].data(),
                                p->inputs[i].dtype.pjrt, p->inputs[i].shape,
                                &err);
    if (!b) {
      for (auto* x : in_bufs) rt->destroy_buffer(x);
      return fail(err);
    }
    in_bufs.push_back(b);
  }
  std::vector<PJRT_Buffer*> args(in_bufs);
  args.insert(args.end(), p->param_bufs.begin(), p->param_bufs.end());

  // a failed re-forward must not leave stale/null outputs readable
  p->has_run = false;
  for (auto* b : p->out_bufs) rt->destroy_buffer(b);
  p->out_bufs.assign(p->outputs.size(), nullptr);

  PJRT_ExecuteOptions eo;
  memset(&eo, 0, sizeof eo);
  eo.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;
  PJRT_Buffer* const* arg_lists[1] = {args.data()};
  PJRT_Buffer** out_lists[1] = {p->out_bufs.data()};
  PJRT_Event* done[1] = {nullptr};
  PJRT_LoadedExecutable_Execute_Args ex;
  memset(&ex, 0, sizeof ex);
  ex.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  ex.executable = p->exec;
  ex.options = &eo;
  ex.argument_lists = arg_lists;
  ex.num_devices = 1;
  ex.num_args = args.size();
  ex.output_lists = out_lists;
  ex.device_complete_events = done;
  PJRT_Error* e = rt->api->PJRT_LoadedExecutable_Execute(&ex);
  if (e) {
    for (auto* b : in_bufs) rt->destroy_buffer(b);
    return fail(rt->err_of(e, "execute"));
  }
  // await device completion BEFORE releasing the input buffers — Destroy on
  // an in-flight buffer is plugin-defined behavior
  bool ok = rt->await_ev(done[0], "execute", &err);
  for (auto* b : in_bufs) rt->destroy_buffer(b);
  if (!ok) return fail(err);
  p->has_run = true;
  return 0;
}

MXNET_DLL int MXPredPartialForward(PredictorHandle handle, int step,
                                   int* step_left) {
  // the whole graph is one XLA executable — a single step (reference
  // semantics degenerate to this when bulk-exec covers the graph)
  if (step_left) *step_left = 0;
  if (step > 0) return 0;
  return MXPredForward(handle);
}

MXNET_DLL int MXPredGetOutputShape(PredictorHandle handle, mx_uint index,
                                   mx_uint** shape_data, mx_uint* shape_ndim) {
  auto* p = static_cast<Predictor*>(handle);
  if (!p) return fail("null predictor");
  if (index >= p->outputs.size()) return fail("output index out of range");
  *shape_data = p->outputs[index].shape_u32.data();
  *shape_ndim = static_cast<mx_uint>(p->outputs[index].shape_u32.size());
  return 0;
}

MXNET_DLL int MXPredGetOutput(PredictorHandle handle, mx_uint index,
                              mx_float* data, mx_uint size) {
  auto* p = static_cast<Predictor*>(handle);
  if (!p) return fail("null predictor");
  if (!p->has_run) return fail("call MXPredForward first");
  if (index >= p->outputs.size() || index >= p->out_bufs.size() ||
      !p->out_bufs[index])
    return fail("output index out of range");
  const IoSpec& s = p->outputs[index];
  if (s.dtype.pjrt != PJRT_Buffer_Type_F32)
    return fail("MXPredGetOutput is float32; output is not float32");
  if (size != static_cast<mx_uint>(s.n_elem))
    return fail("MXPredGetOutput: size mismatch");
  // the executable's output layout is the compiler's choice (observed:
  // column-major for some shapes on TPU); fetch_f32 requests an explicit
  // row-major host layout so dst is always dense C-order
  return fetch_f32(runtime(), p->out_bufs[index], s.shape, data, size);
}

MXNET_DLL int MXPredFree(PredictorHandle handle) {
  delete static_cast<Predictor*>(handle);
  return 0;
}

// ---- MXNDList*: parse a .params blob (pure C++, reference
// c_predict_api.cc MXNDListCreate) -----------------------------------------

struct NDList {
  std::vector<HostArray> arrays;
  std::vector<std::vector<float>> as_float;
};

MXNET_DLL int MXNDListCreate(const char* nd_file_bytes, int nd_file_size,
                             NDListHandle* out, mx_uint* out_length) {
  auto lst = std::make_unique<NDList>();
  std::string err;
  if (!parse_params(nd_file_bytes, nd_file_size, &lst->arrays, &err))
    return fail(err);
  lst->as_float.resize(lst->arrays.size());
  for (size_t i = 0; i < lst->arrays.size(); ++i) {
    const HostArray& a = lst->arrays[i];
    if (a.rec.none) continue;  // empty slot, zero-length view
    if (a.rec.dtype != 0)
      return fail("MXNDListCreate: non-float32 array in list (flag " +
                  std::to_string(a.rec.dtype) + ")");
    const float* f = reinterpret_cast<const float*>(a.rec.data.data());
    lst->as_float[i].assign(f, f + a.rec.data.size() / sizeof(float));
  }
  *out_length = static_cast<mx_uint>(lst->arrays.size());
  *out = lst.release();
  return 0;
}

MXNET_DLL int MXNDListGet(NDListHandle handle, mx_uint index,
                          const char** out_key, const mx_float** out_data,
                          const mx_uint** out_shape, mx_uint* out_ndim) {
  auto* lst = static_cast<NDList*>(handle);
  if (!lst || index >= lst->arrays.size()) return fail("bad NDList index");
  *out_key = lst->arrays[index].name.c_str();
  *out_data = lst->as_float[index].data();
  *out_shape = lst->arrays[index].shape_u32.data();
  *out_ndim = static_cast<mx_uint>(lst->arrays[index].shape_u32.size());
  return 0;
}

MXNET_DLL int MXNDListFree(NDListHandle handle) {
  delete static_cast<NDList*>(handle);
  return 0;
}

// ---- MXTrainNative*: Python-free training ---------------------------------

MXNET_DLL int MXTrainNativeCreateFromFile(const char* artifact_path,
                                          TrainNativeHandle* out) {
  FILE* f = fopen(artifact_path, "rb");
  if (!f) return fail(std::string("cannot open ") + artifact_path);
  fseek(f, 0, SEEK_END);
  long n = ftell(f);
  fseek(f, 0, SEEK_SET);
  std::vector<char> bytes(n > 0 ? n : 0);
  if (n <= 0 || fread(bytes.data(), 1, n, f) != static_cast<size_t>(n)) {
    fclose(f);
    return fail(std::string("cannot read ") + artifact_path);
  }
  fclose(f);
  return train_create(bytes.data(), bytes.size(), out);
}

MXNET_DLL int MXTrainNativeNumInputs(TrainNativeHandle h, mx_uint* out) {
  auto* tr = static_cast<Trainer*>(h);
  if (!tr) return fail("null trainer");
  *out = static_cast<mx_uint>(tr->input_idx.size());
  return 0;
}

MXNET_DLL int MXTrainNativeInputInfo(TrainNativeHandle h, mx_uint index,
                                     const char** name, const char** role,
                                     const mx_uint** shape, mx_uint* ndim) {
  auto* tr = static_cast<Trainer*>(h);
  if (!tr || index >= tr->input_idx.size()) return fail("bad input index");
  const TrainArg& a = tr->args[tr->input_idx[index]];
  *name = a.name.c_str();
  *role = a.role.c_str();
  *shape = a.shape_u32.data();
  *ndim = static_cast<mx_uint>(a.shape_u32.size());
  return 0;
}

MXNET_DLL int MXTrainNativeSetInput(TrainNativeHandle h, const char* key,
                                    const mx_float* data, mx_uint size) {
  auto* tr = static_cast<Trainer*>(h);
  if (!tr) return fail("null trainer");
  for (size_t i = 0; i < tr->input_idx.size(); ++i) {
    const TrainArg& a = tr->args[tr->input_idx[i]];
    if (a.name != key) continue;
    if (a.dtype.pjrt != PJRT_Buffer_Type_F32)
      return fail("MXTrainNativeSetInput is float32; input '" + a.name +
                  "' is not float32 in the artifact");
    if (size != static_cast<mx_uint>(a.n_elem))
      return fail("MXTrainNativeSetInput: size mismatch for '" + a.name +
                  "'");
    memcpy(tr->input_host[i].data(), data, size * sizeof(float));
    return 0;
  }
  return fail(std::string("unknown train input '") + key + "'");
}

MXNET_DLL int MXTrainNativeStep(TrainNativeHandle h, mx_float lr) {
  auto* tr = static_cast<Trainer*>(h);
  if (!tr) return fail("null trainer");
  Runtime* rt = runtime();
  std::string err;
  const size_t ndev = tr->n_dev;

  // assemble kept args per device: carried state, uploaded inputs, lr, t.
  // Replicated args upload the full host bytes to every device; "batch"-
  // sharded args upload each device's contiguous row slice (axis-0 splits
  // are contiguous in row-major).
  std::vector<PJRT_Buffer*> scratch;  // buffers to free after execute
  auto cleanup = [&]() {
    for (auto* b : scratch) rt->destroy_buffer(b);
  };
  auto drop_carry = [&]() {
    // the carried buffers may have been donated/invalidated by a failed
    // execute: drop the handles so the next step hits the "carried state
    // is gone" guard instead of passing consumed buffers back into PJRT
    for (auto& per_dev : tr->carry)
      for (auto& b : per_dev) {
        rt->destroy_buffer(b);
        b = nullptr;
      }
  };
  std::vector<std::vector<PJRT_Buffer*>> exec_args(ndev);
  size_t host_i = 0;
  int32_t t_val = tr->t;
  for (size_t i = 0; i < tr->args.size(); ++i) {
    const TrainArg& a = tr->args[i];
    if (i < tr->n_carry) {
      if (!a.kept) continue;
      for (size_t d = 0; d < ndev; ++d) {
        PJRT_Buffer* b = tr->carry[i][d];
        if (!b) {
          cleanup();
          return fail("carried state '" + a.name + "' is gone (previous "
                      "step failed); recreate the trainer");
        }
        exec_args[d].push_back(b);
      }
      continue;
    }
    if (a.role == "data" || a.role == "label") {
      size_t hi = host_i++;
      if (!a.kept) continue;
      if (a.shard == 1 && ndev > 1) {
        std::vector<int64_t> local = a.shape;
        local[0] /= static_cast<int64_t>(ndev);
        size_t local_bytes =
            static_cast<size_t>(a.n_elem) / ndev * a.dtype.size;
        for (size_t d = 0; d < ndev; ++d) {
          PJRT_Buffer* b =
              rt->upload(tr->input_host[hi].data() + d * local_bytes,
                         a.dtype.pjrt, local, &err, tr->exec_devices[d]);
          if (!b) { cleanup(); return fail(err); }
          scratch.push_back(b);
          exec_args[d].push_back(b);
        }
      } else {
        for (size_t d = 0; d < ndev; ++d) {
          PJRT_Buffer* b = rt->upload(tr->input_host[hi].data(),
                                      a.dtype.pjrt, a.shape, &err,
                                      tr->exec_devices[d]);
          if (!b) { cleanup(); return fail(err); }
          scratch.push_back(b);
          exec_args[d].push_back(b);
        }
      }
      continue;
    }
    if (a.role == "lr" || a.role == "t") {
      if (!a.kept) continue;
      float lv = lr;
      const void* src = a.role == "lr" ? static_cast<const void*>(&lv)
                                       : static_cast<const void*>(&t_val);
      PJRT_Buffer_Type ty = a.role == "lr" ? PJRT_Buffer_Type_F32
                                           : PJRT_Buffer_Type_S32;
      for (size_t d = 0; d < ndev; ++d) {
        PJRT_Buffer* b = rt->upload(src, ty, {}, &err, tr->exec_devices[d]);
        if (!b) { cleanup(); return fail(err); }
        scratch.push_back(b);
        exec_args[d].push_back(b);
      }
      continue;
    }
    cleanup();
    return fail("unknown arg role '" + a.role + "'");
  }

  std::vector<std::vector<PJRT_Buffer*>> outs(
      ndev, std::vector<PJRT_Buffer*>(tr->outputs.size(), nullptr));
  PJRT_ExecuteOptions eo;
  memset(&eo, 0, sizeof eo);
  eo.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;
  // the program aliases carried outputs onto donated inputs; PJRT requires
  // the caller to acknowledge donation is fine (default) — nothing to set.
  std::vector<PJRT_Buffer* const*> arg_lists(ndev);
  std::vector<PJRT_Buffer**> out_lists(ndev);
  std::vector<PJRT_Event*> done(ndev, nullptr);
  for (size_t d = 0; d < ndev; ++d) {
    arg_lists[d] = exec_args[d].data();
    out_lists[d] = outs[d].data();
  }
  PJRT_LoadedExecutable_Execute_Args ex;
  memset(&ex, 0, sizeof ex);
  ex.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  ex.executable = tr->exec;
  ex.options = &eo;
  ex.argument_lists = arg_lists.data();
  ex.num_devices = ndev;
  ex.num_args = exec_args[0].size();
  ex.output_lists = out_lists.data();
  ex.device_complete_events = done.data();
  PJRT_Error* e = rt->api->PJRT_LoadedExecutable_Execute(&ex);
  if (e) {
    cleanup();
    drop_carry();
    return fail(rt->err_of(e, "train step execute"));
  }
  bool ok = true;
  for (size_t d = 0; d < ndev; ++d)
    if (done[d]) {
      std::string dev_err;
      if (!rt->await_ev(done[d], "train step", &dev_err)) {
        ok = false;
        if (err.empty()) err = dev_err;
      }
    }
  cleanup();
  if (!ok) {
    for (auto& per_dev : outs)
      for (auto* b : per_dev) rt->destroy_buffer(b);
    // the carried buffers were donated into the failed step: their device
    // memory is consumed, but the HANDLES still need destroying before the
    // trainer drops them (a nulled handle would leak plugin bookkeeping)
    drop_carry();
    return fail(err);
  }

  // adopt the carried outputs as the new state (old buffers were donated
  // into the step; destroying the handle releases our reference)
  for (size_t i = 0; i < tr->n_carry; ++i)
    for (size_t d = 0; d < ndev; ++d) {
      rt->destroy_buffer(tr->carry[i][d]);
      tr->carry[i][d] = outs[d][i];
    }
  for (auto& per_dev : tr->out_keep)
    for (auto* b : per_dev) rt->destroy_buffer(b);
  tr->out_keep.assign(tr->outputs.size() - tr->n_state_outs, {});
  for (size_t j = tr->n_state_outs; j < tr->outputs.size(); ++j) {
    auto& slot = tr->out_keep[j - tr->n_state_outs];
    slot.assign(ndev, nullptr);
    for (size_t d = 0; d < ndev; ++d) slot[d] = outs[d][j];
  }
  tr->t += 1;
  tr->has_run = true;
  return 0;
}

MXNET_DLL int MXTrainNativeNumOutputs(TrainNativeHandle h, mx_uint* out) {
  auto* tr = static_cast<Trainer*>(h);
  if (!tr) return fail("null trainer");
  *out = static_cast<mx_uint>(tr->outputs.size() - tr->n_state_outs);
  return 0;
}

MXNET_DLL int MXTrainNativeOutputInfo(TrainNativeHandle h, mx_uint index,
                                      const char** name, int* is_loss,
                                      const mx_uint** shape, mx_uint* ndim) {
  auto* tr = static_cast<Trainer*>(h);
  if (!tr) return fail("null trainer");
  size_t i = tr->n_state_outs + index;
  if (i >= tr->outputs.size()) return fail("output index out of range");
  const TrainArg& a = tr->outputs[i];
  *name = a.name.c_str();
  *is_loss = index < tr->loss_flags.size() && tr->loss_flags[index] ? 1 : 0;
  *shape = a.shape_u32.data();
  *ndim = static_cast<mx_uint>(a.shape_u32.size());
  return 0;
}

MXNET_DLL int MXTrainNativeGetOutput(TrainNativeHandle h, mx_uint index,
                                     mx_float* data, mx_uint size) {
  auto* tr = static_cast<Trainer*>(h);
  if (!tr) return fail("null trainer");
  if (!tr->has_run) return fail("call MXTrainNativeStep first");
  if (index >= tr->out_keep.size() || tr->out_keep[index].empty() ||
      !tr->out_keep[index][0])
    return fail("output index out of range");
  const TrainArg& a = tr->outputs[tr->n_state_outs + index];
  if (a.dtype.pjrt != PJRT_Buffer_Type_F32)
    return fail("MXTrainNativeGetOutput is float32");
  if (size != static_cast<mx_uint>(a.n_elem))
    return fail("MXTrainNativeGetOutput: size mismatch");
  if (a.shard == 1 && tr->n_dev > 1) {
    // gather the batch-sharded output: device d holds rows
    // [d*local, (d+1)*local) — contiguous in row-major
    std::vector<int64_t> local = a.shape;
    local[0] /= static_cast<int64_t>(tr->n_dev);
    size_t local_n = static_cast<size_t>(a.n_elem) / tr->n_dev;
    for (size_t d = 0; d < tr->n_dev; ++d) {
      int rc = fetch_f32(runtime(), tr->out_keep[index][d], local,
                         data + d * local_n, local_n);
      if (rc != 0) return rc;
    }
    return 0;
  }
  return fetch_f32(runtime(), tr->out_keep[index][0], a.shape, data, size);
}

MXNET_DLL int MXTrainNativeSaveParams(TrainNativeHandle h, const char* path) {
  auto* tr = static_cast<Trainer*>(h);
  if (!tr) return fail("null trainer");
  Runtime* rt = runtime();
  // collect param/aux carry slots to host
  std::vector<std::pair<std::string, std::vector<float>>> entries;
  std::vector<const TrainArg*> specs;
  for (size_t i = 0; i < tr->n_carry; ++i) {
    const TrainArg& a = tr->args[i];
    if (a.role != "param" && a.role != "aux") continue;
    if (tr->carry[i].empty() || !tr->carry[i][0])
      return fail("carried state is gone (a step failed); cannot save");
    // carry is replicated: device 0's copy is the value
    std::vector<float> host(a.n_elem);
    int rc = fetch_f32(rt, tr->carry[i][0], a.shape, host.data(),
                       host.size());
    if (rc != 0) return rc;
    entries.emplace_back((a.role == "param" ? "arg:" : "aux:") + a.name,
                         std::move(host));
    specs.push_back(&a);
  }
  FILE* f = fopen(path, "wb");
  if (!f) return fail(std::string("cannot open for write: ") + path);
  auto wr = [&](const void* p, size_t n) {
    return fwrite(p, 1, n, f) == n;
  };
  bool ok = true;
  uint64_t magic = mxt_ndwire::kListMagic, reserved = 0,
           count = entries.size();
  ok = ok && wr(&magic, 8) && wr(&reserved, 8) && wr(&count, 8);
  for (size_t i = 0; ok && i < entries.size(); ++i) {
    const TrainArg& a = *specs[i];
    uint32_t nd_magic = mxt_ndwire::kNDArrayMagic;
    // ndim=0 means "none record" to every reader of this wire format
    // (read_ndarray_record, ndarray.py), which would desynchronize the
    // stream — write scalars as shape (1,) like the Python saver does
    std::vector<uint32_t> dims(a.shape_u32.begin(), a.shape_u32.end());
    if (dims.empty()) dims.push_back(1);
    uint32_t ndim = static_cast<uint32_t>(dims.size());
    ok = ok && wr(&nd_magic, 4) && wr(&ndim, 4);
    for (uint32_t v : dims) ok = ok && wr(&v, 4);
    int32_t devctx[2] = {1, 0};  // cpu(0)
    int32_t flag = 0;            // float32
    ok = ok && wr(devctx, 8) && wr(&flag, 4) &&
         wr(entries[i].second.data(), entries[i].second.size() * 4);
  }
  uint64_t n_names = entries.size();
  ok = ok && wr(&n_names, 8);
  for (size_t i = 0; ok && i < entries.size(); ++i) {
    uint64_t len = entries[i].first.size();
    ok = ok && wr(&len, 8) && wr(entries[i].first.data(), len);
  }
  ok = (fclose(f) == 0) && ok;  // flush failures (e.g. full disk) land here
  if (!ok) return fail(std::string("short write: ") + path);
  return 0;
}

MXNET_DLL int MXTrainNativeFree(TrainNativeHandle h) {
  delete static_cast<Trainer*>(h);
  return 0;
}

}  // extern "C"
