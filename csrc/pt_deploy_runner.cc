// pt_deploy_runner — Python-free inference on a jit.save deploy bundle.
//
// Reference analogue: the C++ inference API
// (paddle/fluid/inference/api/analysis_predictor.cc, paddle_inference_api.h)
// that runs exported models without Python. TPU redesign: the exported
// artifact is portable StableHLO (jit.save_deploy_bundle), and execution is
// the PJRT C API against ANY PJRT plugin .so (libtpu.so on a machine with
// a TPU) — the runner is a plain C++17 binary with no framework, protobuf,
// or Python dependency.
//
// Bundle layout (written by paddle_tpu.jit.save_deploy_bundle):
//   manifest.txt        line-based: module/options files, params, inputs
//   module.stablehlo    portable StableHLO bytecode
//   compile_options.pb  serialized CompileOptionsProto (1 replica)
//   p<N>.bin            raw little-endian parameter leaves, call order
//
// Usage:
//   pt_deploy_runner <bundle_dir> --plugin <pjrt_plugin.so> \
//       [--input <raw.bin>]... [--out <prefix>]
//
// Inputs are raw binaries matching the manifest's input dtypes/shapes;
// outputs are written to <prefix><i>.bin and their shapes printed.
//
// Build:
//   g++ -std=c++17 -O2 -I<dir containing xla/pjrt/c/pjrt_c_api.h> \
//       csrc/pt_deploy_runner.cc -o pt_deploy_runner -ldl

#include <dlfcn.h>

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <fstream>
#include <sstream>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

[[noreturn]] void Die(const std::string& msg) {
  std::fprintf(stderr, "pt_deploy_runner: %s\n", msg.c_str());
  std::exit(1);
}

const PJRT_Api* g_api = nullptr;

void Check(PJRT_Error* err, const char* what) {
  if (err == nullptr) return;
  PJRT_Error_Message_Args m;
  std::memset(&m, 0, sizeof(m));
  m.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  m.error = err;
  g_api->PJRT_Error_Message(&m);
  std::string text(m.message, m.message_size);
  PJRT_Error_Destroy_Args d;
  std::memset(&d, 0, sizeof(d));
  d.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  d.error = err;
  g_api->PJRT_Error_Destroy(&d);
  Die(std::string(what) + ": " + text);
}

void Await(PJRT_Event* ev, const char* what) {
  if (ev == nullptr) return;
  PJRT_Event_Await_Args a;
  std::memset(&a, 0, sizeof(a));
  a.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  a.event = ev;
  Check(g_api->PJRT_Event_Await(&a), what);
  PJRT_Event_Destroy_Args d;
  std::memset(&d, 0, sizeof(d));
  d.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  d.event = ev;
  g_api->PJRT_Event_Destroy(&d);
}

std::string ReadFile(const std::string& path) {
  std::ifstream f(path, std::ios::binary);
  if (!f) Die("cannot read " + path);
  std::ostringstream ss;
  ss << f.rdbuf();
  return ss.str();
}

struct TensorSpec {
  std::string file;  // empty for runtime inputs
  PJRT_Buffer_Type type = PJRT_Buffer_Type_F32;
  size_t elem_bytes = 4;
  std::vector<int64_t> dims;
  size_t NumBytes() const {
    size_t n = elem_bytes;
    for (int64_t d : dims) n *= static_cast<size_t>(d);
    return n;
  }
};

PJRT_Buffer_Type ParseType(const std::string& t, size_t* bytes) {
  if (t == "f32") { *bytes = 4; return PJRT_Buffer_Type_F32; }
  if (t == "f16") { *bytes = 2; return PJRT_Buffer_Type_F16; }
  if (t == "bf16") { *bytes = 2; return PJRT_Buffer_Type_BF16; }
  if (t == "f64") { *bytes = 8; return PJRT_Buffer_Type_F64; }
  if (t == "i32" || t == "s32") { *bytes = 4; return PJRT_Buffer_Type_S32; }
  if (t == "i64" || t == "s64") { *bytes = 8; return PJRT_Buffer_Type_S64; }
  if (t == "u8") { *bytes = 1; return PJRT_Buffer_Type_U8; }
  if (t == "i8" || t == "s8") { *bytes = 1; return PJRT_Buffer_Type_S8; }
  if (t == "pred" || t == "bool") { *bytes = 1; return PJRT_Buffer_Type_PRED; }
  Die("unsupported dtype in manifest: " + t);
}

struct Manifest {
  std::string module_file = "module.stablehlo";
  std::string options_file = "compile_options.pb";
  std::vector<TensorSpec> params;
  std::vector<TensorSpec> inputs;
};

Manifest ParseManifest(const std::string& dir) {
  Manifest m;
  std::ifstream f(dir + "/manifest.txt");
  if (!f) Die("cannot read " + dir + "/manifest.txt");
  std::string line;
  while (std::getline(f, line)) {
    if (line.empty() || line[0] == '#') continue;
    std::istringstream ss(line);
    std::string kind;
    ss >> kind;
    if (kind == "module") { ss >> m.module_file; continue; }
    if (kind == "options") { ss >> m.options_file; continue; }
    if (kind == "param" || kind == "input") {
      TensorSpec t;
      std::string ty;
      if (kind == "param") ss >> t.file;
      ss >> ty;
      t.type = ParseType(ty, &t.elem_bytes);
      int64_t d;
      while (ss >> d) t.dims.push_back(d);
      (kind == "param" ? m.params : m.inputs).push_back(t);
      continue;
    }
    // unknown lines (e.g. "output ...") are informational
  }
  return m;
}

PJRT_Buffer* ToDevice(PJRT_Client* client, PJRT_Device* device,
                      const TensorSpec& spec, const std::string& data) {
  if (data.size() != spec.NumBytes())
    Die("size mismatch for " + spec.file + ": file has " +
        std::to_string(data.size()) + " bytes, manifest says " +
        std::to_string(spec.NumBytes()));
  PJRT_Client_BufferFromHostBuffer_Args a;
  std::memset(&a, 0, sizeof(a));
  a.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
  a.client = client;
  a.data = data.data();
  a.type = spec.type;
  a.dims = spec.dims.data();
  a.num_dims = spec.dims.size();
  a.host_buffer_semantics =
      PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
  a.device = device;
  Check(g_api->PJRT_Client_BufferFromHostBuffer(&a), "BufferFromHostBuffer");
  Await(a.done_with_host_buffer, "host buffer transfer");
  return a.buffer;
}

}  // namespace

int main(int argc, char** argv) {
  std::string bundle, plugin, out_prefix = "out";
  std::vector<std::string> input_files;
  // client create_options (PJRT_NamedValue): some plugins require them
  // (a stock libtpu.so needs none)
  std::vector<std::pair<std::string, std::string>> str_opts;
  std::vector<std::pair<std::string, int64_t>> int_opts;
  auto split_kv = [](const std::string& s) {
    size_t eq = s.find('=');
    if (eq == std::string::npos) Die("--opt expects key=value: " + s);
    return std::make_pair(s.substr(0, eq), s.substr(eq + 1));
  };
  for (int i = 1; i < argc; ++i) {
    std::string a = argv[i];
    if (a == "--plugin" && i + 1 < argc) plugin = argv[++i];
    else if (a == "--input" && i + 1 < argc) input_files.push_back(argv[++i]);
    else if (a == "--out" && i + 1 < argc) out_prefix = argv[++i];
    else if (a == "--opt-str" && i + 1 < argc)
      str_opts.push_back(split_kv(argv[++i]));
    else if (a == "--opt-int" && i + 1 < argc) {
      auto kv = split_kv(argv[++i]);
      int_opts.emplace_back(kv.first, std::stoll(kv.second));
    } else if (bundle.empty()) bundle = a;
    else Die("unexpected argument: " + a);
  }
  if (bundle.empty() || plugin.empty())
    Die("usage: pt_deploy_runner <bundle_dir> --plugin <pjrt.so> "
        "[--input raw.bin]... [--out prefix] [--opt-str k=v] "
        "[--opt-int k=v]");

  Manifest mf = ParseManifest(bundle);
  if (input_files.size() != mf.inputs.size())
    Die("bundle expects " + std::to_string(mf.inputs.size()) +
        " runtime inputs, got " + std::to_string(input_files.size()));

  void* lib = dlopen(plugin.c_str(), RTLD_NOW | RTLD_LOCAL);
  if (!lib) Die(std::string("dlopen failed: ") + dlerror());
  auto get_api = reinterpret_cast<const PJRT_Api* (*)()>(
      dlsym(lib, "GetPjrtApi"));
  if (!get_api) Die("plugin has no GetPjrtApi symbol");
  g_api = get_api();
  if (!g_api) Die("GetPjrtApi returned null");
  std::fprintf(stderr, "[runner] plugin PJRT API v%d.%d\n",
               g_api->pjrt_api_version.major_version,
               g_api->pjrt_api_version.minor_version);

  PJRT_Plugin_Initialize_Args pi;
  std::memset(&pi, 0, sizeof(pi));
  pi.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  Check(g_api->PJRT_Plugin_Initialize(&pi), "Plugin_Initialize");

  std::vector<PJRT_NamedValue> nvs;
  for (const auto& [k, v] : str_opts) {
    PJRT_NamedValue nv;
    std::memset(&nv, 0, sizeof(nv));
    nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
    nv.name = k.c_str();
    nv.name_size = k.size();
    nv.type = PJRT_NamedValue_kString;
    nv.string_value = v.c_str();
    nv.value_size = v.size();
    nvs.push_back(nv);
  }
  for (const auto& [k, v] : int_opts) {
    PJRT_NamedValue nv;
    std::memset(&nv, 0, sizeof(nv));
    nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
    nv.name = k.c_str();
    nv.name_size = k.size();
    nv.type = PJRT_NamedValue_kInt64;
    nv.int64_value = v;
    nv.value_size = 1;
    nvs.push_back(nv);
  }

  PJRT_Client_Create_Args cc;
  std::memset(&cc, 0, sizeof(cc));
  cc.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  cc.create_options = nvs.data();
  cc.num_options = nvs.size();
  Check(g_api->PJRT_Client_Create(&cc), "Client_Create");
  PJRT_Client* client = cc.client;

  PJRT_Client_AddressableDevices_Args ad;
  std::memset(&ad, 0, sizeof(ad));
  ad.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  ad.client = client;
  Check(g_api->PJRT_Client_AddressableDevices(&ad), "AddressableDevices");
  if (ad.num_addressable_devices == 0) Die("no addressable devices");
  PJRT_Device* device = ad.addressable_devices[0];

  // compile the portable StableHLO with the bundle's serialized options
  std::string module = ReadFile(bundle + "/" + mf.module_file);
  std::string options = ReadFile(bundle + "/" + mf.options_file);
  PJRT_Program prog;
  std::memset(&prog, 0, sizeof(prog));
  prog.struct_size = PJRT_Program_STRUCT_SIZE;
  prog.code = module.data();
  prog.code_size = module.size();
  static const char kFormat[] = "mlir";
  prog.format = kFormat;
  prog.format_size = sizeof(kFormat) - 1;

  PJRT_Client_Compile_Args co;
  std::memset(&co, 0, sizeof(co));
  co.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  co.client = client;
  co.program = &prog;
  co.compile_options = options.data();
  co.compile_options_size = options.size();
  Check(g_api->PJRT_Client_Compile(&co), "Compile");
  PJRT_LoadedExecutable* exe = co.executable;
  std::fprintf(stderr, "[runner] compiled %zu-byte module\n", module.size());

  // stage arguments: params from the bundle, then runtime inputs
  std::vector<std::string> host_data;
  std::vector<PJRT_Buffer*> args_bufs;
  for (const TensorSpec& p : mf.params)
    host_data.push_back(ReadFile(bundle + "/" + p.file));
  for (size_t i = 0; i < mf.params.size(); ++i)
    args_bufs.push_back(ToDevice(client, device, mf.params[i], host_data[i]));
  for (size_t i = 0; i < input_files.size(); ++i) {
    std::string data = ReadFile(input_files[i]);
    args_bufs.push_back(ToDevice(client, device, mf.inputs[i], data));
  }

  PJRT_LoadedExecutable_GetExecutable_Args ge;
  std::memset(&ge, 0, sizeof(ge));
  ge.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  ge.loaded_executable = exe;
  Check(g_api->PJRT_LoadedExecutable_GetExecutable(&ge), "GetExecutable");
  PJRT_Executable_NumOutputs_Args no;
  std::memset(&no, 0, sizeof(no));
  no.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  no.executable = ge.executable;
  Check(g_api->PJRT_Executable_NumOutputs(&no), "NumOutputs");
  size_t num_outputs = no.num_outputs;

  PJRT_ExecuteOptions eo;
  std::memset(&eo, 0, sizeof(eo));
  eo.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

  std::vector<PJRT_Buffer*> outs(num_outputs, nullptr);
  PJRT_Buffer** out_list = outs.data();
  PJRT_Buffer* const* arg_list = args_bufs.data();
  PJRT_Event* done = nullptr;

  PJRT_LoadedExecutable_Execute_Args ex;
  std::memset(&ex, 0, sizeof(ex));
  ex.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  ex.executable = exe;
  ex.options = &eo;
  ex.argument_lists = &arg_list;
  ex.num_devices = 1;
  ex.num_args = args_bufs.size();
  ex.output_lists = &out_list;
  ex.device_complete_events = &done;
  ex.execute_device = device;
  Check(g_api->PJRT_LoadedExecutable_Execute(&ex), "Execute");
  Await(done, "execute");

  for (size_t i = 0; i < num_outputs; ++i) {
    PJRT_Buffer_ToHostBuffer_Args th;
    std::memset(&th, 0, sizeof(th));
    th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    th.src = outs[i];
    Check(g_api->PJRT_Buffer_ToHostBuffer(&th), "ToHostBuffer(size)");
    std::string host(th.dst_size, '\0');
    std::memset(&th, 0, sizeof(th));
    th.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    th.src = outs[i];
    th.dst = host.data();
    th.dst_size = host.size();
    Check(g_api->PJRT_Buffer_ToHostBuffer(&th), "ToHostBuffer");
    Await(th.event, "to host");
    std::string path = out_prefix + std::to_string(i) + ".bin";
    std::ofstream of(path, std::ios::binary);
    of.write(host.data(), static_cast<std::streamsize>(host.size()));
    std::printf("output %zu: %zu bytes -> %s\n", i, host.size(),
                path.c_str());
  }
  std::printf("OK\n");
  return 0;
}
