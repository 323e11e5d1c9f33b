// shlo_runner: framework-free PJRT consumer of exported StableHLO
// artifacts (docs/frontends.md §2; reference: cpp-package consumes the
// C ABI directly, SURVEY.md §2.3).
//
// Loads a PJRT C-API plugin (.so exporting GetPjrtApi), compiles the
// MLIR module emitted by mxnet_tpu.deploy.export_stablehlo(...,
// emit_text=True), feeds raw binary input files, runs one execution on
// the first addressable device, and writes each output as raw bytes to
// <out_prefix>.<i>.bin plus a one-line "<dtype> <dims...>" header to
// <out_prefix>.<i>.meta.  No Python, no framework — the deployment
// boundary is the compiled program.
//
//   shlo_runner <plugin.so> <module.mlir> <compile_options.pb|-> \
//               <out_prefix> [--opt name=i:42 | --opt name=s:text ...] \
//               [dtype@d0xd1x...@file.bin ...]
//
// --opt passes PJRT_NamedValue client-create options (some plugins
// require platform-specific ones).
//
// Build: ci/runtime_functions.sh native_build (g++ -ldl; the PJRT C API
// header comes from the bundled XLA headers).
#include <dlfcn.h>

#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <fstream>
#include <sstream>
#include <string>
#include <vector>

#include "xla/pjrt/c/pjrt_c_api.h"

namespace {

[[noreturn]] void Die(const std::string& msg) {
  std::fprintf(stderr, "shlo_runner: %s\n", msg.c_str());
  std::exit(1);
}

const PJRT_Api* g_api = nullptr;

void Check(PJRT_Error* err, const char* what) {
  if (err == nullptr) return;
  PJRT_Error_Message_Args margs;
  std::memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = err;
  g_api->PJRT_Error_Message(&margs);
  std::string msg(margs.message, margs.message_size);
  PJRT_Error_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = err;
  g_api->PJRT_Error_Destroy(&dargs);
  Die(std::string(what) + ": " + msg);
}

std::string ReadFile(const std::string& path) {
  std::ifstream f(path, std::ios::binary);
  if (!f) Die("cannot read " + path);
  std::ostringstream ss;
  ss << f.rdbuf();
  return ss.str();
}

struct DType {
  PJRT_Buffer_Type type;
  size_t bytes;
};

int64_t ParseInt(const std::string& s, const std::string& what) {
  try {
    size_t pos = 0;
    int64_t v = std::stoll(s, &pos);
    if (pos != s.size()) throw std::invalid_argument(s);
    return v;
  } catch (const std::exception&) {
    Die("malformed integer '" + s + "' in " + what);
  }
}

DType ParseDType(const std::string& s) {
  if (s == "f32") return {PJRT_Buffer_Type_F32, 4};
  if (s == "f64") return {PJRT_Buffer_Type_F64, 8};
  if (s == "f16") return {PJRT_Buffer_Type_F16, 2};
  if (s == "bf16") return {PJRT_Buffer_Type_BF16, 2};
  if (s == "i8") return {PJRT_Buffer_Type_S8, 1};
  if (s == "u8") return {PJRT_Buffer_Type_U8, 1};
  if (s == "i32") return {PJRT_Buffer_Type_S32, 4};
  if (s == "i64") return {PJRT_Buffer_Type_S64, 8};
  if (s == "pred") return {PJRT_Buffer_Type_PRED, 1};
  Die("unsupported dtype " + s);
}

const char* TypeName(PJRT_Buffer_Type t) {
  switch (t) {
    case PJRT_Buffer_Type_F32: return "f32";
    case PJRT_Buffer_Type_F64: return "f64";
    case PJRT_Buffer_Type_F16: return "f16";
    case PJRT_Buffer_Type_BF16: return "bf16";
    case PJRT_Buffer_Type_S8: return "i8";
    case PJRT_Buffer_Type_U8: return "u8";
    case PJRT_Buffer_Type_S32: return "i32";
    case PJRT_Buffer_Type_S64: return "i64";
    case PJRT_Buffer_Type_PRED: return "pred";
    default: return "unknown";
  }
}

}  // namespace

int main(int argc, char** argv) {
  if (argc < 5) {
    std::fprintf(stderr,
                 "usage: %s <plugin.so> <module.mlir> "
                 "<compile_options.pb|-> <out_prefix> "
                 "[dtype@d0xd1@file.bin ...]\n",
                 argv[0]);
    return 2;
  }
  const char* plugin_path = argv[1];
  const std::string module = ReadFile(argv[2]);
  std::string options;
  if (std::strcmp(argv[3], "-") != 0) options = ReadFile(argv[3]);
  const std::string out_prefix = argv[4];

  void* lib = dlopen(plugin_path, RTLD_NOW | RTLD_LOCAL);
  if (lib == nullptr) Die(std::string("dlopen: ") + dlerror());
  using GetApiFn = const PJRT_Api* (*)();
  auto get_api = reinterpret_cast<GetApiFn>(dlsym(lib, "GetPjrtApi"));
  if (get_api == nullptr) Die("plugin has no GetPjrtApi symbol");
  g_api = get_api();
  if (g_api == nullptr) Die("GetPjrtApi returned null");
  std::fprintf(stderr, "shlo_runner: plugin PJRT API v%d.%d\n",
               g_api->pjrt_api_version.major_version,
               g_api->pjrt_api_version.minor_version);

  {
    PJRT_Plugin_Initialize_Args init;
    std::memset(&init, 0, sizeof(init));
    init.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    Check(g_api->PJRT_Plugin_Initialize(&init), "Plugin_Initialize");
  }

  // client-create options from --opt args (strings kept alive in vectors)
  std::vector<std::string> opt_names, opt_strs;
  std::vector<int64_t> opt_ints;
  std::vector<std::pair<size_t, char>> opt_kinds;  // (index, 'i'|'s')
  std::vector<int> input_argv;
  for (int i = 5; i < argc; ++i) {
    if (std::strcmp(argv[i], "--opt") == 0 && i + 1 < argc) {
      std::string kv(argv[++i]);
      size_t eq = kv.find('=');
      if (eq == std::string::npos || kv.size() < eq + 3 ||
          kv[eq + 2] != ':' || (kv[eq + 1] != 'i' && kv[eq + 1] != 's'))
        Die("bad --opt " + kv + " (want name=i:42 or name=s:text)");
      opt_names.push_back(kv.substr(0, eq));
      if (kv[eq + 1] == 'i') {
        opt_kinds.emplace_back(opt_ints.size(), 'i');
        opt_ints.push_back(ParseInt(kv.substr(eq + 3), "--opt " + kv));
      } else {
        opt_kinds.emplace_back(opt_strs.size(), 's');
        opt_strs.push_back(kv.substr(eq + 3));
      }
    } else {
      input_argv.push_back(i);
    }
  }
  std::vector<PJRT_NamedValue> named(opt_names.size());
  for (size_t i = 0; i < opt_names.size(); ++i) {
    std::memset(&named[i], 0, sizeof(named[i]));
    named[i].struct_size = PJRT_NamedValue_STRUCT_SIZE;
    named[i].name = opt_names[i].c_str();
    named[i].name_size = opt_names[i].size();
    if (opt_kinds[i].second == 'i') {
      named[i].type = PJRT_NamedValue_kInt64;
      named[i].int64_value = opt_ints[opt_kinds[i].first];
      named[i].value_size = 1;
    } else {
      const std::string& s = opt_strs[opt_kinds[i].first];
      named[i].type = PJRT_NamedValue_kString;
      named[i].string_value = s.c_str();
      named[i].value_size = s.size();
    }
  }

  PJRT_Client_Create_Args cargs;
  std::memset(&cargs, 0, sizeof(cargs));
  cargs.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  cargs.create_options = named.data();
  cargs.num_options = named.size();
  Check(g_api->PJRT_Client_Create(&cargs), "Client_Create");
  PJRT_Client* client = cargs.client;

  PJRT_Client_AddressableDevices_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  dargs.client = client;
  Check(g_api->PJRT_Client_AddressableDevices(&dargs),
        "AddressableDevices");
  if (dargs.num_addressable_devices == 0) Die("no addressable devices");
  PJRT_Device* device = dargs.addressable_devices[0];

  // ------------------------------------------------------------- compile
  PJRT_Program program;
  std::memset(&program, 0, sizeof(program));
  program.struct_size = PJRT_Program_STRUCT_SIZE;
  program.code = const_cast<char*>(module.data());
  program.code_size = module.size();
  static const char kFormat[] = "mlir";
  program.format = kFormat;
  program.format_size = sizeof(kFormat) - 1;

  PJRT_Client_Compile_Args comp;
  std::memset(&comp, 0, sizeof(comp));
  comp.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  comp.client = client;
  comp.program = &program;
  comp.compile_options = options.data();
  comp.compile_options_size = options.size();
  Check(g_api->PJRT_Client_Compile(&comp), "Client_Compile");
  PJRT_LoadedExecutable* exec = comp.executable;

  PJRT_LoadedExecutable_GetExecutable_Args gargs;
  std::memset(&gargs, 0, sizeof(gargs));
  gargs.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  gargs.loaded_executable = exec;
  Check(g_api->PJRT_LoadedExecutable_GetExecutable(&gargs),
        "GetExecutable");
  PJRT_Executable_NumOutputs_Args nargs;
  std::memset(&nargs, 0, sizeof(nargs));
  nargs.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  nargs.executable = gargs.executable;
  Check(g_api->PJRT_Executable_NumOutputs(&nargs), "NumOutputs");
  const size_t num_outputs = nargs.num_outputs;

  // ------------------------------------------------- host->device inputs
  std::vector<PJRT_Buffer*> inputs;
  std::vector<std::string> input_bytes;  // keep host data alive
  for (int ia : input_argv) {
    std::string spec(argv[ia]);
    size_t a = spec.find('@');
    size_t b = spec.find('@', a + 1);
    if (a == std::string::npos || b == std::string::npos)
      Die("bad input spec " + spec + " (want dtype@d0xd1@file)");
    DType dt = ParseDType(spec.substr(0, a));
    std::vector<int64_t> dims;
    std::string shape = spec.substr(a + 1, b - a - 1);
    if (shape != "scalar") {
      std::stringstream ss(shape);
      std::string tok;
      while (std::getline(ss, tok, 'x'))
        dims.push_back(ParseInt(tok, "input spec " + spec));
    }
    input_bytes.push_back(ReadFile(spec.substr(b + 1)));
    size_t want = dt.bytes;
    for (int64_t d : dims) want *= static_cast<size_t>(d);
    if (input_bytes.back().size() != want)
      Die("input " + spec + ": file has " +
          std::to_string(input_bytes.back().size()) + " bytes, want " +
          std::to_string(want));

    PJRT_Client_BufferFromHostBuffer_Args bargs;
    std::memset(&bargs, 0, sizeof(bargs));
    bargs.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    bargs.client = client;
    bargs.data = input_bytes.back().data();
    bargs.type = dt.type;
    bargs.dims = dims.data();
    bargs.num_dims = dims.size();
    bargs.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    bargs.device = device;
    Check(g_api->PJRT_Client_BufferFromHostBuffer(&bargs),
          "BufferFromHostBuffer");
    if (bargs.done_with_host_buffer != nullptr) {
      PJRT_Event_Await_Args eargs;
      std::memset(&eargs, 0, sizeof(eargs));
      eargs.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
      eargs.event = bargs.done_with_host_buffer;
      Check(g_api->PJRT_Event_Await(&eargs), "Event_Await(h2d)");
      PJRT_Event_Destroy_Args edargs;
      std::memset(&edargs, 0, sizeof(edargs));
      edargs.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
      edargs.event = bargs.done_with_host_buffer;
      g_api->PJRT_Event_Destroy(&edargs);
    }
    inputs.push_back(bargs.buffer);
  }

  // -------------------------------------------------------------- execute
  PJRT_ExecuteOptions opts;
  std::memset(&opts, 0, sizeof(opts));
  opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

  std::vector<PJRT_Buffer*> outputs(num_outputs, nullptr);
  PJRT_Buffer** output_list = outputs.data();
  PJRT_Buffer* const* arg_list = inputs.data();
  PJRT_Event* done = nullptr;

  PJRT_LoadedExecutable_Execute_Args eargs;
  std::memset(&eargs, 0, sizeof(eargs));
  eargs.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  eargs.executable = exec;
  eargs.options = &opts;
  eargs.argument_lists = &arg_list;
  eargs.num_devices = 1;
  eargs.num_args = inputs.size();
  eargs.output_lists = &output_list;
  eargs.device_complete_events = &done;
  eargs.execute_device = device;
  Check(g_api->PJRT_LoadedExecutable_Execute(&eargs), "Execute");
  if (done != nullptr) {
    PJRT_Event_Await_Args aw;
    std::memset(&aw, 0, sizeof(aw));
    aw.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
    aw.event = done;
    Check(g_api->PJRT_Event_Await(&aw), "Event_Await(execute)");
    PJRT_Event_Destroy_Args ed;
    std::memset(&ed, 0, sizeof(ed));
    ed.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
    ed.event = done;
    g_api->PJRT_Event_Destroy(&ed);
  }

  // ------------------------------------------------ device->host outputs
  for (size_t i = 0; i < num_outputs; ++i) {
    PJRT_Buffer_ElementType_Args targs;
    std::memset(&targs, 0, sizeof(targs));
    targs.struct_size = PJRT_Buffer_ElementType_Args_STRUCT_SIZE;
    targs.buffer = outputs[i];
    Check(g_api->PJRT_Buffer_ElementType(&targs), "ElementType");
    PJRT_Buffer_Dimensions_Args shargs;
    std::memset(&shargs, 0, sizeof(shargs));
    shargs.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
    shargs.buffer = outputs[i];
    Check(g_api->PJRT_Buffer_Dimensions(&shargs), "Dimensions");

    PJRT_Buffer_ToHostBuffer_Args hargs;
    std::memset(&hargs, 0, sizeof(hargs));
    hargs.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    hargs.src = outputs[i];
    Check(g_api->PJRT_Buffer_ToHostBuffer(&hargs), "ToHostBuffer(size)");
    std::vector<char> host(hargs.dst_size);
    hargs.dst = host.data();
    Check(g_api->PJRT_Buffer_ToHostBuffer(&hargs), "ToHostBuffer");
    if (hargs.event != nullptr) {
      PJRT_Event_Await_Args aw;
      std::memset(&aw, 0, sizeof(aw));
      aw.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
      aw.event = hargs.event;
      Check(g_api->PJRT_Event_Await(&aw), "Event_Await(d2h)");
      PJRT_Event_Destroy_Args ed;
      std::memset(&ed, 0, sizeof(ed));
      ed.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
      ed.event = hargs.event;
      g_api->PJRT_Event_Destroy(&ed);
    }

    const std::string stem = out_prefix + "." + std::to_string(i);
    std::ofstream ob(stem + ".bin", std::ios::binary);
    ob.write(host.data(), static_cast<std::streamsize>(host.size()));
    ob.close();
    if (!ob) Die("failed writing " + stem + ".bin");
    std::ofstream om(stem + ".meta");
    om << TypeName(targs.type);
    for (size_t d = 0; d < shargs.num_dims; ++d)
      om << " " << shargs.dims[d];
    om << "\n";
    om.close();
    if (!om) Die("failed writing " + stem + ".meta");
  }
  std::fprintf(stderr, "shlo_runner: wrote %zu output(s)\n", num_outputs);
  return 0;
}
