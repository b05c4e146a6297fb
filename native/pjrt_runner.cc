// Standalone C++ PJRT serving runner — the native executor for the export's
// embedded StableHLO artifact (SURVEY §2.3: "StableHLO export + a C++
// xla::PjRtClient runner on TPU hosts", the role libtensorflow-JNI played
// for the reference's JVM serving path, TFModel.scala:245-292).
//
// Loads any PJRT C-API plugin (libtpu.so on TPU hosts; any GetPjrtApi()
// exporter works), compiles a StableHLO module produced by
// `checkpoint.export_model(..., model=..., embed=...)`, feeds raw host
// buffers, executes on device 0, and writes raw output buffers — no Python,
// no flax, no framework on the serving host.
//
// Usage:
//   pjrt_run --plugin /lib/libtpu.so --program apply_embedded.mlir \
//            --options compile_options.pb \
//            --input f32:128,28,28,1:images.bin [--input ...] \
//            [--create_option key=value ...] \
//            --out /tmp/pred
//
// Inputs are dense row-major host buffers; order must match the module's
// flattened argument order (the export descriptor records it).  Each output
// i is written to <out>.<i>.bin and described on stdout as
//   output <i>: type=<t> dims=<d0,d1,...> bytes=<n>
//
// Build (native.py does this on demand):
//   g++ -O3 -std=c++17 -I<tf-include> -o pjrt_run pjrt_runner.cc -ldl

#include <dlfcn.h>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <fstream>
#include <sstream>
#include <stdexcept>
#include <string>
#include <vector>

#include "tensorflow/compiler/xla/pjrt/c/pjrt_c_api.h"

namespace {

[[noreturn]] void Die(const std::string& msg) {
  std::fprintf(stderr, "pjrt_run: %s\n", msg.c_str());
  std::exit(1);
}

// Fatal-on-error checker: serving is a batch CLI, any API error is terminal.
void Check(const PJRT_Api* api, PJRT_Error* err, const char* what) {
  if (err == nullptr) return;
  PJRT_Error_Message_Args margs;
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.extension_start = nullptr;
  margs.error = err;
  api->PJRT_Error_Message(&margs);
  std::string text(margs.message, margs.message_size);
  PJRT_Error_Destroy_Args dargs;
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.extension_start = nullptr;
  dargs.error = err;
  api->PJRT_Error_Destroy(&dargs);
  Die(std::string(what) + ": " + text);
}

void Await(const PJRT_Api* api, PJRT_Event* event, const char* what) {
  if (event == nullptr) return;
  PJRT_Event_Await_Args aargs;
  aargs.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  aargs.extension_start = nullptr;
  aargs.event = event;
  Check(api, api->PJRT_Event_Await(&aargs), what);
  PJRT_Event_Destroy_Args dargs;
  dargs.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  dargs.extension_start = nullptr;
  dargs.event = event;
  Check(api, api->PJRT_Event_Destroy(&dargs), "event destroy");
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

DType ParseDType(const std::string& s) {
  if (s == "f32") return {PJRT_Buffer_Type_F32, 4};
  if (s == "f64") return {PJRT_Buffer_Type_F64, 8};
  if (s == "f16") return {PJRT_Buffer_Type_F16, 2};
  if (s == "bf16") return {PJRT_Buffer_Type_BF16, 2};
  if (s == "s8") return {PJRT_Buffer_Type_S8, 1};
  if (s == "s16") return {PJRT_Buffer_Type_S16, 2};
  if (s == "s32") return {PJRT_Buffer_Type_S32, 4};
  if (s == "s64") return {PJRT_Buffer_Type_S64, 8};
  if (s == "u8") return {PJRT_Buffer_Type_U8, 1};
  if (s == "u16") return {PJRT_Buffer_Type_U16, 2};
  if (s == "u32") return {PJRT_Buffer_Type_U32, 4};
  if (s == "u64") return {PJRT_Buffer_Type_U64, 8};
  if (s == "pred") return {PJRT_Buffer_Type_PRED, 1};
  Die("unknown dtype " + s + " (use f32/bf16/s32/u8/...)");
}

const char* TypeName(PJRT_Buffer_Type t) {
  switch (t) {
    case PJRT_Buffer_Type_F32: return "f32";
    case PJRT_Buffer_Type_F64: return "f64";
    case PJRT_Buffer_Type_F16: return "f16";
    case PJRT_Buffer_Type_BF16: return "bf16";
    case PJRT_Buffer_Type_S8: return "s8";
    case PJRT_Buffer_Type_S16: return "s16";
    case PJRT_Buffer_Type_S32: return "s32";
    case PJRT_Buffer_Type_S64: return "s64";
    case PJRT_Buffer_Type_U8: return "u8";
    case PJRT_Buffer_Type_U16: return "u16";
    case PJRT_Buffer_Type_U32: return "u32";
    case PJRT_Buffer_Type_U64: return "u64";
    case PJRT_Buffer_Type_PRED: return "pred";
    default: return "other";
  }
}

struct InputSpec {
  DType dtype;
  std::vector<int64_t> dims;
  std::string path;
};

// "f32:128,28,28,1:images.bin" -> InputSpec
InputSpec ParseInput(const std::string& arg) {
  InputSpec spec;
  size_t c1 = arg.find(':');
  size_t c2 = arg.find(':', c1 == std::string::npos ? 0 : c1 + 1);
  if (c1 == std::string::npos || c2 == std::string::npos)
    Die("--input wants dtype:d0,d1,...:path, got " + arg);
  spec.dtype = ParseDType(arg.substr(0, c1));
  std::string dims = arg.substr(c1 + 1, c2 - c1 - 1);
  std::stringstream ds(dims);
  std::string tok;
  while (std::getline(ds, tok, ',')) {
    if (!tok.empty()) spec.dims.push_back(std::stoll(tok));
  }
  spec.path = arg.substr(c2 + 1);
  return spec;
}

// Client-create option, parsed from a repeatable `--create_option key=value`
// flag, for plugins whose PJRT_Client_Create wants options (libtpu accepts
// a bare create).  Value typing: an explicit `int:`/`str:`/
// `bool:`/`float:` prefix wins; otherwise all-digits (optional sign) is
// kInt64, `true`/`false` is kBool, anything else a string.
struct CreateOption {
  std::string name;
  PJRT_NamedValue_Type type;
  std::string str;       // storage for kString
  int64_t i64 = 0;
  float f32 = 0.0f;
  bool b = false;
};

bool AllDigits(const std::string& s) {
  size_t start = (!s.empty() && (s[0] == '-' || s[0] == '+')) ? 1 : 0;
  if (start >= s.size()) return false;
  for (size_t i = start; i < s.size(); ++i)
    if (s[i] < '0' || s[i] > '9') return false;
  return true;
}

int64_t ParseI64OrDie(const std::string& val, const std::string& arg) {
  try {
    size_t used = 0;
    int64_t v = std::stoll(val, &used);
    if (used != val.size()) throw std::invalid_argument(val);
    return v;
  } catch (const std::exception&) {
    Die("--create_option int value '" + val + "' is not a valid int64 in " +
        arg);
  }
}

float ParseF32OrDie(const std::string& val, const std::string& arg) {
  try {
    size_t used = 0;
    float v = std::stof(val, &used);
    if (used != val.size()) throw std::invalid_argument(val);
    return v;
  } catch (const std::exception&) {
    Die("--create_option float value '" + val + "' is not a valid float in " +
        arg);
  }
}

CreateOption ParseCreateOption(const std::string& arg) {
  size_t eq = arg.find('=');
  if (eq == std::string::npos || eq == 0)
    Die("--create_option wants key=value, got " + arg);
  CreateOption opt;
  opt.name = arg.substr(0, eq);
  std::string val = arg.substr(eq + 1);
  auto strip = [&](const char* prefix) {
    size_t n = std::strlen(prefix);
    if (val.compare(0, n, prefix) == 0) { val = val.substr(n); return true; }
    return false;
  };
  if (strip("str:")) {
    opt.type = PJRT_NamedValue_kString; opt.str = val;
  } else if (strip("int:")) {
    opt.type = PJRT_NamedValue_kInt64; opt.i64 = ParseI64OrDie(val, arg);
  } else if (strip("bool:")) {
    // explicit prefix promises typed parsing: reject anything but the
    // canonical literals rather than coercing "True"/"yes" to false.
    if (val == "true" || val == "1") { opt.b = true; }
    else if (val == "false" || val == "0") { opt.b = false; }
    else Die("--create_option bool value '" + val +
             "' must be true/false/1/0 in " + arg);
    opt.type = PJRT_NamedValue_kBool;
  } else if (strip("float:")) {
    opt.type = PJRT_NamedValue_kFloat; opt.f32 = ParseF32OrDie(val, arg);
  } else if (AllDigits(val)) {
    opt.type = PJRT_NamedValue_kInt64; opt.i64 = ParseI64OrDie(val, arg);
  } else if (val == "true" || val == "false") {
    opt.type = PJRT_NamedValue_kBool; opt.b = (val == "true");
  } else {
    opt.type = PJRT_NamedValue_kString; opt.str = val;
  }
  return opt;
}

// Build the PJRT_NamedValue array over stable CreateOption storage.
std::vector<PJRT_NamedValue> ToNamedValues(
    const std::vector<CreateOption>& opts) {
  std::vector<PJRT_NamedValue> nvs;
  nvs.reserve(opts.size());
  for (const CreateOption& o : opts) {
    PJRT_NamedValue nv;
    std::memset(&nv, 0, sizeof(nv));
    nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
    nv.extension_start = nullptr;
    nv.name = o.name.c_str();
    nv.name_size = o.name.size();
    nv.type = o.type;
    switch (o.type) {
      case PJRT_NamedValue_kString:
        nv.string_value = o.str.c_str();
        nv.value_size = o.str.size();
        break;
      case PJRT_NamedValue_kInt64:
        nv.int64_value = o.i64;
        nv.value_size = 1;
        break;
      case PJRT_NamedValue_kFloat:
        nv.float_value = o.f32;
        nv.value_size = 1;
        break;
      case PJRT_NamedValue_kBool:
        nv.bool_value = o.b;
        nv.value_size = 1;
        break;
      default:
        Die("unsupported create-option type");
    }
    nvs.push_back(nv);
  }
  return nvs;
}

}  // namespace

int main(int argc, char** argv) {
  std::string plugin_path, program_path, options_path, out_prefix = "out";
  std::vector<InputSpec> inputs;
  std::vector<CreateOption> create_opts;
  // --batches N: each --input file carries N concatenated buffers of the
  // declared shape; the module compiles ONCE and executes N times (the
  // whole point of a serving runner — compilation is minutes on TPU,
  // execution is milliseconds).  Outputs: out.<b>.<i>.bin when N > 1,
  // the original out.<i>.bin when N == 1.
  size_t batches = 1;
  for (int i = 1; i < argc; ++i) {
    std::string a = argv[i];
    auto next = [&](const char* flag) -> std::string {
      if (i + 1 >= argc) Die(std::string(flag) + " needs a value");
      return argv[++i];
    };
    if (a == "--plugin") plugin_path = next("--plugin");
    else if (a == "--program") program_path = next("--program");
    else if (a == "--options") options_path = next("--options");
    else if (a == "--create_option")
      create_opts.push_back(ParseCreateOption(next("--create_option")));
    else if (a == "--input") inputs.push_back(ParseInput(next("--input")));
    else if (a == "--out") out_prefix = next("--out");
    else if (a == "--batches") {
      batches = static_cast<size_t>(std::stoul(next("--batches")));
      if (batches == 0) Die("--batches must be >= 1");
    }
    else Die("unknown flag " + a);
  }
  if (plugin_path.empty() || program_path.empty())
    Die("--plugin and --program are required");

  // 1. Load the plugin and fetch its API table.
  void* handle = dlopen(plugin_path.c_str(), RTLD_NOW | RTLD_LOCAL);
  if (!handle) Die(std::string("dlopen failed: ") + dlerror());
  using GetApiFn = const PJRT_Api* (*)();
  auto get_api = reinterpret_cast<GetApiFn>(dlsym(handle, "GetPjrtApi"));
  if (!get_api) Die("plugin exports no GetPjrtApi symbol");
  const PJRT_Api* api = get_api();
  if (!api) Die("GetPjrtApi returned null");

  PJRT_Plugin_Initialize_Args init_args;
  init_args.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
  init_args.extension_start = nullptr;
  Check(api, api->PJRT_Plugin_Initialize(&init_args), "plugin init");

  // 2. Create the client and pick device 0.
  std::vector<PJRT_NamedValue> nvs = ToNamedValues(create_opts);
  PJRT_Client_Create_Args cargs;
  std::memset(&cargs, 0, sizeof(cargs));
  cargs.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  cargs.create_options = nvs.empty() ? nullptr : nvs.data();
  cargs.num_options = nvs.size();
  Check(api, api->PJRT_Client_Create(&cargs), "client create");
  PJRT_Client* client = cargs.client;

  PJRT_Client_AddressableDevices_Args dargs;
  dargs.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  dargs.extension_start = nullptr;
  dargs.client = client;
  Check(api, api->PJRT_Client_AddressableDevices(&dargs), "devices");
  if (dargs.num_addressable_devices == 0) Die("no addressable devices");
  PJRT_Device* device = dargs.addressable_devices[0];

  // 3. Compile the StableHLO module.
  std::string code = ReadFile(program_path);
  std::string options =
      options_path.empty() ? std::string() : ReadFile(options_path);
  PJRT_Program program;
  program.struct_size = PJRT_Program_STRUCT_SIZE;
  program.extension_start = nullptr;
  program.code = code.data();
  program.code_size = code.size();
  static const char kFormat[] = "mlir";
  program.format = kFormat;
  program.format_size = sizeof(kFormat) - 1;

  PJRT_Client_Compile_Args comp;
  comp.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  comp.extension_start = nullptr;
  comp.client = client;
  comp.program = &program;
  comp.compile_options = options.data();
  comp.compile_options_size = options.size();
  Check(api, api->PJRT_Client_Compile(&comp), "compile");
  PJRT_LoadedExecutable* exec = comp.executable;

  // 4. Read the input files once; each holds `batches` concatenated
  // buffers of the declared per-batch shape.
  std::vector<std::string> host_data(inputs.size());
  std::vector<size_t> batch_bytes(inputs.size());
  for (size_t i = 0; i < inputs.size(); ++i) {
    const InputSpec& spec = inputs[i];
    host_data[i] = ReadFile(spec.path);
    size_t want = spec.dtype.bytes;
    for (int64_t d : spec.dims) want *= static_cast<size_t>(d);
    batch_bytes[i] = want;
    if (host_data[i].size() != want * batches) {
      std::ostringstream ss;
      ss << "input " << i << " (" << spec.path << "): file has "
         << host_data[i].size() << " bytes, dims need " << want << " x "
         << batches << " batches";
      Die(ss.str());
    }
  }

  // 5. Execute (single device).
  PJRT_Executable_NumOutputs_Args nargs;
  nargs.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  nargs.extension_start = nullptr;
  PJRT_LoadedExecutable_GetExecutable_Args geargs;
  geargs.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  geargs.extension_start = nullptr;
  geargs.loaded_executable = exec;
  Check(api, api->PJRT_LoadedExecutable_GetExecutable(&geargs), "get exec");
  nargs.executable = geargs.executable;
  Check(api, api->PJRT_Executable_NumOutputs(&nargs), "num outputs");
  size_t num_outputs = nargs.num_outputs;

  for (size_t b = 0; b < batches; ++b) {
    // stage this batch's slice of every input
    std::vector<PJRT_Buffer*> arg_buffers(inputs.size());
    for (size_t i = 0; i < inputs.size(); ++i) {
      const InputSpec& spec = inputs[i];
      PJRT_Client_BufferFromHostBuffer_Args bargs;
      std::memset(&bargs, 0, sizeof(bargs));
      bargs.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
      bargs.client = client;
      bargs.data = host_data[i].data() + b * batch_bytes[i];
      bargs.type = spec.dtype.type;
      bargs.dims = spec.dims.data();
      bargs.num_dims = spec.dims.size();
      bargs.host_buffer_semantics =
          PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
      bargs.device = device;
      Check(api, api->PJRT_Client_BufferFromHostBuffer(&bargs), "h2d");
      Await(api, bargs.done_with_host_buffer, "h2d done");
      arg_buffers[i] = bargs.buffer;
    }

    std::vector<PJRT_Buffer*> out_row(num_outputs, nullptr);
    PJRT_Buffer** out_lists[1] = {out_row.data()};
    PJRT_Buffer* const* arg_lists[1] = {arg_buffers.data()};
    PJRT_Event* done_events[1] = {nullptr};

    PJRT_ExecuteOptions opts;
    std::memset(&opts, 0, sizeof(opts));
    opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

    PJRT_LoadedExecutable_Execute_Args eargs;
    std::memset(&eargs, 0, sizeof(eargs));
    eargs.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
    eargs.executable = exec;
    eargs.options = &opts;
    eargs.argument_lists = arg_lists;
    eargs.num_devices = 1;
    eargs.num_args = arg_buffers.size();
    eargs.output_lists = out_lists;
    eargs.device_complete_events = done_events;
    Check(api, api->PJRT_LoadedExecutable_Execute(&eargs), "execute");
    Await(api, done_events[0], "execute done");

    // copy every output back; <out>.<i>.bin (one batch, back-compat) or
    // <out>.<b>.<i>.bin (batched)
    for (size_t i = 0; i < num_outputs; ++i) {
      PJRT_Buffer* buf = out_row[i];

      PJRT_Buffer_ElementType_Args targs;
      targs.struct_size = PJRT_Buffer_ElementType_Args_STRUCT_SIZE;
      targs.extension_start = nullptr;
      targs.buffer = buf;
      Check(api, api->PJRT_Buffer_ElementType(&targs), "output dtype");

      PJRT_Buffer_Dimensions_Args dims_args;
      dims_args.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
      dims_args.extension_start = nullptr;
      dims_args.buffer = buf;
      Check(api, api->PJRT_Buffer_Dimensions(&dims_args), "output dims");

      PJRT_Buffer_ToHostBuffer_Args hargs;
      std::memset(&hargs, 0, sizeof(hargs));
      hargs.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
      hargs.src = buf;
      Check(api, api->PJRT_Buffer_ToHostBuffer(&hargs), "d2h size");
      std::string out(hargs.dst_size, '\0');
      hargs.dst = out.data();
      Check(api, api->PJRT_Buffer_ToHostBuffer(&hargs), "d2h");
      Await(api, hargs.event, "d2h done");

      std::string path = batches == 1
          ? out_prefix + "." + std::to_string(i) + ".bin"
          : out_prefix + "." + std::to_string(b) + "." +
                std::to_string(i) + ".bin";
      std::ofstream f(path, std::ios::binary);
      f.write(out.data(), static_cast<std::streamsize>(out.size()));
      if (!f) Die("cannot write " + path);

      std::ostringstream dimstr;
      for (size_t d = 0; d < dims_args.num_dims; ++d) {
        if (d) dimstr << ",";
        dimstr << dims_args.dims[d];
      }
      std::printf("output %zu.%zu: type=%s dims=%s bytes=%zu file=%s\n", b,
                  i, TypeName(targs.type), dimstr.str().c_str(), out.size(),
                  path.c_str());

      PJRT_Buffer_Destroy_Args bd;
      bd.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
      bd.extension_start = nullptr;
      bd.buffer = buf;
      Check(api, api->PJRT_Buffer_Destroy(&bd), "output destroy");
    }

    for (PJRT_Buffer* buf : arg_buffers) {
      PJRT_Buffer_Destroy_Args bd;
      bd.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
      bd.extension_start = nullptr;
      bd.buffer = buf;
      Check(api, api->PJRT_Buffer_Destroy(&bd), "arg destroy");
    }
  }
  PJRT_LoadedExecutable_Destroy_Args ed;
  ed.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
  ed.extension_start = nullptr;
  ed.executable = exec;
  Check(api, api->PJRT_LoadedExecutable_Destroy(&ed), "exec destroy");
  PJRT_Client_Destroy_Args cd;
  cd.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
  cd.extension_start = nullptr;
  cd.client = client;
  Check(api, api->PJRT_Client_Destroy(&cd), "client destroy");
  return 0;
}
