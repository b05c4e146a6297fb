// Mock PJRT plugin — a test double exporting GetPjrtApi() so the native
// serving runner (pjrt_runner.cc) can EXECUTE everywhere, not just compile
// (the C++ execute path had never run; no real CPU PJRT
// plugin ships in this image and a TPU plugin needs hardware).
//
// Implements exactly the C-API subset the runner drives — error/event
// plumbing, client + device enumeration, compile, host<->device buffers,
// execute — with deterministic test-double semantics the test can assert:
//
// - compile: dumps the received program bytes to $TFOS_MOCK_PROGRAM_DUMP
//   (so the test can verify the exported StableHLO reached the plugin
//   intact) and reads the output signature from $TFOS_MOCK_OUTPUTS
//   ("f32:4;f32:4,4" = two outputs, shapes (4,) and (4,4)).
// - execute: every output element = (sum of all staged argument bytes
//   modulo 1000003) + output_index, as f32/s32.  The checksum covers the
//   exact bytes the runner staged for THIS batch, so a --batches slicing
//   bug or an argument-marshalling bug changes the value.
//
// Build: g++ -O3 -std=c++17 -shared -fPIC -I<tf-include> \
//            -o libmock_pjrt_plugin.so mock_pjrt_plugin.cc

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

// Opaque API types get concrete test-double definitions here (the header
// only forward-declares them).
struct PJRT_Error {
  std::string message;
};
struct PJRT_Event {};  // every mock event is born ready
struct PJRT_Device {
  int id;
};
struct PJRT_Client {
  PJRT_Device device{0};
  PJRT_Device* devices[1];
};
struct PJRT_Buffer {
  PJRT_Buffer_Type type;
  std::vector<int64_t> dims;
  std::string data;
};
struct OutputSpec {
  PJRT_Buffer_Type type;
  size_t elem_bytes;
  std::vector<int64_t> dims;
};
struct PJRT_Executable {
  std::vector<OutputSpec> outputs;
};
struct PJRT_LoadedExecutable {
  PJRT_Executable exec;
};
struct PJRT_TopologyDescription {};

namespace {

PJRT_Error* Err(const std::string& msg) { return new PJRT_Error{msg}; }

PJRT_Error* ErrorMessage(PJRT_Error_Message_Args* args) {
  args->message = args->error->message.c_str();
  args->message_size = args->error->message.size();
  return nullptr;
}

void ErrorDestroy(PJRT_Error_Destroy_Args* args) { delete args->error; }

PJRT_Error* ErrorCode(PJRT_Error_GetCode_Args* args) {
  args->code = PJRT_Error_Code_INTERNAL;
  return nullptr;
}

PJRT_Error* EventAwait(PJRT_Event_Await_Args*) { return nullptr; }
PJRT_Error* EventDestroy(PJRT_Event_Destroy_Args* args) {
  delete args->event;
  return nullptr;
}
PJRT_Error* EventIsReady(PJRT_Event_IsReady_Args* args) {
  args->is_ready = true;
  return nullptr;
}

PJRT_Error* PluginInitialize(PJRT_Plugin_Initialize_Args*) { return nullptr; }

PJRT_Error* ClientCreate(PJRT_Client_Create_Args* args) {
  // When $TFOS_MOCK_OPTIONS_DUMP is set, record the NamedValue create
  // options the caller passed, one `name=typed-value` line each — lets the
  // suite assert the runner's --create_option marshalling end-to-end
  // (some real plugins REQUIRE such options and reject a bare create).
  const char* odump = std::getenv("TFOS_MOCK_OPTIONS_DUMP");
  if (odump != nullptr) {
    std::ofstream f(odump);
    for (size_t i = 0; i < args->num_options; ++i) {
      const PJRT_NamedValue& nv = args->create_options[i];
      f << std::string(nv.name, nv.name_size) << "=";
      switch (nv.type) {
        case PJRT_NamedValue_kString:
          f << "str:" << std::string(nv.string_value, nv.value_size); break;
        case PJRT_NamedValue_kInt64: f << "int:" << nv.int64_value; break;
        case PJRT_NamedValue_kFloat: f << "float:" << nv.float_value; break;
        case PJRT_NamedValue_kBool:
          f << "bool:" << (nv.bool_value ? "true" : "false"); break;
        default: f << "other"; break;
      }
      f << "\n";
    }
  }
  auto* client = new PJRT_Client;
  client->devices[0] = &client->device;
  args->client = client;
  return nullptr;
}

PJRT_Error* ClientDestroy(PJRT_Client_Destroy_Args* args) {
  delete args->client;
  return nullptr;
}

PJRT_Error* AddressableDevices(PJRT_Client_AddressableDevices_Args* args) {
  args->addressable_devices = args->client->devices;
  args->num_addressable_devices = 1;
  return nullptr;
}

// "f32:4;f32:4,4" -> OutputSpecs
PJRT_Error* ParseOutputs(const char* spec, std::vector<OutputSpec>* out) {
  std::stringstream ss(spec);
  std::string item;
  while (std::getline(ss, item, ';')) {
    if (item.empty()) continue;
    size_t colon = item.find(':');
    if (colon == std::string::npos)
      return Err("TFOS_MOCK_OUTPUTS wants dtype:d0,d1;... got " + item);
    std::string ty = item.substr(0, colon);
    OutputSpec os;
    if (ty == "f32") {
      os.type = PJRT_Buffer_Type_F32;
      os.elem_bytes = 4;
    } else if (ty == "s32") {
      os.type = PJRT_Buffer_Type_S32;
      os.elem_bytes = 4;
    } else {
      return Err("mock supports f32/s32 outputs, got " + ty);
    }
    std::stringstream ds(item.substr(colon + 1));
    std::string tok;
    while (std::getline(ds, tok, ',')) {
      if (tok.empty()) continue;
      // report malformed dims as a PJRT_Error, never an exception across
      // the C-API boundary (which would abort the runner process)
      try {
        size_t used = 0;
        int64_t dim = std::stoll(tok, &used);
        if (used != tok.size()) throw std::invalid_argument(tok);
        os.dims.push_back(dim);
      } catch (const std::exception&) {
        return Err("TFOS_MOCK_OUTPUTS has non-numeric dim " + tok + " in " +
                   item);
      }
    }
    out->push_back(os);
  }
  if (out->empty()) return Err("TFOS_MOCK_OUTPUTS parsed to zero outputs");
  return nullptr;
}

PJRT_Error* ClientCompile(PJRT_Client_Compile_Args* args) {
  const char* dump = std::getenv("TFOS_MOCK_PROGRAM_DUMP");
  if (dump != nullptr && *dump != '\0') {
    std::ofstream f(dump, std::ios::binary);
    f.write(args->program->code,
            static_cast<std::streamsize>(args->program->code_size));
    if (!f) return Err(std::string("cannot dump program to ") + dump);
  }
  const char* spec = std::getenv("TFOS_MOCK_OUTPUTS");
  if (spec == nullptr || *spec == '\0')
    return Err("TFOS_MOCK_OUTPUTS not set (mock plugin needs the output "
               "signature)");
  auto* loaded = new PJRT_LoadedExecutable;
  if (PJRT_Error* e = ParseOutputs(spec, &loaded->exec.outputs)) {
    delete loaded;
    return e;
  }
  args->executable = loaded;
  return nullptr;
}

PJRT_Error* GetExecutable(PJRT_LoadedExecutable_GetExecutable_Args* args) {
  args->executable = &args->loaded_executable->exec;
  return nullptr;
}

PJRT_Error* NumOutputs(PJRT_Executable_NumOutputs_Args* args) {
  args->num_outputs = args->executable->outputs.size();
  return nullptr;
}

PJRT_Error* LoadedExecutableDestroy(
    PJRT_LoadedExecutable_Destroy_Args* args) {
  delete args->executable;
  return nullptr;
}

PJRT_Error* BufferFromHostBuffer(
    PJRT_Client_BufferFromHostBuffer_Args* args) {
  if (args->num_byte_strides != 0)
    return Err("mock plugin only supports dense row-major host buffers");
  auto* buf = new PJRT_Buffer;
  buf->type = args->type;
  buf->dims.assign(args->dims, args->dims + args->num_dims);
  size_t elem = 1;
  switch (args->type) {
    case PJRT_Buffer_Type_F64:
    case PJRT_Buffer_Type_S64:
    case PJRT_Buffer_Type_U64:
      elem = 8;
      break;
    case PJRT_Buffer_Type_F32:
    case PJRT_Buffer_Type_S32:
    case PJRT_Buffer_Type_U32:
      elem = 4;
      break;
    case PJRT_Buffer_Type_F16:
    case PJRT_Buffer_Type_BF16:
    case PJRT_Buffer_Type_S16:
    case PJRT_Buffer_Type_U16:
      elem = 2;
      break;
    default:
      elem = 1;
  }
  size_t total = elem;
  for (int64_t d : buf->dims) total *= static_cast<size_t>(d);
  buf->data.assign(static_cast<const char*>(args->data), total);
  args->buffer = buf;
  args->done_with_host_buffer = new PJRT_Event;
  return nullptr;
}

PJRT_Error* BufferElementType(PJRT_Buffer_ElementType_Args* args) {
  args->type = args->buffer->type;
  return nullptr;
}

PJRT_Error* BufferDimensions(PJRT_Buffer_Dimensions_Args* args) {
  args->dims = args->buffer->dims.data();
  args->num_dims = args->buffer->dims.size();
  return nullptr;
}

PJRT_Error* BufferToHostBuffer(PJRT_Buffer_ToHostBuffer_Args* args) {
  if (args->dst == nullptr) {
    args->dst_size = args->src->data.size();
    return nullptr;
  }
  if (args->dst_size < args->src->data.size())
    return Err("dst too small");
  std::memcpy(args->dst, args->src->data.data(), args->src->data.size());
  args->event = new PJRT_Event;
  return nullptr;
}

PJRT_Error* BufferDestroy(PJRT_Buffer_Destroy_Args* args) {
  delete args->buffer;
  return nullptr;
}

PJRT_Error* Execute(PJRT_LoadedExecutable_Execute_Args* args) {
  if (args->num_devices != 1) return Err("mock plugin is single-device");
  // checksum over the exact bytes staged for this execution
  uint64_t sum = 0;
  for (size_t a = 0; a < args->num_args; ++a) {
    const std::string& d = args->argument_lists[0][a]->data;
    for (unsigned char c : d) sum += c;
  }
  sum %= 1000003;
  const auto& outs = args->executable->exec.outputs;
  for (size_t i = 0; i < outs.size(); ++i) {
    const OutputSpec& spec = outs[i];
    auto* buf = new PJRT_Buffer;
    buf->type = spec.type;
    buf->dims = spec.dims;
    size_t n = 1;
    for (int64_t d : spec.dims) n *= static_cast<size_t>(d);
    buf->data.resize(n * spec.elem_bytes);
    double value = static_cast<double>(sum % 1000) + static_cast<double>(i);
    for (size_t e = 0; e < n; ++e) {
      if (spec.type == PJRT_Buffer_Type_F32) {
        float v = static_cast<float>(value);
        std::memcpy(&buf->data[e * 4], &v, 4);
      } else {
        int32_t v = static_cast<int32_t>(value);
        std::memcpy(&buf->data[e * 4], &v, 4);
      }
    }
    args->output_lists[0][i] = buf;
  }
  if (args->device_complete_events != nullptr)
    args->device_complete_events[0] = new PJRT_Event;
  return nullptr;
}

PJRT_Api* BuildApi() {
  static PJRT_Api api;
  std::memset(&api, 0, sizeof(api));
  api.struct_size = PJRT_Api_STRUCT_SIZE;
  api.pjrt_api_version.major_version = PJRT_API_MAJOR;
  api.pjrt_api_version.minor_version = PJRT_API_MINOR;
  api.PJRT_Error_Destroy = +[](PJRT_Error_Destroy_Args* a) {
    ErrorDestroy(a);
  };
  api.PJRT_Error_Message = +[](PJRT_Error_Message_Args* a) {
    ErrorMessage(a);
  };
  api.PJRT_Error_GetCode = ErrorCode;
  api.PJRT_Plugin_Initialize = PluginInitialize;
  api.PJRT_Event_Destroy = EventDestroy;
  api.PJRT_Event_IsReady = EventIsReady;
  api.PJRT_Event_Await = EventAwait;
  api.PJRT_Client_Create = ClientCreate;
  api.PJRT_Client_Destroy = ClientDestroy;
  api.PJRT_Client_AddressableDevices = AddressableDevices;
  api.PJRT_Client_Compile = ClientCompile;
  api.PJRT_Client_BufferFromHostBuffer = BufferFromHostBuffer;
  api.PJRT_LoadedExecutable_Destroy = LoadedExecutableDestroy;
  api.PJRT_LoadedExecutable_GetExecutable = GetExecutable;
  api.PJRT_Executable_NumOutputs = NumOutputs;
  api.PJRT_LoadedExecutable_Execute = Execute;
  api.PJRT_Buffer_ElementType = BufferElementType;
  api.PJRT_Buffer_Dimensions = BufferDimensions;
  api.PJRT_Buffer_ToHostBuffer = BufferToHostBuffer;
  api.PJRT_Buffer_Destroy = BufferDestroy;
  return &api;
}

}  // namespace

extern "C" const PJRT_Api* GetPjrtApi() { return BuildApi(); }
