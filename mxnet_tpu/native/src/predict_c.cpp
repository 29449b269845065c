// predict_c.cpp — C predict API over mx.deploy artifacts.
//
// Reference analogue: src/c_api/c_predict_api.cc. The reference builds
// a GraphExecutor from symbol JSON + NDArray params; here the artifact
// already IS an executable program (StableHLO via jax.export with
// params baked in), so this file only has to (1) host a CPython
// interpreter, (2) hand the artifact to a tiny self-contained loader
// snippet that needs nothing beyond `jax` + `numpy`, and (3) marshal
// float buffers across the C boundary through the buffer protocol —
// no numpy C API, no mxnet_tpu import.
//
// Build: g++ -O2 -std=c++17 -shared -fPIC predict_c.cpp \
//            $(python3-config --includes) \
//            -L$(python3-config --prefix)/lib -lpython3.X \
//            -o libmxtpu_predict.so

#include <Python.h>

#include <cstdint>
#include <cstdio>
#include <cstring>
#include <mutex>
#include <string>
#include <vector>

#include "../include/mxtpu_predict.h"

namespace {

thread_local char g_err[1024] = "";

void set_err(const char *fmt, const char *detail) {
  snprintf(g_err, sizeof(g_err), fmt, detail ? detail : "");
}

// Fetch + clear the pending Python exception into g_err (GIL held).
void set_err_from_python(const char *where) {
  PyObject *type = nullptr, *value = nullptr, *tb = nullptr;
  PyErr_Fetch(&type, &value, &tb);
  PyErr_NormalizeException(&type, &value, &tb);
  std::string msg = "<no exception>";
  if (value) {
    PyObject *s = PyObject_Str(value);
    if (s) {
      const char *c = PyUnicode_AsUTF8(s);
      if (c) msg = c;
      Py_DECREF(s);
    }
  }
  snprintf(g_err, sizeof(g_err), "%s: %s", where, msg.c_str());
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(tb);
}

// The loader lives entirely inside this snippet so the .so has no
// Python-side package dependency. Mirrors deploy.py's file format:
// b"MXTPUPRED1" + <u32 header_len> + json header + jax.export blob.
const char *kLoaderSrc = R"PY(
import json, struct

_MAGIC = b"MXTPUPRED1"

def _pick_device(platforms):
    # the artifact is platform-specific (StableHLO lowered per backend);
    # run it on a device matching its export platform, regardless of the
    # host process's default jax backend
    import jax
    want = {p.lower() for p in platforms}
    for name in ("tpu", "cuda", "rocm", "gpu", "cpu"):
        if name in want or (name in ("cuda", "rocm") and "gpu" in want):
            try:
                return jax.local_devices(backend=name)[0]
            except Exception:
                continue
    if "cpu" in want:
        return jax.local_devices(backend="cpu")[0]
    # a clear error beats the downstream "exported for X used on Y"
    raise RuntimeError(
        "artifact was exported for platforms %r but no matching jax "
        "device is available in this process" % (sorted(want),))

def load(path):
    import numpy as np
    from jax import export as jexport
    with open(path, "rb") as f:
        blob = f.read()
    if not blob.startswith(_MAGIC):
        raise ValueError("not an mxnet_tpu predictor artifact: %s" % path)
    off = len(_MAGIC)
    (hlen,) = struct.unpack_from("<I", blob, off)
    off += 4
    meta = json.loads(blob[off:off + hlen].decode())
    exported = jexport.deserialize(blob[off + hlen:])
    plats = getattr(exported, "platforms", ("cpu",))
    return {
        "meta": meta,
        "exported": exported,
        "shape": tuple(meta["input_shape"]),
        "dtype": meta["input_dtype"],
        "device": _pick_device(plats),
    }

def forward(pred, buf):
    import jax
    import numpy as np
    x = np.frombuffer(buf, dtype=np.float32).reshape(pred["shape"])
    x = x.astype(pred["dtype"], copy=False)
    outs = pred["exported"].call(jax.device_put(x, pred["device"]))
    if not isinstance(outs, (list, tuple)):
        outs = [outs]
    return [np.ascontiguousarray(np.asarray(o), dtype=np.float32)
            for o in outs]
)PY";

struct Predictor {
  PyObject *pred = nullptr;     // dict returned by load()
  PyObject *forward = nullptr;  // loader forward()
  PyObject *outputs = nullptr;  // list of float32 ndarrays (last Forward)
  std::vector<int64_t> input_shape;
  std::vector<std::vector<int64_t>> out_shapes;
};

PyObject *g_loader_ns = nullptr;  // module namespace holding load/forward

class GIL {
 public:
  GIL() : state_(PyGILState_Ensure()) {}
  ~GIL() { PyGILState_Release(state_); }

 private:
  PyGILState_STATE state_;
};

std::mutex g_init_mutex;

// Initialize the interpreter (if this process doesn't already host
// one) and compile the loader snippet once. Returns false + g_err on
// failure. Caller must NOT hold the GIL. The mutex makes concurrent
// first MXTpuPredCreate calls safe (the header allows one handle per
// thread): without it two threads could both see Py_IsInitialized()
// false and race Py_InitializeFromConfig.
bool ensure_loader() {
  std::lock_guard<std::mutex> lock(g_init_mutex);
  if (!Py_IsInitialized()) {
    PyConfig config;
    PyConfig_InitPythonConfig(&config);
    config.install_signal_handlers = 0;  // stay out of the host's way
    PyStatus status = Py_InitializeFromConfig(&config);
    PyConfig_Clear(&config);
    if (PyStatus_Exception(status)) {
      set_err("interpreter init failed: %s",
              status.err_msg ? status.err_msg : "");
      return false;
    }
    // Py_InitializeFromConfig leaves us holding the GIL; drop to a
    // known state so every entry point can use PyGILState_Ensure.
    PyEval_SaveThread();
  }
  GIL gil;
  if (g_loader_ns == nullptr) {
    PyObject *mod = PyModule_New("_mxtpu_c_loader");
    PyObject *ns = mod ? PyModule_GetDict(mod) : nullptr;
    if (ns == nullptr ||
        PyDict_SetItemString(ns, "__builtins__", PyEval_GetBuiltins()) != 0) {
      set_err_from_python("loader namespace");
      Py_XDECREF(mod);
      return false;
    }
    PyObject *r = PyRun_String(kLoaderSrc, Py_file_input, ns, ns);
    if (r == nullptr) {
      set_err_from_python("loader compile");
      Py_DECREF(mod);
      return false;
    }
    Py_DECREF(r);
    g_loader_ns = mod;  // keep the module (and its dict) alive forever
  }
  return true;
}

bool fill_shape(PyObject *ndarray, std::vector<int64_t> *out) {
  Py_buffer view;
  if (PyObject_GetBuffer(ndarray, &view,
                         PyBUF_CONTIG_RO | PyBUF_FORMAT) != 0) {
    set_err_from_python("output buffer");
    return false;
  }
  out->assign(view.shape, view.shape + view.ndim);
  PyBuffer_Release(&view);
  return true;
}

}  // namespace

extern "C" int MXTpuPredCreate(const char *artifact_path,
                               MXTpuPredictorHandle *out) {
  if (out == nullptr || artifact_path == nullptr) {
    set_err("null argument%s", nullptr);
    return -1;
  }
  *out = nullptr;
  if (!ensure_loader()) return -1;
  GIL gil;
  PyObject *ns = PyModule_GetDict(g_loader_ns);
  PyObject *load = PyDict_GetItemString(ns, "load");          // borrowed
  PyObject *forward = PyDict_GetItemString(ns, "forward");    // borrowed
  PyObject *pred =
      PyObject_CallFunction(load, "s", artifact_path);        // new
  if (pred == nullptr) {
    set_err_from_python("load");
    return -1;
  }
  auto *p = new Predictor;
  p->pred = pred;
  p->forward = forward;
  Py_INCREF(p->forward);
  PyObject *shape = PyDict_GetItemString(pred, "shape");      // borrowed
  Py_ssize_t n = PyTuple_Size(shape);
  for (Py_ssize_t i = 0; i < n; ++i)
    p->input_shape.push_back(PyLong_AsLongLong(PyTuple_GetItem(shape, i)));
  *out = p;
  return 0;
}

extern "C" int MXTpuPredGetInputShape(MXTpuPredictorHandle h,
                                      const int64_t **shape, int *ndim) {
  auto *p = static_cast<Predictor *>(h);
  if (p == nullptr) {
    set_err("null handle%s", nullptr);
    return -1;
  }
  *shape = p->input_shape.data();
  *ndim = static_cast<int>(p->input_shape.size());
  return 0;
}

extern "C" int MXTpuPredForward(MXTpuPredictorHandle h, const float *data,
                                size_t size) {
  auto *p = static_cast<Predictor *>(h);
  if (p == nullptr || data == nullptr) {
    set_err("null handle/data%s", nullptr);
    return -1;
  }
  int64_t want = 1;
  for (int64_t d : p->input_shape) want *= d;
  if (static_cast<int64_t>(size) != want) {
    set_err("input size mismatch%s", nullptr);
    return -1;
  }
  GIL gil;
  PyObject *buf = PyBytes_FromStringAndSize(
      reinterpret_cast<const char *>(data), size * sizeof(float));
  if (buf == nullptr) {
    set_err_from_python("input alloc");
    return -1;
  }
  PyObject *outs = PyObject_CallFunctionObjArgs(p->forward, p->pred, buf,
                                                nullptr);
  Py_DECREF(buf);
  if (outs == nullptr) {
    set_err_from_python("forward");
    return -1;
  }
  // stage shapes fully before publishing: a mid-loop failure must leave
  // the handle's previous outputs/shapes consistent, not half-swapped
  std::vector<std::vector<int64_t>> shapes;
  Py_ssize_t n = PyList_Size(outs);
  for (Py_ssize_t i = 0; i < n; ++i) {
    std::vector<int64_t> s;
    if (!fill_shape(PyList_GetItem(outs, i), &s)) {
      Py_DECREF(outs);
      return -1;
    }
    shapes.push_back(std::move(s));
  }
  Py_XDECREF(p->outputs);
  p->outputs = outs;
  p->out_shapes = std::move(shapes);
  return 0;
}

extern "C" int MXTpuPredGetNumOutputs(MXTpuPredictorHandle h, int *num) {
  auto *p = static_cast<Predictor *>(h);
  if (p == nullptr || p->outputs == nullptr) {
    set_err("no outputs (call Forward first)%s", nullptr);
    return -1;
  }
  GIL gil;
  *num = static_cast<int>(PyList_Size(p->outputs));
  return 0;
}

extern "C" int MXTpuPredGetOutputShape(MXTpuPredictorHandle h, unsigned index,
                                       const int64_t **shape, int *ndim) {
  auto *p = static_cast<Predictor *>(h);
  if (p == nullptr || index >= p->out_shapes.size()) {
    set_err("bad output index%s", nullptr);
    return -1;
  }
  *shape = p->out_shapes[index].data();
  *ndim = static_cast<int>(p->out_shapes[index].size());
  return 0;
}

extern "C" int MXTpuPredGetOutput(MXTpuPredictorHandle h, unsigned index,
                                  float *data, size_t size) {
  auto *p = static_cast<Predictor *>(h);
  if (p == nullptr || p->outputs == nullptr) {
    set_err("no outputs (call Forward first)%s", nullptr);
    return -1;
  }
  GIL gil;
  if (index >= static_cast<size_t>(PyList_Size(p->outputs))) {
    set_err("bad output index%s", nullptr);
    return -1;
  }
  Py_buffer view;
  if (PyObject_GetBuffer(PyList_GetItem(p->outputs, index), &view,
                         PyBUF_CONTIG_RO) != 0) {
    set_err_from_python("output buffer");
    return -1;
  }
  if (static_cast<size_t>(view.len) != size * sizeof(float)) {
    PyBuffer_Release(&view);
    set_err("output size mismatch%s", nullptr);
    return -1;
  }
  memcpy(data, view.buf, view.len);
  PyBuffer_Release(&view);
  return 0;
}

extern "C" const char *MXTpuPredGetLastError(void) { return g_err; }

extern "C" void MXTpuPredFree(MXTpuPredictorHandle h) {
  auto *p = static_cast<Predictor *>(h);
  if (p == nullptr) return;
  if (Py_IsInitialized()) {
    GIL gil;
    Py_XDECREF(p->pred);
    Py_XDECREF(p->forward);
    Py_XDECREF(p->outputs);
  }
  delete p;
}
