"""Native C serving ABI (VERDICT r3 missing #2).

reference: paddle/fluid/inference/capi_exp/pd_inference_api.h (C API) +
paddle/fluid/inference/goapi/predictor.go (Go bindings) — non-Python
services embed the predictor through a C surface. Here a pure-C program
links libpaddle_tpu_capi.so, loads a jit.save artifact, and runs
inference; outputs must match the Python predictor bit-for-bit path.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu
import paddle_tpu.inference as inference

pytestmark = pytest.mark.slow   # g++ build + embedded-interpreter boot

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_DRIVER = r"""
#include <stdio.h>
#include <stdint.h>
#include <stdlib.h>
#include <stddef.h>

extern int PD_Init(const char*);
extern void* PD_ConfigCreate(void);
extern void PD_ConfigSetModelDir(void*, const char*);
extern void* PD_PredictorCreate(void*);
extern size_t PD_PredictorGetInputNum(void*);
extern const char* PD_PredictorGetInputName(void*, size_t);
extern size_t PD_PredictorGetOutputNum(void*);
extern const char* PD_PredictorGetOutputName(void*, size_t);
extern void* PD_PredictorGetInputHandle(void*, const char*);
extern void* PD_PredictorGetOutputHandle(void*, const char*);
extern int PD_PredictorRun(void*);
extern void PD_TensorReshape(void*, int, const int64_t*);
extern int PD_TensorCopyFromCpuFloat(void*, const float*);
extern int PD_TensorGetShape(void*, int64_t*, int);
extern int PD_TensorCopyToCpuFloat(void*, float*);
extern const char* PD_GetLastError(void);

int main(int argc, char** argv) {
  if (argc < 3) return 1;
  if (!PD_Init(argv[1])) return 1;
  void* cfg = PD_ConfigCreate();
  PD_ConfigSetModelDir(cfg, argv[2]);
  void* pred = PD_PredictorCreate(cfg);
  if (!pred) { fprintf(stderr, "create: %s\n", PD_GetLastError()); return 2; }
  if (PD_PredictorGetInputNum(pred) < 1) return 2;
  void* in = PD_PredictorGetInputHandle(
      pred, PD_PredictorGetInputName(pred, 0));
  int64_t shape[2] = {3, 4};
  PD_TensorReshape(in, 2, shape);
  float data[12];
  for (int i = 0; i < 12; ++i) data[i] = (float)i * 0.25f - 1.0f;
  if (!PD_TensorCopyFromCpuFloat(in, data)) {
    fprintf(stderr, "copy_from: %s\n", PD_GetLastError()); return 3;
  }
  if (!PD_PredictorRun(pred)) {
    fprintf(stderr, "run: %s\n", PD_GetLastError()); return 4;
  }
  if (PD_PredictorGetOutputNum(pred) < 1) return 4;
  void* out = PD_PredictorGetOutputHandle(
      pred, PD_PredictorGetOutputName(pred, 0));
  int64_t oshape[8];
  int nd = PD_TensorGetShape(out, oshape, 8);
  if (nd < 0) { fprintf(stderr, "shape: %s\n", PD_GetLastError()); return 5; }
  int64_t total = 1;
  for (int i = 0; i < nd; ++i) total *= oshape[i];
  float* buf = (float*)malloc(total * sizeof(float));
  if (!PD_TensorCopyToCpuFloat(out, buf)) {
    fprintf(stderr, "copy_to: %s\n", PD_GetLastError()); return 6;
  }
  printf("SHAPE");
  for (int i = 0; i < nd; ++i) printf(" %lld", (long long)oshape[i]);
  printf("\n");
  for (int64_t i = 0; i < total; ++i) printf("%.6f\n", (double)buf[i]);
  return 0;
}
"""


def _reference_output():
    """The same inputs the C driver feeds, through the Python stack."""
    x = (np.arange(12, dtype=np.float32) * 0.25 - 1.0).reshape(3, 4)
    return x


@pytest.fixture(scope="module")
def capi_lib():
    from paddle_tpu import _native
    return _native.build_capi()


@pytest.fixture()
def saved_model(tmp_path):
    paddle.seed(7)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    net.eval()
    path = str(tmp_path / "model")
    paddle.jit.save(net, path,
                    input_spec=[paddle.jit.api.InputSpec([3, 4])])
    x = _reference_output()
    ref = net(paddle.to_tensor(x)).numpy()
    return path, ref


class TestCServingABI:
    def test_c_program_serves_saved_artifact(self, tmp_path, capi_lib,
                                             saved_model):
        model_path, ref = saved_model
        src = tmp_path / "driver.c"
        src.write_text(_DRIVER)
        exe = tmp_path / "driver"
        libdir = os.path.dirname(capi_lib)
        subprocess.run(
            ["gcc", str(src), "-o", str(exe),
             f"-L{libdir}", f"-l:{os.path.basename(capi_lib)}",
             f"-Wl,-rpath,{libdir}"],
            check=True, capture_output=True)
        env = {k: v for k, v in os.environ.items()}
        env["PYTHONPATH"] = REPO
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run([str(exe), REPO, model_path], env=env,
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, (proc.stdout, proc.stderr[-2000:])
        lines = proc.stdout.strip().splitlines()
        assert lines[0].startswith("SHAPE ")
        shape = tuple(int(v) for v in lines[0].split()[1:])
        vals = np.array([float(v) for v in lines[1:]],
                        np.float32).reshape(shape)
        assert shape == ref.shape
        np.testing.assert_allclose(vals, ref, rtol=1e-5, atol=1e-6)

    def test_ctypes_surface_matches_python_predictor(self, capi_lib,
                                                     saved_model):
        """The same ABI driven in-process via ctypes (the shim must also
        behave when the host process already IS Python)."""
        import ctypes
        model_path, ref = saved_model
        lib = ctypes.CDLL(capi_lib)
        lib.PD_Init.argtypes = [ctypes.c_char_p]
        lib.PD_ConfigCreate.restype = ctypes.c_void_p
        lib.PD_ConfigSetModelDir.argtypes = [ctypes.c_void_p,
                                             ctypes.c_char_p]
        lib.PD_PredictorCreate.restype = ctypes.c_void_p
        lib.PD_PredictorCreate.argtypes = [ctypes.c_void_p]
        lib.PD_PredictorGetInputName.restype = ctypes.c_char_p
        lib.PD_PredictorGetInputName.argtypes = [ctypes.c_void_p,
                                                 ctypes.c_size_t]
        lib.PD_PredictorGetInputHandle.restype = ctypes.c_void_p
        lib.PD_PredictorGetInputHandle.argtypes = [ctypes.c_void_p,
                                                   ctypes.c_char_p]
        lib.PD_PredictorGetOutputName.restype = ctypes.c_char_p
        lib.PD_PredictorGetOutputName.argtypes = [ctypes.c_void_p,
                                                  ctypes.c_size_t]
        lib.PD_PredictorGetOutputHandle.restype = ctypes.c_void_p
        lib.PD_PredictorGetOutputHandle.argtypes = [ctypes.c_void_p,
                                                    ctypes.c_char_p]
        lib.PD_PredictorRun.argtypes = [ctypes.c_void_p]
        lib.PD_TensorReshape.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                         ctypes.POINTER(ctypes.c_int64)]
        lib.PD_TensorCopyFromCpuFloat.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_float)]
        lib.PD_TensorGetShape.argtypes = [ctypes.c_void_p,
                                          ctypes.POINTER(ctypes.c_int64),
                                          ctypes.c_int]
        lib.PD_TensorCopyToCpuFloat.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_float)]
        lib.PD_GetLastError.restype = ctypes.c_char_p

        assert lib.PD_Init(REPO.encode())
        cfg = lib.PD_ConfigCreate()
        lib.PD_ConfigSetModelDir(cfg, model_path.encode())
        pred = lib.PD_PredictorCreate(cfg)
        assert pred, lib.PD_GetLastError()
        name = lib.PD_PredictorGetInputName(pred, 0)
        h = lib.PD_PredictorGetInputHandle(pred, name)
        x = _reference_output()
        shp = (ctypes.c_int64 * 2)(3, 4)
        lib.PD_TensorReshape(h, 2, shp)
        buf = np.ascontiguousarray(x)
        assert lib.PD_TensorCopyFromCpuFloat(
            h, buf.ctypes.data_as(ctypes.POINTER(ctypes.c_float))), \
            lib.PD_GetLastError()
        assert lib.PD_PredictorRun(pred), lib.PD_GetLastError()
        oname = lib.PD_PredictorGetOutputName(pred, 0)
        oh = lib.PD_PredictorGetOutputHandle(pred, oname)
        oshape = (ctypes.c_int64 * 8)()
        nd = lib.PD_TensorGetShape(oh, oshape, 8)
        assert nd == 2, lib.PD_GetLastError()
        out = np.zeros(tuple(oshape[:nd]), np.float32)
        assert lib.PD_TensorCopyToCpuFloat(
            oh, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

        # second run with DIFFERENT inputs through the SAME handles: the
        # python predictor rebuilds its output tensors every run, so a
        # held C handle must read the CURRENT run's values, and handle
        # re-fetches must not grow the handle table
        x2 = np.ascontiguousarray(x * -2.0)
        assert lib.PD_TensorCopyFromCpuFloat(
            h, x2.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        assert lib.PD_PredictorRun(pred), lib.PD_GetLastError()
        oh2 = lib.PD_PredictorGetOutputHandle(pred, oname)
        assert oh2 == oh               # deduped, not a new allocation
        out2 = np.zeros_like(out)
        assert lib.PD_TensorCopyToCpuFloat(
            oh2, out2.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        # build the reference for x2 by reloading the artifact in python
        cfg2 = paddle_tpu.inference.Config(model_path)
        p2 = paddle_tpu.inference.create_predictor(cfg2)
        ih = p2.get_input_handle(p2.get_input_names()[0])
        ih.copy_from_cpu(x2)
        p2.run()
        ref2 = p2.get_output_handle(
            p2.get_output_names()[0]).copy_to_cpu()
        assert not np.allclose(out2, out)   # genuinely fresh values
        np.testing.assert_allclose(out2, ref2, rtol=1e-5, atol=1e-6)
