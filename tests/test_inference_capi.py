"""C inference API end-to-end: save a model from Python, compile a real C
program against csrc/pd_inference_c.h, run it, and compare its printed
outputs against the in-process Python predictor.

Reference analog: paddle/fluid/inference/capi_exp/ +
test/cpp/inference/api/analysis_predictor_tester.cc.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(REPO, "paddle_tpu", "core", "libpaddle_tpu_infer.so")

C_SRC = r"""
#include <stdio.h>
#include <stdlib.h>
#include "pd_inference_c.h"

int main(int argc, char** argv) {
  if (argc < 2) return 2;
  PD_Config* cfg = PD_ConfigCreate();
  PD_ConfigSetModel(cfg, argv[1], "");
  PD_Predictor* pred = PD_PredictorCreate(cfg);
  if (!pred) return 3;
  if (PD_PredictorGetInputNum(pred) != 1) return 4;
  const char* in_name = PD_PredictorGetInputName(pred, 0);
  PD_Tensor* in = PD_PredictorGetInputHandle(pred, in_name);
  int32_t dims[2] = {2, 4};
  PD_TensorReshape(in, 2, dims);
  float data[8];
  for (int i = 0; i < 8; i++) data[i] = 0.125f * (float)(i + 1);
  if (!PD_TensorCopyFromCpuFloat(in, data)) return 5;
  if (!PD_PredictorRun(pred)) return 6;
  const char* out_name = PD_PredictorGetOutputName(pred, 0);
  PD_Tensor* out = PD_PredictorGetOutputHandle(pred, out_name);
  size_t nd = 0;
  int32_t odims[8];
  if (!PD_TensorGetShape(out, &nd, odims)) return 7;
  size_t n = 1;
  for (size_t i = 0; i < nd; i++) n *= (size_t)odims[i];
  float* buf = (float*)malloc(n * sizeof(float));
  if (!PD_TensorCopyToCpuFloat(out, buf)) return 8;
  printf("shape");
  for (size_t i = 0; i < nd; i++) printf(" %d", odims[i]);
  printf("\n");
  for (size_t i = 0; i < n; i++) printf("%.6f\n", buf[i]);
  free(buf);
  PD_TensorDestroy(in);
  PD_TensorDestroy(out);
  PD_PredictorDestroy(pred);
  return 0;
}
"""


@pytest.fixture(scope="module")
def saved_model(tmp_path_factory):
    from paddle_tpu import static

    d = tmp_path_factory.mktemp("capi_model")
    prefix = str(d / "model")
    x_np = (0.125 * np.arange(1, 9, dtype=np.float32)).reshape(2, 4)

    paddle.enable_static()
    try:
        main = static.Program()
        with static.program_guard(main):
            x = static.data("x", [None, 4], "float32")
            h = static.nn.fc(x, 8, activation="relu")
            out = static.nn.fc(h, 3)
        exe = static.Executor()
        static.save_inference_model(prefix, [x], [out], exe, program=main)
        ref = exe.run(main, feed={"x": x_np}, fetch_list=[out])[0]
    finally:
        paddle.disable_static()
    return prefix, ref


def _ensure_lib():
    if not os.path.exists(LIB):
        subprocess.run(["make", "-C", os.path.join(REPO, "csrc"),
                        "inference"], check=True, capture_output=True)
    return LIB


def test_c_program_matches_python(saved_model, tmp_path):
    _ensure_lib()
    prefix, ref = saved_model
    csrc = tmp_path / "main.c"
    csrc.write_text(C_SRC)
    exe = tmp_path / "capi_demo"
    subprocess.run(
        ["gcc", str(csrc), "-I", os.path.join(REPO, "csrc"),
         str(LIB), "-Wl,-rpath," + os.path.dirname(LIB),
         "-Wl,-rpath,/usr/local/lib", "-o", str(exe)],
        check=True, capture_output=True)

    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
    r = subprocess.run([str(exe), prefix], env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
    lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert lines[0].startswith("shape")
    shape = tuple(int(v) for v in lines[0].split()[1:])
    vals = np.array([float(v) for v in lines[1:]], np.float32).reshape(shape)
    assert shape == ref.shape
    np.testing.assert_allclose(vals, ref, rtol=1e-4, atol=1e-5)
