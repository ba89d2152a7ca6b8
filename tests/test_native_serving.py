"""Native C-ABI serving test (reference inference/api/paddle_api.h:199 /
capi analog): save an inference model, compile a REAL C driver program
that links libserving.so, and run it as a separate native process — no
Python on the driver side. The driver feeds a known input and prints the
output, which must match the in-process predictor."""

import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid

C_DRIVER = r"""
#include <stdio.h>
#include <stdlib.h>

extern void* pd_predictor_create(const char* model_dir);
extern int pd_predictor_run(void* h, const char** names,
                            const float** data, const long long** shapes,
                            const int* ndims, int n_inputs,
                            const float** out_data,
                            const long long** out_shapes, int* out_ndims,
                            int max_outputs);
extern void pd_predictor_destroy(void* h);
extern const char* pd_last_error(void);

int main(int argc, char** argv) {
  void* p = pd_predictor_create(argv[1]);
  if (!p) { fprintf(stderr, "create: %s\n", pd_last_error()); return 2; }
  float input[4 * 6];
  for (int i = 0; i < 4 * 6; ++i) input[i] = (float)i * 0.1f - 1.0f;
  const char* names[1] = {"x"};
  const float* data[1] = {input};
  long long shape0[2] = {4, 6};
  const long long* shapes[1] = {shape0};
  int ndims[1] = {2};
  const float* out_data[4];
  const long long* out_shapes[4];
  int out_ndims[4];
  int n = pd_predictor_run(p, names, data, shapes, ndims, 1,
                           out_data, out_shapes, out_ndims, 4);
  if (n < 0) { fprintf(stderr, "run: %s\n", pd_last_error()); return 3; }
  for (int i = 0; i < n; ++i) {
    long long numel = 1;
    for (int d = 0; d < out_ndims[i]; ++d) numel *= out_shapes[i][d];
    for (long long j = 0; j < numel; ++j) printf("%.6f\n", out_data[i][j]);
  }
  pd_predictor_destroy(p);
  return 0;
}
"""


C_DRIVER_I64 = r"""
#include <stdio.h>
#include <stdlib.h>

extern void* pd_predictor_create(const char* model_dir);
extern int pd_predictor_run_ex(void* h, const char** names,
                               const void** data, const int* dtypes,
                               const long long** shapes, const int* ndims,
                               int n_inputs, const float** out_data,
                               const long long** out_shapes, int* out_ndims,
                               int max_outputs);
extern void pd_predictor_destroy(void* h);
extern const char* pd_last_error(void);

int main(int argc, char** argv) {
  void* p = pd_predictor_create(argv[1]);
  if (!p) { fprintf(stderr, "create: %s\n", pd_last_error()); return 2; }
  long long ids[6] = {1, 5, 9, 2, 0, 7};
  const char* names[1] = {"ids"};
  const void* data[1] = {ids};
  int dtypes[1] = {1};  /* int64 */
  long long shape0[2] = {3, 2};
  const long long* shapes[1] = {shape0};
  int ndims[1] = {2};
  const float* out_data[2];
  const long long* out_shapes[2];
  int out_ndims[2];
  int n = pd_predictor_run_ex(p, names, data, dtypes, shapes, ndims, 1,
                              out_data, out_shapes, out_ndims, 2);
  if (n < 0) { fprintf(stderr, "run: %s\n", pd_last_error()); return 3; }
  for (int i = 0; i < n; ++i) {
    long long numel = 1;
    for (int d = 0; d < out_ndims[i]; ++d) numel *= out_shapes[i][d];
    for (long long j = 0; j < numel; ++j) printf("%.6f\n", out_data[i][j]);
  }
  pd_predictor_destroy(p);
  return 0;
}
"""


@pytest.mark.slow
def test_c_driver_int64_inputs(tmp_path):
    """NLP-style serving: int64 id inputs through pd_predictor_run_ex."""
    from paddle_tpu.core.scope import Scope, scope_guard

    model_dir = str(tmp_path / "model")
    main, startup = fluid.Program(), fluid.Program()
    scope = Scope()
    with scope_guard(scope):
        with fluid.program_guard(main, startup):
            ids = fluid.layers.data(name="ids", shape=[3, 2], dtype="int64",
                                    append_batch_size=False)
            emb = fluid.layers.embedding(ids, size=[16, 8])
            pooled = fluid.layers.reduce_mean(emb, dim=1)
            out = fluid.layers.fc(pooled, size=4, act="softmax")
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        fluid.io.save_inference_model(model_dir, ["ids"], [out], exe,
                                      main_program=main)
        from paddle_tpu.inference import create_predictor_from_dir

        feed = np.array([[1, 5], [9, 2], [0, 7]], "int64")
        pred = create_predictor_from_dir(model_dir)
        want = np.asarray(pred.run({"ids": feed})[0], dtype=np.float32)

    from paddle_tpu.native import _build

    so = _build("serving")
    drv_src = tmp_path / "driver_i64.c"
    drv_src.write_text(C_DRIVER_I64)
    drv = str(tmp_path / "driver_i64")
    subprocess.run(["gcc", str(drv_src), so, "-o", drv,
                    "-Wl,-rpath," + os.path.dirname(so)],
                   check=True, capture_output=True)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
         env.get("PYTHONPATH", "")])
    env["JAX_PLATFORMS"] = "cpu"
    env["PD_SERVING_PYINIT"] = (
        'import jax; jax.config.update("jax_platforms", "cpu")')
    res = subprocess.run([drv, model_dir], env=env, capture_output=True,
                         text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    got = np.array([float(l) for l in res.stdout.split()],
                   dtype=np.float32).reshape(want.shape)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_c_driver_matches_python_predictor(tmp_path):
    from paddle_tpu.core.scope import Scope, scope_guard

    model_dir = str(tmp_path / "model")
    main, startup = fluid.Program(), fluid.Program()
    scope = Scope()
    with scope_guard(scope):
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[6], dtype="float32")
            h = fluid.layers.fc(x, size=5, act="tanh")
            out = fluid.layers.fc(h, size=3, act="softmax")
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        fluid.io.save_inference_model(model_dir, ["x"], [out], exe,
                                      main_program=main)

        # in-process expected values
        from paddle_tpu.inference import create_predictor_from_dir

        feed = (np.arange(24, dtype=np.float32) * 0.1 - 1.0).reshape(4, 6)
        pred = create_predictor_from_dir(model_dir)
        want = np.asarray(pred.run({"x": feed})[0], dtype=np.float32)

    # build libserving + the C driver
    from paddle_tpu.native import _build

    so = _build("serving")
    drv_src = tmp_path / "driver.c"
    drv_src.write_text(C_DRIVER)
    drv = str(tmp_path / "driver")
    subprocess.run(["gcc", str(drv_src), so, "-o", drv,
                    "-Wl,-rpath," + os.path.dirname(so)],
                   check=True, capture_output=True)

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
         env.get("PYTHONPATH", "")])
    env["JAX_PLATFORMS"] = "cpu"
    # exercise the shim's pre-init hook: it pins the backend before any
    # framework import
    env["PD_SERVING_PYINIT"] = (
        'import jax; jax.config.update("jax_platforms", "cpu")')
    res = subprocess.run([drv, model_dir], env=env, capture_output=True,
                         text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    got = np.array([float(l) for l in res.stdout.split()],
                   dtype=np.float32).reshape(want.shape)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
