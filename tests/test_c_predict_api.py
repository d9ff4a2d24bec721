"""C predict ABI end-to-end: compile a pure-C client against
include/mxnet_tpu/c_predict_api.h + libmxnet_tpu_predict.so and run the
reference MXPredCreate/SetInput/Forward/GetOutput flow (SURVEY §3.4,
src/c_api/c_predict_api.cc)."""

import os
import shutil
import subprocess
import sys
import sysconfig

import numpy as np
import pytest

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_C_SRC = r"""
#include <stdio.h>
#include <stdlib.h>
#include "mxnet_tpu/c_predict_api.h"

int main(int argc, char** argv) {
    FILE* f = fopen(argv[1], "rb");
    fseek(f, 0, SEEK_END); long jn = ftell(f); fseek(f, 0, SEEK_SET);
    char* json = malloc(jn + 1);
    if (fread(json, 1, jn, f) != (size_t)jn) return 2;
    json[jn] = 0; fclose(f);
    f = fopen(argv[2], "rb");
    fseek(f, 0, SEEK_END); long pn = ftell(f); fseek(f, 0, SEEK_SET);
    void* params = malloc(pn);
    if (fread(params, 1, pn, f) != (size_t)pn) return 2;
    fclose(f);

    const char* keys[] = {"data"};
    uint32_t indptr[] = {0, 2};
    uint32_t shape[] = {2, 6};
    PredictorHandle h;
    if (MXPredCreate(json, params, (int)pn, 1, 0, 1, keys, indptr, shape,
                     &h) != 0) {
        fprintf(stderr, "create: %s\n", MXGetLastError());
        return 1;
    }
    float in[12];
    int i;
    for (i = 0; i < 12; ++i) in[i] = (float)i * 0.1f;
    if (MXPredSetInput(h, "data", in, 12) != 0) return 1;
    if (MXPredForward(h) != 0) return 1;
    uint32_t* shp; uint32_t ndim;
    if (MXPredGetOutputShape(h, 0, &shp, &ndim) != 0) return 1;
    if (ndim != 2 || shp[0] != 2 || shp[1] != 3) return 3;
    float out[6];
    if (MXPredGetOutput(h, 0, out, 6) != 0) return 1;
    float s = out[0] + out[1] + out[2];
    if (s < 0.999f || s > 1.001f) return 4;  /* softmax row sums to 1 */
    MXPredFree(h);
    printf("C PREDICT OK\n");
    return 0;
}
"""


@pytest.mark.skipif(shutil.which("g++") is None or shutil.which("gcc") is None,
                    reason="needs a C/C++ toolchain")
def test_c_predict_api_end_to_end(tmp_path):
    # checkpoint to feed the C client
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=3, name="fc"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (2, 6))],
             label_shapes=[("softmax_label", (2,))])
    np.random.seed(0)
    mod.init_params(mx.init.Xavier())
    prefix = str(tmp_path / "cpred")
    mod.save_checkpoint(prefix, 0)

    inc = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR")
    pylib = "python%d.%d" % sys.version_info[:2]
    lib = tmp_path / "libmxnet_tpu_predict.so"
    r = subprocess.run(
        ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
         os.path.join(REPO, "src", "predict_capi.cc"),
         "-I", inc, "-o", str(lib)],
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-1500:]
    exe = tmp_path / "cpred_test"
    csrc = tmp_path / "t.c"
    csrc.write_text(_C_SRC)
    r = subprocess.run(
        ["gcc", "-O2", "-o", str(exe), str(csrc),
         "-I", os.path.join(REPO, "include"),
         "-L", str(tmp_path), "-lmxnet_tpu_predict",
         "-L", libdir, "-l" + pylib,
         "-Wl,-rpath," + str(tmp_path), "-Wl,-rpath," + libdir],
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-1500:]
    env = dict(os.environ, MXNET_TPU_HOME=REPO, JAX_PLATFORMS="cpu")
    r = subprocess.run([str(exe), prefix + "-symbol.json",
                        prefix + "-0000.params"],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (r.stdout[-500:], r.stderr[-1500:])
    assert "C PREDICT OK" in r.stdout
