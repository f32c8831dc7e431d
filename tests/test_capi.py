"""C inference API tests: build the native shim, load a jit-saved model through
the C ABI via ctypes, and compare against the in-process Python predictor."""
import ctypes
import functools
import os
import subprocess

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn

_NATIVE = os.path.join(os.path.dirname(paddle.__file__), "native")
_SRC = os.path.join(_NATIVE, "capi.cc")
_SO = os.path.join(_NATIVE, "libpaddle_tpu_capi.so")


def _build():
    if (os.path.exists(_SO)
            and os.path.getmtime(_SO) >= os.path.getmtime(_SRC)):
        return _SO
    inc = subprocess.run(["python3-config", "--includes"], check=True,
                         capture_output=True, text=True).stdout.split()
    subprocess.run(["g++", "-O2", "-fPIC", "-shared", "-std=c++17", *inc,
                    "-o", _SO, _SRC], check=True, capture_output=True)
    return _SO


@functools.lru_cache(maxsize=1)
def _jax_export_works():
    """Probe the same path static/io.py's _write_export_artifact takes:
    some jax builds ship a jax.export whose export()/serialize() raises
    (io.py then warns 'jax.export serialization unavailable' and skips
    writing the .pdmodel.jaxexport artifact). Tests that require the
    durable artifact on disk can only run where the environment can
    actually produce one."""
    import jax
    import jax.numpy as jnp

    try:
        exported = jax.export.export(jax.jit(lambda x: x * 2))(
            jax.ShapeDtypeStruct((2,), jnp.float32))
        exported.serialize()
        return True
    except Exception:
        return False


class TestCAPI:
    def test_c_abi_predict_roundtrip(self, tmp_path):
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 3))
        net.eval()
        prefix = str(tmp_path / "capi_model")
        paddle.jit.save(net, prefix,
                        input_spec=[paddle.jit.InputSpec([2, 4], "float32")])

        lib = ctypes.CDLL(_build())
        lib.PD_Init.restype = ctypes.c_int
        lib.PD_CreatePredictor.restype = ctypes.c_void_p
        lib.PD_CreatePredictor.argtypes = [ctypes.c_char_p]
        lib.PD_GetLastError.restype = ctypes.c_char_p
        lib.PD_PredictorRunFloat.restype = ctypes.c_int64
        lib.PD_PredictorRunFloat.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.POINTER(ctypes.c_int)]
        lib.PD_DestroyPredictor.argtypes = [ctypes.c_void_p]

        assert lib.PD_Init() == 0
        h = lib.PD_CreatePredictor(prefix.encode())
        assert h, lib.PD_GetLastError().decode()

        x = np.random.RandomState(0).randn(2, 4).astype(np.float32)
        shape = (ctypes.c_int64 * 2)(2, 4)
        out_buf = (ctypes.c_float * 64)()
        out_shape = (ctypes.c_int64 * 8)()
        out_ndim = ctypes.c_int(0)
        n = lib.PD_PredictorRunFloat(
            h, x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), shape, 2,
            out_buf, 64, out_shape, 8, ctypes.byref(out_ndim))
        assert n == 6, lib.PD_GetLastError().decode()
        assert list(out_shape[:out_ndim.value]) == [2, 3]

        got = np.array(out_buf[:6], np.float32).reshape(2, 3)
        ref = np.asarray(net(paddle.to_tensor(x))._data)
        np.testing.assert_allclose(got, ref, atol=1e-5)
        lib.PD_DestroyPredictor(h)

    def test_c_abi_error_reporting(self):
        lib = ctypes.CDLL(_build())
        lib.PD_CreatePredictor.restype = ctypes.c_void_p
        lib.PD_CreatePredictor.argtypes = [ctypes.c_char_p]
        lib.PD_GetLastError.restype = ctypes.c_char_p
        h = lib.PD_CreatePredictor(b"/nonexistent/model")
        assert not h
        assert b"load" in lib.PD_GetLastError()


class TestCAPITraining:
    """PD_CreateTrainer / PD_TrainStepFloat / PD_GetLoss / PD_TrainerSave
    (reference paddle/fluid/train/demo/demo_trainer.cc): real training from
    the C ABI, params device-side between calls."""

    def _lib(self):
        lib = ctypes.CDLL(_build())
        lib.PD_Init.restype = ctypes.c_int
        lib.PD_CreateTrainer.restype = ctypes.c_void_p
        lib.PD_CreateTrainer.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_double,
            ctypes.c_char_p]
        lib.PD_TrainStepFloat.restype = ctypes.c_int
        lib.PD_TrainStepFloat.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.c_int]
        lib.PD_GetLoss.restype = ctypes.c_double
        lib.PD_GetLoss.argtypes = [ctypes.c_void_p]
        lib.PD_TrainerSave.restype = ctypes.c_int
        lib.PD_TrainerSave.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.PD_DestroyTrainer.argtypes = [ctypes.c_void_p]
        lib.PD_GetLastError.restype = ctypes.c_char_p
        return lib

    def test_train_loss_falls_and_save_serves(self, tmp_path):
        paddle.seed(0)
        net = nn.Sequential(nn.Flatten(), nn.Linear(16, 32), nn.ReLU(),
                            nn.Linear(32, 4))
        # no input_spec: keep the PICKLED-layer artifact authoritative so
        # PD_TrainerSave's updated .pdiparams is what jit.load serves
        prefix = str(tmp_path / "train_model")
        paddle.jit.save(net, prefix)

        lib = self._lib()
        assert lib.PD_Init() == 0
        h = lib.PD_CreateTrainer(prefix.encode(), b"adam", 1e-2,
                                 b"cross_entropy")
        assert h, lib.PD_GetLastError().decode()

        rng = np.random.RandomState(0)
        x = rng.randn(8, 4, 4).astype(np.float32)
        y = rng.randint(0, 4, (8,)).astype(np.int64)
        xs = (ctypes.c_int64 * 3)(8, 4, 4)
        ys = (ctypes.c_int64 * 1)(8)
        losses = []
        for _ in range(30):
            rc = lib.PD_TrainStepFloat(
                h, x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), xs, 3,
                y.ctypes.data_as(ctypes.c_void_p), ys, 1, 0)
            assert rc == 0, lib.PD_GetLastError().decode()
            losses.append(lib.PD_GetLoss(h))
        assert losses[-1] < losses[0] * 0.5, losses[:3] + losses[-3:]

        assert lib.PD_TrainerSave(h, prefix.encode()) == 0, \
            lib.PD_GetLastError().decode()
        lib.PD_DestroyTrainer(h)
        # trained params serve through jit.load (same artifact family)
        served = paddle.jit.load(prefix)
        out = np.asarray(served(paddle.to_tensor(x))._data)
        acc = (out.argmax(-1) == y).mean()
        assert acc >= 0.75, acc   # memorized the batch

    @pytest.mark.skipif(
        not _jax_export_works(),
        reason="this jax build's jax.export.export/serialize raises — "
               "static/io.py falls back to StableHLO text + params "
               "('jax.export serialization unavailable') and never "
               "writes the .pdmodel.jaxexport durable artifact this "
               "test shadows")
    def test_save_over_durable_artifact_serves_trained_params(self,
                                                              tmp_path):
        # jit.save WITH input_spec writes the durable jax.export artifact;
        # PD_TrainerSave must not let it shadow the trained weights
        paddle.seed(0)
        net = nn.Sequential(nn.Flatten(), nn.Linear(16, 4))
        prefix = str(tmp_path / "durable")
        paddle.jit.save(net, prefix,
                        input_spec=[paddle.jit.InputSpec([4, 4, 4],
                                                         "float32")])
        assert os.path.exists(prefix + ".pdmodel.jaxexport")

        lib = self._lib()
        assert lib.PD_Init() == 0
        h = lib.PD_CreateTrainer(prefix.encode(), b"adam", 1e-2,
                                 b"cross_entropy")
        assert h, lib.PD_GetLastError().decode()
        rng = np.random.RandomState(0)
        x = rng.randn(4, 4, 4).astype(np.float32)
        y = np.arange(4).astype(np.int64)
        xs = (ctypes.c_int64 * 3)(4, 4, 4)
        ys = (ctypes.c_int64 * 1)(4)
        for _ in range(25):
            assert lib.PD_TrainStepFloat(
                h, x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), xs, 3,
                y.ctypes.data_as(ctypes.c_void_p), ys, 1, 0) == 0
        assert lib.PD_TrainerSave(h, prefix.encode()) == 0
        lib.PD_DestroyTrainer(h)

        served = paddle.jit.load(prefix)
        out = np.asarray(served(paddle.to_tensor(x))._data)
        assert (out.argmax(-1) == y).mean() >= 0.75

    def test_trainer_error_paths(self, tmp_path):
        lib = self._lib()
        assert lib.PD_Init() == 0
        assert not lib.PD_CreateTrainer(b"/nonexistent/m", b"adam", 1e-3,
                                        b"cross_entropy")
        paddle.seed(0)
        prefix = str(tmp_path / "m")
        paddle.jit.save(nn.Linear(4, 2), prefix)
        assert not lib.PD_CreateTrainer(prefix.encode(), b"nope", 1e-3,
                                        b"cross_entropy")
        assert b"optimizer" in lib.PD_GetLastError()
        h = lib.PD_CreateTrainer(prefix.encode(), b"sgd", 1e-3, b"mse")
        assert h, lib.PD_GetLastError().decode()
        bad_shape = (ctypes.c_int64 * 1)(-3)
        rc = lib.PD_TrainStepFloat(h, None, bad_shape, 1, None, bad_shape,
                                   1, 1)
        assert rc == -1
        lib.PD_DestroyTrainer(h)


class TestStandaloneCHost:
    """A REAL C host binary (gcc + libpython embed) drives the C ABI from a
    non-Python process — exercising PD_Init's GIL release (ADVICE r1 medium:
    PyEval_SaveThread) and a worker-thread call path like the Go client's
    goroutine migration."""

    C_SRC = r'''
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <pthread.h>

extern int PD_Init(void);
extern void* PD_CreatePredictor(const char*);
extern long long PD_PredictorRunFloat(void*, const float*, const long long*,
                                      int, float*, long long,
                                      long long*, int, int*);
extern void PD_DestroyPredictor(void*);
extern const char* PD_GetLastError(void);

static const char* g_prefix;
static int g_ok = 0;

static void* worker(void* arg) {
    /* a DIFFERENT OS thread than the one that ran PD_Init: deadlocks
       unless PD_Init released the GIL */
    void* p = PD_CreatePredictor(g_prefix);
    if (!p) { fprintf(stderr, "create: %s\n", PD_GetLastError()); return 0; }
    float in[8]; long long shape[2] = {2, 4};
    for (int i = 0; i < 8; ++i) in[i] = 1.0f;
    float out[64]; long long out_shape[8]; int out_ndim = 0;
    long long n = PD_PredictorRunFloat(p, in, shape, 2, out, 64,
                                       out_shape, 8, &out_ndim);
    if (n <= 0) { fprintf(stderr, "run: %s\n", PD_GetLastError()); return 0; }
    PD_DestroyPredictor(p);
    g_ok = 1;
    printf("C_HOST_OK n=%lld first=%f\n", n, out[0]);
    return 0;
}

int main(int argc, char** argv) {
    g_prefix = argv[1];
    if (PD_Init() != 0) { fprintf(stderr, "init failed\n"); return 1; }
    pthread_t t;
    pthread_create(&t, 0, worker, 0);
    pthread_join(t, 0);
    return g_ok ? 0 : 2;
}
'''

    TRAIN_C_SRC = r'''
#include <stdio.h>
#include <stdlib.h>

extern int PD_Init(void);
extern void* PD_CreateTrainer(const char*, const char*, double, const char*);
extern int PD_TrainStepFloat(void*, const float*, const long long*, int,
                             const void*, const long long*, int, int);
extern double PD_GetLoss(void*);
extern int PD_TrainerSave(void*, const char*);
extern void PD_DestroyTrainer(void*);
extern const char* PD_GetLastError(void);

/* deterministic LCG: the whole dataset is authored in C — no Python-side
   data path involved */
static unsigned long long lcg_state = 42;
static float lcg_uniform(void) {
    lcg_state = lcg_state * 6364136223846793005ULL + 1442695040888963407ULL;
    return (float)((lcg_state >> 33) & 0xFFFFFF) / (float)0xFFFFFF;
}

int main(int argc, char** argv) {
    const char* prefix = argv[1];
    if (PD_Init() != 0) { fprintf(stderr, "init failed\n"); return 1; }
    void* t = PD_CreateTrainer(prefix, "adam", 1e-2, "cross_entropy");
    if (!t) { fprintf(stderr, "create: %s\n", PD_GetLastError()); return 1; }

    enum { B = 8, C = 1, H = 28, W = 28, STEPS = 50 };
    static float x[B * C * H * W];
    static long long y[B];
    long long xs[4] = {B, C, H, W};
    long long ys[1] = {B};
    for (int i = 0; i < B * C * H * W; ++i) x[i] = lcg_uniform();
    for (int i = 0; i < B; ++i) y[i] = (long long)(lcg_uniform() * 10) % 10;

    double first = 0, last = 0;
    for (int s = 0; s < STEPS; ++s) {
        if (PD_TrainStepFloat(t, x, xs, 4, y, ys, 1, 0) != 0) {
            fprintf(stderr, "step %d: %s\n", s, PD_GetLastError());
            return 1;
        }
        last = PD_GetLoss(t);
        if (s == 0) first = last;
    }
    if (PD_TrainerSave(t, prefix) != 0) {
        fprintf(stderr, "save: %s\n", PD_GetLastError());
        return 1;
    }
    PD_DestroyTrainer(t);
    printf("C_TRAIN_OK first=%f last=%f\n", first, last);
    return (last < first * 0.5) ? 0 : 2;
}
'''

    def _compile_host(self, tmp_path, src_text, name):
        so = _build()
        csrc = str(tmp_path / f"{name}.c")
        with open(csrc, "w") as f:
            f.write(src_text)
        exe = str(tmp_path / name)
        # embed the SAME interpreter that runs pytest (a PATH python3-config
        # could belong to a different python whose site-packages lack jax)
        import sysconfig

        ver = sysconfig.get_config_var("VERSION")
        libdir = sysconfig.get_config_var("LIBDIR")
        ldflags = [f"-L{libdir}", f"-lpython{ver}", "-ldl", "-lm"]
        subprocess.run(
            ["gcc", "-O1", csrc, "-o", exe, so, *ldflags, "-lpthread",
             f"-Wl,-rpath,{os.path.dirname(so)}", f"-Wl,-rpath,{libdir}"],
            check=True, capture_output=True, text=True)
        return exe

    def _host_env(self):
        # the embedded interpreter runs no conftest: JAX_PLATFORMS pins
        # the CPU backend for it
        repo_root = os.path.dirname(os.path.dirname(paddle.__file__))
        pythonpath = repo_root + (
            os.pathsep + os.environ["PYTHONPATH"]
            if os.environ.get("PYTHONPATH") else "")
        return dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=pythonpath)

    def test_c_host_trains_lenet(self, tmp_path):
        """The reference's standalone native trainer, TPU-shaped: a pure C
        binary loads a jit.save'd LeNet, runs 50 real train steps (jitted
        fwd+bwd+Adam, params device-side), and the loss falls."""
        from paddle_tpu.vision.models import LeNet

        paddle.seed(0)
        prefix = str(tmp_path / "lenet_train")
        paddle.jit.save(LeNet(), prefix)   # pickled-layer artifact

        exe = self._compile_host(tmp_path, self.TRAIN_C_SRC, "train_host")
        res = subprocess.run([exe, prefix], capture_output=True, text=True,
                             timeout=600, env=self._host_env())
        assert res.returncode == 0, (res.stdout, res.stderr[-1500:])
        assert "C_TRAIN_OK" in res.stdout, res.stdout
        # the C-trained params landed in the artifact and serve in-process
        served = paddle.jit.load(prefix)
        out = served(paddle.to_tensor(
            np.zeros((1, 1, 28, 28), np.float32)))
        assert tuple(out.shape) == (1, 10)

    def test_c_host_binary(self, tmp_path):
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 3))
        net.eval()
        prefix = str(tmp_path / "chost_model")
        paddle.jit.save(net, prefix,
                        input_spec=[paddle.jit.InputSpec([2, 4], "float32")])

        exe = self._compile_host(tmp_path, self.C_SRC, "host")
        res = subprocess.run([exe, prefix], capture_output=True, text=True,
                             timeout=300, env=self._host_env())
        assert res.returncode == 0, (res.stdout, res.stderr[-1500:])
        assert "C_HOST_OK" in res.stdout, res.stdout
