"""Native serving shim: a C++ client (zero Python in its source) loads a
saved artifact through ``native/serving.cc``'s C ABI and runs inference
(ref ``inference/api/analysis_predictor.h:95`` + the ``capi_exp`` C API —
the SURVEY §7.4 serving deliverable)."""

import os
import subprocess
import sys
import sysconfig

import numpy as np
import pytest

import paddle_hackathon_tpu as paddle
from paddle_hackathon_tpu import nn
from paddle_hackathon_tpu.jit import InputSpec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "paddle_hackathon_tpu", "native", "serving.cc")
_GXX_S = 240  # one g++ -O2 run of serving.cc takes ~15 s alone

CLIENT_CC = r"""
// Pure-C++ serving client: no Python anywhere in this translation unit.
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <vector>

extern "C" {
int32_t pht_serving_init(const char* repo_dir);
void* pht_predictor_create(const char* model_path);
int64_t pht_predictor_run_f32(void*, const float*, const int64_t*, int32_t,
                              float*, int64_t, int64_t*, int32_t);
const char* pht_predictor_last_error();
void pht_predictor_destroy(void*);
}

int main(int argc, char** argv) {
  if (argc != 3) return 2;
  if (pht_serving_init(argv[1]) != 0) {
    std::fprintf(stderr, "init: %s\n", pht_predictor_last_error());
    return 3;
  }
  void* p = pht_predictor_create(argv[2]);
  if (!p) {
    std::fprintf(stderr, "create: %s\n", pht_predictor_last_error());
    return 4;
  }
  // 3x8 input: value (i*8+j)*0.1 - 1.0 (client and test agree on this)
  std::vector<float> in(24);
  for (int i = 0; i < 24; i++) in[i] = 0.1f * i - 1.0f;
  int64_t shape[2] = {3, 8};
  std::vector<float> out(64);
  int64_t out_shape[4] = {0, 0, 0, 0};
  int64_t n = pht_predictor_run_f32(p, in.data(), shape, 2, out.data(), 64,
                                    out_shape, 4);
  if (n < 0) {
    std::fprintf(stderr, "run: %s\n", pht_predictor_last_error());
    return 5;
  }
  std::printf("shape %lld %lld\n", (long long)out_shape[0],
              (long long)out_shape[1]);
  for (int64_t i = 0; i < n; i++) std::printf("%.6f\n", out[i]);
  // second run on the same handle (serving steady-state)
  int64_t n2 = pht_predictor_run_f32(p, in.data(), shape, 2, out.data(), 64,
                                     out_shape, 4);
  if (n2 != n) return 6;
  pht_predictor_destroy(p);
  return 0;
}
"""


GEN_CLIENT_CC = r"""
// Pure-C++ generation client: three OS threads call pht_engine_generate
// CONCURRENTLY on one engine (the continuous-batching contract — requests
// batch into shared device ticks instead of serializing).
#include <cstdint>
#include <cstdio>
#include <thread>
#include <vector>

extern "C" {
int32_t pht_serving_init(const char* repo_dir);
void* pht_engine_create(const char*, int32_t, int32_t, int32_t);
int64_t pht_engine_generate(void*, const int32_t*, int32_t, int32_t,
                            int32_t*, int64_t, double);
const char* pht_predictor_last_error();
void pht_engine_destroy(void*);
}

int main(int argc, char** argv) {
  if (argc != 3) return 2;
  if (pht_serving_init(argv[1]) != 0) {
    std::fprintf(stderr, "init: %s\n", pht_predictor_last_error());
    return 3;
  }
  void* eng = pht_engine_create(argv[2], 4, 64, 4);
  if (!eng) {
    std::fprintf(stderr, "create: %s\n", pht_predictor_last_error());
    return 4;
  }
  // prompts the python test reproduces: client k uses tokens
  // (7*k+1), (7*k+2), ... of length 5+k
  std::vector<std::vector<int32_t>> outs(3);
  std::vector<int64_t> ns(3, 0);
  std::vector<std::thread> threads;
  for (int k = 0; k < 3; k++) {
    threads.emplace_back([&, k] {
      std::vector<int32_t> prompt;
      for (int i = 0; i < 5 + k; i++) prompt.push_back(7 * k + 1 + i);
      outs[k].resize(64);
      // client 0 exercises timeout_s <= 0 == wait-forever (a raw 0.0
      // used to reach Event.wait(0) and time out immediately)
      ns[k] = pht_engine_generate(eng, prompt.data(),
                                  (int32_t)prompt.size(), 6,
                                  outs[k].data(), 64,
                                  k == 0 ? 0.0 : 300.0);
    });
  }
  for (auto& t : threads) t.join();
  for (int k = 0; k < 3; k++) {
    if (ns[k] < 0) {
      std::fprintf(stderr, "generate %d failed: %s\n", k,
                   pht_predictor_last_error());
      return 5;
    }
    std::printf("client %d:", k);
    for (int64_t i = 0; i < ns[k]; i++) std::printf(" %d", outs[k][i]);
    std::printf("\n");
  }
  pht_engine_destroy(eng);
  return 0;
}
"""


class _Net(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(8, 16)
        self.fc2 = nn.Linear(16, 4)

    def forward(self, x):
        return self.fc2(paddle.nn.functional.relu(self.fc1(x)))


@pytest.fixture(scope="module")
def native_bits(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("serving")
    # model artifact + expected output
    paddle.seed(0)
    net = _Net()
    net.eval()
    model = str(tmp / "net")
    paddle.jit.save(net, model, input_spec=[InputSpec([-1, 8], "float32")])
    x = (0.1 * np.arange(24, dtype=np.float32) - 1.0).reshape(3, 8)
    expect = np.asarray(net(paddle.to_tensor(x)).numpy())

    # build the shim + the pure-C++ client
    inc = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR") or "/usr/local/lib"
    pyver = f"python{sys.version_info.major}.{sys.version_info.minor}"
    so = str(tmp / "libphtserving.so")
    try:
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", SRC,
             f"-I{inc}", f"-L{libdir}", f"-l{pyver}",
             f"-Wl,-rpath,{libdir}", "-o", so],
            check=True, capture_output=True, text=True, timeout=_GXX_S)
    except (subprocess.CalledProcessError, FileNotFoundError) as e:
        pytest.skip(f"cannot build serving shim: {e}")
    client_src = tmp / "client.cc"
    client_src.write_text(CLIENT_CC)
    client = str(tmp / "client")
    subprocess.run(
        ["g++", "-O2", "-std=c++17", str(client_src), so,
         f"-Wl,-rpath,{os.path.dirname(so)}", f"-Wl,-rpath,{libdir}",
         "-o", client],
        check=True, capture_output=True, text=True, timeout=_GXX_S)
    return client, model + ".pdmodel", expect


def test_cpp_client_serves_saved_artifact(native_bits):
    client, model_path, expect = native_bits
    env = dict(os.environ)
    env["PHT_SERVING_PLATFORM"] = "cpu"  # hermetic: never reach for a chip
    out = subprocess.run([client, ROOT, model_path], capture_output=True,
                         text=True, timeout=300, env=env)
    assert out.returncode == 0, (out.returncode, out.stderr[-2000:])
    lines = out.stdout.strip().splitlines()
    assert lines[0].split() == ["shape", "3", "4"]
    got = np.asarray([float(v) for v in lines[1:]], np.float32).reshape(3, 4)
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-5)


@pytest.fixture(scope="module")
def gen_bits(tmp_path_factory, native_bits):
    """Generation artifact + concurrent C++ client (reuses the shim the
    predictor fixture built)."""
    client_bin, _, _ = native_bits
    so = os.path.join(os.path.dirname(client_bin), "libphtserving.so")
    tmp = tmp_path_factory.mktemp("gen_serving")
    import jax.numpy as jnp

    from paddle_hackathon_tpu.core.tensor import Tensor
    from paddle_hackathon_tpu.inference.serving import save_for_serving
    from paddle_hackathon_tpu.models.gpt import GPTConfig, GPTForCausalLM

    paddle.seed(3)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=2, max_position_embeddings=64,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
                    use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    mdir = str(tmp / "gptmodel")
    save_for_serving(model, mdir)
    # expected sequences for the client's 3 prompts (greedy)
    expects = []
    for k in range(3):
        prompt = np.arange(7 * k + 1, 7 * k + 1 + 5 + k, dtype=np.int32)
        full = np.asarray(model.generate(
            Tensor(jnp.asarray(prompt[None, :])), max_new_tokens=6,
            temperature=0.0).numpy())[0]
        expects.append(full)

    libdir = sysconfig.get_config_var("LIBDIR") or "/usr/local/lib"
    src = tmp / "gen_client.cc"
    src.write_text(GEN_CLIENT_CC)
    client = str(tmp / "gen_client")
    subprocess.run(
        ["g++", "-O2", "-std=c++17", str(src), so, "-pthread",
         f"-Wl,-rpath,{os.path.dirname(so)}", f"-Wl,-rpath,{libdir}",
         "-o", client],
        check=True, capture_output=True, text=True, timeout=_GXX_S)
    return client, mdir, expects


def test_cpp_concurrent_generation(gen_bits):
    """VERDICT r4 directive #2: concurrent pht_engine_generate calls from
    C++ threads produce exactly the single-request greedy sequences."""
    client, mdir, expects = gen_bits
    env = dict(os.environ)
    env["PHT_SERVING_PLATFORM"] = "cpu"
    out = subprocess.run([client, ROOT, mdir], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, (out.returncode, out.stderr[-2000:])
    got = {}
    for line in out.stdout.strip().splitlines():
        head, _, toks = line.partition(":")
        got[int(head.split()[1])] = np.asarray(
            [int(t) for t in toks.split()], np.int32)
    for k, exp in enumerate(expects):
        np.testing.assert_array_equal(got[k], exp)


def test_error_paths(native_bits):
    client, model_path, _ = native_bits
    env = dict(os.environ)
    env["PHT_SERVING_PLATFORM"] = "cpu"
    out = subprocess.run([client, ROOT, model_path + ".does-not-exist"],
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert out.returncode == 4          # create failed, error reported
    assert out.stderr.strip()           # ...with a message
