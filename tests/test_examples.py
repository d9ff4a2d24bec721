"""Smoke tests for the example/ tree (SURVEY §2.8 capability checklist).

Runs a fast subset end-to-end as subprocesses the way a user would, on CPU
with tiny synthetic data (each example synthesizes its own dataset).
"""

import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
EXAMPLE = os.path.join(ROOT, "example")


def _run(relpath, *args, timeout=300, env_extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    if env_extra:
        env.update(env_extra)
    code = ("import jax; jax.config.update('jax_platforms','cpu');"
            "import sys, runpy; sys.argv=[sys.argv[1]]+sys.argv[2:];"
            "runpy.run_path(sys.argv[0], run_name='__main__')")
    proc = subprocess.run(
        [sys.executable, "-c", code, os.path.join(EXAMPLE, relpath)]
        + list(args),
        capture_output=True, text=True, timeout=timeout, cwd=ROOT, env=env)
    assert proc.returncode == 0, \
        "%s failed:\n%s\n%s" % (relpath, proc.stdout[-2000:],
                                proc.stderr[-2000:])
    return proc.stdout + proc.stderr


def test_train_mnist(tmp_path):
    out = _run("image-classification/train_mnist.py", "--num-epochs", "1",
               "--num-examples", "512", "--data-dir", str(tmp_path))
    assert "Validation-accuracy" in out


def test_serving_example(tmp_path):
    out = _run("serving/serve_mlp.py")
    assert "serving-demo-ok" in out
    assert "0 recompiles" in out


def test_lm_serving_example(tmp_path):
    out = _run("serving/serve_lm.py")
    assert "lm-serving-demo-ok" in out
    assert "traffic phase: 0 recompiles" in out


def test_custom_op_example(tmp_path):
    out = _run("numpy-ops/custom_softmax.py", "--num-epochs", "2")
    assert "Train-accuracy" in out


def test_multi_task(tmp_path):
    out = _run("multi-task/multitask.py", "--num-epochs", "2")
    assert "task1-acc" in out


def test_rl_actor_critic(tmp_path):
    out = _run("reinforcement-learning/parallel_actor_critic/train.py",
               "--num-updates", "60")
    # the bandit must be essentially solved (random = 0.25)
    final = float(out.strip().rsplit("final avg reward ", 1)[1].split()[0])
    assert final > 0.8


def test_lstm_bucketing(tmp_path):
    out = _run("rnn/lstm_bucketing.py", "--num-epochs", "1",
               "--num-hidden", "16", "--num-embed", "16",
               "--num-sentences", "60", "--vocab-size", "20",
               "--batch-size", "8", "--buckets", "10,20")
    assert "Perplexity" in out or "perplexity" in out.lower()


def test_gan_dcgan(tmp_path):
    _run("gan/dcgan.py", "--num-steps", "2", "--batch-size", "4",
         "--ngf", "8", "--ndf", "8", "--z-dim", "8")


def test_rcnn_train(tmp_path):
    _run("rcnn/train.py", "--num-steps", "2", "--image-size", "64",
         "--num-classes", "3")


def test_bi_lstm_sort(tmp_path):
    _run("bi-lstm-sort/lstm_sort.py", "--num-epochs", "1",
         "--seq-len", "4", "--vocab", "8", "--num-hidden", "12",
         "--batch-size", "8", "--num-examples", "64")


def test_nce_lm(tmp_path):
    _run("nce-loss/nce_lm.py", "--num-steps", "4", "--vocab-size", "40",
         "--num-hidden", "12", "--batch-size", "8")


def test_fcn_xs(tmp_path):
    _run("fcn-xs/fcn_xs.py", "--num-epochs", "1", "--side", "32",
         "--batch-size", "2")


def test_autoencoder(tmp_path):
    _run("autoencoder/autoencoder.py", "--num-epochs", "1",
         "--dims", "32,16", "--batch-size", "64")


def test_stochastic_depth(tmp_path):
    _run("stochastic-depth/sd_module.py", "--num-steps", "3",
         "--num-blocks", "2", "--batch-size", "4")


def test_text_cnn(tmp_path):
    _run("cnn_text_classification/text_cnn.py", "--num-epochs", "1",
         "--seq-len", "8", "--vocab", "30", "--embed-dim", "8",
         "--num-filter", "4", "--batch-size", "8",
         "--num-examples", "64")


def test_neural_style(tmp_path):
    _run("neural-style/neural_style.py", "--num-steps", "2",
         "--size", "48")


def test_long_context_lm(tmp_path):
    """Beyond-reference long-context demo: causal transformer LM via the
    MultiHeadAttention op learns the shift task (perplexity trending to
    1), and ring attention over the 8-device mesh matches the
    single-device computation.  Every update compiles Adam's step anew
    (ROADMAP D17), so the run's time is its count of updates, and the
    perplexity falls off a cliff between the 60th and the 90th: at 3.5
    times the example's learning rate 64 updates end at 7.3 (2.8 after
    80; 10.2 and 8.3 at 3e-2 and 4e-2) where the untaught model stays
    at 31."""
    out = _run("long-context/train_lm.py", "--ring", "--epochs", "4",
               "--lr", "3.5e-2", "--ppl-limit", "16",
               env_extra={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=8"})
    assert "LONG CONTEXT EXAMPLE OK" in out
    # the parity check must have run MULTI-way (a 1-way ring compares
    # the code path to itself)
    assert "ring (8-way)" in out, out[-500:]
