#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the main path once, in ONE process, through
the entry points a user calls, on one TPU chip:

* **fit** — what ``example/image-classification/train_imagenet.py
  --benchmark 1`` runs: ``common/fit.py:fit`` -> ``Module.fit`` on
  ``mx.tpu(0)``, ResNet-50 at 224x224, batch 128 float32, SGD+momentum,
  device metrics, the synthetic iterator's 100 steps;
* **bulk** — two ``Module.run_bulk`` bulks of ten full training steps at
  b128 bf16, each one XLA computation (the ``train_sgd_scan`` executor
  kind, and the only bfloat16 training path there is);
* **serve** — ``serving.save_model`` -> ``ModelRegistry`` ->
  ``ServingHTTPServer`` with ResNet-50 at its declared buckets, ``/predict``
  over HTTP against ``Module.predict`` on the same rows, ``/healthz`` and
  ``/metrics``;
* **decode** — ``DecodeEngine`` behind ``/generate`` with the repo's LM at a
  lane-filling shape (vocab 50,304, embed 768, 12 heads, 12 layers, ffn
  3072, ``max_len`` 1024 — a shape, not a published model) under both
  ``kv_layout`` values, greedy tokens against ``forward_logits``.

Depth is never cut and the weights are random, made from ``--seed``.  Each
phase prints one JSON line (seconds, compiles, persistent-cache hits and
misses, dtypes, peak device bytes, the lowering each Pallas-capable op
took); the LAST line of stdout is the verdict,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
Anything that raises — a phase, a missing chip, a kernel that did not lower
— ends the run with ``"ok": false`` and a non-zero exit code.  Times are
set-up information, not metrics.

``--four-chips`` (run by the builder on the four-chip host; the driver never
passes it) runs ONLY what exists across chips: ``Module.fit(kvstore='mesh')``
on a 4-device mesh against the same seed on one device, and a
``ReplicaPool`` of four decode replicas, one per device.

A chip belongs to one process: everything here, the HTTP servers included,
runs in this one, and the only children are the ``g++`` builds of the native
host library, which never touch JAX.  Built artefacts (``mxnet_tpu/native/
*.so``, ``cpp_package/build/``) are removed before the first import so the
run builds from what git commits; nothing under ``/tmp``, ``$HOME`` or an
earlier run's output is read.
"""

import argparse
import glob
import json
import os
import shutil
import sys
import threading
import time
import traceback
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "example", "image-classification"))

#: where the serve phase publishes its model directory: inside the
#: checkout, listed in .gitignore, removed when the phase ends
WORK_DIR = os.path.join(HERE, ".chip_smoke_work")

# The sizes the chip runs are the phase functions' defaults: ResNet-50
# (the model of BASELINE.md and of every driver record) at 224x224 and
# batch 128, and the LM below.  The arguments exist for the CPU-sized
# tests (tests/test_chip_smoke.py).
#: batch buckets the served ResNet-50 declares (serving.save_model default)
SERVE_BUCKETS = (1, 8, 32)
#: the served LM: (vocab, embed, heads, layers, ffn, max_len)
LM_SHAPE = (50304, 768, 12, 12, 3072, 1024)

#: /predict over HTTP against Module.predict on the same rows, as the
#: largest difference over the largest reference magnitude: both are
#: float32 programs on the same device that differ only in batch size, so
#: they agree to accumulation-order noise; 1e-2 is one bf16 pass's worth
PREDICT_REL_TOL = 1e-2
#: greedy decode against the float32-precision forward_logits argmax.  The
#: engine's step multiplies at the device's default precision (bf16 passes
#: on a TPU) in shapes the reference does not use, which moves a logit by
#: a few hundredths at this width; a token that differs from the
#: reference's argmax counts as a failure only where the reference
#: separates the two candidates by more than this many logits
NEAR_TIE_LOGITS = 0.25
#: flash attention kernel against the quadratic reference, bf16 inputs
FLASH_TOL = 5e-2
#: four chips: per-step loss of the mesh fit against the one-device fit.
#: Same seed, same global batch; the mesh sums per-device partial
#: gradients in another order and its BatchNorm statistics are the same
#: global-batch reductions split four ways, so the two runs differ by
#: float32 summation order, which five steps of SGD then amplify.  The
#: learning rate is a tenth of train_imagenet.py's so that they amplify
#: it gently: this compares two programs, it does not train
MESH_LOSS_RTOL = 2e-2
MESH_FIT_LR = 0.01


def emit(obj):
    print(json.dumps(obj), flush=True)


def remove_built_artefacts():
    """Delete what a build leaves in the checkout, so this run builds the
    native library from src/*.cc as a fresh clone would."""
    for so in glob.glob(os.path.join(HERE, "mxnet_tpu", "native", "*.so")):
        os.unlink(so)
    shutil.rmtree(os.path.join(HERE, "cpp_package", "build"),
                  ignore_errors=True)


def device_fields():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _kernel_paths():
    from mxnet_tpu import telemetry

    return dict(telemetry.snapshot()["counters"].get("ops.kernel_path", {}))


class Accounting:
    """Per-phase bookkeeping: wall seconds, executor/engine programs built
    and their first-call seconds (``xla.compile.*``), persistent-cache
    hits and misses (every XLA compile consults the cache, so their sum is
    the number of XLA compiles), peak device bytes, and the lowering each
    Pallas-capable op took during the phase."""

    def __init__(self, name, device):
        self.name = name
        self.device = device

    def __enter__(self):
        from mxnet_tpu import compile_cache, telemetry

        self._t0 = time.time()
        self._built = telemetry.counter_total("xla.compile.count")
        self._secs = telemetry.counter_total("xla.compile.seconds")
        self._cc = compile_cache.stats()
        self._paths = _kernel_paths()
        return self

    def __exit__(self, *exc):
        return False

    def result(self, **fields):
        from mxnet_tpu import compile_cache, telemetry

        cc = compile_cache.stats()
        paths = _kernel_paths()
        stats = self.device.memory_stats() or {}
        out = {
            "phase": self.name,
            "seconds": round(time.time() - self._t0, 2),
            "programs_built": int(
                telemetry.counter_total("xla.compile.count") - self._built),
            "first_call_seconds": round(
                telemetry.counter_total("xla.compile.seconds")
                - self._secs, 2),
            "persistent_cache": {
                "dir": cc["dir"],
                "hits": cc["hits"] - self._cc["hits"],
                "misses": cc["misses"] - self._cc["misses"]},
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "kernel_paths": {k: v - self._paths.get(k, 0)
                             for k, v in paths.items()
                             if v != self._paths.get(k, 0)},
        }
        out.update(fields)
        return out


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _placement(arrays, platform):
    """dtypes of ``arrays`` (jax arrays), after checking that every one
    lives on devices of ``platform`` only."""
    dtypes = set()
    for a in arrays:
        plats = {d.platform for d in a.devices()}
        _check(plats == {platform},
               "array %s%s lives on %s, expected %s"
               % (a.dtype, a.shape, sorted(plats), platform))
        dtypes.add(a.dtype.name)
    return sorted(dtypes)


def _module_arrays(mod):
    ex = mod._exec
    arrays = [a._jx for a in ex.arg_dict.values()]
    arrays += [a._jx for a in ex.aux_dict.values()]
    arrays += [o._jx for o in ex.outputs]
    if mod._updater is not None:
        arrays += [s._jx for s in mod._updater.states.values()
                   if s is not None]
    return arrays


def _all_finite(values):
    import numpy as np

    return bool(np.all(np.isfinite(np.asarray(values, np.float64))))


# -- phase: fit ---------------------------------------------------------------
class _StepRecorder:
    """batch-end callback: per-step cross-entropy, recovered from the
    epoch's running mean (the metric is left alone, so the Speedometer
    beside it logs what it always logs), and one named parameter as it
    stood after the first step."""

    def __init__(self, param_name):
        self.param_name = param_name
        self.losses = []
        self.first = None
        self._sum = 0.0

    def __call__(self, param):
        import numpy as np

        mean = dict(param.eval_metric.get_name_value())["cross-entropy"]
        total = float(mean) * (len(self.losses) + 1)
        self.losses.append(total - self._sum)
        self._sum = total
        if self.first is None:
            mod = param.locals["self"]
            self.first = np.asarray(
                mod._exec.arg_dict[self.param_name]._jx, np.float32)


def phase_fit(ctx, num_layers=50, image_shape=(3, 224, 224),
              num_classes=1000, batch=128, seed=0, data_loader=None):
    """``train_imagenet.py --benchmark 1``, argument for argument
    (``data_loader`` is that script's ``data.get_rec_iter`` unless a test
    hands in a shorter synthetic epoch)."""
    import numpy as np

    import mxnet_tpu as mx
    from common import data as ex_data
    from common import fit as ex_fit
    from mxnet_tpu import models

    parser = argparse.ArgumentParser()
    ex_fit.add_fit_args(parser)
    ex_data.add_data_args(parser)
    ex_data.add_data_aug_args(parser)
    # train_imagenet.py's own defaults
    parser.set_defaults(network="resnet", num_epochs=1, lr=0.1,
                        lr_step_epochs="30,60", num_examples=1024)
    shape_arg = ",".join(str(d) for d in image_shape)
    args = parser.parse_args([
        "--benchmark", "1", "--num-layers", str(num_layers),
        "--batch-size", str(batch), "--image-shape", shape_arg,
        "--num-classes", str(num_classes)])
    sym = models.get_symbol(args.network, num_classes=args.num_classes,
                            num_layers=args.num_layers,
                            image_shape=args.image_shape)
    recorder = _StepRecorder("fc1_weight")
    mx.random.seed(seed)    # the initializers draw from it
    with Accounting("fit", ctx.jax_device()) as acct, ctx:
        mod = ex_fit.fit(args, sym, data_loader or ex_data.get_rec_iter,
                         extra_metrics=[mx.metric.CrossEntropy()],
                         extra_batch_end_callbacks=[recorder])
        _check(mod._context[0] == ctx,
               "fit chose %r, expected %r" % (mod._context[0], ctx))
        last = np.asarray(mod._exec.arg_dict["fc1_weight"]._jx, np.float32)
        _check(len(recorder.losses) >= 5,
               "only %d steps ran" % len(recorder.losses))
        _check(_all_finite(recorder.losses),
               "non-finite loss: %r" % recorder.losses)
        _check(_all_finite(last) and not np.array_equal(recorder.first, last),
               "fc1_weight did not change between step 1 and the last step")
        dtypes = _placement(_module_arrays(mod), ctx.platform)
        return acct.result(
            model="resnet-%d %s b%d" % (num_layers, shape_arg, batch),
            steps=len(recorder.losses),
            loss_first=round(recorder.losses[0], 4),
            loss_last=round(recorder.losses[-1], 4),
            param_changed="fc1_weight", dtypes=dtypes,
            arrays_on=ctx.platform)


# -- phase: bulk --------------------------------------------------------------
def phase_bulk(ctx, num_layers=50, image_shape=(3, 224, 224),
               num_classes=1000, batch=128, bulk=10, dtype="bfloat16",
               seed=0):
    """Full training steps (forward, backward, SGD with momentum) through
    ``Module.run_bulk``: one bulk of ``bulk`` steps scanned inside one XLA
    computation, and one with the per-step outputs kept, for the losses."""
    # fwd+bwd+update as ONE XLA dispatch with donated param buffers
    os.environ.setdefault("MXNET_FUSE_TRAIN_STEP", "1")
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import io as mxio
    from mxnet_tpu.models import resnet

    mx.random.seed(seed)
    data_shape = (batch,) + tuple(image_shape)

    def batch_of(data, label):
        return mxio.DataBatch(
            data=[mx.nd.array(data.astype(np.float32), ctx=ctx, dtype=dtype)],
            label=[mx.nd.array(label.astype(np.float32), ctx=ctx)])

    with Accounting("bulk", ctx.jax_device()) as acct:
        net = resnet.get_symbol(num_classes=num_classes,
                                num_layers=num_layers,
                                image_shape=tuple(image_shape))
        rs = np.random.RandomState(seed)
        warm = [batch_of(rs.rand(*data_shape),
                         rs.randint(0, num_classes, batch))
                for _ in range(bulk)]
        mod = mx.mod.Module(net, context=ctx)
        mod.bind(data_shapes=[("data", data_shape)],
                 label_shapes=[("softmax_label", (batch,))])
        mod.init_params(mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2))
        ex = mod._exec
        # bf16 params/activations; BatchNorm stats stay f32 inside the op.
        # Module has no dtype argument: this in-place cast after init_params
        # is the only bf16 training path there is
        if dtype != "float32":
            for n, a in ex.arg_dict.items():
                if n != "softmax_label":
                    a._jx = a._jx.astype(dtype)
        mod.init_optimizer(kvstore=None, optimizer="sgd",
                           optimizer_params={"learning_rate": 0.05,
                                             "momentum": 0.9, "wd": 1e-4})
        first = np.asarray(ex.arg_dict["fc1_weight"]._jx, np.float32)
        mod.run_bulk(warm)
        # a 1-element host read of a just-updated param is the cheap TRUE
        # device barrier (reading the whole buffer would copy MBs to the
        # host); the last step's update depends on every step before it
        np.asarray(ex.arg_dict["conv0_weight"]._jx.reshape(-1)[:1])
        kinds = {k[0][0] for k in ex._fns if isinstance(k[0], tuple)}
        _check("train_sgd_scan" in kinds,
               "run_bulk did not take the train_sgd_scan kind: %r" % kinds)
        # a second bulk with the per-step outputs stacked:
        # (K, batch, classes) softmax rows against the labels kept here
        rs = np.random.RandomState(seed + 1)
        labels = [rs.randint(0, num_classes, batch) for _ in range(bulk)]
        (probs,) = mod.run_bulk(
            [batch_of(rs.rand(*data_shape), lab) for lab in labels],
            return_outputs=True)
        probs = np.asarray(probs, np.float32)
        losses = [float(-np.log(p[np.arange(batch), lab] + 1e-8).mean())
                  for p, lab in zip(probs, labels)]
        last = np.asarray(ex.arg_dict["fc1_weight"]._jx, np.float32)
        _check(_all_finite(losses), "non-finite loss: %r" % losses)
        _check(_all_finite(last) and not np.array_equal(first, last),
               "fc1_weight did not change over two bulks")
        dtypes = _placement(_module_arrays(mod), ctx.platform)
        _check(ex.arg_dict["fc1_weight"]._jx.dtype.name == dtype,
               "parameters are %s, expected %s"
               % (ex.arg_dict["fc1_weight"]._jx.dtype.name, dtype))
        return acct.result(
            model="resnet-%d b%d %s" % (num_layers, batch, dtype),
            executor_kinds=sorted(kinds), steps=2 * bulk,
            loss_first=round(losses[0], 4), loss_last=round(losses[-1], 4),
            param_changed="fc1_weight", dtypes=dtypes,
            arrays_on=ctx.platform)


# -- HTTP helpers -------------------------------------------------------------
def _lm_config(lm_shape):
    """``LMConfig`` of ``(vocab, embed, heads, layers, ffn, max_len)`` with
    an unreachable EOS (``eos_id == vocab``), so every session runs its
    full length."""
    from mxnet_tpu.models import transformer_lm as tlm

    return tlm.LMConfig(*lm_shape, eos_id=lm_shape[0])


def _decode_step_builds():
    from mxnet_tpu import telemetry

    return telemetry.snapshot()["counters"].get(
        "xla.compile.count", {}).get("kind=decode_step", 0)


def _http(url, payload=None, timeout=600):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        body = resp.read()
        ctype = resp.headers.get("Content-Type", "")
    return json.loads(body) if ctype.startswith("application/json") \
        else body.decode()


# -- phase: serve -------------------------------------------------------------
def phase_serve(ctx, num_layers=50, image_shape=(3, 224, 224),
                num_classes=1000, buckets=SERVE_BUCKETS,
                request_rows=(1, 3, 2), seed=0, work_dir=WORK_DIR):
    """Publish ResNet-50 under ``work_dir`` (removed again), load it
    through the registry, answer /predict over HTTP, and hold the
    answers to Module.predict.

    What is served is the network up to its logits (``fc1_output``): with
    random weights the softmax after them saturates to exact one-hot
    rows, which would agree whatever the convolutions computed."""
    import io as _io

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import models, serving

    with Accounting("serve", ctx.jax_device()) as acct:
        sym = models.get_symbol("resnet", num_layers=num_layers,
                                num_classes=num_classes,
                                image_shape=image_shape
                                ).get_internals()["fc1_output"]
        n_rows = sum(request_rows)
        mx.random.seed(seed)
        ref_mod = mx.mod.Module(sym, context=ctx, label_names=None)
        ref_mod.bind(for_training=False,
                     data_shapes=[("data", (n_rows,) + tuple(image_shape))])
        ref_mod.init_params(mx.init.Xavier(rnd_type="gaussian",
                                           factor_type="in", magnitude=2))
        arg_params, aux_params = ref_mod.get_params()
        blob = _io.BytesIO()
        np.savez(blob, **{k: v.asnumpy() for k, v in
                          list(arg_params.items())
                          + list(aux_params.items())})
        # rows on a 1/256 grid: exact in float32 and short in JSON
        rs = np.random.RandomState(seed)
        rows = (rs.randint(0, 256, (n_rows,) + tuple(image_shape))
                / 256.0).astype(np.float32)
        ref = ref_mod.predict(mx.io.NDArrayIter(rows, batch_size=n_rows))
        _placement([ref._jx], ctx.platform)
        ref = ref.asnumpy()
        scale = float(np.abs(ref).max())
        _check(_all_finite(ref) and scale > 0
               and not np.allclose(ref[0], ref[1]),
               "Module.predict gave a degenerate reference")

        model_dir = os.path.join(work_dir, "resnet")
        reg = serving.ModelRegistry(ctx=ctx)
        srv = None
        try:
            serving.save_model(model_dir, sym, blob.getvalue(), image_shape,
                               buckets=buckets, name="resnet")
            served = reg.load_dir(model_dir)
            _placement([a._jx for a in
                        served._pred._exec.arg_dict.values()], ctx.platform)
            srv = serving.ServingHTTPServer(reg, port=0).start()
            outs, start = [], 0
            for n in request_rows:
                reply = _http(srv.url + "/predict",
                              {"model": "resnet",
                               "data": rows[start:start + n].tolist()})
                _check(reply["shape"] == [n, num_classes],
                       "/predict answered shape %r" % reply["shape"])
                outs.append(np.asarray(reply["output"], np.float32))
                start += n
            got = np.concatenate(outs)
            health = _http(srv.url + "/healthz")
            metrics = _http(srv.url + "/metrics")
        finally:
            if srv is not None:
                srv.stop()
            reg.close()
            shutil.rmtree(work_dir, ignore_errors=True)
        _check(_all_finite(got), "non-finite /predict output")
        err = float(np.abs(got - ref).max()) / scale
        _check(err <= PREDICT_REL_TOL,
               "/predict differs from Module.predict by %.3g of the "
               "largest logit (bound %g)" % (err, PREDICT_REL_TOL))
        _check((got.argmax(1) == ref.argmax(1)).all(),
               "/predict and Module.predict disagree on a row's class")
        _check(health.get("status") == "ok",
               "/healthz said %r" % health.get("status"))
        _check("serving_http_requests" in metrics,
               "/metrics carries no serving counters")
        return acct.result(
            model="resnet-%d logits" % num_layers, buckets=list(buckets),
            requests=len(request_rows), rows=n_rows,
            rel_diff_vs_module_predict=err, rel_tolerance=PREDICT_REL_TOL,
            largest_logit=scale, healthz=health.get("status"),
            dtypes=["float32"], arrays_on=ctx.platform)


# -- phase: decode ------------------------------------------------------------
def _reference_gaps(cfg, params, transcripts, prompt_lens):
    """For each generated position of each transcript: whether the token
    there is the argmax of ``forward_logits`` on the tokens before it,
    and, where it is not, by how many logits the reference prefers its
    own.  One padded batch, float32 matmuls (precision ``highest``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.models import transformer_lm as tlm

    width = max(len(t) for t in transcripts)
    toks = np.zeros((len(transcripts), width), np.int32)
    for i, t in enumerate(transcripts):
        toks[i, :len(t)] = t

    @jax.jit
    def judge(params, toks):
        with jax.default_matmul_precision("highest"):
            logits = tlm.forward_logits(cfg, params, toks[:, :-1])
        best = jnp.argmax(logits, axis=-1)
        nxt = toks[:, 1:]
        took = jnp.take_along_axis(logits, nxt[..., None], axis=-1)[..., 0]
        return best, jnp.max(logits, axis=-1) - took

    best, gap = (np.asarray(a) for a in judge(params, jnp.asarray(toks)))
    mismatches, positions = [], 0
    for i, (t, p) in enumerate(zip(transcripts, prompt_lens)):
        for pos in range(p, len(t)):      # token t[pos] from logits[pos-1]
            positions += 1
            if int(best[i, pos - 1]) != int(t[pos]):
                mismatches.append(float(gap[i, pos - 1]))
    return positions, mismatches


def _flash_kernel_check(device, heads, head_dim, lengths, seed):
    """``flash_attention`` at the prefill shapes ``(1, heads, P,
    head_dim)``, through its public entry so the dispatch that users get
    decides: on a TPU it must lower to ``tpu_custom_call`` and agree with
    the quadratic reference; elsewhere the counted reason says why the
    XLA path ran.  (The prefill of the LM this smoke serves,
    ``transformer_lm``, still scores with a plain einsum, as do
    ``exaone_moe``'s and ``sambay``'s; ``models/smallthinker.py``'s calls
    this kernel, with a window and grouped K/V heads, for every layer.
    ROADMAP S4's third step moves the others.)"""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import attention

    out = {}
    rs = np.random.RandomState(seed)
    for p in lengths:
        q, k, v = (jax.device_put(
            jnp.asarray(rs.normal(0, 1, (1, heads, p, head_dim)),
                        jnp.bfloat16), device) for _ in range(3))
        fn = jax.jit(lambda q, k, v: attention.flash_attention(
            q, k, v, causal=True))
        lowered_to_kernel = "tpu_custom_call" in \
            fn.lower(q, k, v).compile().as_text()
        got = np.asarray(fn(q, k, v), np.float32)
        want = np.asarray(attention._attn_reference(q, k, v, causal=True),
                          np.float32)
        err = float(np.abs(got - want).max())
        _check(err <= FLASH_TOL,
               "flash_attention P=%d differs from the reference by %.3g"
               % (p, err))
        if device.platform == "tpu":
            _check(lowered_to_kernel,
                   "flash_attention P=%d did not lower to tpu_custom_call"
                   % p)
        out["flash_attention(1,%d,%d,%d)" % (heads, p, head_dim)] = {
            "tpu_custom_call": lowered_to_kernel, "max_abs_err": err}
    return out


def phase_decode(device, lm_shape=LM_SHAPE, slots=8,
                 prefill_buckets=(32, 128, 512),
                 prompt_lens=(5, 24, 100, 300, 500), max_new_tokens=16,
                 seed=0):
    """The LM behind /generate under both KV layouts: greedy tokens
    against forward_logits, one decode-step program per layout, dense
    and paged token for token."""
    import jax
    import numpy as np

    from mxnet_tpu import compile_cache, serving
    from mxnet_tpu.models import transformer_lm as tlm

    cfg = _lm_config(lm_shape)
    vocab, embed, heads = cfg.vocab, cfg.embed, cfg.heads
    with Accounting("decode", device) as acct:
        params = jax.device_put(tlm.init_params(cfg, seed=seed), device)
        rs = np.random.RandomState(seed)
        prompts = [[int(t) for t in rs.randint(0, vocab, size=n)]
                   for n in prompt_lens]
        tokens, step_compiles, compiles_under_traffic = {}, {}, {}
        for layout in ("dense", "paged"):
            name = "lm-%s" % layout
            built0 = _decode_step_builds()
            pool = serving.lm_pool(
                cfg, params, n_replicas=1, devices=[device], name=name,
                engine_opts={"slots": slots,
                             "prefill_buckets": prefill_buckets,
                             "kv_layout": layout})
            reg = serving.ModelRegistry()
            srv = None
            try:
                reg.register(name, pool, version=1)
                srv = serving.ServingHTTPServer(reg, port=0).start()
                engine = pool.replicas[0].engine
                _placement(jax.tree_util.tree_leaves(engine._params),
                           device.platform)
                cc0 = compile_cache.stats()
                replies = [None] * len(prompts)

                def ask(i):
                    replies[i] = _http(
                        srv.url + "/generate",
                        {"model": name, "prompt": prompts[i],
                         "max_new_tokens": max_new_tokens,
                         "temperature": 0.0, "timeout_s": 600})

                threads = [threading.Thread(target=ask, args=(i,))
                           for i in range(len(prompts))]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(900)
                _check(all(r is not None and "tokens" in r
                           for r in replies),
                       "%s: a /generate session failed: %r"
                       % (layout, replies))
                cc1 = compile_cache.stats()
            finally:
                if srv is not None:
                    srv.stop()
                reg.close()
            tokens[layout] = [r["tokens"] for r in replies]
            _check(all(len(t) == max_new_tokens for t in tokens[layout]),
                   "%s: a session stopped short" % layout)
            step_compiles[layout] = _decode_step_builds() - built0
            compiles_under_traffic[layout] = \
                (cc1["hits"] + cc1["misses"]) - (cc0["hits"] + cc0["misses"])
            _check(step_compiles[layout] == 1,
                   "%s: %d decode-step programs were built, expected 1"
                   % (layout, step_compiles[layout]))
            _check(compiles_under_traffic[layout] == 0,
                   "%s: %d XLA compiles happened while serving"
                   % (layout, compiles_under_traffic[layout]))
        _check(tokens["dense"] == tokens["paged"],
               "dense and paged layouts disagree: %r vs %r"
               % (tokens["dense"], tokens["paged"]))
        transcripts = [p + t for p, t in zip(prompts, tokens["dense"])]
        positions, mismatches = _reference_gaps(
            cfg, params, transcripts, [len(p) for p in prompts])
        beyond = [g for g in mismatches if g > NEAR_TIE_LOGITS]
        _check(not beyond,
               "greedy tokens differ from the forward_logits argmax where "
               "the reference separates them by %r logits (bound %.2f)"
               % (beyond, NEAR_TIE_LOGITS))
        kernels = _flash_kernel_check(
            device, heads, embed // heads,
            [b for b in prefill_buckets if b % 8 == 0], seed)
        return acct.result(
            model="lm v%d e%d h%d l%d f%d max_len %d" % lm_shape,
            layouts=["dense", "paged"], sessions=len(prompts),
            prompt_lens=list(prompt_lens), max_new_tokens=max_new_tokens,
            decode_step_programs=step_compiles,
            compiles_while_serving=compiles_under_traffic,
            dense_equals_paged=True,
            greedy_vs_forward_logits={
                "positions": positions,
                "argmax_matches": positions - len(mismatches),
                "near_tie_mismatches": len(mismatches),
                "largest_gap_at_a_mismatch":
                    max(mismatches) if mismatches else 0.0,
                "near_tie_bound_logits": NEAR_TIE_LOGITS},
            pallas=kernels, dtypes=["float32"], arrays_on=device.platform)


# -- four chips ---------------------------------------------------------------
def _fit_losses(ctx, kvstore, num_layers, image_shape, num_classes, batch,
                steps, seed):
    """``Module.fit`` for ``steps`` steps from ``seed``; returns the module
    and the per-step cross-entropy."""
    import numpy as np

    import mxnet_tpu as mx
    from common import data as ex_data
    from mxnet_tpu import models

    mx.random.seed(seed)
    np.random.seed(seed)
    sym = models.get_symbol("resnet", num_layers=num_layers,
                            num_classes=num_classes,
                            image_shape=image_shape)
    with ctx:
        train = ex_data.SyntheticDataIter(
            num_classes, (batch,) + tuple(image_shape), steps)
    recorder = _StepRecorder("fc1_weight")
    mod = mx.mod.Module(sym, context=ctx)
    mod.fit(train, num_epoch=1, kvstore=kvstore, optimizer="sgd",
            optimizer_params={"learning_rate": MESH_FIT_LR,
                              "momentum": 0.9, "wd": 1e-4},
            initializer=mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2),
            eval_metric=[mx.metric.CrossEntropy()],
            batch_end_callback=[recorder])
    return mod, recorder.losses


def _collective_group_sizes(hlo_text):
    """Replica-group size of every all-reduce / reduce-scatter in compiled
    HLO text, from either spelling of ``replica_groups``: explicit
    ``{{0,1,2,3}}`` or iota ``[groups,size]<=[n]``."""
    import re

    sizes = []
    for line in hlo_text.splitlines():
        if not re.search(r"\b(all-reduce|reduce-scatter)(-start)?\(", line):
            continue
        m = re.search(r"replica_groups=\{\{([0-9,]*)\}", line)
        if m:
            sizes.append(len(m.group(1).split(",")))
            continue
        m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", line)
        if m:
            sizes.append(int(m.group(2)))
    return sizes


def phase_mesh_fit(ctx, n_devices=4, num_layers=50,
                   image_shape=(3, 224, 224), num_classes=1000, batch=128,
                   steps=5, seed=0):
    """``fit(kvstore='mesh')`` — the mesh ``kvstore_mesh.default_mesh``
    builds from ``jax.devices()`` — against the same seed on one device."""
    import jax
    import numpy as np

    devices = jax.devices()[:n_devices]
    with Accounting("mesh_fit", devices[0]) as acct:
        one_mod, one = _fit_losses(ctx, None, num_layers, image_shape,
                                   num_classes, batch, steps, seed)
        del one_mod
        mod, mesh = _fit_losses(ctx, "mesh", num_layers, image_shape,
                                num_classes, batch, steps, seed)
        _check(mod._kvstore.mesh.devices.size == n_devices,
               "kvstore='mesh' built a mesh of %d devices, expected %d"
               % (mod._kvstore.mesh.devices.size, n_devices))
        _check(len(one) == len(mesh) == steps,
               "steps ran: one device %d, mesh %d" % (len(one), len(mesh)))
        _check(_all_finite(one + mesh), "non-finite loss: %r %r"
               % (one, mesh))
        _check(np.allclose(mesh, one, rtol=MESH_LOSS_RTOL),
               "mesh losses %r differ from one-device losses %r beyond "
               "rtol %g" % (mesh, one, MESH_LOSS_RTOL))
        # the batch dimension is sharded, nothing sits "all on the first"
        ex = mod._exec
        data = ex.arg_dict["data"]._jx
        shard_rows = sorted({s.data.shape[0]
                             for s in data.addressable_shards})
        _check(shard_rows == [batch // n_devices]
               and len(data.sharding.device_set) == n_devices,
               "data is not batch-sharded over %d devices: shards of %r "
               "rows on %d devices" % (n_devices, shard_rows,
                                       len(data.sharding.device_set)))
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices]
        if devices[0].platform == "tpu":
            _check(all(b for b in in_use),
                   "a device holds nothing: bytes_in_use %r" % in_use)
        # the compiled step: a gradient reduction over all n_devices
        fn = ex._get_fn("train")
        text = fn.lower([ex.arg_dict[n]._jx for n in ex.arg_names],
                        [a._jx for a in ex.aux_arrays],
                        ex.next_rng()).compile().as_text()
        sizes = _collective_group_sizes(text)
        full = [s for s in sizes if s == n_devices]
        _check(full, "no all-reduce or reduce-scatter over %d replicas in "
               "the compiled train step (group sizes found: %r)"
               % (n_devices, sorted(set(sizes))))
        return acct.result(
            model="resnet-%d b%d" % (num_layers, batch),
            devices=n_devices, steps=steps,
            losses_one_device=[round(v, 4) for v in one],
            losses_mesh=[round(v, 4) for v in mesh],
            loss_rtol=MESH_LOSS_RTOL, data_shard_rows=shard_rows[0],
            collectives_over_all_devices=len(full),
            bytes_in_use=in_use, dtypes=_placement(
                [a._jx for a in ex.arg_dict.values()],
                devices[0].platform))


def phase_replicas(n_devices=4, lm_shape=LM_SHAPE, slots=8,
                   prefill_buckets=(32,), prompt_len=24, max_new_tokens=8,
                   seed=0):
    """A ``ReplicaPool`` of ``n_devices`` decode replicas, one per device,
    each answering one session."""
    import jax
    import numpy as np

    from mxnet_tpu import serving
    from mxnet_tpu.models import transformer_lm as tlm

    cfg = _lm_config(lm_shape)
    vocab = cfg.vocab
    devices = jax.devices()[:n_devices]
    with Accounting("replicas", devices[0]) as acct:
        params = tlm.init_params(cfg, seed=seed)
        rs = np.random.RandomState(seed)
        prompt = [int(t) for t in rs.randint(0, vocab, size=prompt_len)]
        pool = serving.lm_pool(
            cfg, params, n_replicas=n_devices, devices=devices,
            name="lm-replicas",
            engine_opts={"slots": slots, "prefill_buckets": prefill_buckets})
        try:
            placed = []
            for r in pool.replicas:
                (dev,) = {d for leaf in
                          jax.tree_util.tree_leaves(r.engine._params)
                          for d in leaf.devices()}
                placed.append(dev)
            _check(len(set(placed)) == n_devices,
                   "replica parameters share devices: %r" % placed)
            # one session to each replica's engine, all in flight at
            # once (through pool.generate the split would depend on how
            # fast the first session finishes)
            sessions = [r.engine.submit(prompt,
                                        max_new_tokens=max_new_tokens)
                        for r in pool.replicas]
            tokens = [s.result(900) for s in sessions]
            served = [r.engine.tokens_out for r in pool.replicas]
        finally:
            pool.close()
        _check(all(n == max_new_tokens for n in served),
               "tokens per replica %r: not one session each" % served)
        _check(all(t == tokens[0] for t in tokens),
               "replicas disagree on the same prompt: %r" % tokens)
        positions, mismatches = _reference_gaps(
            cfg, jax.device_put(params, devices[0]), [prompt + tokens[0]],
            [len(prompt)])
        beyond = [g for g in mismatches if g > NEAR_TIE_LOGITS]
        _check(not beyond, "replica tokens differ from the forward_logits "
               "argmax by %r logits" % beyond)
        return acct.result(
            model="lm v%d e%d h%d l%d f%d max_len %d" % lm_shape,
            replicas=n_devices,
            parameter_devices=[str(d) for d in placed],
            tokens_per_replica=served, replicas_agree=True,
            near_tie_mismatches=len(mismatches), positions=positions,
            dtypes=["float32"], arrays_on=devices[0].platform)


# -- entry --------------------------------------------------------------------
def run(four_chips, seed):
    # the chip first, from JAX alone: without one nothing below runs and
    # nothing in the checkout is touched
    device = device_fields()
    want = 4 if four_chips else 1
    if device["platform"] != "tpu" or device["count"] < want:
        raise RuntimeError(
            "chip_smoke needs %d TPU chip(s); JAX reports %r"
            % (want, device))
    remove_built_artefacts()
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import compile_cache, native, telemetry

    telemetry.enable()
    emit({"phase": "start", "device": device, "jax": jax.__version__,
          "compile_cache": compile_cache.stats()["dir"]})
    _check(compile_cache.enabled(),
           "the persistent compile cache is off "
           "(JAX_ENABLE_COMPILATION_CACHE?): the smoke counts on it")
    _check(native.get_lib() is not None and glob.glob(
        os.path.join(HERE, "mxnet_tpu", "native", "*.so")),
        "native.get_lib() did not build src/native.cc")
    ctx = mx.tpu(0)
    if four_chips:
        emit(phase_mesh_fit(ctx, seed=seed))
        emit(phase_replicas(seed=seed))
    else:
        emit(phase_fit(ctx, seed=seed))
        emit(phase_bulk(ctx, seed=seed))
        emit(phase_serve(ctx, seed=seed))
        emit(phase_decode(jax.devices()[0], seed=seed))
    return device


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--four-chips", action="store_true",
                        help="run only the four-chip checks (mesh fit "
                             "against one device; four decode replicas)")
    parser.add_argument("--seed", type=int, default=0,
                        help="seed of the random weights and inputs")
    opts = parser.parse_args()
    t0 = time.time()
    try:
        device = run(opts.four_chips, opts.seed)
    except BaseException:  # noqa: broad-except — report, then fail
        traceback.print_exc()
        sys.stdout.flush()
        emit({"ok": False})
        sys.exit(1)
    emit({"phase": "done", "total_seconds": round(time.time() - t0, 1)})
    emit({"ok": True, "device": device})


if __name__ == "__main__":
    main()
