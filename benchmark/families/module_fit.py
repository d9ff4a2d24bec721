"""Adapter of the training plane: ``Module.fit`` as ``common/fit.py`` drives
it (symbol from ``models/resnet.py``, SGD with momentum, device metrics, a
``Speedometer`` every 20 batches), with the kvstore the traffic file names.

Set-up builds ONE module, drives it from the seed through its first steps
(through the same ``fit`` call, iterator and callbacks as the window) and
hands that same module to the window.  From those first steps it keeps each
step's loss, the norm of every parameter's first gradient as the optimizer
got it (worked out from the momentum after one step) and the norm of every
parameter's change; the reference follows the same steps after the window,
when the program's state has been freed.  Across the window it keeps how far
the parameters moved.

From the program it takes the system under test and its counters only; the
weights and the batch are the reference's (``reference/module_fit.py``),
made on the device from the seed."""

import math
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import annotate
from benchmark.reference import module_fit as ref


@jax.jit
def _first_grad_norm(mom, w0, lr, wd):
    # mom_1 = -lr * (grad + wd * w0)  =>  grad = -mom_1 / lr - wd * w0
    return jnp.sqrt(jnp.sum(jnp.square(-mom / lr - wd * w0)))


@jax.jit
def _moved_norm(w, w0):
    return jnp.sqrt(jnp.sum(jnp.square(w - w0)))


@jax.jit
def _copy_all(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


@jax.jit
def _moved_norm_all(tree, tree0):
    return jnp.sqrt(sum(jnp.sum(jnp.square(tree[k] - tree0[k]))
                        for k in tree))


class System:
    """The trainer protocol the ``train_steps`` generator drives:
    ``train(seconds, tracer)``."""

    def __init__(self, config, traffic, seed, devices):
        import mxnet_tpu as mx
        from mxnet_tpu.models import resnet

        self.mx = mx
        self.config = config
        self.seed = seed
        self.devices = devices
        self.batch = int(traffic["global_batch"])
        self.kvstore = traffic["kvstore"]
        self.check_steps = int(traffic["check_steps"])
        self.opt = dict(config["optimizer"]["params"])
        sym = resnet.resnet(
            units=list(config["units"]), num_stages=len(config["units"]),
            filter_list=list(config["filter_list"]),
            num_classes=int(config["num_classes"]),
            image_shape=tuple(config["image_shape"]),
            bottle_neck=bool(config["bottle_neck"]))
        self.ctx = mx.context.measurement_context()
        dev = devices[0]
        weights = ref.init_weights(config, seed, dev)
        self.param_names = list(weights)
        aux = {}
        with jax.default_device(dev):
            for name, shape in ref.aux_shapes(config).items():
                fill = jnp.ones if name.endswith("_var") else jnp.zeros
                aux[name] = fill(shape, jnp.float32)
        data, labels = ref.make_batch(config, self.batch, seed, dev)
        wrap = mx.nd.NDArray._from_jax
        self._arg_params = {k: wrap(v, self.ctx) for k, v in weights.items()}
        self._aux_params = {k: wrap(v, self.ctx) for k, v in aux.items()}
        self._data = wrap(data, self.ctx)
        self._label = wrap(labels.astype(jnp.float32), self.ctx)
        self.mod = mx.mod.Module(sym, context=self.ctx)
        # ONE metric object for set-up's fit and the window's: fit wraps it
        # for the device and keeps the wrapper (and its compiled update)
        # on it, so the window's fit finds the programs set-up compiled
        self.metric = mx.metric.create(["accuracy",
                                        mx.metric.CrossEntropy()])
        self._stop = False
        self.first = {"loss": [], "first_grad": None, "moved": None}
        self._on_batch = self._record_first
        self._fit()
        self._arg_params = self._aux_params = None
        # the two programs that read the window's parameter change, run
        # once here so that neither compiles inside the window
        now = self._params_now()
        _moved_norm_all(now, _copy_all(now)).block_until_ready()

    # -- the one call both set-up and the window go through ---------------
    def _iterator(self):
        mx, system = self.mx, self

        class Replay(mx.io.DataIter):
            def __init__(self):
                super().__init__(system.batch)
                shape = (system.batch,) + tuple(
                    system.config["image_shape"])
                self.provide_data = [mx.io.DataDesc("data", shape,
                                                    "float32")]
                self.provide_label = [mx.io.DataDesc(
                    "softmax_label", (system.batch,), "float32")]

            def reset(self):
                pass

            def next(self):
                if system._stop:
                    raise StopIteration
                return mx.io.DataBatch(data=[system._data],
                                       label=[system._label], pad=0,
                                       index=None)

        return Replay()

    def _fit(self):
        mx = self.mx
        self._stop = False
        self.steps = 0
        self.mod.fit(
            self._iterator(), num_epoch=1,
            eval_metric=self.metric,
            kvstore=self.kvstore, optimizer=self.config["optimizer"]["name"],
            optimizer_params=dict(self.opt),
            initializer=mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2),
            arg_params=self._arg_params, aux_params=self._aux_params,
            batch_end_callback=[
                mx.callback.Speedometer(self.batch, 20), self._batch_end])

    def _batch_end(self, param):
        self.steps += 1
        with annotate("batch_end"):
            self._on_batch(param)

    def _params_now(self):
        ex = self.mod._exec
        return {n: ex.arg_dict[n]._jx for n in self.param_names}

    def barrier(self):
        """Wait until the device has applied every update issued."""
        jax.block_until_ready(list(self._params_now().values()))

    def _mean_loss(self, param):
        return float(dict(param.eval_metric.get_name_value())[
            "cross-entropy"])

    # -- set-up: the first steps, recorded --------------------------------
    def _record_first(self, param):
        first = self.first
        k = len(first["loss"])
        mean = self._mean_loss(param)       # running mean of the epoch
        first["loss"].append(mean * (k + 1) - sum(first["loss"]))
        lr, wd = self.opt["learning_rate"], self.opt["wd"]
        if k == 0 or k + 1 == self.check_steps:
            w0 = ref.init_weights(self.config, self.seed, self.devices[0])
            w0 = {n: np.asarray(v) for n, v in w0.items()}
        if k == 0:
            updater = self.mod._updater
            names = self.mod._param_names
            norms = {}
            for idx, state in updater.states.items():
                name = names[idx]
                decay = wd if name.endswith(("_weight", "_gamma")) else 0.0
                norms[name] = float(_first_grad_norm(
                    np.asarray(state._jx), w0[name], lr, decay))
            first["first_grad"] = norms
        if k + 1 == self.check_steps:
            first["moved"] = {
                n: float(_moved_norm(np.asarray(w), w0[n]))
                for n, w in self._params_now().items()}
            self._on_batch = self._warm_on

    def _warm_on(self, param):
        """After the recorded steps, as many again with no metric read
        between them: the window's steps accumulate the metric on the
        device without a read, a program the recorded steps never ran."""
        if self.steps >= 2 * self.check_steps:
            self._stop = True

    # -- the window --------------------------------------------------------
    def train(self, seconds, tracer):
        window = {}

        def on_batch(param):
            now = time.monotonic()
            tracer.poll(now - window["t0"])
            if now - window["t0"] >= seconds:
                self.barrier()
                window["t_end"] = time.monotonic()
                tracer.close()
                window["mean_loss"] = self._mean_loss(param)
                self._stop = True

        self._on_batch = on_batch
        at_open = _copy_all(self._params_now())
        self.barrier()
        print("window opens", flush=True)
        window["t0"] = time.monotonic()
        self._fit()
        moved = float(_moved_norm_all(self._params_now(), at_open))
        del at_open
        finite = bool(np.isfinite(window["mean_loss"]))
        print("window: %d steps of %d rows, mean cross-entropy %.6g"
              % (self.steps, self.batch, window["mean_loss"]), flush=True)
        return {"t0": window["t0"], "t_end": window["t_end"],
                "steps": self.steps, "samples": self.steps * self.batch,
                "attempted": self.steps,
                "failed": 0 if finite else self.steps,
                "param_change_norm": moved,
                "data_shard_rows": self._data_shard_rows()}

    def _data_shard_rows(self):
        data = self.mod._exec.arg_dict["data"]._jx
        return sorted({int(s.data.shape[0])
                       for s in data.addressable_shards})

    def counters(self):
        return {"steps": self.steps}

    def scratch_bytes(self):
        """Temporaries of the ``train`` program on one device, by
        ``memory_analysis()`` of the program lowered again for the bound
        arrays (a cache hit): the device allocator's own peak leaves a
        program's scratch out."""
        ex = self.mod._exec
        compiled = ex._get_fn("train").lower(
            [ex.arg_dict[n]._jx for n in ex.arg_names],
            [a._jx for a in ex.aux_arrays], ex.next_rng()).compile()
        return int(compiled.memory_analysis().temp_size_in_bytes)

    def close(self):
        """Free the program's device state, so the reference has the
        chip."""
        ex = self.mod._exec
        arrays = list(ex.arg_dict.values()) + list(ex.aux_dict.values()) \
            + list(ex.grad_dict.values()) + list(ex.outputs)
        if self.mod._updater is not None:
            arrays += [s for s in self.mod._updater.states.values()
                       if s is not None]
        for a in arrays:
            jx = getattr(a, "_jx", None)
            if jx is not None and not jx.is_deleted():
                jx.delete()
        self.mod = self._data = self._label = None

    # -- correct -----------------------------------------------------------
    def check(self, window, with_control=False):
        """The reference follows the first steps on the same weights and
        batch.  Compared are each step's loss, the norm of the first
        gradient and the norm of the parameters' change, both by the worst
        leaf and over all leaves together (:meth:`_compare`), and that the
        window's own steps moved the parameters."""
        limits = self.config["limits"]
        dev = self.devices[0]
        weights = ref.init_weights(self.config, self.seed, dev)
        data, labels = ref.make_batch(self.config, self.batch, self.seed,
                                      dev)
        high = ref.train_steps(self.config, self.opt, self.check_steps,
                               weights, data, labels)
        compared = self._compare(self.first, high, limits)
        moved = window["param_change_norm"]
        compared.append({
            "name": "window_param_change_norm", "value": moved,
            "limit": 0.0, "ok": moved > 0.0 and math.isfinite(moved),
            "why": "the window's steps have to move the parameters: above "
                   "0 and finite"})
        want_rows = self.batch // len(self.devices)
        compared.append({
            "name": "data_shard_rows", "value": window["data_shard_rows"],
            "limit": [want_rows],
            "ok": window["data_shard_rows"] == [want_rows]})
        control = None
        if with_control:
            low = ref.train_steps(self.config, self.opt, self.check_steps,
                                  weights, data, labels,
                                  lower_precision=True)
            as_program = {
                "loss": [float(v) for v in np.asarray(low[0])],
                "first_grad": {k: float(v) for k, v in low[1].items()},
                "moved": {k: float(v) for k, v in low[2].items()}}
            control = self._compare(as_program, high, limits)
        return compared, control

    @staticmethod
    def _compare(got, want, limits):
        """Losses by the widest relative gap of a step.  A norm two ways:
        by the worst leaf, the gap between the program's norm and the
        reference's against the reference's norm of that leaf or of the
        median leaf, whichever is larger (some gradients are all but
        zero): a leaf that is wrong alone shows there.  And over all
        leaves together (the root of the sum of the leaves' squared
        norms), which is steady from seed to seed, so that a fault of a
        few tens of percent in every leaf (a gradient from part of the
        batch, another momentum or learning rate) shows."""
        want_loss = [float(v) for v in np.asarray(want[0])]
        loss_gap = max(abs(g - w) / abs(w)
                       for g, w in zip(got["loss"], want_loss))
        out = [{"name": "loss_rel_gap", "value": loss_gap,
                "limit": limits["loss_rel_gap"],
                "ok": loss_gap <= limits["loss_rel_gap"],
                "program": got["loss"], "reference": want_loss}]
        for key, name, wanted in (
                ("first_grad", "first_grad_norm", want[1]),
                ("moved", "param_change_norm", want[2])):
            wanted = {k: float(v) for k, v in wanted.items()}
            have = {k: (got[key] or {}).get(k, float("inf"))
                    for k in wanted}
            median = statistics.median(wanted.values())
            gaps = {k: abs(have[k] - w) / max(w, median)
                    for k, w in wanted.items()}
            worst = max(gaps, key=gaps.get)
            out.append({"name": name + "_worst_leaf", "value": gaps[worst],
                        "limit": limits[name + "_worst_leaf"],
                        "ok": gaps[worst] <= limits[name + "_worst_leaf"],
                        "leaf": worst})
            whole = math.sqrt(sum(w * w for w in wanted.values()))
            gap = abs(math.sqrt(sum(h * h for h in have.values()))
                      - whole) / whole
            out.append({"name": name + "_all_leaves", "value": gap,
                        "limit": limits[name + "_all_leaves"],
                        "ok": gap <= limits[name + "_all_leaves"]})
        return out
