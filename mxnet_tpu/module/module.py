"""Module — the concrete single-symbol training module.

Reference: ``python/mxnet/module/module.py`` (708 LoC; bind :323,
init_optimizer :432, update :553) + ``executor_group.py``
(DataParallelExecutorGroup :77).

TPU-native data parallelism: where the reference builds one executor per GPU
and reduces gradients through KVStore (``executor_group.py`` decide_slices +
``comm.h`` Reduce), this Module binds ONE executor whose arrays are *global
jax.Arrays over a device mesh*: data/label sharded along the batch axis,
parameters replicated.  XLA GSPMD then compiles the gradient psum over ICI
into the step itself — the ``KVStore('device')`` allreduce with no server and
no separate comm phase.  A single context degenerates to a 1-device mesh.
"""

from __future__ import annotations

import logging
import pickle
import time as _time_mod

import numpy as np

from .. import compile_cache as _compile_cache
from .. import faults as _faults
from .. import metric as _metric
from .. import optimizer as opt
from .. import perfdebug as _perfdebug
from .. import random as _random
from .. import telemetry as _telemetry
from .. import tracing as _tracing
from ..base import MXNetError
from ..context import Context, cpu
from ..executor import Executor
from ..initializer import Uniform, InitDesc
from ..kvstore import KVStore
from ..model import (_create_kvstore, _initialize_kvstore, _update_params,
                     _update_params_on_kvstore, load_checkpoint,
                     save_checkpoint)
from ..ndarray import NDArray, zeros as nd_zeros
from .base_module import BaseModule

__all__ = ["Module"]


def _parse_data_desc(data_shapes):
    out = []
    for d in data_shapes or []:
        if hasattr(d, "name"):
            out.append((d.name, tuple(d.shape)))
        else:
            out.append((d[0], tuple(d[1])))
    return out


class Module(BaseModule):
    """reference ``module.py:50``"""

    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 logger=logging, context=None, work_load_list=None,
                 fixed_param_names=None, shard_rules=None):
        super().__init__(logger=logger)
        # context may be a jax.sharding.Mesh: Module.fit then runs the
        # whole dp(×tp×…) strategy through THIS surface — shard_rules
        # ([(param-name regex, PartitionSpec), ...]) places chosen
        # parameters over model axes and XLA inserts the implied
        # collectives (SURVEY §7.9 north star: `Module.fit` on a mesh)
        self._user_mesh = None
        from jax.sharding import Mesh as _JaxMesh

        if isinstance(context, _JaxMesh):
            self._user_mesh = context
            dev0 = context.devices.flat[0]
            context = [Context("cpu" if dev0.platform == "cpu" else "tpu",
                               0)]
        import re as _re

        self._shard_rules = [(_re.compile(p), spec)
                             for p, spec in (shard_rules or [])]
        if context is None:
            from ..context import current_context

            context = [current_context()]
        if isinstance(context, Context):
            context = [context]
        self._context = list(context)
        self._work_load_list = work_load_list
        self._symbol = symbol
        self._data_names = list(data_names or [])
        self._label_names = list(label_names or [])
        self._fixed_param_names = list(fixed_param_names or [])
        arg_names = symbol.list_arguments()
        input_names = self._data_names + self._label_names
        self._param_names = [n for n in arg_names if n not in input_names]
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()
        self._exec = None
        self._data_shapes = None
        self._label_shapes = None
        self._mesh = None
        self._optimizer = None
        self._kvstore = None
        self._updater = None
        self._update_on_kvstore = False
        self._preload_opt_states = None
        self._grad_req = "write"
        self._fused_step = None
        self._pending_full = False  # staged single-dispatch train step
        self._dist_dp = False  # multi-process in-graph data parallelism
        self._dist_placed_states = set()

    # -- properties -------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        outs = self._symbol.infer_shape(
            **{n: s for n, s in (self._data_shapes +
                                 (self._label_shapes or []))})[1]
        return list(zip(self._output_names, outs))

    def get_params(self):
        assert self.binded and self.params_initialized
        arg = {n: self._exec.arg_dict[n] for n in self._param_names}
        aux = dict(self._exec.aux_dict)
        return (arg, aux)

    # -- binding ----------------------------------------------------------
    def _make_mesh(self):
        import jax
        from jax.sharding import Mesh

        devices = [c.jax_device() for c in self._context]
        if len(set(devices)) != len(devices):
            raise MXNetError("duplicate devices in context list")
        return Mesh(np.array(devices), ("data",))

    def _batch_axis_name(self):
        """Mesh axis that shards the batch: 'data' when present, else the
        first axis."""
        names = self._mesh.axis_names
        return "data" if "data" in names else names[0]

    def _param_spec(self, name):
        from jax.sharding import PartitionSpec as P

        for prog, spec in self._shard_rules:
            if name is not None and prog.match(name):
                return spec
        return P()

    def _mesh_zero_names(self, names):
        """Parameters whose SGD update shards ZeRO-style over the mesh
        batch axis (docs/how_to/multi_devices.md "Sharded fit"): active
        only under ``kvstore='mesh'`` with a >1-device data axis, off
        via ``MXNET_MESH_ZERO=0``.  Eligibility (leading dim divisible
        by the world size, size floor) is
        :func:`~mxnet_tpu.kvstore_mesh.zero_eligible_names`."""
        import os

        kv = self._kvstore
        if self._mesh is None or kv is None \
                or not getattr(kv, "is_mesh", False):
            # clear, don't just skip: a re-init away from the mesh
            # kvstore must not leave _place_opt_state row-sharding
            # fresh states per a stale partition
            self._zero_names = frozenset()
            return ()
        if self._shard_rules:
            # ZeRO assumes dp-replicated params: a shard_rules module
            # keeps its TP layout and the plain fused step
            self._zero_names = frozenset()
            return ()
        env = (os.environ.get("MXNET_MESH_ZERO", "1"),
               os.environ.get("MXNET_MESH_ZERO_MIN_ELEMS"))
        # memoized: this runs on the per-batch dispatch path and the
        # answer only changes with the kvstore/mesh/param-set/env.  The
        # cache holds the kv/mesh objects (identity compare), so a
        # re-init onto a new plane recomputes
        cached = getattr(self, "_zero_names_cache", None)
        if cached is not None and cached[0] is kv \
                and cached[1] is self._mesh \
                and cached[2] == tuple(names) and cached[3] == env:
            return cached[4]
        if env[0] in ("0", "", "false"):
            zero = ()
        else:
            from ..kvstore_mesh import zero_eligible_names

            world = int(self._mesh.shape[self._batch_axis_name()])
            shapes = {n: tuple(self._exec.arg_dict[n].shape)
                      for n in names}
            zero = zero_eligible_names(names, shapes, world)
        # _place_opt_state consults this when it commits the optimizer
        # state arrays: ZeRO params' momentum rows shard with the update
        self._zero_names = frozenset(zero)
        self._zero_names_cache = (kv, self._mesh, tuple(names), env,
                                  zero)
        return zero

    def _snapshot_mesh_info(self):
        """Sharding descriptor for snapshot writes (None = unsharded):
        under ``kvstore='mesh'`` with world > 1 each snapshot generation
        is split into per-shard payload files stitched by a manifest
        entry (``checkpoint.write_snapshot``); ``MXNET_MESH_SHARDED_
        SNAPSHOT=0`` opts out."""
        import os

        kv = self._kvstore
        if self._mesh is None or kv is None \
                or not getattr(kv, "is_mesh", False):
            return None
        if os.environ.get("MXNET_MESH_SHARDED_SNAPSHOT", "1") \
                in ("0", "", "false"):
            return None
        axis = self._batch_axis_name()
        world = int(self._mesh.shape[axis])
        if world <= 1:
            return None
        return {"num_shards": world, "axis": axis,
                "mesh_axes": list(self._mesh.axis_names),
                "mesh_shape": [int(s) for s in self._mesh.devices.shape]}

    def _shard(self, arr, batch_axis, name=None):
        """Place an NDArray globally over the module mesh.

        Batch arrays shard over the batch axis; parameters follow their
        ``shard_rules`` spec (replicated by default — tensor parallelism
        is a rule away).  Multi-process (dist in-graph) mode additionally
        broadcasts non-batch arrays from rank 0 (the reference's Init
        broadcast, ``kvstore_dist.h:58-76``)."""
        if self._mesh is None:
            return
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self._dist_dp:
            from .. import dist as _dist

            if batch_axis:
                return
            arr._jx = _dist.replicate(
                self._mesh,
                _dist.broadcast_from_root(np.asarray(arr._jx)))  # host-sync: ok — dist init-time broadcast
            return
        if len(self._context) == 1 and self._user_mesh is None:
            return
        spec = P(self._batch_axis_name()) if batch_axis \
            else self._param_spec(name)
        arr._jx = jax.device_put(arr._jx, NamedSharding(self._mesh, spec))

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """reference ``module.py:323``"""
        saved_params = None
        if force_rebind:
            if self._exec is not None and self.params_initialized:
                # the reference preserves parameter values across a
                # rebind; dropping them here would silently restart
                # training from whatever the fresh executor allocates
                saved_params = self.get_params()
            self._exec = None
            self.binded = False
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return
        # recorded with tracing off, as init_params and init_optimizer
        # are: a job does each a bounded number of times
        with _tracing.setup_span("module.setup.bind"):
            self._bind(data_shapes, label_shapes, for_training,
                       inputs_need_grad, shared_module, grad_req)
        if saved_params is not None:
            self.set_params(saved_params[0], saved_params[1],
                            force_init=True)

    def _bind(self, data_shapes, label_shapes, for_training,
              inputs_need_grad, shared_module, grad_req):
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self._grad_req = grad_req
        assert not (not for_training and inputs_need_grad)
        self._data_shapes = _parse_data_desc(data_shapes)
        self._label_shapes = _parse_data_desc(label_shapes) \
            if label_shapes else []
        from .. import dist as _dist

        if self._user_mesh is not None:
            # explicit mesh: dp over the batch axis + whatever the
            # shard_rules place on the other axes
            self._mesh = self._user_mesh
            nbatch = self._mesh.shape[self._batch_axis_name()]
            for _, s in self._data_shapes + self._label_shapes:
                if s and s[0] % nbatch != 0:
                    raise MXNetError(
                        "batch size %d not divisible by mesh %r axis "
                        "size %d" % (s[0], self._batch_axis_name(), nbatch))
        elif _dist.is_initialized() and len(self._context) == 1:
            # TPU-native dist_sync: one jitted SPMD step over the GLOBAL
            # mesh; each process feeds its local batch shard and XLA
            # psums the gradients in-graph (SURVEY §5.8)
            import jax

            self._dist_dp = True
            self._mesh = _dist.global_mesh("data")
            local_devs = jax.local_device_count()
            for _, s in self._data_shapes + self._label_shapes:
                if s and s[0] % local_devs != 0:
                    raise MXNetError(
                        "local batch %d not divisible by %d local devices"
                        % (s[0], local_devs))
        elif len(self._context) > 1:
            self._mesh = self._make_mesh()
            if self._work_load_list and \
                    len(set(self._work_load_list)) > 1:
                # XLA sharding splits the batch uniformly; the
                # reference's weighted decide_slices has no SPMD analog
                self.logger.warning(
                    "work_load_list with non-uniform weights is ignored: "
                    "the mesh shards the batch evenly across devices")
            for _, s in self._data_shapes + self._label_shapes:
                if s and s[0] % len(self._context) != 0:
                    raise MXNetError(
                        "batch size %d not divisible by %d devices"
                        % (s[0], len(self._context)))
        shapes = dict(self._data_shapes + self._label_shapes)
        if self._dist_dp:
            # the executor binds GLOBAL batch shapes (local x processes)
            nproc = _dist.num_processes()
            shapes = {n: ((s[0] * nproc,) + tuple(s[1:])
                          if n in (self._data_names + self._label_names)
                          and s else s)
                      for n, s in shapes.items()}
        req = {}
        for n in self._symbol.list_arguments():
            if n in self._param_names and n not in self._fixed_param_names \
                    and for_training:
                req[n] = grad_req if isinstance(grad_req, str) else \
                    grad_req.get(n, "write")
            elif n in self._data_names and inputs_need_grad:
                req[n] = "write"
            else:
                req[n] = "null"
        shared_exec = shared_module._exec if shared_module is not None else None
        self._exec = Executor._simple_bind(
            self._symbol, self._context[0], grad_req=req,
            shared_exec=shared_exec, **shapes)
        if self._dist_dp:
            self._exec._global_mesh = self._mesh
        elif self._mesh is not None:
            # single-process mesh: the executor needs the mesh to build
            # sharded program kinds (the ZeRO train_sgd_mesh step)
            self._exec._spmd_mesh = self._mesh
        # global placement over the mesh
        if self._mesh is not None:
            for n in self._symbol.list_arguments():
                batch_axis = n in self._data_names or n in self._label_names
                if self._exec.arg_dict.get(n) is not None:
                    self._shard(self._exec.arg_dict[n], batch_axis, n)
                if self._exec.grad_dict.get(n) is not None:
                    self._shard(self._exec.grad_dict[n], batch_axis, n)
            for n in self._aux_names:
                self._shard(self._exec.aux_dict[n], False, n)
        self.binded = True
        if shared_module is not None and shared_module.params_initialized:
            self.params_initialized = True

    def reshape(self, data_shapes, label_shapes=None):
        """reference module.py reshape"""
        assert self.binded
        self._data_shapes = _parse_data_desc(data_shapes)
        self._label_shapes = _parse_data_desc(label_shapes) \
            if label_shapes else []
        shapes = dict(self._data_shapes + self._label_shapes)
        self._exec = self._exec.reshape(allow_up_sizing=True, **shapes)

    # -- parameters -------------------------------------------------------
    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False):
        """reference module.py:227"""
        assert self.binded, "call bind before initializing the parameters"
        if self.params_initialized and not force_init:
            return
        with _tracing.setup_span("module.setup.init_params"):
            self._init_params(initializer, arg_params, aux_params,
                              allow_missing)

    def _init_params(self, initializer, arg_params, aux_params,
                     allow_missing):
        attrs = self._symbol.attr_dict()

        def _impl(name, arr, cache):
            if cache is not None and name in cache:
                cache[name].copyto(arr)
            elif cache is not None and not allow_missing:
                raise RuntimeError("%s is not presented" % name)
            elif initializer is not None:
                initializer(InitDesc(name, attrs.get(name),
                                     global_init=initializer), arr)

        for name in self._param_names:
            _impl(name, self._exec.arg_dict[name], arg_params)
        for name in self._aux_names:
            _impl(name, self._exec.aux_dict[name], aux_params)
        # restore global sharding after host-side init writes
        if self._mesh is not None:
            for name in self._param_names:
                self._shard(self._exec.arg_dict[name], False, name)
            for name in self._aux_names:
                self._shard(self._exec.aux_dict[name], False, name)
        self.params_initialized = True

    # -- optimizer --------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """reference ``module.py:432``"""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        with _tracing.setup_span("module.setup.init_optimizer"):
            self._init_optimizer(kvstore, optimizer, optimizer_params)

    def _init_optimizer(self, kvstore, optimizer, optimizer_params):
        arg_params = {n: self._exec.arg_dict[n] for n in self._param_names}
        (kvstore, update_on_kvstore) = _create_kvstore(
            kvstore, len(self._context), arg_params)
        if kvstore is not None and getattr(kvstore, "is_mesh", False) \
                and self._user_mesh is not kvstore.mesh \
                and (self._user_mesh is None
                     or getattr(self, "_kvstore_mesh_adopted", False)):
            # kvstore='mesh': the KVStore IS a device plane — adopt its
            # mesh and re-bind so every bound array becomes a global
            # jax Array (batch sharded over the data axis, params
            # replicated); GSPMD then compiles the gradient psum into
            # the step and push/pull never run per step.  A mesh the
            # USER passed as the module context is never clobbered
            # (their layout — axes, shard_rules targets, device subset
            # — wins; the mesh kvstore then just marks the in-graph
            # plane), but a previously kvstore-ADOPTED mesh is
            # re-adopted so re-initializing onto a new plane works
            self._user_mesh = kvstore.mesh
            self._kvstore_mesh_adopted = True
            self.bind(self._data_shapes, self._label_shapes or None,
                      for_training=self.for_training,
                      inputs_need_grad=self.inputs_need_grad,
                      force_rebind=True, grad_req=self._grad_req)
            arg_params = {n: self._exec.arg_dict[n]
                          for n in self._param_names}
        elif kvstore is not None \
                and getattr(kvstore, "in_graph_sync", False) \
                and not self._dist_dp:
            # the process group came up with the kvstore (after bind):
            # re-bind onto the global mesh, preserving parameters (bind
            # broadcasts rank-0 values during placement)
            self.bind(self._data_shapes, self._label_shapes or None,
                      for_training=self.for_training,
                      inputs_need_grad=self.inputs_need_grad,
                      force_rebind=True, grad_req=self._grad_req)
            arg_params = {n: self._exec.arg_dict[n]
                          for n in self._param_names}
        batch_size = self._data_shapes[0][1][0]
        if kvstore and "dist" in kvstore.type:
            batch_size *= kvstore.num_workers
        rescale_grad = 1.0 / batch_size

        if isinstance(optimizer, str):
            idx2name = {i: n for i, n in enumerate(self._param_names)}
            optimizer_params = dict(optimizer_params)
            # whether rescale_grad is framework-derived (1/global-batch)
            # or user-supplied: an elastic reshard recomputes the former
            # for the new world size but must never clobber the latter
            self._auto_rescale_grad = "rescale_grad" not in optimizer_params
            if self._auto_rescale_grad:
                optimizer_params["rescale_grad"] = rescale_grad
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name,
                                   **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)
            self._auto_rescale_grad = False
            if optimizer.rescale_grad != rescale_grad:
                self.logger.warning(
                    "Optimizer created manually outside Module but "
                    "rescale_grad != 1.0/batch_size (%s vs. %s).",
                    optimizer.rescale_grad, rescale_grad)

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None
        # a (re-)init starts a fresh updater/state generation: stale
        # placed-state bookkeeping would make _place_opt_state skip the
        # new states' mesh placement (single-device momentum entering a
        # mesh jit), and a stale zero partition would row-shard a
        # non-SGD optimizer's fresh states (whose update path never
        # recomputes it) per the old SGD partition
        self._dist_placed_states = set()
        self._zero_names_cache = None
        self._zero_names = frozenset()
        if kvstore:
            _initialize_kvstore(
                kvstore=kvstore,
                param_arrays=[[self._exec.arg_dict[n]]
                              for n in self._param_names],
                arg_params=arg_params, param_names=self._param_names,
                update_on_kvstore=update_on_kvstore)
            if update_on_kvstore:
                kvstore.set_optimizer(self._optimizer)
        if not update_on_kvstore:
            self._updater = opt.get_updater(optimizer)
        self.optimizer_initialized = True
        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def borrow_optimizer(self, shared_module):
        """reference module.py borrow_optimizer"""
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        # whether rescale_grad is framework-derived travels with the
        # optimizer: an elastic reshard recomputes it for the new world
        # only when the lender's init derived it (fit's init_optimizer
        # early-returns on the borrowed flag, so this is the only site
        # that can carry it over)
        self._auto_rescale_grad = getattr(
            shared_module, "_auto_rescale_grad", False)
        self.optimizer_initialized = True

    # -- compute ----------------------------------------------------------
    def _load_io(self, names, arrays):
        import jax

        for name, src in zip(names, arrays or []):
            if name not in self._exec.arg_dict:
                continue
            dst = self._exec.arg_dict[name]
            if self._dist_dp:
                # local batch shard -> global batch-sharded array
                from .. import dist as _dist

                loc = np.asarray(src._transfer_src()  # host-sync: ok — dist shards stage through host numpy
                                 if isinstance(src, NDArray)
                                 else src, dtype=dst.dtype)
                nproc = _dist.num_processes()
                if (loc.shape[0] * nproc,) + loc.shape[1:] != dst.shape:
                    raise MXNetError(
                        "input %r local shape %s does not tile to bound "
                        "global shape %s over %d processes"
                        % (name, loc.shape, dst.shape, nproc))
                dst._jx = _dist.shard_batch(self._mesh, loc)
                continue
            # _transfer_src: host-backed iterator batches hand over their
            # raw numpy buffer — device_put below is then the ONE copy
            jx = src._transfer_src() if isinstance(src, NDArray) else None
            if jx is None:
                dst[:] = src
                continue
            if jx.dtype != dst._jx.dtype:
                jx = jx.astype(dst._jx.dtype)
            if jx.shape != dst.shape:
                raise MXNetError("input %r shape %s != bound shape %s "
                                 "(reshape the module)" %
                                 (name, jx.shape, dst.shape))
            dst._jx = jax.device_put(jx, dst._jx.sharding)

    def forward(self, data_batch, is_train=None, _defer=False):
        """reference executor_group.py:355 forward + _load_data"""
        assert self.binded and self.params_initialized
        if not _defer:
            # a staged fused step must run before its batch data is
            # overwritten, or a later update() would apply stale grads
            self._materialize_pending()
        if is_train is None:
            is_train = self.for_training
        # zip with bind-time data_shapes order (= provide_data order), the
        # reference's _load_data positional contract (executor_group.py:369)
        self._load_io([n for n, _ in self._data_shapes], data_batch.data)
        if self._label_shapes and data_batch.label:
            self._load_io([n for n, _ in self._label_shapes],
                          data_batch.label)
        if not _defer:
            self._exec.forward(is_train=is_train)

    def backward(self, out_grads=None):
        """reference executor_group.py:481"""
        assert self.binded and self.params_initialized
        self._exec.backward(out_grads=out_grads)

    # -- single-dispatch train step ---------------------------------------
    def _full_step_eligible(self):
        """fwd+bwd+update as ONE jit call: plain SGD, no kvstore, no
        monitor/profiler hooks, params-only grads all 'write'.

        Opt-in via ``MXNET_FUSE_TRAIN_STEP=1``: one dispatch per step
        instead of two (not measured on the present machine).  The library
        default stays two-phase because the fused path restricts what get_outputs/
        get_input_grads can observe mid-step; throughput-sensitive loops
        (``chip_smoke.py``'s bulk phase) set the flag.  Numerics are
        identical either way (see
        tests/test_module.py::test_fused_full_step_matches_two_phase).
        """
        import os

        from .. import profiler as _profiler

        if os.environ.get("MXNET_FUSE_TRAIN_STEP", "0") != "1":
            return False
        if not (self.binded and self.params_initialized
                and self.optimizer_initialized):
            return False
        if type(self._optimizer) is not opt.SGD:
            return False
        if self._kvstore is not None and \
                not getattr(self._kvstore, "in_graph_sync", False):
            return False
        if self.inputs_need_grad or self._exec._monitor_callback is not None:
            return False
        if self._exec._segments is not None:
            return False  # group2ctx placement runs the segmented path
        if _profiler.running():
            return False  # unfused path keeps per-phase profiler spans
        diff = self._exec._diff_names()
        names = [n for n in self._param_names
                 if self._exec.grad_dict.get(n) is not None]
        return set(diff) == set(names) and \
            all(self._exec.grad_req[n] == "write" for n in diff)

    def forward_backward(self, data_batch):
        """Stages the batch for a fused fwd+bwd+update dispatch when
        eligible; ``update()`` then runs the whole step as one XLA
        computation.  Reading outputs/grads before ``update()`` falls back
        to the exact two-phase path."""
        if self._full_step_eligible():
            self.forward(data_batch, is_train=True, _defer=True)
            self._pending_full = True
            return
        self.forward(data_batch, is_train=True)
        self.backward()

    def _materialize_pending(self):
        """A staged batch is being observed before update(): run the
        normal fwd+bwd so outputs/grads exist, then clear the stage."""
        if self._pending_full:
            self._pending_full = False
            self._exec.forward(is_train=True)
            self._exec.backward()

    def _install_nan_guard(self, policy):
        """Arm (``policy`` set) or disarm (``None``) the in-graph NaN/Inf
        guard: the train kinds fold a logical-or reduction over
        outputs+grads into the step, the fused step additionally
        withholds a non-finite update in-graph, and the host reads one
        accumulated scalar at the ``MXNET_NAN_CHECK_PERIOD`` cadence
        (docs/resilience.md).  Disarming also drops any accumulated
        flag so it cannot leak into a later guarded fit."""
        if self._exec is not None:
            self._exec._nan_guard = policy is not None
            if policy is None:
                self._exec._nan_acc = None
                self._exec._nan_batch = None
                self._exec._nan_stale = False

    def _run_full_step(self):
        import jax
        import jax.numpy as jnp

        self._pending_full = False
        ex = self._exec
        optimizer = self._optimizer
        updater = self._updater
        names = [n for n in self._param_names
                 if ex.grad_dict.get(n) is not None]
        if not names:
            ex.forward(is_train=True)
            return
        # ZeRO eligibility must be known BEFORE the states are placed:
        # a sharded param's momentum commits row-sharded
        zero = self._mesh_zero_names(names)
        for idx in range(len(names)):
            if idx not in updater.states:
                updater.states[idx] = optimizer.create_state(
                    idx, ex.arg_dict[names[idx]])
            self._place_opt_state(idx, updater.states[idx], names[idx])
            optimizer._update_count(idx)
        lrs, wds = self._get_hyper_arrays(optimizer, len(names))
        clip = optimizer.clip_gradient \
            if optimizer.clip_gradient is not None else -1.0
        guard = bool(getattr(ex, "_nan_guard", False))
        if zero:
            # ZeRO fused step: reduce-scatter grads, sharded update,
            # all-gather params — no full-gradient materialization, so
            # grad_dict is left stale (like run_bulk's on-chip grads)
            fn = ex._get_fn(("train_sgd_mesh", tuple(names), tuple(zero),
                             optimizer.momentum, optimizer.rescale_grad,
                             clip, guard, self._batch_axis_name()))
        else:
            fn = ex._get_fn(("train_sgd", tuple(names), optimizer.momentum,
                             optimizer.rescale_grad, clip, guard))
        names_set = set(names)
        other = [n for n in ex.arg_names if n not in names_set]
        upd_vals = [ex.arg_dict[n]._jx for n in names]
        other_vals = [ex.arg_dict[n]._jx for n in other]
        aux = [a._jx for a in ex.aux_arrays]
        rng = ex.next_rng()
        moms = [updater.states[i]._jx for i in range(len(names))] \
            if optimizer.momentum != 0.0 else []
        grad_list = None
        if guard and zero:
            outs, new_aux, new_p, new_m, acc, batch_flag = fn(
                upd_vals, other_vals, aux, rng, moms, lrs, wds,
                ex._nan_acc_in())
            ex._nan_acc = acc
            ex._nan_batch = batch_flag
            ex._nan_stale = False
        elif guard:
            outs, new_aux, new_p, new_m, grad_list, acc, batch_flag = fn(
                upd_vals, other_vals, aux, rng, moms, lrs, wds,
                ex._nan_acc_in())
            ex._nan_acc = acc
            ex._nan_batch = batch_flag
            ex._nan_stale = False
        elif zero:
            outs, new_aux, new_p, new_m = fn(
                upd_vals, other_vals, aux, rng, moms, lrs, wds)
        else:
            outs, new_aux, new_p, new_m, grad_list = fn(
                upd_vals, other_vals, aux, rng, moms, lrs, wds)
        ex.outputs = [NDArray._from_jax(o, ex._ctx) for o in outs]
        for arr, v in zip(ex.aux_arrays, new_aux):
            arr._jx = v
        for n, p in zip(names, new_p):
            ex.arg_dict[n]._jx = p
        for i, m in enumerate(new_m):
            updater.states[i]._jx = m
        # keep grad_dict observable exactly like the two-phase path
        # (grad-norm logging etc. reads the current batch's gradients).
        # The ZeRO step never materializes full gradients (that is the
        # point — reduce-scatter, not all-reduce): grad_dict goes stale
        if grad_list is not None:
            for n, g in zip(names, grad_list):
                ex.grad_dict[n]._jx = g
        ex._pending_grads = None

    def run_bulk(self, batches, return_outputs=False):
        """Run ``len(batches)`` full fwd+bwd+update steps as ONE XLA
        dispatch: ``lax.scan`` over the stacked batches with params /
        momenta / aux (BN stats) as the scan carry.

        The reference cuts per-op dispatch cost by bulking engine ops
        into segments (``graph_executor.cc:678`` InitOpSegs,
        ``MXNET_EXEC_BULK_EXEC_TRAIN``); on TPU the per-*step* dispatch
        round trip is the analogous overhead, so this bulks whole steps.
        Requires the same eligibility as the fused step
        (``MXNET_FUSE_TRAIN_STEP=1``, plain SGD, local kvstore); falls
        back to per-batch ``forward_backward``+``update`` otherwise.
        With ``return_outputs=True`` every step's outputs are stacked
        and returned, and ``get_outputs()`` reflects the last step.
        With the default ``return_outputs=False`` the scan does NOT
        materialize the per-step output stack at all (at PTB shapes the
        stacked softmax is GBs of HBM nobody reads) — ``get_outputs()``
        is left stale, and per-step gradients are likewise not
        materialized (``grad_dict`` stale — the scan keeps them
        on-chip).

        ``return_outputs=True`` additionally returns, per symbol output,
        a host numpy array stacked over the batches (``(K, ...)``) — one
        transfer for all K steps' outputs, for metric updates.
        ``return_outputs="device"`` returns the same stacks WITHOUT the
        host transfer (jax arrays on the step device) — the sync-free
        fit path feeds them straight to device-resident metrics."""
        import jax
        import jax.numpy as jnp

        if not batches:
            return [] if return_outputs else None

        def _per_batch_fallback():
            per_batch = []
            for b in batches:
                self.forward_backward(b)
                self.update()
                if return_outputs:
                    outs = self.get_outputs()
                    per_batch.append(
                        [o._jx for o in outs] if return_outputs == "device"
                        else [o.asnumpy() for o in outs])  # host-sync: ok — explicit host-output mode
            if not return_outputs:
                return None
            stack = jnp.stack if return_outputs == "device" else np.stack
            return [stack([pb[i] for pb in per_batch])
                    for i in range(len(per_batch[0]))]

        if not self._full_step_eligible() or self._optimizer is None \
                or self._dist_dp:
            return _per_batch_fallback()
        ex = self._exec
        optimizer, updater = self._optimizer, self._updater
        names = [n for n in self._param_names
                 if ex.grad_dict.get(n) is not None]
        if not names:
            return _per_batch_fallback()
        if self._mesh_zero_names(names):
            # the ZeRO-sharded update lands per step (train_sgd_mesh);
            # the scan-bulked kind stays unsharded — fall back so the
            # sharded state layout is consistent across the whole fit
            return _per_batch_fallback()
        self._pending_full = False
        for idx in range(len(names)):
            if idx not in updater.states:
                updater.states[idx] = optimizer.create_state(
                    idx, ex.arg_dict[names[idx]])
        for _ in batches:
            for idx in range(len(names)):
                optimizer._update_count(idx)
        lrs, wds = self._get_hyper_arrays(optimizer, len(names))
        clip = optimizer.clip_gradient \
            if optimizer.clip_gradient is not None else -1.0
        scan_names = [n for n in (self._data_names + self._label_names)
                      if n in ex.arg_dict]
        fn = ex._get_fn(("train_sgd_scan", tuple(names), tuple(scan_names),
                         optimizer.momentum, optimizer.rescale_grad, clip,
                         bool(return_outputs)))
        dev = ex._ctx.jax_device()
        name_pos = {}
        for i, n in enumerate(self._data_names):
            name_pos[n] = ("data", i)
        for i, n in enumerate(self._label_names):
            name_pos[n] = ("label", i)

        def stack(n):
            kind, i = name_pos[n]
            dtype = ex.arg_dict[n]._jx.dtype
            vals = []
            for b in batches:
                v = (b.data if kind == "data" else b.label)[i]
                raw = v._transfer_src() if isinstance(v, NDArray) \
                    else jnp.asarray(v)
                vals.append(raw.astype(dtype))
            if all(isinstance(v, np.ndarray) for v in vals):
                # host-backed batches: stack on host, ship once
                return jax.device_put(np.stack(vals), dev)
            return jax.device_put(jnp.stack(vals), dev)

        # benchmark loops re-submit the same device-resident batches every
        # bulk; re-stacking them costs a dispatch round trip per input, so
        # memoize on the identity of the underlying buffers.  The cache
        # PINS those buffers (keyed list): an id() key alone would go
        # stale when fresh batches reuse a freed object's address
        keyed = [(b.data if k == "data" else b.label)[i]._jx
                 if isinstance((b.data if k == "data" else b.label)[i],
                               NDArray) else None
                 for k, i in name_pos.values() for b in batches]
        skey = tuple(id(v) if v is not None else None for v in keyed)
        cached = getattr(self, "_bulk_stack_cache", None)
        if cached is not None and cached[0] == skey and None not in skey:
            stacks = cached[1]
        else:
            with _telemetry.phase("stack", family="bulk"):
                stacks = [stack(n) for n in scan_names]
            self._bulk_stack_cache = (skey, stacks, keyed)
        names_set = set(names)
        static = [n for n in ex.arg_names
                  if n not in names_set and n not in scan_names]
        upd_vals = [ex.arg_dict[n]._jx for n in names]
        static_vals = [ex.arg_dict[n]._jx for n in static]
        aux = [a._jx for a in ex.aux_arrays]
        rng = ex.next_rng()
        moms = [updater.states[i]._jx for i in range(len(names))] \
            if optimizer.momentum != 0.0 else []
        call_args = (upd_vals, static_vals, aux, rng, moms, lrs, wds,
                     stacks)
        # abstract signature for bulk_cost_analysis (avals survive buffer
        # donation; holding the concrete arrays would not)
        self._last_bulk_sig = (fn, jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), call_args))
        # host-side dispatch wall time (XLA executes async; device time
        # shows up wherever the caller first blocks on results)
        with _telemetry.phase("dispatch", family="bulk"):
            outs_stack, new_aux, new_p, new_m = fn(*call_args)
        if outs_stack is not None:
            ex.outputs = [NDArray._from_jax(o[-1], ex._ctx)
                          for o in outs_stack]
        for arr, v in zip(ex.aux_arrays, new_aux):
            arr._jx = v
        for n, p in zip(names, new_p):
            ex.arg_dict[n]._jx = p
        for i, m in enumerate(new_m):
            updater.states[i]._jx = m
        ex._pending_grads = None
        if return_outputs == "device":
            return list(outs_stack)
        if return_outputs:
            return [np.asarray(o) for o in outs_stack]  # host-sync: ok — explicit host-output mode
        return None

    def bulk_cost_analysis(self):
        """XLA cost analysis of ONE compiled training step.

        Requires a prior :meth:`run_bulk` call (uses its signature).  The
        bulk step is a ``lax.scan`` over K batches; XLA's HLO cost
        analysis counts the loop body once, so the returned ``flops`` /
        ``bytes accessed`` are per-step figures — the measured FLOP count
        the benchmark divides by batch size for FLOPs/image (no
        hand-derived constants).  Returns the cost dict, or None before
        the first bulk call.
        """
        sig = getattr(self, "_last_bulk_sig", None)
        if sig is None:
            return None
        fn, args = sig
        return fn.lower(*args).compile().cost_analysis()

    def predict_bulk(self, batches):
        """Run ``len(batches)`` inference forwards as ONE XLA dispatch
        (lax.scan over the stacked inputs); returns a list of per-batch
        output lists.  The serving-throughput companion of ``run_bulk``."""
        import jax
        import jax.numpy as jnp

        assert self.binded and self.params_initialized
        if not batches:
            return []
        if self._dist_dp or self._exec._segments is not None:
            outs = []
            for b in batches:
                self.forward(b, is_train=False)
                outs.append(list(self.get_outputs()))
            return outs
        ex = self._exec
        scan_names = [n for n in (self._data_names + self._label_names)
                      if n in ex.arg_dict]
        fn = ex._get_fn(("predict_scan", tuple(scan_names)))
        dev = ex._ctx.jax_device()
        name_pos = {}
        for i, n in enumerate(self._data_names):
            name_pos[n] = ("data", i)
        for i, n in enumerate(self._label_names):
            name_pos[n] = ("label", i)

        def stack(n):
            kind, i = name_pos[n]
            vals = []
            for b in batches:
                arrs = b.data if kind == "data" else (b.label or [])
                if i >= len(arrs):  # label-less inference batches
                    vals.append(ex.arg_dict[n]._jx)
                    continue
                v = arrs[i]
                jx = v._jx if isinstance(v, NDArray) else jnp.asarray(v)
                vals.append(jx.astype(ex.arg_dict[n]._jx.dtype))
            return jax.device_put(jnp.stack(vals), dev)

        # cache pins the keyed buffers so id()s cannot be reused stale
        keyed = [v._jx if isinstance(v, NDArray) else None
                 for b in batches
                 for v in list(b.data) + list(b.label or [])]
        skey = tuple(id(v) if v is not None else None for v in keyed)
        cached = getattr(self, "_pred_stack_cache", None)
        if cached is not None and cached[0] == skey and None not in skey:
            stacks = cached[1]
        else:
            stacks = [stack(n) for n in scan_names]
            self._pred_stack_cache = (skey, stacks, keyed)
        static = [n for n in ex.arg_names if n not in scan_names]
        static_vals = [ex.arg_dict[n]._jx for n in static]
        aux = [a._jx for a in ex.aux_arrays]
        call_args = (static_vals, aux, ex.next_rng(), stacks)
        # same abstract signature record as run_bulk, so inference-only
        # benches get bulk_cost_analysis (measured FLOPs -> MFU) too
        self._last_bulk_sig = (fn, jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), call_args))
        outs_stack = fn(*call_args)
        result = []
        for k in range(len(batches)):
            result.append([NDArray._from_jax(o[k], ex._ctx)
                           for o in outs_stack])
        ex.outputs = result[-1]
        return result

    def update(self):
        """reference ``module.py:553`` + model.py:88/99.

        Fast path: for plain/momentum SGD with no kvstore, ONE jitted
        multi-tensor update over all parameters with donated buffers — the
        TPU analog of the reference's fused ``sgd_mom_update`` kernels
        without per-parameter dispatch.  Everything else goes through the
        kvstore/updater path for exact reference semantics.
        """
        assert self.binded and self.params_initialized \
            and self.optimizer_initialized
        if self._pending_full:
            self._run_full_step()
            return
        local_kv = self._kvstore is None or (
            not self._update_on_kvstore and "dist" not in self._kvstore.type) \
            or getattr(self._kvstore, "in_graph_sync", False)
        if local_kv and self._updater is not None \
                and self._try_fused_update():
            return
        param_arrays = [[self._exec.arg_dict[n]] for n in self._param_names]
        grad_arrays = [[self._exec.grad_dict.get(n)]
                       for n in self._param_names]
        if self._mesh is not None and self._updater is not None:
            # mesh-placed weights/grads cannot enter an update jit with
            # locally-committed optimizer state: create + place states
            # (momentum, adam mean/var, ...) on the module mesh up front
            for index, n in enumerate(self._param_names):
                if self._exec.grad_dict.get(n) is None:
                    continue
                if index not in self._updater.states:
                    self._updater.states[index] = \
                        self._optimizer.create_state(
                            index, self._exec.arg_dict[n])
                self._place_opt_state(index, self._updater.states[index], n)
        if self._update_on_kvstore:
            _update_params_on_kvstore(param_arrays, grad_arrays,
                                      self._kvstore)
        else:
            # in_graph_sync: gradients were already globally psum'd inside
            # the step — pushing them through the PS would sum them across
            # num_workers a second time.  The PS stays a control plane
            # (init / explicit push-pull), not a gradient plane.
            kv = None if getattr(self._kvstore, "in_graph_sync", False) \
                else self._kvstore
            _update_params(param_arrays, grad_arrays, updater=self._updater,
                           num_device=1, kvstore=kv)

    def _get_hyper_arrays(self, optimizer, n):
        """Device copies of per-index lr/wd, re-uploaded only when a
        scheduler changes the values.  Multi-process mode passes host
        numpy (pjit replicates them) — a committed local array would
        clash with global-mesh arguments."""
        import jax.numpy as jnp

        lr_vals = tuple(optimizer._get_lr(i) for i in range(n))
        wd_vals = tuple(optimizer._get_wd(i) for i in range(n))
        cached = getattr(self, "_fused_hyper_cache", None)
        if cached is None or cached[0] != lr_vals or cached[1] != wd_vals:
            mk = np.asarray if self._dist_dp else \
                (lambda v, d=None: jnp.asarray(v, jnp.float32))
            self._fused_hyper_cache = (
                lr_vals, wd_vals,
                mk(np.asarray(lr_vals, np.float32)),   # host-sync: ok — python floats, no device buffer
                mk(np.asarray(wd_vals, np.float32)))  # host-sync: ok — python floats, no device buffer
            cached = self._fused_hyper_cache
        return cached[2], cached[3]

    def _place_opt_state(self, idx, state, name=None):
        """Optimizer state arrays (momentum etc.) join the module mesh —
        a locally-committed buffer cannot enter a jit whose other
        arguments are mesh-placed (multihost jit rejects it outright).
        States shard exactly like their parameter (a TP-sharded weight's
        momentum shards with it)."""
        if state is None or self._mesh is None \
                or idx in self._dist_placed_states:
            return state

        def place(arr):
            if arr is None:
                return
            if self._dist_dp:
                from .. import dist as _dist

                arr._jx = _dist.replicate(
                    self._mesh, np.asarray(arr._jx))  # host-sync: ok — dist init-time state placement
            else:
                import jax
                from jax.sharding import NamedSharding, PartitionSpec as P

                spec = self._param_spec(name)
                if name is not None \
                        and name in getattr(self, "_zero_names", ()):
                    # ZeRO: the optimizer state stores row-sharded over
                    # the batch axis — each device holds 1/world of it
                    spec = P(self._batch_axis_name())
                arr._jx = jax.device_put(
                    arr._jx, NamedSharding(self._mesh, spec))

        # multi-array states (adam mean/var, rmsprop n/g/delta) place
        # every element alongside the parameter
        if isinstance(state, (tuple, list)):
            for s in state:
                place(s)
        else:
            place(state)
        self._dist_placed_states.add(idx)
        return state

    def _try_fused_update(self):
        import jax
        import jax.numpy as jnp

        optimizer = self._optimizer
        if type(optimizer) is not opt.SGD:
            return False
        names = [n for n in self._param_names
                 if self._exec.grad_dict.get(n) is not None]
        if not names:
            return True
        updater = self._updater
        zero = self._mesh_zero_names(names)
        # the mesh rides the key: a module re-initialized onto a new
        # device plane must not reuse a step whose shard_map/sharding
        # closures captured the old mesh
        step_key = (tuple(names), optimizer.momentum,
                    optimizer.rescale_grad, optimizer.clip_gradient,
                    tuple(zero), self._mesh)
        # states are created + mesh-placed EVERY call, not just when the
        # step compiles: _place_opt_state memoizes via
        # _dist_placed_states, and a mid-fit set_states restore (NaN
        # rollback, load_optimizer_states) re-commits host arrays AND
        # clears that memo — the re-placement must happen even when the
        # compiled step is cached.  Momentum lives in the Updater so
        # save/load_optimizer_states keeps working
        for idx, n in enumerate(names):
            if idx not in updater.states:
                updater.states[idx] = optimizer.create_state(
                    idx, self._exec.arg_dict[n])
            self._place_opt_state(idx, updater.states[idx], n)
        if self._fused_step is None \
                or getattr(self, "_fused_step_key", None) != step_key:
            momentum = optimizer.momentum
            rescale = optimizer.rescale_grad
            clip = optimizer.clip_gradient if optimizer.clip_gradient \
                is not None else -1.0

            from ..executor import sgd_step_math

            mstep = None
            if zero:
                # the shared per-param dispatch + layout pinning — the
                # same helper train_sgd_mesh compiles, so the two fused
                # paths cannot diverge numerically
                from ..kvstore_mesh import mesh_param_step

                mstep = mesh_param_step(
                    self._mesh, momentum, rescale, clip, zero,
                    axis_name=self._batch_axis_name())
            step_names = list(names)

            def step(params, grads, moms, lrs, wds):
                new_p, new_m = [], []
                for i, (p, g) in enumerate(zip(params, grads)):
                    m_in = moms[i] if momentum != 0.0 else None
                    if mstep is not None:
                        np_, nm, _flag = mstep(step_names[i], p, g,
                                               m_in, lrs[i], wds[i])
                    else:
                        np_, nm = sgd_step_math(
                            p, g, m_in, lrs[i], wds[i], momentum,
                            rescale, clip)
                    new_p.append(np_)
                    if nm is not None:
                        new_m.append(nm)
                return new_p, new_m

            self._fused_step = _compile_cache.instrument(
                _perfdebug.instrument(
                    jax.jit(step, donate_argnums=(0, 2)),
                    self._exec._symbol_name(), "fused_update"),
                self._exec._symbol_name(), "fused_update")
            self._fused_step_key = step_key
        # per-index bookkeeping keeps num_update/scheduler semantics
        for idx in range(len(names)):
            optimizer._update_count(idx)
        lrs, wds = self._get_hyper_arrays(optimizer, len(names))
        params = [self._exec.arg_dict[n]._jx for n in names]
        grads = [self._exec.grad_dict[n]._jx for n in names]
        moms = [updater.states[i]._jx for i in range(len(names))] \
            if optimizer.momentum != 0.0 else []
        new_p, new_m = self._fused_step(params, grads, moms, lrs, wds)
        for n, p in zip(names, new_p):
            self._exec.arg_dict[n]._jx = p
        for i, m in enumerate(new_m):
            updater.states[i]._jx = m
        return True

    def get_outputs(self, merge_multi_context=True):
        assert self.binded
        self._materialize_pending()
        if self._dist_dp:
            # per-worker view: this process's rows of the global batch
            # (the reference's per-worker outputs/metric semantics)
            from .. import dist as _dist
            from ..ndarray import array as nd_array

            return [nd_array(_dist.local_rows(o._jx))
                    for o in self._exec.outputs]
        return self._exec.outputs

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.inputs_need_grad
        self._materialize_pending()
        return [self._exec.grad_dict.get(n) for n in self._data_names]

    def update_metric(self, eval_metric, labels):
        if isinstance(eval_metric, _metric.DeviceMetric) \
                and not self._dist_dp:
            if labels and self._label_shapes:
                # the labels were already loaded onto the executor's
                # device by forward()'s _load_io — hand the bound arrays
                # to the device metric instead of re-shipping (or worse,
                # re-materializing) the iterator's host buffers.  Only
                # when THIS batch carried labels: an unlabeled batch must
                # keep its (empty) list so the metric errors exactly like
                # the host path, not silently read stale bound buffers
                bound = [self._exec.arg_dict[n]
                         for n, _ in self._label_shapes
                         if n in self._exec.arg_dict]
                if len(bound) == len(labels):
                    labels = bound
            # under the in-graph NaN guard, a flagged batch's statistics
            # are zeroed inside the metric's accumulation jit — exact
            # skip-batch metric semantics at ANY check cadence, no sync
            skip = self._exec._nan_batch \
                if getattr(self._exec, "_nan_guard", False) else None
            eval_metric.update(labels, self.get_outputs(), skip=skip)
            return
        eval_metric.update(labels, self.get_outputs())

    def _device_put_batch(self, name, arr):
        """Prefetch-thread H2D placer (``fit(prefetch_to_device=True)``):
        move ONE input batch array onto the bound array's device — with
        the MODULE's sharding for that input, so mesh contexts get the
        same batch-axis placement ``Module._shard`` committed — while
        the previous step's compute is still in flight.  Runs on the
        ``DevicePrefetchIter`` background thread; ``_load_io``'s
        device_put then finds the data already resident (a no-op put).

        The sharding is recomputed from the mesh, NOT read off the
        bound buffer: on a fresh bind the buffer can still carry its
        single-device placement (allocation happens before ``_shard``
        commits the mesh layout, and a rebind can race the background
        producer), and a single-device put would force the step to
        re-lay out every batch on the blocking path — the exact copy
        the prefetch thread exists to hide.  Regression-pinned by
        tests/test_mesh_kvstore.py."""
        import jax

        dst = self._exec.arg_dict.get(name) if self._exec is not None \
            else None
        if dst is None:
            return arr
        sharding = dst._jx.sharding
        if self._mesh is not None and not self._dist_dp:
            from jax.sharding import NamedSharding, PartitionSpec as P

            batch_axis = name in self._data_names \
                or name in self._label_names
            spec = P(self._batch_axis_name()) if batch_axis \
                else self._param_spec(name)
            sharding = NamedSharding(self._mesh, spec)
        raw = arr._transfer_src() if isinstance(arr, NDArray) \
            else np.asarray(arr)  # host-sync: ok — host iterator batch, not a device buffer
        if isinstance(raw, np.ndarray) and raw.dtype != dst._jx.dtype:
            raw = raw.astype(dst._jx.dtype)
        return NDArray._from_jax(jax.device_put(raw, sharding), dst._ctx)

    def install_monitor(self, mon):
        assert self.binded
        mon.install(self._exec)

    # -- cross-replica integrity audit (docs/resilience.md) ---------------
    def _audit_names(self):
        """The replicated state the integrity audit fingerprints: every
        parameter whose spec is fully replicated (TP-sharded
        ``shard_rules`` params live intentionally split — there is no
        cross-replica copy to compare) plus the aux states (BN stats).
        ZeRO params ARE included: the update's all-gather re-enters
        them replicated, which is how the ZeRO-owned rows get their
        post-gather check."""
        from jax.sharding import PartitionSpec as P

        rep = P()
        names = [n for n in self._param_names
                 if self._param_spec(n) == rep
                 and self._exec.arg_dict.get(n) is not None]
        return names + list(self._aux_names)

    def _audit_array(self, name):
        d = self._exec.arg_dict.get(name)
        return d if d is not None else self._exec.aux_dict[name]

    def _bitflip_replica(self, name):
        """fault 'audit.bitflip': rebuild ``name``'s replicated array
        with ONE bit flipped on device 0's replica only — the observable
        state of a host/HBM bit-flip or a corrupt collective that the
        next audit must catch.  Uses per-device buffers under the same
        replicated sharding, so nothing but the audited bit pattern
        changes."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        arr = self._audit_array(name)
        host = np.ascontiguousarray(np.asarray(arr._jx))  # host-sync: ok — fault-injection path, not the hot loop
        bad = host.copy()
        bad.view(np.uint8).flat[0] ^= 1
        devs = list(self._mesh.devices.flat)
        bufs = [jax.device_put(bad if i == 0 else host, d)
                for i, d in enumerate(devs)]
        arr._jx = jax.make_array_from_single_device_arrays(
            host.shape, NamedSharding(self._mesh, P()), bufs)
        self.logger.warning(
            "fault 'audit.bitflip': flipped one bit of %r on replica 0",
            name)

    def _run_integrity_audit(self, policy, prefix, epoch, nbatch):
        """One cross-replica integrity audit
        (:func:`~mxnet_tpu.kvstore_mesh.build_replica_audit`): fold
        per-param bit-pattern checksums per mesh replica, compare
        in-graph, read ONE tiny result pair.  A mismatch is silent
        divergence/corruption — replicated state must agree exactly —
        and trips ``policy``: ``'raise'`` →
        :class:`~mxnet_tpu.sentinel.ReplicaDivergence`, ``'rollback'``
        → restore the last good checkpoint.  No-op (debug-logged once)
        off the mesh plane or on a 1-device mesh, where there are no
        replicas to disagree."""
        kv = self._kvstore
        if self._mesh is None or self._dist_dp or kv is None \
                or not getattr(kv, "is_mesh", False) \
                or int(self._mesh.shape[self._batch_axis_name()]) <= 1:
            if not getattr(self, "_audit_skip_logged", False):
                self._audit_skip_logged = True
                self.logger.debug(
                    "integrity audit skipped: needs fit(kvstore='mesh') "
                    "with a >1-device data axis")
            return None
        names = self._audit_names()
        if not names:
            return None
        if _faults.should_fire("audit.bitflip"):
            self._bitflip_replica(names[0])
        arrays = [self._audit_array(n)._jx for n in names]
        key = (self._mesh,
               tuple((a.shape, str(a.dtype)) for a in arrays))
        cached = getattr(self, "_audit_fn_cache", None)
        if cached is None or cached[0] != key:
            from ..kvstore_mesh import build_replica_audit

            cached = (key, _perfdebug.instrument(
                build_replica_audit(self._mesh, self._batch_axis_name()),
                self._exec._symbol_name(), "replica_audit"))
            self._audit_fn_cache = cached
        res = cached[1](arrays)
        with _tracing.host_read("audit"):
            res = np.asarray(res)  # host-sync: ok — the audit's one tiny result read
        count, first = int(res[0]), int(res[1])
        _telemetry.inc("reliability.audits")
        if count == 0:
            return 0
        bad = names[first] if 0 <= first < len(names) else "?"
        world = int(self._mesh.shape[self._batch_axis_name()])
        _telemetry.inc("reliability.divergences")
        _telemetry.event("reliability.divergence", epoch=epoch,
                         batch=nbatch, arrays=count, first=bad,
                         action=policy)
        _perfdebug.flight_dump("divergence", epoch=epoch, nbatch=nbatch,
                               arrays=count, first=bad)
        if policy == "rollback":
            self.logger.warning(
                "integrity audit: %d replicated array(s) diverged "
                "bit-wise across the %d-way mesh (first: %r); rolling "
                "back to the last valid checkpoint", count, world, bad)
            self._rollback_to_checkpoint(prefix)
            return count
        from ..sentinel import ReplicaDivergence

        raise ReplicaDivergence(
            "cross-replica integrity audit failed at epoch %d batch %d: "
            "%d replicated array(s) diverged bit-wise across the %d-way "
            "mesh (first: %r) — silent divergence or corruption "
            "(replicated state must agree exactly; set "
            "MXNET_AUDIT_POLICY=rollback to auto-recover)"
            % (epoch, nbatch, count, world, bad))

    # -- compile-once warm-up (docs/how_to/perf.md "Compile once") --------
    def warm_from_manifest(self, manifest):
        """Replay a compile-once warm-up manifest: AOT-build + compile
        every executable a previous run of this model recorded, BEFORE
        the first real batch dispatches.  With the persistent compile
        cache populated (on by default, ``compile_cache``) the whole replay
        is disk loads — a ``resume="auto"`` restart performs zero cold
        XLA compiles on the training hot path.  State-safe: nothing
        executes, so parameters / optimizer state / rng are untouched
        (exact-resume bit-identity is preserved).  Returns the replay
        summary dict."""
        assert self.binded, "call bind before warm_from_manifest"
        entries = manifest.get("entries", []) \
            if isinstance(manifest, dict) else list(manifest)
        # the registry records per process, so a multi-model run's
        # manifest can carry foreign executables: prefer the entries
        # recorded for THIS executor when any match (a replay of a
        # foreign program would just burn a trace and log an error)
        mine = [e for e in entries
                if e.get("exec") == self._exec._symbol_name()]
        if mine:
            entries = mine
        t0 = _time_mod.perf_counter()
        summary = self._exec.precompile(entries, logger=self.logger)
        dt = _time_mod.perf_counter() - t0
        _telemetry.inc("compile_cache.manifest.replays")
        _telemetry.event("compile_cache.manifest_replay",
                         exec=self._exec._symbol_name(),
                         seconds=round(dt, 3), **summary)
        self.logger.info(
            "compile_cache: warm-up manifest replayed in %.2fs — %d "
            "program(s) pre-built, %d skipped, %d error(s), %d "
            "fingerprint change(s)", dt, summary["replayed"],
            summary["skipped"], summary["errors"],
            summary["fingerprint_changes"])
        return summary

    # -- checkpointing ----------------------------------------------------
    def _capture_state_arrays(self):
        """Device-side capture for async snapshots (docs/resilience.md):
        one dispatched device-to-device ``NDArray.copy()`` per parameter
        / aux / optimizer-state array — NO host sync on the training
        loop; the background writer does the device→host transfer when
        it serializes.  Returns ``(arg, aux, opt_states, opt_counts)``
        where ``opt_states`` mirrors ``Updater.states`` (None when the
        optimizer plane lives on the kvstore) and ``opt_counts`` carries
        the scheduler-relevant update counters."""
        import jax

        assert self.binded and self.params_initialized
        # a staged fused step must land before its params are captured
        self._materialize_pending()
        ex = self._exec
        # ONE jitted multi-array copy instead of a dispatch per array:
        # at snapshot cadence the per-dispatch round trip would be the
        # whole capture cost
        flat = []

        def _grab(arr):
            flat.append(arr._jx)
            return len(flat) - 1

        param_idx = {n: _grab(ex.arg_dict[n]) for n in self._param_names}
        aux_idx = {n: _grab(a) for n, a in ex.aux_dict.items()}
        state_spec = None
        has_states = self.optimizer_initialized \
            and self._updater is not None and not self._update_on_kvstore
        if has_states:
            def _spec(s):
                if s is None:
                    return None
                if isinstance(s, (tuple, list)):
                    return ("seq", type(s), [_spec(x) for x in s])
                if isinstance(s, NDArray):
                    return ("nd", _grab(s), s._ctx)
                return ("raw", s)

            state_spec = {i: _spec(s)
                          for i, s in self._updater.states.items()}
        fn = getattr(self, "_capture_copy_fn", None)
        if fn is None:
            fn = jax.jit(lambda xs: [x + 0 for x in xs])
            self._capture_copy_fn = fn
        copies = fn(flat) if flat else []

        def _wrap(i, ctx):
            return NDArray._from_jax(copies[i], ctx)

        arg = {n: _wrap(i, ex.arg_dict[n]._ctx)
               for n, i in param_idx.items()}
        aux = {n: _wrap(i, ex.aux_dict[n]._ctx)
               for n, i in aux_idx.items()}
        opt_states = None
        opt_counts = None
        if has_states:
            def _build(spec):
                if spec is None:
                    return None
                kind = spec[0]
                if kind == "seq":
                    return spec[1](_build(x) for x in spec[2])
                if kind == "nd":
                    return _wrap(spec[1], spec[2])
                return spec[1]

            opt_states = {i: _build(s) for i, s in state_spec.items()}
        if self._optimizer is not None:
            opt_counts = {
                "num_update": int(self._optimizer.num_update),
                "index_update_count": {
                    str(k): int(v) for k, v in
                    self._optimizer._index_update_count.items()}}
        return arg, aux, opt_states, opt_counts

    def _elastic_param_entries(self):
        """The kvstore key space of this module's parameters:
        ``[(key, name)]`` in the exact ``init_optimizer`` enumeration
        order — the domain of the elastic reshard's
        :func:`~mxnet_tpu.elastic.assign_keys` key-ownership map."""
        return list(enumerate(self._param_names))

    def _elastic_pull_params(self):
        """Pull every parameter from the (just-rehydrated) coordinator
        into the bound executor — the final step of the elastic reshard
        cycle, after which every member holds the identical
        post-reshard state."""
        assert self._kvstore is not None
        for i, n in enumerate(self._param_names):
            self._kvstore.pull(i, [self._exec.arg_dict[n]], priority=-i)

    def _restore_opt_snapshot(self, states_bytes, opt_counts):
        """Resume half of :meth:`_capture_state_arrays`: re-install the
        pickled updater states and the optimizer's update counters so a
        resumed run's lr schedule continues exactly."""
        if states_bytes is not None and self._updater is not None:
            from ..elastic import SERVER_STATES_KEY

            payload = None
            if SERVER_STATES_KEY.encode() in states_bytes:
                # the marker string can only appear in the pickle of an
                # elastic leader snapshot's marker dict — the bytes scan
                # gates the unpickle so a plain (non-elastic) updater
                # tree is never deserialized twice; the dict check below
                # stays authoritative
                try:
                    payload = pickle.loads(states_bytes)
                except Exception:  # noqa: broad-except — not a plain
                    # pickle; let set_states apply its own format handling
                    payload = None
            if isinstance(payload, dict) and SERVER_STATES_KEY in payload:
                # an elastic leader snapshot: its .states carry the
                # SERVER-side updater blobs (re-installed on the
                # coordinator by the reshard cycle), not a local updater
                # tree — installing them locally would corrupt the state
                # structure.  A non-elastic resume of an elastic prefix
                # restarts local momentum instead.
                self.logger.warning(
                    "resume: snapshot optimizer states are elastic "
                    "coordinator-side blobs; local updater momentum "
                    "restarts from zero")
            else:
                self._updater.set_states(states_bytes)
                # unpickled states are locally-committed host arrays —
                # the next update jit re-places them on the module mesh
                self._dist_placed_states.clear()
        if opt_counts and self._optimizer is not None:
            self._optimizer.num_update = int(
                opt_counts.get("num_update", self._optimizer.num_update))
            idx = opt_counts.get("index_update_count") or {}
            self._optimizer._index_update_count = {
                int(k): int(v) for k, v in idx.items()}

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """reference module.py save_checkpoint"""
        arg_params, aux_params = self.get_params()
        save_checkpoint(prefix, epoch, self.symbol, arg_params, aux_params)
        if save_optimizer_states:
            self.save_optimizer_states("%s-%04d.states" % (prefix, epoch))

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """reference module.py load"""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params_cache = args
        mod._aux_params_cache = auxs

        orig_bind = mod.bind

        def bind_and_set(*a, **kw):
            orig_bind(*a, **kw)
            mod.set_params(args, auxs, allow_missing=False)

        mod.bind = bind_and_set
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            from ..base import atomic_write_bytes

            atomic_write_bytes(fname, self._updater.get_states())

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as fin:
                self._updater.set_states(fin.read())
            # the unpickled states are locally-committed host arrays —
            # they must be re-placed on the module mesh before the next
            # update jit sees them
            self._dist_placed_states.clear()
