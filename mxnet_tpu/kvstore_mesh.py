"""kvstore='mesh' — the GSPMD training plane (``mx.kv.create('mesh')``).

The reference exchanges gradients through a KVStore: per-key ``push``
(aggregate) + ``pull`` (redistribute), with the optimizer applied where
the weights live.  On a TPU mesh that whole plane dissolves into the
jitted train step (PAPER.md north star: ICI ``psum`` replacing
KVStore/NCCL allreduce): data/label shard over the mesh's batch axis,
parameters replicate, and XLA GSPMD compiles the gradient all-reduce
*into* the step — no host round-trips, no socket plane, no per-key RPC.
:class:`KVStoreMesh` is the KVStore-interface face of that plane:
``fit(kvstore='mesh')`` selects it, ``Module.init_optimizer`` adopts its
mesh (re-binding the executor arrays as global jax Arrays), and from
then on the PR 4 fused ``train_sgd``/``train_guard`` executor kinds run
the whole dp step as one XLA program.

ZeRO-style weight-update sharding (Xu et al., "Automatic Cross-Replica
Sharding of Weight Update in Data-Parallel Training"): for eligible
parameters the update itself is sharded over the batch axis —

* the batch-summed gradient is CONSUMED row-sharded, so the GSPMD
  partitioner lowers the would-be all-reduce to a **reduce-scatter**;
* each device owns its row slice of the optimizer state (momentum) and
  computes only its slice of the update — per-device optimizer-state
  HBM drops ~world-size (``optimizer_state_hbm`` pins it);
* the updated rows **all-gather** back into the replicated parameter.

The sharded update runs under :func:`jax.shard_map` with
the collectives spelled explicitly (``all_gather`` / ``psum`` over the
named batch axis), so graftlint's ``collective-consistency`` pass can
prove the axis vocabulary and CI's seeded-mutation test can verify a
swapped axis name is caught.

Snapshots shard with the update plane: see
``checkpoint.write_snapshot`` (per-shard payload files + a stitching
manifest keyed by :func:`mxnet_tpu.elastic.assign_keys`) and
docs/how_to/multi_devices.md "Sharded fit".
"""

from __future__ import annotations

import os

import numpy as np

from .base import MXNetError
from .kvstore import KVStore, _ctype_key_value

__all__ = ["KVStoreMesh", "default_mesh", "zero_sgd_update",
           "zero_eligible_names", "optimizer_state_hbm",
           "build_replica_audit", "DATA_AXIS"]

#: the mesh axis that shards the batch (and the ZeRO update rows)
DATA_AXIS = "data"


def default_mesh():
    """The process-default device plane: a 1-axis ``('data',)`` mesh over
    ``MXNET_MESH_DEVICES`` jax devices (default: all of them)."""
    from .parallel.mesh import make_mesh

    n = os.environ.get("MXNET_MESH_DEVICES")
    n = int(n) if n else None
    return make_mesh(n_devices=n, axis_names=(DATA_AXIS,))


class KVStoreMesh(KVStore):
    """The KVStore interface as a *device plane* over a jax Mesh.

    There is no server and no transport: ``init`` registers the live
    parameter array (mesh-placed by the owning Module), ``push`` sums
    the pushed device list and applies the updater on the stored value
    (the reference's update-where-the-weights-live semantics), ``pull``
    copies the stored value out.  During ``fit`` none of that runs per
    step — ``in_graph_sync`` tells Module the gradient plane is already
    inside the jitted step, so ``update()`` bypasses the store entirely
    and the per-step collective traffic is exactly the in-graph
    ``psum``/reduce-scatter/all-gather GSPMD compiled (pinned by
    tests/test_mesh_kvstore.py: zero kvstore push/pull per step)."""

    #: Module keys mesh adoption / ZeRO / sharded snapshots off this
    is_mesh = True
    #: gradients reduce in-graph; the updater runs locally on every
    #: device (same update everywhere — there is no server optimizer)
    in_graph_sync = True

    def __init__(self, mesh=None):
        super().__init__("mesh")
        self.mesh = mesh if mesh is not None else default_mesh()
        names = self.mesh.axis_names
        self.axis = DATA_AXIS if DATA_AXIS in names else names[0]

    @property
    def world(self):
        """Devices on the batch axis — the gradient-reduction fan-in."""
        return int(self.mesh.shape[self.axis])

    @property
    def num_workers(self):
        # single-process plane: Module already binds the GLOBAL batch,
        # so rescale_grad must NOT be scaled by the device count
        return 1

    # -- data plane (API parity; fit never routes gradients here) --------
    def init(self, key, value):
        """Like the base store, a duplicate key is an error; the stored
        value is a live REFERENCE to the bound (mesh-placed) array, not
        a copy — the mesh store IS the training state, so ``pull``
        observes training progress exactly like the reference's
        update-on-kvstore pull."""
        keys, vals = _ctype_key_value(key, value)
        for k, vlist in zip(keys, vals):
            if k in self._store:
                raise MXNetError("key %r already initialized" % k)
            self._store[k] = vlist[0]

    # push/pull/save_optimizer_states inherit the base local semantics,
    # applied to the live references: push device-merges and runs the
    # updater (or assigns) on the stored value, and the optimizer
    # states may hold mesh-sharded device arrays — pickling gathers
    # each to one full host buffer, so the written bytes match a
    # single-device run's


# -- ZeRO update math --------------------------------------------------------

def zero_eligible_names(names, shapes, world, min_elems=None):
    """The subset of ``names`` whose update can shard over ``world``
    devices: leading dim divisible by the world size, and at least
    ``MXNET_MESH_ZERO_MIN_ELEMS`` elements (sharding tiny biases buys
    nothing and costs an all-gather each)."""
    if world <= 1:
        return ()
    if min_elems is None:
        min_elems = int(os.environ.get(
            "MXNET_MESH_ZERO_MIN_ELEMS", "1024") or 1024)
    out = []
    for n in names:
        shp = shapes[n]
        if shp and shp[0] % world == 0 \
                and int(np.prod(shp)) >= min_elems:
            out.append(n)
    return tuple(out)


def zero_sgd_update(mesh, momentum, rescale_grad, clip_gradient,
                    guard=False, axis_name=DATA_AXIS):
    """Build the ZeRO-sharded SGD(-momentum) step for ONE parameter.

    Returns ``apply(p, g, m, lr, wd) -> (new_p, new_m, flag)`` (``new_m``
    / ``flag`` are None when momentum == 0 / ``guard`` is False).  The
    body runs under ``shard_map`` over ``axis_name``:

    * ``p`` enters row-sharded (a local slice of the replicated param);
    * ``g`` enters row-sharded — the batch-summed gradient consumed at
      ``P(axis)`` is lowered by the partitioner to a reduce-scatter
      instead of the all-reduce the unsharded update would need;
    * ``m`` (the persistent optimizer-state rows) enters and leaves
      row-sharded — each device stores only its 1/world slice;
    * the updated rows ``all_gather`` back into the full parameter, and
      under ``guard`` the per-shard non-finite flag ``psum``s into the
      global batch flag.

    The per-row math is :func:`~mxnet_tpu.executor.sgd_step_math` — the
    same function the unsharded fused step uses, so a 1-device mesh is
    bit-identical to plain ``fit`` by construction.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from .executor import sgd_step_math

    has_mom = momentum != 0.0

    def _shard_math(p, g, m, lr, wd):
        new_p_shard, new_m = sgd_step_math(
            p, g, m, lr, wd, momentum, rescale_grad, clip_gradient)
        new_p = jax.lax.all_gather(new_p_shard, axis_name, axis=0,
                                   tiled=True)
        flag = None
        if guard:
            bad = jnp.logical_not(jnp.all(jnp.isfinite(g)))
            flag = jax.lax.psum(bad.astype(jnp.int32), axis_name) > 0
        return new_p, new_m, flag

    if has_mom:
        def body(p, g, m, lr, wd):
            new_p, new_m, flag = _shard_math(p, g, m, lr, wd)
            return (new_p, new_m, flag) if guard else (new_p, new_m)

        in_specs = (P(axis_name), P(axis_name), P(axis_name), P(), P())
        out_specs = (P(), P(axis_name), P()) if guard \
            else (P(), P(axis_name))
    else:
        def body(p, g, lr, wd):
            new_p, _m, flag = _shard_math(p, g, None, lr, wd)
            return (new_p, flag) if guard else (new_p,)

        in_specs = (P(axis_name), P(axis_name), P(), P())
        out_specs = (P(), P()) if guard else (P(),)

    # check_vma=False: the replicated outputs are established by the
    # explicit all_gather/psum above, which the static replication
    # checker cannot see through
    sm = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)

    def apply(p, g, m, lr, wd):
        res = sm(p, g, m, lr, wd) if has_mom else sm(p, g, lr, wd)
        if has_mom:
            return res if guard else (res[0], res[1], None)
        return (res[0], None, res[1]) if guard else (res[0], None, None)

    return apply


def mesh_param_step(mesh, momentum, rescale_grad, clip_gradient,
                    zero_names, guard=False, axis_name=DATA_AXIS):
    """Per-parameter update dispatch shared by BOTH mesh fused-step
    builders (executor ``train_sgd_mesh`` and Module's two-dispatch
    fused update), so their numerics and layout pinning can never
    diverge.  Returns ``step(name, p, g, m, lr, wd) -> (new_p,
    new_m_or_None, flag_or_None)``: ZeRO-eligible params route through
    :func:`zero_sgd_update`, the rest through plain ``sgd_step_math``;
    every output is pinned with ``with_sharding_constraint`` (params
    replicated, ZeRO momentum row-sharded) — an unconstrained output
    lets the partitioner pick a fresh layout each build and the stored
    arrays drift."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .executor import sgd_step_math

    zero_set = frozenset(zero_names)
    zupd = zero_sgd_update(mesh, momentum, rescale_grad, clip_gradient,
                           guard=guard, axis_name=axis_name)
    rep = NamedSharding(mesh, P())
    row = NamedSharding(mesh, P(axis_name))

    def step(name, p, g, m, lr, wd):
        if name in zero_set:
            new_p, new_m, flag = zupd(p, g, m, lr, wd)
        else:
            new_p, new_m = sgd_step_math(p, g, m, lr, wd, momentum,
                                         rescale_grad, clip_gradient)
            flag = None
        new_p = jax.lax.with_sharding_constraint(new_p, rep)
        if new_m is not None:
            new_m = jax.lax.with_sharding_constraint(
                new_m, row if name in zero_set else rep)
        return new_p, new_m, flag

    return step


# -- cross-replica integrity audit -------------------------------------------

def _bit_checksum(x):
    """uint32 wraparound sum of ``x``'s BIT PATTERN — not a float sum:
    two replicas that differ by one flipped mantissa/exponent/sign bit
    (or by a denormal/NaN payload a float compare would launder) always
    produce different checksums, and -0.0 vs +0.0 — numerically equal,
    bit-distinct — is flagged as the divergence it is.  Traced inside
    the audit program; 8-byte dtypes bitcast to a (..., 2) uint32 view
    (no uint64 dependence — jax's default x64-disabled mode would
    silently truncate it)."""
    import jax
    import jax.numpy as jnp

    if x.dtype == jnp.bool_:
        u = x.astype(jnp.uint8)
    elif jnp.issubdtype(x.dtype, jnp.integer) and x.dtype.itemsize <= 4:
        u = x
    else:
        width = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32,
                 8: jnp.uint32}[x.dtype.itemsize]
        u = jax.lax.bitcast_convert_type(x, width)
    return jnp.sum(u.astype(jnp.uint32))


def build_replica_audit(mesh, axis_name=DATA_AXIS):
    """ONE jitted program that verifies replica integrity in-graph.

    Returns ``audit(arrays) -> jax array [mismatch_count, first_bad]``:
    per mesh replica (shard along ``axis_name``), fold every input
    array to its :func:`_bit_checksum`, ``all_gather`` the per-replica
    checksum vectors over the axis, and count the arrays whose
    checksums do NOT agree bit-exactly across replicas.  Replicated
    params/aux MUST agree exactly — the cross-replica weight-update
    sharding plane (Xu et al.) re-establishes replication every step
    (ZeRO rows re-enter the replicated param through the update's
    all-gather, which is how "ZeRO-owned rows checked post-gather"
    falls out of auditing the params themselves) — so any difference
    is silent divergence or corruption, not numerics.  The caller does
    one small host read of the returned pair; everything else stays on
    device (docs/resilience.md "Cross-replica integrity audits").

    The per-replica view comes from ``shard_map`` with replicated
    in-specs: each device contributes ITS OWN copy of every replicated
    buffer, which is exactly what a bit-flip on one replica corrupts.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def body(arrays):
        local = jnp.stack([_bit_checksum(a) for a in arrays])
        every = jax.lax.all_gather(local, axis_name)   # (world, n)
        bad = jnp.any(every != every[0:1], axis=0)     # (n,)
        count = jnp.sum(bad.astype(jnp.int32))
        first = jnp.argmax(bad).astype(jnp.int32)      # 0 when clean
        return jnp.stack([count, first])

    # check_vma=False: the gathered comparison establishes the
    # replicated output itself — same rationale as zero_sgd_update
    sm = jax.shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                       check_vma=False)
    return jax.jit(lambda arrays: sm(arrays))


# -- accounting --------------------------------------------------------------

def _per_device_bytes(jx):
    """Max bytes any single device holds of ``jx`` (a jax Array):
    ``nbytes/world`` for a row-sharded state, ``nbytes`` for a
    replicated one — the quantity the ZeRO HBM claim is about."""
    per_dev = {}
    try:
        shards = jx.addressable_shards
    except AttributeError:
        return int(jx.nbytes)
    for s in shards:
        per_dev[s.device] = per_dev.get(s.device, 0) + int(s.data.nbytes)
    return max(per_dev.values()) if per_dev else int(jx.nbytes)


def optimizer_state_hbm(module):
    """``(per_device_bytes, total_logical_bytes)`` of the module's local
    updater states — the attribution the ZeRO acceptance pins (per-device
    optimizer-state HBM drops ~world-size vs the replicated baseline,
    where the two numbers are equal).  Complements the compiled-program
    view: with ``MXNET_PERF_ATTRIB=1`` the fused mesh step's
    per-partition ``argument_bytes`` in the :mod:`~mxnet_tpu.perfdebug`
    attribution tables shrinks by the same factor."""
    updater = getattr(module, "_updater", None)
    if updater is None:
        return (0, 0)
    per_dev = 0
    total = 0

    def walk(state):
        nonlocal per_dev, total
        if state is None:
            return
        if isinstance(state, (tuple, list)):
            for s in state:
                walk(s)
            return
        jx = getattr(state, "_jx", None)
        if jx is None:
            return
        per_dev += _per_device_bytes(jx)
        total += int(jx.nbytes)

    for state in updater.states.values():
        walk(state)
    return (per_dev, total)
