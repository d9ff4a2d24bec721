"""Parameter-server process for ``dist_*`` KVStore types.

Reference: ``src/kvstore/kvstore_dist_server.h`` (sync-mode per-key merge
rounds + server-side optimizer; async-mode apply-on-arrival) and
``python/mxnet/kvstore_server.py`` (auto server loop when
``DMLC_ROLE=server``).  The ps-lite ZMQ transport is replaced by
length-prefixed pickles over TCP — the host-side control/parameter plane.
On TPU pods the *gradient* plane should be in-graph ICI/DCN collectives
(``parallel/``); this PS preserves the reference's update-on-server
semantics (optimizer state lives on the server, workers only push/pull),
which collectives alone cannot express.

Wire protocol (all messages are pickled dicts, ``<u64 length><payload>``):

  register(role)                -> {rank, num_workers}
  init(key, value)              -> {version}        (first init wins)
  push(key, value, rank)        -> {version}        (version the push lands in)
  pull(key, version)            -> {value, version} (blocks until >= version)
  barrier()                     -> {}               (blocks for num_workers)
  set_optimizer(bytes)          -> {}               (pickled optimizer)
  stop()                        -> {}               (terminates the server)

Sync mode: pushes for a key accumulate per round (a worker's n-th push for
a key belongs to round n); when all ``num_workers`` land, the merged sum is
applied (updater if set, else assigned) and the key's version increments —
the per-key barrier of ``kvstore_dist_server.h:164``.  Async mode applies
every push immediately.

Elastic membership (``MXNET_ELASTIC``, docs/resilience.md "Elastic
membership & resharding"): the server doubles as the membership
coordinator.  It owns a monotonically increasing *membership epoch*;
workers join via ``register``, leave via graceful ``deregister`` or
heartbeat-death eviction, and every membership change bumps the epoch and
discards the old world's partial sync rounds.  Elastic push/pull/barrier
traffic carries the sender's epoch and is rejected with a typed
``stale_epoch`` reply when it belongs to an old world.  Extra commands:

  deregister(rank)               -> {epoch}        (graceful leave, bumps)
  membership()                   -> {epoch, ranks, num_workers}
  reshard_sync(rank)             -> {epoch, ranks, num_workers}
                                    (quiesce rendezvous: blocks until every
                                    member of the CURRENT epoch arrives;
                                    non-arrivers are evicted after the
                                    quiesce deadline)
  reshard_commit(rank, epoch)    -> {epoch}        (post-rehydration
                                    barrier; stale when membership moved)
  reshard_choice(rank, epoch[, set]) -> {epoch[, choice]}
                                    (adopted-generation rendezvous: the
                                    leader posts the snapshot generation
                                    the world rolls back to via ``set``;
                                    followers block until it lands)
  reload(key, value, epoch)      -> {version: 0}   (snapshot rehydration:
                                    set a key's value and reset its
                                    version/round bookkeeping)
"""

from __future__ import annotations

import importlib
import io as _io
import os
import pickle
import socket
import socketserver
import struct
import sys
import threading
import time
from collections import defaultdict

import numpy as np

__all__ = ["KVStoreServer", "run_server", "_init_kvstore_server_module"]

_LEN = struct.Struct("<Q")


def _pkg_mod(name):
    """Resolve a sibling package module WITHOUT the import system.

    When the auto server loop runs during ``import mxnet_tpu`` (reference
    semantics: a DMLC_ROLE=server process blocks on import), the package's
    import lock is held by the blocked main thread — handler threads doing
    ``from .optimizer import ...`` (or unpickling package classes, which
    __import__s their module) would deadlock on it.  All needed modules are
    already in sys.modules by the time the loop starts, so plain dict
    lookup is both safe and sufficient.
    """
    full = "%s.%s" % (__package__, name)
    mod = sys.modules.get(full)
    if mod is None:
        mod = importlib.import_module(full)
    return mod


def _tele():
    """The telemetry module via sys.modules (import-lock-safe inside
    handler threads, like ``_pkg_mod``); None when the package is not
    fully imported (standalone ``python kvstore_server.py``)."""
    if not __package__:
        return None
    return sys.modules.get("%s.telemetry" % __package__)


def _trace_mod():
    """The tracing module via sys.modules (same import-lock rules as
    :func:`_tele`); None when unavailable or tracing is disabled."""
    if not __package__:
        return None
    tr = sys.modules.get("%s.tracing" % __package__)
    if tr is None or not tr.enabled():
        return None
    return tr


def _elastic_knobs():
    """``(enabled, min_workers, max_workers, quiesce_deadline)`` env
    defaults.  Delegates to ``mxnet_tpu.elastic`` — the single
    definition of the knob grammar — whenever the package is loaded;
    standalone ``python kvstore_server.py`` falls back to the same
    literals (keep the two in sync)."""
    el = sys.modules.get("%s.elastic" % __package__) if __package__ \
        else None
    if el is not None:
        return (el.enabled(), el.min_workers(), el.max_workers(),
                el.quiesce_deadline())
    return (os.environ.get("MXNET_ELASTIC", "0") not in ("0", "", "false"),
            int(os.environ.get("MXNET_ELASTIC_MIN_WORKERS", "1") or 1),
            int(os.environ.get("MXNET_ELASTIC_MAX_WORKERS", "0") or 0),
            float(os.environ.get("MXNET_ELASTIC_QUIESCE_DEADLINE", "30")
                  or 30))


class _SysUnpickler(pickle.Unpickler):
    """Unpickler that prefers sys.modules over __import__ (deadlock-safe
    inside handler threads; see _pkg_mod)."""

    def find_class(self, module, name):
        mod = sys.modules.get(module)
        if mod is not None:
            return getattr(mod, name)
        return super().find_class(module, name)


def _loads(b):
    return _SysUnpickler(_io.BytesIO(b)).load()


def _freeze_states(states):
    """Shallow-clone an updater-state tree so it pickles safely OUTSIDE
    the coordinator lock: NDArray wrappers are rebuilt around their
    current jax values (immutable — an update REBINDS ``_jx``, so the
    clone keeps the view captured under the lock), containers are
    rebuilt per element."""
    ndarray = _pkg_mod("ndarray")

    def clone(v):
        if isinstance(v, ndarray.NDArray):
            return ndarray.NDArray._from_jax(v._jx, v._ctx)
        if isinstance(v, (tuple, list)):
            return type(v)(clone(x) for x in v)
        if isinstance(v, dict):
            return {k: clone(x) for k, x in v.items()}
        return v

    return clone(states)


class _Disconnected(Exception):
    """Raised inside a handler whose peer socket died mid-wait."""


class _DeadPeer(Exception):
    """A *different* worker's rank has been dead past the heartbeat
    deadline while this handler was blocked waiting on it; carries the
    human-readable diagnosis naming the lost rank."""

    def __init__(self, message):
        super().__init__(message)
        self.message = message


def _sock_dead(sock):
    """Non-blocking closed-peer probe (MSG_PEEK)."""
    try:
        return sock.recv(1, socket.MSG_PEEK | socket.MSG_DONTWAIT) == b""
    except (BlockingIOError, InterruptedError):
        return False
    except OSError:
        return True


def send_msg(sock, obj):
    payload = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    sock.sendall(_LEN.pack(len(payload)) + payload)


def recv_msg(sock):
    head = b""
    while len(head) < _LEN.size:
        chunk = sock.recv(_LEN.size - len(head))
        if not chunk:
            return None
        head += chunk
    n, = _LEN.unpack(head)
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(min(1 << 20, n - len(buf)))
        if not chunk:
            return None
        buf += chunk
    return _loads(bytes(buf))


class _KeyState:
    __slots__ = ("value", "version", "rounds", "pushed", "round_base")

    def __init__(self, value):
        self.value = value
        self.version = 0
        # round -> {"sum": running fold, "folded": n, "buf": {rank: v}}:
        # contributions fold in SORTED rank order, so the merged float
        # sum is independent of push arrival order — the property that
        # makes two replays of the same schedule (elastic chaos
        # included) bit-identical.  The fold is an EAGER prefix merge
        # (see _push): only out-of-order arrivals are buffered, so the
        # server does not hold a full world's gradients per round
        self.rounds = defaultdict(dict)
        self.pushed = defaultdict(int)                # rank -> push count
        # rank -> pushed count when the rank's current incarnation
        # registered; client rounds below it predate this incarnation and
        # must not be mistaken for replays (see _push dedup)
        self.round_base = defaultdict(int)


class KVStoreServer:
    """Threaded PS: one handler thread per connection."""

    def __init__(self, num_workers, sync_mode=True, host="127.0.0.1",
                 port=0, heartbeat_deadline=None, elastic=None,
                 min_workers=None, max_workers=None, quiesce_deadline=None):
        self.num_workers = num_workers
        self.sync_mode = sync_mode
        self.keys = {}
        self.lock = threading.Condition()
        self.updater = None
        self.next_rank = 0
        self.registered = set()   # ranks ever assigned (rejoin detection)
        self.live = {}            # rank -> connection currently holding it
        self.dead_since = {}      # rank -> monotonic time its conn died
        self.last_seen = {}       # rank -> monotonic time of last message
        # dead-peer detection: a blocked sync wait (barrier, versioned
        # pull) whose missing peer has been disconnected longer than this
        # raises a clean error naming the lost rank instead of hanging
        # forever (TF-paper-style fail-fast so the job can restart from a
        # checkpoint)
        if heartbeat_deadline is None:
            heartbeat_deadline = float(os.environ.get(
                "MXNET_KVSTORE_HEARTBEAT_DEADLINE", "120"))
        self.heartbeat_deadline = heartbeat_deadline
        self.barrier_waiters = set()  # ranks arrived at the current barrier
        self.barrier_gen = 0
        self.stopped = threading.Event()
        # -- elastic membership coordinator state (all guarded by
        # self.lock; docs/resilience.md "Elastic membership") ------------
        env_elastic, env_min, env_max, env_quiesce = _elastic_knobs()
        if elastic is None:
            elastic = env_elastic
        if min_workers is None:
            min_workers = env_min
        if max_workers is None:
            max_workers = env_max
        if quiesce_deadline is None:
            quiesce_deadline = env_quiesce
        self.elastic = bool(elastic)
        self.min_workers = min_workers
        self.max_workers = max_workers
        self.quiesce_deadline = quiesce_deadline
        self.epoch = 0            # membership epoch (monotonic)
        self.members = set()      # ranks in the current membership
        self.reshard_waiters = set()   # ranks parked at the quiesce sync
        self.reshard_gen = 0
        self.reshard_release = None    # last released membership view
        self.commit_waiters = set()    # ranks parked at the commit barrier
        self.commit_gen = 0
        self.reshard_choice = None     # leader's adopted-generation pick
        self._released_once = False    # initial cohort fully assembled

        outer = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                self.rank = None
                try:
                    while True:
                        msg = recv_msg(self.request)
                        if msg is None:
                            return
                        # worker↔coordinator span stitching: a verb
                        # carrying a trace context gets a server-side
                        # span parented on the sender's span (the
                        # worker's fit batch / reshard cycle), so one
                        # tree spans both processes.  No context, no
                        # span — the non-traced hot path is unchanged.
                        tr = _trace_mod()
                        wire = msg.get("trace") if tr is not None else None
                        sp = tr.start_span(
                            "kvstore.%s" % msg.get("cmd"),
                            trace_id=wire.get("trace_id"),
                            parent_id=wire.get("span_id"),
                            rank=msg.get("rank")) if wire else None
                        reply = None
                        try:
                            reply = outer.dispatch(msg, conn=self)
                        finally:
                            if sp is not None:
                                err = isinstance(reply, dict) \
                                    and "error" in reply
                                sp.end("error" if err or reply is None
                                       else "ok")
                        send_msg(self.request, reply)
                        if msg["cmd"] == "stop":
                            return
                except _Disconnected:
                    return
                finally:
                    outer.on_disconnect(self)

        class Server(socketserver.ThreadingTCPServer):
            daemon_threads = True
            allow_reuse_address = True

        self.server = Server((host, port), Handler)
        self.port = self.server.server_address[1]

    def on_disconnect(self, conn):
        """A worker connection dropped: release its rank and withdraw any
        in-flight barrier contribution so the cluster cannot desync on a
        mid-barrier death + rejoin."""
        with self.lock:
            rank = getattr(conn, "rank", None)
            if rank is not None and self.live.get(rank) is conn:
                del self.live[rank]
                self.dead_since[rank] = time.monotonic()
                self.barrier_waiters.discard(rank)
                self.reshard_waiters.discard(rank)
                self.commit_waiters.discard(rank)
                self.lock.notify_all()

    # -- elastic membership (lock held throughout) -------------------------
    def _world(self):
        """Sync-round/barrier completion count: the live membership in
        elastic mode (world size changes mid-job), the launch-time
        ``num_workers`` otherwise."""
        if self.elastic and self.members:
            return len(self.members)
        return self.num_workers

    def _bump_epoch(self, reason):
        """Advance the membership epoch (lock held).  Partial sync rounds
        belong to the old world and are discarded, and every key's
        version/round bookkeeping restarts at zero — the new world's
        numbering begins clean (clients reset their push/pull counters
        when they adopt the new epoch at ``reshard_sync``), so a
        half-pushed old round can neither complete late nor shift the
        new world's rounds out of phase.  Parked waiters are woken so
        their epoch-aware predicates can return typed stale replies."""
        self.epoch += 1
        self.reshard_choice = None  # the old world's pick is void
        for st in self.keys.values():
            st.rounds.clear()
            st.pushed.clear()
            st.round_base.clear()
            st.version = 0
        t = _tele()
        if t is not None:
            t.set_gauge("elastic.epoch", self.epoch)
            t.event("elastic.membership", epoch=self.epoch, reason=reason,
                    ranks=sorted(self.members))
        self.lock.notify_all()

    def _evict(self, rank, reason):
        """Remove ``rank`` from the membership (lock held) and bump the
        epoch.  Used by graceful ``deregister``, heartbeat-death
        detection, and the reshard quiesce deadline."""
        self.members.discard(rank)
        self.dead_since.pop(rank, None)
        self.barrier_waiters.discard(rank)
        self.reshard_waiters.discard(rank)
        self.commit_waiters.discard(rank)
        t = _tele()
        if t is not None:
            t.inc("elastic.evictions", reason=reason)
        self._bump_epoch("%s rank %s" % (reason, rank))

    def _stale_reply(self, msg_epoch, cmd):
        """Typed stale-epoch reply when elastic traffic carries an old
        membership epoch (lock held); None when current.  Messages
        WITHOUT an epoch (non-elastic clients, the pre-adoption
        init/pull phase) are never checked."""
        if not self.elastic or msg_epoch is None \
                or msg_epoch == self.epoch:
            return None
        t = _tele()
        if t is not None:
            t.inc("elastic.stale_epoch.count", cmd=cmd)
        return {"error": "stale membership epoch %s (current %s) for %r: "
                         "run the reshard cycle before retrying"
                         % (msg_epoch, self.epoch, cmd),
                "stale_epoch": True, "epoch": self.epoch}

    def _member_reply(self, rank, cmd):
        """Typed reply directing a non-member of the current epoch back
        through register/reshard (lock held); None when ``rank`` is a
        member.  An evicted-but-live worker must not contribute to the
        new world's rounds."""
        if not self.elastic or rank in self.members:
            return None
        return {"error": "rank %s is not a member of membership epoch %d "
                         "(%r): re-register and reshard to rejoin"
                         % (rank, self.epoch, cmd),
                "stale_epoch": True, "epoch": self.epoch}

    def _deadline_evict(self, missing, waited, floor, reason):
        """Reshard-deadline discriminator (lock held), shared by the
        quiesce sync and the commit barrier: a live connection is
        evidence of a slow-but-alive member (a long batch, a big
        snapshot reload); a closed one is a death.  Dead missing
        members are evicted at the deadline — the epoch bump restarts
        the cycle on the survivors — while live ones get 3x before
        being treated as wedged, keeping the contract
        resume-or-typed-error, never a hang.  Returns True when the
        caller should keep waiting (members were evicted, or live
        stragglers remain), False when it should fail with its typed
        timeout error."""
        evictable = {r for r in missing if r not in self.live} \
            if waited <= 3 * self.quiesce_deadline else set(missing)
        if evictable and len(self.members) - len(evictable) >= floor:
            for r in sorted(evictable):
                self._evict(r, reason)
            return True
        return bool(missing - evictable)

    def _ok(self, reply):
        """Stamp a success reply with the current membership epoch (lock
        held): clients observe membership movement passively on the
        push/pull traffic every batch already generates, so the
        batch-boundary elastic poll costs no dedicated RPC round-trip."""
        if self.elastic:
            reply["epoch"] = self.epoch
        return reply

    # -- command dispatch --------------------------------------------------
    def dispatch(self, msg, conn=None):
        cmd = msg["cmd"]
        if cmd == "register":
            with self.lock:
                preferred = msg.get("preferred_rank")
                if self.elastic and self.max_workers:
                    joining = preferred is None \
                        or int(preferred) not in self.members
                    if joining and len(self.members) >= self.max_workers:
                        return {"error": "membership is full (%d members, "
                                         "MXNET_ELASTIC_MAX_WORKERS=%d)"
                                         % (len(self.members),
                                            self.max_workers),
                                "membership_full": True}
                if preferred is not None:
                    # restart/rejoin path (reference ps-lite is_recovery,
                    # kvstore_dist.h:35,73): a worker that announces its
                    # DMLC_WORKER_ID keeps that rank across restarts; the
                    # server's weights/versions are intact so it resumes
                    # from current state without re-running init barriers
                    rank = int(preferred)
                    if rank in self.live:
                        # recovery is only for DEAD incarnations; a live
                        # holder means a rank collision, not a restart
                        return {"error": "rank %d is held by a live "
                                         "worker" % rank}
                    recovery = rank in self.registered
                    self.registered.add(rank)
                    if not recovery:
                        self.next_rank = max(self.next_rank, rank + 1)
                else:
                    while self.next_rank in self.registered:
                        self.next_rank += 1
                    rank = self.next_rank
                    self.registered.add(rank)
                    self.next_rank += 1
                    recovery = False
                if conn is not None:
                    conn.rank = rank
                    self.live[rank] = conn
                self.dead_since.pop(rank, None)
                self.last_seen[rank] = time.monotonic()
                if not msg.get("rejoin"):
                    # a fresh worker process (not a same-process
                    # reconnect()) restarts its per-key round numbering
                    # at 0: remember the current pushed counts so its low
                    # rounds are not misread as replays
                    for st in self.keys.values():
                        st.round_base[rank] = st.pushed[rank]
                if self.elastic and rank not in self.members:
                    # a NEW member (first join, or re-admission after an
                    # eviction) changes the world: bump so every elastic
                    # worker reshards around it.  A transient reconnect of
                    # a current member (PR 1 recovery) does NOT bump.
                    self.members.add(rank)
                    self._bump_epoch("register rank %s" % rank)
                return {"rank": rank, "num_workers": self.num_workers,
                        "is_recovery": recovery, "epoch": self.epoch}
        if cmd == "deregister":
            # graceful leave: the worker announces it is going away, so
            # the membership shrinks NOW instead of after a heartbeat
            # deadline of blocked sync rounds
            with self.lock:
                if not self.elastic:
                    return {"error": "deregister requires an elastic "
                                     "server (MXNET_ELASTIC=1)"}
                rank = msg.get("rank", getattr(conn, "rank", None))
                if rank in self.members:
                    self._evict(rank, "deregister")
                return {"epoch": self.epoch}
        if cmd == "membership":
            with self.lock:
                return {"epoch": self.epoch, "ranks": sorted(self.members),
                        "num_workers": self._world()}
        if cmd == "reshard_sync":
            return self._reshard_sync(
                msg.get("rank", getattr(conn, "rank", None)), conn)
        if cmd == "reshard_commit":
            return self._reshard_commit(
                msg.get("rank", getattr(conn, "rank", None)),
                msg.get("epoch"), conn)
        if cmd == "reshard_choice":
            return self._reshard_choice(
                msg.get("rank", getattr(conn, "rank", None)),
                msg.get("epoch"), "set" in msg, msg.get("set"), conn)
        if cmd == "reload":
            with self.lock:
                stale = self._stale_reply(msg.get("epoch"), "reload")
                if stale is not None:
                    return stale
                value = np.array(msg["value"], copy=True)
                st = self.keys.get(msg["key"])
                if st is None:
                    st = self.keys[msg["key"]] = _KeyState(value)
                st.value = value
                st.version = 0
                st.rounds.clear()
                st.pushed.clear()
                st.round_base.clear()
                self.lock.notify_all()
                return {"version": 0}
        if cmd == "heartbeat":
            # liveness ping: refreshes last_seen and reports the cluster
            # view so a worker can see who the server thinks is alive
            t = _tele()
            if t is not None:
                t.inc("kvstore.server.heartbeats")
            with self.lock:
                rank = msg.get("rank", getattr(conn, "rank", None))
                if rank is not None:
                    self.last_seen[rank] = time.monotonic()
                return {"live": sorted(self.live),
                        "num_workers": self._world(),
                        "epoch": self.epoch}
        if cmd == "init":
            with self.lock:
                if msg["key"] not in self.keys:
                    self.keys[msg["key"]] = _KeyState(
                        np.array(msg["value"], copy=True))
                return {"version": self.keys[msg["key"]].version}
        if cmd == "push":
            return self._push(msg["key"], msg["value"], msg["rank"],
                              msg.get("round"), msg.get("epoch"))
        if cmd == "pull":
            return self._pull(msg["key"], msg.get("version", 0), conn,
                              msg.get("epoch"))
        if cmd == "set_optimizer":
            get_updater = _pkg_mod("optimizer").get_updater
            with self.lock:
                self.updater = get_updater(_loads(msg["bytes"]))
            return {}
        if cmd == "barrier":
            return self._barrier(msg.get("rank"),
                                 getattr(conn, "rank", None), conn,
                                 msg.get("epoch"))
        if cmd == "sync_mode":
            # reference kvstore.cc:32-35 — rank 0 commands kSyncMode to
            # servers when the type lacks _async
            with self.lock:
                self.sync_mode = bool(msg.get("value", True))
            return {}
        if cmd == "get_updater_states":
            # the elastic leader calls this once per batch (the snapshot
            # cadence), so the byte-serialization must not run under the
            # coordinator's global lock — it would stall every other
            # rank's push/pull for the duration.  State arrays are
            # immutable jax values rebound on update, so a shallow
            # wrapper clone under the lock freezes a consistent view
            # that pickles safely outside it.
            with self.lock:
                if self.updater is None:
                    return {"error": "optimizer not initialized on server"}
                frozen = _freeze_states(self.updater.states)
            return {"states": pickle.dumps(frozen)}
        if cmd == "set_updater_states":
            with self.lock:
                if self.updater is None:
                    return {"error": "optimizer not initialized on server"}
                # deadlock-safe unpickle (see _pkg_mod)
                self.updater.states = _loads(msg["states"])
            return {}
        if cmd == "user_command":
            # SendCommandToServers parity: unknown app-level commands are
            # accepted and ignored
            return {}
        if cmd == "stop":
            self.stopped.set()
            with self.lock:
                # wake parked barrier/pull/reshard waiters so their
                # handlers exit with the typed shutdown instead of
                # timing out against the heartbeat deadline
                self.lock.notify_all()
            threading.Thread(target=self.server.shutdown,
                             daemon=True).start()
            return {}
        return {"error": "unknown command %r" % cmd}

    def _apply(self, st, key, merged):
        if self.updater is not None:
            # optimizers operate on NDArrays; round-trip through one
            array = _pkg_mod("ndarray").array

            weight = array(st.value)
            self.updater(key, array(merged), weight)
            st.value = weight.asnumpy()
        else:
            st.value = np.array(merged, copy=True)

    def _push(self, key, value, rank, client_round=None, msg_epoch=None):
        value = np.asarray(value)
        t = _tele()
        if t is not None and t.enabled():
            t.inc("kvstore.server.pushes", rank=rank)
            t.inc("kvstore.server.push_bytes", int(value.nbytes))
        with self.lock:
            stale = self._stale_reply(msg_epoch, "push")
            if stale is None and msg_epoch is not None:
                # an old world's gradient must never merge into the new
                # world's rounds — and neither may an evicted-but-live
                # straggler that happens to guess the current epoch
                stale = self._member_reply(rank, "push")
            if stale is not None:
                return stale
            st = self.keys.get(key)
            if st is None:
                return {"error": "key %r not initialized" % key}
            if not self.sync_mode:
                rnd = st.pushed[rank]
                if client_round is not None \
                        and st.round_base[rank] <= client_round < rnd:
                    # replay (reply lost, worker re-pushed after
                    # reconnect()): already applied — ack, don't take a
                    # second optimizer step for the same gradient
                    return self._ok({"version": st.version})
                st.pushed[rank] += 1
                self._apply(st, key, value)
                st.version += 1
                self.lock.notify_all()
                return self._ok({"version": st.version})
            rnd = st.pushed[rank]
            if client_round is not None \
                    and st.round_base[rank] <= client_round < rnd:
                # replay of an already-counted push: the reply was lost
                # mid-transport and the worker re-pushed after
                # reconnect().  Counting it again would shift this rank's
                # contributions one round forward forever, so ack with
                # the original round's reply instead.  (Rounds below the
                # incarnation base are a restarted process's fresh
                # numbering, not replays — those take the normal path.)
                return self._ok({"version": client_round + 1})
            st.pushed[rank] += 1
            # sorted-rank fold with EAGER prefix merging: a contribution
            # folds into the running sum as soon as every lower-sorted
            # rank's has, so only out-of-order arrivals are buffered
            # (expected ~W/2 gradients, not a full world's) while the
            # float sum stays arrival-order independent.  The member set
            # is fixed for a round's lifetime — an epoch bump clears
            # st.rounds wholesale.
            order = sorted(self.members) \
                if self.elastic and self.members \
                else range(self.num_workers)
            slot = st.rounds[rnd]
            if not slot:
                slot.update(sum=None, folded=0, buf={})
            slot["buf"][rank] = value
            while slot["folded"] < len(order) \
                    and order[slot["folded"]] in slot["buf"]:
                v = slot["buf"].pop(order[slot["folded"]])
                slot["sum"] = v if slot["sum"] is None \
                    else slot["sum"] + v
                slot["folded"] += 1
            if slot["folded"] == len(order):
                assert st.version == rnd, "round applied out of order"
                self._apply(st, key, slot["sum"])
                del st.rounds[rnd]
                st.version += 1
                self.lock.notify_all()
            return self._ok({"version": rnd + 1})

    def _check_dead_peers(self, wait_started):
        """Raise _DeadPeer (lock held) when a sync wait is blocked on a
        rank whose connection has been gone past the heartbeat deadline —
        or when, after the deadline, some ranks never registered at all."""
        now = time.monotonic()
        for rank in sorted(self.dead_since):
            dead_for = now - self.dead_since[rank]
            if dead_for > self.heartbeat_deadline:
                if self.elastic and rank not in self.members:
                    # a departed non-member (graceful deregister, then
                    # the socket closed — or an already-evicted rank):
                    # the current world owes it nothing; clean up
                    # instead of poisoning parked waiters with it
                    del self.dead_since[rank]
                    continue
                if self.elastic and rank in self.members and \
                        len(self.members) - 1 >= max(1, self.min_workers):
                    # elastic eviction: a dead member LEAVES the
                    # membership instead of killing the job — the epoch
                    # bump wakes blocked waiters, whose epoch-aware
                    # predicates hand their clients typed StaleEpoch
                    # replies, and the survivors reshard around the loss
                    t = _tele()
                    if t is not None:
                        t.inc("kvstore.server.heartbeat_deaths", rank=rank)
                        t.event("kvstore.heartbeat_death", rank=rank,
                                dead_for_s=round(dead_for, 1),
                                evicted=True)
                    self._evict(rank, "heartbeat-death")
                    continue
                seen = self.last_seen.get(rank)
                seen_txt = "" if seen is None \
                    else ", last message %.1fs ago" % (now - seen)
                t = _tele()
                if t is not None:
                    t.inc("kvstore.server.heartbeat_deaths", rank=rank)
                    t.event("kvstore.heartbeat_death", rank=rank,
                            dead_for_s=round(dead_for, 1))
                raise _DeadPeer(
                    "worker rank %d lost: disconnected %.1fs ago%s "
                    "(> heartbeat deadline %.0fs)"
                    % (rank, dead_for, seen_txt, self.heartbeat_deadline))
        # `registered` is empty only before ANY worker announced itself
        # (workers register on the scheduler and announce their rank to
        # every shard server), and an empty set says nothing about worker
        # liveness — so the never-registered check must not fire then
        if self.registered \
                and len(self.registered) < self.num_workers \
                and now - wait_started > self.heartbeat_deadline:
            raise _DeadPeer(
                "only %d of %d workers ever registered within the "
                "heartbeat deadline (%.0fs); registered ranks: %s"
                % (len(self.registered), self.num_workers,
                   self.heartbeat_deadline, sorted(self.registered)))

    def _wait_interruptible(self, conn, cond, watch_peers=False):
        """Condition-wait (lock held) that notices a dead peer: a blocked
        handler thread must release its rank, or the worker's restarted
        incarnation is refused as a rank collision.  With ``watch_peers``
        the wait also fails fast — _DeadPeer naming the lost rank — when
        a rank it depends on has been dead past the heartbeat deadline."""
        started = time.monotonic()
        while not cond():
            if self.stopped.is_set():
                # server close()/stop wakes parked waiters with a typed
                # shutdown instead of leaving them to ride out the
                # heartbeat deadline against a dead server
                raise _Disconnected()
            self.lock.wait(timeout=1.0)
            if cond():
                return
            if self.stopped.is_set():
                raise _Disconnected()
            if conn is not None and _sock_dead(conn.request):
                raise _Disconnected()
            if watch_peers:
                self._check_dead_peers(started)

    def _pull(self, key, version, conn=None, msg_epoch=None):
        with self.lock:
            stale = self._stale_reply(msg_epoch, "pull")
            if stale is not None:
                return stale
            st = self.keys.get(key)
            if st is None:
                return {"error": "key %r not initialized" % key}

            def _done():
                # an epoch bump aborts the wait: the round this pull is
                # gated on belonged to the old world and was discarded
                if self.elastic and msg_epoch is not None \
                        and self.epoch != msg_epoch:
                    return True
                return st.version >= version

            try:
                self._wait_interruptible(conn, _done, watch_peers=True)
            except _DeadPeer as e:
                # a sync round can never complete without the lost rank's
                # push — fail the pull with the diagnosis, don't hang
                return {"error": "pull(%r) abandoned: %s"
                                 % (key, e.message)}
            stale = self._stale_reply(msg_epoch, "pull")
            if stale is not None:
                return stale
            return self._ok({"value": st.value, "version": st.version})

    def _barrier(self, rank, conn_rank, conn=None, msg_epoch=None):
        """Rank-tracked barrier: a dead worker's contribution is withdrawn
        by on_disconnect, so a restart cannot release a generation early
        or leave it off by one.  A barrier blocked on a rank that stays
        dead past the heartbeat deadline fails with an error naming it.
        Elastic barriers carry the sender's membership epoch and abort
        with a typed stale reply when the membership moves mid-wait."""
        with self.lock:
            stale = self._stale_reply(msg_epoch, "barrier")
            if stale is None and msg_epoch is not None:
                stale = self._member_reply(
                    rank if rank is not None else conn_rank, "barrier")
            if stale is not None:
                return stale
            gen = self.barrier_gen
            r = rank if rank is not None else conn_rank
            self.barrier_waiters.add(r)
            if len(self.barrier_waiters) == self._world():
                self.barrier_waiters.clear()
                self.barrier_gen += 1
                self.lock.notify_all()
            else:
                def _done():
                    if self.elastic and msg_epoch is not None \
                            and self.epoch != msg_epoch:
                        return True
                    return self.barrier_gen != gen

                try:
                    self._wait_interruptible(conn, _done, watch_peers=True)
                except _Disconnected:
                    self.barrier_waiters.discard(r)
                    raise
                except _DeadPeer as e:
                    self.barrier_waiters.discard(r)
                    return {"error": "barrier abandoned: %s" % e.message}
                stale = self._stale_reply(msg_epoch, "barrier")
                if stale is not None:
                    self.barrier_waiters.discard(r)
                    return stale
            return {}

    # -- elastic reshard rendezvous ----------------------------------------
    def _reshard_ready(self, floor):
        """Release condition (lock held): every member of the CURRENT
        epoch has arrived at the quiesce sync and the world is at least
        ``floor`` workers."""
        return bool(self.members) and len(self.members) >= floor \
            and self.members <= self.reshard_waiters

    def _reshard_release(self):
        """Publish the membership view all parked reshard waiters adopt
        (lock held) and advance the rendezvous generation."""
        self.reshard_release = {"epoch": self.epoch,
                                "ranks": sorted(self.members),
                                "num_workers": len(self.members)}
        self.reshard_waiters.clear()
        self.reshard_gen += 1
        self._released_once = True
        self.lock.notify_all()

    def _reshard_sync(self, rank, conn=None):
        """Quiesce rendezvous: block until every member of the current
        membership epoch arrives, then hand all of them one consistent
        ``{epoch, ranks, num_workers}`` view.  Members that fail to
        arrive within the quiesce deadline are evicted (another epoch
        bump) so a worker that died mid-reshard cannot wedge the cycle;
        when eviction would drop the world below the configured floor
        the sync fails with a typed error — resume-or-error, never a
        hang.  The initial cohort additionally waits for the full
        launch-time ``num_workers`` so a lone first worker cannot train
        solo while its peers are still registering."""
        with self.lock:
            if not self.elastic:
                return {"error": "reshard_sync requires an elastic "
                                 "server (MXNET_ELASTIC=1)"}
            not_member = self._member_reply(rank, "reshard_sync")
            if not_member is not None:
                return not_member
            floor = max(1, self.min_workers)
            if not self._released_once:
                floor = max(floor, self.num_workers)
            self.reshard_waiters.add(rank)
            gen = self.reshard_gen
            started = time.monotonic()
            seen_epoch = self.epoch
            while self.reshard_gen == gen:
                if self._reshard_ready(floor):
                    self._reshard_release()
                    break
                if self.stopped.is_set():
                    raise _Disconnected()
                self.lock.wait(timeout=0.25)
                if self.reshard_gen != gen:
                    break
                if self.epoch != seen_epoch:
                    # membership changed while parked (a join, an
                    # eviction): restart this waiter's deadline clock so
                    # a just-registered member gets a full quiesce
                    # window to arrive instead of being evicted by a
                    # clock that started before it even joined
                    seen_epoch = self.epoch
                    started = time.monotonic()
                if conn is not None and _sock_dead(conn.request):
                    self.reshard_waiters.discard(rank)
                    raise _Disconnected()
                if rank not in self.members:
                    # evicted while parked (this worker was itself past
                    # the deadline from another waiter's point of view)
                    return self._member_reply(rank, "reshard_sync")
                waited = time.monotonic() - started
                if waited > self.quiesce_deadline:
                    missing = self.members - self.reshard_waiters
                    if self._deadline_evict(missing, waited, floor,
                                            "quiesce-deadline"):
                        continue
                    self.reshard_waiters.discard(rank)
                    return {"error":
                            "elastic reshard could not assemble a world "
                            "of >= %d workers within the quiesce deadline "
                            "(%.0fs): members %s, arrived %s"
                            % (floor, self.quiesce_deadline,
                               sorted(self.members),
                               sorted(self.reshard_waiters | {rank}))}
            return dict(self.reshard_release)

    def _reshard_choice(self, rank, msg_epoch, has_set, choice, conn=None):
        """Adopted-generation rendezvous, between the quiesce sync and
        the rehydration: the membership LEADER announces which snapshot
        generation (or None) the whole world rolls back to, and every
        other member blocks here until the announcement lands.  Members
        reading the checkpoint manifest independently could adopt
        DIFFERENT generations — a straggler ex-leader's inline write
        racing the reads, shared-FS visibility lag, a per-member sha
        fallback — and silently diverge into mixed server parameters and
        disagreeing data ledgers.  Epoch-checked both ways: a membership
        change mid-rendezvous voids the stored choice (``_bump_epoch``)
        and returns typed stale replies so the whole cycle restarts."""
        with self.lock:
            if not self.elastic:
                return {"error": "reshard_choice requires an elastic "
                                 "server (MXNET_ELASTIC=1)"}
            stale = self._stale_reply(msg_epoch, "reshard_choice")
            if stale is None:
                stale = self._member_reply(rank, "reshard_choice")
            if stale is not None:
                return stale
            if has_set:
                self.reshard_choice = {"epoch": self.epoch,
                                       "choice": choice}
                self.lock.notify_all()
                return {"epoch": self.epoch}
            started = time.monotonic()
            while self.reshard_choice is None \
                    or self.reshard_choice["epoch"] != self.epoch:
                if self.stopped.is_set():
                    raise _Disconnected()
                self.lock.wait(timeout=0.25)
                stale = self._stale_reply(msg_epoch, "reshard_choice")
                if stale is not None:
                    return stale
                if conn is not None and _sock_dead(conn.request):
                    raise _Disconnected()
                if rank not in self.members:
                    return self._member_reply(rank, "reshard_choice")
                waited = time.monotonic() - started
                if waited > self.quiesce_deadline:
                    # the leader died between the sync and its
                    # announcement: its eviction bumps the epoch, every
                    # parked waiter goes stale and the cycle restarts on
                    # the shrunken world with a new leader
                    missing = {min(self.members)} if self.members \
                        else set()
                    if self._deadline_evict(missing, waited,
                                            max(1, self.min_workers),
                                            "choice-deadline"):
                        continue
                    return {"error":
                            "elastic reshard: no adopted-generation "
                            "announcement from the leader within the "
                            "quiesce deadline (%.0fs)"
                            % self.quiesce_deadline}
            return {"epoch": self.epoch,
                    "choice": self.reshard_choice["choice"]}

    def _reshard_commit(self, rank, msg_epoch, conn=None):
        """Post-rehydration barrier: every member's snapshot reloads
        (and the leader's optimizer reinstall) must be visible before
        ANY member resumes training.  Epoch-checked — a membership
        change mid-commit (a kill during the reshard itself) returns a
        typed stale reply and the whole cycle restarts."""
        with self.lock:
            stale = self._stale_reply(msg_epoch, "reshard_commit")
            if stale is None:
                stale = self._member_reply(rank, "reshard_commit")
            if stale is not None:
                return stale
            self.commit_waiters.add(rank)
            gen = self.commit_gen
            if self.members <= self.commit_waiters:
                self.commit_waiters.clear()
                self.commit_gen += 1
                self.lock.notify_all()
                return {"epoch": self.epoch}
            started = time.monotonic()
            while self.commit_gen == gen:
                if self.stopped.is_set():
                    raise _Disconnected()
                self.lock.wait(timeout=0.25)
                stale = self._stale_reply(msg_epoch, "reshard_commit")
                if stale is not None:
                    self.commit_waiters.discard(rank)
                    return stale
                if conn is not None and _sock_dead(conn.request):
                    self.commit_waiters.discard(rank)
                    raise _Disconnected()
                if self.commit_gen != gen:
                    break
                waited = time.monotonic() - started
                if waited > self.quiesce_deadline:
                    # a member died between sync and commit: its eviction
                    # turns everyone's commit stale and the cycle
                    # restarts on the new membership
                    missing = self.members - self.commit_waiters
                    if self._deadline_evict(missing, waited,
                                            max(1, self.min_workers),
                                            "commit-deadline"):
                        continue
                    self.commit_waiters.discard(rank)
                    return {"error": "elastic reshard commit timed out "
                                     "after %.0fs: members %s, committed "
                                     "%s" % (self.quiesce_deadline,
                                             sorted(self.members),
                                             sorted(self.commit_waiters
                                                    | {rank}))}
            return {"epoch": self.epoch}

    # -- lifecycle ---------------------------------------------------------
    def serve_forever(self):
        self.server.serve_forever()

    def start_background(self):
        t = threading.Thread(target=self.serve_forever, daemon=True)
        t.start()
        return t

    def close(self):
        """Shut down, WAKING every handler parked in a barrier/pull/
        reshard wait loop: the typed ``_Disconnected`` shutdown closes
        their connections promptly (clients see ``ConnectionLost``)
        instead of leaving them to ride out the heartbeat deadline."""
        self.stopped.set()
        with self.lock:
            self.lock.notify_all()
        self.server.shutdown()
        self.server.server_close()


def run_server():
    """Blocking server main (the reference ``KVStoreServer.run`` loop)."""
    # a parameter server is a host-side component (reference servers are
    # CPU processes): pin jax to CPU before any backend initializes, or
    # the server's optimizer applies (NDArray math) claim the chip, which
    # belongs to one process: a worker that needs it then fails or hangs
    import jax

    jax.config.update("jax_platforms", "cpu")
    num_workers = int(os.environ["DMLC_NUM_WORKER"])
    # multi-server sharding (reference ps-lite N servers + EncodeKey,
    # kvstore_dist.h:40): server i listens at root port + i; workers
    # route keys/big-array chunks by server id, server 0 doubles as the
    # scheduler (rank assignment, barrier)
    sid = int(os.environ.get("DMLC_SERVER_ID", "0"))
    port = int(os.environ.get("DMLC_PS_ROOT_PORT", "9090")) + sid
    # bind address is separate from the advertised DMLC_PS_ROOT_URI: on
    # multi-host launches the hostname may resolve to loopback locally
    # (Debian's 127.0.1.1 convention), so bind all interfaces whenever the
    # advertised address is non-loopback
    advertised = os.environ.get("DMLC_PS_ROOT_URI", "127.0.0.1")
    default_bind = advertised if advertised in ("127.0.0.1", "localhost") \
        else ""
    host = os.environ.get("MXNET_PS_BIND_HOST", default_bind)
    # mode is commanded by the workers (kSyncMode); start async
    srv = KVStoreServer(num_workers, sync_mode=False, host=host, port=port)
    srv.serve_forever()


def _init_kvstore_server_module():
    """Reference ``python/mxnet/kvstore_server.py`` auto-loop: a process
    started with DMLC_ROLE=server becomes a server and never returns."""
    if os.environ.get("DMLC_ROLE") == "server":
        run_server()
        os._exit(0)


if __name__ == "__main__":
    run_server()
