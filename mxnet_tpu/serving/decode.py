"""Continuous-batching decode engine — autoregressive serving on slots.

The PR 3 batcher coalesces *fixed-shape* requests; an autoregressive LM
breaks that model: every sequence wants a different number of steps, and
naive batching waits for the slowest sequence while the rest of the
batch pads along dead.  The TPU-native answer is the same move the
sync-free fit loop made for training (docs/how_to/perf.md): make the
decode loop ONE fixed-shape jitted step that never recompiles and never
syncs beyond a single packed host read per token — a read the loop makes
with the NEXT step already dispatched, so the device is not left empty
while the host waits, fans tokens out and walks its queue.

:class:`DecodeEngine` owns a device-resident KV cache of fixed shape
``(S slots, max_len)`` per layer and exactly TWO kinds of compiled program:

* **prefill** (one per declared prompt-length bucket): run a
  bucket-padded prompt, scatter its K/V rows into a free slot, sample
  the first token and arm the slot — all in-graph;
* **decode step** (one per ``(S, max_len)``): advance ALL slots one
  token — scatter the incoming token's K/V, attend over each slot's
  ``<= length`` horizon, sample (greedy or temperature), retire
  EOS/length-done slots — returning the packed ``(token, done,
  active)`` buffer whose single host read is the loop's only sync.

Two programs, and one of two tails in them.  A step need not yield one
token a slot: for a model that generates by **diffusion over blocks**
(it declares ``cfg.block = B``; :mod:`~mxnet_tpu.models.sdar`) a step is
one denoising PASS over ``(S, B)`` rows and delivers to a slot either
nothing or a whole block.  A slot then carries, on the device, the
tokens of its current block, which of them are fixed, the pass that
fixed each and the pass the block is at (``finish_block``/``arm_block``
beside ``finish_step``/``arm_slot`` in :meth:`DecodeEngine._build`); a
pass fixes some of the open positions by the rule ``cfg.remasking`` names
(the quota, the three rules and the threshold are the model object's:
no engine option), and the pass that LEAVES its block whole delivers it:
the block's tokens beyond the prompt and below the session's end go out
in order, the slot's length moves by ``B`` and a fresh block opens.  The
K and V of the block's final tokens (the COMMIT, a pass of its own in
the published sampler, which reads every weight and fixes nothing) are
written by the slot's NEXT pass: the finished block stays the slot's
*pending* block, and its ``B`` rows ride beside the open block's for
their K and V alone (the model's step runs ``(S, 2B)`` rows; the pending
rows of a slot with nothing pending are dead).  A session that ends
with its block writes no commit; an admission, a cancel and a deadline
drop a slot's pending block.  The packed read grows to ``(B + 3, S)``
(``B`` tokens or -1, the passes that fixed them as one code, done,
active), the fan-out hands a slot none or up to ``B`` tokens, and an
admission's prefill yields NO token (the published sampler uses a prompt
for K and V alone), so a session's first token, ``ttft()`` and
``serving.decode.ttft_seconds`` are its first block's delivery.
:meth:`DecodeEngine.describe` says which tail an engine runs.

**Sampling keys are position-derived, not sequential.**  Each session
carries one host-side ``seed``; the token that will occupy absolute
position ``i`` of the sequence is drawn with
``fold_in(PRNGKey(seed), i)`` — in the prefill (``i = prompt length``)
and in every decode step (``i = length + 1``) alike.  That makes a
session's sample stream a pure function of ``(seed, transcript)``:
independent of which slot it sits in, of its co-resident sessions, and
of how many times it has been interrupted.  The session transcript
(prompt, tokens emitted so far, seed) is therefore a sufficient
checkpoint: re-prefilling ``prompt + generated-so-far`` on ANY replica
resumes the exact stream an uninterrupted run would have produced —
greedy and temperature — which is what
:class:`~mxnet_tpu.serving.pool.ReplicaPool` failover relies on
(docs/serving.md "Session failover & fault domains").  A block model's
key is of a position AND a pass, ``fold_in(fold_in(PRNGKey(seed), i),
t)``, and its blocks are absolute (block ``k`` is positions ``kB .. kB +
B - 1``): a transcript holds delivered blocks (the last of them perhaps
pending, its K and V never written: the re-prefill writes them as it
writes the prompt's), the block that was being denoised when a replica
was lost is redone from its first pass, and the resumed stream is the
uninterrupted one.

Sequences are admitted into free slots BETWEEN steps (continuous
batching: a late request joins the running batch instead of waiting for
it), retired on EOS/length without recompiling, and stream their tokens
out through per-session callbacks.  Inactive slots ride along at fixed
shape; their scatter rows are unreachable under the attention mask
until a real write replaces them.

**The loop is a pipeline one step deep** (:meth:`DecodeEngine._serve_loop`;
docs/serving.md "The loop's order"): a turn dispatches the admissions'
prefills and step N+1, THEN reads step N and fans it out, then reads the
admissions' first tokens.  The step decides ``done`` and the next
``active`` mask on the device, so step N+1 needs nothing the host learns
from step N; the host's one input, ``keep`` (cancel, deadline), reaches
the device a step late, a finished session's slot is re-admitted a step
late, and a device fault surfaces a dispatch late — when every unread
step is dropped whole, so a transcript never differs from what
``on_token`` saw.  The streams are those of a plain serial loop over the
same programs (``tests/serial_loop.py``).

**The model protocol.**  The engine learns nothing of an architecture; it
is given a *model object* and asks it for four things:

* ``cfg`` with ``vocab``, ``max_len`` and ``eos_id``, and
  ``cache_spec()``: what the model keeps for a slot between steps, one
  entry for each layer that keeps anything (a layer may keep nothing).
  An entry describes TWO per-slot arrays, which
  :meth:`DecodeEngine._fresh_state` builds with ``slots`` in front
  (:func:`~mxnet_tpu.models.transformer_lm.slot_arrays`):

  - a :class:`~mxnet_tpu.models.transformer_lm.CacheLayer` ``(kind, rows,
    kv_heads, head_dim, dtype, heads_major)``: a K and a V of ``(rows,
    kv_heads, head_dim)`` (``heads_major``: ``(kv_heads, rows,
    head_dim)``) — ``kind`` "full" (``rows`` = ``max_len``) or "ring"
    (``rows`` = a window).  Rows a session has not written are hidden by
    its length, so a slot is reused as it stands;
  - a :class:`~mxnet_tpu.models.transformer_lm.StateLayer` ``(kind,
    shapes, dtypes)``, ``kind`` "state": two arrays of fixed shape and
    dtype of their own (a state-space layer's recurrent state and the
    tail of its convolution).  No length hides what a slot's last session
    left in them: an admission overwrites both WHOLE, and so does the
    re-prefill of a migrated transcript (:meth:`DecodeEngine.resume`),
    which is how a recurrent state is restored on another replica;
  - a :class:`~mxnet_tpu.models.transformer_lm.LatentLayer` ``(kind, rows,
    widths, dtype)``, ``kind`` "latent": of each position one compressed
    row that stands for every head's key and value, and the one rotated
    key all heads read, ``(1, rows, widths[i])`` each (latent attention;
    :mod:`~mxnet_tpu.models.deepseek_v2`).  Row ``p`` holds position
    ``p`` and a session's length hides the rest, as in a "full" layer.

  Entries may differ in every field;
* ``prefill(params, tokens, length) -> (last_logits, firsts, seconds)``:
  for each entry the two values to put into ONE slot, written from row 0
  (a K and a V may be shorter than the slot) or whole (a state).  A model
  that declares a block length returns, in the logits' place, the
  ``(B,)`` tokens of the block generation starts in (the prompt's
  ``length % B`` last tokens, which start it as fixed positions, and
  mask tokens), and its K and V cover the bucket, of which the slot's
  length keeps the whole blocks below ``length // B * B``;
* ``decode_step(params, firsts, seconds, last_tok, lengths, active,
  extra) -> (logits, firsts, seconds, extra)``: one token for all slots,
  given and returning every entry's two arrays.  A model that declares a
  block length is given ``(slots, B)`` tokens (each slot's open block)
  and returns ``(slots, B, vocab)`` logits, row ``[i, j]`` of position
  ``lengths[i] + j``'s own token; beside its own extra state it finds in
  ``extra`` the engine's ``pending (slots,)`` bool and ``pending_block
  (slots, B)``: the final tokens of the block at ``lengths - B``, whose K
  and V this pass is to write (they are the engine's, and what the step
  returns of them is dropped);
* ``extra_state()``: optional extra device state the step carries beside
  the cache (routing counters), or None.  It is an argument of its own,
  never donated, so :meth:`DecodeEngine.model_counters` may read the
  latest from any thread; ``model.counters(extra)`` names what it holds;
* ``attended_rows(longest)``: optional, on the host: how many of a slot's
  ``max_len`` rows a step's attention reads when its longest active slot
  holds ``longest`` rows.  The engine asks at every dispatch, by its own
  mirror of the lengths, and :meth:`DecodeEngine.model_counters`
  publishes the mean as ``serving.attn.rows_read_share``.

The engine's donated state is ``(firsts, seconds, last_tok, lengths,
limits, active, temps, seeds)``: every entry's first array, every entry's
second, six arrays of ``(slots,)``; a block model's has the blocks
``(slots, B)`` in ``last_tok``'s place and, after ``seeds``, ``fixed``
and ``fixed_at`` of ``(slots, B)``, ``passes`` of ``(slots,)``, and
``pending`` ``(slots,)`` with ``pending_block`` ``(slots, B)``
(:meth:`DecodeEngine.state_shapes` gives it as shapes to a tool that
lowers the programs without allocating them).  Its extra state holds
the counters the tail counts (``commits``, ``tokens_committed``,
``fixed_by_threshold``, ``fixed_by_quota``).

A bare :class:`~mxnet_tpu.models.transformer_lm.LMConfig` stands for
:class:`~mxnet_tpu.models.transformer_lm.DecodeModel`, the first
implementer; :class:`~mxnet_tpu.models.exaone_moe.ExaoneMoE` is the second
(:mod:`~mxnet_tpu.models.sambay`, :mod:`~mxnet_tpu.models.smallthinker`,
:mod:`~mxnet_tpu.models.deepseek_v2` and :mod:`~mxnet_tpu.models.sdar` the
others).
The model object is the only choice of a model path.  The paged layout
asks for the two further methods ``prefill_paged``/``decode_step_paged``
and a cache of full float32 layers alike; a model without them, and any
block model, is refused with :class:`UnsupportedKVLayout`.

The engine is single-device; multi-replica throughput is
:class:`~mxnet_tpu.serving.pool.ReplicaPool`'s job.  The hot loop is
covered by the graftlint host-sync pass (``ci/graftlint``): the packed
per-step read is the one sanctioned transfer, one read a step.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque, namedtuple

import numpy as np

from .. import compile_cache as _compile_cache
from .. import faults as _faults
from .. import perfdebug as _perfdebug
from .. import random as _random
from .. import telemetry as _telemetry
from .. import tracing as _tracing
from ..base import MXNetError
from ..models import transformer_lm as _tlm
from .batcher import (LATENCY_BUCKETS, DeadlineExceeded, Future,
                      InvalidRequest, Overloaded)
from .kvblocks import KVBlockPool, KVBlocksExhausted

__all__ = ["GenerateSession", "DecodeEngine", "ReplicaKilled",
           "UnsupportedKVLayout", "TTFT_BUCKETS"]

_log = logging.getLogger("mxnet_tpu.serving")

#: time-to-first-token histogram bounds (seconds) — first tokens pay a
#: queue wait + one prefill, so the ladder reaches further than the
#: per-token one
TTFT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                1.0, 2.5, 5.0, 10.0, 30.0)


# shared int-env parser — ONE definition lives in compile_cache.py
# (pool.py imports it from there too)
from ..compile_cache import _env_int  # noqa: E402


class ReplicaKilled(MXNetError):
    """The ``serving.replica.kill`` fault hard-killed this engine
    mid-generation: the engine is permanently closed (a crashed replica
    process, not a transient step fault) and its sessions must migrate
    — the pool treats this as an instant circuit-open."""


class UnsupportedKVLayout(MXNetError):
    """The model's cache specification cannot be held in the KV layout
    asked for: the paged block pool holds full float32 layers of one
    shape, through a model's ``prefill_paged``/``decode_step_paged``."""


class _Poisoned(Exception):
    """A dispatch or a device read of the loop raised ``cause``: the
    donated chain is lost and the turn ends in ``_fail_all``.  Never
    leaves the engine's thread."""

    def __init__(self, cause):
        super().__init__(type(cause).__name__)
        self.cause = cause


#: a decode step dispatched and not read yet: its packed buffer, the
#: slots' sessions and ``keep`` as they were at its dispatch (its fan-out
#: serves that snapshot), its dispatch time and the extra device state
#: it was given
_Step = namedtuple("_Step", "packed sessions keep t0 extra_in")


class GenerateSession:
    """One streaming generation request: queued -> active(slot) ->
    done/shed (or migrated to another replica in between — the session
    object survives the move).  ``result()`` blocks for the full token
    list (prompt NOT included; EOS, when hit, is the last token);
    ``on_token`` streams each token from the engine thread (must be
    cheap and non-blocking — HTTP streaming hands it a queue put).

    The session IS its own failover checkpoint: ``prompt``, ``tokens``
    (everything generated AND delivered so far — the engine appends
    before it emits, and a failed dispatch emits nothing, so the list
    never runs ahead of or behind the client stream), ``seed`` (the
    position-keyed sampling seed) and ``max_new_tokens`` are exactly
    what a healthy replica needs to resume the stream bit-identically.
    """

    __slots__ = ("prompt", "max_new_tokens", "temperature", "deadline",
                 "on_token", "on_event", "tokens", "fixed_at", "future",
                 "seed",
                 "tenant", "migrations", "migrate_t0", "t_submit",
                 "t_admit", "t_first", "t_done", "slot", "admit_step",
                 "done_step",
                 "trace", "_finished", "_lock", "_on_done")

    def __init__(self, prompt, max_new_tokens, temperature, deadline_ms,
                 on_token, on_done=None, seed=0, tenant=None,
                 on_event=None):
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.deadline = None if deadline_ms is None \
            else time.monotonic() + float(deadline_ms) / 1e3
        self.on_token = on_token
        self.on_event = on_event
        self.tokens = []
        #: of a model that generates by blocks: for each of ``tokens`` the
        #: pass of its block at which the position was fixed (0 the first)
        self.fixed_at = []
        self.future = Future()
        self.seed = int(seed) & 0xFFFFFFFF
        self.tenant = tenant
        #: failure-driven migration attempts so far (the pool's retry
        #: budget counts these; version-swap migrations are free)
        self.migrations = 0
        #: failure timestamp of an in-flight migration — the target
        #: engine stamps ``serving.failover.recovery_seconds`` from it
        #: when the re-prefill lands (true failure-to-resumed latency)
        self.migrate_t0 = None
        self.t_submit = time.monotonic()
        #: when the engine's loop handed the session its slot (the
        #: latest admission: a migrated session queues again)
        self.t_admit = None
        self.t_first = None
        self.t_done = None
        self.slot = None
        self.admit_step = None
        self.done_step = None
        #: the session's root span ("serving.generate") — RIDES every
        #: migration with the session, so spans recorded on replica B
        #: after a failover still parent into the same trace.  The
        #: shared no-op span when tracing is off.
        self.trace = _tracing.NULL_SPAN
        self._finished = False
        # session-level lock: completion must stay exactly-once across
        # MIGRATION — engine A's forced stop can race engine B retiring
        # the same (migrated) session, so the flag cannot live under
        # either engine's lock
        self._lock = threading.Lock()
        self._on_done = on_done

    def _resolve(self, error=None):
        """Exactly-once completion: resolve the future and fire the
        pool's on_done hook.  Returns False when the session already
        finished (the caller must then not double-count telemetry)."""
        with self._lock:
            if self._finished:
                return False
            self._finished = True
        self.t_done = time.monotonic()
        # idempotent: shed/migration paths that already ended the span
        # with a more specific status win — this is the fallback close
        self.trace.end(
            "ok" if error is None else
            ("shed" if isinstance(error, (Overloaded, DeadlineExceeded))
             else "error"),
            tokens=len(self.tokens), migrations=self.migrations)
        if error is not None:
            self.future.set_error(error)
        else:
            self.future.set_result(list(self.tokens))
        if self._on_done is not None:
            try:
                self._on_done(self)
            except Exception:  # noqa: broad-except — pool accounting
                # hooks must never kill the resolving thread
                _log.warning("decode: on_done hook failed", exc_info=True)
        return True

    def finished(self):
        """True once the session resolved (result or typed error) — the
        migration path's filter: a session that resolved while waiting
        to migrate must not be re-admitted."""
        with self._lock:
            return self._finished

    def cancel(self):
        """Abandon the request: queued sessions are dropped at the next
        admission scan; an active session is taken out of the next step
        the engine DISPATCHES and retired (its slot freed) when that step
        is read — the token of the step already in flight still arrives.
        Returns False when the session already finished.  ONE cancellation flag — the embedded Future's (the
        same machinery the batcher honors), so ``sess.future.cancel()``
        and ``sess.cancel()`` cannot diverge."""
        return self.future.cancel()

    def cancelled(self):
        return self.future.cancelled()

    def done(self):
        return self.future.done()

    def result(self, timeout=60.0):
        """Block for the full generated token list (re-raising the shed
        or dispatch error when the session failed)."""
        return self.future.result(timeout)

    def ttft(self):
        """Time-to-first-token in seconds (None before the first
        token; of a model that generates by blocks, before its first
        block's delivery: its prefill yields no token)."""
        return None if self.t_first is None \
            else self.t_first - self.t_submit

    def queue_wait(self):
        """Seconds from submit to the slot (None while queued): the part
        of a first-token wait that is queueing, the rest is prefill."""
        return None if self.t_admit is None \
            else self.t_admit - self.t_submit


class DecodeEngine:
    """Slot-based continuous batching over one replica of a model.

    Parameters
    ----------
    cfg : model object, or transformer_lm.LMConfig
        What implements the model protocol (module docstring); a bare
        ``LMConfig`` stands for ``transformer_lm.DecodeModel(cfg)``.
    params : pytree
        Host or device params; committed to ``device``.
    slots : int
        Concurrent sequences S (``MXNET_DECODE_SLOTS`` default, 8).
        The decode step compiles once per ``(S, max_len)``.
    prefill_buckets : tuple of int
        Declared prompt-length buckets; a prompt pads to the smallest
        bucket that fits (``DynamicBatcher`` bucket idiom — the jit
        cache sees ``len(prefill_buckets)`` prefill shapes, ever).
    max_queue : int
        Admission bound on QUEUED sessions; past it ``submit`` raises
        :class:`Overloaded`.
    device : jax.Device, optional
        Replica placement; defaults to the process default device.
    replica : str
        Telemetry label (``replica=<id>``) — the pool names replicas.
    on_step_error / on_step_ok : callable, optional
        Replica-health hooks (the pool's quarantine counter); called
        outside the engine lock.
    kv_layout : str, optional
        ``"dense"`` (the classic ``(S, max_len)`` per-slot cache) or
        ``"paged"`` (block-table storage through
        :mod:`~mxnet_tpu.serving.kvblocks` — prefix reuse, COW,
        oversubscription).  Defaults to ``MXNET_KV_LAYOUT`` (dense).
        Both layouts produce bit-identical streams for the same
        ``(seed, transcript)``.
    kv_block_size / kv_blocks : int, optional
        Paged sizing overrides (``MXNET_KV_BLOCK_SIZE`` /
        ``MXNET_KV_BLOCKS`` defaults; see kvblocks.py).
    kv_prefix_cache : bool, optional
        Paged prefix reuse toggle (``MXNET_KV_PREFIX_CACHE`` default).
    """

    def __init__(self, cfg, params, *, slots=None, prefill_buckets=(8, 32),
                 max_queue=64, device=None, name="lm", replica="0",
                 autostart=True, on_step_error=None, on_step_ok=None,
                 kv_layout=None, kv_block_size=None, kv_blocks=None,
                 kv_prefix_cache=None):
        import jax

        #: the model protocol's implementer; ``cfg`` is its config
        self.model = cfg if hasattr(cfg, "cache_spec") \
            else _tlm.DecodeModel(cfg)
        cfg = self.cfg = self.model.cfg
        self.name = name
        self.replica = str(replica)
        self.slots = int(slots) if slots is not None \
            else _env_int("MXNET_DECODE_SLOTS", 8)
        if self.slots < 1:
            raise MXNetError("DecodeEngine needs >= 1 slot")
        buckets = tuple(sorted({int(b) for b in prefill_buckets}))
        if not buckets or buckets[0] < 1 or buckets[-1] > cfg.max_len:
            raise MXNetError(
                "prefill buckets %r must be within 1..max_len=%d"
                % (buckets, cfg.max_len))
        self.prefill_buckets = buckets
        self.max_queue = int(max_queue)
        self._device = device if device is not None else jax.devices()[0]
        self._params = jax.device_put(params, self._device)
        self._on_step_error = on_step_error
        self._on_step_ok = on_step_ok
        self._on_migrate = None

        self._cond = threading.Condition(threading.Lock())
        self._queue = deque()
        self._slot_sessions = [None] * self.slots
        self._running = False
        self._draining = False
        self._closed = False
        self._thread = None
        self._beat = time.monotonic()
        #: decode steps dispatched and not read yet, oldest first: one
        #: between turns (THE pipeline depth, a constant of the loop's
        #: order and not an option), two between a dispatch and the read
        #: that follows it.  The loop's thread alone touches it.
        self._unread = deque()
        #: when the last packed read ended (the token gap's left end)
        self._read_t = 0.0
        #: decode steps read (tests pin continuous admission on it); a
        #: session's last step is followed by one it rides inactive
        self.steps = 0
        #: ... and how many of them were read with their successor
        #: already dispatched (``serving.decode.overlap_share``)
        self._reads_ahead = 0
        #: total generated tokens
        self.tokens_out = 0
        #: sessions re-admitted here by failover (describe/healthz card)
        self.resumed = 0
        #: prompt+generated tokens re-prefilled for those resumes
        self.reprefilled_tokens = 0
        self._rate_t0 = time.monotonic()
        self._rate_tokens = 0

        layout = kv_layout if kv_layout is not None \
            else (os.environ.get("MXNET_KV_LAYOUT", "dense") or "dense")
        layout = str(layout).strip().lower()
        if layout not in ("dense", "paged"):
            raise MXNetError(
                "kv_layout/MXNET_KV_LAYOUT must be 'dense' or 'paged', "
                "got %r" % layout)
        self.kv_layout = layout
        self._spec = tuple(self.model.cache_spec())
        #: whether an admission overwrites recurrent state (a "state" entry)
        self._resets_state = any(c.kind == "state" for c in self._spec)
        #: positions a step covers for a slot: 0 for a model whose step
        #: yields one token a slot (``finish_step``), ``cfg.block`` for one
        #: that generates by diffusion over blocks (``finish_block``)
        self._block = int(getattr(cfg, "block", 0) or 0)
        if layout == "paged" and (self._block or not (
                hasattr(self.model, "decode_step_paged")
                and all(c.kind == "full" for c in self._spec))):
            raise UnsupportedKVLayout(
                "model %r (%s) has no paged decode path: the block pool "
                "holds full layers of one shape; serve it with "
                "kv_layout='dense'" % (name, type(self.model).__name__))
        #: the model's extra device state (None when it has none): never
        #: donated, replaced by every step, read by model_counters()
        self._extra = None
        self._extra_seen = None
        self._extra_total = None
        #: paged storage control plane (None under the dense layout)
        self._kv = KVBlockPool(
            cfg, self.slots, block_size=kv_block_size,
            num_blocks=kv_blocks, prefix_cache=kv_prefix_cache,
            model=name, replica=self.replica) \
            if layout == "paged" else None
        #: host mirror of each slot's device ``lengths`` — the paged
        #: loop derives the next write position (and block-boundary
        #: appends) from it without a device read, and either loop the
        #: rows a step's attention reads
        self._slot_len = [0] * self.slots
        #: of a model that offers ``attended_rows`` (its dense step's): the
        #: rows a slot its steps' attention read and those steps, totals
        #: the loop writes, and the pair model_counters() last read
        self._attended_rows = getattr(self.model, "attended_rows", None) \
            if layout == "dense" else None
        self._attended = self._attended_seen = (0, 0)

        self._step_fn, self._prefill_fns = None, {}    # built in _build()
        with self._setup_span():
            self._boot_state = self._build()
        labels = {"model": name, "replica": self.replica}
        _telemetry.inc("serving.decode.sessions.count", 0, **labels)
        _telemetry.inc("serving.decode.tokens.count", 0, **labels)
        _telemetry.inc("serving.decode.steps.count", 0, **labels)
        _telemetry.set_gauge("serving.decode.slot_occupancy", 0.0, **labels)
        _telemetry.set_gauge("serving.decode.tokens_per_sec", 0.0, **labels)
        _telemetry.inc("serving.failover.reprefill_tokens.count", 0,
                       **labels)
        for reason in ("deadline", "overload", "abandoned", "drain",
                       "kv_blocks"):
            _telemetry.inc("serving.shed.count", 0, model=name,
                           reason=reason)
        if autostart:
            self.start()

    # -- compiled programs -------------------------------------------------
    def _build(self):
        """Build the two jitted programs and the initial device state;
        warm-compile every shape so no live request ever eats a trace
        (persistent-cache loads on a warm reload, PR 7)."""
        import jax
        import jax.numpy as jnp

        cfg, model = self.cfg, self.model
        s, m = self.slots, cfg.max_len
        eos = np.int32(cfg.eos_id)
        nblock = self._block

        def fold_key(seed, pos):
            # the ONE key derivation (failover invariant): the token
            # that will occupy absolute position ``pos`` of its
            # sequence is drawn with fold_in(PRNGKey(seed), pos) — a
            # pure function of the session transcript, never of slot
            # index, co-residents, or interruption history
            return jax.random.fold_in(jax.random.PRNGKey(seed), pos)

        def sample(keys, logits, temps):
            # greedy when temperature == 0, else temperature sampling;
            # per-row position-derived keys — the loop never touches
            # the host RNG
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            drawn = jax.vmap(
                lambda kk, lg, tt: jax.random.categorical(
                    kk, lg / jnp.maximum(tt, 1e-6)))(
                        keys, logits, temps).astype(jnp.int32)
            return jnp.where(temps > 0.0, drawn, greedy)

        def finish_step(state_rest, logits, keep):
            # shared sampling/retirement tail of both layouts
            last_tok, lengths, limits, active, temps, seeds = state_rest
            active = active & keep
            # last_tok sits at position ``lengths``; the sampled token
            # will occupy ``lengths + 1``
            keys = jax.vmap(fold_key)(seeds, lengths + 1)
            tok = sample(keys, logits, temps)
            new_len = lengths + active.astype(jnp.int32)
            done = active & ((tok == eos) | (new_len >= limits))
            new_active = active & ~done
            new_last = jnp.where(active, tok, last_tok)
            packed = jnp.stack([jnp.where(active, tok, -1),
                                done.astype(jnp.int32),
                                new_active.astype(jnp.int32)])
            return (new_last, new_len, limits, new_active, temps,
                    seeds), packed

        def arm_slot(state_rest, slot, tok_logits, length, limit, temp,
                     seed, activate):
            # shared slot-arming tail of both prefill layouts: the
            # prompt holds positions 0..length-1; the sampled token
            # occupies ``length`` — on a failover re-prefill of
            # prompt+generated this is exactly the key the interrupted
            # replica's next decode step would have used
            last_tok, lengths, limits, active, temps, seeds = state_rest
            tok = sample(fold_key(seed, length)[None], tok_logits[None],
                         jnp.full((1,), temp))[0]
            first_done = (tok == eos) | (limit <= length)
            arm = activate & ~first_done
            last_tok = last_tok.at[slot].set(tok)
            lengths = lengths.at[slot].set(length)
            limits = limits.at[slot].set(limit)
            temps = temps.at[slot].set(temp)
            active = active.at[slot].set(arm)
            seeds = seeds.at[slot].set(seed)
            out = jnp.stack([tok, first_done.astype(jnp.int32)])
            return (last_tok, lengths, limits, active, temps, seeds), out

        def finish_block(state_rest, logits, keep, extra):
            # the tail of a model that generates by diffusion over blocks
            # (``cfg.block`` positions a slot): ``logits (S, B, vocab)``
            # are one pass over each slot's OPEN block, which fixes some of
            # its open positions by ``cfg.remasking``.  A pass that leaves
            # its block whole DELIVERS it at once (the tokens beyond the
            # prompt and below the limit), moves the length by B, opens a
            # fresh block at the next B positions and keeps the finished
            # one as the slot's PENDING block: its final tokens' K and V
            # are not in the cache yet (what the pass wrote are the K and V
            # of the block as it stood at entry), and the slot's next pass
            # writes them beside its own rows.  A session that ends with
            # the block leaves nothing pending.  Blocks, flags and pass
            # index stay on the device; the host learns of a delivery from
            # ``packed (B + 3, S)``: B tokens (-1: none), the passes at
            # which they were fixed (one code a slot), done, active
            (block, lengths, limits, active, temps, seeds, fixed, fixed_at,
             passes, pending, pending_block) = state_rest
            # the blocks whose final K and V the step has just written
            wrote = pending & active
            active = active & keep
            pos = lengths[:, None] + jnp.arange(nblock)[None, :]
            # the key of position p at pass t: a pure function of the
            # transcript (blocks are absolute), so a re-prefill of prompt
            # and delivered blocks resumes the very stream
            keys = jax.vmap(lambda seed, ps, t: jax.vmap(
                lambda q: jax.random.fold_in(fold_key(seed, q), t))(ps))(
                    seeds, pos, passes)
            # read as rows: ``(S, B, vocab)`` with its 4 in the sublanes'
            # place is another layout on the chip, and a copy of the logits
            flat = logits.reshape(s * nblock, -1)

            def first_of(z):
                top = z.max(axis=-1)
                return (jnp.argmax(z, axis=-1).astype(jnp.int32),
                        jnp.exp(top - jax.nn.logsumexp(z, axis=-1)))

            def drawn():
                # a draw from softmax(logits / temperature) where a slot
                # has one, and its probability there
                hot = jnp.repeat(temps, nblock)
                z = flat / jnp.where(hot > 0.0, jnp.maximum(hot, 1e-6),
                                     1.0)[:, None]
                tok = jnp.where(hot > 0.0, jax.vmap(jax.random.categorical)(
                    keys.reshape(s * nblock, -1), z).astype(jnp.int32),
                    first_of(z)[0])
                return tok, jnp.exp(
                    jnp.take_along_axis(z, tok[:, None], axis=-1)[:, 0]
                    - jax.nn.logsumexp(z, axis=-1))

            # greedy slots alone (the usual case) read the logits as they
            # are: the best token and its probability under the softmax
            x0, conf = (a.reshape(s, nblock) for a in jax.lax.cond(
                (temps > 0.0).any(), drawn, lambda: first_of(flat)))
            with jax.named_scope("diffusion.fix"):
                opened = ~fixed & active[:, None]
                # pass t's quota: B // T, one more in the first B % T
                want = (nblock // cfg.denoise_steps + (
                    passes < nblock % cfg.denoise_steps))[:, None]
                # an open position's rank by confidence, ties to the first
                rank = jnp.argsort(jnp.argsort(
                    -jnp.where(opened, conf, -1.0), axis=-1, stable=True),
                    axis=-1)
                best = opened & (rank < want)
                by_threshold = jnp.zeros_like(opened)
                if cfg.remasking == "sequential":
                    take = opened & (jnp.cumsum(opened, axis=-1) <= want)
                elif cfg.remasking == "low_confidence_static":
                    take = best
                else:
                    high = opened & (conf > cfg.threshold)
                    by_threshold = high & (high.sum(-1, keepdims=True)
                                           >= want)
                    take = jnp.where(by_threshold.any(-1, keepdims=True),
                                     high, best)
            # the block as this pass leaves it
            block = jnp.where(take, x0, block)
            fixed = fixed | take
            fixed_at = jnp.where(take, passes[:, None], fixed_at)
            whole = active & fixed.all(-1)
            mine = (fixed_at >= 0) & (pos < limits[:, None])
            is_eos = mine & (block == eos)
            deliver = whole[:, None] & mine \
                & (jnp.cumsum(is_eos, axis=-1) - is_eos == 0)
            new_len = lengths + whole * nblock
            done = whole & ((deliver & is_eos).any(-1)
                            | (new_len >= limits))
            new_active = active & ~done
            fresh = whole[:, None]
            base = cfg.denoise_steps + 1
            packed = jnp.concatenate([
                jnp.where(deliver, block, -1).T,
                ((fixed_at + 1) * base ** jnp.arange(nblock)).sum(-1)[None],
                done.astype(jnp.int32)[None],
                new_active.astype(jnp.int32)[None]])
            if extra is not None:
                counted = {
                    "commits": wrote.sum(),
                    "tokens_committed": deliver.sum(),
                    "fixed_by_threshold": (take & by_threshold).sum(),
                    "fixed_by_quota": (take & ~by_threshold).sum()}
                extra = dict(extra, **{
                    k: extra[k] + v.astype(extra[k].dtype)
                    for k, v in counted.items() if k in extra})
            return (jnp.where(fresh, np.int32(cfg.mask_id), block),
                    new_len, limits, new_active, temps, seeds,
                    fixed & ~fresh, jnp.where(fresh, -1, fixed_at),
                    jnp.where(whole, 0, passes + active),
                    whole & ~done,
                    jnp.where(fresh, block, pending_block)), packed, extra

        def arm_block(state_rest, slot, first, length, limit, temp, seed,
                      activate):
            # the slot-arming tail of a block model's prefill, which
            # yields NO token: ``first (B,)`` is the block that holds
            # position ``length // B * B``, of which the prompt's
            # ``length % B`` last tokens are fixed from the start (pass
            # -1); ``limit`` is the end of the session, prompt included.
            # Whatever block the slot's last session left pending is
            # nobody's now
            (block, lengths, limits, active, temps, seeds, fixed, fixed_at,
             passes, pending, pending_block) = state_rest
            nothing = limit <= length
            out = jnp.stack([jnp.int32(-1), nothing.astype(jnp.int32)])
            return (block.at[slot].set(first),
                    lengths.at[slot].set(length // nblock * nblock),
                    limits.at[slot].set(limit),
                    active.at[slot].set(activate & ~nothing),
                    temps.at[slot].set(temp), seeds.at[slot].set(seed),
                    fixed.at[slot].set(jnp.arange(nblock)
                                       < length % nblock),
                    fixed_at.at[slot].set(-1),
                    passes.at[slot].set(0),
                    pending.at[slot].set(False), pending_block), out

        if self._kv is not None:
            nb, bs = self._kv.num_blocks, self._kv.block_size

            def step(params, state, keep, tables):
                pool_k, pool_v = state[0], state[1]
                logits, pool_k, pool_v = model.decode_step_paged(
                    params, pool_k, pool_v, tables, state[2], state[3])
                rest, packed = finish_step(state[2:], logits, keep)
                return (pool_k, pool_v) + rest, packed

            def prefill(params, state, tokens, start, length, slot,
                        table, limit, temp, seed, activate, cow_src,
                        cow_dst):
                pool_k, pool_v = state[0], state[1]
                # admission-time copy-on-write: duplicate the shared
                # partial tail block before the suffix scatters into
                # the copy; (0, 0) — scratch onto itself — is the
                # no-COW case, so ONE compiled program covers cold,
                # prefix-hit and COW admissions alike
                pool_k = tuple(pk.at[cow_dst].set(pk[cow_src])
                               for pk in pool_k)
                pool_v = tuple(pv.at[cow_dst].set(pv[cow_src])
                               for pv in pool_v)
                last_logits, pool_k, pool_v = model.prefill_paged(
                    params, pool_k, pool_v, table, tokens, start, length)
                rest, out = arm_slot(state[2:], slot, last_logits,
                                     length, limit, temp, seed, activate)
                return (pool_k, pool_v) + rest, out

            self._step_fn = self._instrument(
                jax.jit(step, donate_argnums=(1,)), "decode_step",
                ("decode_step_paged", s, m, nb, bs))
            pf_jit = jax.jit(prefill, donate_argnums=(1,))
            self._prefill_fns = {
                b: self._instrument(pf_jit, "decode_prefill",
                                    ("decode_prefill_paged", b, s, m,
                                     nb, bs))
                for b in self.prefill_buckets}
        else:
            extra0 = model.extra_state()
            self._extra = None if extra0 is None \
                else jax.device_put(extra0, self._device)
            with self._cond:
                if self._extra_seen is not None:
                    # a rebuild counts on from zero; what was read stays
                    self._extra_seen = jax.tree_util.tree_map(
                        np.zeros_like, self._extra_seen)

            def advance(params, state, keep, extra):
                cache_k, cache_v = state[0], state[1]
                if nblock:
                    # the slots' pending blocks are the engine's state, and
                    # reach the model beside its own
                    riding = dict(pending=state[-2], pending_block=state[-1])
                    extra = dict(extra or {}, **riding)
                logits, cache_k, cache_v, extra = model.decode_step(
                    params, cache_k, cache_v, state[2], state[3], state[5],
                    extra)
                if nblock:
                    extra = {k: v for k, v in extra.items()
                             if k not in riding} or None
                    rest, packed, extra = finish_block(state[2:], logits,
                                                       keep, extra)
                else:
                    rest, packed = finish_step(state[2:], logits, keep)
                return (cache_k, cache_v) + rest, packed, extra

            if extra0 is None:
                def step(params, state, keep):
                    return advance(params, state, keep, None)[:2]
            else:
                def step(params, state, keep, extra):
                    return advance(params, state, keep, extra)

            def prefill(params, state, tokens, length, slot, limit,
                        temp, seed, activate):
                last_logits, firsts, seconds = model.prefill(
                    params, tokens, length)

                def into_slot(held, values):
                    # from row 0 of the slot: a K or a V as far as the
                    # bucket reaches, a state whole (its value has the
                    # slot's own shape, so nothing of the last session's
                    # is left)
                    return tuple(
                        jax.lax.dynamic_update_slice(
                            h, v[None], (slot,) + (0,) * v.ndim)
                        for h, v in zip(held, values))

                cache = (into_slot(state[0], firsts),
                         into_slot(state[1], seconds))
                rest, out = (arm_block if nblock else arm_slot)(
                    state[2:], slot, last_logits, length, limit, temp,
                    seed, activate)
                return cache + rest, out

            self._step_fn = self._instrument(
                jax.jit(step, donate_argnums=(1,)), "decode_step",
                ("decode_step", s, m))
            pf_jit = jax.jit(prefill, donate_argnums=(1,))
            self._prefill_fns = {
                b: self._instrument(pf_jit, "decode_prefill",
                                    ("decode_prefill", b, s, m))
                for b in self.prefill_buckets}
        with _tracing.setup_span("serving.setup.state"):
            state = self._fresh_state()
        cc0 = _compile_cache.stats() if _compile_cache.enabled() else None
        with _compile_cache.recording_scope() as rec, \
                _tracing.setup_span("serving.setup.warm"):
            state = self._warm(state)
            cc1 = _compile_cache.stats() if cc0 is not None else None
        self.warmup_entries = rec.entries
        if cc0 is not None:
            # a separate family from the batcher's serving.warmup.* —
            # this one carries a replica label, and a telemetry family
            # must never mix label sets
            _telemetry.set_gauge(
                "serving.decode.warmup.cold_compiles",
                cc1["misses"] - cc0["misses"], model=self.name,
                replica=self.replica)
            _telemetry.set_gauge(
                "serving.decode.warmup.cache_loads",
                cc1["hits"] + cc1["store_hits"] - cc0["hits"]
                - cc0["store_hits"], model=self.name, replica=self.replica)
        if self._kv is None:
            for kind, held in self._cache_bytes().items():
                _telemetry.set_gauge("serving.cache.bytes", held,
                                     model=self.name, replica=self.replica,
                                     kind=kind)
        _telemetry.event("serving.decode.warm", model=self.name,
                         replica=self.replica, slots=s,
                         buckets=len(self.prefill_buckets))
        return state

    def _instrument(self, fn, kind, build_kind):
        """First-call hook (a ``serving.setup.program`` span): count the
        compile and record the build into the warm-up manifest registry.
        The first call is the executable store's
        (``compile_cache.stored_program``): a start that finds the program
        there loads it, and ``fn`` is neither traced nor lowered."""
        # everything the two programs close over: the model with its cfg
        # and cache dtype, and what _build read of the engine
        store = _compile_cache.stored_program(
            "serving:%s" % self.name, build_kind,
            {"model": self.model, "spec": self._spec, "slots": self.slots,
             "buckets": self.prefill_buckets, "layout": self.kv_layout,
             "block": self._block, "donate": (1,),
             "paged": self._kv and (self._kv.num_blocks,
                                    self._kv.block_size,
                                    self._kv.max_blocks)}, self._device)

        def hook(f, args, kwargs, dt):
            _telemetry.inc("xla.compile.count", kind=kind)
            _telemetry.inc("xla.compile.seconds", dt, kind=kind)
            if store is not None and store.hit:
                return  # the store recorded the build its entry held
            if _compile_cache.recording():
                _compile_cache.note_build(
                    "serving:%s" % self.name, build_kind, f.lower, args,
                    kwargs, dt)
            if store is not None:
                store.save(f, args, kwargs)
        return _perfdebug.first_call_hook(
            fn, hook, span=self._program_span(kind, build_kind), store=store)

    def _fresh_state(self):
        """Zeroed device-resident slot state, committed to the replica
        device.  Under the paged layout the K/V tensors are the BLOCK
        POOLS, and rebuilding them from zeros invalidates every block —
        the host control plane (allocator, tables, prefix cache) resets
        in the same breath."""
        import jax
        import jax.numpy as jnp

        s = self.slots
        if self._kv is not None:
            self._kv.reset()
            lead = (self._kv.num_blocks, self._kv.block_size)
        else:
            lead = None
        self._slot_len = [0] * s
        state = self.state_shapes(jnp.zeros, lead)
        if self._block:
            # no position of a fresh block was fixed at any pass
            state = state[:9] + (state[9] - 1,) + state[10:]
        return jax.device_put(state, self._device)

    def state_shapes(self, make, lead=None):
        """The donated slot state as ``make(shape, dtype)`` makes its
        arrays (``jnp.zeros`` for the state itself, a
        ``jax.ShapeDtypeStruct`` for a tool that lowers the programs and
        allocates nothing): every entry's first array, every entry's
        second, then ``last_tok``, ``lengths``, ``limits``, ``active``,
        ``temps`` and the per-slot ``seeds``, each ``(slots,)``.  A block
        model's has the tokens of each slot's current block ``(slots, B)``
        in ``last_tok``'s place and after ``seeds`` which of them are
        fixed, the pass at which each was, both ``(slots, B)``, the pass
        the block is at, which slots hold a pending block (delivered, its
        final K and V not yet written) and its tokens ``(slots, B)``.
        ``lead`` is the paged layout's ``(blocks, block size)`` in the
        slots' place."""
        import jax.numpy as jnp

        s = self.slots

        def entries(i):
            made = []
            for c in self._spec:
                shape, dtype = _tlm.slot_arrays(c)[i]
                made.append(make(
                    lead + (c.kv_heads, c.head_dim) if lead
                    else (s,) + shape, dtype))
            return tuple(made)

        held = (s, self._block) if self._block else (s,)
        state = (entries(0), entries(1), make(held, jnp.int32),
                 make((s,), jnp.int32), make((s,), jnp.int32),
                 make((s,), jnp.bool_), make((s,), jnp.float32),
                 make((s,), jnp.uint32))
        if self._block:
            state += (make(held, jnp.bool_), make(held, jnp.int32),
                      make((s,), jnp.int32), make((s,), jnp.bool_),
                      make(held, jnp.int32))
        return state

    def _warm(self, state):
        """Compile the decode step and every prefill bucket against the
        real state buffers — ``activate=False`` leaves the slots
        disarmed, so warm-up never corrupts serving state.  The paged
        warm-up runs with an all-zero table: every scatter lands in the
        scratch block, which is exactly what makes it harmless."""
        if self._kv is not None:
            mb = self._kv.max_blocks
            ztab = np.zeros((mb,), np.int32)
            for b in self.prefill_buckets:
                state, _out = self._prefill_fns[b](
                    self._params, state, np.zeros((b,), np.int32),
                    np.int32(0), np.int32(1), np.int32(0), ztab,
                    np.int32(0), np.float32(0.0), np.uint32(0),
                    np.bool_(False), np.int32(0), np.int32(0))
            state, _packed = self._step_fn(
                self._params, state, np.ones((self.slots,), bool),
                np.zeros((self.slots, mb), np.int32))
            return state
        for b in self.prefill_buckets:
            state, _out = self._prefill_fns[b](
                self._params, state, np.zeros((b,), np.int32),
                np.int32(1), np.int32(0), np.int32(0), np.float32(0.0),
                np.uint32(0), np.bool_(False))
        state, _packed = self._dispatch_step(
            state, np.ones((self.slots,), bool))
        return state

    def _dispatch_step(self, state, keep):
        """The dense step's one dispatch: a model's extra device state
        rides beside the donated state and comes back new."""
        if self._extra is None:
            return self._step_fn(self._params, state, keep)
        state, packed, extra = self._step_fn(
            self._params, state, keep, self._extra)
        self._extra = extra  # lint: ok[lock-discipline] explicit hand-off: one writer (the thread that runs the step), an atomic swap of an immutable device array that is never donated; model_counters() reads whichever is latest
        return state, packed

    def set_health_hooks(self, on_error=None, on_ok=None,
                         on_migrate=None):
        """Install the pool's replica-health hooks (and its failover
        hand-off: ``on_migrate(sessions, exc)`` receives the sessions a
        failed dispatch was holding INSTEAD of them being shed — the
        pool re-admits them elsewhere or sheds typed).  Call before
        :meth:`start` — plain attribute flips, deliberately outside the
        engine lock (the hooks take the POOL's lock; holding both here
        would order the locks both ways)."""
        self._on_step_error = on_error
        self._on_step_ok = on_ok
        self._on_migrate = on_migrate

    def rewarm(self):
        """Recompile/reload every program (the pool's quarantine
        re-warm): with the persistent compile cache armed this is pure
        cache loads — zero cold compiles on a healthy host.  Refuses a
        running or CLOSED engine — a background re-warm racing a
        pointer-flip version swap must not resurrect the retired
        replica (the pool's except path leaves it quarantined)."""
        with self._cond:
            if self._running:
                raise MXNetError("rewarm() needs a stopped engine")
            if self._closed:
                raise MXNetError("decode engine %r is closed"
                                 % self.name)
        with self._setup_span():
            state = self._build()
        with self._cond:
            self._boot_state = state
            self._draining = False

    def _setup_span(self):
        """``serving.setup.engine``: what a :meth:`_build` lies in, recorded
        with tracing off (``tracing.setup_span``); under it
        ``serving.setup.state``, ``serving.setup.warm`` and, under that
        one, a ``serving.setup.program`` a first call.  Opened at
        ``_build``'s two call sites, and no line added above ``_build``'s
        closures: a kernel's lowered text carries the line numbers of its
        call stack, theirs among them, and the account is held to leaving
        every program's text as it was
        (``tools/perf/program_fingerprints.py --tpu``)."""
        return _tracing.setup_span(
            "serving.setup.engine", model=self.name, replica=self.replica,
            slots=self.slots, buckets=list(self.prefill_buckets))

    @staticmethod
    def _program_span(kind, build_kind):
        """Opens ``serving.setup.program`` round a program's first call."""
        bucket = build_kind[1] if kind == "decode_prefill" else None
        return lambda: _tracing.setup_span("serving.setup.program",
                                           kind=kind, bucket=bucket)

    # -- client side -------------------------------------------------------
    def _validate_admission(self, n, what):
        """THE transcript-length admission validator — ``submit`` and
        ``resume`` used to carry drifting copies of the same two
        checks; they now share this one, which also enforces the paged
        block budget.  ``n`` is the transcript length that will be
        (re-)prefilled; ``what`` names it in the client's error.
        Raises :class:`InvalidRequest` for transcripts no engine of
        this shape could ever hold, and typed
        :class:`KVBlocksExhausted` (an :class:`Overloaded` — clients
        retry it) when the block pool is sized too small for the
        transcript even with every block free."""
        if n > self.prefill_buckets[-1]:
            raise InvalidRequest(
                "%s of %d tokens exceeds the largest prefill bucket %d"
                % (what, n, self.prefill_buckets[-1]))
        if n >= self.cfg.max_len:
            raise InvalidRequest(
                "%s of %d tokens leaves no room under max_len=%d"
                % (what, n, self.cfg.max_len))
        if self._kv is not None and not self._kv.admissible(n):
            _telemetry.inc("serving.shed.count", model=self.name,
                           reason="kv_blocks")
            raise KVBlocksExhausted(
                "%s of %d tokens needs %d KV blocks but the pool holds "
                "only %d allocatable (%d blocks x %d tokens)"
                % (what, n, n // self._kv.block_size + 1,
                   self._kv.num_blocks - 1, self._kv.num_blocks,
                   self._kv.block_size))

    def submit(self, prompt, *, max_new_tokens=16, temperature=0.0,
               deadline_ms=None, on_token=None, on_done=None, seed=None,
               tenant=None, on_event=None):
        """Queue a generation request; returns its
        :class:`GenerateSession`.  Raises :class:`Overloaded` past the
        queue bound and :class:`InvalidRequest` for malformed prompts
        (the client's error, surfaced at submit).

        ``seed`` pins the session's sampling stream (temperature
        replays and cross-replica failover are bit-identical for the
        same seed); None draws one from :mod:`mxnet_tpu.random`, so
        ``mx.random.seed(n)`` still makes single-stream runs
        reproducible end to end."""
        prompt = np.array(prompt, np.int32).ravel()
        if prompt.size < 1:
            raise InvalidRequest("empty prompt")
        self._validate_admission(int(prompt.size), "prompt")
        if prompt.min() < 0 or prompt.max() >= self.cfg.vocab:
            raise InvalidRequest(
                "prompt token ids must be in 0..vocab-1=%d"
                % (self.cfg.vocab - 1))
        if int(max_new_tokens) < 1:
            raise InvalidRequest("max_new_tokens must be >= 1")
        if float(temperature) < 0:
            raise InvalidRequest("temperature must be >= 0")
        if seed is None:
            seed = int(np.asarray(_random.next_key())[0])  # lint: ok[host-sync] tiny submit-time key-material read (one uint32 per session), not the per-step hot loop
        sess = GenerateSession(prompt, max_new_tokens, temperature,
                               deadline_ms, on_token, on_done, seed=seed,
                               tenant=tenant, on_event=on_event)
        # root span for the session's whole lifetime — opened on the
        # CALLER's thread so it parents under any in-flight request
        # span (HTTP handler, batcher); stack=False because it outlives
        # this call and is closed from the engine thread at _resolve
        sess.trace = _tracing.start_span(
            "serving.generate", stack=False, model=self.name,
            prompt_tokens=int(prompt.size))
        with self._cond:
            if self._closed:
                sess.trace.end("error", reason="closed")
                raise MXNetError("decode engine %r is closed" % self.name)
            if self._draining:
                _telemetry.inc("serving.shed.count", model=self.name,
                               reason="drain")
                sess.trace.end("shed", reason="drain")
                raise Overloaded("decode engine %r is draining"
                                 % self.name)
            if len(self._queue) >= self.max_queue:
                _telemetry.inc("serving.shed.count", model=self.name,
                               reason="overload")
                sess.trace.end("shed", reason="overload")
                raise Overloaded(
                    "decode engine %r overloaded: %d sessions queued"
                    % (self.name, len(self._queue)))
            # counted AFTER admission: sessions.count is accepted
            # sessions (completion/shed ratios read against it);
            # rejected submits show only in serving.shed.count
            _telemetry.inc("serving.decode.sessions.count",
                           model=self.name, replica=self.replica)
            self._queue.append(sess)
            self._cond.notify()
        return sess

    def generate(self, prompt, timeout=60.0, **kw):
        """Blocking convenience: ``submit`` + ``result``."""
        sess = self.submit(prompt, **kw)
        try:
            return sess.result(timeout)
        except DeadlineExceeded:
            sess.cancel()
            raise

    def resume(self, sess):
        """Re-admit a migrated session (the pool's failover path): its
        transcript — ``prompt + tokens generated so far`` — is
        re-prefilled into a free slot and decoding continues with the
        same position-derived keys, so the resumed stream is
        bit-identical to what the interrupted replica would have
        produced.  Raises :class:`InvalidRequest` when the combined
        transcript no longer fits a prefill bucket (the caller sheds
        typed) and the engine's closed/draining errors otherwise.

        Deliberately NOT bounded by ``max_queue``: the session already
        holds pool admission (its accounting moved with it) — bouncing
        a migration off the queue bound would turn a survivable replica
        loss into a shed.  Resumed sessions jump the queue: they have
        already waited once."""
        full = int(sess.prompt.size) + len(sess.tokens)
        self._validate_admission(
            full, "migrated transcript (prompt %d + generated %d)"
            % (sess.prompt.size, len(sess.tokens)))
        with self._cond:
            if self._closed:
                raise MXNetError("decode engine %r is closed" % self.name)
            if self._draining:
                raise Overloaded("decode engine %r is draining"
                                 % self.name)
            # not counted under sessions.count — the session was
            # counted at its original admission
            self._queue.appendleft(sess)
            self._cond.notify()
        return sess

    # -- introspection -----------------------------------------------------
    def pending_rows(self):
        """Queued plus active sessions — the graceful-drain quiescence
        probe (one row == one sequence)."""
        with self._cond:
            return len(self._queue) + \
                sum(1 for x in self._slot_sessions if x is not None)

    def outstanding(self):
        """Same number the pool's least-outstanding routing reads."""
        return self.pending_rows()

    def heartbeat_age(self):
        """Seconds since the serve loop last proved liveness, or None
        when no worker has been started.  The loop stamps every
        iteration (idle included), so a stale age means a wedged
        dispatch or a dead worker thread — the fleet controller's
        per-replica liveness probe."""
        with self._cond:
            if self._thread is None:
                return None
            return time.monotonic() - self._beat

    def describe(self):
        with self._cond:
            active = sum(1 for x in self._slot_sessions if x is not None)
            queued = len(self._queue)
            steps = self.steps
            tokens = self.tokens_out
            resumed = self.resumed
            reprefilled = self.reprefilled_tokens
        if self._kv is not None:
            kv = self._kv.describe()
        else:
            kv = {"layout": "dense",
                  "hbm_bytes": sum(self._cache_bytes().values())}
        model = self.model_counters()
        return {"name": self.name, "kind": "generate",
                # which tail the step runs: one token a slot, or a pass
                # over blocks that delivers none or up to ``block`` tokens
                "tail": "block" if self._block else "token",
                **({"block": self._block} if self._block else {}),
                **({"model_counters": model} if model else {}),
                "version": getattr(self, "version", None),
                "replica": self.replica, "device": str(self._device),
                "slots": self.slots, "active": active, "queued": queued,
                "steps": steps, "tokens": tokens,
                "sessions_resumed": resumed,
                "reprefilled_tokens": reprefilled,
                "prefill_buckets": list(self.prefill_buckets),
                "max_len": self.cfg.max_len, "kv": kv}

    def _cache_bytes(self):
        """Bytes of the dense slot state by kind of entry (``full``,
        ``ring``, ``state``, ``latent``), both arrays of every entry over
        all slots, as they lie (a model's padding to whole lanes counted)."""
        out = {}
        for c in self._spec:
            out[c.kind] = out.get(c.kind, 0) + self.slots * sum(
                int(np.prod(shape)) * np.dtype(dtype).itemsize
                for shape, dtype in _tlm.slot_arrays(c))
        return out

    def model_counters(self):
        """What the model's extra device state has counted since the
        engine was built, as whole numbers by the model's own names ({}
        for a model without such state).  One small device read, made by
        the caller's thread and never by the loop: the state is not
        donated, so the latest one a step returned stays readable.  The
        device counts in wrapping uint32; the differences between reads
        are summed here, so a count is exact while reads are less than
        2**32 picks apart.  Of a model that offers ``attended_rows`` also
        the gauge ``serving.attn.rows_read_share``: the rows a slot its
        attention read over ``max_len``, the mean of the steps dispatched
        since the last read, by the host's mirror of the lengths (no
        device read; a step behind the device at most)."""
        out = {}
        extra = self._extra
        if extra is not None:
            import jax

            with _tracing.host_read("decode.model_counters"):
                now = jax.tree_util.tree_map(
                    lambda a: np.asarray(a).astype(np.int64), extra)  # lint: ok[host-sync] counters read on the caller's thread (describe/telemetry), never in the loop
            with self._cond:
                if self._extra_seen is None:
                    self._extra_total = now
                else:
                    self._extra_total = jax.tree_util.tree_map(
                        lambda t, a, b: t + (a - b) % (1 << 32),
                        self._extra_total, now, self._extra_seen)
                self._extra_seen = now
                total = self._extra_total
            out = self.model.counters(total)
        attended = self._attended
        with self._cond:
            rows, steps = (now - seen for now, seen
                           in zip(attended, self._attended_seen))
            self._attended_seen = attended
        if steps:
            out.setdefault("gauges", {})["serving.attn.rows_read_share"] = \
                rows / float(steps * self.cfg.max_len)
        labels = {"model": self.name, "replica": self.replica}
        for name, value in out.get("gauges", {}).items():
            _telemetry.set_gauge(name, value, **labels)
        return out

    # -- worker ------------------------------------------------------------
    def start(self):
        with self._cond:
            if self._closed:
                # a closed engine stays closed: restarting its worker
                # (e.g. a stale re-warm thread) would leak a spinning
                # daemon on a servable nobody routes to
                raise MXNetError("decode engine %r is closed"
                                 % self.name)
            if self._thread is not None:
                return self
            if self._boot_state is None:
                # restart after a plain stop(): the compiled programs
                # survive, only the slot state was consumed — rebuild
                # it from zeros (device_put, no recompile)
                self._boot_state = self._fresh_state()
            self._draining = False
            self._running = True
            self._thread = threading.Thread(
                target=self._serve_loop,
                name="decode-%s-%s" % (self.name, self.replica),
                daemon=True)
            self._thread.start()
        return self

    def stop(self, drain=True, deadline=None, hand_off=None):
        """Stop the engine.  ``drain=True`` keeps stepping until every
        ACTIVE sequence finishes (new admissions stop; queued sessions
        are shed immediately with a typed error) under ``deadline``
        seconds (``MXNET_PREEMPT_DRAIN_DEADLINE``, default 30); past
        the deadline — or with ``drain=False`` — unfinished sessions
        are shed, never silently dropped.  ``hand_off`` (a callable
        taking a session list) is the failover alternative to
        shedding: queued and slot-holding sessions are handed over
        intact for the pool to re-admit elsewhere (quarantine takeover,
        version-swap straggler migration) and do not mark the stop
        unclean.  Either way the worker reads and fans out the step it
        has in flight before it goes, so what is handed over or shed
        holds every token its client saw.  Returns True when the stop
        lost nothing."""
        if deadline is None:
            deadline = float(os.environ.get(
                "MXNET_PREEMPT_DRAIN_DEADLINE", "30") or 30)
        shed = []
        with self._cond:
            self._draining = True
            if not drain:
                self._running = False
            while self._queue:
                shed.append(self._queue.popleft())
            self._cond.notify_all()
        err = MXNetError("decode engine %r stopped before this session "
                         "was served" % self.name)
        clean = not shed or hand_off is not None
        if hand_off is not None and shed:
            hand_off(shed)
        else:
            for sess in shed:
                _telemetry.inc("serving.shed.count", model=self.name,
                               reason="drain")
                self._finish(sess, error=err)
        with self._cond:
            t, self._thread = self._thread, None
        worker_dead = True
        if t is not None:
            t.join(timeout=deadline if drain else 5.0)
            if t.is_alive():
                clean = False
                with self._cond:
                    self._running = False
                    self._cond.notify_all()
                t.join(timeout=10.0)
            worker_dead = not t.is_alive()
        # anything still holding a slot is handed off or shed typed —
        # but hand-off REQUIRES the worker to be provably gone: a
        # wedged dispatch that eventually returns would keep appending
        # tokens to a session another replica now owns, corrupting the
        # stream.  The shed path stays safe either way (idempotent
        # session-level resolve).
        leftovers = []
        with self._cond:
            for i, sess in enumerate(self._slot_sessions):
                if sess is not None:
                    leftovers.append(sess)
                    self._slot_sessions[i] = None
        if hand_off is not None and leftovers and worker_dead:
            hand_off(leftovers)
        else:
            for sess in leftovers:
                clean = False
                _telemetry.inc("serving.shed.count", model=self.name,
                               reason="drain")
                self._finish(sess, error=err)
        self._occupancy_gauge()
        return clean

    def close(self, drain=True):
        """Permanent :meth:`stop`: further submits fail fast."""
        with self._cond:
            self._closed = True
        return self.stop(drain=drain)

    def _serve_loop(self):
        """The engine's one loop, a software pipeline ONE step deep: a
        turn walks the queue, dispatches the prefill of each admission
        and then step N+1, and only then reads step N's packed buffer and
        fans it out — so the device always has a step queued behind the
        read the host waits on, and the fan-out, the retirements and the
        next queue walk run beside it.  Nothing the host learns from
        step N is an input of step N+1 (``finish_step`` decides ``done``
        and the next ``active`` on the device); ``keep`` — cancel,
        deadline — is the host's one input and reaches the device a step
        late.  An idle engine, a drain and a stop land what is in flight
        before they rest or return."""
        with self._cond:
            state = self._boot_state
            self._boot_state = None
        self._unread.clear()
        while True:
            admits = []
            shed = []  # (session, reason) — finished OUTSIDE the lock:
            # _finish runs the pool's on_done hook, which takes the POOL
            # lock, and pool.describe() takes pool-then-engine — holding
            # the engine lock here would order the locks both ways
            isp = _tracing.start_span("serving.decode.iter", loop=True,
                                      replica=self.replica)
            qsp = _tracing.start_span("serving.decode.queue")
            with self._cond:
                if not self._running:
                    break
                # liveness heartbeat: stamped every loop iteration (the
                # idle wait below is 20ms, so an IDLE engine still beats)
                # — only a wedged dispatch or a dead worker goes stale.
                # The fleet controller's per-replica supervision reads it
                # through heartbeat_age().
                self._beat = time.monotonic()
                # a slot is free once the host has READ the step that
                # retired its session (the fan-out clears it): a slot is
                # never re-admitted under a step that may still emit for
                # its previous holder
                free = [i for i, x in enumerate(self._slot_sessions)
                        if x is None]
                # walk the WHOLE queue every iteration: abandoned or
                # expired entries must release the max_queue admission
                # bound (and the pool's outstanding accounting) even
                # while every slot is busy — the batcher's abandoned-
                # entry fix, applied here too.  FIFO order preserved.
                now = time.monotonic()
                keep = deque()
                while self._queue:
                    sess = self._queue.popleft()
                    if sess.cancelled():
                        shed.append((sess, "abandoned"))
                    elif sess.deadline is not None \
                            and now > sess.deadline:
                        shed.append((sess, "deadline"))
                    elif free:
                        sess.slot = free.pop(0)
                        sess.t_admit = now
                        self._slot_sessions[sess.slot] = sess
                        admits.append(sess)
                    else:
                        keep.append(sess)
                self._queue = keep
                have_active = any(x is not None
                                  for x in self._slot_sessions)
                if not admits and not shed and not have_active \
                        and not self._unread:
                    # an iteration without work leaves no record
                    qsp.drop()
                    isp.drop()
                    if self._draining:
                        self._running = False
                        return
                    self._cond.wait(0.02)
                    continue
                queued = len(keep)
            qsp.end("ok", queued=queued)
            for sess, reason in shed:
                # every exit path resolves the future and fires on_done
                # — a dropped session would leak the pool's outstanding
                # accounting forever
                _telemetry.inc("serving.shed.count", model=self.name,
                               reason=reason)
                err = DeadlineExceeded("deadline expired while queued "
                                       "for a decode slot") \
                    if reason == "deadline" else \
                    MXNetError("session abandoned by the client while "
                               "queued")
                sess.trace.end("shed", reason=reason, where="queued")
                self._finish(sess, error=err)
            state = self._turn(state, admits, isp)
        # stopped: the step in flight is read and fanned out before the
        # worker goes, so what stop() hands over or sheds next holds
        # every token the device had finished for it
        qsp.drop()
        if self._unread:
            self._turn(state, (), isp, dispatch=False)
        else:
            isp.drop()

    def _turn(self, state, admits, isp, dispatch=True):
        """One turn of the pipeline, in the order that keeps the device
        fed: the admissions' prefills (dispatched, not waited for), the
        next step, THEN the read and fan-out of the step dispatched a
        turn ago, then the admissions' first tokens.  A dispatch or a
        read that raises ends the turn in :meth:`_fail_all`.  Returns the
        state the next turn donates."""
        active, ahead, committed = 0, 0, 0
        try:
            firsts = []
            for sess in admits:
                state, first = self._admit(sess, state)
                if first is not None:
                    firsts.append(first)
            if dispatch:
                with self._cond:
                    active = sum(x is not None
                                 for x in self._slot_sessions)
            landing = bool(self._unread)
            if active or landing:
                with _tracing.start_span("serving.decode.step") as ssp:
                    if active:
                        state = self._dispatch(state)
                    if landing:
                        ahead, committed = self._land_step(ssp)
            for first in firsts:
                self._land_first(*first)
        except _Poisoned as p:
            state = self._fail_all(p.cause)
        isp.end("ok", admits=len(admits), active=active, ahead=ahead,
                **({"committed": committed} if self._block else {}))
        return state

    def _admit(self, sess, state):
        """Dispatch the prefill of ``sess`` into its (already reserved)
        slot inside its ``serving.admit`` span, which covers the host's
        part of an admission up to the dispatch: padding, the block plan,
        the bucket-shaped call.  Nothing here waits for the device: the
        prefill queues behind the step in flight, ``arm_slot`` arms the
        slot on the device, and the first token is a pending read that
        :meth:`_land_first` makes once the next step is queued behind
        it.  Runs on the ENGINE thread, so the span parents explicitly
        off the session root (the thread-local stack belongs to the loop
        iteration) and is stacked, for its children and its place in a
        device profile.

        A migrated session re-prefills its whole transcript (prompt +
        generated-so-far) — the ``limit`` stays derived from the
        ORIGINAL prompt length, so total generation length is unchanged
        by any number of migrations.  Returns ``(state, first)``:
        ``first`` is :meth:`_land_first`'s arguments, or None when the
        session was shed typed before anything was dispatched."""
        cfg = self.cfg
        with _tracing.start_span(
                "serving.admit", parent=sess.trace, replica=self.replica,
                resumed=len(sess.tokens) > 0,
                queue_wait_ms=round(1e3 * (sess.queue_wait() or 0.0),
                                    3)) as asp:
            p0 = int(sess.prompt.size)
            resumed = len(sess.tokens) > 0
            if resumed:
                gen = np.asarray(sess.tokens, np.int32)  # lint: ok[host-sync] host-list -> ndarray conversion of the transcript, no device value involved
                full = np.concatenate([sess.prompt, gen])
            else:
                full = sess.prompt
            n = int(full.size)
            # the last position a step may sample for; of a block model
            # the end of the session itself, prompt included (its prefill
            # yields no token)
            limit = np.int32(min(p0 + sess.max_new_tokens
                                 - (0 if self._block else 1), cfg.max_len))
            if self._kv is not None:
                try:
                    plan = self._kv.admit(sess.slot, full)
                except Overloaded as e:
                    # typed KV shed: even evicting the prefix cache
                    # cannot cover this transcript right now — nothing
                    # was dispatched (state unpoisoned, no blocks held),
                    # the session sheds typed and the engine keeps
                    # serving
                    _telemetry.inc("serving.shed.count", model=self.name,
                                   reason="kv_blocks")
                    sess.trace.end("shed", reason="kv_blocks",
                                   where="admit")
                    asp.end("shed", reason="kv_blocks")
                    self._retire(sess, error=e)
                    self._occupancy_gauge()
                    return state, None
                # prefix-hit admissions re-/prefill ONLY the unshared
                # suffix: the bucket is chosen by suffix length, so a
                # long shared prompt rides a small prefill program
                suffix = n - plan.start
                bucket = next(b for b in self.prefill_buckets
                              if suffix <= b)
                tokens = np.zeros((bucket,), np.int32)
                tokens[:suffix] = full[plan.start:]
            else:
                plan = None
                bucket = next(b for b in self.prefill_buckets if n <= b)
                tokens = np.zeros((bucket,), np.int32)
                tokens[:n] = full
            asp.annotate(bucket=bucket,
                         reprefilled=n if resumed else 0,
                         prefix_reused=plan.reused_tokens if plan else 0)
            try:
                with _tracing.start_span("serving.prefill.dispatch"):
                    if plan is not None:
                        state, out = self._prefill_fns[bucket](
                            self._params, state, tokens,
                            np.int32(plan.start), np.int32(n),
                            np.int32(sess.slot),
                            np.ascontiguousarray(
                                self._kv.tables[sess.slot]),
                            limit, np.float32(sess.temperature),
                            np.uint32(sess.seed), np.bool_(True),
                            np.int32(plan.cow_src),
                            np.int32(plan.cow_dst))
                    else:
                        state, out = self._prefill_fns[bucket](
                            self._params, state, tokens, np.int32(n),
                            np.int32(sess.slot), limit,
                            np.float32(sess.temperature),
                            np.uint32(sess.seed), np.bool_(True))
            except Exception as e:
                # a poisoned prefill poisons the whole donated state:
                # the turn ends in _fail_all (the queue is untouched)
                asp.end("error", error=type(e).__name__)
                raise _Poisoned(e) from e
            if self._resets_state:
                # the prefill just dispatched put its own recurrent state
                # over whatever the slot's last session left
                _telemetry.inc("serving.ssm.state_resets", model=self.name,
                               replica=self.replica)
            self._slot_len[sess.slot] = n
            if plan is not None:
                # index the (now dispatched) prompt prefix for future
                # admissions — insertion AFTER a successful dispatch only
                self._kv.offer(sess.slot, sess.prompt)
        return state, (sess, out, n, resumed)

    def _land_first(self, sess, out, n, resumed):
        """Read, emit and account an admission's first token (TTFT): one
        tiny admission-time host read, made after the turn's step was
        dispatched, so the device has that step queued while the host
        waits for the prefill."""
        try:
            with _tracing.host_read("prefill.first_token"):
                out = np.asarray(out)  # lint: ok[host-sync] admission-time first-token read (TTFT), made with the turn's step already queued behind the prefill; not the per-step read
        except Exception as e:
            raise _Poisoned(e) from e
        now = time.monotonic()
        tok = int(out[0])
        if tok >= 0:
            sess.tokens.append(tok)
            self._emit(sess, tok)
            self._first_token(sess, now)
            _telemetry.inc("serving.decode.tokens.count", model=self.name,
                           replica=self.replica)
        # else a block model's prefill, which yields no token: the first
        # comes with the first block's delivery (_land_step)
        with self._cond:
            sess.admit_step = self.steps
            self.tokens_out += tok >= 0
            self._rate_tokens += tok >= 0
            if resumed:
                self.resumed += 1
                self.reprefilled_tokens += n
        if resumed:
            _telemetry.inc("serving.failover.reprefill_tokens.count", n,
                           model=self.name, replica=self.replica)
            if sess.migrate_t0 is not None:
                # failure-to-resumed: stamped when the session left its
                # failed replica, observed when it is DECODING again —
                # queue wait and re-prefill included
                _telemetry.observe("serving.failover.recovery_seconds",
                                   now - sess.migrate_t0,
                                   model=self.name)
                sess.migrate_t0 = None
        if out[1]:  # EOS or max_new_tokens == 1: done at prefill
            self._retire(sess)
        self._occupancy_gauge()

    def _first_token(self, sess, now):
        """TTFT is first token EVER — a migrated session already paid
        (and recorded) its first-token latency."""
        if sess.t_first is None:
            sess.t_first = now
            _telemetry.observe("serving.decode.ttft_seconds",
                               sess.t_first - sess.t_submit,
                               buckets=TTFT_BUCKETS, model=self.name)

    def _dispatch(self, state):
        """ONE fixed-shape decode dispatch for all slots, queued behind
        whatever the device is still running.  The snapshot of the slots'
        sessions taken here is the one this step's fan-out uses."""
        keep = np.ones((self.slots,), bool)
        with self._cond:
            sessions = list(self._slot_sessions)
        if self._attended_rows is not None:
            # by the loop's own mirror of the lengths: a step behind at most
            rows, steps = self._attended
            self._attended = (rows + self._attended_rows(max(  # lint: ok[lock-discipline] explicit hand-off: one writer (the loop's thread), an atomic swap of an immutable pair of totals; model_counters() takes whichever is latest, whole
                (n for n, sess in zip(self._slot_len, sessions)
                 if sess is not None), default=0)), steps + 1)
        now = time.monotonic()
        for i, sess in enumerate(sessions):
            if sess is None:
                continue
            if sess.cancelled():
                keep[i] = False
            elif sess.deadline is not None and now > sess.deadline:
                keep[i] = False
        if self._kv is not None:
            # block-boundary appends: the step scatters each live
            # slot's K/V at position ``lengths`` — make sure that
            # block exists BEFORE dispatch.  The host's mirror lags the
            # device by the unread step a session rode: reserve for the
            # position after it, unless that step is the session's last
            # by length (one it ends by EOS over-reserves a block, which
            # its retirement releases).  A dry pool (even after
            # prefix-cache eviction) sheds the session typed, and at
            # once, so that its blocks serve the slots behind it; the
            # token it has in flight is not delivered.
            riding = self._unread[-1].sessions if self._unread \
                else (None,) * self.slots
            for i, sess in enumerate(sessions):
                if sess is None or not keep[i]:
                    continue
                pos = self._slot_len[i]
                if riding[i] is sess:
                    pos += 1
                    if pos >= min(sess.prompt.size + sess.max_new_tokens
                                  - 1, self.cfg.max_len):
                        continue
                try:
                    self._kv.append(i, min(pos, self.cfg.max_len - 1))
                except Overloaded as e:
                    keep[i] = False
                    sessions[i] = None
                    _telemetry.inc("serving.shed.count",
                                   model=self.name, reason="kv_blocks")
                    sess.trace.end("shed", reason="kv_blocks",
                                   where="active")
                    self._retire(sess, error=e)
        t0 = time.perf_counter()
        extra_in = self._extra
        try:
            if _faults.should_fire("serving.decode"):
                raise _faults.FaultInjected(
                    "fault 'serving.decode': decode step of model %r "
                    "killed" % self.name)
            if _faults.should_fire("serving.replica.kill"):
                # a hard replica death, not a transient step fault: the
                # engine closes permanently (the worker exits, submits
                # fail fast, rewarm refuses) and every held session
                # goes down the migration path
                with self._cond:
                    self._closed = True
                    self._running = False
                raise ReplicaKilled(
                    "fault 'serving.replica.kill': replica %s of model "
                    "%r hard-killed mid-generation"
                    % (self.replica, self.name))
            with _tracing.start_span("serving.decode.dispatch"):
                if self._kv is not None:
                    # the tables ride along as a tiny int32 H2D argument
                    # — fixed shape, no recompile, not a device read
                    state, packed = self._step_fn(
                        self._params, state, keep,
                        np.ascontiguousarray(self._kv.tables))
                else:
                    state, packed = self._dispatch_step(state, keep)
        except Exception as e:
            raise _Poisoned(e) from e
        self._unread.append(_Step(packed, sessions, keep, t0, extra_in))
        return state

    def _land_step(self, ssp):
        """The single packed host read of the oldest unread step, and the
        host bookkeeping that fans its tokens out to the sessions that
        still hold the slot they held at its dispatch."""
        step = self._unread[0]
        ahead = int(len(self._unread) > 1)
        try:
            with _tracing.host_read("decode.packed"):
                packed = np.asarray(step.packed)  # lint: ok[host-sync] THE one sanctioned host read per decode step (packed token/done/active buffer), made with the next step already dispatched
        except Exception as e:
            raise _Poisoned(e) from e
        self._unread.popleft()
        t_read = time.perf_counter()
        # one token gap as a client sees it: read to read; a step that
        # was dispatched with nothing in flight counts from its dispatch
        dt = t_read - max(step.t0, self._read_t)
        self._read_t = t_read
        with self._cond:
            holders = list(self._slot_sessions)
        emitted, rode, committed = 0, 0, 0
        nblock = self._block
        with _tracing.start_span("serving.decode.fanout") as fsp:
            for i, sess in enumerate(step.sessions):
                # a session that an earlier fan-out retired (it finished
                # at the step before, or at its prefill) rode this step
                # inactive: tok -1, done 0, nothing to deliver
                if sess is None or holders[i] is not sess:
                    continue
                if not step.keep[i]:
                    reason = "abandoned" if sess.cancelled() \
                        else "deadline"
                    _telemetry.inc("serving.shed.count", model=self.name,
                                   reason=reason)
                    err = DeadlineExceeded("session deadline expired "
                                           "mid-generation") \
                        if reason == "deadline" else \
                        MXNetError("session abandoned by the client")
                    sess.trace.end("shed", reason=reason, where="active")
                    self._retire(sess, error=err)
                    continue
                if nblock:
                    # a pass: nothing, or a delivered block's tokens in
                    # order (at most ``nblock``; fewer at a session's two
                    # ends), each with the pass that fixed it
                    rode += 1
                    code = int(packed[nblock, i])
                    base = self.cfg.denoise_steps + 1
                    gave = 0
                    for j in range(nblock):
                        tok = int(packed[j, i])
                        if tok >= 0:
                            gave += 1
                            sess.tokens.append(tok)
                            sess.fixed_at.append(
                                code // base ** j % base - 1)
                            self._emit(sess, tok)
                    if gave:
                        emitted += gave
                        committed += 1
                        self._first_token(sess, time.monotonic())
                    if packed[nblock + 1, i]:
                        self._retire(sess)
                    continue
                tok = int(packed[0, i])
                if tok >= 0:
                    emitted += 1
                    sess.tokens.append(tok)
                    self._emit(sess, tok)
                    # host mirror of the device ``lengths`` advance
                    # (new_len = lengths + active): the write position
                    # of the slot's next step
                    self._slot_len[i] += 1
                if packed[1, i]:
                    self._retire(sess)
            fsp.annotate(emitted=emitted,
                         **({"committed": committed} if nblock else {}))
        ssp.annotate(live=emitted)
        with self._cond:
            self.steps += 1
            self.tokens_out += emitted
            self._rate_tokens += emitted
            self._reads_ahead += ahead
            share = self._reads_ahead / float(self.steps)
            rate_t0, rate_tokens = self._rate_t0, self._rate_tokens
        _telemetry.inc("serving.decode.steps.count", model=self.name,
                       replica=self.replica)
        if emitted:
            _telemetry.inc("serving.decode.tokens.count", emitted,
                           model=self.name, replica=self.replica)
        if nblock:
            _telemetry.inc("serving.decode.passes.count", rode,
                           model=self.name, replica=self.replica)
            _telemetry.inc("serving.decode.commits.count", committed,
                           model=self.name, replica=self.replica)
        _telemetry.observe("serving.decode.token_latency_seconds", dt,
                           buckets=LATENCY_BUCKETS, model=self.name)
        _telemetry.set_gauge("serving.decode.overlap_share", share,
                             model=self.name, replica=self.replica)
        elapsed = time.monotonic() - rate_t0
        if elapsed >= 0.5:
            _telemetry.set_gauge("serving.decode.tokens_per_sec",
                                 rate_tokens / elapsed, model=self.name,
                                 replica=self.replica)
            with self._cond:
                self._rate_t0 = time.monotonic()
                self._rate_tokens = 0
        self._occupancy_gauge()
        if self._on_step_ok is not None:
            self._on_step_ok()
        return ahead, committed

    def _fail_all(self, exc):
        """A failed dispatch or read poisons the donated chain, the
        unread steps queued on it included: they are dropped WHOLE (a
        step is fanned out entirely or not at all, so ``sess.tokens`` is
        exactly what ``on_token`` saw), the model's extra state falls
        back to the last one a read proved good, every held session is
        handed to the pool's migration hook (or, with no pool above,
        gets the error — the batcher's batch-error contract), the state
        restarts from zeros (same shapes — no recompile), and the worker
        survives to serve the queue (unless a :class:`ReplicaKilled`
        closed it)."""
        _telemetry.inc("serving.error.count", model=self.name)
        if self._unread:
            self._extra = self._unread[0].extra_in  # lint: ok[lock-discipline] same hand-off as _dispatch_step: the loop's thread is the one writer, and the array it puts back was never donated
            self._unread.clear()
        with self._cond:
            held = [x for x in self._slot_sessions if x is not None]
            self._slot_sessions = [None] * self.slots
        # health first: the pool quarantines/opens the circuit BEFORE
        # the migration hook picks a target, so a failing replica does
        # not re-admit its own casualties
        if self._on_step_error is not None:
            self._on_step_error(exc)
        if held:
            migrate = self._on_migrate
            if migrate is not None:
                try:
                    migrate(held, exc)
                except Exception:  # noqa: broad-except — a broken
                    # migration hook must not silently drop sessions:
                    # fall back to the typed batch error
                    _log.warning("decode: migration hook failed; "
                                 "shedding held sessions",
                                 exc_info=True)
                    for sess in held:
                        self._finish(sess, error=exc)
            else:
                for sess in held:
                    self._finish(sess, error=exc)
        self._occupancy_gauge()
        return self._fresh_state()

    # -- session completion ------------------------------------------------
    def _emit(self, sess, tok):
        if sess.on_token is None:
            return
        try:
            sess.on_token(tok)
        except Exception:  # noqa: broad-except — a client callback must
            # never kill the engine thread; drop the stream, keep result()
            _log.warning("decode: on_token callback of %r failed; "
                         "disabling the stream", self.name, exc_info=True)
            sess.on_token = None

    def _retire(self, sess, error=None):
        freed_slot = None
        with self._cond:
            if sess.slot is not None \
                    and self._slot_sessions[sess.slot] is sess:
                self._slot_sessions[sess.slot] = None
                freed_slot = sess.slot
            sess.done_step = self.steps
        if freed_slot is not None and self._kv is not None:
            # outside the engine lock (the allocator has its own); the
            # slot cannot be re-admitted concurrently — admissions run
            # on this same engine thread
            self._kv.release(freed_slot)
        self._finish(sess, error=error)

    def _finish(self, sess, error=None):
        # idempotent ACROSS ENGINES: a forced stop() that timed out its
        # joins can race the still-running worker — or, after a
        # migration, a different engine entirely — retiring the same
        # session; the session's own lock makes the pool's on_done hook
        # fire exactly once either way
        sess._resolve(error=error)

    def _occupancy_gauge(self):
        with self._cond:
            active = sum(1 for x in self._slot_sessions if x is not None)
        _telemetry.set_gauge("serving.decode.slot_occupancy",
                             active / float(self.slots), model=self.name,
                             replica=self.replica)
        if self._kv is not None:
            self._kv.note_sessions(active)
