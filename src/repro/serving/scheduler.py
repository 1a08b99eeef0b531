"""Step-driven continuous-batching scheduler — an OPEN serving session
(``submit`` / ``step`` / ``stream`` / ``cancel``) over a fixed slot batch,
with the host/device work PIPELINED (cf. HOBBIT's overlap of expert I/O
with compute, arXiv 2411.01433, and D²MoE's open serving loop that admits
and schedules requests while execution is in flight, arXiv 2504.15299).

**Lifecycle.** The edge serving loop receives traffic while it runs, so
the session is an open machine rather than a batch call:

    handle = session.submit(request)     # validate, FIFO-queue, return
    session.step()                       # advance ONE chunk boundary:
                                         #   1. free slots of cancelled rows
                                         #   2. admission wave(s) into free
                                         #      slots (one ragged row-local
                                         #      prefill per wave)
                                         #   3. dispatch one fused decode
                                         #      chunk; sync only the (B,)
                                         #      done/emitted masks; evict
                                         #      finished rows; submit the
                                         #      chunk's telemetry-replay job
    handle.stream()                      # TokenChunk events, in replay order
    handle.cancel()                      # slot freed at the next boundary;
                                         #   result() becomes partial
    handle.result()                      # final GenerationResult

Requests may be submitted at ANY point between steps — a newly submitted
request is admitted at the next boundary into whatever slot has drained
(mid-run admission). ``run(requests)`` survives as the batch wrapper:
submit everything, loop ``step()`` until idle, ``flush()`` the replay
stream, collect results — ``DyMoEEngine.generate`` / ``generate_batch``
are thin wrappers over exactly that loop.

**Per-request sampling.** Each request carries ``SamplingParams``
(temperature / top-k / seed, validated at submission). The scheduler
threads them as per-row arrays through
:func:`repro.models.model.decode_many_batched`: row r's step draws its
PRNG key as ``fold_in(PRNGKey(seed_r), n_emitted_r)`` — a counter-derived
stream indexed by the request's OWN token position — and samples through
the per-row sampler (bit-identical to ``sample_token`` on the row).
Because row logits are batch-independent (row-local Critical sets) and
the fold count is the per-row counter, sampled tokens are bit-identical
to a solo ``generate`` of the same request and invariant to
``decode_chunk``, slot placement and admission order. Greedy-only
sessions keep the sampling-free device trace (zero overhead) until the
first sampled request arrives (one retrace).

**At every chunk boundary** the session:

  * **evicts** finished rows (their per-row done-mask froze them on device
    mid-chunk: token re-fed, caches pinned, telemetry zeroed — see
    :func:`repro.models.model.decode_many_batched`), finalizing their
    per-request results once their telemetry replay has drained;
  * **admits** waiting requests into freed slots — ALL same-boundary
    admissions share ONE ragged right-aligned prefill whose Critical sets
    are row-local (:func:`repro.models.model.prefill` with
    ``row_local=True``: per-row Eq. 1–2 importance, dual-buffer
    hi/lo expert execution), then land in the slot batch through one
    jitted donated multi-row scatter. One prefill dispatch + one host
    sync per admission WAVE instead of per admission.

**Pipeline timeline** (``pipeline=True``, the default)::

      boundary:     N                N+1              N+2
      device   ─[ chunk N ]──────[ chunk N+1 ]────[ chunk N+2 ]─→
                     │ sync done/emitted (B,) masks only
      main     ──┤ evict/admit/dispatch ├──┤ evict/admit/dispatch ├──→
                     │ submit replay job N (FIFO)
      worker   ────[ fetch + replay N-1 ]──[ fetch + replay N ]────→

  The inter-chunk data dependency stays ON DEVICE: ``toks_d[-1]`` and the
  slot caches feed the next :func:`decode_many_batched` dispatch as
  device arrays, so chunk N+1 launches before chunk N's telemetry has
  even been fetched. Only the two small ``(B,)`` done/emitted masks are
  synced at the boundary — they drive eviction/admission. The expensive
  part — ``device_get`` of the ``(T, L, B, E)`` telemetry leaves plus the
  per-row replay through the ONE shared
  :class:`~repro.core.orchestrator.DynamicExpertOrchestrator` — runs on a
  single background worker (:class:`~repro.serving.engine.ReplayStream`),
  FIFO over chunks, so the shared cache/clock replay order is exactly the
  serial order and the modeled TTFT/TPOT stay bit-identical to
  ``pipeline=False``. A request's :class:`GenerationResult` is finalized
  by the worker when its last replay drains, which is also when its
  :class:`~repro.serving.request.TokenChunk` stream events fire — stream
  delivery order IS replay (modeled-clock) order.

Ragged prompt lengths need no per-request padding on this path: an
admission wave pads only to ITS OWN longest prompt, each row prefills at
its true length into an ``S_slots``-sized cache (per-row offsets recorded
in the KV cache), and decode reads per-row lengths/positions from the
cache itself.

Three properties the design buys:

  * **Per-request math parity** — admission prefill rows and decode rows
    are row-independent programs (own row-local Critical set per
    request), so every slot's tokens — greedy AND sampled — are
    bit-identical to serving that request alone.
  * **Per-request system accounting** — each row's telemetry block is
    replayed through the ONE shared orchestrator (requests share the
    device's expert cache, as they would share VRAM), yielding real
    modeled TTFT at admission and per-token latencies per request.
  * **Replay off the critical path** — the host-side modeled accounting
    costs ~zero wall-clock when the device (or, on CPU, the XLA compute
    threads) keeps a chunk in flight while the worker replays the
    previous one.

Per-request wall accounting: ``queue_wait_s`` is submission→admission,
``wall_s`` is the SERVICE wall (admission→result), so a short request
admitted late no longer reports the whole run's elapsed time.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from functools import partial
from typing import Deque, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.orchestrator import StepTiming
from repro.models.kv_cache import KVCache
from repro.models.layers.moe import _capacity, scales_after_dot
from repro.models.model import init_decode_state
from repro.serving.faults import NO_FAULTS, AdmissionError, \
    DeadlineExceeded, DispatchError, InjectedFault, QueueFull, \
    ReplayError, SessionClosed, SessionHealth
from repro.serving.policy import SchedulingPolicy, SLOPressure, \
    effective_deadline, make_policy
from repro.serving.request import Request, RequestHandle, TokenChunk
from repro.serving.sampler import raw_key_data, resolve_sampling, \
    sample_token_rows
from repro.serving.spans import group_bytes, latent_cache_bytes, \
    live_groups, span

__all__ = ["SchedulerConfig", "ContinuousBatchingScheduler",
           "live_cap_for"]


def live_cap_for(n_live: int, slots: int) -> int:
    """The static-capacity ladder: the ``live_cap`` jit axis for a chunk
    with ``n_live`` live rows out of ``slots`` device slots.

    Power of two ≥ ``n_live``, clamped to ``slots`` — so across every
    reachable live count a session compiles at most ``log2(slots) + 1``
    decode variants per sampling mode. The retrace-budget rule in
    :mod:`repro.analysis` checks THIS function; changing the ladder here
    is what the linter re-verifies.
    """
    return min(slots, 1 << max(0, n_live - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    num_slots: int = 4            # concurrent device slots (decode batch)
    max_chunks: Optional[int] = None  # run() safety valve; None = auto
    pipeline: bool = True         # overlap host replay with device decode
    # replay-queue bound: a slow host replay backpressures the dispatch
    # loop instead of accumulating unbounded telemetry device arrays
    max_inflight_chunks: int = 4
    # per-slot cache length for OPEN sessions (submit/step); None defaults
    # to sliding_window or cfg.max_seq_len. run() sizes it to its workload.
    slots_len: Optional[int] = None
    # admission-queue bound: submits beyond it raise a typed QueueFull
    # (backpressure) instead of growing latency unbounded. None = no bound.
    max_queue: Optional[int] = None
    # SLO scheduling policy: "fifo" (default — blind FIFO admission, the
    # bit-exactness oracle), "edf" (priority + earliest-deadline-first
    # admission, proactive infeasibility shedding, chunk-boundary
    # preemption, pressure degradation ladder), or a SchedulingPolicy
    # instance (repro.serving.policy)
    policy: Union[str, SchedulingPolicy, None] = "fifo"


@dataclasses.dataclass
class _SlotState:
    """Host-side bookkeeping for one admitted request. Mutated by the
    replay stream only (after admission), read by ``_finalize`` there."""

    handle: RequestHandle
    request: Request
    tokens: List[int]
    prompt_len: int
    admit_t: float                # perf_counter at admission
    queue_wait_s: float           # submission -> admission
    finish_now: bool = False      # one-token request: finalize at prefill
    decode_t0: float = 0.0        # decode-wall clock start (post-prefill)
    ttft_s: float = 0.0           # set by the prefill replay job
    prefill_timing: Optional[StepTiming] = None
    prefill_weight_bytes: int = 0
    step_totals: List[float] = dataclasses.field(default_factory=list)
    decode_timings: List[StepTiming] = dataclasses.field(
        default_factory=list)
    decode_weight_bytes: int = 0


class ContinuousBatchingScheduler:
    """Serve a stream of requests through a fixed slot batch.

    Built ON TOP of a :class:`repro.serving.engine.DyMoEEngine`: it reuses
    the engine's jitted prefill, its telemetry replay and its orchestrator
    factory, and drives the engine's jitted
    :func:`~repro.models.model.decode_many_batched`. Every chunk runs the
    full static ``decode_chunk`` length regardless of per-row remaining
    budgets (frozen rows are free in the modeled accounting and keep the
    trace count at one), so admission/eviction never recompiles.

    One instance is one serving SESSION: state (slot batch, shared
    orchestrator, replay stream) is allocated lazily at the first
    ``submit``/``step`` and lives until :meth:`close`. Only one thread may
    drive ``step()``; ``submit``/``cancel`` are legal from other threads
    (the request queue is lock-guarded), and the replay worker is the
    only other writer (it owns ``_SlotState`` after admission and
    finalizes handles).

    **Failure semantics.** The session's contract under faults (see
    :mod:`repro.serving.faults` for the taxonomy and the injector the
    chaos suite drives these ladders with): EVERY submitted handle
    resolves — with a result or a typed :class:`ServingError` — nothing
    hangs, and a fault only ever takes down the requests it actually
    touched; the session keeps serving everyone else.

      * **Replay fault** (a telemetry-replay job raises): replay jobs are
        wrapped so they can never poison the :class:`ReplayStream`; the
        failing job resolves ITS requests with :class:`ReplayError` and
        marks the session. The next :meth:`step` completes recovery on
        the driving thread: every still-in-flight request fails with
        ``ReplayError`` too (the shared orchestrator's modeled clock and
        expert cache died mid-update, so their accounting is lost), the
        slots are freed, a FRESH orchestrator is built, and replay falls
        back to inline serial mode (``pipeline=False``). Queued requests
        are untouched and serve normally afterwards — degraded: no
        replay/compute overlap, and their modeled numbers restart from a
        cold expert cache. ``health().status`` reports ``"degraded"``
        from then on.

      The dispatch and admission ladders below retry
      :class:`~repro.serving.faults.InjectedFault` only. A real compile
      refusal or device error (``XlaRuntimeError``, ``RESOURCE_EXHAUSTED``)
      is not retried, since a smaller retry would only compile another
      program that fails the same way. The requests it touched — the
      admission boundary's popped candidates, or every in-flight row of
      a dispatch — resolve with :class:`AdmissionError` /
      :class:`DispatchError` whose ``__cause__`` is the XLA error, the
      session turns ``degraded``, and the error propagates out of
      :meth:`step`.

      * **Dispatch fault** (the fused decode dispatch or its boundary
        sync raises): retried through a degradation ladder — halve the
        chunk length down to 1 step (bit-identical by the
        chunking-invariance of :func:`decode_many_batched`), then defer
        half the live rows per retry (deferred rows freeze for the chunk
        and re-dispatch next boundary — also bit-identical), and only
        when a 1-step, single-row dispatch still fails does THAT slot
        resolve with :class:`DispatchError`; remaining rows continue.
      * **Admission fault** (a wave's prefill dispatch raises): the wave
        is requeued and retried at half size down to a single candidate,
        which then resolves with :class:`AdmissionError`; later waves and
        in-flight rows are unaffected.
      * **Backpressure / shedding**: a bounded queue (``max_queue``)
        rejects ``submit`` with :class:`QueueFull` (no handle created);
        queued requests whose ``deadline_s``/``ttft_deadline_s`` expire
        are shed with :class:`DeadlineExceeded`; in-flight requests whose
        ``deadline_s`` expires are evicted at the next boundary like a
        cancel (partial result, ``deadline_expired=True``). Under a
        policy with ``sheds_infeasible`` (``policy="edf"``), a queued
        request whose optimistic modeled service bound no longer fits its
        remaining deadline budget is shed proactively with
        ``DeadlineExceeded(infeasible=True)`` — still a typed resolve,
        never a hang.
      * **Preemption** (``policy="edf"``; never under FIFO): when every
        slot is busy and the queued head strictly outranks the weakest
        in-flight row — higher ``Request.priority``, or an earlier
        effective deadline within the same tier — that row is evicted at
        the chunk boundary through the SAME path a cancel takes (slot
        freed, device row frozen, dispatched telemetry still replayed so
        the shared modeled clock stays consistent), except its handle is
        NOT finalized: it is requeued order-preserving and re-prefilled
        from scratch when re-admitted (resume-without-recompute belongs
        to the prefix-cache roadmap item). Its tokens are bit-identical
        across incarnations (per-row math is row-local and PRNG streams
        are token-position-indexed), the handle's stream suppresses
        already-delivered tokens, and the final result reports
        ``preempted`` with queue-wait/TTFT accounting restarted at the
        final admission. Rows that were NOT preempted keep bit-identical
        tokens; at most one preemption fires per boundary. Requests the
        policy never reorders around or preempts behave exactly as under
        FIFO.
      * **Pressure degradation** (``policy="edf"``): an
        :class:`~repro.serving.policy.SLOPressure` signal (queue depth
        per slot, aggregate deadline headroom) walks a hysteresis-guarded
        ladder of host-side
        :class:`~repro.core.orchestrator.DegradeOverride` rungs — shrink
        the replayed Critical set, tighten ``prefetch_topk``, and at the
        last rung skip sub-critical experts outright ("4/0"). The device
        program is untouched: TOKENS ARE BIT-IDENTICAL AT EVERY RUNG and
        no rung adds a jit trace (the retrace ladder stays
        ``live_cap_for``); only the modeled TTFT/TPOT accounting
        degrades, and full quality is restored when pressure clears. Rung
        installs ride the FIFO replay stream, so in the modeled timeline
        a precision shift lands exactly at its chunk boundary. The
        current rung, transitions, shed/preempt counters are all visible
        in :meth:`health`.
      * **Close**: :meth:`close` drains what finished, then resolves
        every still-unresolved handle with :class:`SessionClosed` so no
        ``result(drive=False)``/``stream(drive=False)`` waiter blocks.

    Fault-untouched requests keep bit-identical tokens AND bit-identical
    modeled TTFT/TPOT: every recovery path is built from transformations
    the scheduler is already invariant to (chunk length, slot count,
    admission order), and the injector's no-op fast path keeps the
    fault-free trace byte-for-byte unchanged.
    """

    def __init__(self, engine, num_slots: Optional[int] = None,
                 scfg: SchedulerConfig = SchedulerConfig(),
                 faults=None):
        self.engine = engine
        self.scfg = scfg
        self._num_slots = num_slots  # None: resolved at start
        self._started = False
        self.closed = False
        self._handles: List[RequestHandle] = []
        self._queue: Deque[RequestHandle] = deque()
        # guards _queue/_handles: submit() is legal from other threads
        # while ONE thread drives step()
        self._lock = threading.Lock()
        self._n_chunks = 0
        # trace indices: spans of one boundary / admission wave share them
        self._n_boundaries = 0
        self._n_waves = 0
        # fault-tolerance state — lives on the instance from birth so
        # health() is answerable before the session lazily starts
        self._health = SessionHealth()
        self._degraded = False
        self._replay_broken = False  # set by the worker on a replay fault
        self._replay_epoch = 0       # bumps turn queued jobs into no-ops
        self._last_fault: Optional[BaseException] = None
        self._max_queue = self.scfg.max_queue
        # per-session injector override (a cluster replica gets its own
        # fault state even when replicas share one engine); defaults to
        # the engine-wide injector
        self._faults = faults or getattr(engine, "faults", None) or NO_FAULTS
        # SLO policy layer (FIFO by default: every hook is a no-op and
        # the scheduler's behavior is byte-for-byte the pre-policy path)
        self._policy = make_policy(self.scfg.policy)
        self._pressure_rung = 0
        self._est_cache: dict = {}   # (prompt_len, max_new) -> modeled s

    # ----------------------------------------------------------- helpers
    def _slot_budget(self, requests: Sequence[Request]) -> int:
        cfg = self.engine.cfg
        if cfg.sliding_window:
            return cfg.sliding_window
        return max(len(r.prompt_tokens) + r.max_new_tokens
                   for r in requests)

    def _can_batch_admissions(self) -> bool:
        """Ragged batched admission prefill needs the right-aligned ragged
        machinery: attention archs, no shared-attention hybrid, no ring
        cache. Everything else admits one request per prefill (the exact
        solo program)."""
        cfg = self.engine.cfg
        return (cfg.stack_kind in ("attn_dense", "attn_moe")
                and not cfg.shared_attn_every
                and cfg.sliding_window is None)

    # jitted (row indices traced, batch donated): an admission wave costs
    # ONE fused dispatch — every admitted row's cache pytree is scattered
    # into its slot at once
    @staticmethod
    @partial(jax.jit, donate_argnums=0)
    def _inject_rows(batch_caches, row_caches, src, dst):
        """Overwrite slots ``dst`` of the batched cache pytree with rows
        ``src`` of a freshly prefilled admission-wave cache (their
        per-layer/site leaves agree on every dim except batch).

        A ragged admission wave prefills right-aligned, so row i's KV
        window sits at slot offset ``S_wave - s_i`` — a layout that would
        both waste ``offset`` slots of the fixed slot budget and differ
        from what a solo admission would have injected. Each row is
        therefore LEFT-ALIGNED here (KV window rolled to offset 0, masked
        slots zeroed), making the injected row bitwise identical to a
        solo prefill of the same request — layout included."""
        def left_align(c):
            if not isinstance(c, KVCache):
                return c

            def roll_row(k, v, pos, off):
                p2 = jnp.roll(pos, -off, axis=-1)          # (S,)
                live = p2 >= 0
                k2 = jnp.where(live[None, :, None],
                               jnp.roll(k, -off, axis=-2), 0)
                v2 = jnp.where(live[None, :, None],
                               jnp.roll(v, -off, axis=-2), 0)
                return k2, v2, p2

            k, v, pos = jax.vmap(jax.vmap(roll_row))(
                c.k, c.v, c.positions, c.offset)
            return KVCache(k=k, v=v, positions=pos, length=c.length,
                           offset=jnp.zeros_like(c.offset), ring=c.ring)

        row_caches = jax.tree.map(
            left_align, row_caches,
            is_leaf=lambda x: isinstance(x, KVCache))
        return jax.tree.map(
            lambda full, one: full.at[:, dst].set(one[:, src]),
            batch_caches, row_caches)

    # --------------------------------------------------------- lifecycle
    def _ensure_started(self, *, num_slots: Optional[int] = None,
                        slots_len: Optional[int] = None,
                        pipeline: Optional[bool] = None,
                        max_queue: Optional[int] = None,
                        policy: Union[str, SchedulingPolicy, None] = None
                        ) -> None:
        if self._started:
            return
        from repro.serving.engine import ReplayStream

        engine, cfg = self.engine, self.engine.cfg
        if max_queue is not None:
            self._max_queue = max_queue
        if policy is not None:
            self._policy = make_policy(policy)
        self._pipeline = self.scfg.pipeline if pipeline is None else pipeline
        b = num_slots or self._num_slots or self.scfg.num_slots
        self._b = max(1, b)
        self._slots_len = (slots_len or self.scfg.slots_len
                           or cfg.sliding_window or cfg.max_seq_len)
        self._chunk = engine.ecfg.decode_chunk
        self._can_batch = self._can_batch_admissions()
        self._orch = engine._make_orchestrator()  # ONE shared cache+clock
        # packed bytes of one live (expert, precision) group: the replay's
        # kernel-weight counter, where decode runs the grouped kernel
        self._group_bytes = (group_bytes(engine.qparams)
                             if cfg.is_moe and engine.qparams is not None
                             and cfg.dymoe.enabled else None)
        b = self._b
        self._states: List[Optional[_SlotState]] = [None] * b
        self._caches = engine.shard_decode_state(
            init_decode_state(cfg, b, self._slots_len))
        # the slot batch's latent cache (multi-head latent attention):
        # reported on the session's first admission span
        self._latent_bytes = (latent_cache_bytes(self._caches)
                              if cfg.is_mla else None)
        self._tok_d = jnp.zeros(b, jnp.int32)  # ON DEVICE between chunks
        self._done = np.ones(b, bool)          # empty slots stay frozen
        self._emitted = np.zeros(b, np.int32)
        self._limits = np.zeros(b, np.int32)
        self._eos = np.full(b, -1, np.int32)
        # per-row sampling state (temperature 0 rows are greedy; the keys
        # of greedy rows are never consumed)
        self._temps = np.zeros(b, np.float32)
        self._topks = np.zeros(b, np.int32)
        self._keys = np.zeros((b, 2), np.uint32)
        self._any_sampling = False
        self._t0 = time.perf_counter()
        self._stream = ReplayStream(pipelined=self._pipeline,
                                    maxsize=self.scfg.max_inflight_chunks)
        self._started = True

    def flush(self) -> None:
        """Block until every submitted replay job has run — i.e. every
        request whose device work is complete has been finalized."""
        if self._started:
            self._stream.drain()

    def drain(self, *, cancel_queued: bool = True) -> None:
        """Graceful shutdown: optionally cancel still-queued requests,
        drive :meth:`step` until every in-flight request resolves, then
        :meth:`flush` the replay stream. The session stays open
        (:meth:`close` tears it down) — the serving CLI's Ctrl-C path
        calls this so in-flight requests finish before exit."""
        if not self._started:
            return
        if cancel_queued:
            with self._lock:
                queued = list(self._queue)
            for h in queued:
                h.cancel()
        while self.step():
            pass
        self.flush()

    def close(self, cause: Optional[BaseException] = None) -> None:
        """Tear the session down. Replay jobs already submitted are
        drained first (requests whose device work completed finalize
        normally); EVERY handle still unresolved after that — queued, in
        flight, or lost to a fault — resolves with a typed
        :class:`~repro.serving.faults.SessionClosed` (its ``__cause__``
        is ``cause``, when given), so no ``result(drive=False)`` /
        ``stream(drive=False)`` waiter is ever left blocked."""
        if self._started and not self.closed:
            try:
                self._stream.drain()
            except Exception:       # noqa: BLE001 — teardown never blocks
                pass                # on a (legacy-)poisoned stream
            self._stream.close()
        self.closed = True
        with self._lock:
            self._queue.clear()
            handles = list(self._handles)
        err = SessionClosed(
            "serving session closed before this request resolved"
            + (f" ({cause!r})" if cause is not None else ""))
        err.__cause__ = cause
        for h in handles:
            if not h.done:
                h._finish_error(err)

    def health(self) -> SessionHealth:
        """Snapshot of the session's fault-tolerance state — see
        :class:`~repro.serving.faults.SessionHealth` for field meanings
        and the status ladder (``ok`` / ``degraded`` / ``closed``)."""
        status = ("closed" if self.closed
                  else "degraded" if self._degraded else "ok")
        with self._lock:
            depth = len(self._queue)
        return dataclasses.replace(
            self._health, status=status, queue_depth=depth,
            in_flight=(sum(s is not None for s in self._states)
                       if self._started else 0))

    def __enter__(self) -> "ContinuousBatchingScheduler":
        return self

    def __exit__(self, *exc) -> None:
        if exc[0] is None:
            self.flush()
        self.close()

    # ------------------------------------------------------------ submit
    def submit(self, request: Request, rng_key=None) -> RequestHandle:
        """Queue one request for admission at the next chunk boundary and
        return its :class:`RequestHandle`. Legal at ANY point in the
        session's life — including while ``step()`` is being driven
        (mid-run admission) — and from threads other than the driving
        one: the shared queue is lock-guarded (only ``step()`` itself
        must stay on a single thread).

        Sampling: the request's sampling params (validated at creation)
        decides; the per-request PRNG stream root is ``rng_key`` if given,
        else ``PRNGKey(request.seed)``. ``temperature > 0`` with neither
        falls back to greedy with a warning (the documented
        ``sample_token`` contract — a keyless request can't crash or
        poison the slot batch).

        Backpressure: with a bounded queue (``max_queue``) a submit over
        the bound raises a typed
        :class:`~repro.serving.faults.QueueFull` and creates NO handle —
        retry later (:func:`~repro.serving.faults.submit_with_retry`) or
        shed the request. A closed session raises
        :class:`~repro.serving.faults.SessionClosed`."""
        if self.closed:
            raise SessionClosed("serving session is closed")
        self._ensure_started()
        need = request.prompt_len + request.max_new_tokens
        if self.engine.cfg.sliding_window is None and need > self._slots_len:
            raise ValueError(
                f"request needs {need} cache slots (prompt {request.prompt_len}"
                f" + max_new {request.max_new_tokens}) but the session's "
                f"slot budget is {self._slots_len}; open the session with a "
                f"larger slots_len")
        # ONE lock section end to end: the queue-bound check, the
        # index -> request_id assignment and the queue append must agree
        # under concurrent submitters, and the handle must be visible to
        # admission only once fully set up
        with self._lock:
            if self._max_queue is not None and \
                    len(self._queue) >= self._max_queue:
                self._health.queue_rejections += 1
                raise QueueFull(
                    f"admission queue is full ({self._max_queue} queued); "
                    "retry later (faults.submit_with_retry) or open the "
                    "session with a larger max_queue")
            h = RequestHandle(self, len(self._handles), request,
                              time.perf_counter())
            self._handles.append(h)
            temp, top_k, key = resolve_sampling(request, rng_key,
                                                context=h.request_id)
            h.temperature, h.top_k = float(temp), int(top_k)
            h.key = raw_key_data(key) if key is not None else None
            if h.temperature > 0.0:
                self._any_sampling = True
            self._queue.append(h)
            self._health.submitted += 1
        return h

    def _note_completed(self) -> None:
        """Handle-finalizer callback (see ``RequestHandle._finish*``):
        bumps the monotonic ``completed`` counter exactly once per
        resolved handle, result and typed-error paths alike."""
        with self._lock:
            self._health.completed += 1

    # -------------------------------------------------------------- step
    def step(self) -> bool:
        """Advance the session by ONE chunk boundary: release cancelled
        rows, admit queued requests into free slots, and (if any row is
        live) dispatch one fused decode chunk + its replay job. Returns
        True while the session is making progress; False when idle (no
        queued, live, or cancelled work) — replay jobs may still be in
        flight, :meth:`flush` waits for them.

        Fault-tolerance work rides the same boundary, in order: finish
        recovering from a replay fault (fail+free affected slots, swap to
        inline replay), shed queued requests whose deadlines expired (and,
        under an SLO policy, queued requests whose modeled service bound
        proves them infeasible), then the sweep also evicts in-flight rows
        past ``deadline_s``. The SLO policy layer rides it too: the
        pressure ladder re-evaluates its rung, and at most one
        chunk-boundary preemption fires before admission."""
        if self.closed:
            raise SessionClosed("serving session is closed")
        if not self._started:
            return False
        boundary = self._n_boundaries
        self._n_boundaries += 1
        with span("step", boundary=boundary):
            progress = self._recover_replay()
            progress |= self._shed_expired()
            progress |= self._sweep_cancelled()
            self._update_pressure()
            progress |= self._preempt_boundary()
            progress |= self._admit_boundary()
            if self._done.all():
                return progress
            self._dispatch_chunk()
            return True

    def _shed_expired(self) -> bool:
        """Shed queued requests whose wall-clock deadline
        (``deadline_s`` or ``ttft_deadline_s``, measured from submission)
        has already expired: they could not possibly meet it, so they
        resolve with a typed :class:`DeadlineExceeded` instead of wasting
        an admission wave's prefill on them.

        Under a policy with ``sheds_infeasible`` (e.g. ``"edf"``), the
        same pass also sheds PROACTIVELY: a queued request whose
        optimistic modeled service bound
        (:func:`repro.serving.policy.estimate_service_s`, cached per
        request shape) no longer fits its remaining deadline budget is
        provably hopeless and resolves with
        ``DeadlineExceeded(infeasible=True)`` now, instead of burning a
        slot until wall-clock expiry."""
        pol = self._policy
        now = time.perf_counter()
        shed: List[RequestHandle] = []
        infeasible: List[RequestHandle] = []
        with self._lock:
            if not self._queue:
                return False
            keep: Deque[RequestHandle] = deque()
            for h in self._queue:
                r = h.request
                waited = now - h.submit_t
                if (r.deadline_s is not None and waited > r.deadline_s) \
                        or (r.ttft_deadline_s is not None
                            and waited > r.ttft_deadline_s):
                    shed.append(h)
                elif pol.sheds_infeasible and pol.infeasible(
                        h, now, self._service_estimate(r)):
                    infeasible.append(h)
                else:
                    keep.append(h)
            if not shed and not infeasible:
                return False
            self._queue = keep
            self._health.deadline_shed += len(shed)
            self._health.infeasible_shed += len(infeasible)
        for h in shed:
            req = h.request
            h._finish_error(DeadlineExceeded(
                f"{h.request_id}: shed after {now - h.submit_t:.3f}s in "
                f"queue (deadline_s={req.deadline_s}, "
                f"ttft_deadline_s={req.ttft_deadline_s})"))
        for h in infeasible:
            req = h.request
            h._finish_error(DeadlineExceeded(
                f"{h.request_id}: provably infeasible — modeled service "
                f"bound {self._service_estimate(req):.4f}s exceeds the "
                f"remaining deadline budget after {now - h.submit_t:.3f}s "
                f"queued (deadline_s={req.deadline_s}, "
                f"ttft_deadline_s={req.ttft_deadline_s})",
                infeasible=True))
        return True

    def _service_estimate(self, request: Request) -> float:
        """Optimistic modeled service bound for one request (policy
        feasibility input), cached per (prompt_len, max_new_tokens)."""
        fn = getattr(self._policy, "service_estimate_fn", None)
        if fn is not None:
            return float(fn(request))
        key = (request.prompt_len, request.max_new_tokens)
        est = self._est_cache.get(key)
        if est is None:
            from repro.serving.policy import estimate_service_s
            est = estimate_service_s(self.engine.cost, self.engine.cfg,
                                     request)
            self._est_cache[key] = est
        return est

    def _update_pressure(self) -> None:
        """Re-evaluate the SLO pressure ladder (policies without a ladder
        — FIFO included — keep this a no-op). A rung change installs the
        rung's host-side :class:`~repro.core.orchestrator.DegradeOverride`
        on the shared orchestrator THROUGH the replay stream, so in the
        modeled timeline the precision shift lands exactly at this
        boundary — never mid-chunk, never racing the worker."""
        pol = self._policy
        if pol.ladder is None or self._orch is None:
            return
        now = time.perf_counter()
        with self._lock:
            queued = list(self._queue)
        states = [st for st in self._states if st is not None]
        headrooms = [
            h.submit_t + b - now
            for h in queued
            if (b := effective_deadline(h.request)) != float("inf")
        ] + [
            st.handle.submit_t + b - now
            for st in states
            if (b := effective_deadline(st.request)) != float("inf")
        ]
        pressure = SLOPressure(
            queue_depth=len(queued), in_flight=len(states), slots=self._b,
            min_headroom_s=min(headrooms) if headrooms else None,
            mean_headroom_s=(sum(headrooms) / len(headrooms)
                             if headrooms else None))
        rung = pol.rung_for(pressure, self._pressure_rung)
        if rung == self._pressure_rung:
            return
        try:
            self._faults.fire("degrade.shift",
                              from_rung=self._pressure_rung, to_rung=rung)
        except InjectedFault as e:
            # chaos: a faulted shift is SKIPPED — the session simply stays
            # at its current rung; nothing fails, nobody's handle resolves
            self._health.last_fault = repr(e)
            self._last_fault = e
            return
        self._pressure_rung = rung
        self._health.pressure_rung = rung
        self._health.rung_transitions += 1
        override = pol.ladder.override_for(rung)
        # epoch-guarded like every replay job: after a replay fault the
        # stale install is skipped and _recover_replay re-installs the
        # current rung on the fresh orchestrator directly
        self._submit_replay(
            partial(self._orch.set_degrade, override), [])

    def _preempt_boundary(self) -> bool:
        """At most ONE chunk-boundary preemption per step, under a
        preemptive policy with every slot busy: the weakest in-flight row
        (policy-chosen victim) is evicted through the existing eviction
        path — slot freed, device row frozen, its already-dispatched
        telemetry still replayed so the modeled timeline stays consistent
        — and its handle is requeued order-preserving (re-prefilled from
        scratch on resume; tokens are bit-identical by construction, and
        the handle's stream suppresses re-delivered tokens). The freed
        slot is taken by the urgent request at THIS boundary's admission
        wave."""
        pol = self._policy
        if not pol.preemptive:
            return False
        in_flight = [(r, st) for r, st in enumerate(self._states)
                     if st is not None]
        free = any(self._done[r] and self._states[r] is None
                   for r in range(self._b))
        with self._lock:
            queued = list(self._queue)
        if free or not queued or not in_flight:
            return False
        decision = pol.preempt(queued, in_flight, time.perf_counter())
        if decision is None:
            return False
        head, (r, st) = decision
        try:
            self._faults.fire("preempt.evict", slot=r,
                              victim=st.handle.request_id,
                              urgent=head.request_id)
        except InjectedFault as e:
            # chaos: a faulted preemption is ABORTED — the victim keeps
            # its slot, the urgent request stays queued; nothing fails
            self._health.last_fault = repr(e)
            self._last_fault = e
            return False
        # the existing eviction path (same as cancel/deadline eviction),
        # minus the finalize: the handle goes back to the queue instead
        self._states[r] = None
        self._done[r] = True     # device row freezes from now on
        st.handle._preempted += 1
        self._health.preemptions += 1
        with self._lock:
            # order-preserving requeue: queue front, so under FIFO-ish
            # ties the victim re-admits before anything submitted later;
            # the policy's admission order decides who takes the slot
            self._queue.appendleft(st.handle)
        return True

    def _sweep_cancelled(self) -> bool:
        """Free the slots (and queue positions) of cancelled requests —
        and of in-flight requests whose ``deadline_s`` expired — and
        finalize their partial results through the replay stream, AFTER
        any already-dispatched chunks' tokens have drained into them."""
        progress = False
        dropped: List[RequestHandle] = []
        with self._lock:
            if any(h.cancel_requested for h in self._queue):
                keep: Deque[RequestHandle] = deque()
                for h in self._queue:
                    if h.cancel_requested:
                        dropped.append(h)
                    else:
                        keep.append(h)
                self._queue = keep
        for h in dropped:   # finalize outside the lock (may run inline)
            self._submit_replay(partial(self._finalize_unadmitted, h), [h])
            progress = True
        now = time.perf_counter()
        for r in range(self._b):
            st = self._states[r]
            if st is None:
                continue
            dl = st.request.deadline_s
            expired = dl is not None and now - st.handle.submit_t > dl
            if st.handle.cancel_requested or expired:
                self._states[r] = None   # freed for the admission below
                self._done[r] = True     # device row freezes from now on
                if expired and not st.handle.cancel_requested:
                    self._health.deadline_evictions += 1
                self._submit_replay(
                    partial(self._finalize, st, cancelled=True,
                            deadline_expired=expired), [st.handle])
                progress = True
        return progress

    # --------------------------------------------------------- admission
    def _admit_boundary(self) -> bool:
        """Fill every free slot from the FIFO queue.

        Waves: up to ``len(free)`` queued requests prefill together
        (one ragged row-local dispatch + ONE host sync for their first
        tokens); requests that finish at their first token free their
        claim immediately, so further waves run until the slots are
        full or the queue drains — the same pop sequence the
        one-at-a-time admission loop would make. Survivors are
        scattered into their slots with one donated injection per
        wave."""
        free = [r for r in range(self._b)
                if self._done[r] and self._states[r] is None]
        if not free or not self._queue:
            return False
        if self._policy.reorders:
            # policy admission order, re-evaluated once per boundary (a
            # stable sort: no-priority/no-deadline queues keep their FIFO
            # order bit-for-bit). The FIFO policy never touches the queue.
            now0 = time.perf_counter()
            with self._lock:
                if len(self._queue) > 1:
                    self._queue = deque(
                        self._policy.order(list(self._queue), now0))
        n_survivors = 0
        cap: Optional[int] = None   # ladder: bound on a retried wave size
        landed: List[tuple] = []    # (state, slot) of earlier waves' rows
        while n_survivors < len(free) and self._queue:
            room = len(free) - n_survivors
            if cap is not None:
                room = min(room, cap)
            cands: List[RequestHandle] = []
            with self._lock:
                while self._queue and len(cands) < room:
                    cands.append(self._queue.popleft())
                if not self._can_batch:
                    cands, rest = cands[:1], cands[1:]
                    for h in reversed(rest):
                        self._queue.appendleft(h)
            now = time.perf_counter()
            lens = [h.request.prompt_len for h in cands]
            n = len(cands)
            wave = self._n_waves
            self._n_waves += 1
            first_wave = ({} if wave or self._latent_bytes is None
                          else {"latent_cache_bytes": self._latent_bytes})
            with span("admit", wave=wave, rows=n, longest_prompt=max(lens),
                      queue_wait_ms_max=1e3 * max(now - h.submit_t
                                                  for h in cands),
                      scaled_after_dot=self._scaled_after_dot(
                          n * _capacity(self.engine.cfg, max(lens))
                          if n > 1 else 0), **first_wave):
                try:
                    rcaches, first, tele = self._prefill_wave(cands, lens,
                                                              wave)
                except InjectedFault as e:
                    # --- admission degradation ladder: requeue the wave
                    # and retry at half size; a single candidate that
                    # still fails resolves with a typed AdmissionError.
                    # Splitting a wave is bit-identical for its survivors
                    # (per-candidate replay order and row-local prefill
                    # rows are unchanged)
                    self._last_fault = e
                    self._health.last_fault = repr(e)
                    if n > 1:
                        with self._lock:
                            for h in reversed(cands):
                                self._queue.appendleft(h)
                        self._health.admission_retries += 1
                        cap = max(1, n // 2)
                        continue
                    self._health.admission_failures += 1
                    err = AdmissionError(
                        f"{cands[0].request_id}: admission prefill failed "
                        f"even as a solo wave ({e!r})")
                    err.__cause__ = e
                    cands[0]._finish_error(err)
                    continue
                except Exception as e:
                    # a real compile/device error: fail this wave and the
                    # earlier waves' survivors, whose slots are freed again
                    for st, r in landed:
                        self._states[r] = None
                        self._done[r] = True
                    popped = cands + [st.handle for st, _ in landed]
                    self._health.admission_failures += len(popped)
                    self._fail_unretried(e, popped, AdmissionError,
                                         "admission prefill")
                    raise
                cap = None   # a clean wave resets the ladder
                wave_states: List[_SlotState] = []
                wave_src: List[int] = []
                wave_tok: List[int] = []
                wave_surv: List[_SlotState] = []
                for i, h in enumerate(cands):
                    req = h.request
                    ft = int(first[i])
                    st = _SlotState(
                        handle=h, request=req, tokens=[ft],
                        prompt_len=lens[i], admit_t=now,
                        queue_wait_s=now - h.submit_t,
                        finish_now=(req.max_new_tokens <= 1
                                    or (req.eos_token is not None
                                        and ft == req.eos_token)))
                    st.decode_t0 = time.perf_counter()
                    wave_states.append(st)
                    if not st.finish_now:
                        wave_src.append(i)
                        wave_tok.append(ft)
                        wave_surv.append(st)
                self._submit_replay(partial(
                    self._replay_prefill, wave, wave_states, tele, n > 1),
                    [st.handle for st in wave_states])
                # decode-wall clock: starts AFTER the prefill replay
                # (inline in serial mode), mirroring solo generate's t_dec
                # — so measured decode throughput excludes prefill + its
                # replay
                t_dec = time.perf_counter()
                for st in wave_surv:
                    st.decode_t0 = t_dec
                if wave_src:
                    # survivors claim free slots in pop order (== the
                    # order the one-at-a-time admission loop would have
                    # filled them)
                    dst = free[n_survivors:n_survivors + len(wave_src)]
                    self._land_wave(rcaches, wave_src, wave_tok, wave_surv,
                                    dst)
                    landed.extend(zip(wave_surv, dst))
                    n_survivors += len(wave_src)
        return True

    def _scaled_after_dot(self, capacity: int) -> int:
        """1 where the grouped expert kernel, at ``capacity`` rows per
        precision region, applies its group scales after the dot; 0 where
        it dequantizes the weights, or no grouped kernel runs
        (``capacity`` 0: a one-row wave runs the solo program)."""
        cfg = self.engine.cfg
        return int(capacity > 0 and self._group_bytes is not None
                   and scales_after_dot(cfg, capacity))

    def _prefill_wave(self, cands: List[RequestHandle], lens: List[int],
                      wave: int):
        """Dispatch one admission wave's prefill — one ragged row-local
        program for several candidates, the exact-shape solo program for
        one — and fetch every candidate's first token: the wave's ONE
        host sync. Returns (row caches, first tokens, the replay's
        (critical, active, predicted) telemetry leaves)."""
        engine, cfg = self.engine, self.engine.cfg
        n = len(cands)
        self._faults.fire("admit.alloc", n=n)
        if n > 1:
            smax = max(lens)
            prompts = np.zeros((n, smax), np.int32)
            for i, h in enumerate(cands):
                prompts[i, smax - lens[i]:] = h.request.prompt_tokens
            logits, rcaches, info = engine._prefill(
                engine.params, tokens=jnp.asarray(prompts),
                qparams=engine.qparams,
                cache_slots=self._slots_len,
                lengths=jnp.asarray(lens, jnp.int32),
                row_local=True,
                # exact host-side solo capacities: the in-graph f32
                # formula can truncate one slot differently
                row_capacities=jnp.asarray(
                    [_capacity(cfg, s) for s in lens], jnp.int32)
                if cfg.is_moe else None)
        else:  # exact-shape solo program (also the SSM/hybrid path)
            prompt = jnp.asarray(
                cands[0].request.prompt_tokens, jnp.int32)[None, :]
            logits, rcaches, info = engine._prefill(
                engine.params, tokens=prompt,
                qparams=engine.qparams,
                cache_slots=self._slots_len)
        # Sampled candidates draw through the per-row sampler with fold
        # count 0 — bit-identical to solo ``sample_token`` over the (1, V)
        # row (greedy rows take the same argmax)
        if any(h.temperature > 0.0 for h in cands):
            keys = np.zeros((n, 2), np.uint32)
            for i, h in enumerate(cands):
                if h.key is not None:
                    keys[i] = h.key
            keys0 = jax.vmap(lambda k: jax.random.fold_in(k, 0))(
                jnp.asarray(keys))
            first_d = sample_token_rows(
                logits, keys0,
                jnp.asarray([h.temperature for h in cands], jnp.float32),
                jnp.asarray([h.top_k for h in cands], jnp.int32))
        else:
            first_d = jnp.argmax(logits, axis=-1)
        with span("sync", wave=wave):
            first = np.asarray(jax.device_get(first_d), np.int32)
        return rcaches, first, (info.critical_masks, info.active_masks,
                                info.predicted_next)

    def _land_wave(self, rcaches, src: List[int], toks: List[int],
                   states: List[_SlotState], dst: List[int]) -> None:
        """Put an admission wave's surviving rows ``src`` into the free
        slots ``dst``: their host bookkeeping, and one donated injection
        of their caches plus their first tokens into the slot batch."""
        for st, r in zip(states, dst):
            h = st.handle
            self._states[r] = st
            self._done[r] = False
            self._emitted[r] = 1
            self._limits[r] = st.request.max_new_tokens
            self._eos[r] = (-1 if st.request.eos_token is None
                            else st.request.eos_token)
            self._temps[r] = h.temperature
            self._topks[r] = h.top_k
            self._keys[r] = h.key if h.key is not None else 0
        self._caches = self._inject_rows(
            self._caches, rcaches, jnp.asarray(src, jnp.int32),
            jnp.asarray(dst, jnp.int32))
        self._tok_d = self._tok_d.at[jnp.asarray(dst, jnp.int32)].set(
            jnp.asarray(toks, jnp.int32))

    # ---------------------------------------------------------- dispatch
    def _dispatch_chunk(self) -> None:
        """Dispatch one fused decode chunk — with a degradation ladder.

        A failed dispatch (or boundary sync — async device errors surface
        there) is retried with a halved chunk length, down to one step;
        then with half the live rows deferred per retry (they freeze for
        this chunk and re-dispatch next boundary); a 1-step single-row
        dispatch that still fails resolves that slot with a typed
        :class:`DispatchError` and the rest continue. Every rung is a
        transformation the scheduler's outputs are invariant to (chunk
        length, row placement), so surviving rows stay bit-identical.
        ``_decode_batched`` donates nothing, so re-dispatching the same
        inputs is safe."""
        engine = self.engine
        emitted_before = self._emitted.copy()
        sample_kw = {}
        if self._any_sampling:
            # traced per-row arrays: mixed temperatures / top-k / keys
            # never retrace; greedy-only sessions keep the leaner trace
            sample_kw = dict(rng_keys=jnp.asarray(self._keys),
                             temperatures=jnp.asarray(self._temps),
                             top_ks=jnp.asarray(self._topks))
        chunk = self._chunk          # transient: self._chunk is untouched
        deferred = np.zeros(self._b, bool)
        while True:
            live = [r for r in range(self._b)
                    if not self._done[r] and not deferred[r]]
            if not live:
                return   # everything deferred/failed; retry next step
            # Fused-MoE capacity cap: size each precision region to the
            # chunk's live-slot count, rounded up to a power of two so at
            # most log2(B) traces ever exist. Finished slots already cost
            # zero FLOPs via the ragged grid; this shrinks the scatter
            # buffers too when the batch is mostly drained.
            live_cap = live_cap_for(len(live), self._b)
            try:
                self._faults.fire("device.dispatch", chunk=self._n_chunks,
                                  num_steps=chunk, rows=len(live))
                after = self._scaled_after_dot(live_cap)
                ctx = (sum(self._states[r].prompt_len + int(self._emitted[r])
                           for r in live)
                       if jax.profiler.TraceAnnotation.is_enabled() else 0)
                with span("dispatch", chunk=self._n_chunks, rows=len(live),
                          live_cap=live_cap, steps=chunk,
                          scaled_after_dot=after, ctx_tokens=ctx):
                    toks_d, caches, infos, done_d, emitted_d = \
                        engine._decode_batched(
                            engine.params, tokens=self._tok_d,
                            caches=self._caches, num_steps=chunk,
                            done=jnp.asarray(self._done | deferred),
                            n_emitted=jnp.asarray(self._emitted),
                            limits=jnp.asarray(self._limits),
                            eos_tokens=jnp.asarray(self._eos),
                            qparams=engine.qparams, live_cap=live_cap,
                            **sample_kw)
                # the boundary sync: ONLY the small (B,) masks cross —
                # the (T, L, B, E) telemetry stays behind for the worker
                with span("sync", chunk=self._n_chunks):
                    done_h, emitted_h = jax.device_get((done_d, emitted_d))
                break
            except InjectedFault as e:
                self._health.dispatch_retries += 1
                self._health.last_fault = repr(e)
                self._last_fault = e
                if chunk > 1:
                    chunk //= 2          # bit-identical: chunk invariance
                    continue
                if len(live) > 1:        # bit-identical: slot invariance
                    for r in live[len(live) // 2:]:
                        deferred[r] = True
                    continue
                # 1-step, single-row dispatch still failing: fail THAT
                # slot with a typed error; everyone else keeps serving
                r = live[0]
                st = self._states[r]
                self._states[r] = None
                self._done[r] = True
                self._health.dispatch_failures += 1
                err = DispatchError(
                    f"{st.handle.request_id}: device decode dispatch kept "
                    f"failing down to a 1-step solo chunk ({e!r})")
                err.__cause__ = e
                st.handle._finish_error(err)
                continue
            except Exception as e:
                # a real compile/device error: fail every in-flight row
                failed = []
                for r in range(self._b):
                    if self._states[r] is not None:
                        failed.append(self._states[r].handle)
                        self._states[r] = None
                        self._done[r] = True
                self._health.dispatch_failures += len(failed)
                self._fail_unretried(e, failed, DispatchError,
                                     "decode dispatch")
                raise
        self._caches = caches
        self._tok_d = toks_d[-1]  # next chunk's data dep: ON DEVICE
        new_done = np.array(done_h)  # device_get views are read-only
        new_emitted = np.array(emitted_h)
        if deferred.any():
            # deferred rows were frozen for THIS dispatch only (we passed
            # done=True for them): restore their host masks so they
            # dispatch again at the next boundary
            new_done[deferred] = self._done[deferred]
            new_emitted[deferred] = self._emitted[deferred]
        self._done = new_done
        self._emitted = new_emitted
        rows = []
        for r in range(self._b):
            st = self._states[r]
            if st is None or deferred[r]:
                continue
            rows.append((r, st,
                         int(self._emitted[r] - emitted_before[r]),
                         st.prompt_len + int(emitted_before[r]),
                         bool(self._done[r])))
            if self._done[r]:
                self._states[r] = None  # evict: free to admit; the
                #                         worker finalizes st later
        self._submit_replay(partial(
            self._replay_chunk, self._n_chunks, toks_d,
            (infos.critical_masks, infos.active_masks,
             infos.predicted_next), rows),
            [st.handle for _, st, _, _, _ in rows])
        self._n_chunks += 1

    def _fail_unretried(self, exc: BaseException, handles, err_cls,
                        what: str) -> None:
        """Resolve ``handles`` with ``err_cls`` caused by a real
        (non-injected) compile or device error and mark the session
        degraded. The caller re-raises ``exc`` out of :meth:`step`."""
        self._degraded = True
        self._last_fault = exc
        self._health.last_fault = repr(exc)
        for h in handles:
            err = err_cls(f"{h.request_id}: {what} raised {exc!r}")
            err.__cause__ = exc
            h._finish_error(err)

    # ------------------------------------------- replay fault tolerance
    def _submit_replay(self, fn, handles) -> None:
        """Submit a replay job WRAPPED so it can never poison the
        :class:`ReplayStream`: if ``fn`` raises, the session is marked
        degraded and the job's OWN handles (the ones ``fn`` would have
        finalized) resolve with a typed :class:`ReplayError` instead of
        the exception propagating into the stream."""
        self._stream.submit(partial(self._run_replay, self._replay_epoch,
                                    fn, handles))

    def _run_replay(self, epoch, fn, handles) -> None:
        # replay-stream context (the worker thread when pipelined)
        if self._replay_broken or epoch != self._replay_epoch:
            # a job from before a replay fault: its telemetry would
            # replay against a clock/cache that died mid-update —
            # skip-fail its requests instead of running it
            err = self._replay_error()
            for h in handles:
                h._finish_error(err)
            return
        try:
            fn()
        except Exception as exc:   # noqa: BLE001 — translated to typed
            self._on_replay_failure(exc, handles)

    def _replay_error(self) -> ReplayError:
        return ReplayError(
            "telemetry replay failed while this request was in flight; "
            "its device tokens may exist but its modeled accounting is "
            f"lost (cause: {self._last_fault!r})")

    def _on_replay_failure(self, exc: BaseException, handles) -> None:
        # worker half of replay-fault handling; _recover_replay() (the
        # driving thread, next step()) completes the fallback
        with self._lock:
            self._last_fault = exc
            self._replay_broken = True
            self._replay_epoch += 1   # queued jobs become stale no-ops
            self._degraded = True
            self._health.replay_faults += 1
            self._health.last_fault = repr(exc)
        err = self._replay_error()
        err.__cause__ = exc
        for h in handles:
            h._finish_error(err)

    def _recover_replay(self) -> bool:
        """Driving-thread half of replay-fault recovery, run at the top
        of :meth:`step`: the shared orchestrator's modeled clock/cache
        died mid-replay, so every in-flight request's accounting is lost
        — fail them with :class:`ReplayError`, free their slots, rebuild
        a FRESH orchestrator, and fall back to inline serial replay
        (``pipeline=False``). Queued requests are untouched: they serve
        normally afterwards, just degraded (no overlap, cold modeled
        cache). The session stays usable; ``health()`` reports
        ``degraded``."""
        if not self._replay_broken:
            return False
        from repro.serving.engine import ReplayStream

        err = self._replay_error()
        progress = False
        for r in range(self._b):
            st = self._states[r]
            if st is not None:
                st.handle._finish_error(err)   # idempotent — the worker
                #                                may have failed it first
                self._states[r] = None
                self._done[r] = True
                progress = True
        self._orch = self.engine._make_orchestrator()  # fresh clock+cache
        if self._orch is not None and self._policy.ladder is not None:
            # any queued set_degrade install died with the old stream
            # (stale epoch): put the fresh orchestrator on the CURRENT
            # rung directly — no concurrency, the old worker is draining
            # stale no-ops and the new stream is inline on this thread
            self._orch.set_degrade(
                self._policy.ladder.override_for(self._pressure_rung))
        old = self._stream
        with self._lock:
            # bump AGAIN: anything submitted between the fault and now is
            # stale, so the OLD worker drains it without ever touching
            # the fresh orchestrator concurrently with this thread
            self._replay_epoch += 1
            self._replay_broken = False
        self._stream = ReplayStream(pipelined=False)  # inline from now on
        old.close()   # fast: stale jobs skip-fail, then the worker exits
        return progress

    # ------------------------------------------------ replay-worker side
    def _emit(self, st: _SlotState, phase: str, tokens: List[int],
              modeled_s: float, tok_start: int) -> None:
        """Push one TokenChunk stream event, suppressing tokens a
        pre-preemption incarnation of this handle already delivered
        (``tok_start`` is the index of ``tokens[0]`` in the request's
        full output; tokens are bit-identical across incarnations, so
        skipping the overlap keeps the stream's concatenation exactly
        equal to ``result().tokens``). Replay-worker context only — the
        single writer of ``handle._streamed``."""
        h = st.handle
        end = tok_start + len(tokens)
        skip = max(0, h._streamed - tok_start)
        new = tokens[skip:]
        if not new:
            return   # fully re-delivered already (resumed prefix replay)
        h._push_event(TokenChunk(request_id=h.request_id, phase=phase,
                                 tokens=new, modeled_s=modeled_s))
        h._streamed = max(h._streamed, end)

    def _finalize(self, st: _SlotState, *, cancelled: bool = False,
                  deadline_expired: bool = False) -> None:
        # replay-stream context: st's telemetry has fully drained.
        # ``cancelled`` comes from the PATH that finalized (the cancel
        # sweep), not from the handle's flag — a cancel() that races a
        # natural completion must not mislabel a complete result partial
        from repro.serving.engine import GenerationResult

        orch = self._orch
        now = time.perf_counter()
        n_dec = max(len(st.tokens) - 1, 1)
        st.handle._finish(GenerationResult(
            tokens=st.tokens,
            ttft_s=float(st.ttft_s),
            tpot_s=float(sum(st.step_totals) / n_dec),
            wall_s=now - st.admit_t,
            queue_wait_s=st.queue_wait_s,
            decode_wall_s=now - st.decode_t0,
            prefill_timing=st.prefill_timing,
            decode_timings=st.decode_timings or None,
            cache_stats=(dataclasses.asdict(orch.cache.stats)
                         if orch else None),
            prefill_weight_bytes=(st.prefill_weight_bytes
                                  if orch else None),
            decode_weight_bytes_per_tok=(
                st.decode_weight_bytes / n_dec
                if st.decode_timings else None),
            cancelled=cancelled, deadline_expired=deadline_expired,
            preempted=st.handle._preempted))

    def _finalize_unadmitted(self, h: RequestHandle) -> None:
        """A request cancelled while still queued: nothing ran for it."""
        from repro.serving.engine import GenerationResult

        h._finish(GenerationResult(
            tokens=[], ttft_s=float("nan"), tpot_s=float("nan"),
            wall_s=0.0, queue_wait_s=time.perf_counter() - h.submit_t,
            cancelled=True))

    def _replay_prefill(self, index: int, wave: List[_SlotState], tele,
                        per_row: bool) -> None:
        """Replay admission wave ``index``'s prefill telemetry, candidate
        by candidate in pop order (the serial admission order), emit each
        candidate's prefill TokenChunk, and finalize the one-token
        requests."""
        with span("replay", kind="prefill", wave=index, rows=len(wave)):
            engine = self.engine
            self._faults.fire("replay.prefill", n=len(wave))
            crit, act, pred = jax.device_get(tele)
            for i, st in enumerate(wave):
                if crit is None:
                    c = a = p = None
                elif per_row:   # (L, B, E) row-local leaves -> this row
                    c, a, p = crit[:, i], act[:, i], pred[:, i]
                else:           # solo admission: (L, E) leaves, B == 1
                    c, a, p = crit, act, pred
                timings, totals, wbytes = engine._replay(
                    c, a, p, phase="prefill",
                    s_ctx=np.asarray([st.prompt_len]), s_q=st.prompt_len,
                    orch=self._orch)
                st.ttft_s = (timings[0].total_s if timings else totals[0])
                st.prefill_timing = timings[0] if timings else None
                st.prefill_weight_bytes = wbytes
                self._emit(st, "prefill", [st.tokens[0]], float(st.ttft_s), 0)
                if st.finish_now:
                    self._finalize(st)

    def _replay_chunk(self, index: int, toks_ref, tele, rows) -> None:
        """Fetch + replay decode chunk ``index``'s telemetry: the job the
        pipeline overlaps with the NEXT chunk's device dispatch. While the
        profiler records, its span also counts the (expert, precision)
        groups the chunk's grouped expert kernel calls found live, and the
        packed bytes they hold."""
        with span("replay", kind="chunk", chunk=index,
                  rows=len(rows)) as sp:
            engine = self.engine
            self._faults.fire("replay.chunk", rows=len(rows))
            toks_np, crit, act, pred = jax.device_get((toks_ref,) + tele)
            toks_np = np.asarray(toks_np)
            for r, st, keep, ctx0, is_done in rows:
                if keep:   # this row's live steps are the chunk's first
                    new = [int(t) for t in toks_np[:keep, r]]
                    st.tokens.extend(new)
                    # telemetry leaves are (T, L, B, E): this row's block
                    timings, totals, wbytes = engine._replay(
                        None if crit is None else crit[:keep, :, r],
                        None if act is None else act[:keep, :, r],
                        None if pred is None else pred[:keep, :, r],
                        phase="decode",
                        s_ctx=ctx0 + np.arange(keep), s_q=1, orch=self._orch)
                    st.step_totals.extend(totals)
                    st.decode_timings.extend(timings)
                    st.decode_weight_bytes += wbytes
                    self._emit(st, "decode", new, float(sum(totals)),
                               ctx0 - st.prompt_len)
                if is_done:
                    self._finalize(st)
            if (self._group_bytes is not None and crit is not None
                    and jax.profiler.TraceAnnotation.is_enabled()):
                hi, lo = live_groups(np.asarray(crit), np.asarray(act),
                                     self.engine.cfg.dymoe.low_bits == 0)
                sp.set_metadata(
                    live_hi_groups=hi, live_lo_groups=lo,
                    kernel_weight_bytes=(hi * self._group_bytes[0]
                                         + lo * self._group_bytes[1]))

    # --------------------------------------------------------------- run
    def run(self, requests: Sequence[Request], *,
            pipeline: Optional[bool] = None,
            rng_keys: Optional[Sequence] = None) -> List:
        """Batch wrapper over the step API: submit every request, loop
        :meth:`step` until idle, :meth:`flush` the replay stream, return
        the results in submission order. ``rng_keys`` optionally gives
        request i an explicit PRNG root (overriding its seed)."""
        if not requests:
            return []
        b = self._num_slots or min(len(requests), self.scfg.num_slots)
        b = max(1, min(b, len(requests)))
        self._ensure_started(num_slots=b,
                             slots_len=self._slot_budget(requests),
                             pipeline=pipeline)
        handles = [self.submit(r, rng_key=rng_keys[i] if rng_keys else None)
                   for i, r in enumerate(requests)]
        chunk = self.engine.ecfg.decode_chunk
        max_chunks = self.scfg.max_chunks or (
            sum(-(-max(r.max_new_tokens - 1, 0) // chunk)
                for r in requests) + len(requests) + 1)
        try:
            while self.step():
                assert self._n_chunks <= max_chunks, \
                    f"scheduler made no progress after {self._n_chunks} chunks"
            self.flush()
        finally:
            self.close()
        assert all(h.done for h in handles)
        # a request that failed under a fault raises its typed error here
        return [h.result() for h in handles]
