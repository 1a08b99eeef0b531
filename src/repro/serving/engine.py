"""DyMoE serving engine — algorithm/system co-designed inference runtime.

Two coupled halves, mirroring the paper's co-design:
  * **Math** — jitted prefill / decode of the real model (optionally
    through the mixed-precision weight store), producing exact logits AND
    DyMoE telemetry (importance, critical masks, active experts, look-ahead
    predictions).
  * **System** — the :class:`DynamicExpertOrchestrator` replays that
    telemetry against the mixed-precision LRU cache and the edge cost model
    to produce TTFT / TPOT accounting under a VRAM budget, exactly as the
    paper's Fig. 10 / Table 3 measurements do on real PCIe hardware.

**Chunked decode architecture.** The decode loop is fused on device:
:func:`repro.models.model.decode_many` runs ``decode_chunk`` decode steps
inside one ``lax.scan`` — attention/MoE forward, sampling (counter-derived
PRNG keys via ``fold_in``, so results are invariant to the chunking) and
telemetry capture all stay on the accelerator — and the engine performs ONE
jitted dispatch and ONE device→host transfer per chunk instead of per
token. The host then replays the whole chunk's stacked ``(chunk, L, E)``
telemetry through the orchestrator's vectorized ``step_batch`` and the
broadcast cost model, so the modeled TTFT/TPOT accounting no longer pays
per-expert Python branching or per-step dispatch on the replay path.
``EngineConfig.decode_chunk`` is the knob: 1
recovers the token-at-a-time loop (bit-identical greedy tokens and modeled
numbers, just slower); ~16 amortizes dispatch away. EOS early-exit happens
between chunks.

**Step-driven serving.** The serving surface is an OPEN engine API built
on :class:`repro.serving.scheduler.ContinuousBatchingScheduler` — the
lifecycle is submission → admission wave → fused decode chunk → telemetry
replay → stream::

    handle = engine.submit(request)   # -> RequestHandle, FIFO-queued
    engine.step()                     # advance one chunk boundary: admit
                                      #   new requests into free slots, run
                                      #   one fused chunk, evict finished /
                                      #   cancelled rows
    for ev in handle.stream():        # TokenChunk events as each replay
        ...                           #   unit finalizes (pipelined worker)
    handle.cancel()                   # slot freed at the next boundary
    res = handle.result()             # final GenerationResult

Requests carry per-request :class:`~repro.serving.request.SamplingParams`
(temperature / top-k / seed, validated at submission); the scheduler
threads them as per-row arrays with counter-derived ``fold_in`` PRNG
streams through the decode scan, so sampled tokens are bit-identical
between solo :meth:`DyMoEEngine.generate`, the static batch and
continuous batching, and invariant to chunk size and admission order.

:meth:`DyMoEEngine.generate` and :meth:`DyMoEEngine.generate_batch` are
thin wrappers over that loop (submit everything, drive ``step()`` until
idle, flush the replay stream) — bit-exact with the single-request fused
reference path :meth:`DyMoEEngine.generate_reference`, which survives as
the oracle the serving tests compare against. The old lockstep batch
survives as ``generate_batch(static=True)`` (ragged-capable via
right-aligned padded prefill, per-row sampling) and is the baseline the
benchmark measures the scheduler against.

Ablation rows map to :class:`EngineConfig` flags (cache / prefetch /
dyquant / 4-2 vs 4-0), matching paper Table 3 rows 1–6.
"""
from __future__ import annotations

import dataclasses
import queue as _queue
import threading
import time
from functools import partial, update_wrapper
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.orchestrator import (
    DynamicExpertOrchestrator,
    OrchestratorConfig,
    StepTiming,
)
from repro.models import ModelConfig
from repro.models.model import decode_many, decode_many_batched, \
    drop_dense_experts, prefill, quantize_model
from repro.serving.cost_model import EdgeCostModel, EdgeProfile, expert_bytes
from repro.serving.request import Request, RequestHandle
from repro.serving.sampler import raw_key_data, resolve_sampling, \
    sample_token, sample_token_rows
from repro.serving.spans import span

__all__ = ["EngineConfig", "DyMoEEngine", "GenerationResult",
           "ReplayStream"]


class ReplayStream:
    """FIFO stream of host-side telemetry-replay jobs.

    The pipelined serving loop moves the expensive host work of a chunk —
    the ``device_get`` of the (T, L, B, E) telemetry leaves plus the
    per-row orchestrator replay — off the dispatch path: jobs are
    submitted at each chunk boundary and executed by ONE worker thread in
    submission order, while the next chunk runs on device. One worker and
    FIFO order are load-bearing, not a simplification: the shared
    :class:`DynamicExpertOrchestrator` advances a modeled clock and an LRU
    cache, so replays must happen in exactly the order the serial loop
    would perform them for the modeled TTFT/TPOT to stay bit-identical.

    ``pipelined=False`` degrades to executing every job inline at
    :meth:`submit` — the serial reference mode the parity tests compare
    against. ``maxsize`` bounds the queue so a slow replay backpressures
    the dispatch loop instead of accumulating unbounded device arrays.

    A job that raises POISONS the stream permanently: the exception is
    re-raised on the submitting thread at the next :meth:`submit` or
    :meth:`drain`, every job still queued (or submitted later) is
    skipped — the orchestrator state is no longer trustworthy — and
    later calls keep failing with a poisoned-stream error.
    """

    _STOP = object()

    def __init__(self, pipelined: bool, maxsize: int = 4):
        self._pipelined = pipelined
        self._exc: Optional[BaseException] = None
        self._poisoned = False   # sticky: survives the _exc hand-off
        if pipelined:
            self._q: _queue.Queue = _queue.Queue(maxsize=max(1, maxsize))
            self._thread = threading.Thread(
                target=self._loop, name="dymoe-replay", daemon=True)
            self._thread.start()

    @property
    def poisoned(self) -> bool:
        """A job failed: queued/later jobs are skipped and no further
        finalize will ever run. Waiters that cannot call submit()/drain()
        (e.g. a non-driving stream consumer) poll this to bail out."""
        return self._poisoned or self._exc is not None

    def _loop(self) -> None:
        while True:
            job = self._q.get()
            try:
                if job is self._STOP:
                    return
                if not self._poisoned:
                    job()
            except BaseException as e:  # noqa: BLE001 — re-raised at submit
                self._poisoned = True
                self._exc = e
            finally:
                self._q.task_done()

    def submit(self, job: Callable[[], None]) -> None:
        self._reraise()
        if not self._pipelined:
            try:
                job()
            except BaseException:
                self._poisoned = True
                raise
            return
        # a full queue blocks here: the span's length is the backpressure
        with span("replay_submit", depth=self._q.qsize()):
            self._q.put(job)

    def drain(self) -> None:
        """Block until every submitted job has run (or been skipped after
        a failure), then surface any worker exception."""
        if self._pipelined:
            self._q.join()
        self._reraise()

    def close(self) -> None:
        if self._pipelined and self._thread.is_alive():
            self._q.put(self._STOP)
            self._thread.join()

    def _reraise(self) -> None:
        if self._exc is not None:
            exc, self._exc = self._exc, None
            raise exc
        if self._poisoned:
            raise RuntimeError(
                "ReplayStream is poisoned by an earlier job failure; its "
                "orchestrator state is not trustworthy")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    profile: EdgeProfile = dataclasses.field(default_factory=EdgeProfile)
    use_dymoe: bool = True          # quantized mixed-precision execution
    enable_cache: bool = True       # ablation rows 1 vs 2
    enable_prefetch: bool = True    # rows 2 vs 3
    enable_dyquant: bool = True     # rows 3 vs 4 (False: all-high requests)
    max_cache_fraction: float = 0.6  # fraction of VRAM granted to experts
    decode_chunk: int = 16          # decode steps fused per device dispatch


@dataclasses.dataclass
class GenerationResult:
    tokens: List[int]
    ttft_s: float                   # modeled edge TTFT
    tpot_s: float                   # modeled edge per-token latency
    # actual CPU wall time (reference only). Scheduler-served requests
    # report SERVICE wall — admission to result — with the time spent
    # waiting in the FIFO queue split out into queue_wait_s, so a short
    # request admitted late no longer reports the whole run's elapsed time
    wall_s: float
    queue_wait_s: Optional[float] = None  # submission -> admission wait
    # wall time of the decode loop alone (clock starts once the first
    # token is sampled and on host; excludes prefill + its replay):
    decode_wall_s: Optional[float] = None
    prefill_timing: Optional[StepTiming] = None
    decode_timings: Optional[List[StepTiming]] = None
    cache_stats: Optional[Dict] = None
    # packed expert-weight bytes the grouped quant-matmul read (what the
    # HLO actually moves now that execution runs from packed buffers):
    prefill_weight_bytes: Optional[int] = None
    decode_weight_bytes_per_tok: Optional[float] = None
    # the request was cancelled mid-flight: ``tokens`` is the partial
    # output up to the chunk boundary where its slot was freed
    cancelled: bool = False
    # the cancellation was forced by the request's wall-clock
    # ``deadline_s`` expiring in flight (deadline evictions are a
    # cancellation: partial tokens, real partial accounting)
    deadline_expired: bool = False
    # times an SLO policy preempted this request at a chunk boundary
    # before it completed (each preemption re-prefilled it on resume;
    # tokens are bit-identical, queue_wait/TTFT accounting restarts at
    # the final admission) — see repro.serving.policy
    preempted: int = 0


def _bind_cfg(f: Callable, cfg: ModelConfig) -> Callable:
    """``partial(f, cfg=cfg)`` under ``f``'s own name, so that its jitted
    program reads ``jit_<f>`` in a profile, not ``jit__unknown``."""
    return update_wrapper(partial(f, cfg=cfg), f)


class DyMoEEngine:
    def __init__(self, cfg: ModelConfig, params, engine_cfg: EngineConfig
                 = EngineConfig(), faults=None, *, mesh=None,
                 expert_parallel: bool = False, qparams=None):
        # ``faults``: optional repro.serving.faults.FaultInjector threaded
        # through the serving hot path (scheduler dispatch/replay/admission
        # sites and the expert cache's blob loads). None = every site is
        # a no-op and the fault-free trace is untouched.
        #
        # ``mesh``: optional jax.sharding.Mesh. The bf16 params and the
        # packed/scales quantized stores are device_put sharded over it at
        # load (``sharding/partition.py`` rules) and every serving
        # session's KV slot state is laid out with ``cache_shardings`` —
        # GSPMD then partitions the jitted prefill/decode programs along
        # the same axes. ``expert_parallel=True`` shards only the routed
        # experts, over E, and replicates the rest; the jitted programs
        # get ``cfg.expert_mesh = mesh``, which runs the expert kernels
        # under shard_map (``models.layers.moe._over_local_experts``).
        #
        # ``qparams``: reuse an already-quantized packed store (e.g. a
        # sibling replica engine's, or one from ``init_quantized_params``)
        # instead of re-running quantize_model — cluster replicas share
        # one copy of the weights. With DyMoE on, ``params`` may then come without
        # the dense routed experts.
        assert engine_cfg.decode_chunk >= 1, engine_cfg.decode_chunk
        self.cfg = cfg
        self.ecfg = engine_cfg
        self.faults = faults
        self.mesh = mesh
        self.expert_parallel = expert_parallel
        if qparams is None and engine_cfg.use_dymoe:
            qparams = quantize_model(params, cfg)
        if engine_cfg.use_dymoe and cfg.dymoe.enabled:
            # the quantized path reads routed experts from the packed
            # store only: the dense copies are not kept, nor passed to jit
            params = drop_dense_experts(params)
        if mesh is not None:
            from repro.sharding.partition import param_shardings, shard_tree
            params = shard_tree(
                params, param_shardings(params, mesh,
                                        expert_parallel=expert_parallel))
            if qparams is not None:
                qparams = shard_tree(
                    qparams, param_shardings(qparams, mesh,
                                             expert_parallel=expert_parallel))
        self.params = params
        self.qparams = qparams if engine_cfg.use_dymoe else None
        self.cost = EdgeCostModel(cfg, engine_cfg.profile)
        if mesh is not None and expert_parallel and cfg.is_moe:
            from repro.sharding.partition import MODEL_AXIS
            if cfg.num_experts % mesh.shape[MODEL_AXIS]:
                raise ValueError(
                    f"expert_parallel: {cfg.num_experts} experts do not "
                    f"divide the {mesh.shape[MODEL_AXIS]}-way "
                    f"{MODEL_AXIS!r} mesh axis")
            cfg = dataclasses.replace(cfg, expert_mesh=mesh)
        self._prefill = jax.jit(_bind_cfg(prefill, cfg),
                                static_argnames=("cache_slots",
                                                 "row_local"))
        # num_steps sets the scan length and top_k shapes lax.top_k, so
        # they are static; temperature stays traced — serving mixed
        # per-request temperatures must not recompile the decode scan
        self._decode_many = jax.jit(
            _bind_cfg(decode_many, cfg),
            static_argnames=("num_steps", "top_k"))
        # slot-batched decode with per-row done-masks (the continuous-
        # batching scheduler's device half); live_cap sizes the fused
        # MoE kernel's capacity regions to the chunk's live-slot count
        self._decode_batched = jax.jit(
            _bind_cfg(decode_many_batched, cfg),
            static_argnames=("num_steps", "live_cap"))
        self._orch: Optional[DynamicExpertOrchestrator] = None
        self._session = None   # engine-owned step-driven serving session

    # ------------------------------------------------------------ system
    def shard_decode_state(self, caches):
        """Lay a freshly initialized decode-state pytree out on the
        engine's mesh (``cache_shardings``: KV slots flash-decode sharded
        over "model", batch over "data"; replicated when expert-parallel).
        Identity on an unsharded engine, so the scheduler calls it
        unconditionally."""
        if self.mesh is None:
            return caches
        from repro.sharding.partition import cache_shardings, shard_tree
        return shard_tree(caches, cache_shardings(
            caches, self.mesh, expert_parallel=self.expert_parallel))

    def _make_orchestrator(self) -> Optional[DynamicExpertOrchestrator]:
        cfg, e = self.cfg, self.ecfg
        if not cfg.is_moe:
            return None
        pol = cfg.dymoe
        budget = int(e.profile.vram_bytes * e.max_cache_fraction)
        ocfg = OrchestratorConfig(
            num_layers=cfg.num_moe_layers,
            num_experts=cfg.num_experts,
            experts_per_token=cfg.num_experts_per_tok,
            bytes_high=expert_bytes(cfg, pol.high_bits),
            bytes_low=(expert_bytes(cfg, pol.low_bits)
                       if pol.low_bits else 0),
            vram_budget_bytes=budget,
            pcie_bw=e.profile.pcie_bw,
            low_is_skip=pol.low_bits == 0,
            enable_cache=e.enable_cache,
            enable_prefetch=e.enable_prefetch,
            enable_dyquant=e.enable_dyquant,
            prefetch_topk=pol.prefetch_topk,
        )
        return DynamicExpertOrchestrator(ocfg, faults=self.faults)

    def _expert_counts(self, crit: np.ndarray, active: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray]:
        """(…, L, E) masks -> (…, L) active hi / lo expert counts."""
        n_active = active.sum(axis=-1)
        n_hi = (active & crit).sum(axis=-1)
        n_lo = n_active - n_hi
        if self.cfg.dymoe.low_bits == 0:
            n_lo = np.zeros_like(n_lo)
        return n_hi, n_lo

    def _replay(self, crit, active, pred, *, phase: str, s_ctx, s_q: int,
                orch: Optional[DynamicExpertOrchestrator]
                ) -> Tuple[List[StepTiming], List[float], int]:
        """Replay a chunk's host-side telemetry through the orchestrator.

        ``crit`` / ``active`` / ``pred`` are the (T, L, E) stacked masks
        (T = chunk length; T = 1 for prefill; (L, E) inputs are promoted)
        — exactly the three DyMoEInfo leaves the replay needs, so callers
        transfer only these; ``s_ctx`` is the per-step context length,
        shape (T,). Returns (timings, per-step modeled seconds,
        weight_bytes) where ``weight_bytes`` is the packed expert-weight
        traffic of the whole chunk — per layer and step, each active
        Critical expert moves its high-bit blob, each active Sub-critical
        one its low-bit blob (zero in the "x/0" skip deployment). This
        mirrors what the grouped quant-matmul kernel reads, byte for byte.

        The replay math is vectorized: expert counts come from numpy
        set-ops on the stacked masks, the cost model broadcasts over
        (T, L), and the orchestrator consumes the block via ``step_batch``.
        (The LRU admission walk itself remains per-expert by design — see
        ``step_batch`` — but the per-expert precision branching and all
        FLOP/byte pricing no longer are.)
        """
        cfg = self.cfg
        s_ctx = np.asarray(s_ctx)
        T = s_ctx.shape[0]
        if orch is None or crit is None:
            per_layer = self.cost.layer_compute_s(
                phase=phase, s_ctx=s_ctx[:, None], s_q=s_q,
                tokens_routed=s_q)                        # (T, 1)
            totals = np.broadcast_to(
                per_layer, (T, cfg.num_layers)).sum(axis=1)
            return [], [float(x) for x in totals], 0
        crit = np.asarray(crit, bool).reshape(T, cfg.num_moe_layers, -1)
        active = np.asarray(active, bool).reshape(crit.shape)
        pred = np.asarray(pred).reshape(crit.shape)
        # SLO pressure ladder: price compute/bytes with the SAME degraded
        # precision mix the orchestrator's cache walk will use (step_batch
        # applies the identical override to the raw masks it receives)
        dcrit, dactive = ((crit, active) if orch.degrade is None
                          else orch.degrade.apply(crit, active))
        n_hi, n_lo = self._expert_counts(dcrit, dactive)  # (T, L)
        wbytes = int(self.cost.moe_weight_bytes(n_hi, n_lo).sum())
        compute = self.cost.layer_compute_s(
            phase=phase, s_ctx=s_ctx[:, None], s_q=s_q,
            active_experts_hi=n_hi, active_experts_lo=n_lo,
            tokens_routed=s_q)                            # (T, L)
        timings = orch.step_batch(crit, active, pred, compute)
        return timings, [t.total_s for t in timings], wbytes

    # ------------------------------------------------- step-driven API
    def serve(self, num_slots: Optional[int] = None, *,
              pipeline: Optional[bool] = None,
              slots_len: Optional[int] = None,
              max_queue: Optional[int] = None,
              policy=None):
        """Open (and remember) a step-driven serving session — the open
        counterpart of ``generate_batch``. Returns the
        :class:`~repro.serving.scheduler.ContinuousBatchingScheduler`
        session; :meth:`submit` / :meth:`step` delegate to it.

        ``slots_len`` sets the per-slot cache length (default:
        ``sliding_window`` or ``cfg.max_seq_len``); a submitted request
        must fit ``prompt_len + max_new_tokens`` inside it.

        ``max_queue`` bounds the admission queue: a ``submit`` beyond it
        raises a typed :class:`~repro.serving.faults.QueueFull` instead of
        growing latency without bound (backpressure; None = unbounded).

        ``policy`` selects the SLO scheduling policy
        (:mod:`repro.serving.policy`): ``"fifo"`` (default — the
        bit-exactness oracle), ``"edf"`` (priority + deadline-aware
        admission, infeasibility shedding, chunk-boundary preemption,
        pressure degradation ladder), or a ``SchedulingPolicy`` instance.

        An existing engine-owned session is retired first: its submitted
        replay jobs are flushed, its worker stopped, and any handle still
        queued or in flight on it resolves with a typed
        :class:`~repro.serving.faults.SessionClosed` error — drain it
        yourself before re-serving if you want their results."""
        from repro.serving.scheduler import ContinuousBatchingScheduler

        if self._session is not None and not self._session.closed:
            self._session.flush()
            self._session.close()
        session = ContinuousBatchingScheduler(self, num_slots=num_slots)
        session._ensure_started(slots_len=slots_len, pipeline=pipeline,
                                max_queue=max_queue, policy=policy)
        self._session = session
        return session

    def submit(self, request: Request, rng_key=None) -> RequestHandle:
        """Queue ``request`` on the engine's serving session (opened with
        defaults if :meth:`serve` wasn't called) for admission at the next
        chunk boundary. Returns a :class:`RequestHandle` — see
        ``handle.stream()`` / ``handle.result()`` / ``handle.cancel()``."""
        if self._session is None or self._session.closed:
            self.serve()
        return self._session.submit(request, rng_key=rng_key)

    def step(self) -> bool:
        """Advance the engine's serving session by one chunk boundary
        (admit → decode chunk → evict). Returns True while there is live
        or queued work. Submission is legal between any two steps."""
        if self._session is None:
            raise RuntimeError(
                "no serving session is open: call serve() or submit() first")
        return self._session.step()

    def health(self):
        """Fault-tolerance snapshot of the engine's serving session —
        see :class:`repro.serving.faults.SessionHealth`. ``status="ok"``
        with zeroed counters when no session has been opened."""
        from repro.serving.faults import SessionHealth

        if self._session is None:
            return SessionHealth(status="ok")
        return self._session.health()

    # -------------------------------------------------------------- API
    def generate(self, request: Request, rng_key=None) -> GenerationResult:
        """Serve one request (edge scenario: batch = 1): a thin wrapper
        over the step-driven API — one fresh single-slot session, submit,
        drive :meth:`~repro.serving.scheduler.ContinuousBatchingScheduler.step`
        to completion. Tokens and modeled TTFT/TPOT are bit-identical to
        :meth:`generate_reference` (greedy and sampled: both index the
        request's PRNG stream by token position), and the serial replay
        keeps ``decode_wall_s`` comparable."""
        from repro.serving.scheduler import ContinuousBatchingScheduler

        sched = ContinuousBatchingScheduler(self, num_slots=1)
        return sched.run([request], pipeline=False, rng_keys=[rng_key])[0]

    def generate_reference(self, request: Request, rng_key=None
                           ) -> GenerationResult:
        """Single-request fused REFERENCE path (no scheduler): prefill +
        ``decode_chunk``-sized :func:`decode_many` chunks with inline
        telemetry replay. Token i's PRNG key is ``fold_in(rng_key, i)``,
        so outputs are chunking-invariant. This is the bit-exactness
        oracle the serving tests compare the step-driven engine against;
        :meth:`generate` must match it token- and modeled-number-exact."""
        cfg = self.cfg
        temperature, top_k, rng_key = resolve_sampling(
            request, rng_key, context="generate")
        sampling = temperature > 0.0
        prompt = jnp.asarray(request.prompt_tokens, jnp.int32)[None, :]
        s = prompt.shape[1]
        slots = cfg.sliding_window or (s + request.max_new_tokens)
        orch = self._make_orchestrator()
        eos = request.eos_token
        t0 = time.perf_counter()

        logits, caches, info = self._prefill(
            self.params, tokens=prompt, qparams=self.qparams,
            cache_slots=slots)
        crit, act, pred = jax.device_get(
            (info.critical_masks, info.active_masks, info.predicted_next))
        pre_timings, pre_totals, pre_wbytes = self._replay(
            crit, act, pred, phase="prefill", s_ctx=np.asarray([s]), s_q=s,
            orch=orch)
        pre_t = pre_timings[0] if pre_timings else None
        ttft = pre_t.total_s if pre_t is not None else pre_totals[0]

        tok = sample_token(
            logits, jax.random.fold_in(rng_key, 0) if sampling else None,
            temperature=temperature, top_k=top_k)
        tokens: List[int] = [int(tok[0])]   # host sync: prefill complete
        t_dec = time.perf_counter()
        decode_timings: List[StepTiming] = []
        tpot_total = 0.0
        dec_wbytes = 0
        done = eos is not None and tokens[0] == eos
        total_steps = request.max_new_tokens - 1
        n_done = 0  # decode steps completed (== tokens sampled - 1)
        while n_done < total_steps and not done:
            chunk = min(self.ecfg.decode_chunk, total_steps - n_done)
            toks_d, caches, infos = self._decode_many(
                self.params, tokens=tok, caches=caches,
                qparams=self.qparams, num_steps=chunk,
                start_step=n_done + 1,
                rng_key=rng_key if sampling else None,
                temperature=temperature, top_k=top_k)
            tok = toks_d[-1]
            # the chunk's ONE device->host transfer: tokens + the three
            # telemetry leaves the replay consumes (nothing else moves)
            toks_np, crit, act, pred = jax.device_get(
                (toks_d, infos.critical_masks, infos.active_masks,
                 infos.predicted_next))
            new = [int(t) for t in toks_np[:, 0]]
            keep = chunk
            if eos is not None and eos in new:
                keep = new.index(eos) + 1
                done = True
            new = new[:keep]
            if keep < chunk and crit is not None:
                crit, act, pred = crit[:keep], act[:keep], pred[:keep]
            s_ctx = s + n_done + 1 + np.arange(keep)
            timings, totals, wbytes = self._replay(
                crit, act, pred, phase="decode", s_ctx=s_ctx, s_q=1,
                orch=orch)
            decode_timings.extend(timings)
            for x in totals:   # per-step adds: bit-equal to decode_chunk=1
                tpot_total += x
            dec_wbytes += wbytes
            tokens.extend(new)
            n_done += keep
        t_end = time.perf_counter()
        wall = t_end - t0
        n_dec = max(len(tokens) - 1, 1)
        return GenerationResult(
            tokens=tokens, ttft_s=float(ttft),
            tpot_s=float(tpot_total / n_dec),
            wall_s=wall, decode_wall_s=t_end - t_dec,
            prefill_timing=pre_t, decode_timings=decode_timings or None,
            cache_stats=(dataclasses.asdict(orch.cache.stats)
                         if orch else None),
            prefill_weight_bytes=(pre_wbytes if pre_t is not None else None),
            decode_weight_bytes_per_tok=(
                dec_wbytes / n_dec if decode_timings else None))

    def generate_batch(self, requests: Sequence[Request], rng_key=None, *,
                       num_slots: Optional[int] = None,
                       static: bool = False,
                       pipeline: Optional[bool] = None,
                       ) -> List[GenerationResult]:
        """Batched serving (throughput path): a thin wrapper over the
        step-driven API — submit every request, drive ``step()`` until the
        session drains, flush the replay stream.

        Default: CONTINUOUS BATCHING — requests stream through a fixed
        set of ``num_slots`` device slots (see
        :class:`repro.serving.scheduler.ContinuousBatchingScheduler`):
        ragged prompt lengths, per-request ``max_new_tokens`` /
        ``eos_token`` / :class:`~repro.serving.request.SamplingParams`
        (temperature / top-k / seed — honored, with per-row
        counter-derived PRNG streams), eviction of finished rows and
        admission of waiting ones at every chunk boundary, per-row tokens
        bit-identical to solo :meth:`generate`, and REAL per-request
        modeled TTFT/TPOT (the old lockstep path returned NaN).

        ``pipeline`` — overlap the host telemetry replay with device
        decode (default on; see the scheduler docstring's timeline).
        ``pipeline=False`` is the serial reference mode: identical tokens
        and bit-identical modeled numbers, host replay on the critical
        path.

        ``static=True`` keeps the old lockstep baseline: one batch for
        the whole call, right-aligned padding for ragged prompts, decode
        until every row finishes, DyMoE telemetry discarded (NaN modeled
        metrics). Per-request sampling is honored (per-row PRNG streams
        indexed by token position, so sampled rows match their solo run
        in the full-precision row-independent regime). It exists as the
        benchmark baseline continuous batching is measured against.

        ``rng_key`` — optional shared PRNG root for requests WITHOUT a
        seed: request i's stream root becomes ``fold_in(rng_key, i)``
        (distinct per request; a request's own seed wins)."""
        rng_keys = None
        if rng_key is not None:
            rng_keys = [None if r.seed is not None
                        else jax.random.fold_in(rng_key, i)
                        for i, r in enumerate(requests)]
        if static:
            return self._generate_batch_static(requests, rng_keys=rng_keys)
        from repro.serving.scheduler import ContinuousBatchingScheduler
        return ContinuousBatchingScheduler(
            self, num_slots=num_slots).run(requests, pipeline=pipeline,
                                           rng_keys=rng_keys)

    def _generate_batch_static(self, requests: Sequence[Request], *,
                               rng_keys: Optional[Sequence] = None
                               ) -> List[GenerationResult]:
        """Lockstep baseline: every request occupies a row for the whole
        call; ragged prompts are right-aligned into one padded batch
        (per-row position/attention offsets threaded through ``prefill``);
        rows that finish early keep burning device steps until the whole
        batch drains. Per-row done state is tracked incrementally — only
        each chunk's new tokens are scanned, not the whole history."""
        cfg = self.cfg
        # per-request sampling: seed-derived per-row PRNG streams indexed
        # by token position (bit-compatible with the solo/scheduler paths)
        temps = np.zeros(len(requests), np.float32)
        topks = np.zeros(len(requests), np.int32)
        keys = np.zeros((len(requests), 2), np.uint32)
        for i, r in enumerate(requests):
            t, k, key = resolve_sampling(
                r, rng_keys[i] if rng_keys is not None else None,
                context=f"generate_batch(static=True) request {i}")
            temps[i], topks[i] = t, k
            if t > 0.0:
                keys[i] = raw_key_data(key)
        any_sampling = bool((temps > 0).any())
        lens = [len(r.prompt_tokens) for r in requests]
        s = max(lens)
        ragged = len(set(lens)) > 1
        b = len(requests)
        prompts = np.zeros((b, s), np.int32)
        for i, r in enumerate(requests):
            prompts[i, s - lens[i]:] = r.prompt_tokens   # right-aligned
        limits = [r.max_new_tokens for r in requests]
        eos = [r.eos_token for r in requests]
        max_new = max(limits)
        slots = cfg.sliding_window or (s + max_new)
        t0 = time.perf_counter()
        logits, caches, _ = self._prefill(
            self.params, tokens=jnp.asarray(prompts), qparams=self.qparams,
            cache_slots=slots,
            lengths=jnp.asarray(lens, jnp.int32) if ragged else None)
        if any_sampling:
            keys_d = jnp.asarray(keys)
            tok = sample_token_rows(
                logits, jax.vmap(lambda k: jax.random.fold_in(k, 0))(keys_d),
                jnp.asarray(temps), jnp.asarray(topks))
        else:
            tok = sample_token(logits)
        rows = [[int(t)] for t in np.asarray(tok)]

        # incremental done tracking: a row is re-examined only over tokens
        # it gained this chunk (the old finished() closure re-sliced and
        # rescanned every row's full history after every chunk — O(n^2))
        done = [len(rows[i]) >= limits[i]
                or (eos[i] is not None and rows[i][0] == eos[i])
                for i in range(b)]
        remaining = b - sum(done)

        row_kw = {}
        if any_sampling:   # per-row mode: step i folds row r's key with i
            row_kw = dict(row_keys=keys_d,
                          row_temperatures=jnp.asarray(temps),
                          row_top_ks=jnp.asarray(topks))
        n_done = 1  # tokens sampled per row so far
        while n_done < max_new and remaining:
            chunk = min(self.ecfg.decode_chunk, max_new - n_done)
            toks_d, caches, _ = self._decode_many(
                self.params, tokens=tok, caches=caches,
                qparams=self.qparams, num_steps=chunk, start_step=n_done,
                **row_kw)
            tok = toks_d[-1]
            toks_np = np.asarray(toks_d)      # one transfer per chunk
            for i in range(b):
                new = [int(t) for t in toks_np[:, i]]
                rows[i].extend(new)
                if not done[i]:
                    hit_eos = eos[i] is not None and any(
                        t == eos[i] for t in new[:limits[i] - n_done])
                    if hit_eos or len(rows[i]) >= limits[i]:
                        done[i] = True
                        remaining -= 1
            n_done += chunk
        wall = time.perf_counter() - t0
        out = []
        for i, row in enumerate(rows):
            row = row[:limits[i]]
            if eos[i] is not None and eos[i] in row:
                row = row[:row.index(eos[i]) + 1]
            out.append(GenerationResult(tokens=row, ttft_s=float("nan"),
                                        tpot_s=float("nan"), wall_s=wall))
        return out
