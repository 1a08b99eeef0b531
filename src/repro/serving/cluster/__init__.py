"""Multi-replica serving tier: expert-parallel sharded engines behind a
load-balancing router.

Everything below this package serves on ONE session over ONE engine;
this is the scale-out layer: N full serving sessions (each with its own
``ReplayStream`` worker, orchestrator clock/cache, and fault/policy
state) behind a front-end router that speaks the exact session surface
— ``submit`` / ``step`` / ``stream`` / ``cancel`` / ``drain`` /
``close`` / ``health``.

Topology::

                               ClusterRouter
                   submit ──► placement (least_loaded | round_robin)
                   health ◄── merge(SessionHealth × N) + reroutes/restarts
                       │
          ┌────────────┼──────────────┐
          ▼            ▼              ▼
      Replica 0    Replica 1  …   Replica N-1          (sticky handles)
          │            │              │
      [_Driver 0]  [_Driver 1]   [_Driver N-1]   one driver thread per
          │            │              │          replica (threaded=True)
          ▼            ▼              ▼          or round-robin step()
       session      session       session        multiplexed on the
     (scheduler)  (scheduler)   (scheduler)      caller (threaded=False)
          │            │              │
     ReplayStream ReplayStream  ReplayStream     per-session workers
          │            │              │
          └────────────┴──────┬───────┘
                              ▼
                        DyMoEEngine(s)           weights + packed quant
               mesh-sharded params/qparams/KV    stores shared across
               (param_shardings(expert_parallel) replicas; jitted
                + cache_shardings over a         programs partitioned
                launch.mesh mesh)                by GSPMD over the mesh

Routing contract:

  * **Sticky handles** — ``submit`` returns a :class:`ClusterHandle`
    bound to the replica that admitted the request; ``result`` /
    ``stream`` / ``cancel`` always go there, whatever the router does
    afterwards. Every handle resolves (result or typed error) under
    every fault the tier tolerates.
  * **Placement** is a pure function of submission order
    (``least_loaded``: queued+in-flight depth, FIFO tie-break on
    lifetime ``submitted`` then replica index) — never of wall-clock
    timing — so a given submission sequence maps to the same replicas on
    every run: the parity oracle. Per-request tokens are bit-identical
    to the solo engine for ANY replica count and placement (the
    scheduler is invariant to batching/chunking/admission order), and
    per-replica modeled TTFT/TPOT equal a standalone session serving the
    same routed subsequence; under stateless accounting
    (``enable_cache=False, enable_prefetch=False`` — no shared
    orchestrator state across requests) modeled numbers are solo-exact
    for every request regardless of placement.
  * **Backpressure reroutes before it surfaces**: a replica's
    ``QueueFull`` moves the request to the next candidate; the typed
    error reaches the caller only when EVERY live replica rejected (and
    then no handle exists — a single session's contract, widened).

Failure semantics:

  * A replica whose session DEGRADES (replay fault → inline-replay
    fallback) is quarantined — placement skips it — then drained through
    the existing recovery path (``drain(cancel_queued=False)``: every
    accepted request resolves normally or with its typed error), closed,
    and COLD-RESTARTED as a fresh session before rejoining the pool.
    Traffic on the other replicas never stops; the router's ``health()``
    reports ``"degraded"`` while any replica is impaired and the
    ``restarts`` counter afterwards.
  * A real compile or device error escaping a replica's session (the
    session has already failed the requests it touched, typed) takes that
    replica out of service for good (``Replica.fail``): no cold restart,
    which would compile the same failing program; its remaining requests
    resolve with ``SessionClosed`` caused by the error, placement skips
    it, and the router's ``health()`` reports ``"degraded"``.
  * ``close()`` stops every driver and closes every session — each
    resolves its outstanding handles with ``SessionClosed``; no waiter
    is left blocked.

The router itself holds no model state: all serving invariants
(bit-exactness, fault tolerance, SLO policies) are the per-session ones,
inherited wholesale.
"""
from repro.serving.cluster.replica import Replica
from repro.serving.cluster.router import ClusterHandle, ClusterHealth, \
    ClusterRouter, PLACEMENTS

__all__ = ["Replica", "ClusterRouter", "ClusterHandle", "ClusterHealth",
           "PLACEMENTS"]
