"""Jaxpr invariant linter: static proof that the hot path stays fused,
packed, and retrace-bounded.

The repo's core performance claims are STRUCTURAL properties of the
traced serving programs, not benchmark numbers — and every new code path
can silently regress them. This package traces prefill, the batched
admission wave, and the scheduler's fused decode chunk for every shipped
config (abstractly, via ``jax.eval_shape``/``jax.make_jaxpr`` over
``ShapeDtypeStruct`` pytrees — full-size weights, zero bytes allocated,
no TPU needed) and checks the jaxprs against a rule registry. Run it as::

    PYTHONPATH=src python -m repro.analysis            # all configs
    PYTHONPATH=src python -m repro.analysis --smoke    # 4 edge configs
    PYTHONPATH=src python -m repro.analysis --json report.json

Exit status is non-zero on any error-severity finding.

The invariant contract
======================

1. **Packed buffers only** (PR 1). A quantized weight exists in exactly
   two forms: the packed uint8 codes + f32 group scales at rest, and a
   per-block dequantized tile inside a Pallas kernel's VMEM. A dense
   float tensor at full dequantized-weight scale — (E, d_model, d_ff) for
   experts — must never appear as an XLA-materialized intermediate.
2. **Fused dispatch budget** (PR 7). The MoE executes gate/up/down in
   exactly 3 ``pallas_call`` dispatches per layer-scan body in every
   phase and bit mix (one per expert matmul; both precision regions
   inside each). Latent attention adds its decode kernel once per traced
   layer body at decode. Dense FFN is 3 (swiglu) or 2 (gelu); SSM
   projections are 2.
3. **VMEM discipline**. Every kernel's working set — double-buffered
   blocks + accumulator scratch + scalar prefetch — fits the backend's
   VMEM budget (~16 MiB/core on TPU), for every config's
   ``block_m/n/k`` override, provable from block shapes alone.
4. **Dtype discipline**. Jitted serving programs carry no f64 (host-side
   f64 — e.g. ``_capacity``'s exact-truncation contract — stays on the
   host, annotated at its definition), and packed codes never widen
   outside a kernel body.
5. **No host syncs**. The decode chunk's one device→host transfer per
   chunk boundary is the ONLY sync: no callbacks/infeed/outfeed inside
   jitted serving programs.
6. **Bounded retraces**. The scheduler's power-of-two ``live_cap``
   ladder (:func:`repro.serving.scheduler.live_cap_for`) compiles at
   most ``log2(B) + 1`` decode variants per sampling mode.

Rule catalog
============

========================  ========  =====================================
rule id                   severity  checks
========================  ========  =====================================
no-dense-dequant          error     contract 1 — float intermediates at
                                    dense dequantized-weight shapes
pallas-dispatch-budget    error     contract 2 — exact pallas_call count
vmem-footprint            error     contract 3 — per-kernel VMEM estimate
                                    vs per-backend budget
dtype-discipline          error     contract 4 — f64 avals; packed-code
                                    upcasts outside kernel bodies
host-sync                 error     contract 5 — callback/transfer
                                    primitives in jitted serving jaxprs
retrace-budget            error     contract 6 — live_cap ladder emits
                                    pow2 caps, ≤ log2(B)+1 distinct
========================  ========  =====================================

Findings are structured (:class:`repro.analysis.rules.Finding`): rule id,
severity, target (config/mix/phase), human message, eqn provenance (the
chain of enclosing primitives, e.g. ``scan/pjit``), offending primitive
and aval — enough to locate the exact equation that broke the contract.

The walker (:mod:`repro.analysis.walker`) is the generic traversal the
structural tests in ``tests/`` also build on, so the linter and the test
gates can never drift apart.
"""
from __future__ import annotations

from repro.analysis.rules import Finding, LintTarget, RULES, \
    expected_dispatch_count, forbidden_weight_shapes, rule, run_rules
from repro.analysis.vmem import PallasVmemEstimate, VMEM_BUDGET_BYTES, \
    estimate_pallas_vmem
from repro.analysis.walker import EqnSite, count_pallas_calls, \
    count_primitive, find_eqns, intermediate_avals, iter_eqns, subjaxprs

__all__ = [
    "EqnSite", "Finding", "LintTarget", "PallasVmemEstimate", "RULES",
    "VMEM_BUDGET_BYTES", "count_pallas_calls", "count_primitive",
    "estimate_pallas_vmem", "expected_dispatch_count", "find_eqns",
    "forbidden_weight_shapes", "intermediate_avals", "iter_eqns", "rule",
    "run_rules", "subjaxprs",
]
