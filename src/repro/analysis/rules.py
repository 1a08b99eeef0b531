"""Invariant rule registry: structured findings over traced jaxprs.

Each rule is a function ``(LintTarget) -> [Finding]`` registered under a
stable rule id. A :class:`LintTarget` is one traced program (prefill /
decode chunk / scheduler admission wave) of one config at one bit mix,
plus the expectations the rules check against (dispatch budget, forbidden
weight shapes, VMEM budget, retrace ladder). Findings carry the rule id,
severity, eqn provenance and the offending aval/shape so a violation
points at the exact equation that broke the contract.

Rule catalog (see the package docstring for the full invariant contract):

  no-dense-dequant      no float intermediate at dense dequantized weight
                        scale anywhere outside kernel bodies
  pallas-dispatch-budget  exact ``pallas_call`` count per layer-scan body
  vmem-footprint        every pallas_call's estimated VMEM working set
                        fits the per-backend budget
  dtype-discipline      no f64 avals; no packed-code upcast outside
                        kernel bodies
  host-sync             no callbacks / infeed / outfeed inside jitted
                        serving programs
  retrace-budget        the live_cap ladder compiles at most
                        ``log2(B) + 1`` decode variants per sampling mode
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp

from repro.analysis.vmem import VMEM_BUDGET_BYTES, estimate_pallas_vmem
from repro.analysis.walker import EqnSite, intermediate_avals, iter_eqns

__all__ = ["Finding", "LintTarget", "RULES", "rule", "run_rules",
           "expected_dispatch_count", "forbidden_weight_shapes",
           "FLOAT_DTYPES", "PACKED_DTYPES", "HOST_SYNC_PRIMITIVES"]


FLOAT_DTYPES = frozenset(
    (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16),
     jnp.dtype(jnp.float16)))
PACKED_DTYPES = frozenset((jnp.dtype(jnp.uint8), jnp.dtype(jnp.int8)))
# Primitives whose presence inside a jitted serving program implies a
# host round-trip (callback dispatch or host transfer) per execution.
HOST_SYNC_PRIMITIVES = frozenset((
    "pure_callback", "io_callback", "debug_callback", "callback",
    "ordered_effect_callback", "infeed", "outfeed", "debug_print",
))


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation, with enough provenance to act on."""

    rule: str
    severity: str          # "error" | "warning"
    target: str            # e.g. "qwen2_moe_a2p7b/4-2/decode_chunk"
    message: str
    provenance: str = ""   # enclosing-primitive chain of the eqn
    primitive: str = ""    # offending primitive name
    aval: str = ""         # offending aval / shape, when one exists

    def to_json(self) -> Dict[str, str]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class LintTarget:
    """One traced program plus the expectations rules check against."""

    name: str                       # "<config>/<mix>/<phase>"
    cfg: Any                        # ModelConfig
    phase: str                      # prefill | admission | decode_chunk
    jaxpr: Optional[Any] = None     # ClosedJaxpr (None: accounting-only)
    backend: str = "tpu"
    # no-dense-dequant: float shapes that must never appear. None =
    # derive from cfg (expert-weight scale).
    forbidden_shapes: Optional[frozenset] = None
    # pallas-dispatch-budget: exact expected count. None = derive.
    expected_dispatches: Optional[int] = None
    # vmem-footprint budget override (bytes). None = per-backend table.
    vmem_budget: Optional[int] = None
    # dtype-discipline: packed operands at/above this byte size must not
    # upcast outside kernels. None = derive from cfg (smallest packed
    # expert leaf).
    packed_upcast_threshold: Optional[int] = None
    # retrace-budget inputs (accounting, no jaxpr needed): slot count and
    # the static-capacity ladder function (n_live, slots) -> live_cap.
    slots: Optional[int] = None
    ladder: Optional[Callable[[int, int], int]] = None
    sampling_variants: int = 2
    # set by the target builder when tracing itself failed; reported as a
    # "trace-error" finding instead of running rules
    trace_error: Optional[str] = None


RuleFn = Callable[[LintTarget], List[Finding]]
RULES: Dict[str, Tuple[str, RuleFn]] = {}


def rule(name: str, severity: str = "error"):
    def deco(fn: RuleFn) -> RuleFn:
        assert name not in RULES, f"duplicate rule {name!r}"
        RULES[name] = (severity, fn)
        fn.rule_name = name
        return fn
    return deco


def run_rules(target: LintTarget,
              only: Optional[Sequence[str]] = None) -> List[Finding]:
    """Run every (selected) registered rule over one target."""
    findings: List[Finding] = []
    for name, (_, fn) in RULES.items():
        if only is not None and name not in only:
            continue
        findings.extend(fn(target))
    return findings


def _finding(target: LintTarget, rule_name: str, message: str,
             site: Optional[EqnSite] = None, aval: Any = None) -> Finding:
    sev = RULES[rule_name][0]
    return Finding(
        rule=rule_name, severity=sev, target=target.name, message=message,
        provenance=site.provenance() if site is not None else "",
        primitive=site.eqn.primitive.name if site is not None else "",
        aval=str(aval) if aval is not None else "")


# --------------------------------------------------------------- helpers


def forbidden_weight_shapes(cfg) -> frozenset:
    """Float shapes that equal a dense dequantized quantized-weight leaf
    (both matmul orientations) — the tensors PR 1 abolished."""
    dm = cfg.d_model
    shapes = set()
    kind = cfg.stack_kind
    if kind == "attn_moe":
        e, dff = cfg.num_experts, cfg.expert_d_ff
        shapes |= {(e, dm, dff), (e, dff, dm)}
    elif kind == "attn_dense":
        dff = cfg.d_ff
        shapes |= {(dm, dff), (dff, dm)}
    else:  # ssm: in_proj/out_proj
        di = cfg.d_inner
        if cfg.ssm_version == 1:
            in_n = 2 * di
        else:
            in_n = 2 * di + 2 * cfg.ssm_state + cfg.ssm_heads
        shapes |= {(dm, in_n), (in_n, dm), (di, dm), (dm, di)}
    return frozenset(shapes)


def expected_dispatch_count(cfg, *, phase: str) -> int:
    """Exact ``pallas_call`` count per layer-scan body for a quantized
    serving trace (the scan body traces once, so this is also the count
    for the whole jaxpr).

    attn_moe: one kernel per expert matmul — gate/up/down = 3, in every
    phase and bit mix: the per-row paths run the grouped kernel over both
    precision regions, the batch-shared prefill (``moe_apply``) the
    critical-masked kernel.
    attn_dense: one kernel per FFN matmul (swiglu 3, gelu 2).
    ssm/hybrid: the two quantized projections (in_proj / out_proj).
    Latent attention adds its decode kernel once per traced layer body
    at decode: the scanned stack's and, with ``first_k_dense``, the
    leading dense stack's (prefill runs it in XLA).
    """
    kind = cfg.stack_kind
    if kind == "attn_moe":
        mla = (1 + bool(cfg.first_k_dense)
               if cfg.is_mla and phase.startswith("decode") else 0)
        return 3 + mla
    if kind == "attn_dense":
        return 3 if cfg.mlp_type == "swiglu" else 2
    return 2


def _default_packed_threshold(cfg) -> int:
    """Smallest packed quantized leaf (bytes) for this config: a uint8
    upcast at/above this size outside a kernel is packed codes being
    unpacked in the XLA graph."""
    dm = cfg.d_model
    kind = cfg.stack_kind
    bits = min(b for b in (cfg.dymoe.high_bits,
                           cfg.dymoe.low_bits or cfg.dymoe.high_bits))
    vpb = 8 // bits
    if kind == "attn_moe":
        return cfg.num_experts * cfg.expert_d_ff * dm // vpb
    if kind == "attn_dense":
        return cfg.d_ff * dm // vpb
    return cfg.d_inner * dm // vpb


# ----------------------------------------------------------------- rules


@rule("no-dense-dequant")
def check_no_dense_dequant(target: LintTarget) -> List[Finding]:
    """No float intermediate at dense dequantized-weight scale anywhere in
    the XLA-visible program: the packed representation must be carried all
    the way into the kernel (PR 1's contract)."""
    if target.jaxpr is None:
        return []
    forbidden = target.forbidden_shapes
    if forbidden is None:
        forbidden = forbidden_weight_shapes(target.cfg)
    out: List[Finding] = []
    for site in iter_eqns(target.jaxpr, into_kernels=False):
        for v in site.eqn.outvars:
            aval = v.aval
            if getattr(aval, "shape", None) in forbidden \
                    and getattr(aval, "dtype", None) in FLOAT_DTYPES:
                out.append(_finding(
                    target, "no-dense-dequant",
                    "dense dequantized weight materialized at "
                    f"{aval.shape} {aval.dtype}", site, aval))
    return out


@rule("pallas-dispatch-budget")
def check_pallas_dispatch_budget(target: LintTarget) -> List[Finding]:
    """Exactly the budgeted number of fused kernel dispatches per
    layer-scan body — one per expert matmul (3)."""
    if target.jaxpr is None:
        return []
    expected = target.expected_dispatches
    if expected is None:
        expected = expected_dispatch_count(target.cfg, phase=target.phase)
    sites = [s for s in iter_eqns(target.jaxpr, into_kernels=False)
             if s.eqn.primitive.name == "pallas_call"]
    if len(sites) == expected:
        return []
    where = sorted({s.provenance() for s in sites})
    return [_finding(
        target, "pallas-dispatch-budget",
        f"{len(sites)} pallas_call dispatches per layer body, expected "
        f"exactly {expected} (sites: {where})",
        sites[0] if sites else None)]


@rule("vmem-footprint")
def check_vmem_footprint(target: LintTarget) -> List[Finding]:
    """Every pallas_call's estimated working set (double-buffered blocks +
    scratch + scalar prefetch) fits the backend's VMEM — catches a bad
    ``block_m/n/k`` override before any TPU run."""
    if target.jaxpr is None:
        return []
    budget = target.vmem_budget
    if budget is None:
        budget = VMEM_BUDGET_BYTES.get(target.backend,
                                       VMEM_BUDGET_BYTES["tpu"])
    out: List[Finding] = []
    for site in iter_eqns(target.jaxpr, into_kernels=False):
        est = estimate_pallas_vmem(site.eqn)
        if est is None:
            continue
        if est.total_bytes > budget:
            out.append(_finding(
                target, "vmem-footprint",
                f"estimated VMEM {est.total_bytes} B exceeds "
                f"{budget} B budget: {est.describe()}", site))
    return out


@rule("dtype-discipline")
def check_dtype_discipline(target: LintTarget) -> List[Finding]:
    """No f64 anywhere in a jitted serving program (host-side f64 — e.g.
    ``_capacity``'s exact-truncation contract — is allowlisted by living
    OUTSIDE traced code), and no packed-code upcast outside kernel
    bodies: weight-scale uint8 buffers may only widen inside a
    ``pallas_call`` (the in-kernel unpack)."""
    if target.jaxpr is None:
        return []
    threshold = target.packed_upcast_threshold
    if threshold is None:
        threshold = _default_packed_threshold(target.cfg)
    f64 = jnp.dtype("float64")
    out: List[Finding] = []
    for site in iter_eqns(target.jaxpr, into_kernels=True):
        for v in site.eqn.outvars:
            if getattr(v.aval, "dtype", None) == f64:
                out.append(_finding(
                    target, "dtype-discipline",
                    f"f64 intermediate {getattr(v.aval, 'shape', ())} in "
                    "traced serving code", site, v.aval))
        if site.in_kernel:
            continue
        # the literal unpack op: packed codes widened in the XLA graph.
        # Higher-order eqns (scan/pjit/pallas_call) legitimately consume
        # packed operands and emit floats — only the element conversion
        # itself is the violation.
        if site.eqn.primitive.name != "convert_element_type":
            continue
        aval = getattr(site.eqn.invars[0], "aval", None)
        if aval is None or getattr(aval, "dtype", None) \
                not in PACKED_DTYPES:
            continue
        size = math.prod(getattr(aval, "shape", ())) * aval.dtype.itemsize
        if size < threshold:
            continue
        od = site.eqn.outvars[0].aval.dtype
        if od not in PACKED_DTYPES and od.itemsize > 1:
            out.append(_finding(
                target, "dtype-discipline",
                f"packed codes ({aval.shape} {aval.dtype}) widen to {od} "
                "outside a kernel body", site,
                site.eqn.outvars[0].aval))
    return out


@rule("host-sync")
def check_host_sync(target: LintTarget) -> List[Finding]:
    """No callbacks or host transfers inside the fused serving programs:
    a callback inside the decode chunk would serialize every chunk on the
    host and break the pipelined scheduler's one-sync-per-boundary
    contract."""
    if target.jaxpr is None:
        return []
    out: List[Finding] = []
    for site in iter_eqns(target.jaxpr, into_kernels=True):
        name = site.eqn.primitive.name
        if name in HOST_SYNC_PRIMITIVES or name.endswith("_callback"):
            out.append(_finding(
                target, "host-sync",
                f"host-sync primitive '{name}' inside the jitted "
                f"{target.phase} program", site))
    return out


@rule("retrace-budget")
def check_retrace_budget(target: LintTarget) -> List[Finding]:
    """The scheduler's static-capacity ladder compiles a bounded trace
    family: over every reachable live-slot count 1..B the ladder must
    emit power-of-two capacities with at most ``floor(log2(B)) + 1``
    distinct values — so a session compiles at most
    ``(log2(B) + 1) x sampling_variants`` decode variants, i.e.
    ``log2(B) + C`` per sampling mode."""
    if target.slots is None or target.ladder is None:
        return []
    b = int(target.slots)
    budget = math.floor(math.log2(b)) + 1 if b > 0 else 1
    caps = sorted({int(target.ladder(n, b)) for n in range(1, b + 1)})
    out: List[Finding] = []
    bad = [c for c in caps if c < 1 or c > b or (c & (c - 1)) != 0]
    if bad:
        out.append(_finding(
            target, "retrace-budget",
            f"live_cap ladder emits non-power-of-two / out-of-range "
            f"capacities {bad} for B={b} — every value is a fresh trace "
            "key"))
    if len(caps) > budget:
        out.append(_finding(
            target, "retrace-budget",
            f"live_cap ladder compiles {len(caps)} variants for B={b} "
            f"(caps={caps}), budget is log2(B)+1 = {budget} per sampling "
            f"mode ({budget * target.sampling_variants} total)"))
    return out
