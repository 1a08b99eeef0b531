"""Trace the serving programs abstractly and run the invariant rules.

Every target is traced with ``jax.eval_shape`` / ``jax.make_jaxpr`` over
``ShapeDtypeStruct`` pytrees: FULL-SIZE configs (mixtral_8x7b included)
trace in seconds with zero bytes of parameters allocated, because
tracing never executes — and the vmem-footprint rule therefore sees the
REAL block shapes each config's ``block_m/n/k`` override produces, not a
smoke-test miniature. :func:`repro.kernels.quant_matmul.ops.force_impl`
pins the Pallas serving path during tracing so the kernel dispatch
structure is inspectable on any backend.

Per config the linter builds:

  prefill       solo prefill (B=1), quantized, DyMoE policy active
  admission     the batched ragged row-local admission wave (attention
                archs without ring caches — mirrors the scheduler's
                ``_can_batch_admissions`` gate)
  decode_chunk  the scheduler's fused multi-step dispatch
                (``decode_many_batched`` with done-mask + ``live_cap``)
  prefill_ep /  the same programs traced under expert-parallel GSPMD
  decode_chunk_ep
                partitioning (MoE configs): params/qparams carry
                ``param_shardings(expert_parallel=True)`` and the decode
                state ``cache_shardings`` over an ABSTRACT 4-way mesh
                (``jax.sharding.AbstractMesh`` — zero devices needed),
                proving the structural contract (dispatch budget, no
                dense dequant, no host sync) survives partitioning —
                the serving-tier guarantee behind ``serving/cluster``
  retrace       accounting-only target for the live_cap ladder

each across the config's bit mixes ("4/2"-style mixed and "4/0").
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.rules import Finding, LintTarget, RULES, run_rules
from repro.configs import ANALYSIS_SMOKE_CONFIGS, ARCH_IDS, get_config
from repro.kernels.quant_matmul.ops import force_impl
from repro.models.config import ModelConfig
from repro.models.model import decode_many_batched, init_decode_state, \
    init_quantized_params, prefill
from repro.quant.qtensor import QuantizedTensor
from repro.serving.scheduler import live_cap_for

__all__ = ["build_targets", "lint_config", "lint_configs", "main",
           "forbidden_shapes_from_qparams"]

# Trace shapes: small token counts keep tracing fast; weight/block shapes
# (what the rules actually measure) come from the config, not from these.
_PREFILL_S = 32
_ADMIT_B = 2
_DECODE_B = 8
_DECODE_CHUNK = 4
_DECODE_SLOTS = 64
# the sharded targets' abstract mesh width (matches the CI cluster leg's
# simulated host-device count)
_SHARD_N = 4


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def _mix_cfg(cfg: ModelConfig, mix: str) -> ModelConfig:
    pol = cfg.dymoe
    if mix == "4/0":
        pol = dataclasses.replace(pol, low_bits=0)
    elif mix != "mixed":
        raise ValueError(f"unknown bit mix {mix!r}")
    return dataclasses.replace(cfg, dymoe=pol)


def _mix_label(cfg: ModelConfig) -> str:
    return f"{cfg.dymoe.high_bits}/{cfg.dymoe.low_bits}"


def _abstract_state(cfg: ModelConfig) -> Tuple[Any, Any]:
    """(params, qparams) as ShapeDtypeStruct pytrees — full size, 0 bytes
    — as the engine holds them: no dense routed experts beside the packed
    store."""
    return jax.eval_shape(
        lambda: init_quantized_params(cfg, jax.random.PRNGKey(0)))


def forbidden_shapes_from_qparams(qparams) -> frozenset:
    """Dense dequantized shapes of every quantized leaf, in both matmul
    orientations, at both the stacked-layers view and the per-layer slice
    the scan body sees."""
    shapes = set()
    leaves = jax.tree_util.tree_leaves(
        qparams, is_leaf=lambda v: isinstance(v, QuantizedTensor))
    for q in leaves:
        if not isinstance(q, QuantizedTensor):
            continue
        lead = tuple(q.packed.shape[:-2])
        n = q.packed.shape[-2]
        for ld in (lead, lead[1:]):     # stacked (L, ...) and per-layer
            shapes.add(ld + (n, q.k))
            shapes.add(ld + (q.k, n))
    return frozenset(shapes)


def _trace(fn, *avals):
    """make_jaxpr under the forced-Pallas serving path."""
    with force_impl("pallas"):
        return jax.make_jaxpr(fn)(*avals)


def _trace_prefill(cfg, params, qparams):
    toks = _sds((1, _PREFILL_S), jnp.int32)

    def f(p, q, tok):
        return prefill(p, cfg, tok, qparams=q, cache_slots=_DECODE_SLOTS)

    return _trace(f, params, qparams, toks)


def _admission_supported(cfg: ModelConfig) -> bool:
    # mirrors scheduler._can_batch_admissions: attention archs only, no
    # weight-shared hybrid blocks, no sliding-window ring caches
    return (cfg.block_kinds()[0] in ("attn_dense", "attn_moe")
            and not cfg.shared_attn_every and cfg.sliding_window is None)


def _trace_admission(cfg, params, qparams):
    toks = _sds((_ADMIT_B, _PREFILL_S), jnp.int32)
    lengths = _sds((_ADMIT_B,), jnp.int32)
    caps = _sds((_ADMIT_B,), jnp.int32)

    def f(p, q, tok, ln, rc):
        return prefill(p, cfg, tok, qparams=q, cache_slots=_DECODE_SLOTS,
                       lengths=ln, row_local=True, row_capacities=rc)

    return _trace(f, params, qparams, toks, lengths, caps)


def _trace_decode_chunk(cfg, params, qparams):
    b = _DECODE_B
    caches = jax.eval_shape(
        lambda: init_decode_state(cfg, b, _DECODE_SLOTS))
    toks = _sds((b,), jnp.int32)
    done = _sds((b,), jnp.bool_)
    counts = _sds((b,), jnp.int32)

    def f(p, q, tok, cch, dn, em, lim, eos):
        return decode_many_batched(
            p, cfg, tok, cch, num_steps=_DECODE_CHUNK, done=dn,
            n_emitted=em, limits=lim, eos_tokens=eos, qparams=q,
            live_cap=live_cap_for(b, b))

    return _trace(f, params, qparams, toks, caches, done, counts, counts,
                  counts)


def _abstract_mesh():
    """A (1, _SHARD_N) ("data", "model") mesh with NO devices behind it:
    ``AbstractMesh`` shardings are legal ``jax.jit`` ``in_shardings`` and
    trace under ``make_jaxpr``, so full-size configs lint their
    partitioned programs on any backend — same zero-allocation property
    as the rest of the linter."""
    from jax.sharding import AbstractMesh
    return AbstractMesh((1, _SHARD_N), ("data", "model"))


def _trace_sharded(cfg, params, qparams, f, extra_avals, extra_shardings):
    """Trace ``f(ep_cfg, params, qparams, *extras)`` jitted with
    expert-parallel param/qparam shardings over the abstract mesh;
    ``ep_cfg`` carries that mesh as ``expert_mesh``, as an
    expert-parallel engine's programs do, so the MoE layers run their
    kernels under shard_map over the expert axis."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.sharding.partition import param_shardings

    mesh = _abstract_mesh()
    repl = NamedSharding(mesh, P())
    in_sh = (param_shardings(params, mesh, expert_parallel=True),
             param_shardings(qparams, mesh, expert_parallel=True),
             *(repl if s is None else s(mesh)
               for s in extra_shardings))
    jf = jax.jit(partial(f, dataclasses.replace(cfg, expert_mesh=mesh)),
                 in_shardings=in_sh)
    return _trace(jf, params, qparams, *extra_avals)


def _trace_prefill_ep(cfg, params, qparams):
    toks = _sds((1, _PREFILL_S), jnp.int32)

    def f(ep_cfg, p, q, tok):
        return prefill(p, ep_cfg, tok, qparams=q,
                       cache_slots=_DECODE_SLOTS)

    return _trace_sharded(cfg, params, qparams, f, (toks,), (None,))


def _trace_decode_chunk_ep(cfg, params, qparams):
    from repro.sharding.partition import cache_shardings

    b = _DECODE_B
    caches = jax.eval_shape(
        lambda: init_decode_state(cfg, b, _DECODE_SLOTS))
    toks = _sds((b,), jnp.int32)
    done = _sds((b,), jnp.bool_)
    counts = _sds((b,), jnp.int32)

    def f(ep_cfg, p, q, tok, cch, dn, em, lim, eos):
        return decode_many_batched(
            p, ep_cfg, tok, cch, num_steps=_DECODE_CHUNK, done=dn,
            n_emitted=em, limits=lim, eos_tokens=eos, qparams=q,
            live_cap=live_cap_for(b, b))

    return _trace_sharded(
        cfg, params, qparams, f,
        (toks, caches, done, counts, counts, counts),
        (None, lambda m: cache_shardings(caches, m, expert_parallel=True),
         None, None, None, None))


def build_targets(name: str, cfg: ModelConfig, *,
                  mixes: Sequence[str] = ("mixed", "4/0"),
                  ) -> List[LintTarget]:
    """Every lint target for one config: traced jaxpr targets per phase ×
    bit mix, plus the accounting-only retrace target. Trace failures
    become error findings via a LintTarget carrying ``trace_error``."""
    targets: List[LintTarget] = []
    seen_mix = set()
    for mix in mixes:
        mcfg = _mix_cfg(cfg, mix)
        label = _mix_label(mcfg)
        if label in seen_mix:   # a "4/0"-native config: one real mix
            continue
        seen_mix.add(label)
        params, qparams = _abstract_state(mcfg)
        forbidden = forbidden_shapes_from_qparams(qparams)
        phases = [("prefill", _trace_prefill)]
        if _admission_supported(mcfg):
            phases.append(("admission", _trace_admission))
        phases.append(("decode_chunk", _trace_decode_chunk))
        if mcfg.is_moe:
            # expert-parallel partitioned traces (abstract mesh): the
            # structural contract must survive GSPMD sharding — the
            # serving tier runs exactly these programs on real meshes
            phases.append(("prefill_ep", _trace_prefill_ep))
            phases.append(("decode_chunk_ep", _trace_decode_chunk_ep))
        for phase, tracer in phases:
            tname = f"{name}/{label}/{phase}"
            try:
                jaxpr = tracer(mcfg, params, qparams)
            except Exception as e:  # noqa: BLE001 - reported as finding
                targets.append(LintTarget(
                    name=tname, cfg=mcfg, phase=phase,
                    trace_error=f"{type(e).__name__}: {e}"))
                continue
            targets.append(LintTarget(
                name=tname, cfg=mcfg, phase=phase, jaxpr=jaxpr,
                fused=True, forbidden_shapes=forbidden))
    targets.append(LintTarget(
        name=f"{name}/scheduler/retrace", cfg=cfg, phase="retrace",
        slots=_DECODE_B, ladder=live_cap_for))
    return targets


def lint_config(name: str, cfg: ModelConfig, *,
                mixes: Sequence[str] = ("mixed", "4/0"),
                only_rules: Optional[Sequence[str]] = None,
                ) -> Tuple[int, List[Finding]]:
    """(target count, findings) for one config."""
    findings: List[Finding] = []
    targets = build_targets(name, cfg, mixes=mixes)
    for t in targets:
        if t.trace_error is not None:
            findings.append(Finding(
                rule="trace-error", severity="error", target=t.name,
                message=f"tracing the {t.phase} program failed: "
                        f"{t.trace_error}"))
            continue
        findings.extend(run_rules(t, only=only_rules))
    return len(targets), findings


def lint_configs(names: Sequence[str], *,
                 only_rules: Optional[Sequence[str]] = None,
                 progress=None) -> Dict[str, Any]:
    """Lint a set of configs into the JSON-able report structure."""
    report: Dict[str, Any] = {
        "version": 1,
        "rules": sorted(RULES),
        "configs": {},
        "findings": [],
    }
    n_targets = 0
    for name in names:
        cfg = get_config(name)
        count, findings = lint_config(name, cfg, only_rules=only_rules)
        n_targets += count
        errs = sum(f.severity == "error" for f in findings)
        report["configs"][name] = {
            "targets": count, "errors": errs,
            "warnings": sum(f.severity == "warning" for f in findings),
        }
        report["findings"].extend(f.to_json() for f in findings)
        if progress is not None:
            progress(name, count, errs)
    report["summary"] = {
        "configs": len(report["configs"]),
        "targets": n_targets,
        "errors": sum(1 for f in report["findings"]
                      if f["severity"] == "error"),
        "warnings": sum(1 for f in report["findings"]
                        if f["severity"] == "warning"),
    }
    return report


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="Jaxpr invariant linter over the shipped configs.")
    ap.add_argument("--config", action="append", default=None,
                    metavar="NAME", help="lint this config (repeatable); "
                    "default: every entry in the registry")
    ap.add_argument("--smoke", action="store_true",
                    help=f"edge-config subset: {ANALYSIS_SMOKE_CONFIGS}")
    ap.add_argument("--rules", default=None, metavar="R1,R2",
                    help="comma-separated rule-id filter")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the JSON report here")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress the per-config progress lines")
    args = ap.parse_args(argv)

    names = args.config or (list(ANALYSIS_SMOKE_CONFIGS) if args.smoke
                            else list(ARCH_IDS))
    only = args.rules.split(",") if args.rules else None
    unknown = set(only or ()) - set(RULES)
    if unknown:
        ap.error(f"unknown rules {sorted(unknown)}; "
                 f"available: {sorted(RULES)}")

    def progress(name: str, count: int, errs: int) -> None:
        if not args.quiet:
            status = "ok" if not errs else f"{errs} error(s)"
            print(f"[lint] {name}: {count} targets, {status}")

    report = lint_configs(names, only_rules=only, progress=progress)

    if args.json:
        with open(args.json, "w") as fh:
            json.dump(report, fh, indent=2, sort_keys=True)
    for f in report["findings"]:
        print(f"{f['severity'].upper()} {f['rule']} @ {f['target']} "
              f"[{f['provenance'] or '<top>'}]: {f['message']}",
              file=sys.stderr)
    s = report["summary"]
    print(f"[lint] {s['configs']} configs / {s['targets']} targets: "
          f"{s['errors']} errors, {s['warnings']} warnings")
    return 1 if s["errors"] else 0


if __name__ == "__main__":
    raise SystemExit(main())
