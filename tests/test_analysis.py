"""Invariant linter: every rule fires on a seeded known-bad fixture (with
correct provenance) and stays silent on the healthy path, and the shipped
edge configs lint clean end-to-end.

The fixtures deliberately commit each forbidden pattern — dense dequant
materialization inside a layer scan, one dispatch per precision region
claiming the fused budget, an oversized block override blowing VMEM, a traced f64
leak, an XLA-graph packed-code unpack, a host callback, a non-pow2
live_cap ladder — and assert the structured finding points at it."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import count_pallas_calls, iter_eqns
from repro.analysis.lint import forbidden_shapes_from_qparams, lint_config
from repro.analysis.rules import LintTarget, RULES, run_rules
from repro.configs import ANALYSIS_SMOKE_CONFIGS, get_config
from repro.kernels.quant_matmul.ops import expert_quant_matmul, \
    expert_quant_matmul_grouped, force_impl
from repro.models.config import DyMoEPolicy, ModelConfig
from repro.models.layers.moe import init_moe, moe_apply_rows, quantize_moe
from repro.quant import MixedPrecisionWeights, mixed_precision_matmul
from repro.serving.scheduler import live_cap_for


def _cfg(low_bits=2):
    return ModelConfig(
        name="t", arch_type="moe", num_layers=1, d_model=32, vocab_size=64,
        num_heads=2, num_kv_heads=2, head_dim=16, num_experts=4,
        num_experts_per_tok=2, moe_d_ff=48, capacity_factor=2.0,
        dtype="float32", remat="none",
        dymoe=DyMoEPolicy(low_bits=low_bits, group_size=16))


def _target(cfg, jaxpr, phase="decode_chunk", **kw):
    return LintTarget(name=f"fixture/{phase}", cfg=cfg, phase=phase,
                      jaxpr=jaxpr, **kw)


def _expert_setup(seed=0):
    cfg = _cfg()
    rng = jax.random.PRNGKey(seed)
    w = jax.random.normal(rng, (cfg.num_experts, cfg.d_model,
                                cfg.expert_d_ff), jnp.float32)
    mp = MixedPrecisionWeights.build(w, 4, 2, 16)
    crit = jnp.asarray([True, False, True, False])
    x = jax.random.normal(rng, (cfg.num_experts, 8, cfg.d_model),
                          jnp.float32)
    return cfg, mp, crit, x


# ------------------------------------------------------- no-dense-dequant


def test_no_dense_dequant_fires_on_materialize_with_scan_provenance():
    """The deliberate dequant materialization (``materialize=True``)
    inside a layer scan: the rule must fire and the finding's provenance
    must name the enclosing scan."""
    cfg, mp, crit, x = _expert_setup()

    def body(carry, _):
        y = mixed_precision_matmul(x, mp, crit, materialize=True,
                                   out_dtype=jnp.float32)
        return carry, y

    jaxpr = jax.make_jaxpr(
        lambda c: jax.lax.scan(body, c, None, length=2))(jnp.zeros(()))
    findings = run_rules(_target(cfg, jaxpr), only=["no-dense-dequant"])
    assert findings, "dense dequant materialization not caught"
    f = findings[0]
    assert f.rule == "no-dense-dequant" and f.severity == "error"
    assert f.provenance.startswith("scan"), f.provenance
    assert str((cfg.num_experts, cfg.d_model, cfg.expert_d_ff)) in f.message \
        or str((cfg.num_experts, cfg.expert_d_ff, cfg.d_model)) in f.message


def test_no_dense_dequant_clean_on_packed_path():
    cfg, mp, crit, x = _expert_setup()
    with force_impl("pallas"):
        jaxpr = jax.make_jaxpr(
            lambda xi: mixed_precision_matmul(xi, mp, crit,
                                              out_dtype=jnp.float32))(x)
    assert not run_rules(_target(cfg, jaxpr), only=["no-dense-dequant"])


# ------------------------------------------------- pallas-dispatch-budget


def test_dispatch_budget_fires_on_dual_path_claiming_fused():
    """The extra-dispatch fixture: one grouped kernel per precision region
    for each of the three expert matmuls launches 6 kernels; the MoE
    budget (3) must fail with both counts in the message, and the
    row-local layer, both regions in each dispatch, must pass it."""
    cfg = _cfg()
    p = init_moe(cfg, jax.random.PRNGKey(0), jnp.float32)
    qw = quantize_moe(p, cfg)
    cap = 8
    x = jax.random.normal(jax.random.PRNGKey(1), (cap, cfg.d_model),
                          jnp.float32)
    crit = jax.random.bernoulli(jax.random.PRNGKey(2), 0.5,
                                (cap, cfg.num_experts))
    xb = jax.random.normal(jax.random.PRNGKey(3),
                           (cfg.num_experts, 2 * cap, cfg.d_model),
                           jnp.float32)

    def per_region(xb):
        def mm(name, h):
            return jnp.concatenate([expert_quant_matmul_grouped(
                h[:, rows], MixedPrecisionWeights(high=qt, low=None),
                cap_hi=cap, out_dtype=h.dtype)
                for qt, rows in ((qw[name].high, slice(0, cap)),
                                 (qw[name].low, slice(cap, None)))], axis=1)
        return mm("w_down", jax.nn.silu(mm("w_gate", xb)) * mm("w_up", xb))

    with force_impl("pallas"):
        dual = jax.make_jaxpr(per_region)(xb)
        fused = jax.make_jaxpr(
            lambda xi: moe_apply_rows(p, cfg, xi, crit, qweights=qw)[0])(x)
    assert count_pallas_calls(dual) == 6
    findings = run_rules(_target(cfg, dual), only=["pallas-dispatch-budget"])
    assert len(findings) == 1
    assert "6" in findings[0].message and "3" in findings[0].message

    assert not run_rules(_target(cfg, fused),
                         only=["pallas-dispatch-budget"])


# ------------------------------------------------------------------ vmem


def test_vmem_footprint_fires_on_oversized_block_override():
    """A block_m/n/k override whose x tile alone is 32 MiB (2x budget,
    4x double-buffered) — caught from block shapes, zero bytes
    allocated (weights built with eval_shape)."""
    cfg = _cfg()
    e, m, k, n = 2, 1024, 8192, 4096
    mp = jax.eval_shape(lambda: MixedPrecisionWeights.build(
        jnp.zeros((e, k, n), jnp.float32), 4, 2, 64))
    x = jax.ShapeDtypeStruct((e, m, k), jnp.float32)

    def f(xa, mpa):
        return expert_quant_matmul(xa, mpa, jnp.ones((e,), bool),
                                   impl="pallas", block_m=m, block_n=n,
                                   block_k=k)

    jaxpr = jax.make_jaxpr(f)(x, mp)
    findings = run_rules(_target(cfg, jaxpr), only=["vmem-footprint"])
    assert findings and findings[0].rule == "vmem-footprint"
    assert "MiB" in findings[0].message

    # kernel-internal eqns exist and are flagged as such by the walker
    assert any(s.in_kernel for s in iter_eqns(jaxpr))

    def g(xa, mpa):  # the shipped default tiles: fits comfortably
        return expert_quant_matmul(xa, mpa, jnp.ones((e,), bool),
                                   impl="pallas")

    assert not run_rules(_target(cfg, jax.make_jaxpr(g)(x, mp)),
                         only=["vmem-footprint"])


# ------------------------------------------------------- dtype-discipline


def test_dtype_discipline_fires_on_traced_f64_leak():
    cfg = _cfg()
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(
            lambda v: (v.astype(jnp.float64) * 2.0).sum()
        )(jnp.zeros((4,), jnp.float32))
    findings = run_rules(
        _target(cfg, jaxpr, phase="prefill", packed_upcast_threshold=1 << 30),
        only=["dtype-discipline"])
    assert findings and "f64" in findings[0].message


def test_dtype_discipline_fires_on_packed_upcast_outside_kernel():
    cfg = _cfg()
    packed = jnp.zeros((4, 48, 16), jnp.uint8)   # a packed-codes buffer
    jaxpr = jax.make_jaxpr(lambda pk: pk.astype(jnp.float32).sum())(packed)
    findings = run_rules(
        _target(cfg, jaxpr, packed_upcast_threshold=1024),
        only=["dtype-discipline"])
    assert findings and "packed codes" in findings[0].message

    # the same widening INSIDE a pallas kernel body is the allowlisted
    # unpack path — the fused expert matmul trace must stay clean
    _, mp, crit, x = _expert_setup()
    with force_impl("pallas"):
        kj = jax.make_jaxpr(
            lambda xi: mixed_precision_matmul(xi, mp, crit,
                                              out_dtype=jnp.float32))(x)
    assert not run_rules(_target(cfg, kj, packed_upcast_threshold=256),
                         only=["dtype-discipline"])


# -------------------------------------------------------------- host-sync


def test_host_sync_fires_on_callback_in_decode_chunk():
    cfg = _cfg()

    def f(v):
        return jax.pure_callback(
            lambda a: a, jax.ShapeDtypeStruct(v.shape, v.dtype), v)

    jaxpr = jax.make_jaxpr(f)(jnp.zeros((4,), jnp.float32))
    findings = run_rules(_target(cfg, jaxpr), only=["host-sync"])
    assert findings and "pure_callback" in findings[0].message
    assert findings[0].primitive == "pure_callback"


# ---------------------------------------------------------- retrace-budget


def test_retrace_budget_fires_on_identity_ladder():
    """A ladder that compiles one variant per live count (the pre-PR-7
    failure mode) busts both the pow2 shape and the log2(B)+1 count."""
    cfg = _cfg()
    bad = LintTarget(name="fixture/retrace", cfg=cfg, phase="retrace",
                     slots=8, ladder=lambda n, b: n)
    findings = run_rules(bad, only=["retrace-budget"])
    assert len(findings) == 2
    assert any("non-power-of-two" in f.message for f in findings)
    assert any("log2(B)+1" in f.message for f in findings)

    good = dataclasses.replace(bad, ladder=live_cap_for)
    assert not run_rules(good, only=["retrace-budget"])


# ------------------------------------------------------------ end to end


def test_rule_registry_ships_the_contract():
    assert {"no-dense-dequant", "pallas-dispatch-budget", "vmem-footprint",
            "dtype-discipline", "host-sync", "retrace-budget"} \
        <= set(RULES)


def test_forbidden_shapes_cover_both_views():
    cfg, mp, _, _ = _expert_setup()
    shapes = forbidden_shapes_from_qparams({"w": mp})
    e, dm, dff = cfg.num_experts, cfg.d_model, cfg.expert_d_ff
    assert (e, dm, dff) in shapes and (e, dff, dm) in shapes


@pytest.mark.parametrize("name", ANALYSIS_SMOKE_CONFIGS)
def test_shipped_edge_configs_lint_clean(name):
    """The sweep: every shipped edge config passes every rule on every
    traced phase × bit mix (the full registry is swept by
    ``python -m repro.analysis``; CI runs this subset per push)."""
    count, findings = lint_config(name, get_config(name))
    assert count >= 5
    assert not findings, [f.to_json() for f in findings]
