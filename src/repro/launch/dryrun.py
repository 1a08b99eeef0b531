import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (architecture × input shape × mesh)
combination lowers AND compiles under the production sharding, and extract
memory / cost / collective data for the roofline analysis.

MUST be imported before any other jax-touching module executes jax device
init — hence the XLA_FLAGS lines above everything else (and no
``from __future__`` import in this module for the same reason).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmoe-1b-7b \
      --shape decode_32k [--multi-pod] [--expert-parallel]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Results are printed and appended as JSON lines to
experiments/dryrun/<mesh>.jsonl for EXPERIMENTS.md §Dry-run / §Roofline.
"""
import argparse
import dataclasses
import json
import time
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.models.config import ModelConfig
from repro.models.model import (
    decode_step,
    drop_dense_experts,
    init_decode_state,
    init_params,
    loss_fn,
    prefill,
    quantize_model,
)
from repro.roofline.analysis import (
    HW,
    collective_bytes_from_hlo,
    model_flops_estimate,
    roofline_terms,
)
from repro.sharding.partition import (
    batch_spec,
    cache_shardings,
    guard_spec,
    param_shardings,
)


def _guarded(mesh, spec: P, struct) -> NamedSharding:
    return NamedSharding(mesh, guard_spec(spec, struct.shape, mesh))
from repro.training.optimizer import AdamW, constant_lr

# input shapes assigned to this paper
SHAPES: Dict[str, Dict[str, Any]] = {
    "train_4k": dict(seq_len=4096, global_batch=256, phase="train"),
    "prefill_32k": dict(seq_len=32768, global_batch=32, phase="prefill"),
    "decode_32k": dict(seq_len=32768, global_batch=128, phase="decode"),
    "long_500k": dict(seq_len=524288, global_batch=1, phase="decode"),
}

LONG_CONTEXT_WINDOW = 8192  # sliding window used by attention archs @500k


def shape_adapted_config(cfg: ModelConfig, shape: str) -> ModelConfig:
    """long_500k requires sub-quadratic attention: attention archs switch to
    the implemented sliding-window ring cache (DESIGN.md §5); SSM archs run
    natively. Training drops DyMoE (it is an inference-time technique)."""
    if shape == "long_500k" and cfg.has_attention:
        cfg = dataclasses.replace(cfg, sliding_window=LONG_CONTEXT_WINDOW)
    return cfg


def strip_expert_weights(params_tree, cfg: ModelConfig):
    """Serving keeps experts ONLY in the quantized store (the paper's whole
    point); drop the bf16 masters from the serve-step inputs."""
    kind = cfg.block_kinds()[0]
    if kind == "attn_moe":
        return drop_dense_experts(params_tree)
    params_tree = dict(params_tree)
    layers = dict(params_tree["layers"])
    if kind == "attn_dense":
        layers["mlp"] = {}
    else:
        layers["ssm"] = {k: v for k, v in layers["ssm"].items()
                         if k not in ("in_proj", "out_proj")}
    params_tree["layers"] = layers
    return params_tree


# ----------------------------------------------------------------- builders


def build_specs(cfg: ModelConfig, shape: str, mesh,
                expert_parallel: bool = False, opts: tuple = ()):
    """ShapeDtypeStruct stand-ins + shardings for one (arch, shape).

    opts: perf levers from §Perf hillclimbing —
      "attn_skip"  causal chunk skipping in prefill/train attention
      "bf16_attn"  bf16 qk/pv einsums (halves KV-read bytes)
      "zero1"      shard optimizer moments over the data axis
      "seq_acts"   sequence-shard the residual carry (remat footprint)
    """
    info = SHAPES[shape]
    s, b, phase = info["seq_len"], info["global_batch"], info["phase"]
    cfg = shape_adapted_config(cfg, shape)
    if "attn_skip" in opts:
        cfg = dataclasses.replace(cfg, attn_causal_skip=True)
    if "bf16_attn" in opts:
        cfg = dataclasses.replace(cfg, attn_compute_dtype="bfloat16")
    if "seq_acts" in opts:
        cfg = dataclasses.replace(cfg, act_seq_shard=True)
    if "dymoe_40" in opts:  # the paper's 4/0 policy: skip sub-critical
        cfg = dataclasses.replace(
            cfg, dymoe=dataclasses.replace(cfg.dymoe, low_bits=0))
    if "local_dispatch" in opts and cfg.is_moe:
        axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
        shards = 1
        for a in axes:
            shards *= mesh.shape[a]
        cfg = dataclasses.replace(cfg, moe_dispatch_shards=shards,
                                  moe_dispatch_axes=axes)
    key = jax.random.PRNGKey(0)

    params = jax.eval_shape(lambda k: init_params(cfg, k), key)
    p_shard = param_shardings(params, mesh, expert_parallel=expert_parallel)
    b_axes = batch_spec(mesh)

    if phase == "train":
        opt = AdamW(lr=constant_lr(1e-4))
        opt_state = jax.eval_shape(opt.init, params)
        if "zero1" in opts:
            from repro.sharding.partition import zero1_shardings
            o_shard = zero1_shardings(opt_state, mesh,
                                      expert_parallel=expert_parallel)
        else:
            o_shard = param_shardings(opt_state, mesh,
                                      expert_parallel=expert_parallel)
        batch = {
            "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b, s), jnp.int32),
        }
        batch_shard = jax.tree.map(
            lambda s: _guarded(mesh, P(b_axes, None), s), batch)

        def step(params, opt_state, batch):
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, cfg, batch)
            params, opt_state = opt.update(params, grads, opt_state)
            return params, opt_state, loss

        args = (params, opt_state, batch)
        shardings = (p_shard, o_shard, batch_shard)
        return cfg, step, args, shardings

    qparams = jax.eval_shape(lambda p: quantize_model(p, cfg), params)
    q_shard = param_shardings(qparams, mesh, expert_parallel=expert_parallel)
    sparams = strip_expert_weights(params, cfg)
    sp_shard = strip_expert_weights(p_shard, cfg)

    if phase == "prefill":
        if cfg.arch_type in ("vlm", "audio"):
            # frontend stub: precomputed patch/frame embeddings
            inp = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
            in_shard = _guarded(mesh, P(b_axes, None, None), inp)

            def step(params, qparams, embeds):
                return prefill(params, cfg, None, embeds=embeds,
                               qparams=qparams, cache_slots=s)
        else:
            inp = jax.ShapeDtypeStruct((b, s), jnp.int32)
            in_shard = _guarded(mesh, P(b_axes, None), inp)

            def step(params, qparams, tokens):
                return prefill(params, cfg, tokens, qparams=qparams,
                               cache_slots=s)

        args = (sparams, qparams, inp)
        shardings = (sp_shard, q_shard, in_shard)
        return cfg, step, args, shardings

    # decode: ONE new token against a seq_len-deep cache
    caches = jax.eval_shape(lambda: init_decode_state(cfg, b, s))
    c_shard = cache_shardings(caches, mesh)
    tokens = jax.ShapeDtypeStruct((b,), jnp.int32)
    t_shard = _guarded(mesh, P(b_axes), tokens)

    def step(params, qparams, tokens, caches):
        return decode_step(params, cfg, tokens, caches, qparams=qparams)

    args = (sparams, qparams, tokens, caches)
    shardings = (sp_shard, q_shard, t_shard, c_shard)
    return cfg, step, args, shardings


# ------------------------------------------------------------------- runner


def _compile_once(cfg0: ModelConfig, shape: str, mesh, expert_parallel: bool,
                  num_layers: Optional[int] = None, opts: tuple = (),
                  scan: bool = True):
    cfg_n = (dataclasses.replace(cfg0, num_layers=num_layers)
             if num_layers else cfg0)
    if not scan:
        cfg_n = dataclasses.replace(cfg_n, scan_layers=False)
    cfg, step, args, shardings = build_specs(cfg_n, shape, mesh,
                                             expert_parallel, opts)
    t0 = time.perf_counter()
    jitted = jax.jit(step, in_shardings=shardings)
    with mesh:  # with_sharding_constraint(PartitionSpec) needs mesh context
        lowered = jitted.lower(*args)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes_from_hlo(compiled.as_text())
    return dict(cfg=cfg, compiled=compiled, t_lower=t_lower,
                t_compile=t_compile,
                flops=float(cost.get("flops", 0.0)),
                bytes=float(cost.get("bytes accessed", 0.0)),
                coll=coll)


def _extrapolate(v_scan: float, v_unroll: float, l_probe: int, l_full: int
                 ) -> float:
    """cost_analysis counts a while-loop (scan) body ONCE regardless of
    trip count, so cost(scan@l) = outside + body while cost(unrolled@l) =
    outside + l·body. Solving:
        body  = (v_unroll - v_scan) / (l - 1)
        total = v_scan + (L_full - 1)·body
    """
    if l_probe <= 1:
        return v_unroll
    body = max(0.0, (v_unroll - v_scan) / (l_probe - 1))
    return v_scan + (l_full - 1) * body


def run_one(arch: str, shape: str, *, multi_pod: bool = False,
            expert_parallel: bool = False, hw: HW = HW(),
            save_dir: Optional[str] = "experiments/dryrun",
            verbose: bool = True, opts: tuple = ()) -> Dict[str, Any]:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    cfg0 = get_config(arch)
    info = SHAPES[shape]

    # 1) full-depth compile: THE dry-run proof + memory analysis
    full = _compile_once(cfg0, shape, mesh, expert_parallel, opts=opts)
    cfg, compiled = full["cfg"], full["compiled"]
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not implement it
        mem_d = {"error": str(e)}

    # 2) per-layer cost recovery: compile the SAME shallow depth scanned and
    #    unrolled; the difference isolates one layer body (cost_analysis
    #    counts a while body once regardless of trip count).
    l_probe = max(2, 2 * (cfg0.shared_attn_every or 1))
    l_probe = min(l_probe, cfg0.num_layers)
    p_scan = _compile_once(cfg0, shape, mesh, expert_parallel, l_probe,
                           opts=opts, scan=True)
    p_unr = _compile_once(cfg0, shape, mesh, expert_parallel, l_probe,
                          opts=opts, scan=False)
    lf = cfg0.num_layers
    flops = _extrapolate(p_scan["flops"], p_unr["flops"], l_probe, lf)
    bytes_ = _extrapolate(p_scan["bytes"], p_unr["bytes"], l_probe, lf)
    coll = {k: int(_extrapolate(p_scan["coll"][k], p_unr["coll"][k],
                                l_probe, lf))
            for k in p_scan["coll"]}
    terms = roofline_terms({"flops": flops, "bytes accessed": bytes_},
                           coll["total"] // n_chips, hw)

    tokens = (info["global_batch"] * info["seq_len"]
              if info["phase"] != "decode" else info["global_batch"])
    mf = model_flops_estimate(cfg, tokens=tokens, phase=info["phase"])
    hlo_flops_total = terms["flops"] * n_chips
    result = dict(
        arch=arch, shape=shape,
        mesh="2x16x16" if multi_pod else "16x16",
        chips=n_chips,
        expert_parallel=expert_parallel,
        opts=list(opts),
        phase=info["phase"],
        lower_s=round(full["t_lower"], 2),
        compile_s=round(full["t_compile"], 2),
        memory=mem_d,
        collectives=coll,
        model_flops=mf,
        useful_flops_ratio=(mf / hlo_flops_total if hlo_flops_total else 0.0),
        **{k: v for k, v in terms.items()},
    )
    if verbose:
        print(json.dumps(result, indent=2, default=str))
    if save_dir:
        os.makedirs(save_dir, exist_ok=True)
        tag = "ep_" if expert_parallel else ""
        if opts:
            tag += "opt-" + "-".join(sorted(opts)) + "_"
        fn = os.path.join(save_dir,
                          f"{tag}{'2x16x16' if multi_pod else '16x16'}.jsonl")
        with open(fn, "a") as f:
            f.write(json.dumps(result, default=str) + "\n")
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS + [a.replace("_", "-")
                                                  for a in ARCH_IDS])
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true",
                    help="all 10 assigned archs x 4 shapes")
    ap.add_argument("--include-paper", action="store_true",
                    help="also run mixtral-8x7b / qwen3-30b-a3b")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--expert-parallel", action="store_true")
    ap.add_argument("--opt", action="append", default=[],
                    choices=["attn_skip", "bf16_attn", "zero1", "seq_acts",
                             "local_dispatch", "dymoe_40"],
                    help="perf levers (repeatable); see §Perf hillclimb")
    ap.add_argument("--save-dir", default="experiments/dryrun")
    args = ap.parse_args()

    if args.all:
        failures = []
        archs = ARCH_IDS if args.include_paper else [
            a for a in ARCH_IDS if a not in ("mixtral_8x7b", "qwen3_30b_a3b")]
        for arch in archs:
            for shape in SHAPES:
                try:
                    r = run_one(arch, shape, multi_pod=args.multi_pod,
                                expert_parallel=args.expert_parallel,
                                save_dir=args.save_dir, verbose=False,
                                opts=tuple(args.opt))
                    print(f"OK   {arch:18s} {shape:12s} "
                          f"compile={r['compile_s']:7.1f}s "
                          f"dominant={r['dominant']}")
                except Exception as e:
                    failures.append((arch, shape, str(e)[:200]))
                    print(f"FAIL {arch:18s} {shape:12s} {e}")
        if failures:
            raise SystemExit(f"{len(failures)} dry-run failures")
        return
    assert args.arch and args.shape, "--arch/--shape or --all"
    run_one(args.arch.replace("-", "_").replace(".", "p"), args.shape,
            multi_pod=args.multi_pod, expert_parallel=args.expert_parallel,
            save_dir=args.save_dir, opts=tuple(args.opt))


if __name__ == "__main__":
    main()
