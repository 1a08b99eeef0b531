"""Host spans of the serving session, in the profiler's own trace.

Every span is a ``jax.profiler.TraceAnnotation`` named ``dymoe.<what>``,
so it lands in the same trace as the device's programs and on the same
clock; its stats ride as the annotation's keyword arguments. When no
trace is being taken a span records nothing and costs about a
microsecond. Stats that cost more than a lookup are computed only while
the profiler records (``jax.profiler.TraceAnnotation.is_enabled()``).

The spans (thread; stats):

* ``dymoe.step`` (stepper; ``boundary``): one ``step()`` call, a chunk
  boundary;
* ``dymoe.admit`` (stepper; ``wave``, ``rows``, ``longest_prompt``,
  ``queue_wait_ms_max``, ``scaled_after_dot``): one admission wave —
  prefill dispatch, first-token fetch, injection into the slot batch. The
  session's first wave of a latent-attention model also carries
  ``latent_cache_bytes``, the slot batch's whole latent cache
  (:func:`latent_cache_bytes`);
* ``dymoe.dispatch`` (stepper; ``chunk``, ``rows``, ``live_cap``,
  ``steps``, ``scaled_after_dot``, ``ctx_tokens``): the enqueue of one
  decode chunk; ``ctx_tokens`` (only while the profiler records, else 0)
  sums over the live rows the context the chunk's first step attends:
  prompt plus tokens emitted so far;
* ``dymoe.sync`` (stepper; ``chunk`` or ``wave``): a blocking device
  fetch — the boundary's done/emitted masks, a wave's first tokens;
* ``dymoe.replay_submit`` (stepper; ``depth``): handing a job to the
  replay worker; ``depth`` is the queue depth found, and a long span is
  backpressure;
* ``dymoe.replay`` (``dymoe-replay``; ``kind``, ``chunk`` or ``wave``,
  ``rows``): one replay job — telemetry fetch, orchestrator replay, token
  delivery. A chunk job also carries the counter of :func:`live_groups`
  as ``live_hi_groups``, ``live_lo_groups`` and ``kernel_weight_bytes``.

``scaled_after_dot`` is 1 where the program's grouped expert kernel
applies its group scales after the dot, 0 where it dequantizes the
weights first or runs no grouped kernel (a one-row wave's solo prefill):
the kernel's static choice for the program's shapes.
"""
from __future__ import annotations

from typing import Tuple

import jax
import numpy as np

__all__ = ["span", "group_bytes", "live_groups", "latent_cache_bytes"]

_PROJECTIONS = ("w_gate", "w_up", "w_down")


def span(what: str, **stats) -> jax.profiler.TraceAnnotation:
    """The host span ``dymoe.<what>`` with ``stats``."""
    return jax.profiler.TraceAnnotation(f"dymoe.{what}", **stats)


def group_bytes(qparams) -> Tuple[int, int]:
    """Packed bytes (codes plus scales of ``w_gate``, ``w_up`` and
    ``w_down``) of one (expert, precision) group of one layer, for the
    high and the low precision: what the grouped expert kernel streams for
    each group it finds live. Read from the stacked stores' shapes alone;
    the low figure is 0 where sub-critical experts are skipped ("x/0")."""
    moe = qparams["layers"]["moe"]
    lead = moe["w_gate"].high.packed.shape[:2]          # (L, E)
    groups = int(np.prod(lead))
    return tuple(sum(moe[name].nbytes(prec) for name in _PROJECTIONS)
                 // groups for prec in ("high", "low"))


def latent_cache_bytes(caches) -> int:
    """Bytes of every attention cache in a decode-state pytree (for a
    latent-attention model, the latents and rope keys of every slot and
    layer), from the shapes alone."""
    from repro.models.kv_cache import KVCache

    leaves = jax.tree_util.tree_leaves(
        caches, is_leaf=lambda c: isinstance(c, KVCache))
    return sum(c.k.nbytes + c.v.nbytes for c in leaves
               if isinstance(c, KVCache))


def live_groups(critical: np.ndarray, active: np.ndarray,
                skip_low: bool) -> Tuple[int, int]:
    """(high, low) (expert, precision) groups a decode chunk's grouped
    expert kernel calls found live, summed over steps and layers.

    ``critical`` / ``active`` are the chunk's (T, L, B, E) masks, zero on
    dead rows. Per (step, layer) an expert's high group is live when some
    row routes to it as Critical, its low group when some row routes to it
    as Sub-critical — exactly the kernel's ``counts > 0``, since a
    region's capacity is never below the live rows. ``skip_low``: the low
    groups are elided from the kernel's grid ("x/0")."""
    hi = int((active & critical).any(axis=2).sum())
    lo = 0 if skip_low else int((active & ~critical).any(axis=2).sum())
    return hi, lo
