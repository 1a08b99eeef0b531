"""Generic jaxpr walker: the traversal every invariant rule shares.

Promoted and generalized from the ad-hoc ``_intermediate_avals`` /
``_subjaxprs`` / ``_count_pallas`` helpers that used to live in
``tests/test_kernels_expert_quant_matmul.py`` — the tests now import from
here, so the structural gates and the linter can never drift apart.

The walker recurses into every sub-jaxpr an equation carries in its params
(``scan``/``cond``/``while`` bodies, ``pjit``/``custom_*`` calls,
``pallas_call`` kernel bodies, …) without knowing the primitive zoo: any
param value that IS a (Closed)Jaxpr — or a list/tuple containing them, as
``cond`` branches are — is walked. Each visited equation is wrapped in an
:class:`EqnSite` carrying provenance: the chain of enclosing primitives,
the nesting depth, and whether the site is INSIDE a Pallas kernel body
(rules like dtype-discipline allowlist kernel-internal upcasts — the
unpack path is exactly the thing that must live in kernels and nowhere
else).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterator, List, Optional, Tuple

from jax.extend import core as jex_core

__all__ = ["EqnSite", "subjaxprs", "iter_eqns", "intermediate_avals",
           "count_primitive", "count_pallas_calls", "find_eqns"]


@dataclasses.dataclass(frozen=True)
class EqnSite:
    """One visited equation plus where it lives.

    path: chain of enclosing primitive names from the root, e.g.
      ``("scan", "pallas_call")`` for an eqn inside a Pallas kernel body
      that is itself inside a layer scan.
    in_kernel: True when any enclosing primitive is a ``pallas_call`` —
      i.e. the eqn is device-kernel-internal, not XLA-visible.
    """

    eqn: Any
    path: Tuple[str, ...]
    in_kernel: bool

    @property
    def depth(self) -> int:
        return len(self.path)

    def provenance(self) -> str:
        """Human-readable location for findings: ``scan/pallas_call``."""
        return "/".join(self.path) or "<top>"


def _as_jaxpr(v: Any) -> Optional[Any]:
    if isinstance(v, jex_core.ClosedJaxpr):
        return v.jaxpr
    if isinstance(v, jex_core.Jaxpr):
        return v
    return None


def subjaxprs(v: Any) -> List[Any]:
    """Every (open) jaxpr reachable from one eqn-param value.

    Handles the three shapes jaxprs hide in params: a bare Jaxpr, a
    ClosedJaxpr, and lists/tuples of either (``cond`` branches).
    """
    j = _as_jaxpr(v)
    if j is not None:
        return [j]
    if isinstance(v, (list, tuple)):
        out: List[Any] = []
        for item in v:
            out.extend(subjaxprs(item))
        return out
    return []


def iter_eqns(jaxpr: Any, *, into_kernels: bool = True
              ) -> Iterator[EqnSite]:
    """Depth-first walk over every eqn, recursing into sub-jaxprs.

    ``jaxpr`` may be a Jaxpr or ClosedJaxpr. ``into_kernels=False`` stops
    at ``pallas_call`` boundaries (the kernel body is a device-internal
    program — XLA-level rules usually want the outside view only).
    """
    root = _as_jaxpr(jaxpr)
    if root is None:
        raise TypeError(f"not a jaxpr: {type(jaxpr)!r}")

    def walk(jx: Any, path: Tuple[str, ...], in_kernel: bool
             ) -> Iterator[EqnSite]:
        for eqn in jx.eqns:
            name = eqn.primitive.name
            yield EqnSite(eqn=eqn, path=path, in_kernel=in_kernel)
            is_kernel = name == "pallas_call"
            if is_kernel and not into_kernels:
                continue
            for v in eqn.params.values():
                for sub in subjaxprs(v):
                    yield from walk(sub, path + (name,),
                                    in_kernel or is_kernel)

    yield from walk(root, (), False)


def intermediate_avals(jaxpr: Any, *, into_kernels: bool = False
                       ) -> List[Any]:
    """All eqn output avals, recursing into sub-jaxprs.

    Kernel bodies are excluded by default: refs inside a ``pallas_call``
    are not XLA-materialized buffers, and the no-dense-dequant contract is
    about what XLA allocates.
    """
    return [v.aval
            for site in iter_eqns(jaxpr, into_kernels=into_kernels)
            for v in site.eqn.outvars]


def find_eqns(jaxpr: Any, pred: Callable[[EqnSite], bool], *,
              into_kernels: bool = True) -> List[EqnSite]:
    return [s for s in iter_eqns(jaxpr, into_kernels=into_kernels)
            if pred(s)]


def count_primitive(jaxpr: Any, name: str) -> int:
    """Number of eqns binding primitive ``name``, recursing into
    sub-jaxprs. A scan body counts once — which is the point for dispatch
    budgets: it IS one dispatch per step."""
    return len(find_eqns(jaxpr, lambda s: s.eqn.primitive.name == name,
                         into_kernels=False))


def count_pallas_calls(jaxpr: Any) -> int:
    return count_primitive(jaxpr, "pallas_call")
