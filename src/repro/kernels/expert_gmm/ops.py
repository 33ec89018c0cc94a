"""Public wrapper of the held-expert grouped product."""

from __future__ import annotations

from repro.kernels.platform import resolve_interpret

from . import kernel as _k
from .ref import expert_gmm_ref

TM = _k.TM


def expert_gmm(lhs, rhs, group_sizes, layer, *, interpret: bool | None = None):
    """``[m, k] x [G, E, k, n][layer]`` by row groups (see kernel.py); the
    Mosaic kernel on a TPU, the Pallas interpreter elsewhere."""
    return _k.expert_gmm(lhs, rhs, group_sizes, layer,
                         interpret=resolve_interpret(interpret))


__all__ = ["TM", "expert_gmm", "expert_gmm_ref"]
