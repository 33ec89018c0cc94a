"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state; ``dryrun.py`` sets ``--xla_force_host_platform_device_count=512``
*before* importing anything else.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e pod mesh: 16×16 = 256 chips per pod; 2 pods = 512 chips.

    Axes: ``data`` (DP/FSDP/ZeRO), ``model`` (TP/EP), plus ``pod`` (plain DP
    across pods — gradients all-reduce over the DCI) in the multi-pod case.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_local_mesh(dp: int | None = None, tp: int | None = None):
    """Local-host mesh with axes ``("pod", "data", "model")``.

    Default (no arguments) keeps the historical shape (1, 1, N): every local
    device on the model axis, so existing single-host TP smoke tests run
    unchanged.  An explicit ``(dp, tp)`` requests a real 2-D mesh —
    ``dp × tp`` devices as (1, dp, tp) — which is what the serving stack's
    ShardPlan and the ``--mesh dpxtp`` CLI flags consume.  Either both or
    neither of ``dp``/``tp`` must be given; ``dp * tp`` may use a leading
    subset of the local devices but must not exceed them.
    """
    import numpy as np

    devs = np.array(jax.devices())
    if (dp is None) != (tp is None):
        raise ValueError("make_local_mesh: pass both dp and tp, or neither")
    if dp is None:
        return jax.sharding.Mesh(devs.reshape(1, 1, -1),
                                 ("pod", "data", "model"))
    dp, tp = int(dp), int(tp)
    if dp < 1 or tp < 1:
        raise ValueError(f"make_local_mesh: dp={dp} and tp={tp} must be >= 1")
    need = dp * tp
    if need > devs.size:
        raise ValueError(
            f"make_local_mesh: dp*tp = {dp}*{tp} = {need} exceeds the "
            f"{devs.size} local device(s); force host devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N or shrink "
            "the mesh")
    return jax.sharding.Mesh(devs[:need].reshape(1, dp, tp),
                             ("pod", "data", "model"))


# Hardware constants for the roofline model (TPU v5e per chip).
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW_PER_LINK = 50e9          # bytes/s per link (~45-50 GB/s on v5e)
