"""Public wrapper of MLA's latent decode attention."""

from __future__ import annotations

from repro.kernels.platform import resolve_interpret

from . import kernel as _k
from .ref import mla_decode_ref

TK = _k.TK
slot_tiles = _k.slot_tiles
tile_rows = _k.tile_rows


def mla_decode(q_lat, q_rope, c_kv, k_rope, layer, pos, *, scale: float,
               block_rows: int = TK, interpret: bool | None = None):
    """One query token a slot against its layer of the latent stacks, over
    rows ``0 .. pos`` only (see kernel.py); the Mosaic kernel on a TPU, the
    Pallas interpreter elsewhere."""
    return _k.mla_decode(q_lat, q_rope, c_kv, k_rope, layer, pos, scale=scale,
                         block_rows=block_rows, interpret=resolve_interpret(interpret))


__all__ = ["TK", "mla_decode", "mla_decode_ref", "slot_tiles", "tile_rows"]
