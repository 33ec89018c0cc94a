"""The comparison that decides ``correct``: served tokens against the reference.

For each sampled request the reference (the configuration module's plain
``jax.numpy`` forward, float32 with its products at ``highest`` precision,
its weights remade from the seed) runs once over ``prompt + served tokens``.
At every served position it gives the gap by which the served token's
reference logit lies below the reference's best logit there.  Greedy
decoding serves the argmax of the program's own logits, so the gap is 0
unless rounding in the program flipped a near tie.  Over the sample:

* ``max_logit_gap``: the widest gap;
* ``mean_logit_gap``: the mean gap over every served position;
* ``flip_share``: the share of served positions whose token is not the
  reference's argmax.

The control is the precision one step below what the configuration states.
Each configuration file states: weights, state, residual stream and every
elementwise operation in float32, matrix products at the TPU's default
precision (operands rounded to bfloat16, float32 accumulation).  The control
is the same reference with everything in bfloat16: weights, activations,
state, and the outputs of products.  At the same positions it reads the gap
of the token that the bfloat16 logits put first, and is judged by the same
limits as the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

ROWS_PER_HEAD_CALL = 1024
PAD_T = 256


def bf16_dot(a, b):
    """The control's matrix product: bfloat16 in, bfloat16 out."""
    return jnp.matmul(a, b, preferred_element_type=jnp.bfloat16)


def to_bf16(weights):
    return jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                        if jnp.issubdtype(a.dtype, jnp.floating) else a, weights)


def sample_requests(finished: list, seed: int, n: int) -> list:
    """``n`` finished requests drawn from the seed, always with the one that
    served the most tokens and the one with the longest prompt."""
    if not finished:
        return []
    by_out = max(finished, key=lambda r: (len(r.out_tokens), r.uid))
    by_prompt = max(finished, key=lambda r: (len(r.prompt), r.uid))
    chosen = {by_out.uid: by_out, by_prompt.uid: by_prompt}
    rest = sorted((r for r in finished if r.uid not in chosen), key=lambda r: r.uid)
    rng = np.random.default_rng(np.random.SeedSequence([abs(int(seed)), 11]))
    for i in rng.permutation(len(rest))[:max(0, n - len(chosen))]:
        chosen[rest[i].uid] = rest[i]
    return [chosen[k] for k in sorted(chosen)]


def _rows(sample, n_rows: int) -> tuple[np.ndarray, list[tuple[int, int]]]:
    """Token rows ``prompt + served[:-1]``, right-padded to a multiple of
    ``PAD_T`` and to ``n_rows`` rows, so that the reference compiles for a
    few shapes only (the forward is causal: padding never reaches a
    compared position)."""
    seqs = [list(r.prompt) + list(r.out_tokens[:-1]) for r in sample]
    T = -(-max(len(s) for s in seqs) // PAD_T) * PAD_T
    rows = np.zeros((max(n_rows, len(seqs)), T), np.int32)
    for i, s in enumerate(seqs):
        rows[i, :len(s)] = s
    spans = [(len(r.prompt) - 1, len(r.out_tokens)) for r in sample]
    return rows, spans


def _head_gaps(mod, sizes, weights, hid, pick_tokens, lo=None):
    """Gaps over hidden rows ``[N, D]`` (``N`` a multiple of
    ``ROWS_PER_HEAD_CALL``), the head applied one block of rows at a time.
    With ``lo = (weights, hidden)`` of the control, the picked token is the
    control's argmax instead of ``pick_tokens``."""
    out = []
    for a in range(0, hid.shape[0], ROWS_PER_HEAD_CALL):
        b = a + ROWS_PER_HEAD_CALL
        ref = mod.reference_logits(sizes, weights, hid[a:b])
        if lo is not None:
            pick = jnp.argmax(mod.reference_logits(sizes, lo[0], lo[1][a:b], mm=bf16_dot),
                              axis=-1)
        else:
            pick = pick_tokens[a:b]
        got = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
        out.append(np.asarray(jnp.max(ref, axis=-1) - got, np.float64))
    return np.concatenate(out)


def served_gaps(mod, sizes: dict, weights: dict, sample, *, n_rows: int = 0,
                control: bool = False) -> np.ndarray:
    """Per served token, the gap below the reference's best logit: of the
    served token, or with ``control`` of the bfloat16 control's argmax."""
    rows, spans = _rows(sample, n_rows)
    T = rows.shape[1]
    idx = np.concatenate([i * T + s + np.arange(n) for i, (s, n) in enumerate(spans)])
    n = idx.size
    pad = -(-n // ROWS_PER_HEAD_CALL) * ROWS_PER_HEAD_CALL - n
    idx = jnp.asarray(np.pad(idx, (0, pad)), jnp.int32)
    served = jnp.asarray(np.pad(np.concatenate([r.out_tokens for r in sample]), (0, pad)),
                         jnp.int32)
    take = lambda h: jnp.take(h.reshape(-1, h.shape[-1]), idx, axis=0)
    hid = take(mod.reference_hidden(sizes, weights, jnp.asarray(rows)))
    lo = None
    if control:
        w_lo = to_bf16(weights)
        lo = (w_lo, take(mod.reference_hidden(sizes, w_lo, jnp.asarray(rows), mm=bf16_dot)))
    return _head_gaps(mod, sizes, weights, hid, served, lo)[:n]


def gap_stats(gaps: np.ndarray) -> dict:
    """The numbers a sample's gaps are judged by (``None`` with no gaps)."""
    if not gaps.size:
        return {"max_logit_gap": None, "mean_logit_gap": None, "flip_share": None}
    return {"max_logit_gap": float(gaps.max()), "mean_logit_gap": float(gaps.mean()),
            "flip_share": float(np.mean(gaps > 0))}


def judge(stats: dict, n_tokens: int, failed: int, limits: dict) -> tuple[dict, bool]:
    """Each compared number beside its limit, and the verdict.  The gap
    numbers compared are those the cell gives a limit."""
    checks = {
        "failed_requests": {"value": failed, "limit": 0},
        "compared_tokens": {"value": n_tokens, "limit": limits["min_compared_tokens"],
                            "at_most": False},
    }
    for name, value in stats.items():
        if name in limits:
            checks[name] = {"value": value, "limit": limits[name]}
    correct = all(c["value"] is not None and (
        c["value"] <= c["limit"] if c.get("at_most", True) else c["value"] >= c["limit"])
        for c in checks.values())
    return checks, correct


__all__ = ["sample_requests", "served_gaps", "gap_stats", "judge"]
