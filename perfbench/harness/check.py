"""Pieces of the comparison that decides ``correct``, shared by the runners
and the control tool: the dense float32 copy of the block-sparse head, the
widest logit gap of served tokens under the plain reference, and the fp8
control: the reference with its weight matrices (one layer's at a time) and
the head rounded through float8_e4m3fn, one scale per array — the
precision step below bfloat16."""

from __future__ import annotations

from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def dense_from_blocks(blocks, mask: np.ndarray, block: int):
    """(gm*block, gk*block) float32 from the live blocks in row-major
    mask order."""
    gm, gk = mask.shape
    rows, cols = np.nonzero(mask)
    tiles = jnp.zeros((gm, gk, block, block), F32).at[rows, cols].set(
        blocks[:rows.size].astype(F32))
    return tiles.transpose(0, 2, 1, 3).reshape(gm * block, gk * block)


@jax.jit
def fp8_round(x):
    """x rounded through float8_e4m3fn with one scale for the array."""
    x = x.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _gap_fn(vocab: int):
    @jax.jit
    def gaps(hidden, head, pos, toks, valid, ctl_hidden, ctl_head):
        """Per served token: the reference's best logit minus the logit
        of the served token, and minus the logit of the token the control
        puts first.  hidden: (S, D); pos/toks/valid: (n,)."""
        rows = hidden[pos]
        ref = jnp.dot(rows, head.T, precision=HIGHEST)[:, :vocab]
        best = jnp.max(ref, -1)
        served = jnp.take_along_axis(ref, toks[:, None], -1)[:, 0]
        crow = ctl_hidden[pos]
        ctl = jnp.dot(crow, ctl_head.T, precision=HIGHEST)[:, :vocab]
        pick = jnp.argmax(ctl, -1)
        ctl_gap = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        return (jnp.where(valid, best - served, 0.0),
                jnp.where(valid, ctl_gap, 0.0))
    return gaps


def served_gaps(hidden_fn: Callable, weights, head_dense,
                seqs: Sequence[Dict], *, vocab: int, max_len: int,
                max_out: int, batch: int,
                control: bool = False) -> Dict[str, List[float]]:
    """Widest gap per request.  ``seqs``: dicts with ``prompt`` (ids) and
    ``served`` (ids).  The reference runs over prompt + served[:-1], padded
    at the end to ``max_len`` (causal, so padding changes nothing before
    it); served token j is scored at position ``len(prompt) - 1 + j``.

    ``hidden_fn(weights, tokens, cast)`` is the reference; with
    ``control`` it runs a second time with ``cast=fp8_round`` (every
    weight matrix, one layer at a time, and the head), over the same
    tokens."""
    fwd = jax.jit(lambda w, t: hidden_fn(w, t, None))
    ctl_fwd = jax.jit(lambda w, t: hidden_fn(w, t, fp8_round))
    gaps = _gap_fn(vocab)
    ctl_head = fp8_round(head_dense) if control else None
    out = {"program": [], "control": []}
    for i in range(0, len(seqs), batch):
        group = list(seqs[i:i + batch])
        toks = np.zeros((batch, max_len), np.int32)
        for j, s in enumerate(group):
            ids = np.concatenate([s["prompt"], s["served"][:-1]])
            toks[j, :ids.size] = ids
        hid = fwd(weights, jnp.asarray(toks))
        chid = ctl_fwd(weights, jnp.asarray(toks)) if control else hid
        for j, s in enumerate(group):
            n = len(s["served"])
            pos = np.zeros(max_out, np.int32)
            tk = np.zeros(max_out, np.int32)
            valid = np.zeros(max_out, bool)
            pos[:n] = len(s["prompt"]) - 1 + np.arange(n)
            tk[:n] = s["served"]
            valid[:n] = True
            g, cg = gaps(hid[j], head_dense, pos, tk, valid, chid[j],
                         ctl_head if control else head_dense)
            out["program"].append(float(jnp.max(g)))
            if control:
                out["control"].append(float(jnp.max(cg)))
    return out
