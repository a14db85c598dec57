"""The block-sparse output head the cells serve and train: its mask comes
from the configuration's ``pattern_seed`` alone (``counters.head_mask``),
its payload from the run's seed.  ``--seed`` changes values, never the
pattern, the live-block count or the plan built from it."""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np


def head_blocks(spec, mask: np.ndarray, key, dtype=jnp.bfloat16):
    """The live blocks' payload, drawn on the device from ``key`` and
    scaled for unit-variance logits."""
    hc = spec["head"]
    scale = (hc["density"] * mask.shape[1] * hc["block"]) ** -0.5
    return (jax.random.normal(key, (int(mask.sum()), hc["block"],
                                    hc["block"]), jnp.float32)
            * scale).astype(dtype)


def block_csr(mask: np.ndarray, blocks, block: int):
    """The program's BlockCSR over ``mask`` (row-major live order)."""
    from repro.core.csr import BlockCSR
    gm, gk = mask.shape
    rows, cols = np.nonzero(mask)
    row_ptr = np.zeros(gm + 1, np.int32)
    np.cumsum(np.bincount(rows, minlength=gm), out=row_ptr[1:])
    return BlockCSR(blocks=blocks, block_col=jnp.asarray(cols, jnp.int32),
                    block_row=jnp.asarray(rows, jnp.int32),
                    row_ptr=jnp.asarray(row_ptr),
                    shape=(gm * block, gk * block),
                    block_shape=(block, block))


def pattern_digest(mask: np.ndarray) -> str:
    return hashlib.sha256(np.packbits(mask).tobytes()
                          + str(mask.shape).encode()).hexdigest()[:16]
