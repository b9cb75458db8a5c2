"""A count sketch of a leaf: every element times a seeded random sign,
summed into ``WIDTH`` buckets. The distance between two leaves' sketches
estimates the norm of the leaves' difference (unbiased; a few per cent of
spread at this width) from ``WIDTH`` numbers a leaf, so the program's
gradient and the reference's can be compared element by element although
the two never share the device.

Why it is there: norms of a leaf differ between two precisions only to
second order in the rounding noise (||g + n||^2 = ||g||^2 + ||n||^2 for
zero-mean n), so the gap of norms that catches a fault (a leaf not moved,
half a batch) cannot tell bf16 from fp8. The sketch distance is first
order in the noise.
"""
from __future__ import annotations

import functools
import statistics

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights as W

WIDTH = 128


@functools.partial(jax.jit, static_argnames=("width",))
def sketch(x, key, scale=1.0, width=WIDTH):
    x = x.reshape(-1).astype(jnp.float32) * scale
    signs = jax.random.rademacher(key, x.shape, jnp.int8).astype(jnp.float32)
    return jnp.pad(x * signs, (0, (-x.size) % width)).reshape(-1, width).sum(0)


def leaf_key(seed: int, index: int):
    return jax.random.fold_in(W.seed_key(seed), 10_000 + index)


def worst_leaf(program: dict, reference: dict):
    """``(gap, leaf)``: the distance between the sketches over the norm of
    the reference's sketch (or the median leaf's, whichever is larger), by
    the worst leaf."""
    norms = {n: float(np.linalg.norm(v)) for n, v in reference.items()}
    floor = statistics.median(norms.values())
    gap, name = 0.0, ""
    for n, r in reference.items():
        g = float(np.linalg.norm(np.asarray(program[n]) - np.asarray(r))) \
            / max(norms[n], floor, 1e-30)
        if not g <= gap:
            gap, name = g, n
    return gap, name
