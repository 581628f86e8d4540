"""A running sum or maximum along a vector, in blocks.

Rows sorted by group need no scatter to be summed: a group's integer
sum is the running sum at its end less the running sum at its start.
The one-chip sort core (`ops/hash_aggregate.py: _SegOps.sum`) and the
mesh group-by (`parallel/sharded.py: _reduce_sorted`) both read their
integer sums so, through this one scan."""

import jax
import jax.numpy as jnp
from jax import lax

_BLOCK = 512


def running_scan(x: jax.Array, scan) -> jax.Array:
    """`scan` (lax.cumsum, lax.cummax) along a vector, as runs of
    _BLOCK with the runs' totals scanned in turn: the chip's compiler
    takes a second over this where it takes minutes over one scan of
    400,000 `i64` (177 s for `jnp.cumsum`, 54 s and 33 MB of code for
    an `associative_scan`)."""
    n = x.shape[0]
    pad = (-n) % _BLOCK
    m = jnp.concatenate([x, jnp.zeros(pad, x.dtype)]).reshape(-1, _BLOCK)
    inner = scan(m, axis=1)
    ends = scan(inner[:, -1], axis=0)
    if scan is lax.cumsum:
        out = inner + (ends - inner[:, -1])[:, None]
    else:  # cummax of values that are never negative
        out = jnp.maximum(inner, jnp.concatenate(
            [jnp.zeros(1, x.dtype), ends[:-1]])[:, None])
    return out.reshape(-1)[:n]
