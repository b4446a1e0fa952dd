"""Sort-based keyed sums for keys that no dense table can index.

The strategies of :mod:`repro.kernels.segment_reduce.ops` fold records
into a direct-indexed ``[num_keys]`` table, which needs a key space that
fits on the device (``4**12`` keys is 64 MiB a column, ``4**21`` would
be 16 TiB).  A two-word key (``[n, 2]`` 32-bit words, high then low:
canonical 21-mers, 64-bit ids) takes this path instead, whose output is
bounded by the records, not by the key space:

1. sort the rows on (high, low), the integer value columns and record
   counts carried along as sort operands;
2. mark each segment's end row (the next row's key differs), and take
   inclusive prefix sums of every column;
3. a second sort on the end rows' positions compacts the segment ends to
   the front, in ascending key order;
4. each segment's sum is its end's prefix sum less the previous end's.

Integer sums are exact: prefix sums wrap like the sums themselves, and
the wrap cancels in the difference (the reason this path sums integers
only; a float prefix difference would lose precision).  Rows outside
``valid`` take the largest key, a value of 0 and a count of 0 before the
sort, so they fold into no real key; the segment they form, if they form
one of their own, is the last, has count 0 and is dropped.
"""
from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class SortAggResult(NamedTuple):
    keys: jax.Array       # [n, 2] distinct keys, ascending, at the front
    values: Any           # pytree of [n, ...] summed values per key
    counts: jax.Array     # [n] int32 records folded into each key
    distinct: jax.Array   # int32 scalar: distinct keys (rows that hold one)


def check_integer_values(values: Any) -> None:
    """Raise unless every leaf of ``values`` is of an integer dtype."""
    bad = [str(np.dtype(leaf.dtype)) for leaf in jax.tree.leaves(values)
           if not np.issubdtype(np.dtype(leaf.dtype), np.integer)]
    if bad:
        raise TypeError(f"a two-word key's sorted keyed stage sums integer "
                        f"values only, got {bad}")


def sort_aggregate(keys: jax.Array, values: Any, counts: jax.Array,
                   valid: jax.Array) -> SortAggResult:
    """Sum the ``valid`` rows of ``values`` per distinct two-word key.

    ``keys``: ``[n, 2]`` 32-bit integers (high, low); ``values``: pytree
    of integer ``[n, ...]`` arrays; ``counts``: int32 ``[n]``, how many
    records each row already stands for (1 for raw records, a partial's
    count after an exchange); ``valid``: bool ``[n]``.  Rows from
    ``distinct`` on hold key 0, value 0 and count 0.
    """
    if keys.ndim != 2 or keys.shape[1] != 2:
        raise ValueError(f"sort_aggregate needs [n, 2] keys, got "
                         f"{keys.shape}")
    check_integer_values(values)
    n = keys.shape[0]
    if n == 0:
        return SortAggResult(keys, values, counts.astype(jnp.int32),
                             jnp.int32(0))
    top = jnp.asarray(jnp.iinfo(keys.dtype).max, keys.dtype)
    hi = jnp.where(valid, keys[:, 0], top)
    lo = jnp.where(valid, keys[:, 1], top)
    leaves, treedef = jax.tree.flatten(values)
    cols = [jnp.where(valid, counts, 0).astype(jnp.int32)]
    for leaf in leaves:
        flat = jnp.where(valid[:, None], leaf.reshape(n, -1), 0)
        cols.extend(flat[:, j] for j in range(flat.shape[1]))

    hi, lo, *cols = jax.lax.sort((hi, lo, *cols), num_keys=2,
                                 is_stable=False)
    end = jnp.concatenate([(hi[1:] != hi[:-1]) | (lo[1:] != lo[:-1]),
                           jnp.ones((1,), bool)])
    sums = [jnp.cumsum(c, dtype=c.dtype) for c in cols]
    pos = jnp.where(end, jnp.arange(n, dtype=jnp.int32), n)
    _, hi, lo, *sums = jax.lax.sort((pos, hi, lo, *sums), num_keys=1,
                                    is_stable=False)
    cols = [s - jnp.concatenate([jnp.zeros((1,), s.dtype), s[:-1]])
            for s in sums]
    segments = jnp.sum(end).astype(jnp.int32)
    distinct = segments - (cols[0][segments - 1] == 0).astype(jnp.int32)
    held = jnp.arange(n) < distinct
    out_keys = jnp.where(held[:, None], jnp.stack([hi, lo], axis=1), 0)
    cols = [jnp.where(held, c, 0) for c in cols]
    out_leaves, off = [], 1
    for leaf in leaves:
        width = math.prod(leaf.shape[1:])
        block = cols[off:off + width]
        off += width
        out_leaves.append(jnp.stack(block, axis=1).reshape(leaf.shape)
                          if leaf.ndim > 1 else block[0])
    return SortAggResult(keys=out_keys,
                         values=jax.tree.unflatten(treedef, out_leaves),
                         counts=cols[0], distinct=distinct)
