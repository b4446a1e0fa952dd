"""Occurrences of a set of characters over all bases: ``grep -o
'[<chars>]' | wc -l`` on each read, then an ``awk`` sum (paper
Listing 1).

Parameters: ``chars``. The plain NumPy count imports nothing of the
program. The number compared, ``wrong_answers``, is how many answers of
the window differ from their query's count. The limit is 0: the
configuration states exact counts.

The control sums the per-read counts in float32 on the device: exact
counts are the guarantee it breaks, since float32 holds every integer
only up to 2**24.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

NUMBER = "wrong_answers"
LIMIT = 0

#: Reads per block of a count.
_ROWS = 1 << 17


def char_histogram(seq: np.ndarray, chars: str) -> Dict[str, int]:
    """Occurrences of each character of ``chars`` over ``seq``."""
    return {c: sum(int(np.count_nonzero(seq[lo:lo + _ROWS] == ord(c)))
                   for lo in range(0, seq.shape[0], _ROWS))
            for c in set(chars)}


def answer(out: Any) -> int:
    (total,) = out
    return int(np.asarray(total).reshape(-1)[0])


def expected(data, specs: List[Dict[str, Any]]) -> List[int]:
    hist = char_histogram(data.seq, "".join(s["chars"] for s in specs))
    return [sum(hist[c] for c in set(s["chars"])) for s in specs]


def control(data, specs: List[Dict[str, Any]]) -> List[int]:
    """Per-read counts summed in float32 on the default device."""
    import jax
    import jax.numpy as jnp
    seq = data.seq
    total = jax.jit(lambda x: jnp.sum(x, dtype=jnp.float32))
    out = []
    for s in specs:
        codes = np.zeros(256, bool)
        codes[[ord(c) for c in s["chars"]]] = True
        per_read = np.zeros(seq.shape[0], np.float32)
        for lo in range(0, seq.shape[0], _ROWS * 4):
            per_read[lo:lo + _ROWS * 4] = codes[seq[lo:lo + _ROWS * 4]].sum(1)
        out.append(int(float(total(per_read))))
    return out


def number(pairs: Sequence[Tuple[int, int]]) -> int:
    return sum(int(got != want) for got, want in pairs)
