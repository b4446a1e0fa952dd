"""Standard container images (the "Docker Hub" of this repo).

Every image registers with an :class:`~repro.core.manifests.ImageManifest`:
a declarative contract carrying record schemas, a capacity transfer
function, reduce-monoid properties, and a typed command grammar.  The
``posix`` image's grammar covers the paper's Listing 1 commands
(``grep-count`` / ``awk-sum``) plus ``grep-chars`` for byte records; each
command dispatches to its own implementation — the central grammar
replaces the per-image ``shlex`` micro-parsers, so an unknown command or a
mistyped argument fails at *pull* time with the image's grammar in the
message, and the planner can type-check whole pipelines before tracing.
"""
from __future__ import annotations

import inspect
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.container import (ContainerOp, Partition, container_op,
                                  make_partition)
from repro.core.manifests import (ArgSpec, CommandSpec, ImageManifest,
                                  PRESERVE, SAME)
from repro.core.schema import Schema, bytes_record_schema, field


# ---------------------------------------------------------------------------
# posix: grep-count / grep-chars / awk-sum (Listing 1 micro-tools)
# ---------------------------------------------------------------------------

#: Single-leaf tuple of scalar records (any dtype) — the token stream the
#: Listing 1 integer pipeline flows through.
_SCALAR_RECORDS = Schema((field(None),))
#: One int32 count record — what the grep counters emit.
_COUNT_RECORDS = Schema((field(jnp.int32),))


def _grep_count(part: Partition, codes: Any = (), **kw: Any) -> Partition:
    """``grep -o '<codes>' | wc -l``: count records whose value is in a
    set of int token codes."""
    code_arr = jnp.asarray(list(codes), jnp.int32)
    (tokens,) = jax.tree.leaves(part.records)
    valid = part.mask()
    hit = jnp.isin(tokens, code_arr) & valid
    total = jnp.sum(hit).astype(jnp.int32)
    return make_partition((total[None],), jnp.int32(1))


def _grep_chars(part: Partition, chars: str = "", **kw: Any) -> Partition:
    """``grep -o '[<chars>]' | wc -l`` over byte records: count occurrences
    of any of the given characters inside each record's valid length."""
    codes = jnp.asarray([ord(c) for c in chars], jnp.uint8)
    data = part.records["data"]
    lens = part.records["len"]
    in_len = jnp.arange(data.shape[1])[None, :] < lens[:, None]
    valid = part.mask()[:, None]
    hit = jnp.isin(data, codes) & in_len & valid
    total = jnp.sum(hit).astype(jnp.int32)
    return make_partition((total[None],), jnp.int32(1))


def _awk_sum(part: Partition, **kw: Any) -> Partition:
    """``awk '{s+=$1} END {print s}'``: sum records to a single record."""
    (vals,) = jax.tree.leaves(part.records)
    valid = part.mask()
    s = jnp.sum(jnp.where(valid, vals, 0), axis=0)
    return make_partition((s[None],), jnp.int32(1))


POSIX_MANIFEST = ImageManifest(
    commands=(
        CommandSpec(
            "grep-count",
            args=(ArgSpec("codes", type=int, required=False, variadic=True),),
            fn=_grep_count,
            input_schema=_SCALAR_RECORDS,
            output_schema=_COUNT_RECORDS,
            out_capacity=1),
        CommandSpec(
            "grep-chars",
            args=(ArgSpec("chars", type=str),),
            fn=_grep_chars,
            input_schema=bytes_record_schema(),
            output_schema=_COUNT_RECORDS,
            out_capacity=1),
        CommandSpec(
            "awk-sum",
            fn=_awk_sum,
            output_schema=SAME,
            out_capacity=1,
            monoid="sum",
            associative_commutative=True),
    ))


def _posix_entry(part: Partition, **kw: Any) -> Partition:
    raise ValueError("posix image requires a command")  # pragma: no cover


#: The paper's `ubuntu` image: POSIX text tools behind a typed grammar.
posix_ubuntu = container_op("ubuntu", manifest=POSIX_MANIFEST)(_posix_entry)
posix = container_op("posix", manifest=POSIX_MANIFEST)(_posix_entry)


# ---------------------------------------------------------------------------
# kmer-stats: FASTA byte records -> packed k-mer keys/counts (arXiv:1807.01566
# workload: reduce_by_key over the 4^k k-mer key space)
# ---------------------------------------------------------------------------

_BASE_CODES = {65: 0, 67: 1, 71: 2, 84: 3}   # A C G T -> 2-bit codes

#: Largest k whose packed code is one int32 (``4**k`` keys, a dense
#: table); up to :data:`KMER_MAX_K` a code takes two uint32 words.
KMER_ONE_WORD_K = 15
KMER_MAX_K = 31


def _kmer_key_field(k: int):
    return (field(jnp.int32) if k <= KMER_ONE_WORD_K
            else field(jnp.uint32, (2,)))


def _canonical_token(token: str) -> bool:
    """The ``canonical`` flag of the ``kmer-stats`` grammar."""
    if token != "canonical":
        raise ValueError(f"expected 'canonical', got {token!r}")
    return True


KMER_MANIFEST = ImageManifest(
    input_schema=bytes_record_schema(),
    output_schema=lambda schema, env: Schema(
        (_kmer_key_field(env["k"]), field(jnp.int32))),
    # every record yields at most W - k + 1 windows
    out_capacity=lambda cap, env: cap * (env["W"] - env["k"] + 1),
    # one-word packed keys cover [0, 4**k) — downstream key tables can be
    # sized (and bounds-checked) at plan time, FastKmer-style; a
    # two-word key has no table (reduce_by_key sorts it)
    key_space=lambda env: (4 ** env["k"] if env["k"] <= KMER_ONE_WORD_K
                           else None),
    commands=(CommandSpec(
        "kmer-stats", args=(ArgSpec("k", type=int, required=False),
                            ArgSpec("canonical", type=_canonical_token,
                                    required=False))),),
    default_command="kmer-stats")


def _kmer_windows(code: jax.Array, k: int, canonical: bool
                  ) -> Tuple[jax.Array, ...]:
    """Packed codes of every k-base window of the 2-bit ``code`` ``[cap,
    W]``: ``(int32,)`` for ``k <= 15``, ``(high, low)`` uint32 words
    beyond.  ``canonical`` takes the lesser of each window's code and its
    reverse complement's (base ``j`` of the window, complemented, is
    digit ``j`` of the reverse complement, least significant first)."""
    nw = code.shape[1] - k + 1
    if k <= KMER_ONE_WORD_K:
        fwd = jnp.zeros((code.shape[0], nw), jnp.int32)
        rc = jnp.zeros_like(fwd)
        for j in range(k):
            c = code[:, j:j + nw]
            fwd = fwd * 4 + c
            rc = rc | ((3 - c) << (2 * j))
        return (jnp.minimum(fwd, rc) if canonical else fwd,)
    code = code.astype(jnp.uint32)
    zeros = jnp.zeros((code.shape[0], nw), jnp.uint32)
    hi, lo, rc_hi, rc_lo = zeros, zeros, zeros, zeros
    for j in range(k):
        c = code[:, j:j + nw]
        hi = (hi << 2) | (lo >> 30)
        lo = (lo << 2) | c
        if canonical:
            if 2 * j < 32:
                rc_lo = rc_lo | ((3 - c) << (2 * j))
            else:
                rc_hi = rc_hi | ((3 - c) << (2 * j - 32))
    if canonical:
        take = (rc_hi < hi) | ((rc_hi == hi) & (rc_lo < lo))
        hi, lo = jnp.where(take, rc_hi, hi), jnp.where(take, rc_lo, lo)
    return hi, lo


@container_op("kmer-stats", manifest=KMER_MANIFEST, k=8)
def kmer_stats(part: Partition, k: int = 8, canonical: bool = False,
               **kw: Any) -> Partition:
    """Emit one ``(packed k-mer key, 1)`` record per k-mer occurrence.

    Input: byte records ``{"data": uint8 [cap, W], "len": int32 [cap]}``
    (the repro.io FASTA contract — each record is one sequence line, so
    k-mers never span records).  Output records: ``(codes, ones int32)``
    with the 2-bit packing ``A=0 C=1 G=2 T=3`` (case-insensitive), first
    base most significant; windows containing any other base (N, gaps)
    are skipped.  ``k`` (1..31) and ``canonical`` come from the params or
    the command grammar (``kmer-stats 21 canonical``).  For ``k <= 15``
    a code is one int32 and ``num_keys = 4**k`` downstream (declared as
    the manifest's ``key_space``, so ``reduce_by_key`` can infer it);
    for ``16 <= k <= 31`` it is one uint32 ``[2]`` leaf, high word then
    low (``2k - 32`` and 32 bits), which ``reduce_by_key`` folds by
    sorting.  ``canonical`` counts a k-mer and its reverse complement as
    one: the lesser of the two codes.
    """
    if not 1 <= k <= KMER_MAX_K:
        raise ValueError(f"kmer-stats needs 1 <= k <= {KMER_MAX_K}, got {k}")
    data = part.records["data"]
    lens = part.records["len"]
    cap, width = data.shape
    if k > width:
        raise ValueError(f"k={k} exceeds record width {width}")
    nw = width - k + 1
    upper = jnp.where((data >= 97) & (data <= 122), data - 32, data)
    code = jnp.zeros_like(upper, dtype=jnp.int32)
    base_ok = jnp.zeros(data.shape, bool)
    for byte, c in _BASE_CODES.items():
        hit = upper == byte
        code = jnp.where(hit, c, code)
        base_ok = base_ok | hit
    if k > KMER_ONE_WORD_K or canonical:
        words = _kmer_windows(code, k, canonical)
        window_ok = jnp.ones((cap, nw), bool)
        for j in range(k):
            window_ok = window_ok & base_ok[:, j:j + nw]
    else:
        # forward one-word codes: the k-mer cells' programs, whose text
        # tests/test_lowered_programs.py pins
        acc = jnp.zeros((cap, nw), jnp.int32)
        window_ok = jnp.ones((cap, nw), bool)
        for j in range(k):
            acc = acc * 4 + code[:, j:j + nw]
            window_ok = window_ok & base_ok[:, j:j + nw]
        words = (acc,)
    in_len = jnp.arange(nw)[None, :] + k <= lens[:, None]
    ok = (window_ok & in_len & part.mask()[:, None]).reshape(-1)
    # compact valid k-mers to the front (partition count semantics) with
    # one sort that carries the words, as a gather of each word through an
    # argsort's order costs several times the sort on a TPU.  The key is
    # the invalid flag over the window's index: valid windows first, each
    # side in order, and no two keys tie, so the sort need not be stable
    # (a stable one carries an index of its own, in time and code)
    key = ((~ok).astype(jnp.uint32) << 31) | jnp.arange(ok.size,
                                                        dtype=jnp.uint32)
    words = jax.lax.sort((key, *(w.reshape(-1) for w in words)),
                         num_keys=1, is_stable=False)[1:]
    codes = words[0] if len(words) == 1 else jnp.stack(words, axis=1)
    total = jnp.sum(ok).astype(jnp.int32)
    ones = (jnp.arange(cap * nw) < total).astype(jnp.int32)
    return make_partition((codes, ones), total)


# ---------------------------------------------------------------------------
# kmer-histo: a k-mer table -> its spectrum (GenomeScope's `jellyfish histo`)
# ---------------------------------------------------------------------------

HISTO_MANIFEST = ImageManifest(
    output_schema=Schema((field(jnp.int32), field(jnp.int32))),
    out_capacity=PRESERVE,
    # bins 0..high: a dense table downstream
    key_space=lambda env: env["high"] + 1,
    commands=(CommandSpec(
        "kmer-histo", args=(ArgSpec("high", type=int, required=False),)),),
    default_command="kmer-histo")


@container_op("kmer-histo", manifest=HISTO_MANIFEST, high=10000)
def kmer_histo(part: Partition, high: int = 10000, **kw: Any) -> Partition:
    """``jellyfish histo``: map each record of a ``reduce_by_key`` output,
    ``(key, values, count)``, to ``(min(count, high), 1)``.  Keyed on
    the first leaf and summed (``reduce_by_key(sum)``, whose table of
    ``high + 1`` bins the manifest's ``key_space`` declares), the ones
    give the k-mer spectrum: how many distinct keys occur ``b`` times,
    for ``b < high``, and ``high`` or more times in the last bin.
    ``high`` comes from the param or the grammar (``kmer-histo 10000``).
    """
    if high < 1:
        raise ValueError(f"kmer-histo needs high >= 1, got {high}")
    counts = jax.tree.leaves(part.records)[-1]
    bins = jnp.minimum(counts, high).astype(jnp.int32)
    return make_partition((bins, part.mask().astype(jnp.int32)),
                          part.count)


# ---------------------------------------------------------------------------
# Generic combinators (used by evaluation pipelines and tests)
# ---------------------------------------------------------------------------

def _accepts_command(fn: Callable[..., Any]) -> bool:
    """Whether ``fn`` can receive the ``command`` keyword (named param or
    **kwargs)."""
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):  # builtins / C callables
        return False
    for p in sig.parameters.values():
        if p.kind is inspect.Parameter.VAR_KEYWORD:
            return True
        if p.name == "command" and p.kind in (
                inspect.Parameter.POSITIONAL_OR_KEYWORD,
                inspect.Parameter.KEYWORD_ONLY):
            return True
    return False


def fn_image(name: str, fn: Callable[..., Partition], *,
             associative_commutative: bool = False,
             manifest: Optional[ImageManifest] = None,
             registry=None, **defaults: Any) -> Callable[..., ContainerOp]:
    """Build + register an image from a python function at runtime
    (the `docker build` analogue for ad-hoc tools).

    The wrapped fn receives the pull-time ``command`` string whenever its
    signature can accept it (a ``command`` parameter or ``**kwargs``) —
    runtime-built images interpret their command like registered ones do.
    """
    from repro.core import container as c
    reg = registry or c.DEFAULT_REGISTRY
    forward_command = _accepts_command(fn)

    @container_op(name, associative_commutative=associative_commutative,
                  manifest=manifest, registry=reg, **defaults)
    def _op(part: Partition, command: str = "", **kw: Any) -> Partition:
        if forward_command:
            return fn(part, command=command, **kw)
        return fn(part, **kw)

    return _op


TOPK_MANIFEST = ImageManifest(
    output_schema=SAME,
    out_capacity=lambda cap, env: min(int(env["k"]), cap))


@container_op("toolbox/topk", associative_commutative=True,
              manifest=TOPK_MANIFEST, k=30)
def topk_image(part: Partition, k: int = 30,
               score_field: int = 0, **kw: Any) -> Partition:
    """sdsorter analogue: keep the k best-scoring records.

    Records: tuple whose first leaf is [cap, ...]; scores are taken from
    ``records[score_field]`` (a [cap] float array).  Associative +
    commutative (paper notes sdsorter top-k is reduce-safe).
    """
    leaves = jax.tree.leaves(part.records)
    scores = leaves[score_field]
    if scores.ndim > 1:
        scores = scores.reshape(scores.shape[0], -1)[:, 0]
    valid = part.mask()
    if jnp.issubdtype(scores.dtype, jnp.floating):
        lowest = jnp.asarray(-jnp.inf, scores.dtype)
    else:
        lowest = jnp.asarray(jnp.iinfo(scores.dtype).min, scores.dtype)
    masked = jnp.where(valid, scores, lowest)
    k_eff = min(k, part.capacity)
    _, idx = jax.lax.top_k(masked, k_eff)
    out = jax.tree.map(lambda l: jnp.take(l, idx, axis=0), part.records)
    cnt = jnp.minimum(part.count, k_eff).astype(jnp.int32)
    return make_partition(out, cnt)


CONCAT_MANIFEST = ImageManifest(output_schema=SAME, out_capacity=PRESERVE)


@container_op("toolbox/concat", associative_commutative=True,
              manifest=CONCAT_MANIFEST)
def concat_image(part: Partition, **kw: Any) -> Partition:
    """vcf-concat analogue: identity on records (concatenation is implicit
    in the tree gather); compacts valid records to the front."""
    return part


SUM_MANIFEST = ImageManifest(output_schema=SAME, out_capacity=1,
                             monoid="sum")


@container_op("toolbox/sum", associative_commutative=True,
              manifest=SUM_MANIFEST)
def sum_image(part: Partition, **kw: Any) -> Partition:
    """Elementwise sum of records into a single record."""
    valid = part.mask()

    def s(leaf):
        m = valid.reshape((-1,) + (1,) * (leaf.ndim - 1))
        return jnp.sum(jnp.where(m, leaf, 0), axis=0)[None]

    return make_partition(jax.tree.map(s, part.records), jnp.int32(1))
