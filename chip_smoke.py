#!/usr/bin/env python3
"""Chip smoke: MaRe's k-mer keyed-aggregation path, end to end, on a TPU.

    python chip_smoke.py               # one chip, every phase below
    python chip_smoke.py --chips 4     # only the four-chip phase
    python chip_smoke.py --small       # tiny CPU rehearsal (any backend)

It writes a FASTA file of 1,048,576 reads x 150 bp (Illumina short-read
length) from ``--seed`` into a temporary directory, ingests it with
``MaRe.from_source(fasta_source(path))`` and runs, in this one process:

* ``kmer12`` — ``map(kmer-stats, k=12) -> reduce_by_key(sum)`` with the
  key space inferred (4**12 keys) and the autotuned segment reduce;
  collected cold, then rebuilt and collected warm, which must hit the plan
  cache (no ``plan.compile`` phase).
* ``kmer6_tiled`` — the same pipeline at k=6 with ``use_kernel=True``;
  the compiled program must hold the Pallas kernel (``tpu_custom_call``).
* ``gc`` — paper Listing 1, ``grep-chars GC`` then ``awk-sum``.

``--chips 4`` runs only ``four_chips`` over 4 x 1,048,576 reads: the
k=12 table over a 4-device mesh, then a 90%-hot-key keyed reduce of
2**25 records with ``combiner=False``, unsalted and with ``salt=8``; it
checks that every shard lives on its own device.

Every phase is compared exactly with a NumPy reference computed on the
host.  One JSON line per phase precedes the last line,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Any mismatch
or error exits non-zero without that line, and so does a backend other
than TPU unless ``--small`` is given.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

READ_LEN = 150
HEADER = 10                      # ">r%07d\n"
HOT_FRAC = 0.9
SKEW_SALT = 8


def write_fasta(path: str, n_reads: int, seed: int) -> np.ndarray:
    """One header and one sequence line per read; about 1 in 1024 bases
    is ``N``.  Returns the ``[n_reads, READ_LEN]`` sequence bytes."""
    rng = np.random.default_rng(seed)
    draw = rng.integers(0, 4096, size=(n_reads, READ_LEN), dtype=np.uint16)
    seq = np.frombuffer(b"ACGT", np.uint8)[draw & 3]
    seq[draw >> 2 == 0] = ord("N")
    lines = np.empty((n_reads, HEADER + READ_LEN + 1), np.uint8)
    lines[:, :2] = np.frombuffer(b">r", np.uint8)
    idx = np.arange(n_reads)
    for d in range(7):
        lines[:, 2 + d] = 48 + (idx // 10 ** (6 - d)) % 10
    lines[:, HEADER - 1] = ord("\n")
    lines[:, HEADER:HEADER + READ_LEN] = seq
    lines[:, -1] = ord("\n")
    lines.tofile(path)
    return seq


def kmer_reference(seq: np.ndarray, k: int) -> np.ndarray:
    """Occurrences of every packed 2-bit k-mer (A=0 C=1 G=2 T=3) over
    windows free of N, by ``np.bincount``."""
    lut = np.zeros(256, np.uint32)
    lut[[ord("C"), ord("G"), ord("T")]] = [1, 2, 3]
    code = lut[seq]
    nw = seq.shape[1] - k + 1
    acc = np.zeros((seq.shape[0], nw), np.uint32)
    for j in range(k):
        np.left_shift(acc, 2, out=acc)
        np.bitwise_or(acc, code[:, j:j + nw], out=acc)
    bad = np.zeros((seq.shape[0], seq.shape[1] + 1), np.int32)
    np.cumsum(seq == ord("N"), axis=1, out=bad[:, 1:])
    ok = bad[:, k:] == bad[:, :nw]
    return np.bincount(acc[ok], minlength=4 ** k)


def key_of(recs):
    return recs[0]


def ones_of(recs):
    return (recs[1],)


def kmer_chain(dataset, k: int, cache, use_kernel=None):
    from repro.core import MaRe
    return (MaRe(dataset, plan_cache=cache)
            .map(image="kmer-stats", k=k)
            .reduce_by_key(key_of, value_by=ones_of, op="sum",
                           use_kernel=use_kernel))


def check_table(name: str, keys, sums, counts, expected: np.ndarray) -> None:
    keys = np.asarray(keys)
    if len(np.unique(keys)) != len(keys):
        raise AssertionError(f"{name}: a key appears twice in the result")
    for what, vals in (("sums", sums), ("counts", counts)):
        got = np.zeros_like(expected)
        got[keys] = np.asarray(vals)
        if not np.array_equal(got, expected):
            bad = int(np.count_nonzero(got != expected))
            raise AssertionError(f"{name}: {what} differ from the NumPy "
                                 f"reference at {bad} keys")


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def timed_collect(chain):
    t0 = time.perf_counter()
    out = chain.collect()
    return out, time.perf_counter() - t0


def phase_kmer12(dataset, seq, device) -> None:
    from repro.core import PlanCache
    from repro.kernels.segment_reduce import tune_report
    cache = PlanCache()
    expected = kmer_reference(seq, 12)
    tuned_before = len(tune_report())
    cold = kmer_chain(dataset, 12, cache)
    (keys, (sums,), counts), cold_s = timed_collect(cold)
    check_table("kmer12 cold", keys, sums, counts, expected)
    warm = kmer_chain(dataset, 12, cache)
    (keys, (sums,), counts), warm_s = timed_collect(warm)
    check_table("kmer12 warm", keys, sums, counts, expected)
    rep = warm.report()
    if rep.programs_compiled or not rep.program_cache_hits \
            or "plan.compile" in rep.phases:
        raise AssertionError(f"kmer12 warm action recompiled: {rep}")
    emit({"phase": "kmer12", "num_keys": 4 ** 12,
          "kmers": int(expected.sum()), "distinct": int(np.count_nonzero(
              expected)),
          "cold_s": cold_s, "warm_s": warm_s,
          "cold_phases": cold.report().phases,
          "warm_phases": rep.phases,
          "tuned": tune_report()[tuned_before:],
          "peak_bytes_in_use": peak_bytes(device)})


def phase_kmer6_tiled(dataset, seq, device, on_tpu: bool) -> None:
    from repro.core import PlanCache
    cache = PlanCache()
    expected = kmer_reference(seq, 6)
    chain = kmer_chain(dataset, 6, cache, use_kernel=True)
    (keys, (sums,), counts), cold_s = timed_collect(chain)
    check_table("kmer6_tiled", keys, sums, counts, expected)
    (program,) = cache.programs()
    kernel_compiled = "tpu_custom_call" in program.as_text()
    if on_tpu and not kernel_compiled:
        raise AssertionError("kmer6_tiled: no tpu_custom_call in the "
                             "compiled program")
    warm = kmer_chain(dataset, 6, cache, use_kernel=True)
    (keys, (sums,), counts), warm_s = timed_collect(warm)
    check_table("kmer6_tiled warm", keys, sums, counts, expected)
    emit({"phase": "kmer6_tiled", "num_keys": 4 ** 6,
          "kmers": int(expected.sum()), "cold_s": cold_s, "warm_s": warm_s,
          "cold_phases": chain.report().phases,
          "warm_phases": warm.report().phases,
          "tpu_custom_call": kernel_compiled,
          "peak_bytes_in_use": peak_bytes(device)})


def phase_gc(dataset, seq, device) -> None:
    from repro.core import MaRe
    expected = int(np.count_nonzero((seq == ord("G")) | (seq == ord("C"))))
    timings = []
    for _ in range(2):
        chain = (MaRe(dataset)
                 .map(image="ubuntu", command="grep-chars GC")
                 .reduce(image="ubuntu", command="awk-sum"))
        t0 = time.perf_counter()
        (total,) = chain.collect(shard=0)
        timings.append(time.perf_counter() - t0)
        if int(total[0]) != expected:
            raise AssertionError(f"gc: got {int(total[0])}, "
                                 f"expected {expected}")
    emit({"phase": "gc", "gc_count": expected, "cold_s": timings[0],
          "warm_s": timings[1], "cold_phases": chain.report().phases,
          "peak_bytes_in_use": peak_bytes(device)})


def check_placement(name: str, dataset) -> None:
    """Each shard of every leaf sits on its own device of the mesh."""
    import jax
    mesh_devices = set(dataset.mesh.devices.flat)
    if len(mesh_devices) != dataset.num_shards:
        raise AssertionError(f"{name}: mesh has {len(mesh_devices)} "
                             f"distinct devices for "
                             f"{dataset.num_shards} shards")
    for leaf in jax.tree.leaves(dataset.records) + [dataset.counts]:
        devs = [s.device for s in leaf.addressable_shards]
        if len(devs) != dataset.num_shards or set(devs) != mesh_devices:
            raise AssertionError(f"{name}: shards on {devs}, mesh "
                                 f"{sorted(d.id for d in mesh_devices)}")
        starts = {s.index[0].start for s in leaf.addressable_shards}
        if len(starts) != dataset.num_shards:
            raise AssertionError(f"{name}: two devices hold the same block")


def phase_four_chips(path: str, seq, seed: int, skew_records: int) -> None:
    import jax

    from repro import compat
    from repro.core import MaRe, PlanCache
    from repro.io import fasta_source
    devices = jax.devices()
    if len(devices) < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found {len(devices)}")
    mesh = compat.make_mesh((4,), ("data",), devices=devices[:4])
    t0 = time.perf_counter()
    base = MaRe.from_source(fasta_source(path), mesh=mesh)
    ingest_s = time.perf_counter() - t0
    check_placement("ingest", base.dataset)
    expected = kmer_reference(seq, 12)
    # scatter is the tuner's pick for this shape on one chip; forcing it
    # keeps minutes of trace-time tuning out of a four-chip call
    chain = kmer_chain(base.dataset, 12, PlanCache(), use_kernel=False)
    t0 = time.perf_counter()
    result = chain.dataset
    check_placement("kmer12 result", result)
    keys, (sums,), counts = MaRe(result).collect()
    kmer_s = time.perf_counter() - t0
    kmer_phases = chain.report().phases
    check_table("four_chips kmer12", keys, sums, counts, expected)

    n = skew_records
    num_keys = 4 ** 12
    rng = np.random.default_rng(seed)
    hot = rng.random(n) < HOT_FRAC
    skew_keys = np.where(hot, 3, rng.integers(0, num_keys, n)).astype(
        np.int32)
    skewed = MaRe((skew_keys, np.ones(n, np.int32)), mesh=mesh).dataset
    check_placement("skewed input", skewed)
    want = np.bincount(skew_keys, minlength=num_keys)
    skew = {}
    for salt in (1, SKEW_SALT):
        chain = MaRe(skewed, plan_cache=PlanCache()).reduce_by_key(
            key_of, value_by=ones_of, op="sum", num_keys=num_keys,
            combiner=False, salt=salt, use_kernel=False)
        t0 = time.perf_counter()
        result = chain.dataset
        check_placement(f"skewed salt={salt} result", result)
        keys, (sums,), counts = MaRe(result).collect()
        wall = time.perf_counter() - t0
        check_table(f"skewed salt={salt}", keys, sums, counts, want)
        d = chain.report().diagnostics
        skew[f"salt{salt}"] = {
            "s": wall, "phases": chain.report().phases,
            "exchange_buffer_rows":
                d["stage0.exchange_buffer_rows"],
            "max_send_count": d["stage0.max_send_count"],
            "dropped": d["stage0.shuffle_dropped"]}
    emit({"phase": "four_chips", "devices": [d.id for d in devices[:4]],
          "ingest_s": ingest_s, "kmer12_s": kmer_s,
          "kmer12_phases": kmer_phases,
          "skew_records": n, "skew": skew,
          "peak_bytes_in_use": [peak_bytes(d) for d in devices[:4]]})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--small", action="store_true",
                    help="rehearse at a tiny size on any backend")
    args = ap.parse_args()

    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.core import MaRe
    from repro.io import fasta_source
    from repro.kernels.common import use_interpret

    enable_compile_cache()
    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    info = {"platform": device.platform, "kind": device.device_kind,
            "count": len(jax.devices())}
    if not on_tpu and not args.small:
        print(f"chip_smoke: no TPU (JAX found {device.platform}); "
              "--small rehearses on other backends", file=sys.stderr)
        return 1
    emit({"phase": "device", **info})
    if on_tpu and use_interpret():
        raise AssertionError("Pallas would run in interpret mode on a TPU")

    # --chips 4 holds 2**20 reads per chip, as the one-chip run does
    n_reads = args.chips * (1024 if args.small else 1 << 20)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        path = os.path.join(tmp, "reads.fa")
        t0 = time.perf_counter()
        seq = write_fasta(path, n_reads, args.seed)
        emit({"phase": "write_fasta", "reads": n_reads,
              "read_len": READ_LEN, "bytes": os.path.getsize(path),
              "s": time.perf_counter() - t0})
        if args.chips == 4:
            phase_four_chips(path, seq, args.seed,
                             4 << (14 if args.small else 23))
        else:
            t0 = time.perf_counter()
            dataset = MaRe.from_source(fasta_source(path)).dataset
            emit({"phase": "ingest", "s": time.perf_counter() - t0,
                  "capacity": dataset.capacity,
                  "width": int(dataset.records["data"].shape[1]),
                  "peak_bytes_in_use": peak_bytes(device)})
            phase_kmer12(dataset, seq, device)
            phase_kmer6_tiled(dataset, seq, device, on_tpu)
            phase_gc(dataset, seq, device)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
