"""k-mer statistics — keyed aggregation over a genome (reduce_by_key demo).

  PYTHONPATH=src python examples/kmer_stats.py             # batch
  PYTHONPATH=src python examples/kmer_stats.py --follow    # live dashboard

The canonical grouped-aggregation genomics workload (arXiv:1807.01566
collects k-mer statistics at scale with exactly this shape): a FASTA
genome is ingested through repro.io, the ``kmer-stats`` container maps
each sequence record to packed 2-bit k-mer keys, and
``MaRe.reduce_by_key`` folds equal keys with a map-side combiner — the
whole chain compiles to ONE shard_map program, and shuffle volume scales
with distinct k-mers, not k-mer occurrences (see
``report().diagnostics["stage1.exchanged_records"]``).

``--follow`` runs the same aggregation as a *live* query
(docs/streaming.md): a sequencer drops FASTA files into an inbox, a
tenant ``Session`` maintains the k-mer table incrementally — each new
file batch runs only the delta through the compiled plan and folds it
into the persisted aggregate — and the dashboard refreshes per epoch.

Note the FASTA reader frames each sequence *line* as one record, so
k-mers spanning a line boundary are not counted — the reference below
mirrors that framing (exact for the chunked statistic, as with GC count).
"""
import argparse
import os
import queue
import sys
import tempfile
from collections import Counter

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import MaRe
from repro.io import fasta_source

K = 6
LINE = 70


def write_genome(path: str, n_bases: int = 50_000, seed: int = 7):
    """Random ATGC genome as FASTA; return its sequence lines."""
    rng = np.random.default_rng(seed)
    seq = "".join(np.array(list("ATGC"))[rng.integers(0, 4, size=n_bases)])
    lines = [seq[i:i + LINE] for i in range(0, len(seq), LINE)]
    with open(path, "w") as f:
        f.write(">chr1 kmer-stats demo\n")
        for ln in lines:
            f.write(ln + "\n")
    return lines


def reference_counts(lines) -> Counter:
    """Per-line k-mer counts (the FASTA record framing)."""
    counts: Counter = Counter()
    code = {"A": 0, "C": 1, "G": 2, "T": 3}
    for ln in lines:
        for i in range(len(ln) - K + 1):
            key = 0
            for ch in ln[i:i + K]:
                key = key * 4 + code[ch]
            counts[key] += 1
    return counts


def decode(key: int) -> str:
    bases = "ACGT"
    return "".join(bases[(key >> (2 * (K - 1 - i))) & 3] for i in range(K))


def key_of(recs):
    return recs[0]


def ones_of(recs):
    return (recs[1],)


def build_kmer_table(m: MaRe) -> MaRe:
    """The aggregation both modes share: map to k-mer keys, fold by key.

    Module-level on purpose — an IncrementalQuery requires the SAME plan
    suffix every epoch (stage signatures key on callable identity)."""
    return (m.map(image="kmer-stats", k=K)
            .reduce_by_key(key_of, value_by=ones_of, op="sum",
                           num_keys=4 ** K))


def top_kmers(table, n: int = 3):
    keys, (occurrences,), _ = table
    got = {int(k): int(c) for k, c in zip(keys, occurrences)}
    top = sorted(got.items(), key=lambda kv: (-kv[1], kv[0]))[:n]
    return got, top


def follow(epochs: int = 4, bases_per_epoch: int = 10_000):
    """Live k-mer dashboard: a sequencer drops FASTA chunks into an
    inbox while a tenant Session maintains the table incrementally."""
    import jax

    from repro import compat
    from repro.serve import QueryService
    from repro.stream import ContinuousSource, LiveQuery

    inbox = tempfile.mkdtemp(prefix="mare_kmer_inbox_")
    stage = tempfile.mkdtemp(prefix="mare_kmer_stage_")
    mesh = compat.make_mesh((jax.device_count(),), ("data",))

    with QueryService() as svc:
        sess = svc.session("genomics")
        cont = ContinuousSource(fasta_source(inbox, split_bytes=1 << 13),
                                mesh, capacity=256)
        query = sess.stream(cont, build_kmer_table, label="genomics/kmers")
        print(query.describe())

        refreshes: queue.Queue = queue.Queue()
        all_lines = []
        # the LiveQuery thread polls the inbox; files appear atomically
        # (written in a staging dir, renamed in) so a half-written chunk
        # is never ingested
        with LiveQuery(query, interval_s=0.05, on_refresh=refreshes.put):
            for epoch in range(epochs):
                name = f"chunk{epoch:03d}.fa"
                all_lines += write_genome(os.path.join(stage, name),
                                          n_bases=bases_per_epoch,
                                          seed=100 + epoch)
                os.rename(os.path.join(stage, name),
                          os.path.join(inbox, name))
                upd = refreshes.get(timeout=120)
                got, top = top_kmers(query.collect())
                print(f"[watermark {upd.watermark}] +{upd.new_splits} "
                      f"splits, fold {upd.fold_s * 1e3:.1f} ms, "
                      f"{sum(got.values())} windows | top: "
                      + "  ".join(f"{decode(k)} x{c}" for k, c in top))

        # every refresh routed one report through the session stream
        reports = sess.follow(0, timeout=30)
        assert len(reports) == epochs
        assert all(r.tenant == "genomics" for r in reports)
        assert reports[-1].counters["stream.watermark"] == epochs - 1
        print(query.describe())

        got, _ = top_kmers(query.collect())
        expected = reference_counts(all_lines)
        assert got == dict(expected), \
            "followed k-mer table mismatch vs host reference"
        print(f"followed {epochs} epochs: {len(got)} distinct {K}-mers "
              f"over {sum(got.values())} windows, exact vs host reference")
        print("OK")


def main():
    enable_compile_cache()
    tmp = tempfile.mkdtemp(prefix="mare_kmer_")
    fasta = os.path.join(tmp, "genome.fa")
    lines = write_genome(fasta)

    base = MaRe.from_source(fasta_source(fasta, split_bytes=1 << 13))
    stats = build_kmer_table(base)
    # describe() shows the inferred schema + capacity at every stage
    # boundary: the kmer-stats manifest's capacity transfer sizes the
    # window buffer (cap * (W - k + 1)) and declares key_space = 4**k,
    # so num_keys above could equally be omitted and inferred:
    inferred = (base
                .map(image="kmer-stats", k=K)
                .reduce_by_key(key_of, value_by=ones_of, op="sum"))
    assert inferred.plan.stages[-1].num_keys == 4 ** K
    print(stats.describe())

    keys, (occurrences, ), record_counts = stats.collect()
    got = {int(k): int(c) for k, c in zip(keys, occurrences)}
    expected = reference_counts(lines)
    assert got == dict(expected), "k-mer table mismatch vs host reference"
    assert np.array_equal(occurrences, record_counts)  # value is 1/record

    top = sorted(got.items(), key=lambda kv: (-kv[1], kv[0]))[:5]
    print(f"{len(got)} distinct {K}-mers over {sum(got.values())} windows")
    for key, cnt in top:
        print(f"  {decode(key)}  x{cnt}")
    diag = stats.report().diagnostics
    print(f"combiner exchange volume: {diag['stage1.exchanged_records']} "
          f"records (vs {sum(got.values())} k-mer occurrences)")

    # Interactive sessions persist the expensive map prefix once; every
    # later query sharing it starts from the cached materialization and
    # only executes its own aggregation (runtime lineage cache):
    base.map(image="kmer-stats", k=K).persist()
    followup = (base
                .map(image="kmer-stats", k=K)
                .reduce_by_key(key_of, value_by=ones_of, op="max"))
    assert "[cached]" in followup.describe()
    followup.collect()
    report = followup.report()
    assert report.cached_stages == 1
    print(f"persisted prefix reused: cached {report.cached_stages}/"
          f"{report.total_stages} stages from {report.cache_tier} tier")
    print("OK")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--follow", action="store_true",
                    help="live dashboard over a polled FASTA inbox")
    args = ap.parse_args()
    follow() if args.follow else main()
