"""Each cell end to end at its rehearsal size on the CPU; the chip check
without ``--rehearse``; and a cell made only of new files."""
import json
import os
import shutil

import pytest

from bench.tests.harness import ROOT, cell_args, result, run

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_correct(cell):
    out = result(run(*cell_args(cell)))
    assert list(out) == KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert "setup_s" in out["metrics"]
    assert all(m["unit"] for m in out["metrics"].values())
    assert out["device"]["count"] == next(
        w["chips"] for w in json.loads(
            (ROOT / "BENCHMARK.json").read_text())["workloads"]
        if w["name"] == cell)
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())


def test_traced_rehearsal_reports_per_layer_metrics():
    out = result(run(*cell_args("kmer12.batch", trace=1)))
    assert out["correct"] is True
    # no device plane on the CPU: only the span and counter readers speak
    assert {"ingest_s.kmer", "device_wait_s.kmer", "plan_s"} <= \
        set(out["metrics"])
    assert "device_idle.kmer" not in out["metrics"]


def test_without_rehearse_a_cpu_run_fails_and_prints_nothing():
    proc = run("--workload", "kmer12.batch", "--seed", "1", "--seconds",
               "1", "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def _copy(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "src", tmp_path / "src")
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    spec = _copy(tmp_path)
    bench = tmp_path / "bench"
    cfg = json.loads((bench / "configs" / "kmer12-reads150.json").read_text())
    cfg.update(name="kmer4-reads100", k=4, read_len=100,
               rehearsal={"reads_per_chip": 512, "split_bytes": 8192})
    (bench / "configs" / "kmer4-reads100.json").write_text(json.dumps(cfg))
    traffic = json.loads((bench / "traffic" / "closed_jobs.json").read_text())
    traffic["check_sample"] = 2
    (bench / "traffic" / "closed_jobs.sample2.json").write_text(
        json.dumps(traffic))
    (bench / "metrics" / "jobs_done.py").write_text(
        "def read(run):\n    return float(len(run.done))\n")
    spec["configs"].append({
        "name": "kmer4-reads100", "source": "https://example.org/k4",
        "file": "bench/configs/kmer4-reads100.json", "reduced": [],
        "why": "added by the test"})
    spec["workloads"].append({
        "name": "kmer4.batch", "config": "kmer4-reads100",
        "traffic": "closed_jobs.sample2", "chips": 1, "why": "test"})
    spec["end_to_end"].append({
        "name": "jobs_done", "unit": "jobs", "better": "higher",
        "bound": 0.25, "source": "host_clock", "workloads": ["kmer4.batch"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    out = result(run(*cell_args("kmer4.batch"),
                     script=tmp_path / "bench" / "run.py"))
    assert out["correct"] is True
    assert out["metrics"]["jobs_done"]["value"] >= 1
    assert out["metrics"]["jobs_done"]["unit"] == "jobs"
    assert "seq_throughput" not in out["metrics"]


GEN = """
from bench import spec


def make(cfg, chips, seed):
    # uniform reads with every A read as G: a GC-rich read set
    reads = spec.module("gen", "uniform_reads").make(cfg, chips, seed)
    seq = reads.seq
    seq[seq == ord("A")] = ord("G")
    return reads
"""

LOOP = """
from bench import spec

Base = spec.module("loops", "closed_jobs").Loop


class Loop(Base):
    # exactly `jobs` jobs, however long the window
    def window(self, seconds, run):
        for i in range(int(self.ctx.traffic["jobs"])):
            run.actions.append(self.job(f"job{i}", i + 1))
"""

REFERENCE = """
import numpy as np

from bench import spec

NUMBER = "wrong_totals"
LIMIT = 0
kmer = spec.module("references", "kmer_table")


def answer(out):
    keys, (sums,), counts = out
    return int(np.asarray(counts).sum())


def expected(data, specs):
    return [int(t.sum()) for t in kmer.expected(data, specs)]


def control(data, specs):
    return [int(kmer.kmer_table(data.seq, int(s["k"]), skip_n=False).sum())
            for s in specs]


def number(pairs):
    return sum(int(a != b) for a, b in pairs)
"""


def test_new_loop_generator_and_reference_are_found_by_name(tmp_path):
    spec = _copy(tmp_path)
    bench = tmp_path / "bench"
    (bench / "gen" / "gc_rich_reads.py").write_text(GEN)
    (bench / "loops" / "fixed_jobs.py").write_text(LOOP)
    (bench / "references" / "kmer_total.py").write_text(REFERENCE)
    cfg = json.loads((bench / "configs" / "kmer12-reads150.json").read_text())
    cfg.update(name="kmer6-gcrich", data="gc_rich_reads")
    (bench / "configs" / "kmer6-gcrich.json").write_text(json.dumps(cfg))
    traffic = json.loads((bench / "traffic" / "closed_jobs.json").read_text())
    traffic.update(loop="fixed_jobs", jobs=3,
                   answer={"reference": "kmer_total", "k": "$k"})
    (bench / "traffic" / "three_jobs.json").write_text(json.dumps(traffic))
    spec["configs"].append({
        "name": "kmer6-gcrich", "source": "https://example.org/gc",
        "file": "bench/configs/kmer6-gcrich.json", "reduced": [],
        "why": "added by the test"})
    spec["workloads"].append({
        "name": "kmer6.gcrich", "config": "kmer6-gcrich",
        "traffic": "three_jobs", "chips": 1, "why": "test"})
    next(m for m in spec["end_to_end"] if m["name"] == "seq_throughput"
         )["workloads"].append("kmer6.gcrich")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    out = result(run(*cell_args("kmer6.gcrich"),
                     script=tmp_path / "bench" / "run.py"))
    assert out["correct"] is True and out["attempted"] == 3
    assert out["checks"]["wrong_totals"] == {"value": 0, "limit": 0}
    assert "seq_throughput" in out["metrics"]
