"""Lookup by name of the benchmark's files (CPU, no JAX)."""
import pytest

from bench import spec


def test_every_named_file_of_the_benchmark_is_found():
    bench = spec.load()
    for w in bench["workloads"]:
        cfg = spec.config(bench, w["config"])
        traffic = spec.traffic(w["traffic"], cfg)
        assert hasattr(spec.module("gen", cfg["data"]), "make")
        assert hasattr(spec.module("loops", traffic["loop"]), "Loop")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.reader(m["name"]))


def test_split_metric_shares_its_base_reader(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "idle.py").write_text(
        "def read(run):\n    return 1.0\n")
    (tmp_path / "metrics" / "idle.own.py").write_text(
        "def read(run):\n    return 2.0\n")
    assert spec.reader("idle.kmer", tmp_path)(None) == 1.0
    assert spec.reader("idle.own", tmp_path)(None) == 2.0


def test_missing_module_names_the_file(tmp_path):
    with pytest.raises(SystemExit, match="no loops module named 'nope'"):
        spec.module("loops", "nope", tmp_path)


def test_traffic_takes_values_from_the_configuration():
    cfg = {"k": 12, "split_bytes": 1024}
    got = spec.resolve({"a": "$k", "b": ["$split_bytes", "x"]}, cfg)
    assert got == {"a": 12, "b": [1024, "x"]}
