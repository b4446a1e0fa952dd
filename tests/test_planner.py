"""Lazy stage-DAG planner: whole-pipeline fusion, compile cache,
shuffle-overflow accounting, keyed aggregation (single device; multi-device
coverage lives in tests/distributed/mare_e2e.py)."""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import (KeyedReduceStage, MaRe, MapStage, Plan, PlanCache,
                        ShuffleStage, from_host, hash_keys,
                        keyed_bucket_capacity, shuffle_partition)
from repro.core import planner as planner_lib
from repro.core.container import ContainerOp, make_partition
from jax.sharding import PartitionSpec as P


def _counting_op(name="trace/counter"):
    """An op whose fn counts how many times it is TRACED (not executed)."""
    traces = {"n": 0}

    def fn(part, **kw):
        traces["n"] += 1
        return part

    return ContainerOp(image=name, fn=fn), traces


def _key_mod5(recs):
    return recs[0] % 5


# -- laziness & fusion --------------------------------------------------------

def test_chain_is_lazy_until_action():
    op, traces = _counting_op()
    m = (MaRe((np.arange(32, dtype=np.int32),), plan_cache=PlanCache())
         .map(op=op)
         .repartition_by(_key_mod5)
         .map(op=op))
    assert traces["n"] == 0                    # nothing traced yet
    assert [type(s) for s in m.plan.stages] == [MapStage, ShuffleStage,
                                                MapStage]
    got = m.collect()
    assert sorted(got[0].tolist()) == list(range(32))
    assert traces["n"] == 2                    # one trace, op appears twice


def test_whole_chain_compiles_one_program():
    cache = PlanCache()
    scores = np.random.default_rng(0).normal(size=64).astype(np.float32)
    ids = np.arange(64, dtype=np.int32)
    m = (MaRe((scores, ids), plan_cache=cache)
         .map(image="toolbox/concat")
         .repartition_by(lambda recs: recs[1] % 3)
         .reduce(image="toolbox/topk", k=8))
    _, top_ids = m.collect(shard=0)
    true_top = set(np.argsort(-scores)[:8].tolist())
    assert set(top_ids.tolist()) == true_top
    assert cache.stats() == {"programs": 1, "hits": 0, "misses": 1}


def test_fused_equals_stage_at_a_time():
    data = (np.arange(48, dtype=np.int32),)

    def run(fuse):
        cache = PlanCache()
        m = (MaRe(data, plan_cache=cache, fuse=fuse)
             .map(image="toolbox/concat")
             .repartition_by(_key_mod5)
             .reduce(image="toolbox/sum"))
        out = m.collect(shard=0)
        return out, cache.stats()

    fused, fused_stats = run(True)
    eager, eager_stats = run(False)
    np.testing.assert_array_equal(fused[0], eager[0])
    assert fused_stats["misses"] == 1
    assert eager_stats["misses"] == 3          # one program per stage


# -- compile cache ------------------------------------------------------------

def test_compile_cache_hits_on_identical_pipeline():
    cache = PlanCache()
    op, traces = _counting_op()
    data = (np.arange(16, dtype=np.int32),)

    def build():
        return (MaRe(data, plan_cache=cache)
                .map(op=op)
                .repartition_by(_key_mod5))

    build().collect()
    assert cache.stats() == {"programs": 1, "hits": 0, "misses": 1}
    first_traces = traces["n"]

    build().collect()                          # fresh MaRe, same pipeline
    assert cache.stats() == {"programs": 1, "hits": 1, "misses": 1}
    assert traces["n"] == first_traces         # zero re-trace

    # same program OBJECT is reused for the same key
    ds = from_host(data, compat.make_mesh((1,), ("data",)))
    plan = build().plan
    p1 = planner_lib.compile_plan(plan, ds, cache)
    p2 = planner_lib.compile_plan(plan, ds, cache)
    assert p1 is p2


def test_numpy_params_key_on_content_not_identity():
    """Array params are baked into the traced program, so the cache must
    key them by content: equal arrays share a program, and mutating one
    in place misses the cache instead of serving stale constants."""
    cache = PlanCache()
    table = np.full((4,), 10, np.int32)

    def add_table(part, table=None, **kw):
        return make_partition((part.records[0] + jnp.asarray(table)[0],),
                              part.count)

    def run():
        op = ContainerOp(image="t/add", fn=add_table,
                         params={"table": table})
        m = MaRe((np.zeros(8, np.int32),), plan_cache=cache).map(op=op)
        return int(m.collect()[0][0])

    assert run() == 10
    assert run() == 10                         # same content -> cache hit
    assert cache.stats()["misses"] == 1 and cache.stats()["hits"] == 1
    table += 90                                # in-place mutation
    assert run() == 100                        # new digest -> recompile
    assert cache.stats()["misses"] == 2


def test_compile_cache_misses_on_shape_or_structure_change():
    cache = PlanCache()
    op, _ = _counting_op()

    def run(n, twice):
        m = MaRe((np.arange(n, dtype=np.int32),), plan_cache=cache).map(op=op)
        if twice:
            m = m.map(op=op)
        m.collect()

    run(16, False)
    run(32, False)                             # shape change -> new program
    run(16, True)                              # structure change -> new one
    assert cache.stats()["misses"] == 3


# -- shuffle overflow ---------------------------------------------------------

def test_shuffle_partition_dropped_accounting():
    """All records hash to one destination; capacity caps what arrives and
    the remainder is counted, never silently lost."""
    mesh = compat.make_mesh((1,), ("data",))

    def interior(records, counts):
        part = make_partition(records, counts[0])
        keys = jnp.zeros((part.capacity,), jnp.int32)   # all -> shard 0
        res = shuffle_partition(part, keys, axis_name="data", axis_size=1,
                                capacity=3)
        return res.part.records, res.part.count[None], res.dropped[None]

    fn = jax.jit(compat.shard_map(
        interior, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P("data"), P("data"))))
    records = (jnp.arange(10, dtype=jnp.int32),)
    counts = jnp.asarray([10], jnp.int32)
    out_records, out_counts, dropped = fn(records, counts)
    assert int(dropped[0]) == 7                # 10 sent, 3 fit
    assert int(out_counts[0]) == 3
    # survivors are a prefix of the stable destination order
    assert out_records[0][:3].tolist() == [0, 1, 2]


def test_repartition_overflow_raises_at_action():
    # capacity=1: any source shard holding >1 record overflows its
    # per-destination send buffer (everything keys to one destination)
    m = (MaRe((np.arange(4 * jax.device_count(), dtype=np.int32),),
              plan_cache=PlanCache())
         .repartition_by(lambda recs: jnp.zeros_like(recs[0]), capacity=1))
    with pytest.raises(RuntimeError, match="overflow"):
        m.collect()


def test_lossless_shuffle_never_raises():
    m = (MaRe((np.arange(12, dtype=np.int32),), plan_cache=PlanCache())
         .repartition_by(lambda recs: jnp.zeros_like(recs[0])))
    got = m.collect()
    assert sorted(got[0].tolist()) == list(range(12))


# -- keyed aggregation (reduce_by_key) ---------------------------------------

def _kv_data(n=64, num_keys=8, seed=0):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, num_keys, size=n).astype(np.int32)
    vals = rng.normal(size=n).astype(np.float32)
    return keys, vals


def _key_first(recs):
    return recs[0]


def _val_second(recs):
    return (recs[1],)


def _expected_groupby(keys, vals):
    return {int(k): (float(vals[keys == k].sum()), int((keys == k).sum()))
            for k in np.unique(keys)}


def _keyed(data, num_keys=8, cache=None, **kw):
    # NB `or` would discard an empty cache: PlanCache.__len__ makes it falsy
    cache = cache if cache is not None else PlanCache()
    return MaRe(data, plan_cache=cache).reduce_by_key(
        _key_first, value_by=_val_second, op="sum", num_keys=num_keys, **kw)


@pytest.mark.parametrize("combiner", [True, False])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_reduce_by_key_matches_groupby(combiner, use_kernel):
    keys, vals = _kv_data()
    m = _keyed((keys, vals), combiner=combiner, use_kernel=use_kernel)
    out_keys, (out_sum,), out_cnt = m.collect()
    got = {int(k): (float(s), int(c))
           for k, s, c in zip(out_keys, out_sum, out_cnt)}
    exp = _expected_groupby(keys, vals)
    assert set(got) == set(exp)
    for k, (s, c) in exp.items():
        assert got[k][1] == c
        assert abs(got[k][0] - s) < 1e-4


def test_reduce_by_key_combiner_shrinks_exchange():
    keys, vals = _kv_data(n=256, num_keys=4)
    on = _keyed((keys, vals), num_keys=4, combiner=True)
    on.collect()
    off = _keyed((keys, vals), num_keys=4, combiner=False)
    off.collect()
    ex_on = on.report().diagnostics["stage0.exchanged_records"]
    ex_off = off.report().diagnostics["stage0.exchanged_records"]
    assert ex_off == 256                   # every record crosses the wire
    # at most one partial per key per shard (CI runs 8 simulated devices)
    assert ex_on <= 4 * jax.device_count()
    assert ex_on < ex_off
    assert on.report().diagnostics["stage0.key_overflow"] == 0


def test_reduce_by_key_is_lazy_and_fuses_to_one_program():
    keys, vals = _kv_data()
    cache = PlanCache()
    m = (MaRe((keys, vals), plan_cache=cache)
         .map(image="toolbox/concat")
         .reduce_by_key(_key_first, value_by=_val_second, op="sum",
                        num_keys=8))
    assert [type(s) for s in m.plan.stages] == [MapStage, KeyedReduceStage]
    assert cache.stats()["misses"] == 0    # nothing compiled yet
    m.collect()
    assert cache.stats() == {"programs": 1, "hits": 0, "misses": 1}


def test_reduce_by_key_cache_hit_on_rerun():
    keys, vals = _kv_data()
    cache = PlanCache()
    _keyed((keys, vals), cache=cache).collect()
    _keyed((keys, vals), cache=cache).collect()
    assert cache.stats() == {"programs": 1, "hits": 1, "misses": 1}


def test_reduce_by_key_max_monoid():
    keys, vals = _kv_data()
    m = MaRe((keys, vals), plan_cache=PlanCache()).reduce_by_key(
        _key_first, value_by=_val_second, op="max", num_keys=8)
    out_keys, (out_max,), _ = m.collect()
    for k, v in zip(out_keys, out_max):
        assert abs(float(v) - float(vals[keys == int(k)].max())) < 1e-6


def test_reduce_by_key_single_distinct_key():
    vals = np.arange(16, dtype=np.float32)
    keys = np.full(16, 3, np.int32)
    m = _keyed((keys, vals), num_keys=8)
    out_keys, (out_sum,), out_cnt = m.collect()
    assert out_keys.tolist() == [3]
    assert out_cnt.tolist() == [16]
    assert float(out_sum[0]) == float(vals.sum())


def test_reduce_by_key_empty_partitions():
    mesh = compat.make_mesh((jax.device_count(),), ("data",))
    ds = from_host((np.zeros(0, np.int32), np.zeros(0, np.float32)),
                   mesh, capacity=8)
    m = MaRe(ds).reduce_by_key(_key_first, value_by=_val_second, op="sum",
                               num_keys=8)
    out_keys, (out_sum,), out_cnt = m.collect()
    assert out_keys.shape[0] == 0 and out_cnt.shape[0] == 0


def test_reduce_by_key_all_records_masked_out():
    keys, vals = _kv_data(n=16)
    mesh = compat.make_mesh((jax.device_count(),), ("data",))
    ds = from_host((keys, vals), mesh)
    ds = dataclasses.replace(ds, counts=ds.counts * 0)   # mask everything
    m = MaRe(ds).reduce_by_key(_key_first, value_by=_val_second, op="sum",
                               num_keys=8)
    out_keys, (out_sum,), out_cnt = m.collect()
    assert out_keys.shape[0] == 0
    assert m.report().diagnostics["stage0.key_overflow"] == 0


@pytest.mark.parametrize("combiner", [True, False])
def test_reduce_by_key_overflow_raises_at_action_not_trace(combiner):
    keys = np.array([0, 1, 200, 300], np.int32)   # two keys out of range
    vals = np.ones(4, np.float32)
    m = _keyed((keys, vals), num_keys=4, combiner=combiner)
    # building + describing the plan must not raise (laziness)
    assert "reduce_by_key[sum, keys=4" in m.describe()
    with pytest.raises(RuntimeError, match="key-table overflow"):
        m.collect()


def test_reduce_by_key_monoid_validation_and_image_spelling():
    keys, vals = _kv_data()
    with pytest.raises(ValueError, match="unknown reduce_by_key op"):
        MaRe((keys, vals)).reduce_by_key(_key_first, op="mean", num_keys=8)
    with pytest.raises(ValueError, match="not a known keyed-reduce monoid"):
        MaRe((keys, vals)).reduce_by_key(_key_first, image="toolbox/topk",
                                         num_keys=8)
    m = MaRe((keys, vals), plan_cache=PlanCache()).reduce_by_key(
        _key_first, value_by=_val_second, image="ubuntu", command="awk-sum",
        num_keys=8)
    assert m.plan.stages[-1].op == "sum"
    out_keys, (out_sum,), _ = m.collect()
    exp = _expected_groupby(keys, vals)
    for k, s in zip(out_keys, out_sum):
        assert abs(float(s) - exp[int(k)][0]) < 1e-4


def test_keyed_bucket_capacity_matches_device_hash():
    num_keys, n = 97, 4
    caps = np.zeros(n, np.int64)
    dest = np.asarray(
        hash_keys(jnp.arange(num_keys, dtype=jnp.int32))) % n
    np.add.at(caps, dest.astype(np.int64), 1)
    assert keyed_bucket_capacity(num_keys, n) == int(caps.max())


def test_keyed_bucket_capacities_partition_the_key_space():
    from repro.core.shuffle import keyed_bucket_capacities
    caps = keyed_bucket_capacities(1000, 8)
    assert caps.shape == (8,)
    assert int(caps.sum()) == 1000            # every key owned exactly once
    assert int(caps.max()) == keyed_bucket_capacity(1000, 8)


# -- hot-key skew: the salted two-hop exchange --------------------------------

def _hot_key_data(n=2048, num_keys=32, hot=7, frac=0.9):
    rng = np.random.default_rng(5)
    keys = np.where(rng.random(n) < frac, hot,
                    rng.integers(0, num_keys, n)).astype(np.int32)
    vals = rng.integers(0, 10, n).astype(np.int32)
    return keys, vals


def test_reduce_by_key_salted_hot_key_matches_groupby():
    keys, vals = _hot_key_data()
    sal = _keyed((keys, vals), num_keys=32, combiner=False, salt=8)
    out_keys, (out_sum,), out_cnt = sal.collect()
    got = {int(k): (int(s), int(c))
           for k, s, c in zip(out_keys, out_sum, out_cnt)}
    exp = {int(k): (int(vals[keys == k].sum()), int((keys == k).sum()))
           for k in np.unique(keys)}
    assert got == exp
    assert sal.report().diagnostics["stage0.shuffle_dropped"] == 0
    assert sal.report().diagnostics["stage0.key_overflow"] == 0


def test_salted_diagnostics_present_and_lossless():
    # Buffer-SHRINK properties of salting need a multi-device mesh (there
    # is nowhere to spread on 1 device) and live in
    # tests/distributed/keyed_skew.py; here: the diagnostics contract.
    keys, vals = _hot_key_data()
    sal = _keyed((keys, vals), num_keys=32, combiner=False, salt=8)
    sal.collect()
    d = sal.report().diagnostics
    assert d["stage0.shuffle_dropped"] == 0
    assert 0 < d["stage0.max_send_count"] <= len(keys)
    assert d["stage0.exchange_buffer_rows"] > 0


def test_salt_validation():
    keys, vals = _kv_data()
    with pytest.raises(ValueError, match="salt must be >= 1"):
        _keyed((keys, vals), salt=0)
    with pytest.raises(ValueError, match="requires combiner=False"):
        _keyed((keys, vals), combiner=True, salt=4)


# -- plan structure & describe ------------------------------------------------

def test_plan_builder_fuses_adjacent_maps():
    op, _ = _counting_op()
    p = Plan().then(op).then(op).then_shuffle(_key_mod5).then(op)
    assert [type(s) for s in p.stages] == [MapStage, ShuffleStage, MapStage]
    assert len(p.stages[0].ops) == 2
    assert len(p.ops) == 3                     # legacy flat view
    assert p.num_shuffles == 1


def test_describe_shows_stage_dag():
    m = (MaRe((np.arange(8, dtype=np.int32),), plan_cache=PlanCache())
         .map(image="toolbox/concat")
         .repartition_by(_key_mod5)
         .reduce(image="toolbox/sum", depth=1))
    d = m.describe()
    assert "map[toolbox/concat:latest]" in d
    assert "shuffle" in d
    assert "reduce[toolbox/sum:latest, depth=1]" in d


def test_describe_shows_keyed_stage_and_counter_specs():
    m = (MaRe((np.arange(8, dtype=np.int32),), plan_cache=PlanCache())
         .repartition_by(_key_mod5)
         .reduce_by_key(_key_first, op="sum", num_keys=5))
    assert "reduce_by_key[sum, keys=5, combiner=on]" in m.describe()
    assert m.plan.counter_specs() == (
        (0, "shuffle_dropped"),
        (1, "key_overflow"), (1, "shuffle_dropped"),
        (1, "exchanged_records"), (1, "max_send_count"),
        (1, "exchange_buffer_rows"))


def test_dataset_property_materializes_pending_plan():
    op, traces = _counting_op()
    m = MaRe((np.arange(8, dtype=np.int32),), plan_cache=PlanCache()).map(
        op=op)
    assert traces["n"] == 0
    ds = m.dataset                             # action: runs the plan
    assert traces["n"] == 1
    assert m.plan.empty
    assert ds.num_shards == jax.device_count()


def test_aot_compile_error_surfaces_from_the_action(monkeypatch):
    def refuse(self, *a, **kw):
        raise RuntimeError("compiler refused the program")

    monkeypatch.setattr(jax.stages.Lowered, "compile", refuse)
    m = MaRe((np.arange(16, dtype=np.int32),), plan_cache=PlanCache()) \
        .map(op=_counting_op()[0])
    with pytest.raises(RuntimeError, match="compiler refused"):
        m.collect()


def test_compiled_program_does_not_rejit_on_mismatched_arguments():
    data = (np.arange(16, dtype=np.int32),)
    ds = from_host(data, compat.make_mesh((1,), ("data",)))
    plan = MaRe(ds).map(op=_counting_op()[0]).plan
    prog = planner_lib.compile_plan(plan, ds, cache=PlanCache())
    prog.ensure_compiled(ds.records, ds.counts)
    wider = (jnp.zeros((32,), jnp.int32),)
    with pytest.raises(Exception):
        prog(wider, ds.counts)


# -- program names and stage scopes -------------------------------------------

def _gc_plan(chars):
    records = {"data": np.zeros((8, 4), np.uint8),
               "len": np.full((8,), 4, np.int32)}
    return (MaRe(records, plan_cache=PlanCache())
            .map(image="ubuntu", command=f"grep-chars {chars}")
            .reduce(image="ubuntu", command="awk-sum")).plan


def test_program_names_differ_by_grep_chars_argument_and_are_stable():
    import subprocess
    import sys
    queries = ("GC", "AT", "N", "ACGT")
    names = [planner_lib.program_name(_gc_plan(q)) for q in queries]
    assert len(set(names)) == len(queries)
    assert all(re.fullmatch(r"mare_ubuntu_grep_chars_[A-Z]+_reduce_ubuntu_"
                            r"awk_sum_[0-9a-f]{6}", n) for n in names)
    assert names == [planner_lib.program_name(_gc_plan(q))
                     for q in queries]
    # the same in a fresh process (a digest of hashlib, not hash())
    code = ("import sys; sys.path.insert(0, 'tests');"
            "import test_planner as t;"
            "print(' '.join(t.planner_lib.program_name(t._gc_plan(q))"
            f" for q in {queries!r}))")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True,
                         env={**os.environ, "PYTHONHASHSEED": "7"})
    assert out.stdout.split() == names


def _mod16(part, **kw):
    return make_partition(((part.records[0] * 7) % 16,), part.count)


def test_compiled_program_is_named_after_its_plan():
    m = (MaRe((np.arange(64, dtype=np.int32),), plan_cache=PlanCache())
         .map(op=ContainerOp(image="test/mod16", fn=_mod16))
         .reduce_by_key(_key_first, op="sum", num_keys=16))
    plan = m.plan
    m.collect()
    (prog,) = m.plan_cache.programs()
    assert prog.name == planner_lib.program_name(plan)
    assert prog.as_text().startswith(f"HloModule jit_{prog.name}")
    scopes = set(prog.op_scopes().values())
    # one device: no exchange, and the combiner's table is the answer
    # (tests/distributed/stage_scopes.py holds the multi-device scopes)
    assert {"s0.map", "s1.reduce_by_key/combine"} <= scopes
    assert not {"s1.reduce_by_key/merge",
                "s1.reduce_by_key/exchange"} & scopes


def _strip_metadata(hlo: str) -> str:
    text = re.sub(r",? metadata=\{[^}]*\}", "", hlo)
    text = re.sub(r"\n\n(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n.*?(?=\n\n)", "", text, flags=re.S)
    return text.split("\n", 1)[1]               # drop the module's name


def test_stage_scopes_change_metadata_only(monkeypatch):
    import contextlib
    data = (np.arange(64, dtype=np.int32),)
    ds = from_host(data, compat.make_mesh((1,), ("data",)))
    plan = (MaRe(ds).map(op=ContainerOp(image="test/mod16", fn=_mod16))
            .reduce_by_key(_key_first, op="sum", num_keys=16,
                           combiner=False, salt=2).plan)

    def compiled_text() -> str:
        prog = planner_lib.compile_plan(plan, ds, cache=PlanCache())
        prog.ensure_compiled(ds.records, ds.counts)
        return prog.as_text()

    scoped = compiled_text()
    assert 'op_name="jit(mare_' in scoped and "/s1.reduce_by_key/merge/" \
        in scoped
    monkeypatch.setattr(planner_lib.jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = compiled_text()
    assert "s1.reduce_by_key" not in bare
    assert _strip_metadata(scoped) == _strip_metadata(bare)
