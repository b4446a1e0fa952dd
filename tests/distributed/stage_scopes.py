"""2-device stage scopes: the program of ``from_source -> map(kmer-stats)
-> reduce_by_key`` names its ops by stage, and its keyed stage's
combine, exchange (an all-to-all between the two devices) and merge,
with and without the salted second hop; on two devices the stage is not
lowered for one."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import tempfile

import jax
import numpy as np

from repro.core import MaRe, PlanCache
from repro.io import fasta_source

assert jax.device_count() == 2
rng = np.random.default_rng(11)
path = os.path.join(tempfile.mkdtemp(), "reads.fa")
with open(path, "w") as f:
    f.write("".join(f">r{i}\n{''.join(rng.choice(list('ACGT'), 60))}\n"
                    for i in range(256)))

PARTS = {"s0.map", "s1.reduce_by_key/combine", "s1.reduce_by_key/exchange",
         "s1.reduce_by_key/merge"}
for kw in ({}, {"combiner": False, "salt": 2}):
    cache = PlanCache()
    m = MaRe.from_source(fasta_source(path))
    m.plan_cache = cache
    q = m.map(image="kmer-stats", k=4).reduce_by_key(
        lambda r: r[0], value_by=lambda r: (r[1],), op="sum", **kw)
    keys, (sums,), counts = q.collect()
    assert len(keys) == 256 and int(counts.sum()) == 256 * 57, kw
    assert q.report().diagnostics["stage1.local_keyed"] == 0, kw
    (prog,) = cache.programs()
    scopes = prog.op_scopes()
    assert PARTS <= set(scopes.values()), (kw, set(scopes.values()))
    text = prog.as_text()
    a2a = [n for n in scopes if n.startswith("all-to-all")]
    assert a2a and all(scopes[n] == "s1.reduce_by_key/exchange"
                       for n in a2a), (kw, {n: scopes[n] for n in a2a})
    assert prog.name.startswith("mare_kmer_stats_reduce_by_key_")
    assert f"HloModule jit_{prog.name}," in text
print("OK")
