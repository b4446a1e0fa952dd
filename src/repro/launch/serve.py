"""Serving drivers.

Two modes behind one entry point:

* ``--service`` — the **multi-tenant query-service loop**: builds a
  shared dataset, starts a :class:`repro.serve.QueryService`, runs N
  tenant sessions issuing rounds of aggregation queries against the
  persisted shared prefix, and prints per-round latency (live p50/p99
  from the metrics registry), batch occupancy, per-tenant queue depths
  and the final cache/fairness picture.  This is the interactive
  serving demonstrator — ``benchmarks/serve.py`` is its measured twin.

      PYTHONPATH=src python -m repro.launch.serve --service \\
          --tenants 4 --rounds 5

* ``--model-smoke`` — the original batched token-decode smoke (prefill
  a prompt batch, greedy-decode N tokens):

      PYTHONPATH=src python -m repro.launch.serve --model-smoke \\
          --arch smollm-135m --smoke --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import sys
import threading
import time

import numpy as np

from repro.compile_cache import enable_compile_cache


# -- the legacy token-decode smoke -------------------------------------------

def _model_smoke(args) -> int:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, get_smoke_config
    from repro.models import build_model

    if args.arch is None:
        print("--model-smoke requires --arch", file=sys.stderr)
        return 2
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(
        args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    max_len = args.prompt_len + args.gen + (cfg.num_patches or 0)
    batch = {"tokens": jnp.asarray(rng.integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32)}
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(rng.normal(size=(
            args.batch, cfg.encoder_seq, cfg.d_model)), cfg.param_dtype)
    if cfg.family == "vlm" and cfg.num_patches:
        batch["patch_embeds"] = jnp.asarray(rng.normal(size=(
            args.batch, cfg.num_patches, cfg.d_model)), cfg.param_dtype)

    t0 = time.monotonic()
    prefill = jax.jit(lambda p, b: model.prefill(p, b, max_len))
    logits, caches = prefill(params, batch)
    jax.block_until_ready(logits)
    t_pre = time.monotonic() - t0
    decode = jax.jit(model.decode_step)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    out_tokens = [tok]
    t0 = time.monotonic()
    for i in range(args.gen - 1):
        lg, caches = decode(params, caches, tok)
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_dec = time.monotonic() - t0
    gen = np.stack([np.asarray(t) for t in out_tokens], axis=1)
    print(f"prefill: {t_pre*1e3:.1f} ms for {args.batch}x{args.prompt_len}")
    print(f"decode:  {t_dec/max(args.gen-1,1)*1e3:.2f} ms/token "
          f"(batch {args.batch})")
    print("generated:", gen[:2].tolist())
    return 0


# -- the query-service loop ---------------------------------------------------

READ_LEN = 64
QUERY_OPS = ("sum", "max", "min")


def _make_reads(n_reads: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    bases = np.frombuffer(b"ACGT", np.uint8)
    data = bases[rng.integers(0, 4, size=(n_reads, READ_LEN))]
    return {"data": data, "len": np.full((n_reads,), READ_LEN, np.int32)}


def _key_of(recs):
    # module-level keyBy/valueBy: lineage signatures and the compile
    # cache key on callable identity, so every session sharing these
    # functions shares programs AND batch keys; lambdas would defeat both
    return recs[0]


def _ones_of(recs):
    return (recs[1],)


def _service_loop(args) -> int:
    import jax

    from repro import compat
    from repro.core import MaRe
    from repro.obs import METRICS
    from repro.serve import QueryService, ServiceConfig

    k = args.k
    num_keys = 4 ** k
    mesh = compat.make_mesh((jax.device_count(),), ("data",))
    shared = MaRe(_make_reads(args.reads), mesh=mesh).dataset

    config = ServiceConfig(
        batch_window_s=args.batch_window,
        max_queued_per_tenant=args.max_queued,
        tenant_device_budget_bytes=(args.tenant_budget_mb << 20
                                    if args.tenant_budget_mb else None))
    print(f"service: {args.tenants} tenants x {args.rounds} rounds, "
          f"{jax.device_count()} devices, k={k} ({num_keys} keys), "
          f"batch_window={config.batch_window_s*1e3:.0f}ms")

    with QueryService(config=config) as svc:
        sessions = [svc.session(f"tenant{i}")
                    for i in range(args.tenants)]

        # shared prefix: one tenant persists the expensive map once;
        # every session's queries then start from the cached lineage node
        sessions[0].mare(shared).map(image="kmer-stats", k=k).persist()

        def query(sess, op):
            return (sess.mare(shared)
                    .map(image="kmer-stats", k=k)
                    .reduce_by_key(_key_of, value_by=_ones_of, op=op,
                                   num_keys=num_keys)
                    .collect(label=f"{op} query"))

        barrier = threading.Barrier(len(sessions))
        lat_lock = threading.Lock()
        latencies = []

        def client(sess):
            for rnd in range(args.rounds):
                op = QUERY_OPS[rnd % len(QUERY_OPS)]
                barrier.wait()          # all tenants fire together
                t0 = time.monotonic()
                query(sess, op)
                with lat_lock:
                    latencies.append(time.monotonic() - t0)

        threads = [threading.Thread(target=client, args=(s,), daemon=True)
                   for s in sessions]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t0

        total = len(latencies)
        lat = np.sort(np.asarray(latencies))
        occ = METRICS.histogram("serve.batch_occupancy")
        print(f"served {total} actions in {wall:.2f}s "
              f"({total / wall:.1f} QPS), "
              f"p50={lat[int(0.50 * (total - 1))] * 1e3:.1f}ms "
              f"p99={lat[int(0.99 * (total - 1))] * 1e3:.1f}ms, "
              f"mean batch occupancy={occ.mean:.2f}")
        # live histogram view (bucket resolution) vs the exact numbers
        h = METRICS.histogram("phase.queue_wait")
        if h.count:
            print(f"queue_wait (live est.): p50~{h.percentile(50)*1e3:.1f}ms "
                  f"p99~{h.percentile(99)*1e3:.1f}ms over {h.count} waits")
        for sess in sessions:
            rep = sess.report()
            print(f"  {sess.tenant}: {sess.reports.appended} actions, "
                  f"last={rep.describe() if rep else '<none>'}")
        print(METRICS.render("serve."))
        stats = svc.executor.mat_cache.stats()
        print(f"mat_cache: hits={stats['hits']} "
              f"shared_hits={stats['shared_hits']} "
              f"tenant_budget_violations={stats['tenant_budget_violations']}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--service", action="store_true",
                      help="run the multi-tenant query-service loop")
    mode.add_argument("--model-smoke", action="store_true",
                      help="legacy batched token-decode smoke")
    # service knobs
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--reads", type=int, default=2_048)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--batch-window", type=float, default=0.01)
    ap.add_argument("--max-queued", type=int, default=8)
    ap.add_argument("--tenant-budget-mb", type=int, default=None)
    # model-smoke knobs
    ap.add_argument("--arch")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--greedy", action="store_true", default=True)
    args = ap.parse_args(argv)

    enable_compile_cache()
    if args.model_smoke:
        return _model_smoke(args)
    return _service_loop(args)


if __name__ == "__main__":
    sys.exit(main())
