#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, and print its result line.

    python bench/run.py --workload kmer12.batch --seed 7 --seconds 40 --trace 0

In order: the data is made from ``--seed``; set-up ingests, compiles and
warms up the cell's own shapes (``setup_s``, from process start); the
window drives the cell's traffic for ``--seconds``; the answers are
compared with the plain reference; the last line of standard output is
one JSON object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``,
each number compared beside its limit (also the last lines of standard
error). ``--trace 0`` reports the cell's end-to-end metrics; ``--trace
1`` profiles the window and reports its per-layer metrics.

Off a TPU it exits 2 without a result, unless ``--rehearse`` is given:
that runs the cell at the configuration's ``rehearsal`` sizes on any
backend, with virtual CPU devices for a multi-chip cell. JAX's
persistent compilation cache is ``<checkout>/.jax_cache``; a traced run
writes its profile under ``<checkout>/.bench_trace``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
TRACE_DIR = ROOT / ".bench_trace"


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="rehearsal sizes, on any backend")
    return ap.parse_args(argv)


def _environment(chips: int, rehearse: bool) -> None:
    """Before JAX is imported: the segment reduce takes its static
    default (no minutes of tuning per process), the compile cache lives
    in the checkout, and a CPU rehearsal gets one device per chip."""
    os.environ["REPRO_SEGMENT_AUTOTUNE"] = "0"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    if rehearse and chips > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}").strip()


def _profiler_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def execute(args: argparse.Namespace) -> int:
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import spec as spec_lib
    spec = spec_lib.load()
    cell = spec_lib.workload(spec, args.workload)
    cfg = spec_lib.config(spec, cell["config"])
    chips = int(cell["chips"])
    if args.rehearse:
        cfg = {**cfg, **cfg.get("rehearsal", {})}
    traffic = spec_lib.traffic(cell["traffic"], cfg)
    _environment(chips, args.rehearse)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"bench: no TPU (JAX found {platform}); --rehearse runs "
              "the rehearsal sizes on other backends", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    devices = devices[:chips]
    if platform == "tpu":
        from bench.peaks import peaks
        peaks(devices[0].device_kind)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from repro import compat
    from repro.compile_cache import enable_compile_cache
    from repro.obs import TRACER

    from bench import check, drive
    from bench.trace import find_xplane, reduce_trace
    enable_compile_cache()

    traced = bool(args.trace)
    annotate = jax.profiler.TraceAnnotation if traced \
        else (lambda name: contextlib.nullcontext())
    mesh = compat.make_mesh((chips,), ("data",), devices=devices)
    data = spec_lib.module("gen", cfg["data"]).make(cfg, chips, args.seed)
    run = drive.Run()
    loop = drive.load_loop(drive.Context(traffic, data, mesh, args.seed,
                                         annotate))
    run.setup_actions = loop.setup()

    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR),
                                 profiler_options=_profiler_options())
        TRACER.start()
    run.window_t0 = time.perf_counter()
    run.setup_s = run.window_t0 - T_START
    with annotate("bench.window"):
        loop.window(args.seconds, run)
    if traced:
        TRACER.stop()
        run.spans = TRACER.events()
        jax.profiler.stop_trace()

    run.peak_bytes = [int((d.memory_stats() or {}).get(
        "peak_bytes_in_use", 0)) for d in devices]
    del loop                         # the program's state, before the check
    if traced:
        xplane = find_xplane(str(TRACE_DIR))
        run.trace = reduce_trace(xplane) if xplane else None

    numbers = check.compare(run.actions, data)
    metrics = {}
    for m in spec_lib.metrics_for(spec, args.workload, traced):
        value = spec_lib.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": chips, "memory_peak_bytes": max(run.peak_bytes)}
    result = {"correct": check.is_correct(numbers),
              "attempted": len(run.actions),
              "failed": numbers["failed_actions"][0],
              "metrics": metrics, "device": device}
    if traced and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {
            "device_ops": [list(x) for x in run.trace.device_ops],
            "idle_gaps": [list(x) for x in run.trace.idle_gaps]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in numbers.items()}
    for k, (v, lim) in numbers.items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    return execute(parse(argv))


if __name__ == "__main__":
    sys.exit(main())
