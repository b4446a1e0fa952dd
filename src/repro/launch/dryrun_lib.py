"""Dry-run library: lower + compile every (arch x shape x mesh) cell and
extract the three roofline terms from the compiled artifact.

Terms (TPU v5e targets, per chip):
  compute    = HLO_FLOPs(per-device) / 197e12 FLOP/s (bf16)
  memory     = HLO_bytes(per-device) / 819e9 B/s (HBM)
  collective = weighted collective bytes(per-device) / 50e9 B/s (ICI link)

``cost_analysis`` supplies FLOPs/bytes of the post-SPMD per-device module;
collective bytes are parsed from ``compiled.as_text()`` with standard
per-op wire-cost factors (ring algorithms):
  all-reduce 2(n-1)/n, all-gather/reduce-scatter/all-to-all (n-1)/n,
  collective-permute 1.0 — n = largest mesh axis (conservative).
"""
from __future__ import annotations

import re
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.shapes import ShapeConfig
from repro.models import build_model
from repro.models.common import ModelConfig, param_count_analytic
from repro.optim import adafactor, adamw
from repro.optim.schedule import cosine_warmup
from repro.sharding import Rules, make_rules, use_rules
from repro.train.step import StepConfig, TrainState, make_train_step

# v5e hardware model
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # B/s per chip
ICI_BW = 50e9                # B/s per link

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16}

_COLL_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0,
                "reduce-scatter": 1.0, "all-to-all": 1.0,
                "collective-permute": 1.0}

_SHAPE_RE = re.compile(r"(bf16|f64|f32|f16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|"
                       r"s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]")


# ---------------------------------------------------------------------------
# Rules / sharding selection per (arch x shape)
# ---------------------------------------------------------------------------

def rules_for(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh) -> Rules:
    # Sequence-sharding breaks seq-chunked recurrences; SSM/hybrid keep
    # seq local and instead spread BATCH over the whole mesh when it
    # divides (16x fewer tokens/device than DP-only — §Perf hymba-1).
    seq_shard = cfg.family not in ("ssm", "hybrid")
    if cfg.family == "hybrid" and cfg.ssm_cp and shape.kind == "prefill":
        seq_shard = True          # context-parallel SSM (§Perf hymba-3)
    if shape.is_decode:
        seq_shard = False
    rules = make_rules(mesh, fsdp=True, seq_shard=seq_shard)
    if cfg.family in ("ssm", "hybrid") and not shape.is_decode:
        axes_all = tuple(a for a in ("pod", "data", "model")
                         if a in mesh.shape)
        size_all = 1
        for a in axes_all:
            size_all *= int(mesh.shape[a])
        if shape.global_batch % size_all == 0:
            table = dict(rules.table)
            table["batch"] = axes_all
            if cfg.family == "ssm":
                # xLSTM: 4 heads never shard over model=16, but head_dim
                # (512) does — TP the mLSTM head_dim so grads stop being
                # replicated-over-model (§Perf xlstm-1)
                table["hd"] = "model"
            rules = Rules(table=table, mesh_shape=rules.mesh_shape)
    return rules


def choose_optimizer(cfg: ModelConfig):
    """Adafactor above 10B params (factored 2nd moments — the 1T memory
    budget), AdamW below."""
    if param_count_analytic(cfg) > 10e9:
        return adafactor(), "adafactor"
    return adamw(), "adamw"


# ---------------------------------------------------------------------------
# input_specs — ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Batch stand-ins (no allocation).  For train: tokens+labels; audio
    adds stub frame embeddings; vlm adds stub patch embeddings (text len
    shrinks so total positions == shape.seq_len)."""
    B, S = shape.global_batch, shape.seq_len
    dt = cfg.param_dtype
    i32 = jnp.int32
    s_text = S - (cfg.num_patches if cfg.family == "vlm" else 0)
    specs: Dict[str, Any] = {}
    if shape.kind == "train":
        specs["tokens"] = jax.ShapeDtypeStruct((B, s_text), i32)
        specs["labels"] = jax.ShapeDtypeStruct((B, s_text), i32)
    elif shape.kind == "prefill":
        specs["tokens"] = jax.ShapeDtypeStruct((B, s_text), i32)
    else:   # decode / long_decode: one new token (cache specs built apart)
        specs["tokens"] = jax.ShapeDtypeStruct((B,), i32)
    if cfg.family == "audio" and shape.kind != "decode":
        specs["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder_seq, cfg.d_model), dt)
    if cfg.family == "vlm" and shape.kind in ("train", "prefill"):
        specs["patch_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.num_patches, cfg.d_model), dt)
    if shape.kind == "train" and cfg.family == "vlm":
        specs["labels"] = jax.ShapeDtypeStruct((B, s_text), i32)
    return specs


def batch_shardings(specs: Dict[str, Any], mesh: Mesh, rules: Rules
                    ) -> Dict[str, Any]:
    out = {}
    for k, v in specs.items():
        if v.ndim == 2 and k in ("tokens", "labels"):
            spec = rules.spec_for(("batch", "seq"), dims=v.shape)
        elif v.ndim == 3:
            spec = rules.spec_for(("batch", "seq", None), dims=v.shape)
        elif v.ndim == 1:
            spec = rules.spec_for(("batch",), dims=v.shape)
        else:
            spec = P()
        out[k] = NamedSharding(mesh, spec)
    return out


def _cache_sharding(leaf, cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                    rules: Rules) -> NamedSharding:
    """Heuristic cache shardings: batch dim -> data axes, cache-seq dim ->
    model axis (context-sharded KV for long decode)."""
    dims = list(leaf.shape)
    B = shape.global_batch
    logical = [None] * len(dims)
    for i, d in enumerate(dims):
        if d == B and "batch" not in logical:
            logical[i] = "batch"
        elif d >= 1024 and d >= shape.seq_len // 2:
            logical[i] = "kv_seq"
    return NamedSharding(mesh, rules.spec_for(logical, dims=dims))


# ---------------------------------------------------------------------------
# Collective-byte extraction
# ---------------------------------------------------------------------------

def _shape_bytes(text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str, ring_n: int = 16) -> Dict[str, Any]:
    """Sum result bytes of every collective op in the per-device module,
    with ring wire-cost factors applied."""
    per_op: Dict[str, int] = {k: 0 for k in _COLL_FACTOR}
    counts: Dict[str, int] = {k: 0 for k in _COLL_FACTOR}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+"
                     r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                     r"collective-permute)", ls)
        if not m:
            continue
        shape_txt, op = m.group(1), m.group(2)
        b = _shape_bytes(shape_txt)
        per_op[op] += b
        counts[op] += 1
    factor = {k: _COLL_FACTOR[k] * (ring_n - 1) / ring_n
              if k != "collective-permute" else 1.0 for k in _COLL_FACTOR}
    wire = {k: per_op[k] * factor[k] for k in per_op}
    return {"bytes_by_op": per_op, "counts": counts,
            "wire_bytes": sum(wire.values())}


# ---------------------------------------------------------------------------
# Cell dry-run
# ---------------------------------------------------------------------------

def dry_run_cell(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                 extract_collectives: bool = True,
                 step_cfg: Optional[StepConfig] = None,
                 donate: bool = True,
                 save_hlo: Optional[str] = None) -> Dict[str, Any]:
    scfg0 = step_cfg or StepConfig(grad_sync="fused")
    moe_mode = scfg0.moe_mode
    if cfg.is_moe and shape.is_decode and moe_mode == "weight_gather":
        # decode policy: weights >> tokens, so activation-stationary
        # dispatch wins by ~30x on the collective term (§Perf kimi-d1)
        moe_mode = "token_gather"
    if cfg.is_moe and moe_mode != cfg.moe_mode:
        cfg = cfg.scaled(moe_mode=moe_mode)
    if cfg.family == "hybrid" and shape.kind == "prefill" and \
            shape.global_batch % mesh.size != 0:
        cfg = cfg.scaled(ssm_cp=True)   # seq-shard via boundary exchange
    model = build_model(cfg)
    if scfg0.grad_sync == "mare_tree":
        # paper-faithful: replicated params, explicit K-level ppermute tree
        from repro.sharding import data_only_rules
        rules = data_only_rules(mesh)
    else:
        rules = rules_for(cfg, shape, mesh)
    specs = input_specs(cfg, shape)
    b_shard = batch_shardings(specs, mesh, rules)
    scfg = step_cfg or StepConfig(grad_sync="fused")
    t0 = time.monotonic()

    params_struct = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    ax = model.logical_axes()
    p_shard = jax.tree.map(
        lambda leaf, axes: NamedSharding(
            mesh, rules.spec_for(tuple(axes), dims=leaf.shape)),
        params_struct, ax,
        is_leaf=lambda t: isinstance(t, tuple) and all(
            isinstance(e, (str, type(None))) for e in t))

    if shape.kind == "train":
        opt, opt_name = choose_optimizer(cfg)
        step = make_train_step(model, opt,
                               cosine_warmup(3e-4, 100, 10000),
                               scfg, mesh=mesh, rules=rules)
        state_struct = jax.eval_shape(
            lambda p: TrainState(params=p, opt_state=opt.init(p),
                                 step=jnp.zeros((), jnp.int32),
                                 residual=()), params_struct)
        st_shard = TrainState(
            params=p_shard,
            opt_state=jax.tree.map(lambda _: None, state_struct.opt_state),
            step=NamedSharding(mesh, P()), residual=())
        jitted = jax.jit(step,
                         in_shardings=(st_shard, b_shard),
                         donate_argnums=(0,) if donate else ())
        lowered = jitted.lower(state_struct, specs)
    elif shape.kind == "prefill":
        def prefill_fn(params, batch):
            with use_rules(rules, mesh):
                logits, caches = model.prefill(params, batch, shape.seq_len)
            return logits, caches

        jitted = jax.jit(prefill_fn, in_shardings=(p_shard, b_shard))
        lowered = jitted.lower(params_struct, specs)
    else:
        # decode: cache of seq_len, one new token
        with use_rules(rules, mesh):
            if cfg.family == "audio":
                pre_specs = {"tokens": jax.ShapeDtypeStruct(
                    (shape.global_batch, 8), jnp.int32),
                    "frames": specs["frames"] if "frames" in specs else
                    jax.ShapeDtypeStruct(
                        (shape.global_batch, cfg.encoder_seq, cfg.d_model),
                        cfg.param_dtype)}
                _, cache_struct = jax.eval_shape(
                    lambda p, b: model.prefill(p, b, shape.seq_len),
                    params_struct, pre_specs)
            else:
                cache_struct = jax.eval_shape(
                    lambda: model.init_cache(shape.global_batch,
                                             shape.seq_len))
        c_shard = jax.tree.map(
            lambda leaf: _cache_sharding(leaf, cfg, shape, mesh, rules),
            cache_struct)

        def decode_fn(params, caches, tokens):
            with use_rules(rules, mesh):
                return model.decode_step(params, caches, tokens)

        jitted = jax.jit(decode_fn,
                         in_shardings=(p_shard, c_shard,
                                       b_shard["tokens"]),
                         donate_argnums=(1,) if donate else ())
        lowered = jitted.lower(params_struct, cache_struct,
                               specs["tokens"])

    t_lower = time.monotonic() - t0
    compiled = lowered.compile()
    t_compile = time.monotonic() - t0 - t_lower

    # XLA's own cost_analysis (trip-count-blind; kept as cross-check)
    cost = compiled.cost_analysis() or {}
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "temp_size_in_bytes", 0) or 0) +
                          (getattr(mem, "argument_size_in_bytes", 0) or 0),
        }
    except Exception as e:          # CPU backend may not implement it
        mem_info = {"error": str(e)}

    coll: Dict[str, Any] = {}
    flops = xla_flops
    byt = xla_bytes
    if extract_collectives:
        from repro.launch.hlo_cost import analyze
        text = compiled.as_text()
        walk = analyze(text)
        flops = walk["flops"]              # trip-count-aware, per device
        byt = walk["bytes"]
        coll = {"bytes_by_op": walk["coll_bytes_by_op"],
                "counts": walk["coll_counts"],
                "wire_bytes": walk["wire_bytes"],
                "unresolved_whiles": walk["unresolved_whiles"]}
        if save_hlo:
            with open(save_hlo, "w") as f:
                f.write(text)

    n_chips = mesh.size
    result = {
        "arch": cfg.name, "shape": shape.name,
        "mesh": dict(mesh.shape),
        "flops": flops, "bytes": byt,
        "xla_flops": xla_flops, "xla_bytes": xla_bytes,
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": byt / HBM_BW,
        "collective_s": (coll.get("wire_bytes", 0.0) / ICI_BW
                         if coll else None),
        "collectives": coll,
        "memory": mem_info,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "n_chips": n_chips,
    }
    return result
