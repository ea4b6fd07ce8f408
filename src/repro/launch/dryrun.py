import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + os.environ.get("REPRO_DRYRUN_DEVICES", "512"))

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces (artifacts/<mesh>/<arch>__<shape>.json):
  * compiled.memory_analysis()  — per-device bytes (args/temp/output)
  * compiled.cost_analysis()    — per-device HLO FLOPs + bytes accessed
  * collective bytes parsed from the post-optimization HLO text, split by
    collective kind (all-reduce / all-gather / reduce-scatter / all-to-all /
    collective-permute, including -start async forms)
  * the three §Roofline terms (compute / memory / collective, seconds) and
    MODEL_FLOPS = 6·N_active·D (train) or 2·N_active·D (prefill/decode).

The FIRST TWO LINES of this file set XLA_FLAGS before any jax import —
jax locks the device count at first init.  Smoke tests and benchmarks do NOT
import this module, so they see 1 device.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
        [--mesh single|multi|both] [--out artifacts/]
"""

import argparse
import json
import re
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ASSIGNED_ARCHS, SHAPES, cell_runnable, get_config
from ..models import serve as mserve
from ..models.transformer import (ModelConfig, logical_axes, param_specs)
from ..train.optimizer import default_opt_for
from ..train.train_step import (TrainConfig, make_train_step,
                                train_state_logical_axes, train_state_specs)
from .mesh import make_production_mesh
from .sharding import (batch_is_sharded, batch_sharding, frontend_sharding,
                       replicated, tree_shardings)

# -- hardware constants (TPU v5e) -------------------------------------------
PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link (per-chip effective, documented)

_COLL_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8}


def parse_collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Sum operand bytes of every collective instruction, by kind."""
    out: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        if "-done" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        # operands appear inside the call parens with their shapes
        paren = line[m.end() - 1:]
        total = 0.0
        for dt, dims in _SHAPE_RE.findall(paren):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        out[kind] = out.get(kind, 0.0) + total
        out["count_" + kind] = out.get("count_" + kind, 0.0) + 1
    out["total"] = sum(v for k, v in out.items()
                       if not k.startswith("count_") and k != "total")
    return out


# ---------------------------------------------------------------------------
# Model FLOPs accounting (6·N_active·D)
# ---------------------------------------------------------------------------


def param_counts(cfg: ModelConfig) -> Tuple[float, float]:
    """(total, active) parameter counts (active discounts un-routed experts)."""
    specs = param_specs(cfg)
    total = float(sum(np.prod(s.shape) for s in specs.values()))
    embed = float(np.prod(specs["embed"].shape))
    expert = 0.0
    for k, s in specs.items():
        if ".moe_w_" in k or k.startswith("moe_w_") or "moe_w_" in k:
            expert += float(np.prod(s.shape))
    active = total - embed
    if cfg.n_experts:
        active -= expert * (1.0 - cfg.top_k / cfg.n_experts)
    return total, active


def model_flops(cfg: ModelConfig, shape_name: str) -> float:
    cell = SHAPES[shape_name]
    total, active = param_counts(cfg)
    if cell.kind == "train":
        tokens = cell.seq_len * cell.global_batch
        return 6.0 * active * tokens
    if cell.kind == "prefill":
        tokens = cell.seq_len * cell.global_batch
        return 2.0 * active * tokens
    # decode: one token per sequence
    return 2.0 * active * cell.global_batch


# ---------------------------------------------------------------------------
# Analytic per-device HBM traffic model (the roofline memory term)
#
# The census brackets HBM traffic ([hbm floor, post-fusion upper bound]) but
# cannot see TPU kernel fusion (per-tile flash/SSD traffic stays in VMEM).
# The structural model below counts what MUST cross HBM on the TPU target:
#   weights (gathered, per pass) - saved residuals - attention K/V chunk
#   re-reads - loss-head embedding/logits chunks - KV-cache reads -
#   optimizer state.  Formulas per cell kind.
# ---------------------------------------------------------------------------


def analytic_memory_bytes(cfg: ModelConfig, shape_name: str, mesh) -> float:
    cell = SHAPES[shape_name]
    n_chips = int(np.prod(mesh.devices.shape))
    tp = dict(mesh.shape).get("model", 1)
    dp = n_chips // tp
    B_loc = max(cell.global_batch // dp, 1)
    S = cell.seq_len
    total, active = param_counts(cfg)
    specs = param_specs(cfg)
    p_expert = sum(float(np.prod(sp.shape)) for k, sp in specs.items()
                   if "moe_w_" in k)
    p_dense = total - p_expert
    # per-device weight bytes read per pass (bf16): FSDP gathers the dense
    # weights to every device; experts stay EP-local
    w_pass = (p_dense + p_expert / tp) * 2.0

    if cell.kind == "train":
        passes = 3.0      # fwd + bwd (2x weight reads: dgrad + wgrad)
        opt = (total / n_chips) * (4 + 4 + 8)   # master r/w + moment traffic
        resid = cfg.n_layers * B_loc * (S / tp) * cfg.d_model * 2 * 2
        attn_kv = 0.0
        if cfg.n_heads:
            nq = max(S // cfg.q_chunk, 1)
            h_loc = max(cfg.n_heads / tp, 1)
            attn_kv = (cfg.n_layers * B_loc * S * h_loc * cfg.head_dim
                       * 2 * 2 * nq * 3)
        nc = max(S // cfg.loss_chunk, 1)
        loss = nc * (cfg.vocab / tp) * cfg.d_model * 2 * 2   # embed reads f+b
        loss += B_loc * S * (cfg.vocab / tp) * 4 * 2          # logits w+r
        return w_pass * passes + opt + resid + attn_kv + loss
    if cell.kind == "prefill":
        resid = cfg.n_layers * B_loc * (S / tp) * cfg.d_model * 2
        attn_kv = 0.0
        if cfg.n_heads:
            nq = max(S // cfg.q_chunk, 1)
            h_loc = max(cfg.n_heads / tp, 1)
            attn_kv = cfg.n_layers * B_loc * S * h_loc * cfg.head_dim * 2 * 2 * nq
        return w_pass + resid + attn_kv
    # decode: weights shard read once + full cache read/write
    cache = mserve.cache_specs(cfg, cell.global_batch, S)
    cache_bytes = sum(float(np.prod(sp.shape)) * 2 for sp in cache.values())
    return total * 2 / n_chips + cache_bytes / n_chips * 1.01


# ---------------------------------------------------------------------------
# input specs per (arch, shape)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, shape_name: str) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of the cell."""
    cell = SHAPES[shape_name]
    B, S = cell.global_batch, cell.seq_len
    i32 = jnp.int32
    dt = cfg.compute_dtype
    if cell.kind in ("train", "prefill"):
        toks = S
        batch = {}
        if cfg.frontend == "patch":
            toks = S - cfg.n_frontend_tokens
            batch["frontend"] = jax.ShapeDtypeStruct(
                (B, cfg.n_frontend_tokens, cfg.d_model), dt)
        elif cfg.frontend == "audio":
            batch["frontend"] = jax.ShapeDtypeStruct(
                (B, cfg.n_frontend_tokens, cfg.d_model), dt)
        batch["tokens"] = jax.ShapeDtypeStruct((B, toks), i32)
        if cell.kind == "train":
            batch["targets"] = jax.ShapeDtypeStruct((B, toks), i32)
        return batch
    # decode
    specs = {
        "cache": mserve.cache_specs(cfg, B, S),
        "tokens": jax.ShapeDtypeStruct((B, 1), i32),
        "lengths": jax.ShapeDtypeStruct((B,), i32),
    }
    return specs


# ---------------------------------------------------------------------------
# cell lowering
# ---------------------------------------------------------------------------


def lower_cell(arch: str, shape_name: str, mesh, *,
               compile_: bool = True) -> Dict[str, Any]:
    cfg = get_config(arch)
    cell = SHAPES[shape_name]
    t0 = time.perf_counter()
    bs = batch_is_sharded(mesh, cell.global_batch)

    if cell.kind == "train":
        n_micro = 4 if arch in ("mistral-large-123b", "arctic-480b",
                                "phi3.5-moe-42b-a6.6b", "zamba2-7b") else 1
        tc = TrainConfig(opt=default_opt_for(arch), n_microbatches=n_micro)
        step_fn = make_train_step(cfg, tc)
        state_specs = train_state_specs(cfg, tc)
        state_lax = train_state_logical_axes(cfg, tc)
        state_sh = {
            "step": replicated(mesh),
            "params": tree_shardings(mesh, state_specs["params"],
                                     state_lax["params"]),
            "opt": tree_shardings(mesh, state_specs["opt"], state_lax["opt"]),
        }
        batch = input_specs(cfg, shape_name)
        bsh = {k: (frontend_sharding(mesh, cell.global_batch)
                   if k == "frontend" else batch_sharding(mesh, cell.global_batch))
               for k in batch}
        fn = jax.jit(step_fn, in_shardings=(state_sh, bsh),
                     donate_argnums=(0,))
        with jax.set_mesh(mesh):
            lowered = fn.lower(state_specs, batch)
    elif cell.kind == "prefill":
        def fn_prefill(params, batch):
            return mserve.prefill_step(params, cfg, batch)
        pspecs = param_specs(cfg)
        psh = tree_shardings(mesh, pspecs, logical_axes(cfg))
        batch = input_specs(cfg, shape_name)
        bsh = {k: (frontend_sharding(mesh, cell.global_batch)
                   if k == "frontend" else batch_sharding(mesh, cell.global_batch))
               for k in batch}
        fn = jax.jit(fn_prefill, in_shardings=(psh, bsh))
        with jax.set_mesh(mesh):
            lowered = fn.lower(pspecs, batch)
    else:  # decode
        def fn_decode(params, cache, tokens, lengths):
            return mserve.decode_step(params, cfg, cache, tokens, lengths)
        pspecs = param_specs(cfg)
        psh = tree_shardings(mesh, pspecs, logical_axes(cfg))
        specs = input_specs(cfg, shape_name)
        csh = tree_shardings(mesh, specs["cache"],
                             mserve.cache_logical_axes(cfg, cell.global_batch,
                                                       cell.seq_len),
                             batch_sharded=bs)
        tsh = batch_sharding(mesh, cell.global_batch)
        fn = jax.jit(fn_decode, in_shardings=(psh, csh, tsh, tsh),
                     donate_argnums=(1,))
        with jax.set_mesh(mesh):
            lowered = fn.lower(pspecs, specs["cache"], specs["tokens"],
                               specs["lengths"])

    res: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": list(mesh.devices.shape),
                           "mesh_axes": list(mesh.axis_names),
                           "lower_s": time.perf_counter() - t0}
    if not compile_:
        return res
    t1 = time.perf_counter()
    compiled = lowered.compile()
    res["compile_s"] = time.perf_counter() - t1

    ma = compiled.memory_analysis()
    res["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "code_bytes": int(ma.generated_code_size_in_bytes),
    }
    per_dev = (ma.argument_size_in_bytes + ma.temp_size_in_bytes +
               ma.output_size_in_bytes - ma.alias_size_in_bytes)
    res["memory"]["per_device_total"] = int(per_dev)

    # raw cost_analysis counts loop bodies ONCE (a lax.scan over 88 layers is
    # under-counted 88x) — kept for reference; the census below re-derives
    # FLOPs/bytes/collectives from the HLO text with while-trip scaling.
    ca = compiled.cost_analysis() or {}
    res["cost_raw"] = {"flops": float(ca.get("flops", 0.0)),
                       "bytes_accessed": float(ca.get("bytes accessed", 0.0))}

    text = compiled.as_text()
    from .hlo_census import census
    cs = census(text)
    flops = cs.flops
    bytes_accessed = cs.hbm_bytes
    res["cost"] = {"flops": flops, "bytes_accessed": bytes_accessed,
                   "bytes_upper_bound": cs.bytes_accessed}
    res["collectives"] = {**{k: v for k, v in cs.collective_bytes.items()},
                          **{"count_" + k: v
                             for k, v in cs.collective_counts.items()},
                          "total": cs.total_collective_bytes}
    res["while_trip_counts"] = cs.while_trip_counts

    n_chips = int(np.prod(mesh.devices.shape))
    mf = model_flops(cfg, shape_name)
    total, active = param_counts(cfg)
    # census numbers are per-device (the partitioned module)
    compute_t = flops / PEAK_FLOPS
    # memory term: analytic structural HBM traffic (what must cross HBM on
    # the TPU target); the census floor (>=8MiB tensors) and post-fusion
    # upper bound bracket it in the artifact (EXPERIMENTS.md §Roofline notes)
    analytic_bytes = analytic_memory_bytes(cfg, shape_name, mesh)
    memory_t = analytic_bytes / HBM_BW
    coll_t = cs.total_collective_bytes / ICI_BW
    dominant = max((("compute", compute_t), ("memory", memory_t),
                    ("collective", coll_t)), key=lambda kv: kv[1])[0]
    res["roofline"] = {
        "n_chips": n_chips,
        "compute_s": compute_t,
        "memory_s": memory_t,
        "memory_census_floor_s": cs.hbm_bytes / HBM_BW,
        "memory_upper_s": cs.bytes_accessed / HBM_BW,
        "analytic_bytes": analytic_bytes,
        "collective_s": coll_t,
        "dominant": dominant,
        "model_flops_total": mf,
        "model_flops_per_chip": mf / n_chips,
        "hlo_flops_per_chip": flops,
        "useful_flops_ratio": (mf / n_chips) / flops if flops else 0.0,
        "params_total": total,
        "params_active": active,
    }
    return res


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts")
    ap.add_argument("--lower-only", action="store_true")
    args = ap.parse_args()

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod", make_production_mesh(multi_pod=True)))

    archs = [args.arch] if args.arch else ASSIGNED_ARCHS
    shapes = [args.shape] if args.shape else list(SHAPES)

    n_ok = n_skip = n_fail = 0
    for mesh_name, mesh in meshes:
        outdir = os.path.join(args.out, mesh_name)
        os.makedirs(outdir, exist_ok=True)
        for arch in archs:
            for shape in shapes:
                ok, why = cell_runnable(arch, shape)
                tag = f"{mesh_name}/{arch}__{shape}"
                path = os.path.join(outdir, f"{arch}__{shape}.json")
                if not ok:
                    with open(path, "w") as f:
                        json.dump({"arch": arch, "shape": shape,
                                   "skipped": why}, f, indent=1)
                    print(f"SKIP {tag}: {why}", flush=True)
                    n_skip += 1
                    continue
                try:
                    res = lower_cell(arch, shape, mesh,
                                     compile_=not args.lower_only)
                    with open(path, "w") as f:
                        json.dump(res, f, indent=1)
                    r = res.get("roofline", {})
                    print(f"OK   {tag}: compile={res.get('compile_s', 0):.1f}s "
                          f"mem/dev={res.get('memory', {}).get('per_device_total', 0)/2**30:.2f}GiB "
                          f"dom={r.get('dominant', '?')}", flush=True)
                    n_ok += 1
                except Exception as e:
                    n_fail += 1
                    with open(path, "w") as f:
                        json.dump({"arch": arch, "shape": shape,
                                   "error": repr(e),
                                   "traceback": traceback.format_exc()}, f,
                                  indent=1)
                    print(f"FAIL {tag}: {e}", flush=True)
    print(f"dry-run done: ok={n_ok} skip={n_skip} fail={n_fail}", flush=True)
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
