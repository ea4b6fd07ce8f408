"""Mixture-of-Experts FFN: top-k routing with capacity, expert-parallel GEMMs.

TPU-native dispatch (static shapes, no ragged tensors): per expert, the top-C
tokens among those that routed to it are gathered (``top_k`` over the masked
router scores), pushed through the expert's stacked-weight GEMM, and
scatter-added back scaled by the gate.  Tokens beyond capacity are dropped
(standard GShard/Switch semantics); an aux load-balancing loss is returned.

Sharding: expert-stacked weights (E, d, ff) shard E on the "model" axis (EP)
and d on "data" (FSDP); the (E, C, d) dispatch buffer shards E on "model" —
XLA SPMD emits the all-to-all-equivalent collective pattern for the
gather/scatter between token space (batch-sharded) and expert space.

The paper's tie-in (DESIGN §4): the union sampler's i.i.d. guarantee is what
makes the load-balancing statistics unbiased.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _pspec(*parts):
    return jax.sharding.PartitionSpec(*parts)


def _constrain(x: jnp.ndarray, parts) -> jnp.ndarray:
    """Best-effort sharding constraint (no-op without an ambient mesh)."""
    am = _ambient_mesh()
    if am is None:
        return x
    axes = am.axis_names
    fixed = []
    for dim, p in zip(x.shape, parts):
        if p is None:
            fixed.append(None)
            continue
        names = p if isinstance(p, tuple) else (p,)
        names = tuple(n for n in names if n in axes)
        n = int(np.prod([am.shape[a] for a in names])) if names else 1
        if names and n > 1 and dim % n == 0:
            fixed.append(names if len(names) > 1 else names[0])
        else:
            fixed.append(None)
    if all(f is None for f in fixed):
        return x
    return jax.lax.with_sharding_constraint(x, _pspec(*fixed))


@dataclasses.dataclass(frozen=True)
class MoEDims:
    d_model: int
    n_experts: int
    top_k: int
    d_ff: int
    capacity_factor: float = 1.25


def moe_param_shapes(dims: MoEDims) -> Dict[str, Tuple[int, ...]]:
    return {
        "router": (dims.d_model, dims.n_experts),
        "w_gate": (dims.n_experts, dims.d_model, dims.d_ff),
        "w_up": (dims.n_experts, dims.d_model, dims.d_ff),
        "w_down": (dims.n_experts, dims.d_ff, dims.d_model),
    }


def moe_ffn(params: Dict[str, jnp.ndarray], x: jnp.ndarray, dims: MoEDims,
            capacity: int | None = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B,S,d) -> (out (B,S,d), aux_loss scalar).

    ``capacity=T`` gives dropless routing (the decode path uses this: at
    one-token-per-sequence batches, capacity dropping would be semantic).
    """
    Bsz, S, d = x.shape
    T = Bsz * S
    xt = x.reshape(T, d)
    E, K = dims.n_experts, dims.top_k
    C = capacity if capacity is not None else max(
        int(dims.capacity_factor * K * T / E), 1)
    C = min(C, T)

    xt = _constrain(xt, [("pod", "data"), None])   # tokens stay DP-sharded
    logits = jnp.einsum("td,de->te", xt, params["router"].astype(x.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)      # (T,E)
    topv, topi = jax.lax.top_k(probs, K)                             # (T,K)
    # normalized combine weights over the chosen experts
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # mask of token->expert assignment, scored by gate for capacity ranking
    assign = jnp.zeros((T, E), jnp.float32)
    assign = assign.at[jnp.arange(T)[:, None], topi].set(topv)       # (T,E)

    # per expert: top-C tokens by gate score (capacity enforcement)
    scores_eT = assign.T                                             # (E,T)
    cap_score, cap_idx = jax.lax.top_k(scores_eT, C)                 # (E,C)
    valid = cap_score > 0.0                                          # (E,C)

    xg = jnp.take(xt, cap_idx.reshape(-1), axis=0).reshape(E, C, d)
    xg = _constrain(xg, ["model", None, None])     # EP: experts on "model"
    xg = xg * valid[..., None].astype(xg.dtype)

    g = jnp.einsum("ecd,edf->ecf", xg, params["w_gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", xg, params["w_up"].astype(x.dtype))
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                   params["w_down"].astype(x.dtype))
    y = y * (cap_score[..., None] * valid[..., None]).astype(y.dtype)
    y = _constrain(y, ["model", None, None])

    out = jnp.zeros((T, d), y.dtype).at[cap_idx.reshape(-1)].add(
        y.reshape(E * C, d))
    # combine lands DP-sharded: the cross-expert reduction is then a
    # reduce-scatter over "model" of LOCAL token rows, not a global AR
    out = _constrain(out, [("pod", "data"), None])

    # Switch-style aux loss: E * sum_e (frac tokens to e) * (mean router prob e)
    imp = probs.mean(axis=0)                                         # (E,)
    load = (assign > 0).astype(jnp.float32).mean(axis=0)             # (E,)
    aux = E * jnp.sum(imp * load)
    return out.reshape(Bsz, S, d), aux


# ---------------------------------------------------------------------------
# shard_map expert-parallel MoE (§Perf arctic iteration: explicit collective
# schedule — local dispatch + one bf16 psum over "model", replacing GSPMD's
# gather+f32-all-reduce lowering of jnp.take across shards)
# ---------------------------------------------------------------------------


def _ambient_mesh():
    from ..launch.mesh import ambient_mesh
    return ambient_mesh()


def moe_ffn_dist(params: Dict[str, jnp.ndarray], x: jnp.ndarray,
                 dims: MoEDims) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE under shard_map.

    Per (data, model) shard: route the shard's tokens, build local-expert
    capacity buffers, run the local expert GEMMs, scatter back, and psum the
    partial outputs over "model".  Collectives per layer: the seq all-gather
    at entry (GSPMD reshard) + one psum — vs the gather+f32-AR pattern GSPMD
    derives from cross-shard ``jnp.take`` (≈25x more bytes, measured:
    EXPERIMENTS.md §Perf cell 3).
    """
    am = _ambient_mesh()
    axes = am.axis_names
    P = jax.sharding.PartitionSpec
    da = tuple(a for a in ("pod", "data") if a in axes)
    dd = int(np.prod([am.shape[a] for a in da])) if da else 1
    mo = am.shape["model"]
    E, K = dims.n_experts, dims.top_k
    E_loc = E // mo
    Bsz, S, d = x.shape
    T_loc = (Bsz // dd) * S
    C = min(max(int(dims.capacity_factor * K * T_loc / E), 1), T_loc)
    da_spec = (da if len(da) > 1 else da[0]) if da else None

    def block(xb, wr, wg, wu, wd):
        Tb = xb.shape[0] * xb.shape[1]
        xt = xb.reshape(Tb, d)
        probs = jax.nn.softmax(
            jnp.einsum("td,de->te", xt, wr.astype(xt.dtype)).astype(jnp.float32),
            axis=-1)
        topv, topi = jax.lax.top_k(probs, K)
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
        assign = jnp.zeros((Tb, E), jnp.float32)
        assign = assign.at[jnp.arange(Tb)[:, None], topi].set(topv)
        cap_score, cap_idx = jax.lax.top_k(assign.T, C)          # (E, C)
        j = jax.lax.axis_index("model")
        cs = jax.lax.dynamic_slice_in_dim(cap_score, j * E_loc, E_loc, 0)
        ci = jax.lax.dynamic_slice_in_dim(cap_idx, j * E_loc, E_loc, 0)
        valid = cs > 0.0
        xg = jnp.take(xt, ci.reshape(-1), axis=0).reshape(E_loc, C, d)
        xg = xg * valid[..., None].astype(xg.dtype)
        g = jnp.einsum("ecd,edf->ecf", xg, wg.astype(xt.dtype))
        u = jnp.einsum("ecd,edf->ecf", xg, wu.astype(xt.dtype))
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                       wd.astype(xt.dtype))
        y = y * (cs[..., None] * valid[..., None]).astype(y.dtype)
        out = jnp.zeros((Tb, d), y.dtype).at[ci.reshape(-1)].add(
            y.reshape(E_loc * C, d))
        out = jax.lax.psum(out, "model")
        imp = probs.mean(axis=0)
        load = (assign > 0).astype(jnp.float32).mean(axis=0)
        aux = E * jnp.sum(imp * load)
        if da:
            aux = jax.lax.pmean(aux, da)   # model axis is already invariant
        return out.reshape(xb.shape), aux

    fn = jax.shard_map(
        block, mesh=am,
        in_specs=(P(da_spec, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(da_spec, None, None), P()))
    return fn(x, params["router"], params["w_gate"], params["w_up"],
              params["w_down"])


def moe_ffn_auto(params: Dict[str, jnp.ndarray], x: jnp.ndarray,
                 dims: MoEDims) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """shard_map EP path when the ambient mesh allows it; dense otherwise."""
    am = _ambient_mesh()
    if am is not None and "model" in am.axis_names:
        mo = am.shape["model"]
        da = tuple(a for a in ("pod", "data") if a in am.axis_names)
        dd = int(np.prod([am.shape[a] for a in da])) if da else 1
        if mo > 1 and dims.n_experts % mo == 0 and x.shape[0] % max(dd, 1) == 0:
            return moe_ffn_dist(params, x, dims)
    return moe_ffn(params, x, dims)
