"""Fine-grained Mixture-of-Experts (DeepSeekMoE / DeepSeek-V2 / Jamba).

Dropless sort-based dispatch:
  1. router top-k per token,
  2. tokens replicated k ways and sorted by expert id,
  3. grouped expert matmuls via `jax.lax.ragged_dot` (the TPU analogue of
     MegaBlocks' grouped GEMM — no (T, E, C) one-hot dispatch tensor),
  4. weighted scatter-add back to token order.

Shared experts (DeepSeek) run as a plain dense MLP on every token.
Expert weights are sharded on the `model` mesh axis (EP); token tensors
on `data` — GSPMD inserts the dispatch collectives, and the shard_map
all-to-all variant is a perf-iteration option (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .layers import Params, dense_init, mlp, mlp_init


def moe_init(key, cfg) -> Params:
    d = cfg.d_model
    de = cfg.d_expert or cfg.d_ff
    E = cfg.n_experts
    ks = jax.random.split(key, 5)
    scale = 1.0 / np.sqrt(d)
    p: Params = {
        "router": dense_init(ks[0], d, E, scale=0.02),
        "w_gate": jax.random.normal(ks[1], (E, d, de), jnp.float32) * scale,
        "w_up": jax.random.normal(ks[2], (E, d, de), jnp.float32) * scale,
        "w_down": jax.random.normal(ks[3], (E, de, d), jnp.float32)
        / np.sqrt(de),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], d, cfg.n_shared_experts * de)
    return p


def moe_forward(p: Params, cfg, x: jnp.ndarray
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out, aux_loss). Dispatches to the expert-parallel
    shard_map path on a distributed mesh, else the local sort path."""
    from repro.distributed.hints import _STATE, axis_size, hints_enabled
    dp_size = 1
    for a in _STATE["data_axes"]:
        dp_size *= _STATE["sizes"].get(a, 1)
    tokens = x.shape[0] * x.shape[1]
    if hints_enabled() and axis_size("model") > 1 and \
            cfg.n_experts % axis_size("model") == 0 and \
            tokens % max(dp_size, 1) == 0:
        return moe_forward_ep(p, cfg, x)
    return moe_forward_local(p, cfg, x)


def moe_forward_local(p: Params, cfg, x: jnp.ndarray
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Single-device dropless path (sort + ragged_dot): x: (B, S, D)."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    dt = x.dtype
    T = B * S
    xf = x.reshape(T, D)

    logits = (xf @ p["router"].astype(dt)).astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, k)                  # (T, k)
    weights = top_vals / jnp.maximum(
        top_vals.sum(axis=-1, keepdims=True), 1e-9)

    # load-balance aux (Switch-style): E * Σ_e f_e · p̄_e
    f = jnp.zeros((E,), jnp.float32).at[top_idx.reshape(-1)].add(1.0) \
        / (T * k)
    aux = E * jnp.sum(f * probs.mean(axis=0))

    # sort token-replicas by expert
    flat_expert = top_idx.reshape(T * k)
    sort_idx = jnp.argsort(flat_expert)
    token_of = sort_idx // k
    xs = jnp.take(xf, token_of, axis=0)                          # (T·k, D)
    group_sizes = jnp.bincount(flat_expert, length=E).astype(jnp.int32)

    g = jax.lax.ragged_dot(xs, p["w_gate"].astype(dt), group_sizes)
    u = jax.lax.ragged_dot(xs, p["w_up"].astype(dt), group_sizes)
    h = jax.nn.silu(g) * u
    eo = jax.lax.ragged_dot(h, p["w_down"].astype(dt), group_sizes)

    w_sorted = weights.reshape(T * k)[sort_idx].astype(dt)
    out = jnp.zeros((T, D), dt).at[token_of].add(eo * w_sorted[:, None])

    if cfg.n_shared_experts:
        out = out + mlp(p["shared"], xf)
    return out.reshape(B, S, D), aux


def moe_forward_ep(p: Params, cfg, x: jnp.ndarray
                   ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE via shard_map over the `model` axis.

    Auto-GSPMD on the sort-based dispatch replicates token buffers and
    gathers expert weights (measured 2 TiB/device temp on
    deepseek-v2 × train_4k — §Perf log), so the distributed path is
    explicit: experts are sharded on `model`; every model rank holds its
    data shard's full token set, locally gathers the (capacity-bounded)
    slots routed to *its* experts, runs the expert FFNs, and the
    weighted partial outputs are psum'd over `model`. Capacity factor
    cfg.capacity_factor bounds memory (Switch-style token dropping,
    overflow slots masked).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.distributed.hints import _STATE, current_mesh

    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    dt = x.dtype
    mesh = current_mesh()
    n_ranks = _STATE["sizes"].get("model", 1)
    e_local = E // n_ranks
    dp = _STATE["data_axes"]
    dpa = dp if len(dp) > 1 else (dp[0] if dp else None)

    # cast OUTSIDE shard_map: the FSDP('data')->EP('model') reshard
    # all-gather then moves bf16, not f32 (halves gather traffic and
    # the transient gathered buffer)
    router = p["router"].astype(dt)
    experts = {kk: p[kk].astype(dt) for kk in ("w_gate", "w_up", "w_down")}

    def rank_fn(xf, router_w, w_gate, w_up, w_down):
        # xf: (T_loc, D) local tokens; expert weights: (e_local, ·, ·)
        T_loc = xf.shape[0]
        Tk = T_loc * k
        rank = jax.lax.axis_index("model")
        logits = (xf @ router_w).astype(jnp.float32)             # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_vals, top_idx = jax.lax.top_k(probs, k)
        weights = top_vals / jnp.maximum(
            top_vals.sum(axis=-1, keepdims=True), 1e-9)
        # globally exact load-balance aux: sum counts/probs over the data
        # axes BEFORE the nonlinear f·p̄ product (per-shard means differ)
        counts = jnp.zeros((E,), jnp.float32).at[top_idx.reshape(-1)].add(1.0)
        probs_sum = probs.sum(axis=0)
        t_glob = T_loc
        if dp:
            counts = jax.lax.psum(counts, tuple(dp))
            probs_sum = jax.lax.psum(probs_sum, tuple(dp))
            t_glob = T_loc * int(
                np.prod([_STATE["sizes"][a] for a in dp]))
        aux = E * jnp.sum((counts / (t_glob * k))
                          * (probs_sum / t_glob))

        flat_e = top_idx.reshape(Tk)
        flat_w = weights.reshape(Tk)
        tok_of = jnp.arange(Tk, dtype=jnp.int32) // k
        mine = (flat_e // e_local) == rank
        local_e = jnp.clip(flat_e - rank * e_local, 0, e_local - 1)
        # per-EXPERT capacity buffers -> dense batched matmuls with
        # ideal fwd AND bwd flops (ragged_dot's reference grad computes
        # every expert over the full buffer — measured 10× waste, §Perf)
        Ce = max(int(Tk / E * cfg.capacity_factor + 7) // 8 * 8, 8)
        onehot = (local_e[:, None] == jnp.arange(e_local)[None]) \
            & mine[:, None]                                      # (Tk, eL)
        pos = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
        pos_slot = jnp.take_along_axis(pos, local_e[:, None], axis=1)[:, 0]
        keep = mine & (pos_slot < Ce)
        # scatter slots into (e_local, Ce) index/weight buffers
        flat_idx = jnp.where(keep, local_e * (Ce + 1) + pos_slot,
                             e_local * (Ce + 1))
        buf_tok = jnp.full((e_local * (Ce + 1) + 1,), T_loc, jnp.int32
                           ).at[flat_idx].set(jnp.where(keep, tok_of, T_loc))
        buf_w = jnp.zeros((e_local * (Ce + 1) + 1,), jnp.float32
                          ).at[flat_idx].set(jnp.where(keep, flat_w, 0.0))
        buf_tok = buf_tok[:-1].reshape(e_local, Ce + 1)[:, :Ce]
        buf_w = buf_w[:-1].reshape(e_local, Ce + 1)[:, :Ce]

        xpad = jnp.concatenate([xf, jnp.zeros((1, D), dt)], axis=0)
        xs = jnp.take(xpad, buf_tok.reshape(-1), axis=0
                      ).reshape(e_local, Ce, D)
        g = jnp.einsum("ecd,edf->ecf", xs, w_gate)
        u = jnp.einsum("ecd,edf->ecf", xs, w_up)
        h = jax.nn.silu(g) * u
        eo = jnp.einsum("ecf,efd->ecd", h, w_down)
        out = jnp.zeros((T_loc + 1, D), dt).at[buf_tok.reshape(-1)].add(
            (eo * buf_w[..., None].astype(dt)).reshape(-1, D))[:T_loc]
        # NOTE: psum_scatter into the seq-parallel layout was tried and
        # REGRESSED (coll 39.5s -> 131.8s on deepseek-v2×train_4k): its
        # backward transposes to an all-gather per layer and the residual
        # stream resharding costs more than the (n-1)/n wire it saves.
        # §Perf iteration A7 (refuted). Plain psum kept.
        out = jax.lax.psum(out, "model")
        return out, aux

    xf = x.reshape(B * S, D)
    sm_kwargs = dict(
        mesh=mesh,
        in_specs=(P(dpa, None), P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P(dpa, None), P()))
    wrapped = shard_map(rank_fn, check_vma=False, **sm_kwargs)
    out, aux = wrapped(xf, router, experts["w_gate"], experts["w_up"],
                       experts["w_down"])

    if cfg.n_shared_experts:
        out = out + mlp(p["shared"], xf)
    return out.reshape(B, S, D), aux


def moe_forward_dense_fallback(p: Params, cfg, x: jnp.ndarray
                               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle: compute every expert densely, combine by router weights.

    O(E) compute — tests only. Must match `moe_forward` exactly (the
    dispatch path is dropless, so no capacity mismatch)."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    dt = x.dtype
    xf = x.reshape(B * S, D)
    logits = (xf @ p["router"].astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, k)
    weights = top_vals / jnp.maximum(
        top_vals.sum(axis=-1, keepdims=True), 1e-9)
    dense_w = jnp.zeros_like(probs).at[
        jnp.arange(xf.shape[0])[:, None], top_idx].set(weights)
    g = jnp.einsum("td,edf->tef", xf, p["w_gate"].astype(dt))
    u = jnp.einsum("td,edf->tef", xf, p["w_up"].astype(dt))
    h = jax.nn.silu(g) * u
    eo = jnp.einsum("tef,efd->ted", h, p["w_down"].astype(dt))
    out = jnp.einsum("ted,te->td", eo, dense_w.astype(dt))
    f = jnp.zeros((E,), jnp.float32).at[top_idx.reshape(-1)].add(1.0) \
        / (xf.shape[0] * k)
    aux = E * jnp.sum(f * probs.mean(axis=0))
    if cfg.n_shared_experts:
        out = out + mlp(p["shared"], xf)
    return out.reshape(B, S, D), aux
