"""Federated parameter server over pods (SystemDS §4.3 + DiLoCo-style
relaxed sync).

`FedAvgTrainer` simulates K federated sites (pods): each site runs H
local optimizer steps on its own data shard, then sites exchange
parameter deltas (optionally int8-compressed with error feedback) and
apply the average. Cross-site traffic per sync = one (compressed) param
delta instead of H gradient all-reduces — the knob that makes the pod
axis tolerant of slow inter-pod links (DCN vs ICI).

This is the host-level simulation used by the tests; on a real
multi-pod mesh the same schedule maps to a shard_map over the `pod`
axis (params carry a leading pod dim between syncs).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim.adamw import AdamWState, adamw_init, adamw_update

from . import compress


@dataclass
class SiteState:
    params: Any
    opt_state: AdamWState
    err: Any = None            # error-feedback residual (compression)


@dataclass
class FedAvgTrainer:
    loss_fn: Callable[[Any, dict], tuple]  # (params, batch) -> (loss, aux)
    n_sites: int
    sync_every: int = 8
    lr: float = 1e-3
    compress_int8: bool = False
    sites: list[SiteState] = field(default_factory=list)
    anchor: Any = None         # last synced global params
    bytes_exchanged: int = 0
    step: int = 0

    def init(self, params: Any) -> None:
        self.anchor = params
        self.sites = [
            SiteState(params=jax.tree_util.tree_map(jnp.copy, params),
                      opt_state=adamw_init(params),
                      err=compress.init_error_state(params))
            for _ in range(self.n_sites)]
        self._grad = jax.jit(jax.value_and_grad(self.loss_fn, has_aux=True))

    def local_step(self, site: int, batch: dict) -> float:
        s = self.sites[site]
        (loss, _), grads = self._grad(s.params, batch)
        s.params, s.opt_state, _ = adamw_update(
            grads, s.opt_state, s.params, lr=self.lr, weight_decay=0.0)
        return float(loss)

    def maybe_sync(self) -> bool:
        self.step += 1
        if self.step % self.sync_every:
            return False
        # exchange deltas from the anchor (what actually crosses pods)
        deltas = []
        for s in self.sites:
            delta = jax.tree_util.tree_map(
                lambda p, a: p.astype(jnp.float32) - a.astype(jnp.float32),
                s.params, self.anchor)
            if self.compress_int8:
                q, scale, s.err = compress.compress_tree(delta, s.err)
                self.bytes_exchanged += compress.compressed_bytes(delta)[0]
                delta = jax.tree_util.tree_map(compress.dequantize, q, scale)
            else:
                self.bytes_exchanged += compress.compressed_bytes(delta)[1]
            deltas.append(delta)
        mean_delta = jax.tree_util.tree_map(
            lambda *ds: sum(ds) / len(ds), *deltas)
        self.anchor = jax.tree_util.tree_map(
            lambda a, d: (a.astype(jnp.float32) + d).astype(a.dtype),
            self.anchor, mean_delta)
        for s in self.sites:
            s.params = jax.tree_util.tree_map(jnp.copy, self.anchor)
        return True
