"""TCMM incremental trajectory clustering (Li, Lee, Li & Han 2010) — the
paper's §4 evaluation workload, in JAX.

Two jobs, exactly as the paper wires them (§4.1):

  * **micro-clustering job** — consumes trajectory points from a topic;
    each point merges with the nearest micro-cluster within the distance
    threshold (cluster-feature-vector update) or spawns a new
    micro-cluster; publishes micro-cluster *change events* (event
    sourcing) to a topic.
  * **macro-clustering job** — consumes the change events, periodically
    re-clusters micro-cluster centroids with k-means and publishes macro
    cluster changes.

The nearest-micro-cluster search is the measured hot spot ("the
micro-clusters size grows over time and decelerates the
micro-clustering") — it runs on the compiled ``tcmm_assign`` Pallas
kernel on a TPU and on its jnp oracle elsewhere
(``repro.kernels.platform.compiled_kernels``).

Micro-cluster state is a cluster-feature vector (n, linear sum, square
sum) per cluster: associative and mergeable, so restarts reconstruct it
by replaying the published change events.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.tcmm import TCMMConfig
from repro.core.messages import Message
from repro.kernels.platform import compiled_kernels
from repro.kernels.tcmm_assign.ops import tcmm_assign
from repro.kernels.tcmm_assign.ref import tcmm_assign_ref


@dataclass
class MicroClusterState:
    """Cluster-feature vectors: CF = (n, LS, SS) per micro-cluster."""

    cfg: TCMMConfig
    n: np.ndarray = None          # [M]
    ls: np.ndarray = None         # [M, F] linear sums
    ss: np.ndarray = None         # [M] squared norms sum
    num_active: int = 0
    processed: int = 0

    def __post_init__(self):
        m, f = self.cfg.max_micro_clusters, self.cfg.feature_dim
        if self.n is None:
            self.n = np.zeros((m,), dtype=np.float32)
            self.ls = np.zeros((m, f), dtype=np.float32)
            self.ss = np.zeros((m,), dtype=np.float32)

    def centroids(self) -> np.ndarray:
        denom = np.maximum(self.n[:, None], 1.0)
        return self.ls / denom

    def valid(self) -> np.ndarray:
        return self.n > 0

    # -- event sourcing -----------------------------------------------------
    def apply_event(self, ev: Dict[str, Any]) -> None:
        """Events: {"kind": "merge"|"new", "cluster": i, "point": [...]}"""
        i = ev["cluster"]
        p = np.asarray(ev["point"], dtype=np.float32)
        if ev["kind"] == "new":
            self.n[i] = 1.0
            self.ls[i] = p
            self.ss[i] = float(p @ p)
            self.num_active = max(self.num_active, i + 1)
        else:
            self.n[i] += 1.0
            self.ls[i] += p
            self.ss[i] += float(p @ p)
        self.processed += 1

    def ingest(self, point: np.ndarray) -> Dict[str, Any]:
        """Assign a point; returns the change event (already applied)."""
        if self.num_active == 0:
            ev = {"kind": "new", "cluster": 0, "point": point.tolist()}
            self.apply_event(ev)
            return ev
        assign = tcmm_assign if compiled_kernels() else tcmm_assign_ref
        idx, d2 = assign(
            jnp.asarray(point[None]), jnp.asarray(self.centroids()),
            jnp.asarray(self.valid()),
        )
        i, dist2 = int(idx[0]), float(d2[0])
        if dist2 <= self.cfg.distance_threshold ** 2:
            ev = {"kind": "merge", "cluster": i, "point": point.tolist()}
        elif self.num_active < self.cfg.max_micro_clusters:
            ev = {"kind": "new", "cluster": self.num_active, "point": point.tolist()}
        else:
            ev = {"kind": "merge", "cluster": i, "point": point.tolist()}
        self.apply_event(ev)
        return ev

    @staticmethod
    def replay(cfg: TCMMConfig, events: List[Dict[str, Any]]) -> "MicroClusterState":
        st = MicroClusterState(cfg)
        for ev in events:
            st.apply_event(ev)
        return st


class MicroClusterJob:
    """Processing callable for the micro-clustering job: point message ->
    [change event payloads]. Stateful; state is event-sourced by design
    (its outputs ARE its change log)."""

    def __init__(self, cfg: TCMMConfig) -> None:
        self.state = MicroClusterState(cfg)

    def __call__(self, msg: Message) -> List[Any]:
        point = np.asarray(msg.payload, dtype=np.float32)
        return [self.state.ingest(point)]


def kmeans(
    centroids: jnp.ndarray,  # [M, F] micro centroids
    weights: jnp.ndarray,    # [M] micro cluster sizes
    k: int,
    iters: int,
    seed: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Weighted k-means over micro-cluster centroids (macro step)."""
    m, f = centroids.shape
    rng = jax.random.PRNGKey(seed)
    init_idx = jax.random.choice(rng, m, (k,), replace=False, p=weights / weights.sum())
    centers = centroids[init_idx]

    def step(centers, _):
        d2 = (
            jnp.sum(centroids**2, axis=1, keepdims=True)
            - 2 * centroids @ centers.T
            + jnp.sum(centers**2, axis=1)[None, :]
        )
        assign = jnp.argmin(d2, axis=1)
        onehot = jax.nn.one_hot(assign, k) * weights[:, None]
        totals = onehot.sum(axis=0)  # [k]
        sums = onehot.T @ centroids  # [k, F]
        new_centers = jnp.where(
            totals[:, None] > 0, sums / jnp.maximum(totals[:, None], 1e-9), centers
        )
        return new_centers, assign

    centers, assign = jax.lax.scan(step, centers, None, length=iters)
    return centers, assign[-1]


class MacroClusterJob:
    """Processing callable for the macro-clustering job: consumes micro
    change events, maintains a replica of the micro state by replay, and
    every ``macro_period`` events recomputes macro clusters."""

    def __init__(self, cfg: TCMMConfig) -> None:
        self.cfg = cfg
        self.replica = MicroClusterState(cfg)
        self.macro_centers: Optional[np.ndarray] = None
        self.macro_runs = 0

    def __call__(self, msg: Message) -> List[Any]:
        self.replica.apply_event(msg.payload)
        if self.replica.processed % self.cfg.macro_period == 0:
            valid = self.replica.valid()
            if valid.sum() >= self.cfg.num_macro_clusters:
                centers, _ = kmeans(
                    jnp.asarray(self.replica.centroids()[valid]),
                    jnp.asarray(self.replica.n[valid]),
                    self.cfg.num_macro_clusters,
                    self.cfg.kmeans_iters,
                    seed=self.cfg.seed,
                )
                self.macro_centers = np.asarray(centers)
                self.macro_runs += 1
                return [{"kind": "macro", "centers": self.macro_centers.tolist()}]
        return []
