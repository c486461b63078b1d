"""Elastic scaling: legal mesh enumeration + re-mesh planning after capacity
changes (node loss / scale-up), preserving DP/TP semantics.

A (pod, data, model) mesh is *legal* for an arch/shape when
  - global_batch % (pod*data) == 0            (DP divisibility)
  - the model's TP-shardable dims tolerate 'model' (the divisibility-aware
    rules replicate what doesn't divide, so any model size is legal, but we
    prefer meshes that keep FFN/vocab sharded)
Re-mesh = pick the best legal mesh for the surviving chip count, then
checkpoint-restore resharding (parameters are saved shard-agnostically).
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.codebook import DEFAULT_BF16_CODEBOOK, Codebook
from repro.launch.mesh import make_mesh
from repro.serving.plan import TransferConfig, TransferPlan, TransferStats


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    shape: Tuple[int, ...]
    axes: Tuple[str, ...]
    score: float

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def legal_meshes(n_chips: int, cfg: ArchConfig, shape: ShapeConfig,
                 multi_pod: bool = False, n_pods: int = 1) -> List[MeshPlan]:
    """Enumerate (data, model) splits of n_chips (per pod), scored."""
    plans = []
    per_pod = n_chips // n_pods if multi_pod else n_chips
    for model in _divisors(per_pod):
        data = per_pod // model
        dp = data * (n_pods if multi_pod else 1)
        # DP divisibility: every replica needs a non-empty, equal batch
        # slice.  (This must also reject dp > global_batch — those meshes
        # would give some replicas a zero per-replica batch.)
        if shape.global_batch % dp != 0:
            continue
        score = 0.0
        # prefer: FFN sharded, vocab sharded, heads sharded, batch not over-split
        if cfg.d_ff and cfg.d_ff % model == 0:
            score += 2.0
        if cfg.vocab_size % model == 0:
            score += 1.5
        if cfg.num_heads and cfg.num_heads % model == 0:
            score += 1.0
        # mild preference for more TP on big models (memory), more DP on small
        big = cfg.param_count() > 8e9
        score += 0.01 * (model if big else data)
        if multi_pod:
            plans.append(MeshPlan((n_pods, data, model),
                                  ("pod", "data", "model"), score))
        else:
            plans.append(MeshPlan((data, model), ("data", "model"), score))
    return sorted(plans, key=lambda p: -p.score)


def replan_after_failure(current: MeshPlan, surviving_chips: int,
                         cfg: ArchConfig, shape: ShapeConfig) -> Optional[MeshPlan]:
    """Best legal mesh at the surviving capacity (None if impossible)."""
    multi = "pod" in current.axes
    n_pods = current.shape[0] if multi else 1
    if multi and surviving_chips < n_pods:
        multi, n_pods = False, 1
    # round down to a power-of-two-ish usable chip count for clean meshes
    usable = surviving_chips
    while usable > 0:
        plans = legal_meshes(usable, cfg, shape, multi_pod=multi, n_pods=n_pods)
        if plans:
            return plans[0]
        usable -= 1
    return None


def reshard(state, old_mesh_plan: Optional[MeshPlan],
            new_mesh_plan: MeshPlan, *, shardings=None,
            codebook: Codebook = DEFAULT_BF16_CODEBOOK,
            compress_fp32: bool = True, faults=None, verify: bool = False
            ) -> Tuple[Any, TransferStats]:
    """Ship ``state`` from ``old_mesh_plan``'s configuration onto
    ``new_mesh_plan``'s mesh through the bulk-data plane: one local
    :class:`TransferPlan` over the state pytree, host-staged splitzip
    streams via the session's tensor executor (bit-exact; fp32 rides the
    hi/lo split, integrity framing and re-fetches ride the session's
    ``verify=`` / ``faults=``), then ``device_put`` onto the new mesh.  The
    old mesh may already be gone (that's the point — after a node loss the
    state is only host-addressable), so the plan is built with
    ``mesh=None`` and the hop never touches old-mesh collectives.

    ``shardings``: optional pytree of :class:`NamedSharding` matching
    ``state``; defaults to replicated on the new mesh (the training step's
    own ``ShardingPolicy`` re-shards parameters lazily on first use).
    ``faults=`` / ``verify=`` thread into the session so recovery drills
    exercise the wire-integrity re-fetch path.  Returns
    ``(state_on_new_mesh, TransferStats)``."""
    if new_mesh_plan.n_devices > jax.device_count():
        raise ValueError(
            f"new mesh {new_mesh_plan.shape} needs {new_mesh_plan.n_devices} "
            f"devices; only {jax.device_count()} visible")
    tc = TransferConfig(codebook=codebook, backend="wire",
                        compress_fp32=compress_fp32)
    sess = TransferPlan.build(state, tc).session(faults=faults, verify=verify)
    if shardings is None:
        mesh = make_mesh(new_mesh_plan.shape, new_mesh_plan.axes)
        repl = NamedSharding(mesh, P())
        shardings = jax.tree.map(lambda _: repl, state)
    out = jax.device_put(sess.transfer(state), shardings)
    return out, sess.last_stats


@dataclasses.dataclass
class ElasticEvent:
    step: int
    kind: str                 # 'shrink' | 'grow'
    chips_delta: int


def simulate_elastic_run(events: List[ElasticEvent], start_chips: int,
                         cfg: ArchConfig, shape: ShapeConfig) -> List[MeshPlan]:
    """Drive replanning through a capacity-change schedule; returns the mesh
    history (used by tests + the elasticity example)."""
    chips = start_chips
    plan = legal_meshes(chips, cfg, shape)[0]
    history = [plan]
    for ev in sorted(events, key=lambda e: e.step):
        chips = max(1, chips + ev.chips_delta)
        nxt = replan_after_failure(plan, chips, cfg, shape)
        if nxt is None:
            raise RuntimeError(f"no legal mesh at {chips} chips")
        plan = nxt
        history.append(plan)
    return history
