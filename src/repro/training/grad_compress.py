"""Compressed cross-pod gradient all-reduce (beyond-paper feature).

Hierarchical DP at multi-pod scale: within a pod, gradient reduction rides
the fast ICI (XLA's automatic all-reduce); *across* pods it crosses slow DCN.
Since pod-level gradients are bf16, the SplitZip codec applies verbatim —
**lossless**, so unlike lossy gradient compression (top-k, 1-bit Adam, ...)
it changes no optimization semantics; the only numerics are the same bf16
adds any all-reduce performs.

The caller produces *pod-partial* gradients with a leading pod dim (via
vmap over a pod-split batch — see train_step.py); a cached
:class:`~repro.serving.plan.TransferPlan` routes each leaf (bf16 above
``MIN_COMPRESS_ELEMS`` -> splitzip stream, everything else raw), and
:class:`_Ring` runs the rotating-ring ppermute exchange over the compressed
streams (n_pod - 1 hops, decode + fp32 accumulate per hop) inside
``shard_map`` over the 'pod' axis.  The ppermute operand bytes in the
lowered HLO shrink by ~1/rho vs a raw DCN all-reduce — the number the
roofline's collective term scores.  Codec calls go through the plan's
backend (CI-grep-guarded); per-step accounting surfaces as
:class:`~repro.serving.plan.TransferStats` in ``last_stats``.
"""

from __future__ import annotations

from typing import Callable, Dict, FrozenSet, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

# default gradient codebook: bf16 gradients of normalized networks
# concentrate in small-magnitude exponents — the same sub-bias band as the
# shared activation fallback.  Refreshed by calibrate_on_grads.
from repro.core.codebook import (Codebook,
                                 DEFAULT_BF16_CODEBOOK as DEFAULT_GRAD_CODEBOOK)
from repro.core.profile import resolve_profile
from repro.serving.plan import (LeafRoute, TransferConfig, TransferPlan,
                                TransferStats)
from repro.serving.session import permute_leaf

# Leaves smaller than this ship raw — codec framing would not pay for
# itself.  Applied per ring participant via TransferConfig.min_compress_elems.
MIN_COMPRESS_ELEMS = 16384

#: TransferStats of the most recent ``compressed_cross_pod_mean`` exchange
#: (None until the first multi-pod call; single-pod meshes never hit DCN).
last_stats: Optional[TransferStats] = None

_RINGS: Dict[Tuple, "_Ring"] = {}


def gradient_transfer_config(codebook: Codebook = DEFAULT_GRAD_CODEBOOK,
                             compress: bool = True) -> TransferConfig:
    """Routing policy for gradient pytrees: bf16 leaves at or above
    ``MIN_COMPRESS_ELEMS`` ride the splitzip stream, small/odd-dtype leaves
    go raw, and fp32 stays raw (the in-graph ring cannot ship a hi/lo split
    — and losslessness must not depend on it)."""
    return TransferConfig(codebook=codebook, enabled=compress,
                          compress_fp32=False,
                          min_compress_elems=MIN_COMPRESS_ELEMS)


def calibrate_on_grads(grads, k: int = 16) -> Codebook:
    """Offline calibration pass over a representative gradient pytree."""
    import numpy as np
    from repro.core import codebook as cbm
    leaves = [np.asarray(jax.lax.bitcast_convert_type(
        g.astype(jnp.bfloat16), jnp.uint16)).ravel()
        for g in jax.tree.leaves(grads)]
    return cbm.calibrate(leaves, k=k)


class _Ring:
    """The compressed ring all-reduce of one gradient plan over the mesh's
    'pod' axis: each participant's pod-partial contribution circles the
    ring as a compressed stream, and every participant ends with the mean.

    In-graph execution cannot branch on escape overflow, so every hop also
    emits an ``ok`` flag; a leaf whose compressed hops overflowed anywhere
    on the ring is re-run on a raw (bit-pinned) ring — detected off-graph,
    healed by the raw fallback, recorded in the stats' ``leaf_ok``.  One
    program is built per set of leaves forced raw."""

    def __init__(self, plan: TransferPlan):
        self.plan = plan
        self.n_pod = plan.mesh.shape["pod"]
        self.routes = self._participant_routes()
        self._fns: Dict[FrozenSet[int], Callable] = {}

    def _participant_routes(self) -> Tuple[LeafRoute, ...]:
        """The plan was built over pod-stacked leaves, so re-resolve on the
        stripped shapes (the per-hop payloads) — this is where
        ``tc.min_compress_elems`` bites."""
        local = []
        for r in self.plan.routes:
            if not r.shape or r.shape[0] % self.n_pod:
                raise ValueError(
                    f"gradient leaf {r.key!r} has no leading pod-divisible "
                    f"dimension (shape {r.shape})")
            local.append(jax.ShapeDtypeStruct(
                (r.shape[0] // self.n_pod,) + r.shape[1:],
                jnp.dtype(r.dtype)))
        return TransferPlan.build(
            jax.tree_util.tree_unflatten(self.plan.treedef, local),
            self.plan.tc, granularity="tensor").routes

    def reduce(self, stacked) -> Tuple[object, TransferStats]:
        """(n_pod, ...)-stacked leaves -> (their pod mean, replicated; the
        call's accounting)."""
        leaves = jax.tree_util.tree_leaves(stacked)
        expected = self.n_pod * (self.n_pod - 1)   # ok hops per leaf, psum'd
        out, oks = self._fn(frozenset())(*leaves)
        failed = frozenset(j for j, ok in enumerate(oks)
                           if int(ok) != expected)
        if failed:
            out, _ = self._fn(failed)(*leaves)
        return (jax.tree_util.tree_unflatten(self.plan.treedef, out),
                self._stats(failed))

    def _fn(self, force_raw: FrozenSet[int]) -> Callable:
        if force_raw not in self._fns:
            self._fns[force_raw] = self._build(force_raw)
        return self._fns[force_raw]

    def _build(self, force_raw: FrozenSet[int]) -> Callable:
        plan, tc, n_pod = self.plan, self.plan.tc, self.n_pod
        perm = [(i, (i + 1) % n_pod) for i in range(n_pod)]

        def ring(x, codebook, cap, compress):
            # bit-pinned rotate-and-accumulate; encode/decode per hop keeps
            # only the compressed stream on the wire.  ``ok`` counts hops
            # whose escape capacity held — the traced flag the host checks.
            acc = x.astype(jnp.float32)
            rotating = x
            ok = jnp.int32(0)
            for _ in range(n_pod - 1):
                if compress:
                    ct = plan.backend.encode(rotating, codebook,
                                             chunk=tc.chunk, cap=cap,
                                             layout=tc.layout)
                    ok = ok + plan.backend.ok(ct).astype(jnp.int32)
                    moved = jax.tree.map(
                        lambda s: jax.lax.ppermute(s, "pod", perm), ct)
                    rotating = jnp.asarray(
                        plan.backend.decode(moved)).reshape(x.shape)
                else:
                    ok = ok + 1
                    rotating = permute_leaf(rotating, "pod", perm)
                acc = acc + rotating.astype(jnp.float32)
            return acc, jax.lax.psum(ok, "pod")

        def body(*leaves_flat):
            out, oks = [], []
            for j, (lf, r) in enumerate(zip(leaves_flat, self.routes)):
                x = lf[0]    # local slice of the stacked leaf, leading dim 1
                if r.route == "splitzip" and j not in force_raw:
                    total, ok = ring(x, tc.codebook, r.cap, True)
                elif r.route == "fp8" and j not in force_raw:
                    total, ok = ring(x, plan.fp8_codebook, r.cap, True)
                else:
                    total, ok = ring(x, None, 0, False)
                out.append((total / n_pod).astype(x.dtype))
                oks.append(ok)
            return tuple(out), tuple(oks)

        specs = lambda s: tuple(s for _ in self.routes)
        return shard_map(body, mesh=plan.mesh, in_specs=specs(P("pod")),
                         out_specs=(specs(P()), specs(P())),
                         check_vma=False)

    def _stats(self, failed: FrozenSet[int]) -> TransferStats:
        """Host-side accounting of one exchange, every hop charged raw (the
        lowered HLO's ppermute operands are the measured wire bytes)."""
        hops = self.n_pod - 1
        stats = TransferStats(chunk_wire_bytes=[], chunk_ok=[],
                              raw_passthrough_bytes=0.0,
                              n_elements=sum(r.n_elements for r in self.routes
                                             if r.route != "raw"))
        for j, r in enumerate(self.routes):
            if r.route == "raw":
                stats.raw_passthrough_bytes += r.raw_bytes * hops
            elif j in failed:
                # overflowed: the wasted compressed attempt shipped, then
                # the raw re-run (charged as a raw re-fetch)
                stats.leaf_wire_bytes[r.key] = r.raw_bytes * hops
                stats.leaf_ok[r.key] = False
                stats.refetches += 1
                stats.raw_refetches += 1
                stats.refetch_wire_bytes += r.raw_bytes * hops
            elif r.route == "fp8":
                stats.fp8_wire_bytes += r.raw_bytes * hops
                stats.leaf_ok[r.key] = True
            else:
                stats.leaf_wire_bytes[r.key] = r.raw_bytes * hops
                stats.leaf_ok[r.key] = True
        return stats


def _ring(grads_stacked, mesh: Mesh, codebook: Codebook,
          compress: bool) -> _Ring:
    """Ring cache: the plan is a property of (structure, mesh, policy), not
    of the step — the compiled ring programs amortize across the whole
    training run."""
    leaves, treedef = jax.tree_util.tree_flatten(grads_stacked)
    key = (treedef, tuple((tuple(x.shape), str(x.dtype)) for x in leaves),
           mesh, codebook, compress)
    ring = _RINGS.get(key)
    if ring is None:
        plan = TransferPlan.build(
            grads_stacked, gradient_transfer_config(codebook, compress),
            mesh=mesh, specs=tuple(P("pod") for _ in leaves))
        ring = _RINGS[key] = _Ring(plan)
    return ring


def compressed_cross_pod_mean(grads_stacked, mesh: Mesh,
                              codebook: Codebook = DEFAULT_GRAD_CODEBOOK,
                              compress: bool = True):
    """(n_pod, ...)-stacked pod-partial grads -> pod-replicated mean grads.

    Input leaves are sharded P('pod', *param_spec); output leaves drop the pod
    dim and are replicated across pods (every pod computed the same sum)."""
    global last_stats
    if "pod" not in mesh.shape:
        # single-pod mesh: nothing to exchange, just average the leading dim
        return jax.tree.map(lambda g: jnp.mean(g.astype(jnp.float32), axis=0)
                            .astype(g.dtype), grads_stacked)
    out, last_stats = _ring(grads_stacked, mesh, codebook,
                            compress).reduce(grads_stacked)
    return out


def cross_pod_wire_bytes(grads, n_pod: int = 2, compress: bool = True,
                         profile: str = "paper",
                         codebook: Codebook = DEFAULT_GRAD_CODEBOOK,
                         link_bw: float = 1.0) -> float:
    """Analytic DCN bytes per step for the ring exchange (for reports).

    The byte classes come from the gradient plan's route table and the
    compression ratio from the resolved codec profile (paper Table 2 or a
    calibration artifact) — not a hard-coded guess."""
    plan = TransferPlan.build(grads, gradient_transfer_config(
        codebook, compress), granularity="tensor")
    ratio = (resolve_profile(profile, link_bw=link_bw).ratio
             if compress else 1.0)
    # each hop ships the routed stream at the codec ratio plus the
    # incompressible bytes at full cost
    stream, fp8, out = plan.byte_split()
    return ((stream + fp8) / max(ratio, 1e-9) + out) * (n_pod - 1)
