"""Divisibility-aware sharding rules for params, activations and caches.

Production posture (DESIGN.md §5): mesh axes are ``(pod, data, model)`` (DCN ×
ICI × ICI).  DP runs over (pod, data); TP/EP over model.  Rules shard a tensor
dim on an axis only when the dim divides the axis size — otherwise the dim is
replicated (e.g. minitron's 24 heads never shard over model=16; its attention
falls back to sequence sharding via the activation rules).

Model code never names mesh axes directly: it calls ``constrain(x, kind)``,
which is a no-op unless a :class:`ShardingPolicy` is active (smoke tests run
without one; jitted programs install one via ``use_policy``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import re
import threading
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_STATE = threading.local()

DP_AXES = ("pod", "data")  # flattened data-parallel axes (present subset used)


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        if a in mesh.shape:
            n *= mesh.shape[a]
    return n


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """Resolves logical shard requests against a concrete mesh."""

    mesh: Mesh
    # how to shard attention activations when heads don't divide 'model':
    #   'seq'  — shard the sequence dim over model (sequence parallelism)
    #   'none' — replicate over model
    attn_fallback: str = "seq"
    # ZeRO-3/FSDP: additionally shard params + optimizer state over 'data'
    # (within-pod ICI; pods stay pure DP so no param gathers cross the DCN).
    # XLA inserts the per-layer all-gather at use sites.
    fsdp: bool = False
    # constrain MoE dispatch intermediates (token buffers over dp, expert
    # buffers over model) instead of letting GSPMD guess — see models/moe.py
    moe_dispatch_sharding: bool = False
    # PD-disaggregated serving: the 'pod' axis separates prefill/decode
    # workers, so activations/caches shard over 'data' only (replicated over
    # 'pod'); the pod axis is reserved for the KV-transfer DCN hop.
    pd_disaggregated: bool = False

    def dp_axes(self) -> Tuple[str, ...]:
        axes = DP_AXES if not self.pd_disaggregated else ("data",)
        return tuple(a for a in axes if a in self.mesh.shape)

    def fsdp_axes(self) -> Tuple[str, ...]:
        return ("data",) if ("data" in self.mesh.shape and self.fsdp) else ()

    def dp_size(self) -> int:
        return _axis_size(self.mesh, self.dp_axes())

    def tp_size(self) -> int:
        return _axis_size(self.mesh, "model")

    # -- helpers ---------------------------------------------------------------
    def _maybe(self, dim: int, axes):
        """axes if dim divides their product (and dim is concrete), else None.

        Singleton axis tuples are unwrapped to the bare name: P(('data',),) and
        P('data',) are semantically identical but compare unequal on jax
        versions that don't normalize PartitionSpec entries."""
        n = _axis_size(self.mesh, axes)
        if n > 1 and dim % n == 0:
            if isinstance(axes, tuple) and len(axes) == 1:
                return axes[0]
            return axes
        return None

    def spec_for_activation(self, kind: str, shape: Tuple[int, ...]) -> P:
        dp = self.dp_axes()
        tp = "model" if "model" in self.mesh.shape else None
        if kind == "btd":            # (B, S, D) hidden states
            b = self._maybe(shape[0], dp)
            return P(b, None, None)
        if kind == "btd_seq":        # (B, S, D) sequence-sharded over model
            b = self._maybe(shape[0], dp)
            s = self._maybe(shape[1], tp)
            return P(b, s, None)
        if kind == "bthd":           # (B, S, H, hd) attention activations
            b = self._maybe(shape[0], dp)
            h = self._maybe(shape[2], tp)
            if h is not None:
                return P(b, None, h, None)
            if self.attn_fallback == "seq":
                s = self._maybe(shape[1], tp)
                return P(b, s, None, None)
            return P(b, None, None, None)
        if kind == "logits":         # (B, S, V) or (B, V)
            b = self._maybe(shape[0], dp)
            v = self._maybe(shape[-1], tp)
            spec = [b] + [None] * (len(shape) - 2) + [v]
            return P(*spec)
        if kind == "kvcache":        # (B, S, Hkv, hd) or (B, S, r)
            b = self._maybe(shape[0], dp)
            s = self._maybe(shape[1], tp)
            spec = [b, s] + [None] * (len(shape) - 2)
            return P(*spec)
        if kind == "state":          # (B, ...) recurrent states
            b = self._maybe(shape[0], dp)
            return P(*([b] + [None] * (len(shape) - 1)))
        if kind == "tokens":         # (B, S) int
            b = self._maybe(shape[0], dp)
            return P(*([b] + [None] * (len(shape) - 1)))
        # --- MoE dispatch intermediates (models/moe.py) ----------------------
        if kind == "moe_td":         # (T, D) flattened token stream
            if not self.moe_dispatch_sharding:
                return None
            t = self._maybe(shape[0], dp)
            return P(t, None)
        if kind == "moe_te":         # (T, E) router probs/logits
            if not self.moe_dispatch_sharding:
                return None
            t = self._maybe(shape[0], dp)
            return P(t, None)
        raise KeyError(f"unknown activation kind {kind!r}")

    def spec_for_cache(self, name: str, shape: Tuple[int, ...]) -> P:
        """Layer-stacked inference caches (see models/kvcache.py layouts)."""
        dp = self.dp_axes()
        tp = "model" if "model" in self.mesh.shape else None
        leaf = name.split("/")[-1]
        if leaf in ("k", "v", "ckv", "krope"):      # (L, B, S, ...)
            b = self._maybe(shape[1], dp)
            s = self._maybe(shape[2], tp)
            return P(None, b, s, *([None] * (len(shape) - 3)))
        if leaf == "ssm":                            # (L, B, H, P, N)
            b = self._maybe(shape[1], dp)
            h = self._maybe(shape[2], tp)
            return P(None, b, h, None, None)
        if leaf == "conv":                           # (L, B, W-1, C)
            b = self._maybe(shape[1], dp)
            c = self._maybe(shape[3], tp)
            return P(None, b, None, c)
        if leaf in ("attn_k", "attn_v"):             # (nt, B, W, Hkv, hd)
            b = self._maybe(shape[1], dp)
            h = self._maybe(shape[3], tp)
            return P(None, b, None, h, None)
        if leaf == "rec_h":                          # (nt, 2, B, U)
            b = self._maybe(shape[2], dp)
            u = self._maybe(shape[3], tp)
            return P(None, None, b, u)
        if leaf == "rec_conv":                       # (nt, 2, B, cw-1, U)
            b = self._maybe(shape[2], dp)
            u = self._maybe(shape[4], tp)
            return P(None, None, b, None, u)
        if leaf == "extra_h":                        # (ne, B, U)
            b = self._maybe(shape[1], dp)
            u = self._maybe(shape[2], tp)
            return P(None, b, u)
        if leaf == "extra_conv":                     # (ne, B, cw-1, U)
            b = self._maybe(shape[1], dp)
            u = self._maybe(shape[3], tp)
            return P(None, b, None, u)
        # unknown cache leaf: batch-only
        return P(*([None] + [self._maybe(shape[1], dp)] +
                   [None] * (len(shape) - 2))) if len(shape) > 1 else P(None)

    def cache_specs(self, cache):
        """Pytree of PartitionSpecs matching ``cache`` (arrays or SDS).

        This is what a mesh-targeted :class:`~repro.serving.plan.TransferPlan`
        consumes as ``specs=``: the plan resolves the per-leaf shard layout
        once at build time instead of re-deriving it per transfer call."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
        out = []
        for path, leaf in flat:
            name = "/".join(_key_str(k) for k in path)
            out.append(self.spec_for_cache(name, tuple(leaf.shape)))
        return jax.tree_util.tree_unflatten(treedef, out)

    def cache_sharding(self, cache):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self.cache_specs(cache),
                            is_leaf=lambda x: isinstance(x, P))

    # -- parameter rules ---------------------------------------------------------
    # matched against the '/'-joined param path, first hit wins
    PARAM_RULES = (
        # (regex, dims-spec builder name)
        (re.compile(r"(embed|tok_embed)$"), "vocab_row"),        # (V, D)
        (re.compile(r"lm_head$"), "vocab_col"),                  # (D, V)
        (re.compile(r"w[qkv]$"), "heads_mid"),                   # (D, H, hd)
        (re.compile(r"wo$"), "heads_first"),                     # (H, hd, D)
        (re.compile(r"w_(gate|up)$"), "ff_col"),                 # (D, F)
        (re.compile(r"w_down$"), "ff_row"),                      # (F, D)
        (re.compile(r"w_gate_up$"), "expert"),                   # (E, D, 2F)
        (re.compile(r"router$"), "replicate"),
        (re.compile(r"wq_a$|wkv_a$"), "ff_col"),                 # (D, r)
        (re.compile(r"wq_b$|wkv_b$"), "mla_b"),                  # (r, H, ·)
        (re.compile(r"in_proj$"), "ff_col"),                     # (D, K)
        (re.compile(r"out_proj$|w_out$"), "ff_row"),             # (K, D)
        (re.compile(r"w_gate_branch$|w_in$"), "ff_col"),
        (re.compile(r"w_a$|w_x$"), "lru_sq"),                    # (U, U)
        (re.compile(r"frontend_proj$"), "ff_col"),
    )

    def spec_for_param(self, path: str, shape: Tuple[int, ...]) -> P:
        tp = "model" if "model" in self.mesh.shape else None
        # leading layer-stack dim (scan over layers) is never sharded
        lead = ()
        if path.startswith("layers/") or "/stack/" in path or path.startswith("triples/"):
            lead = (None,)
            shape = shape[1:]
        kind = "replicate"
        leaf = path.split("/")[-1]
        for rx, k in self.PARAM_RULES:
            if rx.search(leaf):
                kind = k
                break
        def mk(*spec):
            if self.fsdp:
                spec = self._add_fsdp(spec, shape)
            return P(*(lead + spec))
        if len(shape) == 0:
            return mk()
        if kind == "vocab_row":
            return mk(self._maybe(shape[0], tp), *([None] * (len(shape) - 1)))
        if kind == "vocab_col":
            return mk(*([None] * (len(shape) - 1)), self._maybe(shape[-1], tp))
        if kind == "heads_mid" and len(shape) == 3:
            h = self._maybe(shape[1], tp)
            return mk(None, h, None)
        if kind == "heads_first" and len(shape) == 3:
            h = self._maybe(shape[0], tp)
            return mk(h, None, None)
        if kind == "ff_col":
            return mk(*([None] * (len(shape) - 1)), self._maybe(shape[-1], tp))
        if kind == "ff_row":
            return mk(self._maybe(shape[0], tp), *([None] * (len(shape) - 1)))
        if kind == "expert":
            return mk(self._maybe(shape[0], tp), *([None] * (len(shape) - 1)))
        if kind == "mla_b" and len(shape) == 3:
            h = self._maybe(shape[1], tp)
            return mk(None, h, None)
        if kind == "lru_sq":
            return mk(*([None] * (len(shape) - 1)), self._maybe(shape[-1], tp))
        return mk(*([None] * len(shape)))

    def _add_fsdp(self, spec, shape):
        """ZeRO-3: place 'data' on the largest still-unsharded divisible dim.
        Leaves too-small params (norm scales, biases) replicated — the cost
        of gathering them is larger than the memory they hold."""
        axes = self.fsdp_axes()
        n = _axis_size(self.mesh, axes)
        if n <= 1:
            return spec
        spec = list(spec) + [None] * (len(shape) - len(spec))
        cands = [i for i, s in enumerate(spec)
                 if s is None and i < len(shape) and shape[i] % n == 0
                 and shape[i] >= 4 * n]
        if cands:
            best = max(cands, key=lambda i: shape[i])
            spec[best] = axes if len(axes) > 1 else axes[0]
        return tuple(spec)

    def param_sharding(self, params) -> "jax.tree_util.PyTreeDef":
        """Pytree of NamedShardings matching ``params`` (arrays or SDS)."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        out = []
        for path, leaf in flat:
            pstr = "/".join(_key_str(k) for k in path)
            spec = self.spec_for_param(pstr, tuple(leaf.shape))
            out.append(NamedSharding(self.mesh, spec))
        return jax.tree_util.tree_unflatten(treedef, out)

    def param_specs(self, params):
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        out = []
        for path, leaf in flat:
            pstr = "/".join(_key_str(k) for k in path)
            out.append(self.spec_for_param(pstr, tuple(leaf.shape)))
        return jax.tree_util.tree_unflatten(treedef, out)


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


# ---------------------------------------------------------------------------
# thread-local policy + constrain()
# ---------------------------------------------------------------------------

def current_policy() -> Optional[ShardingPolicy]:
    return getattr(_STATE, "policy", None)


@contextlib.contextmanager
def use_policy(policy: Optional[ShardingPolicy]):
    prev = current_policy()
    _STATE.policy = policy
    try:
        yield
    finally:
        _STATE.policy = prev


def constrain(x: jax.Array, kind: str) -> jax.Array:
    """Apply an activation sharding constraint if a policy is active."""
    pol = current_policy()
    if pol is None:
        return x
    spec = pol.spec_for_activation(kind, tuple(x.shape))
    if spec is None:  # policy declines to constrain this kind
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(pol.mesh, spec))


def constrain_tree(tree, kind: str):
    pol = current_policy()
    if pol is None:
        return tree
    return jax.tree.map(lambda x: constrain(x, kind), tree)
