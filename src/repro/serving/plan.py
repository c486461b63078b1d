"""TransferPlan: compile-once / run-many policy resolution for bulk transfer.

The PD transfer path used to re-decide per-leaf policy (bf16 vs fp32 vs fp8,
chunking, escape capacity, local vs mesh execution) on every call, in three
divergent entry points.  A :class:`TransferPlan` resolves all of it ONCE per
model from the *structure* (shapes + dtypes — abstract values work), and a
:class:`~repro.serving.session.TransferSession` then executes the plan many
times.  KVServe-style service-aware connectors and ZipServ-style
hardware-aware dispatch both make this argument: policy is a property of the
model + deployment, not of the individual transfer.

The structure is ANY pytree, not just a KV cache: train states, optimizer
states, and pod-partial gradient trees build plans the same way, so
checkpointing (``distributed/checkpoint.py``), elastic resharding and the
compressed gradient ring (``training/grad_compress.py``) share the plan's
route table and accounting.

Per-leaf routing table (resolved at build time):

  bf16 leaf                    -> 'splitzip'   : the calibrated exponent codec
                                  via the backend registry; folded into the
                                  chunked bit stream when ``n_chunks > 1``.
  fp32 leaf (compress_fp32)    -> 'fp32_hilo'  : hi/lo u16 split; the hi half
                                  has the BF16 bit layout so the SAME codebook
                                  compresses it (folded into the chunked
                                  stream too); the lo mantissa half ships raw
                                  but is counted on the wire.
  float8 leaf                  -> 'fp8'        : e5m2 repack — bitcast to the
                                  u8 container and encoded under the e5m2
                                  exponent codebook (``tc.fp8_codebook`` or a
                                  default normal-band book); lossless for any
                                  float8 bits, ratio suffers only if the
                                  codebook band is off.
  everything else              -> 'raw'        : dtype-exact passthrough.

Capacity policy: each encoded unit (tensor or pipeline chunk) gets a
geometric retry schedule ``cap -> 2*cap -> 4*cap -> layout='global'``
(:meth:`repro.core.backend.CodecBackend.capacity_schedule`), replacing the
old single 2x retry; exhaustion still means the unconditional raw fallback.

Execution target: ``mesh=None`` plans run the local pipelined loop;
``mesh=`` plans run per-chunk ``lax.ppermute`` over the 'pod' axis with
double-buffering inside ``shard_map`` (n_chunks == 1 degenerates to the
whole-tensor collective).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import codec as C
from repro.core.backend import CodecBackend, get_backend, resolve_backend
from repro.core.codebook import Codebook
from repro.core.pipeline import (CodecProfile, degraded_stage_times,
                                 expected_schedule_attempts,
                                 flowshop_makespan)


@dataclasses.dataclass(frozen=True)
class TransferConfig:
    codebook: Codebook
    chunk: int = C.DEFAULT_CHUNK
    cap: int = C.DEFAULT_CAP
    enabled: bool = True          # False => native raw-bytes baseline
    compress_fp32: bool = False   # fp32 hi/lo-split codec toggle
    layout: str = "chunked"       # 'chunked' (paper) | 'global' (beyond-paper)
    global_budget: float = 0.01   # escape-capacity budget for layout='global'
    backend: str = "xla"          # codec backend registry key (core/backend.py)
    n_chunks: int = 1             # >1 => chunked pipelined transfer engine
    # codebook for the fp8 'e5m2 repack' route; None => default normal band
    fp8_codebook: Optional[Codebook] = None
    # geometric capacity schedule: number of cap doublings before the
    # layout='global' last resort (0 disables retries entirely)
    retry_doublings: int = 2
    retry_global_budget: float = 0.05
    # route threshold: encoded routes need at least this many elements —
    # smaller leaves ship raw (codec framing would not pay for itself).
    # Gradient plans set this to grad_compress.MIN_COMPRESS_ELEMS.
    min_compress_elems: int = 0

    def get_backend(self) -> CodecBackend:
        return get_backend(self.backend)


# default 'e5m2 repack' codebook: the 16-exponent band around the e5m2 bias
# (15), covering normal activations; escapes handle the rest losslessly
FP8_DEFAULT_CODEBOOK = Codebook(fmt="fp8_e5m2", exponents=tuple(range(8, 24)))


def leaf_key(path) -> str:
    """Canonical pytree-path -> string key.  Compression, wire accounting,
    segmentation, and reassembly all index by this; it must stay one
    definition or decompression silently misroutes leaves."""
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _is_float8(dtype) -> bool:
    return str(jnp.dtype(dtype)).startswith("float8")


def _resolve_cap(tc: TransferConfig, n: int) -> int:
    cap = tc.cap
    if tc.layout == "global" and cap == C.DEFAULT_CAP:
        cap = C.default_global_cap(n, tc.global_budget)
    return cap


# ---------------------------------------------------------------------------
# per-leaf routes and per-chunk segments
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LeafRoute:
    """One leaf's resolved transfer policy."""

    key: str
    shape: Tuple[int, ...]
    dtype: str
    route: str                    # 'splitzip' | 'fp32_hilo' | 'fp8' | 'raw'
    cap: int = 0                  # level-0 escape capacity (encoded routes)

    @property
    def n_elements(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def raw_bytes(self) -> float:
        return float(self.n_elements * jnp.dtype(self.dtype).itemsize)


@dataclasses.dataclass(frozen=True)
class SegmentSpec:
    """One pipeline chunk of the folded u16 bit stream: a contiguous,
    codec-chunk-aligned [start, stop) element range with its resolved
    level-0 escape capacity."""

    start: int
    stop: int
    cap: int

    @property
    def n_elements(self) -> int:
        return self.stop - self.start

    @property
    def raw_bytes(self) -> float:
        return 2.0 * self.n_elements


@dataclasses.dataclass
class TransferStats:
    """Per-transfer accounting emitted by a :class:`TransferSession` run.

    Chunked executions fill the ``chunk_*`` lists (one entry per pipeline
    chunk); whole-tensor executions fill ``leaf_wire_bytes``/``leaf_ok``
    (one entry per encoded leaf).  Either way ``wire_bytes``/``all_ok``
    give the engine a uniform view."""

    chunk_wire_bytes: List[float]   # wire bytes actually shipped per chunk
    chunk_ok: List[bool]            # escape capacity held for this chunk?
    raw_passthrough_bytes: float    # unrouted leaves shipped outside the pipe
    n_elements: int                 # u16 elements routed through the pipe
    # chunks whose first encode overflowed and were re-encoded on the
    # geometric capacity schedule (chunk_ok reflects the final attempt)
    chunk_retried: List[bool] = dataclasses.field(default_factory=list)
    # extra encode attempts per chunk (0 == first encode held); the full
    # geometric schedule is cap -> 2cap -> 4cap -> layout='global'
    chunk_retry_steps: List[int] = dataclasses.field(default_factory=list)
    # whole-tensor execution: per-leaf accounting (raw-fallback applied)
    leaf_wire_bytes: Dict[str, float] = dataclasses.field(default_factory=dict)
    leaf_ok: Dict[str, bool] = dataclasses.field(default_factory=dict)
    # fp32 hi/lo route: raw lo mantissa halves counted on the wire (chunked
    # executions fold the hi halves into chunk_wire_bytes)
    fp32_lo_wire_bytes: float = 0.0
    # fp8 route: sidecar-encoded float8 leaves' wire bytes
    fp8_wire_bytes: float = 0.0
    # wire-integrity path (verify=True sessions / injected faults): checksum
    # mismatches + drops observed, re-fetches issued, re-fetches that shipped
    # the unit's raw bits, and the extra bytes those re-fetches put on the
    # wire (chunk_*/leaf_* keep their first-ship meaning)
    verify_failures: int = 0
    refetches: int = 0
    raw_refetches: int = 0
    refetch_wire_bytes: float = 0.0
    # injected-fault bookkeeping (FaultChannel): faults applied this call and
    # wire latency added by 'delay' faults
    faults_injected: int = 0
    fault_delay_s: float = 0.0
    # prefix-aware delta transfer (ISSUE 10): raw bytes of segments/sidecars
    # NOT shipped because the receiver's prefix index already held them
    # bit-identically.  Deliberately excluded from ``wire_bytes`` — that
    # property stays "bytes actually on the wire", and the saving is the gap
    # between raw_bytes and it
    prefix_hit_bytes: float = 0.0
    # device-to-host reads the call made (``core.spans.host_read``)
    host_reads: int = 0

    @property
    def wire_bytes(self) -> float:
        return (sum(self.chunk_wire_bytes) + sum(self.leaf_wire_bytes.values())
                + self.raw_passthrough_bytes + self.fp32_lo_wire_bytes
                + self.fp8_wire_bytes + self.refetch_wire_bytes)

    @property
    def all_ok(self) -> bool:
        return all(self.chunk_ok) and all(self.leaf_ok.values())

    @property
    def n_retries(self) -> int:
        """Units (chunks/leaves) that needed at least one re-encode."""
        return sum(self.chunk_retried)

    @property
    def n_retry_steps(self) -> int:
        """Total extra encode attempts across the capacity schedule."""
        return sum(self.chunk_retry_steps)


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TransferPlan:
    """A resolved, leaf-aware transfer program.  Build once per model with
    :meth:`build`, execute many times through :meth:`session`."""

    tc: TransferConfig
    treedef: Any
    routes: Tuple[LeafRoute, ...]
    backend: CodecBackend
    segments: Tuple[SegmentSpec, ...]   # chunked-granularity stream cuts
    stream_len: int                     # u16 elements folded into the stream
    mesh: Optional[Mesh] = None
    src_pod: int = 0
    dst_pod: int = 1
    in_specs: Optional[Tuple[P, ...]] = None

    # -- construction --------------------------------------------------------
    @classmethod
    def build(cls, cache_structure, tc: TransferConfig,
              mesh: Optional[Mesh] = None, *, specs=None,
              src_pod: int = 0, dst_pod: int = 1,
              granularity: Optional[str] = None) -> "TransferPlan":
        """Resolve the full per-leaf policy from shapes + dtypes.

        ``cache_structure`` is ANY pytree — a KV cache, a train/optimizer
        state, or a pod-partial gradient tree — holding concrete arrays or
        ShapeDtypeStructs: only ``.shape``/``.dtype`` are read, so plans can
        be built from abstract states (dry-run) or inside a trace (shapes
        are static).  Leaves below ``tc.min_compress_elems`` elements route
        'raw' regardless of dtype.

        ``granularity`` forces 'chunked' (segment even when ``n_chunks ==
        1``) or 'tensor'; None picks 'chunked' iff ``tc.n_chunks > 1``."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(cache_structure)
        backend = resolve_backend(tc.backend, require_jittable=mesh is not None)
        if mesh is not None and "pod" not in mesh.shape:
            raise ValueError("mesh execution needs a 'pod' mesh axis")

        routes: List[LeafRoute] = []
        stream_len = 0
        for path, leaf in flat:
            key = leaf_key(path)
            shape, dtype = tuple(leaf.shape), jnp.dtype(leaf.dtype)
            n = int(np.prod(shape)) if shape else 1
            if n < tc.min_compress_elems:
                routes.append(LeafRoute(key, shape, str(dtype), "raw"))
                continue
            if dtype == jnp.bfloat16 and tc.enabled:
                route = LeafRoute(key, shape, str(dtype), "splitzip",
                                  cap=_resolve_cap(tc, n))
                stream_len += n
            elif dtype == jnp.float32 and tc.enabled and tc.compress_fp32:
                route = LeafRoute(key, shape, str(dtype), "fp32_hilo",
                                  cap=_resolve_cap(tc, n))
                stream_len += n                     # the folded hi half
            elif _is_float8(dtype) and tc.enabled:
                route = LeafRoute(key, shape, str(dtype), "fp8",
                                  cap=_resolve_cap(tc, n))
            else:
                route = LeafRoute(key, shape, str(dtype), "raw")
            routes.append(route)

        if granularity is None:
            granularity = "chunked" if tc.n_chunks > 1 else "tensor"
        segments: List[SegmentSpec] = []
        if granularity == "chunked" and stream_len and tc.enabled:
            per = -(-stream_len // max(1, tc.n_chunks))        # ceil split
            per = max(tc.chunk, -(-per // tc.chunk) * tc.chunk)  # align up
            for start in range(0, stream_len, per):
                stop = min(start + per, stream_len)
                segments.append(SegmentSpec(start, stop,
                                            _resolve_cap(tc, stop - start)))

        in_specs = None
        if mesh is not None:
            if specs is not None:
                in_specs = tuple(jax.tree_util.tree_leaves(
                    specs, is_leaf=lambda x: isinstance(x, P)))
            else:
                in_specs = tuple(cls._default_leaf_spec(leaf, mesh)
                                 for _, leaf in flat)
        return cls(tc=tc, treedef=treedef, routes=tuple(routes),
                   backend=backend, segments=tuple(segments),
                   stream_len=stream_len, mesh=mesh, src_pod=src_pod,
                   dst_pod=dst_pod, in_specs=in_specs)

    @staticmethod
    def _default_leaf_spec(x, mesh: Mesh) -> P:
        # cache leaves: (L, B, S, ...) — batch over data, replicated over
        # pod/model (the host-staged value; prefill pod is the logical owner)
        spec = [None] * len(x.shape)
        if len(x.shape) >= 2 and x.shape[1] % mesh.shape["data"] == 0:
            spec[1] = "data"
        return P(*spec)

    # -- derived views -------------------------------------------------------
    @property
    def granularity(self) -> str:
        return "chunked" if len(self.segments) > 0 else "tensor"

    @property
    def n_chunks(self) -> int:
        return len(self.segments)

    @property
    def fp8_codebook(self) -> Codebook:
        return self.tc.fp8_codebook or FP8_DEFAULT_CODEBOOK

    def route_map(self) -> Dict[str, LeafRoute]:
        return {r.key: r for r in self.routes}

    def matches(self, cache) -> bool:
        """Does ``cache`` have exactly the structure this plan was built for?"""
        flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
        if treedef != self.treedef or len(flat) != len(self.routes):
            return False
        return all(tuple(leaf.shape) == r.shape
                   and str(jnp.dtype(leaf.dtype)) == r.dtype
                   for (_, leaf), r in zip(flat, self.routes))

    def schedule_for(self, n: int, cap: int) -> Tuple[Tuple[CodecBackend, str, int], ...]:
        """The geometric capacity schedule for one encoded unit of ``n``
        elements (see ``CodecBackend.capacity_schedule``)."""
        return self.backend.capacity_schedule(
            self.tc.layout, cap, n, doublings=self.tc.retry_doublings,
            global_budget=self.tc.retry_global_budget)

    def raw_bytes(self) -> float:
        return float(sum(r.raw_bytes for r in self.routes))

    def chunk_raw_bytes(self, scale: float = 1.0) -> List[float]:
        """Raw byte size of each pipeline chunk, as actually segmented.
        ``scale`` shrinks/grows every segment proportionally (the scheduler's
        per-prompt-length byte scaling within a bucket plan)."""
        return [s.raw_bytes * scale for s in self.segments]

    def byte_split(self, scale: float = 1.0) -> Tuple[float, float, float]:
        """(stream_bytes, fp8_sidecar_bytes, incompressible_bytes) under the
        route table: stream = bf16 bits + fp32 hi halves (codec ratio
        applies), fp8 sidecars compress outside the pipe, incompressible =
        raw passthrough + fp32 lo halves (full link cost — no ratio).
        ``scale`` multiplies every class (per-prompt-length scaling)."""
        stream = 2.0 * self.stream_len
        fp8 = out = 0.0
        for r in self.routes:
            if r.route == "fp8":
                fp8 += r.raw_bytes
            elif r.route == "fp32_hilo":
                out += 2.0 * r.n_elements           # the raw lo half
            elif r.route == "raw":
                out += r.raw_bytes
        return stream * scale, fp8 * scale, out * scale

    def expected_attempts(self, overflow_p: float) -> Tuple[float, float]:
        """``(expected encode attempts per unit, raw-fallback fraction)``
        under THIS plan's geometric capacity schedule when each attempt
        independently overflows with probability ``overflow_p``.  The
        schedule length is read off a representative encoded unit — the
        first segment (chunked) or the largest encoded leaf (tensor)."""
        if overflow_p <= 0.0:
            return 1.0, 0.0
        if self.segments:
            n, cap = self.segments[0].n_elements, self.segments[0].cap
        else:
            enc = [r for r in self.routes if r.route != "raw"]
            if not enc:
                return 1.0, 0.0
            big = max(enc, key=lambda r: r.n_elements)
            n, cap = big.n_elements, big.cap
        return expected_schedule_attempts(len(self.schedule_for(n, cap)),
                                          overflow_p)

    def estimate_time(self, profile: CodecProfile, *, scale: float = 1.0,
                      overflow_p: float = 0.0) -> float:
        """Plan-aware a-priori transfer time for ONE execution: the flowshop
        recurrence over the plan's ACTUAL segment sizes (tensor granularity:
        additive), charging the codec ratio only on routed bytes —
        incompressible sidecars (lo halves, raw passthrough) pay full link
        cost.

        ``scale`` evaluates the plan at a different payload size (the
        scheduler charges requests of one prompt-length bucket off one plan);
        ``overflow_p`` walks the capacity schedule in expectation: encode
        re-attempts inflate the encode stage and the exhausted fraction
        ships raw at full link bandwidth."""
        stream, fp8, out = self.byte_split(scale)
        attempts, raw_frac = self.expected_attempts(overflow_p)
        # fp8 sidecars walk the same capacity schedule: their exhausted
        # fraction also ships raw at full link cost
        t_side = (fp8 * ((1.0 - raw_frac) / (profile.ratio * profile.link_bw)
                         + raw_frac / profile.link_bw)
                  + out / profile.link_bw)
        if self.granularity == "chunked":
            times = [degraded_stage_times(s, profile, attempts=attempts,
                                          raw_frac=raw_frac)
                     for s in self.chunk_raw_bytes(scale)]
            return (flowshop_makespan(times) + profile.fixed_overhead_s
                    + t_side)
        t_enc, t_xfer, t_dec = degraded_stage_times(stream, profile,
                                                    attempts=attempts,
                                                    raw_frac=raw_frac)
        t_enc += attempts * fp8 / profile.g_enc      # fp8 sidecars are
        t_dec += (1.0 - raw_frac) * fp8 / profile.g_dec  # codec-touched too
        return t_enc + t_xfer + t_dec + t_side + profile.fixed_overhead_s

    def describe(self) -> str:
        """Human-readable routing table (serve launcher / docs)."""
        counts: Dict[str, int] = {}
        bytes_: Dict[str, float] = {}
        for r in self.routes:
            counts[r.route] = counts.get(r.route, 0) + 1
            bytes_[r.route] = bytes_.get(r.route, 0.0) + r.raw_bytes
        target = ("local" if self.mesh is None
                  else f"mesh(pod {self.src_pod}->{self.dst_pod})")
        lines = [f"TransferPlan[{self.granularity}, backend={self.backend.name}, "
                 f"target={target}, n_chunks={max(1, self.n_chunks)}]"]
        for route in ("splitzip", "fp32_hilo", "fp8", "raw"):
            if route in counts:
                lines.append(f"  {route:10s}: {counts[route]:3d} leaves, "
                             f"{bytes_[route] / 2**20:8.2f} MiB raw")
        if self.segments:
            lines.append(f"  segments  : {self.n_chunks} x "
                         f"~{self.segments[0].n_elements} u16 elems "
                         f"(cap {self.segments[0].cap})")
        return "\n".join(lines)

    # -- stream folding (chunked granularity) --------------------------------
    def fold_stream(self, cache) -> Tuple[jax.Array, Dict, Dict, Dict]:
        """Flatten every routed leaf into ONE u16 bit stream in route order:
        bf16 leaves contribute their container bits, fp32 leaves their hi
        halves (lo halves returned separately, shipped raw).  Returns
        ``(stream, lo_halves, fp8_leaves, raw_leaves)``."""
        flat = jax.tree_util.tree_flatten_with_path(cache)[0]
        parts: List[jax.Array] = []
        lo: Dict[str, jax.Array] = {}
        fp8: Dict[str, jax.Array] = {}
        raw: Dict[str, jax.Array] = {}
        for (path, leaf), r in zip(flat, self.routes):
            if r.route == "splitzip":
                parts.append(jax.lax.bitcast_convert_type(
                    leaf, jnp.uint16).reshape(-1))
            elif r.route == "fp32_hilo":
                u = jax.lax.bitcast_convert_type(leaf, jnp.uint32).reshape(-1)
                parts.append((u >> 16).astype(jnp.uint16))
                lo[r.key] = (u & 0xFFFF).astype(jnp.uint16)
            elif r.route == "fp8":
                fp8[r.key] = leaf
            else:
                raw[r.key] = leaf
        if not parts:
            stream = jnp.zeros((0,), jnp.uint16)
        else:
            stream = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        return stream, lo, fp8, raw

    def unfold_stream(self, bits_out: jax.Array, lo: Dict, fp8_decoded: Dict,
                      raw: Dict):
        """Inverse of :meth:`fold_stream` against the plan's structure."""
        leaves, off = [], 0
        for r in self.routes:
            n = r.n_elements
            if r.route == "splitzip":
                leaves.append(jax.lax.bitcast_convert_type(
                    bits_out[off:off + n].reshape(r.shape), jnp.bfloat16))
                off += n
            elif r.route == "fp32_hilo":
                hi = bits_out[off:off + n].astype(jnp.uint32)
                u = (hi << 16) | lo[r.key].astype(jnp.uint32)
                leaves.append(jax.lax.bitcast_convert_type(
                    u.reshape(r.shape), jnp.float32))
                off += n
            elif r.route == "fp8":
                leaves.append(jnp.asarray(fp8_decoded[r.key]).reshape(r.shape))
            else:
                leaves.append(raw[r.key])
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    # -- session -------------------------------------------------------------
    def session(self, *, faults=None, verify: bool = False,
                retain_last: bool = False) -> "TransferSession":
        """``faults`` is ``None | registry name | FaultPlan`` (see
        :mod:`repro.serving.faults`); ``verify=True`` checksum-verifies every
        wire hop and routes failures through the capacity-retry machinery;
        ``retain_last=True`` keeps the last transfer's pristine compressed
        payloads sender-side so a decode-worker failover can re-send them
        (``TransferSession.resend_last``) without re-encoding."""
        from repro.serving.session import TransferSession
        return TransferSession(self, faults=faults, verify=verify,
                               retain_last=retain_last)


def raw_wire_bytes(cache) -> float:
    return float(sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache)))


# ---------------------------------------------------------------------------
# analytic transfer report (paper Fig. 3 / Fig. 4 accounting)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TransferReport:
    raw_bytes: float
    wire_bytes: float
    t_native: float
    t_splitzip: float
    t_encode: float
    t_transfer: float
    t_decode: float

    @property
    def ratio(self) -> float:
        return self.raw_bytes / max(self.wire_bytes, 1.0)

    @property
    def speedup(self) -> float:
        return self.t_native / max(self.t_splitzip, 1e-12)


def transfer_report(raw_bytes: float, wire_bytes: float,
                    profile: CodecProfile, n_chunks: int = 1,
                    plan: Optional[TransferPlan] = None) -> TransferReport:
    """Analytic accounting from MEASURED wire bytes: additive
    encode + compressed transfer + decode (Fig. 4) when ``n_chunks == 1``,
    chunked steady-state pipeline (Appendix A) when ``n_chunks > 1``.

    With ``plan=`` the pipeline term splits the MEASURED totals across
    chunks in the plan's ACTUAL segment proportions (short tail chunk
    included) and runs the flowshop recurrence — still a function of the
    measured raw/wire bytes, so it stays consistent when the totals
    accumulate over many engine calls and when raw fallbacks inflate the
    wire bytes (``plan.estimate_time`` is the single-transfer a-priori
    estimate instead)."""
    t_enc = raw_bytes / profile.g_enc
    t_dec = raw_bytes / profile.g_dec
    t_xfer = wire_bytes / profile.link_bw
    if plan is not None and plan.granularity == "chunked":
        seg = plan.chunk_raw_bytes()
        fracs = [s / sum(seg) for s in seg]
        t_total = flowshop_makespan(
            [(f * t_enc, f * t_xfer, f * t_dec) for f in fracs]
        ) + profile.fixed_overhead_s
    elif n_chunks > 1:
        per = [t / n_chunks for t in (t_enc, t_xfer, t_dec)]
        t_total = sum(per) + (n_chunks - 1) * max(per) + profile.fixed_overhead_s
    else:
        t_total = t_enc + t_xfer + t_dec + profile.fixed_overhead_s
    return TransferReport(
        raw_bytes=raw_bytes,
        wire_bytes=wire_bytes,
        t_native=raw_bytes / profile.link_bw + profile.fixed_overhead_s,
        t_splitzip=t_total,
        t_encode=t_enc, t_transfer=t_xfer, t_decode=t_dec,
    )
