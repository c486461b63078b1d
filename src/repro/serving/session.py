"""TransferSession: executes a resolved :class:`TransferPlan` many times.

One session == one (plan, execution-target) pair.  ``send(cache)`` runs the
prefill-side work (encode + the wire hop), ``recv()`` the decode-side work,
``transfer(cache)`` fuses both; ``last_stats`` carries per-call accounting.
All serving consumers (``DisaggregatedEngine``, launchers, benchmarks,
examples) go through this API — the free functions in
:mod:`repro.serving.transfer` are deprecation shims over a one-shot plan.

Five execution paths, selected by the plan and the entry point:

* **local / tensor** (``mesh=None, n_chunks == 1``): per-leaf encode ->
  hand-off -> decode, per-tensor raw fallback, geometric capacity retries.
* **local / chunked** (``mesh=None, n_chunks > 1``): the pipelined engine —
  ``ChunkSchedule`` drives encode of chunk t / ship of t-1 / decode of t-2
  over the plan's precomputed codec-chunk-aligned segments, with fp32 hi
  halves folded into the stream and per-chunk retries + raw fallback.
* **mesh** (``mesh=``): the same two granularities traced inside
  ``shard_map`` over the 'pod' axis.  ``n_chunks > 1`` ships each chunk with
  its own ``lax.ppermute`` and holds at most two chunks in flight
  (double-buffering: encode of chunk t is issued while chunk t-1's permute
  and chunk t-2's decode are outstanding), so the overlap is structural in
  the traced program, not just modeled.  In-graph execution cannot branch on
  the concrete ``ok`` flag, so the mesh path encodes once at plan capacity;
  overflow is detected off-graph exactly as on the whole-tensor path.
* **persistent** (``save(path)`` / ``load(path)``): per-leaf SZ02 wire
  frames on disk plus a plan-derived JSON manifest
  (docs/wire_format.md §9).  Loads re-verify Fletcher-32 per file AND the
  payload's own integrity-frame table; mismatches re-fetch down the plan's
  retry budget and raise :class:`~repro.core.wire.WireIntegrityError` when
  the corruption is persistent.  distributed/checkpoint.py is a thin
  wrapper over this executor.
* **collective** (``ring_reduce(stacked)``): grad_compress's rotating-ring
  ppermute exchange over compressed streams, traced inside ``shard_map``
  over the plan's pod axis with the mesh executor's bit-pinned permutes.
  training/grad_compress.py is a thin wrapper over this executor.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil
import tempfile
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from repro.core.backend import CodecBackend, WireCompressed, get_backend
from repro.core.pipeline import ChunkSchedule
from repro.core.spans import host_read, span
from repro.core.wire import WireIntegrityError, WireStats, fletcher32
from repro.serving.faults import FaultChannel, resolve_faults
from repro.serving.plan import TransferPlan, TransferStats, leaf_key

_WIRE_INT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}

# persistent-executor manifest (docs/wire_format.md §9)
PERSIST_MANIFEST = "manifest.json"
PERSIST_FORMAT = "szpersist-1"

# hard ceiling on wire attempts per unit (initial ship + re-fetches).  The
# default FaultPlan stops randomized faults at max_attempt=8, so only an
# explicitly-persistent adversarial plan can reach this — and then the
# session fails LOUDLY instead of decoding garbage or spinning forever.
_MAX_WIRE_ATTEMPTS = 32


class TransferIntegrityError(RuntimeError):
    """A wire unit could not be delivered intact within the attempt budget —
    every capacity-schedule re-fetch and the terminal raw re-fetches all
    failed verification.  Raised instead of ever decoding corrupt bytes."""


def _backend_for(comp_obj, be: CodecBackend) -> CodecBackend:
    """Resolve the backend that can actually decode ``comp_obj``.

    Wire payloads decode only with the wire backend, in-graph
    CompressedTensors only with a jittable one (xla and pallas share the
    stream layout, so either decodes either).  A mismatched backend is
    corrected instead of crashing with an opaque AttributeError."""
    from repro.core.backend import WireCompressed
    if isinstance(comp_obj, WireCompressed):
        return be if be.name == "wire" else get_backend("wire")
    return be if be.jittable else get_backend("xla")


def _permute_leaf(x: jax.Array, axis_name: str, src: int, dst: int) -> jax.Array:
    """ppermute with the payload pinned to its exact bit width.

    XLA CPU (and some TPU paths) upcast small-float collectives — doubling
    the wire bytes and silently defeating the codec.  Bitcasting to a
    same-width integer type before the collective guarantees the HLO moves
    exactly the bytes we account for; the roundtrip is a bitcast, hence
    lossless."""
    if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype.itemsize in _WIRE_INT:
        w = _WIRE_INT[x.dtype.itemsize]
        y = jax.lax.ppermute(jax.lax.bitcast_convert_type(x, w), axis_name,
                             perm=[(src, dst)])
        return jax.lax.bitcast_convert_type(y, x.dtype)
    return jax.lax.ppermute(x, axis_name, perm=[(src, dst)])


# ---------------------------------------------------------------------------
# per-leaf encode/decode (tensor granularity; also the mesh whole-tensor body)
# ---------------------------------------------------------------------------

def _encode_scheduled(plan: TransferPlan, x, codebook, n: int, cap: int,
                      *, scheduled: bool, key: str,
                      stats: Optional[TransferStats] = None):
    """Encode ``x`` down the plan's geometric capacity schedule.

    Returns ``(ct, ok, extra_attempts)``.  ``scheduled=False`` (one-shot
    shims, in-graph tracing) encodes once at plan capacity and leaves ``ok``
    traced — the schedule's concrete ``ok`` branch is host-side control
    flow.  A scheduled encode is eager host code: it runs inside a
    ``sz.transfer.encode`` span, and its ``ok`` reads count on ``stats``."""
    tc = plan.tc
    if not scheduled:
        ct = plan.backend.encode(x, codebook, chunk=tc.chunk, cap=cap,
                                 layout=tc.layout)
        return ct, plan.backend.ok(ct), 0
    with span("transfer.encode", key=key):
        ct = plan.backend.encode(x, codebook, chunk=tc.chunk, cap=cap,
                                 layout=tc.layout)
        if host_read(plan.backend.ok(ct), "ok", stats, bool):
            return ct, True, 0
        extra = 0
        for be, layout, c in plan.schedule_for(n, cap)[1:]:
            extra += 1
            ct = be.encode(x, codebook, chunk=tc.chunk, cap=c, layout=layout)
            if host_read(be.ok(ct), "ok", stats, bool):
                return ct, True, extra
        return ct, False, extra


def _record_unit(stats: Optional[TransferStats], key: str, ok: bool,
                 extra: int) -> None:
    if stats is None:
        return
    stats.leaf_ok[key] = ok
    stats.chunk_retried.append(extra > 0)
    stats.chunk_retry_steps.append(extra)


def encode_leaves(plan: TransferPlan, cache, *, scheduled: bool = True,
                  stats: Optional[TransferStats] = None) -> Tuple[Dict, Dict]:
    """Per-leaf route execution -> (comp, raw) in the legacy key convention:
    ``comp[key]`` holds splitzip/fp8 streams, ``comp[key + '#hi']`` the fp32
    hi half, ``raw[key + '#lo']`` its raw lo half, ``raw[key]`` passthrough
    (including the raw fallback of units whose capacity schedule exhausted).

    ``scheduled=False`` is the one-shot / in-graph mode: single encode at
    plan capacity, streams kept regardless of the (traced) ``ok`` flag."""
    tc = plan.tc
    be = plan.backend
    comp: Dict[str, object] = {}
    raw: Dict[str, jax.Array] = {}
    flat = jax.tree_util.tree_flatten_with_path(cache)[0]
    for (path, leaf), r in zip(flat, plan.routes):
        key = r.key
        if r.route == "splitzip":
            ct, ok, extra = _encode_scheduled(plan, leaf, tc.codebook,
                                              r.n_elements, r.cap,
                                              scheduled=scheduled, key=key,
                                              stats=stats)
            if scheduled and not bool(ok):
                raw[key] = leaf
                if stats is not None:
                    stats.leaf_wire_bytes[key] = r.raw_bytes
                _record_unit(stats, key, False, extra)
            else:
                comp[key] = ct
                if stats is not None:
                    stats.leaf_wire_bytes[key] = host_read(
                        be.wire_bytes(ct), "wire_bytes", stats, float)
                _record_unit(stats, key, True, extra)
        elif r.route == "fp32_hilo":
            u = jax.lax.bitcast_convert_type(leaf, jnp.uint32)
            hi = (u >> 16).astype(jnp.uint16)
            lo = (u & 0xFFFF).astype(jnp.uint16)
            ct, ok, extra = _encode_scheduled(plan, hi, tc.codebook,
                                              r.n_elements, r.cap,
                                              scheduled=scheduled, key=key,
                                              stats=stats)
            if scheduled and not bool(ok):
                # an overflowed hi half means the WHOLE fp32 leaf ships raw
                raw[key] = leaf
                if stats is not None:
                    stats.leaf_wire_bytes[key] = r.raw_bytes
                _record_unit(stats, key, False, extra)
            else:
                comp[key + "#hi"] = ct
                raw[key + "#lo"] = lo
                if stats is not None:
                    stats.leaf_wire_bytes[key] = host_read(
                        be.wire_bytes(ct), "wire_bytes", stats, float)
                    stats.fp32_lo_wire_bytes += 2.0 * r.n_elements
                _record_unit(stats, key, True, extra)
        elif r.route == "fp8":
            ct, ok, extra = _encode_scheduled(plan, leaf, plan.fp8_codebook,
                                              r.n_elements, r.cap,
                                              scheduled=scheduled, key=key,
                                              stats=stats)
            if scheduled and not bool(ok):
                raw[key] = leaf
                if stats is not None:
                    stats.fp8_wire_bytes += r.raw_bytes
                _record_unit(stats, key, False, extra)
            else:
                comp[key] = ct
                if stats is not None:
                    stats.fp8_wire_bytes += host_read(
                        be.wire_bytes(ct), "wire_bytes", stats, float)
                _record_unit(stats, key, True, extra)
        else:
            raw[key] = leaf
            if stats is not None:
                stats.raw_passthrough_bytes += r.raw_bytes
    return comp, raw


def decode_leaves(comp: Dict, raw: Dict, structure, backend: str = "xla"):
    """Inverse of :func:`encode_leaves` against the original pytree structure.
    Per-object backend dispatch (:func:`_backend_for`) tolerates a
    ``backend=`` argument that doesn't match what produced ``comp``."""
    be = get_backend(backend)
    flat, treedef = jax.tree_util.tree_flatten_with_path(structure)
    # the mesh executor decodes inside shard_map: no spans while tracing
    eager = not any(isinstance(x, jax.core.Tracer)
                    for x in jax.tree.leaves((comp, raw)))
    leaves = []
    for path, leaf in flat:
        key = leaf_key(path)
        if key in comp:
            ct = comp[key]
            with _leaf_span(eager, "transfer.decode", key):
                leaves.append(jnp.asarray(
                    _backend_for(ct, be).decode(ct)).reshape(leaf.shape))
        elif key + "#hi" in comp:  # fp32 hi/lo split
            ct = comp[key + "#hi"]
            with _leaf_span(eager, "transfer.decode", key):
                hi = jnp.asarray(
                    _backend_for(ct, be).decode(ct)).reshape(leaf.shape)
                lo = raw[key + "#lo"]
                u = (hi.astype(jnp.uint32) << 16) | lo.astype(jnp.uint32)
                leaves.append(jax.lax.bitcast_convert_type(u, jnp.float32))
        else:
            leaves.append(raw[key])
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _leaf_span(eager: bool, name: str, key: str):
    """A per-leaf span in eager code; none while tracing, where it would
    time the trace and not the work."""
    return span(name, key=key) if eager else contextlib.nullcontext()


# ---------------------------------------------------------------------------
# prefix-delta index (transfer_delta)
# ---------------------------------------------------------------------------

def _host_bits(x, stats: TransferStats) -> np.ndarray:
    """Flat byte view of any array-like, on host.  Sender-shadow comparison
    runs in the BIT domain, not the numeric one — NaN payloads, negative
    zeros, and denormals all compare exactly."""
    return np.ascontiguousarray(host_read(x, "shadow", stats)).view(
        np.uint8).reshape(-1)


@dataclasses.dataclass
class _PrefixEntry:
    """One session's resident cache, seen from both ends of the wire:
    sender-side bit shadows (what to compare the next turn against) and
    receiver-side objects (what a hit re-uses without any wire traffic)."""

    stream: np.ndarray                   # sender u16 shadow of fold_stream
    seg_bits: List[jax.Array]            # receiver decoded bits per segment
    side_shadow: Dict[str, np.ndarray]   # "<fam>:<key>" -> sender host bits
    side_obj: Dict[str, object]          # "<fam>:<key>" -> receiver object
    nbytes: float                        # raw-byte footprint (LRU accounting)


class PrefixIndex:
    """LRU-by-bytes map of session id -> :class:`_PrefixEntry`.

    This is the execution-side twin of the scheduler's sim-side
    ``PrefixDirectory``: where the directory *models* residency in token
    counts, this index *holds* the actual receiver objects and the sender
    shadows that :meth:`TransferSession.transfer_delta` compares against.
    ``capacity_bytes=None`` means unbounded; otherwise least-recently-used
    sessions are dropped until the raw-byte footprint fits (a single entry
    larger than the whole budget is dropped immediately — residency must
    never exceed the stated HBM envelope)."""

    def __init__(self, capacity_bytes: Optional[float] = None):
        if capacity_bytes is not None and capacity_bytes <= 0:
            raise ValueError("capacity_bytes must be positive (or None)")
        self.capacity_bytes = capacity_bytes
        self.evictions = 0
        self._entries: "OrderedDict[object, _PrefixEntry]" = OrderedDict()

    def __len__(self) -> int:
        return len(self._entries)

    def sessions(self):
        return list(self._entries)

    @property
    def resident_bytes(self) -> float:
        return sum(e.nbytes for e in self._entries.values())

    def get(self, session_id) -> Optional[_PrefixEntry]:
        e = self._entries.get(session_id)
        if e is not None:
            self._entries.move_to_end(session_id)
        return e

    def put(self, session_id, entry: _PrefixEntry) -> None:
        self._entries[session_id] = entry
        self._entries.move_to_end(session_id)
        if self.capacity_bytes is None:
            return
        while self._entries and self.resident_bytes > self.capacity_bytes:
            self._entries.popitem(last=False)
            self.evictions += 1

    def drop(self, session_id) -> None:
        self._entries.pop(session_id, None)

    def clear(self) -> None:
        self._entries.clear()


# ---------------------------------------------------------------------------
# the session
# ---------------------------------------------------------------------------

class TransferSession:
    """Run a :class:`TransferPlan` repeatedly: ``send``/``recv`` or the fused
    ``transfer``.  Accumulates ``calls``/``total_wire_bytes``; per-call
    accounting is in ``last_stats`` (None on the mesh path, whose wire bytes
    are read from the lowered HLO — see analysis/roofline.py).

    **Wire integrity** (``verify=True`` and/or ``faults=``): every wire
    object — pipeline chunks, tensor-path leaves, sidecars — ships inside a
    Fletcher-32 checksum frame over a :class:`~repro.serving.faults.
    FaultChannel`.  With ``verify`` on, a mismatched or dropped frame is
    re-fetched through the plan's capacity-retry machinery (re-encode at the
    next schedule step, re-ship with the fault coordinate re-keyed), with
    the unit's RAW bits as the terminal re-fetch; corrupt bytes are never
    decoded, and exhaustion raises :class:`TransferIntegrityError` instead
    of degrading silently.  ``faults=`` injects a seeded
    :class:`~repro.serving.faults.FaultPlan` into the channel so all of this
    is testable on CPU.  Local paths only — the mesh path's wire is a traced
    collective with no host frame to checksum."""

    def __init__(self, plan: TransferPlan, *, faults=None,
                 verify: bool = False, retain_last: bool = False):
        self.plan = plan
        self.verify = verify
        self.retain_last = retain_last
        self.faults = resolve_faults(faults)
        if plan.mesh is not None and (verify or self.faults is not None):
            raise ValueError(
                "verify/faults run on the host wire hop; the mesh path's "
                "collective permute has no host-side frame to checksum")
        # the checksum-framed wire: active whenever faults are injected or
        # verification is on, so the happy path pays nothing
        self._channel = (FaultChannel(self._object_checksum, self.faults)
                         if (verify or self.faults is not None) else None)
        self.last_stats: Optional[TransferStats] = None
        self.calls = 0
        self.total_wire_bytes = 0.0
        self._uid = 0         # per-send transfer id (fault-plan keying)
        self._injected_seen = 0
        self._staged = None   # in-flight payload between send() and recv()
        # failover re-send: the pristine encoded payload of the most recent
        # tensor-path send, kept only under retain_last (see resend_last)
        self._retained = None
        # prefix-delta state: session-id -> _PrefixEntry (see transfer_delta)
        self._prefix_index: Optional[PrefixIndex] = None
        # executor closures, built on first use: a mesh plan may only ever
        # run the collective executor (ring specs don't fit the send/recv
        # out_specs convention), so neither shard_map is constructed eagerly
        self._mesh_fn = None
        self._ring_fns = {}         # frozenset(raw-forced leaf idx) -> fn
        self._ring_routes = None    # per-participant routes for the ring

    def _object_checksum(self, obj) -> int:
        """Fletcher-32 over any wire object — compressed (backend leaves or
        host payload bytes) or a raw array."""
        return _backend_for(obj, self.plan.backend).checksum(obj)

    # -- public API ----------------------------------------------------------
    def send(self, cache, check: bool = True) -> None:
        """Prefill-side half: encode every routed leaf and put the payload on
        the (simulated or collective) wire.  Call ``recv`` to complete.
        ``check=False`` skips the structure validation for callers that
        already ran ``plan.matches`` themselves (one pytree walk saved per
        call on the hot path)."""
        if self._staged is not None:
            raise RuntimeError("send() called twice without recv()")
        if check:
            self._check_structure(cache)
        self._uid += 1
        if self.plan.mesh is not None:
            self._staged = ("mesh", cache)
        elif self.plan.granularity == "chunked":
            self._staged = ("chunked", self._send_chunked(cache))
        else:
            self._staged = ("tensor", self._send_tensor(cache))

    def _set_verify(self, verify: Optional[bool]) -> None:
        """Per-call ``verify=`` knob: None keeps the session default."""
        if verify is None:
            return
        if verify and self._channel is None:
            raise ValueError(
                "this session shipped unframed payloads (no checksums on the "
                "wire); build it with plan.session(verify=True) or faults=")
        self.verify = bool(verify)

    def recv(self, select_dst: bool = True, verify: Optional[bool] = None):
        """Decode-side half: returns the reassembled cache pytree.
        ``verify=True`` enforces the checksum frames shipped by ``send``
        (re-fetch on mismatch; see class docs), ``verify=False`` delivers
        without enforcement, None keeps the session default."""
        if self._staged is None:
            raise RuntimeError("recv() called before send()")
        self._set_verify(verify)
        kind, payload = self._staged
        self._staged = None
        if kind == "mesh":
            out = self._run_mesh(payload, select_dst=select_dst)
        elif kind == "chunked":
            out = self._recv_chunked(payload)
        else:
            out = self._recv_tensor(payload)
        self._account()
        return out

    def transfer(self, cache, select_dst: bool = True, check: bool = True,
                 verify: Optional[bool] = None):
        """Fused send + recv.  The local chunked path interleaves the stages
        on the explicit ``ChunkSchedule`` (encode t / ship t-1 / decode t-2),
        exactly the ordering deployment wall-clock overlaps; the result is
        bit-identical to split send()+recv().  ``verify=`` as on ``recv``."""
        self._set_verify(verify)
        if self.plan.mesh is None and self.plan.granularity == "chunked":
            if self._staged is not None:
                raise RuntimeError("transfer() called with a send() pending")
            if check:
                self._check_structure(cache)
            self._uid += 1
            out = self._transfer_chunked_interleaved(cache)
            self._account()
            return out
        self.send(cache, check=check)
        return self.recv(select_dst=select_dst)

    def transfer_compressed(self, cache, check: bool = True,
                            verify: Optional[bool] = None):
        """Tensor-path transfer that STOPS at the compressed streams.

        Resident-KV admission consumes the received ``CompressedTensor``s
        directly (``models/kvpool.KVPool.admit_from_wire``) — the decode
        worker never rehydrates the stream it is about to keep compressed.
        Returns ``(comp, raw)`` in the ``encode_leaves`` key convention;
        leaves that fell back to raw (escape overflow, un-routed dtypes)
        appear in ``raw`` and make the batch inadmissible for residency.

        Only the local tensor path qualifies: chunked and mesh granularities
        re-segment leaves, so their wire streams are not page-addressable."""
        if self.plan.mesh is not None or self.plan.granularity == "chunked":
            raise ValueError(
                "transfer_compressed requires the local tensor path "
                "(mesh=None, n_chunks == 1); use transfer() and raw "
                "residency for segmented transfers")
        self._set_verify(verify)
        self.send(cache, check=check)
        _, payload = self._staged
        self._staged = None
        comp, raw, structure, pristine_comp, pristine_raw = payload
        if self._channel is not None:
            comp, raw = self._deliver_tensor(comp, raw, structure,
                                             pristine_comp, pristine_raw)
        self._account()
        return comp, raw

    def resend_last(self, verify: Optional[bool] = None):
        """Re-ship the most recent tensor-path transfer from its retained
        encoded payload — the decode-worker-failover path.

        When the destination worker dies after the wire hop completed, the
        prefill side still holds the pristine compressed streams of the last
        ``send`` (kept under ``retain_last=True``); re-sending them to the
        replacement worker costs one wire hop, not a re-encode.  Returns the
        decoded cache, bit-identical to the original transfer's result;
        ``last_stats`` / ``total_wire_bytes`` account the repeated hop like
        any other call.  Tensor granularity only — chunked/mesh payloads are
        not retained (their streams are re-segmented per transfer)."""
        if self.plan.mesh is not None or self.plan.granularity == "chunked":
            raise ValueError(
                "resend_last requires the local tensor path (mesh=None, "
                "n_chunks == 1); chunked/mesh transfers are not retained")
        if self._retained is None:
            raise RuntimeError(
                "no retained transfer to re-send; build the session with "
                "retain_last=True and complete a transfer first")
        if self._staged is not None:
            raise RuntimeError("resend_last() called with a send() pending")
        self._set_verify(verify)
        comp, raw, cache = self._retained
        be = self.plan.backend
        stats = TransferStats(chunk_wire_bytes=[], chunk_ok=[],
                              raw_passthrough_bytes=0.0, n_elements=0)
        for r in self.plan.routes:
            key = r.key
            if key in comp:
                nbytes = float(_backend_for(comp[key], be)
                               .wire_bytes(comp[key]))
                if r.route == "fp8":
                    stats.fp8_wire_bytes += nbytes
                else:
                    stats.leaf_wire_bytes[key] = nbytes
                stats.leaf_ok[key] = True
            elif key + "#hi" in comp:
                hi = comp[key + "#hi"]
                stats.leaf_wire_bytes[key] = float(
                    _backend_for(hi, be).wire_bytes(hi))
                stats.fp32_lo_wire_bytes += 2.0 * r.n_elements
                stats.leaf_ok[key] = True
            elif r.route == "raw":
                stats.raw_passthrough_bytes += r.raw_bytes
            else:
                # a leaf that fell back to raw on the original encode
                if r.route == "fp8":
                    stats.fp8_wire_bytes += r.raw_bytes
                else:
                    stats.leaf_wire_bytes[key] = r.raw_bytes
                stats.leaf_ok[key] = False
        self.last_stats = stats
        self._uid += 1
        if self._channel is not None:
            comp_f = {k: self._channel.ship(v, self._uid, ci, 0)
                      for ci, (k, v) in enumerate(comp.items())}
            raw_f = {k: self._channel.ship(v, self._uid, len(comp) + ci, 0)
                     for ci, (k, v) in enumerate(raw.items())}
            comp_d, raw_d = self._deliver_tensor(comp_f, raw_f, cache,
                                                 comp, raw)
        else:
            comp_d, raw_d = comp, raw
        out = decode_leaves(comp_d, raw_d, cache,
                            backend=self.plan.tc.backend)
        self._account()
        return out

    # -- prefix-delta transfer ----------------------------------------------
    def enable_prefix_cache(self,
                            capacity_bytes: Optional[float] = None
                            ) -> PrefixIndex:
        """Attach a :class:`PrefixIndex` so :meth:`transfer_delta` can skip
        segments the destination already holds.  Chunked local path only —
        delta granularity IS the plan's codec-aligned segmentation.  Returns
        the index (idempotent; the first capacity wins)."""
        if self.plan.mesh is not None or self.plan.granularity != "chunked":
            raise ValueError(
                "prefix-delta transfer rides the chunked local path "
                "(mesh=None, n_chunks > 1); build the plan with "
                "granularity='chunked'")
        if self._prefix_index is None:
            self._prefix_index = PrefixIndex(capacity_bytes)
        return self._prefix_index

    def transfer_delta(self, cache, session_id, *, check: bool = True,
                       verify: Optional[bool] = None):
        """Prefix-aware transfer: ship only the segments (and sidecars) that
        CHANGED since this session id's last transfer.

        The sender compares each segment of the folded stream bit-for-bit
        against its retained shadow of the previous turn; an identical
        segment costs zero wire bytes — the receiver re-uses the decoded
        bits it already holds — and its raw size lands in
        ``last_stats.prefix_hit_bytes`` (deliberately excluded from
        ``wire_bytes``).  Changed segments run the normal chunked machinery:
        capacity-schedule retries, checksum framing, verified re-fetches.
        Sidecars (fp32 lo halves, fp8 leaves, raw passthrough) delta the
        same way on whole-object bit equality.  The result is bit-identical
        to a full ``transfer`` of the same cache; a cold session id degrades
        to exactly a full transfer.  Requires :meth:`enable_prefix_cache`."""
        if self._prefix_index is None:
            raise RuntimeError(
                "prefix cache not enabled; call enable_prefix_cache() first")
        if self._staged is not None:
            raise RuntimeError("transfer_delta() called with a send() "
                               "pending")
        self._set_verify(verify)
        if check:
            self._check_structure(cache)
        self._uid += 1
        plan = self.plan
        stats = self._new_chunked_stats()
        stream, lo, fp8, raw = plan.fold_stream(cache)
        host_stream = host_read(stream, "stream", stats)
        entry = self._prefix_index.get(session_id)

        # pipelined stream: per-segment sender-shadow comparison
        bits: List[jax.Array] = []
        for i, seg in enumerate(plan.segments):
            if entry is not None and np.array_equal(
                    host_stream[seg.start:seg.stop],
                    entry.stream[seg.start:seg.stop]):
                bits.append(entry.seg_bits[i])
                stats.prefix_hit_bytes += seg.raw_bytes
                # chunk_wire_bytes[i] stays 0.0: nothing crossed the wire
            else:
                p = self._wire_hop(stream, i, self._encode_chunk(stream, i),
                                   stats)
                bits.append(self._chunk_out(stream, i, p, stats))

        # sidecars: whole-object bit equality against the shadow
        lo_out: Dict[str, object] = {}
        fp8_dec: Dict[str, object] = {}
        raw_out: Dict[str, object] = {}
        miss_lo: Dict[str, object] = {}
        miss_fp8: Dict[str, object] = {}
        miss_raw: Dict[str, object] = {}

        def _side_hit(fam: str, key: str, sender_obj) -> bool:
            if entry is None:
                return False
            shadow = entry.side_shadow.get(f"{fam}:{key}")
            return (shadow is not None
                    and np.array_equal(_host_bits(sender_obj, stats), shadow))

        for r in plan.routes:
            k = r.key
            if r.route == "fp32_hilo":
                if _side_hit("lo", k, lo[k]):
                    lo_out[k] = entry.side_obj[f"lo:{k}"]
                    stats.prefix_hit_bytes += 2.0 * r.n_elements
                else:
                    miss_lo[k] = lo[k]
                    stats.fp32_lo_wire_bytes += 2.0 * r.n_elements
            elif r.route == "fp8":
                if _side_hit("fp8", k, fp8[k]):
                    fp8_dec[k] = entry.side_obj[f"fp8:{k}"]
                    stats.prefix_hit_bytes += r.raw_bytes
                else:
                    ct, ok, extra = _encode_scheduled(
                        plan, fp8[k], plan.fp8_codebook, r.n_elements, r.cap,
                        scheduled=True, key=k, stats=stats)
                    _record_unit(stats, k, bool(ok), extra)
                    stats.fp8_wire_bytes += (
                        host_read(plan.backend.wire_bytes(ct), "wire_bytes",
                                  stats, float) if ok
                        else r.raw_bytes)
                    miss_fp8[k] = ct if ok else fp8[k]
            elif r.route == "raw":
                if _side_hit("raw", k, raw[k]):
                    raw_out[k] = entry.side_obj[f"raw:{k}"]
                    stats.prefix_hit_bytes += r.raw_bytes
                else:
                    miss_raw[k] = raw[k]
                    stats.raw_passthrough_bytes += r.raw_bytes

        if self._channel is not None:
            lo_f, fp8_f, raw_f = self._ship_sidecars(miss_lo, miss_fp8,
                                                     miss_raw)
            miss_lo, miss_fp8, miss_raw = self._deliver_sidecars(
                lo_f, fp8_f, raw_f, (miss_lo, miss_fp8, miss_raw), stats)
        lo_out.update(miss_lo)
        raw_out.update(miss_raw)
        for k, p in miss_fp8.items():
            if isinstance(p, (jax.Array, np.ndarray)):  # raw fallback leaf
                fp8_dec[k] = jnp.asarray(p)
            else:
                fp8_dec[k] = _backend_for(p, plan.backend).decode(p)

        bits_out = (jnp.concatenate(bits) if len(bits) > 1 else bits[0])
        out = plan.unfold_stream(bits_out, lo_out, fp8_dec, raw_out)

        # refresh the shadow + receiver objects for the NEXT turn
        shadow: Dict[str, np.ndarray] = {}
        side_obj: Dict[str, object] = {}
        nbytes = 2.0 * host_stream.size
        for r in plan.routes:
            k = r.key
            if r.route == "fp32_hilo":
                shadow[f"lo:{k}"] = _host_bits(lo[k], stats).copy()
                side_obj[f"lo:{k}"] = lo_out[k]
                nbytes += 2.0 * r.n_elements
            elif r.route == "fp8":
                shadow[f"fp8:{k}"] = _host_bits(fp8[k], stats).copy()
                side_obj[f"fp8:{k}"] = fp8_dec[k]
                nbytes += r.raw_bytes
            elif r.route == "raw":
                shadow[f"raw:{k}"] = _host_bits(raw[k], stats).copy()
                side_obj[f"raw:{k}"] = raw_out[k]
                nbytes += r.raw_bytes
        self._prefix_index.put(session_id, _PrefixEntry(
            stream=host_stream.copy(), seg_bits=list(bits),
            side_shadow=shadow, side_obj=side_obj, nbytes=nbytes))

        self.last_stats = stats
        self._account()
        return out

    def lower_hlo(self, cache) -> str:
        """Post-SPMD HLO of the mesh program on ``cache``: the
        collective-permute operand sizes are the actual wire bytes."""
        if self.plan.mesh is None:
            raise ValueError("lower_hlo is only meaningful for mesh plans")
        if self._mesh_fn is None:
            self._mesh_fn = self._build_mesh_fn()
        leaves = jax.tree_util.tree_leaves(cache)
        return self._mesh_fn.lower(*leaves).compile().as_text()

    # -- persistent executor -------------------------------------------------
    def save(self, path: str, tree, *, extra: Optional[Dict] = None,
             check: bool = True) -> str:
        """Write ``tree`` to ``path`` as one SZ02 wire frame per routed leaf
        plus a plan-derived JSON manifest (docs/wire_format.md §9).

        Routes execute exactly as on the wire: 'splitzip' leaves become SZ02
        payloads (with their embedded Fletcher-32 integrity sections), fp32
        hi/lo leaves an SZ02 hi-half payload followed by the raw lo bytes,
        'fp8' leaves an SZ02 payload under the fp8 codebook, 'raw' leaves
        their exact bytes.  Atomicity rule: everything is written into a
        temp directory next to ``path`` and renamed into place, so a
        directory named ``path`` is either absent or complete.  Returns
        ``path``; per-call accounting in ``last_stats``."""
        if self.plan.mesh is not None:
            raise ValueError("save/load run on host files; build the plan "
                             "with mesh=None")
        if check:
            self._check_structure(tree)
        self._uid += 1
        plan, tc = self.plan, self.plan.tc
        wire_be = get_backend("wire")
        stats = TransferStats(chunk_wire_bytes=[], chunk_ok=[],
                              raw_passthrough_bytes=0.0,
                              n_elements=plan.stream_len)
        flat = jax.tree_util.tree_flatten_with_path(tree)[0]
        parent = os.path.dirname(os.path.abspath(path)) or "."
        os.makedirs(parent, exist_ok=True)
        tmp = tempfile.mkdtemp(dir=parent, prefix=".tmp_persist_")
        entries = []
        try:
            for i, ((_, leaf), r) in enumerate(zip(flat, plan.routes)):
                fname = f"leaf_{i:05d}.szc"
                payload, tail = b"", b""
                if r.route == "splitzip":
                    ct = wire_be.encode(leaf, tc.codebook, chunk=tc.chunk)
                    payload = ct.payload
                    stats.leaf_wire_bytes[r.key] = float(len(payload))
                    stats.leaf_ok[r.key] = True
                elif r.route == "fp32_hilo":
                    u = jax.lax.bitcast_convert_type(leaf, jnp.uint32)
                    hi = jax.lax.bitcast_convert_type(
                        (u >> 16).astype(jnp.uint16), jnp.bfloat16)
                    ct = wire_be.encode(hi, tc.codebook, chunk=tc.chunk)
                    payload = ct.payload
                    tail = np.asarray((u & 0xFFFF).astype(jnp.uint16)).tobytes()
                    stats.leaf_wire_bytes[r.key] = float(len(payload))
                    stats.leaf_ok[r.key] = True
                    stats.fp32_lo_wire_bytes += float(len(tail))
                elif r.route == "fp8":
                    ct = wire_be.encode(leaf, plan.fp8_codebook, chunk=tc.chunk)
                    payload = ct.payload
                    stats.fp8_wire_bytes += float(len(payload))
                    stats.leaf_ok[r.key] = True
                else:
                    tail = np.asarray(leaf).tobytes()
                    stats.raw_passthrough_bytes += float(len(tail))
                blob = payload + tail
                with open(os.path.join(tmp, fname), "wb") as f:
                    f.write(blob)
                entries.append({
                    "key": r.key, "file": fname, "route": r.route,
                    "shape": list(r.shape), "dtype": r.dtype,
                    "sz_bytes": len(payload),
                    "checksum": int(fletcher32(np.frombuffer(blob, np.uint8))),
                })
            manifest = {"format": PERSIST_FORMAT,
                        "codebook": {"fmt": tc.codebook.fmt,
                                     "exponents": list(tc.codebook.exponents)},
                        "extra": extra or {}, "leaves": entries}
            with open(os.path.join(tmp, PERSIST_MANIFEST), "w") as f:
                json.dump(manifest, f)
            if os.path.exists(path):
                shutil.rmtree(path)
            os.rename(tmp, path)
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        self.last_stats = stats
        self._account()
        return path

    def load(self, path: str) -> Tuple[object, Dict]:
        """Read a :meth:`save` directory back into the plan's pytree,
        bit-exactly.  Returns ``(tree, extra)``.

        Every leaf file is verified twice: Fletcher-32 over the file bytes
        against the manifest, then the SZ02 payload's own integrity-frame
        table during decode.  A mismatch (or an injected ``faults=`` frame
        fault) re-fetches the file down the plan's retry budget
        (``retry_doublings + 1`` re-reads, counted in
        ``last_stats.refetches``); persistent corruption raises
        :class:`~repro.core.wire.WireIntegrityError` — the caller
        (distributed/checkpoint.py) falls back to the previous step."""
        if self.plan.mesh is not None:
            raise ValueError("save/load run on host files; build the plan "
                             "with mesh=None")
        plan, tc = self.plan, self.plan.tc
        self._uid += 1
        with open(os.path.join(path, PERSIST_MANIFEST)) as f:
            manifest = json.load(f)
        entries = manifest["leaves"]
        if manifest.get("format") != PERSIST_FORMAT:
            raise ValueError(f"unknown persistent format "
                             f"{manifest.get('format')!r} at {path}")
        if len(entries) != len(plan.routes):
            raise ValueError(
                f"{path} holds {len(entries)} leaves; this plan expects "
                f"{len(plan.routes)} — rebuild the plan for the structure")
        wire_ver = get_backend("wire-verify")
        stats = TransferStats(chunk_wire_bytes=[], chunk_ok=[],
                              raw_passthrough_bytes=0.0,
                              n_elements=plan.stream_len)
        leaves = []
        for i, (r, meta) in enumerate(zip(plan.routes, entries)):
            if (meta["key"] != r.key or meta["route"] != r.route
                    or tuple(meta["shape"]) != r.shape
                    or meta["dtype"] != r.dtype):
                raise ValueError(
                    f"leaf {i} ({meta['key']!r}) does not match the plan "
                    f"route {r.key!r}; structure drifted since save")
            try:
                blob = self._read_verified(os.path.join(path, meta["file"]),
                                           meta, i, stats)
            except WireIntegrityError:
                # Publish the partial accounting (verify failures, re-fetch
                # bytes burned on the abandoned candidate) before bubbling up
                # to the fallback policy in distributed/checkpoint.py.
                stats.leaf_ok[r.key] = False
                self.last_stats = stats
                self._account()
                raise
            sz = meta["sz_bytes"]
            if r.route == "splitzip":
                ct = self._persist_comp(blob[:sz], r, tc.codebook.fmt,
                                        r.dtype)
                leaves.append(jnp.asarray(wire_ver.decode(ct)))
                stats.leaf_wire_bytes[r.key] = float(sz)
                stats.leaf_ok[r.key] = True
            elif r.route == "fp32_hilo":
                ct = self._persist_comp(blob[:sz], r, tc.codebook.fmt,
                                        "bfloat16")
                hi = jax.lax.bitcast_convert_type(
                    jnp.asarray(wire_ver.decode(ct)), jnp.uint16)
                lo = np.frombuffer(blob[sz:], np.uint16).reshape(r.shape)
                u = ((hi.astype(jnp.uint32) << 16)
                     | jnp.asarray(lo).astype(jnp.uint32))
                leaves.append(jax.lax.bitcast_convert_type(u, jnp.float32))
                stats.leaf_wire_bytes[r.key] = float(sz)
                stats.leaf_ok[r.key] = True
                stats.fp32_lo_wire_bytes += float(len(blob) - sz)
            elif r.route == "fp8":
                ct = self._persist_comp(blob[:sz], r, plan.fp8_codebook.fmt,
                                        r.dtype)
                leaves.append(jnp.asarray(wire_ver.decode(ct)))
                stats.fp8_wire_bytes += float(sz)
                stats.leaf_ok[r.key] = True
            else:
                arr = np.frombuffer(blob, dtype=jnp.dtype(r.dtype))
                leaves.append(jnp.asarray(arr.reshape(r.shape)))
                stats.raw_passthrough_bytes += float(len(blob))
        tree = jax.tree_util.tree_unflatten(plan.treedef, leaves)
        self.last_stats = stats
        self._account()
        return tree, manifest.get("extra", {})

    @staticmethod
    def _persist_comp(payload: bytes, r, fmt: str, dtype: str) -> WireCompressed:
        stats = WireStats(n_elements=r.n_elements, n_escapes=0,
                          payload_bytes=len(payload),
                          raw_bytes=int(r.raw_bytes))
        return WireCompressed(payload=payload, shape=r.shape, dtype=dtype,
                              fmt=fmt, stats=stats)

    def _read_verified(self, fpath: str, meta: Dict, ci: int,
                       stats: TransferStats) -> bytes:
        """One leaf file off disk, Fletcher-verified against the manifest,
        optionally through the session's :class:`FaultChannel` (so injected
        wire faults exercise the re-fetch path on CPU).  Re-reads follow the
        plan's capacity-schedule length — ``retry_doublings + 1`` re-fetches
        — then raise :class:`WireIntegrityError` with the leaf index."""
        budget = self.plan.tc.retry_doublings + 2
        for attempt in range(budget):
            with open(fpath, "rb") as f:
                blob = f.read()
            intact = True
            if self._channel is not None:
                frame = self._channel.ship(
                    jnp.asarray(np.frombuffer(blob, np.uint8)),
                    self._uid, ci, attempt)
                payload, intact = self._channel.deliver(frame)
                stats.fault_delay_s += frame.delay_s
                blob = (np.asarray(payload).tobytes()
                        if payload is not None else b"")
            if intact and fletcher32(np.frombuffer(blob, np.uint8)) == \
                    meta["checksum"]:
                return blob
            stats.verify_failures += 1
            if attempt + 1 < budget:
                stats.refetches += 1
                stats.refetch_wire_bytes += float(len(blob))
        raise WireIntegrityError((ci,))

    # -- collective executor (compressed ring all-reduce) --------------------
    def ring_reduce(self, stacked, *, axis: str = "pod", mean: bool = True,
                    ratio: Optional[float] = None, check: bool = True):
        """Rotating-ring compressed all-reduce over ``axis``: each
        participant's pod-partial contribution circles the ring as a
        compressed stream ((n_pod - 1) hops, decode + fp32 accumulate per
        hop), exactly grad_compress's exchange but planned, routed, and
        accounted here.  Input leaves carry a leading ``axis`` dimension
        (sharded ``P(axis)``); output leaves drop it and are replicated.

        In-graph execution cannot branch on escape overflow, so every hop
        also emits an ``ok`` flag; a leaf whose compressed hops overflowed
        anywhere on the ring is re-run on a raw (bit-pinned) ring — the one
        overflow story: detected off-graph, healed by the raw fallback,
        recorded in ``last_stats.leaf_ok``.  In-graph wire bytes live in
        the lowered HLO (``lower_hlo``); for host-side reports
        ``last_stats`` carries the plan's analytic estimate via
        :meth:`TransferPlan.collective_wire_bytes` — pass ``ratio`` (a
        calibrated profile's codec ratio) to price the compressed hops,
        else they're counted raw."""
        plan = self.plan
        if plan.mesh is None or axis not in plan.mesh.shape:
            raise ValueError(f"ring_reduce needs a mesh plan with a "
                             f"{axis!r} axis")
        if check:
            self._check_structure(stacked)
        self._uid += 1
        n_pod = plan.mesh.shape[axis]
        expected = n_pod * (n_pod - 1)      # ok hops per leaf, psum'd
        leaves = jax.tree_util.tree_leaves(stacked)
        fn = self._ring_fns.get(frozenset())
        if fn is None:
            fn = self._ring_fns.setdefault(
                frozenset(), self._build_ring_fn(axis, mean, frozenset()))
        out, oks = fn(*leaves)
        failed = frozenset(j for j, ok in enumerate(oks)
                           if int(ok) != expected)
        if failed:
            fb = self._ring_fns.get(failed)
            if fb is None:
                fb = self._ring_fns.setdefault(
                    failed, self._build_ring_fn(axis, mean, failed))
            out, _ = fb(*leaves)
        self.last_stats = self._ring_stats(axis, ratio, failed)
        self._account()
        return jax.tree_util.tree_unflatten(plan.treedef, out)

    def _ring_participant_routes(self, axis: str):
        """Per-participant routes: the plan was built over ``axis``-stacked
        leaves, so re-resolve on the stripped shapes (the per-hop payloads)
        — this is where ``tc.min_compress_elems`` bites."""
        if self._ring_routes is None:
            n = self.plan.mesh.shape[axis]
            local = []
            for r in self.plan.routes:
                if not r.shape or r.shape[0] % n:
                    raise ValueError(
                        f"ring_reduce leaf {r.key!r} has no leading "
                        f"{axis}-divisible dimension (shape {r.shape})")
                local.append(jax.ShapeDtypeStruct(
                    (r.shape[0] // n,) + r.shape[1:], jnp.dtype(r.dtype)))
            lp = TransferPlan.build(
                jax.tree_util.tree_unflatten(self.plan.treedef, local),
                self.plan.tc, granularity="tensor")
            self._ring_routes = lp.routes
        return self._ring_routes

    def _build_ring_fn(self, axis: str, mean: bool, force_raw: frozenset):
        from jax.sharding import PartitionSpec as P
        plan, tc = self.plan, self.plan.tc
        n_pod = plan.mesh.shape[axis]
        routes = self._ring_participant_routes(axis)
        for r in routes:
            if r.route == "fp32_hilo":
                raise ValueError(
                    "ring_reduce does not take the fp32 hi/lo route (build "
                    "the gradient plan with compress_fp32=False); fp32 "
                    "leaves ship raw, bit-pinned")
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
                        lambda s: jax.lax.ppermute(s, axis, perm), ct)
                    rotating = jnp.asarray(
                        plan.backend.decode(moved)).reshape(x.shape)
                else:
                    ok = ok + 1
                    w = _WIRE_INT.get(x.dtype.itemsize)
                    if jnp.issubdtype(x.dtype, jnp.floating) and w is not None:
                        y = jax.lax.ppermute(
                            jax.lax.bitcast_convert_type(rotating, w),
                            axis, perm)
                        rotating = jax.lax.bitcast_convert_type(y, x.dtype)
                    else:
                        rotating = jax.lax.ppermute(rotating, axis, perm)
                acc = acc + rotating.astype(jnp.float32)
            return acc, jax.lax.psum(ok, axis)

        def body(*leaves_flat):
            out, oks = [], []
            for j, (lf, r) in enumerate(zip(leaves_flat, routes)):
                x = lf[0]    # local slice of the stacked leaf, leading dim 1
                if r.route == "splitzip" and j not in force_raw:
                    total, ok = ring(x, tc.codebook, r.cap, True)
                elif r.route == "fp8" and j not in force_raw:
                    total, ok = ring(x, plan.fp8_codebook, r.cap, True)
                else:
                    total, ok = ring(x, None, 0, False)
                if mean:
                    total = total / n_pod
                out.append(total.astype(x.dtype))
                oks.append(ok)
            return tuple(out), tuple(oks)

        n_leaves = self.plan.treedef.num_leaves
        specs = lambda s: tuple(s for _ in range(n_leaves))
        return shard_map(body, mesh=plan.mesh,
                         in_specs=specs(P(axis)),
                         out_specs=(specs(P()), specs(P())),
                         check_vma=False)

    def _ring_stats(self, axis: str, ratio: Optional[float],
                    failed: frozenset = frozenset()) -> TransferStats:
        """Analytic per-call accounting for the collective executor (the
        traced HLO is the ground truth; this is the host-side estimate all
        consumers report through)."""
        n_pod = self.plan.mesh.shape[axis]
        hops = n_pod - 1
        routes = self._ring_participant_routes(axis)
        stats = TransferStats(chunk_wire_bytes=[], chunk_ok=[],
                              raw_passthrough_bytes=0.0,
                              n_elements=sum(r.n_elements for r in routes
                                             if r.route != "raw"))
        rho = ratio if ratio is not None else 1.0
        for j, r in enumerate(routes):
            if r.route == "raw":
                stats.raw_passthrough_bytes += r.raw_bytes * hops
            elif j in failed:
                # overflowed: the wasted compressed attempt shipped, then
                # the raw re-run (charged as a raw re-fetch)
                stats.leaf_wire_bytes[r.key] = r.raw_bytes / rho * hops
                stats.leaf_ok[r.key] = False
                stats.refetches += 1
                stats.raw_refetches += 1
                stats.refetch_wire_bytes += r.raw_bytes * hops
            elif r.route == "fp8":
                stats.fp8_wire_bytes += r.raw_bytes / rho * hops
                stats.leaf_ok[r.key] = True
            else:
                stats.leaf_wire_bytes[r.key] = r.raw_bytes / rho * hops
                stats.leaf_ok[r.key] = True
        return stats

    # -- reshard hop (elastic scaling) ---------------------------------------
    def reshard(self, tree, dst_shardings, *, check: bool = True,
                verify: Optional[bool] = None):
        """One elastic reshard hop: encode every routed leaf to splitzip
        streams, ship them through this session's wire (integrity framing
        and re-fetches included when the session carries ``verify=`` /
        ``faults=``), decode, and place the result on ``dst_shardings``
        (a pytree of shardings matching ``tree``; see
        ``distributed/elastic.reshard``).  Bit-exact end to end."""
        if self.plan.mesh is not None:
            raise ValueError(
                "reshard ships host-staged streams (the old mesh may not "
                "exist anymore); build the plan with mesh=None")
        self._set_verify(verify)
        self.send(tree, check=check)
        out = self.recv()
        if dst_shardings is not None:
            out = jax.device_put(out, dst_shardings)
        return out

    # -- internals -----------------------------------------------------------
    def _check_structure(self, cache) -> None:
        if not self.plan.matches(cache):
            raise ValueError(
                "cache structure does not match this TransferPlan; rebuild "
                "the plan for the new structure (TransferPlan.build)")

    def _account(self) -> None:
        self.calls += 1
        if self.last_stats is not None:
            if self._channel is not None:
                # per-call slice of the channel's running fault counter
                self.last_stats.faults_injected = (self._channel.injected
                                                   - self._injected_seen)
                self._injected_seen = self._channel.injected
            self.total_wire_bytes += self.last_stats.wire_bytes

    # -- local / tensor ------------------------------------------------------
    def _send_tensor(self, cache):
        stats = TransferStats(chunk_wire_bytes=[], chunk_ok=[],
                              raw_passthrough_bytes=0.0, n_elements=0)
        comp, raw = encode_leaves(self.plan, cache, scheduled=True,
                                  stats=stats)
        if self.retain_last:
            # pristine (pre-framing) payload: a decode-worker failover can
            # re-ship the exact encoded streams without re-running the codec
            self._retained = (comp, raw, cache)
        self.last_stats = stats
        if self._channel is None:
            return comp, raw, cache, None, None
        # frame every wire object; keep the pristine dicts sender-side so a
        # verified re-fetch can re-ship the exact same object
        comp_f = {k: self._channel.ship(v, self._uid, ci, 0)
                  for ci, (k, v) in enumerate(comp.items())}
        raw_f = {k: self._channel.ship(v, self._uid, len(comp) + ci, 0)
                 for ci, (k, v) in enumerate(raw.items())}
        return comp_f, raw_f, cache, comp, raw

    def _recv_tensor(self, payload):
        comp, raw, structure, pristine_comp, pristine_raw = payload
        if self._channel is not None:
            comp, raw = self._deliver_tensor(comp, raw, structure,
                                             pristine_comp, pristine_raw)
        return decode_leaves(comp, raw, structure,
                             backend=self.plan.tc.backend)

    def _deliver_tensor(self, comp_f, raw_f, structure, pristine_comp,
                        pristine_raw):
        """Unframe + verify every tensor-path entry.  A compressed entry
        whose re-ships exhaust the retry budget falls back to the whole
        ORIGINAL leaf shipped raw (mirroring the encode-overflow fallback);
        raw entries re-ship themselves until intact."""
        stats = self.last_stats
        leaves = {leaf_key(p): leaf for p, leaf in
                  jax.tree_util.tree_flatten_with_path(structure)[0]}
        comp: Dict[str, object] = {}
        raw: Dict[str, object] = {}
        ci = 0
        for key, frame in comp_f.items():
            base = key[:-3] if key.endswith("#hi") else key
            obj, fell_raw = self._deliver_entry(
                frame, ci, stats, resend=pristine_comp[key],
                raw_payload=leaves[base])
            if fell_raw:
                raw[base] = obj      # whole leaf ships raw; lo sidecar unused
            else:
                comp[key] = obj
            ci += 1
        for key, frame in raw_f.items():
            obj, _ = self._deliver_entry(frame, ci, stats,
                                         resend=pristine_raw[key],
                                         raw_payload=pristine_raw[key])
            raw.setdefault(key, obj)
            ci += 1
        return comp, raw

    def _deliver_entry(self, frame, ci: int, stats: TransferStats, *,
                       resend, raw_payload):
        """``(payload, used_raw_fallback)`` for one framed wire entry.

        Verified mode re-fetches on mismatch/drop: ``retry_doublings + 1``
        re-ships of the staged compressed object (each attempt re-keys the
        fault plan, so injected faults re-roll), then the raw payload as the
        terminal re-fetch — itself verified and retried, failing loud past
        ``_MAX_WIRE_ATTEMPTS``.  Unverified mode delivers whatever arrived
        (corruption flows through undetected — the hazard ``verify=``
        closes); only a full drop heals from the staged raw payload."""
        payload, intact = self._channel.deliver(frame)
        stats.fault_delay_s += frame.delay_s
        if not self.verify:
            if payload is None:      # dropped in flight: heal from the
                return raw_payload, True  # staged raw payload, raw-routed
            return payload, False
        is_raw = resend is raw_payload
        attempt = 1
        while not intact:
            stats.verify_failures += 1
            if attempt >= _MAX_WIRE_ATTEMPTS:
                raise TransferIntegrityError(
                    f"wire entry {ci}: integrity not established after "
                    f"{attempt} attempts (raw re-fetches included)")
            if attempt <= self.plan.tc.retry_doublings + 1:
                obj, is_raw = resend, resend is raw_payload
            else:
                obj, is_raw = raw_payload, True
            stats.refetches += 1
            stats.raw_refetches += int(is_raw)
            stats.refetch_wire_bytes += self._object_wire_bytes(obj, is_raw,
                                                             stats)
            frame = self._channel.ship(obj, self._uid, ci, attempt)
            payload, intact = self._channel.deliver(frame)
            stats.fault_delay_s += frame.delay_s
            attempt += 1
        return payload, is_raw

    def _object_wire_bytes(self, obj, is_raw: bool,
                           stats: TransferStats) -> float:
        if is_raw or isinstance(obj, (jax.Array, np.ndarray)):
            a = host_read(obj, "refetch", stats)
            return float(a.size * a.dtype.itemsize)
        return host_read(_backend_for(obj, self.plan.backend).wire_bytes(obj),
                         "wire_bytes", stats, float)

    # -- local / chunked -----------------------------------------------------
    def _encode_chunk(self, stream, i: int):
        """Encode segment ``i`` at base capacity (schedule step 0)."""
        seg = self.plan.segments[i]
        tc = self.plan.tc
        return self.plan.backend.encode(
            stream[seg.start:seg.stop], tc.codebook, chunk=tc.chunk,
            cap=seg.cap, layout=tc.layout)

    def _ship_chunk(self, stream, i: int, ct, stats: TransferStats):
        """The wire hop for chunk ``i``: walk the remaining capacity schedule
        on overflow, then raw fallback.  Returns the in-flight payload
        (compressed object, or None when the chunk ships its raw bits)."""
        plan, tc = self.plan, self.plan.tc
        seg = plan.segments[i]
        be = plan.backend
        ok = host_read(be.ok(ct), "ok", stats, bool)
        extra = 0
        if not ok:
            for rbe, layout, cap in plan.schedule_for(seg.n_elements,
                                                      seg.cap)[1:]:
                extra += 1
                ct2 = rbe.encode(stream[seg.start:seg.stop], tc.codebook,
                                 chunk=tc.chunk, cap=cap, layout=layout)
                if host_read(rbe.ok(ct2), "ok", stats, bool):
                    ct, ok = ct2, True
                    break
        stats.chunk_retried[i] = extra > 0
        stats.chunk_retry_steps[i] = extra
        stats.chunk_ok[i] = ok
        stats.chunk_wire_bytes[i] = (
            host_read(be.wire_bytes(ct), "wire_bytes", stats, float) if ok
            else seg.raw_bytes)
        return ct if ok else None

    def _decode_chunk(self, stream, i: int, payload):
        """Receiver side: straight to the shipped bit stream (``decode_bits``
        — the fused pallas decode emits these bits from its single kernel)."""
        seg = self.plan.segments[i]
        if payload is None:      # raw fallback: the original bits shipped
            return stream[seg.start:seg.stop]
        if isinstance(payload, (jax.Array, np.ndarray)):
            # explicit raw bits (fault-channel mode ships them for real)
            return jnp.asarray(payload).reshape(-1)
        be = _backend_for(payload, self.plan.backend)
        return jnp.asarray(be.decode_bits(payload)).reshape(-1)

    def _wire_hop(self, stream, i: int, ct, stats: TransferStats):
        """Chunk ``i``'s full send side: the capacity-schedule walk, then the
        checksum-framed channel when active.  Under a channel the raw
        fallback ships its EXPLICIT bits — the local-slice shortcut would
        make the wire hop unfalsifiable under fault injection."""
        p = self._ship_chunk(stream, i, ct, stats)
        if self._channel is None:
            return p
        seg = self.plan.segments[i]
        payload = p if p is not None else stream[seg.start:seg.stop]
        return self._channel.ship(payload, self._uid, i, 0)

    def _chunk_out(self, stream, i: int, p, stats: TransferStats):
        if self._channel is None:
            return self._decode_chunk(stream, i, p)
        return self._deliver_chunk(stream, i, p, stats)

    def _deliver_chunk(self, stream, i: int, frame, stats: TransferStats):
        """Receiver side of chunk ``i`` under an active channel.  Verified
        mode routes a mismatched/dropped frame through the REMAINING capacity
        schedule — re-encode at the next step, re-ship with the attempt
        re-keyed so injected faults re-roll — and past the schedule's end
        re-fetches the chunk's raw bits (also verified).  Never hands corrupt
        bytes to the decoder; fails loud past ``_MAX_WIRE_ATTEMPTS``."""
        seg = self.plan.segments[i]
        tc = self.plan.tc
        payload, intact = self._channel.deliver(frame)
        stats.fault_delay_s += frame.delay_s
        if not self.verify:
            # unverified: corruption flows through; a drop falls back to the
            # local-slice shortcut (visible only in channel.injected)
            return self._decode_chunk(stream, i, payload)
        sched = self.plan.schedule_for(seg.n_elements, seg.cap)
        attempt = 1
        while not intact:
            stats.verify_failures += 1
            if attempt >= _MAX_WIRE_ATTEMPTS:
                raise TransferIntegrityError(
                    f"chunk {i}: integrity not established after "
                    f"{attempt} attempts (raw re-fetches included)")
            if attempt < len(sched):
                be, layout, cap = sched[attempt]
                ct = be.encode(stream[seg.start:seg.stop], tc.codebook,
                               chunk=tc.chunk, cap=cap, layout=layout)
                if host_read(be.ok(ct), "ok", stats, bool):
                    obj, nbytes, is_raw = ct, host_read(
                        be.wire_bytes(ct), "wire_bytes", stats, float), False
                else:
                    obj, nbytes, is_raw = (stream[seg.start:seg.stop],
                                           seg.raw_bytes, True)
            else:
                obj, nbytes, is_raw = (stream[seg.start:seg.stop],
                                       seg.raw_bytes, True)
            stats.refetches += 1
            stats.raw_refetches += int(is_raw)
            stats.refetch_wire_bytes += nbytes
            frame = self._channel.ship(obj, self._uid, i, attempt)
            payload, intact = self._channel.deliver(frame)
            stats.fault_delay_s += frame.delay_s
            attempt += 1
        return self._decode_chunk(stream, i, payload)

    def _chunked_sidecars(self, cache, stats: TransferStats):
        """Everything outside the pipelined stream: fold the stream, encode
        fp8 sidecar leaves, count lo halves + raw passthrough."""
        plan = self.plan
        stream, lo, fp8, raw = plan.fold_stream(cache)
        fp8_payload: Dict[str, object] = {}
        for r in plan.routes:
            if r.route == "fp32_hilo":
                stats.fp32_lo_wire_bytes += 2.0 * r.n_elements
            elif r.route == "fp8":
                ct, ok, extra = _encode_scheduled(
                    plan, fp8[r.key], plan.fp8_codebook, r.n_elements, r.cap,
                    scheduled=True, key=r.key, stats=stats)
                _record_unit(stats, r.key, bool(ok), extra)
                stats.fp8_wire_bytes += (
                    host_read(plan.backend.wire_bytes(ct), "wire_bytes",
                              stats, float) if ok else r.raw_bytes)
                fp8_payload[r.key] = ct if ok else fp8[r.key]
            elif r.route == "raw":
                stats.raw_passthrough_bytes += r.raw_bytes
        return stream, lo, fp8_payload, raw

    def _new_chunked_stats(self) -> TransferStats:
        n = self.plan.n_chunks
        return TransferStats(
            chunk_wire_bytes=[0.0] * n, chunk_ok=[True] * n,
            raw_passthrough_bytes=0.0, n_elements=self.plan.stream_len,
            chunk_retried=[False] * n, chunk_retry_steps=[0] * n)

    def _ship_sidecars(self, lo, fp8_payload, raw):
        """Frame the non-pipelined wire objects (lo halves, fp8 sidecars,
        raw passthrough).  Chunk-index keying continues past the pipeline
        chunks so every fault coordinate stays unique within the transfer."""
        framed = {}
        ci = self.plan.n_chunks
        for name, d in (("lo", lo), ("fp8", fp8_payload), ("raw", raw)):
            framed[name] = {k: self._channel.ship(v, self._uid, ci + j, 0)
                            for j, (k, v) in enumerate(d.items())}
            ci += len(d)
        return framed["lo"], framed["fp8"], framed["raw"]

    def _deliver_sidecars(self, lo_f, fp8_f, raw_f, pristine, stats):
        """Unframe + verify the sidecars; a faulted sidecar re-ships its
        pristine object (it IS the terminal payload — no cheaper encoding
        below it) until intact."""
        out = []
        ci = self.plan.n_chunks
        for frames, orig in zip((lo_f, fp8_f, raw_f), pristine):
            d = {}
            for j, (k, frame) in enumerate(frames.items()):
                d[k], _ = self._deliver_entry(frame, ci + j, stats,
                                              resend=orig[k],
                                              raw_payload=orig[k])
            out.append(d)
            ci += len(frames)
        return out

    def _send_chunked(self, cache):
        stats = self._new_chunked_stats()
        stream, lo, fp8_payload, raw = self._chunked_sidecars(cache, stats)
        in_flight = [self._wire_hop(stream, i, self._encode_chunk(stream, i),
                                    stats)
                     for i in range(self.plan.n_chunks)]
        self.last_stats = stats
        if self._channel is None:
            return stream, in_flight, lo, fp8_payload, raw, None
        pristine = (lo, fp8_payload, raw)
        lo_f, fp8_f, raw_f = self._ship_sidecars(lo, fp8_payload, raw)
        return stream, in_flight, lo_f, fp8_f, raw_f, pristine

    def _recv_chunked(self, payload):
        stream, in_flight, lo, fp8_payload, raw, pristine = payload
        stats = self.last_stats
        decoded = [self._chunk_out(stream, i, p, stats)
                   for i, p in enumerate(in_flight)]
        if self._channel is not None:
            lo, fp8_payload, raw = self._deliver_sidecars(
                lo, fp8_payload, raw, pristine, stats)
        return self._reassemble(decoded, lo, fp8_payload, raw)

    def _reassemble(self, decoded_bits: List[jax.Array], lo, fp8_payload, raw):
        plan = self.plan
        bits_out = (jnp.concatenate(decoded_bits) if len(decoded_bits) > 1
                    else decoded_bits[0])
        fp8_dec = {}
        for r in plan.routes:
            if r.route == "fp8":
                p = fp8_payload[r.key]
                if isinstance(p, jax.Array):   # raw fallback leaf
                    fp8_dec[r.key] = p
                else:
                    fp8_dec[r.key] = _backend_for(p, plan.backend).decode(p)
        return plan.unfold_stream(bits_out, lo, fp8_dec, raw)

    def _transfer_chunked_interleaved(self, cache):
        """The fused chunked path on the explicit overlap schedule: at step t
        encode chunk t, ship chunk t-1, decode chunk t-2."""
        stats = self._new_chunked_stats()
        stream, lo, fp8_payload, raw = self._chunked_sidecars(cache, stats)
        n = self.plan.n_chunks
        encoded: Dict[int, object] = {}
        in_flight: Dict[int, object] = {}
        decoded: Dict[int, jax.Array] = {}
        for enc_i, xfer_i, dec_i in ChunkSchedule(n).stages():
            if 0 <= enc_i < n:
                encoded[enc_i] = self._encode_chunk(stream, enc_i)
            if 0 <= xfer_i < n:
                in_flight[xfer_i] = self._wire_hop(
                    stream, xfer_i, encoded.pop(xfer_i), stats)
            if 0 <= dec_i < n:
                decoded[dec_i] = self._chunk_out(
                    stream, dec_i, in_flight.pop(dec_i), stats)
        if self._channel is not None:
            lo_f, fp8_f, raw_f = self._ship_sidecars(lo, fp8_payload, raw)
            lo, fp8_payload, raw = self._deliver_sidecars(
                lo_f, fp8_f, raw_f, (lo, fp8_payload, raw), stats)
        self.last_stats = stats
        return self._reassemble([decoded[i] for i in range(n)], lo,
                                fp8_payload, raw)

    # -- mesh ----------------------------------------------------------------
    def _build_mesh_fn(self):
        plan = self.plan
        tc = plan.tc
        treedef = plan.treedef

        def body(*leaves_flat):
            local = jax.tree_util.tree_unflatten(treedef, leaves_flat)
            # a plan over the LOCAL shard structure: shapes inside shard_map
            # are the per-shard views, so segmentation/routing re-resolves
            # here (trace-time only — once per compilation, not per call)
            lp = TransferPlan.build(local, tc, granularity=plan.granularity)
            perm = lambda x: _permute_leaf(x, "pod", plan.src_pod,
                                           plan.dst_pod)
            if lp.granularity == "chunked":
                out = self._mesh_chunked_body(lp, local, perm)
            else:
                comp, raw = encode_leaves(lp, local, scheduled=False)
                moved_comp = jax.tree.map(perm, comp)
                moved_raw = jax.tree.map(perm, raw)
                out = decode_leaves(moved_comp, moved_raw, local,
                                    backend=tc.backend)
            # fresh leading 'pod' axis: index dst_pod holds the decoded
            # cache, index src_pod whatever the non-receiving pod decodes
            # from its zero-filled streams
            return tuple(x[None] for x in jax.tree_util.tree_leaves(out))

        from jax.sharding import PartitionSpec as P
        out_specs = tuple(P("pod", *s) for s in plan.in_specs)
        # jitted, so every call after the first reuses the executable
        return jax.jit(shard_map(body, mesh=plan.mesh, in_specs=plan.in_specs,
                                 out_specs=out_specs, check_vma=False))

    def _mesh_chunked_body(self, lp: TransferPlan, local, perm):
        """Per-chunk collective with double-buffering: at any schedule step
        at most two chunks are live between stages (t-1 permuting, t-2
        decoding) while chunk t encodes."""
        tc = lp.tc
        be = lp.backend
        stream, lo, fp8, raw = lp.fold_stream(local)
        n = lp.n_chunks
        encoded: Dict[int, object] = {}
        in_flight: Dict[int, object] = {}
        decoded: Dict[int, jax.Array] = {}
        for enc_i, xfer_i, dec_i in ChunkSchedule(n).stages():
            if 0 <= enc_i < n:
                seg = lp.segments[enc_i]
                encoded[enc_i] = be.encode(
                    stream[seg.start:seg.stop], tc.codebook,
                    chunk=tc.chunk, cap=seg.cap, layout=tc.layout)
            if 0 <= xfer_i < n:
                in_flight[xfer_i] = jax.tree.map(perm, encoded.pop(xfer_i))
            if 0 <= dec_i < n:
                decoded[dec_i] = jnp.asarray(
                    be.decode_bits(in_flight.pop(dec_i))).reshape(-1)
        bits_out = (jnp.concatenate([decoded[i] for i in range(n)])
                    if n > 1 else decoded[0] if n else
                    jnp.zeros((0,), jnp.uint16))
        fp8_dec = {}
        for r in lp.routes:
            if r.route == "fp8":
                ct = be.encode(fp8[r.key], lp.fp8_codebook, chunk=tc.chunk,
                               cap=r.cap, layout=tc.layout)
                fp8_dec[r.key] = be.decode(jax.tree.map(perm, ct))
        lo_m = {k: perm(v) for k, v in lo.items()}
        raw_m = {k: perm(v) for k, v in raw.items()}
        return lp.unfold_stream(bits_out, lo_m, fp8_dec, raw_m)

    def _run_mesh(self, cache, select_dst: bool = True):
        plan = self.plan
        if self._mesh_fn is None:
            self._mesh_fn = self._build_mesh_fn()
        leaves = jax.tree_util.tree_leaves(cache)
        moved = self._mesh_fn(*leaves)
        self.last_stats = None   # mesh wire bytes live in the HLO (roofline)
        if select_dst:
            # convenience view for eager callers (tests/examples).  Inside a
            # jit this slice forces GSPMD to bounce the DECODED cache back
            # across the pod axis — production consumers keep the cache
            # pod-resident: select_dst=False and read index dst_pod locally.
            moved = tuple(x[plan.dst_pod] for x in moved)
        return jax.tree_util.tree_unflatten(plan.treedef, moved)
