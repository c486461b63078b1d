"""Checkpoint save/restore over the bulk-data plane's persistent format.

Layout: one directory per step, one ``.szc`` SZ02 wire frame per pytree
leaf plus the plan-derived JSON manifest (normative format in
docs/wire_format.md §9).  :func:`save_tree` / :func:`load_tree` execute a
:class:`~repro.serving.plan.TransferPlan` against that directory: the
plan's routes pick each leaf's encoding, loads verify Fletcher-32 per file
and the payload's own integrity frames, and every call is accounted as
:class:`~repro.serving.plan.TransferStats`.  :class:`Checkpointer` adds the
step-directory convention and the corruption-fallback policy: integrity
failures (:class:`~repro.core.wire.WireIntegrityError` after the plan's
re-fetch budget, truncated directories, structure drift) fall back to the
previous checkpoint.  Codec calls go through the backend registry and
integrity through Fletcher-32, never a side hash (CI-grep-guarded).
"""

from __future__ import annotations

import json
import os
import shutil
import tempfile
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backend import WireCompressed, get_backend
from repro.core.codebook import Codebook
from repro.core.wire import WireIntegrityError, WireStats, fletcher32
from repro.serving.faults import FaultChannel, resolve_faults
from repro.serving.plan import TransferConfig, TransferPlan, TransferStats

# checkpoint codec codebook: calibrated once on model-weight statistics;
# weights/optimizer bf16 state shares the activation exponent concentration.
CKPT_CODEBOOK = Codebook(fmt="bf16", exponents=tuple(range(113, 129)))

# the persistent directory's manifest name and format tag (§9)
MANIFEST = "manifest.json"
FORMAT = "szpersist-1"


class CheckpointCorrupt(RuntimeError):
    pass


def _step_dir(directory: str, step: int) -> str:
    return os.path.join(directory, f"step_{step:010d}")


def _new_stats(plan: TransferPlan) -> TransferStats:
    return TransferStats(chunk_wire_bytes=[], chunk_ok=[],
                         raw_passthrough_bytes=0.0,
                         n_elements=plan.stream_len)


# ---------------------------------------------------------------------------
# the persistent executor
# ---------------------------------------------------------------------------

def save_tree(path: str, tree, plan: TransferPlan, *,
              extra: Optional[Dict] = None) -> TransferStats:
    """Write ``tree`` to ``path`` as one SZ02 wire frame per routed leaf
    plus a plan-derived JSON manifest (docs/wire_format.md §9).

    Routes execute exactly as on the wire: 'splitzip' leaves become SZ02
    payloads (with their embedded Fletcher-32 integrity sections), fp32
    hi/lo leaves an SZ02 hi-half payload followed by the raw lo bytes,
    'fp8' leaves an SZ02 payload under the fp8 codebook, 'raw' leaves
    their exact bytes.  Atomicity rule: everything is written into a
    temp directory next to ``path`` and renamed into place, so a
    directory named ``path`` is either absent or complete.  Returns the
    call's accounting."""
    if not plan.matches(tree):
        raise ValueError(
            "tree structure does not match this TransferPlan; rebuild "
            "the plan for the new structure (TransferPlan.build)")
    tc = plan.tc
    wire_be = get_backend("wire")
    stats = _new_stats(plan)
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
        manifest = {"format": FORMAT,
                    "codebook": {"fmt": tc.codebook.fmt,
                                 "exponents": list(tc.codebook.exponents)},
                    "extra": extra or {}, "leaves": entries}
        with open(os.path.join(tmp, MANIFEST), "w") as f:
            json.dump(manifest, f)
        if os.path.exists(path):
            shutil.rmtree(path)
        os.rename(tmp, path)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    return stats


def load_tree(path: str, plan: TransferPlan, *,
              stats: Optional[TransferStats] = None, faults=None,
              uid: int = 0) -> Tuple[Any, Dict, TransferStats]:
    """Read a :func:`save_tree` directory back into the plan's pytree,
    bit-exactly.  Returns ``(tree, extra, stats)``.

    Every leaf file is verified twice: Fletcher-32 over the file bytes
    against the manifest, then the SZ02 payload's own integrity-frame
    table during decode.  A mismatch re-fetches the file down the plan's
    retry budget (``retry_doublings + 1`` re-reads, counted in
    ``stats.refetches``); persistent corruption raises
    :class:`~repro.core.wire.WireIntegrityError`.  ``faults`` (``None |
    registry name | FaultPlan``) reads every file through a checksum-framed
    :class:`~repro.serving.faults.FaultChannel` whose faults are keyed by
    ``uid``, so the re-fetch path is testable on CPU.  Pass ``stats`` to
    keep the accounting of a load that raises: it is filled in place."""
    tc = plan.tc
    stats = _new_stats(plan) if stats is None else stats
    fault_plan = resolve_faults(faults)
    channel = (None if fault_plan is None else FaultChannel(
        lambda a: fletcher32(np.asarray(a).tobytes()), fault_plan))
    try:
        with open(os.path.join(path, MANIFEST)) as f:
            manifest = json.load(f)
        entries = manifest["leaves"]
        if manifest.get("format") != FORMAT:
            raise ValueError(f"unknown persistent format "
                             f"{manifest.get('format')!r} at {path}")
        if len(entries) != len(plan.routes):
            raise ValueError(
                f"{path} holds {len(entries)} leaves; this plan expects "
                f"{len(plan.routes)} — rebuild the plan for the structure")
        wire_ver = get_backend("wire-verify")
        leaves = []
        for i, (r, meta) in enumerate(zip(plan.routes, entries)):
            if (meta["key"] != r.key or meta["route"] != r.route
                    or tuple(meta["shape"]) != r.shape
                    or meta["dtype"] != r.dtype):
                raise ValueError(
                    f"leaf {i} ({meta['key']!r}) does not match the plan "
                    f"route {r.key!r}; structure drifted since save")
            try:
                blob = _read_verified(os.path.join(path, meta["file"]),
                                      meta, i, plan, stats, channel, uid)
            except WireIntegrityError:
                stats.leaf_ok[r.key] = False
                raise
            sz = meta["sz_bytes"]
            if r.route == "splitzip":
                ct = _persist_comp(blob[:sz], r, tc.codebook.fmt, r.dtype)
                leaves.append(jnp.asarray(wire_ver.decode(ct)))
                stats.leaf_wire_bytes[r.key] = float(sz)
                stats.leaf_ok[r.key] = True
            elif r.route == "fp32_hilo":
                ct = _persist_comp(blob[:sz], r, tc.codebook.fmt, "bfloat16")
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
                ct = _persist_comp(blob[:sz], r, plan.fp8_codebook.fmt,
                                   r.dtype)
                leaves.append(jnp.asarray(wire_ver.decode(ct)))
                stats.fp8_wire_bytes += float(sz)
                stats.leaf_ok[r.key] = True
            else:
                arr = np.frombuffer(blob, dtype=jnp.dtype(r.dtype))
                leaves.append(jnp.asarray(arr.reshape(r.shape)))
                stats.raw_passthrough_bytes += float(len(blob))
    finally:
        if channel is not None:
            stats.faults_injected = channel.injected
    tree = jax.tree_util.tree_unflatten(plan.treedef, leaves)
    return tree, manifest.get("extra", {}), stats


def _persist_comp(payload: bytes, r, fmt: str, dtype: str) -> WireCompressed:
    stats = WireStats(n_elements=r.n_elements, n_escapes=0,
                      payload_bytes=len(payload), raw_bytes=int(r.raw_bytes))
    return WireCompressed(payload=payload, shape=r.shape, dtype=dtype,
                          fmt=fmt, stats=stats)


def _read_verified(fpath: str, meta: Dict, ci: int, plan: TransferPlan,
                   stats: TransferStats, channel: Optional[FaultChannel],
                   uid: int) -> bytes:
    """One leaf file off disk, Fletcher-verified against the manifest,
    optionally through ``channel`` (so injected wire faults exercise the
    re-fetch path on CPU).  Re-reads follow the plan's capacity-schedule
    length — ``retry_doublings + 1`` re-fetches — then raise
    :class:`WireIntegrityError` with the leaf index."""
    budget = plan.tc.retry_doublings + 2
    for attempt in range(budget):
        with open(fpath, "rb") as f:
            blob = f.read()
        intact = True
        if channel is not None:
            frame = channel.ship(jnp.asarray(np.frombuffer(blob, np.uint8)),
                                 uid, ci, attempt)
            payload, intact = channel.deliver(frame)
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


# ---------------------------------------------------------------------------
# the checkpoint manager
# ---------------------------------------------------------------------------

class Checkpointer:
    """Plan-backed checkpoint manager.

    One :class:`TransferPlan` per state structure (cached across calls — the
    plan is a property of the model, not of the step), executed by
    :func:`save_tree` / :func:`load_tree`.  ``faults=`` threads into every
    load, so recovery drills run the same re-fetch machinery production
    would.  ``stats`` aggregates
    :class:`TransferStats` across every save/restore this manager ran
    (``refetches`` and ``refetch_wire_bytes`` accumulate even for candidate
    steps that were ultimately abandoned)."""

    def __init__(self, directory: str, *, codebook: Codebook = CKPT_CODEBOOK,
                 compress_fp32: bool = True, faults=None):
        self.directory = directory
        self.tc = TransferConfig(codebook=codebook, backend="wire",
                                 compress_fp32=compress_fp32)
        self.faults = faults
        self._uid = 0         # per-call id, saves included (fault keying)
        self._plans: Dict[Any, TransferPlan] = {}
        self.stats = TransferStats(chunk_wire_bytes=[], chunk_ok=[],
                                   raw_passthrough_bytes=0.0, n_elements=0)

    def _plan(self, tree) -> TransferPlan:
        flat, treedef = jax.tree_util.tree_flatten(tree)
        key = (treedef, tuple((tuple(x.shape), str(x.dtype)) for x in flat))
        plan = self._plans.get(key)
        if plan is None:
            plan = self._plans[key] = TransferPlan.build(tree, self.tc)
        return plan

    def _merge(self, s: TransferStats) -> None:
        agg = self.stats
        agg.raw_passthrough_bytes += s.raw_passthrough_bytes
        agg.fp32_lo_wire_bytes += s.fp32_lo_wire_bytes
        agg.fp8_wire_bytes += s.fp8_wire_bytes
        agg.verify_failures += s.verify_failures
        agg.refetches += s.refetches
        agg.raw_refetches += s.raw_refetches
        agg.refetch_wire_bytes += s.refetch_wire_bytes
        agg.faults_injected += s.faults_injected
        agg.fault_delay_s += s.fault_delay_s
        agg.n_elements = s.n_elements
        agg.leaf_wire_bytes.update(s.leaf_wire_bytes)
        agg.leaf_ok.update(s.leaf_ok)

    def save(self, step: int, tree, extra: Optional[Dict] = None) -> str:
        """Atomically write the checkpoint for ``step``; returns its path."""
        self._uid += 1
        path = _step_dir(self.directory, step)
        self._merge(save_tree(path, tree, self._plan(tree), extra=extra))
        return path

    def restore(self, tree_like, step: Optional[int] = None
                ) -> Tuple[Any, Dict, int]:
        """Load ``step`` (default latest), bit-exactly; on corruption —
        persistent integrity failure past the plan's re-fetch budget,
        missing files, structure drift — fall back to the previous
        checkpoint.  Returns ``(tree, extra, step_loaded)``."""
        steps = steps_available(self.directory)
        if not steps:
            raise FileNotFoundError(f"no checkpoints under {self.directory}")
        plan = self._plan(tree_like)
        candidates = [s for s in steps if step is None or s == step]
        for s in reversed(candidates):
            self._uid += 1
            stats = _new_stats(plan)
            try:
                tree, extra, _ = load_tree(_step_dir(self.directory, s), plan,
                                           stats=stats, faults=self.faults,
                                           uid=self._uid)
                return tree, extra, s
            except (WireIntegrityError, OSError, KeyError, ValueError):
                continue
            finally:
                self._merge(stats)
        raise CheckpointCorrupt(
            f"all candidate checkpoints corrupt in {self.directory}")


# -- module-level convenience API (one-shot managers) ------------------------

def save(directory: str, step: int, tree, extra: Optional[Dict] = None,
         codebook: Codebook = CKPT_CODEBOOK) -> str:
    """Atomically write checkpoint for ``step``; returns the final path."""
    return Checkpointer(directory, codebook=codebook).save(step, tree, extra)


def restore(directory: str, tree_like, step: Optional[int] = None
            ) -> Tuple[Any, Dict, int]:
    """Load ``step`` (default latest); on corruption, fall back to the
    previous checkpoint (fault-tolerance requirement).  Returns
    (tree, extra, step_loaded)."""
    return Checkpointer(directory).restore(tree_like, step)


def steps_available(directory: str) -> list:
    if not os.path.isdir(directory):
        return []
    out = []
    for d in os.listdir(directory):
        if d.startswith("step_"):
            out.append(int(d.split("_")[1]))
    return sorted(out)


def latest_step(directory: str) -> Optional[int]:
    steps = steps_available(directory)
    return steps[-1] if steps else None


def checkpoint_bytes(directory: str, step: int) -> int:
    path = _step_dir(directory, step)
    return sum(os.path.getsize(os.path.join(path, f)) for f in os.listdir(path))
