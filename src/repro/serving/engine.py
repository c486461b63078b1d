"""Disaggregated serving engine: prefill worker -> SplitZip transfer -> decode
worker, as one orchestrated pipeline.

Two operating modes:

* **local** (tests, examples, CPU): both workers in-process; the transfer is a
  real compress -> (simulated wire) -> decompress roundtrip through the
  in-graph codec, so bit-exactness of the whole serving path is checked
  end-to-end (paper Table 9).
* **mesh** (dry-run, TPU): the transfer runs a mesh-targeted ``TransferPlan``
  (shard_map + per-chunk ppermute over the pod axis); prefill/decode are
  pjit'd with the sharding policy.

The transfer stage is the plan/execute API: the engine builds ONE
:class:`~repro.serving.plan.TransferPlan` per cache structure (per-leaf codec
routes, chunk segmentation, capacity schedule resolved once) and executes it
through a cached :class:`~repro.serving.session.TransferSession` on every
``transfer`` call.  ``n_chunks == 1`` runs the whole-tensor granularity,
``n_chunks > 1`` the chunked pipelined engine; both are bit-exact by
construction, and per-chunk wire bytes / capacity-schedule retry steps land
in ``EngineStats``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.codebook import Codebook
from repro.core.pipeline import CodecProfile
from repro.core.spans import host_read, span
from repro.models import model as M
from repro.models.kvcache import DecodeState, cache_bytes
from repro.serving import transfer as T
from repro.serving.plan import TransferPlan
from repro.serving.session import TransferSession
from repro.serving.decode import make_decode_fn
from repro.serving.prefill import make_prefill_fn


@dataclasses.dataclass
class EngineStats:
    raw_cache_bytes: float = 0.0
    wire_bytes: float = 0.0
    prefill_calls: int = 0
    decode_tokens: int = 0
    codec_ok: bool = True
    # per-chunk wire bytes, one entry per pipeline chunk per transfer call
    # (chunked mode only; the whole-tensor path leaves this empty)
    chunk_wire_bytes: List[float] = dataclasses.field(default_factory=list)
    # units (chunks/tensors) re-encoded on the geometric capacity schedule
    chunk_retries: int = 0
    # total extra encode attempts across the schedule (cap -> 2cap -> 4cap ->
    # layout='global'); > chunk_retries when a unit needed several steps
    chunk_retry_steps: int = 0
    # fp32 hi/lo route: raw lo mantissa halves shipped alongside the stream
    fp32_lo_wire_bytes: float = 0.0
    # encoded units (chunks + leaves) that went down the capacity schedule —
    # the denominator for the observed overflow probability
    encoded_units: int = 0
    # per-prompt-length overflow observations: cache_len -> [units, retried].
    # DisaggregatedEngine.overflow_priors() buckets these into the
    # scheduler's per-bucket overflow_p priors
    overflow_obs: Dict[int, List[int]] = dataclasses.field(default_factory=dict)
    # wire-integrity path (verify=True / faults= engines): checksum
    # mismatches seen, re-fetches issued, and re-fetches that shipped raw
    verify_failures: int = 0
    refetches: int = 0
    raw_refetches: int = 0
    faults_injected: int = 0
    # compressed-resident KV (resident="compressed"): batches admitted into
    # the paged pool without rehydration, batches demoted to raw residency
    # (unsupported stream/family, escape overflow, pool exhaustion), and the
    # pool's HBM footprint vs what the same cache costs raw-resident
    resident_admits: int = 0
    resident_demotions: int = 0
    resident_hbm_bytes: float = 0.0
    resident_raw_bytes: float = 0.0
    # failover plane: retained-payload re-sends issued after a decode-worker
    # death (retain_for_failover=True engines)
    failover_resends: int = 0
    # prefix-delta transfer: raw bytes the destination already held and the
    # wire therefore never carried (excluded from wire_bytes by construction)
    prefix_hit_bytes: float = 0.0
    # host reads (``core.spans.host_read``): device-to-host reads made by the
    # transfer (the session's per-unit ``ok`` and wire-byte reads, the
    # cache-length read, the pool's admission reads) over ``transfer_calls``;
    # and by the resident decode loop's tail flushes over ``resident_steps``,
    # of which ``resident_page_flushes`` mapped pages (one jitted program and
    # one ``ok`` read each)
    transfer_calls: int = 0
    transfer_host_reads: int = 0
    resident_steps: int = 0
    resident_host_reads: int = 0
    resident_page_flushes: int = 0
    # mixture-of-experts routing (``model.moe_counts``), carried on the device
    # in the prefill's and the raw decode's states and read when ``stats`` is
    # read: (token, held expert) pairs computed and held experts with a pair,
    # summed over MoE layers, token blocks and steps; the decode part of each
    # again on its own. The compressed-resident loop does not count.
    moe_pairs: int = 0
    moe_experts_touched: int = 0
    moe_decode_pairs: int = 0
    moe_decode_experts_touched: int = 0

    @property
    def resident_ratio(self) -> float:
        """raw-resident / compressed-resident HBM bytes — the decode-worker
        capacity multiplier (fig6)."""
        return self.resident_raw_bytes / max(self.resident_hbm_bytes, 1.0)

    @property
    def transfer_ratio(self) -> float:
        return self.raw_cache_bytes / max(self.wire_bytes, 1.0)

    @property
    def observed_overflow_p(self) -> float:
        """Fraction of encoded units whose FIRST attempt overflowed — the
        maximum-likelihood estimate of the per-attempt overflow probability
        the scheduler's capacity-schedule expectation model takes."""
        if self.encoded_units <= 0:
            return 0.0
        return self.chunk_retries / self.encoded_units


class DisaggregatedEngine:
    """Local-mode PD engine with a real compressed transfer stage."""

    def __init__(self, cfg: ArchConfig, params, codebook: Codebook,
                 *, compress: bool = True, chunk: int = 1024, cap: int = 64,
                 backend: str = "xla", n_chunks: int = 1,
                 compress_fp32: bool = False,
                 profile: Optional[CodecProfile] = None,
                 verify: bool = False, faults=None,
                 resident: str = "raw", page_bytes: Optional[int] = None,
                 retain_for_failover: bool = False,
                 prefix_cache_bytes: Optional[float] = None):
        if resident not in ("raw", "compressed"):
            raise ValueError(f"resident={resident!r}: expected 'raw' or "
                             "'compressed'")
        if resident == "compressed":
            # the pool consumes page-addressable in-graph streams: whole
            # tensors (chunked transfer re-segments leaves) from a jittable
            # backend, with compression actually on
            if n_chunks != 1:
                raise ValueError("resident='compressed' requires n_chunks=1 "
                                 "(chunked streams are not page-addressable)")
            if not compress:
                raise ValueError("resident='compressed' requires compress=True")
        if retain_for_failover and n_chunks != 1:
            raise ValueError("retain_for_failover requires n_chunks=1 (only "
                             "tensor-path payloads are retained)")
        if prefix_cache_bytes is not None:
            if n_chunks <= 1:
                raise ValueError("prefix_cache_bytes requires n_chunks > 1 "
                                 "(delta granularity is the chunked "
                                 "segmentation)")
            if not compress:
                raise ValueError("prefix_cache_bytes requires compress=True")
        self.cfg = cfg
        self.params = params
        self.tc = T.TransferConfig(codebook=codebook, chunk=chunk, cap=cap,
                                   enabled=compress, backend=backend,
                                   n_chunks=n_chunks,
                                   compress_fp32=compress_fp32)
        self.profile = profile
        # wire-integrity knobs, passed through to every TransferSession:
        # verify=True checksum-verifies each wire hop (re-fetch on failure),
        # faults injects a seeded FaultPlan (repro.serving.faults)
        self.verify = verify
        self.faults = faults
        self.resident = resident
        self.page_bytes = page_bytes
        self.retain_for_failover = retain_for_failover
        self.prefix_cache_bytes = prefix_cache_bytes
        self._stats = EngineStats()
        self._moe_pending: List[Tuple[str, dict]] = []   # (stage, counts)
        self._session: Optional[TransferSession] = None
        self._pool = None   # KVPool of the last admitted batch
        self._decode_calls = 0
        # jitted prefill/decode programs, one per (stage, static size): a
        # repeated call reuses its executable instead of re-tracing
        self._programs: Dict[Tuple[str, Optional[int]], object] = {}

    @property
    def stats(self) -> EngineStats:
        """The engine's counters. Routing counts still on the device are
        read here, in one transfer for every call since the last read, so a
        stage never waits on them."""
        for stage, counts in jax.device_get(self._moe_pending):
            for name, v in counts.items():
                names = (name, name.replace("moe_", "moe_decode_"))
                for n in names[:2 if stage == "decode" else 1]:
                    setattr(self._stats, n, getattr(self._stats, n) + int(v))
        self._moe_pending = []
        return self._stats

    def _program(self, stage: str, size: Optional[int]):
        key = (stage, size)
        if key not in self._programs:
            make = make_prefill_fn if stage == "prefill" else make_decode_fn
            self._programs[key] = make(self.cfg, size)
        return self._programs[key]

    # -- plan/session caching ------------------------------------------------
    def _session_for(self, cache) -> TransferSession:
        """Build the TransferPlan once per cache structure; reuse its session
        for every subsequent transfer (compile-once / run-many).  One
        ``plan.matches`` walk per call doubles as the session's structure
        validation (the transfer below passes ``check=False``)."""
        if self._session is None or not self._session.plan.matches(cache):
            self._session = TransferPlan.build(cache, self.tc).session(
                verify=self.verify, faults=self.faults,
                retain_last=self.retain_for_failover)
            if self.prefix_cache_bytes is not None:
                self._session.enable_prefix_cache(self.prefix_cache_bytes)
        return self._session

    @property
    def plan(self) -> Optional[TransferPlan]:
        return self._session.plan if self._session is not None else None

    def describe_plan(self) -> str:
        """The resolved per-leaf routing table (empty before first transfer)."""
        return self.plan.describe() if self.plan is not None else "(no plan yet)"

    def overflow_priors(self, bucket_tokens: int = 1024) -> Dict[int, float]:
        """Per-bucket overflow priors from THIS engine's observed retries.

        ``EngineStats.overflow_obs`` accumulates, per transferred cache
        length, how many encoded units walked the capacity schedule and how
        many needed at least one re-encode; bucketing those observations at
        the scheduler's granularity yields the per-bucket per-attempt
        overflow probability ``SchedulerConfig.overflow_priors`` feeds into
        ``TransferPlan.estimate_time`` (ROADMAP: "per-bucket overflow
        priors").  Buckets with no observations are simply absent — the
        scheduler falls back to its scalar ``overflow_p`` for them."""
        b = max(1, bucket_tokens)
        agg: Dict[int, List[int]] = {}
        for length, (units, retried) in self._stats.overflow_obs.items():
            bucket = max(b, -(-length // b) * b)
            acc = agg.setdefault(bucket, [0, 0])
            acc[0] += units
            acc[1] += retried
        return {bucket: retried / units
                for bucket, (units, retried) in agg.items() if units > 0}

    def scheduler_config(self, profile: Optional[CodecProfile] = None,
                         **overrides) -> "SchedulerConfig":
        """A :class:`~repro.serving.scheduler.SchedulerConfig` whose admission
        engine charges transfers through THIS engine's transfer policy: the
        already-resolved :class:`TransferPlan` when one exists (the same
        object the session executes — the scheduler's numbers then flow
        through the real transfer path's plan), else per-bucket plans built
        from the engine's ``TransferConfig``.  ``profile`` defaults to the
        engine's profile; observed codec overflow feeds back as the
        scheduler's expected-retry model (scalar ``overflow_p`` plus the
        per-bucket ``overflow_priors`` when the engine has per-length
        observations); any other ``SchedulerConfig`` field passes through
        ``overrides``."""
        from repro.serving.scheduler import SchedulerConfig
        kw = dict(profile=profile if profile is not None else self.profile,
                  plan=self.plan, transfer_config=self.tc,
                  compress=self.tc.enabled,
                  n_chunks=max(1, self.tc.n_chunks),
                  overflow_p=self._stats.observed_overflow_p)
        kw.update(overrides)
        if "overflow_priors" not in overrides and self._stats.overflow_obs:
            kw["overflow_priors"] = self.overflow_priors(
                kw.get("bucket_tokens", SchedulerConfig.bucket_tokens))
        return SchedulerConfig(**kw)

    # -- the three pipeline stages ------------------------------------------
    def prefill(self, batch: Dict, max_seq: Optional[int] = None):
        with span("prefill", batch=self._stats.prefill_calls):
            out = self._program("prefill", max_seq)(self.params, batch)
        self._stats.prefill_calls += 1
        if out.state.moe is not None:
            self._moe_pending.append(("prefill", out.state.moe))
        return out

    def transfer(self, state: DecodeState,
                 session_id: Optional[int] = None) -> DecodeState:
        """Compress -> ship -> decompress.  Bit-exact by construction.

        Escape-capacity overflow (``ok == False``) walks the plan's geometric
        capacity schedule and then triggers the raw fallback — per tensor on
        the whole-tensor path, per chunk on the pipelined path — so
        losslessness is unconditional even on adversarial activation
        distributions, and the accounting charges raw bytes for exactly the
        payload that actually shipped raw.

        ``session_id`` (with ``prefix_cache_bytes`` configured) routes the
        call through the prefix-delta path: segments the destination already
        holds for that session never cross the wire, and their raw size lands
        in ``EngineStats.prefix_hit_bytes``."""
        with span("transfer", batch=self._stats.transfer_calls):
            self._stats.transfer_calls += 1
            return self._transfer(state, session_id)

    def _transfer(self, state: DecodeState, session_id: Optional[int]):
        raw = T.raw_wire_bytes(state.cache)
        self._stats.raw_cache_bytes += raw
        if not self.tc.enabled or not state.cache:
            self._stats.wire_bytes += raw
            return state
        sess = self._session_for(state.cache)
        if self.resident == "compressed":
            return self._transfer_resident(sess, state)
        if session_id is not None and self.prefix_cache_bytes is not None:
            cache = sess.transfer_delta(state.cache, session_id, check=False)
        else:
            cache = sess.transfer(state.cache, check=False)
        self._absorb_transfer_stats(sess.last_stats, state)
        return DecodeState(cache=cache, cache_len=state.cache_len,
                           moe=state.moe)

    def resend_cache(self, state: DecodeState) -> DecodeState:
        """Failover re-send: re-ship the last transfer's retained payload to
        a replacement decode worker (``retain_for_failover=True`` engines).

        The scheduler's ``on_failover`` hook calls this when a decode worker
        dies after its transfer completed — the prefill side re-ships the
        pristine compressed streams (one wire hop, no re-encode) and the
        rebuilt state is bit-identical to what the dead worker held."""
        if not self.tc.enabled or not state.cache:
            return state
        sess = self._session_for(state.cache)
        cache = sess.resend_last()
        self._stats.failover_resends += 1
        self._stats.raw_cache_bytes += T.raw_wire_bytes(state.cache)
        self._absorb_transfer_stats(sess.last_stats, state)
        return DecodeState(cache=cache, cache_len=state.cache_len,
                           moe=state.moe)

    def _absorb_transfer_stats(self, cstats, state: DecodeState) -> None:
        self._stats.wire_bytes += cstats.wire_bytes
        self._stats.codec_ok &= cstats.all_ok
        self._stats.chunk_retries += cstats.n_retries
        self._stats.chunk_retry_steps += cstats.n_retry_steps
        self._stats.fp32_lo_wire_bytes += cstats.fp32_lo_wire_bytes
        self._stats.prefix_hit_bytes += cstats.prefix_hit_bytes
        self._stats.verify_failures += cstats.verify_failures
        self._stats.refetches += cstats.refetches
        self._stats.raw_refetches += cstats.raw_refetches
        self._stats.faults_injected += cstats.faults_injected
        # overflow observations: units that walked the capacity schedule on
        # this call, keyed by the transferred prompt length — the raw
        # material for the scheduler's per-bucket overflow priors
        units = len(cstats.chunk_retried)
        if units:
            self._stats.encoded_units += units
            lens = jnp.asarray(state.cache_len)
            length = (host_read(jnp.max(lens), "cache_len", cstats, int)
                      if lens.size else 0)
            obs = self._stats.overflow_obs.setdefault(length, [0, 0])
            obs[0] += units
            obs[1] += cstats.n_retries
        if self.tc.n_chunks > 1:
            self._stats.chunk_wire_bytes.extend(cstats.chunk_wire_bytes)
        self._stats.transfer_host_reads += cstats.host_reads

    def resident_tokens_per_page(self, batch: int = 1) -> int:
        """Page granularity the pool will use for this arch (max_seq must be
        a multiple; ``generate`` rounds up automatically)."""
        from repro.models import kvcache as KC
        from repro.models import kvpool as KVP
        cache = jax.eval_shape(
            lambda: KC.init_cache(self.cfg, batch, 8 * self.tc.chunk))
        return KVP.tokens_per_page_for(
            cache, self.tc.chunk, self.page_bytes or KVP.DEFAULT_PAGE_BYTES)

    def _transfer_resident(self, sess, state: DecodeState):
        """Admit the wire streams into a paged pool — no rehydration.

        Any inadmissible stream (raw-fallback leaf, layout/codebook drift,
        page-escape overflow, non-page-aligned max_seq) demotes THIS batch to
        raw residency: the already-received streams decode once
        (rehydrate-then-reference fallback) and decode runs the classic
        path.  Losslessness is unconditional either way."""
        from repro.core.backend import resolve_backend
        from repro.models import kvpool as KVP
        from repro.serving.session import decode_leaves

        comp, raw = sess.transfer_compressed(state.cache, check=False)
        self._absorb_transfer_stats(sess.last_stats, state)
        pool = None
        try:
            with span("resident.admit"):
                pool = KVP.KVPool.for_cache(
                    state.cache, self.tc.codebook,
                    resolve_backend(self.tc.backend, require_jittable=True),
                    chunk=self.tc.chunk,
                    page_bytes=self.page_bytes or KVP.DEFAULT_PAGE_BYTES)
                rst = pool.admit_from_wire(comp, state.cache_len)
        except KVP.ResidencyError:
            if pool is not None:
                self._stats.transfer_host_reads += pool.host_reads
            self._stats.resident_demotions += 1
            cache = decode_leaves(comp, raw, state.cache,
                                  backend=self.tc.backend)
            return DecodeState(cache=cache, cache_len=state.cache_len,
                               moe=state.moe)
        self._pool = pool
        self._stats.transfer_host_reads += pool.host_reads
        self._stats.resident_admits += 1
        self._stats.resident_hbm_bytes += pool.hbm_bytes()
        self._stats.resident_raw_bytes += pool.raw_bytes()
        return rst

    def decode(self, first_token: jax.Array, state, num_steps: int
               ) -> jax.Array:
        from repro.models.kvpool import ResidentState
        from repro.serving.decode import resident_decode_loop
        with span("decode", batch=self._decode_calls):
            self._decode_calls += 1
            if isinstance(state, ResidentState):
                pool = self._pool
                reads, flushes = pool.host_reads, pool.flushes
                paged = pool.page_flushes
                toks, _, demoted = resident_decode_loop(
                    self.params, first_token, state, pool, self.cfg,
                    num_steps)
                self._stats.resident_demotions += int(demoted)
                self._stats.resident_host_reads += pool.host_reads - reads
                self._stats.resident_steps += pool.flushes - flushes
                self._stats.resident_page_flushes += pool.page_flushes - paged
            else:
                toks, final = self._program("decode", num_steps)(
                    self.params, first_token, state)
                if final.moe is not None:     # counted since the prefill
                    self._moe_pending.append(("decode", jax.tree.map(
                        jnp.subtract, final.moe, state.moe)))
        self._stats.decode_tokens += int(toks.size)
        return toks

    # -- end-to-end ----------------------------------------------------------
    def generate(self, batch: Dict, num_steps: int,
                 max_seq: Optional[int] = None) -> jax.Array:
        """prompt batch -> (B, 1 + num_steps) generated ids (greedy)."""
        if self.resident == "compressed":
            # pages are fixed-size: pad the cache to a page multiple.  The
            # default max_seq (prompt + first token + decode steps) must be
            # derived HERE — prefill's own default (the raw prompt length)
            # is almost never page-aligned and would demote every batch.
            tp = self.resident_tokens_per_page()
            if max_seq is None:
                max_seq = batch["tokens"].shape[1] + 1 + num_steps
            max_seq = -(-max_seq // tp) * tp
        pre = self.prefill(batch, max_seq=max_seq)
        state = self.transfer(pre.state)
        toks = self.decode(pre.first_token, state, num_steps)
        return jnp.concatenate([pre.first_token[:, None], toks], axis=1)

    def transfer_report(self) -> Optional[T.TransferReport]:
        if self.profile is None:
            return None
        return T.transfer_report(self._stats.raw_cache_bytes,
                                 self._stats.wire_bytes, self.profile,
                                 n_chunks=self.tc.n_chunks, plan=self.plan)
