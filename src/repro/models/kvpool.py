"""Paged compressed-resident KV pool (ROADMAP item 1, ISSUE 8 tentpole).

The transfer plane already ships KV as splitzip streams; this module keeps
them compressed **at rest in HBM** on the decode worker.  Storage is paged:

* a *page* covers ``tokens_per_page`` tokens of ONE leaf stream (one
  ``(layer, batch)`` row of a cache leaf).  The token count is chosen so the
  page's element count is a multiple of the codec chunk for every
  compressible leaf in the cache — pages are **codec-chunk-aligned**, so a
  page's streams are a contiguous, self-contained slice of the wire
  ``CompressedTensor`` streams and admission is pure reshape + scatter, with
  **no rehydration** (``admit_from_wire``).
* per page and per leaf the pool holds the two dense streams plus a
  page-level sparse escape list (positions rebased from chunk-relative to
  page-relative and compacted into ``page_escape_cap`` slots — the wire's
  per-chunk capacity is a transfer-overflow bound, the page capacity is a
  residency bound; either can overflow independently, and overflow always
  demotes to raw residency rather than lossy storage).
* a per-``(layer, batch)`` **page table** maps logical page index → physical
  page id (−1 = unmapped); physical pages come from a host-side free-list.
* decode-time growth appends raw tokens to a per-row **tail page** in the
  container dtype; when a row's tail fills (``cache_len % tokens_per_page ==
  0``) ``flush_full_tails`` recompresses it through the registered codec
  backend into fresh pages.  The host decides which rows flush from the
  lengths and page map it keeps itself, so an ordinary step reads nothing
  from the device; a page-boundary flush is one jitted program whose only
  read is its overflow flag.  The attention kernel
  (``kernels/splitzip_attention.py``) therefore only ever sees FULL
  compressed pages + a raw tail, and the decode *step* never touches the
  codec's decompress path (CI grep-guards this).

``KVPool`` is the host-side owner (free-list, geometry, demotion);
``ResidentState`` is the pytree that jitted decode steps consume.  Bytes
accounting (``hbm_bytes`` vs ``raw_bytes``) backs the scheduler's
HBM-derived decode-slot capacity and ``benchmarks/fig6_resident_capacity``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codebook import FORMATS, Codebook
from repro.core import codec as C
from repro.core.backend import CodecBackend, get_backend
from repro.core.spans import host_read

# Default raw-payload bytes per page per leaf.  32 KiB ≅ 128 tokens for the
# benchmark GQA arch (m = 128 elem/token) and keeps the per-page escape
# metadata overhead under 1.2% of payload; benchmarks/table5_granularity.py
# sweeps this knob (8K..128K) and 32K sits on the ratio/throughput knee.
DEFAULT_PAGE_BYTES = 32 * 1024

# One page-level escape slot per 256 payload elements (0.39% of elements).
# The paper's calibrated escape rate is ~0.16%, so pages overflow only on
# genuinely escape-heavy tensors, which demote to raw residency.
ESC_SLOT_PER_ELEMS = 256


# ---------------------------------------------------------------------------
# geometry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LeafGeometry:
    """Static page geometry of one compressible cache leaf."""

    key: str                 # leaf key, e.g. "k" / "v" / "ckv" / "krope"
    shape: tuple             # full cache shape (L, B, S, *token_dims)
    dtype: str               # container dtype name ("bfloat16", ...)
    fmt: str                 # codec format ("bf16", "fp8_e5m2", ...)
    m: int                   # elements per token (= prod(token_dims))
    page_elems: int          # tokens_per_page * m (multiple of chunk)
    page_chunks: int         # page_elems // chunk
    escape_cap: int          # page-level escape slots
    n_pages: int             # physical pages in this leaf's pool


@dataclasses.dataclass(frozen=True)
class PoolGeometry:
    """Static geometry shared by the pool, the kernel, and the docs model."""

    tokens_per_page: int
    chunk: int
    max_pages: int           # logical pages per (layer, batch) row
    n_layers: int
    batch: int
    max_seq: int
    exponents: tuple
    leaves: Tuple[LeafGeometry, ...]

    def leaf(self, key: str) -> LeafGeometry:
        for lg in self.leaves:
            if lg.key == key:
                return lg
        raise KeyError(key)


def _token_elems(shape: tuple) -> int:
    return int(np.prod(shape[3:])) if len(shape) > 3 else 1


def tokens_per_page_for(cache: Dict[str, jax.Array], chunk: int,
                        page_bytes: int = DEFAULT_PAGE_BYTES) -> int:
    """Largest chunk-aligned token count per page under the byte budget.

    Alignment: a page of ``Tp`` tokens of a leaf with ``m`` elements/token
    holds ``Tp * m`` elements; that is a multiple of ``chunk`` for every
    leaf iff ``Tp`` is a multiple of ``lcm_over_leaves(chunk / gcd(chunk,
    m))``."""
    align = 1
    m_max, itemsize_max = 1, 1
    for leaf in cache.values():
        m = _token_elems(leaf.shape)
        align = math.lcm(align, chunk // math.gcd(chunk, m))
        m_max = max(m_max, m)
        itemsize_max = max(itemsize_max, jnp.dtype(leaf.dtype).itemsize)
    target = max(1, page_bytes // (itemsize_max * m_max))
    return max(align, (target // align) * align)


class ResidencyError(RuntimeError):
    """Raised when a stream cannot be admitted/kept compressed-resident.

    The engine catches this and demotes the batch to raw residency (the
    rehydrate-then-``flash_attention`` fallback) — never lossy storage."""


# ---------------------------------------------------------------------------
# pytrees
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PagedLeaf:
    """Device arrays of one leaf's page pool.

    Streams are indexed by physical page id; ``page_table`` is (L, B, P)
    logical→physical (−1 unmapped); ``tail`` is the raw growth page."""

    sign_mantissa: jax.Array   # u8 (n_pages, page_chunks, chunk)
    packed: jax.Array          # u8 (n_pages, page_chunks, chunk // 2)
    esc_pos: jax.Array         # u16 (n_pages, 1, escape_cap), pad = page_elems
    esc_val: jax.Array         # u8 (n_pages, 1, escape_cap)
    esc_cnt: jax.Array         # i32 (n_pages, 1)
    page_table: jax.Array      # i32 (L, B, P)
    tail: jax.Array            # dtype (L, B, tokens_per_page, m)

    def streams(self):
        return (self.sign_mantissa, self.packed, self.esc_pos, self.esc_val,
                self.esc_cnt)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ResidentState:
    """What a jitted resident decode step consumes/returns.

    The page pools are read-only inside a step; only ``tail`` rows and
    ``cache_len`` change (flushes run between steps)."""

    leaves: Dict[str, PagedLeaf]
    cache_len: jax.Array       # (B,) i32
    geom: PoolGeometry = dataclasses.field(metadata=dict(static=True))


# ---------------------------------------------------------------------------
# stream math (page-level escape rebase/compaction; pure jnp, vectorized)
# ---------------------------------------------------------------------------

def _page_escapes(pos_c, val_c, cnt_c, *, chunk: int, page_chunks: int,
                  cap_page: int):
    """Per-chunk escape buffers -> page-level buffers.

    Inputs are (..., page_chunks, cap_chunk) position/value and (...,
    page_chunks) TRUE counts; positions are chunk-relative with padding ==
    chunk.  Outputs are (..., cap_page) page-relative (padding ==
    page_elems) plus (...,) page counts.  Counts are true sums, so a page
    whose total (or any chunk clipped by the wire cap) exceeds capacity is
    detectable by the caller."""
    lead = pos_c.shape[:-2]
    cap_c = pos_c.shape[-1]
    page_elems = chunk * page_chunks
    pos_c = pos_c.astype(jnp.int32)
    valid = pos_c < chunk                                    # occupied slots
    clipped = jnp.minimum(cnt_c, cap_c)
    # destination slot = exclusive running count of prior chunks + own rank
    base = jnp.cumsum(clipped, axis=-1) - clipped            # (..., pc)
    rank = jnp.broadcast_to(jnp.arange(cap_c), pos_c.shape)
    dest = base[..., None] + rank                            # (..., pc, cap)
    dest = jnp.where(valid, dest, cap_page)                  # drop padding
    dest = jnp.minimum(dest, cap_page)                       # drop overflow
    chunk_base = (jnp.arange(page_chunks) * chunk)[..., None]
    pos_page = jnp.where(valid, pos_c + chunk_base, page_elems)

    # scatter along the last axis, batched over the leading dims via 2D view
    n_lead = int(np.prod(lead)) if lead else 1
    dest2 = dest.reshape(n_lead, -1)
    pos2 = pos_page.reshape(n_lead, -1)
    val2 = val_c.reshape(n_lead, -1)
    rows = jnp.broadcast_to(jnp.arange(n_lead)[:, None], dest2.shape)
    out_pos = jnp.full((n_lead, cap_page + 1), page_elems, jnp.int32)
    out_val = jnp.zeros((n_lead, cap_page + 1), jnp.uint8)
    out_pos = out_pos.at[rows, dest2].set(pos2, mode="drop")
    out_val = out_val.at[rows, dest2].set(val2.astype(jnp.uint8), mode="drop")
    out_pos = out_pos[:, :cap_page].reshape(*lead, cap_page)
    out_val = out_val[:, :cap_page].reshape(*lead, cap_page)
    cnt_page = cnt_c.sum(axis=-1).astype(jnp.int32)          # true totals
    return out_pos.astype(jnp.uint16), out_val, cnt_page


def _paged_views(ct, lg: LeafGeometry, geom: PoolGeometry):
    """Reshape a CompressedTensor's flat streams into per-page views.

    Valid because streams are flat row-major over the (L, B, S, *tok) leaf:
    the (l, b) sub-stream is contiguous and S*m % page_elems == 0.  Returns
    (sm, packed, pos, val, cnt) with leading dims (L, B, P_logical)."""
    L_, B, S = lg.shape[0], lg.shape[1], lg.shape[2]
    P = S // geom.tokens_per_page
    pc, chunk = lg.page_chunks, geom.chunk
    sm = ct.sign_mantissa.reshape(L_, B, P, pc, chunk)
    packed = ct.packed.reshape(L_, B, P, pc, chunk // 2)
    pos = ct.esc_pos.reshape(L_, B, P, pc, ct.cap)
    val = ct.esc_val.reshape(L_, B, P, pc, ct.cap)
    cnt = ct.esc_count.reshape(L_, B, P, pc)
    return sm, packed, pos, val, cnt


def _decode_pool_pages(leaf: PagedLeaf, lg: LeafGeometry,
                       geom: PoolGeometry) -> jax.Array:
    """All physical pages -> container bits (n_pages, page_elems).

    Host/fallback path only (rehydrate, tests) — the decode step itself uses
    the fused kernel."""
    spec = FORMATS[lg.fmt]
    mbits, bits_width = spec["mbits"], spec["bits"]
    npg = leaf.sign_mantissa.shape[0]
    pe = lg.page_elems
    code = C.unpack_nibbles(leaf.packed, geom.chunk).reshape(npg, pe).astype(
        jnp.int32)
    e = jnp.zeros_like(code)
    for i, exp in enumerate(geom.exponents):
        e = jnp.where(code == i, exp, e)
    a = leaf.sign_mantissa.reshape(npg, pe).astype(jnp.int32)
    sign = (a >> mbits) & 1
    bits = (sign << (bits_width - 1)) | (e << mbits) | (a & ((1 << mbits) - 1))
    # patch page-level escapes
    keep = ((1 << bits_width) - 1) ^ (((1 << (bits_width - mbits - 1)) - 1)
                                      << mbits)
    cap = leaf.esc_pos.shape[-1]
    slot = jnp.arange(cap)
    pos = leaf.esc_pos[:, 0].astype(jnp.int32)
    occupied = slot[None, :] < leaf.esc_cnt            # (npg, cap)
    pos = jnp.where(occupied, pos, pe)
    rows = jnp.broadcast_to(jnp.arange(npg)[:, None], pos.shape)
    old = jnp.take_along_axis(bits, jnp.minimum(pos, pe - 1), axis=1)
    new = (old & keep) | (leaf.esc_val[:, 0].astype(jnp.int32) << mbits)
    return bits.at[rows, pos].set(jnp.where(occupied, new, 0), mode="drop")


@functools.partial(jax.jit, static_argnames=("geom", "backend"))
def _flush_pages(leaves, pids, page, *, geom: PoolGeometry, backend: str):
    """Recompress every leaf's tail into the pages ``pids`` names.

    ``pids[key]`` is (L, B) physical page ids and ``page`` (B,) the logical
    page each row's tail becomes; rows that do not flush carry out-of-range
    ids in both, so their writes drop and one executable serves any set of
    flushing rows.  Returns the new leaves and ``ok``: every flushing row's
    page escape count fits its leaf's ``escape_cap``.  The inputs are not
    donated, so a caller that finds ``ok`` false still holds its state.

    Module-level with hashable static arguments (the geometry carries the
    codebook's exponents), so pools of one geometry share one compile."""
    be = get_backend(backend)
    L_, B, chunk = geom.n_layers, geom.batch, geom.chunk
    flushing = page < geom.max_pages                         # (B,)
    ok = jnp.asarray(True)
    out = {}
    for lg in geom.leaves:
        leaf = leaves[lg.key]
        pc = lg.page_chunks
        ct = be.encode(leaf.tail.reshape(-1),
                       Codebook(fmt=lg.fmt, exponents=geom.exponents),
                       chunk=chunk, cap=lg.escape_cap, layout="chunked")
        pos_pg, val_pg, cnt_pg = _page_escapes(
            ct.esc_pos.reshape(L_, B, pc, -1),
            ct.esc_val.reshape(L_, B, pc, -1),
            ct.esc_count.reshape(L_, B, pc), chunk=chunk, page_chunks=pc,
            cap_page=lg.escape_cap)
        ok &= jnp.all((cnt_pg <= lg.escape_cap) | ~flushing[None, :])
        pid = pids[lg.key]                                   # (L, B)
        out[lg.key] = dataclasses.replace(
            leaf,
            sign_mantissa=leaf.sign_mantissa.at[pid].set(
                ct.sign_mantissa.reshape(L_, B, pc, chunk), mode="drop"),
            packed=leaf.packed.at[pid].set(
                ct.packed.reshape(L_, B, pc, chunk // 2), mode="drop"),
            esc_pos=leaf.esc_pos.at[pid, 0].set(pos_pg, mode="drop"),
            esc_val=leaf.esc_val.at[pid, 0].set(val_pg, mode="drop"),
            esc_cnt=leaf.esc_cnt.at[pid, 0].set(cnt_pg, mode="drop"),
            page_table=leaf.page_table.at[
                jnp.arange(L_)[:, None], jnp.arange(B)[None, :],
                page[None, :]].set(pid, mode="drop"))
    return out, ok


# ---------------------------------------------------------------------------
# the pool
# ---------------------------------------------------------------------------

class KVPool:
    """Host-side owner of the paged compressed KV pool.

    Not a pytree: holds the free-list and geometry, and mutates a
    ``ResidentState`` between jitted steps.  One physical-page namespace per
    leaf (leaves have different stream widths, so pages are not shared)."""

    def __init__(self, geom: PoolGeometry, backend: CodecBackend,
                 codebook: Codebook):
        self.geom = geom
        self.backend = backend
        self.codebook = codebook
        if tuple(codebook.exponents) != tuple(geom.exponents):
            raise ValueError("codebook/geometry exponent mismatch")
        self._free: Dict[str, list] = {
            lg.key: list(range(lg.n_pages - 1, -1, -1)) for lg in geom.leaves}
        # device-to-host reads made by admission and flushes, flush calls
        # (one per resident decode step), and flushes that mapped pages
        self.host_reads = 0
        self.flushes = 0
        self.page_flushes = 0
        # the host's copy of ``self.state.cache_len`` and of how many
        # logical pages each row has mapped in ``self.state`` (the same for
        # every layer and leaf); ``sync`` points both at another state
        self.lens = np.zeros((geom.batch,), np.int64)
        self.mapped = np.zeros((geom.batch,), np.int64)
        self.state = ResidentState(
            leaves={lg.key: self._empty_leaf(lg) for lg in geom.leaves},
            cache_len=jnp.zeros((geom.batch,), jnp.int32),
            geom=geom)

    # -- construction ------------------------------------------------------

    @classmethod
    def for_cache(cls, cache: Dict[str, jax.Array], codebook: Codebook,
                  backend: CodecBackend, *, chunk: int,
                  page_bytes: int = DEFAULT_PAGE_BYTES,
                  compressible: Optional[Dict[str, str]] = None) -> "KVPool":
        """Build a pool sized for ``cache`` (dict of (L, B, S, ...) leaves).

        ``compressible`` maps leaf key -> codec fmt (default: every bf16
        leaf as "bf16", fp8 leaves as their format).  S must be a multiple
        of the derived ``tokens_per_page`` (the engine rounds ``max_seq``
        up before building the pool)."""
        if compressible is None:
            compressible = {}
            for k, v in cache.items():
                if v.dtype == jnp.bfloat16:
                    compressible[k] = "bf16"
                elif v.dtype == jnp.float8_e5m2:
                    compressible[k] = "fp8_e5m2"
        if len(codebook.exponents) > 16:
            raise ResidencyError("resident pool requires a nibble-packed "
                                 "(k<=16) codebook")
        tp = tokens_per_page_for(
            {k: cache[k] for k in compressible}, chunk, page_bytes)
        first = next(iter(compressible))
        L_, B, S = cache[first].shape[:3]
        if S % tp:
            raise ResidencyError(
                f"max_seq {S} not a multiple of tokens_per_page {tp}")
        P = S // tp
        leaves = []
        for k in compressible:
            arr = cache[k]
            m = _token_elems(arr.shape)
            pe = tp * m
            leaves.append(LeafGeometry(
                key=k, shape=tuple(arr.shape), dtype=str(arr.dtype),
                fmt=compressible[k], m=m, page_elems=pe,
                page_chunks=pe // chunk,
                escape_cap=max(8, pe // ESC_SLOT_PER_ELEMS),
                n_pages=L_ * B * P))
        geom = PoolGeometry(
            tokens_per_page=tp, chunk=chunk, max_pages=P, n_layers=L_,
            batch=B, max_seq=S, exponents=tuple(codebook.exponents),
            leaves=tuple(leaves))
        return cls(geom, backend, codebook)

    def _empty_leaf(self, lg: LeafGeometry) -> PagedLeaf:
        g = self.geom
        return PagedLeaf(
            sign_mantissa=jnp.zeros((lg.n_pages, lg.page_chunks, g.chunk),
                                    jnp.uint8),
            packed=jnp.zeros((lg.n_pages, lg.page_chunks, g.chunk // 2),
                             jnp.uint8),
            esc_pos=jnp.full((lg.n_pages, 1, lg.escape_cap), lg.page_elems,
                             jnp.uint16),
            esc_val=jnp.zeros((lg.n_pages, 1, lg.escape_cap), jnp.uint8),
            esc_cnt=jnp.zeros((lg.n_pages, 1), jnp.int32),
            page_table=jnp.full((g.n_layers, g.batch, g.max_pages), -1,
                                jnp.int32),
            tail=jnp.zeros((g.n_layers, g.batch, g.tokens_per_page, lg.m),
                           jnp.dtype(lg.dtype)))

    # -- free-list ---------------------------------------------------------

    def _alloc(self, key: str, n: int) -> np.ndarray:
        free = self._free[key]
        if len(free) < n:
            raise ResidencyError(f"leaf {key!r}: pool exhausted "
                                 f"({n} pages requested, {len(free)} free)")
        return np.array([free.pop() for _ in range(n)], np.int32)

    def _release(self, key: str, ids) -> None:
        self._free[key].extend(int(i) for i in ids)

    def free_pages(self, key: str) -> int:
        return len(self._free[key])

    def allocated_pages(self, key: str) -> int:
        return self.geom.leaf(key).n_pages - len(self._free[key])

    # -- admission (zero-rehydration) --------------------------------------

    def admit_from_wire(self, comp: Dict[str, object],
                        cache_len: jax.Array) -> ResidentState:
        """Map received ``CompressedTensor`` streams into pages.

        No rehydration: pages are contiguous stream slices, so admission is
        reshape + page-escape compaction + scatter by physical page id.
        Only the sub-page tail region (``cache_len % tokens_per_page``
        tokens per row) passes through the backend's bounded decode — one
        page-group per (layer, row), never the full cache.  Raises
        :class:`ResidencyError` (caller demotes) on any unsupported stream
        shape or page-escape overflow."""
        g = self.geom
        cache_len = jnp.asarray(cache_len, jnp.int32)
        lens = host_read(cache_len, "cache_len", self)
        n_full = lens // g.tokens_per_page
        leaves = {}
        for lg in g.leaves:
            ct = comp.get(lg.key)
            if ct is None:
                raise ResidencyError(
                    f"leaf {lg.key!r} arrived raw (codec fallback); "
                    "cannot admit compressed-resident")
            if getattr(ct, "layout", None) != "chunked":
                raise ResidencyError(f"leaf {lg.key!r}: layout "
                                     f"{getattr(ct, 'layout', None)!r} "
                                     "not admissible (need 'chunked')")
            if ct.chunk != g.chunk or tuple(ct.exponents) != g.exponents:
                raise ResidencyError(
                    f"leaf {lg.key!r}: wire chunk/codebook mismatch")
            if tuple(ct.shape) != lg.shape:
                raise ResidencyError(
                    f"leaf {lg.key!r}: wire shape {ct.shape} != pool shape "
                    f"{lg.shape}")
            leaves[lg.key] = self._admit_leaf(ct, lg, lens, n_full)
        self.state = ResidentState(leaves=leaves, cache_len=cache_len,
                                   geom=g)
        self.lens, self.mapped = lens, n_full
        return self.state

    def _admit_leaf(self, ct, lg: LeafGeometry, lens: np.ndarray,
                    n_full: np.ndarray) -> PagedLeaf:
        g = self.geom
        leaf = self._empty_leaf(lg)
        sm, packed, pos_c, val_c, cnt_c = _paged_views(ct, lg, g)
        pos_pg, val_pg, cnt_pg = _page_escapes(
            pos_c, val_c, cnt_c, chunk=g.chunk, page_chunks=lg.page_chunks,
            cap_page=lg.escape_cap)

        # admitted (l, b, p) triples: every layer, rows' full pages only
        idx_l, idx_b, idx_p = [], [], []
        for b in range(g.batch):
            for p in range(int(n_full[b])):
                for l in range(g.n_layers):
                    idx_l.append(l)
                    idx_b.append(b)
                    idx_p.append(p)
        if idx_l:
            idx_l = np.array(idx_l)
            idx_b = np.array(idx_b)
            idx_p = np.array(idx_p)
            cnts = host_read(cnt_pg, "esc_cnt", self)[idx_l, idx_b, idx_p]
            if (cnts > lg.escape_cap).any():
                raise ResidencyError(
                    f"leaf {lg.key!r}: page escape overflow "
                    f"(max {int(cnts.max())} > cap {lg.escape_cap})")
            pids = self._alloc(lg.key, len(idx_l))
            leaf = dataclasses.replace(
                leaf,
                sign_mantissa=leaf.sign_mantissa.at[pids].set(
                    sm[idx_l, idx_b, idx_p]),
                packed=leaf.packed.at[pids].set(packed[idx_l, idx_b, idx_p]),
                esc_pos=leaf.esc_pos.at[pids, 0].set(
                    pos_pg[idx_l, idx_b, idx_p]),
                esc_val=leaf.esc_val.at[pids, 0].set(
                    val_pg[idx_l, idx_b, idx_p]),
                esc_cnt=leaf.esc_cnt.at[pids, 0].set(
                    cnt_pg[idx_l, idx_b, idx_p]),
                page_table=leaf.page_table.at[idx_l, idx_b, idx_p].set(pids))

        # tail: bounded decode of ONE page-group per (layer, row)
        tail = leaf.tail
        if (lens % g.tokens_per_page).any():
            tail = self._decode_wire_tail(ct, lg, n_full)
        return dataclasses.replace(leaf, tail=tail)

    def _decode_wire_tail(self, ct, lg: LeafGeometry,
                          n_full: np.ndarray) -> jax.Array:
        """Gather each (layer, row)'s tail page-group chunks into a small
        CompressedTensor and decode it through the registered backend."""
        g = self.geom
        L_, B = g.n_layers, g.batch
        pc, chunk = lg.page_chunks, g.chunk
        chunks_per_row = (lg.shape[2] * lg.m) // chunk       # S*m/chunk
        # chunk index of each (l, b) row's tail group start
        start = (np.arange(L_)[:, None] * B + np.arange(B)[None, :]) \
            * chunks_per_row + np.minimum(
                n_full[None, :], g.max_pages - 1) * pc
        gather = (start[..., None] + np.arange(pc)).reshape(-1)  # (L*B*pc,)
        n_chunks_total = ct.sign_mantissa.shape[0] // chunk
        sm = ct.sign_mantissa.reshape(n_chunks_total, chunk)[gather]
        packed = ct.packed.reshape(n_chunks_total, chunk // 2)[gather]
        sub = C.CompressedTensor(
            sign_mantissa=sm.reshape(-1), packed=packed.reshape(-1),
            esc_pos=ct.esc_pos[gather], esc_val=ct.esc_val[gather],
            esc_count=ct.esc_count[gather],
            ok=jnp.asarray(True),
            shape=(L_ * B * pc * chunk,), dtype=lg.dtype, fmt=lg.fmt,
            exponents=g.exponents, chunk=chunk, cap=ct.cap, layout="chunked")
        vals = self.backend.decode(sub)
        return vals.reshape(L_, B, g.tokens_per_page, lg.m)

    # -- decode-time growth ------------------------------------------------

    def sync(self, state: ResidentState,
             lens: Optional[np.ndarray] = None) -> np.ndarray:
        """Point the host's lengths and page map at ``state``; return the
        lengths.

        Both describe ``self.state``, the state the pool made last.  A
        state that descends from it through decode steps (which grow only
        tails and lengths) shares its page tables, so the map holds; any
        other (an earlier snapshot, a state built by hand) has its map read
        back from leaf 0's page table.  The lengths are ``lens`` when the
        caller knows them, the pool's own when ``state`` shares
        ``self.state``'s ``cache_len``, and read from the device otherwise."""
        key = self.geom.leaves[0].key
        table = state.leaves[key].page_table
        if table is not self.state.leaves[key].page_table:
            self.mapped = (host_read(table, "page_table", self)[0] >= 0
                           ).sum(-1)
        if lens is None:
            lens = (self.lens if state.cache_len is self.state.cache_len
                    else host_read(state.cache_len, "cache_len", self))
        self.state, self.lens = state, np.asarray(lens)
        return self.lens

    def flush_full_tails(self, state: ResidentState,
                         lens: Optional[np.ndarray] = None) -> ResidentState:
        """Recompress rows whose tail page just filled into fresh pages.

        Between steps.  ``lens`` is the caller's knowledge of
        ``state.cache_len`` (the resident decode loop passes it); ``sync``
        supplies what the host does not already know about ``state``.  A
        row flushes when its length is a page multiple and the page it just
        filled is not yet mapped.  Nothing else is read: the page ids are
        popped from the free-list first, then one jitted program
        (``_flush_pages``) encodes every leaf's tail and scatters the
        flushing rows' pages, and the host reads its ``ok`` flag.  Pool
        exhaustion or page-escape overflow raises :class:`ResidencyError`
        (→ demotion) with the free-list as it was and ``state`` untouched."""
        g = self.geom
        tp = g.tokens_per_page
        self.flushes += 1
        lens = self.sync(state, lens)
        rows = np.flatnonzero((lens % tp == 0) & (self.mapped < lens // tp))
        if not rows.size:
            return state
        page = np.full((g.batch,), g.max_pages, np.int32)
        page[rows] = lens[rows] // tp - 1
        popped, pids = {}, {}
        try:
            for lg in g.leaves:
                ids = popped[lg.key] = self._alloc(lg.key,
                                                   g.n_layers * rows.size)
                pid = np.full((g.n_layers, g.batch), lg.n_pages, np.int32)
                pid[:, rows] = ids.reshape(g.n_layers, rows.size)
                pids[lg.key] = pid
            leaves, ok = _flush_pages(state.leaves, pids, page, geom=g,
                                      backend=self.backend.name)
            if not host_read(ok, "ok", self, bool):
                raise ResidencyError(
                    "tail recompress escape overflow (a flushed page holds "
                    "more escapes than its leaf's escape_cap)")
        except ResidencyError:
            for key, ids in popped.items():
                self._release(key, ids[::-1])   # the free-list's old order
            raise
        self.page_flushes += 1
        self.mapped[rows] = lens[rows] // tp
        self.state = dataclasses.replace(state, leaves=leaves)
        return self.state

    # -- fallback / teardown ----------------------------------------------

    def rehydrate(self, state: Optional[ResidentState] = None
                  ) -> Dict[str, jax.Array]:
        """Reconstruct the raw cache dict (bit-exact; demotion/tests).

        Unmapped pages and tokens beyond ``cache_len`` come back zero-filled
        (matching ``init_cache``'s zero padding)."""
        state = state or self.state
        g = self.geom
        out = {}
        for lg in g.leaves:
            leaf = state.leaves[lg.key]
            bits = _decode_pool_pages(leaf, lg, g)           # (npg, pe)
            zero = jnp.zeros((1, lg.page_elems), bits.dtype)
            bits = jnp.concatenate([bits, zero], axis=0)     # id −1 → zeros
            pages = bits[leaf.page_table]                    # (L, B, P, pe)
            spec = FORMATS[lg.fmt]
            u = pages.astype(jnp.uint16 if spec["bits"] == 16 else jnp.uint8)
            vals = jax.lax.bitcast_convert_type(u, jnp.dtype(lg.dtype))
            vals = vals.reshape(g.n_layers, g.batch, g.max_pages,
                                g.tokens_per_page, lg.m)
            # splice each row's tail page over its first unmapped slot.  A
            # row at a page boundary (cache_len % Tp == 0) whose just-filled
            # page cache_len//Tp - 1 is still UNMAPPED (a flush failed before
            # the page table was written) holds that page's data only in the
            # tail: splice the FULL tail there, not an empty one at n_full —
            # otherwise demotion would silently zero tokens_per_page tokens.
            n_full = state.cache_len // g.tokens_per_page    # (B,)
            tail_tok = state.cache_len % g.tokens_per_page   # (B,)
            L_, B = g.n_layers, g.batch
            prev = jnp.maximum(n_full - 1, 0)
            prev_pid = jnp.take_along_axis(
                leaf.page_table,
                jnp.broadcast_to(prev[None, :, None], (L_, B, 1)),
                axis=2)[..., 0]                              # (L, B)
            pending = ((tail_tok[None, :] == 0) & (n_full[None, :] > 0)
                       & (prev_pid < 0))                     # (L, B)
            eff_page = jnp.where(pending, prev[None, :], n_full[None, :])
            eff_tok = jnp.where(pending, g.tokens_per_page,
                                tail_tok[None, :])           # (L, B)
            t_idx = jnp.arange(g.tokens_per_page)
            tail_mask = (t_idx[None, None, :] < eff_tok[..., None])
            tail = jnp.where(tail_mask[..., None], leaf.tail, 0)
            p_idx = jnp.arange(g.max_pages)
            is_tail_page = (p_idx[None, None, :] == eff_page[..., None])
            vals = jnp.where(is_tail_page[..., None, None],
                             tail[:, :, None], vals)
            out[lg.key] = vals.reshape(g.n_layers, g.batch,
                                       g.max_seq, *lg.shape[3:])
        return out

    def free_rows(self, rows) -> None:
        """Return all physical pages of the given batch rows to the
        free-list and unmap them (sequence eviction)."""
        g = self.geom
        new_leaves = {}
        for lg in g.leaves:
            leaf = self.state.leaves[lg.key]
            table = np.asarray(leaf.page_table)
            pt = leaf.page_table
            for b in rows:
                ids = table[:, b, :].reshape(-1)
                self._release(lg.key, ids[ids >= 0])
                pt = pt.at[:, b, :].set(-1)
            new_leaves[lg.key] = dataclasses.replace(leaf, page_table=pt)
        self.state = dataclasses.replace(self.state, leaves=new_leaves)
        self.mapped[list(rows)] = 0

    # -- accounting --------------------------------------------------------

    def page_bytes(self, lg: LeafGeometry) -> int:
        """HBM bytes of ONE physical page (streams + escape metadata)."""
        return (lg.page_elems + lg.page_elems // 2
                + lg.escape_cap * 3 + 4)

    def hbm_bytes(self, *, allocated_only: bool = False) -> int:
        """Resident footprint: page pools (+ tables + tails)."""
        g = self.geom
        total = 0
        for lg in g.leaves:
            n = (self.allocated_pages(lg.key) if allocated_only
                 else lg.n_pages)
            total += n * self.page_bytes(lg)
            total += g.n_layers * g.batch * g.max_pages * 4   # page table
            total += (g.n_layers * g.batch * g.tokens_per_page * lg.m
                      * jnp.dtype(lg.dtype).itemsize)          # tail
        return total

    def raw_bytes(self) -> int:
        """What the same cache costs raw-resident."""
        g = self.geom
        return sum(g.n_layers * g.batch * g.max_seq * lg.m
                   * jnp.dtype(lg.dtype).itemsize for lg in g.leaves)

    def resident_ratio(self) -> float:
        """raw / resident — the capacity multiplier fig6 measures."""
        return self.raw_bytes() / self.hbm_bytes()


# ---------------------------------------------------------------------------
# decode-step glue (one fused pallas_call per attention layer)
# ---------------------------------------------------------------------------

def _append_tail(tail: jax.Array, new: jax.Array, t: jax.Array) -> jax.Array:
    """Write each row's new token into its tail page at slot ``t`` (B,)."""
    return jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice(
        c, n, (i, 0)))(tail, new.astype(tail.dtype), t)


def paged_decode_attention_block(p, x, k_streams, v_streams, pt_k, pt_v,
                                 tail_k, tail_v, cache_len, theta, *,
                                 geom: PoolGeometry, fmt: str = "bf16",
                                 interpret: bool | None = None):
    """Mirror of ``layers.decode_attention_block`` with a compressed prefix.

    The prefix (``cache_len // Tp`` full pages) is attended by the fused
    kernel directly over the splitzip streams; the new token is appended to
    the raw tail page and the tail partials merge in plain jnp.  Stream
    arrays are per-leaf pools shared by every layer; ``pt_*``/``tail_*`` are
    THIS layer's page-table rows (B, P) and tail pages (B, Tp, m).

    Returns ``(attn_out, (tail_k, tail_v))`` — the compressed pool is
    read-only inside the step; only tails grow (flushes are host-side)."""
    from repro.kernels import splitzip_attention as SA
    from repro.models import layers as Ly

    tp = geom.tokens_per_page
    positions = cache_len[:, None]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = Ly.apply_rope(q, positions, theta)
    k = Ly.apply_rope(k, positions, theta)
    b, _, hkv, hd = k.shape
    h = q.shape[2]
    g = h // hkv
    dv = v.shape[-1]
    t = cache_len % tp
    tail_k = _append_tail(tail_k, k.reshape(b, 1, hkv * hd), t)
    tail_v = _append_tail(tail_v, v.reshape(b, 1, hkv * dv), t)

    scale = 1.0 / np.sqrt(hd)
    acc, m, l = SA.paged_gqa_attention(
        q, k_streams, v_streams, pt_k, pt_v, cache_len,
        exponents=geom.exponents, fmt=fmt, chunk=geom.chunk,
        tokens_per_page=tp, hkv=hkv, causal=True, scale=scale,
        interpret=interpret)
    acc = acc.reshape(b, 1, hkv, g, dv)
    m = m.reshape(b, 1, hkv, g)
    l = l.reshape(b, 1, hkv, g)

    tk = tail_k.reshape(b, tp, hkv, hd).astype(jnp.float32)
    tv = tail_v.reshape(b, tp, hkv, dv).astype(jnp.float32)
    qf = q.astype(jnp.float32).reshape(b, 1, hkv, g, hd)
    s_t = jnp.einsum("bqhgd,bthd->bqhgt", qf, tk,
                     preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(tp)[None, :] < (t + 1)[:, None]   # incl. the new token
    part = SA.merge_partials((acc, m, l), SA.tail_partials(s_t, tv, valid))
    o = SA.finalize(part[0], part[2], dtype=x.dtype).reshape(b, 1, h, dv)
    return Ly.attention_out(p, o), (tail_k, tail_v)


def paged_mla_decode(p, x, ckv_streams, kr_streams, pt_c, pt_r,
                     tail_c, tail_r, cache_len, cfg, theta, *,
                     geom: PoolGeometry, fmt: str = "bf16",
                     interpret: bool | None = None):
    """Mirror of ``mla.mla_decode`` over compressed latent pages.

    Scores/context run in the latent space inside the kernel (absorbed
    form); the ``w_v``/``wo`` up-projections apply after the tail merge."""
    from repro.kernels import splitzip_attention as SA
    from repro.models import mla as M

    tp = geom.tokens_per_page
    b = x.shape[0]
    positions = cache_len[:, None]
    q_nope, q_rope = M._queries(p, x, positions, cfg, theta)     # (B,1,H,·)
    c_new, kr_new = M._latent_kv(p, x, positions, cfg, theta)    # (B,1,r/p)
    t = cache_len % tp
    tail_c = _append_tail(tail_c, c_new, t)
    tail_r = _append_tail(tail_r, kr_new, t)

    w_knope = p["wkv_b"][..., : cfg.qk_nope_head_dim]            # (r, H, n)
    w_v = p["wkv_b"][..., cfg.qk_nope_head_dim:]                 # (r, H, v)
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_knope)
    scale = cfg.softmax_scale

    acc, m, l = SA.paged_mla_attention(
        q_lat, q_rope, ckv_streams, kr_streams, pt_c, pt_r, cache_len,
        exponents=geom.exponents, fmt=fmt, chunk=geom.chunk,
        tokens_per_page=tp, scale=scale, causal=True, interpret=interpret)

    tc = tail_c.astype(jnp.float32)                              # (B,Tp,r)
    tr = tail_r.astype(jnp.float32)
    qlf = q_lat.astype(jnp.float32)
    qrf = q_rope.astype(jnp.float32)
    s_t = (jnp.einsum("bqhr,btr->bqht", qlf, tc,
                      preferred_element_type=jnp.float32)
           + jnp.einsum("bqhp,btp->bqht", qrf, tr,
                        preferred_element_type=jnp.float32)) * scale
    valid = jnp.arange(tp)[None, :] < (t + 1)[:, None]
    part = SA.merge_partials((acc, m, l), SA.tail_partials(s_t, tc, valid))
    ctx_lat = SA.finalize(part[0], part[2], dtype=tail_c.dtype)  # (B,1,H,r)
    o = jnp.einsum("bqhr,rhv->bqhv", ctx_lat, w_v)
    out = jnp.einsum("bqhv,hvd->bqd", o, p["wo"])
    return out, (tail_c, tail_r)


def bytes_per_token_resident(m: int, tokens_per_page: int,
                             *, chunk: int = 1024,
                             esc_slot_per_elems: int = ESC_SLOT_PER_ELEMS
                             ) -> float:
    """Analytic HBM bytes/token of the paged resident format (DESIGN.md
    capacity model): 1.5 B/elem dense streams (sign-mantissa byte + packed
    nibble) + page escape metadata, independent of the source dtype.  ``m``
    is compressed elements per token (all compressible leaves summed)."""
    pe = tokens_per_page * m
    cap = max(8, pe // esc_slot_per_elems)
    return (pe + pe // 2 + cap * 3 + 4) / tokens_per_page
