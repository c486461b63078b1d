"""Compressed-resident KV tests (ISSUE 8).

What the paged pool + fused attention promise, pinned here:

1. **Losslessness** — ``KVPool.admit_from_wire`` followed by ``rehydrate``
   is bit-identical to the original cache, for dense-GQA and MLA streams,
   escape-bearing tensors, ragged (mixed-length) batches, and across
   tail-page growth + recompression (``flush_full_tails``).  The fused
   kernel's in-register page decode is pinned bitwise against the same
   pages decoded outside the kernel (integer ops, arch-independent), so the
   attention consumes EXACTLY the values a rehydrate would produce; the
   attention partials themselves are compared at f32 round-off tolerance
   (dot-product summation order inside ``pallas_call`` is not guaranteed to
   match an einsum's).
2. **Zero-rehydration admission** — admission never routes the full stream
   through the backend decoder: only the sub-page tail region (bounded by
   one page per (layer, row)) may be decoded.
3. **Pool invariants** — free-list accounting across admit/grow/free,
   escape-overflow and pool-exhaustion demotion (``ResidencyError``), and
   the one-``pallas_call``-per-layer structure of the resident decode step.
4. **Engine integration** — ``resident='compressed'`` serves end-to-end,
   demotes gracefully (bit-identical to raw-resident serving when it does),
   and the scheduler's HBM-derived slot budget reflects the footprint win.
5. **Ragged decode** (satellite): mixed-length prefill scores each row at
   its own last real token and decodes correctly from per-row cache_len.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import codebook as cbm
from repro.core.backend import resolve_backend
from repro.kernels import splitzip_attention as SA
from repro.models import kvpool as KVP
from repro.models import model as M
from repro.serving.engine import DisaggregatedEngine
from repro.serving.plan import TransferConfig, TransferPlan
from repro.serving.scheduler import SchedulerConfig
from repro.serving.session import encode_leaves

CHUNK = 1024


def _calibrate(cache):
    bits = np.concatenate(
        [np.asarray(jax.lax.bitcast_convert_type(v, jnp.uint16)).ravel()
         for v in cache.values() if v.dtype == jnp.bfloat16])
    return cbm.calibrate(bits, k=16, fmt="bf16")


def _dense_cache(L=2, B=2, S=64, hkv=2, hd=32, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return {
        "k": jnp.asarray(rng.standard_normal((L, B, S, hkv, hd)) * scale,
                         jnp.bfloat16),
        "v": jnp.asarray(rng.standard_normal((L, B, S, hkv, hd)) * scale,
                         jnp.bfloat16),
    }


def _encode(cache, cb, backend="xla"):
    tc = TransferConfig(codebook=cb, chunk=CHUNK, backend=backend)
    plan = TransferPlan.build(cache, tc)
    return encode_leaves(plan, cache)


def _pool_for(cache, cb, page_bytes=2048):
    backend = resolve_backend("xla", require_jittable=True)
    return KVP.KVPool.for_cache(cache, cb, backend, chunk=CHUNK,
                                page_bytes=page_bytes)


def _append_rows(pool, rs, rng, grown=None, values=None):
    """Append one token to every (layer, row) tail and advance cache_len.

    ``values`` maps leaf key -> (L, B, m) override of the random draw (used
    to inject escape-heavy data into one leaf); ``grown`` (dict of f32
    copies of the original cache) records the appended values for bit-exact
    comparison after rehydrate."""
    g = pool.geom
    tp = g.tokens_per_page
    lens = np.asarray(rs.cache_len)
    for lg in g.leaves:
        key, m = lg.key, lg.m
        leaf = rs.leaves[key]
        new = (values or {}).get(key)
        if new is None:
            new = jnp.asarray(
                rng.standard_normal((g.n_layers, g.batch, m)), jnp.bfloat16)
        t = rs.cache_len % tp
        tail = leaf.tail
        for layer in range(g.n_layers):
            tail = tail.at[layer].set(KVP._append_tail(
                tail[layer], new[layer][:, None, :], t))
        rs = dataclasses.replace(rs, leaves={
            **rs.leaves, key: dataclasses.replace(leaf, tail=tail)})
        if grown is not None:
            for row in range(g.batch):
                grown[key][:, row, lens[row]] = np.asarray(
                    new[:, row], np.float32).reshape(
                        g.n_layers, *grown[key].shape[3:])
    return dataclasses.replace(
        rs, cache_len=jnp.asarray(lens + 1, jnp.int32))


def _assert_cache_equal(a, b, lens=None):
    """Bitwise equality, optionally restricted to each row's valid prefix."""
    for key in a:
        xa = np.asarray(jax.lax.bitcast_convert_type(a[key], jnp.uint16))
        xb = np.asarray(jax.lax.bitcast_convert_type(b[key], jnp.uint16))
        if lens is not None:
            for row, n in enumerate(np.asarray(lens)):
                np.testing.assert_array_equal(
                    xa[:, row, :n], xb[:, row, :n], err_msg=key)
        else:
            np.testing.assert_array_equal(xa, xb, err_msg=key)


# ---------------------------------------------------------------------------
# pool: admit / rehydrate / grow / free
# ---------------------------------------------------------------------------

class TestPool:
    def test_admit_rehydrate_bit_exact_ragged(self):
        """Mixed-length admission (full pages, page-boundary, mid-page,
        mid-chunk rows) rehydrates bit-identically; unmapped tail region
        stays zero; free-list accounting matches the page count."""
        cache = _dense_cache(L=2, B=3, S=64)
        cb = _calibrate(cache)
        pool = _pool_for(cache, cb)
        tp = pool.geom.tokens_per_page
        assert 64 % tp == 0 and tp >= 1
        lens = jnp.asarray([64, 2 * tp, tp // 2 + 1], jnp.int32)
        comp, _ = _encode(cache, cb)
        rs = pool.admit_from_wire(comp, lens)

        reh = pool.rehydrate(rs)
        _assert_cache_equal(reh, cache, lens)
        # pages wholly past the row's tail page are unmapped -> zero
        # (within the tail page, positions past cache_len are unspecified:
        # the wire tail decodes at chunk granularity)
        for key in reh:
            x = np.asarray(reh[key], np.float32)
            for row, n in enumerate(np.asarray(lens)):
                nxt = (n // tp + 1) * tp
                if nxt < 64:
                    assert not x[:, row, nxt:].any()

        n_full = np.asarray(lens) // tp
        want = 2 * int(n_full.sum())           # L * sum(full pages)
        for key in ("k", "v"):
            assert pool.allocated_pages(key) == want

    def test_admission_decodes_at_most_the_tail(self):
        """Zero-rehydration: the backend decoder sees only sub-page tails
        (bounded by page_elems per call), never the full stream."""
        cache = _dense_cache(L=2, B=2, S=256)
        cb = _calibrate(cache)
        pool = _pool_for(cache, cb)
        tp = pool.geom.tokens_per_page

        decoded = []
        real = pool.backend

        class Counting:
            def __getattr__(self, name):
                return getattr(real, name)

            def decode(self, ct):
                decoded.append(int(np.prod(ct.shape)))
                return real.decode(ct)

        pool.backend = Counting()
        comp, _ = _encode(cache, cb)
        lens = jnp.asarray([256, tp + tp // 2], jnp.int32)
        rs = pool.admit_from_wire(comp, lens)
        pool.backend = real

        total = sum(int(np.prod(v.shape)) for v in cache.values())
        # bounded: one page-group per (layer, row) per leaf, batched into a
        # single small decode — never the full stream
        g = pool.geom
        bound = g.n_layers * g.batch * max(lg.page_elems for lg in g.leaves)
        assert decoded and all(n <= bound for n in decoded)
        assert sum(decoded) < total // 4
        _assert_cache_equal(pool.rehydrate(rs), cache, lens)

    def test_tail_growth_and_recompress_bit_exact(self):
        """Decode-time growth: tokens appended to the raw tail page, flushed
        into fresh compressed pages at each boundary — including a page
        that is part admission-tail, part appended — stay bit-exact."""
        cache = _dense_cache(L=2, B=2, S=64)
        cb = _calibrate(cache)
        pool = _pool_for(cache, cb)
        tp = pool.geom.tokens_per_page
        start = np.array([tp + tp // 2, tp - 1])   # both mid-page
        comp, _ = _encode(cache, cb)
        rs = pool.admit_from_wire(comp, jnp.asarray(start, jnp.int32))

        rng = np.random.default_rng(7)
        grown = {k: np.asarray(v, np.float32).copy() for k, v in cache.items()}
        lens = start.copy()
        before = {k: pool.allocated_pages(k) for k in ("k", "v")}
        for _ in range(tp + 2):                    # crosses >=1 boundary/row
            for key in ("k", "v"):
                leaf = rs.leaves[key]
                m = pool.geom.leaf(key).m
                new = jnp.asarray(
                    rng.standard_normal((2, 2, m)), jnp.bfloat16)  # (L,B,m)
                t = rs.cache_len % tp
                tail = leaf.tail                 # (L,B,Tp,m): append per layer
                for layer in range(2):
                    tail = tail.at[layer].set(KVP._append_tail(
                        tail[layer], new[layer][:, None, :], t))
                rs = dataclasses.replace(rs, leaves={
                    **rs.leaves, key: dataclasses.replace(leaf, tail=tail)})
                for row in range(2):
                    grown[key][:, row, lens[row]] = np.asarray(
                        new[:, row], np.float32).reshape(2, *grown[key].shape[3:])
            lens += 1
            rs = dataclasses.replace(
                rs, cache_len=jnp.asarray(lens, jnp.int32))
            rs = pool.flush_full_tails(rs)

        reh = pool.rehydrate(rs)
        for key in reh:
            got = np.asarray(reh[key], np.float32)
            for row in range(2):
                np.testing.assert_array_equal(
                    got[:, row, :lens[row]], grown[key][:, row, :lens[row]],
                    err_msg=key)
        # every crossed boundary allocated exactly L pages per leaf
        crossed = sum((lens[r] // tp) - (start[r] // tp) for r in range(2))
        for key in ("k", "v"):
            assert pool.allocated_pages(key) - before[key] == 2 * crossed

    def test_failed_flush_rehydrates_full_tail_page(self):
        """A ResidencyError inside flush_full_tails strikes when a row's
        just-filled logical page is still unmapped and its data lives ONLY
        in the tail.  Demotion (rehydrate) must splice the FULL tail at that
        page index — zeroing it would silently lose tokens_per_page tokens
        of KV (REVIEW: 'bit-exact demotion' violation)."""
        cache = _dense_cache(L=2, B=2, S=64)
        cb = _calibrate(cache)
        pool = _pool_for(cache, cb)
        tp = pool.geom.tokens_per_page
        start = np.array([tp - 1, tp // 2])        # row 0 one short of a page
        comp, _ = _encode(cache, cb)
        rs = pool.admit_from_wire(comp, jnp.asarray(start, jnp.int32))

        rng = np.random.default_rng(9)
        grown = {k: np.asarray(v, np.float32).copy()
                 for k, v in cache.items()}
        rs = _append_rows(pool, rs, rng, grown)    # row 0's tail is now FULL
        lens = start + 1
        assert int(lens[0]) % tp == 0

        def boom(key, n):
            raise KVP.ResidencyError("injected flush failure")

        orig_alloc, pool._alloc = pool._alloc, boom
        with pytest.raises(KVP.ResidencyError):
            pool.flush_full_tails(rs)
        pool._alloc = orig_alloc

        reh = pool.rehydrate(rs)
        for key in reh:
            got = np.asarray(reh[key], np.float32)
            for row in range(2):
                np.testing.assert_array_equal(
                    got[:, row, :lens[row]], grown[key][:, row, :lens[row]],
                    err_msg=f"{key} row {row}")

    def test_failed_flush_leaves_free_list_intact(self):
        """A flush that fails partway must not leak free-list pages: escape
        overflow is checked for ALL leaves before any allocation, and an
        exhaustion on a later leaf returns the earlier leaves' pages.  The
        pool stays fully usable afterwards (REVIEW)."""
        cache = _dense_cache(L=2, B=2, S=64)
        cb = _calibrate(cache)
        pool = _pool_for(cache, cb)
        g = pool.geom
        tp = g.tokens_per_page
        start = np.array([tp - 1, tp - 1])
        comp, _ = _encode(cache, cb)
        rs = pool.admit_from_wire(comp, jnp.asarray(start, jnp.int32))
        rng = np.random.default_rng(10)
        grown = {k: np.asarray(v, np.float32).copy()
                 for k, v in cache.items()}

        # (a) escape overflow on the LATER leaf ("v"): "k" encodes clean
        # first but must not have allocated anything when "v" raises
        hot = jnp.full((g.n_layers, g.batch, g.leaf("v").m), 1e30,
                       jnp.bfloat16)                # every element escapes
        bad = _append_rows(pool, rs, rng, values={"v": hot})
        free_before = {k: pool.free_pages(k) for k in ("k", "v")}
        with pytest.raises(KVP.ResidencyError, match="escape"):
            pool.flush_full_tails(bad)
        assert {k: pool.free_pages(k) for k in ("k", "v")} == free_before

        # (b) pool exhaustion on the later leaf: "k"'s fresh pages must be
        # returned when "v"'s allocation fails
        rs = _append_rows(pool, rs, rng, grown)
        stash, pool._free["v"] = pool._free["v"], []
        with pytest.raises(KVP.ResidencyError, match="exhausted"):
            pool.flush_full_tails(rs)
        assert pool.free_pages("k") == free_before["k"]
        pool._free["v"] = stash

        # (c) the same flush now succeeds and the pool rehydrates bit-exact
        rs = pool.flush_full_tails(rs)
        lens = start + 1
        reh = pool.rehydrate(rs)
        for key in reh:
            got = np.asarray(reh[key], np.float32)
            for row in range(2):
                np.testing.assert_array_equal(
                    got[:, row, :lens[row]], grown[key][:, row, :lens[row]],
                    err_msg=f"{key} row {row}")

    def test_free_rows_returns_pages(self):
        cache = _dense_cache(L=2, B=2, S=64)
        cb = _calibrate(cache)
        pool = _pool_for(cache, cb)
        comp, _ = _encode(cache, cb)
        pool.admit_from_wire(comp, jnp.asarray([64, 64], jnp.int32))
        held = pool.allocated_pages("k")
        assert held > 0
        pool.free_rows([0])
        assert pool.allocated_pages("k") == held // 2
        pool.free_rows([1])
        assert pool.allocated_pages("k") == 0
        # pool is reusable after a full free
        rs = pool.admit_from_wire(comp, jnp.asarray([64, 32], jnp.int32))
        _assert_cache_equal(pool.rehydrate(rs), cache,
                            jnp.asarray([64, 32]))

    def test_escape_overflow_raises_residency_error(self):
        """A page whose true escape count exceeds its slot budget must NOT
        be admitted silently-lossy: ResidencyError -> engine demotes.

        ~2%% of elements escape: comfortably under the wire's per-chunk cap
        (the stream still arrives compressed) but well over the page-level
        budget (page_elems / ESC_SLOT_PER_ELEMS slots)."""
        cache = _dense_cache(L=1, B=1, S=64)
        rng = np.random.default_rng(13)
        k = np.asarray(cache["k"], np.float32).ravel()
        hot = rng.choice(k.size, size=k.size // 50, replace=False)
        k[hot] = 1e30                              # exponent far out of band
        cache["k"] = jnp.asarray(k.reshape(cache["k"].shape), jnp.bfloat16)
        cb = _calibrate({"v": cache["v"]})         # calibrated without spikes
        pool = _pool_for(cache, cb)
        comp, _ = _encode(cache, cb)
        assert hasattr(comp["k"], "esc_count"), "stream must arrive compressed"
        with pytest.raises(KVP.ResidencyError, match="escape"):
            pool.admit_from_wire(comp, jnp.asarray([64], jnp.int32))

    def test_pool_exhaustion_raises(self):
        cache = _dense_cache(L=2, B=2, S=64)
        cb = _calibrate(cache)
        pool = _pool_for(cache, cb)
        comp, _ = _encode(cache, cb)
        pool.admit_from_wire(comp, jnp.asarray([64, 64], jnp.int32))
        # every page is held; a second admission must exhaust the free-list
        with pytest.raises(KVP.ResidencyError):
            pool.admit_from_wire(comp, jnp.asarray([64, 64], jnp.int32))

    def test_capacity_model_vs_measured(self):
        """bytes_per_token_resident (the DESIGN.md capacity model) matches
        the pool's own page accounting."""
        cache = _dense_cache(L=2, B=2, S=64)
        cb = _calibrate(cache)
        pool = _pool_for(cache, cb)
        g = pool.geom
        for lg in g.leaves:
            got = pool.page_bytes(lg) / g.tokens_per_page
            want = KVP.bytes_per_token_resident(lg.m, g.tokens_per_page,
                                                chunk=g.chunk)
            assert abs(got - want) < 1e-9


# ---------------------------------------------------------------------------
# fused attention over pages
# ---------------------------------------------------------------------------

class TestFusedAttention:
    def _admitted(self, S=64, lens=None, seed=0):
        cfg = get_config("smollm-135m").reduced()
        cache = _dense_cache(L=cfg.num_layers, B=2, S=S,
                             hkv=cfg.num_kv_heads, hd=cfg.head_dim, seed=seed)
        cb = _calibrate(cache)
        pool = _pool_for(cache, cb)
        tp = pool.geom.tokens_per_page
        if lens is None:
            lens = jnp.asarray([S, S - tp // 2], jnp.int32)
        comp, _ = _encode(cache, cb)
        rs = pool.admit_from_wire(comp, lens)
        return cfg, cache, pool, rs, lens

    def test_in_kernel_decode_bit_exact(self):
        """The values the kernel attends over are EXACTLY the rehydrated
        cache: pool pages decoded by the same machinery compare bitwise
        against the original bf16 bit patterns, escapes included."""
        cfg, cache, pool, rs, lens = self._admitted()
        g = pool.geom
        tp = g.tokens_per_page
        for key in ("k", "v"):
            lg = g.leaf(key)
            bits = KVP._decode_pool_pages(rs.leaves[key], lg, g)
            src = np.asarray(jax.lax.bitcast_convert_type(
                cache[key], jnp.uint16)).reshape(
                    lg.shape[0], lg.shape[1], -1)
            table = np.asarray(rs.leaves[key].page_table)
            for (layer, row, p), pid in np.ndenumerate(table):
                if pid < 0:
                    continue
                page = np.asarray(bits[pid], np.uint16)
                want = src[layer, row,
                           p * lg.page_elems:(p + 1) * lg.page_elems]
                np.testing.assert_array_equal(page, want)

    def test_kernel_partials_vs_mirror(self):
        """Fused kernel partials vs an identical-op-order jnp mirror over
        the rehydrated pages (f32 round-off only: pallas dot ordering)."""
        cfg, cache, pool, rs, lens = self._admitted()
        g = pool.geom
        tp = g.tokens_per_page
        B, hkv, hd, H = 2, cfg.num_kv_heads, cfg.head_dim, cfg.num_heads
        grp = H // hkv
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.bfloat16)
        kl, vl = rs.leaves["k"], rs.leaves["v"]
        acc, m, l = SA.paged_gqa_attention(
            q, kl.streams(), vl.streams(), kl.page_table[0],
            vl.page_table[0], rs.cache_len, exponents=g.exponents,
            chunk=g.chunk, tokens_per_page=tp, hkv=hkv, interpret=True)

        reh = pool.rehydrate(rs)
        kf, vf = reh["k"][0], reh["v"][0]
        scale = 1.0 / np.sqrt(hd)
        n_full = np.asarray(lens) // tp
        qr = q.reshape(B, 1, hkv, grp, hd).astype(jnp.float32)
        accs, ms, ls = [], [], []
        for b in range(B):
            mm = jnp.full((1, hkv, grp), SA.NEG_INF, jnp.float32)
            ll = jnp.zeros((1, hkv, grp), jnp.float32)
            aa = jnp.zeros((1, hkv, grp, hd), jnp.float32)
            for p in range(int(n_full[b])):
                kt = kf[b, p * tp:(p + 1) * tp].astype(jnp.float32)
                vt = vf[b, p * tp:(p + 1) * tp].astype(jnp.float32)
                s = jnp.einsum("qhgd,thd->qhgt", qr[b], kt,
                               preferred_element_type=jnp.float32) * scale
                m_new = jnp.maximum(mm, s.max(axis=-1))
                pexp = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(mm - m_new)
                ll = ll * corr + pexp.sum(axis=-1)
                aa = aa * corr[..., None] + jnp.einsum(
                    "qhgt,thd->qhgd", pexp.astype(jnp.bfloat16),
                    vt.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
                mm = m_new
            accs.append(aa.reshape(1, H, hd))
            ms.append(mm.reshape(1, H))
            ls.append(ll.reshape(1, H))
        np.testing.assert_array_equal(np.asarray(m), np.stack(ms))
        np.testing.assert_allclose(np.asarray(l), np.stack(ls),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(acc), np.stack(accs),
                                   rtol=1e-5, atol=1e-6)

    def test_gqa_kernel_v_geometry_differs(self):
        """dv != head_dim: V pages carry their OWN page_chunks and escape
        cap.  The kernel must consume V's geometry for the V streams —
        reusing K's reads the wrong block shape / past the V escape arrays
        (REVIEW)."""
        L, B, S, hkv, hd, dv = 1, 2, 128, 2, 32, 16
        rng = np.random.default_rng(21)
        cache = {
            "k": jnp.asarray(rng.standard_normal((L, B, S, hkv, hd)),
                             jnp.bfloat16),
            "v": jnp.asarray(rng.standard_normal((L, B, S, hkv, dv)),
                             jnp.bfloat16),
        }
        cb = _calibrate(cache)
        pool = _pool_for(cache, cb, page_bytes=8192)
        g = pool.geom
        assert g.leaf("k").page_chunks != g.leaf("v").page_chunks
        assert g.leaf("k").escape_cap != g.leaf("v").escape_cap
        tp = g.tokens_per_page
        lens = jnp.asarray([S, S - tp], jnp.int32)
        comp, _ = _encode(cache, cb)
        rs = pool.admit_from_wire(comp, lens)
        _assert_cache_equal(pool.rehydrate(rs), cache, lens)

        H, grp = 2 * hkv, 2
        q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), jnp.bfloat16)
        kl, vl = rs.leaves["k"], rs.leaves["v"]
        acc, m, l = SA.paged_gqa_attention(
            q, kl.streams(), vl.streams(), kl.page_table[0],
            vl.page_table[0], rs.cache_len, exponents=g.exponents,
            chunk=g.chunk, tokens_per_page=tp, hkv=hkv, interpret=True)
        assert acc.shape == (B, 1, H, dv)

        reh = pool.rehydrate(rs)
        kf, vf = reh["k"][0], reh["v"][0]
        scale = 1.0 / np.sqrt(hd)
        n_full = np.asarray(lens) // tp
        qr = q.reshape(B, 1, hkv, grp, hd).astype(jnp.float32)
        for b in range(B):
            mm = jnp.full((1, hkv, grp), SA.NEG_INF, jnp.float32)
            ll = jnp.zeros((1, hkv, grp), jnp.float32)
            aa = jnp.zeros((1, hkv, grp, dv), jnp.float32)
            for p in range(int(n_full[b])):
                kt = kf[b, p * tp:(p + 1) * tp].astype(jnp.float32)
                vt = vf[b, p * tp:(p + 1) * tp].astype(jnp.float32)
                # the kernel's op order: one (g, hd) x (Tp, hd)^T dot per head
                s = jnp.stack([jax.lax.dot_general(
                    qr[b, 0, hh], kt[:, hh], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                    for hh in range(hkv)])[None] * scale
                m_new = jnp.maximum(mm, s.max(axis=-1))
                pexp = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(mm - m_new)
                ll = ll * corr + pexp.sum(axis=-1)
                aa = aa * corr[..., None] + jnp.einsum(
                    "qhgt,thd->qhgd", pexp.astype(jnp.bfloat16),
                    vt.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
                mm = m_new
            np.testing.assert_array_equal(
                np.asarray(m[b]), np.asarray(mm.reshape(1, H)))
            np.testing.assert_allclose(
                np.asarray(l[b]), np.asarray(ll.reshape(1, H)),
                rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(
                np.asarray(acc[b]), np.asarray(aa.reshape(1, H, dv)),
                rtol=1e-5, atol=1e-6)

    def test_one_pallas_call_per_layer(self):
        """Resident decode step structure: exactly one ``pallas_call`` in
        the per-layer scan body, and no codec decode primitives."""
        cfg, cache, pool, rs, lens = self._admitted()
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        tok = jnp.zeros((2, 1), jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda p, t, s: M.resident_decode_step(p, t, s, cfg,
                                                   interpret=True)
        )(params, tok, rs)
        txt = str(jaxpr)
        assert txt.count("pallas_call") == 1  # one per scanned layer

    def test_decode_step_matches_raw_across_page_boundary(self):
        """Same-token resident vs raw decode: logits agree to bf16
        accumulation tolerance across steps that cross a page boundary
        (raw decode_attention accumulates in bf16, the fused path in f32).
        The cache is model-generated (a real prefill) — a synthetic +-4
        sigma cache amplifies the accumulation-order difference through
        softmax far beyond anything a trained/initialized model produces."""
        cfg = get_config("smollm-135m").reduced()
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(11)
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 24)),
                           jnp.int32)
        lens = jnp.asarray([24, 17], jnp.int32)
        _, st0 = M.prefill(params, {"tokens": toks, "lengths": lens}, cfg,
                           max_seq=64)
        cb = _calibrate(st0.cache)
        pool = _pool_for(st0.cache, cb)
        tp = pool.geom.tokens_per_page
        comp, _ = _encode(st0.cache, cb)
        rs = pool.admit_from_wire(comp, st0.cache_len)
        st_raw, st_res = st0, rs
        for step in range(tp // 2 + 2):            # row 1 crosses a boundary
            tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 1)),
                              jnp.int32)
            lr, st_raw = M.decode_step(params, tok, st_raw, cfg)
            lc, st_res = M.resident_decode_step(params, tok, st_res, cfg,
                                                interpret=True)
            a = np.asarray(lr, np.float32)
            b = np.asarray(lc, np.float32)
            # raw decode_attention accumulates probs*v in bf16; the fused
            # path accumulates in f32 — on a synthetic +-4 sigma bf16 cache
            # the layered amplification reaches a few percent of the scale
            scale = max(1e-3, float(np.abs(a).max()))
            assert float(np.abs(a - b).max()) < 0.12 * scale, f"step {step}"
            st_res = pool.flush_full_tails(st_res)


class TestFusedAttentionMLA:
    def test_mla_decode_matches_raw(self):
        """Absorbed-MLA resident decode vs mla_decode over the rehydrated
        cache, across a page boundary."""
        cfg = get_config("minicpm3-4b").reduced()
        from repro.models.kvcache import DecodeState, init_cache
        params = M.init_params(cfg, jax.random.PRNGKey(1))
        rng = np.random.default_rng(5)
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 40)), jnp.int32)
        lens = jnp.asarray([40, 29], jnp.int32)
        _, st0 = M.prefill(params, {"tokens": toks, "lengths": lens}, cfg,
                           max_seq=256)
        cb = _calibrate(st0.cache)
        pool = _pool_for(st0.cache, cb, page_bytes=4096)
        tp = pool.geom.tokens_per_page
        assert 256 % tp == 0
        comp, _ = _encode(st0.cache, cb)
        rs = pool.admit_from_wire(comp, st0.cache_len)
        _assert_cache_equal(pool.rehydrate(rs), st0.cache, lens)

        st_raw, st_res = st0, rs
        steps = tp - 40 + 3 if tp >= 40 else 3     # row 0 crosses a boundary
        for step in range(min(steps, 16)):
            tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 1)),
                              jnp.int32)
            lr, st_raw = M.decode_step(params, tok, st_raw, cfg)
            lc, st_res = M.resident_decode_step(params, tok, st_res, cfg,
                                                interpret=True)
            a = np.asarray(lr, np.float32)
            b = np.asarray(lc, np.float32)
            # raw decode_attention accumulates probs*v in bf16; the fused
            # path accumulates in f32 — on a synthetic +-4 sigma bf16 cache
            # the layered amplification reaches a few percent of the scale
            scale = max(1e-3, float(np.abs(a).max()))
            assert float(np.abs(a - b).max()) < 0.12 * scale, f"step {step}"
            st_res = pool.flush_full_tails(st_res)

    def test_mla_kernel_per_leaf_escape_caps(self):
        """kv_lora_rank != qk_rope_head_dim gives the two MLA leaves
        different page_chunks AND escape caps; the kernel must use each
        leaf's own cap for its escape BlockSpecs/unroll — taking both from
        ckv reads past the krope escape arrays (REVIEW)."""
        L, B, S, r, p_dim, H = 1, 2, 128, 128, 32, 4
        rng = np.random.default_rng(23)
        cache = {
            "ckv": jnp.asarray(rng.standard_normal((L, B, S, r)),
                               jnp.bfloat16),
            "krope": jnp.asarray(rng.standard_normal((L, B, S, p_dim)),
                                 jnp.bfloat16),
        }
        cb = _calibrate(cache)
        pool = _pool_for(cache, cb, page_bytes=16384)
        g = pool.geom
        assert g.leaf("ckv").page_chunks != g.leaf("krope").page_chunks
        assert g.leaf("ckv").escape_cap != g.leaf("krope").escape_cap
        tp = g.tokens_per_page
        lens = jnp.asarray([S, S - tp], jnp.int32)
        comp, _ = _encode(cache, cb)
        rs = pool.admit_from_wire(comp, lens)
        _assert_cache_equal(pool.rehydrate(rs), cache, lens)

        q_lat = jnp.asarray(rng.standard_normal((B, 1, H, r)), jnp.bfloat16)
        q_rope = jnp.asarray(rng.standard_normal((B, 1, H, p_dim)),
                             jnp.bfloat16)
        scale = 1.0 / np.sqrt(r + p_dim)
        cl, rl = rs.leaves["ckv"], rs.leaves["krope"]
        acc, m, l = SA.paged_mla_attention(
            q_lat, q_rope, cl.streams(), rl.streams(), cl.page_table[0],
            rl.page_table[0], rs.cache_len, exponents=g.exponents,
            chunk=g.chunk, tokens_per_page=tp, scale=scale, interpret=True)
        assert acc.shape == (B, 1, H, r)

        reh = pool.rehydrate(rs)
        cf, rf = reh["ckv"][0], reh["krope"][0]
        n_full = np.asarray(lens) // tp
        qlf = q_lat.astype(jnp.float32)
        qrf = q_rope.astype(jnp.float32)
        for b in range(B):
            mm = jnp.full((1, H), SA.NEG_INF, jnp.float32)
            ll = jnp.zeros((1, H), jnp.float32)
            aa = jnp.zeros((1, H, r), jnp.float32)
            for p in range(int(n_full[b])):
                ct = cf[b, p * tp:(p + 1) * tp].astype(jnp.float32)
                rt = rf[b, p * tp:(p + 1) * tp].astype(jnp.float32)
                s = (jnp.einsum("qhr,tr->qht", qlf[b], ct,
                                preferred_element_type=jnp.float32)
                     + jnp.einsum("qhp,tp->qht", qrf[b], rt,
                                  preferred_element_type=jnp.float32)) * scale
                m_new = jnp.maximum(mm, s.max(axis=-1))
                pexp = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(mm - m_new)
                ll = ll * corr + pexp.sum(axis=-1)
                aa = aa * corr[..., None] + jnp.einsum(
                    "qht,tr->qhr", pexp.astype(jnp.bfloat16),
                    ct.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
                mm = m_new
            np.testing.assert_array_equal(np.asarray(m[b]), np.asarray(mm))
            np.testing.assert_allclose(np.asarray(l[b]), np.asarray(ll),
                                       rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(np.asarray(acc[b]), np.asarray(aa),
                                       rtol=1e-5, atol=1e-6)

    def test_mla_one_pallas_call_per_layer(self):
        cfg = get_config("minicpm3-4b").reduced()
        params = M.init_params(cfg, jax.random.PRNGKey(1))
        rng = np.random.default_rng(5)
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 40)), jnp.int32)
        _, st0 = M.prefill(params, {"tokens": toks}, cfg, max_seq=256)
        cb = _calibrate(st0.cache)
        pool = _pool_for(st0.cache, cb, page_bytes=4096)
        comp, _ = _encode(st0.cache, cb)
        rs = pool.admit_from_wire(comp, st0.cache_len)
        jaxpr = jax.make_jaxpr(
            lambda p, t, s: M.resident_decode_step(p, t, s, cfg,
                                                   interpret=True)
        )(params, jnp.zeros((2, 1), jnp.int32), rs)
        assert str(jaxpr).count("pallas_call") == 1


# ---------------------------------------------------------------------------
# engine + scheduler integration
# ---------------------------------------------------------------------------

class TestEngineResident:
    def _setup(self, arch="smollm-135m", seed=0):
        cfg = get_config(arch).reduced()
        params = M.init_params(cfg, jax.random.PRNGKey(seed))
        rng = np.random.default_rng(seed)
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 24)),
                           jnp.int32)
        _, st = M.prefill(params, {"tokens": toks}, cfg, max_seq=32)
        cb = _calibrate(st.cache)
        return cfg, params, {"tokens": toks}, cb

    def test_resident_generate_serves(self):
        cfg, params, batch, cb = self._setup()
        eng = DisaggregatedEngine(cfg, params, cb, resident="compressed",
                                  page_bytes=2048)
        out = eng.generate(batch, num_steps=6, max_seq=64)
        assert out.shape == (2, 7)             # first token + 6 steps
        assert eng.stats.resident_admits == 1
        assert eng.stats.resident_demotions == 0
        assert eng.stats.resident_ratio > 0

    def test_resident_generate_default_max_seq_stays_resident(self):
        """generate() without max_seq must derive a page-aligned default
        (prompt + first token + steps, rounded up) — prefill's raw-prompt
        default is not page-aligned and used to silently demote every
        batch that didn't pass max_seq explicitly."""
        cfg, params, batch, cb = self._setup()
        eng = DisaggregatedEngine(cfg, params, cb, resident="compressed",
                                  page_bytes=2048)
        out = eng.generate(batch, num_steps=6)   # no max_seq on purpose
        assert out.shape == (2, 7)
        assert eng.stats.resident_admits == 1
        assert eng.stats.resident_demotions == 0

    def test_demotion_is_bit_identical_to_raw(self):
        """A stream the pool cannot admit (here: an out-of-band codebook
        making every element escape) demotes to raw residency; the served
        tokens must then be BIT-identical to the raw-resident engine."""
        cfg, params, batch, _ = self._setup()
        bad = cbm.Codebook(fmt="bf16", exponents=tuple(range(16)))
        eng_res = DisaggregatedEngine(cfg, params, bad, resident="compressed",
                                      page_bytes=2048)
        eng_raw = DisaggregatedEngine(cfg, params, bad, resident="raw")
        out_res = eng_res.generate(batch, num_steps=6, max_seq=64)
        out_raw = eng_raw.generate(batch, num_steps=6, max_seq=64)
        assert eng_res.stats.resident_demotions == 1
        np.testing.assert_array_equal(np.asarray(out_res),
                                      np.asarray(out_raw))

    def test_flush_failure_midstream_matches_raw_tokens(self):
        """A ResidencyError raised by flush_full_tails MID-GENERATION (the
        just-filled page's data still only in the tail) demotes losslessly:
        the whole served sequence must match the raw-resident path.  Before
        the rehydrate fix, demotion at a flush boundary zeroed a full page
        of KV and decode silently continued on garbage (REVIEW, high)."""
        from repro.serving import decode as D

        cfg = get_config("smollm-135m").reduced()
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(17)
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 24)),
                           jnp.int32)
        _, st0 = M.prefill(params, {"tokens": toks}, cfg, max_seq=64)
        cb = _calibrate(st0.cache)
        pool = _pool_for(st0.cache, cb)
        tp = pool.geom.tokens_per_page
        comp, _ = _encode(st0.cache, cb)
        rs = pool.admit_from_wire(comp, st0.cache_len)

        # fail the FIRST flush that actually has a full unmapped tail page
        orig = pool.flush_full_tails
        state = {"failed": False}

        def failing(st):
            lens_ = np.asarray(st.cache_len)
            table0 = np.asarray(
                st.leaves[pool.geom.leaves[0].key].page_table)
            needs = any(
                lens_[b] > 0 and lens_[b] % tp == 0
                and table0[0, b, lens_[b] // tp - 1] < 0
                for b in range(lens_.shape[0]))
            if needs and not state["failed"]:
                state["failed"] = True
                raise KVP.ResidencyError("injected flush failure")
            return orig(st)

        pool.flush_full_tails = failing
        first = jnp.asarray(rng.integers(0, cfg.vocab_size, (2,)),
                            jnp.int32)
        n = tp + 4                                 # crosses >=1 boundary
        toks_res, _, demoted = D.resident_decode_loop(
            params, first, rs, pool, cfg, n)
        assert demoted and state["failed"]
        toks_raw, _ = D.decode_loop(params, first, st0, cfg, n)
        np.testing.assert_array_equal(np.asarray(toks_res),
                                      np.asarray(toks_raw))

    def test_hbm_derived_decode_slots(self):
        """SchedulerConfig.derived_decode_slots: the compressed-resident
        footprint buys >= 1.25x the slots of raw at the same HBM budget."""
        m = 2 * 2 * 8 * 64                       # L * kv * Hkv * hd
        raw_bpt = 2.0 * m
        comp_bpt = KVP.bytes_per_token_resident(m, 1024)
        base = dict(hbm_bytes_per_worker=1 << 30, slot_tokens=4096)
        raw = SchedulerConfig(resident_bytes_per_token=raw_bpt, **base)
        comp = SchedulerConfig(resident_bytes_per_token=comp_bpt, **base)
        s_raw, s_comp = raw.derived_decode_slots(), comp.derived_decode_slots()
        assert s_comp / s_raw >= 1.25
        # the fleet multiplies; the flat budget survives when unset
        two = SchedulerConfig(resident_bytes_per_token=comp_bpt,
                              n_decode_workers=2, **base)
        assert two.derived_decode_slots() == 2 * s_comp
        assert SchedulerConfig(max_decode_slots=7).derived_decode_slots() == 7
        with pytest.raises(ValueError):
            SchedulerConfig(hbm_bytes_per_worker=1 << 30).derived_decode_slots()
        # a budget that fits no slot must raise, not silently floor to 1
        # per worker (that would over-commit the stated HBM budget)
        with pytest.raises(ValueError, match="fits no"):
            SchedulerConfig(hbm_bytes_per_worker=1024,
                            resident_bytes_per_token=raw_bpt,
                            slot_tokens=4096).derived_decode_slots()


# ---------------------------------------------------------------------------
# ragged (mixed-length) batches — satellite of ISSUE 8
# ---------------------------------------------------------------------------

class TestRaggedLengths:
    def test_prefill_scores_each_row_at_its_own_length(self):
        """Batched ragged prefill == each row prefilled solo: the logits
        must come from every row's OWN last real token, and the decode
        continuation from its own cache_len — not the padded length."""
        cfg = get_config("smollm-135m").reduced()
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(2)
        full = rng.integers(0, cfg.vocab_size, (2, 20))
        lens = np.array([20, 13])
        toks = full.copy()
        toks[1, 13:] = 0                          # right-padding
        logits, st = M.prefill(
            params, {"tokens": jnp.asarray(toks, jnp.int32),
                     "lengths": jnp.asarray(lens, jnp.int32)},
            cfg, max_seq=32)
        np.testing.assert_array_equal(np.asarray(st.cache_len), lens)

        for row in range(2):
            solo = jnp.asarray(full[row:row + 1, :lens[row]], jnp.int32)
            lr, sr = M.prefill(params, {"tokens": solo}, cfg, max_seq=32)
            a = np.asarray(logits[row], np.float32)
            b = np.asarray(lr[0], np.float32)
            np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)

        # decode continues from per-row lengths: batched next tokens match
        # the solo continuations
        tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        l2, _ = M.decode_step(params, tok0[:, None], st, cfg)
        for row in range(2):
            solo = jnp.asarray(full[row:row + 1, :lens[row]], jnp.int32)
            lr, sr = M.prefill(params, {"tokens": solo}, cfg, max_seq=32)
            ls, _ = M.decode_step(
                params, jnp.argmax(lr, -1).astype(jnp.int32)[:, None], sr, cfg)
            np.testing.assert_allclose(
                np.asarray(l2[row], np.float32),
                np.asarray(ls[0], np.float32), rtol=2e-2, atol=2e-2)

    def test_prefill_rejects_ragged_recurrent_families(self):
        cfg = get_config("mamba2-2.7b").reduced()
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        toks = jnp.zeros((2, 8), jnp.int32)
        with pytest.raises(ValueError):
            M.prefill(params, {"tokens": toks,
                               "lengths": jnp.asarray([8, 5])}, cfg,
                      max_seq=16)

    def test_valid_mask(self):
        from repro.models.kvcache import DecodeState
        cache = _dense_cache(L=1, B=2, S=8)
        st = DecodeState(cache=cache, cache_len=jnp.asarray([8, 3]))
        mask = np.asarray(st.valid_mask())
        assert mask.shape == (2, 8)
        assert mask[0].all() and mask[1, :3].all() and not mask[1, 3:].any()
