"""Grouped matrix product over the routed rows of a mixture-of-experts layer.

``moe_grouped_matmul(lhs, rhs, group_sizes)`` computes, for rows sorted by
group, ``lhs[rows of g] @ rhs[g]`` for every group ``g``: the expert products
of the (token, expert) pairs routed to the experts a chip holds, with the
pairs sorted by expert. ``glu=True`` takes ``rhs`` as ``[gate | up]`` along
its last axis and returns ``silu(lhs @ gate) * (lhs @ up)``, SwiGLU's first
half, so the gate and up products never reach HBM.

The rows are packed (group g starts where group g - 1 ends), so a tile of
``tm`` rows may hold rows of several groups. The grid walks *visits*: one per
(tile, group) pair that shares a row, in row order, and only as many as the
routed rows need: the grid's middle axis is a traced count. A visit loads its
group's weight blocks and stores only the rows of its group; a tile visited by
two groups is visited twice in a row, so its output block stays in VMEM
between them. Untouched experts cost nothing: their weights are never read.

The weights may be a whole stack of layers, (L, G, K, N), with the layer to
use given as ``layer``: a scan over layers then hands the kernel the stack
itself, and no layer's weights are sliced out into a copy first.

Rows past ``sum(group_sizes)`` are left unwritten; callers read only the rows
of the routed pairs. ``grouped_matmul_ref`` is the plain XLA product the
kernel is tested against; the kernel's gradient is that product's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import auto_interpret

# the largest row and weight tiles: a (1024, 1024) bf16 weight block is 2 MiB,
# so the GLU form double-buffers 8 MiB of weights
MAX_TM, MAX_TK, MAX_TN = 256, 1024, 1024
VMEM_LIMIT = 64 * 1024 * 1024


def _tile(dim: int, cap: int) -> int:
    """The largest multiple of 128 at most ``cap`` that divides ``dim``, else
    ``dim`` whole (a full-extent block needs no alignment)."""
    for t in range(min(cap, dim) // 128 * 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def row_tile(m: int) -> int:
    """Row tile for ``m`` rows: a multiple of 16 (a bf16 sublane pair)."""
    return min(MAX_TM, -(-m // 16) * 16)


def visits(group_sizes: jax.Array, m: int, tm: int):
    """The kernel's visit list: ``(group, tile, count)``.

    ``group[v]`` and ``tile[v]`` name the group and the row tile of visit
    ``v``, in row order; ``count`` (1,) is how many visits there are. The
    arrays are sized for the most visits ``m`` rows can need, ``m // tm +
    G - 1``; entries past ``count`` repeat the last visit."""
    g = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    n = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    total = jnp.sum(n)
    cum = jnp.cumsum(n)
    v = jnp.minimum(jnp.arange(m // tm + g - 1), jnp.maximum(total - 1, 0))
    group = jnp.minimum(jnp.searchsorted(cum, v, side="right"), g - 1)
    tile = first[group] + v - (cum - n)[group]
    return (group.astype(jnp.int32), tile.astype(jnp.int32),
            total.reshape(1).astype(jnp.int32))


def _kernel(layer, group, tile, count, bounds, lhs, *refs, tm, nk, glu):
    """One (n block, visit, k block) step: accumulate, store at the last k."""
    if glu:
        gate, up, out, acc_g, acc_u = refs
    else:
        w, out, acc = refs
    v, k = pl.program_id(1), pl.program_id(2)

    @pl.when(v < count[0])
    def _():
        @pl.when(k == 0)
        def _():
            if glu:
                acc_g[...] = jnp.zeros_like(acc_g)
                acc_u[...] = jnp.zeros_like(acc_u)
            else:
                acc[...] = jnp.zeros_like(acc)

        x = lhs[...]
        if glu:
            acc_g[...] += jnp.dot(x, gate[...],
                                  preferred_element_type=jnp.float32)
            acc_u[...] += jnp.dot(x, up[...],
                                  preferred_element_type=jnp.float32)
        else:
            acc[...] += jnp.dot(x, w[...],
                                preferred_element_type=jnp.float32)

        @pl.when(k == nk - 1)
        def _():
            g = group[v]
            rows = tile[v] * tm + jax.lax.broadcasted_iota(
                jnp.int32, out.shape, 0)
            mine = (rows >= bounds[0, g]) & (rows < bounds[1, g])
            if glu:
                a = acc_g[...]
                y = a * jax.nn.sigmoid(a) * acc_u[...]
            else:
                y = acc[...]
            out[...] = jnp.where(mine, y.astype(out.dtype), out[...])


@functools.partial(jax.jit, static_argnames=("glu", "interpret"))
def moe_grouped_matmul(lhs: jax.Array, rhs: jax.Array,
                       group_sizes: jax.Array, layer=None, *,
                       glu: bool = False,
                       interpret: bool | None = None) -> jax.Array:
    """Rows of ``lhs`` (M, K), sorted by group, times their group's ``rhs``
    (G, K, N), or of ``rhs[layer]`` for a stack (L, G, K, N).
    ``group_sizes`` (G,) int32 sums to at most M. With ``glu``, ``rhs`` is
    (..., G, K, 2F) and the result (M, F) is silu(gate) * up."""
    if rhs.ndim == 3:
        rhs, layer = rhs[None], 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    return _gmm(lhs, rhs, group_sizes.astype(jnp.int32), layer, glu,
                interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _gmm(lhs, rhs, group_sizes, layer, glu, interpret):
    """The kernel's call; ``rhs`` (L, G, K, N), ``layer`` (1,) int32."""
    m, kdim = lhs.shape
    _, g, _, n = rhs.shape
    n_out = n // 2 if glu else n
    tm = row_tile(m)
    m_pad = -(-m // tm) * tm
    if m_pad != m:
        lhs = jnp.pad(lhs, ((0, m_pad - m), (0, 0)))
    tk, tn = _tile(kdim, MAX_TK), _tile(n_out, MAX_TN)
    nk, nn = kdim // tk, n_out // tn
    ends = jnp.cumsum(group_sizes)
    bounds = jnp.stack([ends - group_sizes, ends]).astype(jnp.int32)
    group, tile, count = visits(group_sizes, m_pad, tm)
    grid = (nn, jnp.maximum(count[0], 1), nk)

    def rows_of(j, v, k, layer, group, tile, count, bounds):
        return tile[v], k

    def weights(shift):
        return lambda j, v, k, layer, group, tile, count, bounds: (
            layer[0], group[v], k, j + shift)

    def out_of(j, v, k, layer, group, tile, count, bounds):
        return tile[v], j

    w_specs = [pl.BlockSpec((None, None, tk, tn), weights(0))]
    scratch = [pltpu.VMEM((tm, tn), jnp.float32)]
    if glu:
        w_specs.append(pl.BlockSpec((None, None, tk, tn), weights(nn)))
        scratch.append(pltpu.VMEM((tm, tn), jnp.float32))
    kernel = functools.partial(_kernel, tm=tm, nk=nk, glu=glu)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=[pl.BlockSpec((tm, tk), rows_of), *w_specs],
            out_specs=pl.BlockSpec((tm, tn), out_of),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_out), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=auto_interpret(interpret),
        name="moe_grouped_matmul",
    )(layer, group, tile, count, bounds, lhs, *([rhs] * (2 if glu else 1)))
    return out[:m]


def _gmm_fwd(lhs, rhs, group_sizes, layer, glu, interpret):
    return (_gmm(lhs, rhs, group_sizes, layer, glu, interpret),
            (lhs, rhs, group_sizes, layer))


def _gmm_bwd(glu, interpret, res, ct):
    lhs, rhs, group_sizes, layer = res
    _, vjp = jax.vjp(lambda a, b: grouped_matmul_ref(a, b[layer[0]],
                                                      group_sizes, glu=glu),
                     lhs, rhs)
    return (*vjp(ct), None, None)


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul_ref(lhs: jax.Array, rhs: jax.Array,
                       group_sizes: jax.Array, *, glu: bool = False
                       ) -> jax.Array:
    """The same product in XLA, one group at a time over every row, each
    row kept where it belongs (rows past the groups come out zero)."""
    rows = jnp.arange(lhs.shape[0])
    ends = jnp.cumsum(group_sizes)

    def one(acc, xs):
        w, lo, hi = xs
        y = jnp.dot(lhs, w, preferred_element_type=jnp.float32)
        if glu:
            a, u = jnp.split(y, 2, axis=-1)
            y = a * jax.nn.sigmoid(a) * u
        return acc + jnp.where(((rows >= lo) & (rows < hi))[:, None], y, 0.0), None

    n = rhs.shape[2] // 2 if glu else rhs.shape[2]
    acc, _ = jax.lax.scan(one, jnp.zeros((lhs.shape[0], n), jnp.float32),
                          (rhs, ends - group_sizes, ends))
    return acc.astype(lhs.dtype)
