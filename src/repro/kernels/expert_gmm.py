"""Grouped matmuls over the experts one chip holds (Pallas TPU).

An expert layer that holds G of its experts sorts the routes to them by
expert, rows ``[start_g, start_g + sizes[g])`` of ``lhs`` for expert g,
and leaves the routes to experts held elsewhere in the rows past
``sum(sizes)``. Three products train it:

- ``gmm``: ``out[r] = lhs[r] @ rhs[g(r)]`` (forward, and with
  ``transpose_rhs`` the input gradient ``dout @ rhs[g]^T``);
- ``tgmm``: ``drhs[g] = lhs[rows of g]^T @ dout[rows of g]`` (the weight
  gradient).

Both visit only the row tiles that hold a held route: no compute is
spent on routes to absent experts, whose output rows read zero, and no
route is dropped. The row tiles of each expert come from the
group metadata of the megablox kernels shipped with JAX
(``jax.experimental.pallas.ops.tpu.megablox``), whose ``gmm``/``tgmm``
these kernels follow; here every argument carries a leading station
axis S, each station with its own routes and weights, so the trainer's
vmap over stations folds into one kernel call
(``jax.custom_batching.custom_vmap``) rather than a loop of calls. The
``pallas_call`` names ``expert_gmm`` and ``expert_tgmm`` are what the
device trace shows.

``grouped_matmul`` is the differentiable entry (a ``custom_vjp`` over
the two kernels); ``grouped_matmul_xla`` is the same arithmetic in XLA,
which the callers take off the TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

VMEM_LIMIT = 64 * 1024 * 1024


def _tile(dim: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``cap``; the whole dim where none does (small test shapes)."""
    for t in range(cap - cap % 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def _rows_tile(m: int, tm: int) -> int:
    return tm if m >= tm else -(-m // 8) * 8


def _metadata(sizes, m: int, tm: int, visit_empty: bool):
    """Per station: the row offsets of the G groups, the group and row
    tile of each grid step, and how many grid steps are live."""
    g = sizes.shape[-1]

    def one(gs):
        (offsets, gids, mtids), n = make_group_metadata(
            group_sizes=gs, m=m, tm=tm, start_group=jnp.int32(0),
            num_nonzero_groups=g, visit_empty_groups=visit_empty)
        return offsets, gids, mtids, n.astype(jnp.int32)
    return jax.vmap(one)(sizes.astype(jnp.int32))


def _live(t, n):
    """The grid step that step ``t`` loads: itself while live, else the
    last live one, so a dead step fetches nothing new."""
    return jnp.minimum(t, jnp.maximum(n - 1, 0))


def _gmm_call(lhs, rhs, sizes, *, transpose_rhs: bool, tiles: tuple,
              precision, interpret: bool):
    """(S, M, K) x (S, G, K, N) -> (S, M, N) in the layout above; rows
    past ``sum(sizes)`` are left unwritten."""
    s, m, k = lhs.shape
    n = rhs.shape[2] if transpose_rhs else rhs.shape[3]
    tm, tk, tn = tiles
    offsets, gids, mtids, live = _metadata(sizes, m, tm, visit_empty=False)
    steps = gids.shape[1]
    tiles_k, tiles_n = k // tk, n // tn

    def kernel(offsets, gids, mtids, live, lhs_ref, rhs_ref, out_ref, acc):
        st, t, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)

        @pl.when(ki == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(t < live[st])
        def _():
            dims = (((1,), (1,)), ((), ())) if transpose_rhs else (
                ((1,), (0,)), ((), ()))
            acc[...] += jax.lax.dot_general(
                lhs_ref[...], rhs_ref[...], dims, precision=precision,
                preferred_element_type=jnp.float32)

            @pl.when(ki == tiles_k - 1)
            def _():
                grp = gids[st, t]
                rows = (jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
                        + mtids[st, t] * tm)
                mine = ((rows >= offsets[st, grp])
                        & (rows < offsets[st, grp + 1]))
                out_ref[...] = jnp.where(mine, acc[...],
                                         out_ref[...].astype(jnp.float32)
                                         ).astype(out_ref.dtype)

    def lhs_map(st, ni, t, ki, offsets, gids, mtids, live):
        return st, mtids[st, _live(t, live[st])], ki

    def rhs_map(st, ni, t, ki, offsets, gids, mtids, live):
        grp = gids[st, _live(t, live[st])]
        return (st, grp, ni, ki) if transpose_rhs else (st, grp, ki, ni)

    def out_map(st, ni, t, ki, offsets, gids, mtids, live):
        return st, mtids[st, _live(t, live[st])], ni

    rhs_block = (None, None, tn, tk) if transpose_rhs else (
        None, None, tk, tn)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((s, m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec((None, tm, tk), lhs_map),
                      pl.BlockSpec(rhs_block, rhs_map)],
            out_specs=pl.BlockSpec((None, tm, tn), out_map),
            grid=(s, tiles_n, steps, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="expert_gmm",
    )(offsets, gids, mtids, live, lhs, rhs)


def _tgmm_call(lhs, dout, sizes, *, tiles: tuple, precision,
               interpret: bool):
    """(S, M, K) and (S, M, N) -> (S, G, K, N): each held expert's
    ``lhs^T @ dout`` over its own rows; an expert with no rows reads 0."""
    s, m, k = lhs.shape
    n = dout.shape[2]
    g = sizes.shape[1]
    tm, tk, tn = tiles
    offsets, gids, mtids, live = _metadata(sizes, m, tm, visit_empty=True)
    steps = gids.shape[1]

    def kernel(offsets, gids, mtids, live, lhs_ref, dout_ref, out_ref, acc):
        st, t = pl.program_id(0), pl.program_id(3)
        grp = gids[st, t]
        first = (t == 0) | (gids[st, jnp.maximum(t - 1, 0)] != grp)

        @pl.when(first)
        def _():
            acc[...] = jnp.zeros_like(acc)

        rows_of_grp = offsets[st, grp + 1] - offsets[st, grp]

        @pl.when((t < live[st]) & (rows_of_grp > 0))
        def _():
            rows = (jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
                    + mtids[st, t] * tm)
            mine = ((rows >= offsets[st, grp])
                    & (rows < offsets[st, grp + 1]))
            x = jnp.where(mine, lhs_ref[...], 0)
            y = jnp.where(mine, dout_ref[...], 0)
            acc[...] += jax.lax.dot(x.swapaxes(0, 1), y,
                                    precision=precision,
                                    preferred_element_type=jnp.float32)

        last = (t == steps - 1) | (gids[st, jnp.minimum(t + 1, steps - 1)]
                                   != grp)

        @pl.when((t < live[st]) & (last | (t == live[st] - 1)))
        def _():
            out_ref[...] = acc[...].astype(out_ref.dtype)

    def lhs_map(st, ni, kk, t, offsets, gids, mtids, live):
        return st, mtids[st, _live(t, live[st])], kk

    def dout_map(st, ni, kk, t, offsets, gids, mtids, live):
        return st, mtids[st, _live(t, live[st])], ni

    def out_map(st, ni, kk, t, offsets, gids, mtids, live):
        return st, gids[st, _live(t, live[st])], kk, ni

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((s, g, k, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec((None, tm, tk), lhs_map),
                      pl.BlockSpec((None, tm, tn), dout_map)],
            out_specs=pl.BlockSpec((None, None, tk, tn), out_map),
            grid=(s, n // tn, k // tk, steps),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="expert_tgmm",
    )(offsets, gids, mtids, live, lhs, dout)


def _padded_rows(m: int, tm: int) -> int:
    return -(-m // tm) * tm


def _with_rows(fn, m_args, tm):
    """Run ``fn`` on its station-stacked args with the row axis of the
    first ``m_args`` padded to a multiple of ``tm``."""
    def run(*args):
        m = args[0].shape[1]
        mp = _padded_rows(m, tm)
        if mp != m:
            args = [jnp.pad(a, ((0, 0), (0, mp - m), (0, 0)))
                    if i < m_args else a for i, a in enumerate(args)]
        return fn(*args), m
    return run


def _stationwise(fn):
    """``fn`` takes and returns arrays with a leading station axis; the
    result takes a vmap over stations (or over anything else) by folding
    the mapped axis into that station axis, so any nesting of vmaps is
    one kernel call."""
    @jax.custom_batching.custom_vmap
    def f(*args):
        return fn(*args)

    @f.def_vmap
    def rule(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        s = args[0].shape[1]
        out = f(*[a.reshape((axis_size * a.shape[1],) + a.shape[2:])
                  for a in args])
        return out.reshape((axis_size, s) + out.shape[1:]), True
    return f


@functools.lru_cache(maxsize=None)
def _kernels(tm: int, cap: int, precision, interpret: bool):
    """The three station-stacked products at these tile caps and
    precision."""
    def gmm(transpose_rhs):
        def fn(lhs, rhs, sizes):
            k = lhs.shape[2]
            n = rhs.shape[2] if transpose_rhs else rhs.shape[3]
            tiles = (_rows_tile(lhs.shape[1], tm), _tile(k, cap),
                     _tile(n, cap))
            out, m = _with_rows(functools.partial(
                _gmm_call, transpose_rhs=transpose_rhs, tiles=tiles,
                precision=precision, interpret=interpret), 1,
                tiles[0])(lhs, rhs, sizes)
            out = out[:, :m]
            rows = jnp.arange(m)[None, :, None]
            # rows past the held routes were never written
            return jnp.where(rows < sizes.sum(-1)[:, None, None], out, 0)
        return _stationwise(fn)

    def tgmm(lhs, dout, sizes):
        tiles = (_rows_tile(lhs.shape[1], tm), _tile(lhs.shape[2], cap),
                 _tile(dout.shape[2], cap))
        out, _ = _with_rows(functools.partial(
            _tgmm_call, tiles=tiles, precision=precision,
            interpret=interpret), 2, tiles[0])(lhs, dout, sizes)
        return out
    return gmm(False), gmm(True), _stationwise(tgmm)


def _one(f, *args):
    """``f`` on one station's args (the station axis added, then taken)."""
    return f(*[a[None] for a in args])[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _grouped(lhs, rhs, sizes, tm, cap, precision, interpret):
    fwd, _, _ = _kernels(tm, cap, precision, interpret)
    return _one(fwd, lhs, rhs, sizes)


def _grouped_fwd(lhs, rhs, sizes, tm, cap, precision, interpret):
    return (_grouped(lhs, rhs, sizes, tm, cap, precision, interpret),
            (lhs, rhs, sizes))


def _grouped_bwd(tm, cap, precision, interpret, res, dout):
    lhs, rhs, sizes = res
    _, dgmm, tgmm = _kernels(tm, cap, precision, interpret)
    dlhs = _one(dgmm, dout, rhs, sizes)
    drhs = _one(tgmm, lhs, dout, sizes)
    return dlhs, drhs, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs, rhs, sizes, *, precision=None, tm: int = 512,
                   cap: int = 1024, interpret: bool = False):
    """``lhs`` (M, K) routes sorted by held expert, ``rhs`` (G, K, N) the
    held experts' weights, ``sizes`` (G,) int32 routes per held expert
    -> (M, N): ``lhs[r] @ rhs[g(r)]``, and 0 in the rows past
    ``sum(sizes)``. Differentiable in ``lhs`` and ``rhs``; a vmap over
    stations folds into the kernels' station axis. ``precision`` is the
    products' (forward and both gradients): ``None``, the backend's
    default (one bfloat16 pass for float32 operands on the TPU), or
    ``Precision.HIGHEST``, a float32 contraction; ``tm`` is the row
    tile, ``cap`` the largest contraction and output tile."""
    return _grouped(lhs, rhs, sizes.astype(jnp.int32), tm, cap, precision,
                    interpret)


def grouped_matmul_xla(lhs, rhs, sizes, precision=None):
    """The same product in XLA, differentiated and vmapped by JAX: each
    row spread into its expert's block of a (M, G x K) matrix, zero
    elsewhere (and everywhere past ``sum(sizes)``), times the experts'
    weights stacked (G x K, N). Its zeros cost G times the work: it is
    the form for small shapes off the TPU."""
    m, k = lhs.shape
    g = rhs.shape[0]
    ends = jnp.cumsum(sizes)
    rows = jnp.arange(m)[:, None]
    mine = (rows >= ends - sizes) & (rows < ends)             # (M, G)
    spread = jnp.where(mine[:, :, None], lhs[:, None, :], 0)
    return jnp.matmul(spread.reshape(m, g * k), rhs.reshape(g * k, -1),
                      precision=precision)
