"""The grouped product of the dropless expert layer as a Pallas TPU kernel.

:func:`gmm` has ``jax.lax.ragged_dot``'s meaning as
``transformer/moe.dropless_experts`` uses it: rows of ``xs [m, k]`` sorted
by group, ``sizes [G]`` rows a group, ``w [G, k, n]``; row ``r`` of group
``g`` is multiplied by ``w[g]``. Of the ``G`` groups only ``count``, from
group ``at`` on (``at`` may be traced: a layer scan's own step), are
computed: ``w`` is the stack of every expert layer's experts end to end and
a layer's own lie somewhere in it. Rows of no computed group (those past the
last group, those of a group outside ``at .. at + count - 1``) belong to no
product: what the result holds there is whatever the buffer held, and the
caller lets it count for nothing.

The walk. The sorted rows are cut into tiles of ``tm`` rows, the columns into
tiles of ``tn``; the whole contraction ``k`` is one tile. The grid is (column
tiles, visits): column tiles outermost, and inside one the *visits* in sorted
order, a visit being one (row tile, group) pair that share a row. A row tile
that lies inside one group is one visit; one that straddles ``j`` groups is
``j`` consecutive visits, each writing its own rows (the others masked); an
empty group and a row tile past the last group are no visit. There are at
most ``tiles + min(count, m) - 1`` of them, which with ``count`` and the rows
sizes the grid, never ``G``; the visits past the true number repeat the last
one's block indices, so they fetch nothing, and skip the product. The lists
of visits (group, row tile), the groups' row offsets, each group's successor,
``at`` and the number of visits go in as scalar prefetch. The weights stay in
HBM: a touched expert's ``[k, tn]`` tile is copied from ``w[at + group]``
where it lies into one of two VMEM buffers, once a column tile (consecutive
visits of one expert fetch nothing), asked for when the group before it is
first visited; no ``[count, k, n]`` slice of the stack exists.

With ``k`` whole a row's result is one ``[1, k] x [k, tn]`` product a column
tile, accumulated in float32 in the MXU's fixed order and rounded once to
``xs``'s dtype: a row's bits depend on its own values and its expert's
weights, not on the rows beside it, on where its group starts or on the tile
it lands in.

Tile sizes are a function of the static shape (:func:`tiles`), chosen from
the sweep of ``tools/gmm_sweep.py`` on a TPU v5e (docs/transformer.md has
the table). The kernel has no derivative: ``dropless_experts`` is the layer
as it is served.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import pallas_config

# what a call's blocks may take of the core's VMEM (128 MiB on a v5e, of
# which Mosaic scopes 16 MiB to a kernel unless told otherwise): the x, w
# and out tiles twice (the pipeline's two buffers) and the float32 product
VMEM_BUDGET = 40 << 20


def vmem_bytes(tm, tn, k, itemsize):
    return 2 * (tm * k + k * tn + tm * tn) * itemsize + tm * tn * 4


def tiles(m: int, k: int, n: int, itemsize: int = 2):
    """``(tm, tn)`` for ``[m, k]`` rows against groups of ``[k, n]``: the
    static shape alone decides (the sweep: docs/transformer.md).

    Rows: 128, one pass of the MXU. A visit multiplies a whole row tile
    whatever share of it is the group's, so a larger tile buys nothing where
    a group has a few hundred rows (256 and 512 read the same or worse at
    every shape), and below 128 a visit costs the loading of the weights and
    no less. Columns: the widest tile that divides ``n`` and fits the
    budget: the rows are then read once (LFM2's widths) or twice
    (Trinity's), and an expert's tile is one long run of HBM."""
    sub = 32 // itemsize                      # sublanes a tile of this dtype
    tm = min(128, -(-m // sub) * sub)
    cols = [c for c in range(128, n + 1, 128) if n % c == 0] or [n]
    fit = [c for c in cols if vmem_bytes(tm, c, k, itemsize) <= VMEM_BUDGET]
    if not fit:
        raise ValueError(
            f"apex_gmm keeps the whole contraction in one tile: k={k} does "
            f"not fit {VMEM_BUDGET >> 20} MiB of VMEM at {tm} x {cols[0]}")
    return tm, fit[-1]


def _visits(sizes, at, count, m, tm):
    """The walk over the sorted rows: ``(group [V], tile [V], offsets
    [count + 1], after [count], total [1])``, all int32. ``group[v]``
    (counted from ``at``) and ``tile[v]`` are visit ``v``'s;
    ``offsets[g] .. offsets[g + 1]`` are group ``g``'s rows; ``after[g]`` is
    the next group that has rows, or ``count`` + the first that has any when
    ``g`` is the last (the walk then starts again, a column tile on);
    ``total`` is how many visits there are, and the entries of ``group`` and
    ``tile`` past it repeat the last."""
    row_tiles = -(-m // tm)
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    offsets = jax.lax.dynamic_slice(
        jnp.concatenate([jnp.zeros(1, jnp.int32), ends]), (at,), (count + 1,))
    start, end = offsets[:-1], offsets[1:]
    first = start // tm
    spans = jnp.where(end > start, (end - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(spans)                  # visits up to and with group g
    total = upto[-1:]
    v = jnp.minimum(jnp.arange(row_tiles + min(count, m) - 1, dtype=jnp.int32),
                    jnp.maximum(total - 1, 0))
    ids = jnp.arange(count, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(upto[None, :] <= v[:, None], axis=1,
                                dtype=jnp.int32), count - 1)
    # tile = first[group] + (v - visits before the group), without a gather
    tile = v + jnp.sum(jnp.where(group[:, None] == ids[None, :],
                                 (first - upto + spans)[None, :], 0),
                       axis=1, dtype=jnp.int32)
    later = jnp.where((spans > 0)[None, :] & (ids[None, :] > ids[:, None]),
                      ids[None, :], count)
    after = jnp.min(later, axis=1)
    after = jnp.where(after < count, after,
                      count + jnp.min(jnp.where(spans > 0, ids, count)))
    return (group, jnp.clip(tile, 0, row_tiles - 1), offsets,
            after.astype(jnp.int32), total)


def _kernel(tm, tn, count, at_ref, group_ref, tile_ref, offsets_ref,
            after_ref, total_ref, x_ref, w_hbm, o_ref, w_ref, arrived, seen):
    """One visit. The weights stay in HBM and come through two buffers of
    this kernel's own: a group's ``[k, tn]`` tile is asked for when the group
    before it is first visited, so that it arrives under all of that group's
    visits and not under its last one alone, which is all the grid's own
    pipeline would hide it under."""
    j, v = pl.program_id(0), pl.program_id(1)
    g = group_ref[v]

    def fetch(group, column, slot):
        return pltpu.make_async_copy(
            w_hbm.at[at_ref[0] + group, :, pl.ds(column * tn, tn)],
            w_ref.at[slot], arrived.at[slot])

    @pl.when((v == 0) & (j == 0))
    def _():
        seen[0] = 0

        @pl.when(total_ref[0] > 0)
        def _():
            fetch(g, 0, 0).start()

    new = (v == 0) | (g != group_ref[jnp.maximum(v - 1, 0)])

    @pl.when(new & (v < total_ref[0]))
    def _():
        slot = seen[0] % 2
        after = after_ref[g]
        again = after >= count          # the walk is over: a column tile on

        # asked for before this group's own is waited for: the two follow
        # each other in the DMA queue with no pause between them
        @pl.when(~again | (j + 1 < pl.num_programs(0)))
        def _():
            fetch(jnp.where(again, after - count, after),
                  jnp.where(again, j + 1, j), 1 - slot).start()

        fetch(g, j, slot).wait()
        seen[0] += 1

    @pl.when(v < total_ref[0])
    def _():
        y = jnp.dot(x_ref[...], w_ref[(seen[0] - 1) % 2],
                    preferred_element_type=jnp.float32).astype(o_ref.dtype)
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, y.shape, 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        o_ref[...] = jnp.where(mine, y, o_ref[...])


def gmm_tiled(xs, w, sizes, at, count, tm, tn):
    """:func:`gmm` at given tiles (the sweep's entry; ``tn`` divides ``n``,
    ``tm`` is a whole number of sublanes)."""
    return _call(xs, w, sizes, at, count, tm, tn, pallas_config.interpret())


# jitted so that a program lowers the kernel once a shape and not once a
# call: a serving program holds twelve calls of two shapes, and a Mosaic
# kernel takes 75 ms to lower, cached program or not (8 s of an engine's
# warm-up at eight programs). The mode is read outside: a trace is kept
@functools.partial(jax.jit,
                   static_argnames=("count", "tm", "tn", "interpret"))
def _call(xs, w, sizes, at, count, tm, tn, interpret):
    m, k = xs.shape
    n = w.shape[2]
    group, tile, offsets, after, total = _visits(sizes, at, count, m, tm)
    at = jnp.asarray(at, jnp.int32).reshape(1)
    itemsize = jnp.dtype(xs.dtype).itemsize
    return pl.pallas_call(
        functools.partial(_kernel, tm, tn, count),
        out_shape=pallas_config.out_struct((m, n), xs.dtype, xs, w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(n // tn, group.shape[0]),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, at, group, tile, *_:
                             (tile[v], 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, at, group, tile,
                                   *_: (tile[v], j)),
            scratch_shapes=[pltpu.VMEM((2, k, tn), w.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(tm, tn, k, itemsize) + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(count * k * n + m * k * (n // tn) + m * n)
            * itemsize),
        name="apex_gmm",
        interpret=interpret,
    )(at, group, tile, offsets, after, total, xs, w)


def gmm(xs, w, sizes, at=0, count=None):
    """``ys [m, n]`` with ``ys[r] = xs[r] @ w[g]`` for every row ``r`` of a
    group ``g`` in ``at .. at + count - 1`` (all ``G`` without ``count``),
    the rows sorted by group and ``sizes [G]`` of them a group; float32
    accumulation, the result in ``xs``'s dtype. Every other row of ``ys`` is
    unspecified (module docstring)."""
    if xs.dtype != w.dtype:
        raise ValueError(f"rows of {xs.dtype} against weights of {w.dtype}")
    count = w.shape[0] if count is None else count
    m, k = xs.shape
    tm, tn = tiles(m, k, w.shape[2], jnp.dtype(xs.dtype).itemsize)
    return gmm_tiled(xs, w, sizes, at, count, tm, tn)
