"""The Pallas grouped product `ops/grouped_matmul.gmm` (`apex_gmm`, ISSUE 36),
its body run on the CPU by `force("interpret")`: against `jax.lax.ragged_dot`
and against a numpy loop over the groups, at the walks the kernel has to get
right (empty groups, a group over several row tiles, a row tile over several
groups, rows past the last group, the layer's groups found at a traced `at`
in a longer stack, under `jit` and inside a `lax.scan`), at the serving
cells' widths cut down in rows, and that a row's bits are its own."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import grouped_matmul as gm
from apex_tpu.ops import pallas_config


@pytest.fixture(autouse=True)
def kernel_body_on_the_cpu():
    with pallas_config.force("interpret"):
        yield


def operands(m, k, n, groups, seed=0, dtype=jnp.float32):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kx, (m, k), dtype),
            (jax.random.normal(kw, (groups, k, n)) * k ** -0.5).astype(dtype))


def loop_over_groups(xs, w, sizes, at=0, count=None):
    """`(ys, computed)`: the products in float64, a group at a time, and
    which rows belong to one."""
    xs, w = np.asarray(xs, np.float64), np.asarray(w, np.float64)
    count = w.shape[0] if count is None else count
    starts = np.concatenate([[0], np.cumsum(sizes)])
    ys = np.zeros((xs.shape[0], w.shape[2]))
    computed = np.zeros(xs.shape[0], bool)
    for g in range(at, at + count):
        rows = slice(starts[g], starts[g + 1])
        ys[rows] = xs[rows] @ w[g]
        computed[rows] = True
    return ys, computed


# name: (m, sizes, (tm, tn) or None for the shape's own)
WALKS = {
    "every_group_some_rows": (48, [6, 6, 6, 6, 6, 6, 6, 6], None),
    "empty_groups": (48, [0, 11, 0, 0, 20, 0, 17, 0], None),
    "all_groups_empty": (32, [0, 0, 0, 0], None),
    "one_group_takes_every_row": (40, [0, 0, 40, 0], (16, 128)),
    "a_group_larger_than_a_row_tile": (80, [3, 50, 0, 27], (16, 128)),
    "a_row_tile_over_three_groups": (64, [5, 4, 3, 20, 1, 1, 30], (16, 128)),
    "a_row_tile_over_three_groups_at_128": (300, [40, 50, 30, 100, 0, 80],
                                            None),
    "rows_past_the_last_group": (96, [4, 0, 9, 1], (16, 128)),
    "a_last_tile_that_is_not_whole": (70, [30, 25, 15], (16, 128)),
    "two_column_tiles": (64, [10, 0, 30, 24], (16, 128)),
}


@pytest.mark.parametrize("name", sorted(WALKS))
def test_the_walk_is_ragged_dots_and_the_loop_over_groups(name):
    m, sizes, tiles = WALKS[name]
    xs, w = operands(m, 32, 256 if name == "two_column_tiles" else 128,
                     len(sizes))
    sizes_ = jnp.asarray(sizes, jnp.int32)
    got = np.asarray(gm.gmm(xs, w, sizes_) if tiles is None else
                     gm.gmm_tiled(xs, w, sizes_, 0, len(sizes), *tiles))
    want, computed = loop_over_groups(xs, w, sizes)
    assert computed.sum() == sum(sizes)
    np.testing.assert_allclose(got[computed], want[computed], atol=1e-5)
    ragged = np.asarray(jax.lax.ragged_dot(xs, w, sizes_))
    np.testing.assert_allclose(got[computed], ragged[computed], atol=1e-5)


@pytest.mark.parametrize("at", [0, 8, 16])
def test_a_layers_groups_in_a_stack_of_several_at_a_traced_offset(at):
    """Three layers of eight experts end to end; the layer's own from `at`
    on, `at` a traced value: the grid is sized by the 8 and the rows."""
    sizes = np.zeros(24, np.int32)
    sizes[at:at + 8] = [7, 0, 12, 1, 0, 30, 9, 5]
    xs, w = operands(72, 32, 128, 24)
    got = jax.jit(lambda at: gm.gmm(xs, w, jnp.asarray(sizes), at, 8))(
        jnp.int32(at))
    want, computed = loop_over_groups(xs, w, sizes, at, 8)
    assert computed.sum() == 64
    np.testing.assert_allclose(np.asarray(got)[computed], want[computed],
                               atol=1e-5)
    # the same rows against the layer's experts cut out: the same bits
    alone = gm.gmm(xs, w[at:at + 8], jnp.asarray(sizes[at:at + 8]))
    np.testing.assert_array_equal(np.asarray(got)[computed],
                                  np.asarray(alone)[computed])


def test_groups_of_another_layer_that_have_rows_are_left_alone():
    """`ragged_dot`'s offsets: rows of the groups before `at` come first and
    are no part of the product; the layer's own start after them."""
    sizes = np.asarray([5, 6, 0, 0, 10, 0, 20, 3, 4, 4], np.int32)
    xs, w = operands(64, 32, 128, 10)
    got = np.asarray(gm.gmm_tiled(xs, w, jnp.asarray(sizes), jnp.int32(4), 4,
                                  16, 128))
    want, computed = loop_over_groups(xs, w, sizes, 4, 4)
    assert list(np.flatnonzero(computed)[[0, -1]]) == [11, 43]
    np.testing.assert_allclose(got[computed], want[computed], atol=1e-5)


def test_inside_a_layer_scan_each_step_finds_its_own_experts():
    """As `llama.scan_passes` runs it: `at` is the scan step's own, the stack
    of all the layers' experts is closed over whole."""
    layers, held = 3, 4
    xs, w = operands(40, 32, 128, layers * held)
    sizes = np.asarray([[9, 0, 21, 10], [0, 0, 40, 0], [10, 10, 10, 3]],
                       np.int32)

    def step(carry, layer):
        at, row = layer
        full = jnp.zeros(layers * held, jnp.int32)
        full = jax.lax.dynamic_update_slice(full, row, (at,))
        return carry, gm.gmm(xs, w, full, at, held)

    _, got = jax.jit(lambda: jax.lax.scan(step, 0, (
        jnp.arange(layers, dtype=jnp.int32) * held, jnp.asarray(sizes))))()
    for layer in range(layers):
        full = np.zeros(layers * held, np.int32)
        full[layer * held:(layer + 1) * held] = sizes[layer]
        want, computed = loop_over_groups(xs, w, full, layer * held, held)
        np.testing.assert_allclose(np.asarray(got[layer])[computed],
                                   want[computed], atol=1e-5)


@pytest.mark.parametrize("k,n,groups", [(2048, 1536, 3), (1536, 2048, 3),
                                        (3072, 3072, 2)],
                         ids=["lfm2_up", "lfm2_down", "trinity"])
def test_the_serving_cells_widths_cut_down_in_rows(k, n, groups):
    """bf16 rows and weights, float32 accumulation, bf16 out, at the tiles
    `tiles()` gives the width (the whole contraction in one)."""
    m = 40
    assert gm.tiles(4096, k, n) == (128, min(n, 2048) if k < 3072 else 1536)
    assert gm.tiles(m, k, n)[0] == 48         # whole bf16 sublanes
    sizes = [17, 0, 19][:groups] if groups == 3 else [30, 6]
    xs, w = operands(m, k, n, groups, dtype=jnp.bfloat16)
    got = gm.gmm(xs, w, jnp.asarray(sizes, jnp.int32))
    assert got.dtype == jnp.bfloat16
    want, computed = loop_over_groups(xs.astype(jnp.float32),
                                      w.astype(jnp.float32), sizes)
    gap = np.abs(np.asarray(got, np.float32)[computed] - want[computed])
    assert gap.max() < 0.02 * np.abs(want).max()
    ragged = jax.lax.ragged_dot(xs, w, jnp.asarray(sizes, jnp.int32))
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[computed],
        np.asarray(ragged, np.float32)[computed], atol=0.02 * np.abs(
            want).max())


@pytest.mark.parametrize("tiles", [None, (16, 128)], ids=["own", "16x128"])
def test_a_rows_bits_are_its_own(tiles):
    """The same row against the same expert: among other rows, at another
    place of its tile, in another tile, its group starting elsewhere, the
    layer elsewhere in the stack: the same bits."""
    xs, w = operands(96, 64, 128, 6, seed=3)

    def run(rows, sizes, at, count, w=w):
        rows, sizes = jnp.asarray(rows), jnp.asarray(sizes, jnp.int32)
        if tiles is None:
            return np.asarray(gm.gmm(rows, w, sizes, at, count))
        return np.asarray(gm.gmm_tiled(rows, w, sizes, at, count, *tiles))

    base = run(xs, [20, 0, 30, 10, 16, 20], 0, 6)
    # group 2's thirty rows alone in the batch
    alone = run(xs[20:50], [0, 0, 30, 0, 0, 0], 0, 6)
    np.testing.assert_array_equal(base[20:50], alone[:30])
    # behind other rows of other groups, shifted by 7 and by 33
    for shift, sizes in ((7, [7, 0, 30, 0, 0, 0]), (33, [13, 20, 30, 0, 0, 0])):
        rows = jnp.concatenate([xs[60:60 + shift], xs[20:50]])
        got = run(rows, sizes, 0, 6)
        np.testing.assert_array_equal(base[20:50], got[shift:shift + 30])
    # the layer's experts three groups into a longer stack
    stack = jnp.concatenate([w[3:], w])
    got = run(xs, [0, 0, 0, 20, 0, 30, 10, 16, 20], 3, 6, stack)
    np.testing.assert_array_equal(base, got)


def test_rows_and_weights_of_two_dtypes_are_refused():
    xs, w = operands(16, 32, 128, 2)
    with pytest.raises(ValueError, match="bfloat16"):
        gm.gmm(xs, w.astype(jnp.bfloat16), jnp.asarray([8, 8], jnp.int32))


def test_a_contraction_too_long_for_one_tile_is_refused():
    with pytest.raises(ValueError, match="whole contraction"):
        gm.tiles(4096, 1 << 17, 1536)
