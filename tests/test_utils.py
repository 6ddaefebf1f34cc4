"""Utilities (reference deeplearning4j-util + nn/util/TimeSeriesUtils)."""
import numpy as np
import pytest

from deeplearning4j_tpu import utils


class TestTimeSeriesUtils:
    def test_moving_average(self):
        out = utils.moving_average(np.array([1., 2., 3., 4., 5.]), 2)
        np.testing.assert_allclose(out, [1.5, 2.5, 3.5, 4.5])

    def test_reshape_round_trip(self):
        x = np.random.randn(4, 7, 3).astype(np.float32)
        two = utils.reshape_3d_to_2d(x)
        assert two.shape == (28, 3)
        np.testing.assert_array_equal(utils.reshape_2d_to_3d(two, 4), x)
        m = (np.random.rand(4, 7) > 0.3).astype(np.float32)
        v = utils.reshape_time_series_mask_to_vector(m)
        assert v.shape == (28, 1)
        np.testing.assert_array_equal(
            utils.reshape_vector_to_time_series_mask(v, 4), m)

    def test_reverse_time_series_masked(self):
        x = np.arange(2 * 4 * 1, dtype=np.float32).reshape(2, 4, 1)
        mask = np.array([[1, 1, 1, 0], [1, 1, 0, 0]], np.float32)
        out = utils.reverse_time_series(x, mask)
        # seq 0 has length 3: [0,1,2,pad] -> [2,1,0,pad]
        np.testing.assert_allclose(out[0, :, 0], [2, 1, 0, 3])
        np.testing.assert_allclose(out[1, :, 0], [5, 4, 6, 7])
        # unmasked: plain flip
        np.testing.assert_allclose(
            utils.reverse_time_series(x)[0, :, 0], [3, 2, 1, 0])

    def test_pull_last_time_steps(self):
        x = np.arange(2 * 4 * 2, dtype=np.float32).reshape(2, 4, 2)
        mask = np.array([[1, 1, 0, 0], [1, 1, 1, 1]], np.float32)
        last, idx = utils.pull_last_time_steps(x, mask)
        np.testing.assert_array_equal(idx, [1, 3])
        np.testing.assert_allclose(last[0], x[0, 1])
        np.testing.assert_allclose(last[1], x[1, 3])


class TestMovingWindowMatrix:
    def test_windows_quadrants(self):
        m = np.array([[1, 1, 2, 2], [1, 1, 2, 2], [3, 3, 4, 4], [3, 3, 4, 4]])
        wins = utils.MovingWindowMatrix(m, 2, 2).windows()
        assert len(wins) == 4
        np.testing.assert_array_equal(wins[0], [[1, 1], [1, 1]])
        np.testing.assert_array_equal(wins[3], [[4, 4], [4, 4]])
        flat = utils.MovingWindowMatrix(m, 2, 2).windows(flattened=True)
        assert flat[1].shape == (4,)

    def test_rotations(self):
        m = np.arange(4).reshape(2, 2)
        wins = utils.MovingWindowMatrix(m, 2, 2, add_rotate=True).windows()
        assert len(wins) == 4  # original + 3 rotations
        np.testing.assert_array_equal(wins[1], np.rot90(m, 1))


class TestStringGrid:
    def test_filter_dedup_sort(self, tmp_path):
        g = utils.StringGrid.from_lines(
            ["b,2", "a,1", "b,3", "c,1"], sep=",")
        assert len(g) == 4
        assert g.get_column(0) == ["b", "a", "b", "c"]
        assert len(g.get_rows_with_column_value(1, "1")) == 2
        assert g.dedup_by_column(0).get_column(0) == ["b", "a", "c"]
        assert g.sort_by_column(0).get_column(0) == ["a", "b", "b", "c"]
        p = tmp_path / "g.csv"
        g.write_file(str(p))
        back = utils.StringGrid.from_file(str(p))
        assert back.to_lines() == g.to_lines()


def test_compile_cache_is_placed_from_outside_or_in_the_checkout():
    """``runtime.enable_compile_cache``: a cache already placed (by
    ``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself, or by this
    harness) is left alone; with none, it is ``<checkout>/.jax_cache`` —
    a fixed path, never a temp name."""
    import os

    import jax

    from deeplearning4j_tpu import runtime

    placed = jax.config.jax_compilation_cache_dir
    assert placed  # conftest.py or the environment did
    assert runtime.enable_compile_cache() == placed
    assert jax.config.jax_compilation_cache_dir == placed
    # an entry is found by the computation alone, so that two checkouts
    # of one program share executables (runtime.py says what that costs)
    assert not jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        path = runtime.enable_compile_cache()
        assert path == os.path.join(runtime.CHECKOUT, ".jax_cache")
        assert os.path.isfile(os.path.join(runtime.CHECKOUT, "chip_smoke.py"))
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", placed)
