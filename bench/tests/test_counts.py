from harness import counts, peaks


def test_per_word_counts_at_the_papers_shapes():
    assert counts.window_flops(w_f=3, negatives=5, dim=128) == 27_792
    assert counts.window_bytes(w_f=3, negatives=5, dim=128) == 7_168


def test_sgns_is_bound_by_bytes_on_v5e():
    peak = peaks.peak_for("TPU v5 lite")
    least, bound = counts.least_seconds(27_792, 7_168, peak)
    assert bound == "bytes"
    assert abs(least - 7_168 / 819e9) < 1e-15


def test_serving_sweep_counts():
    assert counts.sweep_bytes(400_000, 128) == 204_800_000
    assert counts.query_flops(400_000, 128) == 102_400_000


def test_e4m3_rounding_matches_the_format():
    import jax.numpy as jnp
    import numpy as np
    from harness import reference
    x = jnp.array([0.0, 1.0, 1.0625, 1.1, -0.3, 0.0157, 0.001, 1000.0])
    got = np.asarray(reference.round_e4m3(x))
    want = np.asarray(x.astype(jnp.float8_e4m3fn).astype(jnp.float32))
    np.testing.assert_array_equal(got[:-1], want[:-1])
    assert got[-1] == 448.0
