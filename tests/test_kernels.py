"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (ref.py)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import distance_tile, ops, ref


DIMS = [2, 3, 4, 5, 6]
DTYPES = [np.float32, np.float64]


@pytest.mark.parametrize("n", DIMS)
@pytest.mark.parametrize("dt", DTYPES)
@pytest.mark.parametrize("nq,npts", [(1, 1), (7, 500), (256, 256), (300, 1000)])
def test_distance_tile_hits(n, dt, nq, npts):
    rng = np.random.default_rng(n * 100 + npts)
    q = rng.uniform(0, 10, (nq, n)).astype(dt)
    p = rng.uniform(0, 10, (npts, n)).astype(dt)
    eps = 1.3
    got = distance_tile.distance_tile_hits(jnp.asarray(q), jnp.asarray(p),
                                           eps, interpret=True)
    want = ref.distance_tile_hits_ref(jnp.asarray(q), jnp.asarray(p), eps)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [2, 4, 6])
@pytest.mark.parametrize("dt", DTYPES)
@pytest.mark.parametrize("npts", [3, 129, 700])
def test_distance_tile_counts(n, dt, npts):
    rng = np.random.default_rng(n + npts)
    p = rng.uniform(0, 5, (npts, n)).astype(dt)
    eps = 0.9
    got = distance_tile.distance_tile_counts(jnp.asarray(p), eps,
                                             interpret=True)
    want = ref.distance_tile_counts_ref(jnp.asarray(p), eps)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_distance_tile_tile_size_invariance():
    rng = np.random.default_rng(0)
    p = rng.uniform(0, 5, (400, 3))
    for tq, tc in [(64, 64), (128, 256), (512, 512)]:
        got = distance_tile.distance_tile_counts(
            jnp.asarray(p), 0.8, tq=tq, tc=tc, interpret=True)
        want = ref.distance_tile_counts_ref(jnp.asarray(p), 0.8)
        assert np.array_equal(np.asarray(got), np.asarray(want)), (tq, tc)


def test_distance_tile_bf16_close():
    """bf16 kernel vs f32 oracle: hits may differ only at the threshold."""
    rng = np.random.default_rng(1)
    q = rng.uniform(0, 4, (64, 3)).astype(np.float32)
    p = rng.uniform(0, 4, (200, 3)).astype(np.float32)
    eps = 1.0
    got = np.asarray(distance_tile.distance_tile_hits(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(p, jnp.bfloat16), eps,
        interpret=True))
    d2 = ((q[:, None, :] - p[None, :, :]) ** 2).sum(-1)
    # the MXU form qn+pn-2ab in bf16 has absolute error ~ (qn+pn) * 2^-8:
    # coords up to 4 in 3-D -> norms up to 48 -> band ~ 0.4. Exactness is
    # required outside that band; inside it bf16 legitimately flips.
    qn = (q ** 2).sum(-1)[:, None]
    pn = (p ** 2).sum(-1)[None, :]
    band = (qn + pn) * 2.0 ** -8 + 0.02
    sure = np.abs(d2 - eps * eps) > band
    want = d2 <= eps * eps
    assert np.array_equal(got[sure], want[sure])
    assert (got == want).mean() > 0.98


@pytest.mark.parametrize("n", DIMS)
@pytest.mark.parametrize("dt", DTYPES)
@pytest.mark.parametrize("b,c", [(1, 8), (57, 24), (512, 8), (600, 40)])
def test_fused_window_hits(n, dt, b, c):
    """``ops.fused_window_hits``, the 'compact' count route's refine: the
    candidates arrive as positions into the points array, and the hits
    equal the oracle's over the gathered windows."""
    rng = np.random.default_rng(b * 7 + c)
    points = rng.uniform(0, 10, (b + c, n)).astype(dt)
    q = rng.uniform(0, 10, (b, n)).astype(dt)
    cand_pos = rng.integers(0, b + c, (b, c)).astype(np.int32)
    valid = rng.random((b, c)) < 0.7
    eps = 1.1
    got = ops.fused_window_hits(jnp.asarray(points), jnp.asarray(q),
                                jnp.asarray(cand_pos), jnp.asarray(valid),
                                eps)
    want = ref.cell_join_hits_ref(jnp.asarray(q),
                                  jnp.asarray(points[cand_pos]),
                                  jnp.asarray(valid), eps)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_cell_join_all_invalid():
    points = jnp.zeros((8, 3))
    q = jnp.zeros((16, 3))
    cand_pos = jnp.zeros((16, 8), jnp.int32)
    valid = jnp.zeros((16, 8), bool)
    got = ops.fused_window_hits(points, q, cand_pos, valid, 1.0)
    assert not np.asarray(got).any()


def test_mxu_formulation_numerics():
    """||a-b||^2 = ||a||^2+||b||^2-2ab can go (slightly) negative for
    coincident points; the threshold compare must still classify them in."""
    pts = np.array([[1e3, 1e3], [1e3, 1e3], [1e3 + 0.5, 1e3]])
    got = np.asarray(distance_tile.distance_tile_hits(
        jnp.asarray(pts, jnp.float32), jnp.asarray(pts, jnp.float32), 0.6,
        interpret=True))
    assert got.all()  # all pairwise distances <= 0.6
