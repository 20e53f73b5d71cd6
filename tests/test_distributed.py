"""Distributed slab join + cross-pod compression, on 8 placeholder devices.

Runs in a subprocess-free way: conftest has NOT set a device count, so this
module re-execs itself? No -- simpler: these tests run under the 8-device
flag via the pytest-xdist-free trick of setting XLA_FLAGS in a subprocess.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# the 8-device matrix tests spawn an 8-placeholder-device subprocess and
# compile SPMD programs -- minutes of wall time; they carry the ``slow``
# marker individually. The 2-device smoke below is NOT slow-marked, so
# tier-1 always exercises the slab join end to end.
slow = pytest.mark.slow


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_distributed_smoke_two_devices():
    """Tier-1 (NOT slow): the fused slab join on 2 placeholder devices.

    Tiny workload so the subprocess stays in seconds: pair-set parity of
    ``distributed_self_join`` against the single-device fused join, the
    count contract, and the empty-slab regression (more slabs than
    points crashed the halo-reach scan: coords[i, gids[i] >= 0, 0].min()
    on a zero-point slab)."""
    out = run_sub(textwrap.dedent("""
        import numpy as np
        from repro.core.distributed import (distributed_self_join,
                                            distributed_self_join_count)
        from repro.core.selfjoin import self_join
        from repro.core.brute import brute_force_count
        from repro.launch.mesh import make_slab_mesh
        rng = np.random.default_rng(11)
        pts = rng.uniform(0, 6, size=(400, 2))
        eps = 0.5
        mesh = make_slab_mesh(2)
        ref = self_join(pts, eps, distance_impl='fused')
        got = distributed_self_join(pts, eps, mesh)
        assert np.array_equal(got, ref), (got.shape, ref.shape)
        n = distributed_self_join(pts, eps, mesh, return_pairs=False)
        assert n == ref.shape[0], (n, ref.shape)
        # empty-slab regression: 1 point, 2 slabs
        one = pts[:1]
        assert distributed_self_join(one, eps, mesh).shape == (0, 2)
        assert distributed_self_join_count(one, eps, mesh) == 0
        assert (distributed_self_join_count(pts[:3], eps, mesh)
                == brute_force_count(pts[:3], eps))
        print('OK')
    """), devices=2)
    assert "OK" in out


def test_overlapped_slabs_match_single_device_four_slabs():
    """Tier-1: at 4 slabs, with the slabs' stages driven in step, the
    pairs are bit-identical to the single-device fused join, UNICOMP on
    and off, on uniform and clustered points."""
    out = run_sub(textwrap.dedent("""
        import numpy as np
        from repro.core.distributed import distributed_self_join
        from repro.core.selfjoin import self_join
        from repro.launch.mesh import make_slab_mesh
        rng = np.random.default_rng(7)
        uni = rng.uniform(0, 12, size=(1200, 2))
        k = rng.integers(0, 5, 1200)
        clus = rng.uniform(0, 12, (5, 2))[k] + rng.normal(0, 0.4, (1200, 2))
        mesh = make_slab_mesh(4)
        for name, pts, eps in (('uniform', uni, 0.6),
                               ('clustered', clus, 0.2)):
            for unicomp in (True, False):
                ref = self_join(pts, eps, unicomp=unicomp,
                                distance_impl='fused')
                got = distributed_self_join(pts, eps, mesh, unicomp=unicomp)
                assert np.array_equal(got, ref), (name, unicomp, got.shape,
                                                  ref.shape)
        print('OK')
    """), devices=4)
    assert "OK" in out


def test_every_slab_launches_before_any_pairs_fetch():
    """Tier-1: a dispatch recorder sees each of the 4 slabs' first fused
    launch, each on its own device, before the first fetch of any slab's
    pairs (host emit: its hit-set fetch; device emit: the emit that the
    pairs fetch waits on), and the spans of the whole join nest."""
    out = run_sub(textwrap.dedent("""
        import numpy as np
        from repro.core import distributed, selfjoin
        from repro.kernels import ops
        from repro.launch.mesh import make_slab_mesh
        events, stack = [], []
        launch = ops.fused_join_hits
        real_span = selfjoin.span

        def launch_rec(*a, **kw):
            out = launch(*a, **kw)
            events.append(('launch', str(list(out[1].devices())[0])))
            return out

        def fetch_rec(real):
            def f(*a, **kw):
                events.append(('fetch', None))
                return real(*a, **kw)
            return f

        class span_rec:
            def __init__(self, name, **kw):
                self.name, self.inner = name, real_span(name, **kw)
            def __enter__(self):
                stack.append(self.name)
                return self.inner.__enter__()
            def __exit__(self, *a):
                assert stack.pop() == self.name, 'spans do not nest'
                return self.inner.__exit__(*a)

        ops.fused_join_hits = launch_rec
        selfjoin._emit_from_hits_host = fetch_rec(
            selfjoin._emit_from_hits_host)
        selfjoin._emit_from_hits = fetch_rec(selfjoin._emit_from_hits)
        selfjoin.span = distributed.span = span_rec
        rng = np.random.default_rng(2)
        pts = rng.uniform(0, 12, size=(1600, 2))
        mesh = make_slab_mesh(4)
        for emit in ('host', 'device'):
            events.clear()
            distributed.distributed_self_join(pts, 0.6, mesh, emit=emit)
            assert not stack
            first = {}
            for i, (kind, dev) in enumerate(events):
                if kind == 'launch':
                    first.setdefault(dev, i)
            fetch = min(i for i, (kind, _) in enumerate(events)
                        if kind == 'fetch')
            assert len(first) == 4, (emit, first)
            assert max(first.values()) < fetch, (emit, first, fetch)
        print('OK')
    """), devices=4)
    assert "OK" in out


@pytest.mark.parametrize("capacity", [64, 250, 400])
def test_pack_mask_keeps_masked_rows_in_order(capacity):
    """Tier-1, one device: the sort-free halo parcel packing selects the
    masked rows in row order into ``capacity`` slots, as a stable argsort
    of the mask would, flags exactly the filled slots, and reports an
    overflow only past the capacity (under, near and over it here)."""
    import jax.numpy as jnp

    from repro.core.distributed import _pack_mask

    rng = np.random.default_rng(capacity)
    coords = rng.uniform(0, 1, (500, 2))
    gids = rng.permutation(500).astype(np.int32)
    mask = rng.uniform(0, 1, 500) < 0.5
    c, g, sent, over = _pack_mask(jnp.asarray(coords), jnp.asarray(gids),
                                  jnp.asarray(mask), capacity)
    rows = np.flatnonzero(mask)[:capacity]
    sent = np.asarray(sent)
    assert sent.shape == (capacity,)
    assert sent.sum() == rows.size and sent[:rows.size].all()
    assert np.array_equal(np.asarray(c)[:rows.size], coords[rows])
    assert np.array_equal(np.asarray(g)[:rows.size], gids[rows])
    assert bool(over) == (mask.sum() > capacity)


@pytest.mark.parametrize("emit", ["host", "device"])
def test_staged_one_chip_join_keeps_its_order(monkeypatch, emit):
    """Tier-1, one device: ``_self_join_fused`` driven through its stages
    dispatches and waits as the one-chip join always has -- run-loop and
    order fetches, the caps planner then its fetch, each launch
    dispatched one ahead of the previous launch's counts fetch, emit and
    pairs fetch -- and its pairs equal the brute force's."""
    from repro.core import grid, selfjoin
    from repro.core.brute import brute_force_join
    from repro.kernels import ops

    rng = np.random.default_rng(3)
    pts = np.concatenate([rng.uniform(0, 10, (1500, 2)),
                          rng.normal(5, 0.3, (700, 2))])
    index = grid.build_grid(pts, 0.3)
    log = []
    real_span = selfjoin.span

    class span_rec:
        def __init__(self, name, **kw):
            self.name, self.inner = name, real_span(name, **kw)

        def __enter__(self):
            if self.name == "selfjoin.sync":
                log.append("wait")
            return self.inner.__enter__()

        def __exit__(self, *a):
            return self.inner.__exit__(*a)

    def rec(real, tag):
        def f(*a, **kw):
            log.append(tag)
            return real(*a, **kw)
        return f

    monkeypatch.setattr(selfjoin, "span", span_rec)
    monkeypatch.setattr(ops, "fused_join_hits",
                        rec(ops.fused_join_hits, "kernel"))
    monkeypatch.setattr(selfjoin, "_emit_from_hits",
                        rec(selfjoin._emit_from_hits, "emit"))
    monkeypatch.setattr(grid, "_cell_window_caps_device",
                        rec(grid._cell_window_caps_device, "caps"))
    got = selfjoin._self_join_fused(index, unicomp=True, sort_result=True,
                                    emit=emit)
    monkeypatch.undo()
    n = log.count("kernel")
    assert n >= 2
    drain = ["wait"] + (["emit", "wait"] if emit == "device" else [])
    want = ["wait", "wait", "caps", "wait", "kernel"]
    for _ in range(n - 1):
        want += ["kernel"] + drain
    want += drain
    assert log == want, log
    ref = brute_force_join(pts, 0.3)
    assert np.array_equal(got, ref)


@slow
def test_distributed_pairs_parity_matrix():
    """Acceptance matrix: pair sets bit-identical to the single-device
    fused join at 2, 4, and 8 slabs, UNICOMP on/off, merged-range sweep
    on/off, on uniform and clustered workloads."""
    out = run_sub(textwrap.dedent("""
        import numpy as np
        from repro.core.distributed import distributed_self_join
        from repro.core.selfjoin import self_join
        from repro.launch.mesh import make_slab_mesh
        rng = np.random.default_rng(5)
        uni = rng.uniform(0, 10, size=(900, 2))
        k = rng.integers(0, 6, 900)
        centers = rng.uniform(0, 10, (6, 2))
        clus = centers[k] + rng.normal(0, 0.3, (900, 2))
        for name, pts, eps in (('uniform', uni, 0.5),
                               ('clustered', clus, 0.25)):
            for n_slabs in (2, 4, 8):
                mesh = make_slab_mesh(n_slabs)
                for unicomp in (True, False):
                    for merge in (True, False):
                        ref = self_join(pts, eps, unicomp=unicomp,
                                        distance_impl='fused',
                                        merge_last_dim=merge)
                        got = distributed_self_join(
                            pts, eps, mesh, unicomp=unicomp,
                            merge_last_dim=merge)
                        assert np.array_equal(got, ref), (
                            name, n_slabs, unicomp, merge,
                            got.shape, ref.shape)
        print('OK')
    """))
    assert "OK" in out


@slow
def test_halo_capacity_overflow_pairs():
    """An explicit too-small halo capacity raises (never silent)."""
    out = run_sub(textwrap.dedent("""
        import numpy as np
        from repro.core.distributed import distributed_self_join
        from repro.launch.mesh import make_slab_mesh
        rng = np.random.default_rng(3)
        pts = rng.uniform(0, 1.0, size=(400, 2))   # eps >> slab width
        mesh = make_slab_mesh(2)
        try:
            distributed_self_join(pts, 0.5, mesh, halo_capacity=2)
        except RuntimeError as e:
            assert 'halo capacity overflow' in str(e), e
            print('OK')
    """), devices=2)
    assert "OK" in out


@slow
def test_distributed_count_matches_brute():
    out = run_sub(textwrap.dedent("""
        import numpy as np, jax
        from repro.core.distributed import distributed_self_join_count
        from repro.core.brute import brute_force_count
        from repro.launch.mesh import make_mesh_compat
        rng = np.random.default_rng(1)
        for n, eps in ((2, 0.8), (3, 1.0)):
            pts = rng.uniform(0, 10, size=(1500, n))
            bf = brute_force_count(pts, eps)
            m1 = make_mesh_compat((8,), ('slab',))
            c1 = distributed_self_join_count(pts, eps, m1, unicomp=True)
            m2 = make_mesh_compat((4, 2), ('slab', 'model'))
            c2 = distributed_self_join_count(pts, eps, m2, unicomp=True,
                                             model_axis='model')
            c3 = distributed_self_join_count(pts, eps, m2, unicomp=False,
                                             model_axis='model')
            assert bf == c1 == c2 == c3, (n, bf, c1, c2, c3)
        print('OK')
    """))
    assert "OK" in out


@slow
def test_distributed_skewed_data_balanced():
    """Equal-count partitioner keeps slabs balanced under heavy skew."""
    out = run_sub(textwrap.dedent("""
        import numpy as np, jax
        from repro.core.distributed import (distributed_self_join_count,
                                            partition_points_host)
        from repro.core.brute import brute_force_count
        from repro.launch.mesh import make_mesh_compat
        rng = np.random.default_rng(2)
        # 90% of points clustered in 5% of the range
        a = rng.uniform(0, 0.5, size=(1800, 2))
        b = rng.uniform(0, 10, size=(200, 2))
        pts = np.concatenate([a, b])
        coords, gids, width = partition_points_host(pts, 8)
        counts = (gids >= 0).sum(axis=1)
        assert counts.max() - counts.min() <= 1, counts
        m = make_mesh_compat((8,), ('slab',))
        got = distributed_self_join_count(pts, 0.2, m)
        assert got == brute_force_count(pts, 0.2)
        print('OK')
    """))
    assert "OK" in out


@slow
def test_halo_overflow_detected():
    out = run_sub(textwrap.dedent("""
        import numpy as np, jax
        from repro.launch.mesh import make_mesh_compat
        from repro.core.distributed import (DistJoinConfig,
                                            make_distributed_count_step,
                                            partition_points_host)
        rng = np.random.default_rng(3)
        pts = rng.uniform(0, 1.0, size=(800, 2))  # eps >> slab width
        mesh = make_mesh_compat((8,), ('slab',))
        coords, gids, _ = partition_points_host(pts, 8)
        cfg = DistJoinConfig(pts_per_device=coords.shape[1], n_dims=2,
                             halo_capacity=4, max_per_cell=64,
                             model_axis=None)
        step, in_sh = make_distributed_count_step(mesh, cfg)
        import jax.numpy as jnp
        c = jax.device_put(coords.reshape(-1, 2), in_sh[0])
        g = jax.device_put(gids.reshape(-1), in_sh[1])
        total, halo_of, cell_of = step(c, g, jnp.asarray(0.5, pts.dtype))
        assert int(halo_of) == 1  # overflow detected, not silent
        print('OK')
    """))
    assert "OK" in out


@slow
def test_compressed_train_step_end_to_end():
    """Full train step with int8 cross-pod grad exchange on a (2,2,2) mesh:
    loss decreases and tracks the uncompressed step closely."""
    out = run_sub(textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh_compat
        from repro.configs import get_config
        from repro.models.lm import LMModel
        from repro.train.optimizer import AdamWConfig, adamw_init, opt_state_specs
        from repro.train.steps import make_train_step
        from repro.train.compression import init_error_state

        mesh = make_mesh_compat((2, 2, 2), ('pod', 'data', 'model'))
        cfg = get_config('smoke-lm', reduced=True)
        rng = np.random.default_rng(0)
        batch = {'tokens': jnp.asarray(rng.integers(0, cfg.vocab, (4, 32)), jnp.int32),
                 'labels': jnp.asarray(rng.integers(0, cfg.vocab, (4, 32)), jnp.int32)}
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=1)

        def run(compress):
            model = LMModel(cfg, mesh)
            params, specs = model.init(jax.random.PRNGKey(0))
            opt = adamw_init(params, ocfg)
            if compress:
                opt['grad_error'] = init_error_state(params)
            step = jax.jit(make_train_step(model, ocfg, compress_pods=compress,
                                           param_specs=specs))
            losses = []
            with mesh:
                for _ in range(4):
                    params, opt, m = step(params, opt, batch)
                    losses.append(float(m['loss']))
            return losses

        plain = run(False)
        comp = run(True)
        assert comp[-1] < comp[0], comp
        assert abs(comp[0] - plain[0]) < 1e-2, (comp[0], plain[0])
        assert abs(comp[-1] - plain[-1]) < 0.1, (comp, plain)
        print('OK')
    """))
    assert "OK" in out


@slow
def test_compressed_crosspod_grads():
    """int8 all-gather grad exchange: mean error small, error feedback
    carries the residual; exact for pod-identical gradients."""
    out = run_sub(textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh_compat
        from repro.train.compression import compressed_psum_mean
        mesh = make_mesh_compat((2, 4), ('pod', 'data'))
        rng = np.random.default_rng(0)
        g_global = rng.normal(size=(2, 64)).astype(np.float32)  # per-pod rows

        def f(g, e):
            m, ne = compressed_psum_mean({'w': g}, {'w': e}, 'pod', 2)
            return m['w'], ne['w']

        from repro.compat import shard_map
        sm = shard_map(f, mesh=mesh,
                       in_specs=(P('pod'), P('pod')),
                       out_specs=(P(), P('pod')),
                       axis_names={'pod'}, check_vma=False)
        g = jax.device_put(g_global.reshape(-1),
                           NamedSharding(mesh, P('pod')))
        e = jnp.zeros_like(g)
        mean, err = jax.jit(sm)(g, e)
        true_mean = g_global.mean(axis=0)
        got = np.asarray(mean)
        scale = np.abs(g_global).max() / 127.0
        assert got.shape == (64,)
        assert np.max(np.abs(got - true_mean)) <= scale + 1e-6
        # error feedback holds the quantization residual per pod
        err = np.asarray(err).reshape(2, 64)
        q = np.clip(np.round(g_global / scale), -127, 127)
        resid = g_global - q * scale
        assert np.allclose(err, resid, atol=1e-6)
        print('OK')
    """))
    assert "OK" in out
