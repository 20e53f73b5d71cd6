"""repro.analysis: contract prover, retrace/dtype linter, sanitizer.

Covers ISSUE 7's tentpole and satellites 3/4: the prover passes on
healthy geometries and catches injected planner faults, the linter flags
the PR-2 per-call ``@jax.jit`` pattern while the fixed ``range_query``
and ``BatchingJoinService`` paths lint clean, the static no-retrace
model proves the warm ladder covers canned request mixes, and sanitized
kernel mode catches a corrupted window descriptor (OOB gather) and an
undersized window cap in interpreter mode.
"""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import contracts, lint, sanitize
from repro.analysis import findings as F
from repro.core.brute import brute_force_join
from repro.core.grid import (BucketPlan, build_grid_host, occupancy_plan,
                             sentinel_margin)
from repro.kernels.fused_join import TQ_DEFAULT

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _uniform(n=300, d=2, eps=0.08, seed=0):
    rng = np.random.default_rng(seed)
    return rng.uniform(0.0, 1.0, (n, d)), eps


def _clustered(n=300, d=3, eps=0.1, seed=3):
    rng = np.random.default_rng(seed)
    centers = rng.uniform(0.0, 1.0, (4, d))
    return centers[rng.integers(0, 4, n)] + rng.normal(0.0, 0.03, (n, d)), eps


# ---------------------------------------------------------------------------
# contract prover
# ---------------------------------------------------------------------------

class TestContracts:
    @pytest.mark.parametrize("mk", [_uniform, _clustered])
    def test_healthy_index_proves_clean(self, mk):
        pts, eps = mk()
        found = contracts.prove_index_contracts(build_grid_host(pts, eps))
        errors = [f for f in found if f.severity == "error"]
        assert errors == [], [f.render() for f in errors]

    def test_recomputed_caps_match_planner(self):
        """The coordinate-space re-derivation and the linear-key planner
        agree exactly on a healthy index (the planner may only overcount,
        and on interior geometries it should not even do that)."""
        from repro.core.grid import cell_window_caps

        pts, eps = _clustered()
        index = build_grid_host(pts, eps)
        for merged in (False, True):
            exact = contracts.recompute_cell_caps(index, merged)
            planner = np.asarray(cell_window_caps(index, merged=merged))
            assert np.all(planner >= exact)

    def test_tampered_plan_caught(self):
        """Mutation (a): a plan granting less than a cell's worst-case
        window must produce a cap-coverage finding."""
        pts, eps = _clustered()
        index = build_grid_host(pts, eps)
        assert contracts.recompute_cell_caps(index, merged=True).max() > 8
        plan = occupancy_plan(index, merged=True)
        tampered = BucketPlan(caps=(8,), sel=(None,),
                              cap_global=plan.cap_global,
                              hist={8: index.num_points})
        found = contracts.check_window_caps(index, merged=True,
                                            plan=tampered, tag="t")
        assert any(f.rule == "cap-coverage" for f in found)

    def test_tampered_partition_caught(self):
        """A plan that drops rows is not a partition."""
        pts, eps = _clustered()
        index = build_grid_host(pts, eps)
        plan = occupancy_plan(index, merged=True)
        half = np.arange(index.num_points // 2, dtype=np.int32)
        tampered = BucketPlan(caps=(plan.cap_global,), sel=(half,),
                              cap_global=plan.cap_global,
                              hist={plan.cap_global: half.size})
        found = contracts.check_window_caps(index, merged=True,
                                            plan=tampered, tag="t")
        assert any(f.rule == "plan-partition" for f in found)

    def test_sentinel_margin(self):
        assert sentinel_margin([10, 10]) == 2**31 - 1 - 99
        assert sentinel_margin([1 << 20, 1 << 11]) > 0       # int32 boundary
        assert sentinel_margin([1 << 32, 1 << 20]) > 0       # int64 route
        # forced-narrow dtype on a too-big volume: negative margin = alias
        assert sentinel_margin([1 << 20, 1 << 12], np.int32) <= 0

    def test_external_cap_exact(self):
        from repro.core.grid import external_range_cap

        pts, eps = _clustered()
        index = build_grid_host(pts, eps)
        assert int(external_range_cap(index)) >= \
            contracts.recompute_external_cap(index)

    def test_vmem_contract_flags_oversized_tile(self):
        from repro.launch.roofline import VMEM_BYTES, fused_join_vmem_bytes

        pts, eps = _uniform()
        index = build_grid_host(pts, eps)
        plan = occupancy_plan(index, merged=True)
        # a tile big enough to blow the budget at the plan's largest cap
        cap = int(max(plan.caps))
        huge_tq = (VMEM_BYTES // cap) + 1024
        assert fused_join_vmem_bytes(c=cap, tq=huge_tq) > VMEM_BYTES
        found = contracts.check_vmem(
            index, merged=True, plan=plan,
            tiles={int(c): huge_tq for c in plan.caps}, tag="t")
        assert any(f.rule == "vmem-budget" for f in found)

    def test_halo_contracts_healthy(self):
        pts, eps = _uniform(n=200)
        found = contracts.prove_halo_contracts(pts, eps, n_slabs=4)
        assert [f for f in found if f.severity == "error"] == []

    def test_halo_capacity_finding_names_worst_parcel(self):
        pts, eps = _uniform(n=200)
        found = contracts.prove_halo_contracts(pts, eps, n_slabs=4,
                                               halo_capacity=1)
        caps = [f for f in found if f.site.endswith(":capacity")]
        assert caps and "slab" in caps[0].message
        assert "halo_capacity >=" in caps[0].message


class TestHaloPlan:
    def test_plan_max_is_exact_capacity(self):
        from repro.core.distributed import (exact_halo_capacity,
                                            halo_capacity_plan, halo_reach,
                                            partition_points_host,
                                            slab_extents)

        pts, eps = _uniform(n=257, d=2)
        coords, gids, _ = partition_points_host(pts, 4)
        mins, maxs = slab_extents(coords, gids)
        k = halo_reach(mins, maxs, eps)
        plan = halo_capacity_plan(coords, gids, mins, maxs, eps, k)
        assert plan
        assert max(p.need for p in plan) == \
            exact_halo_capacity(coords, gids, mins, maxs, eps, k)

    def test_overflow_error_is_actionable(self):
        """Satellite 1: the under-capacity raise names the offending
        slab/parcel and the minimal sufficient capacity."""
        from repro.core.distributed import (_halo_overflow_error,
                                            halo_capacity_plan, halo_reach,
                                            partition_points_host,
                                            slab_extents)

        pts, eps = _uniform(n=200, d=2)
        coords, gids, _ = partition_points_host(pts, 4)
        mins, maxs = slab_extents(coords, gids)
        k = halo_reach(mins, maxs, eps)
        plan = halo_capacity_plan(coords, gids, mins, maxs, eps, k)
        err = _halo_overflow_error(1, plan)
        worst = max(plan, key=lambda p: p.need)
        msg = str(err)
        assert f"slab {worst.slab} -> slab {worst.dest}" in msg
        assert f"halo_capacity >= {worst.need}" in msg


# ---------------------------------------------------------------------------
# linter
# ---------------------------------------------------------------------------

_PR2_FIXTURE = '''
import jax
import numpy as np

def range_query(index, q, eps):
    """The PR-2 bug shape: a fresh jitted closure per call."""
    @jax.jit
    def _probe(q):
        return q * 2
    return _probe(q)
'''

_SYNC_FIXTURE = '''
import jax
import numpy as np

@jax.jit
def bad(x):
    v = x.sum().item()
    w = np.asarray(x)
    return v + float(x[0])
'''

_I64_FIXTURE = '''
import numpy as np

def build_table(keys):
    pad = np.iinfo(np.int64).max
    return np.where(keys == 9223372036854775807, -1, keys), pad
'''


class TestLinter:
    def test_pr2_percall_jit_flagged(self):
        found = lint.lint_source(_PR2_FIXTURE, "fixture.py")
        jit = [f for f in found if f.rule == lint.RULE_JIT]
        assert len(jit) == 1
        assert jit[0].site == "fixture.py::range_query"
        assert "_probe" in jit[0].message

    def test_module_level_jit_clean(self):
        src = ("import jax\n\n@jax.jit\ndef f(x):\n    return x\n\n"
               "g = jax.jit(lambda x: x)\n")
        found = lint.lint_source(src, "m.py")
        assert [f for f in found if f.rule == lint.RULE_JIT] == []

    def test_host_sync_in_jit_flagged(self):
        found = lint.lint_source(_SYNC_FIXTURE, "fixture.py")
        sync = [f for f in found if f.rule == lint.RULE_SYNC]
        msgs = " ".join(f.message for f in sync)
        assert ".item()" in msgs and "np.asarray" in msgs
        assert any(f.severity == "warning" for f in sync)  # float()

    def test_host_sync_outside_jit_clean(self):
        src = "def f(x):\n    return x.sum().item()\n"
        found = lint.lint_source(src, "m.py")
        assert [f for f in found if f.rule == lint.RULE_SYNC] == []

    def test_int64_literals_flagged(self):
        found = lint.lint_source(_I64_FIXTURE, "fixture.py")
        i64 = [f for f in found if f.rule == lint.RULE_I64]
        assert len(i64) == 2           # iinfo(int64) + the bare literal

    def test_fixed_paths_lint_clean(self):
        """Satellite 3: range_query / per_point_neighbor_counts
        (core/selfjoin.py) and BatchingJoinService (launch/serve.py) carry
        no retrace or dtype findings after the fixes."""
        sj = lint.lint_paths([os.path.join(SRC, "repro/core/selfjoin.py")],
                             root=os.path.dirname(SRC))
        bad = [f for f in sj
               if "range_query" in f.site
               or "per_point_neighbor_counts" in f.site
               or "neighbor_counts" in f.site]
        assert bad == [], [f.render() for f in bad]
        assert [f for f in sj if f.rule == lint.RULE_I64] == [], \
            [f.render() for f in sj if f.rule == lint.RULE_I64]
        sv = lint.lint_paths([os.path.join(SRC, "repro/launch/serve.py")],
                             root=os.path.dirname(SRC))
        bad = [f for f in sv if "BatchingJoinService" in f.site
               or "JoinService" in f.site]
        assert bad == [], [f.render() for f in bad]

    def test_query_join_lints_clean(self):
        qj = lint.lint_paths([os.path.join(SRC, "repro/core/query_join.py")],
                             root=os.path.dirname(SRC))
        assert qj == [], [f.render() for f in qj]


# ---------------------------------------------------------------------------
# static no-retrace model
# ---------------------------------------------------------------------------

class TestNoRetrace:
    def _prepared(self, mk=_clustered):
        from repro.core.query_join import prepare

        pts, eps = mk()
        return prepare(build_grid_host(pts, eps))

    def test_full_ladder_covers_mix(self):
        pj = self._prepared()
        found = lint.check_no_retrace(
            pj, max_batch=256, request_sizes=(1, 7, 32, 128, 256))
        assert found == [], [f.render() for f in found]

    def test_oversized_request_caught(self):
        pj = self._prepared()
        found = lint.check_no_retrace(
            pj, max_batch=128, request_sizes=(512,))
        assert found and all(f.rule == "static-retrace" for f in found)

    def test_single_size_warm_misses_other_sizes(self):
        """A fixed-size JoinService.warmup covers only its own request
        bucket on the non-bucketed path: the model reports the miss."""
        from repro.core.query_join import prepare

        pts, eps = _uniform(n=40, eps=0.03)      # sparse: one class
        pj = prepare(build_grid_host(pts, eps))
        assert not pj.bucketed
        found = lint.check_no_retrace(
            pj, max_batch=256, warm_sizes=(256,), request_sizes=(8,))
        assert found

    def test_lowering_count_bounded(self):
        pj = self._prepared()
        n = lint.count_distinct_lowerings(pj, sizes=(1, 32, 256))
        assert 0 < n <= 2 * len(pj.classes) * (
            1 + max(0, (256 // TQ_DEFAULT).bit_length()))


# ---------------------------------------------------------------------------
# findings / baseline protocol
# ---------------------------------------------------------------------------

class TestBaseline:
    def test_round_trip(self, tmp_path):
        f1 = F.Finding("lint", "per-call-jit", "a.py::f", "m1", line=3)
        f2 = F.Finding("contracts", "cap-coverage", "index:t", "m2")
        path = str(tmp_path / "base.json")
        F.save_baseline([f1, f2], path)
        base = F.load_baseline(path)
        assert base == {f1.key, f2.key}
        f3 = F.Finding("lint", "per-call-jit", "b.py::g", "new")
        assert F.new_findings([f1, f2, f3], base) == [f3]

    def test_key_excludes_line_and_message(self):
        a = F.Finding("lint", "r", "s.py::f", "msg one", line=1)
        b = F.Finding("lint", "r", "s.py::f", "msg two", line=99)
        assert a.key == b.key

    def test_committed_baseline_accepts_tree(self):
        """The committed baseline accepts the current tree's lint findings
        (the full gate incl. prover runs in scripts/ci.sh)."""
        base = F.load_baseline(
            os.path.join(SRC, "..", "scripts", "analysis_baseline.json"))
        fresh = F.new_findings(lint.lint_tree(SRC), base)
        assert fresh == [], [f.render() for f in fresh]


# ---------------------------------------------------------------------------
# sanitized kernel mode (satellite 4: interpreter-mode Pallas kernel)
# ---------------------------------------------------------------------------

class TestSanitizer:
    def setup_method(self):
        sanitize.set_enabled(True)
        sanitize.clear()

    def teardown_method(self):
        sanitize.set_enabled(None)
        sanitize.clear()

    def _launch(self, ws=None, wc=None):
        from repro.kernels import ops
        from repro.kernels.fused_join import pad_points

        rng = np.random.default_rng(0)
        pts = np.sort(rng.uniform(0, 1, (64, 2)), axis=0)
        c, tq, qp, n_off = 8, 16, 16, 9
        points_pad = pad_points(jnp.asarray(pts), c)
        ws = jnp.zeros((n_off, qp), jnp.int32) if ws is None else ws
        wc = jnp.zeros((n_off, qp), jnp.int32) if wc is None else wc
        # method='kernel' exercises the Pallas kernel in interpreter mode
        return ops.fused_join_hits(
            points_pad, jnp.zeros((qp, 8)), ws, wc,
            jnp.zeros((n_off,), jnp.int32), jnp.zeros((qp,), jnp.int32),
            0.1, c=c, n_real=2, unicomp=False, external=True, tq=tq,
            method="kernel")

    def test_clean_launch_passes(self):
        self._launch()
        assert sanitize.pending() == 1
        sanitize.raise_pending()              # no raise
        assert sanitize.pending() == 0

    def test_corrupted_window_descriptor_oob_gather(self):
        ws = jnp.zeros((9, 16), jnp.int32).at[0, 0].set(1000)
        wc = jnp.zeros((9, 16), jnp.int32).at[0, 0].set(3)
        self._launch(ws=ws, wc=wc)
        with pytest.raises(sanitize.SanitizerError, match="oob-gather"):
            sanitize.raise_pending()

    def test_undersized_window_cap(self):
        wc = jnp.zeros((9, 16), jnp.int32).at[0, 0].set(13)   # > c = 8
        self._launch(wc=wc)
        with pytest.raises(sanitize.SanitizerError, match="cap-overflow"):
            sanitize.raise_pending()

    def test_driver_drains_at_result(self):
        """The count->fill drivers raise pending codes at their sync
        points: a poisoned pending queue surfaces from PendingJoin.result."""
        from repro.core.query_join import prepare

        pts, eps = _uniform(n=100)
        pj = prepare(build_grid_host(pts, eps))
        pend = pj.join_async(pts[:4])
        sanitize.record("poisoned", jnp.asarray(7, jnp.int32))
        with pytest.raises(sanitize.SanitizerError):
            pend.result()

    def test_self_join_clean_under_sanitize(self):
        from repro.core import selfjoin

        pts, eps = _uniform(n=150)
        ref = brute_force_join(pts, eps)
        got = selfjoin.self_join(pts, eps, distance_impl="fused")
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
        assert sanitize.pending() == 0        # drained by the driver

    def test_decode(self):
        assert sanitize.decode(3) == ["oob-gather", "cap-overflow"]
        assert sanitize.decode(0) == []

    def test_env_gate(self):
        sanitize.set_enabled(None)
        old = os.environ.pop("REPRO_SANITIZE", None)
        try:
            assert not sanitize.enabled()
            os.environ["REPRO_SANITIZE"] = "1"
            assert sanitize.enabled()
            os.environ["REPRO_SANITIZE"] = "0"
            assert not sanitize.enabled()
        finally:
            if old is None:
                os.environ.pop("REPRO_SANITIZE", None)
            else:
                os.environ["REPRO_SANITIZE"] = old
