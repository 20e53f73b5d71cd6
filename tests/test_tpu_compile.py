"""The fused join kernel compiles for a TPU v5e (described, not attached).

The rest of the suite runs the kernel in interpret mode, which cannot see
what Mosaic refuses: 64-bit scalars, scalar stores to VMEM, 1-D vectors,
unaligned DMA slices, boolean selects. These tests lower and compile
``_fused_join_hits_pallas`` with ``interpret=False`` for one chip of a
described ``v5e:2x2`` topology, at the real window capacities and query
tiles, with x64 on as ``import repro`` sets it. Each compile takes a couple
of seconds; nothing runs.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro  # noqa: F401  (x64 on, as every entry point has it)
from repro.kernels import fused_join as fj


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, *, c, tq, n_off=2, qp=1024, npts=8192, n_real=2,
             np_pad=fj.NP_PAD, **static):
    def shape(sh, dt):
        return jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)

    args = (shape((npts + c, np_pad), jnp.float32),
            shape((qp, np_pad), jnp.float32),
            shape((n_off, qp), jnp.int32), shape((n_off, qp), jnp.int32),
            shape((n_off,), jnp.int32), shape((qp,), jnp.int32),
            shape((qp,), jnp.int32), shape((1, 1), jnp.float32))
    run = jax.jit(lambda *a: fj._fused_join_hits_pallas(
        *a, c=c, tq=tq, n_real=n_real, unicomp=True, interpret=False,
        **static))
    return run.lower(*args).compile()


CASES = {
    # the L2 row loop over the bucket ladder's small and large capacities
    "row-c8-tq128": dict(c=8, tq=128),
    "row-c256-tq256": dict(c=256, tq=256),
    # the cell-run DMA loop, with and without the hit buffer
    "run-c32-tq128": dict(c=32, tq=128, run_loop=True),
    "run-count-c64-tq256": dict(c=64, tq=256, run_loop=True,
                                keep_hits=False),
    # merged-range sweep of the 6-D paper set (122 UNICOMP offsets)
    "merged-6d-c16": dict(c=16, tq=128, n_off=122, n_real=6, merged=True,
                          keep_hits=False),
    # slab join: global ids in the pad lane after the merged lane
    "merged-gid-c32": dict(c=32, tq=128, merged=True, gid_pairs=True),
    "cosine-c128": dict(c=128, tq=128, n_real=4, metric="cosine"),
    # jaccard: bitmap words widen the row to pad_width 16, popcount refine
    "jaccard-w16-c32": dict(c=32, tq=128, n_real=1, n_feat=12,
                            np_pad=fj.pad_width(13), metric="jaccard"),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_fused_kernel_compiles_for_v5e(one_chip, name):
    compiled = _compile(one_chip, **CASES[name])
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_kernel_compiles_with_x64_off(one_chip):
    with jax.enable_x64(False):
        compiled = _compile(one_chip, c=32, tq=128, merged=True)
    assert "tpu_custom_call" in compiled.as_text()
