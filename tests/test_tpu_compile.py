"""Ahead-of-time compiles of the main path's device programs for a
described (not attached) v5e chip, at the Stanford-Web replica's shape.

Interpret mode cannot see what the Mosaic compiler refuses (SMEM and VMEM
budgets, tiling) nor whether a program fits HBM; these compiles can, with
no chip. Keep them in this one file: the topology may be described by one
process at a time, so the fixture that describes it must stay here (see
the fixture's docstring).
"""
import os

import pytest
import jax
import jax.numpy as jnp

from repro.core.backend import BackendMeta, BackendSpec, google_apply
from repro.core.pagerank import _solve_jit
from repro.graph.generate import STANFORD_N, STANFORD_NNZ
from repro.kernels.bsr_spmv import bsr_spmv

HBM_BYTES = 16 * 10**9          # one v5e chip
BM = 128
# hybrid_bsr(bm=128) of stanford_web_replica(seed=0): 2203 block-rows,
# K=70 blocks per row, 6.9% of the links on the hub COO side
NBR, K = 2203, 70
HUB_NNZ = 160_000


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2. Described here, never at import:
    only one process may load libtpu at a time, and under xdist every
    worker imports every test module."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("accum,nv", [("f32", 1), ("kahan", 1), ("f32", 16)])
def test_bsr_spmv_stanford_shape(one_chip, accum, nv):
    """The BSR kernel at the paper's graph size: a (nbr, K) column table
    in SMEM would overflow its 1 MiB; the chunked flat table fits."""
    fn = jax.jit(lambda b, c, x: bsr_spmv(b, c, x, accum=accum))
    compiled = fn.lower(
        _shape(one_chip, (NBR, K, BM, BM), jnp.float32),
        _shape(one_chip, (NBR, K), jnp.int32),
        _shape(one_chip, (NBR, BM, nv), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def test_fused_power_loop_stanford_shape(one_chip):
    """solve_power(backend="bsr_pallas")'s fused while_loop, as the chip
    runs it: the kernel inside, the ~10.1 GB block array not copied."""
    meta = BackendMeta(spec=BackendSpec(name="bsr_pallas", impl="pallas",
                                        bm=BM),
                       n=STANFORD_N, nv=1, n_pad=NBR * BM, alpha=0.85)
    f32, i32 = jnp.float32, jnp.int32
    dev = dict(blocks=_shape(one_chip, (NBR, K, BM, BM), f32),
               blk_cols=_shape(one_chip, (NBR, K), i32),
               hub_rows=_shape(one_chip, (HUB_NNZ,), i32),
               hub_cols=_shape(one_chip, (HUB_NNZ,), i32),
               hub_vals=_shape(one_chip, (HUB_NNZ,), f32),
               valid=_shape(one_chip, (NBR, BM, 1), f32),
               dang=_shape(one_chip, (NBR, BM, 1), f32),
               v=_shape(one_chip, (NBR, BM, 1), f32))
    compiled = _solve_jit.lower(
        dev, _shape(one_chip, (NBR, BM, 1), f32), _shape(one_chip, (1,), f32),
        meta=meta, linear=False, max_iters=1000).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def test_segment_sum_f64_apply_stanford_shape(one_chip):
    """The f64 gather + segment-sum Google apply (solve_linear's default,
    and the served updater's drain) at 281,903 x 2,312,497."""
    meta = BackendMeta(spec=BackendSpec(name="segment_sum"), n=STANFORD_N,
                       nv=1, n_pad=STANFORD_N, alpha=0.85)
    with jax.enable_x64(True):
        f64, i32 = jnp.float64, jnp.int32
        dev = dict(src=_shape(one_chip, (STANFORD_NNZ,), i32),
                   weight=_shape(one_chip, (STANFORD_NNZ,), f64),
                   row_ids=_shape(one_chip, (STANFORD_NNZ,), i32),
                   dangling=_shape(one_chip, (STANFORD_N,), jnp.bool_),
                   v=_shape(one_chip, (STANFORD_N, 1), f64))
        fn = jax.jit(lambda d, x: google_apply(meta, d, x, linear=True))
        compiled = fn.lower(
            dev, _shape(one_chip, (STANFORD_N, 1), f64)).compile()
    assert _device_bytes(compiled) < HBM_BYTES
