"""Kernel benches (CPU container: correctness + arithmetic-intensity
derivations; wall-times are for the jnp reference paths — TPU numbers come
from the roofline analysis, not from this box)."""
from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp

from repro.graph.generate import powerlaw_webgraph
from repro.graph.csr import TransitionT, pt_matvec
from repro.kernels.bsr_spmv import bsr_from_transition, pad_x, spmv, \
    bsr_spmv_ref

RESULTS = Path(__file__).parent / "results"


def _time(f, n=5):
    f()  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        r = f()
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / n


def spmv_bench(n=16384, nnz=131072, nv=8):
    g = powerlaw_webgraph(n=n, target_nnz=nnz, n_dangling=16, seed=4)
    pt = TransitionT.from_graph(g)
    bsr = bsr_from_transition(pt)
    dev = {k: jnp.asarray(v) for k, v in pt.device_arrays().items()}
    x = np.random.default_rng(0).random((n, nv)).astype(np.float32)
    xp = jnp.asarray(pad_x(x, n, bsr.bn))
    xf = jnp.asarray(x[:, 0])

    t_csr = _time(jax.jit(lambda: pt_matvec(dev, xf, n)))
    t_ref = _time(jax.jit(lambda: bsr_spmv_ref(*bsr.device(), xp)))

    # derived: bytes and flops per multi-vector SpMV
    flops = 2.0 * g.nnz * nv
    blk_bytes = bsr.blocks.nbytes + bsr.blk_cols.nbytes
    csr_bytes = g.nnz * (4 + 4 + 4)
    rec = dict(
        n=n, nnz=g.nnz, nv=nv, K=bsr.K, nbr=bsr.nbr,
        fill_ratio=bsr.fill_ratio,
        csr_matvec_us=t_csr * 1e6, bsr_ref_multivec_us=t_ref * 1e6,
        flops_multivec=flops,
        bsr_bytes=blk_bytes, csr_bytes=csr_bytes,
        bsr_arith_intensity=flops / blk_bytes,
        csr_arith_intensity=(2.0 * g.nnz) / csr_bytes,
    )
    print(f"  spmv n={n} nnz={g.nnz}: csr(1v)={t_csr*1e6:.0f}us "
          f"bsr-ref({nv}v)={t_ref*1e6:.0f}us "
          f"AI: bsr={rec['bsr_arith_intensity']:.3f} "
          f"csr={rec['csr_arith_intensity']:.3f} flop/B "
          f"(fill={bsr.fill_ratio:.4f}, K={bsr.K})")
    RESULTS.mkdir(exist_ok=True, parents=True)
    (RESULTS / "kernel_spmv.json").write_text(json.dumps(rec, indent=1))
    return rec


def flash_bench(B=1, H=8, S=1024, D=64):
    from repro.models.attention import flash_attn_jnp
    from repro.kernels.flash_attention import mha_ref
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    t_flash = _time(jax.jit(lambda: flash_attn_jnp(q, k, v, chunk_q=256,
                                                   chunk_k=256)))
    t_naive = _time(jax.jit(lambda: mha_ref(q, k, v)))
    flops = 4.0 * B * H * S * S * D
    rec = dict(B=B, H=H, S=S, D=D, flash_us=t_flash * 1e6,
               naive_us=t_naive * 1e6, flops=flops,
               naive_score_bytes=B * H * S * S * 4,
               flash_score_bytes=B * H * 256 * 256 * 4)
    print(f"  attn S={S}: flash={t_flash*1e6:.0f}us naive={t_naive*1e6:.0f}us"
          f" score-mem {rec['flash_score_bytes']/rec['naive_score_bytes']:.4f}x")
    (RESULTS / "kernel_attention.json").write_text(json.dumps(rec, indent=1))
    return rec


def main():
    print("[kernel] bsr spmv")
    spmv_bench()
    print("[kernel] flash attention (jnp path)")
    flash_bench()


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
