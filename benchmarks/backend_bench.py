"""Matvec-backend benchmark: us-per-apply for segment_sum vs bsr_pallas at
several graph sizes, the host-side BSR packing micro-bench (bincount scatter
vs the old np.add.at scatter), and a solver-level rank-agreement record.

Writes the machine-readable perf trajectory file (BENCH_PR<N>.json at the
repo root, consumed by CI / later PRs to track the hot path over time);
benchmarks.run passes the current PR's path via ``--out``.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp

from repro.graph.generate import powerlaw_webgraph
from repro.graph.csr import TransitionT
from repro.graph.google import GoogleOperator
from repro.core import solve_power, kendall_tau_topk
from repro.core.backend import as_spec, prepare, google_apply
from repro.kernels.bsr_spmv import build_hybrid_bsr

REPO_ROOT = Path(__file__).parent.parent
RESULTS = Path(__file__).parent / "results"

SIZES = ((5_000, 40_000), (16_384, 131_072), (50_000, 400_000))


def _time(f, n=10):
    jax.block_until_ready(f())  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        r = f()
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / n


def apply_bench(sizes=SIZES, nv=1, seed=4):
    """Fused Google-apply wall time per backend (jitted, device-resident)."""
    rows = []
    for n, nnz in sizes:
        g = powerlaw_webgraph(n=n, target_nnz=nnz,
                              n_dangling=max(4, n // 1000), seed=seed)
        op = GoogleOperator(pt=TransitionT.from_graph(g), alpha=0.85)
        rec = dict(n=n, nnz=g.nnz, nv=nv)
        for name in ("segment_sum", "bsr_pallas"):
            spec = as_spec(name)
            dev, meta, x0 = prepare(op, spec, dtype=jnp.float32,
                                    v=np.tile(op.teleport()[:, None],
                                              (1, nv)))
            from functools import partial

            @partial(jax.jit, static_argnames=())
            def step(dev, x, _meta=meta):
                return google_apply(_meta, dev, x, False)

            t = _time(lambda: step(dev, x0))
            rec[f"{name}_us_per_apply"] = t * 1e6
            if name == "bsr_pallas":
                hyb = op.hybrid_bsr(bm=spec.bm, bn=spec.bm,
                                    hub_quantile=spec.hub_quantile)
                rec.update(bsr_impl=spec.impl, bsr_bm=spec.bm,
                           bsr_K=hyb.bsr.K,
                           bsr_fill_ratio=hyb.bsr.fill_ratio,
                           hub_nnz_frac=hyb.hub_nnz_frac)
        print(f"  apply n={n:6d} nnz={g.nnz:7d}: "
              f"segment_sum={rec['segment_sum_us_per_apply']:.0f}us "
              f"bsr_pallas[{rec['bsr_impl']}]="
              f"{rec['bsr_pallas_us_per_apply']:.0f}us "
              f"(K={rec['bsr_K']}, fill={rec['bsr_fill_ratio']:.4f}, "
              f"hub={rec['hub_nnz_frac']:.2%})")
        rows.append(rec)
    return rows


_PACK_CHILD = """
import numpy as np, time
from repro.graph.generate import powerlaw_webgraph
from repro.graph.csr import TransitionT
from repro.kernels.bsr_spmv import build_bsr, build_hybrid_bsr
g = powerlaw_webgraph(n={n}, target_nnz={nnz}, n_dangling=16, seed={seed})
pt = TransitionT.from_graph(g)
rows = pt.row_ids.astype(np.int64); cols = pt.src.astype(np.int64)
vals = np.asarray(pt.weight, np.float32)
t0 = time.perf_counter()
if "{mode}" == "seed":
    # the seed path verbatim: fixed-K layout at the kernel default block
    # size, np.add.at scatter, no hub split
    build_bsr(rows, cols, vals, pt.n, pt.n, bm=128, bn=128,
              scatter="add_at")
else:
    build_hybrid_bsr(rows, cols, vals, pt.n, pt.n, bm={bm}, bn={bm},
                     hub_quantile=0.99, unique_pairs=True)
print((time.perf_counter() - t0) * 1e3)
"""


def _pack_cold(mode, n, nnz, bm, seed):
    """One cold packing run in a fresh process (packing happens once per
    operator and is then cached, so cold is the scenario that matters —
    in-process repeats inherit warm pages and measure something else)."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    # host packing only: the child must never reach a chip
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(REPO_ROOT / "src")
    code = _PACK_CHILD.format(mode=mode, n=n, nnz=nnz, bm=bm, seed=seed)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    return float(out.stdout.strip().splitlines()[-1])


def packing_bench(n=32_768, nnz=262_144, bm=0, seed=4, repeats=3):
    """Host-side BSR packing: the solve-grade recipe (hub split + raveled
    bincount/assignment scatter + CPU-sized blocks) vs the seed recipe
    (fixed-K 128x128 layout + np.add.at), one cold build per process.

    n defaults to the largest size the seed recipe packs in a few hundred
    MB; at the acceptance scale (50k) its dense-block array would need
    ~10 GB of host memory, so only the solve-grade recipe is timed there.
    """
    if bm == 0:
        from repro.core.backend import as_spec
        bm = as_spec("bsr_pallas").bm
    med = lambda xs: float(np.median(xs))
    t_seed = med([_pack_cold("seed", n, nnz, bm, seed)
                  for _ in range(repeats)])
    t_new = med([_pack_cold("new", n, nnz, bm, seed)
                 for _ in range(repeats)])

    n50, nnz50 = 50_000, 400_000
    t_new_50k = med([_pack_cold("new", n50, nnz50, bm, 3)
                     for _ in range(repeats)])

    rec = dict(
        platform="cpu",      # host packing in JAX_PLATFORMS=cpu children
        acceptance_scale=dict(
            n=n50, nnz=nnz50, solve_grade_cold_ms=t_new_50k),
        largest_seed_packable=dict(
            n=n, nnz=nnz, bm=bm,
            seed_add_at_cold_ms=t_seed,
            solve_grade_cold_ms=t_new,
            speedup=t_seed / t_new),
        note=("cold one-shot builds, median of fresh processes; packing is "
              "memoized on GoogleOperator so it runs once per operator. "
              "numpy>=1.24 already vectorized ufunc.at, so the same-layout "
              "scatter swap alone is ~2-3x; the big win is the solve-grade "
              "layout (hub split + CPU-sized blocks) that keeps packing "
              "linear where the seed layout grows quadratically and OOMs."))
    print(f"  packing n={n}: seed(add_at,128)={t_seed:.0f}ms "
          f"solve-grade(bincount,{bm})={t_new:.0f}ms "
          f"({t_seed / t_new:.1f}x); n=50k: new={t_new_50k:.0f}ms")
    return rec


def solver_bench(n=50_000, nnz=400_000, seed=3):
    """Solver-level check: both backends end to end, rank agreement."""
    g = powerlaw_webgraph(n=n, target_nnz=nnz, n_dangling=50, seed=seed)
    op = GoogleOperator(pt=TransitionT.from_graph(g), alpha=0.85)
    t0 = time.perf_counter()
    ref = solve_power(op, tol=1e-9, max_iters=500)
    t_ref = time.perf_counter() - t0
    t0 = time.perf_counter()
    bsr = solve_power(op, tol=1e-6, max_iters=300, backend="bsr_pallas")
    t_bsr = time.perf_counter() - t0
    tau = kendall_tau_topk(ref.x, bsr.x, k=100)
    rec = dict(n=n, nnz=g.nnz, segment_sum_iters=ref.iters,
               segment_sum_s=t_ref, bsr_pallas_iters=bsr.iters,
               bsr_pallas_s=t_bsr, kendall_tau_top100=tau)
    print(f"  solver n={n}: segsum {ref.iters}it/{t_ref:.1f}s "
          f"bsr {bsr.iters}it/{t_bsr:.1f}s tau100={tau:.5f}")
    return rec


def main(out_path: Path = REPO_ROOT / "BENCH_PR2.json"):
    rec = dict(
        bench="matvec backends",
        device=jax.default_backend(),
        note=("us_per_apply is the fused Google-apply (SpMV + dangling + "
              "teleport) per backend; on CPU bsr_pallas lowers to the "
              "blocked-einsum contraction, on TPU to the Pallas MXU "
              "kernel"),
        apply=apply_bench(),
        packing=packing_bench(),
        solver=solver_bench(),
    )
    out_path.write_text(json.dumps(rec, indent=1))
    RESULTS.mkdir(exist_ok=True, parents=True)
    (RESULTS / "backend_bench.json").write_text(json.dumps(rec, indent=1))
    print(f"  wrote {out_path}")
    return rec


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
