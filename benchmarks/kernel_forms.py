"""On-chip micro-benchmark of the BSR kernel's streaming schedule (TPU only).

    python benchmarks/kernel_forms.py [--reps 10] [--out FILE.jsonl]

Builds a block array at the Stanford-Web replica's shape on the device
(2203 block-rows of 128x128 f32 blocks, up to 70 stored per row, rows
ragged between 20 and 70 with median ~46) and times one `bsr_spmv` apply,
warm, as the mean of `--reps` applies chained inside one jitted loop:

  * group sizes Kg at nv = 1 (the VPU form), which set `BLOCK_VMEM_BYTES`;
  * the VPU and MXU product forms at nv = 1..16, which set
    `VPU_MAX_LANES`;
  * each form and accumulation lane against the blocked-einsum oracle at
    Precision.HIGHEST (max error over the largest output, first 256 block
    rows), at nv = 1 and 16.

One JSON line per measurement on standard output, and in `--out` when
given. Refuses to run off a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

NBR, K_MAX, BM = 2203, 70, 128


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp
    import importlib
    from repro.kernels.bsr_spmv import bsr_spmv, bsr_spmv_ref
    bk = importlib.import_module("repro.kernels.bsr_spmv.bsr_spmv")

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"refused: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    sink = None
    if args.out:
        out = Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        sink = out.open("w")

    def emit(**rec):
        rec["device_kind"] = dev.device_kind
        line = json.dumps(rec)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")

    rng = np.random.default_rng(0)
    counts = np.clip(rng.normal(46, 12, NBR).round(), 20, K_MAX).astype(int)
    counts[rng.integers(0, NBR)] = K_MAX
    K = 80                        # a multiple of every group size below
    cols = np.zeros((NBR, K), np.int32)
    for r, c in enumerate(counts):
        cols[r, :c] = np.sort(rng.choice(NBR, size=c, replace=False))
    @jax.jit
    def make_blocks(counts):
        # one fused elementwise pass, so only the ~11.5 GB result is held
        r, k, i, j = (jax.lax.broadcasted_iota(jnp.int32, (NBR, K, BM, BM), d)
                      for d in range(4))
        v = ((r * 31 + k * 17 + i * 7 + j * 3) % 101).astype(jnp.float32)
        return jnp.where(k < counts[:, None, None, None], v * 1e-5, 0.0)

    blocks = jax.block_until_ready(make_blocks(jnp.asarray(counts)))
    cols = jnp.asarray(cols)
    emit(what="layout", nbr=NBR, K=K, stored=int(counts.sum()),
         median=float(np.median(counts)), max=int(counts.max()))

    def schedule(group, form):
        """Set the kernel's group cap and product form (its module
        constants) and return the rows' group counts."""
        bk.BLOCK_VMEM_BYTES = 2 * 4 * BM * BM * group
        bk.VPU_MAX_LANES = 16 if form == "vpu" else 0
        assert bk.group_size(K, BM, BM) == group
        jax.clear_caches()
        return jnp.asarray(-(-counts // group), jnp.int32)

    def timed(nv, group, form, accum="f32"):
        x = jax.random.uniform(jax.random.key(nv), (nv, NBR, BM))
        rg = schedule(group, form)

        @jax.jit
        def loop(blocks, cols, rg, x):
            def body(_, x):
                y = bsr_spmv(blocks, cols, x, rg, accum=accum)
                return y / jnp.max(y)
            return jax.lax.fori_loop(0, args.reps, body, x)

        t0 = time.perf_counter()
        jax.block_until_ready(loop(blocks, cols, rg, x))
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready(loop(blocks, cols, rg, x))
        s = (time.perf_counter() - t0) / args.reps
        streamed = int(np.asarray(rg).sum()) * group
        emit(what="time", nv=nv, group=group, form=form, accum=accum,
             ms_per_apply=s * 1e3, compile_s=compile_s,
             streamed_blocks=streamed,
             gb_per_s=streamed * BM * BM * 4 / s / 1e9)

    for group in (8, 10, 16, 20, 40):
        timed(1, group, "vpu")
    for nv in (1, 2, 4, 8, 16):
        for form in ("vpu", "mxu"):
            timed(nv, 16, form)

    # errors on the first 256 block rows (the oracle's gather and
    # contraction would not fit beside the whole array)
    rows = 256
    blocks_s, cols_s = blocks[:rows], cols[:rows]
    for nv in (1, 16):
        x = jax.random.uniform(jax.random.key(7), (nv, NBR, BM))
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(partial(bsr_spmv_ref, accum="f32"))(
                blocks_s, cols_s, x)
        scale = float(jnp.max(jnp.abs(ref)))
        for form in ("vpu", "mxu"):
            rg = schedule(16, form)[:rows]
            for accum in ("f32", "kahan"):
                y = bsr_spmv(blocks_s, cols_s, x, rg, accum=accum)
                emit(what="error", nv=nv, form=form, accum=accum,
                     max_rel_err=float(jnp.max(jnp.abs(y - ref))) / scale)
    if sink:
        sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
