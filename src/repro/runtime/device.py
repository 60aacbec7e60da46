"""DeviceShardTransport — the eq. (5) cycle as p device programs.

The third rendering of the shard transport seam (threads and procpool are
in transport.py): the per-shard cycle runs as a jax `shard_map` program —
one shard program per device along a `ue` mesh axis — built from the SAME
traced ShardStep the SPMD solver runs (runtime/step.py):

  drain     — `shard_local_update` over the shard's operator slice (the
              Pallas BSR block path with its compensated/f64 accumulation
              lanes, or the segment-sum slice).
  exchange  — an `exchange.spmd_exchange` collective schedule:
              `ppermute` ring, strided all-gathers, or the §6 sparsified
              plan (top-k |delta| rows as (idx, value) payloads with the
              forced-full-refresh bounded-delay escape hatch).
  report    — the all-reduced Fig. 1 bits (`TerminationDriver.bits_step`
              over `transport.mesh_psum`), fed by the *value* criterion:
              the psum'd L1 of the fragment delta, which for the linear
              form (eq. 7) is ||r||_1 of the previous iterate up to view
              staleness.

On CPU, p shard programs are exercised with
`XLA_FLAGS=--xla_force_host_platform_device_count=p` (the forced-host-
device idiom the multidevice tests use); on TPU the mesh maps onto
real devices.

Numerics contract: the streaming updater certifies ||x - x*||_1 <= tol at
tol = 1e-8 scales, below the float32 representation floor (~n * eps32) —
so the transport runs the whole program under `jax.enable_x64(True)`
when `dtype="float64"` (the default), with the segment-sum
backend whose operator slices are packed in the run dtype.  The BSR
backend keeps its blocks in float32 (the MXU layout); it is the TPU
rendering for looser tolerances and carries the compensated-summation
lane (`accum="kahan"`) to tighten accumulation error.

The transport reports its in-loop (rows, fulls) exchange counters through
`step.comm_bytes_model` — the identical accounting the SPMD solver uses,
cross-checked by benchmarks/check_device_transport.py.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

from .observe import span


@dataclasses.dataclass
class DeviceRunResult:
    """One device-program drain: the new iterate plus honest telemetry."""
    x: np.ndarray                # (n,) float64, NOT renormalized
    supersteps: int
    rows_sent: int               # sparsified: sparse payload rows shipped
    fulls: int                   # sparsified: forced full refreshes
    comm_bytes_total: int        # via step.comm_bytes_model
    device_resid: float          # final psum'd fragment-delta L1 (device view)
    converged: bool              # in-loop Fig. 1 fired before the step cap
    p: int = 0
    schedule: str = ""
    devices: int = 0             # devices the shard program's output spans


class DeviceShardTransport:
    """p shard programs over a `ue` device mesh, one ShardStep each.

    Unlike the host transports this rendering is bulk-synchronous inside
    (XLA collectives are), so "async" means what §6 says it means:
    sparsified, delayed, bounded-staleness exchange — not unblocked
    threads.  Determinism follows: a run is a pure function of
    (operator, x0, config), which neither host transport can promise.

    Parameters mirror the SPMD solver's exchange/backend knobs; `mesh`
    overrides the default first-p-devices mesh.
    """

    def __init__(self, p: int, *, exchange: str = "sparsified",
                 dtype: str = "float64", backend: str = "segment_sum",
                 bsr_bm: int = 0, bsr_impl: str = "auto",
                 accum: Optional[str] = None, sync_every: int = 4,
                 sparsify_k: int = 0, sparsify_thresh: float = 0.0,
                 sparsify_refresh_every: int = 4,
                 sparsify_adaptive: bool = False,
                 pc_max_compute: int = 1, pc_max_monitor: int = 1,
                 seed: int = 0, mesh=None):
        if exchange not in ("allgather", "allgather_k", "ring",
                            "sparsified"):
            raise ValueError(f"unknown exchange schedule {exchange!r}")
        if backend not in ("segment_sum", "bsr_pallas"):
            raise ValueError(f"unknown backend {backend!r}")
        self.p = int(p)
        self.exchange = exchange
        self.dtype = str(dtype)
        self.backend = backend
        self.bsr_bm = bsr_bm
        self.bsr_impl = bsr_impl
        # the accumulation lane: wide accumulate whenever the run itself
        # is wide, the plain f32 contract otherwise (callers may pin
        # "kahan" for the compensated kernel lane on f32 runs)
        self.accum = accum if accum is not None else (
            "f64" if self.dtype == "float64" else "f32")
        self.sync_every = sync_every
        self.sparsify_k = sparsify_k
        self.sparsify_thresh = sparsify_thresh
        self.sparsify_refresh_every = sparsify_refresh_every
        self.sparsify_adaptive = sparsify_adaptive
        self.pc_max_compute = pc_max_compute
        self.pc_max_monitor = pc_max_monitor
        self.seed = seed
        self.mesh = mesh

    # -- mesh ------------------------------------------------------------
    def _mesh(self):
        import jax
        if self.mesh is not None:
            return self.mesh
        devs = jax.devices()
        if len(devs) < self.p:
            raise RuntimeError(
                f"device transport needs {self.p} devices, have "
                f"{len(devs)}; on CPU launch with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={self.p}")
        # Auto: the shard_map body is fully manual and nothing outside it
        # reads sharding-in-types, so Auto and Explicit compile to the same
        # program (bitwise-equal results on 4 host devices); Auto is pinned
        # because JAX 0.9 changed make_mesh's default to Explicit
        return jax.make_mesh((self.p,), ("ue",),
                             axis_types=(jax.sharding.AxisType.Auto,),
                             devices=devs[: self.p])

    # -- the drain -------------------------------------------------------
    def run(self, op, x0: np.ndarray, *, target: float,
            max_supersteps: int = 2000,
            v: Optional[np.ndarray] = None,
            phase_s: Optional[Dict[str, float]] = None) -> DeviceRunResult:
        """Drain `op`'s linear form (eq. 7) from warm start `x0` until the
        all-reduced fragment-delta L1 holds <= `target` for the Fig. 1
        persistence window, or `max_supersteps` elapse.

        `target` is an *absolute* L1 threshold on the device-visible
        delta; the streaming caller derives it from its l1_target with a
        margin and publishes only the host-side exact-residual
        certificate (incremental._exact_residual), never this loop's own
        criterion.

        The run is three spans (`runtime.observe.span`), whose wall
        seconds are added to `phase_s` when it is given:
        `transport.pack` (host layout, exchange plan, uploads),
        `transport.dispatch` (trace, lower, compile or cache read,
        enqueue) and `transport.fetch` (reading the outputs back, which
        waits for the device program to finish).
        """
        if self.dtype == "float64":
            import jax
            with jax.enable_x64(True):
                return self._run(op, x0, target=target,
                                 max_supersteps=max_supersteps, v=v,
                                 phase_s=phase_s)
        return self._run(op, x0, target=target,
                         max_supersteps=max_supersteps, v=v,
                         phase_s=phase_s)

    def _run(self, op, x0: np.ndarray, *, target: float,
             max_supersteps: int, v: Optional[np.ndarray],
             phase_s: Optional[Dict[str, float]]) -> DeviceRunResult:
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from ..core.partition import block_rows
        from ..core.spmd import SPMDConfig, _pack_blocks, _resolve_bsr
        from . import step as _step
        from .exchange import spmd_exchange

        p = self.p
        n = op.n
        alpha = float(op.alpha)
        np_dtype = np.dtype(self.dtype)
        mesh = self._mesh()

        v_stack = np.asarray(op.teleport() if v is None else v,
                             dtype=np.float64)
        if v_stack.ndim == 1:
            v_stack = v_stack[:, None]
        if v_stack.shape != (n, 1):
            raise ValueError(f"device transport is single-lane; teleport "
                             f"has shape {v_stack.shape}")

        x0 = np.asarray(x0, dtype=np.float64)
        if x0.shape != (n,):
            raise ValueError(f"x0 has shape {x0.shape}, expected ({n},)")
        with span("transport.pack", into=phase_s):
            # reuse the SPMD packer verbatim (one packing layout to
            # maintain); only the schedule/backend fields are consulted
            cfg = SPMDConfig(p=p, schedule=self.exchange, dtype=self.dtype,
                             backend=self.backend, bsr_bm=self.bsr_bm,
                             bsr_impl=self.bsr_impl)
            part = block_rows(n, p)
            packed = _pack_blocks(op, part, np_dtype, cfg, v_stack)
            bsize, n_pad = packed["bsize"], packed["n_pad"]
            use_bsr = self.backend == "bsr_pallas"
            if use_bsr:
                bm, bsr_impl = _resolve_bsr(cfg)

            x0_blocks = np.zeros((p, bsize, 1), dtype=np_dtype)
            for i in range(p):
                s, t = part.block(i)
                x0_blocks[i, : t - s, 0] = x0[s:t]

            init_comm, comm = spmd_exchange(
                self.exchange, p=p, bsize=bsize, n_pad=n_pad,
                sync_every=self.sync_every, sparsify_k=self.sparsify_k,
                sparsify_row_thresh=self.sparsify_thresh,
                sparsify_refresh_every=self.sparsify_refresh_every,
                sparsify_adaptive=self.sparsify_adaptive,
                # endgame guard at the drain target's scale:
                # near-converged delta mass ships full payloads so the
                # persistence window can settle
                sparsify_endgame_mass=target)

            sh = lambda *spec: jax.NamedSharding(mesh, P(*spec))
            valid = jax.device_put(packed["valid"], sh("ue", None))
            dang = jax.device_put(
                np.broadcast_to(packed["dang"], (p, n_pad)).copy(),
                sh("ue", None))
            vblk = jax.device_put(packed["vblk"].astype(np_dtype),
                                  sh("ue", None, None))
            x0_dev = jax.device_put(x0_blocks, sh("ue", None, None))
            if use_bsr:
                op_args = tuple(
                    jax.device_put(packed[k], sh("ue", *([None] * nd)))
                    for k, nd in (("blk", 4), ("bcols", 2), ("rgroups", 1),
                                  ("hrow", 1), ("hcol", 1), ("hval", 1)))
            else:
                op_args = tuple(jax.device_put(packed[k], sh("ue", None))
                                for k in ("src", "wgt", "rid"))

        accum = self.accum

        # the program's name is what a profile shows (`jit_...`); the
        # named scopes tell its while loop from the final delta there
        def device_shard_drain(vblk, valid, dang, x0, *op_args):
            vb_, val_, dg_, myx = vblk[0], valid[0], dang[0], x0[0]
            i = jax.lax.axis_index("ue")
            op_slice = tuple(a[0] for a in op_args)
            if use_bsr:
                pt_apply = _step.shard_pt_apply(
                    op_slice, use_bsr=True, bsize=bsize, nv=1,
                    n_pad=n_pad, bm=bm, impl=bsr_impl, accum=accum)
            else:
                pt_apply = _step.shard_pt_apply(
                    op_slice, use_bsr=False, bsize=bsize, nv=1)
            local_update = _step.shard_local_update(
                pt_apply, alpha=alpha, linear=True, n=n,
                vb=vb_, val=val_, dang=dg_)
            superstep, cond = _step.shard_superstep_fns(
                local_update, comm, i=i, p=p, tol=target,
                pc_max_compute=self.pc_max_compute,
                pc_max_monitor=self.pc_max_monitor,
                seed=self.seed, q=1.0, freeze_lanes=False,
                max_steps=max_supersteps, conv="l1_psum", axis="ue")

            carry = _step.init_carry(myx, init_comm, nv=1, n_pad=n_pad,
                                     axis="ue")
            with jax.named_scope("drain_loop"):
                (view, frag, _, step, pc, mon_pc, lane_done, lane_step,
                 rows_sent, fulls) = jax.lax.while_loop(
                    cond, lambda c: superstep(c), carry)
            # final device-visible delta L1 (telemetry only — the caller
            # certifies with the host-side exact residual)
            from . import transport as _transport
            with jax.named_scope("final_delta"):
                dl1 = _transport.mesh_psum("ue")(
                    jnp.sum(jnp.abs(local_update(view) - frag)))
            return (frag[None], step[None], dl1[None],
                    lane_done[None], rows_sent[None], fulls[None])

        mapped = jax.shard_map(
            device_shard_drain, mesh=mesh,
            in_specs=(P("ue", None, None), P("ue", None), P("ue", None),
                      P("ue", None, None))
            + tuple(P("ue", *([None] * (a.ndim - 1))) for a in op_args),
            out_specs=(P("ue", None, None), P("ue"), P("ue"),
                       P("ue", None), P("ue"), P("ue")),
            check_vma=False,
        )
        with span("transport.dispatch", into=phase_s):
            outs = jax.jit(mapped)(vblk, valid, dang, x0_dev, *op_args)
        with span("transport.fetch", into=phase_s):
            frag_mat, steps, dl1, lane_done, rows_sent, fulls = (
                np.asarray(a) for a in outs)

        frag_mat = frag_mat.astype(np.float64, copy=False)
        supersteps = int(steps.max())
        x = np.empty(n, dtype=np.float64)
        for i in range(p):
            s, t = part.block(i)
            x[s:t] = frag_mat[i, : t - s, 0]
        rows_total = int(rows_sent.sum())
        fulls_total = int(fulls.sum())
        comm_total = _step.comm_bytes_model(
            self.exchange, p=p, bsize=bsize, itemsize=np_dtype.itemsize,
            nv=1, steps=supersteps, rows=rows_total, fulls=fulls_total,
            sync_every=self.sync_every)
        return DeviceRunResult(
            x=x, supersteps=supersteps, rows_sent=rows_total,
            fulls=fulls_total, comm_bytes_total=comm_total,
            device_resid=float(dl1[0]),
            converged=bool(lane_done.all()),
            p=p, schedule=self.exchange,
            devices=len(outs[0].sharding.device_set))
