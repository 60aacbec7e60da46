"""ShardStep — the eq. (5) cycle as a per-shard step, in two renderings.

PR 5 wrote the paper's intake / hysteresis-gated drain / §6-gated exchange /
Fig. 1 report cycle once, as `transport.shard_worker_loop`, behind the
`TransportContext` seam.  That made the cycle transport-agnostic but left it
a *host* loop: a Python `while` driving numpy, which no accelerator can
run.  This module splits the cycle one level deeper — into a per-shard
**step** with two renderings:

  `HostShardStep`      — the host rendering: one `round()` is exactly one
                         iteration of the PR 5 worker loop (the loop body
                         was transplanted verbatim; tests/test_executor.py,
                         tests/test_transport.py and tests/test_runtime.py
                         golden-gate the threads/procpool behavior
                         bit-for-bit).  `transport.shard_worker_loop` is now
                         a thin driver over it.
  device step builders — the jax-traceable rendering: `shard_pt_apply` /
                         `shard_local_update` build one shard's eq. (5)
                         local update over the Pallas BSR path
                         (kernels/bsr_spmv, with the compensated/f64
                         accumulation lanes) or the segment-sum path;
                         `shard_superstep_fns` fuses it with an
                         `exchange.spmd_exchange` collective schedule (the
                         §6 sparsified top-k + forced-refresh rendering
                         included) and the all-reduced Fig. 1
                         `TerminationDriver.bits_step` into one traced
                         superstep body.  `core.spmd.solve_spmd` and
                         `runtime.device.DeviceShardTransport` both run
                         THIS body — the bulk-synchronous solver and the
                         async streaming drain share one traced function,
                         so every future kernel or collective win lands in
                         one place.

The device rendering's convergence test is pluggable (`conv=`):

  "linf"     — per-lane inf-norm of the fragment delta vs `tol` (the SPMD
               solver's historic criterion, bit-identical to pre-refactor).
  "l1_psum"  — the all-reduced L1 of the fragment delta vs `tol` (a global
               scalar, identical on every shard).  For the *linear* form
               (eq. 7) the fragment delta IS the local residual of the
               previous iterate, so the psum'd delta is ||r||_1 up to view
               staleness — the device transport's drain-to-target test, with
               the host-side exact recompute as the published certificate.

`comm_bytes_model` is the one byte-accounting model both the SPMD solver
and the device transport report through (checked against each other by
benchmarks/check_device_transport.py).
"""
from __future__ import annotations

import time
from typing import Optional

import numpy as np

from ..core.partition import Partition
from .exchange import ExchangePlan
from .observe import (C_CAPPED, C_CONVERGES, C_DIVERGES, C_DRAIN_MASS,
                      C_DRAIN_ROWS, C_DRAINS, C_EXCHANGE_BYTES,
                      C_EXCHANGE_ROWS, C_EXCHANGES, C_INTAKES, C_STOPS,
                      EV_CAPPED, EV_CONVERGE, EV_DIVERGE, EV_DRAIN,
                      EV_EXCHANGE, EV_INTAKE, EV_STOP, ShardObserver)


# ---------------------------------------------------------------------------
# host rendering — one round() == one iteration of the PR 5 worker loop
# ---------------------------------------------------------------------------
class HostShardStep:
    """One shard's eq. (5) cycle as a resumable step object.

    Construction captures everything the PR 5 loop hoisted above its
    `while`: the block geometry, the per-shard convergence target and drain
    floor, the boundary-batched exchange gate, and the cached L1s of the
    two O(n) structures this worker owns.  `round()` then runs exactly one
    loop iteration — intake, hysteresis-gated drain, §6-gated exchange,
    value publish, Fig. 1 report, idle backoff — and returns False on the
    loop's exit paths (observed STOP, round cap, push cap, own STOP).

    The body is the PR 5 `shard_worker_loop` body transplanted verbatim
    (split at the seam comments); the soundness argument is unchanged and
    lives in transport.py's module docstring.
    """

    def __init__(self, i: int, r: np.ndarray, part: Partition,
                 plan: ExchangePlan, cfg, ctx, drain_fn,
                 obs: Optional[ShardObserver] = None):
        self.i = i
        self.r = r
        self.part = part
        self.plan = plan
        self.cfg = cfg
        self.ctx = ctx
        self.drain_fn = drain_fn
        self.obs = obs

        self.p = part.p
        self.s, self.e = part.block(i)
        self.bs = self.e - self.s
        self.n = part.n
        self.conv_target = (cfg.l1_target * (self.bs / self.n)
                            if self.n else cfg.l1_target)
        self.drain_floor = 0.5 * self.conv_target
        self.outbox = ctx.outbox(i)
        self.peers = [d for d in range(self.p) if d != i]
        # boundary-batched DrainSchedule: pair shipments coalesce behind
        # this gate (None for every other schedule — the zero-cost default)
        self.gate = cfg.schedule.gate(self.p)
        # cached L1s of the two O(n) structures this worker owns — only
        # intake/drain/exchange can change them, so idle rounds cost O(p)
        # instead of O(n)
        self.own_l1 = float(np.abs(r[self.s:self.e]).sum())
        # a restarted worker can inherit a non-empty outbox (plan-withheld
        # or backpressured mass from the dead incarnation) — seed the cache
        # from the structure itself, never assume empty
        self.outbox_l1 = float(np.abs(self.outbox).sum())
        self.own_dirty = False
        self.outbox_dirty = False
        self.it = 0            # raw rounds (spin included): caps, telemetry
        self.updates = 0       # *local updates*: the ExchangePlan's clock
        self.tick_pending = False
        self.idle_total = 0.0
        self.prev_verdict: Optional[bool] = None  # Fig. 1 flip edge detector

    # -- the four seams, each a method so renderings/tests can drive them
    #    individually; round() composes them in the PR 5 order ------------
    def intake(self) -> bool:
        """Fold incoming mail + my uniform share; retract convergence
        BEFORE the mass leaves the sender's books (see transport.py)."""
        i, obs = self.i, self.obs
        progressed = False
        if self.ctx.intake_ready(i):
            t_ev = obs.now() if obs is not None else 0.0
            self.ctx.retract(i)
            if self.ctx.fold_intake(i, self.r, self.s, self.e):
                progressed = True
                self.own_dirty = True
            if obs is not None:
                obs.ctr[i, C_INTAKES] += 1
                obs.emit(EV_INTAKE, i, t_ev, dur=obs.now() - t_ev,
                         gen=self.updates, a=float(progressed))
        return progressed

    def drain(self, step_target: float) -> bool:
        """Hysteresis-gated local update: drain own rows to the sliding
        target, foreign contributions into the outbox."""
        i, cfg, obs = self.i, self.cfg, self.obs
        if self.own_dirty:
            self.own_l1 = float(np.abs(self.r[self.s:self.e]).sum())
            self.own_dirty = False
        did_drain = False
        if self.own_l1 > (cfg.hysteresis * step_target
                          if step_target > self.drain_floor
                          else self.drain_floor):
            if obs is None:
                got, c_add = self.drain_fn(i, self.s, self.e, step_target,
                                           self.outbox)
            else:
                t_ev = obs.now()
                a0 = (obs.attr[i].copy()
                      if obs.attr is not None else None)
                got, c_add = self.drain_fn(i, self.s, self.e, step_target,
                                           self.outbox)
                dt_ev = obs.now() - t_ev
                da_local = da_boundary = 0.0
                if a0 is not None:
                    da = obs.attr[i] - a0
                    da_local, da_boundary = float(da[1]), float(da[2])
                obs.ctr[i, C_DRAINS] += 1
                obs.ctr[i, C_DRAIN_ROWS] += got
                obs.ctr[i, C_DRAIN_MASS] += max(self.own_l1 - step_target,
                                                0.0)
                obs.observe_drain_s(i, dt_ev)
                obs.emit(EV_DRAIN, i, t_ev, dur=dt_ev, gen=self.updates,
                         a=float(got), b=self.own_l1, c=da_local,
                         d=da_boundary)
            self.ctx.uniform_add(i, c_add)
            self.own_dirty = self.outbox_dirty = True
            did_drain = True
            self._drain_got = got
        return did_drain

    def exchange(self, step_target: float) -> bool:
        """§6-gated exchange: plan consulted per *local update*; the
        boundary-batched gate and mass gates may withhold (mass stays in
        the counted outbox)."""
        i, cfg, obs = self.i, self.cfg, self.obs
        plan, gate, ctx = self.plan, self.gate, self.ctx
        progressed = False
        self.updates += 1
        self.tick_pending = False
        if self.outbox_dirty:
            self.outbox_l1 = float(np.abs(self.outbox).sum())
            self.outbox_dirty = False
        for d in self.peers:
            if not plan.wants(i, d, self.updates):
                continue
            if self.outbox_l1 == 0.0:
                # nothing pending anywhere: the receiver's copy already
                # reflects everything this shard produced, so the epoch
                # counts as a (zero-byte) refresh — quiet pairs must not
                # bank forced-refresh debt
                plan.note_sent(i, d, self.updates)
                if gate is not None:
                    gate.note_quiet(d, self.updates)
                continue
            sd, ed = self.part.block(d)
            box = self.outbox[sd:ed]
            mass = float(np.abs(box).sum())
            if mass == 0.0:
                plan.note_sent(i, d, self.updates)
                if gate is not None:
                    gate.note_quiet(d, self.updates)
                continue
            if gate is not None and not gate.ready(
                    d, self.updates, mass, step_target):
                # boundary-batched: the pair's mass keeps folding in the
                # outbox (still counted in this shard's value) until the
                # batch window expires or the coalesced payload is worth
                # a generation
                continue
            if not plan.gate_mass(i, d, self.updates, mass):
                continue
            t_ev = obs.now() if obs is not None else 0.0
            nz = ctx.send(i, d, box)
            if nz < 0:
                # channel backpressure (a full procpool ring): the mass
                # stays in the outbox — still counted in this shard's
                # value — and ships on a later update
                continue
            if obs is not None:
                nbytes = nz * (4 + cfg.bytes_per_entry)
                obs.ctr[i, C_EXCHANGES] += 1
                obs.ctr[i, C_EXCHANGE_ROWS] += nz
                obs.ctr[i, C_EXCHANGE_BYTES] += nbytes
                obs.emit(EV_EXCHANGE, i, t_ev,
                         dur=obs.now() - t_ev, gen=self.updates,
                         a=float(d), b=float(nz), c=float(nbytes))
            self.outbox_dirty = True
            plan.note_sent(i, d, self.updates)
            plan.on_result(i, d, True)
            if gate is not None:
                gate.note_sent(d, self.updates)
            ctx.note_exchange(i, nz)
            progressed = True
        return progressed

    def value(self) -> float:
        """Everything this shard is accountable for right now (the
        conservation invariant): own rows, undelivered outbox, channel
        mass *I* put in flight, and my rows' share of the pending
        uniform."""
        if self.own_dirty:
            self.own_l1 = float(np.abs(self.r[self.s:self.e]).sum())
            self.own_dirty = False
        if self.outbox_dirty:
            self.outbox_l1 = float(np.abs(self.outbox).sum())
            self.outbox_dirty = False
        return (self.own_l1 + self.outbox_l1
                + abs(self.ctx.uniform_pending(self.i)) * self.bs
                + self.ctx.inflight_l1(self.i))

    def report(self, value: float) -> bool:
        """Fig. 1, message rendering: publish the verdict; True = STOP."""
        i, obs = self.i, self.obs
        verdict = value <= self.conv_target
        if obs is not None and verdict != self.prev_verdict:
            if verdict:
                obs.ctr[i, C_CONVERGES] += 1
                obs.emit(EV_CONVERGE, i, obs.now(), gen=self.updates,
                         a=value)
            else:
                obs.ctr[i, C_DIVERGES] += 1
                obs.emit(EV_DIVERGE, i, obs.now(), gen=self.updates,
                         a=value)
            self.prev_verdict = verdict
        self._verdict = verdict
        return self.ctx.report(i, verdict, self.it)

    # -- one full round ---------------------------------------------------
    def round(self) -> bool:
        """Run one cycle round; False means the worker loop should exit."""
        i, cfg, ctx, obs = self.i, self.cfg, self.ctx, self.obs
        if ctx.stopped():
            # the other clean exit: a peer's report chain stamped the
            # global STOP and this shard observed it at the round top —
            # trace it so every shard's stream ends in exactly one STOP
            # (the report()-True path below emits its own)
            if obs is not None:
                obs.ctr[i, C_STOPS] += 1
                obs.emit(EV_STOP, i, obs.now(), gen=self.updates,
                         a=float(self.it))
            return False
        if self.it >= cfg.max_rounds:
            if obs is not None:
                obs.ctr[i, C_CAPPED] += 1
                obs.emit(EV_CAPPED, i, obs.now(), gen=self.updates,
                         a=float(self.it))
            ctx.note_capped()
            return False
        self.it += 1
        progressed = False

        # -- receive ------------------------------------------------------
        if self.intake():
            progressed = True

        # -- local update: drain own rows to a sliding target -------------
        approx_total = ctx.values_total()
        step_target = max(self.drain_floor,
                          cfg.drain_frac * approx_total / self.p)
        did_drain = self.drain(step_target)
        if did_drain and self._drain_got:
            ctx.add_pushes(i, self._drain_got)
            progressed = True
        if (cfg.max_total_pushes is not None
                and ctx.total_pushes() > cfg.max_total_pushes):
            if obs is not None:
                obs.ctr[i, C_CAPPED] += 1
                obs.emit(EV_CAPPED, i, obs.now(), gen=self.updates,
                         a=float(self.it))
            ctx.note_capped()
            return False

        # -- exchange: plan consulted per *local update*, not per spin
        #    round — idle-converged rounds must not tick the §6 refresh
        #    clock.  A blocked-but-unconverged round (tick_pending) still
        #    ticks: mass parked above the convergence target keeps the
        #    bounded-delay escape hatch live. -----------------------------
        if did_drain or self.tick_pending:
            if self.exchange(step_target):
                progressed = True

        # -- value + Fig. 1 report ----------------------------------------
        if self.report(self.value_and_publish()):
            if obs is not None:
                obs.ctr[i, C_STOPS] += 1
                obs.emit(EV_STOP, i, obs.now(), gen=self.updates,
                         a=float(self.it))
            return False
        if not self._verdict and not progressed:
            # parked above target with the plan withholding: count the
            # next round as a local update so the forced refresh can fire
            # (no livelock)
            self.tick_pending = True

        # -- idle backoff: park until mail can have arrived ---------------
        if not progressed:
            t_idle = time.perf_counter()
            ctx.idle_wait(cfg.idle_sleep)
            self.idle_total += time.perf_counter() - t_idle
        return True

    def value_and_publish(self) -> float:
        v = self.value()
        self.ctx.publish_value(self.i, v)
        return v


# ---------------------------------------------------------------------------
# device rendering — the jax-traceable step (shared by SPMD + DeviceShard)
# ---------------------------------------------------------------------------
def hash_uniform(seed: int, step, lane):
    """Counter-based uniform in [0, 1): a SplitMix-style integer mix of
    (seed, superstep, shard). jax.random inside shard_map lowers to a
    PartitionId instruction XLA's SPMD partitioner rejects; this hash is
    deterministic, partitionable, and plenty for a drop model."""
    import jax.numpy as jnp
    z = (step.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
         + lane.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)
         + jnp.uint32(seed & 0xFFFFFFFF))
    z = (z ^ (z >> 16)) * jnp.uint32(0x7FEB352D)
    z = (z ^ (z >> 15)) * jnp.uint32(0x846CA68B)
    z = z ^ (z >> 16)
    return z.astype(jnp.float32) * jnp.float32(2.0 ** -32)


def shard_pt_apply(op_slice: tuple, *, use_bsr: bool, bsize: int,
                   nv: int, n_pad: int = 0, bm: int = 0,
                   impl: str = "ref", accum: str = "f32"):
    """One shard's P^T apply over its operator slice.

    op_slice: (blk, bcols, rgroups, hrow, hcol, hval) for the BSR
    backend — the Pallas block kernel (fed the view in its lane-dense
    (nv, nbc, bn) layout) plus the hub segment-sum side path — or
    (src, wgt, rid) for the segment-sum backend.  `accum` threads the
    kernel's accumulation lane through (f32 | kahan | f64): with "f32" the
    view is cast to float32 on entry (the historic MXU contract); the
    tight lanes keep the view's own dtype so an x64 device program stays
    f64 end to end.
    """
    import jax
    import jax.numpy as jnp

    if use_bsr:
        from ..kernels.bsr_spmv import bsr_matvec
        blk_, bcols_, rgroups_, hrow_, hcol_, hval_ = op_slice

        def pt_apply(view):
            cast = view.astype(jnp.float32) if accum == "f32" else view
            xb = cast.T.reshape(nv, n_pad // bm, bm)
            y = bsr_matvec(blk_, bcols_, xb, impl=impl, accum=accum,
                           row_groups=rgroups_)
            hub = jax.ops.segment_sum(
                hval_.astype(cast.dtype)[:, None] * cast[hcol_],
                hrow_, num_segments=bsize)
            return (y.reshape(nv, bsize).T + hub).astype(view.dtype)
        return pt_apply

    src_, wgt_, rid_ = op_slice

    def pt_apply(view):
        contrib = wgt_[:, None] * view[src_]
        return jax.ops.segment_sum(contrib, rid_, num_segments=bsize)
    return pt_apply


def shard_local_update(pt_apply, *, alpha: float, linear: bool, n: int,
                       vb, val, dang):
    """f_i: one shard's eq. (5) local update — the new own fragment from
    the (stale) full view, per lane.  The scalar dangling/teleport
    corrections are masked so block-aligned padding rows stay exactly
    zero.  `vb` (bsize, nv) teleport fragment, `val` (bsize,) valid-row
    mask, `dang` (n_pad,) dangling mask in packed-view coordinates."""
    import jax.numpy as jnp

    def local_update(view):
        y = alpha * pt_apply(view)
        dmass = jnp.sum(jnp.where(dang[:, None], view, 0.0), axis=0)
        y = y + alpha * dmass[None, :] / n * val[:, None]
        if linear:
            y = y + (1.0 - alpha) * vb
        else:
            y = y + (1.0 - alpha) * jnp.sum(view, axis=0)[None, :] \
                * vb
        return y * val[:, None]
    return local_update


def shard_superstep_fns(local_update, comm, *, i, p: int, tol: float,
                        pc_max_compute: int, pc_max_monitor: int,
                        seed: int, q: float, freeze_lanes: bool,
                        max_steps, compact_exit: bool = False,
                        exit_k: int = 0, conv: str = "linf",
                        axis: str = "ue"):
    """The one traced superstep body + loop condition.

    Fuses the shard's local update, the collective exchange schedule
    (`exchange.spmd_exchange` — §6 sparsified targeting included) and the
    all-reduced Fig. 1 protocol (`TerminationDriver.bits_step` over the
    transport layer's mesh psum) into one function of the loop carry:

      (view, frag, comm_state, step, pc, mon_pc, lane_done, lane_step,
       rows_sent, fulls)

    `conv` picks the convergence criterion (see module docstring); both
    run through the identical bits_step persistence machinery.
    """
    import jax.numpy as jnp
    from . import driver as _driver
    from . import transport as _transport

    def superstep(carry):
        (view, frag, comm_state, step, pc, mon_pc, lane_done,
         lane_step, rows_sent, fulls) = carry
        newfrag = local_update(view)
        if freeze_lanes:
            # frozen lanes keep their fragment — the monitor already
            # observed persistent global convergence
            newfrag = jnp.where(lane_done[None, :], frag, newfrag)
        delta = jnp.abs(newfrag - frag)
        if conv == "linf":
            locally_conv = jnp.max(delta, axis=0) < tol       # (nv,)
        else:
            # "l1_psum": the all-reduced L1 of the fragment delta — for
            # the linear form this is ||r||_1 of the previous iterate up
            # to view staleness, identical on every shard (the
            # value-rendering of Fig. 1 mapped onto the bit machinery)
            total = _transport.mesh_psum(axis)(jnp.sum(delta, axis=0))
            locally_conv = total <= tol                       # (nv,)

        # ---- communication (ExchangePlan, bulk-sync) ---------------------
        accept = hash_uniform(seed, step, i) < q
        view, comm_state, nsent, nfull = comm(
            i, view, newfrag, comm_state, step, accept)

        # ---- in-loop Fig. 1 protocol (all-reduced bits) ------------------
        # the reduction channel comes from the transport layer: the mesh
        # psum is the bulk-synchronous rendering of the same seam the
        # host drivers reduce through
        pc, mon_pc, done_now = _driver.TerminationDriver.bits_step(
            locally_conv, pc, mon_pc, p=p,
            pc_max_compute=pc_max_compute,
            pc_max_monitor=pc_max_monitor,
            psum=_transport.mesh_psum(axis))
        lane_step = jnp.where(done_now & (lane_step < 0),
                              step + 1, lane_step)
        # counter dtypes pinned: under enable_x64 the schedule closures'
        # counts can come back int64 and silently widen the carry
        return (view, newfrag, comm_state, step + 1, pc, mon_pc,
                done_now, lane_step,
                rows_sent + jnp.asarray(nsent, rows_sent.dtype),
                fulls + jnp.asarray(nfull, fulls.dtype))

    def cond(carry):
        _, _, _, step, _, _, lane_done, *_ = carry
        keep = jnp.logical_and(~jnp.all(lane_done), step < max_steps)
        if compact_exit:
            # the pow2-compaction hook: once exit_k lanes are frozen,
            # hand control back to the host so the stack can shrink
            # instead of masking dead lanes
            keep = jnp.logical_and(
                keep, jnp.sum(lane_done.astype(jnp.int32)) < exit_k)
        return keep

    return superstep, cond


def init_carry(myx, init_comm, *, nv: int, n_pad: int, axis: str = "ue"):
    """The loop carry at step 0: full view all-gathered from the shard
    fragments, fresh protocol counters, zeroed comm telemetry."""
    import jax
    import jax.numpy as jnp
    view0 = jax.lax.all_gather(myx, axis).reshape(n_pad, nv)
    # the step counter is pinned to int32 — under enable_x64 a bare
    # jnp.asarray(0) would turn int64 and ripple into the schedule
    # closures' index arithmetic
    return (view0, myx, init_comm(myx), jnp.asarray(0, jnp.int32),
            jnp.zeros(nv, jnp.int32), jnp.zeros(nv, jnp.int32),
            jnp.zeros(nv, bool), jnp.full(nv, -1, jnp.int32),
            jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32))


def comm_bytes_model(schedule: str, *, p: int, bsize: int, itemsize: int,
                     nv: int, steps: int, rows: int, fulls: int,
                     sync_every: int = 4) -> int:
    """Payload bytes moved by one shard_map loop segment — the single
    byte-accounting model for every device-side exchange schedule (the
    static schedules scale with the lane count; sparsified uses the
    honest in-loop (rows, fulls) counters)."""
    frag_bytes = bsize * itemsize
    if schedule == "ring":
        return p * frag_bytes * nv * steps
    if schedule == "allgather_k":
        return (p * (p - 1) * frag_bytes * nv // sync_every) * steps
    if schedule == "sparsified":
        # (idx, value-lanes) pairs to p-1 peers per sparse payload row,
        # plus the forced full refreshes (each due step is one full
        # all-gather)
        entry = 4 + itemsize * nv
        return (rows * (p - 1) * entry
                + fulls * (p - 1) * frag_bytes * nv)
    return p * (p - 1) * frag_bytes * nv * steps
