"""TPU-native bounded-staleness PageRank under shard_map (beyond-paper form).

True message-level asynchrony cannot exist inside one XLA program (its
collectives are bulk-synchronous). The paper's own conclusion points the way
to the TPU adaptation: the win is not unblocking threads but *reducing and
re-scheduling communication* — "we would like to avoid the use of all-to-all
communication schemes ... the flexibility of asynchronous iterations gives
us a choice on the targets of produced messages" (§6).

We therefore express asynchrony as bounded staleness over sparsified
collective schedules.  The schedules are the bulk-synchronous rendering of
`runtime.ExchangePlan` (see runtime/exchange.py — the host rendering drives
the DES engine and the sharded streaming updater):

  schedule="allgather"    : all-gather every superstep (synchronous baseline,
                            eq. 4 distributed).
  schedule="allgather_k"  : all-gather every k supersteps; local iterations
                            in between use stale fragments (staleness <= k-1).
  schedule="ring"         : one collective_permute stage per superstep — each
                            shard refreshes exactly one peer fragment per
                            step (1/p of the all-gather bytes; staleness of
                            fragment j at shard i is (i - j) mod p steps).
  schedule="sparsified"   : the §6 message-targeting plan — each shard ships
                            only the top-k rows whose |delta| since the last
                            send exceeds a threshold, as (idx, value) pairs;
                            payloads shrink as shards converge, and a forced
                            full all-gather every `sparsify_refresh_every`
                            supersteps keeps delays bounded.
  delivery_prob < 1       : models canceled/dropped messages (paper cancels
                            overdue send threads); a rejected delivery keeps
                            the stale copy, exactly like eq. (5) with larger
                            tau.

Every schedule's local update runs through the selected matvec backend
(cfg.backend): "segment_sum" (gather + segment-sum over the shard's edge
slice) or "bsr_pallas" (each UE packs its own block-row slice of P^T into
the hub-split BSR layout once, then every superstep is dense block
multiplies + a small segment-sum side path — the MXU form on TPU).

The teleport may be an (n, nv) stack: nv personalized PageRank lanes share
every operator load, with per-lane Fig. 1 termination counters.  With
``freeze_lanes=True`` a lane whose all-reduced monitor counter has fired is
frozen (its fragment stops updating — the multi-lane rendering of the
per-lane freezing in core.pagerank), so finished lanes stop perturbing the
exchange while slow lanes run to their own tolerance.

Convergence for all schedules follows from bounded delays (Frommer-Szyld
[15]; Lubachevsky-Mitra [21] for the unit-spectral-radius power form; the
sparsified plan's forced refresh is exactly the bounded-delay condition).
Termination detection runs in-loop through
`runtime.TerminationDriver.bits_step` — per-shard persistence counters plus
a monitor counter over the all-reduced convergence bits, the
bulk-synchronous rendering of Fig. 1.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .partition import Partition, block_rows
# submodule reference (see des.py): runtime.driver imports core.termination,
# so its class attributes may not exist yet during an `import repro.runtime`
from ..runtime import driver as _runtime_driver
from ..runtime import step as _runtime_step
from ..runtime import transport as _runtime_transport
from ..runtime.exchange import spmd_exchange
from ..graph.google import GoogleOperator


@dataclasses.dataclass
class SPMDConfig:
    p: int                       # number of UEs = mesh size along 'ue'
    schedule: str = "allgather"  # allgather | allgather_k | ring | sparsified
    sync_every: int = 4          # k for allgather_k
    delivery_prob: float = 1.0   # per-fragment acceptance probability
    tol: float = 1e-6            # local convergence threshold (inf-norm)
    pc_max_compute: int = 1
    pc_max_monitor: int = 1
    max_supersteps: int = 2000
    kind: str = "power"          # power (eq. 6) | linear (eq. 7)
    dtype: str = "float32"
    seed: int = 0
    backend: str = "segment_sum"  # segment_sum | bsr_pallas
    bsr_bm: int = 0               # block edge; 0 = auto (128 TPU / 8 CPU)
    bsr_impl: str = "auto"        # auto | pallas | interpret | ref
    hub_quantile: float = 0.99    # rows above this row-nnz quantile -> COO
    freeze_lanes: bool = False    # freeze lanes whose monitor counter fired
    compact_lanes: bool = False   # pow2 lane *compaction* between shard_map
    #                             # chunks: exit the while_loop once enough
    #                             # lanes are frozen (see compact_exit),
    #                             # shrink the (n, nv) stack to the
    #                             # unfinished lanes (padded to the next
    #                             # pow2) and re-enter — frozen lanes stop
    #                             # costing flops instead of being masked
    #                             # (requires freeze_lanes)
    compact_exit: Union[str, float] = "auto"
    #                             # when a compact chunk hands back to the
    #                             # host: a float f exits once done lanes
    #                             # >= ceil(f * lanes) (0.5 pins the
    #                             # historic half rule on pow2 widths);
    #                             # "auto" exits at the earliest count that
    #                             # can actually shrink the pow2 stack and,
    #                             # when the previous chunk's lane
    #                             # completions clustered, runs to all-done
    #                             # instead (a boundary would not pay)
    # --- sparsified schedule (runtime.ExchangePlan, §6 targeting) ---
    sparsify_k: int = 0           # max rows per payload; 0 = auto (bsize/8)
    sparsify_thresh: float = 0.0  # per-row |delta| floor (0 = any change)
    sparsify_refresh_every: int = 16  # forced full all-gather cadence
    sparsify_adaptive: bool = False   # pick k from the observed row-delta
    #                                 # distribution (sparsify_k becomes a
    #                                 # static budget; EWMA-smoothed)
    sparsify_cover_frac: float = 0.9  # |delta| mass the payload must cover
    sparsify_ewma: float = 0.5        # new-observation EWMA weight


@dataclasses.dataclass
class SPMDResult:
    x: np.ndarray                # (n,) — or (n, nv) for teleport stacks
    supersteps: int
    local_resid: np.ndarray      # (p,) final per-shard residuals
                                 # ((p, nv) for teleport stacks)
    comm_bytes_per_step: int     # payload bytes moved per superstep (model)
    comm_bytes_total: int = 0    # payload bytes over the whole run (model)
    rows_sent: int = 0           # sparsified: sparse payload rows shipped
    lane_supersteps: Optional[np.ndarray] = None  # (nv,) first-done step
    lane_chunks: int = 1         # shard_map chunks run (compact_lanes)
    # observe=True: one dict per shard_map chunk (lanes/steps/rows/fulls/
    # bytes).  The in-loop counters restart at zero on every chunk's
    # schedule re-keying, so the cumulative contract is
    # comm_bytes_total == sum(c["bytes"]) and rows_sent == sum(c["rows"])
    # across the log (pinned by tests/test_observe.py)
    chunk_log: Optional[List[dict]] = None
    devices: int = 0             # devices the sharded operands span


# the accept-draw hash moved to the shared step module; kept under the
# historic name for the kernel/SPMD tests that pin its distribution
_hash_uniform = _runtime_step.hash_uniform


def _resolve_bsr(cfg: SPMDConfig) -> Tuple[int, str]:
    """Resolve auto block size / impl with the same policy as the solver
    backends (single source of truth in BackendSpec.resolved())."""
    from .backend import BackendSpec
    spec = BackendSpec(name="bsr_pallas", impl=cfg.bsr_impl,
                       bm=cfg.bsr_bm).resolved()
    return spec.bm, spec.impl


def _pack_blocks(op: GoogleOperator, part: Partition, dtype,
                 cfg: SPMDConfig, v_stack: np.ndarray):
    """Pad per-block state of P^T to common budgets so the sharded arrays
    have static shapes.

    segment_sum: per-shard edge slices padded to a common edge count.
    bsr_pallas : per-shard hub-split BSR — a global hub mask (row-nnz
                 quantile over all pages) splits each shard's edges; the
                 block-CSR parts share one K budget, the COO hub parts one
                 edge budget.
    Always packed: per-shard teleport fragments ((bsize, nv) lanes) and a
    valid-row mask (the scalar dangling/teleport corrections must not leak
    into padding rows).
    """
    from .partition import slice_transition

    p = part.p
    nv = v_stack.shape[1]
    bsize = int(part.sizes().max())
    if cfg.backend == "bsr_pallas":
        bm, _ = _resolve_bsr(cfg)
        bsize = -(-bsize // bm) * bm       # block-align every fragment
    n = part.n
    n_pad = p * bsize

    blocks = [slice_transition(op.pt, part, i) for i in range(p)]
    vblk = np.zeros((p, bsize, nv), dtype=dtype)
    valid = np.zeros((p, bsize), dtype=dtype)
    for i in range(p):
        s, t = part.block(i)
        vblk[i, : t - s] = v_stack[s:t]
        valid[i, : t - s] = 1.0
    # the dangling mask lives in *packed-view* coordinates: with
    # block-aligned fragments the view rows shift relative to page ids
    dang = np.zeros((n_pad,), dtype=bool)
    for i in range(p):
        s, t = part.block(i)
        dang[i * bsize: i * bsize + (t - s)] = op.pt.dangling[s:t]

    packed = dict(vblk=vblk, valid=valid, dang=dang, bsize=bsize,
                  n_pad=n_pad)

    if cfg.backend == "bsr_pallas":
        from ..kernels.bsr_spmv import build_bsr
        row_nnz = np.diff(op.pt.indptr)
        if cfg.hub_quantile < 1.0:
            cut = np.quantile(row_nnz, cfg.hub_quantile)
            hub_row = row_nnz > cut
        else:
            hub_row = np.zeros(n, dtype=bool)

        # per-shard split; columns live in packed-view coordinates
        col_map = np.zeros(n, dtype=np.int64)
        for j in range(p):
            s, t = part.block(j)
            col_map[s:t] = np.arange(j * bsize, j * bsize + (t - s))

        shard = []
        for i, b in enumerate(blocks):
            s, t = part.block(i)
            rows_g = b["row_ids"].astype(np.int64) + s
            is_hub = hub_row[rows_g]
            shard.append(dict(
                rows=b["row_ids"].astype(np.int64)[~is_hub],
                cols=col_map[b["src"].astype(np.int64)[~is_hub]],
                vals=np.asarray(b["weight"], dtype=np.float32)[~is_hub],
                h_rows=b["row_ids"].astype(np.int64)[is_hub],
                h_cols=col_map[b["src"].astype(np.int64)[is_hub]],
                h_vals=np.asarray(b["weight"], dtype=np.float32)[is_hub],
            ))

        # shared K budget across shards (static shapes under shard_map)
        nbc_g = n_pad // bm
        K = 1
        for sh in shard:
            key = np.unique((sh["rows"] // bm) * nbc_g + sh["cols"] // bm)
            if len(key):
                per = np.bincount((key // nbc_g).astype(np.int64),
                                  minlength=bsize // bm)
                K = max(K, int(per.max()))
        hmax = max(1, max(len(sh["h_rows"]) for sh in shard))

        nbr_l = bsize // bm
        hrow = np.zeros((p, hmax), dtype=np.int32)
        hcol = np.zeros((p, hmax), dtype=np.int32)
        hval = np.zeros((p, hmax), dtype=np.float32)
        fills = []
        for i, sh in enumerate(shard):
            b = build_bsr(sh["rows"], sh["cols"], sh["vals"],
                          n_rows=bsize, n_cols=n_pad, bm=bm, bn=bm,
                          k_budget=K, unique_pairs=True)
            if i == 0:
                # build_bsr pads the shared K to a multiple of the
                # kernel's group size, alike for every shard
                blk = np.zeros((p, nbr_l, b.K, bm, bm), dtype=np.float32)
                bcols = np.zeros((p, nbr_l, b.K), dtype=np.int32)
                rgroups = np.zeros((p, nbr_l), dtype=np.int32)
            blk[i] = b.blocks
            bcols[i] = b.blk_cols
            rgroups[i] = b.row_groups
            e = len(sh["h_rows"])
            hrow[i, :e] = sh["h_rows"]
            hcol[i, :e] = sh["h_cols"]
            hval[i, :e] = sh["h_vals"]
            fills.append(b.fill_ratio)
        packed.update(blk=blk, bcols=bcols, rgroups=rgroups, hrow=hrow,
                      hcol=hcol, hval=hval, K=blk.shape[2], bm=bm,
                      fill_ratio=float(np.mean(fills)))
    else:
        emax = max(b["src"].shape[0] for b in blocks)
        src = np.zeros((p, emax), dtype=np.int32)
        wgt = np.zeros((p, emax), dtype=dtype)
        rid = np.zeros((p, emax), dtype=np.int32)
        for i, b in enumerate(blocks):
            e = b["src"].shape[0]
            # sources also live in packed-view coordinates
            src[i, :e] = col_map_seg(part, bsize, b["src"])
            wgt[i, :e] = b["weight"]
            rid[i, :e] = b["row_ids"]
        packed.update(src=src, wgt=wgt, rid=rid, emax=emax)
    return packed


def col_map_seg(part: Partition, bsize: int, cols: np.ndarray) -> np.ndarray:
    """Map global column ids into packed-view coordinates (identity when
    fragments are unpadded, shifted when block-aligned)."""
    out = np.empty(len(cols), dtype=np.int32)
    owners = np.searchsorted(np.asarray(part.ends), cols, side="right")
    starts = np.asarray(part.starts)
    out[:] = owners * bsize + (cols - starts[owners])
    return out


def solve_spmd(op: GoogleOperator, cfg: SPMDConfig,
               mesh: Optional[Mesh] = None,
               v: Optional[np.ndarray] = None,
               observe: bool = False) -> SPMDResult:
    if cfg.compact_lanes and not cfg.freeze_lanes:
        raise ValueError("compact_lanes=True requires freeze_lanes=True "
                         "(compaction shrinks the stack to unfrozen lanes)")
    ce = cfg.compact_exit
    if not (ce == "auto" or (isinstance(ce, (int, float))
                             and not isinstance(ce, bool)
                             and 0.0 < float(ce) <= 1.0)):
        raise ValueError(f"compact_exit must be 'auto' or a fraction in "
                         f"(0, 1], got {ce!r}")
    p = cfg.p
    n = op.n
    dtype = jnp.dtype(cfg.dtype)
    if mesh is None:
        devs = jax.devices()
        assert len(devs) >= p, f"need {p} devices, have {len(devs)}"
        # Auto pinned for the reason given in runtime/device.py::_mesh
        mesh = jax.make_mesh((p,), ("ue",),
                             axis_types=(jax.sharding.AxisType.Auto,),
                             devices=devs[:p])

    v_stack = np.asarray(op.teleport() if v is None else v,
                         dtype=np.float64)
    if v_stack.ndim == 1:
        v_stack = v_stack[:, None]
    if v_stack.shape[0] != n:
        raise ValueError(f"teleport v has {v_stack.shape[0]} rows, "
                         f"operator has {n}")
    nv = v_stack.shape[1]

    # uniform blocks (paper's ceil(n/p) scheme) padded to p * bsize
    part = block_rows(n, p)
    packed = _pack_blocks(op, part, np.dtype(cfg.dtype), cfg, v_stack)
    bsize = packed["bsize"]
    n_pad = packed["n_pad"]

    alpha = float(op.alpha)
    linear = cfg.kind == "linear"
    tol = cfg.tol
    q = cfg.delivery_prob
    seed = cfg.seed
    use_bsr = cfg.backend == "bsr_pallas"
    if use_bsr:
        bm, bsr_impl = _resolve_bsr(cfg)

    init_comm, comm = spmd_exchange(
        cfg.schedule, p=p, bsize=bsize, n_pad=n_pad,
        sync_every=cfg.sync_every, sparsify_k=cfg.sparsify_k,
        sparsify_row_thresh=cfg.sparsify_thresh,
        sparsify_refresh_every=cfg.sparsify_refresh_every,
        sparsify_adaptive=cfg.sparsify_adaptive,
        sparsify_cover_frac=cfg.sparsify_cover_frac,
        sparsify_ewma=cfg.sparsify_ewma,
        # endgame guard: a delta mass at the tolerance scale ships full
        # payloads so the persistence counters can settle
        sparsify_endgame_mass=cfg.tol * bsize * nv)

    # device inputs, sharded over 'ue' (lane-independent ones placed once)
    sh = lambda *spec: jax.NamedSharding(mesh, P(*spec))
    valid = jax.device_put(packed["valid"], sh("ue", None))
    dang = jax.device_put(
        np.broadcast_to(packed["dang"], (p, n_pad)).copy(), sh("ue", None))
    x0_blocks = (np.full((p, bsize, nv), 1.0 / n, dtype=cfg.dtype)
                 * packed["valid"].astype(cfg.dtype)[:, :, None])

    if use_bsr:
        op_args = tuple(jax.device_put(packed[k], sh("ue", *([None] * nd)))
                        for k, nd in (("blk", 4), ("bcols", 2),
                                      ("rgroups", 1), ("hrow", 1),
                                      ("hcol", 1), ("hval", 1)))
    else:
        op_args = tuple(jax.device_put(packed[k], sh("ue", None))
                        for k in ("src", "wgt", "rid"))

    def run_chunk(vblk_np, x0_np, max_steps, compact_exit, exit_k=0):
        """One shard_map while_loop over the lanes of `vblk_np`
        ((p, bsize, nv_c) teleport blocks) from iterate `x0_np`.  With
        `compact_exit` the loop also exits once `exit_k` lanes are done
        (the pow2-compaction hook, threshold picked by the host per
        chunk); otherwise behavior is the pre-compaction loop
        verbatim."""
        nv_c = vblk_np.shape[2]
        vblk = jax.device_put(np.ascontiguousarray(vblk_np),
                              sh("ue", None, None))
        x0 = jax.device_put(np.ascontiguousarray(x0_np),
                            sh("ue", None, None))

        def body_fn(vblk, valid, dang, x0, *op_args):
            """Runs on one shard. vblk/x0: (1, bsize, nv), valid:
            (1, bsize), dang: (1, n_pad); op_args are the shard's
            operator slice (edge or block form).

            The body is assembled from the shared ShardStep builders
            (runtime/step.py) — the same traced step the device
            transport runs, so the bulk-synchronous solver and the async
            streaming drain share one local update / exchange /
            termination body."""
            vb_, val_, dg_, myx = vblk[0], valid[0], dang[0], x0[0]
            i = jax.lax.axis_index("ue")

            op_slice = tuple(a[0] for a in op_args)
            if use_bsr:
                pt_apply = _runtime_step.shard_pt_apply(
                    op_slice, use_bsr=True, bsize=bsize, nv=nv_c,
                    n_pad=n_pad, bm=bm, impl=bsr_impl)
            else:
                pt_apply = _runtime_step.shard_pt_apply(
                    op_slice, use_bsr=False, bsize=bsize, nv=nv_c)
            local_update = _runtime_step.shard_local_update(
                pt_apply, alpha=alpha, linear=linear, n=n,
                vb=vb_, val=val_, dang=dg_)
            superstep, cond = _runtime_step.shard_superstep_fns(
                local_update, comm, i=i, p=p, tol=tol,
                pc_max_compute=cfg.pc_max_compute,
                pc_max_monitor=cfg.pc_max_monitor,
                seed=seed, q=q, freeze_lanes=cfg.freeze_lanes,
                max_steps=max_steps, compact_exit=compact_exit,
                exit_k=exit_k, conv="linf", axis="ue")

            carry = _runtime_step.init_carry(myx, init_comm, nv=nv_c,
                                             n_pad=n_pad, axis="ue")
            (view, frag, _, step, pc, mon_pc, lane_done, lane_step,
             rows_sent, fulls) = jax.lax.while_loop(
                cond, lambda c: superstep(c), carry)
            resid = jnp.max(jnp.abs(local_update(view) - frag), axis=0)
            return (frag[None], step[None], resid[None], lane_step[None],
                    rows_sent[None], fulls[None])

        mapped = jax.shard_map(
            body_fn, mesh=mesh,
            in_specs=(P("ue", None, None), P("ue", None), P("ue", None),
                      P("ue", None, None))
            + tuple(P("ue", *([None] * (a.ndim - 1))) for a in op_args),
            out_specs=(P("ue", None, None), P("ue"), P("ue", None),
                       P("ue", None), P("ue"), P("ue")),
            check_vma=False,
        )
        frags, steps, resids, lane_steps, rows_sent, fulls = \
            jax.jit(mapped)(vblk, valid, dang, x0, *op_args)
        return (np.asarray(frags, dtype=np.float64), int(steps.max()),
                np.asarray(resids), np.asarray(lane_steps,
                                               dtype=np.int64).max(axis=0),
                int(np.asarray(rows_sent).sum()),
                int(np.asarray(fulls).sum()))

    def chunk_bytes(nv_c, steps_c, rows_c, fulls_c):
        """The per-chunk rendering of the byte model (the static schedules
        scale with the chunk's lane count; sparsified uses the honest
        in-loop counters).  Delegates to the one shared model in
        runtime/step.py — the device transport and its bench gate report
        through the identical accounting."""
        return _runtime_step.comm_bytes_model(
            cfg.schedule, p=p, bsize=bsize,
            itemsize=np.dtype(cfg.dtype).itemsize, nv=nv_c,
            steps=steps_c, rows=rows_c, fulls=fulls_c,
            sync_every=cfg.sync_every)

    compact = bool(cfg.compact_lanes and cfg.freeze_lanes and nv > 1)
    vblk_full = packed["vblk"]
    chunk_log: Optional[List[dict]] = [] if observe else None
    if not compact:
        frag_mat, supersteps, resid_mat, lane_out, rows_total, fulls_total \
            = run_chunk(vblk_full, x0_blocks, cfg.max_supersteps, False)
        comm_total = chunk_bytes(nv, supersteps, rows_total, fulls_total)
        chunks = 1
        if chunk_log is not None:
            chunk_log.append(dict(chunk=0, lanes=nv, steps=supersteps,
                                  rows=rows_total, fulls=fulls_total,
                                  bytes=comm_total))
    else:
        # ---- pow2 lane compaction between shard_map chunks -------------
        # Run until enough active lanes are frozen (compact_exit), then
        # shrink the (bsize, nv) stack to the survivors padded to the
        # next pow2 (padding duplicates a survivor so the Fig. 1 bits of
        # every carried lane are real) and re-enter with the current
        # fragments as x0.  Frozen lanes stop costing flops and exchange
        # bytes; their results are recorded at the chunk boundary.
        frag_mat = np.zeros((p, bsize, nv))
        resid_mat = np.zeros((p, nv), dtype=cfg.dtype)
        lane_out = np.full(nv, -1, dtype=np.int64)
        active = list(range(nv))            # real lane id per position
        real = [True] * nv                  # padding positions are False
        cur_v, cur_x0 = vblk_full, x0_blocks
        steps_done = 0
        comm_total = 0
        rows_total = fulls_total = 0
        chunks = 0
        prev_done_rel = None    # last chunk's lane-completion steps
        prev_st = 0
        while True:
            chunks += 1
            budget = cfg.max_supersteps - steps_done
            nv_c = cur_v.shape[2]
            if ce != "auto":
                exit_k = max(1, int(np.ceil(float(ce) * nv_c)))
            else:
                # the earliest done-count at which the pow2 stack width
                # can actually shrink (on pow2 widths this is the
                # historic half rule; ragged first chunks exit sooner)
                half = (1 << max(nv_c - 1, 0).bit_length()) // 2
                exit_k = max(1, nv_c - half)
                # spread adaptation: when the previous chunk's lane
                # completions clustered inside a quarter of the chunk,
                # the survivors are expected to land together too — run
                # this chunk to all-done instead of paying a compaction
                # boundary the stragglers would immediately catch up to
                if (prev_done_rel is not None and prev_done_rel.size >= 2
                        and prev_st > 0
                        and float(prev_done_rel.max() - prev_done_rel.min())
                        <= 0.25 * prev_st):
                    exit_k = nv_c + 1
            fr, st, rs, ls, rows_c, fulls_c = run_chunk(
                cur_v, cur_x0, budget, True, exit_k)
            prev_done_rel = ls[np.asarray(real) & (ls >= 0)]
            prev_st = st
            steps_done += st
            cb = chunk_bytes(len(active), st, rows_c, fulls_c)
            # the in-loop counters restarted at zero with this chunk's
            # re-keyed schedule state, so the totals accumulate here —
            # comm_bytes_total / rows_sent are cumulative across every
            # chunk boundary (the chunk_log makes that checkable)
            comm_total += cb
            rows_total += rows_c
            fulls_total += fulls_c
            if chunk_log is not None:
                chunk_log.append(dict(chunk=chunks - 1, lanes=len(active),
                                      steps=st, rows=rows_c,
                                      fulls=fulls_c, bytes=cb))
            done_pos = ls >= 0
            for pos, lane in enumerate(active):
                if not real[pos]:
                    continue
                finished = bool(done_pos[pos])
                if finished or steps_done >= cfg.max_supersteps \
                        or np.all(done_pos):
                    frag_mat[:, :, lane] = fr[:, :, pos]
                    resid_mat[:, lane] = rs[:, pos]
                    if finished:
                        lane_out[lane] = steps_done - st + int(ls[pos])
            survivors = [active[pos] for pos in range(len(active))
                         if real[pos] and not done_pos[pos]]
            if not survivors or steps_done >= cfg.max_supersteps:
                break
            nv_next = 1 << (len(survivors) - 1).bit_length()
            pad = nv_next - len(survivors)
            pos_of = {lane: pos for pos, lane in enumerate(active)}
            keep_pos = [pos_of[ln] for ln in survivors] \
                + [pos_of[survivors[0]]] * pad
            cur_v = np.ascontiguousarray(cur_v[:, :, keep_pos])
            cur_x0 = np.ascontiguousarray(
                fr[:, :, keep_pos].astype(cfg.dtype))
            active = survivors + [survivors[0]] * pad
            real = [True] * len(survivors) + [False] * pad
        supersteps = steps_done

    # un-pack: drop each fragment's block-alignment padding
    x = np.empty((n, nv), dtype=np.float64)
    for i in range(p):
        s, t = part.block(i)
        x[s:t] = frag_mat[i, : t - s]
    s_ = x.sum(axis=0)
    x = np.where(s_ > 0, x / np.where(s_ > 0, s_, 1.0), x)

    comm_step = comm_total // max(supersteps, 1)
    resid_out = np.asarray(resid_mat)                   # (p, nv)
    if nv == 1:
        x = x[:, 0]
        resid_out = resid_out[:, 0]
    return SPMDResult(x=x, supersteps=supersteps,
                      local_resid=resid_out,
                      comm_bytes_per_step=int(comm_step),
                      comm_bytes_total=int(comm_total),
                      rows_sent=int(rows_total),
                      lane_supersteps=lane_out if nv > 1 else None,
                      lane_chunks=chunks, chunk_log=chunk_log,
                      devices=len(valid.sharding.device_set))
