"""Shard runtime — the substrate-independent core of the paper's
asynchronous iteration (see docs/runtime.md).

The paper's cycle — local fragment updates over stale views (eq. 5),
flexible message targeting (§6), and the Fig. 1 termination protocol — is
independent of the execution substrate.  This package factors it out of the
three substrates that used to hand-roll it (`core.des`, `core.spmd`,
`streaming`):

  state    — ShardState: one shard's owned fragment + versioned stale views.
  local    — LocalSolver protocol + the backend-dispatched block update
             (eq. 6/7 restricted to a partition block) every substrate
             shares.
  exchange — ExchangePlan: who messages whom, when, and with what fragment
             subset.  Covers all_to_all / ring / adaptive / allgather_k and
             the §6 `sparsified` plan (residual-mass targeting + top-k row
             payloads), in both the host/event rendering (DES, streaming)
             and the bulk-synchronous jax rendering (SPMD shard_map).
  driver   — TerminationDriver: drives the pure Fig. 1 machines
             (core.termination) in the message-passing, all-reduced-value,
             and all-reduced-bit renderings.
  transport— the transport-agnostic shard-worker layer: the per-shard
             cycle (`shard_worker_loop`) written once against the
             `TransportContext`/`Channel` seam, with two host renderings —
             threads (PairMailbox accumulators, driver lock) and procpool
             (worker processes over a ShardArena, mailboxes and Fig. 1
             messages on lock-free shared rings).
  step     — ShardStep: the cycle one level deeper, as a per-shard step —
             `HostShardStep` (the worker-loop round, verbatim) plus the
             jax-traceable builders (`shard_pt_apply` /
             `shard_local_update` / `shard_superstep_fns`) that core.spmd
             and the device transport assemble into one traced body, and
             `comm_bytes_model`, the shared exchange byte accounting.
  device   — DeviceShardTransport: the third transport rendering — p
             shard programs over a `ue` device mesh running the traced
             ShardStep (Pallas BSR or segment-sum drain, collective
             exchange, all-reduced Fig. 1 bits), float64 end-to-end for
             1e-8 certificates.
  executor — AsyncShardExecutor: the thread rendering's public face — one
             thread per shard, per-pair boundary-residual mailboxes (no
             superstep barrier), ExchangePlan consulted per local update,
             termination through the driver's message rendering.
  faults   — FaultPlan / FaultyContext: deterministic seeded fault
             injection (worker kill/hang, exchange drop/dup/delay, slow
             shards) at the TransportContext seam, for both renderings.
  supervisor — ShardSupervisor: self-healing for the procpool rendering —
             supervised worker restart with capped backoff, checkpoint
             restore, ledger reconciliation, conservative Fig. 1 re-entry.
  observe  — ShardObserver: lock-cheap per-shard metrics registry,
             ring-buffered event tracing at the cycle seams (Chrome
             trace_event export), and push-inflation attribution — the
             same arrays work in-process and as ShardArena views, and
             everything is zero-cost when off (docs/observability.md).
  schedule — DrainSchedule: pluggable update ordering for the drain hot
             paths — priority (D-Iteration fluid retention),
             boundary-batched exchange coalescing, seeded randomized
             control — selected by `ScheduleSpec` and threaded through
             `update_ranks_sharded(schedule=)` / `WorkerConfig.schedule` /
             `RankServer(drain_schedule=)`; mass accounting and the L1
             certificate are schedule-independent by construction.
"""
from .state import (ArenaHandle, ShardArena, ShardState,
                    sweep_stale_segments)
from .local import LocalSolver, BlockLocalSolver
from .exchange import (ExchangePlan, AllToAllPlan, RingPlan, AdaptivePlan,
                       SparsifiedPlan, make_plan, spmd_exchange)
from .driver import TerminationDriver
from .faults import (FaultPlan, FaultState, FaultyContext,
                     InjectedWorkerKill)
from .observe import (EV_NAMES, OBS_COUNTERS, ShardObserver,
                      attribute_frontier, chrome_trace, kernel_counters,
                      render_prometheus, span, write_chrome_trace)
from .schedule import (DEFAULT_SCHEDULE, SCHEDULES, DrainOrder,
                       ExchangeGate, PriorityOrder, RandomizedOrder,
                       ScheduleSpec, make_schedule)
from .supervisor import BackoffPolicy, RestartEvent, ShardSupervisor
from .transport import (Channel, HostAllReduce, ProcPoolShardExecutor,
                        ReductionChannel, ShmRing, ThreadedShardTransport,
                        TransportContext, WorkerConfig, default_pool_size,
                        mesh_psum, shard_worker_loop)
from .step import (HostShardStep, comm_bytes_model, shard_local_update,
                   shard_pt_apply, shard_superstep_fns)
from .device import DeviceRunResult, DeviceShardTransport
from .executor import (AsyncRunResult, AsyncShardExecutor, PairMailbox,
                       UniformAccumulator)

__all__ = [
    "ShardState", "ShardArena", "ArenaHandle", "sweep_stale_segments",
    "LocalSolver", "BlockLocalSolver",
    "ExchangePlan", "AllToAllPlan", "RingPlan", "AdaptivePlan",
    "SparsifiedPlan", "make_plan", "spmd_exchange",
    "TerminationDriver",
    "FaultPlan", "FaultState", "FaultyContext", "InjectedWorkerKill",
    "BackoffPolicy", "RestartEvent", "ShardSupervisor",
    "ShardObserver", "EV_NAMES", "OBS_COUNTERS", "attribute_frontier",
    "chrome_trace", "write_chrome_trace", "render_prometheus", "span",
    "kernel_counters",
    "ScheduleSpec", "SCHEDULES", "DEFAULT_SCHEDULE", "make_schedule",
    "DrainOrder", "PriorityOrder", "RandomizedOrder", "ExchangeGate",
    "Channel", "TransportContext", "WorkerConfig", "shard_worker_loop",
    "ThreadedShardTransport", "ProcPoolShardExecutor", "ShmRing",
    "default_pool_size", "ReductionChannel", "HostAllReduce", "mesh_psum",
    "HostShardStep", "shard_pt_apply", "shard_local_update",
    "shard_superstep_fns", "comm_bytes_model",
    "DeviceShardTransport", "DeviceRunResult",
    "AsyncRunResult", "AsyncShardExecutor", "PairMailbox",
    "UniformAccumulator",
]
