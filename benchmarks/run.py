"""Benchmark orchestrator — one section per paper table/figure plus the
beyond-paper studies. Prints ``name,us_per_call,derived`` CSV at the end.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--out BENCH_PRN.json]

Every run (including --quick) starts with the matvec-backend bench, the
streaming-update bench, the sharded-runtime bench (sparsified vs
allgather), the async-executor bench (async vs superstep shard
drains, threads vs procpool vs the PR 9 device transport), the
observability bench (push-inflation attribution, chaos trace demo,
zero-cost-when-off gate), the drain-schedule bench (priority /
boundary-batched / randomized inflation arms, PR 8) and the query-tier
bench (batched PPR vs sequential + closed-loop load gen under a live
updater, PR 10) and writes the machine-readable
perf-trajectory file (``--out``, default BENCH_PR10.json) at the repo
root; ``--tier1-seconds`` embeds the measured suite runtime for the
check_tier1_runtime.py gate; --quick then skips the slow DES paper-table
and SPMD staleness studies.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).parent.parent
RESULTS = Path(__file__).parent / "results"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the slowest studies")
    ap.add_argument("--skip-spmd", action="store_true")
    ap.add_argument("--out", default="BENCH_PR10.json",
                    help="perf-trajectory output (BENCH_PR<N>.json for "
                         "PR N; relative paths land at the repo root)")
    ap.add_argument("--tier1-seconds", default=None,
                    help="measured tier-1 suite runtime (seconds, or a "
                         "file holding it); embedded as `tier1_seconds` "
                         "so benchmarks/check_tier1_runtime.py can gate "
                         "against the best of the last two BENCH files")
    args = ap.parse_args()
    out_path = Path(args.out)
    if not out_path.is_absolute():
        out_path = REPO_ROOT / out_path
    tier1_seconds = None
    if args.tier1_seconds is not None:
        raw = args.tier1_seconds
        tier1_seconds = float(Path(raw).read_text().strip()
                              if Path(raw).exists() else raw)

    csv_rows = [("name", "us_per_call", "derived")]
    t_all = time.time()

    print(f"== Matvec backends (segment_sum vs bsr_pallas) -> "
          f"{out_path.name} ==")
    from benchmarks import backend_bench
    t0 = time.time()
    brec = backend_bench.main(out_path=out_path)
    big = brec["apply"][-1]
    csv_rows.append((
        "backend_apply",
        f"{big['bsr_pallas_us_per_apply']:.0f}",
        f"n={big['n']}:segsum={big['segment_sum_us_per_apply']:.0f}us,"
        f"bsr={big['bsr_pallas_us_per_apply']:.0f}us,"
        f"tau100={brec['solver']['kendall_tau_top100']:.4f}"))
    csv_rows.append((
        "bsr_packing",
        f"{brec['packing']['acceptance_scale']['solve_grade_cold_ms']*1e3:.0f}",
        f"vs_seed_at_32k="
        f"{brec['packing']['largest_seed_packable']['speedup']:.1f}x"))

    print("== Streaming incremental updates (push vs fallback) ==")
    from benchmarks import streaming_bench
    srec = streaming_bench.main()
    single = srec["delta_sweep"]["sweep"][0]
    csv_rows.append((
        "streaming_delta",
        f"{single['us_per_batch']:.0f}",
        f"single_edge:{single['path']}:visited"
        f"{100 * single['visited_frac']:.1f}%:"
        f"{single['speedup_vs_cold']:.0f}x_vs_cold,"
        f"fresh={srec['replay']['fresh_pct']:.0f}%"))
    brec["streaming"] = srec

    print("== Sharded runtime (sparsified vs allgather, 50k) ==")
    from benchmarks import shard_bench
    shrec = shard_bench.main()
    sp = next(r for r in shrec["spmd"] if r["schedule"] == "sparsified")
    csv_rows.append((
        "spmd_sparsified",
        f"{sp['total_comm_bytes']}",
        f"vs_allgather={sp['vs_allgather']:.2f}x,"
        f"steps={sp['supersteps']},err={sp['err']:.1e}"))
    sh = next(r for r in shrec["sharded_stream"]
              if r["exchange"] == "sparsified")
    csv_rows.append((
        "sharded_stream",
        f"{sh['s'] * 1e6:.0f}",
        f"path={sh['path']},steps={sh['supersteps']},"
        f"cert={sh['cert']:.1e},bytes={sh['bytes_moved']}"))
    brec["sharded"] = shrec

    print("== Async shard executor (threads vs procpool, 50k, p=1..8) ==")
    from benchmarks import async_shard_bench
    arec = async_shard_bench.main()
    a4 = next(r for r in arec["drain_dominated"]
              if r["mode"] == "async" and r["p"] == 4)
    csv_rows.append((
        "async_shard",
        f"{a4['s'] * 1e6:.0f}",
        f"p4_vs_p1_async={arec['speedup_p4_vs_p1_async']:.2f}x,"
        f"raw={arec['raw_speedup_p4_vs_p1_async']:.2f}x,"
        f"hetero_vs_superstep="
        f"{arec['speedup_async_vs_superstep_hetero_p4']:.2f}x"))
    pp4 = next(r for r in arec["drain_dominated_burn"]
               if r["transport"] == "procpool" and r["p"] == 4)
    csv_rows.append((
        "procpool_shard",
        f"{pp4['s'] * 1e6:.0f}",
        f"burn_p4_vs_p1={arec['procpool_burn_speedup_p4_vs_p1']:.2f}x,"
        f"threads_burn={arec['threads_burn_speedup_p4_vs_p1']:.2f}x,"
        f"raw_p4_vs_p1={arec['procpool_raw_speedup_p4_vs_p1']:.2f}x,"
        f"cores={arec['cores']}"))
    dv4 = next(r for r in arec["device"] if r["p"] == 4)
    csv_rows.append((
        "device_shard",
        f"{dv4['s'] * 1e6:.0f}",
        f"p4_vs_p1={arec['device_speedup_p4_vs_p1']:.2f}x,"
        f"steps={dv4['supersteps']},cert={dv4['cert']:.1e},"
        f"path={dv4['path']},bytes={dv4['bytes_moved']}"))
    ck = next(r for r in arec["chaos"] if r["faults"] == "kill_drop_dup")
    csv_rows.append((
        "chaos_recovery",
        f"{ck['s'] * 1e6:.0f}",
        f"recoveries={ck['recoveries']},"
        f"recovery_s={ck['recovery_s']:.3f},"
        f"overhead_vs_no_faults={ck['overhead_vs_no_faults']:.2f}x,"
        f"cert={ck['cert']:.1e}"))
    brec["async_shard"] = arec

    print("== Runtime observability (attribution, trace, overhead) ==")
    from benchmarks import observe_bench
    orec = observe_bench.main()
    inf = orec["inflation"]["procpool"]
    csv_rows.append((
        "observe_attribution",
        f"{inf['inflation']}",
        f"pp_inflation={inf['inflation_ratio']:.2f}x,"
        f"boundary_share={inf['boundary_share_of_inflation']},"
        f"threads_boundary_share="
        f"{orec['inflation']['threads']['boundary_share_of_inflation']},"
        f"trace_events={orec['trace_demo']['events']}"))
    ov = orec["overhead"]
    csv_rows.append((
        "observe_overhead",
        f"{ov['off_s'] * 1e6:.0f}",
        f"off_vs_baseline={ov['off_vs_baseline']},"
        f"on_vs_off={ov['on_vs_off']:.3f}x,"
        f"within_{ov['limit']}x={ov['within_limit']}"))
    brec["observe"] = orec

    print("== Drain schedules (priority/boundary/randomized inflation) ==")
    from benchmarks import schedule_bench
    screc = schedule_bench.main()
    for transport in ("threads", "procpool"):
        b = screc["best"][transport]
        d0 = screc["summary"][transport]["default"]
        csv_rows.append((
            f"schedule_{transport}",
            f"{b['pushes_p4']}",
            f"best={b['schedule']}:{b['inflation_ratio']:.3f}x,"
            f"default={d0['inflation_ratio']:.3f}x,"
            f"local_excess={b['local_excess']},"
            f"boundary={b['boundary_p4']}"))
    csv_rows.append((
        "schedule_burn",
        f"{screc['burn']['projected_speedup_p4_vs_p1']:.3f}",
        f"projected_p4_vs_p1={screc['burn']['projected_speedup_p4_vs_p1']}"
        f"x_at_{screc['burn']['project_cores']}cores,"
        f"measured={screc['burn']['measured']},"
        f"cores={screc['burn']['cores']}"))
    brec["schedule"] = screc

    print("== Query tier (batched PPR + closed-loop load gen, 50k) ==")
    from benchmarks import query_bench
    qrec = query_bench.main()
    qb = qrec["batched"]
    csv_rows.append((
        "query_batched_ppr",
        f"{qb['sweep'][-1]['ms_per_query'] * 1e3:.0f}",
        f"speedup16={qb['speedup_at_16']:.2f}x,"
        f"seq={qb['sequential_ms_per_query']:.0f}ms,"
        f"path={qb['sweep'][-1]['path']},"
        f"certs_ok={all(r['certs_ok'] for r in qb['sweep'])}"))
    ql = qrec["load"]
    csv_rows.append((
        "query_load_gen",
        f"{ql['latency_ms']['top_k']['p99'] * 1e3:.0f}",
        f"qps={ql['qps_under_update']:.0f},"
        f"updates={ql['updater']['batches_applied']},"
        f"topk_p50={ql['latency_ms']['top_k']['p50']:.2f}ms,"
        f"ppr_p99={ql['latency_ms']['ppr']['p99']:.0f}ms,"
        f"cache_hits={ql['cache']['hits']},"
        f"rejects={ql['router']['rejects']}"))
    brec["query"] = qrec

    if tier1_seconds is not None:
        brec["tier1_seconds"] = tier1_seconds
    out_path.write_text(json.dumps(brec, indent=1))
    (RESULTS / "streaming_bench.json").write_text(
        json.dumps(srec, indent=1))

    if not args.quick:
        from benchmarks import paper_tables
        print("== Paper Table 1 (sync vs async, 2/4/6 UEs) ==")
        op = paper_tables._ops()
        t0 = time.time()
        rows1 = paper_tables.table1(op)
        csv_rows.append(("table1_paper_repro", f"{(time.time()-t0)*1e6:.0f}",
                         f"speedups={[r['speedup'] for r in rows1]}"))

        print("== Paper Table 2 (completed imports) ==")
        t0 = time.time()
        rec2 = paper_tables.table2(op)
        csv_rows.append(("table2_imports", f"{(time.time()-t0)*1e6:.0f}",
                         f"completed_pct={rec2['completed_pct']}"))

        print("== Rank quality vs relaxed thresholds (paper §5.2 question) ==")
        t0 = time.time()
        rq = paper_tables.rank_quality(op)
        csv_rows.append(("rank_quality", f"{(time.time()-t0)*1e6:.0f}",
                         f"tau100@1e-6={next(r['kendall_tau_top100'] for r in rq if r['local_tol']==1e-6)}"))

    if not args.skip_spmd and not args.quick:
        print("== SPMD bounded-staleness schedules (8 host devices) ==")
        from benchmarks import spmd_staleness
        t0 = time.time()
        rows = spmd_staleness.main()
        base = next(r for r in rows if r["schedule"] == "allgather")
        best = min(rows, key=lambda r: r["total_comm_bytes"])
        csv_rows.append(("spmd_staleness", f"{(time.time()-t0)*1e6:.0f}",
                         f"best={best['schedule']}:{best['total_comm_bytes']/base['total_comm_bytes']:.2f}x_comm"))

    print("== Kernel benches ==")
    from benchmarks import kernel_bench
    t0 = time.time()
    spmv_rec = kernel_bench.spmv_bench()
    csv_rows.append(("bsr_spmv_ref", f"{spmv_rec['bsr_ref_multivec_us']:.0f}",
                     f"AI={spmv_rec['bsr_arith_intensity']:.3f}flop/B"))
    att_rec = kernel_bench.flash_bench()
    csv_rows.append(("flash_attention_jnp", f"{att_rec['flash_us']:.0f}",
                     f"score_mem_ratio={att_rec['flash_score_bytes']/att_rec['naive_score_bytes']:.4f}"))

    print("== BSR layout study (orderings x block size x hub split) ==")
    from benchmarks import bsr_layout_study
    t0 = time.time()
    rows_b = bsr_layout_study.main()
    best = min(rows_b, key=lambda r: r["bsr_bytes_per_nnz"])
    csv_rows.append(("bsr_layout_study", f"{(time.time()-t0)*1e6:.0f}",
                     f"best={best['order']}/bm{best['bm']}:"
                     f"{best['bsr_bytes_per_nnz']:.0f}B_per_nnz"))

    print("== Roofline report (from cached dry-run) ==")
    try:
        from benchmarks import roofline_report
        t0 = time.time()
        roofline_report.main()
        tbl = json.loads((RESULTS / "roofline_16x16.json").read_text())
        csv_rows.append(("roofline_cells", f"{(time.time()-t0)*1e6:.0f}",
                         f"n={len(tbl)}"))
    except Exception as e:
        print(f"  (roofline report unavailable: {e})")

    print(f"\nTotal bench time: {time.time()-t_all:.0f}s\n")
    print("\n".join(",".join(map(str, r)) for r in csv_rows))


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
