"""The metrics that read the program's spans and the kernel's named
operations out of a traced run, on recorded run records."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
from workmodel import least_apply_s  # noqa: E402

PEAK = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}


def reader(name):
    return harness.load_module(BENCH, "metrics", name).read


def refresh_run(idle_gaps, units=2, busy_s=30.0):
    return dict(units=units, counters=[{"units": 1}] * units,
                trace=dict(busy_s=busy_s, window_s=32.0, devices=1,
                           breakdown=dict(device_ops=[["fusion.113", 26.1]],
                                          idle_gaps=idle_gaps)))


SPAN_METRICS = [("transport_idle_s_per_batch", "transport"),
                ("certify_idle_s_per_batch", "certify"),
                ("update_idle_s_per_batch", "update")]


@pytest.mark.parametrize("metric,layer", SPAN_METRICS)
def test_span_idle_sums_its_layers_gaps_per_batch(metric, layer):
    gaps = [["transport.dispatch", 0.4], ["transport.pack", 0.2],
            ["certify.exact_residual", 0.3], ["update.apply_delta", 0.1],
            ["serving.publish", 0.05], ["bench.refresh", 0.7],
            ["(no host span)", 0.01], ["transporter", 9.0]]
    want = {"transport": 0.6, "certify": 0.3, "update": 0.1}[layer]
    assert reader(metric)(refresh_run(gaps)) == pytest.approx(want / 2)


@pytest.mark.parametrize("metric,layer", SPAN_METRICS)
def test_span_idle_reads_zero_where_no_gap_is_under_its_spans(metric,
                                                              layer):
    # the parent of the spans: every gap under the benchmark's own span
    run = refresh_run([["bench.refresh", 1.781212625],
                       ["np.asarray_jax.Array_", 4.4e-06]])
    assert reader(metric)(run) == 0.0


@pytest.mark.parametrize("metric,layer", SPAN_METRICS)
def test_span_idle_reads_nothing_without_a_traced_device(metric, layer):
    assert reader(metric)(dict(units=2, counters=[], trace=None)) is None
    no_device = refresh_run([["transport.pack", 0.2]], busy_s=0.0)
    assert reader(metric)(no_device) is None


def batch_run(device_ops, iters=(41, 41), busy_s=5.47, devices=1):
    return dict(units=len(iters), counters=[{"iters": i} for i in iters],
                graph=dict(n=281_903, nnz=2_312_497),
                work=dict(nv=1, itemsize=4), peaks=PEAK,
                trace=dict(busy_s=busy_s, window_s=5.48, devices=devices,
                           breakdown=dict(device_ops=device_ops,
                                          idle_gaps=[])))


def test_kernel_roofline_reads_the_kernels_chunks_alone():
    ops = [["bsr_spmv.13", 2.5], ["bsr_spmv.12", 1.5], ["bsr_spmv", 1.0],
           ["fusion.24", 0.4], ["bsr_spmv_prep", 7.0], ["copy.35", 0.07]]
    least = least_apply_s(281_903, 2_312_497, 1, 4, PEAK)
    want = 100.0 * least / (5.0 / 82)
    read = reader("kernel_roofline")
    assert read(batch_run(ops)) == pytest.approx(want)
    # per chip: two chips' ops are summed in the breakdown
    assert read(batch_run(ops, devices=2)) == pytest.approx(2 * want)
    # the kernel's share exceeds the whole apply's, never the reverse
    apply_share = reader("apply_roofline")(batch_run(ops))
    assert apply_share < read(batch_run(ops))


def test_kernel_roofline_reads_nothing_where_no_kernel_ran():
    read = reader("kernel_roofline")
    # graph500-22.batch: segment-sum gather and scatter, no Pallas kernel
    assert read(batch_run([["fusion.16", 21.4], ["fusion.17", 13.5]])) \
        is None
    run = batch_run([["bsr_spmv.12", 3.0]])
    run["trace"] = None
    assert read(run) is None
    assert read(batch_run([["bsr_spmv.12", 3.0]], busy_s=0.0)) is None
