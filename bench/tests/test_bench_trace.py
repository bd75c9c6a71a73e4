"""The trace reduction on a small synthetic trace: busy union, idle
share, kernel match by name, collectives and the breakdown."""

import re
import types

import pytest

from bench import trace
from bench.run import metric_reader

KERNEL = ("%radic_batched_partial_pallas.1 = f32[1,8,256] custom-call(...),"
          " custom_call_target=\"tpu_custom_call\"")
GRAD = ("%radic_batched_grad_partial_pallas.2 = f32[1,56,17] custom-call()"
        ", custom_call_target=\"tpu_custom_call\"")
PSUM = ("%psum.7 = f32[]{:T(128)} all-reduce(%slice_reduce_fusion), "
        "channel_id=1, replica_groups={{0,1,2,3}}")
AFTER_PSUM = "%add.5 = f32[] add(f32[] %all-reduce.3, f32[] %y)"
COPY = "%copy.4 = s32[33,13] copy(s32[33,13] %table)"


def _planes(dev_events):
    planes = [{"name": "/host:CPU", "lines": {
        "python": [("bench.window", 1000.0, 9000.0),
                   ("np.asarray(jax.Array)", 6000.0, 500.0)]}}]
    for i, evs in enumerate(dev_events):
        planes.append({"name": f"/device:TPU:{i}",
                       "lines": {"XLA Ops": evs,
                                 "XLA Modules": [("jit_x", 0.0, 1e5)]}})
    planes.append({"name": "/device:CUSTOM:Megascale Trace", "lines": {
        "XLA Ops": [(KERNEL, 0.0, 1e6)]}})
    return planes


DEV0 = [(KERNEL, 2000.0, 3000.0),      # 2000-5000
        (COPY, 4000.0, 2000.0),        # overlaps: union 2000-6000
        (GRAD, 7000.0, 1000.0),        # 7000-8000
        (PSUM, 9500.0, 1000.0)]        # clipped at 10000: 500 ns
DEV1 = [(KERNEL, 1500.0, 5000.0)]      # 1500-6500


def test_span_and_op_names():
    planes = _planes([DEV0])
    assert trace.find_span(planes, "bench.window") == (1000.0, 10000.0)
    with pytest.raises(KeyError):
        trace.find_span(planes, "no.such.span")
    assert trace.op_name(KERNEL) == "radic_batched_partial_pallas"
    assert trace.op_name(GRAD) == "radic_batched_grad_partial_pallas"
    assert trace.is_collective(PSUM) and not trace.is_collective(KERNEL)
    assert not trace.is_collective(AFTER_PSUM)


def test_union_of_overlapping_intervals():
    assert trace.union_seconds([(0, 10), (5, 20), (30, 40)]) == \
        pytest.approx(30e-9)
    assert trace.union_seconds([]) == 0.0


def test_reduce_window_busy_kernels_collectives():
    planes = _planes([DEV0, DEV1])
    devs = trace.reduce_window(planes, 1000.0, 10000.0)
    assert [d["device"] for d in devs] == ["/device:TPU:0", "/device:TPU:1"]
    d0, d1 = devs
    # union: 2000-6000, 7000-8000, 9500-10000 = 5500 ns
    assert d0["busy_s"] == pytest.approx(5500e-9)
    assert d0["op_s"]["radic_batched_partial_pallas"] == pytest.approx(3e-6)
    assert d0["collective_s"] == pytest.approx(500e-9)
    assert d1["busy_s"] == pytest.approx(5000e-9)


def _run(planes, devices_n=2):
    devs = trace.reduce_window(planes, 1000.0, 10000.0)[:devices_n]
    return types.SimpleNamespace(
        devices=devs, window_s=9000e-9, config={"shape": [3, 5]},
        traffic={"grad_frac": 0.0}, device_kind="TPU v5 lite",
        rec={"vals": [1.0, 2.0], "t0": 0.0, "t1": 1.0},
        counters0={}, counters1={})


def test_metric_readers_on_the_trace():
    run = _run(_planes([DEV0, DEV1]))
    idle = metric_reader("idle_pct.solve")(run)
    assert idle == pytest.approx(100 * (1 - (5500 + 5000) / 2 / 9000))
    # kernel 3000 ns and 5000 ns: (5000 - 4000) / 4000
    assert metric_reader("grain_imbalance_pct.solve")(run) == \
        pytest.approx(25.0)
    assert metric_reader("collective_pct.solve")(run) == \
        pytest.approx(100 * 250 / 9000)
    # 2 solves of C(5,3)=10 minors at 20 flops, 2·4·16 bytes: at this
    # toy size the bytes bound; over 8000 ns of kernel
    roof = metric_reader("radic_combo_roofline.solve")(run)
    assert roof == pytest.approx(100 * (128 / 819e9) / 8000e-9)
    run.config["shape"] = [12, 32]
    roof = metric_reader("radic_combo_roofline.solve")(run)
    assert roof == pytest.approx(
        100 * (2 * 225_792_840 * 1154 / 197e12) / 8000e-9)


def test_readers_return_nothing_without_something_to_read():
    run = _run(_planes([[(COPY, 2000.0, 100.0)]]), devices_n=1)
    assert metric_reader("radic_combo_roofline.solve")(run) is None
    assert metric_reader("grain_imbalance_pct.solve")(run) is None
    assert metric_reader("collective_pct.solve")(run) is None
    run.devices = None
    assert metric_reader("idle_pct.latency")(run) is None


def test_breakdown_names_gaps_by_host_span():
    planes = _planes([DEV0])
    devs = trace.reduce_window(planes, 1000.0, 10000.0)
    b = trace.breakdown(planes, devs, 1000.0, 10000.0)
    names = [n for n, _ in b["device_ops"]]
    assert names[0] == "radic_batched_partial_pallas"
    # gaps, longest first: 8000-9500, then 1000-2000 and 6000-7000
    # (the host in np.asarray)
    gaps = [(round(s * 1e9), n) for n, s in b["idle_gaps"]]
    assert [g for g, _ in gaps] == [1500, 1000, 1000]
    assert re.match(r"bench\.window", gaps[0][1])
    assert sorted(n for _, n in gaps[1:]) == ["bench.window",
                                              "np.asarray(jax.Array)"]
