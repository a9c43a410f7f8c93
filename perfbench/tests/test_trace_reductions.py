"""The reductions from a trace to numbers, against values worked out by hand:
first on a trace small enough to draw on paper, then on the recorded one."""

import os

import pytest

import tiny  # noqa: F401
from perfbench import xtrace

HERE = os.path.dirname(os.path.abspath(__file__))


def _paper_trace():
    # times in ns. Device 0: two steps; in each a prepare program [0,400) then
    # a step program [500,1500), shifted by 2000 for the second step.
    #   ops of the step: conv [500,900), fusion [900,1200),
    #   all-reduce [1150,1400) (50 under the fusion, 200 exposed), copy [1400,1500)
    #   ops of prepare: gather fusion [0,400)
    def step(t):
        modules = [["jit_prepare(1)", t + 0, 400], ["jit_step(2)", t + 500, 1000]]
        ops = [
            ["%fusion.7", t + 0, 400],
            ["%convolution.3 = bf16[256,13,13,512] convolution(bf16[256,13,13,512] %a, f32[3,3,512,512] %k)", t + 500, 400],
            ["%fusion.9", t + 900, 300],
            ["%all-reduce.1", t + 1150, 250],
            ["%copy.4", t + 1400, 100],
        ]
        return modules, ops

    m0, o0 = step(0)
    m1, o1 = step(2000)
    m2, o2 = step(4000)
    dev0 = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": m0 + m1 + m2},
        {"name": "XLA Ops", "events": o0 + o1 + o2},
    ]}
    # device 1 runs only the first step's ops
    dev1 = {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Modules", "events": m0},
        {"name": "XLA Ops", "events": o0},
    ]}
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ["obs/data_wait", 1450, 500], ["obs/step", 1950, 60], ["python noise", 0, 10],
    ]}]}
    return xtrace.Trace([host, dev1, dev0])


def test_busy_union_and_idle():
    t = _paper_trace()
    # device 0: per step the ops cover [0,400) and [500,1500): 1400 ns; three steps
    assert xtrace.union_ns(xtrace.Trace.line(t.device_planes()[0], "XLA Ops")) == 4200
    assert xtrace.fullest_busy_s(t) == pytest.approx(4200e-9)
    assert xtrace.busy_s(t) == pytest.approx((4200 + 1400) / 2 * 1e-9)
    # idle share of a 6,000 ns window on the fullest device: 1 - 4200/6000
    assert 1 - xtrace.fullest_busy_s(t) / 6000e-9 == pytest.approx(0.3)


def test_overlapping_and_nested_intervals():
    events = [["a", 0, 10], ["b", 5, 10], ["c", 6, 2], ["d", 20, 5], ["e", 25, 5]]
    assert xtrace.union_ns(events) == 15 + 10


def test_module_time_and_ops_inside():
    t = _paper_trace()
    # three executions each; the capture's first is left out as clipped
    assert xtrace.module_time_s(t, "jit_step") == (pytest.approx(2000e-9), 2)
    assert xtrace.module_time_s(t, "jit_prepare") == (pytest.approx(800e-9), 2)
    inside = xtrace.ops_inside(t, "jit_step")
    assert [xtrace.short_name(e[0]) for e in inside] == [
        "%convolution.3", "%fusion.9", "%all-reduce.1", "%copy.4"] * 2
    buckets = xtrace.bucket_time_s(inside, batch=256)
    assert buckets["conv"] == pytest.approx(800e-9)
    assert buckets["elementwise_bn"] == pytest.approx(600e-9)
    assert buckets["collectives"] == pytest.approx(500e-9)
    assert buckets["copy"] == pytest.approx(200e-9)
    # the gather fusion of the input program is not inside the step
    assert all(e[0] != "%fusion.7" for e in inside)


def test_a_last_run_cut_short_is_left_out():
    # the capture ends 2 ns into the fifth run of the input program
    modules = [["jit_prepare(1)", t, 400] for t in (0, 1000, 2000, 3000)] + [["jit_prepare(1)", 4000, 2]]
    t = xtrace.Trace([{"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": modules}]}])
    assert xtrace.module_time_s(t, "jit_prepare") == (pytest.approx(1200e-9), 3)
    # a whole last run stays
    modules[-1][2] = 390
    assert xtrace.module_time_s(t, "jit_prepare") == (pytest.approx(1590e-9), 4)


def test_collective_overlap():
    # each all-reduce runs 250 ns, 50 of them under the fusion: 200 exposed
    assert xtrace.exposed_collective_s(_paper_trace()) == pytest.approx(600e-9)


def test_breakdown():
    t = _paper_trace()
    top = xtrace.top_ops(t, 2)
    assert xtrace.short_name(top[0][0]) in ("%fusion.7", "%convolution.3")
    assert top[0][1] == pytest.approx(1200e-9)
    gaps = xtrace.idle_gaps(t, 2)
    # the long gaps on device 0 are [1500,2000) and [3500,4000); the first
    # lies under the host's data_wait span, no span covers the second
    assert gaps == [["data_wait", pytest.approx(500e-9)], ["unattributed", pytest.approx(500e-9)]]


def test_buckets_go_by_name_and_convolutions_by_shape():
    assert xtrace.bucket_of("%all-reduce-start.2 = f32[8] all-reduce-start(f32[8] %x)") == "collectives"
    assert xtrace.bucket_of("%convolution_add_fusion.5 = bf16[256,26,26,512] fusion()", 256) == "conv"
    assert xtrace.bucket_of("%reduce.12 = f32[] reduce(f32[8] %x)") == "elementwise_bn"
    assert xtrace.bucket_of("%custom-call.3 = f32[] custom-call()") == "other"
    # a plain %fusion that reads an activation and a kernel runs a convolution
    fwd = ("%fusion.9 = bf16[256,13,13,512]{3,0,2,1} fusion(bf16[256,13,13,512]{3,0,2,1} %a, "
           "f32[3,3,512,512]{3,2,1,0} %k), kind=kOutput, calls=%fused_computation.9")
    wgrad = ("%convert_reduce_fusion.4 = (f32[512]{0}, f32[1,1,1024,512]{3,2,1,0}) fusion("
             "bf16[256,13,13,1024]{3,0,2,1} %a, bf16[256,13,13,512]{3,0,2,1} %g), kind=kCustom")
    adam = ("%fusion.1202 = (f32[3,3,512,512]{3,2,1,0}, f32[3,3,512,512]{3,2,1,0}) fusion("
            "f32[3,3,512,512]{3,2,1,0} %p, f32[3,3,512,512]{3,2,1,0} %g), kind=kLoop")
    bn = "%fusion.77 = bf16[256,13,13,512]{3,0,2,1} fusion(bf16[256,13,13,512]{3,0,2,1} %a, f32[512]{0} %s), kind=kLoop"
    assert xtrace.is_conv_op(fwd, 256) and xtrace.is_conv_op(wgrad, 256)
    assert not xtrace.is_conv_op(adam, 256) and not xtrace.is_conv_op(bn, 256)
    assert not xtrace.is_conv_op(fwd, 64)  # another cell's batch
    assert xtrace.bucket_of(adam, 256) == xtrace.bucket_of(bn, 256) == "elementwise_bn"


# -- the recorded trace: two steps of tgs_kfold_train on the chip (PR 23), ops
# -- of 250 us and more, times in ns from the first program's start


def _recorded():
    return xtrace.Trace.from_json(os.path.join(HERE, "recorded_trace.json"))


def test_recorded_modules():
    t = _recorded()
    # two executions of each program were recorded; the first counts as clipped
    assert xtrace.module_time_s(t, "jit_step") == (pytest.approx(0.146908480), 1)
    assert xtrace.module_time_s(t, "jit_prepare") == (pytest.approx(0.499957320), 1)


def test_recorded_busy_and_buckets():
    t = _recorded()
    ops = xtrace.Trace.line(t.device_planes()[0], "XLA Ops")
    # a second way to the union: sweep over sorted boundaries
    marks = sorted([(s, 1) for _, s, d in ops] + [(s + d, -1) for _, s, d in ops])
    depth, since, busy = 0, None, 0.0
    for at, step in marks:
        if depth == 0 and step == 1:
            since = at
        depth += step
        if depth == 0:
            busy += at - since
    assert xtrace.union_ns(ops) == pytest.approx(busy)
    window = 1146864685.0 + 146908480.0  # the second step's end
    assert 0.8 * window < busy <= window  # the kept ops alone cover most of it
    inside = xtrace.ops_inside(t, "jit_step")
    buckets = xtrace.bucket_time_s(inside, batch=256)
    # the step's convolutions: between the 59.7 ms their FLOPs take at peak
    # and the whole step program
    assert 0.03 < buckets["conv"] < 0.0778  # the whole trace read 77.8 ms a step
    assert buckets["conv"] + buckets["elementwise_bn"] <= 0.146908480
    assert all(1146864685.0 <= e[1] < 1146864685.0 + 146908480.0 for e in inside)


def test_recorded_gaps_carry_host_spans():
    gaps = xtrace.idle_gaps(_recorded(), 3)
    assert gaps and all(name in ("fetch_wait", "step", "data_wait", "unattributed") for name, _ in gaps)
