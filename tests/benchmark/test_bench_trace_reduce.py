"""The reduction from trace events to busy time, gaps and kernel time,
on hand-made events and on the recorded fixture."""
import glob
import os

import pytest

from bench_util import ROOT

from benchmark import trace_reduce as tr

FIXTURES = os.path.join(ROOT, "tests", "benchmark", "fixtures")


def test_union_counts_overlaps_once():
    assert tr.union_seconds([(0, 2), (1, 3), (5, 6)]) == pytest.approx(4.0)
    assert tr.union_seconds([]) == 0.0
    assert tr.union_seconds([(0, 10), (2, 3)]) == pytest.approx(10.0)


def test_gaps_are_the_uncovered_stretches():
    assert tr.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert tr.gaps([(0, 6)], 0, 6) == []
    assert tr.gaps([], 0, 2) == [(0, 2)]


def test_self_time_charges_an_enclosing_op_for_the_rest():
    got = tr.self_times([("while", 0.0, 10.0), ("fusion.1", 1.0, 4.0),
                         ("fusion.2", 5.0, 6.0), ("copy", 11.0, 12.0)])
    assert got == {"while": pytest.approx(6.0), "fusion.1": pytest.approx(3.0),
                   "fusion.2": pytest.approx(1.0), "copy": pytest.approx(1.0)}


def test_reduce_on_hand_made_events():
    kernel = ('%attn.7 = bf16[8,128]{1,0:T(8,128)(2,1)} custom-call(bf16[8,128]'
              '{1,0} %p.1), custom_call_target="tpu_custom_call"')
    glue = ('%custom-call.3 = bf16[8,8]{1,0} custom-call(bf16[4,8]{1,0} '
            '%s.1, bf16[4,8]{1,0} %s.2), custom_call_target="ConcatBitcast"')
    device = [[("fusion.1", 1.0, 2.0), (kernel, 2.0, 2.5), (glue, 2.5, 2.5),
               ("fusion.1", 4.0, 5.0), (kernel, 5.0, 5.5),
               ("fusion.9", 9.5, 12.0)]]
    host = [("bench_window", 0.0, 10.0), ("step_call", 0.0, 0.4),
            ("feed", 2.6, 3.9), ("step_call", 5.6, 9.4)]
    got = tr.reduce(device, host, "host_other")
    assert got["window_s"] == pytest.approx(10.0)
    assert got["busy_s"] == pytest.approx(3.5)      # fusion.9 clipped at 10
    assert got["custom_calls"] == 2
    assert got["custom_call_s"] == pytest.approx(1.0)
    assert got["device_ops"][0] == ["fusion.1 x2", pytest.approx(2.0)]
    assert got["device_ops"][1] == ["attn custom-call bf16[8,128] x2",
                                    pytest.approx(1.0)]
    assert got["idle_gaps"][0] == ["step_call", pytest.approx(4.0)]
    assert got["idle_gaps"][1] == ["feed", pytest.approx(1.5)]
    assert ["step_call", pytest.approx(1.0)] in got["idle_gaps"]


def test_short_names_drop_numbers_layouts_and_operands():
    assert tr.short_name(
        "%copy.177 = bf16[1025,24,16,16,128]{4,3,2,1,0:T(8,128)(2,1)} "
        "copy(bf16[1025,24,16,16,128]{4,2,3,1,0:T(8,128)(2,1)} %fusion.34)"
    ) == "copy bf16[1025,24,16,16,128]"
    assert tr.short_name(
        "%fusion.918 = (bf16[50257,2048]{1,0:T(8,128)(2,1)}, f32[50257,2048]"
        "{1,0:T(8,128)}) fusion(bf16[8]{0} %p.1), kind=kLoop"
    ) == "fusion (bf16[50257,2048], ...)"
    assert tr.short_name("x" * 200) == "x" * 80


def test_reduce_without_the_window_span_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce([[("fusion", 0.0, 1.0)]], [], "host_other")


def test_recorded_trace_from_the_chip():
    """``lm_train --rehearse --trace 1`` on a TPU v5e (two layers, real
    flash kernels), cut to the first 16.5 ms of its window: three steps,
    each with a forward and two backward kernels a layer."""
    paths = glob.glob(os.path.join(FIXTURES, "*.xplane.pb"))
    assert paths, "the recorded trace is missing from tests/benchmark/fixtures"
    devices, host = tr.load(paths[0], 1, ("feed", "step_call", "wait_prev"))
    assert len(devices) == 1 and len(devices[0]) == 1749
    assert {n for n, _s, _e in host} == {"bench_window", "feed", "step_call",
                                         "wait_prev"}
    got = tr.reduce(devices, host, "host_other")
    assert got["window_s"] == pytest.approx(0.0165)
    assert got["busy_s"] == pytest.approx(422.803e-6, rel=1e-6)
    assert got["custom_calls"] == 3 * 3 * 2
    assert got["custom_call_s"] == pytest.approx(111.503e-6, rel=1e-6)
    name, secs = got["device_ops"][0]
    assert name == "transpose_jvp___ custom-call (bf16[8,128,128], ...) x6"
    assert secs == pytest.approx(46.96e-6, rel=1e-3)
    assert len(got["device_ops"]) == 10 and len(got["idle_gaps"]) == 5
    assert got["idle_gaps"][0] == ["wait_prev", pytest.approx(0.013151752)]
