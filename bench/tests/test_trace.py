"""The trace reduction on a small synthetic trace."""

import pytest

from benchlib import trace
from benchlib.trace import Event

DEV = "/device:TPU:0"
KERNEL = ('%_fused.9 = s8[512,{},96]{{2,1,0}} custom-call(s32[512] %a), '
          'custom_call_target="tpu_custom_call"')


def tiny():
    ops = [Event("fusion.1", 0, 100), Event("fusion.2", 50, 100),
           Event(KERNEL.format(8), 300, 50),
           Event(KERNEL.format(32), 400, 100),
           Event("%while.2 = (s32[]) while((s32[]) %t), body=%b", 0, 500),
           Event("fusion.1", 1000, 10)]
    host = [Event("window", 0, 2000), Event("PjitFunction(seg)", 160, 100),
            Event("TransferFromDevice", 520, 470)]
    return trace.from_events({DEV: ops}, host)


def test_busy_is_the_union_of_op_intervals():
    assert trace.merged(tiny().device_ops[DEV]) == [(0, 500), (1000, 1010)]
    assert trace.busy_s(tiny()) == pytest.approx(510e-9)


def test_kernel_time_sums_matching_ops():
    secs, n = trace.kernel_s(tiny(), lambda e: e.name.startswith("%_fused"))
    assert (secs, n) == (pytest.approx(150e-9), 2)


def test_top_ops_by_total_time_without_control_flow():
    top = trace.top_ops(tiny(), 2)
    assert [name for name, _ in top] == ["fusion.1", "fusion.2"]
    assert top[0][1] == pytest.approx(110e-9)
    assert all("while" not in name for name, _ in trace.top_ops(tiny()))


def test_idle_gaps_named_by_the_shortest_covering_host_span():
    ops = [e for e in tiny().device_ops[DEV] if not e.container]
    tr = trace.from_events({DEV: ops}, tiny().host)
    gaps = trace.idle_gaps(tr)
    assert gaps[0] == ["TransferFromDevice", pytest.approx(500e-9)]
    # (150, 300): the dispatch span covers 100 of 150 ns
    assert gaps[1] == ["PjitFunction(seg)", pytest.approx(150e-9)]
    assert gaps[2] == ["window", pytest.approx(50e-9)]


def test_unattributed_gap():
    tr = trace.from_events({DEV: [Event("a", 0, 10), Event("b", 110, 10)]},
                           [])
    assert trace.idle_gaps(tr) == [["unattributed", pytest.approx(100e-9)]]


def test_kernel_matchers_split_decode_from_chunk_calls():
    from metrics.decode_attn_roofline import is_decode_kernel
    from metrics.prefill_attn_roofline import is_chunk_kernel
    ops = tiny().device_ops[DEV]
    assert [e.start_ns for e in ops if is_decode_kernel(e)] == [300]
    assert [e.start_ns for e in ops if is_chunk_kernel(e)] == [400]
