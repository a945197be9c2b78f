"""The reduction from a trace to numbers, on a small recorded trace kept
beside this file (``data/small_trace.json``, in the form
``trace_reduce.trace_to_json`` writes; two devices, two steps each, times
in seconds).  CPU only; no profiler and no device is touched.

Device 0, per step of 100 ms: a ``while`` that encloses a 30 ms
convolution fusion, an asynchronous all-reduce (start 1 ms, done 2 ms) in
flight over a 29 ms fusion, and an 18 ms Mosaic call; then a buffer
allocation of no duration, a 10 ms synchronous all-reduce, a 2 ms gap, an
8 ms copy.  5 ms between the steps.  No ``Async XLA Ops`` line, so starts
are paired with dones.  Device 1: an asynchronous all-gather, in flight
for 50 ms on the ``Async XLA Ops`` line, under 100 ms of back-to-back
fusions; 3 ms between the steps."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.layer_metrics import (conv_ms, device_idle_pct,  # noqa: E402
                                     exchange_exposed_ms, host_gap_ms,
                                     per_step, step_device_ms)

DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "small_trace.json")) as f:
        return tr.trace_from_json(f.read())


class Ctx:
    convolutions = {"fusion.1"}         # what the step's HLO text would say

    def __init__(self, trace):
        self.trace = trace


@pytest.mark.parametrize("intervals, union", [
    ([(0, 1), (2, 3)], 2), ([(0, 2), (1, 3)], 3), ([(0, 5), (1, 2)], 5),
    ([(1, 1), (3, 2)], 0), ([], 0)])
def test_total_is_the_union_not_the_sum(intervals, union):
    assert tr.total(intervals) == union


@pytest.mark.parametrize("a, b, left", [
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 10)], [(0, 10)], []),
    ([(0, 4), (6, 10)], [(3, 7)], [(0, 3), (7, 10)]),
    ([(0, 4)], [], [(0, 4)])])
def test_subtract(a, b, left):
    assert tr.subtract(a, b) == left


def test_control_flow_encloses_its_body_and_is_not_counted(trace):
    leaves = tr.leaf_ops(trace.devices[DEV0].ops)
    assert len(leaves) == 14 and not any(
        o.name.startswith("while") for o in leaves)
    assert [o.name for o in leaves[:3]] == [
        "fusion.1", "all-reduce-start.1", "fusion.2"]


def test_busy_is_the_union_of_leaf_intervals(trace):
    dev = trace.devices[DEV0]
    # 100 ms per step less the 2 ms gap; the `while` adds nothing.
    assert tr.busy_seconds(dev) == pytest.approx(2 * 0.098)
    assert sum(o.seconds for o in dev.ops) == pytest.approx(2 * 0.178)
    assert sum(o.seconds for o in tr.leaf_ops(dev.ops)) == pytest.approx(
        2 * 0.098)
    assert tr.busy_seconds(trace.devices[DEV1]) == pytest.approx(0.2)


def test_an_overlapped_collective_counts_zero_exposed_time(trace):
    dev = trace.devices[DEV1]
    assert tr.collectives_in_flight(dev) == [
        pytest.approx((0.040, 0.090)), pytest.approx((0.143, 0.193))]
    assert tr.exposed_collective_seconds(dev) == 0
    # The same without the Async line: the instants pair up.
    paired = tr.DeviceTrace(dev.ops, dev.modules)
    assert tr.collectives_in_flight(paired) == tr.collectives_in_flight(dev)
    assert tr.exposed_collective_seconds(paired) == 0


def test_exposed_time_is_in_flight_time_no_compute_covers(trace):
    # Per step: the start (1 ms) and the done's wait (2 ms) of the
    # asynchronous all-reduce, and all 10 ms of the synchronous one.
    assert tr.exposed_collective_seconds(trace.devices[DEV0]) == \
        pytest.approx(2 * 0.013)
    assert exchange_exposed_ms.read(Ctx(trace)) == pytest.approx(13.0)


def test_a_done_without_its_start_still_counts_its_own_wait():
    ops = [tr.Op("all-reduce-done.7", 0.0, 0.004, "all-reduce-done"),
           tr.Op("fusion.1", 0.004, 0.010, "fusion", "kLoop")]
    assert tr.exposed_collective_seconds(tr.DeviceTrace(ops, [])) == \
        pytest.approx(0.004)


def test_step_program_is_the_module_that_takes_most_time(trace):
    steps = tr.step_modules(trace.devices[DEV0])
    assert [s.name for s in steps] == ["jit_step(1)"] * 2
    assert step_device_ms.read(Ctx(trace)) == pytest.approx(100.0)
    assert host_gap_ms.read(Ctx(trace)) == pytest.approx(5.0)   # device 0


def test_kernel_time_per_step_by_category(trace):
    ctx = Ctx(trace)
    # (milliseconds, events) per step; which kernel a Mosaic call is, only
    # the program's scopes say (test_benchmark_phase_split.py).
    assert per_step(ctx, tr.is_mosaic) == (pytest.approx(18.0), 1)
    assert conv_ms.read(ctx) == pytest.approx(40.0)             # device 1
    seconds, events = tr.sum_seconds(trace.devices[DEV0], tr.is_mosaic)
    assert (events, seconds) == (2, pytest.approx(0.036))


def test_idle_share_is_the_idlest_devices(trace):
    lo, hi = tr.window(trace)
    assert (lo, hi) == (0.0, pytest.approx(0.2062))
    assert device_idle_pct.read(Ctx(trace)) == pytest.approx(
        100 * (1 - 0.196 / 0.2062))


def test_idle_gaps_are_named_by_what_the_host_was_doing(trace):
    gaps = dict(tr.idle_gaps(trace.devices[DEV0], trace.host_spans,
                             (0.0, 0.205)))
    # 2 ms inside each step: the first under bench.dispatch, the second
    # under nothing of the benchmark's; 5 ms between the steps under
    # bench.fetch_loss.
    assert gaps == {"bench.fetch_loss": pytest.approx(0.005),
                    "bench.dispatch": pytest.approx(0.002),
                    "host:other": pytest.approx(0.002)}


def test_top_device_ops_sums_leaves_by_instruction_and_category(trace):
    top = tr.top_device_ops(trace.devices[DEV0], n=3)
    assert [name for name, _ in top] == [
        "fusion [fusion kOutput]", "fusion [fusion kLoop]",
        "closed_call [custom-call tpu_custom_call]"]
    assert top[0][1] == pytest.approx(0.060)
    labelled = tr.top_device_ops(
        trace.devices[DEV0], n=1,
        label=lambda op: "convolution" if op.name == "fusion.1" else "")
    assert labelled[0][0] == "fusion [fusion kOutput convolution]"


def test_readers_return_nothing_without_a_trace_or_their_events(trace):
    assert per_step(Ctx(None), tr.is_mosaic) == (None, None)
    assert conv_ms.read(Ctx(None)) is None
    assert host_gap_ms.read(Ctx(None)) is None
    only_dev1 = tr.Trace({DEV1: trace.devices[DEV1]}, [])
    assert per_step(Ctx(only_dev1), tr.is_mosaic) == (None, None)
    no_collectives = tr.Trace({"d": tr.DeviceTrace(
        [tr.Op("fusion.1", 0.0, 1.0, "fusion")],
        [tr.Op("jit_step", 0.0, 1.0)])}, [])
    assert exchange_exposed_ms.read(Ctx(no_collectives)) is None


def test_json_round_trip(trace):
    again = tr.trace_from_json(tr.trace_to_json(trace))
    assert again == trace


@pytest.mark.parametrize("text, parsed", [
    ("%copy-start.62 = (s32[8,4096]{1,0:T(8,128)S(1)}, s32[8,4096]{1,0:T(8,128)}"
     ", u32[]{:S(2)}) copy-start(s32[8,4096]{1,0:T(8,128)} %batch_0_.1)",
     ("copy-start.62", "copy-start", "")),
    ("%fusion.1 = bf16[32768,1024]{1,0:T(8,128)(2,1)} fusion(bf16[30528,1024]"
     "{1,0:T(8,128)(2,1)S(1)} %convert.3), kind=kOutput, calls=%fused.1",
     ("fusion.1", "fusion", "kOutput")),
    ("%closed_call.47 = (f32[8,16,4096,64]{3,2,1,0:T(8,128)}, /*index=1*/"
     "f32[8,16,4096,1]{3,2,1,0}) custom-call(s32[1]{0:T(128)} %gte.2590), "
     'custom_call_target="tpu_custom_call", operand_layout_constraints={}',
     ("closed_call.47", "custom-call", "tpu_custom_call")),
    ("%while.124 = (s32[]{:T(128)}, bf16[8,4096,1024]{1,2,0}) while((s32[]"
     "{:T(128)}, bf16[8,4096,1024]{1,2,0}) %tuple.9), condition=%c, body=%b",
     ("while.124", "while", "")),
    ("%all-reduce-start.3 = f32[1024]{0} all-reduce-start(f32[1024]{0} %p), "
     "replica_groups={{0,1,2,3}}, to_apply=%add",
     ("all-reduce-start.3", "all-reduce-start", "")),
    ("jit_local_step(6015551554743719998)",
     ("jit_local_step(6015551554743719998)", "", ""))])
def test_parse_instruction_as_the_v5e_trace_names_events(text, parsed):
    assert tr.parse_instruction(text) == parsed


def test_collectives_and_mosaic_calls_are_recognised_by_opcode():
    op = tr.Op
    assert tr.is_collective(op("all-reduce-start.3", 0, 1, "all-reduce-start"))
    assert tr.is_collective(op("reduce-scatter.1", 0, 1, "async-start"))
    assert not tr.is_collective(op("slice-start.12", 0, 1, "async-start"))
    assert not tr.is_collective(op("copy-start.62", 0, 1, "copy-start"))
    assert tr.is_mosaic(op("closed_call.47", 0, 1, "custom-call",
                           "tpu_custom_call"))
    assert not tr.is_mosaic(op("custom-call.17", 0, 1, "custom-call",
                               "AllocateBuffer"))


HLO_MODULE = """HloModule jit_step, entry_computation_layout={()->f32[]}

%fused_computation.1 (param_0: bf16[8,8]) -> bf16[8,8] {
  %param_0 = bf16[8,8]{1,0} parameter(0)
  %convolution.7 = bf16[8,8]{1,0} convolution(bf16[8,8]{1,0} %param_0, bf16[8,8]{1,0} %param_0), dim_labels=bf_io->bf
  ROOT %add.1 = bf16[8,8]{1,0} add(bf16[8,8]{1,0} %convolution.7, bf16[8,8]{1,0} %param_0)
}

%fused_computation.2 (param_0.1: bf16[8,8]) -> bf16[8,8] {
  %param_0.1 = bf16[8,8]{1,0} parameter(0)
  ROOT %multiply.1 = bf16[8,8]{1,0} multiply(bf16[8,8]{1,0} %param_0.1, bf16[8,8]{1,0} %param_0.1)
}

ENTRY %main.9 (p: bf16[8,8]) -> bf16[8,8] {
  %p = bf16[8,8]{1,0} parameter(0)
  %convolution_add_fusion = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %p), kind=kOutput, calls=%fused_computation.1
  %fusion.2 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %convolution_add_fusion), kind=kLoop, calls=%fused_computation.2
  ROOT %convolution.9 = bf16[8,8]{1,0} convolution(bf16[8,8]{1,0} %fusion.2, bf16[8,8]{1,0} %p), dim_labels=bf_io->bf
}
"""


def test_the_steps_hlo_text_says_which_fusions_hold_a_convolution():
    assert tr.instructions_holding(HLO_MODULE, "convolution") == {
        "convolution.7", "convolution_add_fusion", "convolution.9"}
