"""The trace reduction against a small trace recorded on the v5e in PR 22
(``record_fixture.py --chips 1``: three steps of a two-layer GPT of width
512 with the flash kernels, each followed by a ``bench/report`` span that
sleeps 20 ms; ``--chips 4`` the same under ``fsdp=2 x tp=2``), and its
interval arithmetic against cases done by hand. The traces were cut to size
with ``xplane_pb2``: the ``/host:metadata`` plane (HLO text, two thirds of
the bytes) is dropped, and of the four chips' planes the first two are
kept."""

import os

import pytest

import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def reduced():
    data = xplane.load(os.path.join(DATA, "v5e_1chip_tiny.xplane.pb.gz"))
    return xplane.reduce_trace(data)


def test_union_and_merge():
    assert xplane.union_seconds([]) == 0.0
    assert xplane.union_seconds([(0, 1), (0.5, 2), (3, 4)]) == 3.0
    assert xplane.union_seconds([(0, 10), (1, 2), (3, 4)]) == 10.0
    assert xplane.merged([(3, 4), (0, 1), (1, 2)]) == [(0, 2), (3, 4)]


def test_parse_op():
    text = ("%fusion.12 = bf16[8,128]{1,0:T(8,128)(2,1)S(1)} fusion("
            "bf16[8,128]{1,0:T(8,128)(2,1)} %p.1), kind=kLoop")
    assert xplane.parse_op(text) == ("fusion.12", "fusion", None)
    kernel = ('%closed_call.13 = (bf16[4,512,128]{2,1,0:T(8,128)(2,1)}, '
              'f32[4,1,512]{2,1,0}) custom-call(bf16[4,512,128] %x), '
              'custom_call_target="tpu_custom_call", operand_layout=...')
    assert xplane.parse_op(kernel) == ("closed_call.13", "custom-call",
                                       "tpu_custom_call")
    loop = ("%while.16 = (s32[]{:T(128)}, bf16[2,512]{1,0:T(2,128)(2,1)}) "
            "while((s32[]{:T(128)}, bf16[2,512]) %tuple.235), condition=%c")
    assert xplane.parse_op(loop)[:2] == ("while.16", "while")
    assert xplane.parse_op("jit_step(123)") == ("jit_step(123)",
                                                "jit_step(123)", None)
    assert xplane.is_collective("all-gather-start.3", "all-gather-start")
    assert xplane.is_collective("all-reduce-fusion.1", "async-start")
    assert not xplane.is_collective("fusion.12", "fusion")


def test_idle_gaps_by_hand():
    busy = [(1.0, 2.0), (5.0, 6.0)]
    spans = {"bench/window": [(0.0, 10.0)], "bench/report": [(2.0, 4.5)],
             "bench/step": [(4.5, 6.5)]}
    gaps = dict(xplane.idle_gaps(busy, (0.0, 10.0), spans))
    # 0-1 window, 2-4.5 report, 4.5-5 and 6-6.5 step, 6.5-10 window.
    assert gaps == {"report": 2.5, "step": 1.0, "window": 4.5}
    assert dict(xplane.idle_gaps(busy, (0.0, 7.0), {})) == {"other": 5.0}


def test_recorded_trace(reduced):
    assert reduced["devices"] == ["/device:TPU:0"]
    steps = reduced["steps_device_s"]
    assert len(steps) == 3
    # The chip repeats a step to the microsecond: 2.3332 ms three times.
    assert all(abs(s - 2.3335e-3) < 2e-6 for s in steps)
    # Busy is what the three steps took, less the gaps inside them.
    assert 0.95 * sum(steps) < reduced["busy_s"] <= sum(steps)
    assert 0.075 < reduced["window_s"] < 0.082
    assert reduced["collective_s"] == 0.0
    # The flash kernels: forward, dq and dk/dv, forward once more in remat.
    assert 0.18 < reduced["mosaic_s"] / reduced["busy_s"] < 0.25
    names = [name for name, _ in reduced["device_ops"]]
    assert len(names) == 10 and not any(n.startswith("while") for n in names)
    gaps = dict(reduced["idle_gaps"])
    # Three sleeps of 20 ms in bench/report, and the device idle in them.
    assert 0.060 < gaps["report"] < 0.066
    assert abs(sum(gaps.values()) + reduced["busy_s"]
               - reduced["window_s"]) < 1e-6
    assert set(reduced["host_spans"]) == {"bench/window", "bench/step",
                                          "bench/report"}


def test_recorded_trace_on_a_mesh():
    data = xplane.load(os.path.join(DATA, "v5e_4chip_tiny.xplane.pb.gz"))
    reduced = xplane.reduce_trace(data)
    assert reduced["devices"] == ["/device:TPU:0", "/device:TPU:1"]
    steps = reduced["steps_device_s"]
    # The step, not the six runs of the small program that slices the loss.
    assert len(steps) == 3 and all(2.4e-3 < s < 2.7e-3 for s in steps)
    # At this toy size the step is mostly collectives, all synchronous but
    # one collective-permute: 147 all-reduces, 168 all-gathers and 30
    # all-to-alls took 4.48 ms of the first chip's 7.3 ms.
    assert 4.4e-3 < reduced["collective_s"] < 4.8e-3
    assert reduced["collective_s"] < reduced["busy_s"] < sum(steps)
    assert reduced["device_ops"][0][0].startswith("all-reduce")
    assert 0.04 < reduced["mosaic_s"] / reduced["busy_s"] < 0.07
