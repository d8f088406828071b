"""The trace reducer gives known figures on a hand-built trace.

``data/two_devices.xplane.pb`` is an XSpace written by :func:`build`
below, field by field after ``tsl/profiler/protobuf/xplane.proto``; the
first test pins the file to the builder, so the figures asserted here can
be read off the table of events.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import xplane  # noqa: E402

TRACE = os.path.join(HERE, "data", "two_devices.xplane.pb")

US = 1_000_000  # picoseconds in a microsecond

# (event name as the v5e's profiler writes it: the HLO text, start_us,
# duration_us) on each device's "XLA Ops" line.
FUSION = "%fusion.{} = f32[16,768]{{1,0:T(8,128)}} fusion(f32[16]{{0:T(1024)S(1)}} %p.1), kind=kLoop, calls=%fused_computation.{}"
KERNEL = "%block_0.7 = (bf16[16,12,1024,64]{3,2,1,0:T(8,128)(2,1)}, f32[8]{0}) custom-call(bf16[16]{0} %custom-call.2), custom_call_target=\"tpu_custom_call\""
ALL_REDUCE = "%all-reduce.3 = f32[972187,128]{1,0:T(8,128)} all-reduce(f32[972187,128]{1,0:T(8,128)} %fusion.4), replica_groups={{0,1}}"
DEVICE_0 = [
    (FUSION.format(1, 1), 100, 300),   # 100-400
    (KERNEL, 400, 200),                # 400-600, back to back
    (ALL_REDUCE, 550, 250),            # 550-800: 50 under the kernel
    (FUSION.format(2, 2), 1000, 100),  # 1000-1100 after a 200 us gap
    (FUSION.format(9, 9), 1110, 90),   # 1110-1200 after a 10 us gap
]
DEVICE_1 = [
    (FUSION.format(1, 1), 100, 500),   # 100-600
    (ALL_REDUCE, 600, 200),            # 600-800, wholly exposed
]
MARK_US = 50


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field: int, value: int) -> bytes:
    return _varint(field << 3) + _varint(value)


def _bytes(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _str(field: int, text: str) -> bytes:
    return _bytes(field, text.encode())


def _plane(pid: int, name: str, line_name: str, events) -> bytes:
    names = sorted({e[0] for e in events})
    meta = b"".join(
        _bytes(4, _int(1, i + 1) + _bytes(2, _int(1, i + 1) + _str(2, n)))
        for i, n in enumerate(names))
    evs = b"".join(
        _bytes(4, _int(1, names.index(n) + 1) + _int(2, start * US)
               + _int(3, dur * US))
        for n, start, dur in events)
    line = _bytes(3, _int(1, 1) + _str(2, line_name) + _int(3, 0) + evs)
    return _bytes(1, _int(1, pid) + _str(2, name) + line + meta)


def build() -> bytes:
    return (
        _plane(1, "/device:TPU:0", "XLA Ops", DEVICE_0)
        + _plane(2, "/device:TPU:1", "XLA Ops", DEVICE_1)
        + _plane(3, "/host:CPU", "python3", [(xplane.MARK, MARK_US, 1)])
    )


def test_recorded_file_is_what_the_builder_writes():
    with open(TRACE, "rb") as f:
        assert f.read() == build()


@pytest.fixture(scope="module")
def trace():
    return xplane.load(TRACE)


def test_planes_lines_and_mark(trace):
    assert [d.name for d in trace.devices] == ["/device:TPU:0", "/device:TPU:1"]
    assert [len(d.ops) for d in trace.devices] == [5, 2]
    assert trace.mark_s == pytest.approx(MARK_US * 1e-6)
    assert trace.devices[0].ops[1][0::3] == ("block_0", "custom-call")
    assert xplane.parse_op("not hlo text") == ("not hlo text", "")


def test_busy_idle_operations_and_gaps(trace):
    us = 1e-6
    spans = [("host_fence", 790 * us, 1010 * us), ("step", 0, 2000 * us)]
    r = xplane.reduce(trace, 0.0, 1300 * us, spans)
    # device 0 busy: 100-800 and 1000-1100 and 1110-1200 = 890; device 1: 700.
    assert r["busy_s"] == pytest.approx((890 + 700) / 2 * us)
    assert r["window_s"] == pytest.approx(1300 * us)
    assert r["custom_call_s"] == pytest.approx(200 / 2 * us)
    assert r["collective_s"] == pytest.approx((250 + 200) / 2 * us)
    # device 0's all-reduce runs alone from 600 to 800; device 1's all of it.
    assert r["collective_exposed_s"] == pytest.approx((200 + 200) / 2 * us)
    ops = dict((k, v) for k, v in r["device_ops"])
    assert ops["fusion__fusion__x2"] == pytest.approx((490 + 500) / 2 * us)
    assert ops["block_0__custom-call__x0"] == pytest.approx(100 * us)
    assert ops["all-reduce__all-reduce__x1"] == pytest.approx(225 * us)
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    # device 0: 0-100 and 1200-1300 in `step`, 800-1000 in `host_fence` (the
    # innermost span over the gap's middle), 10 us under the threshold;
    # device 1: 0-100 and 800-1300 in `step` (its middle, 1050, is outside
    # host_fence).
    assert gaps["host_in_span_step"] == pytest.approx((200 + 600) / 2 * us)
    assert gaps["host_in_span_host_fence"] == pytest.approx(200 / 2 * us)
    assert gaps["under_20_us__between_operations"] == pytest.approx(10 / 2 * us)
    assert r["busy_in_span"]["host_fence"] == pytest.approx((20 + 10) / 2 * us)


def test_window_clips_operations(trace):
    r = xplane.reduce(trace, 300e-6, 500e-6)
    assert r["busy_s"] == pytest.approx(200e-6)
    assert r["idle_gaps"] == []


if __name__ == "__main__":
    os.makedirs(os.path.dirname(TRACE), exist_ok=True)
    with open(TRACE, "wb") as f:
        f.write(build())
