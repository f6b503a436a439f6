"""The trace reduction (``bench/trace_reduce.py``) on a small profiler
trace whose every interval is known: a device plane with operations and
programs, and a host plane with the window and the server's spans,
written in the XSpace format the profiler writes."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce  # noqa: E402

MS = 1_000_000_000       # picoseconds in a millisecond


def _varint(n: int) -> bytes:
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        if not n:
            return out + bytes([b])
        out += bytes([b | 0x80])


def _int(field: int, v: int) -> bytes:
    return _varint(field << 3) + _varint(v)


def _bytes(field: int, b: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(b)) + b


def _plane(pid: int, name: str, lines: dict) -> bytes:
    """XPlane: ``lines`` maps a line name to (event name, start ms,
    duration ms) triples; event names become the plane's metadata."""
    names: dict = {}
    out = _int(1, pid) + _bytes(2, name.encode())
    for lid, (lname, events) in enumerate(lines.items(), 1):
        evs = b""
        for ev, start, dur in events:
            mid = names.setdefault(ev, len(names) + 1)
            evs += _bytes(4, _int(1, mid) + _int(2, int(start * MS))
                          + _int(3, int(dur * MS)))
        out += _bytes(3, _int(1, lid) + _bytes(2, lname.encode())
                      + _int(3, 0) + evs)
    for ev, mid in names.items():
        out += _bytes(4, _int(1, mid)
                      + _bytes(2, _int(1, mid) + _bytes(2, ev.encode())))
    return out


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    device = _plane(1, "/device:TPU:0", {
        "XLA Ops": [("fusion.1", 0, 5), ("quant_pack_kernel", 10, 2),
                    ("fusion.1", 11, 3), ("fusion.2", 30, 1)],
        "XLA Modules": [("jit_run(123)", 0, 6), ("jit_pack(7)", 10, 4)]})
    host = _plane(2, "/host:CPU", {
        "python": [("bench/window", 0, 20), ("fl/pack", 8, 7),
                   ("fl/broadcast", 15, 5),
                   ("PjRtClient::Execute", 16, 1)]})
    path = tmp_path_factory.mktemp("trace") / "host.xplane.pb"
    path.write_bytes(_bytes(1, device) + _bytes(1, host))
    return trace_reduce.reduce(str(path))


def test_busy_time_is_the_union_of_operations_in_the_window(trace):
    assert trace["devices"] == 1
    assert trace["window_s"] == pytest.approx(0.020)
    # [0, 5] and [10, 14] ms; the op at 30 ms is outside the window
    assert trace["busy_s"] == pytest.approx(0.009)
    assert trace["idle_share"] == pytest.approx(0.55)


def test_device_time_by_program_and_operation(trace):
    assert trace["programs"] == pytest.approx({"jit_run": 0.006,
                                               "jit_pack": 0.004})
    assert trace["ops"] == pytest.approx({"fusion.1": 0.008,
                                          "quant_pack_kernel": 0.002})
    assert trace["op_counts"] == {"fusion.1": 2, "quant_pack_kernel": 1}
    assert trace["host"]["fl/pack"] == pytest.approx(0.007)


def test_idle_gaps_are_named_by_the_host_span_open_in_them(trace):
    # 14-20 ms lies in fl/broadcast (the runtime's own span is not a
    # layer's); 5-10 ms has its middle before fl/pack opens
    assert trace["idle_gaps"] == [("fl/broadcast", pytest.approx(0.006)),
                                  ("no host span", pytest.approx(0.005))]
    got = trace_reduce.breakdown(trace)
    assert got["device_ops"][0] == ["fusion.1", pytest.approx(0.008)]
    assert len(got["idle_gaps"]) == 2


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]


def test_a_trace_without_the_window_or_a_device_is_refused(tmp_path):
    host = _plane(2, "/host:CPU", {"python": [("fl/pack", 0, 1)]})
    device = _plane(1, "/device:TPU:0", {"XLA Ops": [("f", 0, 1)]})
    for name, blob in (("a", _bytes(1, host)),
                       ("b", _bytes(1, device) + _bytes(1, host))):
        path = tmp_path / f"{name}.xplane.pb"
        path.write_bytes(blob)
        with pytest.raises(ValueError):
            trace_reduce.reduce(str(path))
