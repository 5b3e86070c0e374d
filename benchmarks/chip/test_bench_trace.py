"""The trace reduction, on a hand-made trace laid out as a TPU v5e's
(device operations named by their HLO text on an ``XLA Ops`` line, the
loop's own event spanning its body, the host's sync mark on a host
plane).  ``record_testdata.py`` records a small real one on the chip."""

import pytest

from chip import trace_reduce


# device ops (start ns, end ns, HLO text); the host's sync mark at 500 ns
OPS = [
    (1000, 9000, "%while.1 = (s32[]) while((s32[]) %t), condition=%c, "
                 "body=%b"),
    (1000, 3000, "%fusion.1 = f32[8,4]{1,0} fusion(f32[8,4]{1,0} %p), "
                 "kind=kLoop, calls=%f1"),
    (3000, 4000, "%fused_roundtrip.2 = f32[8,4]{1,0} custom-call("
                 "f32[8,4]{1,0} %fusion.1), custom_call_target="
                 "\\\"tpu_custom_call\\\""),
    (6000, 8000, "%fusion.3 = f32[8,4]{1,0} fusion(f32[8,4]{1,0} "
                 "%fused_roundtrip.2), kind=kLoop, calls=%f3"),
]


def _events(rows):
    meta = "".join(f'  event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{name}" }} }}\n'
                   for i, (_, _, name) in enumerate(rows, 1))
    events = "".join(f"    events {{ metadata_id: {i} offset_ps: {a * 1000} "
                     f"duration_ps: {(b - a) * 1000} }}\n"
                     for i, (a, b, _) in enumerate(rows, 1))
    return events, meta


@pytest.fixture(scope="module")
def made():
    from jax.profiler import ProfileData
    dev, dev_meta = _events(OPS)
    host, host_meta = _events([(500, 501, trace_reduce.SYNC)])
    text = (f'planes {{ id: 1 name: "/device:TPU:0" lines {{ id: 1 '
            f'name: "XLA Ops" timestamp_ns: 0\n{dev}  }}\n{dev_meta}}}\n'
            f'planes {{ id: 2 name: "/host:CPU" lines {{ id: 1 '
            f'name: "python" timestamp_ns: 0\n{host}  }}\n{host_meta}}}\n')
    data = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))
    return trace_reduce.Trace(data)


def test_reduces_a_hand_made_trace(made):
    sync = 100.0                      # host seconds at the sync mark

    def host(ns):
        return sync + (ns - 500) * 1e-9

    spans = [("run", host(1000), host(10000)), ("pack", host(4000),
                                                 host(5000))]
    red = trace_reduce.reduce(made, sync, (host(1000), host(10000)), spans,
                              ["roundtrip"])
    approx = pytest.approx
    assert red["window_s"] == approx(9e-6) and red["devices"] == 1
    # the loop's own event is left out; its body's ops make busy
    assert red["busy_s"] == approx(5e-6)
    assert red["ops"] == {"fusion.1": approx(2e-6),
                          "fused_roundtrip.2": approx(1e-6),
                          "fusion.3": approx(2e-6)}
    # the kernel by its own name, not where another op reads its output
    assert red["kernels"] == {"roundtrip": approx(1e-6)}
    assert red["kernel_calls"] == {"roundtrip": 1}
    assert red["custom_calls"] == {"fused_roundtrip.2": approx(1e-6)}
    # each idle instant goes to the innermost host span over it
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps == {"run": approx(3e-6), "pack": approx(1e-6)}
    assert red["breakdown"]["device_ops"][0][0] in ("fusion.1", "fusion.3")


def test_the_sync_mark_and_a_device_are_required():
    from jax.profiler import ProfileData
    empty = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(
            'planes { id: 2 name: "/host:CPU" }'))
    with pytest.raises(RuntimeError, match="annotation"):
        trace_reduce.Trace(empty)

