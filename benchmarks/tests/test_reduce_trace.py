"""The trace reduction, on hand-made events whose answers are worked
out by hand and on the trace recorded on the chip (tests/data/)."""

import json
import os

import pytest

from benchmarks import reduce_trace as rt
from benchmarks.reduce_trace import Event, Reduction, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e-3


def _ev(name, start_ms, end_ms, path=""):
    return Event(name, path, start_ms * MS, end_ms * MS)


def test_interval_arithmetic():
    assert rt.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]) == \
        [[0, 3], [5, 7]]
    assert rt.total([[0, 3], [5, 7]]) == 5
    assert rt.subtract([[0, 10]], [[1, 2], [4, 6], [9, 12]]) == \
        [[0, 1], [2, 4], [6, 9]]
    assert rt.subtract([[0, 1], [2, 3]], [[0, 3]]) == []
    assert rt.subtract([[0, 1]], []) == [[0, 1]]


def test_self_time_takes_nested_ops_out_of_their_parent():
    rows = rt.self_times([
        _ev("while", 0, 10),
        _ev("fusion.1", 1, 4, "jit(f)/attention/dot_general"),
        _ev("flash_fwd", 4, 6, "jit(f)/attention/flash_fwd"),
        _ev("fusion.2", 12, 13),
    ])
    got = {e.name: (round(s / MS, 6), leaf) for e, s, leaf in rows}
    assert got == {"while": (5.0, False), "fusion.1": (3.0, True),
                   "flash_fwd": (2.0, True), "fusion.2": (1.0, True)}


@pytest.fixture
def two_devices():
    """Window [0, 20] ms.  Device 0: busy 0-10 and 12-16; device 1: busy
    0-8.  On device 0 a while op spans 5-10 and holds a kernel, a
    fusion and an all-reduce-done that waits 8-10."""
    dev0 = [
        _ev("fusion.1", 0, 5, "jit(step)/mlp/dot_general"),
        _ev("while.9", 5, 10, "jit(step)/while"),
        _ev("flash_fwd.2", 5, 6, "jit(step)/while/body/attention/flash_fwd"),
        _ev("fusion.4", 6, 8, "jit(step)/while/body/mlp/dot_general"),
        _ev("all-reduce-done.3", 8, 10,
            "jit(step)/while/body/attention/psum"),
        _ev("fusion.5", 12, 16, "jit(step)/transpose(jvp(attention))/mul"),
    ]
    dev1 = [_ev("fusion.1", 0, 8, "jit(step)/mlp/dot_general")]
    host = [
        Event("bench.trace_window", "", 0.0, 20 * MS, "main"),
        Event("bench.step_dispatch", "", 9 * MS, 11 * MS, "main"),
        Event("bench.sync", "", 10.5 * MS, 20 * MS, "main"),
        Event("bench.feed_next", "", 10 * MS, 12.2 * MS, "main"),
    ]
    return Trace({0: dev0, 1: dev1}, host)


def test_busy_union_and_idle_share(two_devices):
    red = Reduction(two_devices, 0.0, 20 * MS)
    # (10 + 4 + 8) / 2 devices
    assert red.busy_s == pytest.approx(11 * MS)
    assert red.idle_share == pytest.approx(1 - 11 / 20)
    assert red.n_devices == 2


def test_time_by_name_and_by_scope(two_devices):
    red = Reduction(two_devices, 0.0, 20 * MS)
    flash = rt.matcher(["flash_fwd"])
    assert red.seconds(flash) == pytest.approx(0.5 * MS)  # 1 ms on one of 2
    assert red.count(flash) == 0.5
    # the named scope survives jvp and transpose in the name path
    scope = rt.matcher([r"(^|/|\()attention(/|\)|$)"], "path")
    assert red.seconds(scope) == pytest.approx((1 + 2 + 4) / 2 * MS)
    mlp = rt.matcher(["/mlp/"], "path")
    assert red.seconds(mlp) == pytest.approx((5 + 2 + 8) / 2 * MS)


def test_collective_time_is_the_self_time_of_collective_ops(two_devices):
    red = Reduction(two_devices, 0.0, 20 * MS)
    coll = rt.matcher(["all-reduce", "collective-permute"], "name")
    # 8-10 ms on device 0 only, none on device 1; the while around it
    # keeps nothing of it
    assert red.seconds(coll) == pytest.approx(1 * MS)
    assert red.seconds(rt.matcher(["^while"], "name")) == 0.0


def test_every_instant_of_a_gap_goes_to_the_shortest_host_event(
        two_devices):
    red = Reduction(two_devices, 0.0, 20 * MS)
    gaps = dict(red.idle_gaps())
    # bench.trace_window (the whole window) explains no gap and is left
    # out.  Device 0 idles 10-12: step_dispatch (2 ms long) is the
    # shortest event until it ends at 11, then feed_next (2.2 ms); and
    # 16-20: sync.  Device 1 idles 8-20: 8-9 nothing, 9-11
    # step_dispatch, 11-12.2 feed_next, 12.2-20 sync
    assert gaps["bench.feed_next"] == pytest.approx((1 + 1.2) / 2 * MS)
    assert gaps["bench.sync"] == pytest.approx((4 + 7.8) / 2 * MS)
    assert gaps["bench.step_dispatch"] == pytest.approx((1 + 2) / 2 * MS)
    assert gaps["(no host event)"] == pytest.approx(1 / 2 * MS)
    assert "bench.trace_window" not in gaps
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s)


def test_short_gaps_are_the_devices_own():
    ops = [_ev("fusion.1", 0, 1), _ev("fusion.2", 1.004, 2)]
    red = Reduction(Trace({0: ops}, [Event("bench.sync", "", 0, 2 * MS)]),
                    0.0, 2 * MS)
    assert dict(red.idle_gaps()) == {
        "(gaps under 20 us)": pytest.approx(0.004 * MS)}


def test_window_clips_events(two_devices):
    red = Reduction(two_devices, 4 * MS, 14 * MS)
    # device 0: 4-10 and 12-14; device 1: 4-8
    assert red.busy_s == pytest.approx((8 + 4) / 2 * MS)
    assert red.window_s == pytest.approx(10 * MS)


def test_breakdown_has_at_most_ten_of_each(two_devices):
    b = Reduction(two_devices, 0.0, 20 * MS).breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in b.values())
    top = dict(b["device_ops"])
    assert top["mlp/dot_general"] == pytest.approx((5 + 2 + 8) / 2 * MS)


def test_json_round_trip(tmp_path, two_devices):
    path = str(tmp_path / "t.json.gz")
    rt.save_json(two_devices, path)
    again = rt.load_json(path)
    assert Reduction(again, 0, 20 * MS).busy_s == \
        Reduction(two_devices, 0, 20 * MS).busy_s
    assert again.span("bench.sync").start == pytest.approx(10.5 * MS)


# ---- the trace recorded on the chip ----------------------------------

@pytest.fixture(scope="module")
def recorded():
    """300 ms of serve-flagship-chat on one v5e (my chip run, PR 22):
    4,116 device ops of decode and prefill programs and the host's
    events beside them, as load_xplane read them; cut out of a 4 s
    trace, shifted to start at 0."""
    return rt.load_json(os.path.join(DATA, "chat_v5e_300ms.json.gz"))


def test_recorded_trace_busy_and_idle(recorded):
    red = Reduction(recorded, 0.0, 0.3)
    assert red.n_devices == 1
    assert red.busy_s == pytest.approx(0.234056, abs=1e-6)
    assert red.idle_share == pytest.approx(0.2198, abs=1e-4)
    gaps = red.idle_gaps(n=1000)
    assert sum(s for _, s in gaps) == pytest.approx(0.3 - red.busy_s)
    # the host waits on device-to-host copies, then works unobserved
    assert [k for k, _ in gaps[:2]] == ["np.asarray(jax.Array)",
                                        "(no host event)"]


def test_recorded_trace_kernels_and_scopes(recorded):
    red = Reduction(recorded, 0.0, 0.3)
    paged = rt.matcher(["paged_attn"])
    flash = rt.matcher(["flash_fwd"])
    # 16 layers: 74 paged calls are four and a half decode steps, 32
    # flash calls are two prefills
    assert red.count(paged) == 74 and red.count(flash) == 32
    assert red.seconds(paged) == pytest.approx(0.027786, abs=1e-6)
    assert red.seconds(flash) == pytest.approx(0.000439, abs=1e-6)
    # the kernels sit under the attention named scope in the HLO name
    # path, as do the pool slices; the unembed has a scope of its own
    scope = rt.matcher([r"(^|/)attention(/|$)"], "path")
    assert red.seconds(scope) == pytest.approx(0.106722, abs=1e-6)
    assert red.seconds(scope) > red.seconds(paged) + red.seconds(flash)
    unembed = rt.matcher([r"(^|/)unembed(/|$)"], "path")
    assert red.seconds(unembed) == pytest.approx(0.000911, abs=1e-6)
    # self times by label add up to the busy time on one device
    assert sum(s for _, s in red.top_ops(n=10000)) == \
        pytest.approx(red.busy_s, rel=1e-3)
    assert red.top_ops(1)[0][0] == "copy"  # the undonated pools


def test_hlo_text_is_cut_to_the_instructions_name():
    assert rt._instruction(
        "%paged_attn.16 = (f32[32,128,128]{2,1,0}) custom-call(s32[32,22] "
        "%copy.234), custom_call_target=\"tpu_custom_call\"") == \
        "paged_attn.16"
    assert rt._instruction("ThunkExecutor::Execute") == \
        "ThunkExecutor::Execute"


def test_wire_decoder_reads_nested_messages():
    # field 1 varint 150; field 2 bytes {field 1 bytes "hi"}; field 3
    # fixed32
    msg = bytes([0x08, 0x96, 0x01, 0x12, 0x04, 0x0A, 0x02]) + b"hi" + \
        bytes([0x1D, 1, 0, 0, 0])
    fields = list(rt._wire_fields(memoryview(msg)))
    assert fields[0] == (1, 150)
    assert bytes(rt._first(fields[1][1], 1)) == b"hi"
    assert fields[2][0] == 3


@pytest.fixture(scope="module")
def recorded_ring():
    """40 ms from the middle of a train step of train-flagship-ring4 on
    a four-chip v5e host, sp=2 x tp=2 (my chip run, PR 22)."""
    return rt.load_json(os.path.join(DATA, "ring4_v5e_40ms.json.gz"))


def test_recorded_ring_collectives_by_name_and_by_primitive(recorded_ring):
    with open(os.path.join(os.path.dirname(DATA), "..", "layer_metrics",
                           "collective_exposed_share.json")) as f:
        params = json.load(f)["params"]
    red = Reduction(recorded_ring, 0.0, 0.04)
    assert red.n_devices == 4
    assert red.busy_s == pytest.approx(0.04, rel=1e-3)  # never idle
    coll = rt.matcher(params["patterns"], params["field"])
    # XLA calls a psum's all-reduce psum_invariant.N: only the name path
    # tells; 16 collective ops per device in these 40 ms
    by_name = {e.name.rsplit(".", 1)[0] for e in recorded_ring.devices[0]
               if coll(e)}
    assert by_name == {"collective-permute-start", "collective-permute-done",
                       "all-reduce", "psum_invariant"}
    assert red.count(coll) == 16
    # mean over devices; the first sp rank waits three times as long
    assert red.seconds(coll) == pytest.approx(0.003077, abs=1e-6)
    per_device = [sum(s for e, s, _ in rt.self_times(evs) if coll(e))
                  for evs in recorded_ring.devices.values()]
    assert per_device[0] > 2.5 * per_device[2]
    # the ring step is the flash_fwd kernel, under the attention scope
    flash = [e for e in recorded_ring.devices[0] if "flash_fwd" in e.name]
    assert flash and all("/attention/" in e.path for e in flash)


def test_idle_seconds_during_host_spans(two_devices):
    red = Reduction(two_devices, 0.0, 20 * MS)
    feed = rt.matcher([r"^bench\.feed_next$"], "name")
    # feed_next spans 10-12.2: device 0 idles 10-12 of it, device 1 all
    assert red.idle_seconds_during(feed) == pytest.approx((2 + 2.2) / 2 * MS)
    assert red.idle_seconds_during(rt.matcher(["^nothing$"], "name")) == 0.0
