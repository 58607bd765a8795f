"""The trace reduction on a small trace recorded on a v5e, and the
recording path end to end on the CPU."""

import os

import pytest

import roofline
import xtrace

DATA = os.path.join(os.path.dirname(__file__), "data", "v5e_ops.pbtxt")


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    with open(DATA) as f:
        pd = ProfileData.from_text_proto(f.read())
    return xtrace.reduce_profile(pd, z_logical=48, device_kind="TPU v5 lite")


def test_busy_and_idle_share(trace):
    # seven recorded ops inside the 1.7 ms window, the last one cut at
    # its end: 770 + 2 + 5 + 2 + 1608 + 463 + 1356 ns
    assert trace.window_ns == 1_700_000
    assert trace.busy_ns == pytest.approx(4206)
    assert trace.idle_frac == pytest.approx(1 - 4206 / 1.7e6)
    assert trace.busy_s == pytest.approx(4206e-9)


def test_ops_grouped_by_instruction(trace):
    assert trace.ops["bf_relax"] == pytest.approx(1608 + 1599)
    assert trace.ops["copy-start"] == pytest.approx(2 + 5 + 2)
    assert set(trace.ops) == {"dynamic-slice_select_fusion", "copy-start",
                              "bf_relax", "compare_reduce_fusion"}


def test_idle_gaps_labelled_by_host_marks(trace):
    gaps = trace.breakdown()["idle_gaps"]
    # the recording's "wait" annotation is none of the harness's marks
    assert gaps[0][0] == "other"
    assert gaps[0][1] == pytest.approx((1694812 - 19569) / 1e9)
    labels = {n for n, _ in gaps}
    assert labels == {"tick", "other"}


def test_kernel_shapes_and_roofline(trace):
    assert [(s, j) for s, j, _ in trace.kernel] == [(1, 8), (1, 8)]
    bytes_, ops = roofline.relax_work(1, 8, 48)
    assert bytes_ == 4 * (48 * 48 + 4 * 8 * 48 + 8)
    assert ops == 2 * 8 * 48 * 48
    least = 2 * bytes_ / 819e9
    assert trace.relax_roofline == pytest.approx(100 * least / 3207e-9)


def test_union_of_overlapping_intervals():
    busy, gaps = xtrace.union_ns([(0, 10), (5, 20), (30, 40)], 0, 50)
    assert busy == 30
    assert gaps == [(20, 30), (40, 50)]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v0")


def test_cpu_recording_round_trip():
    import jax.numpy as jnp

    tr = xtrace.Tracer()
    tr.start()
    with tr.annotate("tick"):
        jnp.ones(64).sum().block_until_ready()
    tr.stop()
    red = tr.reduce(48, "TPU v5 lite")
    assert red.window_ns > 0
    assert red.busy_ns == 0  # no TPU plane on the CPU
    assert not os.path.exists(tr.dir)
