"""`reduce_trace.py` returns the known numbers for a small hand-built
trace of the shape a v5e writes (see the module's docstring)."""

import os

import pytest

from benchmarks.lib import readers, reduce_trace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "small_trace.textproto")


@pytest.fixture(scope="module")
def reduced():
    return reduce_trace.reduce(reduce_trace.load(FIXTURE))


def test_busy_share_and_window(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(1e-3)
    assert reduced["busy_s"] == pytest.approx(0.8e-3)
    assert readers.device_idle_pct({"trace": reduced}) == pytest.approx(20.0)


def test_top_operation_counts_a_while_once(reduced):
    # fusion.9 runs inside while.1: its time is the while's, not twice
    assert reduced["device_ops"][0] == ["while.1", pytest.approx(0.4e-3)]
    assert "fusion.9" not in [name for name, _ in reduced["device_ops"]]
    assert sum(t for _, t in reduced["device_ops"]) == pytest.approx(0.8e-3)


def test_gap_owner_is_the_innermost_host_annotation(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert gaps["PjitFunction(step)"] == pytest.approx(0.1e-3)
    assert gaps["(no host annotation)"] == pytest.approx(0.1e-3)
    assert "bench/window" not in gaps and "serving/admit" not in gaps


def test_collective_time_and_the_part_nothing_hides(reduced):
    # all-reduce.3 runs alone (0.1 ms); the async all-gather (0.2 ms) lies
    # under while.1 and attn.2
    assert reduced["collective_s"] == pytest.approx(0.3e-3)
    assert reduced["collective_exposed_s"] == pytest.approx(0.1e-3)
    assert readers.collective_exposed_pct({"trace": reduced}) == \
        pytest.approx(10.0)


def test_mosaic_custom_calls(reduced):
    assert reduced["custom_calls"] == 1
    assert reduced["custom_call_s"] == pytest.approx(0.2e-3)


def test_flash_roofline_share_from_shapes_and_the_trace(reduced):
    obs = {"trace": dict(reduced, custom_call_s=2.76e-3, custom_calls=1),
           "device_kind": "TPU v5 lite",
           "flash": {"batch_per_chip": 2, "heads": 16, "seq": 4096,
                     "head_dim": 64}}
    assert readers.flash_fwd_roofline(obs) == pytest.approx(12.6, abs=0.1)
    assert readers.flash_fwd_roofline({"trace": reduced}) is None


@pytest.mark.parametrize("text,want", [
    ('%attn.24 = (bf16[2,16,4096,64]{3,2,1,0:T(8,128)(2,1)S(1)}, '
     'f32[2,16,4096,1]{3,2,1,0:T(8,128)}) custom-call(bf16[2] %x), '
     'custom_call_target="tpu_custom_call"', ("attn.24", "custom-call")),
    ("%fusion.1192 = bf16[2,4096,50257]{1,2,0:T(8,128)(2,1)} "
     "fusion(bf16[50257,1024]{1,0:T(8,128)(2,1)S(1)} %a)",
     ("fusion.1192", "fusion")),
    ("%while.10 = (s32[]{:T(128)}, f32[2,4096]{1,0:T(8,128)S(1)}) "
     "while((s32[]{:T(128)}) %tuple.2155), condition=%c",
     ("while.10", "while")),
    ("%all-reduce-start.1 = f32[10]{0} all-reduce-start(f32[10] %p)",
     ("all-reduce-start.1", "all-reduce-start")),
    ("all-reduce.3", ("all-reduce.3", "all-reduce")),
])
def test_parse_op(text, want):
    assert reduce_trace.parse_op(text) == want


def test_a_trace_without_device_operations_is_refused():
    from jax.profiler import ProfileData

    host_only = ProfileData.from_text_proto(
        'planes { name: "/host:CPU" lines { name: "python" } }')
    with pytest.raises(ValueError):
        reduce_trace.reduce(host_only)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    assert readers.device_idle_pct({}) is None
    assert readers.step_ms({}) is None
    assert readers.mfu_pct({"step_s": [0.1]}) is None
    assert readers.collective_exposed_pct(
        {"trace": {"collective_s": 0.0, "window_s": 1.0}}) is None
    assert readers.ratio({"counters": {"syncs": 3}}, "syncs",
                         "generated") is None
