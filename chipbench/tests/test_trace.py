"""Trace reduction, on a small trace recorded on one TPU v5e chip
(``data/two_routes.xplane.pb``): two Robust04-shaped calls (16 topics,
XLA full sort) and two MS MARCO-shaped calls (64 queries, nDCG@10 and
friends, the top-k Pallas kernel), inside one ``chipbench.window`` span."""

import os

import pytest

from chipbench import harness
from chipbench import trace as tr
from chipbench.metrics._device import is_ranking, is_topk

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "two_routes.xplane.pb")
V5E = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture(scope="module")
def red():
    return tr.reduce(FIXTURE)


def readings(red, calls=4):
    return harness.Readings(red, calls, {}, V5E)


def test_window_and_busy_union(red):
    assert 0 < red.window_s < 5
    assert list(red.busy_s) == ["/device:TPU:0"]
    ops = red.ops["/device:TPU:0"]
    assert ops and 0 < red.busy_s["/device:TPU:0"] <= red.window_s
    # ops on one core run one after another: the union is their sum
    assert red.busy_s["/device:TPU:0"] == pytest.approx(
        sum(op.dur_ns for op in ops) / 1e9, rel=1e-6)
    idle = sum(s for _, s in red.idle_gaps)
    assert idle == pytest.approx(red.window_s - red.mean_busy_s, rel=1e-6)


def test_both_routes_are_classified(red):
    ops = red.all_ops()
    sorts = [op for op in ops if op.opcode == "sort"]
    topks = [op for op in ops if is_topk(op)]
    assert sorts and topks
    assert all(is_ranking(op) for op in sorts + topks)
    assert any(op.opcode == "fusion" and not is_ranking(op) for op in ops)


def test_per_layer_readers(red):
    r = readings(red)
    ranking = harness.reader("ranking_ms")(r)
    measures = harness.reader("measures_ms")(r)
    assert ranking > 0 and measures > 0
    assert (ranking + measures) * 4 == pytest.approx(
        red.mean_busy_s * 1e3, rel=1e-6)
    share = harness.reader("topk_roofline")(r)
    assert 0 < share <= 100
    idle = harness.reader("device_idle_share.lib")(r)
    assert 0 < idle < 1
    assert harness.reader("ranking_ms")(readings(None)) is None
    assert harness.reader("topk_roofline")(readings(None)) is None


def test_topk_bytes_from_the_call_shapes():
    mod = harness.metric_module("topk_roofline")
    hlo = ('%topk.1 = (f32[8192,128]{1,0:T(8,128)}, s32[8192,128]{1,0}) '
           'custom-call(f32[8192,1024]{1,0:T(8,128)S(1)} %x), '
           'custom_call_target="tpu_custom_call", '
           'operand_layout_constraints={f32[8192,1024]{1,0}}')
    assert mod.topk_bytes(hlo) == 4 * 8192 * 1024 + 2 * 4 * 8192 * 128


def test_parse_hlo():
    assert tr.parse_hlo("%sort.16 = (f32[2,8]{1,0}, s32[2,8]{1,0}) sort("
                        "f32[2,8] %a, s32[2,8] %b), dimensions={1}") == \
        ("sort.16", "sort")
    assert tr.parse_hlo("%fusion.4 = f32[256]{0} fusion(f32[256,8] %c), "
                        "kind=kLoop") == ("fusion.4", "fusion")
    assert tr.parse_hlo("not an instruction") == ("not an instruction", "")


def test_union_and_breakdown(red):
    assert tr.union_ns([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    b = tr.breakdown(red)
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert tr.breakdown(None) is None
