"""The peaks table and the shapes -> bytes/operations functions, on shapes
counted by hand."""

import math

import pytest

import cost


def test_fused_cycle_cost_by_hand():
    # one pool, 4 task rows, 2 hosts, 2 users, cap 2
    c = cost.fused_cycle_cost(P=1, T=4, H=2, U=2, C=2)
    inputs = 4 * 25 + 2 * 32 + 2 * 34           # rows, user tables, hosts
    outputs = 4 * 4 + 3 * 2 * 4 + 4             # queue rows, candidates, n
    assert c["bytes"] == inputs + outputs == 276
    assert c["ops"] == 14 * 4 + 4 * math.log2(4) + 12 * 2 * 2 == 112


def test_fused_cycle_cost_scales_with_pools():
    one = cost.fused_cycle_cost(1, 131072, 8192, 256, 1024)
    eight = cost.fused_cycle_cost(8, 131072, 8192, 256, 1024)
    assert eight["bytes"] == 8 * one["bytes"]
    assert eight["ops"] == 8 * one["ops"]
    assert 4.0e6 < one["bytes"] < 4.5e6         # ~4.2 MB at the 1-pool cell


def test_delta_scatter_cost_by_hand():
    assert cost.delta_scatter_cost(2048) == {"bytes": 2048 * 14.0,
                                             "ops": 2048.0}


def test_least_seconds_names_its_bound_and_refuses_unknown_devices():
    c = cost.fused_cycle_cost(1, 131072, 8192, 256, 1024)
    least = cost.least_seconds(c, "TPU v5 lite")
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(c["bytes"] / 819e9)
    with pytest.raises(KeyError):
        cost.least_seconds(c, "TPU v9 imaginary")
