"""The numbers that decide ``correct``, on readings whose answers are known."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, inputs

REF = {"loss": [12.0, 11.9, 11.8], "grad": [1.0, 2.0, 4.0, 0.0],
       "change": [3.0, 6.0, 12.0, 0.0],
       "sign": np.array([1, -1, 0, 1, -1, 1, 0, 0], np.int8)}


def test_identical_readings_read_zero():
    assert compare.numbers(REF, REF) == \
        {"loss_gap": 0.0, "grad_gap": 0.0, "change_gap": 0.0,
         "sign_gap": 0.0}


def test_sign_gap_is_the_share_of_coordinates_whose_sign_differs():
    # one sign flipped, one zero where the reference moved: 2 of 8
    prog = dict(REF, sign=np.array([-1, -1, 0, 1, 0, 1, 0, 0], np.int8))
    assert compare.numbers(prog, REF)["sign_gap"] == pytest.approx(0.25)
    flipped = dict(REF, sign=-REF["sign"])
    assert compare.numbers(flipped, REF)["sign_gap"] == pytest.approx(5 / 8)


def test_change_signs_sample_the_same_coordinates_for_the_same_key():
    big = compare.SAMPLE + 5
    a = {"w": jnp.arange(big, dtype=jnp.float32) % 3 - 1.0,
         "b": jnp.array([2.0, -2.0, 0.0])}
    zero = {"w": jnp.zeros(big), "b": jnp.zeros(3)}
    key = inputs.signs_key(2 ** 33 + 1)
    s1 = compare.change_signs(a, zero, key)
    assert s1.dtype == np.int8 and s1.shape == (3 + compare.SAMPLE,)
    # leaves in tree order: "b" (whole, 3 < SAMPLE) then a sample of "w"
    assert s1[:3].tolist() == [1, -1, 0]
    assert np.array_equal(s1, compare.change_signs(a, zero, key))
    assert {-1, 0, 1} == set(s1[3:].tolist())
    s2 = compare.change_signs(a, zero, inputs.signs_key(7))
    assert not np.array_equal(s1, s2)


def test_gaps_by_worst_leaf_against_the_median_leaf():
    prog = dict(REF, loss=[12.0, 11.95, 11.8], grad=[1.1, 2.0, 4.0, 0.2],
                change=[3.0, 6.0, 12.0, 5.0])
    n = compare.numbers(prog, REF)
    assert n["loss_gap"] == pytest.approx(0.05)
    # median of (1, 2, 4, 0) is 1.5: leaf 0 reads 0.1/1.5, leaf 3 0.2/1.5
    assert n["grad_gap"] == pytest.approx(0.2 / 1.5)
    # leaf 3's first reference gradient is 0 (under a thousandth of the
    # median): left out of the change, whatever the program did there
    assert n["change_gap"] == 0.0


def test_unchanged_state_reads_one():
    prog = dict(REF, change=[0.0, 0.0, 0.0, 0.0])
    assert compare.numbers(prog, REF)["change_gap"] == pytest.approx(1.0)


def test_judge():
    limits = {"limits": {"loss_gap": 0.1, "grad_gap": None,
                         "change_gap": 0.5, "sign_gap": 0.01}}
    ok, checks = compare.judge(
        {"loss_gap": 0.05, "grad_gap": 9.0, "change_gap": 0.4,
         "sign_gap": 0.0}, limits)
    assert ok and checks["grad_gap"] == {"value": 9.0, "limit": None}
    ok, _ = compare.judge(
        {"loss_gap": 0.2, "grad_gap": 0.0, "change_gap": 0.4,
         "sign_gap": 0.0}, limits)
    assert not ok
    ok, _ = compare.judge(
        {"loss_gap": 0.0, "grad_gap": 0.0, "change_gap": 0.0,
         "sign_gap": 0.4}, limits)
    assert not ok
    ok, _ = compare.judge(
        {"loss_gap": math.nan, "grad_gap": 0.0, "change_gap": 0.0,
         "sign_gap": 0.0}, limits)
    assert not ok
