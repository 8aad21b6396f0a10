"""The comparison's arithmetic on hand-made trees: which leaves a number is
taken over, and that a fault in the kernels alone cannot hide behind the
vectors that outnumber them."""

import pytest

from benchmarks.lib import compare

# two thirds of a ResNet's leaves are norm scales and biases
REFERENCE = {
    "loss": [7.0, 6.9, 6.8],
    "grad": {"conv0": 4.0, "conv1": 6.0, "scale0": 1.0, "bias0": 1.0,
             "scale1": 1.0, "bias1": 1.0},
    "delta": {"conv0": 0.04, "conv1": 0.06, "scale0": 0.01, "bias0": 0.01,
              "scale1": 0.01, "bias1": 0.01},
    "rank": {"conv0": 4, "conv1": 4, "scale0": 1, "bias0": 1, "scale1": 1,
             "bias1": 1},
}
LIMITS = {"grad1_med": 0.02, "delta_med": 0.02, "grad1_kernel": 0.12,
          "delta_kernel": 0.12}


def program(**changed):
    out = {k: (list(v) if isinstance(v, list) else dict(v))
           for k, v in REFERENCE.items() if k != "rank"}
    for tree in ("grad", "delta"):
        for leaf, factor in changed.items():
            out[tree][leaf] *= factor
    return out


def test_the_reference_against_itself_is_exact():
    numbers = compare.training_numbers(program(), REFERENCE)
    assert all(v == 0.0 for v in numbers.values())
    assert compare.judge(numbers, LIMITS)[0] is True


def test_noise_in_a_vector_does_not_reach_the_kernel_numbers():
    numbers = compare.training_numbers(program(scale0=1.4), REFERENCE)
    assert numbers["grad1"] == pytest.approx(0.4)
    assert numbers["grad1_kernel"] == 0.0 and numbers["grad1_med"] == 0.0
    assert compare.judge(numbers, LIMITS)[0] is True


@pytest.mark.parametrize("leaves", [("conv0",), ("conv0", "conv1")])
def test_a_fault_in_the_kernels_alone_is_caught(leaves):
    """Every kernel's gradient half as large again: the median leaf is a
    vector and reads nothing, the kernel numbers read the fault."""
    numbers = compare.training_numbers(
        program(**{leaf: 1.5 for leaf in leaves}), REFERENCE)
    assert numbers["grad1_med"] == 0.0 and numbers["delta_med"] == 0.0
    assert numbers["grad1_kernel"] == pytest.approx(0.5)
    ok, table = compare.judge(numbers, LIMITS)
    assert ok is False
    assert {k for k, (v, lim) in table.items() if v > lim} == {
        "grad1_kernel", "delta_kernel"}


def test_a_tree_with_no_kernel_is_an_error_not_a_pass():
    with pytest.raises(ValueError):
        compare.worst_kernel({"a": 0.0}, {"a": 1})
    assert compare.worst_kernel({"a": float("nan"), "b": 0.1},
                                {"a": 2, "b": 2}) != \
        compare.worst_kernel({"a": 0.2, "b": 0.1}, {"a": 2, "b": 2})


def test_a_limit_for_a_number_that_was_not_read_is_an_error():
    numbers = compare.training_numbers(program(), REFERENCE)
    with pytest.raises(KeyError):
        compare.judge(numbers, {"stats_med": 0.001})
