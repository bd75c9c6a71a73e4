"""The plain reference against Definition 3 term by term, and the
control: the reference at bfloat16 operand precision, which the limits
have to reject while the program passes them."""

import json
import os
from itertools import combinations

import numpy as np
import pytest

from bench import check, reference

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _limits(config):
    with open(os.path.join(REPO, "bench", "configs", config + ".json")) as f:
        return json.load(f)["limits"]


@pytest.mark.parametrize("m,n", [(1, 1), (1, 6), (2, 2), (2, 7), (3, 8),
                                 (4, 9), (5, 11), (6, 10)])
def test_dp_matches_definition_3(m, n):
    As = np.random.default_rng(m * 100 + n).standard_normal((3, m, n))
    want = np.array([reference.brute_force(A) for A in As])
    got = reference.radic_dp(As)
    assert np.max(np.abs(got - want) / reference.radic_scale(As)) < 1e-12


def test_m_greater_than_n_is_zero():
    assert np.all(reference.radic_dp(np.ones((2, 5, 3))) == 0)


def test_scale_is_the_root_sum_of_squared_minors():
    A = np.random.default_rng(1).standard_normal((4, 9))
    minors = [np.linalg.det(A[:, list(J)]) for J in combinations(range(9), 4)]
    assert reference.radic_scale(A[None])[0] == pytest.approx(
        np.sqrt(np.sum(np.square(minors))), rel=1e-10)


def test_gradient_matches_finite_differences():
    A = np.random.default_rng(2).standard_normal((1, 4, 9))
    g = reference.radic_grad_dp(A)[0]
    eps = 1e-6
    for i, j in [(0, 0), (2, 5), (3, 8)]:
        Ap = A.copy()
        Ap[0, i, j] += eps
        fd = (reference.radic_dp(Ap)[0] - reference.radic_dp(A)[0]) / eps
        assert fd == pytest.approx(g[i, j], rel=1e-5, abs=1e-6)
    assert reference.grad_scale(A).shape == (1, 4, 9)


def test_jnp_path_of_the_reference_agrees():
    import jax.numpy as jnp
    As = np.random.default_rng(3).standard_normal((4, 5, 11))
    got = np.asarray(reference.radic_dp(As, jnp, jnp.float32))
    assert np.max(np.abs(got - reference.radic_dp(As))
                  / reference.radic_scale(As)) < 1e-5


@pytest.mark.parametrize("config,m,n", [("paper_det", 8, 20),
                                        ("det_serve", 7, 17)])
def test_control_fails_the_limit_and_the_program_passes(config, m, n):
    """At a size a test run holds: the reference with bfloat16 operands
    in the program's place reads above the value limit; the program's
    own float32 path (jnp backend here, on the CPU) reads under it."""
    from repro.core import radic_det_batched
    limit = _limits(config)["value_err"]
    As = np.random.default_rng(4).standard_normal((16, m, n)).astype(
        np.float32)
    control = reference.radic_dp(reference.to_bfloat16(As), np,
                                 np.float32)
    assert check.value_gap(list(As), control) > 3 * limit
    got = np.asarray(radic_det_batched(As))
    assert check.value_gap(list(As), got) < limit / 3
