"""Registry-wide OpTest harness, the third of every four ops by name
(machinery and reference model: tests/op_harness_sweep.py)."""
import pytest

from op_harness_sweep import (PARTS, check_bf16_smoke, check_coverage,
                              check_forward_and_grad, check_whitelist)

OPS = PARTS[2]


@pytest.mark.parametrize("name", OPS)
def test_op_forward_and_grad(name):
    check_forward_and_grad(name)


@pytest.mark.parametrize("name", OPS)
def test_op_bf16_smoke(name):
    check_bf16_smoke(name)


# last: the sweep above has made this part's plans by now

def test_registry_fully_covered():
    check_coverage(OPS)


def test_whitelist_is_exact():
    check_whitelist(OPS)
