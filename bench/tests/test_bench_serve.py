"""The `horeg16.serve_poisson` cell run whole at CPU-test size through the
harness's own functions, with the chip check skipped."""
import pytest

from cellcheck import FAULTS, check_fault, check_result_line


def test_result_line():
    check_result_line("horeg16.serve_poisson", 1)


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(fault):
    check_fault("horeg16.serve_poisson", 1, fault)
