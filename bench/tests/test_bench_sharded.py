"""The `hyperrep4.sharded` cell run whole at CPU-test size through the
harness's own functions, on 4 virtual CPU devices, with the chip check
skipped."""
import pytest

from cellcheck import FAULTS, check_fault, check_result_line


def test_result_line():
    check_result_line("hyperrep4.sharded", 4)


@pytest.mark.parametrize("fault", FAULTS + ("no_exchange",))
def test_fault_is_not_correct(fault):
    check_fault("hyperrep4.sharded", 4, fault)
