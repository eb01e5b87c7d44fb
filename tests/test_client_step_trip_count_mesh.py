"""tests/test_client_step_trip_count.py's checks on the 8-virtual-device
`clients` mesh, where the engine's rule gives `wide_from` 1 and builds no
job loop: every feed runs the full-width loop to its last step. A file of
its own so that `--dist loadfile` gives the mesh a worker beside the
one-device cases."""
import pytest

import trip_count_cases as tc

# solo_lane, two_tails and two_jobs differ from heavy_tail only in what the
# job loop would take over: without one they are heavy_tail's loop again, at
# 30-44 s a case on virtual devices (PR 29's table)
MESH_CASES = ("heavy_tail", "all_full", "empty_client", "check_k1",
              "check_k3")


@pytest.fixture(scope="module")
def pair():
    exp = tc.make_experiment(8)
    assert exp.engine.wide_from == 1
    return exp, tc.make_experiment(8, full_length=True)


@pytest.mark.parametrize("case", MESH_CASES)
def test_round_is_bit_equal_to_the_full_length_loop(pair, case):
    tc.check_round_is_bit_equal_to_the_full_length_loop(pair, case)


def test_train_phase_is_one_wide_while(pair):
    tc.check_train_phase_is_a_wide_while_then_a_width_1_job_loop(pair)


def test_one_program_for_every_trip_count_and_the_host_counts_it(pair):
    tc.check_one_program_for_every_trip_count_and_the_host_counts_it(pair)
