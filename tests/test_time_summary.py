"""The run's own account of its time (`tests/conftest.py`,
`where_the_time_went`): made-up reports in, the lines out."""

from tests.conftest import where_the_time_went


def test_the_summary_sums_phases_into_tests_and_tests_into_files():
    reports = [
        ("tests/test_a.py::test_one", 0.25),        # set-up
        ("tests/test_a.py::test_one", 10.0),        # call
        ("tests/test_a.py::test_one", 0.25),        # teardown
        ("tests/test_a.py::test_two[x-1]", 2.0),
        ("tests/test_b.py::test_three", 30.0),
        ("tests/sub/test_c.py::test_four", 1.0),
    ]
    assert where_the_time_went(reports, files=2, tests=3) == [
        "44 s summed over 4 tests in 3 files; the 2 costliest files "
        "(seconds, tests):",
        "    30.0    1  tests/test_b.py",
        "    12.5    2  tests/test_a.py",
        "the 3 costliest tests (seconds):",
        "    30.0  tests/test_b.py::test_three",
        "    10.5  tests/test_a.py::test_one",
        "     2.0  tests/test_a.py::test_two[x-1]",
    ]
    # no line can be taken for the run's line of dots
    assert all(" " in line.strip() for line in where_the_time_went(reports))
