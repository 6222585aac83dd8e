"""``tools/tier1_times.py`` over a run's JUnit XML written here: the table by
file, the sums, and the exit code where a file is over the limit."""
import pytest

from tools import tier1_times

XML = """<?xml version="1.0" encoding="utf-8"?>
<testsuites><testsuite name="pytest" errors="0" failures="1" skipped="0" tests="4" time="{run}">
<testcase classname="tests.test_a" name="test_one[x-1]" time="{one}" />
<testcase classname="tests.test_a" name="test_two" time="2.5"><failure message="no">no</failure></testcase>
<testcase classname="tests.test_b" name="test_three" time="30.0" />
<testcase classname="tests.test_b" name="test_four" time="0.5" />
</testsuite></testsuites>
"""


def written(tmp_path, one, run=40.0):
    path = tmp_path / "_t1.xml"
    path.write_text(XML.format(one=one, run=run))
    return str(path)


def test_the_table_is_by_file_longest_first_with_the_sums(tmp_path, capsys):
    assert tier1_times.main([written(tmp_path, 9.5)]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert [line.split() for line in lines[:4]] == [
        ["cases", "seconds", "file"],
        ["2", "30.5", "tests/test_b.py"],
        ["2", "12.0", "tests/test_a.py"],
        ["4", "42.5", "sum", "of", "2", "files"],
    ]
    assert lines[4].split()[:4] == [f"{42.5 / 6:.1f}", "sum", "over", "6"]
    assert lines[5].split()[0] == "40.0"
    # the longest cases, by their ids after the file
    assert [line.split()[1] for line in lines[7:]] == [
        "tests/test_b.py::test_three", "tests/test_a.py::test_one[x-1]",
        "tests/test_a.py::test_two", "tests/test_b.py::test_four"]


def test_a_file_over_the_limit_is_named_and_the_exit_code_is_not_zero(tmp_path, capsys):
    over = tier1_times.FILE_LIMIT_S - 2.0  # with test_two's 2.5 s
    assert tier1_times.main([written(tmp_path, over)]) == 1
    assert capsys.readouterr().out.splitlines()[-1] == (
        f"over {tier1_times.FILE_LIMIT_S} s a file: tests/test_a.py")
    with pytest.raises(SystemExit):
        tier1_times.main([])
