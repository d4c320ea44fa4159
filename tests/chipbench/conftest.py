"""Two tests that were here before PR 39 hold what their PR appended to
`BENCHMARK.json` to being its LAST entries, so they fail as soon as any
later PR appends a cell or a per-layer metric after them, whatever it is:

- tests/chipbench/test_hybrid_moe_lm.py (PR 35) takes
  ``bench["workloads"][:-1]`` for "every cell but Solar's" and wants the
  last entry of nine metrics' ``workloads`` lists to be Solar's cell;
- tests/chipbench/test_reduction_spans.py (PR 37) wants the last four
  entries of ``per_layer`` to be its four dp4 metrics.

A PR that adds a cell may edit no benchmark file that is there, those tests
among them, and appends its own entries after theirs (an entry put in the
middle of a list reads as a change to what was there); a `benchmark` PR owes
each its repair. Until then the two RUN and are expected to fail, by name
and with this reason: the run's summary counts them as ``xfailed``, in
plain sight, and strictly, so the repair that lets one pass fails it until
its name is taken out of this file (and the file with the last name). What
they checked is checked without the assumption by
tests/chipbench/test_ssm_moe_lm.py
(`test_each_familys_metrics_are_reported_in_its_own_cells_only`,
`test_the_four_dp4_metrics_keep_their_readers_and_entries`).
"""

import pytest

ASSUME_THEIR_ENTRIES_ARE_THE_LAST = (
    "tests/chipbench/test_hybrid_moe_lm.py::"
    "test_the_new_metrics_are_reported_in_the_new_cell_only",
    "tests/chipbench/test_reduction_spans.py::"
    "test_the_four_metrics_have_a_reader_and_an_entry_on_dp4_only",
)


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid in ASSUME_THEIR_ENTRIES_ARE_THE_LAST:
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="assumes its PR's entries are BENCHMARK.json's last; "
                       "held by test_ssm_moe_lm.py meanwhile; a benchmark "
                       "PR repairs it (conftest.py beside it)"))
