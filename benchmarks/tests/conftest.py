"""One known gap, said aloud instead of left red.

test_correct.py's control case takes every cell of BENCHMARK.json but builds
lineitem, orders and customer only (`small_data`), so its case for
`embedded_sf10_multiway` — whose references read all eight tables — ends in
KeyError: 'supplier'.  test_correct_multiway.py runs the control for that cell
over the eight tables.  An accepted benchmark file is a `benchmark` PR's to
edit (PERF.md, Open questions row 6): that PR widens `small_data` and deletes
this file — the mark is strict, so the case passing fails the run until it
does.
"""

import pytest

KNOWN = "test_correct.py::test_lowered_precision_is_not_correct[embedded_sf10_multiway]"


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(KNOWN):
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=KeyError,
                reason="small_data holds three of the cell's eight tables; "
                       "test_correct_multiway.py runs this control"))
