"""One case of one test in this directory waits for a ``benchmark`` PR.

``test_benchmark.py::test_schedule_is_a_pure_function_of_the_seed_and_respects_its_clips``
runs over every file of ``benchmark/workloads/`` and ends by holding each
request to ``prompt + output <= 2048``: every configuration before
``trinity-large-preview`` was served at ``--max-model-len 2048``, and the
number stands in the test and not in the configuration's file.
``long.trinity-large-preview`` is the first cell past it (prompts 5,120-6,656
under ``--max-model-len 8192``), and a PR that adds a cell edits no file the
benchmark has. So that one case is marked as expected to fail on its last line,
STRICTLY: once a ``benchmark`` PR reads the bound from the cell's
configuration (ROADMAP B11 (c)) the case passes, this mark turns it red, and
this file goes. Everything the case holds before that line, and the bound by
the configuration's own ``--max-model-len``, is held for the cell by
``test_trinity_cell.py::test_the_schedule_is_the_seeds_and_fits_the_configurations_positions``.
"""

import pytest

WAITS = ("test_schedule_is_a_pure_function_of_the_seed_and_respects_its_clips"
         "[long.trinity-large-preview]")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.name == WAITS:
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="test_benchmark.py:113 holds every cell to 2,048 positions; this cell's "
                       "configuration serves 8,192 (ROADMAP B11 (c): read --max-model-len)"))
