"""Fixtures the benchmark's test files share."""

import os

import pytest

from bench_presets import (REPO, manifest_with_a_later_prs_additions,
                           manifest_with_serving_cell)


@pytest.fixture(params=["as_committed", "with_the_serving_cell",
                        "with_a_later_prs_additions"])
def manifest_path(request, tmp_path):
    """The manifest; the manifest once a PR has added the decode cell's
    entries to it (the mix and its readers are here, the cell is not); and
    the manifest once a PR has brought a configuration, a cell and a
    per-layer metric as files and appended entries. What holds for the
    first has to hold for the other two: the next PR's manifest is one of
    their kind."""
    if request.param == "as_committed":
        return os.path.join(REPO, "BENCHMARK.json")
    if request.param == "with_the_serving_cell":
        return manifest_with_serving_cell(str(tmp_path))
    return manifest_with_a_later_prs_additions(tmp_path)
