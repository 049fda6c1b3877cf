"""A tiny copy of the benchmark's data files: the same cells at sizes the CPU
can hold, under a temporary root that `perf.manifest.Manifest` reads."""
import json
import os

import pytest

from perf_testdata import ROOT, TINY, copy_data


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    to = str(tmp_path_factory.mktemp("perf_tiny"))
    copy_data(ROOT, to)
    for rel, changes in TINY.items():
        path = os.path.join(to, "perf", rel)
        with open(path) as f:
            data = json.load(f)
        data.update(changes)
        with open(path, "w") as f:
            json.dump(data, f)
    return to
