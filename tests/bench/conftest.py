import os
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from bench_helpers import write_smoke_root  # noqa: E402


@pytest.fixture(scope="session")
def smoke_root(tmp_path_factory):
    return write_smoke_root(str(tmp_path_factory.mktemp("bench_root")))
