"""Fixtures of the benchmark's CPU tests."""

import pytest

from bench_toy import make_checkout


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("checkout"))
