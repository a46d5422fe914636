"""Fixtures of the chip benchmark's CPU tests."""

import pytest

from chipbench_util import TinyCheckout


@pytest.fixture
def checkout(tmp_path, monkeypatch):
    # the persistent compile cache is for the chip; keep CPU compiles out
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    return TinyCheckout(tmp_path)
