import pytest

from benchmark import harness


@pytest.fixture(autouse=True)
def _bench_cache(tmp_path, monkeypatch):
    """Every test makes its logs and references anew, in a cache of its
    own."""
    monkeypatch.setattr(harness, "CACHE_DIR", str(tmp_path / "bench_cache"))
