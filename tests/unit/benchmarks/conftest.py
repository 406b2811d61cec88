"""``bench``: BENCHMARK.json as accepted and with one cell appended
(appended.py). A test that names the fixture runs under both."""
import pytest

from tests.unit.benchmarks import appended


@pytest.fixture(params=list(appended.BENCHES))
def bench(request):
    return appended.BENCHES[request.param]
