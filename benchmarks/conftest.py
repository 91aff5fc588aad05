"""Every test under ``benchmarks/`` starts with the program's span ring
empty: the program's coarse spans are always recorded
(``tmr_tpu/obs/tracing.py``) and the ring is the process's, so without this
a rehearsal run of one test would be read by the reducers of the next."""

import pytest


@pytest.fixture(autouse=True)
def _empty_span_ring():
    # imported here: ``tests/conftest.py`` puts the repo on the path
    from tmr_tpu.obs import tracing

    tracing.clear()
