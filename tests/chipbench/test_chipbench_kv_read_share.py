"""``model.kv_read_share``: the share of the decode cache's positions the
decode waves' attention read, from the engine's own counters."""
import chipbench_testpaths  # noqa: F401  (sys.path for chipbench)
from types import SimpleNamespace

import pytest

from chipbench import run


@pytest.mark.parametrize("counters,want", [
    ({"kv_rows_read": 1536, "kv_rows_held": 16384}, 9.375),
    ({"kv_rows_read": 0, "kv_rows_held": 16384}, 0.0),
    ({"kv_rows_read": 0, "kv_rows_held": 0}, None),   # no decode wave
    ({"prefills": 4}, None),                 # a program without the counters
])
def test_kv_read_share_reads_the_engine_counters(counters, want):
    got = run.reader("model.kv_read_share")(
        SimpleNamespace(counters=counters))
    assert got == (pytest.approx(want) if want is not None else None)
