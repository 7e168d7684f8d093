"""The reader of ``decode_pages_walked_share`` on counter windows."""
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import spec  # noqa: E402


def _w(walked, table):
    c0 = {"decode_steps": 5, "occupancy_sum": 0}
    c1 = {"decode_steps": 15, "occupancy_sum": 0}
    if walked is not None:
        c0["engine.decode_pages_walked"], c1["engine.decode_pages_walked"] \
            = walked
        c0["engine.decode_table_pages"], c1["engine.decode_table_pages"] \
            = table
    return SimpleNamespace(counters0=c0, counters1=c1, trace=None,
                           decode_ctx=[], admissions=[], dims=None,
                           peaks=None)


@pytest.mark.parametrize("walked,table,want", [
    ((100, 196), (640, 1280), 15.0),     # 96 of 640 entries
    ((0, 64), (0, 64), 100.0),           # every entry walked
    ((30, 30), (64, 64), None),          # no decode dispatch in the window
    (None, None, None),                  # a program without the counters
])
def test_decode_pages_walked_share_reader(walked, table, want):
    got = spec.metric_reader("decode_pages_walked_share").read(
        _w(walked, table))
    assert got == (pytest.approx(want) if want is not None else None)
