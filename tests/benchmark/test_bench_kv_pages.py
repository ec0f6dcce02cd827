"""The reader of the paged-read counters (``metrics/kv_pages_read_share.
offline.py``, CPU, no chip): the window's pages read over its table
pages, and nothing from a program without the counters."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import run  # noqa: E402

READ = "repro_paged_kv_pages_read_total"
TABLE = "repro_paged_kv_table_pages_total"


def _run(c0, c1):
    return {"status0": {"metrics": {"counters": c0}},
            "status1": {"metrics": {"counters": c1}}}


def test_share_is_the_window_delta_of_the_counters():
    """Before the window 100 of 1,000 table pages were read; in it 2,100
    of 10,000: 21 %. A program without the counters (a slotted layout,
    or a commit without them) reads nothing and raises nothing."""
    share = run._load(CHIP / "metrics" / "kv_pages_read_share.offline.py",
                      "m_kv_pages")
    assert share.read(_run({READ: 100, TABLE: 1000},
                           {READ: 2200, TABLE: 11000})) == pytest.approx(21.0)
    assert share.read(_run({}, {})) is None
    assert share.read(_run({READ: 5, TABLE: 50}, {READ: 5, TABLE: 50})) is None
