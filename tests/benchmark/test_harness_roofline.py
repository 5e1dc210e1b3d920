"""The least-bytes functions against shapes counted by hand, and the peaks table."""

from __future__ import annotations

import pytest

from benchmark import roofline


@pytest.mark.parametrize(
    "P, C, E, schemes, expected",
    [
        # inputs: A, B (8 B) and valid (1 B) per period-cell, horizon per cell;
        # per scheme: records (1 + 8 + 1 B) per period-cell, finals 33 B per cell
        (2, 3, 0, ["none"], 2 * 3 * 17 + 3 * 8 + (2 * 3 * 10 + 3 * 33)),
        (2, 3, 5, ["none", "opt"], 2 * 3 * 17 + 3 * 8 + 2 * (2 * 3 * 10 + 3 * 33)),
        # EDGE adds the edges (8 B each), base and count per cell (16 B) and
        # the per-period cursor (8 B per period-cell)
        (2, 3, 5, ["edge"], 2 * 3 * 17 + 3 * 8 + 5 * 8 + 3 * 16 + 2 * 3 * 8
         + (2 * 3 * 10 + 3 * 33)),
        # the full catalog of the catalog cells: 219 periods, 10,496 cells
        (219, 10496, 98953, ["none", "opt", "hour", "edge"], 151839560),
    ],
)
def test_sweep_bytes(P, C, E, schemes, expected):
    assert roofline.sweep_bytes(P, C, E, schemes) == expected


def test_peaks_by_device_kind():
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
