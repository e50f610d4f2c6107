"""Useful-work operation and byte counts, the roofline share, and the
table of peaks."""
import json

import pytest

from bench import work

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_krls_chunk_counts_by_hand():
    # D=4, d=2: per tick 5*16 + 2*2*4 + 8*4 = 128 flops.
    flops, nbytes = work.krls_chunk(4, 2, ticks=3, tenant_flushes=2, flushes=1)
    assert flops == 3 * 128
    # P and theta of 2 tenants read and written: 2 * 2 * (16 + 4) floats;
    # W, b, scale once: 2*4 + 2*4; 3 ticks of x (2), y, mask, pred, err.
    assert nbytes == 4 * (80 + 16 + 3 * 6)


def test_krls_chunk_counts_only_unmasked_ticks():
    """A (4 tenants, T=16) flush where tenant 0 has 16 arrivals, tenant 1
    has 1 and two are idle counts 17 ticks and 2 tenants, not 64 and 4."""
    d, dfeat = 5, 1000
    flops, nbytes = work.krls_chunk(dfeat, d, ticks=17, tenant_flushes=2, flushes=1)
    assert flops == 17 * 5_018_000
    assert nbytes == 4 * (2 * 2 * 1_001_000 + 7000 + 17 * 9)
    full_f, full_b = work.krls_chunk(dfeat, d, ticks=64, tenant_flushes=4, flushes=1)
    assert full_f > flops and full_b > nbytes


def test_roofline_share_takes_the_binding_bound():
    # One second of work at peak FLOP/s reads 100%; at half speed 50%.
    assert work.roofline_share(197e12, 1.0, 1.0, PEAK) == pytest.approx(100.0)
    assert work.roofline_share(197e12, 1.0, 2.0, PEAK) == pytest.approx(50.0)
    # Bytes bind when they need longer: 8.19 GB is 10 ms at 819 GB/s.
    assert work.roofline_share(1.0, 8.19e9, 0.1, PEAK) == pytest.approx(10.0)


def test_peak_table_knows_the_v5e_and_refuses_other_kinds(tmp_path):
    v5e = work.peak("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        work.peak("TPU v4")
    table = tmp_path / "peaks.json"
    table.write_text(json.dumps({"source": "x", "kinds": {}}))
    with pytest.raises(KeyError):
        work.peak("TPU v5 lite", table)
