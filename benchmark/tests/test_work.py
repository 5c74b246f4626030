"""work.py: the least-work count depends on shapes alone; the peaks come
from the keyed table, and a device that is not in it is an error."""
import json

import pytest

from harness import work


def test_count_depends_only_on_shapes():
    a = work.scan_bytes(5000, 950, False)
    assert a == work.scan_bytes(5000, 950, False)
    # padded to whole lanes: 5,000 and 5,120 nodes read the same planes
    assert a == work.scan_bytes(5120, 950, False)
    assert a == 950 * (5120 * work.PLAIN_BYTES_PER_NODE_STEP
                       + work.WRITTEN_BYTES_PER_STEP)
    # linear in the steps, larger with the stanza planes, larger with nodes
    assert work.scan_bytes(5000, 1900, False) == 2 * a
    assert work.scan_bytes(5000, 950, True) > a
    assert work.scan_bytes(10000, 950, False) > a
    assert work.scan_ops(5000, 950, True) > work.scan_ops(5000, 950, False)


def test_bound_is_bytes_on_the_v5e():
    peaks = work.load_peaks("TPU v5 lite")
    secs, bound = work.least_seconds([(5000, 950, True)] * 64, peaks)
    assert bound == "bytes"
    assert secs == pytest.approx(
        64 * work.scan_bytes(5000, 950, True) / 819e9)


def test_unknown_device_kind_is_an_error(tmp_path):
    with pytest.raises(KeyError, match="not in"):
        work.load_peaks("cpu")
    table = tmp_path / "peaks.json"
    table.write_text(json.dumps({"TPU v9": {"hbm_bytes_per_s": 1.0,
                                             "bf16_flops_per_s": 1.0}}))
    with pytest.raises(KeyError):
        work.load_peaks("TPU v5 lite", str(table))
    assert work.load_peaks("TPU v9", str(table))["hbm_bytes_per_s"] == 1.0
