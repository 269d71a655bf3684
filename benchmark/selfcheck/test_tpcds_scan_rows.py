"""``tpcds_sf10_1chip``'s file against the benchmark's own generator at
SF 10: rows, the dimensions' pins, and ``scan_rows`` (nothing is pushed
into a tpcds scan, so every scan delivers its table). That the engine's
scans deliver those lanes, in that order, is held in tier-1
(``tests/test_tpcds_reference.py``), at ``tiny``."""

import json
import os

from reference import tpcds_answers, tpcds_rows

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_rows_pins_and_scan_rows_are_the_reference_s():
    with open(os.path.join(HERE, "configs", "tpcds_sf10_1chip.json")) as f:
        config = json.load(f)
    assert set(config["scan_rows"]) == {"q7", "q96"}
    for table, spec in config["tables"].items():
        assert spec["rows"] == tpcds_rows.table_rows(table, 10.0)
        assert spec["pin_sql"] == tpcds_answers.PIN_SQL[table]
    for cls, tables in config["scan_rows"].items():
        assert list(tables) == list(config["lanes_read"][cls])
        for table, spec in tables.items():
            assert spec == {"rows": config["tables"][table]["rows"],
                            "pushed": ""}
    # store_sales' pin takes 10 s of numpy at SF 10: by hand,
    # python -c "from reference import tpcds_answers as t; print(t.pins(10.0))"
    for table, key in tpcds_answers._PIN_KEY.items():
        k = getattr(tpcds_rows, table)(10.0)[key]
        assert config["tables"][table]["pin_sum"] == int(k.sum())
