"""Generated TPC-H columns kept as files (connectors/tpch/columns.py), and
the two statements of the SF10 served cell through the served path at SF0.01
against the benchmark's plain references."""

import importlib.util
import json
import os
import sys
import threading

import numpy as np
import pytest

from trino_tpu.connectors import tpch as tpch_connector
from trino_tpu.connectors.tpch import columns as column_files
from trino_tpu.connectors.tpch.generator import TPCH_SCHEMAS, generate_table
from trino_tpu.data.page import CodedStrings

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
SCALE = 0.01


@pytest.fixture()
def empty_dir(tmp_path, monkeypatch):
    monkeypatch.setenv(column_files.ENV, str(tmp_path))
    return tmp_path


def _counts():
    return {k: column_files.COLUMNS.value(k) for k in ("file", "generated")}


def _counts_since(before):
    return {k: v - before[k] for k, v in _counts().items()}


@pytest.mark.parametrize("table", list(TPCH_SCHEMAS))
def test_column_files_give_what_generate_table_gives(empty_dir, table):
    want = generate_table(table, SCALE)
    for _process in range(2):  # the one that writes the files, one that finds them
        got = tpch_connector._Table(table, SCALE)
        assert list(got) == list(want)
        for c in want:
            assert got[c].dtype == want[c].dtype, c
            assert np.array_equal(got[c], want[c]), c
            assert got[c] is got[c]  # a reference keys what it derives by id()
    # a string column is stored, and handed to a scan, as Dictionary.encode's form
    raw, _source = got.raw(list(want))
    for c, t in TPCH_SCHEMAS[table]:
        assert isinstance(raw[c], CodedStrings) == t.is_string, c
        if t.is_string:
            values, codes = np.unique(want[c], return_inverse=True)
            assert np.array_equal(raw[c].dictionary, values), c
            assert np.array_equal(raw[c].codes, codes) and raw[c].codes.dtype == np.int32, c
    assert not [f for f in os.listdir(column_files._folder(table, SCALE))
                if f.endswith(".tmp")]


def test_a_second_process_generates_nothing_and_reads_only_what_it_asks_for(
        empty_dir, monkeypatch):
    asked = ["l_extendedprice", "l_discount", "l_quantity", "l_shipdate"]
    before = _counts()
    _cols, source = tpch_connector._Table("lineitem", SCALE).raw(asked)
    assert source == "generated"
    assert _counts_since(before) == {"file": 0, "generated": 4}

    opened = []
    open_ = column_files._open
    monkeypatch.setattr(column_files, "_open",
                        lambda folder, name, s: opened.append(name) or open_(folder, name, s))
    monkeypatch.setattr(column_files.generator, "generate_columns",
                        lambda *a, **k: pytest.fail("a column was generated again"))
    before = _counts()
    second = tpch_connector._Table("lineitem", SCALE)
    cols, source = second.raw(asked)
    assert source == "file" and sorted(opened) == sorted(asked)
    assert _counts_since(before) == {"file": 4, "generated": 0}
    assert all(isinstance(cols[c], np.memmap) and not cols[c].flags.writeable for c in asked)
    # what the first pass made beside the four is there too, and costs no pass
    more, source = second.raw(["l_returnflag", "l_tax"])
    assert source == "file" and isinstance(more["l_returnflag"], CodedStrings)
    assert _counts_since(before) == {"file": 6, "generated": 0}


def test_the_connector_says_where_a_split_came_from(empty_dir, monkeypatch):
    from trino_tpu.connectors.spi import Split

    monkeypatch.setattr(tpch_connector, "_TABLES", {})
    conn = tpch_connector.TpchConnector(SCALE)
    first, source = conn.read_split_from(Split("tpch", "orders", 1, 2), ["o_orderstatus", "o_totalprice"])
    _again, source_again = conn.read_split_from(Split("tpch", "orders", 1, 2), ["o_orderstatus", "o_totalprice"])
    assert (source, source_again) == ("generated", "file")
    want = generate_table("orders", SCALE)
    n = len(want["o_totalprice"])
    assert np.array_equal(first["o_totalprice"], want["o_totalprice"][n // 2:])
    assert np.array_equal(first["o_orderstatus"].decode(), want["o_orderstatus"][n // 2:])
    stats = conn.table_stats("orders")
    assert stats.row_count == n and stats.columns["o_orderstatus"].ndv == 3.0
    assert stats.columns["o_totalprice"].min == float(want["o_totalprice"].min())


def test_two_writers_at_once_leave_whole_files(empty_dir):
    start = threading.Barrier(2)
    got, errors = [None, None], []

    def writer(i):
        try:
            start.wait()
            got[i] = column_files.load("partsupp", SCALE, [c for c, _t in TPCH_SCHEMAS["partsupp"]])
        except Exception as e:  # pragma: no cover - the assertion below reports it
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errors, errors
    want = generate_table("partsupp", SCALE)
    folder = column_files._folder("partsupp", SCALE)
    assert not [f for f in os.listdir(folder) if f.endswith(".tmp")]
    found, source = column_files.load("partsupp", SCALE, list(want))
    assert source == "file"
    for cols in (got[0][0], got[1][0], found):
        for c in want:
            have = cols[c].decode() if isinstance(cols[c], CodedStrings) else cols[c]
            assert np.array_equal(have, want[c]), c


# ---------------------------------------------------------- the served path


def _bench_module(*parts):
    for d in (BENCH, os.path.join(BENCH, "reference")):
        if d not in sys.path:
            sys.path.insert(0, d)
    path = os.path.join(BENCH, *parts)
    spec = importlib.util.spec_from_file_location("bench_" + parts[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served():
    """Coordinator + 1 worker over TPC-H SF0.01 under tpch_sf10_served's
    session.  On the CPU the two statements take the operator-at-a-time path
    (f64 sums); the fused scan kernel they take on the chip is held to its
    own arithmetic, interpreted, in test_pallas_relops.py."""
    from trino_tpu.client.client import StatementClient
    from trino_tpu.testing.runner import DistributedQueryRunner

    with open(os.path.join(BENCH, "configs", "tpch_sf10_served.json")) as f:
        config = json.load(f)
    runner = DistributedQueryRunner(num_workers=int(config["layout"]["workers"]))
    runner.register_catalog("tpch", tpch_connector.TpchConnector(SCALE))
    runner.start()
    for prop, value in config["session"].items():
        runner.coordinator.session.set(prop, str(value))
    try:
        yield config, StatementClient(runner.client_url)
    finally:
        runner.stop()


@pytest.mark.parametrize("name", ["q06", "q01"])
def test_served_text_equals_the_plain_reference_under_the_configuration_limits(
        served, name):
    config, client = served
    assert config["limits"] == {"exact_mismatches": 0, "decimal_rel_err": 1e-08,
                                "double_rel_err": 3e-08}
    with open(os.path.join(BENCH, "templates", name + ".json")) as f:
        template = json.load(f)
    binding = _bench_module("traffic.py").validation(template)
    _cols, rows = client.execute("\n".join(template["text"]), timeout=300.0)
    data = {"lineitem": tpch_connector.tpch_data("lineitem", SCALE)}
    want = _bench_module("reference", template["reference"] + ".py").reference(
        data, *binding.args)
    got = _bench_module("compare.py").compare(rows, want, template["ordered"])
    assert got["rows"] == len(want) > 0
    for k, limit in config["limits"].items():
        assert got[k] <= limit, (k, got[k], rows, want)
