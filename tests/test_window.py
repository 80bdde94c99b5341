"""Window function differential tests vs sqlite (which has full window
support), mirroring the reference's AbstractTestWindowQueries suite
(testing/trino-testing/.../AbstractTestWindowQueries.java)."""

import pytest

from tests.oracle import assert_rows_equal

WINDOW_QUERIES = {
    "row_number": """
        select o_custkey, o_orderkey, row_number() over
          (partition by o_custkey order by o_orderdate, o_orderkey) as rn
        from orders where o_custkey < 100
    """,
    "rank_dense": """
        select o_custkey, o_orderpriority,
          rank() over (partition by o_custkey order by o_orderpriority) as r,
          dense_rank() over (partition by o_custkey order by o_orderpriority) as dr
        from orders where o_custkey < 50
    """,
    "running_sum": """
        select o_custkey, o_orderkey,
          sum(o_totalprice) over (partition by o_custkey order by o_orderdate, o_orderkey
                                  rows unbounded preceding) as running
        from orders where o_custkey < 60
    """,
    "range_peers": """
        select o_custkey, o_orderdate,
          count(*) over (partition by o_custkey order by o_orderdate) as cnt_range
        from orders where o_custkey < 60
    """,
    "whole_partition": """
        select o_custkey, o_orderkey,
          sum(o_totalprice) over (partition by o_custkey) as total,
          count(*) over (partition by o_custkey) as n,
          max(o_totalprice) over (partition by o_custkey) as mx
        from orders where o_custkey < 80
    """,
    "global_window": """
        select o_orderkey, sum(o_totalprice) over () as grand_total
        from orders where o_orderkey < 200
    """,
    # RANGE offsets order by a NUMERIC column: sqlite holds our DATE columns
    # as TEXT, so its value-distance arithmetic over dates cannot oracle
    "range_offset_frame": """
        select o_custkey, o_orderkey,
          sum(o_totalprice) over (partition by o_custkey order by o_totalprice
                                  range between 20000 preceding and 20000 following) as s20k,
          count(*) over (partition by o_custkey order by o_totalprice
                         range between 50000 preceding and current row) as c50k
        from orders where o_custkey < 60
    """,
    "range_offset_desc": """
        select o_custkey, o_orderkey,
          count(*) over (partition by o_custkey order by o_totalprice desc
                         range between 30000 preceding and 30000 following) as c30k
        from orders where o_custkey < 40
    """,
    "lag_lead": """
        select o_custkey, o_orderkey,
          lag(o_orderkey) over (partition by o_custkey order by o_orderdate, o_orderkey) as prev_k,
          lead(o_orderkey) over (partition by o_custkey order by o_orderdate, o_orderkey) as next_k
        from orders where o_custkey < 40
    """,
    "first_last": """
        select o_custkey, o_orderkey,
          first_value(o_orderkey) over (partition by o_custkey order by o_orderdate, o_orderkey) as fv
        from orders where o_custkey < 40
    """,
    "window_over_agg": """
        select o_custkey, sum(o_totalprice) as s,
          rank() over (order by sum(o_totalprice) desc) as r
        from orders where o_custkey < 30 group by o_custkey
    """,
    "avg_min_running": """
        select o_custkey, o_orderkey,
          avg(o_totalprice) over (partition by o_custkey order by o_orderkey
                                  rows unbounded preceding) as ra,
          min(o_totalprice) over (partition by o_custkey order by o_orderkey
                                  rows unbounded preceding) as rm
        from orders where o_custkey < 40
    """,
    "offset_frame_sum": """
        select o_custkey, o_orderkey,
          sum(o_totalprice) over (partition by o_custkey order by o_orderkey
                                  rows between 2 preceding and 1 following) as s,
          count(*) over (partition by o_custkey order by o_orderkey
                         rows between 1 preceding and 1 following) as c
        from orders where o_custkey < 40
    """,
    "offset_frame_minmax": """
        select o_custkey, o_orderkey,
          min(o_totalprice) over (partition by o_custkey order by o_orderkey
                                  rows between 2 preceding and current row) as mn,
          max(o_totalprice) over (partition by o_custkey order by o_orderkey
                                  rows between current row and unbounded following) as mx
        from orders where o_custkey < 40
    """,
    "ntile_ranks": """
        select o_custkey, o_orderkey,
          ntile(3) over (partition by o_custkey order by o_orderkey) as nt,
          percent_rank() over (partition by o_custkey order by o_orderkey) as pr,
          cume_dist() over (partition by o_custkey order by o_orderkey) as cd
        from orders where o_custkey < 40
    """,
    "nth_value": """
        select o_custkey, o_orderkey,
          nth_value(o_orderkey, 2) over (partition by o_custkey
                                         order by o_orderkey
                                         rows between unbounded preceding
                                         and unbounded following) as nv
        from orders where o_custkey < 40
    """,
}


@pytest.fixture(scope="module")
def engine(tpch_tiny):
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runtime.engine import Engine

    eng = Engine()
    eng.register_catalog("tpch", TpchConnector(0.01))
    return eng


# SUM over a SUM of DECIMAL(12,2) is DECIMAL(38,2): two int64 limbs, which
# the window operator does not carry.  Strict: the gate says so the day it does.
_DECIMAL128 = pytest.mark.xfail(
    strict=True, raises=NotImplementedError,
    reason="ROADMAP A6: decimal128 columns do not go through a window yet")


@pytest.mark.parametrize("name", [
    pytest.param(n, marks=_DECIMAL128) if n == "window_over_agg" else n
    for n in sorted(WINDOW_QUERIES)])
def test_window(name, engine, oracle):
    sql = WINDOW_QUERIES[name]
    got = engine.query(sql)
    expected = oracle.query(sql)
    assert_rows_equal(got, expected, ordered=False)


@pytest.fixture(scope="module")
def dist_engine(tpch_tiny):
    import jax

    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.runtime.engine import Engine

    eng = Engine(distributed=True, devices=jax.devices()[:4])
    eng.register_catalog("tpch", TpchConnector(0.01))
    return eng


# whole_partition: repartition by the partition keys, a window per shard;
# global_window: an unpartitioned window gathers to one shard
@pytest.mark.parametrize("name", ["whole_partition", "global_window"])
def test_window_distributed(name, dist_engine, oracle):
    sql = WINDOW_QUERIES[name]
    assert_rows_equal(dist_engine.query(sql), oracle.query(sql), ordered=False)
