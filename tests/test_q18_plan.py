"""The planner's part of ISSUE 36: TPC-H q18's ``o_orderkey IN
(subquery)`` is sunk onto ``orders``, below both joins (it was asked of
all of lineitem joined to orders and customer before); an ``IN`` whose
key is not one relation's stays where it was; a semi join's estimate
follows its filtering side; and the six query classes of the accepted
benchmark cells plan byte for byte as they did on the parent commit
(``EXPLAIN`` at ``tiny`` and at ``sf10``, taken from the parent's tree
on 2026-10-04)."""

import pytest

from trino_tpu.benchmarks.tpcds_queries import TPCDS_QUERIES
from trino_tpu.benchmarks.tpch_queries import TPCH_QUERIES
from trino_tpu.runner import LocalQueryRunner
from trino_tpu.session import Session


def explain(catalog: str, schema: str, text: str) -> str:
    r = LocalQueryRunner(session=Session(catalog=catalog, schema=schema))
    return "\n".join(row[0] for row in r.execute("EXPLAIN " + text).rows)


def depth_of(plan: str, needle: str, nth: int = 0) -> int:
    line = [ln for ln in plan.splitlines() if needle in ln][nth]
    return len(line) - len(line.lstrip())


def test_q18_semi_join_lands_on_orders_below_both_joins():
    plan = explain("tpch", "tiny", TPCH_QUERIES[18])
    lines = plan.splitlines()
    semi = next(i for i, ln in enumerate(lines) if "- SemiJoin" in ln)
    # its mark's filter right above it, orders its source, the grouped
    # lineitem its filtering side
    assert "Filter[insubquery" in lines[semi - 1]
    assert "TableScan[tpch.tiny.orders]" in lines[semi + 1]
    assert depth_of(plan, "tpch.tiny.orders") == depth_of(plan, "SemiJoin") + 3
    assert "Aggregation[SINGLE by(l_orderkey" in plan
    # both joins are ABOVE it, and the marked orders are the BUILD side
    # (the right one) of the join with lineitem
    joins = [i for i, ln in enumerate(lines) if "- Join[" in ln]
    assert len(joins) == 2 and all(i < semi for i in joins)
    assert "l_orderkey" in lines[joins[1]] and "o_orderkey" in lines[joins[1]]
    assert "TableScan[tpch.tiny.lineitem]" in lines[joins[1] + 1]
    assert depth_of(plan, "Filter[insubquery") == \
        depth_of(plan, "tpch.tiny.lineitem")
    assert all(depth_of(plan, "- Join[", n) < depth_of(plan, "SemiJoin")
               for n in range(2))


def test_q18_answers_as_before_the_sinking():
    from trino_tpu.benchmarks.q18_oracle import q18_oracle
    import datetime
    r = LocalQueryRunner(session=Session(catalog="tpch", schema="tiny"))
    got = r.execute(TPCH_QUERIES[18].replace("> 300", "> 200")).rows
    want = q18_oracle(0.01, qty_bar=200.0)
    epoch = datetime.date(1970, 1, 1)
    assert [[g[0], g[1], g[2], (g[3] - epoch).days, g[4], g[5]]
            for g in got] == want and len(want) == 100


@pytest.mark.parametrize("text,where", [
    # the key is an expression over BOTH relations: a projection sits
    # between the semi join and the join, nothing to sink onto
    ("select count(*) from orders, customer where o_custkey = c_custkey "
     "and o_orderkey + c_nationkey in (select l_orderkey from lineitem)",
     "above"),
    # an outer join: a null-extended row's mark is not the key's
    ("select count(*) from customer left join orders on o_custkey = "
     "c_custkey where o_orderkey in (select l_orderkey from lineitem)",
     "above"),
    # one relation's key under an inner join: sunk, to either side
    ("select count(*) from orders, customer where o_custkey = c_custkey "
     "and c_custkey in (select l_suppkey from lineitem)", "below"),
    ("select count(*) from orders, customer where o_custkey = c_custkey "
     "and o_orderkey not in (select l_orderkey from lineitem "
     "where l_quantity > 49)", "below"),
])
def test_an_in_goes_down_only_onto_the_one_relation_of_its_key(text, where):
    plan = explain("tpch", "tiny", text)
    semi = depth_of(plan, "- SemiJoin")
    join = depth_of(plan, "- Join[")
    assert (semi < join) == (where == "above"), plan


def test_in_keeps_its_three_values_under_the_join():
    """NOT IN over a filtering side with a NULL is never TRUE, with or
    without the sinking: the mark is the row's, wherever it is made."""
    r = LocalQueryRunner(session=Session(catalog="tpch", schema="tiny"))
    sub = ("(select case when l_orderkey = 1 then null else l_orderkey end "
           "from lineitem where l_orderkey < 40)")
    n = r.execute("select count(*) from orders, customer where o_custkey = "
                  f"c_custkey and o_orderkey not in {sub}").rows[0][0]
    assert n == 0
    n = r.execute("select count(*) from orders, customer where o_custkey = "
                  f"c_custkey and o_orderkey in {sub}").rows[0][0]
    alone = r.execute("select count(*) from orders where o_orderkey in "
                      f"{sub}").rows[0][0]
    assert n == alone > 0


def test_a_semi_join_s_estimate_follows_its_filtering_side():
    from trino_tpu.planner.stats import estimate_rows
    r = LocalQueryRunner(session=Session(catalog="tpch", schema="sf10"))

    def semi_rows(sub: str) -> float:
        plan = r.plan_sql(f"select o_orderkey from orders where o_orderkey in "
                          f"({sub})")
        node = plan
        while type(node).__name__ != "SemiJoinNode":
            node = node.sources[0]
        return estimate_rows(node, r.catalogs)
    few = semi_rows("select n_nationkey from nation")
    many = semi_rows("select l_orderkey from lineitem")
    assert few <= 25 < 1_000_000 < many <= 7_500_000


GOLDEN = {'tpcds.sf10.q3': '- Output[d_year, i_brand_id, i_brand, sum_agg]\n'
                  "   - TopN[100 by ['d_year$52', 'sum_agg$55', "
                  "'i_brand_id$53']]\n"
                  '      - Project[d_year$52 := d_year$2, i_brand_id$53 := '
                  'i_brand_id$40, i_brand$54 := i_brand$41, sum_agg$55 := '
                  'sum$51]\n'
                  '         - Aggregation[SINGLE by(d_year$2, i_brand_id$40, '
                  'i_brand$41) sum$51 := sum(ss_ext_sales_price$25)]\n'
                  '            - Join[inner ss_item_sk$13 = i_item_sk$33]\n'
                  '               - Join[inner ss_sold_date_sk$11 = '
                  'd_date_sk$0]\n'
                  '                  - TableScan[tpcds.sf10.store_sales]\n'
                  '                  - Filter[=(d_moy$3, 11)]\n'
                  '                     - TableScan[tpcds.sf10.date_dim]\n'
                  '               - Filter[=(i_manufact_id$42, 128)]\n'
                  '                  - TableScan[tpcds.sf10.item]',
 'tpcds.sf10.q7': '- Output[i_item_id, agg1, agg2, agg3, agg4]\n'
                  "   - TopN[100 by ['i_item_id$72']]\n"
                  '      - Project[i_item_id$72 := i_item_id$43, agg1$73 := '
                  'avg$68, agg2$74 := avg$69, agg3$75 := avg$70, agg4$76 := '
                  'avg$71]\n'
                  '         - Aggregation[SINGLE by(i_item_id$43) avg$68 := '
                  'avg(ss_quantity$10), avg$69 := avg(ss_list_price$12), '
                  'avg$70 := avg(ss_coupon_amt$19), avg$71 := '
                  'avg(ss_sales_price$13)]\n'
                  '            - Join[inner ss_item_sk$2 = i_item_sk$42]\n'
                  '               - Join[inner ss_sold_date_sk$0 = '
                  'd_date_sk$31]\n'
                  '                  - Join[inner ss_promo_sk$8 = '
                  'p_promo_sk$60]\n'
                  '                     - Join[inner ss_cdemo_sk$4 = '
                  'cd_demo_sk$22]\n'
                  '                        - '
                  'TableScan[tpcds.sf10.store_sales]\n'
                  '                        - Filter[and(and(=(cd_gender$23, '
                  "'M'), =(cd_marital_status$24, 'S')), "
                  "=(cast(cd_education_status$25 as varchar), 'College'))]\n"
                  '                           - '
                  'TableScan[tpcds.sf10.customer_demographics]\n'
                  '                     - Filter[or(=(p_channel_email$63, '
                  "'N'), =(p_channel_event$65, 'N'))]\n"
                  '                        - '
                  'TableScan[tpcds.sf10.promotion]\n'
                  '                  - Filter[=(d_year$33, 2000)]\n'
                  '                     - TableScan[tpcds.sf10.date_dim]\n'
                  '               - TableScan[tpcds.sf10.item]',
 'tpcds.sf10.q96': '- Output[cnt]\n'
                   '   - Project[cnt$50 := cnt$50]\n'
                   "      - TopN[100 by ['sortkey$51']]\n"
                   '         - Project[cnt$50 := count$49, sortkey$51 := '
                   'count$49]\n'
                   '            - Aggregation[SINGLE by() count$49 := '
                   'count_star(*)]\n'
                   '               - Join[inner ss_sold_time_sk$1 = '
                   't_time_sk$27]\n'
                   '                  - Join[inner ss_hdemo_sk$5 = '
                   'hd_demo_sk$22]\n'
                   '                     - Join[inner ss_store_sk$7 = '
                   's_store_sk$34]\n'
                   '                        - '
                   'TableScan[tpcds.sf10.store_sales]\n'
                   '                        - Filter[=(cast(s_store_name$36 '
                   "as varchar), 'ese')]\n"
                   '                           - '
                   'TableScan[tpcds.sf10.store]\n'
                   '                     - Filter[=(hd_dep_count$25, 7)]\n'
                   '                        - '
                   'TableScan[tpcds.sf10.household_demographics]\n'
                   '                  - Filter[and(=(t_hour$29, 20), '
                   '>=(t_minute$30, 30))]\n'
                   '                     - TableScan[tpcds.sf10.time_dim]',
 'tpcds.tiny.q3': '- Output[d_year, i_brand_id, i_brand, sum_agg]\n'
                  "   - TopN[100 by ['d_year$52', 'sum_agg$55', "
                  "'i_brand_id$53']]\n"
                  '      - Project[d_year$52 := d_year$2, i_brand_id$53 := '
                  'i_brand_id$40, i_brand$54 := i_brand$41, sum_agg$55 := '
                  'sum$51]\n'
                  '         - Aggregation[SINGLE by(d_year$2, i_brand_id$40, '
                  'i_brand$41) sum$51 := sum(ss_ext_sales_price$25)]\n'
                  '            - Join[inner ss_sold_date_sk$11 = '
                  'd_date_sk$0]\n'
                  '               - Join[inner ss_item_sk$13 = '
                  'i_item_sk$33]\n'
                  '                  - TableScan[tpcds.tiny.store_sales]\n'
                  '                  - Filter[=(i_manufact_id$42, 128)]\n'
                  '                     - TableScan[tpcds.tiny.item]\n'
                  '               - Filter[=(d_moy$3, 11)]\n'
                  '                  - TableScan[tpcds.tiny.date_dim]',
 'tpcds.tiny.q7': '- Output[i_item_id, agg1, agg2, agg3, agg4]\n'
                  "   - TopN[100 by ['i_item_id$72']]\n"
                  '      - Project[i_item_id$72 := i_item_id$43, agg1$73 := '
                  'avg$68, agg2$74 := avg$69, agg3$75 := avg$70, agg4$76 := '
                  'avg$71]\n'
                  '         - Aggregation[SINGLE by(i_item_id$43) avg$68 := '
                  'avg(ss_quantity$10), avg$69 := avg(ss_list_price$12), '
                  'avg$70 := avg(ss_coupon_amt$19), avg$71 := '
                  'avg(ss_sales_price$13)]\n'
                  '            - Join[inner ss_sold_date_sk$0 = '
                  'd_date_sk$31]\n'
                  '               - Join[inner ss_item_sk$2 = i_item_sk$42]\n'
                  '                  - Join[inner ss_promo_sk$8 = '
                  'p_promo_sk$60]\n'
                  '                     - Join[inner ss_cdemo_sk$4 = '
                  'cd_demo_sk$22]\n'
                  '                        - '
                  'TableScan[tpcds.tiny.store_sales]\n'
                  '                        - Filter[and(and(=(cd_gender$23, '
                  "'M'), =(cd_marital_status$24, 'S')), "
                  "=(cast(cd_education_status$25 as varchar), 'College'))]\n"
                  '                           - '
                  'TableScan[tpcds.tiny.customer_demographics]\n'
                  '                     - Filter[or(=(p_channel_email$63, '
                  "'N'), =(p_channel_event$65, 'N'))]\n"
                  '                        - '
                  'TableScan[tpcds.tiny.promotion]\n'
                  '                  - TableScan[tpcds.tiny.item]\n'
                  '               - Filter[=(d_year$33, 2000)]\n'
                  '                  - TableScan[tpcds.tiny.date_dim]',
 'tpcds.tiny.q96': '- Output[cnt]\n'
                   '   - Project[cnt$50 := cnt$50]\n'
                   "      - TopN[100 by ['sortkey$51']]\n"
                   '         - Project[cnt$50 := count$49, sortkey$51 := '
                   'count$49]\n'
                   '            - Aggregation[SINGLE by() count$49 := '
                   'count_star(*)]\n'
                   '               - Join[inner ss_sold_time_sk$1 = '
                   't_time_sk$27]\n'
                   '                  - Join[inner ss_hdemo_sk$5 = '
                   'hd_demo_sk$22]\n'
                   '                     - Join[inner ss_store_sk$7 = '
                   's_store_sk$34]\n'
                   '                        - '
                   'TableScan[tpcds.tiny.store_sales]\n'
                   '                        - Filter[=(cast(s_store_name$36 '
                   "as varchar), 'ese')]\n"
                   '                           - '
                   'TableScan[tpcds.tiny.store]\n'
                   '                     - Filter[=(hd_dep_count$25, 7)]\n'
                   '                        - '
                   'TableScan[tpcds.tiny.household_demographics]\n'
                   '                  - Filter[and(=(t_hour$29, 20), '
                   '>=(t_minute$30, 30))]\n'
                   '                     - TableScan[tpcds.tiny.time_dim]',
 'tpch.sf10.q1': '- Output[l_returnflag, l_linestatus, sum_qty, '
                 'sum_base_price, sum_disc_price, sum_charge, avg_qty, '
                 'avg_price, avg_disc, count_order]\n'
                 '   - Sort\n'
                 '      - Project[l_returnflag$26 := l_returnflag$8, '
                 'l_linestatus$27 := l_linestatus$9, sum_qty$28 := sum$16, '
                 'sum_base_price$29 := sum$17, sum_disc_price$30 := sum$19, '
                 'sum_charge$31 := sum$21, avg_qty$32 := avg$22, '
                 'avg_price$33 := avg$23, avg_disc$34 := avg$24, '
                 'count_order$35 := count$25]\n'
                 '         - Aggregation[SINGLE by(l_returnflag$8, '
                 'l_linestatus$9) sum$16 := sum(l_quantity$4), sum$17 := '
                 'sum(l_extendedprice$5), sum$19 := sum(sum_arg$18), sum$21 '
                 ':= sum(sum_arg$20), avg$22 := avg(l_quantity$4), avg$23 := '
                 'avg(l_extendedprice$5), avg$24 := avg(l_discount$6), '
                 'count$25 := count_star(*)]\n'
                 '            - Project[l_quantity$4 := l_quantity$4, '
                 'l_extendedprice$5 := l_extendedprice$5, l_discount$6 := '
                 'l_discount$6, l_returnflag$8 := l_returnflag$8, '
                 'l_linestatus$9 := l_linestatus$9, sum_arg$18 := '
                 '*(l_extendedprice$5, -(1.0, l_discount$6)), sum_arg$20 := '
                 '*(*(l_extendedprice$5, -(1.0, l_discount$6)), +(1.0, '
                 'l_tax$7))]\n'
                 '               - Filter[<=(l_shipdate$10, '
                 'date_sub_interval(10561, 7776000000))]\n'
                 '                  - TableScan[tpch.sf10.lineitem]',
 'tpch.sf10.q3': '- Output[l_orderkey, revenue, o_orderdate, '
                 'o_shippriority]\n'
                 "   - TopN[10 by ['revenue$36', 'o_orderdate$37']]\n"
                 '      - Project[l_orderkey$35 := l_orderkey$17, revenue$36 '
                 ':= sum$34, o_orderdate$37 := o_orderdate$12, '
                 'o_shippriority$38 := o_shippriority$15]\n'
                 '         - Aggregation[SINGLE by(l_orderkey$17, '
                 'o_orderdate$12, o_shippriority$15) sum$34 := '
                 'sum(sum_arg$33)]\n'
                 '            - Project[o_orderdate$12 := o_orderdate$12, '
                 'o_shippriority$15 := o_shippriority$15, l_orderkey$17 := '
                 'l_orderkey$17, sum_arg$33 := *(l_extendedprice$22, -(1.0, '
                 'l_discount$23))]\n'
                 '               - Join[inner o_custkey$9 = c_custkey$0]\n'
                 '                  - Join[inner l_orderkey$17 = '
                 'o_orderkey$8]\n'
                 '                     - TableScan[tpch.sf10.lineitem '
                 'constraint=(l_shipdate:1 ranges)]\n'
                 '                     - TableScan[tpch.sf10.orders '
                 'constraint=(o_orderdate:1 ranges)]\n'
                 '                  - Filter[=(cast(c_mktsegment$6 as '
                 "varchar), 'BUILDING')]\n"
                 '                     - TableScan[tpch.sf10.customer]',
 'tpch.sf10.q6': '- Output[revenue]\n'
                 '   - Project[revenue$18 := sum$17]\n'
                 '      - Aggregation[SINGLE by() sum$17 := '
                 'sum(sum_arg$16)]\n'
                 '         - Project[sum_arg$16 := *(l_extendedprice$5, '
                 'l_discount$6)]\n'
                 '            - Filter[and(and(<(l_shipdate$10, '
                 'date_add_interval(8766, 12)), >=(l_discount$6, '
                 "cast(decimal_-('0.06', '0.01') as double))), "
                 "<=(l_discount$6, cast(decimal_+('0.06', '0.01') as "
                 'double)))]\n'
                 '               - TableScan[tpch.sf10.lineitem '
                 'constraint=(l_quantity:1 ranges, l_shipdate:1 ranges)]',
 'tpch.tiny.q1': '- Output[l_returnflag, l_linestatus, sum_qty, '
                 'sum_base_price, sum_disc_price, sum_charge, avg_qty, '
                 'avg_price, avg_disc, count_order]\n'
                 '   - Sort\n'
                 '      - Project[l_returnflag$26 := l_returnflag$8, '
                 'l_linestatus$27 := l_linestatus$9, sum_qty$28 := sum$16, '
                 'sum_base_price$29 := sum$17, sum_disc_price$30 := sum$19, '
                 'sum_charge$31 := sum$21, avg_qty$32 := avg$22, '
                 'avg_price$33 := avg$23, avg_disc$34 := avg$24, '
                 'count_order$35 := count$25]\n'
                 '         - Aggregation[SINGLE by(l_returnflag$8, '
                 'l_linestatus$9) sum$16 := sum(l_quantity$4), sum$17 := '
                 'sum(l_extendedprice$5), sum$19 := sum(sum_arg$18), sum$21 '
                 ':= sum(sum_arg$20), avg$22 := avg(l_quantity$4), avg$23 := '
                 'avg(l_extendedprice$5), avg$24 := avg(l_discount$6), '
                 'count$25 := count_star(*)]\n'
                 '            - Project[l_quantity$4 := l_quantity$4, '
                 'l_extendedprice$5 := l_extendedprice$5, l_discount$6 := '
                 'l_discount$6, l_returnflag$8 := l_returnflag$8, '
                 'l_linestatus$9 := l_linestatus$9, sum_arg$18 := '
                 '*(l_extendedprice$5, -(1.0, l_discount$6)), sum_arg$20 := '
                 '*(*(l_extendedprice$5, -(1.0, l_discount$6)), +(1.0, '
                 'l_tax$7))]\n'
                 '               - Filter[<=(l_shipdate$10, '
                 'date_sub_interval(10561, 7776000000))]\n'
                 '                  - TableScan[tpch.tiny.lineitem]',
 'tpch.tiny.q3': '- Output[l_orderkey, revenue, o_orderdate, '
                 'o_shippriority]\n'
                 "   - TopN[10 by ['revenue$36', 'o_orderdate$37']]\n"
                 '      - Project[l_orderkey$35 := l_orderkey$17, revenue$36 '
                 ':= sum$34, o_orderdate$37 := o_orderdate$12, '
                 'o_shippriority$38 := o_shippriority$15]\n'
                 '         - Aggregation[SINGLE by(l_orderkey$17, '
                 'o_orderdate$12, o_shippriority$15) sum$34 := '
                 'sum(sum_arg$33)]\n'
                 '            - Project[o_orderdate$12 := o_orderdate$12, '
                 'o_shippriority$15 := o_shippriority$15, l_orderkey$17 := '
                 'l_orderkey$17, sum_arg$33 := *(l_extendedprice$22, -(1.0, '
                 'l_discount$23))]\n'
                 '               - Join[inner o_custkey$9 = c_custkey$0]\n'
                 '                  - Join[inner l_orderkey$17 = '
                 'o_orderkey$8]\n'
                 '                     - TableScan[tpch.tiny.lineitem '
                 'constraint=(l_shipdate:1 ranges)]\n'
                 '                     - TableScan[tpch.tiny.orders '
                 'constraint=(o_orderdate:1 ranges)]\n'
                 '                  - Filter[=(cast(c_mktsegment$6 as '
                 "varchar), 'BUILDING')]\n"
                 '                     - TableScan[tpch.tiny.customer]',
 'tpch.tiny.q6': '- Output[revenue]\n'
                 '   - Project[revenue$18 := sum$17]\n'
                 '      - Aggregation[SINGLE by() sum$17 := '
                 'sum(sum_arg$16)]\n'
                 '         - Project[sum_arg$16 := *(l_extendedprice$5, '
                 'l_discount$6)]\n'
                 '            - Filter[and(and(<(l_shipdate$10, '
                 'date_add_interval(8766, 12)), >=(l_discount$6, '
                 "cast(decimal_-('0.06', '0.01') as double))), "
                 "<=(l_discount$6, cast(decimal_+('0.06', '0.01') as "
                 'double)))]\n'
                 '               - TableScan[tpch.tiny.lineitem '
                 'constraint=(l_quantity:1 ranges, l_shipdate:1 ranges)]'}


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_the_accepted_classes_plan_as_on_the_parent(name):
    catalog, schema, q = name.split(".")
    queries = TPCH_QUERIES if catalog == "tpch" else TPCDS_QUERIES
    assert explain(catalog, schema, queries[int(q[1:])]) == GOLDEN[name]
