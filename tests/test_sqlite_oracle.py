"""Second, independent differential oracle: stdlib sqlite3.

VERDICT r2 Missing #5: the matrix's pandas oracles live in the same file
as the engine plans, written by the same author - a shared misreading of
a query would pass both sides. The reference avoids this by validating
against a genuinely separate engine (vanilla Spark,
dev/run-tpcds-test:38-57). This module is that second engine: the same
synthetic tables are loaded into an in-memory SQLite database (3.40:
CTEs + window functions) and each query is expressed a THIRD way - as
SQL - executed by SQLite's own planner/runtime. The test asserts
sqlite(SQL) == pandas oracle; the main matrix separately asserts
engine == pandas oracle, so all three formulations must agree.

Coverage: ALL 99 TPC-DS queries (round 4 closed the last 24) - set
shapes (EXISTS/EXCEPT/INTERSECT), window functions, rollup unions,
multi-channel concats, decorrelated AVG subqueries, pivots, time-band
unions, left-anti shapes, order-stat aggregates.
"""

import os
import sqlite3

import pandas as pd
import pytest

from tests.tpcds_support import gen_tables
from tests.test_tpcds_queries import ORACLES, assert_frames_match

# ---------------------------------------------------------------------------
# SQL formulations (column lists match the oracle outputs positionally)
# ---------------------------------------------------------------------------

SQL = {}

SQL["q1"] = """
WITH ctr AS (
  SELECT sr_customer_sk AS cust, sr_store_sk AS store,
         SUM(sr_return_amt) AS total
  FROM store_returns
  JOIN date_dim ON sr_returned_date_sk = d_date_sk AND d_year = 2000
  GROUP BY sr_customer_sk, sr_store_sk
)
SELECT c_customer_id
FROM ctr
JOIN (SELECT store AS s2, AVG(total) AS avg_r FROM ctr
      WHERE store IS NOT NULL GROUP BY store) ON store = s2
JOIN store ON store = s_store_sk AND s_state = 'TN'
JOIN customer ON cust = c_customer_sk
WHERE total > 1.2 * avg_r
ORDER BY c_customer_id LIMIT 100
"""

SQL["q3"] = """
SELECT d_year, i_brand_id AS brand_id, i_brand AS brand,
       SUM(ss_ext_sales_price) AS sum_agg
FROM store_sales
JOIN date_dim ON ss_sold_date_sk = d_date_sk AND d_moy = 11
JOIN item ON ss_item_sk = i_item_sk AND i_manufact_id = 128
GROUP BY d_year, i_brand_id, i_brand
ORDER BY d_year, sum_agg DESC, brand_id LIMIT 100
"""

SQL["q6"] = """
SELECT ca_state AS state, COUNT(*) AS cnt
FROM store_sales
JOIN date_dim ON ss_sold_date_sk = d_date_sk
JOIN item ON ss_item_sk = i_item_sk
JOIN customer ON ss_customer_sk = c_customer_sk
JOIN customer_address ON c_current_addr_sk = ca_address_sk
WHERE d_month_seq IN (SELECT DISTINCT d_month_seq FROM date_dim
                      WHERE d_year = 1999 AND d_moy = 1)
  AND i_current_price > 1.2 * (
      SELECT AVG(i_current_price) FROM item i2
      WHERE i2.i_category = item.i_category)
GROUP BY ca_state
HAVING COUNT(*) >= 10
ORDER BY cnt, state LIMIT 100
"""

SQL["q7"] = """
SELECT i_item_id, AVG(ss_quantity) AS agg1, AVG(ss_list_price) AS agg2,
       AVG(ss_coupon_amt) AS agg3, AVG(ss_sales_price) AS agg4
FROM store_sales
JOIN date_dim ON ss_sold_date_sk = d_date_sk AND d_year = 2000
JOIN customer_demographics ON ss_cdemo_sk = cd_demo_sk
  AND cd_gender = 'M' AND cd_marital_status = 'S'
  AND cd_education_status = 'College'
JOIN promotion ON ss_promo_sk = p_promo_sk
  AND (p_channel_email = 'N' OR p_channel_event = 'N')
JOIN item ON ss_item_sk = i_item_sk
GROUP BY i_item_id ORDER BY i_item_id LIMIT 100
"""

SQL["q13"] = """
SELECT AVG(ss_quantity) AS avg_qty, AVG(ss_ext_sales_price) AS avg_esp,
       AVG(ss_ext_wholesale_cost) AS avg_wc,
       SUM(ss_ext_wholesale_cost) AS sum_wc
FROM store_sales
JOIN date_dim ON ss_sold_date_sk = d_date_sk AND d_year = 2000
JOIN customer_demographics ON ss_cdemo_sk = cd_demo_sk
  AND ((cd_marital_status = 'M' AND cd_education_status = 'College')
    OR (cd_marital_status = 'S' AND cd_education_status = 'Primary'))
JOIN store ON ss_store_sk = s_store_sk
WHERE (ss_sales_price BETWEEN 50.0 AND 150.0)
   OR (ss_sales_price BETWEEN 10.0 AND 60.0)
"""

SQL["q15"] = """
SELECT ca_zip, SUM(cs_ext_sales_price) AS s
FROM catalog_sales
JOIN date_dim ON cs_sold_date_sk = d_date_sk
  AND d_year = 1999 AND d_moy BETWEEN 1 AND 3
JOIN customer ON cs_bill_customer_sk = c_customer_sk
JOIN customer_address ON c_current_addr_sk = ca_address_sk
WHERE substr(ca_zip, 1, 5) IN
        ('85669', '86197', '88274', '83405', '86475')
   OR ca_state IN ('CA', 'GA')
   OR cs_ext_sales_price > 500.0
GROUP BY ca_zip ORDER BY ca_zip LIMIT 100
"""

SQL["q19"] = """
SELECT i_brand_id AS brand_id, i_brand AS brand,
       SUM(ss_ext_sales_price) AS ext_price
FROM store_sales
JOIN date_dim ON ss_sold_date_sk = d_date_sk
  AND d_year = 1999 AND d_moy = 11
JOIN item ON ss_item_sk = i_item_sk AND i_manager_id <= 20
JOIN customer ON ss_customer_sk = c_customer_sk
JOIN customer_address ON c_current_addr_sk = ca_address_sk
JOIN store ON ss_store_sk = s_store_sk
WHERE substr(ca_zip, 1, 5) <> substr(s_zip, 1, 5)
GROUP BY i_brand_id, i_brand
ORDER BY ext_price DESC, brand_id LIMIT 100
"""

SQL["q25"] = """
SELECT i_item_id, SUM(ss_net_profit) AS store_profit,
       SUM(sr_net_loss) AS return_loss,
       SUM(cs_ext_sales_price) AS catalog_sales
FROM catalog_sales
JOIN store_returns ON cs_bill_customer_sk = sr_customer_sk
  AND cs_item_sk = sr_item_sk
JOIN store_sales ON sr_customer_sk = ss_customer_sk
  AND sr_item_sk = ss_item_sk
JOIN date_dim ON ss_sold_date_sk = d_date_sk AND d_year = 1998
JOIN item ON ss_item_sk = i_item_sk
GROUP BY i_item_id ORDER BY i_item_id LIMIT 100
"""

SQL["q26"] = """
SELECT i_item_id, AVG(cs_quantity) AS agg1, AVG(cs_list_price) AS agg2,
       AVG(cs_coupon_amt) AS agg3, AVG(cs_sales_price) AS agg4
FROM catalog_sales
JOIN date_dim ON cs_sold_date_sk = d_date_sk AND d_year = 2000
JOIN customer_demographics ON cs_cdemo_sk = cd_demo_sk
  AND cd_gender = 'F' AND cd_marital_status = 'M'
  AND cd_education_status = '4 yr Degree'
JOIN promotion ON cs_promo_sk = p_promo_sk
  AND (p_channel_email = 'N' OR p_channel_event = 'N')
JOIN item ON cs_item_sk = i_item_sk
GROUP BY i_item_id ORDER BY i_item_id LIMIT 100
"""

SQL["q29"] = """
SELECT i_item_id, SUM(ss_quantity) AS store_qty, COUNT(*) AS paths
FROM catalog_sales
JOIN store_returns ON cs_bill_customer_sk = sr_customer_sk
  AND cs_item_sk = sr_item_sk
JOIN store_sales ON sr_customer_sk = ss_customer_sk
  AND sr_item_sk = ss_item_sk
JOIN date_dim ON ss_sold_date_sk = d_date_sk AND d_year = 1999
JOIN item ON ss_item_sk = i_item_sk
GROUP BY i_item_id ORDER BY i_item_id LIMIT 100
"""

SQL["q42"] = """
SELECT d_year, i_category, SUM(ss_ext_sales_price) AS total
FROM store_sales
JOIN date_dim ON ss_sold_date_sk = d_date_sk
  AND d_year = 1999 AND d_moy = 11
JOIN item ON ss_item_sk = i_item_sk AND i_manager_id = 1
GROUP BY d_year, i_category
ORDER BY total DESC, d_year, i_category LIMIT 100
"""

SQL["q43"] = """
SELECT s_store_name,
  SUM(CASE WHEN d_day_name = 'Sunday' THEN ss_ext_sales_price END)
    AS sun_sales,
  SUM(CASE WHEN d_day_name = 'Monday' THEN ss_ext_sales_price END)
    AS mon_sales,
  SUM(CASE WHEN d_day_name = 'Tuesday' THEN ss_ext_sales_price END)
    AS tue_sales,
  SUM(CASE WHEN d_day_name = 'Wednesday' THEN ss_ext_sales_price END)
    AS wed_sales,
  SUM(CASE WHEN d_day_name = 'Thursday' THEN ss_ext_sales_price END)
    AS thu_sales,
  SUM(CASE WHEN d_day_name = 'Friday' THEN ss_ext_sales_price END)
    AS fri_sales,
  SUM(CASE WHEN d_day_name = 'Saturday' THEN ss_ext_sales_price END)
    AS sat_sales
FROM store_sales
JOIN date_dim ON ss_sold_date_sk = d_date_sk AND d_year = 1999
JOIN store ON ss_store_sk = s_store_sk
GROUP BY s_store_name ORDER BY s_store_name LIMIT 100
"""

_BRAND_MONTH = """
SELECT i_brand_id AS brand_id, i_brand AS brand,
       SUM(ss_ext_sales_price) AS ext_price
FROM store_sales
JOIN date_dim ON ss_sold_date_sk = d_date_sk
  AND d_year = 1998 AND d_moy = 12
JOIN item ON ss_item_sk = i_item_sk AND ({cond})
GROUP BY i_brand_id, i_brand
ORDER BY ext_price DESC, brand_id LIMIT 100
"""

SQL["q52"] = _BRAND_MONTH.format(cond="i_manager_id = 1")
SQL["q55"] = _BRAND_MONTH.format(
    cond="i_manager_id BETWEEN 20 AND 40")

SQL["q61"] = """
WITH sales AS (
  SELECT ss_ext_sales_price AS price, ss_promo_sk
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy = 11
  JOIN item ON ss_item_sk = i_item_sk AND i_category = 'Books'
)
SELECT
  (SELECT SUM(price) FROM sales
   JOIN promotion ON ss_promo_sk = p_promo_sk
   WHERE p_channel_dmail = 'Y' OR p_channel_email = 'Y'
      OR p_channel_tv = 'Y') AS promotions,
  (SELECT SUM(price) FROM sales) AS total,
  (SELECT SUM(price) FROM sales
   JOIN promotion ON ss_promo_sk = p_promo_sk
   WHERE p_channel_dmail = 'Y' OR p_channel_email = 'Y'
      OR p_channel_tv = 'Y') * 100.0
    / (SELECT SUM(price) FROM sales) AS pct
"""

SQL["q79"] = """
SELECT c_last_name, c_first_name, s_city, profit, ss_ticket_number, amt
FROM (
  SELECT ss_ticket_number, ss_customer_sk, s_city,
         SUM(ss_coupon_amt) AS amt, SUM(ss_net_profit) AS profit
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
    AND d_dow = 1 AND d_year BETWEEN 1998 AND 2000
  JOIN store ON ss_store_sk = s_store_sk
  JOIN household_demographics ON ss_hdemo_sk = hd_demo_sk
    AND (hd_dep_count = 6 OR hd_vehicle_count > 2)
  GROUP BY ss_ticket_number, ss_customer_sk, s_city
)
JOIN customer ON ss_customer_sk = c_customer_sk
ORDER BY c_last_name, c_first_name, s_city, profit, ss_ticket_number
LIMIT 100
"""

SQL["q84"] = """
SELECT c_customer_id AS customer_id, c_last_name AS customername
FROM customer
JOIN customer_address ON c_current_addr_sk = ca_address_sk
  AND ca_city = 'Midway'
JOIN household_demographics ON c_current_hdemo_sk = hd_demo_sk
JOIN income_band ON hd_income_band_sk = ib_income_band_sk
  AND ib_lower_bound >= 30000 AND ib_upper_bound <= 80000
JOIN customer_demographics ON c_current_cdemo_sk = cd_demo_sk
JOIN store_returns ON cd_demo_sk = sr_cdemo_sk
ORDER BY customer_id LIMIT 100
"""

_Q88_BAND = """
  (SELECT COUNT(*) FROM store_sales
   JOIN time_dim ON ss_sold_time_sk = t_time_sk
     AND (t_hour > {h1} OR (t_hour = {h1} AND t_minute >= {m1}))
     AND (t_hour < {h2} OR (t_hour = {h2} AND t_minute < {m2}))
   JOIN household_demographics ON ss_hdemo_sk = hd_demo_sk
     AND hd_dep_count = {dep}
   JOIN store ON ss_store_sk = s_store_sk
     AND s_store_name = 'store_0') AS {name}
"""

SQL["q88"] = "SELECT\n" + ",\n".join(
    _Q88_BAND.format(h1=h1, m1=m1, h2=h2, m2=m2, dep=dep, name=name)
    for (h1, m1, h2, m2, dep), name in zip(
        [(8, 30, 9, 0, 4), (9, 0, 9, 30, 3), (9, 30, 10, 0, 2),
         (10, 0, 10, 30, 4), (10, 30, 11, 0, 3), (11, 0, 11, 30, 2),
         (11, 30, 12, 0, 4), (12, 0, 12, 30, 3)],
        ["h8_30_to_9", "h9_to_9_30", "h9_30_to_10", "h10_to_10_30",
         "h10_30_to_11", "h11_to_11_30", "h11_30_to_12",
         "h12_to_12_30"])
)

SQL["q90"] = """
SELECT
  (SELECT COUNT(*) * 1.0 FROM web_sales
   JOIN time_dim ON ws_sold_time_sk = t_time_sk
     AND t_hour >= 7 AND t_hour < 9
   JOIN web_page ON ws_web_page_sk = wp_web_page_sk
     AND wp_char_count BETWEEN 4500 AND 5500)
  /
  (SELECT COUNT(*) FROM web_sales
   JOIN time_dim ON ws_sold_time_sk = t_time_sk
     AND t_hour >= 19 AND t_hour < 21
   JOIN web_page ON ws_web_page_sk = wp_web_page_sk
     AND wp_char_count BETWEEN 4500 AND 5500) AS am_pm_ratio
"""

SQL["q91"] = """
SELECT cc_name, cd_marital_status, cd_education_status,
       SUM(cr_net_loss) AS net_loss
FROM catalog_returns
JOIN date_dim ON cr_returned_date_sk = d_date_sk
  AND d_year = 1999 AND d_moy = 11
JOIN call_center ON cr_call_center_sk = cc_call_center_sk
JOIN customer ON cr_returning_customer_sk = c_customer_sk
JOIN customer_demographics ON c_current_cdemo_sk = cd_demo_sk
  AND ((cd_marital_status = 'M' AND cd_education_status = 'College')
    OR (cd_marital_status = 'S' AND cd_education_status = 'Primary'))
JOIN household_demographics ON c_current_hdemo_sk = hd_demo_sk
  AND hd_buy_potential = '>10000'
GROUP BY cc_name, cd_marital_status, cd_education_status
ORDER BY net_loss DESC LIMIT 100
"""

SQL["q92"] = """
WITH ws AS (
  SELECT ws_item_sk, ws_ext_discount_amt
  FROM web_sales
  JOIN date_dim ON ws_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy <= 3
)
SELECT SUM(ws_ext_discount_amt) AS excess_discount
FROM ws
JOIN (SELECT ws_item_sk AS tk,
             AVG(ws_ext_discount_amt) * 1.3 AS threshold
      FROM ws GROUP BY ws_item_sk) ON ws_item_sk = tk
WHERE ws_ext_discount_amt > threshold
"""

SQL["q93"] = """
SELECT ss_customer_sk, SUM(act_sales) AS sumsales
FROM (
  SELECT ss_customer_sk,
         CASE WHEN r_reason_desc = 'reason 3'
              THEN (ss_quantity - sr_return_quantity) * ss_sales_price
              ELSE ss_quantity * ss_sales_price END AS act_sales
  FROM store_sales
  LEFT JOIN (SELECT sr_ticket_number, sr_item_sk, sr_return_quantity,
                    r_reason_desc
             FROM store_returns
             JOIN reason ON sr_reason_sk = r_reason_sk)
    ON ss_ticket_number = sr_ticket_number AND ss_item_sk = sr_item_sk
)
GROUP BY ss_customer_sk
ORDER BY sumsales, ss_customer_sk LIMIT 100
"""

SQL["q96"] = """
SELECT COUNT(*) AS cnt
FROM store_sales
JOIN time_dim ON ss_sold_time_sk = t_time_sk
  AND t_hour = 20 AND t_minute >= 30
JOIN household_demographics ON ss_hdemo_sk = hd_demo_sk
  AND hd_dep_count = 6
JOIN store ON ss_store_sk = s_store_sk AND s_store_name = 'store_1'
"""

SQL["q99"] = """
SELECT w_warehouse_name, sm_type, cc_name,
  SUM(CASE WHEN cs_ship_date_sk - cs_sold_date_sk <= 30
           THEN 1 ELSE 0 END) AS d30,
  SUM(CASE WHEN cs_ship_date_sk - cs_sold_date_sk > 30
            AND cs_ship_date_sk - cs_sold_date_sk <= 60
           THEN 1 ELSE 0 END) AS d60,
  SUM(CASE WHEN cs_ship_date_sk - cs_sold_date_sk > 60
            AND cs_ship_date_sk - cs_sold_date_sk <= 90
           THEN 1 ELSE 0 END) AS d90,
  SUM(CASE WHEN cs_ship_date_sk - cs_sold_date_sk > 90
            AND cs_ship_date_sk - cs_sold_date_sk <= 120
           THEN 1 ELSE 0 END) AS d120,
  SUM(CASE WHEN cs_ship_date_sk - cs_sold_date_sk > 120
           THEN 1 ELSE 0 END) AS dmore
FROM catalog_sales
JOIN date_dim ON cs_ship_date_sk = d_date_sk AND d_year = 1999
JOIN warehouse ON cs_warehouse_sk = w_warehouse_sk
JOIN ship_mode ON cs_ship_mode_sk = sm_ship_mode_sk
JOIN call_center ON cs_call_center_sk = cc_call_center_sk
GROUP BY w_warehouse_name, sm_type, cc_name
ORDER BY w_warehouse_name, sm_type, cc_name LIMIT 100
"""


SQL["q9"] = """
SELECT
""" + ",\n".join(
    f"""  CASE WHEN (SELECT COUNT(*) FROM store_sales
         WHERE ss_quantity BETWEEN {lo} AND {hi}) > 7438
       THEN (SELECT AVG(ss_ext_discount_amt) FROM store_sales
             WHERE ss_quantity BETWEEN {lo} AND {hi})
       ELSE (SELECT AVG(ss_net_profit) FROM store_sales
             WHERE ss_quantity BETWEEN {lo} AND {hi}) END AS bucket{i}"""
    for i, (lo, hi) in enumerate(
        [(1, 20), (21, 40), (41, 60), (61, 80), (81, 100)], 1)
)

SQL["q28"] = " UNION ALL ".join(
    f"""SELECT {i} AS bucket, AVG(ss_list_price) AS avg_p,
        COUNT(*) AS cnt, COUNT(DISTINCT ss_list_price) AS distinct_cnt
        FROM store_sales
        WHERE ss_list_price >= {lo} AND ss_list_price < {hi}"""
    for i, (lo, hi) in enumerate(
        [(0, 50), (50, 100), (100, 150), (150, 200), (200, 250),
         (0, 250)])
)

SQL["q32"] = """
WITH cs AS (
  SELECT cs_item_sk, cs_ext_discount_amt
  FROM catalog_sales
  JOIN date_dim ON cs_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy <= 3
)
SELECT SUM(cs_ext_discount_amt) AS excess_discount
FROM cs
JOIN (SELECT cs_item_sk AS tk,
             AVG(cs_ext_discount_amt) * 1.3 AS threshold
      FROM cs GROUP BY cs_item_sk) ON cs_item_sk = tk
WHERE cs_ext_discount_amt > threshold
"""

SQL["q37"] = """
SELECT DISTINCT i_item_id, i_item_desc, i_current_price
FROM item
JOIN inventory ON i_item_sk = inv_item_sk
  AND inv_quantity_on_hand BETWEEN 100 AND 500
JOIN date_dim ON inv_date_sk = d_date_sk
  AND d_date_sk BETWEEN 400 AND 460
WHERE i_current_price >= 10.0
  AND i_item_sk IN (SELECT cs_item_sk FROM catalog_sales)
ORDER BY i_item_id LIMIT 100
"""

SQL["q40"] = """
SELECT i_item_id,
  SUM(CASE WHEN d_date_sk < 700
           THEN cs_ext_sales_price - COALESCE(cr_return_amount, 0.0)
           ELSE 0.0 END) AS sales_before,
  SUM(CASE WHEN d_date_sk >= 700
           THEN cs_ext_sales_price - COALESCE(cr_return_amount, 0.0)
           ELSE 0.0 END) AS sales_after
FROM catalog_sales
JOIN date_dim ON cs_sold_date_sk = d_date_sk
  AND d_date_sk BETWEEN 670 AND 730
LEFT JOIN catalog_returns ON cs_order_number = cr_order_number
  AND cs_item_sk = cr_item_sk
JOIN item ON cs_item_sk = i_item_sk
GROUP BY i_item_id ORDER BY i_item_id LIMIT 100
"""

SQL["q62"] = """
SELECT w_warehouse_name, sm_type, web_name,
  SUM(CASE WHEN ws_ship_date_sk - ws_sold_date_sk <= 30
           THEN 1 ELSE 0 END) AS d30,
  SUM(CASE WHEN ws_ship_date_sk - ws_sold_date_sk > 30
            AND ws_ship_date_sk - ws_sold_date_sk <= 60
           THEN 1 ELSE 0 END) AS d60,
  SUM(CASE WHEN ws_ship_date_sk - ws_sold_date_sk > 60
            AND ws_ship_date_sk - ws_sold_date_sk <= 90
           THEN 1 ELSE 0 END) AS d90,
  SUM(CASE WHEN ws_ship_date_sk - ws_sold_date_sk > 90
            AND ws_ship_date_sk - ws_sold_date_sk <= 120
           THEN 1 ELSE 0 END) AS d120,
  SUM(CASE WHEN ws_ship_date_sk - ws_sold_date_sk > 120
           THEN 1 ELSE 0 END) AS dmore
FROM web_sales
JOIN date_dim ON ws_ship_date_sk = d_date_sk AND d_year = 1999
JOIN warehouse ON ws_warehouse_sk = w_warehouse_sk
JOIN ship_mode ON ws_ship_mode_sk = sm_ship_mode_sk
JOIN web_site ON ws_web_site_sk = web_site_sk
GROUP BY w_warehouse_name, sm_type, web_name
ORDER BY w_warehouse_name, sm_type, web_name LIMIT 100
"""

SQL["q82"] = """
SELECT DISTINCT i_item_id, i_item_desc, i_current_price
FROM item
JOIN inventory ON i_item_sk = inv_item_sk
  AND inv_quantity_on_hand BETWEEN 100 AND 500
JOIN date_dim ON inv_date_sk = d_date_sk AND d_year = 1999
JOIN store_sales ON i_item_sk = ss_item_sk
WHERE i_current_price BETWEEN 30.0 AND 60.0
  AND i_manufact_id IN (10, 20, 30, 40, 50, 60)
ORDER BY i_item_id LIMIT 100
"""

_Q45_ZIPS = sorted({f"{(24000 + (i % 500) * 131) % 90000:05d}"
                    for i in range(0, 40)})
_Q45_ITEMS = sorted(range(2, 30, 3))
SQL["q45"] = f"""
SELECT ca_zip, SUM(ws_ext_sales_price) AS total
FROM web_sales
JOIN date_dim ON ws_sold_date_sk = d_date_sk
  AND d_year = 1999 AND d_moy BETWEEN 1 AND 3
JOIN customer ON ws_bill_customer_sk = c_customer_sk
JOIN customer_address ON c_current_addr_sk = ca_address_sk
WHERE substr(ca_zip, 1, 5) IN ({", ".join(repr(z) for z in _Q45_ZIPS)})
   OR ws_item_sk IN ({", ".join(str(i) for i in _Q45_ITEMS)})
GROUP BY ca_zip ORDER BY ca_zip LIMIT 100
"""


_DEV_WINDOW = """
WITH agg AS (
  SELECT {group_cols}, SUM(ss_sales_price) AS sum_sales
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk AND d_year = 1999
  JOIN item ON ss_item_sk = i_item_sk
    AND i_category IN ('Books', 'Home', 'Sports')
  JOIN store ON ss_store_sk = s_store_sk
  GROUP BY {group_cols}
), w AS (
  SELECT *, AVG(sum_sales) OVER (PARTITION BY {part_cols}) AS avg_sales
  FROM agg
)
SELECT {out_cols} FROM w
WHERE avg_sales > 0 AND ABS(sum_sales - avg_sales) / avg_sales > 0.1
ORDER BY {order_cols} LIMIT 100
"""

SQL["q53"] = _DEV_WINDOW.format(
    group_cols="i_manufact_id, d_qoy",
    part_cols="i_manufact_id",
    out_cols="i_manufact_id, sum_sales, avg_sales",
    order_cols="avg_sales, sum_sales, i_manufact_id",
)
SQL["q63"] = _DEV_WINDOW.format(
    group_cols="i_manager_id, d_moy",
    part_cols="i_manager_id",
    out_cols="i_manager_id, sum_sales, avg_sales",
    order_cols="i_manager_id, avg_sales, sum_sales",
)
SQL["q89"] = _DEV_WINDOW.format(
    group_cols=("i_category, i_class, i_brand, s_store_name, "
                "s_company_name, d_moy"),
    part_cols="i_category, i_brand, s_store_name, s_company_name",
    out_cols=("i_category, i_class, i_brand, s_store_name, "
              "s_company_name, d_moy, sum_sales, avg_sales"),
    order_cols=("sum_sales - avg_sales, s_store_name, i_category, "
                "i_class, i_brand, d_moy"),
)

_CLASS_RATIO = """
WITH rev AS (
  SELECT i_item_id, i_item_desc, i_category, i_current_price,
         SUM({prefix}_ext_sales_price) AS itemrevenue
  FROM {table}
  JOIN date_dim ON {prefix}_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy <= 2
  JOIN item ON {prefix}_item_sk = i_item_sk
    AND i_category IN ('Books', 'Home', 'Sports')
  GROUP BY i_item_id, i_item_desc, i_category, i_current_price
)
SELECT i_item_id, i_category, itemrevenue,
       itemrevenue * 100.0
         / SUM(itemrevenue) OVER (PARTITION BY i_category)
         AS revenueratio
FROM rev ORDER BY i_category, i_item_id LIMIT 100
"""

SQL["q12"] = _CLASS_RATIO.format(prefix="ws", table="web_sales")
SQL["q20"] = _CLASS_RATIO.format(prefix="cs", table="catalog_sales")

SQL["q98"] = """
WITH rev AS (
  SELECT i_item_id, i_item_desc, i_category, i_class,
         i_current_price, SUM(ss_ext_sales_price) AS itemrevenue
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy <= 2
  JOIN item ON ss_item_sk = i_item_sk
    AND i_category IN ('Books', 'Home', 'Sports')
  GROUP BY i_item_id, i_item_desc, i_category, i_class,
           i_current_price
)
SELECT i_item_id, i_item_desc, i_category, i_class, i_current_price,
       itemrevenue,
       itemrevenue * 100.0
         / SUM(itemrevenue) OVER (PARTITION BY i_class)
         AS revenueratio
FROM rev
ORDER BY i_category, i_class, i_item_id, i_item_desc, revenueratio
LIMIT 100
"""


SQL["q51"] = """
WITH web_daily AS (
  SELECT ws_item_sk AS item_sk, d_date_sk AS date_sk,
         SUM(ws_ext_sales_price) AS rev
  FROM web_sales
  JOIN date_dim ON ws_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy <= 2
  GROUP BY ws_item_sk, d_date_sk
), web AS (
  SELECT item_sk, date_sk,
         SUM(rev) OVER (PARTITION BY item_sk ORDER BY date_sk
                        ROWS UNBOUNDED PRECEDING) AS cume
  FROM web_daily
), store_daily AS (
  SELECT ss_item_sk AS item_sk, d_date_sk AS date_sk,
         SUM(ss_ext_sales_price) AS rev
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy <= 2
  GROUP BY ss_item_sk, d_date_sk
), store AS (
  SELECT item_sk, date_sk,
         SUM(rev) OVER (PARTITION BY item_sk ORDER BY date_sk
                        ROWS UNBOUNDED PRECEDING) AS cume
  FROM store_daily
)
SELECT COALESCE(web.item_sk, store.item_sk) AS item_sk,
       COALESCE(web.date_sk, store.date_sk) AS date_sk,
       web.cume AS web_cume, store.cume AS store_cume
FROM web
FULL OUTER JOIN store ON web.item_sk = store.item_sk
  AND web.date_sk = store.date_sk
WHERE COALESCE(web.cume, 0.0) > COALESCE(store.cume, 0.0)
ORDER BY 1, 2 LIMIT 200
"""


SQL["q16"] = """
WITH sold AS (
  SELECT cs_item_sk, cs_ext_sales_price
  FROM catalog_sales
  JOIN date_dim ON cs_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy BETWEEN 2 AND 4
  WHERE cs_item_sk NOT IN
    (SELECT cr_item_sk FROM catalog_returns
     WHERE cr_item_sk IS NOT NULL)
), dist AS (
  SELECT cs_item_sk, SUM(cs_ext_sales_price) AS net
  FROM sold GROUP BY cs_item_sk
)
SELECT COUNT(*) AS order_count, SUM(net) AS total_net FROM dist
"""

SQL["q22"] = """
WITH inv AS (
  SELECT i_brand, i_manufact_id, inv_quantity_on_hand AS q
  FROM inventory
  JOIN date_dim ON inv_date_sk = d_date_sk
    AND d_month_seq BETWEEN 1188 AND 1199
  JOIN item ON inv_item_sk = i_item_sk
)
SELECT i_brand AS brand, i_manufact_id AS manufact_id, AVG(q) AS qoh
FROM inv GROUP BY i_brand, i_manufact_id
UNION ALL
SELECT i_brand, NULL, AVG(q) FROM inv GROUP BY i_brand
UNION ALL
SELECT NULL, NULL, AVG(q) FROM inv
"""

SQL["q33"] = """
WITH books AS (
  SELECT i_item_sk, i_manufact_id FROM item
  WHERE i_category = 'Books'
), ch AS (
  SELECT i_manufact_id, SUM(ss_ext_sales_price) AS total_sales
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy = 3
  JOIN books ON ss_item_sk = i_item_sk
  GROUP BY i_manufact_id
  UNION ALL
  SELECT i_manufact_id, SUM(cs_ext_sales_price)
  FROM catalog_sales
  JOIN date_dim ON cs_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy = 3
  JOIN books ON cs_item_sk = i_item_sk
  GROUP BY i_manufact_id
  UNION ALL
  SELECT i_manufact_id, SUM(ws_ext_sales_price)
  FROM web_sales
  JOIN date_dim ON ws_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy = 3
  JOIN books ON ws_item_sk = i_item_sk
  GROUP BY i_manufact_id
)
SELECT i_manufact_id, SUM(total_sales) AS total_sales
FROM ch GROUP BY i_manufact_id
ORDER BY total_sales DESC, i_manufact_id LIMIT 100
"""

SQL["q41"] = """
SELECT DISTINCT i_product_name
FROM item
WHERE i_manufact_id BETWEEN 100 AND 140
  AND i_manufact IN (
    SELECT i_manufact FROM item
    WHERE (i_color IN ('red', 'blue') AND i_units IN ('Oz', 'Case')
           AND i_size IN ('small', 'large'))
       OR (i_color IN ('green', 'navy') AND i_units IN ('Ton', 'Each')
           AND i_size IN ('medium', 'petite'))
  )
ORDER BY i_product_name LIMIT 100
"""

SQL["q65"] = """
WITH sb AS (
  SELECT ss_store_sk, ss_item_sk, SUM(ss_sales_price) AS revenue
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
    AND d_month_seq BETWEEN 1188 AND 1199
  GROUP BY ss_store_sk, ss_item_sk
), sc AS (
  SELECT ss_store_sk AS sk2, AVG(revenue) AS ave
  FROM sb GROUP BY ss_store_sk
)
SELECT s_store_name, i_item_desc, revenue, i_current_price, i_brand
FROM sb
JOIN sc ON ss_store_sk = sk2
JOIN store ON ss_store_sk = s_store_sk
JOIN item ON ss_item_sk = i_item_sk
WHERE revenue <= 0.1 * ave
ORDER BY s_store_name, i_item_desc, revenue LIMIT 100
"""


SQL["q30"] = """
WITH ctr AS (
  SELECT c_customer_sk, c_customer_id, ca_state,
         SUM(wr_return_amt) AS total
  FROM web_returns
  JOIN date_dim ON wr_returned_date_sk = d_date_sk AND d_year = 1999
  JOIN customer ON wr_returning_customer_sk = c_customer_sk
  JOIN customer_address ON c_current_addr_sk = ca_address_sk
  GROUP BY c_customer_sk, c_customer_id, ca_state
)
SELECT c_customer_id, total
FROM ctr
JOIN (SELECT ca_state AS st2, AVG(total) AS avg_r FROM ctr
      WHERE ca_state IS NOT NULL GROUP BY ca_state)
  ON ca_state = st2
WHERE total > 1.2 * avg_r
ORDER BY c_customer_id LIMIT 100
"""

SQL["q34"] = """
SELECT c_last_name, c_first_name, ss_ticket_number, cnt
FROM (
  SELECT ss_ticket_number, ss_customer_sk, COUNT(*) AS cnt
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk AND d_year = 1999
  JOIN household_demographics ON ss_hdemo_sk = hd_demo_sk
    AND hd_buy_potential IN ('>10000', '0-500')
  GROUP BY ss_ticket_number, ss_customer_sk
)
JOIN customer ON ss_customer_sk = c_customer_sk
WHERE cnt BETWEEN 3 AND 8
ORDER BY c_last_name, c_first_name, ss_ticket_number LIMIT 1000
"""

SQL["q73"] = """
SELECT c_last_name, c_first_name, ss_ticket_number, cnt
FROM (
  SELECT ss_ticket_number, ss_customer_sk, COUNT(*) AS cnt
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
    AND d_dom BETWEEN 1 AND 2 AND d_year BETWEEN 1998 AND 2000
  JOIN household_demographics ON ss_hdemo_sk = hd_demo_sk
    AND hd_buy_potential IN ('>10000', '0-500')
    AND hd_vehicle_count > 0
  GROUP BY ss_ticket_number, ss_customer_sk
)
JOIN customer ON ss_customer_sk = c_customer_sk
WHERE cnt BETWEEN 1 AND 5
ORDER BY cnt DESC, c_last_name, ss_ticket_number
"""

_Q8_LIST = [f"{(24000 + (i % 500) * 131) % 90000:05d}"
            for i in range(0, 400)][:200]
SQL["q8"] = f"""
WITH good_zips AS (
  SELECT substr(ca_zip, 1, 5) AS zip5
  FROM customer_address
  WHERE substr(ca_zip, 1, 5) IN
    ({", ".join(repr(z) for z in sorted(set(_Q8_LIST)))})
  INTERSECT
  SELECT zip5 FROM (
    SELECT substr(ca_zip, 1, 5) AS zip5, COUNT(*) AS cnt
    FROM customer_address
    JOIN customer ON ca_address_sk = c_current_addr_sk
      AND c_preferred_cust_flag = 'Y'
    GROUP BY substr(ca_zip, 1, 5)
    HAVING COUNT(*) > 10
  )
)
SELECT s_store_name, SUM(ss_net_profit) AS net_profit
FROM store_sales
JOIN date_dim ON ss_sold_date_sk = d_date_sk
  AND d_year = 1998 AND d_moy = 2
JOIN store ON ss_store_sk = s_store_sk
WHERE substr(s_zip, 1, 2) IN
  (SELECT DISTINCT substr(zip5, 1, 2) FROM good_zips)
GROUP BY s_store_name ORDER BY s_store_name LIMIT 100
"""


SQL["q35"] = """
SELECT cd_gender, cd_marital_status, cd_dep_count,
       cd_dep_employed_count, cd_dep_college_count,
       COUNT(*) AS cnt, MIN(cd_dep_count) AS min_dep,
       MAX(cd_dep_count) AS max_dep, AVG(cd_dep_count) AS avg_dep
FROM customer
JOIN customer_demographics ON c_current_cdemo_sk = cd_demo_sk
WHERE c_customer_sk IN (
    SELECT ss_customer_sk FROM store_sales
    JOIN date_dim ON ss_sold_date_sk = d_date_sk
      AND d_year = 1999 AND d_qoy < 4)
  AND c_customer_sk IN (
    SELECT ws_bill_customer_sk FROM web_sales
    JOIN date_dim ON ws_sold_date_sk = d_date_sk
      AND d_year = 1999 AND d_qoy < 4
    UNION
    SELECT cs_bill_customer_sk FROM catalog_sales
    JOIN date_dim ON cs_sold_date_sk = d_date_sk
      AND d_year = 1999 AND d_qoy < 4)
GROUP BY cd_gender, cd_marital_status, cd_dep_count,
         cd_dep_employed_count, cd_dep_college_count
ORDER BY cd_gender, cd_marital_status, cd_dep_count,
         cd_dep_employed_count, cd_dep_college_count
LIMIT 100
"""

SQL["q38"] = """
SELECT COUNT(*) AS num_customers FROM (
  SELECT ss_customer_sk FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy <= 2
  WHERE ss_customer_sk IS NOT NULL
  INTERSECT
  SELECT cs_bill_customer_sk FROM catalog_sales
  JOIN date_dim ON cs_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy <= 2
  INTERSECT
  SELECT ws_bill_customer_sk FROM web_sales
  JOIN date_dim ON ws_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy <= 2
)
"""

SQL["q69"] = """
SELECT cd_gender, cd_marital_status, cd_education_status,
       cd_purchase_estimate, cd_credit_rating, COUNT(*) AS cnt
FROM customer
JOIN customer_address ON c_current_addr_sk = ca_address_sk
  AND ca_state IN ('TN', 'GA', 'CA')
JOIN customer_demographics ON c_current_cdemo_sk = cd_demo_sk
WHERE c_customer_sk IN (
    SELECT ss_customer_sk FROM store_sales
    JOIN date_dim ON ss_sold_date_sk = d_date_sk
      AND d_year = 2000 AND d_moy BETWEEN 1 AND 3)
  AND c_customer_sk NOT IN (
    SELECT ws_bill_customer_sk FROM web_sales
    JOIN date_dim ON ws_sold_date_sk = d_date_sk
      AND d_year = 2000 AND d_moy BETWEEN 1 AND 3
    WHERE ws_bill_customer_sk IS NOT NULL)
  AND c_customer_sk NOT IN (
    SELECT cs_bill_customer_sk FROM catalog_sales
    JOIN date_dim ON cs_sold_date_sk = d_date_sk
      AND d_year = 2000 AND d_moy BETWEEN 1 AND 3
    WHERE cs_bill_customer_sk IS NOT NULL)
GROUP BY cd_gender, cd_marital_status, cd_education_status,
         cd_purchase_estimate, cd_credit_rating
ORDER BY cd_gender, cd_marital_status, cd_education_status,
         cd_purchase_estimate, cd_credit_rating
LIMIT 100
"""

SQL["q87"] = """
WITH d AS (
  SELECT d_date_sk FROM date_dim
  WHERE d_month_seq BETWEEN 1188 AND 1199
), sp AS (
  SELECT DISTINCT ss_customer_sk AS c, ss_sold_date_sk AS dt
  FROM store_sales JOIN d ON ss_sold_date_sk = d_date_sk
)
SELECT
  (SELECT COUNT(*) FROM sp WHERE c IS NULL)
  + (SELECT COUNT(*) FROM (
      SELECT c, dt FROM sp WHERE c IS NOT NULL
      EXCEPT
      SELECT DISTINCT ws_bill_customer_sk, ws_sold_date_sk
      FROM web_sales JOIN d ON ws_sold_date_sk = d_date_sk
      EXCEPT
      SELECT DISTINCT cs_bill_customer_sk, cs_sold_date_sk
      FROM catalog_sales JOIN d ON cs_sold_date_sk = d_date_sk
    )) AS num_store_only
"""

SQL["q97"] = """
WITH d AS (
  SELECT d_date_sk FROM date_dim
  WHERE d_month_seq BETWEEN 1188 AND 1199
), sp AS (
  SELECT DISTINCT ss_customer_sk AS c, ss_item_sk AS i
  FROM store_sales JOIN d ON ss_sold_date_sk = d_date_sk
  WHERE ss_customer_sk IS NOT NULL
), cp AS (
  SELECT DISTINCT cs_bill_customer_sk AS c, cs_item_sk AS i
  FROM catalog_sales JOIN d ON cs_sold_date_sk = d_date_sk
  WHERE cs_bill_customer_sk IS NOT NULL
)
SELECT
  (SELECT COUNT(*) FROM (SELECT * FROM sp EXCEPT SELECT * FROM cp))
    AS store_only,
  (SELECT COUNT(*) FROM (SELECT * FROM cp EXCEPT SELECT * FROM sp))
    AS catalog_only,
  (SELECT COUNT(*) FROM (SELECT * FROM sp INTERSECT
                         SELECT * FROM cp)) AS store_and_catalog
"""


SQL["q17"] = """
SELECT i_item_id, COUNT(ss_quantity) AS qty_count,
       AVG(ss_quantity) AS qty_avg,
       CASE WHEN COUNT(ss_quantity) > 1 THEN
         sqrt((SUM(1.0 * ss_quantity * ss_quantity)
               - 1.0 * SUM(ss_quantity) * SUM(ss_quantity)
                 / COUNT(ss_quantity))
              / (COUNT(ss_quantity) - 1))
       END AS qty_stdev
FROM store_sales
JOIN date_dim ON ss_sold_date_sk = d_date_sk AND d_year = 1998
JOIN store_returns ON ss_item_sk = sr_item_sk
JOIN item ON ss_item_sk = i_item_sk
GROUP BY i_item_id ORDER BY i_item_id LIMIT 100
"""

SQL["q18"] = """
WITH j AS (
  SELECT i_item_id, ca_state, cs_ext_sales_price AS p
  FROM catalog_sales
  JOIN date_dim ON cs_sold_date_sk = d_date_sk AND d_year = 1998
  JOIN customer ON cs_bill_customer_sk = c_customer_sk
  JOIN customer_address ON c_current_addr_sk = ca_address_sk
  JOIN item ON cs_item_sk = i_item_sk
)
SELECT i_item_id, ca_state, AVG(p) AS a
FROM j GROUP BY i_item_id, ca_state
UNION ALL
SELECT NULL, ca_state, AVG(p) FROM j GROUP BY ca_state
UNION ALL
SELECT NULL, NULL, AVG(p) FROM j
"""

SQL["q27"] = """
WITH j AS (
  SELECT i_item_id, s_state, ss_quantity AS q, ss_list_price AS lp
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk AND d_year = 2000
  JOIN customer_demographics ON ss_cdemo_sk = cd_demo_sk
    AND cd_gender = 'M' AND cd_marital_status = 'S'
    AND cd_education_status = 'College'
  JOIN store ON ss_store_sk = s_store_sk
  JOIN item ON ss_item_sk = i_item_sk
)
SELECT i_item_id, s_state, AVG(q) AS agg1, AVG(lp) AS agg2
FROM j GROUP BY i_item_id, s_state
UNION ALL
SELECT i_item_id, NULL, AVG(q), AVG(lp) FROM j GROUP BY i_item_id
UNION ALL
SELECT NULL, NULL, AVG(q), AVG(lp) FROM j
"""

SQL["q36"] = """
WITH j AS (
  SELECT i_category, i_class, ss_net_profit AS np,
         ss_ext_sales_price AS sp
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk AND d_year = 1999
  JOIN item ON ss_item_sk = i_item_sk
)
SELECT i_category, i_class, SUM(np) / SUM(sp) AS gross_margin
FROM j GROUP BY i_category, i_class
UNION ALL
SELECT i_category, NULL, SUM(np) / SUM(sp) FROM j GROUP BY i_category
UNION ALL
SELECT NULL, NULL, SUM(np) / SUM(sp) FROM j
"""

SQL["q50"] = """
SELECT s_store_name,
  SUM(CASE WHEN sr_returned_date_sk - d_date_sk <= 30
           THEN 1 ELSE 0 END) AS d30,
  SUM(CASE WHEN sr_returned_date_sk - d_date_sk > 30
            AND sr_returned_date_sk - d_date_sk <= 60
           THEN 1 ELSE 0 END) AS d60,
  SUM(CASE WHEN sr_returned_date_sk - d_date_sk > 60
            AND sr_returned_date_sk - d_date_sk <= 90
           THEN 1 ELSE 0 END) AS d90,
  SUM(CASE WHEN sr_returned_date_sk - d_date_sk > 90
           THEN 1 ELSE 0 END) AS d90plus
FROM store_returns
JOIN store_sales ON sr_customer_sk = ss_customer_sk
  AND sr_item_sk = ss_item_sk
JOIN date_dim ON ss_sold_date_sk = d_date_sk AND d_year = 1999
JOIN store ON ss_store_sk = s_store_sk
WHERE sr_returned_date_sk >= d_date_sk
GROUP BY s_store_name ORDER BY s_store_name LIMIT 100
"""


_YEAR_TOTAL = """
  SELECT c_customer_sk AS sk, c_customer_id AS cid, d_year,
         SUM(({p}_ext_list_price - {p}_ext_discount_amt) / 2.0)
           AS year_total
  FROM {table}
  JOIN date_dim ON {p}_sold_date_sk = d_date_sk
  JOIN customer ON {p}_bill_customer_sk = c_customer_sk
  GROUP BY c_customer_sk, c_customer_id, d_year
"""
_YEAR_TOTAL_SS = _YEAR_TOTAL.replace(
    "{p}_bill_customer_sk", "ss_customer_sk"
).format(p="ss", table="store_sales")

_YOY = """
WITH s_yt AS ({s_yt}), o_yt AS ({o_yt})
SELECT s1.cid
FROM s_yt s1
JOIN s_yt s2 ON s1.sk = s2.sk AND s2.d_year = 1999
JOIN o_yt o1 ON s1.sk = o1.sk AND o1.d_year = 1998
JOIN o_yt o2 ON s1.sk = o2.sk AND o2.d_year = 1999
WHERE s1.d_year = 1998 AND s1.year_total > 0 AND o1.year_total > 0
  AND o2.year_total / o1.year_total
      > s2.year_total / s1.year_total
ORDER BY s1.cid LIMIT 100
"""

SQL["q4"] = _YOY.format(
    s_yt=_YEAR_TOTAL_SS,
    o_yt=_YEAR_TOTAL.format(p="cs", table="catalog_sales"),
)
SQL["q11"] = _YOY.format(
    s_yt=_YEAR_TOTAL_SS,
    o_yt=_YEAR_TOTAL.format(p="ws", table="web_sales"),
)

SQL["q31"] = """
WITH ssq AS (
  SELECT ca_county, d_qoy, SUM(ss_ext_sales_price) AS s
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk AND d_year = 1999
    AND d_qoy IN (1, 2, 3)
  JOIN customer_address ON ss_addr_sk = ca_address_sk
  GROUP BY ca_county, d_qoy
), wsq AS (
  SELECT ca_county, d_qoy, SUM(ws_ext_sales_price) AS s
  FROM web_sales
  JOIN date_dim ON ws_sold_date_sk = d_date_sk AND d_year = 1999
    AND d_qoy IN (1, 2, 3)
  JOIN customer_address ON ws_bill_addr_sk = ca_address_sk
  GROUP BY ca_county, d_qoy
)
SELECT ss1.ca_county,
       ws2.s / ws1.s AS web_q1_q2_increase,
       ss2.s / ss1.s AS store_q1_q2_increase,
       ws3.s / ws2.s AS web_q2_q3_increase,
       ss3.s / ss2.s AS store_q2_q3_increase
FROM ssq ss1
JOIN ssq ss2 ON ss1.ca_county = ss2.ca_county AND ss2.d_qoy = 2
JOIN ssq ss3 ON ss1.ca_county = ss3.ca_county AND ss3.d_qoy = 3
JOIN wsq ws1 ON ss1.ca_county = ws1.ca_county AND ws1.d_qoy = 1
JOIN wsq ws2 ON ss1.ca_county = ws2.ca_county AND ws2.d_qoy = 2
JOIN wsq ws3 ON ss1.ca_county = ws3.ca_county AND ws3.d_qoy = 3
WHERE ss1.d_qoy = 1
  AND ws2.s / ws1.s > ss2.s / ss1.s
  AND ws3.s / ws2.s > ss3.s / ss2.s
ORDER BY ss1.ca_county
"""


SQL["q2"] = """
WITH both_ch AS (
  SELECT ws_sold_date_sk AS sold_date_sk,
         ws_ext_sales_price AS sales_price FROM web_sales
  UNION ALL
  SELECT cs_sold_date_sk, cs_ext_sales_price FROM catalog_sales
), weekly AS (
  SELECT d_week_seq,
         SUM(CASE WHEN d_day_name = 'Sunday' THEN sales_price END) AS sun_sales,
         SUM(CASE WHEN d_day_name = 'Monday' THEN sales_price END) AS mon_sales,
         SUM(CASE WHEN d_day_name = 'Tuesday' THEN sales_price END) AS tue_sales,
         SUM(CASE WHEN d_day_name = 'Wednesday' THEN sales_price END) AS wed_sales,
         SUM(CASE WHEN d_day_name = 'Thursday' THEN sales_price END) AS thu_sales,
         SUM(CASE WHEN d_day_name = 'Friday' THEN sales_price END) AS fri_sales,
         SUM(CASE WHEN d_day_name = 'Saturday' THEN sales_price END) AS sat_sales
  FROM date_dim JOIN both_ch ON d_date_sk = sold_date_sk
  GROUP BY d_week_seq
), wk AS (
  SELECT DISTINCT d_week_seq, d_year FROM date_dim
)
SELECT y1.d_week_seq AS d_week_seq1,
       ROUND(y1.sun_sales / y2.sun_sales, 2) AS sun_r,
       ROUND(y1.mon_sales / y2.mon_sales, 2) AS mon_r,
       ROUND(y1.tue_sales / y2.tue_sales, 2) AS tue_r,
       ROUND(y1.wed_sales / y2.wed_sales, 2) AS wed_r,
       ROUND(y1.thu_sales / y2.thu_sales, 2) AS thu_r,
       ROUND(y1.fri_sales / y2.fri_sales, 2) AS fri_r,
       ROUND(y1.sat_sales / y2.sat_sales, 2) AS sat_r
FROM weekly y1
JOIN wk w1 ON y1.d_week_seq = w1.d_week_seq AND w1.d_year = 1998
JOIN weekly y2
JOIN wk w2 ON y2.d_week_seq = w2.d_week_seq AND w2.d_year = 1999
WHERE y2.d_week_seq = y1.d_week_seq + 53
ORDER BY y1.d_week_seq
"""

SQL["q59"] = """
WITH wss AS (
  SELECT d_week_seq, ss_store_sk,
         SUM(CASE WHEN d_day_name = 'Sunday' THEN ss_sales_price END) AS sun_sales,
         SUM(CASE WHEN d_day_name = 'Monday' THEN ss_sales_price END) AS mon_sales,
         SUM(CASE WHEN d_day_name = 'Tuesday' THEN ss_sales_price END) AS tue_sales,
         SUM(CASE WHEN d_day_name = 'Wednesday' THEN ss_sales_price END) AS wed_sales,
         SUM(CASE WHEN d_day_name = 'Thursday' THEN ss_sales_price END) AS thu_sales,
         SUM(CASE WHEN d_day_name = 'Friday' THEN ss_sales_price END) AS fri_sales,
         SUM(CASE WHEN d_day_name = 'Saturday' THEN ss_sales_price END) AS sat_sales
  FROM date_dim JOIN store_sales ON d_date_sk = ss_sold_date_sk
  GROUP BY d_week_seq, ss_store_sk
), named AS (
  SELECT wss.*, s_store_id, s_store_name
  FROM wss JOIN store ON ss_store_sk = s_store_sk
)
SELECT y1.s_store_name, y1.s_store_id, y1.d_week_seq,
       y1.sun_sales / y2.sun_sales AS sun_r,
       y1.mon_sales / y2.mon_sales AS mon_r,
       y1.tue_sales / y2.tue_sales AS tue_r,
       y1.wed_sales / y2.wed_sales AS wed_r,
       y1.thu_sales / y2.thu_sales AS thu_r,
       y1.fri_sales / y2.fri_sales AS fri_r,
       y1.sat_sales / y2.sat_sales AS sat_r
FROM named y1
JOIN named y2 ON y1.s_store_id = y2.s_store_id
  AND y2.d_week_seq - 52 = y1.d_week_seq
WHERE y1.d_week_seq BETWEEN 5 AND 20
  AND y2.d_week_seq BETWEEN 57 AND 72
ORDER BY y1.s_store_name, y1.s_store_id, y1.d_week_seq LIMIT 100
"""


SQL["q48"] = """
SELECT SUM(ss_quantity) AS total_qty
FROM store_sales
JOIN date_dim ON ss_sold_date_sk = d_date_sk AND d_year = 1999
JOIN customer_demographics ON ss_cdemo_sk = cd_demo_sk
JOIN customer ON ss_customer_sk = c_customer_sk
JOIN customer_address ON c_current_addr_sk = ca_address_sk
WHERE (cd_marital_status = 'M' AND cd_education_status = '4 yr Degree'
       AND ss_sales_price BETWEEN 100.0 AND 150.0)
   OR (cd_marital_status = 'D' AND cd_education_status = '2 yr Degree'
       AND ss_sales_price BETWEEN 50.0 AND 100.0)
   OR (ca_state IN ('TN', 'GA')
       AND ss_net_profit BETWEEN 0.0 AND 100.0)
"""

SQL["q56"] = """
WITH sel AS (
  SELECT DISTINCT i_item_id FROM item WHERE {cond}
), ch AS (
  SELECT i_item_id, SUM(ss_ext_sales_price) AS total_sales
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy = 2
  JOIN item ON ss_item_sk = i_item_sk
  WHERE i_item_id IN (SELECT i_item_id FROM sel)
  GROUP BY i_item_id
  UNION ALL
  SELECT i_item_id, SUM(cs_ext_sales_price)
  FROM catalog_sales
  JOIN date_dim ON cs_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy = 2
  JOIN item ON cs_item_sk = i_item_sk
  WHERE i_item_id IN (SELECT i_item_id FROM sel)
  GROUP BY i_item_id
  UNION ALL
  SELECT i_item_id, SUM(ws_ext_sales_price)
  FROM web_sales
  JOIN date_dim ON ws_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy = 2
  JOIN item ON ws_item_sk = i_item_sk
  WHERE i_item_id IN (SELECT i_item_id FROM sel)
  GROUP BY i_item_id
)
SELECT i_item_id, SUM(total_sales) AS total_sales
FROM ch GROUP BY i_item_id
ORDER BY {order} LIMIT 100
""".format(
    cond="i_color IN ('red', 'navy', 'khaki')",
    order="total_sales, i_item_id",
)

SQL["q60"] = """
WITH sel AS (
  SELECT DISTINCT i_item_id FROM item WHERE {cond}
), ch AS (
  SELECT i_item_id, SUM(ss_ext_sales_price) AS total_sales
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy = 2
  JOIN item ON ss_item_sk = i_item_sk
  WHERE i_item_id IN (SELECT i_item_id FROM sel)
  GROUP BY i_item_id
  UNION ALL
  SELECT i_item_id, SUM(cs_ext_sales_price)
  FROM catalog_sales
  JOIN date_dim ON cs_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy = 2
  JOIN item ON cs_item_sk = i_item_sk
  WHERE i_item_id IN (SELECT i_item_id FROM sel)
  GROUP BY i_item_id
  UNION ALL
  SELECT i_item_id, SUM(ws_ext_sales_price)
  FROM web_sales
  JOIN date_dim ON ws_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy = 2
  JOIN item ON ws_item_sk = i_item_sk
  WHERE i_item_id IN (SELECT i_item_id FROM sel)
  GROUP BY i_item_id
)
SELECT i_item_id, SUM(total_sales) AS total_sales
FROM ch GROUP BY i_item_id
ORDER BY {order} LIMIT 100
""".format(
    cond="i_category = 'Music'",
    order="i_item_id, total_sales",
)

SQL["q76"] = """
WITH allch AS (
  SELECT 'store' AS channel, 'ss_customer_sk' AS col_name,
         d_year, i_category, ss_ext_sales_price AS p
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
  JOIN item ON ss_item_sk = i_item_sk
  WHERE ss_customer_sk IS NULL
  UNION ALL
  SELECT 'web', 'ws_bill_customer_sk', d_year, i_category,
         ws_ext_sales_price
  FROM web_sales
  JOIN date_dim ON ws_sold_date_sk = d_date_sk
  JOIN item ON ws_item_sk = i_item_sk
  WHERE ws_bill_customer_sk IS NULL
  UNION ALL
  SELECT 'catalog', 'cs_bill_addr_sk', d_year, i_category,
         cs_ext_sales_price
  FROM catalog_sales
  JOIN date_dim ON cs_sold_date_sk = d_date_sk
  JOIN item ON cs_item_sk = i_item_sk
  WHERE cs_bill_addr_sk IS NULL
)
SELECT channel, col_name, d_year, i_category,
       COUNT(*) AS sales_cnt, SUM(p) AS sales_amt
FROM allch
GROUP BY channel, col_name, d_year, i_category
ORDER BY channel, col_name, d_year, i_category LIMIT 100
"""


SQL["q46"] = """
WITH per AS (
  SELECT ss_ticket_number, ss_customer_sk, ca_city AS bought_city,
         SUM({amt}) AS amt, SUM({profit}) AS profit
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
    AND d_dow IN (6, 0) AND d_year BETWEEN 1998 AND 2000
  JOIN store ON ss_store_sk = s_store_sk
    AND s_city IN ('Midway', 'Fairview')
  JOIN household_demographics ON ss_hdemo_sk = hd_demo_sk
    AND ({hd})
  JOIN customer_address ON ss_addr_sk = ca_address_sk
  GROUP BY ss_ticket_number, ss_customer_sk, ca_city
)
SELECT c_last_name, c_first_name, ss_ticket_number, bought_city,
       amt, profit
FROM per
JOIN customer ON ss_customer_sk = c_customer_sk
JOIN customer_address ON c_current_addr_sk = ca_address_sk
WHERE ca_city <> bought_city
ORDER BY {order} LIMIT 100
""".format(
    amt="ss_coupon_amt", profit="ss_net_profit",
    hd="hd_dep_count = 4 OR hd_vehicle_count = 3",
    order="c_last_name, c_first_name, bought_city, ss_ticket_number",
)

SQL["q68"] = """
WITH per AS (
  SELECT ss_ticket_number, ss_customer_sk, ca_city AS bought_city,
         SUM({amt}) AS amt, SUM({profit}) AS profit
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
    AND d_dow IN (6, 0) AND d_year BETWEEN 1998 AND 2000
  JOIN store ON ss_store_sk = s_store_sk
    AND s_city IN ('Midway', 'Fairview')
  JOIN household_demographics ON ss_hdemo_sk = hd_demo_sk
    AND ({hd})
  JOIN customer_address ON ss_addr_sk = ca_address_sk
  GROUP BY ss_ticket_number, ss_customer_sk, ca_city
)
SELECT c_last_name, c_first_name, ss_ticket_number, bought_city,
       amt, profit
FROM per
JOIN customer ON ss_customer_sk = c_customer_sk
JOIN customer_address ON c_current_addr_sk = ca_address_sk
WHERE ca_city <> bought_city
ORDER BY {order} LIMIT 100
""".format(
    amt="ss_ext_sales_price", profit="ss_ext_list_price",
    hd="hd_dep_count = 5 OR hd_vehicle_count = 3",
    order="c_last_name, ss_ticket_number",
)


SQL["q21"] = """
SELECT w_warehouse_name, i_item_id,
       SUM(CASE WHEN inv_date_sk < 500
                THEN inv_quantity_on_hand ELSE 0 END) AS inv_before,
       SUM(CASE WHEN inv_date_sk >= 500
                THEN inv_quantity_on_hand ELSE 0 END) AS inv_after
FROM inventory
JOIN date_dim ON inv_date_sk = d_date_sk
  AND d_date_sk BETWEEN 470 AND 530
JOIN warehouse ON inv_warehouse_sk = w_warehouse_sk
JOIN item ON inv_item_sk = i_item_sk
GROUP BY w_warehouse_name, i_item_id
HAVING inv_before > 0
  AND 1.0 * inv_after / inv_before >= 2.0 / 3.0
  AND 1.0 * inv_after / inv_before <= 3.0 / 2.0
ORDER BY w_warehouse_name, i_item_id LIMIT 100
"""

SQL["q81"] = """
WITH ctr AS (
  SELECT cr_returning_customer_sk AS cust, ca_state,
         SUM(cr_return_amount) AS total
  FROM catalog_returns
  JOIN date_dim ON cr_returned_date_sk = d_date_sk AND d_year = 2000
  JOIN customer_address ON cr_returning_addr_sk = ca_address_sk
  GROUP BY cr_returning_customer_sk, ca_state
)
SELECT c_customer_id, c_first_name, c_last_name, total
FROM ctr
JOIN (SELECT ca_state AS st2, AVG(total) AS avg_r FROM ctr
      WHERE ca_state IS NOT NULL GROUP BY ca_state)
  ON ctr.ca_state = st2
JOIN customer ON cust = c_customer_sk
JOIN customer_address ca2 ON c_current_addr_sk = ca2.ca_address_sk
  AND ca2.ca_state = 'GA'
WHERE total > 1.2 * avg_r
ORDER BY c_customer_id, total LIMIT 100
"""

SQL["q83"] = """
WITH d AS (
  SELECT d_date_sk FROM date_dim
  WHERE d_week_seq IN (20, 60, 100)
), sr AS (
  SELECT i_item_id, SUM(sr_return_quantity) AS qty
  FROM store_returns
  JOIN d ON sr_returned_date_sk = d_date_sk
  JOIN item ON sr_item_sk = i_item_sk GROUP BY i_item_id
), cr AS (
  SELECT i_item_id, SUM(cr_return_quantity) AS qty
  FROM catalog_returns
  JOIN d ON cr_returned_date_sk = d_date_sk
  JOIN item ON cr_item_sk = i_item_sk GROUP BY i_item_id
), wr AS (
  SELECT i_item_id, SUM(wr_return_quantity) AS qty
  FROM web_returns
  JOIN d ON wr_returned_date_sk = d_date_sk
  JOIN item ON wr_item_sk = i_item_sk GROUP BY i_item_id
)
SELECT sr.i_item_id AS item_id, sr.qty AS sr_qty,
       sr.qty / ((sr.qty + cr.qty + wr.qty) / 3.0) * 100.0 AS sr_dev,
       cr.qty AS cr_qty,
       cr.qty / ((sr.qty + cr.qty + wr.qty) / 3.0) * 100.0 AS cr_dev,
       wr.qty AS wr_qty,
       wr.qty / ((sr.qty + cr.qty + wr.qty) / 3.0) * 100.0 AS wr_dev,
       (sr.qty + cr.qty + wr.qty) / 3.0 AS average
FROM sr
JOIN cr ON sr.i_item_id = cr.i_item_id
JOIN wr ON sr.i_item_id = wr.i_item_id
ORDER BY item_id, sr_qty LIMIT 100
"""


SQL["q58"] = """
WITH d AS (
  SELECT d_date_sk FROM date_dim WHERE d_week_seq = 60
), ss AS (
  SELECT i_item_id, SUM(ss_ext_sales_price) AS rev
  FROM store_sales JOIN d ON ss_sold_date_sk = d_date_sk
  JOIN item ON ss_item_sk = i_item_sk GROUP BY i_item_id
), cs AS (
  SELECT i_item_id, SUM(cs_ext_sales_price) AS rev
  FROM catalog_sales JOIN d ON cs_sold_date_sk = d_date_sk
  JOIN item ON cs_item_sk = i_item_sk GROUP BY i_item_id
), ws AS (
  SELECT i_item_id, SUM(ws_ext_sales_price) AS rev
  FROM web_sales JOIN d ON ws_sold_date_sk = d_date_sk
  JOIN item ON ws_item_sk = i_item_sk GROUP BY i_item_id
)
SELECT ss.i_item_id AS item_id, ss.rev AS ss_rev, cs.rev AS cs_rev,
       ws.rev AS ws_rev, (ss.rev + cs.rev + ws.rev) / 3.0 AS average
FROM ss
JOIN cs ON ss.i_item_id = cs.i_item_id
JOIN ws ON ss.i_item_id = ws.i_item_id
WHERE ss.rev BETWEEN 0.9 * (ss.rev + cs.rev + ws.rev) / 3.0
                 AND 1.1 * (ss.rev + cs.rev + ws.rev) / 3.0
  AND cs.rev BETWEEN 0.9 * (ss.rev + cs.rev + ws.rev) / 3.0
                 AND 1.1 * (ss.rev + cs.rev + ws.rev) / 3.0
  AND ws.rev BETWEEN 0.9 * (ss.rev + cs.rev + ws.rev) / 3.0
                 AND 1.1 * (ss.rev + cs.rev + ws.rev) / 3.0
ORDER BY item_id, ss_rev LIMIT 100
"""

SQL["q71"] = """
WITH allch AS (
  SELECT ws_ext_sales_price AS ext_price, ws_item_sk AS item_sk,
         ws_sold_time_sk AS time_sk
  FROM web_sales
  JOIN date_dim ON ws_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy = 12
  UNION ALL
  SELECT cs_ext_sales_price, cs_item_sk, cs_sold_time_sk
  FROM catalog_sales
  JOIN date_dim ON cs_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy = 12
  UNION ALL
  SELECT ss_ext_sales_price, ss_item_sk, ss_sold_time_sk
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
    AND d_year = 1999 AND d_moy = 12
)
SELECT i_brand_id, i_brand, t_hour, t_minute,
       SUM(ext_price) AS ext_price
FROM allch
JOIN item ON item_sk = i_item_sk AND i_manager_id = 1
JOIN time_dim ON time_sk = t_time_sk
  AND (t_hour BETWEEN 7 AND 8 OR t_hour BETWEEN 18 AND 19)
GROUP BY i_brand_id, i_brand, t_hour, t_minute
ORDER BY ext_price DESC, i_brand_id, t_hour, t_minute
"""

SQL["q44"] = """
WITH base AS (
  SELECT ss_item_sk, ss_customer_sk, ss_net_profit
  FROM store_sales WHERE ss_store_sk = 4
), nullavg AS (
  SELECT AVG(ss_net_profit) AS na FROM base
  WHERE ss_customer_sk IS NULL
), by_item AS (
  SELECT ss_item_sk, AVG(ss_net_profit) AS rank_col
  FROM base GROUP BY ss_item_sk
), q AS (
  SELECT ss_item_sk, rank_col FROM by_item, nullavg
  WHERE rank_col > 0.9 * na
), ranked AS (
  SELECT ss_item_sk,
         RANK() OVER (ORDER BY rank_col ASC) AS rnk_a,
         RANK() OVER (ORDER BY rank_col DESC) AS rnk_d
  FROM q
)
SELECT a.rnk_a AS a_rnk, ia.i_product_name AS best_performing,
       id.i_product_name AS worst_performing
FROM ranked a
JOIN ranked d ON a.rnk_a = d.rnk_d
JOIN item ia ON a.ss_item_sk = ia.i_item_sk
JOIN item id ON d.ss_item_sk = id.i_item_sk
WHERE a.rnk_a <= 10
ORDER BY a_rnk
"""




# ---------------------------------------------------------------------------
# round-4 additions: the 24 formulations that closed the 99/99 matrix
# ---------------------------------------------------------------------------

SQL["q5"] = """
WITH ch AS (
  SELECT 'store channel' AS channel, ss_sold_date_sk AS date_sk,
         ss_item_sk AS id, ss_ext_sales_price AS sales_price,
         0.0 AS return_amt FROM store_sales
  UNION ALL
  SELECT 'store channel', sr_returned_date_sk, sr_item_sk, 0.0,
         sr_return_amt FROM store_returns
  UNION ALL
  SELECT 'catalog channel', cs_sold_date_sk, cs_item_sk,
         cs_ext_sales_price, 0.0 FROM catalog_sales
  UNION ALL
  SELECT 'catalog channel', cr_returned_date_sk, cr_item_sk, 0.0,
         cr_return_amount FROM catalog_returns
  UNION ALL
  SELECT 'web channel', ws_sold_date_sk, ws_item_sk,
         ws_ext_sales_price, 0.0 FROM web_sales
  UNION ALL
  SELECT 'web channel', wr_returned_date_sk, wr_item_sk, 0.0,
         wr_return_amt FROM web_returns
),
detail AS (
  SELECT channel, id, SUM(sales_price) AS sales,
         SUM(return_amt) AS returns_
  FROM ch JOIN date_dim ON date_sk = d_date_sk AND d_year = 1998
  GROUP BY channel, id
)
SELECT channel, id, sales, returns_ FROM detail
UNION ALL
SELECT channel, NULL, SUM(sales), SUM(returns_) FROM detail
GROUP BY channel
UNION ALL
SELECT NULL, NULL, SUM(sales), SUM(returns_) FROM detail
"""

SQL["q10"] = """
WITH d AS (SELECT d_date_sk FROM date_dim
           WHERE d_year = 2000 AND d_moy BETWEEN 1 AND 4)
SELECT cd_gender, cd_marital_status, cd_education_status,
       cd_purchase_estimate, cd_credit_rating, COUNT(*) AS cnt
FROM customer
JOIN customer_address ON c_current_addr_sk = ca_address_sk
     AND ca_county IN ('Rich County', 'Walker County')
JOIN customer_demographics ON c_current_cdemo_sk = cd_demo_sk
WHERE c_customer_sk IN (
        SELECT ss_customer_sk FROM store_sales
        JOIN d ON ss_sold_date_sk = d_date_sk)
  AND c_customer_sk IN (
        SELECT ws_bill_customer_sk FROM web_sales
        JOIN d ON ws_sold_date_sk = d_date_sk
        UNION
        SELECT cs_bill_customer_sk FROM catalog_sales
        JOIN d ON cs_sold_date_sk = d_date_sk)
GROUP BY cd_gender, cd_marital_status, cd_education_status,
         cd_purchase_estimate, cd_credit_rating
ORDER BY cd_gender NULLS FIRST, cd_marital_status NULLS FIRST,
         cd_education_status NULLS FIRST,
         cd_purchase_estimate NULLS FIRST,
         cd_credit_rating NULLS FIRST
LIMIT 100
"""

SQL["q14"] = """
WITH cross_pairs AS (
  SELECT i_brand_id, i_manufact_id FROM store_sales
  JOIN item ON ss_item_sk = i_item_sk
  INTERSECT
  SELECT i_brand_id, i_manufact_id FROM catalog_sales
  JOIN item ON cs_item_sk = i_item_sk
  INTERSECT
  SELECT i_brand_id, i_manufact_id FROM web_sales
  JOIN item ON ws_item_sk = i_item_sk
),
cross_items AS (
  SELECT i_item_sk FROM item
  JOIN cross_pairs USING (i_brand_id, i_manufact_id)
),
all_sales AS (
  SELECT ss_item_sk AS item_sk, ss_ext_sales_price AS sales
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk AND d_year = 1999
  UNION ALL
  SELECT cs_item_sk, cs_ext_sales_price FROM catalog_sales
  JOIN date_dim ON cs_sold_date_sk = d_date_sk AND d_year = 1999
  UNION ALL
  SELECT ws_item_sk, ws_ext_sales_price FROM web_sales
  JOIN date_dim ON ws_sold_date_sk = d_date_sk AND d_year = 1999
),
by_brand AS (
  SELECT i_brand_id AS brand_id, SUM(sales) AS sales,
         COUNT(*) AS number_sales
  FROM all_sales
  JOIN item ON item_sk = i_item_sk
  WHERE item_sk IN (SELECT i_item_sk FROM cross_items)
  GROUP BY i_brand_id
),
detail AS (
  SELECT * FROM by_brand
  WHERE sales > (SELECT AVG(sales) FROM all_sales)
)
SELECT brand_id, sales, number_sales FROM detail
UNION ALL
SELECT NULL, SUM(sales), SUM(number_sales) FROM detail
"""

SQL["q23"] = """
WITH frequent AS (
  SELECT ss_item_sk FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk AND d_year = 2000
  GROUP BY ss_item_sk HAVING COUNT(*) > 2
),
csales AS (
  SELECT ss_customer_sk AS cust,
         SUM(CAST(ss_quantity AS REAL) * ss_sales_price) AS v
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
       AND d_year IN (2000, 2001)
  WHERE ss_customer_sk IS NOT NULL
  GROUP BY ss_customer_sk
),
best AS (
  SELECT cust FROM csales
  WHERE v > 0.5 * (SELECT MAX(v) FROM csales)
),
month AS (SELECT d_date_sk FROM date_dim
          WHERE d_year = 2000 AND d_moy = 3)
SELECT (SELECT SUM(CAST(cs_quantity AS REAL) * cs_list_price)
        FROM catalog_sales
        JOIN month ON cs_sold_date_sk = d_date_sk
        WHERE cs_item_sk IN (SELECT ss_item_sk FROM frequent)
          AND cs_bill_customer_sk IN (SELECT cust FROM best))
     + (SELECT SUM(CAST(ws_quantity AS REAL) * ws_list_price)
        FROM web_sales
        JOIN month ON ws_sold_date_sk = d_date_sk
        WHERE ws_item_sk IN (SELECT ss_item_sk FROM frequent)
          AND ws_bill_customer_sk IN (SELECT cust FROM best))
       AS total
"""

SQL["q24"] = """
WITH ssales AS (
  SELECT c_last_name, c_first_name, s_store_name, i_color,
         SUM(ss_net_paid) AS netpaid
  FROM store_sales
  JOIN store_returns ON ss_ticket_number = sr_ticket_number
       AND ss_item_sk = sr_item_sk
  JOIN store ON ss_store_sk = s_store_sk AND s_market_id <= 5
  JOIN item ON ss_item_sk = i_item_sk
  JOIN customer ON ss_customer_sk = c_customer_sk
  JOIN customer_address ON c_current_addr_sk = ca_address_sk
       AND ca_state IS NOT NULL AND s_state = ca_state
  GROUP BY c_last_name, c_first_name, s_store_name, i_color
)
SELECT c_last_name, c_first_name, s_store_name, i_color, netpaid
FROM ssales
WHERE netpaid > 0.05 * (SELECT AVG(netpaid) FROM ssales)
ORDER BY c_last_name NULLS FIRST, c_first_name NULLS FIRST,
         s_store_name NULLS FIRST, i_color NULLS FIRST
LIMIT 100
"""

SQL["q39"] = """
WITH stats AS (
  SELECT d_moy AS moy, inv_warehouse_sk AS w, inv_item_sk AS i,
         AVG(CAST(inv_quantity_on_hand AS REAL)) AS mean,
         COUNT(*) AS n,
         SUM(CAST(inv_quantity_on_hand AS REAL)
             * inv_quantity_on_hand) AS s2,
         SUM(CAST(inv_quantity_on_hand AS REAL)) AS s1
  FROM inventory
  JOIN date_dim ON inv_date_sk = d_date_sk AND d_year = 1999
       AND d_moy IN (1, 2)
  GROUP BY d_moy, inv_warehouse_sk, inv_item_sk
),
cov AS (
  SELECT moy, w, i, mean,
         SQRT((s2 - s1 * s1 / n) / (n - 1)) / mean AS cov
  FROM stats WHERE n > 1 AND mean != 0
)
SELECT a.w AS w_warehouse_sk, a.i AS i_item_sk,
       a.mean AS mean1, a.cov AS cov1,
       b.mean AS mean2, b.cov AS cov2
FROM cov a JOIN cov b ON a.w = b.w AND a.i = b.i
     AND a.moy = 1 AND b.moy = 2
WHERE a.cov > 1.0 AND b.cov > 1.0
ORDER BY a.w, a.i
"""

_Q47_LIKE = """
WITH agg AS (
  SELECT i_category, i_brand, {entity_cols}, d_year, d_moy,
         SUM({sum_col}) AS sum_sales
  FROM {sales}
  JOIN date_dim ON {date_col} = d_date_sk
       AND d_year BETWEEN 1998 AND 2000
  JOIN item ON {item_fk} = i_item_sk
  JOIN {entity} ON {entity_fk} = {entity_sk}
  GROUP BY i_category, i_brand, {entity_cols}, d_year, d_moy
),
win AS (
  SELECT *,
         AVG(sum_sales) OVER (
           PARTITION BY i_category, i_brand, {entity_cols}, d_year
         ) AS avg_monthly_sales,
         LAG(sum_sales) OVER (
           PARTITION BY i_category, i_brand, {entity_cols}
           ORDER BY d_year, d_moy) AS psum,
         LEAD(sum_sales) OVER (
           PARTITION BY i_category, i_brand, {entity_cols}
           ORDER BY d_year, d_moy) AS nsum
  FROM agg
)
SELECT i_category, i_brand, {entity_cols}, d_year, d_moy, sum_sales,
       avg_monthly_sales, psum, nsum
FROM win
WHERE d_year = 1999 AND avg_monthly_sales > 0
  AND ABS(sum_sales - avg_monthly_sales) / avg_monthly_sales > 0.1
ORDER BY sum_sales - avg_monthly_sales, i_category NULLS FIRST,
         i_brand NULLS FIRST, {order_tail}, d_year, d_moy
LIMIT 100
"""

SQL["q47"] = _Q47_LIKE.format(
    sales="store_sales", date_col="ss_sold_date_sk",
    item_fk="ss_item_sk", sum_col="ss_sales_price",
    entity="store", entity_sk="s_store_sk", entity_fk="ss_store_sk",
    entity_cols="s_store_name, s_company_name",
    order_tail="s_store_name NULLS FIRST, s_company_name NULLS FIRST",
)

SQL["q57"] = _Q47_LIKE.format(
    sales="catalog_sales", date_col="cs_sold_date_sk",
    item_fk="cs_item_sk", sum_col="cs_sales_price",
    entity="call_center", entity_sk="cc_call_center_sk",
    entity_fk="cs_call_center_sk", entity_cols="cc_name",
    order_tail="cc_name NULLS FIRST",
)

SQL["q49"] = """
WITH chan AS (
  SELECT 'web' AS channel, ws_item_sk AS item, ws_quantity AS qty,
         ws_ext_sales_price AS amt, wr_return_quantity AS rqty,
         wr_return_amt AS ramt
  FROM web_sales
  LEFT JOIN web_returns ON ws_order_number = wr_order_number
       AND ws_item_sk = wr_item_sk
  UNION ALL
  SELECT 'catalog', cs_item_sk, cs_quantity, cs_ext_sales_price,
         cr_return_quantity, cr_return_amount
  FROM catalog_sales
  LEFT JOIN catalog_returns ON cs_order_number = cr_order_number
       AND cs_item_sk = cr_item_sk
  UNION ALL
  SELECT 'store', ss_item_sk, ss_quantity, ss_ext_sales_price,
         sr_return_quantity, sr_return_amt
  FROM store_sales
  LEFT JOIN store_returns ON ss_ticket_number = sr_ticket_number
       AND ss_item_sk = sr_item_sk
),
g AS (
  SELECT channel, item,
         CAST(SUM(COALESCE(rqty, 0)) AS REAL) / SUM(qty) AS qty_ratio,
         SUM(COALESCE(ramt, 0.0)) / SUM(amt) AS amt_ratio
  FROM chan GROUP BY channel, item
),
r AS (
  SELECT channel, item, amt_ratio,
         RANK() OVER (PARTITION BY channel
                      ORDER BY qty_ratio NULLS LAST) AS return_rank,
         RANK() OVER (PARTITION BY channel
                      ORDER BY amt_ratio NULLS LAST) AS currency_rank
  FROM g
)
SELECT channel, item, amt_ratio AS return_ratio, return_rank,
       currency_rank
FROM r
WHERE return_rank <= 10 OR currency_rank <= 10
ORDER BY channel, return_rank, currency_rank, item
LIMIT 100
"""




SQL["q54"] = """
WITH my_customers AS (
  SELECT DISTINCT customer_sk FROM (
    SELECT cs_sold_date_sk AS sold_date_sk, cs_item_sk AS item_sk,
           cs_bill_customer_sk AS customer_sk FROM catalog_sales
    UNION ALL
    SELECT ws_sold_date_sk, ws_item_sk, ws_bill_customer_sk
    FROM web_sales
  )
  JOIN item ON item_sk = i_item_sk AND i_category = 'Books'
  JOIN date_dim ON sold_date_sk = d_date_sk
       AND d_year = 1999 AND d_moy = 3
  WHERE customer_sk IS NOT NULL
),
eligible AS (
  SELECT DISTINCT c_customer_sk
  FROM customer
  JOIN my_customers ON c_customer_sk = customer_sk
  JOIN customer_address ON c_current_addr_sk = ca_address_sk
  JOIN (SELECT DISTINCT s_county, s_state FROM store)
       ON ca_county = s_county AND ca_state = s_state
),
rev AS (
  SELECT c_customer_sk AS cust,
         SUM(ss_ext_sales_price) AS revenue
  FROM eligible
  JOIN store_sales ON c_customer_sk = ss_customer_sk
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
       AND d_month_seq BETWEEN 1191 AND 1193
  GROUP BY c_customer_sk
)
SELECT CAST(revenue / 50.0 AS INTEGER) AS segment,
       COUNT(*) AS num_customers,
       CAST(revenue / 50.0 AS INTEGER) * 50 AS segment_base
FROM rev
GROUP BY CAST(revenue / 50.0 AS INTEGER)
ORDER BY segment, num_customers
LIMIT 100
"""

SQL["q64"] = """
WITH ui AS (
  SELECT cs_item_sk AS item
  FROM catalog_sales
  JOIN catalog_returns ON cs_order_number = cr_order_number
       AND cs_item_sk = cr_item_sk
  GROUP BY cs_item_sk
  HAVING SUM(cs_ext_list_price)
         > (SUM(cr_return_amount) + SUM(cr_net_loss)) * 2.0
),
cs_base AS (
  SELECT d_year, i_product_name, ss_item_sk, s_store_name, s_zip,
         ss_ext_wholesale_cost, ss_ext_list_price, ss_coupon_amt
  FROM store_sales
  JOIN store_returns ON ss_ticket_number = sr_ticket_number
       AND ss_item_sk = sr_item_sk
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
       AND d_year IN (1999, 2000)
  JOIN store ON ss_store_sk = s_store_sk
  JOIN customer ON ss_customer_sk = c_customer_sk
  JOIN household_demographics ON c_current_hdemo_sk = hd_demo_sk
  JOIN income_band ON hd_income_band_sk = ib_income_band_sk
  JOIN customer_address ca1 ON c_current_addr_sk = ca1.ca_address_sk
  JOIN customer_address ca2 ON ss_addr_sk = ca2.ca_address_sk
  JOIN item ON ss_item_sk = i_item_sk
       AND i_color IN ('red', 'navy', 'khaki')
  WHERE ss_item_sk IN (SELECT item FROM ui)
),
per_year AS (
  SELECT d_year, i_product_name, ss_item_sk, s_store_name, s_zip,
         COUNT(*) AS cnt, SUM(ss_ext_wholesale_cost) AS s1,
         SUM(ss_ext_list_price) AS s2, SUM(ss_coupon_amt) AS s3
  FROM cs_base
  GROUP BY d_year, i_product_name, ss_item_sk, s_store_name, s_zip
)
SELECT y1.i_product_name, y1.s_store_name, y1.s_zip,
       y1.cnt AS y1_cnt, y1.s1 AS y1_s1, y2.cnt AS y2_cnt,
       y2.s1 AS y2_s1
FROM per_year y1
JOIN per_year y2 ON y1.ss_item_sk = y2.ss_item_sk
     AND y1.s_store_name = y2.s_store_name AND y1.s_zip = y2.s_zip
     AND y1.d_year = 1999 AND y2.d_year = 2000
WHERE y2.cnt <= y1.cnt
ORDER BY y1.i_product_name NULLS FIRST, y1.s_store_name NULLS FIRST,
         y1.s1 NULLS FIRST
LIMIT 100
"""

SQL["q66"] = """
WITH both_ch AS (
  SELECT w_warehouse_name AS wn, d_moy,
         ws_ext_sales_price AS price
  FROM web_sales
  JOIN date_dim ON ws_sold_date_sk = d_date_sk AND d_year = 1999
  JOIN ship_mode ON ws_ship_mode_sk = sm_ship_mode_sk
       AND sm_type IN ('EXPRESS', 'REGULAR')
  JOIN warehouse ON ws_warehouse_sk = w_warehouse_sk
  UNION ALL
  SELECT w_warehouse_name, d_moy, cs_ext_sales_price
  FROM catalog_sales
  JOIN date_dim ON cs_sold_date_sk = d_date_sk AND d_year = 1999
  JOIN ship_mode ON cs_ship_mode_sk = sm_ship_mode_sk
       AND sm_type IN ('EXPRESS', 'REGULAR')
  JOIN warehouse ON cs_warehouse_sk = w_warehouse_sk
)
SELECT wn AS w_warehouse_name,
       SUM(CASE WHEN d_moy = 1 THEN price END) AS m1_sales,
       SUM(CASE WHEN d_moy = 2 THEN price END) AS m2_sales,
       SUM(CASE WHEN d_moy = 3 THEN price END) AS m3_sales,
       SUM(CASE WHEN d_moy = 4 THEN price END) AS m4_sales,
       SUM(CASE WHEN d_moy = 5 THEN price END) AS m5_sales,
       SUM(CASE WHEN d_moy = 6 THEN price END) AS m6_sales,
       SUM(CASE WHEN d_moy = 7 THEN price END) AS m7_sales,
       SUM(CASE WHEN d_moy = 8 THEN price END) AS m8_sales,
       SUM(CASE WHEN d_moy = 9 THEN price END) AS m9_sales,
       SUM(CASE WHEN d_moy = 10 THEN price END) AS m10_sales,
       SUM(CASE WHEN d_moy = 11 THEN price END) AS m11_sales,
       SUM(CASE WHEN d_moy = 12 THEN price END) AS m12_sales
FROM both_ch
GROUP BY wn
ORDER BY wn
LIMIT 100
"""

SQL["q67"] = """
WITH base AS (
  SELECT i_category, i_class, i_brand, i_product_name, d_year, d_qoy,
         d_moy, s_store_id,
         SUM(ss_sales_price * CAST(ss_quantity AS REAL)) AS sumsales
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
       AND d_month_seq BETWEEN 1188 AND 1199
  JOIN item ON ss_item_sk = i_item_sk
  JOIN store ON ss_store_sk = s_store_sk
  GROUP BY i_category, i_class, i_brand, i_product_name, d_year,
           d_qoy, d_moy, s_store_id
),
rolled AS (
  SELECT * FROM base
  UNION ALL
  SELECT i_category, i_class, i_brand, i_product_name, d_year, d_qoy,
         d_moy, NULL, SUM(sumsales) FROM base
  GROUP BY 1, 2, 3, 4, 5, 6, 7
  UNION ALL
  SELECT i_category, i_class, i_brand, i_product_name, d_year, d_qoy,
         NULL, NULL, SUM(sumsales) FROM base GROUP BY 1, 2, 3, 4, 5, 6
  UNION ALL
  SELECT i_category, i_class, i_brand, i_product_name, d_year, NULL,
         NULL, NULL, SUM(sumsales) FROM base GROUP BY 1, 2, 3, 4, 5
  UNION ALL
  SELECT i_category, i_class, i_brand, i_product_name, NULL, NULL,
         NULL, NULL, SUM(sumsales) FROM base GROUP BY 1, 2, 3, 4
  UNION ALL
  SELECT i_category, i_class, i_brand, NULL, NULL, NULL, NULL, NULL,
         SUM(sumsales) FROM base GROUP BY 1, 2, 3
  UNION ALL
  SELECT i_category, i_class, NULL, NULL, NULL, NULL, NULL, NULL,
         SUM(sumsales) FROM base GROUP BY 1, 2
  UNION ALL
  SELECT i_category, NULL, NULL, NULL, NULL, NULL, NULL, NULL,
         SUM(sumsales) FROM base GROUP BY 1
  UNION ALL
  SELECT NULL, NULL, NULL, NULL, NULL, NULL, NULL, NULL,
         SUM(sumsales) FROM base
),
ranked AS (
  SELECT *, RANK() OVER (PARTITION BY i_category
                         ORDER BY sumsales DESC) AS rk
  FROM rolled
)
SELECT i_category, i_class, i_brand, i_product_name, d_year, d_qoy,
       d_moy, s_store_id, sumsales, rk
FROM ranked WHERE rk <= 100
ORDER BY i_category NULLS FIRST, i_class NULLS FIRST,
         i_brand NULLS FIRST, i_product_name NULLS FIRST,
         d_year NULLS FIRST, d_qoy NULLS FIRST, d_moy NULLS FIRST,
         s_store_id NULLS FIRST, sumsales NULLS FIRST, rk
LIMIT 100
"""

SQL["q70"] = """
WITH j AS (
  SELECT s_state, s_county, ss_net_profit
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
       AND d_month_seq BETWEEN 1188 AND 1199
  JOIN store ON ss_store_sk = s_store_sk
),
top_states AS (
  SELECT s_state FROM (
    SELECT s_state,
           RANK() OVER (ORDER BY SUM(ss_net_profit) DESC) AS rnk
    FROM j GROUP BY s_state
  ) WHERE rnk <= 5
),
base AS (
  SELECT s_state, s_county, SUM(ss_net_profit) AS total_sum
  FROM j WHERE s_state IN (SELECT s_state FROM top_states)
  GROUP BY s_state, s_county
),
rolled AS (
  SELECT s_state, s_county, total_sum, 0 AS lochierarchy FROM base
  UNION ALL
  SELECT s_state, NULL, SUM(total_sum), 1 FROM base GROUP BY s_state
  UNION ALL
  SELECT NULL, NULL, SUM(total_sum), 2 FROM base
),
ranked AS (
  SELECT *, RANK() OVER (
    PARTITION BY lochierarchy,
                 CASE WHEN lochierarchy = 0 THEN s_state END
    ORDER BY total_sum DESC) AS rank_within_parent
  FROM rolled
)
SELECT s_state, s_county, total_sum, lochierarchy, rank_within_parent
FROM ranked
ORDER BY lochierarchy DESC, s_state NULLS FIRST,
         s_county NULLS FIRST, rank_within_parent
LIMIT 100
"""

SQL["q72"] = """
SELECT i_item_desc, w_warehouse_name, sold_week.d_week_seq AS week,
       COUNT(*) AS no_promo
FROM catalog_sales
JOIN date_dim sold_week ON cs_sold_date_sk = sold_week.d_date_sk
     AND sold_week.d_year = 1999
JOIN inventory ON cs_item_sk = inv_item_sk
JOIN warehouse ON inv_warehouse_sk = w_warehouse_sk
JOIN date_dim inv_week ON inv_date_sk = inv_week.d_date_sk
     AND inv_week.d_week_seq = sold_week.d_week_seq
JOIN household_demographics ON cs_bill_hdemo_sk = hd_demo_sk
     AND hd_buy_potential = '>10000'
JOIN customer_demographics ON cs_bill_cdemo_sk = cd_demo_sk
     AND cd_marital_status = 'M'
JOIN item ON cs_item_sk = i_item_sk
WHERE CAST(cs_ship_date_sk AS REAL) - cs_sold_date_sk > 5
  AND inv_quantity_on_hand < cs_quantity
GROUP BY i_item_desc, w_warehouse_name, sold_week.d_week_seq
ORDER BY no_promo DESC, i_item_desc, w_warehouse_name, week
LIMIT 100
"""

SQL["q74"] = """
WITH s_yt AS (
  SELECT c_customer_sk, c_customer_id, c_first_name, c_last_name,
         d_year, SUM(ss_sales_price) AS yt
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
       AND d_year BETWEEN 1998 AND 1999
  JOIN customer ON ss_customer_sk = c_customer_sk
  GROUP BY c_customer_sk, c_customer_id, c_first_name, c_last_name,
           d_year
),
w_yt AS (
  SELECT c_customer_sk, d_year, SUM(ws_ext_sales_price) AS yt
  FROM web_sales
  JOIN date_dim ON ws_sold_date_sk = d_date_sk
       AND d_year BETWEEN 1998 AND 1999
  JOIN customer ON ws_bill_customer_sk = c_customer_sk
  GROUP BY c_customer_sk, d_year
)
SELECT s1.c_customer_id AS customer_id,
       s1.c_first_name AS first_name, s1.c_last_name AS last_name
FROM s_yt s1
JOIN s_yt s2 ON s1.c_customer_sk = s2.c_customer_sk
     AND s1.d_year = 1998 AND s2.d_year = 1999
JOIN w_yt w1 ON s1.c_customer_sk = w1.c_customer_sk
     AND w1.d_year = 1998
JOIN w_yt w2 ON s1.c_customer_sk = w2.c_customer_sk
     AND w2.d_year = 1999
WHERE s1.yt > 0 AND w1.yt > 0 AND w2.yt / w1.yt > s2.yt / s1.yt
ORDER BY s1.c_customer_id
LIMIT 100
"""

SQL["q75"] = """
WITH allch AS (
  SELECT d_year, i_brand_id,
         cs_quantity - COALESCE(cr_return_quantity, 0) AS sales_cnt,
         cs_ext_sales_price - COALESCE(cr_return_amount, 0.0)
           AS sales_amt
  FROM catalog_sales
  JOIN date_dim ON cs_sold_date_sk = d_date_sk
       AND d_year BETWEEN 1998 AND 1999
  JOIN item ON cs_item_sk = i_item_sk AND i_category = 'Books'
  LEFT JOIN catalog_returns ON cs_order_number = cr_order_number
       AND cs_item_sk = cr_item_sk
  UNION ALL
  SELECT d_year, i_brand_id,
         ss_quantity - COALESCE(sr_return_quantity, 0),
         ss_ext_sales_price - COALESCE(sr_return_amt, 0.0)
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk
       AND d_year BETWEEN 1998 AND 1999
  JOIN item ON ss_item_sk = i_item_sk AND i_category = 'Books'
  LEFT JOIN store_returns ON ss_ticket_number = sr_ticket_number
       AND ss_item_sk = sr_item_sk
  UNION ALL
  SELECT d_year, i_brand_id,
         ws_quantity - COALESCE(wr_return_quantity, 0),
         ws_ext_sales_price - COALESCE(wr_return_amt, 0.0)
  FROM web_sales
  JOIN date_dim ON ws_sold_date_sk = d_date_sk
       AND d_year BETWEEN 1998 AND 1999
  JOIN item ON ws_item_sk = i_item_sk AND i_category = 'Books'
  LEFT JOIN web_returns ON ws_order_number = wr_order_number
       AND ws_item_sk = wr_item_sk
),
by_year AS (
  SELECT d_year, i_brand_id, SUM(sales_cnt) AS cnt,
         SUM(sales_amt) AS amt
  FROM allch GROUP BY d_year, i_brand_id
)
SELECT p.d_year AS prev_year, c.d_year AS year, c.i_brand_id,
       p.cnt AS prev_yr_cnt, c.cnt AS curr_yr_cnt,
       c.cnt - p.cnt AS sales_cnt_diff, c.amt - p.amt AS sales_amt_diff
FROM by_year p
JOIN by_year c ON p.i_brand_id = c.i_brand_id
     AND p.d_year = 1998 AND c.d_year = 1999
WHERE CAST(c.cnt AS REAL) / p.cnt < 0.9
ORDER BY sales_cnt_diff, c.i_brand_id
LIMIT 100
"""

SQL["q77"] = """
WITH d AS (SELECT d_date_sk FROM date_dim
           WHERE d_year = 1999 AND d_moy <= 2),
ss AS (
  SELECT ss_store_sk AS id, SUM(ss_ext_sales_price) AS sales,
         SUM(ss_net_profit) AS profit
  FROM store_sales JOIN d ON ss_sold_date_sk = d_date_sk
  GROUP BY ss_store_sk
),
sr AS (
  SELECT sr_store_sk AS id, SUM(sr_return_amt) AS returns_,
         SUM(sr_net_loss) AS loss
  FROM store_returns JOIN d ON sr_returned_date_sk = d_date_sk
  GROUP BY sr_store_sk
),
ws AS (
  SELECT ws_web_page_sk AS id, SUM(ws_ext_sales_price) AS sales,
         SUM(ws_ext_discount_amt) AS profit
  FROM web_sales JOIN d ON ws_sold_date_sk = d_date_sk
  GROUP BY ws_web_page_sk
),
wr AS (
  SELECT wr_web_page_sk AS id, SUM(wr_return_amt) AS returns_,
         SUM(wr_net_loss) AS loss
  FROM web_returns JOIN d ON wr_returned_date_sk = d_date_sk
  GROUP BY wr_web_page_sk
),
detail AS (
  SELECT 'store channel' AS channel, ss.id AS id, ss.sales,
         COALESCE(sr.returns_, 0.0) AS returns_,
         ss.profit - COALESCE(sr.loss, 0.0) AS profit
  FROM ss LEFT JOIN sr ON ss.id = sr.id
  UNION ALL
  SELECT 'catalog channel', NULL,
         (SELECT SUM(cs_ext_sales_price) FROM catalog_sales
          JOIN d ON cs_sold_date_sk = d_date_sk),
         (SELECT SUM(cr_return_amount) FROM catalog_returns
          JOIN d ON cr_returned_date_sk = d_date_sk),
         (SELECT SUM(cs_ext_discount_amt) FROM catalog_sales
          JOIN d ON cs_sold_date_sk = d_date_sk)
         - (SELECT SUM(cr_net_loss) FROM catalog_returns
            JOIN d ON cr_returned_date_sk = d_date_sk)
  UNION ALL
  SELECT 'web channel', ws.id, ws.sales,
         COALESCE(wr.returns_, 0.0),
         ws.profit - COALESCE(wr.loss, 0.0)
  FROM ws LEFT JOIN wr ON ws.id = wr.id
),
rolled AS (
  SELECT channel, id, sales, returns_, profit FROM detail
  UNION ALL
  SELECT channel, NULL, SUM(sales), SUM(returns_), SUM(profit)
  FROM detail GROUP BY channel
  UNION ALL
  SELECT NULL, NULL, SUM(sales), SUM(returns_), SUM(profit)
  FROM detail
)
SELECT channel, id, sales, returns_, profit FROM rolled
ORDER BY channel NULLS FIRST, id NULLS FIRST, sales NULLS FIRST
LIMIT 100
"""

SQL["q78"] = """
WITH ss AS (
  SELECT ss_item_sk AS item, ss_customer_sk AS cust,
         SUM(ss_quantity) AS qty, SUM(ss_ext_sales_price) AS amt
  FROM store_sales
  JOIN date_dim ON ss_sold_date_sk = d_date_sk AND d_year = 1999
  WHERE NOT EXISTS (SELECT 1 FROM store_returns
                    WHERE sr_ticket_number = ss_ticket_number
                      AND sr_item_sk = ss_item_sk)
    AND ss_customer_sk IS NOT NULL
  GROUP BY ss_item_sk, ss_customer_sk
),
ws AS (
  SELECT ws_item_sk AS item, ws_bill_customer_sk AS cust,
         SUM(ws_quantity) AS qty, SUM(ws_ext_sales_price) AS amt
  FROM web_sales
  JOIN date_dim ON ws_sold_date_sk = d_date_sk AND d_year = 1999
  WHERE NOT EXISTS (SELECT 1 FROM web_returns
                    WHERE wr_order_number = ws_order_number
                      AND wr_item_sk = ws_item_sk)
    AND ws_bill_customer_sk IS NOT NULL
  GROUP BY ws_item_sk, ws_bill_customer_sk
)
SELECT ss.item, ss.cust, ss.qty AS ss_qty,
       CAST(ws.qty AS REAL) / ss.qty AS ratio,
       ss.amt AS ss_amt, ws.amt AS ws_amt
FROM ws JOIN ss ON ws.item = ss.item AND ws.cust = ss.cust
ORDER BY ratio, ss.item, ss.cust
LIMIT 100
"""

SQL["q80"] = """
WITH month AS (SELECT d_date_sk FROM date_dim
               WHERE d_year = 2000 AND d_moy = 8),
items AS (SELECT i_item_sk FROM item WHERE i_current_price > 50.0),
promos AS (SELECT p_promo_sk FROM promotion WHERE p_channel_tv = 'N'),
both_ch AS (
  SELECT 'store channel' AS channel, ss_store_sk AS id,
         ss_ext_sales_price AS sales,
         COALESCE(sr_return_amt, 0.0) AS returns,
         ss_net_profit - COALESCE(sr_net_loss, 0.0) AS profit
  FROM store_sales
  LEFT JOIN store_returns ON ss_ticket_number = sr_ticket_number
       AND ss_item_sk = sr_item_sk
  JOIN month ON ss_sold_date_sk = d_date_sk
  JOIN items ON ss_item_sk = i_item_sk
  JOIN promos ON ss_promo_sk = p_promo_sk
  UNION ALL
  SELECT 'catalog channel', cs_call_center_sk, cs_ext_sales_price,
         COALESCE(cr_return_amount, 0.0),
         cs_net_profit - COALESCE(cr_net_loss, 0.0)
  FROM catalog_sales
  LEFT JOIN catalog_returns ON cs_order_number = cr_order_number
       AND cs_item_sk = cr_item_sk
  JOIN month ON cs_sold_date_sk = d_date_sk
  JOIN items ON cs_item_sk = i_item_sk
  JOIN promos ON cs_promo_sk = p_promo_sk
  UNION ALL
  SELECT 'web channel', ws_web_site_sk, ws_ext_sales_price,
         COALESCE(wr_return_amt, 0.0),
         ws_net_profit - COALESCE(wr_net_loss, 0.0)
  FROM web_sales
  LEFT JOIN web_returns ON ws_order_number = wr_order_number
       AND ws_item_sk = wr_item_sk
  JOIN month ON ws_sold_date_sk = d_date_sk
  JOIN items ON ws_item_sk = i_item_sk
  JOIN promos ON ws_promo_sk = p_promo_sk
)
SELECT channel, id, SUM(sales) AS sales, SUM(returns) AS returns,
       SUM(profit) AS profit
FROM both_ch
GROUP BY channel, id
ORDER BY channel, id
LIMIT 100
"""

SQL["q85"] = """
SELECT r_reason_desc AS reason,
       AVG(CAST(ws_quantity AS REAL)) AS avg_qty,
       AVG(wr_refunded_cash) AS avg_cash,
       AVG(wr_fee) AS avg_fee
FROM web_sales
JOIN web_returns ON ws_order_number = wr_order_number
     AND ws_item_sk = wr_item_sk
JOIN web_page ON ws_web_page_sk = wp_web_page_sk
JOIN customer_demographics cd1 ON wr_refunded_cdemo_sk = cd1.cd_demo_sk
JOIN customer_demographics cd2 ON wr_returning_cdemo_sk = cd2.cd_demo_sk
     AND cd1.cd_marital_status = cd2.cd_marital_status
JOIN customer_address ON wr_refunded_addr_sk = ca_address_sk
JOIN date_dim ON ws_sold_date_sk = d_date_sk AND d_year = 2000
JOIN reason ON wr_reason_sk = r_reason_sk
WHERE ((cd1.cd_marital_status = 'M'
        AND cd1.cd_education_status = '4 yr Degree'
        AND ws_sales_price BETWEEN 100.0 AND 150.0)
    OR (cd1.cd_marital_status = 'S'
        AND cd1.cd_education_status = 'College'
        AND ws_sales_price BETWEEN 50.0 AND 100.0))
  AND ((ca_state IN ('TN', 'GA') AND ws_net_profit >= 100.0)
    OR (ca_state IN ('CA', 'TX') AND ws_net_profit >= 50.0))
GROUP BY r_reason_desc
ORDER BY reason
LIMIT 100
"""

SQL["q86"] = """
WITH base AS (
  SELECT i_category, i_class, SUM(ws_ext_sales_price) AS total_sum
  FROM web_sales
  JOIN date_dim ON ws_sold_date_sk = d_date_sk
       AND d_month_seq BETWEEN 1188 AND 1199
  JOIN item ON ws_item_sk = i_item_sk
  GROUP BY i_category, i_class
),
rolled AS (
  SELECT i_category, i_class, total_sum, 0 AS lochierarchy FROM base
  UNION ALL
  SELECT i_category, NULL, SUM(total_sum), 1 FROM base
  GROUP BY i_category
  UNION ALL
  SELECT NULL, NULL, SUM(total_sum), 2 FROM base
),
ranked AS (
  SELECT *, RANK() OVER (
    PARTITION BY lochierarchy,
                 CASE WHEN lochierarchy = 0 THEN i_category END
    ORDER BY total_sum DESC) AS rank_within_parent
  FROM rolled
)
SELECT i_category, i_class, total_sum, lochierarchy,
       rank_within_parent
FROM ranked
ORDER BY lochierarchy DESC, i_category NULLS FIRST,
         i_class NULLS FIRST, rank_within_parent
LIMIT 100
"""

_Q94_LIKE = """
WITH multi AS (
  SELECT ws_order_number FROM
    (SELECT DISTINCT ws_order_number, ws_warehouse_sk FROM web_sales)
  GROUP BY ws_order_number HAVING COUNT(*) > 1
),
base AS (
  SELECT ws_order_number, ws_ext_ship_cost, ws_net_profit
  FROM web_sales
  JOIN date_dim ON ws_ship_date_sk = d_date_sk AND d_year = 1999
  JOIN customer_address ON ws_ship_addr_sk = ca_address_sk
       AND ca_state = '{state}'
  JOIN web_site ON ws_web_site_sk = web_site_sk
       AND web_name = 'site_0'
  WHERE ws_order_number IN (SELECT ws_order_number FROM multi)
    AND ws_order_number {neg} IN
        (SELECT wr_order_number FROM web_returns
         WHERE wr_order_number IS NOT NULL)
)
SELECT COUNT(DISTINCT ws_order_number) AS order_count,
       SUM(ws_ext_ship_cost) AS total_shipping_cost,
       SUM(ws_net_profit) AS total_net_profit
FROM base
"""

SQL["q94"] = _Q94_LIKE.format(state="CA", neg="NOT")
SQL["q95"] = _Q94_LIKE.format(state="TX", neg="")


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def db():
    tables = gen_tables()
    conn = sqlite3.connect(":memory:")
    for name, df in tables.items():
        df.to_sql(name, conn, index=False)
    # q78's NOT EXISTS probes the returns once a sales row; with no
    # index each probe scans the table (127 s of the gate, half of
    # this file's time)
    conn.execute("CREATE INDEX sr_ticket_item ON store_returns "
                 "(sr_ticket_number, sr_item_sk)")
    conn.execute("CREATE INDEX wr_order_item ON web_returns "
                 "(wr_order_number, wr_item_sk)")
    yield tables, conn
    conn.close()


@pytest.mark.parametrize("q", sorted(SQL, key=lambda s: int(s[1:])))
def test_sqlite_agrees_with_pandas_oracle(db, q):
    tables, conn = db
    got = pd.read_sql_query(SQL[q], conn)
    exp = ORACLES[q](tables)
    got.columns = list(exp.columns)
    assert_frames_match(got, exp, f"{q}/sqlite")
