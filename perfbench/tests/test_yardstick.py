"""The yardstick's arithmetic against hand-worked values: least bytes,
the generator's shapes, the benchmark's own Spark murmur3, its reader of
shuffle files, each template's comparison, the table of peaks and the
traffic generator's promises."""

import json
import os
import struct

import numpy as np
import pyarrow as pa
import pytest
import zstandard

from perfbench import datagen, layers, murmur3, segipc, traffic
from perfbench.templates import _rows, q1_group, q6_scan, repart200

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name, split_rows=4096, splits=2):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        cfg = json.load(f)
    for t in cfg["data"]["tables"].values():
        t["split_rows"], t["splits"] = split_rows, splits
    return cfg


def frames(name, table, seed=11, **kw):
    cfg = config(name, **kw)
    return cfg, datagen.gen_tables(cfg["data"], cfg["generator"],
                                   seed)[table]


SS = "tpcds_sf1000_store_sales"
SR = "tpcds_sf1000_store_returns"


def test_least_bytes():
    ss = {c["name"]: c["type"] for c in
          config(SS)["data"]["tables"]["store_sales"]["columns"]}
    sr = {c["name"]: c["type"] for c in
          config(SR)["data"]["tables"]["store_returns"]["columns"]}
    # three int32 in, two int32 out for every passing row
    assert q6_scan.least_bytes(1048576, 977824, ss) \
        == 12 * 1048576 + 8 * 977824
    # 10 int32, one int64 and 12 decimal(7,2) at 4 bytes: 96 B a row,
    # read once and written once
    assert repart200.least_bytes(1048576, 0, ss) == 2 * 96 * 1048576
    # date, two keys, amount in; two keys and an 8-byte sum a group out
    assert q1_group.least_bytes(1048576, 724114, sr) \
        == 16 * 1048576 + 16 * 724114
    assert _rows.width("decimal(17,2)") == 8


def test_store_sales_has_the_spec_shape():
    cfg, (a, b) = frames(SS, "store_sales", split_rows=65536)
    assert len(a["types"]) == 23 and a["rows"] == 65536
    assert sum(t == "decimal(7,2)" for t in a["types"].values()) == 12
    v, ok = a["values"], a["valid"]
    assert ok["ss_item_sk"] is None and ok["ss_ticket_number"] is None
    for c in ("ss_customer_sk", "ss_sold_date_sk", "ss_net_profit"):
        assert 0.035 < 1 - ok[c].mean() < 0.055  # 9% of rows, one bit in two
    card = cfg["data"]["cardinalities"]
    assert 1 <= v["ss_customer_sk"].min() \
        and v["ss_customer_sk"].max() <= card["customer"]
    assert v["ss_customer_sk"].max() > card["customer"] // 2
    # a ticket: 8 to 16 rows, one customer, one day, distinct items
    sizes = np.bincount(v["ss_ticket_number"] - v["ss_ticket_number"][0])
    assert sizes[1:-1].min() >= 8 and sizes.max() <= 16
    first = np.searchsorted(v["ss_ticket_number"], v["ss_ticket_number"])
    assert np.array_equal(v["ss_customer_sk"], v["ss_customer_sk"][first])
    assert np.array_equal(v["ss_sold_date_sk"],
                          v["ss_sold_date_sk"][first])
    pairs = v["ss_ticket_number"] * 10 ** 6 + v["ss_item_sk"]
    assert len(np.unique(pairs)) == len(pairs)
    # set_pricing's identities, in cents, and every amount in 7 digits
    assert np.array_equal(v["ss_ext_sales_price"],
                          v["ss_sales_price"] * v["ss_quantity"])
    assert np.array_equal(v["ss_net_paid_inc_tax"],
                          v["ss_net_paid"] + v["ss_ext_tax"])
    money = [c for c, t in a["types"].items() if t == "decimal(7,2)"]
    assert max(np.abs(v[c]).max() for c in money) < 10 ** 7
    # January 2001, and the next split goes on where this one ends
    lo, hi = q6_scan.month_keys({"year": 2001, "month": 1})
    assert lo == 2451911 and hi == 2451941
    assert lo <= v["ss_sold_date_sk"].min() \
        and b["values"]["ss_sold_date_sk"].max() <= hi
    again = frames(SS, "store_sales", split_rows=65536)[1][0]
    assert all(np.array_equal(again["values"][c], v[c]) for c in v)
    other = frames(SS, "store_sales", seed=12, split_rows=65536)[1][0]
    assert not np.array_equal(other["values"]["ss_customer_sk"],
                              v["ss_customer_sk"])


def test_store_returns_come_from_the_sales():
    cfg, (a, _) = frames(SR, "store_returns", split_rows=65536)
    assert len(a["types"]) == 20
    v, ok = a["values"], a["valid"]
    assert ok["sr_item_sk"] is None and ok["sr_ticket_number"] is None
    lo, hi = q1_group.year_keys({"year": 2000})
    assert (lo, hi) == (2451545, 2451910)
    assert lo <= v["sr_returned_date_sk"].min() \
        and v["sr_returned_date_sk"].max() <= hi
    # returns of one ticket mostly keep the buyer: far fewer customers
    # than rows, far more than tickets' worth of repeats would leave
    groups = len(np.unique(v["sr_customer_sk"] * 2000 + v["sr_store_sk"]))
    assert 0.6 * a["rows"] < groups < 0.9 * a["rows"]
    assert np.array_equal(v["sr_return_amt_inc_tax"],
                          v["sr_return_amt"] + v["sr_return_tax"])


def test_arrow_and_parquet_round_trip(tmp_path):
    import pyarrow.parquet as pq

    cfg, (a, _) = frames(SS, "store_sales")
    path = str(tmp_path / "a.parquet")
    datagen._write(a, path, cfg["parquet"])
    meta = pq.read_metadata(path).schema
    assert meta.column(13).physical_type == "INT32"  # decimal(7,2)
    back = datagen.from_arrow(pq.read_table(path), a["types"])
    assert list(back["values"]) == list(a["types"])
    assert _rows.rows_differ(a, back, list(a["types"])) == 0
    cents = a["values"]["ss_net_profit"]
    assert cents.min() < 0  # a negative decimal survives the trip
    got = pq.read_table(path).column("ss_net_profit").to_pylist()
    k = int(np.flatnonzero(a["valid"]["ss_net_profit"])[0])
    assert int(got[k] * 100) == cents[k]


def test_murmur3_spark_values():
    # Spark: SELECT hash(1), hash(0), hash(-1), hash(1L), hash(0L)
    assert murmur3.hash_int([1, 0, -1]).tolist() == [
        -559580957, 933211791, -1604776387]
    assert murmur3.hash_long([1, 0]).tolist() == [-1712319331, -1670924195]
    # one value by hand: hashInt(0, 42) is fmix(mixH1(42, 0), 4)
    h = (((42 << 13) | (42 >> 19)) * 5 + 0xE6546B64) & 0xFFFFFFFF
    h ^= 4
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & 0xFFFFFFFF
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & 0xFFFFFFFF
    h ^= h >> 16
    assert murmur3.hash_int([0]).view(np.uint32)[0] == h
    assert murmur3.hash_long([7])[0] != murmur3.hash_int([7])[0]


def test_pmod_is_never_negative():
    got = murmur3.pmod(np.array([-1, -200, -201, 5, 200], np.int32), 200)
    assert got.tolist() == [199, 0, 199, 5, 0]


def test_segmented_ipc_reader(tmp_path):
    def part(table):
        sink = pa.BufferOutputStream()
        with pa.ipc.new_stream(sink, table.schema) as w:
            w.write_table(table)
        raw = zstandard.ZstdCompressor().compress(sink.getvalue().to_pybytes())
        return struct.pack("<Q", len(raw)) + raw

    t0 = pa.table({"k": pa.array([1, 2], pa.int64())})
    t2 = pa.table({"k": pa.array([3], pa.int64())})
    seg0 = part(t0) + struct.pack("<Q", 0) + part(t2)  # a zero-length part
    seg2 = part(t2)
    data, index = tmp_path / "s.data", tmp_path / "s.index"
    data.write_bytes(seg0 + seg2)
    index.write_bytes(np.array(
        [0, len(seg0), len(seg0), len(seg0) + len(seg2)], "<i8").tobytes())
    parts = segipc.read_partitions(str(data), str(index))
    assert [None if p is None else p.column("k").to_pylist()
            for p in parts] == [[1, 2, 3], None, [3]]
    index.write_bytes(np.array([0, 5, 3], "<i8").tobytes())
    with pytest.raises(ValueError):
        segipc.read_partitions(str(data), str(index))


def test_peaks_unknown_kind_is_an_error():
    assert layers.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        layers.peaks_of("TPU v9 imaginary")
    with pytest.raises(KeyError):
        layers.peaks_of("about")


def shuffled(side, rng):
    order = rng.permutation(len(next(iter(side["values"].values()))))
    out = {"values": {c: v[order] for c, v in side["values"].items()},
           "valid": {c: None if v is None else v[order]
                     for c, v in side["valid"].items()}}
    if "partition" in side:
        out["partition"] = side["partition"][order]
    return out


def test_repart_compare_sees_each_fault():
    rng = np.random.default_rng(5)
    _, (frame, _) = frames(SS, "store_sales")
    want = repart200.reference(frame, {})
    nulls = ~frame["valid"]["ss_customer_sk"]
    assert nulls.any() and (want["partition"][nulls] == 42).all()
    sound = shuffled(want, rng)  # the writer may reorder rows
    assert repart200.compare(want, sound) == {
        "rows_misplaced": 0, "rows_differ": 0, "partitions_wrong": 0}
    moved = dict(sound, partition=np.where(
        np.arange(frame["rows"]) < 3, (sound["partition"] + 1) % 200,
        sound["partition"]))
    assert repart200.compare(want, moved)["rows_misplaced"] == 3
    altered = dict(sound, values=dict(sound["values"]))
    altered["values"]["ss_net_paid"] = sound["values"]["ss_net_paid"].copy()
    altered["values"]["ss_net_paid"][7] += 1  # one cent
    assert repart200.compare(want, altered)["rows_differ"] >= 1
    unnulled = dict(sound, valid=dict(sound["valid"]))
    unnulled["valid"]["ss_promo_sk"] = None  # NULLs come back as zeroes
    assert repart200.compare(want, unnulled)["rows_differ"] >= 1
    lost = {"values": {c: v[1:] for c, v in sound["values"].items()},
            "valid": {c: None if v is None else v[1:]
                      for c, v in sound["valid"].items()},
            "partition": sound["partition"][1:]}
    assert repart200.compare(want, lost)["rows_differ"] == 1
    assert repart200.compare(want, None)["partitions_wrong"] == 1
    # the control: a NULL key hashed as 0 lands elsewhere than 42
    control = repart200.compare(want, repart200.control(frame, {}))
    assert control["rows_misplaced"] == int(nulls.sum())


def test_q6_scan_compare_sees_each_fault():
    rng = np.random.default_rng(6)
    _, (frame, _) = frames(SS, "store_sales")
    params = {"year": 2001, "month": 1}
    want = q6_scan.reference(frame, params)
    n = len(want["values"]["ss_item_sk"])
    assert 0.9 * frame["rows"] < n < 0.96 * frame["rows"]
    assert q6_scan.compare(want, shuffled(want, rng)) == {
        "rows_differ": 0, "answer_shape_wrong": 0}
    one_off = shuffled(want, rng)
    one_off["values"]["ss_item_sk"][0] += 1
    assert q6_scan.compare(want, one_off)["rows_differ"] >= 1
    assert q6_scan.compare(want, None)["answer_shape_wrong"] == 1
    assert q6_scan.compare(
        want, q6_scan.control(frame, params))["rows_differ"] > 0
    # another month holds none of these days
    assert len(q6_scan.reference(frame, {"year": 2001, "month": 2})
               ["values"]["ss_item_sk"]) == 0


def test_q1_group_compare_sees_each_fault():
    rng = np.random.default_rng(7)
    _, (frame, _) = frames(SR, "store_returns")
    params = {"year": 2000, "agg_field": "sr_return_amt"}
    want = q1_group.reference(frame, params)
    v, ok = frame["values"], frame["valid"]
    # one group by hand: the first row's, summed in plain Python
    c, s = int(v["sr_customer_sk"][0]), int(v["sr_store_sk"][0])
    assert ok["sr_customer_sk"][0] and ok["sr_store_sk"][0]
    rows = [i for i in range(frame["rows"])
            if v["sr_customer_sk"][i] == c and ok["sr_customer_sk"][i]
            and v["sr_store_sk"][i] == s and ok["sr_store_sk"][i]
            and ok["sr_returned_date_sk"][i]]
    cents = sum(int(v["sr_return_amt"][i]) for i in rows
                if ok["sr_return_amt"][i])
    g = np.flatnonzero((want["values"]["ctr_customer_sk"] == c)
                       & want["valid"]["ctr_customer_sk"]
                       & (want["values"]["ctr_store_sk"] == s)
                       & want["valid"]["ctr_store_sk"])
    assert len(g) == 1 and want["values"]["ctr_total_return"][g[0]] == cents
    assert (~want["valid"]["ctr_customer_sk"]).any()  # NULL is a key
    assert q1_group.compare(want, shuffled(want, rng)) == {
        "groups_wrong": 0, "answer_shape_wrong": 0}
    cent_off = shuffled(want, rng)
    cent_off["values"]["ctr_total_return"][3] += 1
    assert q1_group.compare(want, cent_off)["groups_wrong"] == 1
    assert q1_group.compare(want, None)["answer_shape_wrong"] == 1


@pytest.mark.parametrize("name", ["q6_scan.s4", "repart200.s4",
                                  "q1_group.s4"])
def test_traffic_same_work_every_seed(name):
    """Every seed sends the same set of work in another order: over whole
    blocks the kinds of request are counted alike, and the same seed gives
    the same requests."""
    spec = traffic.load(traffic.path_of(HERE, name))
    block = sum(int(e.get("count", 1)) for e in spec["block"])

    def kinds(seed, n):
        t = traffic.Traffic(spec, seed, 8)
        reqs = [next(s) for s in [t.stream(0)] * n]
        return reqs, sorted(r["template"] for r in reqs)

    a, ka = kinds(1, 4 * block)
    b, kb = kinds(2 ** 31 + 11, 4 * block)
    assert ka == kb
    assert json.dumps(a) == json.dumps(kinds(1, 4 * block)[0])
    if block > 1:
        assert json.dumps(a) != json.dumps(b)
    t = traffic.Traffic(spec, 3, 8)
    s = t.stream(0)
    first = [next(s)["split"] for _ in range(8)]
    assert sorted(first) == list(range(8))  # a round walks every split
    orders = {tuple(next(s)["split"] for _ in range(8)) for _ in range(4)}
    assert len(orders) > 1  # each round in an order of its own
