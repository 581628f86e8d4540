"""TPC-DS's `date_dim` and `item`, made the way dsdgen makes them, and the
two relations Spark broadcasts from them for query 3's first stage.

What is kept from dsdgen (`w_datetbl.c`, `w_item.c`; written from memory
of the kit's source, there is no network here, and each point is listed
under `assumed` in the configuration's file):

- `date_dim` holds one row a day from `first_date` on; `d_date_sk` is the
  Julian day number, `d_year` and `d_moy` the Gregorian calendar's;
- `item` holds one row an `i_item_sk`, 1..rows (revisions are not kept);
  `i_manufact_id` is uniform on 1..manufacturers; a category, a class of
  the category and a brand number are uniform; `i_brand_id` is
  category * 1,000,000 + class * 1,000 + brand number and `i_brand` is
  `mk_word` of category * 10 + class over the brand syllables, then
  " #" and the brand number.

The configuration's `broadcast` section is what this module reads; it has
a seed of its own, so the dimensions are the same in every run and the
run's seed draws the fact table alone. Pure numpy: the plain reference
rebuilds the relations from here, importing nothing of the program.
"""

from __future__ import annotations

import numpy as np

JULIAN = 1721425  # d_date_sk is the Julian day number: ordinal + JULIAN


def mk_word(seed: int, syllables) -> str:
    """dsdgen's `mk_word`: a syllable for each digit of the seed in the
    syllables' base, least significant first."""
    word, n = "", int(seed)
    while n > 0:
        n, k = divmod(n, len(syllables))
        word += syllables[k]
    return word


def date_dim(cfg: dict) -> dict:
    """{"rows", "values": {d_date_sk, d_year, d_moy}}, all int32."""
    days = np.arange(int(cfg["rows"]), dtype=np.int64)
    day = np.datetime64(cfg["first_date"], "D") + days
    first = int(np.datetime64(cfg["first_date"], "D").astype(np.int64))
    # 1970-01-01 is ordinal 719163
    sk = first + 719163 + JULIAN + days
    years = day.astype("datetime64[Y]").astype(np.int64) + 1970
    months = day.astype("datetime64[M]").astype(np.int64) % 12 + 1
    return {"rows": len(days), "values": {
        "d_date_sk": sk.astype(np.int32),
        "d_year": years.astype(np.int32),
        "d_moy": months.astype(np.int32)}}


def item(cfg: dict, seed: int) -> dict:
    """{"rows", "values": {i_item_sk, i_manufact_id, i_category_id,
    i_class_id, i_brand_id (int32), i_brand (str objects)}}."""
    n = int(cfg["rows"])
    rng = np.random.default_rng([seed, 0x17E3])
    classes = np.asarray(cfg["classes_per_category"], dtype=np.int64)
    manufact = rng.integers(1, int(cfg["manufacturers"]) + 1, n)
    category = rng.integers(1, len(classes) + 1, n)
    klass = rng.integers(1, classes[category - 1] + 1)
    brand_no = rng.integers(1, int(cfg["brands_per_class"]) + 1, n)
    brand_id = category * 1_000_000 + klass * 1_000 + brand_no
    # a name a brand id: the few hundred words are made once
    uniq, inv = np.unique(brand_id, return_inverse=True)
    syl = cfg["brand_syllables"]
    names = np.array([
        f"{mk_word(b // 1_000_000 * 10 + b // 1_000 % 1_000, syl)}"
        f" #{b % 1_000}" for b in uniq.tolist()], dtype=object)
    return {"rows": n, "values": {
        "i_item_sk": np.arange(1, n + 1, dtype=np.int32),
        "i_manufact_id": manufact.astype(np.int32),
        "i_category_id": category.astype(np.int32),
        "i_class_id": klass.astype(np.int32),
        "i_brand_id": brand_id.astype(np.int32),
        "i_brand": names[inv.reshape(-1)]}}


def generate(bcfg: dict) -> dict:
    """{"date_dim": ..., "item": ...} of the configuration's `broadcast`
    section."""
    return {"date_dim": date_dim(bcfg["date_dim"]),
            "item": item(bcfg["item"], int(bcfg["seed"]))}


def broadcast(dims: dict, month: int, manufact: int) -> dict:
    """The two relations query 3's broadcast jobs send, filtered and
    projected as Spark plans them: {"date_dim": {d_date_sk, d_year} where
    d_moy = month, "item": {i_item_sk, i_brand_id, i_brand} where
    i_manufact_id = manufact}, each {column: array} in that order."""
    d, i = dims["date_dim"]["values"], dims["item"]["values"]
    dm = d["d_moy"] == int(month)
    im = i["i_manufact_id"] == int(manufact)
    return {
        "date_dim": {"d_date_sk": d["d_date_sk"][dm],
                     "d_year": d["d_year"][dm]},
        "item": {"i_item_sk": i["i_item_sk"][im],
                 "i_brand_id": i["i_brand_id"][im],
                 "i_brand": i["i_brand"][im]},
    }
