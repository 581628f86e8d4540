"""Whole rows compared exactly, whatever order they come in: what the
templates' `compare` functions share. A side is {"values": {col: array},
"valid": {col: bool array, or None where no row is NULL}}; decimals are
unscaled int64. Pure numpy: the reference's side of the yardstick."""

from __future__ import annotations

import numpy as np


def is_valid(side: dict, col: str) -> np.ndarray:
    v = side["valid"][col]
    return np.ones(len(side["values"][col]), bool) if v is None else v


def settled(side: dict, columns) -> dict:
    """{col: (values with 0 under every NULL, valid)}: what lies under a
    NULL is nobody's business, so it is made the same on both sides."""
    out = {}
    for c in columns:
        ok = is_valid(side, c)
        out[c] = (np.where(ok, side["values"][c], 0), ok)
    return out


def _mix(cols: dict, lead=None) -> np.ndarray:
    """A 64-bit mix of each whole row: it pairs the rows of two sides;
    what is compared afterwards is the rows themselves."""
    n = len(next(iter(cols.values()))[0])
    h = np.zeros(n, np.uint64) if lead is None else lead.astype(np.uint64)
    with np.errstate(over="ignore"):
        for v, ok in cols.values():
            bits = v.astype(np.int64).view(np.uint64) * np.uint64(2) \
                + ok.astype(np.uint64)
            h = (h ^ bits) * np.uint64(0x9E3779B97F4A7C15)
            h ^= h >> np.uint64(29)
    return h


def rows_differ(want: dict, got: dict, columns, want_lead=None,
                got_lead=None) -> int:
    """Rows of `want` that `got` lacks or rows `got` has too many,
    whichever is more: the two sides as multisets of whole rows. A column
    that `got` lacks, or holds in another integer width, makes every row
    differ. `*_lead` is a number that belongs to each row besides its
    columns (the partition it lies in)."""
    n_want = len(want["values"][columns[0]])
    for c in columns:
        if c not in got["values"] \
                or got["values"][c].dtype != want["values"][c].dtype:
            return max(n_want, 1)
    w, g = settled(want, columns), settled(got, columns)
    hw, hg = _mix(w, want_lead), _mix(g, got_lead)
    _, inv = np.unique(np.concatenate((hw, hg)), return_inverse=True)
    cw = np.bincount(inv[:n_want], minlength=inv.max() + 1)
    cg = np.bincount(inv[n_want:], minlength=len(cw))
    lacking = int(np.maximum(cw - cg, 0).sum())
    extra = int(np.maximum(cg - cw, 0).sum())
    # rows whose mix pairs them off are then compared as rows
    paired = cw == cg
    wi = np.flatnonzero(paired[inv[:n_want]])
    gi = np.flatnonzero(paired[inv[n_want:]])
    wi = wi[np.argsort(hw[wi], kind="stable")]
    gi = gi[np.argsort(hg[gi], kind="stable")]
    bad = np.zeros(len(wi), bool)
    for c in columns:
        bad |= (w[c][0][wi] != g[c][0][gi]) | (w[c][1][wi] != g[c][1][gi])
    return max(lacking, extra) + int(np.count_nonzero(bad))


def width(t: str) -> int:
    """The fewest bytes a value of this type takes: a decimal of up to 9
    digits fits 4 bytes (parquet's INT32), of up to 18 digits 8."""
    if t.startswith("decimal("):
        return 4 if int(t[8:-1].split(",")[0]) <= 9 else 8
    return {"int32": 4, "int64": 8}[t]
