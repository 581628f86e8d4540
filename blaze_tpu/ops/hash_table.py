"""Device open-addressing hash table: sort-free grouping and join lookup.

Why: the engine's original grouping/join cores are sort-based (one lexsort
of the full input per aggregate, argsort+searchsorted per join build/probe).
Sorts are O(n log n) with a large constant on XLA:CPU (an 8M-row argsort
measures ~3.5s on one core vs ~0.03s for a scatter over the same rows) and
the sort result is only used to assign group ids / locate matches. This
module replaces that with the classic vectorized open-addressing scheme,
built entirely from scatter/gather primitives that XLA executes in O(n):

  insert:  every live row hashes to a home slot in a power-of-two table;
           rounds of `table.at[slot].min(row_index)` claim empty slots
           (ties resolved by the min), a gather-back + exact key
           comparison resolves rows whose key already owns the slot, and
           unresolved rows advance to the next slot of their triangular
           (quadratic) probe sequence inside one `lax.while_loop`.
           Occupied slots are never overwritten, so the probe-sequence
           invariant (no empty slot EARLIER in a key's triangular
           sequence than its resting slot) holds and lookups may stop
           at the first empty slot they encounter on that sequence.
  lookup:  probe rows walk the same chain, comparing true key values at
           each step - hash collisions cost extra steps, never wrong
           answers.

Equality is exact (not hash equality): NaN matches NaN (Spark normalizes
NaN keys), and NULL handling is caller-chosen: grouping treats NULL as a
key value (SQL GROUP BY: NULL groups with NULL), joins never match NULL.

Reference counterpart: the DataFusion hash-join/hash-aggregate RawTable
paths the reference reuses (from_proto.rs:349-545). The design here is
deliberately not a row-cursor translation: every step is a whole-array
scatter/gather so one XLA program handles the entire batch.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as _np

import jax
import jax.numpy as jnp
from jax import lax


def table_size_for(capacity: int) -> int:
    """Power-of-two table with load factor <= 0.5 at full capacity, so
    insertion always terminates (an empty slot exists on every probe
    chain) and expected chains stay O(1)."""
    t = 1024
    while t < 2 * capacity:
        t <<= 1
    return t


def probe_table_size(capacity: int) -> int:
    """Table sizing for JOIN probes: the lookup while_loop runs one
    full-probe-array pass per round until the LONGEST chain resolves,
    so load factor directly multiplies probe cost (measured at 131k
    build keys / 8M probes on XLA:CPU: 1.51s at load 0.5, 0.36s at
    load 0.125). Aim for 8x the build size, capped at 2^23 slots
    (32MB) so giant builds degrade to the guaranteed-terminating 2x.
    Grouping keeps the 2x table: dense_group_ids scans the whole
    table, so oversizing it costs more than the shorter chains save."""
    t = table_size_for(capacity)
    while t < 8 * capacity and t < (1 << 23):
        t <<= 1
    return t


def cheap_hash(
    key_cols: Sequence[Tuple[jax.Array, Optional[jax.Array]]],
    capacity: int,
) -> jax.Array:
    """Fast intra-engine mixer for PRIVATE table slots (Fibonacci
    multiply + xorshift finalizer, ~3x cheaper than the full murmur3
    pipeline at 8M rows). NOT for shuffle partitioning - row placement
    across executors is a bit-compat contract that must stay
    spark-murmur3 (exprs/hashing.py). Collisions only cost extra probe
    rounds, never wrong answers (exact-key verification)."""
    phi = jnp.uint32(0x9E3779B9)
    acc = jnp.full(capacity, jnp.uint32(0x243F6A88))
    for v, m in key_cols:
        if jnp.issubdtype(v.dtype, jnp.floating):
            # narrow to normalized f32 bits: -0.0 == 0.0 and NaN
            # payloads collapse so equal keys hash equal; f64 pairs
            # distinct only beyond f32 precision merely share a chain
            # (exact comparison still separates them)
            f32 = v.astype(jnp.float32)
            f32 = jnp.where(f32 == 0.0, jnp.float32(0.0), f32)
            f32 = jnp.where(
                jnp.isnan(f32), jnp.float32(jnp.nan), f32
            )
            u = jax.lax.bitcast_convert_type(f32, jnp.uint32)
        elif v.dtype == jnp.bool_:
            u = v.astype(jnp.uint32)
        else:
            # ALL integer widths route through the int64 fold so the
            # hash is a function of the VALUE, not the storage width:
            # an i32 build key then hashes identically to an equal i64
            # probe key and the generic table joins mixed-width keys
            # correctly (equality already promotes)
            b = v.astype(jnp.int64).astype(jnp.uint64)
            u = (b ^ (b >> jnp.uint64(32))).astype(jnp.uint32)
        u = u * phi
        if m is not None:
            u = jnp.where(m, u, jnp.uint32(0x85EBCA6B))
        acc = ((acc << jnp.uint32(5)) | (acc >> jnp.uint32(27))) ^ u
    acc = acc ^ (acc >> jnp.uint32(16))
    acc = acc * jnp.uint32(0x85EBCA6B)
    acc = acc ^ (acc >> jnp.uint32(13))
    return acc.astype(jnp.int32)


def _tri_slot(u0, r, mask):
    """Probe slot r of the triangular (quadratic) sequence
    h, h+1, h+3, h+6, ... (offsets r(r+1)/2). Triangular offsets visit
    every slot of a power-of-two table exactly once per period, so
    termination guarantees carry over from linear probing, but probe
    sequences from clustered home slots diverge immediately - measured
    max chain at 131k keys / 1M slots drops from 8 (linear) to ~4."""
    off = (r * (r + jnp.uint32(1))) >> jnp.uint32(1)
    return jnp.asarray((u0 + off) & mask, dtype=jnp.int32)


def _pairwise_eq(av, am, bv, bm, null_equal: bool):
    """Exact equality of key values gathered from two row sets.

    `av/am` and `bv/bm` are aligned (already gathered) value/validity
    arrays. NaN == NaN; NULL semantics per `null_equal`."""
    eq = av == bv
    if jnp.issubdtype(av.dtype, jnp.floating):
        eq = eq | (jnp.isnan(av) & jnp.isnan(bv))
    if am is None and bm is None:
        return eq
    at = am if am is not None else jnp.ones(av.shape[0], jnp.bool_)
    bt = bm if bm is not None else jnp.ones(bv.shape[0], jnp.bool_)
    if null_equal:
        # (both valid and equal) or (both null)
        return jnp.where(at & bt, eq, at == bt)
    return eq & at & bt


def _keys_at(key_cols, idx):
    """Gather (values, validity) of every key column at row indices."""
    out = []
    for v, m in key_cols:
        out.append(
            (
                jnp.take(v, idx, axis=0),
                jnp.take(m, idx) if m is not None else None,
            )
        )
    return out


def insert(
    h: jax.Array,
    key_cols: Sequence[Tuple[jax.Array, Optional[jax.Array]]],
    live: jax.Array,
    capacity: int,
    table_size: int,
    null_equal: bool,
    max_rounds: Optional[int] = None,
):
    """Insert all live rows; equal keys share one slot.

    `max_rounds` bounds the probe loop for UNDERSIZED tables (a table
    smaller than 2*capacity cannot guarantee an empty slot on every
    chain, so insertion of more distinct keys than fit would never
    terminate): when the bound trips, the leftover rows surface in the
    `overflow` flag and the caller re-runs with a full-size table (the
    same ladder that handles group-capacity overflow).

    Returns (slot, rep_tab, dup, overflow):
      slot     i32[capacity]  resolved slot per row (undefined for dead)
      rep_tab  i32[table_size] first (minimum) row index owning each
               slot; `capacity` marks an empty slot
      dup      bool scalar    any live row's key was already present
               (its representative is a different row)
      overflow bool scalar    rows left unresolved by the round bound
    """
    cap = capacity
    mask = jnp.uint32(table_size - 1)
    rowidx = jnp.arange(cap, dtype=jnp.int32)
    empty = jnp.int32(cap)
    slot0 = jnp.asarray(
        h.astype(jnp.uint32) & mask, dtype=jnp.int32
    )

    def keys_match(rep, self_keys):
        reps = jnp.clip(rep, 0, cap - 1)
        rep_keys = _keys_at(key_cols, reps)
        ok = jnp.ones(cap, dtype=jnp.bool_)
        for (bv, bm), (sv, sm) in zip(rep_keys, self_keys):
            ok = ok & _pairwise_eq(sv, sm, bv, bm, null_equal)
        return ok

    self_keys = [(v, m) for v, m in key_cols]

    # lean carry: the probing slot is DERIVED from the round counter
    # (triangular probing: slot_r = home + r(r+1)/2); only the resolved
    # slot, activity and the table ride the carry
    u0 = slot0.astype(jnp.uint32)

    def cond(state):
        _, _, active, _, rounds = state
        more = jnp.any(active)
        if max_rounds is not None:
            more = more & (rounds < jnp.uint32(max_rounds))
        return more

    def body(state):
        tab, final_slot, active, dup, rounds = state
        slot = _tri_slot(u0, rounds, mask)
        occupant = jnp.take(tab, slot)
        # claim only EMPTY slots: occupied slots are immutable, which
        # preserves the probe-sequence invariant lookups depend on
        cand = jnp.where(
            active & (occupant == empty), rowidx, empty
        )
        tab = tab.at[slot].min(cand, mode="drop")
        rep = jnp.take(tab, slot)
        found = active & (rep != empty) & keys_match(rep, self_keys)
        dup = dup | jnp.any(found & (rep != rowidx))
        final_slot = jnp.where(found, slot, final_slot)
        active = active & ~found
        return tab, final_slot, active, dup, rounds + jnp.uint32(1)

    tab0 = jnp.full(table_size, empty, dtype=jnp.int32)
    state = (
        tab0,
        jnp.zeros(cap, dtype=jnp.int32),
        live,
        jnp.asarray(False),
        jnp.uint32(0),
    )
    tab, final_slot, active, dup, _ = lax.while_loop(
        cond, body, state
    )
    return final_slot, tab, dup, jnp.any(active)


def key_u32(v: jax.Array, m) -> Optional[jax.Array]:
    """Exact 32-bit encoding of a single narrow join key, or None when
    the dtype doesn't fit. Equality of encodings == SQL equality of
    keys: floats normalize -0.0 to +0.0 and every NaN payload to the
    canonical quiet NaN (Spark joins match NaN with NaN)."""
    if v.ndim != 1:
        return None
    if v.dtype == jnp.float32:
        # f64 is NOT eligible: narrowing would merge keys distinct
        # beyond f32 precision, and unlike hashing this encoding IS the
        # equality check
        f = jnp.where(v == 0.0, jnp.float32(0.0), v)
        bits = jax.lax.bitcast_convert_type(f, jnp.uint32)
        return jnp.where(jnp.isnan(f), jnp.uint32(0x7FC00000), bits)
    if v.dtype == jnp.bool_:
        return v.astype(jnp.uint32)
    if jnp.issubdtype(v.dtype, jnp.integer) and v.dtype.itemsize <= 4:
        return v.astype(jnp.int32).astype(jnp.uint32)
    return None


# numpy scalar, NOT jnp: a concrete jnp array at module level gets
# lifted into every closing jaxpr as a runtime input, which breaks
# re-execution of cached kernels (jit fastpath supplies one fewer
# buffer than the compiled program expects)
_KR_EMPTY = _np.uint64(0xFFFFFFFFFFFFFFFF)


def insert_kr(
    k32: jax.Array,
    h: jax.Array,
    live: jax.Array,
    capacity: int,
    table_size: int,
):
    """Single-narrow-key insert into a fused (key32 << 32 | row) u64
    table: each probe round is ONE gather + compare (no second
    indirection through build-key columns), which matters because the
    while_loop runs for the LONGEST chain and every round is a full
    pass over the input. Returns (tab u64[table_size], dup).

    Caveat: a key whose encoding is 0xFFFFFFFF with row index
    0xFFFFFFFF would alias the EMPTY sentinel; row indices are < 2^31,
    so no live entry can equal EMPTY."""
    cap = capacity
    mask = jnp.uint32(table_size - 1)
    rowidx = jnp.arange(cap, dtype=jnp.uint32)
    entries = (k32.astype(jnp.uint64) << jnp.uint64(32)) | (
        rowidx.astype(jnp.uint64)
    )
    u0 = h.astype(jnp.uint32) & mask

    def cond(state):
        _, active, _, _ = state
        return jnp.any(active)

    def body(state):
        tab, active, dup, r = state
        slot = _tri_slot(u0, r, mask)
        occupant = jnp.take(tab, slot)
        cand = jnp.where(
            active & (occupant == _KR_EMPTY), entries, _KR_EMPTY
        )
        tab = tab.at[slot].min(cand, mode="drop")
        entry = jnp.take(tab, slot)
        same_key = (entry >> jnp.uint64(32)).astype(
            jnp.uint32
        ) == k32
        found = active & (entry != _KR_EMPTY) & same_key
        dup = dup | jnp.any(
            found
            & ((entry & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
               != rowidx)
        )
        active = active & ~found
        return tab, active, dup, r + jnp.uint32(1)

    tab0 = jnp.full(table_size, _KR_EMPTY, dtype=jnp.uint64)
    tab, _, dup, _ = lax.while_loop(
        cond, body, (tab0, live, jnp.asarray(False), jnp.uint32(0))
    )
    return tab, dup


def lookup_kr(
    tab: jax.Array,
    k32: jax.Array,
    h: jax.Array,
    probe_live: jax.Array,
):
    """Probe a fused key-row table: one gather + one compare per round.
    Returns (match_idx i32 - -1-clipped garbage when unmatched - and
    matched bool)."""
    table_size = tab.shape[0]
    mask = jnp.uint32(table_size - 1)
    pcap = k32.shape[0]
    u0 = h.astype(jnp.uint32) & mask

    def round_(r, u0_, k32_, active, match):
        slot = _tri_slot(u0_, r, mask)
        entry = jnp.take(tab, slot)
        is_empty = entry == _KR_EMPTY
        hit = active & ~is_empty & (
            (entry >> jnp.uint64(32)).astype(jnp.uint32) == k32_
        )
        match = jnp.where(
            hit,
            (entry & jnp.uint64(0xFFFFFFFF)).astype(jnp.int32),
            match,
        )
        active = active & ~is_empty & ~hit
        return active, match

    # unrolled head rounds: the vast majority of probes resolve within
    # two steps (hit or empty slot) as straight-line code with no
    # loop-carry traffic
    active = probe_live
    match = jnp.full(pcap, -1, dtype=jnp.int32)
    active, match = round_(jnp.uint32(0), u0, k32, active, match)
    active, match = round_(jnp.uint32(1), u0, k32, active, match)

    # compacted tail: the ~1% of probes still active (clustered or
    # displaced keys) gather into a pcap/16 sub-problem so the
    # remaining rounds touch 16x less memory; if the stragglers ever
    # exceed the buffer (adversarial clustering), fall back to
    # full-width rounds - correctness never depends on the estimate
    tail_cap = max(1024, pcap // 16)
    n_active = jnp.sum(active)

    def full_width(args):
        active_, match_ = args

        def cond(state):
            _, a, _ = state
            return jnp.any(a)

        def body(state):
            r, a, m = state
            a, m = round_(r, u0, k32, a, m)
            return r + jnp.uint32(1), a, m

        _, _, m = lax.while_loop(
            cond, body, (jnp.uint32(2), active_, match_)
        )
        return m

    def compacted(args):
        active_, match_ = args
        idxs = jnp.nonzero(
            active_, size=tail_cap, fill_value=pcap
        )[0]
        safe = jnp.clip(idxs, 0, pcap - 1)
        s_u0 = jnp.take(u0, safe)
        s_k32 = jnp.take(k32, safe)
        s_act = idxs < pcap
        s_match = jnp.full(tail_cap, -1, dtype=jnp.int32)

        def cond(state):
            _, a, _ = state
            return jnp.any(a)

        def body(state):
            r, a, m = state
            a, m = round_(r, s_u0, s_k32, a, m)
            return r + jnp.uint32(1), a, m

        _, _, s_match = lax.while_loop(
            cond, body, (jnp.uint32(2), s_act, s_match)
        )
        return match_.at[idxs].set(s_match, mode="drop")

    match = lax.cond(
        n_active > tail_cap, full_width, compacted, (active, match)
    )
    return match, match >= 0


# int32 row indices are < 2^31, so INT32_MAX can never be a live row
_DIRECT_EMPTY = _np.int32(0x7FFFFFFF)


def insert_direct(
    keys: jax.Array,
    live: jax.Array,
    capacity: int,
    base: jax.Array,
    table_size: int,
):
    """Dense-domain dimension table: tab[key - base] = row index.

    The TPC-DS dimension pattern (Spark's LongHashedRelation takes the
    same dense-array fast path): surrogate keys are near-contiguous
    ints, so the "hash table" degenerates to ONE 4-byte-per-slot array
    that fits in L2 for typical dims (131k keys = 512KB vs the 8MB
    key|row u64 table), and probing is a single gather with no hash,
    no probe rounds, no key comparison - slot identity IS key equality.

    `base`/`table_size`: base is the (dynamic, device-scalar) minimum
    live key; table_size the static power-of-two >= key span, so one
    compiled kernel serves every relation with the same span bucket.
    Returns (tab i32[table_size], dup): dup=True means two live rows
    share a key (the caller demotes to the sorted core, exactly like
    the hash insert's duplicate detection)."""
    cap = capacity
    idx = jnp.clip(
        keys.astype(jnp.int64) - base.astype(jnp.int64),
        0, table_size - 1,
    ).astype(jnp.int32)
    rows = jnp.arange(cap, dtype=jnp.int32)
    tab = jnp.full(table_size, _DIRECT_EMPTY, dtype=jnp.int32)
    tab = tab.at[idx].min(
        jnp.where(live, rows, _DIRECT_EMPTY), mode="drop"
    )
    rep = jnp.take(tab, idx)
    dup = jnp.any(live & (rep != rows))
    return tab, dup


def lookup_direct(
    tab: jax.Array,
    base: jax.Array,
    span: jax.Array,
    keys: jax.Array,
    probe_live: jax.Array,
):
    """Probe a dense-domain table: one subtract + range check + gather.
    Returns (match_idx i32, matched bool) - the lookup_kr contract."""
    table_size = tab.shape[0]
    idx = keys.astype(jnp.int64) - base.astype(jnp.int64)
    in_range = (idx >= 0) & (idx < span.astype(jnp.int64))
    rep = jnp.take(
        tab,
        jnp.clip(idx, 0, table_size - 1).astype(jnp.int32),
    )
    matched = probe_live & in_range & (rep != _DIRECT_EMPTY)
    return jnp.where(matched, rep, jnp.int32(-1)), matched


def direct_table_size(span: int) -> int:
    """Static power-of-two table size for a key span (>= 1024 so span
    jitter across relations reuses one compiled kernel)."""
    t = 1024
    while t < span:
        t <<= 1
    return t


def group_slots(
    key_cols: Sequence[Tuple[jax.Array, Optional[jax.Array]]],
    live: jax.Array,
    capacity: int,
    table_size: int,
    max_rounds: Optional[int] = None,
):
    """Slot assignment for GROUPING (null_equal semantics).

    Single-integer-key inputs get a direct-indexing branch: when the
    live value range fits the table (dictionary codes, `x % N` bucket
    ids, narrow ints - the overwhelmingly common TPC-DS group keys),
    slot = value - min(value) with one reserved slot for NULL, skipping
    the probe loop entirely (one scatter instead of ~2 rounds of
    scatter+gather+compare). The branch decision is data-dependent, so
    both variants compile under one `lax.cond`; out-of-range or
    multi-key inputs take the hash-insert path.

    Hashing happens lazily inside the hash branch (cheap_hash): the
    direct branch never pays for it.

    Returns (slot, rep_tab, overflow)."""
    cap = capacity
    single_int = (
        len(key_cols) == 1
        and key_cols[0][0].ndim == 1
        and (
            jnp.issubdtype(key_cols[0][0].dtype, jnp.integer)
            # bool keys (2-3 groups incl. NULL) are the direct path's
            # best case; they cast to int32 below
            or key_cols[0][0].dtype == jnp.bool_
        )
    )

    def hash_insert():
        h = cheap_hash(key_cols, cap)
        slot, tab, _dup, overflow = insert(
            h, key_cols, live, cap, table_size, True, max_rounds
        )
        return slot, tab, overflow

    if not single_int:
        return hash_insert()

    v, m = key_cols[0]
    valid = live if m is None else (live & m)
    if v.dtype == jnp.bool_:
        v = v.astype(jnp.int32)
    info = jnp.iinfo(v.dtype)
    # scalar min/max reductions stay in the ORIGINAL dtype; only the
    # two scalars widen - converting 8M rows to int64 for arithmetic
    # that (inside the taken branch) provably fits 2^23 costs ~0.1s/8M
    # on one core
    kmin = jnp.min(jnp.where(valid, v, info.max))
    kmax = jnp.max(jnp.where(valid, v, info.min))
    diff = kmax.astype(jnp.int64) - kmin.astype(jnp.int64)
    # reserve one slot for the NULL group when the key is nullable.
    # int64 wrap on an astronomically wide range makes diff negative,
    # which the >= 0 guard rejects (a true range >= 2^63 can never wrap
    # into [0, table_size))
    need = diff + (2 if m is not None else 1)
    in_range = (diff >= 0) & (need <= table_size) & jnp.any(valid)

    def direct(_):
        # per-row subtraction: int32/int64 keys subtract in their own
        # dtype (VALID rows cannot wrap: range < table_size <= 2^23 in
        # the taken branch; invalid rows may wrap but are overridden by
        # null_slot/clip). int8/int16 widen to int32 first - their own
        # range CAN overflow the narrow dtype (e.g. int8 span 254).
        vw = v if v.dtype.itemsize >= 4 else v.astype(jnp.int32)
        raw = jnp.clip(
            (vw - kmin.astype(vw.dtype)).astype(jnp.int32),
            0, table_size - 1,
        )
        null_slot = jnp.clip(diff + 1, 0, table_size - 1).astype(
            jnp.int32
        )
        slot = jnp.where(valid, raw, null_slot)
        cand = jnp.where(
            live, jnp.arange(cap, dtype=jnp.int32), jnp.int32(cap)
        )
        tab = jnp.full(table_size, cap, dtype=jnp.int32)
        tab = tab.at[slot].min(cand, mode="drop")
        return slot, tab, jnp.asarray(False)

    def hashed(_):
        return hash_insert()

    return lax.cond(in_range, direct, hashed, operand=None)


def lookup(
    rep_tab: jax.Array,
    h_probe: jax.Array,
    probe_key_cols: Sequence[Tuple[jax.Array, Optional[jax.Array]]],
    build_key_cols: Sequence[Tuple[jax.Array, Optional[jax.Array]]],
    probe_live: jax.Array,
    build_capacity: int,
    null_equal: bool = False,
):
    """Find each probe row's matching build row (first inserted row of
    the equal key), walking the probe chain to the first empty slot.

    Returns (match_idx i32[pcap] - build row index, clip-safe garbage
    when unmatched - and matched bool[pcap])."""
    table_size = rep_tab.shape[0]
    mask = jnp.uint32(table_size - 1)
    pcap = h_probe.shape[0]
    empty = jnp.int32(build_capacity)
    slot0 = jnp.asarray(
        h_probe.astype(jnp.uint32) & mask, dtype=jnp.int32
    )

    def keys_match(rep):
        reps = jnp.clip(rep, 0, build_capacity - 1)
        rep_keys = _keys_at(build_key_cols, reps)
        ok = jnp.ones(pcap, dtype=jnp.bool_)
        for (bv, bm), (pv, pm) in zip(rep_keys, probe_key_cols):
            ok = ok & _pairwise_eq(pv, pm, bv, bm, null_equal)
        return ok

    # lean carry: the probe slot is DERIVED from the round counter
    # (triangular probing: slot_r = home + r(r+1)/2), and the matched
    # flag lives in the match sentinel (-1 = no match) - every array
    # dropped from the carry saves a full-probe-array rewrite per round
    u0 = slot0.astype(jnp.uint32)

    def round_(r, active, match):
        slot = _tri_slot(u0, r, mask)
        rep = jnp.take(rep_tab, slot)
        is_empty = rep == empty
        hit = active & ~is_empty & keys_match(rep)
        match = jnp.where(hit, rep, match)
        active = active & ~is_empty & ~hit
        return active, match

    def cond(state):
        _, active, _ = state
        return jnp.any(active)

    def body(state):
        r, active, match = state
        active, match = round_(r, active, match)
        return r + jnp.uint32(1), active, match

    # unroll the first two rounds: they resolve the vast majority of
    # probes as straight-line code with no loop-carry copies
    active = probe_live
    match = jnp.full(pcap, -1, dtype=jnp.int32)
    active, match = round_(jnp.uint32(0), active, match)
    active, match = round_(jnp.uint32(1), active, match)
    _, _, match = lax.while_loop(
        cond, body, (jnp.uint32(2), active, match)
    )
    return match, match >= 0


def dense_group_ids(
    slot: jax.Array,
    rep_tab: jax.Array,
    live: jax.Array,
    capacity: int,
    out_cap: int,
):
    """Compact occupied slots to dense group ids [0, n_groups).

    Returns (row_gid i32[capacity] - dead rows park in out_cap-1,
    n_groups i32 scalar, bpos i32[out_cap] - representative row index
    per group, zero-padded).

    The production scatter core no longer calls this: hash_aggregate
    reduces on RAW slots and compacts only the (out_cap,)-sized states
    (inlining the occupied/nonzero/bpos math here, minus the full-row
    gid gather). This remains the reference formulation."""
    occupied = rep_tab != jnp.int32(capacity)
    gid_of_slot = jnp.cumsum(occupied.astype(jnp.int32)) - 1
    row_gid = jnp.where(
        live,
        jnp.take(gid_of_slot, slot),
        jnp.int32(out_cap - 1),
    )
    n_groups = jnp.sum(occupied.astype(jnp.int32))
    occ_slots = jnp.nonzero(
        occupied, size=out_cap, fill_value=0
    )[0]
    bpos = jnp.clip(
        jnp.take(rep_tab, occ_slots), 0, capacity - 1
    )
    return row_gid, n_groups, bpos
