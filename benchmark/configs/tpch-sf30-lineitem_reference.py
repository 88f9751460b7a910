"""Plain reference of the TPC-H LINEITEM deployment, and the comparison that decides ``correct``.

Imports nothing of the program and takes nothing the program made: the table is
made again from the seed on the host with NumPy, a block of rows at a time, by
this file's own copy of the rule (TPC-H clause 4.2.3's domains, as the
configuration's ``generator`` and ``assumed`` state them), and both queries are
evaluated on it in float64, with exact integer counts:

    Q1  where l_shipdate <= 1998-09-02 (DELTA = 90), by (l_returnflag, l_linestatus):
        sum(qty), sum(price), sum(price (1 - disc)), sum(price (1 - disc)(1 + tax)), sum(disc), count
    Q6  where 1994-01-01 <= l_shipdate < 1995-01-01, 0.05 <= disc <= 0.07, qty < 24:
        sum(price disc)

Decisions (which rows pass a predicate, which group a row is in) are made on the
integers the values are drawn as, not on their float32 images. ``precision``
"bfloat16" is the control: every value and every product rounded to bfloat16.
"""

from __future__ import annotations

import datetime
import hashlib
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 20
THREADS = min(12, os.cpu_count() or 1)
SUMS = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge", "sum_disc")
_M = ((0x9E3779B1, 15), (0x85EBCA77, 13), (0xC2B2AE3D, 16))


def stream_key(seed: int, stream: str) -> int:
    return int.from_bytes(hashlib.blake2b(f"{int(seed)}:{stream}".encode(),
                                          digest_size=4).digest(), "little")


def mix(idx: np.ndarray, key: int) -> np.ndarray:
    h = idx ^ np.uint32(key)
    for mult, shift in _M:
        h *= np.uint32(mult)
        h ^= h >> np.uint32(shift)
    return h


def field(h: np.ndarray, lo: int, bits: int, n: int) -> np.ndarray:
    return ((((h >> np.uint32(lo)) & np.uint32((1 << bits) - 1)) * np.uint32(n))
            >> np.uint32(bits)).astype(np.int32)


def day_of(gen: dict, y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - datetime.date(*gen["start_date"])).days


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """float64 -> nearest bfloat16 (ties to even), as float64."""
    b = x.astype(np.float32).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))) & np.uint32(0xFFFF0000)
    return b.view(np.float32).astype(np.float64)


def block_answers(gen: dict, keys, row0: int, rows: int, precision: str):
    """Q1's six sums by group and Q6's sum over rows [row0, row0 + rows)."""
    idx = np.arange(row0, row0 + rows, dtype=np.uint32)
    h1, h2, h3 = (mix(idx, k) for k in keys)
    ship = field(h1, 0, 20, gen["order_days"]) + 1 + field(h1, 20, 12, gen["ship_after_max"])
    receipt = ship + 1 + field(h2, 0, 8, gen["receipt_after_max"])
    coin = ((h2 >> np.uint32(8)) & np.uint32(1)).astype(np.int32)
    qty = 1 + field(h2, 9, 10, gen["quantity_max"])
    disc_c = field(h2, 19, 6, gen["discount_max_cents"] + 1)
    tax_c = field(h2, 25, 7, gen["tax_max_cents"] + 1)
    part = (h3 % np.uint32(gen["parts"])).astype(np.int32) + 1
    retail_c = 90000 + (part // 10) % 20001 + 100 * (part % 1000)
    today = gen["current_day"]
    flag = np.where(receipt <= today, 2 * coin, 1)
    group = flag * 2 + (ship > today)

    rnd = to_bfloat16 if precision == "bfloat16" else (lambda v: v)
    # the stored values are float32: cents times float32(0.01), as the loader makes them
    cent = np.float32(0.01)
    price = rnd(((qty * retail_c).astype(np.float32) * cent).astype(np.float64))
    disc = rnd((disc_c.astype(np.float32) * cent).astype(np.float64))
    tax = rnd((tax_c.astype(np.float32) * cent).astype(np.float64))
    q = rnd(qty.astype(np.float64))
    disc_price = rnd(price * rnd(1.0 - disc))
    charge = rnd(disc_price * rnd(1.0 + tax))

    m1 = ship <= day_of(gen, 1998, 9, 2)
    g = group[m1]
    out = {"count": np.bincount(g, minlength=6)}
    for name, v in zip(SUMS, (q, price, disc_price, charge, disc)):
        out[name] = np.bincount(g, weights=v[m1], minlength=6)
    m6 = ((ship >= day_of(gen, 1994, 1, 1)) & (ship < day_of(gen, 1995, 1, 1))
          & (disc_c >= 5) & (disc_c <= 7) & (qty < 24))
    out["revenue"] = float(rnd(price[m6] * disc[m6]).sum())
    return out


def answers(cfg, seed: int, precision: str = "float64") -> dict:
    gen, rows = cfg["generator"], cfg["rows"]
    keys = [stream_key(seed, f"lineitem.{n}") for n in (1, 2, 3)]
    total = None
    with ThreadPoolExecutor(THREADS) as pool:
        for part in pool.map(lambda r0: block_answers(gen, keys, r0, min(BLOCK, rows - r0),
                                                      precision), range(0, rows, BLOCK)):
            total = part if total is None else {k: total[k] + part[k] for k in total}
    return total


def compare(cfg, want: dict, served: list) -> dict:
    """{name: (value, limit)} over every answer served (each a {"q01": ..., "q06": ...})."""
    lim = cfg["check"]
    if not served:
        return {"answers_missing": (1.0, 0.0)}
    counts_wrong, sum_gap, rev_gap = 0, 0.0, 0.0
    full = want["count"] > 0
    for a in served:
        q1, q6 = a["q01"], a["q06"]
        got_count = np.where(np.asarray(q1["valid"], bool), np.asarray(q1["count"], np.int64), 0)
        counts_wrong += int((got_count != want["count"]).sum())
        for name in SUMS:
            got = np.asarray(q1[name], np.float64)[full]
            sum_gap = max(sum_gap, float(np.max(np.abs(got - want[name][full])
                                                / np.abs(want[name][full]))))
        got = float(np.asarray(q6["revenue"], np.float64).reshape(-1)[0])
        rev_gap = max(rev_gap, abs(got - want["revenue"]) / abs(want["revenue"]))
    if not np.isfinite(sum_gap) or not np.isfinite(rev_gap):
        sum_gap = rev_gap = float("inf")
    return {"q01_counts_wrong": (float(counts_wrong), 0.0),
            "q01_sum_rel_gap_max": (sum_gap, float(lim["q01_sum_rel_gap_max"])),
            "q06_revenue_rel_gap": (rev_gap, float(lim["q06_revenue_rel_gap"]))}


def check(cfg, seed: int, served, rng) -> dict:
    return compare(cfg, answers(cfg, seed), served)
