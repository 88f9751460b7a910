"""One rule from (seed, stream, index) to a value, the same on the device and on the host.

Every array of a deployment is made from ``--seed`` by hashing each element's
index with a 32-bit integer mixer. Integer arithmetic modulo 2**32 and the
conversion of a 24-bit integer to float32 are exact on both sides, so the
launcher can make the arrays on the chip with ``jax.numpy`` and the plain
reference can make the same bits on the host with NumPy, with no array of the
program's in between. The values carry 24 significant bits on purpose: a
matrix product in a precision below the stated one then rounds them and is
seen by the comparison (values that fit bfloat16 would hide it).

``xp`` is ``numpy`` or ``jax.numpy``; nothing here imports JAX.
"""

from __future__ import annotations

import hashlib

import numpy as np

_M1, _M2, _M3 = 0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D


def stream_key(seed: int, stream: str) -> int:
    """A 32-bit key for one array of one run: any whole-number seed, any name."""
    digest = hashlib.blake2b(f"{int(seed)}:{stream}".encode(), digest_size=4).digest()
    return int.from_bytes(digest, "little")


def _mix_numpy(idx: np.ndarray, key) -> np.ndarray:
    """``mix`` for NumPy with two buffers and no other temporaries (the reference's hot loop)."""
    u = np.uint32
    h = idx.astype(u)  # a copy: the caller's index array is left alone
    t = np.empty_like(h)
    np.bitwise_xor(h, u(key), out=h)
    for mult, shift in ((_M1, 15), (_M2, 13), (_M3, 16)):
        np.multiply(h, u(mult), out=h)
        np.right_shift(h, u(shift), out=t)
        np.bitwise_xor(h, t, out=h)
    return h


def mix(xp, idx, key):
    """uint32 index array -> well-mixed uint32 array (murmur3's finaliser)."""
    if xp is np:
        return _mix_numpy(idx, key)
    u = xp.uint32
    h = idx.astype(u) ^ xp.asarray(key, dtype=u)
    h = h * u(_M1)
    h = h ^ (h >> u(15))
    h = h * u(_M2)
    h = h ^ (h >> u(13))
    h = h * u(_M3)
    h = h ^ (h >> u(16))
    return h


def unit24(xp, h, scale_pow2: int = 0):
    """uint32 hash -> float32 in [-1, 1) * 2**scale_pow2, 24 significant bits, exact."""
    if xp is np:  # in place: h is this module's own buffer
        np.right_shift(h, np.uint32(8), out=h)
        n = h.view(np.int32)
        np.subtract(n, np.int32(1 << 23), out=n)
        out = n.astype(np.float32)
        np.multiply(out, np.float32(2.0 ** (scale_pow2 - 23)), out=out)
        return out
    n = (h >> xp.uint32(8)).astype(xp.int32) - xp.int32(1 << 23)
    return n.astype(xp.float32) * xp.float32(2.0 ** (scale_pow2 - 23))


def matrix(xp, key, rows: int, cols: int, scale_pow2: int = 0,
           row0=0, col0=0, ld: int | None = None):
    """The ``rows x cols`` window at (row0, col0) of a matrix whose row length is ``ld``.

    Element (i, j) of the whole matrix depends on ``i * ld + j`` alone, so a
    block made on the host equals the same block of the matrix made whole on
    the device. The whole matrix must have fewer than 2**32 elements. ``key``,
    ``row0`` and ``col0`` may be traced scalars: one compiled program then serves
    every seed and every window, and the persistent cache holds it.
    """
    ld = cols if ld is None else ld
    u = xp.uint32
    i = (xp.arange(rows, dtype=u) + xp.asarray(row0, dtype=u))[:, None]
    j = (xp.arange(cols, dtype=u) + xp.asarray(col0, dtype=u))[None, :]
    return unit24(xp, mix(xp, i * u(ld) + j, key), scale_pow2)


def scaled(xp, h, bits_lo: int, bits: int, n: int):
    """``bits`` bits of ``h`` starting at ``bits_lo`` -> integer in [0, n) (multiply-shift)."""
    u = xp.uint32
    field = (h >> u(bits_lo)) & u((1 << bits) - 1)
    return ((field * u(n)) >> u(bits)).astype(xp.int32)


def dates_table(first: tuple, days: int) -> np.ndarray:
    """Day number -> yyyymmdd int32, from ``first`` (y, m, d) for ``days`` days."""
    import datetime

    d0 = datetime.date(*first)
    out = np.empty(days, np.int32)
    for k in range(days):
        d = d0 + datetime.timedelta(days=k)
        out[k] = d.year * 10000 + d.month * 100 + d.day
    return out
