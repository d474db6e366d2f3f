"""Integer mix hashing in pure JAX (int32 lane pairs — no x64 requirement).

Both directions of the host/device mirror matter now: the sharded plane's
*ingest* routing stays host-side numpy (``mix32_np``), while the serving
*query* path routes on device (``KeyPermutation.device_call``) so a whole
request batch enters one fused program — shard id, per-shard rank, padded
grid and gather-back all computed on the mesh.  The two are bit-exact by
construction (identical constants, identical masked-shift formulation).

TPUs have no 64-bit integer lanes worth using; we emulate a splitmix-style
64-bit mixer on (hi, lo) int32 pairs so feature signatures hash identically
on CPU (tests), TPU (target), and inside Pallas kernels.  All functions are
deterministic pure functions of their inputs — a requirement for the paper's
offline↔online consistency guarantee (the same raw value must produce the
same signature in both pipelines).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

__all__ = ["mix32", "mix64", "fold_hash", "mix32_np", "KeyPermutation"]

_M1 = np.int32(-2048144789)   # 0x85ebca6b
_M2 = np.int32(-1028477387)   # 0xc2b2ae35


def _as_i32(x: jnp.ndarray) -> jnp.ndarray:
    """Reinterpret/convert arbitrary numeric input to int32 deterministically."""
    if x.dtype == jnp.float32:
        # bitcast so 1.0 and 1 hash differently from 1.5 etc.; NaN-safe.
        return jnp.asarray(x).view(jnp.int32)
    if x.dtype in (jnp.int32, jnp.uint32):
        return x.astype(jnp.int32)
    return x.astype(jnp.int32)


def mix32(x: jnp.ndarray, salt: int = 0) -> jnp.ndarray:
    """murmur3-finalizer style avalanche mix over int32 lanes."""
    h = _as_i32(x) ^ jnp.int32(salt & 0x7FFFFFFF)
    h = h ^ (h >> 16)
    h = (h * _M1).astype(jnp.int32)
    h = h ^ ((h >> 13) & jnp.int32(0x0007FFFF))
    h = (h * _M2).astype(jnp.int32)
    h = h ^ ((h >> 16) & jnp.int32(0x0000FFFF))
    return h


def mix64(x: jnp.ndarray, salt: int = 0, bits: int = 32) -> jnp.ndarray:
    """Two-round 32-bit mix folded to ``bits`` bits, result in [0, 2**bits).

    (Named for its role — emulating a 64-bit-quality mixer with two
    dependent 32-bit rounds — not its output width.)
    """
    x = jnp.asarray(x)
    h1 = mix32(x, salt=salt)
    h2 = mix32(h1 ^ jnp.int32(0x5BD1E995), salt=salt ^ 0x27D4EB2F)
    h = h1 ^ (h2 * jnp.int32(5) + jnp.int32(0x38495AB5))
    if bits >= 31:  # int32 non-negative range is 31 usable bits
        return jnp.abs(h) & jnp.int32(0x7FFFFFFF)
    return jnp.abs(h) % jnp.int32(2 ** bits)


def fold_hash(parts, salt: int = 0, bits: int = 20) -> jnp.ndarray:
    """Order-sensitive fold of several arrays into one hashed id per row."""
    acc = None
    for i, p in enumerate(parts):
        h = mix64(jnp.asarray(p), salt=salt + 0x9E37 * (i + 1), bits=32)
        acc = h if acc is None else mix64(acc * 31 + h, salt=salt, bits=32)
    assert acc is not None
    return jnp.mod(acc, 2 ** bits).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Host-side mirrors (numpy) — the sharded plane's routing runs on the host
# straight from request columns, so it must not pay a device dispatch.
# ---------------------------------------------------------------------------


def _np_i32(v: np.ndarray) -> np.ndarray:
    """Wrap int64 intermediates to signed 32-bit (int32 overflow semantics)."""
    return ((v + 2**31) % 2**32) - 2**31


def mix32_np(x, salt: int = 0) -> np.ndarray:
    """Bit-exact numpy mirror of :func:`mix32` for int inputs.

    Computed in int64 with explicit 32-bit wrapping — numpy's int32 ops
    would warn (or differ by platform) on overflow, and jnp dispatch on the
    serving host's routing path costs more than the hash itself.
    """
    h = _np_i32(np.asarray(x, np.int64) ^ (salt & 0x7FFFFFFF))
    h = _np_i32(h ^ (h >> 16))
    h = _np_i32(h * -2048144789)            # 0x85ebca6b
    h = _np_i32(h ^ ((h >> 13) & 0x0007FFFF))
    h = _np_i32(h * -1028477387)            # 0xc2b2ae35
    h = _np_i32(h ^ ((h >> 16) & 0x0000FFFF))
    return h


class KeyPermutation:
    """Deterministic bijection on ``[0, upper)`` — Feistel rounds of the
    module's mixer, with cycle-walking down to the exact domain.

    The sharded serving plane routes ``shard = perm(key) % S`` so that
    adversarial or strided key patterns (every key ≡ 0 mod S — the classic
    failure of raw modulo routing) still spread across shards, while
    ``local = perm(key) // S`` remains dense and collision-free per shard
    *because* the map is a bijection: two keys can only share a local id if
    they land on different shards.

    Stateless and host-side (pure numpy): routing never needs a lookup
    table, so any router replica — or a recovering one — maps keys
    identically.
    """

    def __init__(self, upper: int, rounds: int = 4, salt: int = 0):
        if upper < 1:
            raise ValueError(f"permutation domain must be >= 1, got {upper}")
        self.upper = int(upper)
        bits = max(2, (self.upper - 1).bit_length())
        bits += bits & 1  # even split -> balanced Feistel halves
        self.half = bits // 2
        self.mask = (1 << self.half) - 1
        self.size = 1 << bits
        self.rounds = int(rounds)
        self.salt = int(salt)

    def _once(self, x: np.ndarray) -> np.ndarray:
        left = x >> self.half
        right = x & self.mask
        for r in range(self.rounds):
            f = mix32_np(right, salt=self.salt + 0x9E37 * (r + 1)) & self.mask
            left, right = right, left ^ f
        return (left << self.half) | right

    def _once_inv(self, x: np.ndarray) -> np.ndarray:
        """Inverse of one Feistel pass: run the rounds backwards.

        Forward round r maps (L, R) -> (R, L ^ F_r(R)), so its inverse is
        (L', R') -> (R' ^ F_r(L'), L') with the same round function —
        Feistel networks invert without inverting F.
        """
        left = x >> self.half
        right = x & self.mask
        for r in reversed(range(self.rounds)):
            f = mix32_np(left, salt=self.salt + 0x9E37 * (r + 1)) & self.mask
            left, right = right ^ f, left
        return (left << self.half) | right

    def __call__(self, key) -> np.ndarray:
        """Vectorized permuted ids; walks cycles until back in [0, upper)."""
        x = np.atleast_1d(np.asarray(key)).astype(np.int64)
        out = self._once(x)
        bad = out >= self.upper
        while bad.any():
            out[bad] = self._once(out[bad])
            bad = out >= self.upper
        return out.reshape(np.shape(key))

    def inverse(self, key) -> np.ndarray:
        """Exact inverse of :meth:`__call__` on [0, upper):
        ``inverse(perm(k)) == k`` for every k in the domain.

        Cycle-walking inverts by walking the same cycle backwards: every
        intermediate value of the forward walk lies outside [0, upper), so
        applying the inverse pass until the value re-enters the domain
        retraces the forward walk exactly.  Vectorized host-side numpy,
        like the forward map — migrations use it to decode routed ring
        coordinates back to global keys without materializing a
        full-domain lookup table.
        """
        x = np.atleast_1d(np.asarray(key)).astype(np.int64)
        if x.size and (x.min() < 0 or x.max() >= self.upper):
            raise ValueError(
                f"inverse domain is [0, {self.upper}): "
                f"got [{x.min()}, {x.max()}]"
            )
        out = self._once_inv(x)
        bad = out >= self.upper
        while bad.any():
            out[bad] = self._once_inv(out[bad])
            bad = out >= self.upper
        return out.reshape(np.shape(key))

    # -- device mirror (the fused on-mesh request path) ---------------------

    def _once_device(self, x: jnp.ndarray) -> jnp.ndarray:
        """jnp mirror of :meth:`_once` — bit-exact because every Feistel
        half stays below ``2**half`` and mix32 / mix32_np agree on the low
        ``half`` bits (two's-complement masking is width-independent)."""
        left = x >> self.half
        right = x & self.mask
        for r in range(self.rounds):
            f = mix32(right, salt=self.salt + 0x9E37 * (r + 1)) & jnp.int32(
                self.mask
            )
            left, right = right, left ^ f
        return (left << self.half) | right

    def device_call(self, key: jnp.ndarray) -> jnp.ndarray:
        """Permuted ids computed on device, jit/vmap-safe; identical values
        to :meth:`__call__` for every key in [0, upper).

        Cycle-walking becomes a ``lax.while_loop`` re-permuting only the
        out-of-domain lanes — the loop is data-dependent but terminates in
        a handful of rounds (the walk expects ``size/upper`` < 4 steps).
        """
        import jax

        if self.size > 0x7FFFFFFF:  # pragma: no cover - >2^31 key domains
            raise ValueError(
                f"device permutation needs an int32 domain; size "
                f"{self.size} overflows (route on host instead)"
            )
        x = jnp.asarray(key, jnp.int32)
        out = self._once_device(x)

        def cond(o):
            return jnp.any(o >= self.upper)

        def body(o):
            return jnp.where(o >= self.upper, self._once_device(o), o)

        return jax.lax.while_loop(cond, body, out)
