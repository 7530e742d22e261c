"""Pallas TPU kernel: in-VMEM bitonic sort of (key, value) pairs.

The packed-key engine (``repro.core.sortkeys``) turns every ESC compress and
merge into *one* single-key sort plus linear scans. For tile sizes that fit
VMEM this kernel keeps that sort entirely on-chip as a bitonic network — the
TPU-friendly sorting-network rendering the paper's §IV-D observation asks for
(sorting maps to compare-exchange stages, not data-dependent branches).

The network runs log²(N) compare-exchange stages. Each stage pairs element i
with i^j; because j is a power of two the pairing is a regular interleave, so
it is expressed as a reshape to (N/2j, 2, j) and a swap along the middle axis
— reshapes and selects only, no gathers (TPU has no efficient per-lane random
access, which is why the seed's ``lexsort`` was the bottleneck this engine
replaces).

Above ``MAX_BITONIC_ELEMS`` (or on non-TPU backends) callers should use the
XLA path (``jax.lax.sort``) via ``sort_pairs`` below — same contract.

Contract: keys ascending; vals carried along. The network is NOT stable —
equal keys may permute their values. All repo call sites reduce values per
key afterwards, so this is observable only through bitwise float-sum order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import resolve_interpret

#: Largest pair count sorted on-chip: 2 i32/f32 arrays × a few network copies
#: must fit in ~16 MB VMEM with headroom.
MAX_BITONIC_ELEMS = 1 << 14


def _compare_exchange(keys, vals, jj: int, kk: int, length: int):
    """One bitonic stage: element i vs i^jj, ascending iff (i & kk) == 0."""
    rrows = length // (2 * jj)
    k3 = keys.reshape(rrows, 2, jj)
    v3 = vals.reshape(rrows, 2, jj)
    # direction is constant per (2*jj)-row: bit log2(kk) of i comes from r
    r = jax.lax.broadcasted_iota(jnp.int32, (rrows, 1), 0)
    asc = ((r * (2 * jj)) & kk) == 0
    a_k, b_k = k3[:, 0, :], k3[:, 1, :]
    a_v, b_v = v3[:, 0, :], v3[:, 1, :]
    in_order = a_k <= b_k
    swap = jnp.where(asc, ~in_order, in_order)
    new_a_k = jnp.where(swap, b_k, a_k)
    new_b_k = jnp.where(swap, a_k, b_k)
    new_a_v = jnp.where(swap, b_v, a_v)
    new_b_v = jnp.where(swap, a_v, b_v)
    keys = jnp.stack([new_a_k, new_b_k], axis=1).reshape(length)
    vals = jnp.stack([new_a_v, new_b_v], axis=1).reshape(length)
    return keys, vals


def _bitonic_kernel(k_ref, v_ref, ko_ref, vo_ref, *, length: int):
    keys = k_ref[...]
    vals = v_ref[...]
    nstages = length.bit_length() - 1
    for kk_exp in range(1, nstages + 1):
        kk = 1 << kk_exp
        for jj_exp in range(kk_exp - 1, -1, -1):
            keys, vals = _compare_exchange(keys, vals, 1 << jj_exp, kk, length)
    ko_ref[...] = keys
    vo_ref[...] = vals


def bitonic_sort_pairs_pallas(keys, vals, *, interpret: bool = None):
    """Sort ``keys`` ascending carrying ``vals``; length must be a power of 2."""
    (length,) = keys.shape
    assert length & (length - 1) == 0, f"length {length} not a power of two"
    assert vals.shape == (length,)
    if length <= 1:
        return keys, vals
    return pl.pallas_call(
        functools.partial(_bitonic_kernel, length=length),
        out_shape=(
            jax.ShapeDtypeStruct((length,), keys.dtype),
            jax.ShapeDtypeStruct((length,), vals.dtype),
        ),
        interpret=resolve_interpret(interpret),
    )(keys, vals)


def _next_pow2(x: int) -> int:
    return 1 << max(x - 1, 1).bit_length() if x > 1 else 1


def sort_pairs(
    keys,
    vals,
    *,
    use_pallas: bool = False,
    interpret: bool = None,
    max_bitonic: int = MAX_BITONIC_ELEMS,
):
    """Single-key sort of (keys, vals): bitonic Pallas network for VMEM-resident
    sizes, XLA ``lax.sort`` otherwise. Pads to the next power of two with the
    dtype max (sentinels sort last) and slices back.

    Contract for the Pallas path on non-power-of-two lengths: keys must be
    strictly below the key dtype's max. A real max-valued key would tie with
    the padding sentinels and — the network being unstable — its value could
    be dropped in favor of a padding zero. Packed (row, col) keys satisfy
    this by construction (key < key_space <= INT32_MAX); arbitrary callers
    that can't guarantee it should use the XLA path (``use_pallas=False``).
    """
    (length,) = keys.shape
    if not use_pallas or length > max_bitonic:
        return jax.lax.sort((keys, vals), num_keys=1)
    padded = _next_pow2(length)
    if padded != length:
        fill = jnp.iinfo(keys.dtype).max
        keys = jnp.pad(keys, (0, padded - length), constant_values=fill)
        vals = jnp.pad(vals, (0, padded - length))
    ks, vs = bitonic_sort_pairs_pallas(keys, vals, interpret=interpret)
    return ks[:length], vs[:length]
