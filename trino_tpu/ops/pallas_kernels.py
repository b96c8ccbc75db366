"""Pallas TPU kernels for hot operator pipelines.

Reference blueprint: the role of gen/columnar (compiled columnar filters,
SURVEY.md §2.4) taken below XLA: a fused scan→filter→aggregate pass written
against the TPU VPU directly. XLA fuses Q6-shaped pipelines by itself, so the
value here is (a) proving the Pallas path end-to-end for round-2 kernels (join
build/probe, grouped aggregation) where XLA's lowering is weaker, and (b) exact
integer accumulation without int64 emulation. All three kernels compile
through Mosaic on a TPU v5e at SF1 shapes and equal numpy (PR 21); none has
been timed against XLA on today's code (ROADMAP S4/D4).

Exactness trick: the VPU has no int64, so block sums of int32 products are
accumulated as two int32 lanes — sum(x & 0xFFFF) and sum(x >> 16) — recombined
as int64 on the host side (low + (high << 16)). Each lane stays well inside
int32 for blocks up to 8 sublanes x 1024 lanes.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl

LANES = 1024          # block width  (multiple of 128)
SUBLANES = 8          # block height (multiple of 8)
BLOCK = LANES * SUBLANES


def _q6_kernel(shipdate_ref, discount_ref, quantity_ref, price_ref, mask_ref, out_ref,
               *, lo_date, hi_date, lo_disc, hi_disc, hi_qty):
    sd = shipdate_ref[:]
    disc = discount_ref[:]
    qty = quantity_ref[:]
    price = price_ref[:]
    mask = mask_ref[:]
    keep = (
        (sd >= lo_date)
        & (sd < hi_date)
        & (disc >= lo_disc)
        & (disc <= hi_disc)
        & (qty < hi_qty)
        & (mask != 0)
    )
    product = jnp.where(keep, price * disc, jnp.int32(0))
    # dtype pinned to int32: under jax_enable_x64, sum() would promote to int64,
    # which the Pallas TPU lowering rejects
    low = jnp.sum(product & jnp.int32(0xFFFF), dtype=jnp.int32)
    high = jnp.sum(product >> jnp.int32(16), dtype=jnp.int32)
    # output blocks must be (8, 128)-tiled; scatter is not lowerable on TPU,
    # so place the two partials via iota masks (lanes [0,0] and [0,1])
    rows = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    first_row = rows == 0
    out = jnp.where(first_row & (cols == 0), low, jnp.int32(0)) + jnp.where(
        first_row & (cols == 1), high, jnp.int32(0)
    )
    out_ref[0] = out


def q6_fused(
    shipdate: jnp.ndarray,
    discount: jnp.ndarray,
    quantity: jnp.ndarray,
    extendedprice: jnp.ndarray,
    mask: jnp.ndarray,
    lo_date: int,
    hi_date: int,
    lo_disc: int,
    hi_disc: int,
    hi_qty: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused Q6: sum(price * discount) over the predicate; exact int64 result.

    Inputs are int32 1-D arrays (dates as days, decimals as cents) plus an
    int32 0/1 mask (active & validity). Length is padded to a whole number of
    (8, 1024) blocks; padding rides in with mask=0.
    """
    n = shipdate.shape[0]
    padded = ((n + BLOCK - 1) // BLOCK) * BLOCK

    def prep(x, fill=0):
        x = x.astype(jnp.int32)
        if padded != n:
            x = jnp.pad(x, (0, padded - n), constant_values=fill)
        return x.reshape(padded // LANES, LANES)

    sd = prep(shipdate)
    disc = prep(discount)
    qty = prep(quantity)
    price = prep(extendedprice)
    msk = prep(mask)

    rows = padded // LANES
    grid = rows // SUBLANES
    kernel = partial(
        _q6_kernel,
        lo_date=lo_date,
        hi_date=hi_date,
        lo_disc=lo_disc,
        hi_disc=hi_disc,
        hi_qty=hi_qty,
    )
    block_in = pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0))
    # the engine runs with jax_enable_x64; inside the kernel trace x64 weak-type
    # promotion produces int64 convert_element_type ops that the Mosaic TPU
    # lowering cannot handle (it recurses) — trace the kernel in x32 scope.
    # Kernel literals are pinned jnp.int32(...) throughout: when the kernel
    # runs under interpret mode INSIDE an enclosing jit (the engine's
    # direct-aggregate program), lowering happens after this scope exits and
    # weak-typed literals would re-promote to int64 against int32 operands
    with jax.enable_x64(False):
        partials = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((grid, 8, 128), jnp.int32),
            grid=(grid,),
            in_specs=[block_in] * 5,
            out_specs=pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0)),
            interpret=interpret,
        )(sd, disc, qty, price, msk)
    low = partials[:, 0, 0].astype(jnp.int64)
    high = partials[:, 0, 1].astype(jnp.int64)
    return jnp.sum(low) + (jnp.sum(high) << 16)


# --------------------------------------------------------------------------- #
# grouped aggregation (round-3 kernel tier)
#
# Role of FlatHash.java:39 / BigintGroupByHash's small-domain fast path
# (GroupByHash.java:82-98) for the direct-indexed aggregation strategy: given a
# precomputed dense group id per row, produce per-group sums/counts in ONE
# sequential-grid pass over the data, with every int64 value split into 16-bit
# limbs accumulated in native int32 (the VPU has no int64) and recombined in
# int64 by XLA afterwards. Exact for arbitrary int64 inputs (mod-2^64, i.e.
# identical to int64 wraparound).
#
# Measured v5e SF1 (6M rows, chained-loop slope, 2026-07-29): Q1 (G=12)
# XLA 0.98 ms vs Pallas 1.38 ms; 3-key G=60 shape XLA 0.93 ms vs 1.23 ms.
# XLA fuses the [G, n] masked reduction to the HBM roofline on this shape, so
# the engine's AUTO mode keeps the XLA formulation and these kernels sit behind
# pallas_aggregation=force (executor._pallas_mode documents the policy). They
# stay maintained as the substrate for shapes where XLA's lowering is weaker.
# --------------------------------------------------------------------------- #

# [G, 8, 1024] int32 temporaries must stay well inside VMEM (~16 MB/core)
PALLAS_GROUP_LIMIT = 64


def _pad_blocks(x: jnp.ndarray, fill=0) -> jnp.ndarray:
    """1-D int32 array -> [rows, LANES] padded to whole (8, 1024) blocks."""
    n = x.shape[0]
    padded = max(((n + BLOCK - 1) // BLOCK) * BLOCK, BLOCK)
    x = x.astype(jnp.int32)
    if padded != n:
        x = jnp.pad(x, (0, padded - n), constant_values=fill)
    return x.reshape(padded // LANES, LANES)


def _gsum_kernel(gid_ref, w_ref, *refs, G_pad, nlimbs):
    """One grid block: per-group limb sums placed into lanes [g, limb]."""
    out_ref = refs[-1]
    val_refs = refs[:-1]
    gid = gid_ref[:]
    w = w_ref[:] != 0
    limbs = []
    if nlimbs == 4:
        lo, hi = val_refs[0][:], val_refs[1][:]
        limbs.append(lo & jnp.int32(0xFFFF))
        limbs.append(jax.lax.shift_right_logical(lo, jnp.int32(16)))
        limbs.append(hi & jnp.int32(0xFFFF))
        limbs.append(jax.lax.shift_right_arithmetic(hi, jnp.int32(16)))
    else:
        v = val_refs[0][:]
        limbs.append(v & jnp.int32(0xFFFF))
        limbs.append(jax.lax.shift_right_arithmetic(v, jnp.int32(16)))
    groups = jax.lax.broadcasted_iota(jnp.int32, (G_pad, 1, 1), 0)
    m = (gid[None, :, :] == groups) & w[None, :, :]  # [G_pad, 8, 1024]
    sums = [
        jnp.sum(jnp.where(m, l[None, :, :], jnp.int32(0)), axis=2, dtype=jnp.int32).sum(
            axis=1, dtype=jnp.int32
        )
        for l in limbs
    ]  # each [G_pad]
    cols = jax.lax.broadcasted_iota(jnp.int32, (G_pad, 128), 1)
    out = jnp.zeros((G_pad, 128), jnp.int32)
    for j, s in enumerate(sums):
        out = out + jnp.where(cols == j, s[:, None], jnp.int32(0))
    out_ref[0] = out


def _grouped_limb_sums(gid, weight, vals32, num_groups, nlimbs, interpret):
    """Shared driver: [grid, G_pad, 128] int32 partials from one data pass."""
    gid2 = _pad_blocks(gid)
    w2 = _pad_blocks(weight.astype(jnp.int32))
    vals2 = [_pad_blocks(v) for v in vals32]
    rows = gid2.shape[0]
    grid = rows // SUBLANES
    G_pad = max(8, ((num_groups + 7) // 8) * 8)
    kernel = partial(_gsum_kernel, G_pad=G_pad, nlimbs=nlimbs)
    block_in = pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0))
    with jax.enable_x64(False):
        partials = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((grid, G_pad, 128), jnp.int32),
            grid=(grid,),
            in_specs=[block_in] * (2 + len(vals2)),
            out_specs=pl.BlockSpec((1, G_pad, 128), lambda i: (i, 0, 0)),
            interpret=interpret,
        )(gid2, w2, *vals2)
    return partials


def grouped_sum_i64(
    values: jnp.ndarray,
    weight: jnp.ndarray,
    gid: jnp.ndarray,
    num_groups: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """out[g] = sum(values[i] for gid[i]==g and weight[i]), exact int64.

    values int64, weight bool, gid int32 in [0, num_groups). The int64 value is
    carried as (low word unsigned, high word signed); each word splits into two
    16-bit limbs in-kernel, so block accumulators stay below 2^29 < int32."""
    lo32 = values.astype(jnp.int32)  # low word (mod-2^32 truncation)
    hi32 = (values >> 32).astype(jnp.int32)  # arithmetic high word
    partials = _grouped_limb_sums(gid, weight, [lo32, hi32], num_groups, 4, interpret)
    p = partials[:, :num_groups, :4].astype(jnp.int64).sum(axis=0)  # [G, 4]
    low_word = p[:, 0] + (p[:, 1] << 16)
    high_word = p[:, 2] + (p[:, 3] << 16)
    return low_word + (high_word << 32)


def grouped_sum_i32(
    values: jnp.ndarray,
    weight: jnp.ndarray,
    gid: jnp.ndarray,
    num_groups: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """out[g] = sum of int32-range values per group (exact int64 result).
    Covers count (values = weight) and narrow integer sums with 2 limbs."""
    partials = _grouped_limb_sums(
        gid, weight, [values.astype(jnp.int32)], num_groups, 2, interpret
    )
    p = partials[:, :num_groups, :2].astype(jnp.int64).sum(axis=0)  # [G, 2]
    return p[:, 0] + (p[:, 1] << 16)


def q6_reference(shipdate, discount, quantity, extendedprice, mask,
                 lo_date, hi_date, lo_disc, hi_disc, hi_qty) -> jnp.ndarray:
    """XLA formulation of the same computation (the engine's compiled path)."""
    keep = (
        (shipdate >= lo_date)
        & (shipdate < hi_date)
        & (discount >= lo_disc)
        & (discount <= hi_disc)
        & (quantity < hi_qty)
        & (mask != 0)
    )
    return jnp.sum(
        jnp.where(keep, extendedprice.astype(jnp.int64) * discount.astype(jnp.int64), 0)
    )
