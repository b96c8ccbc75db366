"""Fragment-fused Pallas megakernels: hash join + partial agg + repartition.

Reference blueprint: "Query Processing on Tensor Computation Runtimes"
(arXiv:2203.01877) and "Accelerating Presto with GPUs" (PAPERS.md) both put
the dominant win in eliminating per-operator dispatch and the HBM round-trips
between operators. The device-batching plane (round 13) amortizes *launches*
across queries; each launched program is still a chain of discrete XLA ops.
This module fuses the hot fragment shapes into Pallas kernel launches:

- **hash join** — SplitMix64 bucketing + in-kernel probe, replacing the
  full-cosort internals of ops/kernels.join_match. The sort-based join exists
  because XLA TPU *scatters serialize*; inside a Pallas kernel scatter no
  longer serializes the program (stores into VMEM scratch are the intended
  build-side formulation, pallas_guide.md "Dynamic Indexing"), so the
  classic build/probe shape becomes expressible: a sequential build loop
  inserts active build rows into a bucketed slot table, and the probe side
  resolves matches with vectorized gathers — no multi-pass cosort, no
  rank-space merge sort.
- **join -> partial-agg fusion** — when the join feeds a direct-indexed
  aggregation (small static key domains: dictionary codes / booleans), the
  group-accumulate stage runs on the expanded rows inside the same kernel;
  the join output never materializes to HBM between operators.
- **repartition epilogue** — when the fragment output feeds a hash exchange
  (executor.repartition_hint), the engine-wide partition hash runs as the
  kernel's output stage and rides out as a ``dest`` lane attached to the
  page; ops/repartition consumes it instead of dispatching the standalone
  hash program. ``fused_epilogue`` additionally runs the full
  hash -> stable-cosort -> offsets epilogue as one kernel (the TPU-tier
  formulation, bit-identical to ops/repartition._repartition_epilogue).

Bit-identity contract (tier-1, interpret mode): every kernel runs under
``pl.pallas_call(..., interpret=True)`` on CPU, and the fused results are
bit-identical to the serial op-chain oracle BY CONSTRUCTION:

- slot assignment reuses kernels.expand_probe_slots — the same math the
  sort-based expansion uses, so probe row i's outputs land at the same slots;
- within equal keys, bucket insertion order is ascending original build index
  (the sequential build loop), exactly the stable sort order of the serial
  path's perm_b — so the d-th match of every probe row is the same build row;
- the fused aggregation re-traces executor._direct_aggregate_impl — the
  serial formulas, inside the kernel;
- the fused dest re-traces repartition._partition_dest.

Hardware status: the interpret path IS the contract tier-1 enforces. On a
TPU v5e (PR 21, jax 0.9.0) Mosaic refuses the first fused launch of a
join->aggregate query before it looks at the body: "The Pallas TPU lowering
currently supports only blocks of rank >= 1 ... outputs[5] ... has block
shape ()" — ``_mega_call`` hands scalar leaves over as rank-0 refs. With
``pallas_fusion`` on, that error is the query's error on the chip (no serial
fall-back for a compiled launch); what comes after it — whole-array int64
refs, the build loop's scalar stores, the probe gathers — Mosaic has not been
shown yet (ROADMAP S4/D3). Unsupported shapes (nested layouts,
non-equi residuals, FULL joins, multi-lane keys, sort-path aggregations)
fall back to the op-chain path per-fragment with a labeled
``trino_tpu_pallas_fallbacks_total`` tick — see ARCHITECTURE.md "Megakernel
plane" for the full fallback matrix.

Shape-class discipline: bucket counts key on capstore.capacity_class of the
build capacity and bucket slot widths on 4x-spaced classes (base 8), so the
kernel compile cache collapses varying fragment sizes into a handful of
classes — the same contract the OOC bucket loops and the device-batching
keys rely on.
"""

from __future__ import annotations

from dataclasses import replace
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl

from . import kernels as K
from ..runtime import kernelcost
from ..spi.page import Column, Page

# initial per-bucket slot width; retried at the 4x-spaced class of the
# observed max bucket population when a bucket overflows (duplicate-heavy
# build keys), then gives up at the table entry limit below
DEFAULT_BUCKET_CAP = 32
# (B+1) * C entries beyond this mean pathological key skew (one key owning a
# capacity-class worth of duplicates): the quadratic probe-compare block
# would dwarf the fused win, so the fragment falls back to the sort path
TABLE_ENTRY_LIMIT = 1 << 22

# fused-op labels carried on flight spans
OP_JOIN = "hash_join"
OP_AGG = "partial_agg"
OP_REPART = "repartition"


# --------------------------------------------------------------------------- #
# observability: launch/fallback counters + paired compile/launch spans
# --------------------------------------------------------------------------- #


def _launch_counter():
    from ..runtime.metrics import REGISTRY

    return REGISTRY.counter(
        "trino_tpu_pallas_launches_total",
        help="fused Pallas megakernel launches (one per pl.pallas_call "
        "dispatch: probe/expand phases and standalone epilogues)",
    )


def _fallback_counter(reason: str):
    from ..runtime.metrics import REGISTRY

    return REGISTRY.counter(
        "trino_tpu_pallas_fallbacks_total",
        {"reason": reason},
        help="fragments that fell back from the fused megakernel path to "
        "the serial op-chain, by reason",
    )


def on_pallas_launch(n: int = 1) -> None:
    _launch_counter().inc(n)


def on_pallas_fallback(reason: str) -> None:
    """One fragment declined the fused path; ``reason`` is a short stable
    label (shape, bucket_skew, kernel_error, ...) — the fallback matrix in
    ARCHITECTURE.md enumerates them."""
    _fallback_counter(reason).inc()
    from ..runtime.observability import RECORDER

    RECORDER.instant("pallas_fallback", "pallas", reason=reason)


def pallas_launches() -> float:
    return _launch_counter().value


def pallas_fallbacks(reason: str) -> float:
    return _fallback_counter(reason).value


# signatures whose first trace already happened — the driver wraps the first
# call of each in a pallas_compile span (shape class + fused ops on E-args)
_COMPILED: set = set()


def _spanned_call(phase: str, fused_ops: str, shape_class: str, sig, call):
    from ..runtime.observability import RECORDER

    def _launch():
        with RECORDER.span("pallas_launch", "pallas", phase=phase) as end:
            out = call()
            end["shape_class"] = shape_class
            end["fused_ops"] = fused_ops
        on_pallas_launch()
        return out

    if sig not in _COMPILED:
        _COMPILED.add(sig)
        with RECORDER.span("pallas_compile", "pallas", phase=phase) as end:
            out = _launch()
            end["shape_class"] = shape_class
            end["fused_ops"] = fused_ops
        return out
    return _launch()


# --------------------------------------------------------------------------- #
# the megakernel harness: one traced body -> ONE pl.pallas_call
# --------------------------------------------------------------------------- #


def _mega_call(fn, tree, interpret: bool):
    """Run ``fn(tree) -> out_tree`` as ONE pallas kernel over full-array refs.

    The body is traced once (jax.eval_shape derives the output refs), then
    every input leaf becomes an input ref and every output leaf an output
    ref of a single ``pl.pallas_call`` — the whole fused fragment is one
    kernel launch. Grid-free full-block processing: fragment pages arrive in
    canonical capacity classes, so block tiling happens at the class level,
    not inside the kernel."""
    flat, treedef = jax.tree_util.tree_flatten(tree)

    def fn_flat(*xs):
        return fn(jax.tree_util.tree_unflatten(treedef, list(xs)))

    # trace the fused body once; jaxpr constants (e.g. jnp.array([n])
    # literals folded during tracing) become explicit kernel operands — a
    # pallas kernel cannot capture constants
    closed, out_shape = jax.make_jaxpr(fn_flat, return_shape=True)(*flat)
    consts = [jnp.asarray(c) for c in closed.consts]
    flat_out, out_tree = jax.tree_util.tree_flatten(out_shape)
    n_args = len(flat)
    n_consts = len(consts)

    def kernel(*refs):
        cs = [r[...] for r in refs[:n_consts]]
        ins = [r[...] for r in refs[n_consts:n_consts + n_args]]
        res = jax.core.eval_jaxpr(closed.jaxpr, cs, *ins)
        for r, v in zip(refs[n_consts + n_args:], res):
            r[...] = v

    out = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct(s.shape, s.dtype) for s in flat_out],
        interpret=interpret,
    )(*consts, *flat)
    return jax.tree_util.tree_unflatten(out_tree, out)


def _capacity_class(n: int, base: int = 1024) -> int:
    from ..runtime.capstore import capacity_class

    return capacity_class(n, base)


# --------------------------------------------------------------------------- #
# key normalization + bucket hashing (shared by both phases)
# --------------------------------------------------------------------------- #


def _normalized_keys(key_cols, luts):
    """(data, valid) pairs -> (normalized int64 keys, all-columns-valid).

    Mirrors the serial path's semantics exactly: dictionary-coded probe keys
    translate through the build dictionary's LUT (absent values become
    invalid — a real value that simply never matches), every column equality
    happens on kernels.order_key bits (floats via the sign-magnitude unfold,
    the engine-wide join equality)."""
    keys: List[jnp.ndarray] = []
    ok = None
    for (d, v), lut in zip(key_cols, luts):
        if lut is not None:
            d = lut[jnp.clip(d, 0, lut.shape[0] - 1)]
            v = v & (d >= 0)
        keys.append(K.order_key(d))
        ok = v if ok is None else (ok & v)
    return keys, ok


def _bucket_of(keys: Sequence[jnp.ndarray], n_buckets: int) -> jnp.ndarray:
    """SplitMix64 bucketing over the normalized key tuple. Internal layout
    only — never part of the bit-identity surface, so the fold is free to be
    a plain chained finalizer."""
    h = None
    for k in keys:
        h = K.splitmix64(k if h is None else h + k)
    return (h & jnp.int64(n_buckets - 1)).astype(jnp.int32)


def _bucket_match(table, counts, bucket, pk, pk_ok, bk, C: int):
    """Probe rows against their bucket's slots: ``eq[i, c]`` == slot c of
    row i's bucket holds a build row whose key tuple equals row i's.
    Returns (eq, rows) where ``rows[i, c]`` is the build row index in slot c
    (clipped; only meaningful where the slot is occupied)."""
    rows = table[bucket]  # [N, C] original build indices, insertion order
    m = bk[0].shape[0]
    rows_c = jnp.clip(rows, 0, m - 1)
    occ = (
        jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
        < counts[bucket][:, None]
    )
    eq = occ & pk_ok[:, None]
    for p, b in zip(pk, bk):
        eq = eq & (b[rows_c] == p[:, None])
    return eq, rows_c


# --------------------------------------------------------------------------- #
# phase 1: build the bucket table + per-probe match counts (one kernel)
# --------------------------------------------------------------------------- #


def _probe_phase_body(B: int, C: int, left_outer: bool, tree):
    pkeys, bkeys, luts, probe_active, build_active = tree
    pk, pv = _normalized_keys(pkeys, luts)
    bk, bv = _normalized_keys(bkeys, (None,) * len(bkeys))
    pa = probe_active & pv
    ba = build_active & bv
    bucket_b = _bucket_of(bk, B)
    bucket_p = _bucket_of(pk, B)
    m = ba.shape[0]

    # build stage: sequential insertion keeps ascending original index
    # within each bucket — within equal keys this IS the serial path's
    # stable-sort order, the property the bit-identity proof leans on.
    # Inactive/NULL-key rows insert into the trash bucket B.
    def body(j, carry):
        table, counts = carry
        b = jnp.where(ba[j], bucket_b[j], jnp.int32(B))
        c = counts[b]
        table = table.at[b, jnp.minimum(c, C - 1)].set(jnp.int32(j))
        return table, counts.at[b].add(1)

    table, counts = jax.lax.fori_loop(
        0,
        m,
        body,
        (
            jnp.zeros((B + 1, C), jnp.int32),
            jnp.zeros((B + 1,), jnp.int32),
        ),
    )
    max_count = jnp.max(counts[:B])

    # probe stage: vectorized bucket-compare, no sorts, no merge
    eq, _ = _bucket_match(table, counts, bucket_p, pk, pa, bk, C)
    count = jnp.sum(eq, axis=1, dtype=jnp.int32)
    if left_outer:
        emit = jnp.where(probe_active, jnp.maximum(count, 1), 0)
    else:
        emit = count
    return table, counts, bucket_p, count, emit, max_count


@partial(kernelcost.jit, static_argnums=(0, 1, 2, 3))
def _jit_probe_phase(B, C, left_outer, interpret, tree):
    return _mega_call(
        partial(_probe_phase_body, B, C, left_outer), tree, interpret
    )


def probe_phase(
    pkeys,
    bkeys,
    luts,
    probe_active,
    build_active,
    left_outer: bool,
    interpret: bool,
) -> Optional[Dict[str, object]]:
    """Launch the build+count megakernel (retrying once at a larger bucket
    class when duplicate-heavy keys overflow the default slot width).

    Returns the phase-2 inputs plus ``emit`` (the array the executor sizes
    the output capacity from — the same host sync the serial join performs),
    or None after an ``on_pallas_fallback`` tick when the key distribution
    is too skewed for a bounded table."""
    B = _capacity_class(int(build_active.shape[0]))
    C = DEFAULT_BUCKET_CAP
    shape_class = f"p{probe_active.shape[0]}/b{build_active.shape[0]}/B{B}"
    tree = (tuple(pkeys), tuple(bkeys), tuple(luts), probe_active, build_active)
    for _attempt in range(2):
        sig = ("probe", B, C, left_outer, _tree_sig(tree))
        table, counts, bucket_p, count, emit, max_count = _spanned_call(
            "probe", OP_JOIN, f"{shape_class}/C{C}", sig,
            lambda: _jit_probe_phase(B, C, left_outer, interpret, tree),
        )
        need = int(max_count)
        if need <= C:
            return {
                "table": table, "counts": counts, "bucket_p": bucket_p,
                "count": count, "emit": emit, "B": B, "C": C,
                "shape_class": shape_class,
            }
        C = _capacity_class(need, base=8)
        if (B + 1) * C > TABLE_ENTRY_LIMIT:
            on_pallas_fallback("bucket_skew")
            return None
    on_pallas_fallback("bucket_skew")
    return None


def _tree_sig(tree) -> Tuple:
    return tuple(
        (tuple(x.shape), str(x.dtype))
        for x in jax.tree_util.tree_leaves(tree)
    )


# --------------------------------------------------------------------------- #
# phase 2: expand + (partial agg) + (repartition dest) (one kernel)
# --------------------------------------------------------------------------- #


def _expand_phase_body(out_capacity: int, C: int, symbols, proj_spec,
                       agg_spec, epi_spec, tree):
    (
        pkeys, bkeys, luts, probe_page, build_page,
        table, counts, bucket_p, count, emit,
    ) = tree
    from ..runtime.executor import (
        _cval_of,
        _direct_aggregate_impl,
        _group_sort_impl,
        _permute_column,
        _project_impl,
    )

    pk, pv = _normalized_keys(pkeys, luts)
    bk, _ = _normalized_keys(bkeys, (None,) * len(bkeys))
    pa = probe_page.active & pv

    # slot assignment: the EXACT math of the serial expansion — probe row i's
    # output rows occupy the same slots on both paths
    probe_idx, d, out_active, _total = K.expand_probe_slots(emit, out_capacity)
    matched = d < count[probe_idx]

    # d-th match of each output slot's probe row: within the bucket, the
    # (d+1)-th slot whose key equals the probe key — ascending original
    # build index, identical to perm_b[lo + d] on the serial path
    pk_sel = [k[probe_idx] for k in pk]
    eq, rows = _bucket_match(
        table, counts, bucket_p[probe_idx], pk_sel, pa[probe_idx], bk, C
    )
    cum = jnp.cumsum(eq.astype(jnp.int32), axis=1)
    sel = eq & (cum == (d + 1).astype(jnp.int32)[:, None])
    slot = jnp.argmax(sel, axis=1)
    bpos = jnp.take_along_axis(rows, slot[:, None].astype(jnp.int32), axis=1)[:, 0]

    cols: List[Column] = []
    for c in probe_page.columns:
        cols.append(_permute_column(c, probe_idx))
    for c in build_page.columns:
        pc = _permute_column(c, bpos)
        cols.append(replace(pc, valid=pc.valid & matched))
    out = Page(tuple(cols), out_active)

    if proj_spec is not None:
        # the ProjectNode between join and aggregation, traced in-kernel:
        # the serial _project_impl body over the expanded env (projections
        # are row-preserving, so everything downstream sees the same rows)
        compiled, _proj_symbols = proj_spec
        env = {s: _cval_of(c) for s, c in zip(symbols, out.columns)}
        out = _project_impl(compiled, env, out)
    if agg_spec is not None:
        mode, payload = agg_spec
        if mode == "direct":
            group_keys, aggregations, domains, agg_symbols = payload
            out = _direct_aggregate_impl(
                group_keys, aggregations, domains, agg_symbols, out, "off"
            )
        elif mode == "sort":
            # sort-path grouping: co-sort + boundary detection in-kernel;
            # the reduction stage runs as aggregate_phase after the host
            # reads num_groups (the same sync the serial path performs)
            group_keys, needed, agg_symbols = payload
            return _group_sort_impl(group_keys, needed, agg_symbols, out)
        else:  # "presorted": the self-verifying in-place grouping the
            # serial path takes when the input is ordered on the first
            # group key; the joined page rides out too so a detected
            # violation can re-group through group_sort_phase (the same
            # fallback decision the serial path host-syncs)
            from ..runtime.executor import _presorted_group_impl

            group_keys, needed, agg_symbols = payload
            p, ng, n_grp, viol = _presorted_group_impl(
                group_keys, needed, agg_symbols, out
            )
            return out, p, ng, n_grp, viol
    if epi_spec is not None:
        from .repartition import _partition_dest

        key_idx, n_parts = epi_spec
        dest = _partition_dest(n_parts, key_idx, out)
        return out, dest
    return out, None


@partial(kernelcost.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6))
def _jit_expand_phase(out_capacity, C, symbols, proj_spec, agg_spec,
                      epi_spec, interpret, tree):
    return _mega_call(
        partial(_expand_phase_body, out_capacity, C, symbols, proj_spec,
                agg_spec, epi_spec),
        tree,
        interpret,
    )


def expand_phase(
    probe_result: Dict[str, object],
    pkeys,
    bkeys,
    luts,
    probe_page: Page,
    build_page: Page,
    out_capacity: int,
    symbols,
    proj_spec,
    agg_spec,
    epi_spec,
    interpret: bool,
):
    """Launch the expand(+project)(+agg)(+repartition) megakernel.

    Returns ``(page, dest)`` — the fused output page plus, when
    ``epi_spec`` is set, the per-row exchange destination computed as the
    kernel's output stage (attach with ``attach_epilogue`` so
    ops/repartition skips its standalone program). For the sort-path
    aggregation (``agg_spec = ("sort", ...)``) it instead returns
    ``(sorted_page, new_group, num_groups)`` — feed those to
    :func:`aggregate_phase` after host-reading num_groups."""
    C = probe_result["C"]
    fused = [OP_JOIN]
    if proj_spec is not None:
        fused.append("project")
    if agg_spec is not None:
        fused.append(OP_AGG)
    if epi_spec is not None:
        fused.append(OP_REPART)
    tree = (
        tuple(pkeys), tuple(bkeys), tuple(luts), probe_page, build_page,
        probe_result["table"], probe_result["counts"],
        probe_result["bucket_p"], probe_result["count"], probe_result["emit"],
    )
    sig = (
        "expand", out_capacity, C, symbols, proj_spec, agg_spec, epi_spec,
        _tree_sig(tree),
    )
    return _spanned_call(
        "expand",
        "+".join(fused),
        f"{probe_result['shape_class']}/out{out_capacity}",
        sig,
        lambda: _jit_expand_phase(
            out_capacity, C, symbols, proj_spec, agg_spec, epi_spec,
            interpret, tree
        ),
    )


def _group_sort_body(group_keys, needed, symbols, page):
    from ..runtime.executor import _group_sort_impl

    return _group_sort_impl(group_keys, needed, symbols, page)


@partial(kernelcost.jit, static_argnums=(0, 1, 2, 3))
def _jit_group_sort_phase(group_keys, needed, symbols, interpret, page):
    return _mega_call(
        partial(_group_sort_body, group_keys, needed, symbols), page, interpret
    )


def group_sort_phase(group_keys, needed, symbols, page: Page, interpret: bool):
    """Standalone group-sort kernel: the rare re-group after the presorted
    fast path detected a sortedness violation on the joined page (the same
    one-extra-pass cost the serial path pays for a wrong or stale
    sortedness declaration)."""
    sig = ("group_sort", group_keys, needed, symbols, _tree_sig((page,)))
    return _spanned_call(
        "group_sort", OP_AGG, f"cap{page.capacity}", sig,
        lambda: _jit_group_sort_phase(group_keys, needed, symbols, interpret,
                                      page),
    )


def _agg_phase_body(group_keys, aggregations, needed, out_cap, epi_spec, tree):
    sorted_page, new_group, num_groups = tree
    from ..runtime.executor import _aggregate_impl

    out = _aggregate_impl(
        group_keys, aggregations, needed, out_cap, 0,
        sorted_page, new_group, num_groups,
    )
    if epi_spec is not None:
        from .repartition import _partition_dest

        key_idx, n_parts = epi_spec
        return out, _partition_dest(n_parts, key_idx, out)
    return out, None


@partial(kernelcost.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _jit_agg_phase(group_keys, aggregations, needed, out_cap, epi_spec,
                   interpret, tree):
    return _mega_call(
        partial(_agg_phase_body, group_keys, aggregations, needed, out_cap,
                epi_spec),
        tree,
        interpret,
    )


def aggregate_phase(
    group_keys, aggregations, needed, out_cap: int,
    sorted_page: Page, new_group, num_groups, epi_spec, interpret: bool,
) -> Tuple[Page, Optional[jnp.ndarray]]:
    """The sort-path reduction stage as ONE kernel: the serial
    _aggregate_impl body (cumsum-at-boundaries segment sums et al) over the
    group-sorted page the expand phase produced, plus the optional fused
    repartition dest. Lane-valued aggregates (array_agg & co) never reach
    here — their static lane width needs its own host sync, so the executor
    keeps them on the serial path."""
    tree = (sorted_page, new_group, num_groups)
    sig = (
        "aggregate", group_keys, aggregations, needed, out_cap, epi_spec,
        _tree_sig(tree),
    )
    fused = OP_AGG if epi_spec is None else f"{OP_AGG}+{OP_REPART}"
    return _spanned_call(
        "aggregate", fused, f"out{out_cap}", sig,
        lambda: _jit_agg_phase(
            group_keys, aggregations, needed, out_cap, epi_spec, interpret,
            tree
        ),
    )


# --------------------------------------------------------------------------- #
# standalone fused repartition epilogue (the TPU-tier output stage)
# --------------------------------------------------------------------------- #


def fused_epilogue(page: Page, key_idx: Sequence[int], n_parts: int,
                   interpret: bool = True):
    """hash -> stable cosort -> offsets as ONE kernel: the full device
    epilogue of ops/repartition run as a megakernel output stage, returning
    (sorted_page, offsets, counts) bit-identical to
    repartition._repartition_epilogue (it re-traces the same body).

    Status: the TPU-tier formulation staged for the ROADMAP item-2
    hardware ladder — the live CPU exchange path consumes the cheaper
    fused ``dest`` lane instead (repartition_to_host's host grouping needs
    no device cosort), so today's only caller is the tier-1 bit-identity
    test. Wire this into repartition_to_host's TPU branch when the Mosaic
    lowering lands; keeping it under the interpret contract is what stops
    that wiring from regressing in the meantime."""
    key_idx = tuple(key_idx)
    sig = ("epilogue", n_parts, key_idx, _tree_sig((page,)))
    return _spanned_call(
        "epilogue", OP_REPART, f"cap{page.capacity}/n{n_parts}", sig,
        lambda: _jit_fused_epilogue(n_parts, key_idx, interpret, page),
    )


@partial(kernelcost.jit, static_argnums=(0, 1, 2))
def _jit_fused_epilogue(n_parts, key_idx, interpret, page):
    from .repartition import _repartition_epilogue

    return _mega_call(
        lambda p: _repartition_epilogue(n_parts, key_idx, p), page, interpret
    )


def attach_epilogue(page: Page, dest, key_idx: Sequence[int], n_parts: int,
                    keys: Sequence[str] = ()) -> None:
    """Ride the fused per-row destination on the page object; consumed once
    by ops/repartition._take_fused_dest for the matching exchange spec.
    ``keys`` (symbol names) let :func:`reattach_epilogue` carry the payload
    across column-reordering page rewraps at fragment boundaries."""
    page._megakernel_epilogue = {
        "dest": dest, "key_idx": tuple(key_idx), "n_parts": int(n_parts),
        "keys": tuple(keys),
    }


def reattach_epilogue(src_page: Page, dst_page: Page,
                      dst_symbols: Sequence[str]) -> None:
    """Fragment roots rewrap their relation into an output-symbol-ordered
    Page (parallel/runner.run_fragment_partition); the fused dest survives
    the rewrap by re-deriving key_idx against the new column order. The
    dest VALUES stay valid — they are a function of key values, and rewraps
    reorder columns without touching rows."""
    payload = src_page.__dict__.pop("_megakernel_epilogue", None)
    if not payload:
        return
    keys = payload.get("keys")
    dst_symbols = tuple(dst_symbols)
    if not keys or any(k not in dst_symbols for k in keys):
        return
    dst_page._megakernel_epilogue = {
        "dest": payload["dest"], "n_parts": payload["n_parts"],
        "keys": keys,
        "key_idx": tuple(dst_symbols.index(k) for k in keys),
    }
