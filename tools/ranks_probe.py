"""What each way of a join's ranks back to the probe's order costs on the chip,
by shape: the constants over ``K.ranks_form`` come from it.

    python tools/ranks_probe.py <out dir>        # on a machine with a TPU, from the tree's root

Parts (``--part``, all by default), each written to ``<out>/<part>.json``:

- ``ways``: the two ways alone on merged-order arrays of the join cells'
  shapes: ``K.merged_ranks`` (a sort of the n + m rows by the probe row
  number, carrying the rank words) and ``K.emitting_ranks`` (``live_indices``
  over the emitting mask, one gather, a sort of the listed rows) at several
  shares of emitting rows; and ``K.expand_probe_slots``' scatter over a probe
  of ``orders``' capacity against one over its listed rows;
- ``programs``: the whole ``_jit_join_expand`` in each form (``RanksWay``)
  after the real ``_jit_join_match``, one bigint column a side, at Q3's,
  Q13's and Q5's shapes (``PROGRAMS``; ``--shapes`` picks some), with the
  device's operations by form and ``K.ranks_form``'s choice beside each.

Times are medians of runs after one that compiles (``compile_s``). On a CPU
the same script runs at the shapes of ``--small`` and its times mean nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import trino_tpu  # noqa: F401  (x64, the compile cache)
from tools.gather_probe import device_ops, timed
from trino_tpu.ops import kernels as K
from trino_tpu.runtime import executor as E
from trino_tpu.spi.page import Column, Page
from trino_tpu.spi.types import BIGINT

# (probe rows, build rows, emitting rows) of the join cells (PERF.md section 5):
# Q3 `lineitem` x `orders`; Q8 and Q5 `lineitem` x one part type / `supplier`
# (one rank word); Q7 and Q12 `orders` x `lineitem` (one-to-many)
WAYS = {
    "q3": (16_777_216, 524_288, (16_384, 131_072, 1_000_000, 2_000_000, 4_000_000)),
    "q8_q5": (18_874_368, 8_192, (131_072, 1_000_000, 3_600_000)),
    "q12": (5_242_880, 16_777_216, (150_000, 1_000_000)),
}
SMALL = {"q3": (65_536, 2_048, (256, 3_000, 9_000)), "q12": (4_096, 16_384, (300,))}


def merged_arrays(n: int, m: int, emitting: int, seed: int):
    """(qid, lo, count, emit) in a merged order of n + m rows: the probe row
    numbers and n for the builds in a shuffled order, ``emitting`` of the
    probe rows emitting one to three rows."""
    rng = np.random.default_rng(seed)
    qid = rng.permutation(np.concatenate([np.arange(n), np.full(m, n)]).astype(np.int32))
    emits = np.zeros(n + m, bool)
    probes = np.flatnonzero(qid < n)
    emits[rng.choice(probes, emitting, replace=False)] = True
    count = np.where(emits, rng.integers(1, 4, n + m), 0).astype(np.int32)
    lo = rng.integers(0, m - 3, n + m).astype(np.int32)
    return [jnp.asarray(a) for a in (qid, lo, count, count)]


def ways(args, record: dict, save) -> None:
    for name, (n, m, shares) in (SMALL if args.small else WAYS).items():
        words = K.rank_words(m)
        for emitting in shares:
            qid, lo, count, emit = merged_arrays(n, m, emitting, args.seed)
            slots = E._round_capacity(emitting + 1)
            walk = slots * K.LIVE_INDEX_SHARE <= n + m
            key = f"{name} n={n} m={m} emitting={emitting} slots={slots}"
            record[f"emitting {key}"] = dict(
                timed(jax.jit(K.emitting_ranks, static_argnums=(4, 5)), qid, lo, count, emit, m, slots),
                words=words, walk=walk,
                unique=K.ranks_form(n + m, n, slots, words, True),
                general=K.ranks_form(n + m, n, slots, words, False),
            )
            save()
            print(key, record[f"emitting {key}"], flush=True)
            if name == "q12":
                scatter = jax.jit(lambda e, cap: K.expand_probe_slots(e, cap)[0], static_argnums=1)
                record[f"scatter listed {key}"] = timed(scatter, jnp.ones(slots, jnp.int32), 2 * slots)
                record[f"scatter probe {key}"] = timed(scatter, emit[:n], 2 * slots)
                save()
        record[f"merged {name} n={n} m={m}"] = dict(
            timed(jax.jit(K.merged_ranks, static_argnums=3), qid, lo, count, m), words=words
        )
        save()
        print(name, record[f"merged {name} n={n} m={m}"], flush=True)


# (probe rows, live probe rows, build rows, live build rows, build keys drawn
# from, LEFT join) of whole expansions: Q3 at two shares of emitting rows;
# Q13's `customer` LEFT JOIN `orders` (every customer emits, an order's
# customer among the two thirds that order); Q5's `lineitem` x `supplier`
# (3.6M of 18M lines emit)
PROGRAMS = {
    "q3_131k": (16_777_216, 16_777_216, 524_288, 524_288, 128, False),
    "q3_1m": (16_777_216, 16_777_216, 524_288, 524_288, 16, False),
    "q13": (524_288, 450_000, 5_242_880, 4_461_975, 0, True),
    "q5": (18_874_368, 17_993_932, 8_192, 8_192, 5, False),
}
SMALL_PROGRAMS = {"q3_1m": (65_536, 65_536, 2_048, 2_048, 16, False), "q13": (2_048, 1_800, 20_480, 17_000, 0, True)}


def join_sides(n, n_live, m, m_live, spread, seed):
    """Probe and build pages of one bigint key and their match's keys: the
    build keys unique (Q3, Q5: a probe row meets one in ``spread``) or the
    probe's live keys repeated about ten times, a third of them never (Q13)."""
    rng = np.random.default_rng(seed)
    probe_on = np.arange(n) < n_live
    build_on = np.arange(m) < m_live
    if spread:
        bkey = np.arange(m, dtype=np.int64) * 2
        pkey = rng.integers(0, m * spread, n).astype(np.int64) * 2
    else:
        pkey = np.arange(n, dtype=np.int64)
        ordering = np.flatnonzero(pkey[:n_live] % 3 != 0)
        bkey = rng.choice(ordering, m).astype(np.int64)
    probe = Page((Column(BIGINT, jnp.asarray(pkey), jnp.ones(n, bool)),), jnp.asarray(probe_on))
    build = Page((Column(BIGINT, jnp.asarray(bkey), jnp.ones(m, bool)),), jnp.asarray(build_on))
    return probe, build


def programs(args, record: dict, save) -> None:
    shapes = SMALL_PROGRAMS if args.small else PROGRAMS
    for name in args.shapes or sorted(shapes):
        n, n_live, m, m_live, spread, left = shapes[name]
        probe, build = join_sides(n, n_live, m, m_live, spread, args.seed)
        keys = (((probe.columns[0].data, probe.columns[0].valid),),
                ((build.columns[0].data, build.columns[0].valid),), (None,))
        emit, count, lo, perm_b, totals, qid = E._jit_join_match(left, *keys, probe.active, build.active, None, None, True)
        read = [int(v) for v in np.asarray(totals)]
        out_capacity, slots, unique = E._round_capacity(max(read[0], 1)), E._round_capacity(read[3] + 1), read[2] <= 1
        for form in ("emitting", "merged"):
            way = E.RanksWay(form, left, slots if form == "emitting" else 0)
            call = (out_capacity, unique, emit, count, lo, perm_b, probe, build, qid, way)
            key = f"{form} {name} n={n} m={m} emitting={read[3]} out={read[0]}"
            record[key] = dict(timed(E._jit_join_expand, *call), ops=device_ops(E._jit_join_expand, *call),
                               rule=K.ranks_form(n + m, n, slots, K.rank_words(m), unique))
            save()
            print(key, {k: v for k, v in record[key].items() if k != "ops"}, flush=True)


PARTS = {"ways": ways, "programs": programs}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("out")
    ap.add_argument("--part", nargs="+", choices=sorted(PARTS), default=sorted(PARTS))
    ap.add_argument("--small", action="store_true", help="tiny shapes, for a rehearsal on a CPU")
    ap.add_argument("--shapes", nargs="+", help="programs: these shapes only")
    ap.add_argument("--seed", type=int, default=4400000001)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    device = jax.devices()[0]
    for part in args.part:
        record: dict = {
            "device": device.device_kind, "platform": device.platform,
            "JAX_COMPILATION_CACHE_DIR": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        }

        def save():
            with open(os.path.join(args.out, f"{part}.json"), "w") as f:
                json.dump(record, f, indent=1)

        PARTS[part](args, record, save)


if __name__ == "__main__":
    main()
