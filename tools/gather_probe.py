"""What each form of ``K.gather_rows`` costs on the chip, by shape (PR 35).

    chiprun --timeout 1500 -- python tools/gather_probe.py chiprun_out/pr35_probe

Moves q14's eight arrays (a bigint, two decimal(12,2), a date and the four
validity masks: eight 32-bit words) by ``m`` ascending indices out of ``n``
rows, as ``_jit_compact`` does after ``K.live_indices``, in each form:

- ``plain``: ``[a[idx] for a in arrays]``;
- ``packed``: ``K._gather_packed``, the words as one [words, n] matrix;
- ``packed_t``: the same words as [n, words], a row a slot;
- ``tiles``: each array seen as [n/128, 128], the rows ``idx >> 7`` gathered
  and the lane ``idx & 127`` selected (ROADMAP S1's form: no pass over the
  page).

Writes a file a part: ``<out>/table.json`` ({"<form> n=<n> m=<m>":
{compile_s, run_ms, min_ms, equal}}, at q14's shape the device's operations by
form from the profiler's trace, ``ops``, and the compiled text of the packed
form, ``<out>/packed_q14.hlo.txt``); ``<out>/widths.json`` (one, two and
sixteen arrays, plain and packed, beside what ``K.gather_form`` chooses);
``<out>/compact.json`` (the whole ``_jit_compact`` of a page of q14's shape as
the tree under test builds it). PR 35's first call, made before the rule was
touched, wrote table and compact into one ``pr35_probe/probe.json``. On a CPU
the same script runs at the shapes of ``--small`` and its times mean nothing.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import trino_tpu  # noqa: F401  (x64, the compile cache)
from trino_tpu.ops import kernels as K
from trino_tpu.runtime import executor as E
from trino_tpu.spi.page import Column, Page
from trino_tpu.spi.types import BIGINT, DATE, decimal_type

Q14 = (18_874_368, 262_144)
NS = (1_048_576, 16_777_216, 18_874_368)
MS = (1_024, 16_384, 65_536, 262_144, 1_048_576)
LANES = 128


def q14_arrays(n: int) -> list:
    """l_partkey, l_extendedprice, l_discount (64-bit), l_shipdate (32-bit)
    and their validity, as ``_permute_columns`` hands them: data, valid, ..."""
    i = jnp.arange(n, dtype=jnp.int64)
    datas = [i * 7 + 1, (i * 2654435761) % 10_000_000, i % 11, (i % 2526).astype(jnp.int32)]
    out = []
    for k, d in enumerate(datas):
        out += [d, (i % (k + 5)) != 0]
    return out


def ascending(n: int, m: int, seed: int) -> np.ndarray:
    """What ``live_indices`` and ``_jit_compact`` give: about 0.92 m distinct
    positions in row order, the padding slots clamped to the last row."""
    rng = np.random.default_rng(seed)
    live = np.unique(rng.integers(0, n, size=min(int(m * 0.92), n), dtype=np.int64))[:m]
    return np.concatenate([live, np.full(m - len(live), n - 1)]).astype(np.int32)


def plain(arrays, idx):
    return [a[idx] for a in arrays]


def packed(arrays, idx):
    return K._gather_packed(list(arrays), idx)


def packed_t(arrays, idx):
    """``packed`` with the matrix the other way up: a row of words a slot."""
    arrays = list(arrays)
    words, plan = K._pack_words(arrays)
    moved = jnp.stack(words, axis=1)[idx].T
    return [K._unpack_words(moved, plan[i], a) for i, a in enumerate(arrays)]


def tiles(arrays, idx):
    row, lane = idx >> 7, idx & (LANES - 1)
    at = lane[:, None] == jnp.arange(LANES, dtype=idx.dtype)[None, :]
    out = []
    for a in arrays:
        rows = a.reshape(-1, LANES)[row]
        if a.dtype == jnp.bool_:
            out.append(jnp.any(rows & at, axis=1))
        else:
            out.append(jnp.sum(jnp.where(at, rows, jnp.zeros((), a.dtype)), axis=1, dtype=a.dtype))
    return out


FORMS = {"plain": plain, "packed": packed, "packed_t": packed_t, "tiles": tiles}


def timed(fn, *args, budget_s: float = 0.4, most: int = 20) -> dict:
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    runs = []
    while len(runs) < 3 or (sum(runs) < budget_s and len(runs) < most):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        runs.append(time.perf_counter() - t0)
    return {
        "compile_s": round(first - min(runs), 3), "run_ms": round(statistics.median(runs) * 1e3, 4),
        "min_ms": round(min(runs) * 1e3, 4), "runs": len(runs),
    }


def device_ops(fn, *args, reps: int = 5) -> list:
    """[(program, operation, ms a call)] of the device's `XLA Ops` line, longest first."""
    from benchmark.trace import DEVICE_PLANE, MODULES_LINE, OPS_LINE, short

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(reps):
                jax.block_until_ready(fn(*args))
        (path,) = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(path)
    total: dict = {}
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name in (MODULES_LINE, OPS_LINE):
                for e in line.events:
                    key = (line.name, short(e.name))
                    total[key] = total.get(key, 0.0) + e.duration_ns / 1e6 / reps
    return [[line, name, round(ms, 4)] for (line, name), ms in sorted(total.items(), key=lambda kv: -kv[1])][:14]


def q14_page(n: int, share: float, seed: int) -> Page:
    arrays = q14_arrays(n)
    types = (BIGINT, decimal_type(12, 2), decimal_type(12, 2), DATE)
    cols = tuple(Column(t, arrays[2 * k], arrays[2 * k + 1]) for k, t in enumerate(types))
    return Page(cols, jax.random.uniform(jax.random.PRNGKey(seed), (n,)) < share)


def table(args, record: dict, save) -> None:
    """Every form at every (n, m); at q14's shape the device's operations too."""
    ns, ms, q14 = (NS, MS, Q14) if not args.small else ((4096, 16384), (128, 1024), (16384, 1024))
    jitted = {name: jax.jit(fn) for name, fn in FORMS.items()}
    for n in ns:
        arrays = q14_arrays(n)
        for m in ms:
            if m > n:
                continue
            idx = jnp.asarray(ascending(n, m, args.seed + m))
            want = jax.block_until_ready(jitted["plain"](arrays, idx))
            for name, fn in jitted.items():
                got = timed(fn, arrays, idx)
                got["equal"] = all(bool(jnp.array_equal(g, w)) for g, w in zip(fn(arrays, idx), want))
                record[f"{name} n={n} m={m}"] = got
                print(name, n, m, got, flush=True)
            if (n, m) == q14:
                rng = np.random.default_rng(args.seed)
                shuffled = jnp.asarray(rng.permutation(np.asarray(idx)))
                for name in ("plain", "packed"):
                    record[f"{name} n={n} m={m} shuffled"] = timed(jitted[name], arrays, shuffled)
                record["ops"] = {name: device_ops(fn, arrays, idx) for name, fn in jitted.items()}
                text = jitted["packed"].lower(arrays, idx).compile().as_text()
                with open(os.path.join(args.out, "packed_q14.hlo.txt"), "w") as f:
                    f.write(text)
            save()
        del arrays


def widths(args, record: dict, save) -> None:
    """Fewer and more arrays than q14's eight, plain and packed, beside what
    ``K.gather_form`` says of the shape: the rule's terms in ``gathers`` and
    ``words`` are held to the chip here."""
    n = Q14[0] if not args.small else 16384
    eight = q14_arrays(n)
    sets = {
        "bigint": eight[:1], "date+mask": eight[6:8], "bigint+mask": eight[:2],
        "sixteen": eight + [a + 1 if a.dtype != jnp.bool_ else ~a for a in eight],
    }
    forms = {name: jax.jit(FORMS[name]) for name in ("plain", "packed")}
    for label, arrays in sets.items():
        gathers, words = K.gather_shape(arrays)
        for m in (MS[1:4] if not args.small else (128, 1024)):
            idx = jnp.asarray(ascending(n, m, args.seed + m))
            got = {name: timed(fn, arrays, idx)["run_ms"] for name, fn in forms.items()}
            got.update(gathers=gathers, words=words, rule=K.gather_form(n, m, gathers, words))
            record[f"{label} n={n} m={m}"] = got
            print(label, n, m, got, flush=True)
            save()


def compact(args, record: dict, save) -> None:
    """The whole ``_jit_compact`` of a page of q14's shape, as this tree's rule builds it."""
    n, m = Q14 if not args.small else (16384, 1024)
    page = q14_page(n, 0.0127, args.seed)
    got = timed(E._jit_compact, m, page)
    got["gather"] = K.gather_form(n, m, *K.gather_shape(E._flat_arrays(page.columns)[1]))
    got["ops"] = device_ops(E._jit_compact, m, page)
    record["jit_compact_q14"] = got
    print("jit_compact_q14", got, flush=True)
    save()


PARTS = {"table": table, "widths": widths, "compact": compact}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("out")
    ap.add_argument("--part", nargs="+", choices=sorted(PARTS), default=sorted(PARTS))
    ap.add_argument("--small", action="store_true", help="tiny shapes, for a rehearsal on a CPU")
    ap.add_argument("--seed", type=int, default=3500000001)
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
