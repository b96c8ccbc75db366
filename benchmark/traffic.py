"""The one traffic generator. A mix is a data file, `mixes/<name>.json`:

    {"clients": 3,
     "templates": [{"name": "q01", "k": 2}, {"name": "q06", "k": 2}, {"name": "q14", "k": 2}],
     "stream_orders": [["q06", "q14", "q01"], ["q06", "q14", "q01"], ["q06", "q01", "q14"]]}

Every closed-loop client is a stream of its own (TPC-H's query streams, clause
5.3). `--seed` draws, per template, `clients * k` distinct parameter tuples
from the domain the template states (qgen's substitution parameters), `k` for
each stream, and, where the mix fixes no order, the order in which a stream
sends its statements. It does not change the amount of work.

With `stream_orders` stream s sends the templates in the s-th order, over and
over, with its j-th tuple of each template in its j-th pass (mod k). Without
it a stream sends cycles that each hold every one of its statements once:
inside a cycle each template's statements are shuffled and the templates
interleaved evenly, so wherever the window ends it has seen the same mix to
within a statement per template.
"""

from __future__ import annotations

import importlib
import json
import random
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Statement:
    index: int
    template: str
    params: dict
    sql: str

    @property
    def label(self) -> str:
        return f"{self.template}{json.dumps(self.params, sort_keys=True)}"


def load_template(name: str):
    return importlib.import_module(f"benchmark.templates.{name}")


def draw_params(domain: dict, rng: random.Random, k: int) -> list:
    """`k` distinct tuples of the domain's Cartesian product."""
    names = list(domain)
    size = 1
    for n in names:
        size *= len(domain[n])
    if k > size:
        raise ValueError(f"k = {k} tuples asked of a domain of {size}")
    out = []
    for code in rng.sample(range(size), k):
        p = {}
        for n in names:
            code, i = divmod(code, len(domain[n]))
            p[n] = domain[n][i]
        out.append(p)
    return out


class Stream:
    """One client's statements and the order it sends them in."""

    def __init__(self, by_template: dict, order: list, rng: random.Random):
        self.by_template = by_template    # {template: its k Statements}, in the mix's order
        self.order = order                # the templates of one pass, or None: seeded cycles
        self._rng = rng
        self._pending: list = []
        self._passes = 0

    def cycle(self) -> list:
        """One cycle: every template's statements shuffled, the templates
        interleaved evenly."""
        keyed = []
        for mine in self.by_template.values():
            mine = list(mine)
            self._rng.shuffle(mine)
            offset = self._rng.random()
            keyed.extend(((j + offset) / len(mine), s.index, s) for j, s in enumerate(mine))
        return [s for _, _, s in sorted(keyed)]

    def next(self) -> Statement:
        if not self._pending:
            if self.order is None:
                self._pending = self.cycle()[::-1]
            else:
                j = self._passes
                self._pending = [
                    self.by_template[t][j % len(self.by_template[t])] for t in self.order
                ][::-1]
            self._passes += 1
        return self._pending.pop()


class Traffic:
    def __init__(self, mix: dict, seed: int, schema: str):
        self.clients = int(mix["clients"])
        self.templates = {t["name"]: load_template(t["name"]) for t in mix["templates"]}
        orders = mix.get("stream_orders")
        if orders is not None and (
            len(orders) != self.clients or any(set(o) != set(self.templates) for o in orders)
        ):
            raise ValueError("stream_orders: one order over all the templates for each client")
        drawn = {}
        for t in mix["templates"]:
            rng = random.Random(f"{seed}:params:{t['name']}")
            k = int(t["k"])
            tuples = draw_params(self.templates[t["name"]].DOMAIN, rng, self.clients * k)
            drawn[t["name"]] = [tuples[c * k:(c + 1) * k] for c in range(self.clients)]
        self.statements: list = []    # every distinct statement of the seed, for the warm-up
        self.streams: list = []
        for c in range(self.clients):
            by_template = {}
            for name, module in self.templates.items():
                by_template[name] = []
                for p in drawn[name][c]:
                    sql = module.SQL.format(schema=schema, **module.literals(p))
                    by_template[name].append(Statement(len(self.statements), name, p, sql))
                    self.statements.append(by_template[name][-1])
            rng = random.Random(f"{seed}:order" if c == 0 else f"{seed}:order:{c}")
            self.streams.append(Stream(by_template, orders[c] if orders else None, rng))

    def next(self, client: int) -> Statement:
        """The next statement of client `client`; called by that client's thread only."""
        return self.streams[client].next()


def load_mix(name: str) -> dict:
    return json.loads((ROOT / "mixes" / f"{name}.json").read_text())
