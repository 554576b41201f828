"""The one general traffic generator: a cell's data file -> what each
stream sends.

A traffic mix is `workloads/<cell>.json`:

  config         name of the configuration (also in BENCHMARK.json)
  front          module under `fronts/` the clients speak to
  loop           module under `loops/` that drives the clients
  streams        client threads, each with a connection of its own
  family         directory under `queries/` holding the query files
  queries        query names, in the order of one rotation
  param_sets     parameter sets per query, drawn from the seed: a number,
                 or {query: number}
  expected_path  the execution path every query must report in set-up
  rate           (open loops) offered statements per second

Every seed gives every stream the same rotation of queries and the same
number of parameter sets, in another order and with other literals: the
seed moves the values, not the amount of work.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def load_module(kind: str, name: str):
    """`<kind>/<name>.py` under benchmark/, found by the name in the data."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    mod_name = "bench_" + "".join(
        c if c.isalnum() else "_" for c in f"{kind}_{name}")
    if mod_name not in sys.modules:
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[mod_name]
            raise
    return sys.modules[mod_name]


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass(frozen=True)
class Item:
    """One (query, parameter set) of the cell's list."""
    query: str
    set_no: int
    params: tuple          # sorted (key, value) pairs: hashable
    sql: str


def _rng(seed: int, *words) -> np.random.Generator:
    # independent of the data generator's stream; any seed up to 2**63
    salt = [sum(ord(c) << (8 * (i % 7)) for i, c in enumerate(str(w)))
            for w in words]
    return np.random.default_rng([int(seed), *salt])


def _sets_per_query(mix: dict) -> dict:
    """`param_sets` is one number for every query, or {query: number}."""
    n = mix["param_sets"]
    return {q: int(n[q] if isinstance(n, dict) else n) for q in mix["queries"]}


def build_items(mix: dict, seed: int) -> tuple[dict, list[Item]]:
    """The cell's list: for each query its number of distinct parameter
    sets, drawn from the seed. Returned in a canonical order (rotation by
    rotation, each query's sets sorted by value), which is the order the
    warm-up runs them in: what a program derives from the first literals
    it sees (a sticky capacity, say) then does not depend on the draw's
    order. Returns ({query: module}, items)."""
    mods = {q: load_module(f"queries/{mix['family']}", q)
            for q in mix["queries"]}
    counts = _sets_per_query(mix)
    per_query: dict = {}
    for q, mod in mods.items():
        rng = _rng(seed, "params", q)
        seen: set = set()
        for _ in range(1000):
            seen.add(tuple(sorted(mod.sample(rng).items())))
            if len(seen) == counts[q]:
                break
        else:
            raise ValueError(f"{q}: fewer than {counts[q]} distinct "
                             "parameter sets in its ranges")
        per_query[q] = [Item(q, i, key, mod.sql(dict(key)))
                        for i, key in enumerate(sorted(seen))]
    items = [per_query[q][i] for i in range(max(counts.values()))
             for q in mix["queries"] if i < counts[q]]
    return mods, items


def stream_plan(mix: dict, items: list[Item], seed: int, stream: int) -> list[Item]:
    """What stream `stream` sends, round after round: the queries in the
    cell's rotation (each stream starting one query further on), each
    query's parameter sets in a permutation of this stream's own (a query
    with fewer sets than another goes round its own again)."""
    queries = list(mix["queries"])
    counts = _sets_per_query(mix)
    rng = _rng(seed, "stream", stream)
    order = {q: rng.permutation(counts[q]) for q in queries}
    by = {(it.query, it.set_no): it for it in items}
    shift = stream % len(queries)
    rot = queries[shift:] + queries[:shift]
    return [by[(q, int(order[q][r % counts[q]]))]
            for r in range(max(counts.values())) for q in rot]
