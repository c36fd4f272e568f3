"""The one traffic generator. A mix is a data file, traffic/<name>.json:

    {"loop": "closed", "clients": 1, "templates": ["q6", "q1"]}

and optionally "parameters": {"<template>": [<parameter set>, ...]}, the
part of a template's domain the mix draws from before the rest, and
"literal_keyed_sites": [<compile site>, ...], the sites that new
literals are known to compile (none since PR 30; run.py names any site
that fires in a window and is not listed).

Each closed-loop client sends its next statement when the last one's
answer has been read to the end. A client's stream is its templates in
rotation; each statement takes the template's next parameter set from a
seeded order of the template's whole TPC-H domain, so none is drawn
twice in a run, and the validation set (the warm-up's) is never drawn.
Clients share one order and take disjoint slices of it. TPC-H's domains
are what they are (q1: 60 sets, q18: 4): a window ends when the template
with the fewest sets has none left for another round (`round_left`),
never by a second pass over a domain.

The order is a seeded shuffle, then spread: the next set is the one
whose slot values have been used least so far (the warm-up's count),
first in the shuffle among equals. Q3 has five SEGMENTs; drawn freely,
some seeds send the same one twice in a window, the exchange spool
answers that statement's customer build fragment, and the seed has
changed the work (seen on the chip: `spool_hits` 1 in two of three
runs). Spread, a slot value comes again only when every other has been
used as often.
"""

import importlib
import json
import os
import random

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    if mix.get("loop") != "closed":
        raise ValueError(f"traffic {name!r}: loop kind {mix.get('loop')!r} "
                         f"is not implemented (closed only)")
    if int(mix.get("clients", 0)) < 1 or not mix.get("templates"):
        raise ValueError(f"traffic {name!r}: needs clients >= 1 and "
                         f"templates")
    return mix


def load_template(name: str):
    return importlib.import_module(f"queries.{name}")


def draws(template, seed: int, first=()):
    """The template's domain without its validation set, in the order
    this seed gives it. `first` (a mix's `parameters` for the template)
    are the sets that come before all others."""
    domain = template.domain()
    for p in first:
        if p not in domain or p == template.VALIDATION:
            raise ValueError(f"{template.NAME}: {p} is not a parameter set "
                             f"a window may draw")
    used = {(k, v): 1 for k, v in template.VALIDATION.items()}
    rng = random.Random(f"{seed}:{template.NAME}")
    order = []
    for pool in (list(first), [p for p in domain if p not in first
                               and p != template.VALIDATION]):
        rng.shuffle(pool)
        while pool:
            i = min(range(len(pool)), key=lambda i: sum(
                used.get(kv, 0) for kv in pool[i].items()))
            for kv in pool[i].items():
                used[kv] = used.get(kv, 0) + 1
            order.append(pool.pop(i))
    return order


class Stream:
    """One client's statements: (template, params, sql), until a
    template's share of the domain runs out. The caller asks
    `round_left()` before each round; `next()` past the end is an
    error."""

    def __init__(self, mix: dict, seed: int, schema: str, client: int = 0):
        self.schema = schema
        self.templates = [load_template(t) for t in mix["templates"]]
        n = int(mix["clients"])
        first = mix.get("parameters", {})
        self.pools = [draws(t, seed, first.get(t.NAME, ()))[client::n]
                      for t in self.templates]
        # every seed sends the same sequence of templates, so the same
        # amount of work; clients start one template apart
        self.turn = client
        self.taken = [0] * len(self.templates)

    def __iter__(self):
        return self

    def round_left(self) -> bool:
        """Whether every template has a set left for this client: one
        more statement of each, a round, can be drawn."""
        return self.spent() is None

    def spent(self):
        """(template name, sets drawn) of the first template with none
        left, or None while a round is left."""
        for t, n, pool in zip(self.templates, self.taken, self.pools):
            if n >= len(pool):
                return t.NAME, n
        return None

    def __next__(self):
        i = self.turn % len(self.templates)
        self.turn += 1
        if self.taken[i] >= len(self.pools[i]):
            raise RuntimeError(
                f"{self.templates[i].NAME}: domain exhausted after "
                f"{self.taken[i]} statements")
        params = self.pools[i][self.taken[i]]
        self.taken[i] += 1
        t = self.templates[i]
        return t, params, t.render(params, self.schema)
