"""The "digits" mix: the sweep's steps, placed by a BCube's symmetries.

Everything but placement is the sweep's (drivers/sweep.py): steps of
one co-flow from every template of the deck, one `solve_fast_batch`
call, the certificate, the horizon retry and the check.  A template
gives every task its server's address as labels, one per digit (a_k
first): at every digit the tasks' values in order of first appearance.
A draw (key (seed, 1, step, i), as the sweep's) maps the labels of each
digit to values by one random permutation of that digit's values, then
moves digit p to digit order[p] by one random permutation of the
digits.  Both are automorphisms of BCube_k(n) (a level-l switch joins
the servers that differ only in digit l; permuting digits permutes
levels), so a draw is its template up to a symmetry of the fabric, with
one LP shape and one amount of work, on servers the key chose.  The
mix's "radix" gives each digit's number of values (all equal, n, since
levels are permuted); the task servers, in address order, number their
product.

The window runs inside `repro.trace.recording()` and puts the seconds
of the program's spans, by name, in `obs["program_spans"]`.
"""
from __future__ import annotations

import numpy as np

import traffic
from drivers.sweep import Sweep


class Digits(Sweep):
    def __init__(self, cfg: dict, mix: dict, fabric, topo, seed: int):
        super().__init__(cfg, {**mix, "levels": mix["radix"]}, fabric, topo,
                         seed)
        radix = list(mix["radix"])
        servers = np.asarray(fabric.task_servers)
        if len(set(radix)) != 1 or len(servers) != np.prod(radix):
            raise ValueError(f"{len(servers)} task servers are no "
                             f"addresses of digits {radix}")
        self.radix = radix
        self.grid = servers.reshape(radix)

    def place(self, template: dict, r: np.random.Generator) -> np.ndarray:
        """Servers for a template's tasks (map, then reduce) under one
        automorphism drawn from `r`."""
        labels = np.array(template["map"] + template["reduce"])
        digits = np.arange(len(self.radix))
        values = np.stack([r.permutation(m) for m in self.radix])
        addr = np.empty_like(labels)
        addr[:, r.permutation(digits)] = values[digits, labels]
        return self.grid[tuple(addr.T)]

    def coflows(self, *key: int) -> list:
        """The step's co-flows: every template of the deck, drawn."""
        spec = self.cfg["shuffle"]
        n_map, n_reduce = spec["n_map"], spec["n_reduce"]
        out = []
        for i, (_, template) in enumerate(self.order(*key)):
            servers = self.place(template, traffic.rng(self.seed, *key, i))
            if len(servers) != n_map + n_reduce:
                raise ValueError(f"a template of {len(servers)} tasks for "
                                 f"a job of {n_map} + {n_reduce}")
            # the shuffle's flows, as traffic.shuffle makes them
            mappers, reducers = servers[:n_map], servers[n_map:]
            per_flow = spec["total_gbits"] / n_map / n_reduce
            out.append(traffic.Coflow(src=np.repeat(mappers, n_reduce),
                                      dst=np.tile(reducers, n_map),
                                      size=np.full(n_map * n_reduce,
                                                   per_flow)))
        return out

    def window(self, seconds: float, spans) -> dict:
        from repro import trace

        with trace.recording() as rec:
            obs = super().window(seconds, spans)
        obs["program_spans"] = rec.seconds()
        return obs
