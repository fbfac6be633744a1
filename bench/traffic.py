"""The benchmark's one traffic generator: MapReduce shuffle co-flows.

A shuffle (arXiv:2008.03497 §IV-B; sort with identity mappers, as in
Indy GraySort) sends every map output to every reducer, one flow per
(mapper, reducer) pair, and every map output is total / n_map.  A
configuration's "shuffle" block fixes the job: n_map, n_reduce and
total_gbits.

Tasks sit one to a server.  Where they sit is drawn from a mix's deck
of placement templates: a template gives every task a path of group
labels down the fabric's hierarchy (the mix's "levels": the task
servers split into levels[0] equal groups in their order, each of those
into levels[1], and so on; a PON cell's racks, a fat-tree's pods and
edge switches).  A draw maps the labels at every level to distinct
groups at random, and the tasks of one group to distinct servers of it
at random: the co-flow is the template's up to a symmetry of the fabric,
so its LP has the template's shape and work, on servers the key chose.
A step of a mix takes every template of the deck, so every step does the
same work in a new place and order.

Every draw comes from numpy's generator seeded with a key, so one key
gives one co-flow.  The horizon is the paper's slot count heuristic:
the slowest endpoint's continuous-time bound, stretched by a slack,
plus extra slots.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from reference import Fabric


@dataclasses.dataclass
class Coflow:
    src: np.ndarray
    dst: np.ndarray
    size: np.ndarray


def rng(*key: int) -> np.random.Generator:
    """A generator keyed by whole numbers of any size (taken mod 2**64)."""
    return np.random.default_rng([int(k) % 2 ** 64 for k in key])


def place(fab: Fabric, levels: list[int], paths: list[list[int]],
          r: np.random.Generator) -> np.ndarray:
    """Servers for tasks with group-label `paths`, drawn from `r`: labels
    map to distinct groups under each parent group, tasks of one leaf
    group to distinct servers of it."""
    servers = np.asarray(fab.task_servers)
    if len(servers) % int(np.prod(levels)):
        raise ValueError(f"{len(servers)} task servers do not split into "
                         f"groups of {levels}")
    grid = servers.reshape(*levels, -1)
    perms, leaves, out = {}, {}, []
    for path in paths:
        group = ()
        for depth, label in enumerate(path):
            if group not in perms:
                perms[group] = r.permutation(levels[depth])
            group += (int(perms[group][label]),)
        if group not in leaves:
            leaves[group] = iter(r.permutation(grid[group]))
        out.append(next(leaves[group]))
    return np.array(out)


def shuffle(fab: Fabric, spec: dict, levels: list[int], template: dict,
            r: np.random.Generator) -> Coflow:
    """One shuffle co-flow placed by `template` ({"map": paths, "reduce":
    paths}), drawn from `r`."""
    n_map, n_reduce = spec["n_map"], spec["n_reduce"]
    if (len(template["map"]), len(template["reduce"])) != (n_map, n_reduce):
        raise ValueError(f"a template of {len(template['map'])} + "
                         f"{len(template['reduce'])} tasks for a job of "
                         f"{n_map} + {n_reduce}")
    servers = place(fab, levels, template["map"] + template["reduce"], r)
    mappers, reducers = servers[:n_map], servers[n_map:]
    out = np.full(n_map, spec["total_gbits"] / n_map)
    return Coflow(src=np.repeat(mappers, n_reduce),
                  dst=np.tile(reducers, n_map),
                  size=np.repeat(out / n_reduce, n_reduce))


def n_slots(fab: Fabric, cf: Coflow, rho: float, slack: float,
            extra: int) -> int:
    """Slots for a co-flow: ceil(slack x bound / slot) + extra, where the
    bound is the largest offered volume over an endpoint's rate (egress
    capped at rho)."""
    V = fab.n_vertices
    out_g, in_g = np.zeros(V), np.zeros(V)
    np.add.at(out_g, cf.src, cf.size)
    np.add.at(in_g, cf.dst, cf.size)
    cap_out, cap_in = np.zeros(V), np.zeros(V)
    per_edge = fab.cap.sum(axis=1)
    np.add.at(cap_out, fab.edges[:, 0], per_edge)
    np.add.at(cap_in, fab.edges[:, 1], per_edge)
    bound = max(float((out_g / np.minimum(np.maximum(cap_out, 1e-9), rho))
                      .max(initial=0.0)),
                float((in_g / np.maximum(cap_in, 1e-9)).max(initial=0.0)))
    return max(int(np.ceil(slack * bound / fab.slot_s)) + extra, 2)
