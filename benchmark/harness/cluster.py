"""The fleet of a configuration, made from the seed as plain arrays.

Every seed gets the same multiset of node classes (the configuration's
shares, exactly) in another order with other ids, so the seed changes
which node is which and never how much capacity the cluster has. Imports
nothing of the program: system.py turns a Fleet into the program's nodes,
reference.py reads the arrays.
"""
from __future__ import annotations

import dataclasses
import uuid

import numpy as np


@dataclasses.dataclass
class Fleet:
    ids: list
    names: list
    cpu: np.ndarray
    mem: np.ndarray
    disk: np.ndarray
    rcpu: np.ndarray
    rmem: np.ndarray
    rdisk: np.ndarray
    linux: np.ndarray
    dc: np.ndarray          # index into dc_names
    dc_names: list

    def __len__(self) -> int:
        return len(self.ids)


def _by_share(values_to_share: dict, n: int) -> list:
    """``n`` values in the stated shares, largest remainders rounded up."""
    items = [(k, float(s)) for k, s in values_to_share.items()]
    total = sum(s for _, s in items)
    exact = [(k, n * s / total) for k, s in items]
    counts = {k: int(e) for k, e in exact}
    short = n - sum(counts.values())
    for k, e in sorted(exact, key=lambda ke: ke[1] - int(ke[1]), reverse=True)[:short]:
        counts[k] += 1
    out = []
    for k, _ in items:
        out += [k] * counts[k]
    return out


def make_fleet(cluster: dict, seed: int) -> Fleet:
    """``cluster`` is the ``cluster`` object of a configuration file."""
    n = int(cluster["nodes"])
    rng = np.random.default_rng([int(seed), 0xC1A5])
    cpus, mems = cluster["cpu_mhz"], cluster["memory_mb"]
    # the cpu x mem classes in equal shares, as the source's uniform draw
    combos = [(c, m) for c in cpus for m in mems]
    cls = [combos[i % len(combos)] for i in range(n)]
    disk = [int(k) for k in _by_share(cluster["disk_mb"], n)]
    n_win = int(round(n * float(cluster.get("windows_share", 0.0))))
    linux = [False] * n_win + [True] * (n - n_win)
    dcs = _by_share(cluster["datacenters"], n)
    dc_names = list(cluster["datacenters"])
    # each attribute takes its own order, so the classes mix freely
    cls = [cls[i] for i in rng.permutation(n)]
    disk = [disk[i] for i in rng.permutation(n)]
    linux = [linux[i] for i in rng.permutation(n)]
    dcs = [dcs[i] for i in rng.permutation(n)]
    res = cluster["reserved"]
    i64 = lambda xs: np.asarray(xs, np.int64)  # noqa: E731
    return Fleet(
        ids=[str(uuid.UUID(bytes=rng.bytes(16), version=4)) for _ in range(n)],
        names=[f"node-{i}" for i in range(n)],
        cpu=i64([c for c, _ in cls]), mem=i64([m for _, m in cls]),
        disk=i64(disk),
        rcpu=i64([res["cpu_mhz"]] * n), rmem=i64([res["memory_mb"]] * n),
        rdisk=i64([res["disk_mb"]] * n),
        linux=np.asarray(linux, bool),
        dc=i64([dc_names.index(d) for d in dcs]), dc_names=dc_names,
    )
