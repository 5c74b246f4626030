"""The plain reference against a second witness: the program's HOST
iterator stack (scheduler_algorithm="binpack"), which shares no code with
the device path the benchmark times. Jobs one after another on a small
fleet; every served placement has to be the node the reference chooses,
and the device path's recorded score the reference's own."""
import re

import numpy as np
import pytest

import tiny

SPREAD = {"attribute": "${node.datacenter}", "weight": 50,
          "targets": {"dc1": 60, "dc2": 40}}
AFFINITY = {"linux": True, "weight": 50}
BOTH = ["dc1", "dc2"]
TEMPLATES = [
    dict(kind="service", cpu=500, mem=700, disk=50, count=60, datacenters=BOTH,
         linux_only=True),
    dict(kind="batch", cpu=800, mem=900, disk=50, count=50, datacenters=BOTH,
         linux_only=False),
    dict(kind="service", cpu=50, mem=64, disk=50, count=40, datacenters=BOTH,
         linux_only=True, spread=SPREAD, affinity=AFFINITY),
    dict(kind="service", cpu=300, mem=300, disk=50, count=45, datacenters=["dc1"],
         linux_only=True, affinity=AFFINITY,
         spread={"attribute": "${node.datacenter}", "weight": 50,
                 "targets": {"dc1": 100}}),
    dict(kind="service", cpu=1200, mem=2000, disk=50, count=70, datacenters=BOTH,
         linux_only=True),
]


@pytest.mark.parametrize("algorithm", ["binpack", "tpu_binpack"])
def test_reference_places_as_the_program_does(algorithm):
    from harness import cluster, jobs, reference, system

    system.import_program()
    fleet = cluster.make_fleet(dict(tiny.TINY_CONFIG["cluster"], nodes=160), 7)
    server = system.start_server(
        dict(num_schedulers=2, device_batch=4, deterministic=True,
             device_min_placements=0, scheduler_algorithm=algorithm),
        "ref-" + algorithm, 100.0)
    try:
        system.register_nodes(server, system.program_nodes(fleet))
        state = server.fsm.state
        index = {nid: i for i, nid in enumerate(fleet.ids)}
        usage = [np.zeros(len(fleet), np.int64) for _ in range(3)]
        for rep in range(2):
            for t, template in enumerate(TEMPLATES):
                spec = jobs.job_spec(template, f"j{rep}-{t}")
                server.register_job(system.program_job(spec))
                system._wait(lambda: system.committed_count(state, spec["id"])
                             >= spec["count"] and system.quiescent(server),
                             120, spec["id"])
                allocs = system.run_allocs(state, spec["id"])
                served = [None] * spec["count"]
                scores = [None] * spec["count"]
                for a in allocs:
                    k = int(re.search(r"\[(\d+)\]$", a.name).group(1))
                    served[k] = index[a.node_id]
                    scores[k] = system.recorded_score(a)
                mism, gap, steps = reference.compare(
                    fleet, usage, spec, allocs[0].eval_id, served,
                    scores if algorithm == "tpu_binpack" else None)
                assert (mism, steps) == (0, spec["count"]), spec["id"]
                # Q30 fixed point against float64
                assert gap < 1e-6, (spec["id"], gap)
                for a in allocs:
                    i = index[a.node_id]
                    usage[0][i] += spec["cpu"]
                    usage[1][i] += spec["mem"]
                    usage[2][i] += spec["disk"]
    finally:
        assert system.teardown(server) == []


def test_a_near_tie_is_a_mismatch():
    """The comparison is exact: a served node that scores a hair under the
    reference's best is a mismatch, as an exact tie broken against the ring
    and a real gap are."""
    from harness import cluster, reference

    def fleet(mems):
        n = len(mems)
        a = lambda v: np.asarray([v] * n, np.int64)  # noqa: E731
        return cluster.Fleet(
            ids=[f"n{i}" for i in range(n)], names=[f"n{i}" for i in range(n)],
            cpu=a(8000), mem=np.asarray(mems, np.int64), disk=a(100000),
            rcpu=a(0), rmem=a(0), rdisk=a(0), linux=np.ones(n, bool),
            dc=a(0), dc_names=["dc1"])

    spec = {"id": "j", "kind": "service", "count": 1, "cpu": 100, "mem": 1000,
            "disk": 10, "datacenters": ["dc1"], "linux_only": False,
            "spread": None, "affinity": None}
    zero = lambda f: [np.zeros(len(f), np.int64)] * 3  # noqa: E731

    def against(mems):
        f = fleet(mems)
        best = reference.follow(f, zero(f), spec, "e", [0])[0][0]
        right = reference.compare(f, zero(f), spec, "e", [best], None)
        wrong = reference.compare(f, zero(f), spec, "e", [1 - best], None)
        return right[0], wrong[0]

    assert against([16_000_000, 16_000_001]) == (0, 1)     # a near tie
    assert against([16_000_000, 16_000_000]) == (0, 1)     # an exact tie
    assert against([16_000_000, 17_000_000]) == (0, 1)     # a real gap
