"""The deployment seam (harness/deployment.py): a configuration that brings
no module is judged, streamed and warmed as the harness before the seam did
it; one that brings a module (the rehearsal's ``tiny-sys-64``: a system job
kind with priorities, a cluster filled in set-up) runs as files only, and
each planted fault turns ``correct`` false through the check it names; and
``compare.Usage`` counts an allocation that left ``run`` below the index it
left at and not from there on."""
import io
import json
import os
import subprocess
import sys
import tarfile
import uuid

import numpy as np
import pytest

import tiny

# the commit before the seam: its harness is the one the defaults restate
PARENT = "a272de9b8b30c9700039032c6b3543060534eec2"
SEED = 2 ** 31 + 99


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    import run
    from harness import system

    system.import_program()
    tmp = str(tmp_path_factory.mktemp("bench"))
    repo, manifest = tiny.scratch_checkout(tmp)
    return {"run": run, "repo": repo, "manifest": manifest, "tmp": tmp,
            "device": system.device_facts()}


@pytest.fixture(scope="module")
def parent(tmp_path_factory):
    """PARENT's benchmark/harness, imported as the package harness_parent."""
    tmp = tmp_path_factory.mktemp("parent")
    try:
        tar = subprocess.run(
            ["git", "-C", tiny.REPO, "archive", PARENT, "benchmark/harness"],
            capture_output=True, check=True, timeout=60).stdout
    except (OSError, subprocess.SubprocessError):
        pytest.skip("the parent commit is not at hand (no git history here)")
    tarfile.open(fileobj=io.BytesIO(tar)).extractall(tmp, filter="data")
    os.rename(tmp / "benchmark" / "harness", tmp / "harness_parent")
    sys.path.insert(0, str(tmp))
    try:
        import harness_parent.compare
        import harness_parent.jobs
    finally:
        sys.path.remove(str(tmp))
    return harness_parent


def _cell(checkout, name, seed=SEED):
    return checkout["run"].run_cell(
        checkout["manifest"], checkout["repo"], name, seed, 3.0, False,
        checkout["device"], out_dir=checkout["tmp"])


def _config(name):
    with open(os.path.join(tiny.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


# -- nothing that exists moved ----------------------------------------------

def test_the_parents_judge_reads_one_store_as_the_changes(checkout, parent,
                                                          monkeypatch):
    from harness import compare

    real, seen = compare.judge, {}

    def judge(*args, **kw):
        seen["args"], seen["kw"] = args, kw
        seen["result"] = real(*args, **kw)
        return seen["result"]

    monkeypatch.setattr(compare, "judge", judge)
    result = _cell(checkout, "tiny-64.open")
    assert result["correct"], result["checks"]
    assert seen["kw"] == {} and seen["args"][8] == []   # no set-up records
    theirs = parent.compare.judge(*seen["args"][:8])
    fresh = real(*seen["args"][:8])
    for ours in (seen["result"], fresh):
        for key in ("checks", "replayed", "failed_jobs"):
            assert ours[key] == theirs[key], key
    assert theirs["replayed"] and list(theirs["checks"]) == list(result["checks"])


@pytest.mark.parametrize("config", ["svc-spread-5k", "c1m-5k"])
def test_job_streams_and_warm_steps_are_the_parents(parent, config):
    from harness import deployment, jobs

    cfg = _config(config)
    dep = deployment.load(tiny.BENCH, config)
    for seed in (1, 2 ** 31 + 5, 4200000101):
        ours = jobs.JobStream(cfg["jobs"]["templates"], seed, spec_of=dep.job_spec)
        theirs = parent.jobs.JobStream(cfg["jobs"]["templates"], seed)
        assert [ours.next() for _ in range(200)] == [theirs.next() for _ in range(200)]
    assert (jobs.warm_steps(cfg["jobs"], dep.job_spec)
            == parent.jobs.warm_steps(cfg["jobs"]))


# -- a deployment that brings its own module --------------------------------

def test_a_system_kind_on_a_cluster_filled_in_set_up_is_correct(checkout):
    result = _cell(checkout, "tiny-sys-64.open")
    assert result["correct"], result["checks"]
    checks = result["checks"]
    assert checks["placements_that_left_run"]["value"] == 0
    # the largest job is a system job over every linux node, and replayed
    assert checks["placements_compared"]["value"] > 61
    assert result["failed"] == 0 and result["attempted"] >= 6


def _load_with(monkeypatch, change):
    """deployment.load, with ``change(dep)`` applied to tiny-sys-64's hooks."""
    from harness import deployment

    real = deployment.load

    def load(root, config):
        dep = real(root, config)
        if config == "tiny-sys-64":
            change(dep)
        return dep

    monkeypatch.setattr(deployment, "load", load)


def _skip_a_node(dep):
    module = dep.replay.__globals__
    placements = module["placements"]
    module["placements"] = lambda *a: tuple(x[1:] for x in placements(*a))


def _two_on_one_node(monkeypatch):
    from nomad_tpu.tpu.engine import TpuPlacementEngine

    real = TpuPlacementEngine.run_forced

    def doubled(self, enc, *a, **kw):
        chosen, *rest = real(self, enc, *a, **kw)
        chosen = np.array(chosen)
        if enc.p > 1:
            chosen[1] = chosen[0]
        return (chosen, *rest)

    monkeypatch.setattr(TpuPlacementEngine, "run_forced", doubled)


def _overfill_in_set_up(dep):
    """After set-up, eight more copies of one of its service allocations
    (cpu 500 each) on a 4,000 MHz node, written past the scheduler."""
    from harness import system
    from nomad_tpu.server.fsm import ALLOC_UPDATE

    real = dep.setup

    def setup(server, fleet, config, seed):
        records = real(server, fleet, config, seed)
        state = server.fsm.state
        alloc = system.run_allocs(state, "svc-base")[0]
        node = int(np.flatnonzero(fleet.cpu == 4000)[0])
        copies = []
        for _ in range(8):
            a = alloc.copy_skip_job()
            a.id, a.node_id = str(uuid.uuid4()), fleet.ids[node]
            copies.append(a)
        server.raft_apply(ALLOC_UPDATE, copies)
        return records

    dep.setup = setup


@pytest.mark.parametrize("fault,check", [
    ("reference_skips_a_node", "placements_mismatching_reference"),
    ("two_on_one_node", "jobs_with_a_placement_twice"),
    ("over_capacity_from_set_up", "nodes_over_capacity"),
])
def test_a_planted_fault_is_not_correct(checkout, monkeypatch, fault, check):
    if fault == "reference_skips_a_node":
        _load_with(monkeypatch, _skip_a_node)
    elif fault == "two_on_one_node":
        _two_on_one_node(monkeypatch)
    else:
        _load_with(monkeypatch, _overfill_in_set_up)
    result = _cell(checkout, "tiny-sys-64.open")
    assert not result["correct"]
    assert not result["checks"][check]["ok"], result["checks"][check]
    assert result["checks"][check]["value"] >= 1


# -- Usage over live intervals ---------------------------------------------

def _back(fleet, node, asks):
    """A read-back by hand: job 0 placed in set-up at index 5 and gone at
    12; job 1 placed on the same node in the window at 10."""
    from harness import compare, jobs

    t = tiny.TINY_CONFIG["jobs"]["templates"][1]
    recs = [{"id": f"j{k}", "count": 1,
             "spec": jobs.job_spec(dict(t, count=1, cpu=c, mem=8, disk=8), f"j{k}")}
            for k, c in enumerate(asks)]
    return {"jobs": [{"rec": r} for r in recs], "node": np.array([node, node]),
            "cidx": np.array([5, 10]), "left": np.array([12, compare.NEVER]),
            "job": np.array([0, 1])}


def test_usage_counts_a_departure_below_its_index_and_not_from_it():
    from harness import cluster, compare

    fleet = cluster.make_fleet(tiny.TINY_CONFIG["cluster"], 3)
    back = _back(fleet, 0, (300, 200))
    asks = np.array([[300, 8, 8], [200, 8, 8]])
    usage = compare.Usage(back, asks, len(fleet))
    cpu = {i: int(used[0][0]) for i, used in usage.series([20, 12, 11, 10, 9, 5, 4])}
    assert cpu == {20: 200, 12: 200, 11: 500, 10: 500, 9: 300, 5: 300, 4: 0}


def test_a_missed_eviction_is_a_node_over_capacity():
    """Job 1 is placed on a node that holds job 0 and has room for only one
    of them; job 0 leaves two commits later (its eviction missed the plan
    that placed job 1). The last state is within capacity: only the state
    the departure was applied to shows it."""
    from harness import cluster, compare

    fleet = cluster.make_fleet(tiny.TINY_CONFIG["cluster"], 3)
    node = int(np.flatnonzero(fleet.cpu == 4000)[0])
    back = _back(fleet, node, (2000, 2000))
    assert compare.invariants(back, fleet)["nodes_over_capacity"] == 1
    back["left"][0] = 10          # evicted by the plan that placed job 1
    assert compare.invariants(back, fleet)["nodes_over_capacity"] == 0
