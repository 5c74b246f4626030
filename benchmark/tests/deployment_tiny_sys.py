"""The rehearsal's deployment module: tiny.py copies it into a scratch
checkout as ``benchmark/deployments/tiny-sys-64.py``, which is all that a
configuration with system jobs adds besides its data files.

A job template of ``"kind": "system"`` is one allocation on every eligible
node (upstream's system scheduler); every template may carry a
``priority``. The configuration's ``jobs.setup`` lists the jobs that are
placed before the window, each ``{"id": ..., "template": {...}}``.
Nothing here evicts: the guarantee checked beside the shared ones is that
no placement leaves ``run``.
"""
import numpy as np

from harness import compare, jobs, reference, system


def _is_system(spec: dict) -> bool:
    return spec["kind"] == "system"


def job_spec(template: dict, job_id: str) -> dict:
    spec = jobs.job_spec(template, job_id)
    spec["priority"] = int(template.get("priority", 50))
    return spec


def program_job(spec: dict):
    if not _is_system(spec):
        job = system.program_job(spec)
        job.priority = spec["priority"]
        return job
    from nomad_tpu import mock
    from nomad_tpu.structs.structs import Constraint, Resources

    job = mock.system_job()
    job.id = spec["id"]
    job.priority = spec["priority"]
    job.datacenters = list(spec["datacenters"])
    job.constraints = ([Constraint(ltarget="${attr.kernel.name}",
                                   rtarget="linux", operand="=")]
                       if spec["linux_only"] else [])
    tg = job.task_groups[0]
    tg.ephemeral_disk.size_mb = spec["disk"]
    tg.tasks[0].resources = Resources(cpu=spec["cpu"], memory_mb=spec["mem"])
    return job


def _eligible(spec: dict, fleet) -> np.ndarray:
    """Nodes of the job's datacenters that pass its constraint."""
    dcs = [k for k, d in enumerate(fleet.dc_names) if d in spec["datacenters"]]
    return np.isin(fleet.dc, dcs) & (fleet.linux | (not spec["linux_only"]))


def expected_placements(spec: dict, fleet) -> list:
    # every eligible node: this deployment's system jobs fit beside anything
    if _is_system(spec):
        return [fleet.ids[i] for i in np.flatnonzero(_eligible(spec, fleet))]
    return list(range(spec["count"]))


def placement_key(alloc):
    job = alloc.job
    if job is not None and job.type == "system":
        return alloc.node_id
    return compare.name_index(alloc.name)


def setup(server, fleet, config: dict, seed: int) -> list:
    state = server.fsm.state
    records = []
    for entry in config["jobs"]["setup"]:
        spec = job_spec(entry["template"], entry["id"])
        rec = {"id": spec["id"], "spec": spec,
               "count": len(expected_placements(spec, fleet))}
        server.register_job(program_job(spec))
        system._wait(lambda: system.committed_count(state, rec["id"])
                     >= rec["count"] and system.quiescent(server),
                     300.0, f"set-up job {rec['id']}")
        records.append(rec)
    return records


def placements(fleet, used, spec: dict) -> tuple:
    """The plain reference of a system eval with nothing to evict
    (system_sched.go computeJobAllocs -> diffSystemAllocs ->
    computePlacements): every ready node of the job's datacenters that holds
    no allocation of the job is offered to the system stack on its own; the
    job's constraint, then the binpack fit beside what the snapshot holds
    (reserved included) decide; a constraint miss is no placement, a fit
    miss a failed one. The final score is the binpack term alone
    (structs/funcs.go ScoreFit, BestFit-v3): the system stack has no
    anti-affinity, spread or affinity iterator. Returns the fleet nodes
    placed, in node order, and their scores in float64.

    Departures from upstream: the nodes are taken in the fleet's order, not
    the state store's by id (one placement a node: no order changes the
    result); a failed placement's blocked eval is not modelled (this
    deployment sizes its system jobs to fit everywhere); no node starts
    with an allocation of the job (each job is registered once)."""
    ask = np.array([spec["cpu"], spec["mem"], spec["disk"]], np.int64)
    tot = np.stack([fleet.cpu, fleet.mem, fleet.disk])
    res = np.stack([fleet.rcpu, fleet.rmem, fleet.rdisk])
    util = res + np.stack(used) + ask[:, None]
    fits = _eligible(spec, fleet) & np.all(util <= tot, axis=0)
    free = 1.0 - util[:2] / (tot[:2] - res[:2]).astype(np.float64)
    score = np.clip(20.0 - (10.0 ** free[0] + 10.0 ** free[1]), 0.0, 18.0) / 18.0
    nodes = np.flatnonzero(fits)
    return nodes, score[nodes]


def replay(fleet, used, spec, eval_id, served, scores, stop_at_first=False):
    if not _is_system(spec):
        return reference.compare(fleet, used, spec, eval_id, served, scores,
                                 stop_at_first)
    nodes, ref_scores = placements(fleet, used, spec)
    want = dict(zip(nodes.tolist(), ref_scores.tolist()))
    mismatched = len(set(served) ^ set(want))
    gap = 0.0
    for node, score in zip(served, scores):
        if node in want:
            gap = max(gap, abs(float(score) - want[node]))
    return mismatched, gap, len(want)


def checks(back: dict, fleet) -> dict:
    return {"placements_that_left_run": int((back["left"] < compare.NEVER).sum())}
