"""What a configuration may bring of its own: its deployment module.

``benchmark/deployments/<config>.py``, where it exists, is found by the
configuration's name, as a metric's reader is found by the metric's. It
defines any of the hooks below; for each it does not define, the shared
default here holds, which is the harness's own code for service and batch
jobs on an empty cluster. A configuration without a file (``svc-spread-5k``,
``c1m-5k``) runs through the defaults alone. A module imports nothing of the
program at import time, and its reference nothing of the program at all.

The contract, hook by hook: what it carries, its default, and what reads it.

``job_spec(template, job_id) -> dict``
    One job of the configuration's ``jobs.templates`` as the harness reads
    it. A module passes through keys the default does not know (a job
    ``type``, ``priority``, more constraints). Default ``jobs.job_spec``:
    the ten keys of a service or batch job. Read by ``jobs.JobStream`` and
    ``jobs.warm_steps``, so by the window, the warm-up and every check.
``program_job(spec)``
    That job as the program's ``Job``. Default ``system.program_job``
    (``mock.job()`` or ``mock.batch_job()``). Read by ``loadgen`` (the
    window) and ``system.warm_up``.
``expected_placements(spec, fleet) -> list``
    The placement keys a whole job holds. Default: the name indices
    ``range(spec["count"])``. Their number is what ``loadgen.Observer`` and
    ``system.warm_up`` wait for; ``compare.read_back`` calls a job whole
    when its keys are exactly these (``jobs_not_committed``).
``placement_key(alloc)``
    What tells a job's placements apart. Default: the name index (a system
    job's allocations share one name: a module keys them by node). Read by
    ``jobs_with_a_placement_twice`` and by the served order a replay gets.
``setup(server, fleet, config, seed) -> list``
    The cluster state the window opens on: it runs after ``warm_up`` and
    before the window, through ``Server.register_job`` and the served path,
    waits until the server is quiescent, and returns the records
    (``{"id", "spec", "count"}``) of the jobs it placed. Its time falls
    inside ``setup_s``. Default: places nothing, returns ``[]``. Its
    records' placements are read back beside the window's and counted by
    ``compare.Usage`` (every snapshot a replay sees, ``nodes_over_capacity``)
    and by the per-placement checks; they are not held to
    ``jobs_not_committed`` or replayed.
``replay(fleet, used, spec, eval_id, served, scores, stop_at_first)``
    The plain reference of one eval on one snapshot (``used``: the
    (cpu, mem, disk) arrays live there, reserved excluded; ``served``: the
    fleet node of each placement in ``placement_key`` order, ``scores`` what
    the program recorded). Returns ``(mismatched, score_gap, steps)``.
    Default ``reference.compare``: upstream's generic stack in float64.
    Read by ``placements_mismatching_reference`` and ``widest_score_gap``.
``sample_kind(spec)``
    What ``compare.choose_sample`` takes one job of each of. Default
    ``compare._kind``: (kind, has stanzas).
``checks(back, fleet) -> {name: count}``
    Counts of the deployment's own guarantees over the read-back (see
    ``compare.read_back``: every placement's node, create index and, for
    one that left ``run``, the index it left at), each held to 0 by
    ``compare.judge``. Default: none.

What the shared code reads for every configuration: an allocation of a
window's or set-up's job that left ``run`` (desired status stop or evict)
is recorded with the raft index it left at (its ``modify_index``);
``compare.Usage`` counts each placement over ``[create index, left index)``;
``nodes_over_capacity`` is read at the end and on the state each departure
was applied to; ``loadgen.run_window``'s ``placed0``/``placed1`` count the
allocations created and ``left0``/``left1`` those that left ``run``.
"""
from __future__ import annotations

import importlib.util
import os
import types

from . import compare, jobs, reference, system


def expected_placements(spec: dict, fleet) -> list:
    return list(range(int(spec["count"])))


def placement_key(alloc):
    return compare.name_index(alloc.name)


def setup(server, fleet, config: dict, seed: int) -> list:
    return []


def checks(back: dict, fleet) -> dict:
    return {}


DEFAULTS = {
    "job_spec": jobs.job_spec,
    "program_job": system.program_job,
    "expected_placements": expected_placements,
    "placement_key": placement_key,
    "setup": setup,
    "replay": reference.compare,
    "sample_kind": compare._kind,
    "checks": checks,
}


def defaults() -> types.SimpleNamespace:
    return types.SimpleNamespace(**DEFAULTS)


def load(root: str, config: str) -> types.SimpleNamespace:
    """The hooks of configuration ``config``: its module's where
    ``<root>/deployments/<config>.py`` defines them, the defaults' else."""
    mod = None
    path = os.path.join(root, "deployments", config + ".py")
    if os.path.isfile(path):
        spec = importlib.util.spec_from_file_location(
            "bench_deployment_" + config.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    return types.SimpleNamespace(**{
        hook: getattr(mod, hook, default) for hook, default in DEFAULTS.items()})
