"""The comparison that decides ``correct``.

After the window has closed, what the timed path left in the state store is
read back and held against the configuration's guarantees:

* every job due in the window is committed in full, once (no job missing,
  no placement twice);
* no node is over capacity, no placement sits outside its job's
  datacenters or on a node its constraint excludes: plain arithmetic over
  every placement of the run;
* a sample of the finished jobs, drawn from the seed with the largest job
  in it and one of every template kind the window finished, is replayed
  step by step through the plain reference (reference.py) against the
  snapshot the plan was made on: every served placement must be the node
  the reference chooses (exact, limit 0), and the score the program
  recorded for it (what ``nomad alloc status`` shows) must be the
  reference's float64 score of that node to within the limit that the
  traffic file states, set between the program's readings and the
  lower-precision control's;
* every fallback counter reads zero and nothing compiled inside the
  window: a window served by a fallback is not this system's result.

A configuration's deployment module (deployment.py states the contract)
may bring how its jobs' placements are keyed and how many a whole job
holds, its own reference and checks, and the placements set-up made: those
count in every snapshot and in the capacity check, which reads the last
state and the one each departure from ``run`` was applied to.

The snapshot: the program does not say which raft index a plan was made
against (see PERF.md, for the tracing issue), but a plan sees all of the
fleet and, of the placements, exactly those live at its snapshot's index,
and the store changes only at commits. So the candidates are the states
after each commit between the evaluation's creation and its own plan's
commit; the reference walks each until its first disagreement.
A plan that agrees with the reference on no candidate is a mismatch, and
is reported with the candidate that agreed longest.
"""
from __future__ import annotations

import time

import numpy as np

from . import reference, system


def name_index(name: str) -> int:
    return int(name[name.rindex("[") + 1:-1])


NEVER = np.iinfo(np.int64).max   # the left index of a placement still in run
LEFT_RUN = ("stop", "evict")


def read_back(state, records: list, fleet, dep, setup_records: list = ()) -> dict:
    """Every placement of the run's jobs, and of the jobs that set-up
    placed, from the state store, as arrays: its node, the raft index it
    was created at and the one it left ``run`` at (NEVER while it runs).
    Per job the placements still in ``run``, keyed by the deployment's
    ``placement_key``; a window's job is whole when its keys are its
    ``expected_placements``. Set-up's jobs follow the window's, marked
    ``setup``."""
    node_of = {nid: i for i, nid in enumerate(fleet.ids)}
    jobs = []
    node, cidx, left, jobno = [], [], [], []
    for j, rec in enumerate(list(records) + list(setup_records)):
        every = state.allocs_by_job(system.NS, rec["id"], True)
        allocs = [a for a in every if a.desired_status == "run"]
        gone = [a for a in every if a.desired_status in LEFT_RUN]
        ks = [dep.placement_key(a) for a in allocs]
        ns = [node_of.get(a.node_id, -1) for a in allocs]
        cs = [int(a.create_index) for a in allocs]
        setup = j >= len(records)
        jobs.append({
            "rec": rec, "k": ks, "node": ns, "cidx": cs, "setup": setup,
            "score": [system.recorded_score(a) for a in allocs],
            "evals": sorted({a.eval_id for a in allocs}),
            "whole": sorted(ks) == sorted(dep.expected_placements(rec["spec"], fleet)),
        })
        node += ns + [node_of.get(a.node_id, -1) for a in gone]
        cidx += cs + [int(a.create_index) for a in gone]
        left += [NEVER] * len(allocs) + [int(a.modify_index) for a in gone]
        jobno += [j] * (len(ns) + len(gone))
    return {"jobs": jobs, "node": np.asarray(node, np.int64),
            "cidx": np.asarray(cidx, np.int64),
            "left": np.asarray(left, np.int64),
            "job": np.asarray(jobno, np.int64)}


class _Falling:
    """Per-node sums of ``per`` over the entries at or below an index, for
    a falling series of indices: the entries in index order, taken away
    from the newest sums as the index falls."""

    def __init__(self, index, node, per, n_nodes) -> None:
        order = np.argsort(index, kind="stable")
        self.index, self.node, self.per = index[order], node[order], per[order]
        self.n = n_nodes
        self.hi = len(self.index)
        self.cur = None

    def at(self, index) -> list:
        lo = int(np.searchsorted(self.index, index, side="right"))
        if self.cur is None:
            self.cur = [np.bincount(self.node[:lo], weights=self.per[:lo, d],
                                    minlength=self.n) for d in range(3)]
        else:
            for d in range(3):
                self.cur[d] -= np.bincount(self.node[lo:self.hi],
                                           weights=self.per[lo:self.hi, d],
                                           minlength=self.n)
        self.hi = lo
        return self.cur


class Usage:
    """(cpu, mem, disk) used per node at a raft index: every placement
    counts over ``[create index, left index)``, set-up's included. What was
    created at or below the index, less what left at or below it."""

    def __init__(self, back: dict, asks: np.ndarray, n_nodes: int) -> None:
        ok = back["node"] >= 0
        gone = ok & (back["left"] < NEVER)
        self.made = (back["cidx"][ok], back["node"][ok], asks[back["job"][ok]])
        self.gone = (back["left"][gone], back["node"][gone],
                     asks[back["job"][gone]])
        self.departures = np.unique(self.gone[0])
        self.n = n_nodes

    def series(self, indices: list):
        """Yields (index, [cpu, mem, disk] arrays) for ``indices``, which
        fall."""
        made = _Falling(*self.made, self.n)
        gone = _Falling(*self.gone, self.n) if len(self.departures) else None
        for index in indices:
            cur = made.at(index)
            if gone is not None:
                cur = [u - g for u, g in zip(cur, gone.at(index))]
            yield index, [np.rint(u).astype(np.int64) for u in cur]


def invariants(back: dict, fleet) -> dict:
    """Counts that the guarantees hold to zero, over every placement.
    Capacity is read on the last state and on the one each departure was
    applied to: between two departures a node's usage only grows, so these
    are its peaks."""
    recs = [j["rec"] for j in back["jobs"]]
    asks = np.asarray([[r["spec"]["cpu"], r["spec"]["mem"], r["spec"]["disk"]]
                       for r in recs], np.int64).reshape(-1, 3)
    n = len(fleet)
    usage = Usage(back, asks, n)
    peaks = [NEVER] + [int(i) - 1 for i in usage.departures[::-1]]
    over = np.zeros(n, bool)
    for _, used in usage.series(peaks):
        for u, tot, res in zip(used, (fleet.cpu, fleet.mem, fleet.disk),
                               (fleet.rcpu, fleet.rmem, fleet.rdisk)):
            over |= u + res > tot
    unknown = int((back["node"] < 0).sum())
    nodes = np.where(back["node"] >= 0, back["node"], 0)
    linux_only = np.asarray([r["spec"]["linux_only"] for r in recs], bool)
    bad_constraint = int((linux_only[back["job"]] & ~fleet.linux[nodes]).sum())
    dc_ok = np.asarray([[d in r["spec"]["datacenters"] for d in fleet.dc_names]
                        for r in recs], bool).reshape(-1, len(fleet.dc_names))
    bad_dc = int((~dc_ok[back["job"], fleet.dc[nodes]]).sum())
    return {"nodes_over_capacity": int(over.sum()),
            "placements_on_unknown_node": unknown,
            "placements_breaking_constraint": bad_constraint,
            "placements_outside_datacenters": bad_dc,
            "asks": asks}


MAX_SNAPSHOTS = 48     # candidate snapshots tried for one replayed job


def snapshots_of(state, back: dict, job: dict) -> list:
    """The raft indices at which job's one plan can have been made, newest
    first: its evaluation's creation, and every commit between that and
    its own."""
    ev = state.eval_by_id(job["evals"][0])
    born = int(ev.create_index) if ev is not None else 0
    commit = job["cidx"][0]
    between = back["commits"][np.searchsorted(back["commits"], born, side="right"):
                              np.searchsorted(back["commits"], commit, side="left")]
    return [int(c) for c in between[::-1]] + [born]


def choose_sample(state, back: dict, seed: int, want: int, kind_of) -> list:
    """Indices of the jobs to replay: whole, committed by one plan, and
    with no more candidate snapshots than MAX_SNAPSHOTS (a job that waited
    through more commits than that is left to the checks over every
    placement); the largest first, then one of each kind the window
    finished (``kind_of``, the deployment's ``sample_kind``), then more
    drawn from the seed. Set-up's jobs are not replayed."""
    ok = []
    for i, j in enumerate(back["jobs"]):
        if (j["whole"] and not j["setup"] and len(set(j["cidx"])) == 1
                and len(j["evals"]) == 1):
            j["snapshots"] = snapshots_of(state, back, j)
            if len(j["snapshots"]) <= MAX_SNAPSHOTS:
                ok.append(i)
    if not ok:
        return []
    rng = np.random.default_rng([int(seed), 0x5A3B])
    order = [ok[i] for i in rng.permutation(len(ok))]
    count = lambda i: back["jobs"][i]["rec"]["count"]  # noqa: E731
    picked = [max(order, key=count)]
    seen = {kind_of(back["jobs"][picked[0]]["rec"]["spec"])}
    for i in order:
        kind = kind_of(back["jobs"][i]["rec"]["spec"])
        if kind not in seen and len(picked) < want:
            seen.add(kind)
            picked.append(i)
    for i in order:
        if len(picked) >= want:
            break
        if i not in picked:
            picked.append(i)
    return picked


def _kind(spec: dict) -> tuple:
    return (spec["kind"], bool(spec.get("spread") or spec.get("affinity")))


def served_plan(job: dict) -> tuple:
    """(fleet node, recorded score) per placement key of a read-back job."""
    order = sorted(range(len(job["k"])), key=job["k"].__getitem__)
    return ([job["node"][i] for i in order], [job["score"][i] for i in order])


def replay_job(back: dict, usage: Usage, fleet, i: int, gap_limit: float,
               dep) -> dict:
    """Replay job ``i`` against each candidate snapshot, newest first, until
    one agrees in every node and, within ``gap_limit``, in every recorded
    score (two snapshots that differ in a few placements often lead to the
    same nodes, and the scores tell them apart); else the result of the
    candidate that agreed longest, the closer in score of two such, by
    the deployment's ``replay``."""
    job = back["jobs"][i]
    spec = job["rec"]["spec"]
    served, scores = served_plan(job)
    eval_id = job["evals"][0]
    candidates = job["snapshots"]
    best = None
    for index, used in usage.series(candidates):
        mism, gap, steps = dep.replay(
            fleet, used, spec, eval_id, served, scores, stop_at_first=True)
        res = {"job": spec["id"], "kind": dep.sample_kind(spec),
               "placements": len(served),
               "mismatched": mism, "agreed": steps - mism, "score_gap": gap,
               "snapshot": index, "candidates": len(candidates)}
        if best is None or (res["agreed"], -gap) > (best["agreed"],
                                                    -best["score_gap"]):
            best = res
        if mism == 0 and gap <= gap_limit:
            break
    if best["mismatched"]:
        # count every disagreement on the candidate that agreed longest
        _, used = next(usage.series([best["snapshot"]]))
        mism, gap, _ = dep.replay(fleet, used, spec, eval_id, served, scores,
                                  stop_at_first=False)
        best.update(mismatched=mism, score_gap=gap)
    return best


def judge(state, records: list, fleet, seed: int, want: int,
          score_gap_limit: float, counters: dict,
          compiles_in_window: int, setup_records: list = (),
          dep=None) -> dict:
    """All of the above; returns {"correct", "checks": [[name, value,
    limit], ...], "replayed": [...], "failed_jobs": n}. ``dep`` is the
    configuration's deployment (deployment.load); None is the defaults."""
    from . import deployment

    dep = dep or deployment.defaults()
    t0 = time.perf_counter()
    back = read_back(state, records, fleet, dep, setup_records)
    t_read = time.perf_counter() - t0
    inv = invariants(back, fleet)
    asks = inv.pop("asks")
    window_jobs = [j for j in back["jobs"] if not j["setup"]]
    not_whole = sum(1 for j in window_jobs if not j["whole"])
    twice = sum(1 for j in window_jobs if len(j["k"]) != len(set(j["k"])))
    back["commits"] = np.unique(np.concatenate(
        [back["cidx"], back["left"][back["left"] < NEVER]]))
    sample = choose_sample(state, back, seed, want, dep.sample_kind)
    usage = Usage(back, asks, len(fleet))
    replayed = [replay_job(back, usage, fleet, i, score_gap_limit, dep)
                for i in sample]
    compared = sum(r["placements"] for r in replayed)
    mismatched = sum(r["mismatched"] for r in replayed)
    checks = {}

    def check(name, value, limit, side="most"):
        ok = value <= limit if side == "most" else value >= limit
        checks[name] = {"value": value, "limit": limit, "side": side,
                        "ok": bool(ok)}

    check("jobs_not_committed", not_whole, 0)
    check("jobs_with_a_placement_twice", twice, 0)
    check("placements_mismatching_reference", mismatched, 0)
    check("widest_score_gap", max([r["score_gap"] for r in replayed],
                                  default=0.0), score_gap_limit)
    check("placements_compared", compared, min(want, 1), "least")
    for k, v in inv.items():
        check(k, v, 0)
    for k, v in dep.checks(back, fleet).items():
        check(k, v, 0)
    for k, (v, limit) in sorted(counters.items()):
        check(k, v, limit)
    check("compiles_in_window", compiles_in_window, 0)
    correct = len(records) > 0 and all(c["ok"] for c in checks.values())
    return {"correct": bool(correct), "checks": checks, "replayed": replayed,
            "back": back, "usage": usage,
            "failed_jobs": not_whole, "compared_placements": compared,
            "read_back_s": t_read,
            "multi_plan_jobs": sum(1 for j in window_jobs
                                   if len(set(j["cidx"])) > 1)}


def control_variants() -> dict:
    """{name: keyword arguments of reference.Replay}. The program scores in
    Q30 fixed point, which tracks the float64 score as float32 does, so the
    nearest precision below it is bfloat16: that is the control. float32
    is read beside it to show that it is not below the program. The other
    two break plan identity itself, each by a step that would make the
    kernel cheaper: ring_start_ignored starts the candidate ring at node 0
    instead of crc32(eval id), so every tie falls to another node;
    spread_ignored leaves the spread stanza's boost out of the score, and
    with it the spread planes."""
    import ml_dtypes

    return {"bfloat16": {"dtype": ml_dtypes.bfloat16},
            "float32": {"dtype": np.float32},
            "ring_start_ignored": {"ring_from_eval": False},
            "spread_ignored": {"with_spread": False}}


def control(verdict: dict, fleet) -> dict:
    """The controls' readings: the reference, changed as control_variants
    says, put in the program's place. It need not place: at every step of
    the same replayed jobs, on the snapshot that the served plan agreed
    with, the node the changed reference puts first and the score it gives
    that node are held against the float64 reference's, as the program's
    are. Returns, per variant, the two numbers that ``correct`` holds
    (mismatches, widest score gap) and the steps compared. tools/control.py
    and the tests call this; a benchmark run never does."""
    back = verdict["back"]
    by_id = {j["rec"]["id"]: j for j in back["jobs"]}
    out = {name: {"placements_mismatching_reference": 0,
                  "widest_score_gap": 0.0, "steps": 0}
           for name in control_variants()}
    for r in verdict["replayed"]:
        job = by_id[r["job"]]
        served, _ = served_plan(job)
        spec, eval_id = job["rec"]["spec"], job["evals"][0]
        _, used = next(verdict["usage"].series([r["snapshot"]]))
        ref = reference.follow(fleet, used, spec, eval_id, served)
        for name, variant in control_variants().items():
            picks, scores = reference.follow(fleet, used, spec, eval_id,
                                             served, **variant)
            mism, gap = reference.differences(picks, scores, *ref)
            o = out[name]
            o["placements_mismatching_reference"] += mism
            o["widest_score_gap"] = max(o["widest_score_gap"], gap)
            o["steps"] += len(ref[0])
    return out
