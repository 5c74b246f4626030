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

The snapshot: the program does not say which raft index a plan was made
against (see PERF.md, for the tracing issue), but a plan sees all of the
fleet and, of the placements, exactly those committed at or below its
snapshot's index, and the window only adds placements. So the candidates
are the states after each commit between the evaluation's creation and its
own plan's commit; the reference walks each until its first disagreement.
A plan that agrees with the reference on no candidate is a mismatch, and
is reported with the candidate that agreed longest.
"""
from __future__ import annotations

import time

import numpy as np

from . import reference, system


def name_index(name: str) -> int:
    return int(name[name.rindex("[") + 1:-1])


def read_back(state, records: list, fleet) -> dict:
    """Every placement of the run's jobs from the state store, as arrays."""
    node_of = {nid: i for i, nid in enumerate(fleet.ids)}
    jobs = []
    node, cidx, jobno = [], [], []
    for j, rec in enumerate(records):
        allocs = system.run_allocs(state, rec["id"])
        ks = [name_index(a.name) for a in allocs]
        ns = [node_of.get(a.node_id, -1) for a in allocs]
        cs = [int(a.create_index) for a in allocs]
        jobs.append({
            "rec": rec, "k": ks, "node": ns, "cidx": cs,
            "score": [system.recorded_score(a) for a in allocs],
            "evals": sorted({a.eval_id for a in allocs}),
            "whole": sorted(ks) == list(range(rec["count"])),
        })
        node += ns
        cidx += cs
        jobno += [j] * len(ns)
    return {"jobs": jobs, "node": np.asarray(node, np.int64),
            "cidx": np.asarray(cidx, np.int64),
            "job": np.asarray(jobno, np.int64)}


class Usage:
    """(cpu, mem, disk) used per node by the placements committed at or
    below a raft index, for a falling series of indices without summing
    the whole run again each time: the placements in commit order, taken
    away from the newest state as the index falls."""

    def __init__(self, back: dict, asks: np.ndarray, n_nodes: int) -> None:
        ok = back["node"] >= 0
        order = np.argsort(back["cidx"][ok], kind="stable")
        self.cidx = back["cidx"][ok][order]
        self.node = back["node"][ok][order]
        self.per = asks[back["job"][ok][order]]
        self.n = n_nodes

    def series(self, indices: list):
        """Yields (index, [cpu, mem, disk] arrays) for ``indices``, which
        fall."""
        hi = len(self.cidx)
        cur = None
        for index in indices:
            lo = int(np.searchsorted(self.cidx, index, side="right"))
            if cur is None:
                cur = [np.bincount(self.node[:lo], weights=self.per[:lo, d],
                                   minlength=self.n) for d in range(3)]
            else:
                for d in range(3):
                    cur[d] -= np.bincount(self.node[lo:hi],
                                          weights=self.per[lo:hi, d],
                                          minlength=self.n)
            hi = lo
            yield index, [np.rint(u).astype(np.int64) for u in cur]


def invariants(back: dict, fleet) -> dict:
    """Counts that the guarantees hold to zero, over every placement."""
    recs = [j["rec"] for j in back["jobs"]]
    asks = np.asarray([[r["spec"]["cpu"], r["spec"]["mem"], r["spec"]["disk"]]
                       for r in recs], np.int64).reshape(-1, 3)
    n = len(fleet)
    _, used = next(Usage(back, asks, n).series([np.iinfo(np.int64).max]))
    over = np.zeros(n, bool)
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


def choose_sample(state, back: dict, seed: int, want: int) -> list:
    """Indices of the jobs to replay: whole, committed by one plan, and
    with no more candidate snapshots than MAX_SNAPSHOTS (a job that waited
    through more commits than that is left to the checks over every
    placement); the largest first, then one of each kind the window
    finished, then more drawn from the seed."""
    ok = []
    for i, j in enumerate(back["jobs"]):
        if j["whole"] and len(set(j["cidx"])) == 1 and len(j["evals"]) == 1:
            j["snapshots"] = snapshots_of(state, back, j)
            if len(j["snapshots"]) <= MAX_SNAPSHOTS:
                ok.append(i)
    if not ok:
        return []
    rng = np.random.default_rng([int(seed), 0x5A3B])
    order = [ok[i] for i in rng.permutation(len(ok))]
    count = lambda i: back["jobs"][i]["rec"]["count"]  # noqa: E731
    picked = [max(order, key=count)]
    seen = {_kind(back["jobs"][picked[0]]["rec"]["spec"])}
    for i in order:
        kind = _kind(back["jobs"][i]["rec"]["spec"])
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
    """(fleet node, recorded score) per name index of a read-back job."""
    order = sorted(range(len(job["k"])), key=job["k"].__getitem__)
    return ([job["node"][i] for i in order], [job["score"][i] for i in order])


def replay_job(back: dict, usage: Usage, fleet, i: int, gap_limit: float) -> dict:
    """Replay job ``i`` against each candidate snapshot, newest first, until
    one agrees in every node and, within ``gap_limit``, in every recorded
    score (two snapshots that differ in a few placements often lead to the
    same nodes, and the scores tell them apart); else the result of the
    candidate that agreed longest, the closer in score of two such."""
    job = back["jobs"][i]
    spec = job["rec"]["spec"]
    served, scores = served_plan(job)
    eval_id = job["evals"][0]
    candidates = job["snapshots"]
    best = None
    for index, used in usage.series(candidates):
        mism, gap, steps = reference.compare(
            fleet, used, spec, eval_id, served, scores, stop_at_first=True)
        res = {"job": spec["id"], "kind": _kind(spec), "placements": len(served),
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
        mism, gap, _ = reference.compare(fleet, used, spec, eval_id, served,
                                         scores)
        best.update(mismatched=mism, score_gap=gap)
    return best


def judge(state, records: list, fleet, seed: int, want: int,
          score_gap_limit: float, counters: dict,
          compiles_in_window: int) -> dict:
    """All of the above; returns {"correct", "checks": [[name, value,
    limit], ...], "replayed": [...], "failed_jobs": n}."""
    t0 = time.perf_counter()
    back = read_back(state, records, fleet)
    t_read = time.perf_counter() - t0
    inv = invariants(back, fleet)
    asks = inv.pop("asks")
    not_whole = sum(1 for j in back["jobs"] if not j["whole"])
    twice = sum(1 for j in back["jobs"] if len(j["k"]) != len(set(j["k"])))
    back["commits"] = np.unique(back["cidx"])
    sample = choose_sample(state, back, seed, want)
    usage = Usage(back, asks, len(fleet))
    replayed = [replay_job(back, usage, fleet, i, score_gap_limit)
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
    for k, (v, limit) in sorted(counters.items()):
        check(k, v, limit)
    check("compiles_in_window", compiles_in_window, 0)
    correct = len(records) > 0 and all(c["ok"] for c in checks.values())
    return {"correct": bool(correct), "checks": checks, "replayed": replayed,
            "back": back, "usage": usage,
            "failed_jobs": not_whole, "compared_placements": compared,
            "read_back_s": t_read,
            "multi_plan_jobs": sum(1 for j in back["jobs"]
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
