"""The job stream of a configuration, drawn from the seed.

A configuration lists its job templates as data. The stream is the
template list in blocks: each block holds every template once, in an
order drawn from the seed, so every seed submits the same sizes in another
order. Imports nothing of the program.
"""
from __future__ import annotations

import numpy as np


def job_spec(template: dict, job_id: str) -> dict:
    """One job as the reference and system.py read it."""
    return {
        "id": job_id,
        "kind": template["kind"],
        "count": int(template["count"]),
        "cpu": int(template["cpu"]),
        "mem": int(template["mem"]),
        "disk": int(template["disk"]),
        "datacenters": list(template["datacenters"]),
        "linux_only": bool(template["linux_only"]),
        "spread": template.get("spread"),
        "affinity": template.get("affinity"),
    }


class JobStream:
    """``next()`` gives job 0, 1, 2, ... of the seed's stream; calling it
    from several clients under their own lock is the caller's business."""

    def __init__(self, templates: list, seed: int, prefix: str = "job",
                 spec_of=job_spec):
        self.templates = templates
        self.spec_of = spec_of
        self.rng = np.random.default_rng([int(seed), 0x10B5])
        self.prefix = prefix
        self.i = 0
        self._block: list = []

    def next(self) -> dict:
        if not self._block:
            self._block = list(self.rng.permutation(len(self.templates)))
        t = int(self._block.pop(0))
        spec = self.spec_of(self.templates[t], f"{self.prefix}-{self.i}")
        spec["template"] = t
        self.i += 1
        return spec


def warm_steps(jobs_cfg: dict, spec_of=job_spec) -> list:
    """[(job dict, scale_to or 0)] from a configuration's ``jobs.warm``:
    [{"template": t, "counts": [...], "scale_by": k}]. Each count is one job
    of the template at that size; ``scale_by`` registers the last of them
    again with that many more tasks. The counts span the step buckets a
    retry of any size can be padded into, so that nothing compiles in the
    window."""
    steps = []
    for w in jobs_cfg["warm"]:
        template = jobs_cfg["templates"][int(w["template"])]
        counts = [int(c) for c in w["counts"]]
        for i, count in enumerate(counts):
            spec = spec_of(dict(template, count=count),
                           f"warm-{w['template']}-{count}")
            last = i == len(counts) - 1
            steps.append((spec, count + int(w.get("scale_by", 0))
                          if last and w.get("scale_by") else 0))
    return steps
