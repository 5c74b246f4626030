"""From a profiler trace to numbers.

``extract`` reads the profiler's trace with nothing but JAX and keeps what
the reduction needs: for each device plane the events of its operation line
and of its program line, as (name, start_ns, duration_ns). ``reduce`` is
pure arithmetic over that, so it is checked against the small recorded
extract in testdata/.

Busy is the union of the intervals in which an operation ran on the
device; idle is the rest of the traced window. The idle gaps are named by
what the host was doing in them, from the program's phase spans on the
host clock, aligned on the mark the harness drops into the trace at the
window's start.
"""
from __future__ import annotations

import glob
import os
import re

OPS_LINES = ("XLA Ops",)
PROGRAM_LINES = ("XLA Modules",)
MARK = "bench_trace_mark"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def stop_and_read(log_dir: str):
    """End the running profiler session and return its trace as
    jax.profiler.ProfileData. The session's own ``stop()`` hands the trace
    over as bytes at once; ``jax.profiler.stop_trace`` would first export a
    JSON copy of it, which took four minutes for a 4 s slice of the
    p=1024 scan (2.8 M operation events), inside the run's time limit.
    Where this JAX keeps its session elsewhere, the public way is taken
    and the file it writes under ``log_dir`` is read."""
    import jax

    try:
        from jax._src import profiler as _p

        state = _p._profile_state
        with state.lock:
            xspace = state.profile_session.stop()
            state.reset()
        return jax.profiler.ProfileData.from_serialized_xspace(xspace)
    except (ImportError, AttributeError):
        jax.profiler.stop_trace()
        return jax.profiler.ProfileData.from_file(find_xplane(log_dir))


def extract(data) -> dict:
    """{"devices": [{"name", "ops": [...], "programs": [...]}],
    "mark_ns": start of the harness's mark on the trace's clock or None}
    from a jax.profiler.ProfileData."""
    devices, mark = [], None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            dev = {"name": plane.name, "ops": [], "programs": [],
                   "lines": []}
            for line in plane.lines:
                dev["lines"].append(line.name)
                if line.name in OPS_LINES:
                    dev["ops"] = [(e.name, float(e.start_ns), float(e.duration_ns))
                                  for e in line.events]
                elif line.name in PROGRAM_LINES:
                    dev["programs"] = [(e.name, float(e.start_ns),
                                        float(e.duration_ns))
                                       for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:") and mark is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == MARK:
                        mark = float(e.start_ns)
                        break
                if mark is not None:
                    break
    return {"devices": devices, "mark_ns": mark}


def union(intervals: list) -> list:
    """Sorted disjoint intervals covering the same points."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def program_name(name: str) -> str:
    """'jit_body(123)' -> 'jit_body': the id changes with every compile."""
    return re.sub(r"\(\d+\)$", "", name)


def op_name(name: str) -> str:
    """An operation's own name: XLA prints '%while.8 = (u32[]...) while(...)',
    the whole instruction; what comes before ' = ' names it."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def reduce(extract_: dict, lo_ns: float, hi_ns: float) -> dict:
    """Busy union, idle gaps, per-operation and per-program device seconds
    inside [lo_ns, hi_ns], averaged over the device planes that ran
    anything."""
    window_s = (hi_ns - lo_ns) / 1e9
    per_dev = []
    for dev in extract_["devices"]:
        events = dev["ops"] or dev["programs"]
        clipped = [(max(s, lo_ns), min(s + d, hi_ns))
                   for _, s, d in events if s + d > lo_ns and s < hi_ns]
        if not clipped:
            continue
        busy = union(clipped)
        gaps, at = [], lo_ns
        for a, b in busy:
            if a > at:
                gaps.append((at, a))
            at = b
        if hi_ns > at:
            gaps.append((at, hi_ns))
        ops: dict = {}
        for name, s, d in dev["ops"]:
            if s + d > lo_ns and s < hi_ns:
                name = op_name(name)
                ops[name] = ops.get(name, 0.0) + (min(s + d, hi_ns) - max(s, lo_ns)) / 1e9
        programs: dict = {}
        for name, s, d in dev["programs"]:
            if s + d > lo_ns and s < hi_ns:
                key = program_name(name)
                rec = programs.setdefault(key, {"seconds": 0.0, "runs": 0,
                                                "events": []})
                rec["seconds"] += (min(s + d, hi_ns) - max(s, lo_ns)) / 1e9
                rec["runs"] += 1
                rec["events"].append((s, s + d))
        per_dev.append({"name": dev["name"],
                        "busy_s": sum(b - a for a, b in busy) / 1e9,
                        "gaps": gaps, "ops": ops, "programs": programs})
    if not per_dev:
        return {"window_s": window_s, "busy_s": 0.0, "devices": 0,
                "gaps": [], "ops": {}, "programs": {}}
    first = per_dev[0]
    return {"window_s": window_s,
            "busy_s": sum(d["busy_s"] for d in per_dev) / len(per_dev),
            "devices": len(per_dev),
            "gaps": sorted(first["gaps"], key=lambda g: g[0] - g[1]),
            "ops": first["ops"], "programs": first["programs"]}


WAITS = ("device_wait", "plan_submit", "wait_index", "engine_gate", "worker_busy")
NOT_PHASES = ("any_host", "busy", "window", "untracked")


def name_gaps(gaps: list, to_host_s, host_shares, top: int = 10,
              least_ns: float = 1000.0) -> list:
    """[[what the host was doing, seconds], ...] for the ``top`` longest
    gaps of a microsecond or more. ``to_host_s`` maps a trace time in ns to
    the host clock; ``host_shares(t0, t1)`` gives seconds per phase inside
    a host-clock interval (the program's phases.wall_shares). A gap is
    named after the phase of host WORK that filled most of it; where only
    phases that wait were open (a worker parked on the device or on the
    plan queue), after the longest of those, marked as a wait."""
    out = []
    for a, b in gaps:
        if len(out) >= top or b - a < least_ns:
            break
        shares = {k: v for k, v in host_shares(to_host_s(a), to_host_s(b)).items()
                  if k not in NOT_PHASES and v > 0}
        work = {k: v for k, v in shares.items() if k not in WAITS}
        if work:
            what = max(work, key=work.get)
        elif shares:
            what = "waiting:" + max(shares, key=shares.get)
        else:
            what = "no_phase_open"
        out.append([what, (b - a) / 1e9])
    return out


def cut(extract_: dict, lo_ns: float, hi_ns: float) -> dict:
    """The events of ``extract_`` that touch [lo_ns, hi_ns], times made
    relative to lo_ns and names cut short: a recorded trace small enough to
    keep under testdata/."""
    def keep(events):
        return [[op_name(n), s - lo_ns, d] for n, s, d in events
                if s + d > lo_ns and s < hi_ns]
    mark = extract_.get("mark_ns")
    return {"devices": [{"name": d["name"], "lines": d["lines"],
                         "ops": keep(d["ops"]), "programs": keep(d["programs"])}
                        for d in extract_["devices"]],
            "mark_ns": None if mark is None else mark - lo_ns,
            "span_ns": hi_ns - lo_ns}
