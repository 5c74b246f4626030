#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell of BENCHMARK.json, on the machine it is started on.
Everything that belongs to one configuration, one traffic mix or one metric
is a file found by its name:

    benchmark/configs/<config>.json       the deployment's sizes
    benchmark/deployments/<config>.py     where it has them: its own job
                                          kinds, starting state and
                                          reference (harness/deployment.py
                                          states the hooks and defaults)
    benchmark/traffic/<traffic>.json      the mix
    benchmark/metrics/<metric>.py         read(ctx) -> number or None

so a later cell or metric is new files and a new entry in BENCHMARK.json,
and no edit here. It refuses any platform but ``tpu`` (exit 2, no result
line); benchmark/tests/ call ``run_cell`` directly at tiny sizes.

The last line of stdout is the result object; the numbers that decided
``correct`` are its last key and the last lines of stderr.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from harness import (cluster, compare, deployment, jobs, loadgen, system,  # noqa: E402
                     trace, traffic, work)

TRACE_MARGIN_S = 0.05      # a traced run profiles the window less this, each end
WARM_TIMEOUT_S = 900.0


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


def load_manifest(repo: str) -> dict:
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(manifest: dict, name: str) -> tuple:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            for cfg in manifest["configs"]:
                if cfg["name"] == cell["config"]:
                    return cell, cfg
            raise SystemExit(f"cell {name}: no config {cell['config']!r}")
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(manifest: dict, cell: str, traced: bool) -> list:
    """The metrics this run reports: the cell's end-to-end metrics, or with
    --trace 1 its per-layer ones. A metric without a ``workloads`` key is
    every cell's."""
    group = manifest["per_layer"] if traced else manifest["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_reader(root: str, name: str):
    path = os.path.join(root, "metrics", name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"metric {name}: no reader {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Sampler(threading.Thread):
    """Traced runs only: the batcher's counters every few milliseconds, so a
    device program's run in the trace can be matched to the dispatch that
    the batcher counted when it returned."""

    def __init__(self, server) -> None:
        super().__init__(name="bench-sampler", daemon=True)
        self.server = server
        self.series: list = []   # (perf_counter, dispatches, evals)
        self._stop_evt = threading.Event()

    def stop(self) -> None:
        self._stop_evt.set()

    def run(self) -> None:
        last = None
        while not self._stop_evt.is_set():
            s = self.server.device_batcher.stats
            now = (s["dispatches"], s["evals"])
            if now != last:
                self.series.append((time.perf_counter(),) + now)
                last = now
            time.sleep(0.003)


class Profiler(threading.Thread):
    """Runs the JAX profiler over a slice of the window and drops a mark
    into the trace that ties its clock to the host's."""

    def __init__(self, log_dir: str, start_at: float, length_s: float) -> None:
        super().__init__(name="bench-profiler", daemon=True)
        self.log_dir, self.start_at, self.length_s = log_dir, start_at, length_s
        self.mark_host = None
        self.data = None
        self.t_start = self.t_stop = None
        self.error = None

    def run(self) -> None:
        import jax

        try:
            wait = self.start_at - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self.t_start = time.perf_counter()
            self.mark_host = time.perf_counter()
            with jax.profiler.TraceAnnotation(trace.MARK):
                time.sleep(0.001)
            # starting the session takes a moment: the end stays where it was
            time.sleep(max(0.0, self.start_at + self.length_s - time.perf_counter()))
            self.t_stop = time.perf_counter()
            self.data = trace.stop_and_read(self.log_dir)
        except Exception as e:  # noqa: BLE001: reported, the run then has no trace metrics
            self.error = e


def run_cell(manifest: dict, repo: str, workload: str, seed: int,
             seconds: float, traced: bool, device: dict,
             out_dir: str = "", mix_changes: dict = None,
             with_control: bool = False) -> dict:
    """Everything but the look for a chip. Returns the result object.
    ``mix_changes`` overrides keys of the traffic file: tools/sweep.py's
    way to try a rate, never the benchmark command's. ``with_control``
    adds the controls' readings (compare.control) under "control":
    tools/control.py's, never the benchmark command's."""
    root = os.path.join(repo, manifest["paths"][0])
    cell, cfg_entry = find_cell(manifest, workload)
    with open(os.path.join(repo, cfg_entry["file"])) as f:
        config = json.load(f)
    mix = traffic.load(traffic.find(root, cell["traffic"]), mix_changes)
    wanted = metrics_of(manifest, workload, traced)
    readers = {m["name"]: load_reader(root, m["name"]) for m in wanted}

    from nomad_tpu.trace import lifecycle
    from nomad_tpu.utils import metrics as prog_metrics
    from nomad_tpu.utils import phases

    meter = system.CompileMeter()
    sink = system.CounterSink()
    prog_metrics.register_sink(sink)
    dep = deployment.load(root, cfg_entry["name"])
    fleet = cluster.make_fleet(config["cluster"], seed)
    templates = config["jobs"]["templates"]
    stream = jobs.JobStream(templates, seed, spec_of=dep.job_spec)

    def count_of(spec):
        return len(dep.expected_placements(spec, fleet))

    server = system.start_server(config["server"], "bench-" + workload,
                                 WARM_TIMEOUT_S / 3)
    lingering: list = []
    try:
        system.register_nodes(server, system.program_nodes(fleet))
        log(f"{len(fleet)} nodes registered "
            f"({time.perf_counter() - T_PROCESS:.1f}s since process start)")
        warm = jobs.warm_steps(config["jobs"], dep.job_spec)
        dispatches = system.warm_up(server, warm, WARM_TIMEOUT_S,
                                    dep.program_job, count_of)
        log(f"warm: {len(warm)} job(s), {dispatches} dispatches; "
            f"compile-or-load {meter.seconds():.1f}s, "
            f"cache hits {meter.cache_hits} misses {meter.cache_misses}")
        setup_records = dep.setup(server, fleet, config, seed)
        if setup_records:
            log(f"set-up: {len(setup_records)} job(s) placed")
        shapes0 = system.batcher_shapes(server)
        log(f"batcher shapes after warm-up: {shapes0}")
        lifecycle.reset()
        stats0 = system.batcher_stats(server)
        counters0 = sink.snapshot()
        due = traffic.due_times(mix, seed, seconds) if mix["loop"] == "open" else []
        sampler = profiler = None
        trace_dir = os.path.join(out_dir or os.path.join(repo, "bench_out"),
                                 "trace")
        if traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            phases.enable()
            sampler = Sampler(server)
            sampler.start()
            # the whole window unless the mix says how much of it: the slice
            # then sits in the window's middle
            length = min(float(mix.get("trace_s", seconds)),
                         seconds) - 2 * TRACE_MARGIN_S
            profiler = Profiler(trace_dir, time.perf_counter()
                                + (seconds - length) / 2, length)
            profiler.start()
        setup_s = time.perf_counter() - T_PROCESS
        window = loadgen.run_window(server, stream, mix, due, seconds,
                                    dep.program_job, count_of)
        memory_peak = system.memory_peak_bytes()
        if traced:
            profiler.join()
            sampler.stop()
            sampler.join()
            phases.disable()
        stats1 = system.batcher_stats(server)
        counters1 = sink.snapshot()
        compiles = meter.inside(window["t0"], window["t1"])
        latencies = loadgen.latencies_ms(window)
        log(f"window {window['t1'] - window['t0']:.2f}s: "
            f"{len(window['records'])} jobs due, "
            f"{window['placed1'] - window['placed0']} placements inside it, "
            f"{window['left1'] - window['left0']} left run; "
            f"drained {window['t_drained'] - window['t1']:.2f}s")
        log("commits after the window closed, by second: "
            f"{loadgen.late_commits(window)}; backlog at half and at the "
            f"end of the window: {loadgen.backlog(window)}")
        shapes_new = {k: v for k, v in system.batcher_shapes(server).items()
                      if k not in shapes0}
        log(f"batcher shapes first seen after warm-up: {shapes_new}")
        log("program counters over window and drain: " + json.dumps(
            {k: counters1[k] - counters0.get(k, 0.0) for k in sorted(counters1)
             if k.startswith(("nomad.pipeline.", "nomad.plan.",
                              "nomad.tpu_engine.", "nomad.broker."))
             and counters1[k] != counters0.get(k, 0.0)}))

        ctx = {
            "seconds": window["t1"] - window["t0"],
            "setup_s": setup_s, "window": window, "latencies_ms": latencies,
            "n_nodes": len(fleet),
            "stats": {k: stats1[k] - stats0[k] for k in stats1
                      if isinstance(stats1[k], (int, float))},
            "counters": {k: counters1.get(k, 0.0) - counters0.get(k, 0.0)
                         for k in counters1},
            "phases": None, "lifecycle": None, "trace": None, "work": work,
            "sampler": sampler.series if sampler else None,
            "device_kind": device["kind"],
        }
        breakdown = None
        if traced:
            ctx["phases"] = phases.wall_shares(window["t0"], window["t1"])
            ctx["lifecycle"] = lifecycle.raw_records()
            if profiler.error is None and profiler.t_start is not None:
                ex = trace.extract(profiler.data)
                profiler.data = None
                mark = ex["mark_ns"] if ex["mark_ns"] is not None else 0.0
                host0 = profiler.mark_host

                def to_host_s(ns, mark=mark, host0=host0):
                    return host0 + (ns - mark) / 1e9

                lo = mark
                hi = mark + (profiler.t_stop - profiler.mark_host) * 1e9
                red = trace.reduce(ex, lo, hi)
                ctx["profile_t0"], ctx["profile_t1"] = host0, profiler.t_stop
                red["to_trace_ns"] = lambda t, mark=mark, host0=host0: mark + (t - host0) * 1e9
                ctx["trace"] = red
                breakdown = {
                    "device_ops": [[k, v] for k, v in sorted(
                        red["ops"].items(), key=lambda kv: -kv[1])[:10]],
                    "idle_gaps": trace.name_gaps(red["gaps"], to_host_s,
                                                 phases.wall_shares),
                }
                os.makedirs(trace_dir, exist_ok=True)
                with open(os.path.join(trace_dir, "extract_cut.json"), "w") as f:
                    # 0.15 s from the middle of the slice: the recorded trace
                    # that testdata/ keeps was made from such a file
                    mid = (lo + hi) / 2
                    json.dump(trace.cut(ex, mid, mid + 0.15e9), f)
                log(f"trace: device planes {[d['name'] for d in ex['devices']]}, "
                    f"lines {[d['lines'] for d in ex['devices']]}, "
                    f"programs {sorted(red['programs'])}")
            else:
                log(f"trace: none ({profiler.error!r})")

        out_metrics = {}
        for m in wanted:
            value = readers[m["name"]](ctx)
            if value is not None:
                out_metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

        # every path that ends in a plan without the device having done the
        # work reads zero; the server's own routing rule (an eval of fewer
        # placements than device_min_placements goes to the host stack
        # until the batcher is warm) is held as a share of the jobs due, to
        # the limit the traffic file states
        fallbacks = {n: (ctx["counters"].get(n, 0.0), 0)
                     for n in system.ENGINE_FALLBACK_COUNTERS}
        fallbacks.update({"device_batcher." + k: (ctx["stats"][k], 0)
                          for k in system.BATCHER_FALLBACK_STATS})
        small = ctx["counters"].get(system.SMALL_EVAL_COUNTER, 0.0)
        fallbacks["evals_by_host_stack_pct"] = (
            100.0 * small / max(1, len(window["records"])),
            float(mix["limits"]["evals_by_host_stack_pct"]))
        # the program's threads are stopped and its device state freed
        # before the reference runs; the state store stays to be read
        state = server.fsm.state
        lingering = system.teardown(server)
        server = None
        t_check = time.perf_counter()
        verdict = compare.judge(state, window["records"], fleet,
                                seed, int(mix["sample_jobs"]),
                                float(mix["limits"]["widest_score_gap"]),
                                fallbacks, len(compiles), setup_records, dep)
        log(f"comparison: {verdict['compared_placements']} placements of "
            f"{len(verdict['replayed'])} jobs replayed in "
            f"{time.perf_counter() - t_check:.1f}s "
            f"(reading the store back took {verdict['read_back_s']:.1f}s) "
            f"(most candidate snapshots tried for a job: "
            f"{max([r['candidates'] for r in verdict['replayed']], default=0)}; "
            f"jobs committed by more than one plan: {verdict['multi_plan_jobs']})")
        for fn, secs in compiles:
            log(f"compiled inside the window: {fn} {secs:.2f}s")
        control = compare.control(verdict, fleet) if with_control else None
    finally:
        if server is not None:
            lingering = system.teardown(server)
        prog_metrics.deregister_sink(sink)
    if lingering:
        raise RuntimeError(f"device threads alive after teardown: {lingering}")

    dev = dict(device, memory_peak_bytes=memory_peak)
    if traced and ctx["trace"] is not None:
        dev["busy_s"] = ctx["trace"]["busy_s"]
        dev["window_s"] = ctx["trace"]["window_s"]
    result = {"correct": verdict["correct"],
              "attempted": len(window["records"]),
              "failed": verdict["failed_jobs"],
              "metrics": out_metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    late = sorted((r["t_sent"] - r["t_due"]) * 1000.0 for r in window["records"]
                  if "t_sent" in r)
    result["notes"] = {
        "backlog_half_end": list(loadgen.backlog(window)),
        "late_commits": {str(k): v for k, v in loadgen.late_commits(window).items()},
        "drained_s": window["t_drained"] - window["t1"],
        "generator_late_p95_ms": loadgen.percentile(late, 0.95) if late else None,
        "compared_placements": verdict["compared_placements"],
    }
    if control is not None:
        result["control"] = control
    result["checks"] = verdict["checks"]
    return result


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit: at {c['side']} {c['limit']})"
              f"{'' if c['ok'] else '  <-- FAILS'}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    # the contract's keys, and the numbers compared under a key that comes last
    keys = ["correct", "attempted", "failed", "metrics", "device", "breakdown",
            "checks"]
    print(json.dumps({k: result[k] for k in keys if k in result}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr,
                        format="%(asctime)s %(levelname)s:%(name)s:%(message)s",
                        datefmt="%H:%M:%S")

    repo = os.path.dirname(HERE)
    manifest = load_manifest(repo)
    cell, _ = find_cell(manifest, args.workload)
    system.import_program()
    device = system.require_tpu(int(cell["chips"]))
    result = run_cell(manifest, repo, args.workload, args.seed, args.seconds,
                      bool(args.trace), device)
    print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
