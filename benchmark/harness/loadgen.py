"""Closed- and open-loop clients and the commit observer.

One submitter thread calls ``Server.register_job`` as ``nomad job run``
would; one observer thread watches the state store for each job's last
placement. Times are the benchmark's own (``time.perf_counter``), never a
stamp made by the program. A job's latency runs from the moment it was DUE,
so a stall of the generator or the server is counted, and how late the
generator ran is kept beside it.
"""
from __future__ import annotations

import concurrent.futures
import queue
import threading
import time

from . import system

POLL_S = 0.002
OPEN_LOOP_SENDERS = 4


class Observer(threading.Thread):
    """Marks each watched job with the moment its last placement was in the
    state store. Polls the store's index and, when it moved, the few jobs
    in flight: counts of blocks, never a materialised allocation."""

    def __init__(self, state) -> None:
        super().__init__(name="bench-observer", daemon=True)
        self.state = state
        self._lock = threading.Lock()
        self._pending: dict = {}
        self._stop_evt = threading.Event()
        self.on_done = None

    def watch(self, rec: dict) -> None:
        with self._lock:
            self._pending[rec["id"]] = rec

    def pending(self) -> int:
        with self._lock:
            return len(self._pending)

    def stop(self) -> None:
        self._stop_evt.set()

    def run(self) -> None:
        last = -1
        while not self._stop_evt.is_set():
            index = self.state.latest_index
            if index != last:
                last = index
                with self._lock:
                    watched = list(self._pending.values())
                for rec in watched:
                    if system.committed_count(self.state, rec["id"]) >= rec["count"]:
                        rec["t_commit"] = time.perf_counter()
                        with self._lock:
                            self._pending.pop(rec["id"], None)
                        if self.on_done is not None:
                            self.on_done(rec)
            time.sleep(POLL_S)


def _submit(server, stream, observer, t_due: float, records: list,
            pool, job_of, count_of) -> dict:
    spec = stream.next()
    job = job_of(spec)
    rec = {"id": spec["id"], "spec": spec, "count": count_of(spec),
           "t_due": t_due, "t_commit": None}
    records.append(rec)
    observer.watch(rec)

    def send():
        rec["t_sent"] = time.perf_counter()
        server.register_job(job)
        rec["t_registered"] = time.perf_counter()

    if pool is None:
        send()
    else:
        rec["sending"] = pool.submit(send)
    return rec


def run_window(server, stream, mix: dict, due: list, seconds: float,
               job_of, count_of) -> dict:
    """Drive the mix for ``seconds``; returns the records of every job due
    in the window, the window's bounds on the benchmark's clock, and at
    both ends the allocations the state store had been given (``placed0``,
    ``placed1``: their difference is the placements made inside the window)
    and of those the ones that had left ``run`` (``left0``, ``left1``).
    ``job_of`` and ``count_of`` are the deployment's ``program_job`` and
    the number of its ``expected_placements``: what a job's record waits
    for."""
    state = server.fsm.state
    observer = Observer(state)
    records: list = []
    stop = threading.Event()
    closed = mix["loop"] == "closed"
    free: "queue.Queue" = queue.Queue()
    if closed:
        observer.on_done = lambda rec: free.put(rec["id"])
    observer.start()

    def closed_loop(t0: float) -> None:
        # each client submits its next job the moment its last is committed
        for _ in range(int(mix["clients"])):
            if stop.is_set():
                return
            _submit(server, stream, observer, time.perf_counter(), records,
                    None, job_of, count_of)
        while not stop.is_set():
            try:
                free.get(timeout=0.05)
            except queue.Empty:
                continue
            if stop.is_set():
                return
            _submit(server, stream, observer, time.perf_counter(), records,
                    None, job_of, count_of)

    def open_loop(t0: float) -> None:
        # independent owners: a register call that is slow to return does
        # not hold the next arrival back, up to OPEN_LOOP_SENDERS at once
        pool = concurrent.futures.ThreadPoolExecutor(
            OPEN_LOOP_SENDERS, thread_name_prefix="bench-sender")
        for offset in due:
            wait = t0 + offset - time.perf_counter()
            if wait > 0 and stop.wait(wait):
                break
            if stop.is_set():
                break
            _submit(server, stream, observer, t0 + offset, records, pool,
                    job_of, count_of)
        pool.shutdown(wait=False)

    placed0, left0 = system.alloc_counts(state)
    t0 = time.perf_counter()
    worker = threading.Thread(target=closed_loop if closed else open_loop,
                              args=(t0,), name="bench-submitter", daemon=True)
    worker.start()
    remaining = t0 + seconds - time.perf_counter()
    if remaining > 0:
        time.sleep(remaining)
    placed1, left1 = system.alloc_counts(state)
    t1 = time.perf_counter()
    stop.set()
    worker.join()
    # the drain: jobs due in the window may still commit. A register call
    # that has not returned by its end (a server that pushes back) leaves
    # its job failed; it does not hold the run
    deadline = t1 + float(mix["drain_s"])
    for rec in records:
        sending = rec.pop("sending", None)
        if sending is not None:
            try:
                sending.result(timeout=max(0.0, deadline - time.perf_counter()))
            except concurrent.futures.TimeoutError:
                sending.cancel()
    while observer.pending() and time.perf_counter() < deadline:
        time.sleep(0.01)
    t_drained = time.perf_counter()
    observer.stop()
    observer.join()
    return {"records": records, "t0": t0, "t1": t1, "t_drained": t_drained,
            "placed0": placed0, "placed1": placed1,
            "left0": left0, "left1": left1}


def percentile(sorted_values: list, q: float) -> float:
    """Nearest rank: the smallest value with at least q of the list at or
    below it."""
    if not sorted_values:
        raise ValueError("no values")
    rank = max(1, -(-int(round(q * 1000)) * len(sorted_values) // 1000))
    return sorted_values[min(len(sorted_values), rank) - 1]


def latencies_ms(window: dict) -> list:
    """Sorted latencies in ms of every job due in the window: due -> last
    placement committed. A job not committed when the drain
    ended is failed and lies beyond every percentile: it is given the whole
    wait to the end of the drain, and sorts last."""
    done, late = [], []
    for rec in window["records"]:
        if rec["t_commit"] is None:
            late.append((window["t_drained"] - rec["t_due"]) * 1000.0)
        else:
            done.append((rec["t_commit"] - rec["t_due"]) * 1000.0)
    big = max(done + late) if done or late else 0.0
    return sorted(done) + [max(v, big) for v in sorted(late)]


def late_commits(window: dict) -> dict:
    """{whole seconds after the window's end: jobs committed in that
    second}, and under "never" the jobs not committed when the drain
    ended: how the drain went, for the log."""
    out: dict = {}
    for rec in window["records"]:
        if rec["t_commit"] is None:
            out["never"] = out.get("never", 0) + 1
        elif rec["t_commit"] > window["t1"]:
            key = int(rec["t_commit"] - window["t1"])
            out[key] = out.get(key, 0) + 1
    return dict(sorted(out.items(), key=lambda kv: (isinstance(kv[0], str), kv[0])))


def backlog(window: dict) -> tuple:
    """Jobs due and not yet committed at the middle and at the end of the
    window: a queue that grows through the run shows here."""
    def at(t):
        return sum(1 for r in window["records"] if r["t_due"] <= t
                   and (r["t_commit"] is None or r["t_commit"] > t))
    return at((window["t0"] + window["t1"]) / 2), at(window["t1"])
