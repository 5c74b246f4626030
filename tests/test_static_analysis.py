"""nomad-lint (nomad_tpu/analysis): the repo's invariants, enforced in tier-1.

Two layers:

  1. The whole-tree gate: every checker over ``nomad_tpu/`` must report
     zero findings beyond the shipped baseline — this is the same pass
     ``python -m nomad_tpu.analysis`` runs, so CI needs no extra plumbing.
  2. Fixture units per checker: a positive (the exact bug-shaped pattern
     each satellite fix removed — reverting a fix re-creates it) and a
     negative (the fixed shape) per rule, plus suppression/baseline
     mechanics.

Plus behavioral regressions for the two engine fixes a linter can't see
structurally: the single-flight claim release on unexpected exceptions,
and the stale-claim waiter-cohort wakeup.
"""
import json
import os
import textwrap
import threading
import time

import pytest

from nomad_tpu.analysis import (
    Finding,
    apply_baseline,
    load_baseline,
    run_paths,
    run_source,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO_ROOT, "nomad_tpu")
BASELINE = os.path.join(PKG, "analysis", "baseline.json")


def dedent(s: str) -> str:
    return textwrap.dedent(s).lstrip("\n")


# ---------------------------------------------------------------------------
# 1. the tree gate
# ---------------------------------------------------------------------------


def test_tree_is_clean_modulo_baseline():
    """`python -m nomad_tpu.analysis nomad_tpu/` semantics: zero
    non-baselined findings across the whole package."""
    findings = run_paths([PKG], rel_to=REPO_ROOT)
    baseline = load_baseline(BASELINE) if os.path.exists(BASELINE) else []
    new, _stale = apply_baseline(findings, baseline)
    assert new == [], "\n".join(f.render() for f in new)


def test_cli_module_exits_zero():
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "-m", "nomad_tpu.analysis", "nomad_tpu"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# 2. fixture units — dtype-discipline
# ---------------------------------------------------------------------------


def test_dtype_flags_uncast_int64_subtraction():
    # the exact epoch_usage_arrays bug shape (reverting the encode.py
    # satellite fix re-creates this finding)
    src = dedent("""
        import numpy as np
        def epoch_usage_arrays(fleet, n_pad, n_real, fdtype):
            totals4 = fleet["totals4"]
            reserved4 = fleet["reserved4"]
            node_c2 = np.zeros((n_pad, 2), np.int64)
            node_c2[:n_real] = (totals4[:, :2] - reserved4[:, :2]).astype(np.int64)
            return node_c2
    """)
    fs = run_source(src, "tpu/encode.py")
    assert [f.rule for f in fs] == ["dtype-discipline"]
    assert "int64 cast of a subtraction" in fs[0].message


def test_dtype_accepts_percast_operands():
    # the fixed shape: each operand cast to the eval dtype first
    src = dedent("""
        import numpy as np
        def epoch_usage_arrays(fleet, n_pad, n_real, fdtype):
            totals4 = fleet["totals4"]
            reserved4 = fleet["reserved4"]
            node_c2 = np.zeros((n_pad, 2), np.int64)
            node_c2[:n_real] = (
                totals4[:, :2].astype(fdtype) - reserved4[:, :2].astype(fdtype)
            ).astype(np.int64)
            return node_c2
    """)
    assert run_source(src, "tpu/encode.py") == []


def test_dtype_flags_float64_allocation_arithmetic():
    src = dedent("""
        import numpy as np
        def f(x):
            buf = np.zeros((4, 4), dtype=np.float64)
            return buf - x
    """)
    fs = run_source(src, "tpu/intscore.py")
    assert [f.rule for f in fs] == ["dtype-discipline"]
    assert "float64 operand" in fs[0].message


def test_packed_lane_flags_raw_bit_unpack():
    # hand-rolled unpack of a packed plane in a consumer module (the
    # scan step) must go through the blessed intscore helpers
    src = dedent("""
        import jax.numpy as jnp
        def step(static):
            feat_packed = static[3]
            feas = (feat_packed >> 0) & 1
            return feas
    """)
    fs = run_source(src, "tpu/engine.py")
    assert [f.rule for f in fs] == ["dtype-discipline"]
    assert "raw bit unpack" in fs[0].message
    assert "feat_packed" in fs[0].message


def test_packed_lane_accepts_blessed_helpers():
    # the helpers themselves are the sanctioned bit surgery — both their
    # definitions and calls through them are clean
    src = dedent("""
        import jax.numpy as jnp
        def unpack_feat_lane(packed, bit):
            return ((packed >> bit) & 1).astype(bool)
        def step(static):
            feat_packed = static[3]
            return unpack_feat_lane(feat_packed, 0)
    """)
    assert run_source(src, "tpu/engine.py") == []


def test_packed_lane_flags_float_promotion():
    src = dedent("""
        import numpy as np
        def bad_cast(feat_packed):
            return feat_packed.astype(np.float32)
        def bad_arith(count_packed):
            return count_packed * 0.5
    """)
    fs = run_source(src, "tpu/batcher.py")
    assert [f.rule for f in fs] == ["dtype-discipline"] * 2
    assert "float promotion" in fs[0].message
    assert "float promotion" in fs[1].message


def test_packed_lane_scoped_to_kernel_modules():
    # packed-named arrays elsewhere (host code, tests) are not the
    # kernel's lane layout; no findings outside the packed target list
    src = dedent("""
        def f(msg_packed):
            return (msg_packed >> 8) & 0xFF
    """)
    assert run_source(src, "server/worker.py") == []


def test_dtype_scoped_to_parity_modules():
    # the same pattern outside encode/intscore is host-path float64 by
    # design and not flagged
    src = dedent("""
        import numpy as np
        def f(a, b):
            return (a - b).astype(np.int64)
    """)
    assert run_source(src, "server/worker.py") == []


# ---------------------------------------------------------------------------
# fixture units — shared-state-discipline (guarded-by path)
# ---------------------------------------------------------------------------

BATCHER_DECL = dedent("""
    import threading
    class DeviceBatcher:
        def __init__(self):
            self._lock = threading.Lock()
            self.stats = {"dispatches": 0}  # guarded-by: _lock
""")


def test_lock_flags_unguarded_cross_module_write():
    # the exact run_forced bug shape (reverting the engine.py satellite
    # fix re-creates this finding)
    src = dedent("""
        def compute_system_placements(batcher):
            batcher.stats["dispatches"] = batcher.stats.get("dispatches", 0) + 1
    """)
    fs = run_source(src, "tpu/engine.py",
                    extra_modules=[(BATCHER_DECL, "tpu/batcher.py")])
    assert [f.rule for f in fs] == ["shared-state-discipline"]
    assert "batcher.stats" in fs[0].message


def test_lock_accepts_with_lock_write():
    src = dedent("""
        def compute_system_placements(batcher):
            with batcher._lock:
                batcher.stats["dispatches"] = batcher.stats.get("dispatches", 0) + 1
    """)
    assert run_source(src, "tpu/engine.py",
                      extra_modules=[(BATCHER_DECL, "tpu/batcher.py")]) == []


def test_lock_flags_self_write_in_declaring_class():
    # the annotated declaration itself is exempt
    assert run_source(BATCHER_DECL, "tpu/batcher.py") == []

    src2 = dedent("""
        import threading
        class DeviceBatcher:
            def __init__(self):
                self._lock = threading.Lock()
                self.stats = {"d": 0}  # guarded-by: _lock
            def _run_batch(self):
                self.stats["d"] += 1
            def _run_batch_locked(self):
                with self._lock:
                    self.stats["d"] += 1
    """)
    fs = run_source(src2, "tpu/batcher.py")
    assert len(fs) == 1 and fs[0].rule == "shared-state-discipline"
    assert fs[0].line == 7


def test_lock_ignores_unannotated_same_name_attr():
    # worker.py has its own self.stats with no annotation: self-writes in
    # a NON-declaring class are not flagged
    src = dedent("""
        class Worker:
            def __init__(self):
                self.stats = {"evals_processed": 0}
            def run(self):
                self.stats["evals_processed"] += 1
    """)
    fs = run_source(src, "server/worker.py",
                    extra_modules=[(BATCHER_DECL, "tpu/batcher.py")])
    assert fs == []


# ---------------------------------------------------------------------------
# fixture units — shared-state-discipline (inferred-sharing path)
# ---------------------------------------------------------------------------


def test_shared_state_flags_unguarded_write_from_two_roots():
    src = dedent("""
        import threading

        class Broker:
            def __init__(self):
                self._lock = threading.Lock()
                self.pending = {}
                threading.Thread(target=self._pump, daemon=True).start()
                threading.Thread(target=self._drain, daemon=True).start()

            def _pump(self):
                self.pending["a"] = 1

            def _drain(self):
                self.pending.pop("a", None)
    """)
    fs = run_source(src, "server/brokerfix.py")
    hits = [f for f in fs if f.rule == "shared-state-discipline"]
    assert hits, fs
    assert any("Broker.pending" in f.message
               and "concurrent roots" in f.message for f in hits)


def test_shared_state_accepts_lexically_held_writes():
    src = dedent("""
        import threading

        class Broker:
            def __init__(self):
                self._lock = threading.Lock()
                self.pending = {}
                threading.Thread(target=self._pump, daemon=True).start()
                threading.Thread(target=self._drain, daemon=True).start()

            def _pump(self):
                with self._lock:
                    self.pending["a"] = 1

            def _drain(self):
                with self._lock:
                    self.pending.pop("a", None)
    """)
    assert run_source(src, "server/brokerfix.py") == []


def test_shared_state_all_call_sites_held_proof():
    # _bump never takes the lock itself; every call site does, which the
    # interprocedural proof accepts
    src = dedent("""
        import threading

        class Broker:
            def __init__(self):
                self._lock = threading.Lock()
                self.pending = {}
                threading.Thread(target=self._pump, daemon=True).start()
                threading.Thread(target=self._drain, daemon=True).start()

            def _pump(self):
                with self._lock:
                    self._bump()

            def _drain(self):
                with self._lock:
                    self._bump()

            def _bump(self):
                self.pending["n"] = 1
    """)
    assert run_source(src, "server/brokerfix.py") == []


def test_shared_state_race_ok_suppresses_with_reason():
    src = dedent("""
        import threading

        class Broker:
            def __init__(self):
                self._lock = threading.Lock()
                self.hits = []
                threading.Thread(target=self._pump, daemon=True).start()
                threading.Thread(target=self._drain, daemon=True).start()

            def _pump(self):
                self.hits.append(1)  # race-ok: GIL-atomic append, read at join

            def _drain(self):
                self.hits.append(2)  # race-ok: GIL-atomic append, read at join
    """)
    assert run_source(src, "server/brokerfix.py") == []


def test_shared_state_race_ok_requires_reason():
    src = dedent("""
        import threading

        class Broker:
            def __init__(self):
                self._lock = threading.Lock()
                self.hits = []
                threading.Thread(target=self._pump, daemon=True).start()
                threading.Thread(target=self._drain, daemon=True).start()

            def _pump(self):
                self.hits.append(1)  # race-ok:

            def _drain(self):
                self.hits.append(2)  # race-ok: GIL-atomic append
    """)
    fs = run_source(src, "server/brokerfix.py")
    assert len(fs) == 1
    assert "needs a reason" in fs[0].message


def test_shared_state_stale_race_ok_fails():
    # a race-ok that suppresses nothing is itself a finding: the ratchet
    # only tightens
    src = dedent("""
        import threading

        class Broker:
            def __init__(self):
                self._lock = threading.Lock()
                self.hits = []  # race-ok: nothing here needs suppressing

            def _pump(self):
                with self._lock:
                    self.hits.append(1)
    """)
    fs = run_source(src, "server/brokerfix.py")
    assert len(fs) == 1
    assert "stale '# race-ok'" in fs[0].message


def test_shared_state_immutable_after_init_is_clean():
    # construction-path writes (__init__ and helpers called only from
    # it) happen-before publication
    src = dedent("""
        import threading

        class Broker:
            def __init__(self):
                self._lock = threading.Lock()
                self.pending = {}
                self._load()
                threading.Thread(target=self._pump, daemon=True).start()
                threading.Thread(target=self._drain, daemon=True).start()

            def _load(self):
                self.pending["seed"] = 0

            def _pump(self):
                with self._lock:
                    self.pending["a"] = 1

            def _drain(self):
                with self._lock:
                    self.pending.pop("a", None)
    """)
    assert run_source(src, "server/brokerfix.py") == []


# ---------------------------------------------------------------------------
# fixture units — jit-purity
# ---------------------------------------------------------------------------


def test_jit_flags_impure_call_in_decorated_fn():
    src = dedent("""
        import jax, time
        @jax.jit
        def f(x):
            t = time.time()
            return x
    """)
    fs = run_source(src, "tpu/kernels.py")
    assert [f.rule for f in fs] == ["jit-purity"]
    assert "time.time" in fs[0].message


def test_jit_flags_transitive_callee_and_jit_call_form():
    # the engine's builder pattern: jax.jit(fn) on a closure that calls a
    # same-module helper
    src = dedent("""
        import jax
        import numpy as np
        def _make_step():
            def helper(c):
                print("debug", c)
                return c
            def step(c, x):
                return helper(c), x
            return step
        def build():
            step = _make_step()
            return jax.jit(step)
    """)
    fs = run_source(src, "tpu/kernels.py")
    assert [f.rule for f in fs] == ["jit-purity"]
    assert "print" in fs[0].message


def test_jit_flags_partial_jit_and_global_mutation():
    src = dedent("""
        import jax
        from functools import partial
        COUNTER = 0
        @partial(jax.jit, static_argnames=("n",))
        def f(n, x):
            global COUNTER
            COUNTER += 1
            return x
    """)
    fs = run_source(src, "tpu/kernels.py")
    assert [f.rule for f in fs] == ["jit-purity"]
    assert "global" in fs[0].message


def test_jit_clean_scan_passes():
    src = dedent("""
        import jax
        @jax.jit
        def f(x):
            import jax.numpy as jnp
            return jnp.where(x > 0, x, -x)
    """)
    assert run_source(src, "tpu/kernels.py") == []


def test_jit_alias_resolution():
    src = dedent("""
        import jax
        import time as _time
        def body(c):
            return c + _time.monotonic_ns()
        def build():
            return jax.jit(body)
    """)
    fs = run_source(src, "tpu/kernels.py")
    assert len(fs) == 1 and "time.monotonic_ns" in fs[0].message


# ---------------------------------------------------------------------------
# fixture units — fsm-determinism
# ---------------------------------------------------------------------------


def test_fsm_flags_wall_clock_in_handler():
    src = dedent("""
        import time
        class NomadFSM:
            def _apply_eval_update(self, index, payload):
                stamp = time.time_ns()
                self.state.upsert(index, payload, stamp)
        _DISPATCH = {"eval-update": NomadFSM._apply_eval_update}
    """)
    fs = run_source(src, "server/fsm.py")
    assert [f.rule for f in fs] == ["fsm-determinism"]
    assert "time.time_ns" in fs[0].message


def test_fsm_flags_transitive_self_call():
    src = dedent("""
        import random
        class NomadFSM:
            def _apply_plan(self, index, payload):
                self._helper(payload)
            def _helper(self, payload):
                return random.random()
        _DISPATCH = {"plan": NomadFSM._apply_plan}
    """)
    fs = run_source(src, "server/fsm.py")
    assert len(fs) == 1 and "random.random" in fs[0].message


def test_fsm_clean_handlers_and_unreachable_impurity():
    # impure code NOT reachable from the dispatch table is out of scope
    src = dedent("""
        import time
        class NomadFSM:
            def _apply_x(self, index, payload):
                self.state.upsert(index, payload)
            def leader_only_tick(self):
                return time.time()
        _DISPATCH = {"x": NomadFSM._apply_x}
    """)
    assert run_source(src, "server/fsm.py") == []


def test_fsm_real_module_is_deterministic():
    fsm_path = os.path.join(PKG, "server", "fsm.py")
    from nomad_tpu.analysis.fsm_determinism import FsmDeterminismChecker
    from nomad_tpu.analysis.core import parse_file

    module, err = parse_file(fsm_path, "nomad_tpu/server/fsm.py")
    assert err is None
    # the real dispatch table is found (non-trivially exercised: 30 handlers)
    checker = FsmDeterminismChecker()
    assert checker.check(module) == []


# ---------------------------------------------------------------------------
# fixture units — trace-span-discipline
# ---------------------------------------------------------------------------


def test_trace_span_flags_bare_track_call():
    # discarding the context manager: the span never opens (or worse,
    # opens in __init__-style factories and never closes)
    src = dedent("""
        from nomad_tpu.utils import phases
        def process(ev):
            phases.track("rank")
            return rank(ev)
    """)
    fs = run_source(src, "server/worker.py")
    assert [f.rule for f in fs] == ["trace-span-discipline"]
    assert "phases.track" in fs[0].message


def test_trace_span_flags_manual_enter_dance():
    # storing the manager for a manual __enter__/__exit__ pair: an
    # exception between the two leaves the span open forever
    src = dedent("""
        from ..utils import phases as _phases
        def process(ev):
            cm = _phases.track("rank")
            cm.__enter__()
            work(ev)
            cm.__exit__(None, None, None)
    """)
    fs = run_source(src, "server/worker.py")
    assert [f.rule for f in fs] == ["trace-span-discipline"]
    assert "_phases.track" in fs[0].message


def test_trace_span_flags_bare_worker_span():
    src = dedent("""
        class Worker:
            def _process(self, ev):
                self._span("invoke_scheduler", ev.id)
                self.sched.process(ev)
    """)
    fs = run_source(src, "server/worker.py")
    assert [f.rule for f in fs] == ["trace-span-discipline"]
    assert "._span" in fs[0].message


def test_trace_span_accepts_with_and_enter_context():
    src = dedent("""
        from contextlib import ExitStack
        from nomad_tpu.utils import phases
        class Worker:
            def _process(self, ev):
                with phases.track("worker_busy"):
                    with self._span("invoke_scheduler", ev.id):
                        work(ev)
                with ExitStack() as st:
                    st.enter_context(phases.track("rank"))
                    work(ev)
    """)
    assert run_source(src, "server/worker.py") == []


# ---------------------------------------------------------------------------
# fixture units — pipeline-stage-discipline
# ---------------------------------------------------------------------------


def test_pipeline_flags_raft_apply_from_pipeline_code():
    # the bug shape the rule exists to forbid: the dispatch-stage thread
    # committing around the plan queue
    src = dedent("""
        class Applier:
            def commit(self, entry_type, payload):
                return self.server.raft_apply(entry_type, payload)
    """)
    fs = run_source(src, "nomad_tpu/pipeline/applier.py")
    assert [f.rule for f in fs] == ["pipeline-stage-discipline"]
    assert "raft apply" in fs[0].message


def test_pipeline_flags_raft_dot_apply_chain():
    src = dedent("""
        class Applier:
            def commit(self, entry_type, payload):
                return self.server.raft.apply(self.server.peer, entry_type, payload)
    """)
    fs = run_source(src, "nomad_tpu/pipeline/redispatch.py")
    assert [f.rule for f in fs] == ["pipeline-stage-discipline"]
    assert "raft apply" in fs[0].message


def test_pipeline_flags_state_store_write():
    src = dedent("""
        class Applier:
            def commit(self, index, allocs):
                self.server.fsm.state.upsert_allocs(index, allocs)
    """)
    fs = run_source(src, "nomad_tpu/pipeline/applier.py")
    assert [f.rule for f in fs] == ["pipeline-stage-discipline"]
    assert "state-store write" in fs[0].message


def test_pipeline_flags_unbounded_handoff_queue():
    src = dedent("""
        import queue
        class Stage:
            def __init__(self):
                self.out = queue.Queue()
    """)
    fs = run_source(src, "nomad_tpu/pipeline/queues.py")
    assert [f.rule for f in fs] == ["pipeline-stage-discipline"]
    assert "unbounded stage queue" in fs[0].message


def test_pipeline_accepts_bounded_handoff_and_plan_queue():
    # the fixed shape: commits via plan_queue.enqueue, handoff via a
    # bounded queue; state READS (snapshot) are fine
    src = dedent("""
        import queue
        class Applier:
            def __init__(self, maxsize):
                self.out = queue.Queue(maxsize=maxsize)
            def submit(self, plan):
                snap = self.server.fsm.state.snapshot()
                pending = self.server.plan_queue.enqueue(plan)
                self.out.put(pending)
    """)
    assert run_source(src, "nomad_tpu/pipeline/applier.py") == []


def test_pipeline_rule_scoped_to_pipeline_package():
    # raft applies outside nomad_tpu/pipeline/ are the normal commit path
    src = dedent("""
        class Planner:
            def commit(self, entry_type, payload):
                return self.server.raft_apply(entry_type, payload)
    """)
    assert run_source(src, "server/plan_apply.py") == []


def test_pipeline_real_package_is_clean():
    from nomad_tpu.analysis.core import parse_file
    from nomad_tpu.analysis.pipeline_stage_discipline import (
        PipelineStageDisciplineChecker,
    )

    checker = PipelineStageDisciplineChecker()
    pkg = os.path.join(PKG, "pipeline")
    for fn in sorted(os.listdir(pkg)):
        if not fn.endswith(".py"):
            continue
        module, err = parse_file(
            os.path.join(pkg, fn), f"nomad_tpu/pipeline/{fn}")
        assert err is None
        assert checker.check(module) == [], fn


# ---------------------------------------------------------------------------
# suppression + baseline mechanics
# ---------------------------------------------------------------------------


def test_inline_suppression():
    src = dedent("""
        import jax, time
        @jax.jit
        def f(x):
            t = time.time()  # nomad-lint: disable=jit-purity
            return x
    """)
    assert run_source(src, "tpu/kernels.py") == []


def test_suppression_is_rule_scoped():
    src = dedent("""
        import jax, time
        @jax.jit
        def f(x):
            t = time.time()  # nomad-lint: disable=dtype-discipline
            return x
    """)
    assert len(run_source(src, "tpu/kernels.py")) == 1


def test_baseline_subtracts_and_reports_stale():
    f1 = Finding("jit-purity", "a.py", 3, "impure call 'time.time' in f")
    f2 = Finding("jit-purity", "a.py", 9, "impure call 'print' in g")
    base = [
        {"rule": "jit-purity", "file": "a.py",
         "message": "impure call 'time.time' in f"},
        {"rule": "dtype-discipline", "file": "b.py", "message": "gone"},
    ]
    new, stale = apply_baseline([f1, f2], base)
    assert new == [f2]
    assert stale == [{"rule": "dtype-discipline", "file": "b.py",
                      "message": "gone"}]


def test_shipped_baseline_is_valid_json_list():
    with open(BASELINE) as fh:
        data = json.load(fh)
    assert isinstance(data, list)
    for ent in data:
        assert set(ent) == {"rule", "file", "message"}


# ---------------------------------------------------------------------------
# behavioral regressions for the engine single-flight fixes
# ---------------------------------------------------------------------------


def test_release_enc_claim_clears_cache_and_wakes():
    from nomad_tpu.tpu.engine import _release_enc_claim

    ev = threading.Event()
    cache = {"key": ev}
    cell = {"ev": ev, "cache": cache, "key": "key"}
    _release_enc_claim(cell)
    assert ev.is_set() and "key" not in cache and cell == {}
    _release_enc_claim(cell)  # idempotent

    # published-entry case: the cache now holds data, not the claim — the
    # release must NOT evict it
    ev2 = threading.Event()
    cache2 = {"key": (3, "enc")}
    _release_enc_claim({"ev": ev2, "cache": cache2, "key": "key"})
    assert ev2.is_set() and cache2 == {"key": (3, "enc")}


def test_encode_eval_releases_claim_on_unexpected_exception():
    """An exception AFTER the single-flight claim must release it (pop the
    parked Event and set it) so same-key waiters don't burn their 10s
    grace period. Exercised end-to-end through encode_eval's finally."""
    from nomad_tpu.tpu.engine import TpuPlacementEngine

    engine = TpuPlacementEngine()

    class _Boom(RuntimeError):
        pass

    class _Sched:
        # encode_eval touches sched.job first inside the impl; raising
        # there models any unexpected host error mid-encode
        @property
        def job(self):
            raise _Boom("unexpected encode failure")

    cell_seen = {}
    orig = TpuPlacementEngine._encode_eval_impl

    def spy(self, sched, destructive, place, claim_cell):
        # plant a fake claim exactly as the impl's claim path would
        ev = threading.Event()
        cache = {"k": ev}
        claim_cell["ev"] = ev
        claim_cell["cache"] = cache
        claim_cell["key"] = "k"
        cell_seen["ev"] = ev
        cell_seen["cache"] = cache
        return orig(self, sched, destructive, place, claim_cell)

    TpuPlacementEngine._encode_eval_impl = spy
    try:
        with pytest.raises(_Boom):
            engine.encode_eval(_Sched(), [], [object()])
    finally:
        TpuPlacementEngine._encode_eval_impl = orig

    assert cell_seen["ev"].is_set(), "claim Event not released"
    assert cell_seen["cache"] == {}, "stuck claim left parked in enc_cache"


def test_stale_claim_timeout_wakes_waiter_cohort():
    """A timed-out waiter pops the stuck claim AND sets the dead Event so
    the remaining cohort re-reads the cache immediately instead of each
    serving its own full grace period. Modeled on the engine's waiter
    loop with a short timeout."""
    enc_cache = {}
    cache_key = "k"
    stuck = threading.Event()  # the wedged owner's claim, never set by it
    enc_cache[cache_key] = stuck

    results = []

    def waiter(grace):
        # the engine's loop shape: wait; on timeout pop + set; on wake
        # re-read the cache
        t0 = time.monotonic()
        while True:
            hit = enc_cache.get(cache_key)
            if hit is None or not isinstance(hit, threading.Event):
                results.append(("healed", time.monotonic() - t0))
                return
            if not hit.wait(timeout=grace):
                if enc_cache.get(cache_key) is hit:
                    enc_cache.pop(cache_key, None)
                hit.set()  # wake the cohort (the fix under test)
                results.append(("timeout", time.monotonic() - t0))
                return
            continue

    # one short-fuse waiter and three long-fuse cohort members
    threads = [threading.Thread(target=waiter, args=(0.2,))]
    threads += [threading.Thread(target=waiter, args=(30.0,)) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=5.0)
    assert not any(t.is_alive() for t in threads), \
        "cohort members still parked on the dead claim"
    kinds = sorted(k for k, _ in results)
    assert kinds == ["healed", "healed", "healed", "timeout"]
    # the cohort healed promptly (well under its own 30s grace)
    assert all(dt < 2.0 for k, dt in results if k == "healed")


# ---------------------------------------------------------------------------
# fault-injection-discipline
# ---------------------------------------------------------------------------


def test_fault_injection_blessed_fire_hook_is_clean():
    # the ONE production shape the chaos harness allows
    src = dedent("""
        from ..chaos.injector import fire as chaos_fire

        class EvalBroker:
            def ack(self, eval_id, token):
                chaos_fire("broker_ack", eval_id=eval_id)
                return self._ack_locked(eval_id, token)
    """)
    assert run_source(src, "nomad_tpu/server/eval_broker.py") == []


def test_fault_injection_flags_adhoc_chaos_flag():
    # the bug shape rule 1 forbids: a second, registry-invisible fault path
    src = dedent("""
        CHAOS_ENABLED = False

        class Batcher:
            def run(self, enc):
                if CHAOS_ENABLED:
                    raise RuntimeError("injected")
                return self._dispatch(enc)
    """)
    fs = run_source(src, "nomad_tpu/tpu/batcher.py")
    assert fs and all(f.rule == "fault-injection-discipline" for f in fs)
    assert any("ad-hoc chaos" in f.message for f in fs)


def test_fault_injection_flags_env_gated_chaos():
    src = dedent("""
        import os

        class Planner:
            def evaluate_plan(self, snapshot, plan):
                if os.getenv("NOMAD_CHAOS_PLAN"):
                    raise RuntimeError("injected")
    """)
    fs = run_source(src, "nomad_tpu/server/plan_apply.py")
    assert [f.rule for f in fs] == ["fault-injection-discipline"]
    assert "environment-gated" in fs[0].message


def test_fault_injection_flags_production_injector_import():
    # production may import the fire hook only, never the arming surface
    src = dedent("""
        from ..chaos.injector import ChaosInjector

        class Server:
            pass
    """)
    fs = run_source(src, "nomad_tpu/server/server.py")
    assert [f.rule for f in fs] == ["fault-injection-discipline"]
    assert "only the 'fire' hook" in fs[0].message


def test_fault_injection_flags_unknown_fire_point():
    src = dedent("""
        from ..chaos.injector import fire as chaos_fire

        def apply(entry):
            chaos_fire("raft_aply")
    """)
    fs = run_source(src, "nomad_tpu/server/server.py")
    assert [f.rule for f in fs] == ["fault-injection-discipline"]
    assert "unknown injection point" in fs[0].message


def test_fault_injection_arm_with_finally_disarm_is_clean():
    src = dedent("""
        from nomad_tpu.chaos import ChaosInjector

        def test_device_fault():
            inj = ChaosInjector(seed=1)
            inj.arm("device_dispatch", prob=1.0)
            try:
                run_replay()
            finally:
                inj.disarm_all()
    """)
    assert run_source(src, "tests/test_chaos.py") == []


def test_fault_injection_flags_arm_without_finally():
    # the leak shape rule 2 forbids: an armed injector outliving its test
    src = dedent("""
        from nomad_tpu.chaos import ChaosInjector

        def test_device_fault():
            inj = ChaosInjector(seed=1)
            inj.arm("device_dispatch", prob=1.0)
            run_replay()
            inj.disarm_all()
    """)
    fs = run_source(src, "tests/test_chaos.py")
    assert [f.rule for f in fs] == ["fault-injection-discipline"]
    assert "finally" in fs[0].message


def test_fault_injection_flags_module_scope_arm():
    src = dedent("""
        from nomad_tpu.chaos import ChaosInjector

        INJ = ChaosInjector(seed=1)
        INJ.arm("heartbeat", prob=0.5)
    """)
    fs = run_source(src, "tests/test_chaos.py")
    assert [f.rule for f in fs] == ["fault-injection-discipline"]
    assert "module scope" in fs[0].message


def test_fault_injection_unblock_enqueue_point_is_known():
    # the storm-flush fire point registered with ISSUE 13: a production
    # fire on it is clean, a near-miss typo is flagged
    src = dedent("""
        from ..chaos.injector import fire as chaos_fire

        class BlockedEvals:
            def _flush_pending_locked(self):
                chaos_fire("unblock_enqueue", batch=len(self._pending))
                self.eval_broker.enqueue_all(dict(self._pending))
    """)
    assert run_source(src, "nomad_tpu/server/blocked_evals.py") == []
    typo = src.replace("unblock_enqueue", "unblock_enqueu")
    fs = run_source(typo, "nomad_tpu/server/blocked_evals.py")
    assert [f.rule for f in fs] == ["fault-injection-discipline"]
    assert "unknown injection point" in fs[0].message


def test_fault_injection_known_points_match_injector_registry():
    """The lint's _KNOWN_POINTS copy is maintained by hand (the rule
    must not import production code); this pins it to the injector's
    POINTS so a new fire point can't silently lint as unknown."""
    from nomad_tpu.analysis.fault_injection_discipline import _KNOWN_POINTS
    from nomad_tpu.chaos.injector import POINTS

    assert set(_KNOWN_POINTS) == set(POINTS)


# ---------------------------------------------------------------------------
# subprocess-discipline


def test_subprocess_flags_run_without_timeout():
    src = dedent("""
        import subprocess

        def launch():
            subprocess.run(["server", "--once"], check=True)
    """)
    fs = run_source(src, "tests/test_crash.py")
    assert [f.rule for f in fs] == ["subprocess-discipline"]
    assert "timeout" in fs[0].message


def test_subprocess_accepts_bounded_run():
    src = dedent("""
        import subprocess

        def launch():
            subprocess.run(["server", "--once"], check=True, timeout=30)
    """)
    assert run_source(src, "tests/test_crash.py") == []


def test_subprocess_flags_unbounded_proc_wait():
    src = dedent("""
        def reap(proc):
            proc.kill()
            proc.wait()
    """)
    fs = run_source(src, "nomad_tpu/chaos/crash.py")
    assert [f.rule for f in fs] == ["subprocess-discipline"]
    assert ".wait()" in fs[0].message


def test_subprocess_accepts_bounded_wait_and_lock_wait():
    # a condition-variable wait() is not a process reap: no finding
    src = dedent("""
        def reap(proc, cond):
            proc.kill()
            proc.wait(timeout=10)
            with cond:
                cond.wait()
    """)
    assert run_source(src, "nomad_tpu/chaos/crash.py") == []


def test_subprocess_flags_unowned_popen():
    # local Popen, no finally reap, not a self-attribute: leaks on the
    # first exception between spawn and reap
    src = dedent("""
        import subprocess

        def boot():
            proc = subprocess.Popen(["server"])
            wait_ready(proc)
            return proc
    """)
    fs = run_source(src, "tests/test_crash.py")
    assert [f.rule for f in fs] == ["subprocess-discipline"]
    assert "Popen" in fs[0].message


def test_subprocess_accepts_finally_reaped_popen():
    src = dedent("""
        import subprocess

        def boot():
            proc = subprocess.Popen(["server"])
            try:
                wait_ready(proc)
            finally:
                proc.kill()
                proc.wait(timeout=10)
    """)
    assert run_source(src, "tests/test_crash.py") == []


def test_subprocess_accepts_class_owned_popen():
    # the ServerProcess pattern: Popen held as a self-attribute of a
    # class that defines a reap method
    src = dedent("""
        import subprocess

        class Proc:
            def spawn(self):
                self.proc = subprocess.Popen(["server"])

            def terminate(self):
                self.proc.terminate()
                self.proc.wait(timeout=10)
    """)
    assert run_source(src, "nomad_tpu/chaos/crash.py") == []


def test_subprocess_scoped_to_harness_code():
    # production client drivers manage their own lifecycles: out of scope
    src = dedent("""
        import subprocess

        def start_task():
            p = subprocess.Popen(["workload"])
            return p
    """)
    assert run_source(src, "nomad_tpu/client/drivers/exec_driver.py") == []


# ---------------------------------------------------------------------------
# fixture units — metrics-discipline
# ---------------------------------------------------------------------------

REGISTRY_DECL = dedent("""
    FAMILIES = {
        "nomad.broker": "eval broker",
        "nomad.trace": "lifecycle spans",
    }
""")


def test_metrics_flags_fstring_name_in_loop():
    # the exact failover.py bug shape: per-key metric names minted inside
    # a loop (reverting the publish_family fix re-creates this finding)
    src = dedent("""
        from ..utils import metrics

        def publish(fields):
            for k, v in fields.items():
                metrics.set_gauge(f"nomad.chaos.failover.{k}", float(v))
    """)
    fs = run_source(src, "nomad_tpu/trace/failover.py")
    assert [f.rule for f in fs] == ["metrics-discipline"]
    assert "inside a loop" in fs[0].message
    assert "publish_family" in fs[0].message


def test_metrics_flags_non_nomad_literal():
    src = dedent("""
        from nomad_tpu.utils import metrics

        def tick():
            metrics.incr_counter("broker_enqueues")
    """)
    fs = run_source(src, "nomad_tpu/server/eval_broker.py")
    assert [f.rule for f in fs] == ["metrics-discipline"]
    assert "not a dotted" in fs[0].message


def test_metrics_flags_fully_dynamic_name():
    src = dedent("""
        from nomad_tpu.utils import metrics

        def tick(eval_id):
            metrics.add_sample("nomad.sched." + eval_id, 1.0)
    """)
    fs = run_source(src, "nomad_tpu/server/worker.py")
    assert [f.rule for f in fs] == ["metrics-discipline"]
    assert "dynamic" in fs[0].message


def test_metrics_flags_headless_fstring():
    # an f-string whose literal head isn't 'nomad.<family>.' hides the
    # family from grep even outside loops
    src = dedent("""
        from nomad_tpu.utils import metrics

        def tick(prefix):
            metrics.set_gauge(f"{prefix}.depth", 1.0)
    """)
    fs = run_source(src, "nomad_tpu/server/worker.py")
    assert [f.rule for f in fs] == ["metrics-discipline"]
    assert "literal head" in fs[0].message


def test_metrics_flags_unregistered_family_with_registry():
    # family enforcement arms only when the registry module is in the
    # collect set (full-tree runs; fixtures opt in via extra_modules)
    src = dedent("""
        from nomad_tpu.utils import metrics

        def tick():
            metrics.incr_counter("nomad.mystery.count")
    """)
    fs = run_source(
        src, "nomad_tpu/server/worker.py",
        extra_modules=[(REGISTRY_DECL, "nomad_tpu/utils/metric_names.py")])
    assert [f.rule for f in fs] == ["metrics-discipline"]
    assert "nomad.mystery" in fs[0].message and "FAMILIES" in fs[0].message


def test_metrics_accepts_literal_constant_and_bounded_fstring():
    src = dedent("""
        from nomad_tpu.utils import metrics

        STALL_GAUGE = "nomad.watchdog.stalled_s"

        def tick(eval_type):
            metrics.incr_counter("nomad.broker.enqueues")
            metrics.set_gauge(STALL_GAUGE, 2.0)
            # bounded enum suffix outside a loop: family stays greppable
            metrics.add_sample(f"nomad.trace.eval_ms.{eval_type}", 5.0)
    """)
    assert run_source(
        src, "nomad_tpu/server/worker.py",
        extra_modules=[(REGISTRY_DECL, "nomad_tpu/utils/metric_names.py")]) \
        == []


def test_metrics_accepts_publish_family_door_in_loop():
    # the blessed dynamic-name door: a literal registered prefix, dict
    # fan-out handled inside metric_names (which is itself exempt)
    src = dedent("""
        from ..utils import metric_names

        def publish(snapshots):
            for snap in snapshots:
                metric_names.publish_family("nomad.broker", snap)
    """)
    assert run_source(
        src, "nomad_tpu/server/eval_broker.py",
        extra_modules=[(REGISTRY_DECL, "nomad_tpu/utils/metric_names.py")]) \
        == []


def test_metrics_flags_dynamic_publish_family_prefix():
    src = dedent("""
        from ..utils import metric_names

        def publish(prefix, fields):
            metric_names.publish_family(prefix, fields)
    """)
    fs = run_source(src, "nomad_tpu/server/server.py")
    assert [f.rule for f in fs] == ["metrics-discipline"]
    assert "prefix" in fs[0].message


def test_metrics_exempts_sink_plumbing():
    # the sink's own fan-out and the registry door are the two modules
    # allowed to touch dynamic names
    src = dedent("""
        from . import metrics

        def publish_family(prefix, mapping):
            for key, value in mapping.items():
                metrics.set_gauge(f"{prefix}.{key}", float(value))
    """)
    assert run_source(src, "nomad_tpu/utils/metric_names.py") == []


# ---------------------------------------------------------------------------
# fixture units — lock-order
# ---------------------------------------------------------------------------


def test_lock_order_flags_lexical_inversion():
    # the planted A->B / B->A shape: two methods of one class take the
    # same pair of locks in opposite orders
    src = dedent("""
        import threading

        class A:
            def __init__(self):
                self._lk1 = threading.Lock()
                self._lk2 = threading.Lock()

            def fwd(self):
                with self._lk1:
                    with self._lk2:
                        pass

            def rev(self):
                with self._lk2:
                    with self._lk1:
                        pass
    """)
    fs = run_source(src, "server/locky.py")
    assert [f.rule for f in fs] == ["lock-order"]
    assert "potential deadlock" in fs[0].message
    assert "locky.A._lk1" in fs[0].message
    assert "locky.A._lk2" in fs[0].message


def test_lock_order_accepts_consistent_order():
    src = dedent("""
        import threading

        class A:
            def __init__(self):
                self._lk1 = threading.Lock()
                self._lk2 = threading.Lock()

            def fwd(self):
                with self._lk1:
                    with self._lk2:
                        pass

            def also_fwd(self):
                with self._lk1:
                    with self._lk2:
                        pass
    """)
    assert run_source(src, "server/locky.py") == []


def test_lock_order_walks_through_calls():
    # neither inversion is lexical: each second lock is taken in a
    # callee while the first is held in the caller — only the
    # interprocedural walk sees the cycle
    src = dedent("""
        import threading

        class B:
            def __init__(self):
                self._x = threading.Lock()
                self._y = threading.Lock()

            def top(self):
                with self._x:
                    self._grab_y()

            def _grab_y(self):
                with self._y:
                    pass

            def other(self):
                with self._y:
                    self._grab_x()

            def _grab_x(self):
                with self._x:
                    pass
    """)
    fs = run_source(src, "server/calls.py")
    assert [f.rule for f in fs] == ["lock-order"]
    assert "calls.B._x" in fs[0].message
    assert " via " in fs[0].message  # the call chain is named in the edge


def test_lock_order_through_call_consistent_is_clean():
    src = dedent("""
        import threading

        class B:
            def __init__(self):
                self._x = threading.Lock()
                self._y = threading.Lock()

            def top(self):
                with self._x:
                    self._grab_y()

            def _grab_y(self):
                with self._y:
                    pass
    """)
    assert run_source(src, "server/calls.py") == []


def test_lock_order_uses_witness_factory_literal_keys():
    # witness-created locks carry their static key as a literal: the
    # finding names the LITERAL keys, proving the static side and the
    # runtime witness share one namespace by construction
    src = dedent("""
        from nomad_tpu.utils.lock_witness import witness_lock

        class Broker:
            def __init__(self):
                self._lock = witness_lock("eval_broker.Broker._lock")
                self._q = witness_lock("eval_broker.Broker._q")

            def fwd(self):
                with self._lock:
                    with self._q:
                        pass

            def rev(self):
                with self._q:
                    with self._lock:
                        pass
    """)
    fs = run_source(src, "server/eval_broker.py")
    assert [f.rule for f in fs] == ["lock-order"]
    assert "eval_broker.Broker._lock" in fs[0].message
    assert "eval_broker.Broker._q" in fs[0].message


def test_lock_order_same_name_nesting_is_reentrant():
    # lock-class semantics: a snapshot's lock shares the live store's
    # key, so same-key nesting must not self-edge into a "cycle"
    src = dedent("""
        import threading

        class Store:
            def __init__(self):
                self._lock = threading.RLock()

            def snapshot(self):
                with self._lock:
                    other = Store()
                    with other._lock:
                        pass
    """)
    assert run_source(src, "state/state_store.py") == []


# ---------------------------------------------------------------------------
# fixture units — r06 worker-pool shapes (lock-order, trace-span-discipline)
# ---------------------------------------------------------------------------
# The parallel-lifecycle round added two concurrency-sensitive shapes:
# the batcher's demand-aware expect/cancel counter (engine threads touch
# batcher._lock while the dispatcher thread holds it around stats), and
# the worker's coalesced idle-span recording. These fixtures pin that
# the SHIPPED shapes are clean AND that the bug-shaped variants a
# refactor could reintroduce still trip the rules.


def test_worker_pool_demand_counter_shape_is_clean():
    # engine-side expect()/cancel_expected() + dispatcher-side stats
    # bump, each under the single batcher lock: no ordering edge exists
    src = dedent("""
        import threading

        class DeviceBatcher:
            def __init__(self):
                self._lock = threading.Lock()
                self._expected = 0
                self.stats = {"gathers": 0}  # guarded-by: _lock

            def expect(self, n=1):
                with self._lock:
                    self._expected += n

            def cancel_expected(self):
                with self._lock:
                    self._expected = max(0, self._expected - 1)

            def _dispatch_loop(self):
                with self._lock:
                    self.stats["gathers"] += 1
    """)
    assert run_source(src, "tpu/batcher.py") == []


def test_worker_pool_lock_order_inversion_still_trips():
    # the regression a "hold the pool lock while announcing demand"
    # refactor would create: worker pool lock -> batcher lock in one
    # path, batcher lock -> pool lock in the drain path
    src = dedent("""
        import threading

        class WorkerPool:
            def __init__(self):
                self._pool_lock = threading.Lock()
                self._batcher_lock = threading.Lock()

            def announce(self):
                with self._pool_lock:
                    with self._batcher_lock:
                        pass

            def drain(self):
                with self._batcher_lock:
                    with self._pool_lock:
                        pass
    """)
    fs = run_source(src, "server/worker.py")
    assert [f.rule for f in fs] == ["lock-order"]
    assert "potential deadlock" in fs[0].message


def test_worker_idle_span_recording_shape_is_clean():
    # the shipped worker idle pattern: pipeline_record is a plain
    # timestamped event (not a span context manager), so recording a
    # coalesced idle interval on the next successful dequeue is NOT a
    # bare-span violation — while real span entries stay `with`-guarded
    src = dedent("""
        from nomad_tpu.trace import lifecycle as _lifecycle
        from nomad_tpu.utils import phases

        class Worker:
            def _run(self):
                idle_t0 = None
                while True:
                    poll_t0 = _lifecycle.pipeline_now()
                    ev = self.dequeue()
                    if ev is None:
                        if idle_t0 is None:
                            idle_t0 = poll_t0
                        continue
                    if idle_t0 is not None:
                        _lifecycle.pipeline_record(
                            _lifecycle.IDLE_STAGE, "worker-0",
                            idle_t0, _lifecycle.pipeline_now())
                        idle_t0 = None
                    with phases.track("worker_busy"):
                        self._process(ev)
    """)
    assert run_source(src, "server/worker.py") == []


def test_worker_idle_as_bare_span_still_trips():
    # the tempting-but-wrong variant: opening a phases.track("idle")
    # manager at idle start and parking it in a local — a worker that
    # dies idle leaves the span open forever
    src = dedent("""
        from nomad_tpu.utils import phases

        class Worker:
            def _run(self):
                cm = phases.track("idle")
                cm.__enter__()
                ev = self.dequeue()
                cm.__exit__(None, None, None)
    """)
    fs = run_source(src, "server/worker.py")
    assert [f.rule for f in fs] == ["trace-span-discipline"]
    assert "phases.track" in fs[0].message


# ---------------------------------------------------------------------------
# fixture units — lifecycle.stage / phases.record (one span call per site)
# ---------------------------------------------------------------------------

_STAGE_HEAD = """
    from ..trace import lifecycle as _tlc
    from ..utils import phases as _phases
"""

STAGE_FIXTURES = {
    # trace-span-discipline: stage() is a span factory
    "bare_stage_call_leaks": ("""
        def encode(sched):
            _tlc.stage("encode", sched.eval.id)
            return work(sched)
    """, ["trace-span-discipline"]),
    "stored_pipeline_stage_leaks": ("""
        def encode(sched):
            cm = _tlc.pipeline_stage("encode", sched.eval.id)
            cm.__enter__()
    """, ["trace-span-discipline"]),
    "with_stage_and_plain_record_are_clean": ("""
        def dispatch(batch, eval_id):
            with _tlc.stage("device_wait", eval_id) as waited:
                run(batch)
            _phases.record("gather", waited.t0, waited.t1)
            _tlc.pipeline_record(_tlc.IDLE_STAGE, "worker-0", waited.t0,
                                 waited.t1)
    """, []),
    # pipeline-stage-discipline: one interval, one call
    "double_bracket_in_one_with": ("""
        def encode(sched, wave_id):
            with _phases.track("encode"), _tlc.pipeline_stage("encode", wave_id):
                return work(sched)
    """, ["pipeline-stage-discipline"]),
    "double_bracket_nested": ("""
        def evaluate(plan):
            with _tlc.stage("plan_evaluate", plan.eval_id):
                with _phases.track("plan_evaluate"):
                    return check(plan)
    """, ["pipeline-stage-discipline"]),
    "stage_inside_a_wider_phase_is_clean": ("""
        def process(ev):
            with _phases.track("worker_busy"):
                prepare(ev)
                with _tlc.stage("snapshot", ev.id):
                    snap(ev)
    """, []),
    # metrics-discipline: span names are a bounded set
    "dynamic_stage_name": ("""
        def bracket(kind, eval_id):
            with _tlc.stage(f"stage-{kind}", eval_id):
                pass
    """, ["metrics-discipline"]),
    "dynamic_record_name": ("""
        def note(name, t0, t1):
            _phases.record(name, t0, t1)
    """, ["metrics-discipline"]),
}


@pytest.mark.parametrize("fixture", sorted(STAGE_FIXTURES))
def test_span_call_discipline(fixture):
    body, rules = STAGE_FIXTURES[fixture]
    fs = run_source(dedent(_STAGE_HEAD) + dedent(body), "tpu/engine.py")
    assert sorted(f.rule for f in fs) == rules, [f.message for f in fs]


def test_no_site_brackets_one_interval_twice():
    """The tree itself: no module pairs phases.track with a lifecycle
    span, and the four device_batcher sample timers are gone."""
    import re

    from nomad_tpu.analysis.core import parse_file
    from nomad_tpu.analysis.pipeline_stage_discipline import _double_brackets

    for base, _dirs, files in os.walk(PKG):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(base, fn)
            module, err = parse_file(path, os.path.relpath(path, PKG))
            assert err is None
            assert _double_brackets(module) == [], path
            with open(path) as fh:
                assert not re.search(
                    r"nomad\.device_batcher\.(pad_stack|dispatch|compute|transfer)\b",
                    fh.read()), path


# ---------------------------------------------------------------------------
# fixture units — condition-discipline
# ---------------------------------------------------------------------------


def test_condition_flags_bare_wait():
    src = dedent("""
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._cv = threading.Condition(self._lock)
                self._items = []

            def take(self):
                with self._cv:
                    self._cv.wait()
                    return self._items.pop()
    """)
    fs = run_source(src, "server/condy.py")
    assert [f.rule for f in fs] == ["condition-discipline"]
    assert "while-predicate loop" in fs[0].message


def test_condition_accepts_while_loop_and_wait_for():
    src = dedent("""
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._cv = threading.Condition(self._lock)
                self._items = []

            def take(self):
                with self._cv:
                    while not self._items:
                        self._cv.wait(timeout=1.0)
                    return self._items.pop()

            def take2(self):
                with self._cv:
                    self._cv.wait_for(lambda: self._items, timeout=1.0)
                    return self._items.pop()
    """)
    assert run_source(src, "server/condy.py") == []


def test_condition_flags_unheld_notify():
    src = dedent("""
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._cv = threading.Condition(self._lock)
                self._items = []

            def put(self, x):
                self._items.append(x)
                self._cv.notify()
    """)
    fs = run_source(src, "server/condy.py")
    assert [f.rule for f in fs] == ["condition-discipline"]
    assert "not provably issued with the lock held" in fs[0].message


def test_condition_accepts_provably_held_notify():
    # three proofs: lexical with, the *_locked naming convention, and
    # every-call-site-holds-it
    src = dedent("""
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._cv = threading.Condition(self._lock)
                self._items = []

            def put(self, x):
                with self._cv:
                    self._items.append(x)
                    self._cv.notify()

            def _wake_locked(self):
                self._cv.notify_all()

            def _wake(self):
                self._cv.notify()

            def put2(self, x):
                with self._lock:
                    self._items.append(x)
                    self._wake()
    """)
    assert run_source(src, "server/condy.py") == []


def test_condition_ignores_non_condition_waits():
    # Event.wait / subprocess wait are not inventoried Conditions
    src = dedent("""
        import threading

        def reap(ev, proc):
            ev.wait(timeout=5)
            proc.wait(timeout=5)
    """)
    assert run_source(src, "server/condy.py") == []


# ---------------------------------------------------------------------------
# CLI satellites: --json / --rule / stale-baseline exit / --prune
# ---------------------------------------------------------------------------

CYCLE_SRC = dedent("""
    import threading

    class A:
        def __init__(self):
            self._lk1 = threading.Lock()
            self._lk2 = threading.Lock()

        def fwd(self):
            with self._lk1:
                with self._lk2:
                    pass

        def rev(self):
            with self._lk2:
                with self._lk1:
                    pass
""")


def _cli(argv):
    from nomad_tpu.analysis.__main__ import main
    return main(argv)


def test_cli_json_output_shape(tmp_path, capsys):
    mod = tmp_path / "locky.py"
    mod.write_text(CYCLE_SRC)
    rc = _cli(["--json", "--no-baseline", str(mod)])
    out = capsys.readouterr().out
    assert rc == 1
    data = json.loads(out)
    assert set(data) == {"findings", "counts", "stale_baseline",
                        "rule_wall_ms"}
    assert data["counts"] == {"lock-order": 1}
    # per-rule wall time: every reporting rule appears, plus the shared
    # interprocedural build on its own line
    assert "lock-order" in data["rule_wall_ms"]
    assert "shared-state-discipline" in data["rule_wall_ms"]
    assert "call-graph" in data["rule_wall_ms"]
    assert all(isinstance(v, (int, float)) and v >= 0
               for v in data["rule_wall_ms"].values())
    (f,) = data["findings"]
    assert set(f) == {"rule", "file", "line", "message", "rendered"}
    assert f["rule"] == "lock-order"
    assert "potential deadlock" in f["message"]
    assert f["rendered"].startswith(f["file"])
    assert data["stale_baseline"] == []


def test_cli_rule_filter(tmp_path, capsys):
    mod = tmp_path / "locky.py"
    mod.write_text(CYCLE_SRC)
    # filtered to an unrelated rule, the cycle is out of scope
    rc = _cli(["--rule", "condition-discipline", "--no-baseline", str(mod)])
    capsys.readouterr()
    assert rc == 0
    # comma-separated form includes it again
    rc = _cli(["--rule", "condition-discipline,lock-order", "--no-baseline",
               str(mod)])
    capsys.readouterr()
    assert rc == 1


def test_cli_changed_only_scopes_reporting(tmp_path, capsys):
    dirty = tmp_path / "locky.py"
    dirty.write_text(CYCLE_SRC)
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")

    # scoped to the clean file, the cycle in the other file is not
    # reported (though the collect pass still saw the whole tree)
    rc = _cli(["--changed-only", str(clean), "--no-baseline",
               str(tmp_path)])
    capsys.readouterr()
    assert rc == 0

    rc = _cli(["--changed-only", str(dirty), "--no-baseline",
               str(tmp_path)])
    capsys.readouterr()
    assert rc == 1

    # comma-separated form; a deleted file scopes to nothing
    rc = _cli(["--changed-only",
               f"{clean},{tmp_path / 'deleted.py'}",
               "--no-baseline", str(tmp_path)])
    capsys.readouterr()
    assert rc == 0


def test_cli_changed_only_restricts_baseline_matching(tmp_path, capsys):
    dirty = tmp_path / "locky.py"
    dirty.write_text(CYCLE_SRC)
    base = tmp_path / "baseline.json"
    # a baseline entry for a file OUTSIDE the scope must not be
    # reported stale by a scoped run
    base.write_text(json.dumps([
        {"rule": "lock-order", "file": "elsewhere.py",
         "message": "potential deadlock: out of scope"},
    ]))
    rc = _cli(["--changed-only", str(dirty), "--baseline", str(base),
               str(tmp_path)])
    capsys.readouterr()
    assert rc == 1  # the in-scope cycle still fails...
    rc = _cli(["--changed-only", str(tmp_path / "other.py"),
               "--baseline", str(base), str(tmp_path)])
    err = capsys.readouterr().err
    assert rc == 0  # ...but the out-of-scope stale entry does not
    assert "stale" not in err


def test_cli_stale_baseline_fails_and_prune_heals(tmp_path, capsys):
    mod = tmp_path / "clean.py"
    mod.write_text("x = 1\n")
    base = tmp_path / "baseline.json"
    stale_entry = {"rule": "lock-order", "file": "gone.py",
                   "message": "potential deadlock: long since fixed"}
    base.write_text(json.dumps([stale_entry]))

    # stale entries are a FAILURE, not a warning: the ratchet only tightens
    rc = _cli(["--baseline", str(base), str(mod)])
    err = capsys.readouterr().err
    assert rc == 1
    assert "stale baseline" in err

    # --prune removes exactly the stale entries and the run goes green
    rc = _cli(["--baseline", str(base), "--prune", str(mod)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "pruned 1 stale entry" in out
    assert json.loads(base.read_text()) == []

    rc = _cli(["--baseline", str(base), str(mod)])
    capsys.readouterr()
    assert rc == 0


def test_cli_prune_never_adds_entries(tmp_path, capsys):
    # a tree with a NEW finding and a stale baseline: prune drops the
    # stale entry but must not launder the new finding in
    mod = tmp_path / "locky.py"
    mod.write_text(CYCLE_SRC)
    base = tmp_path / "baseline.json"
    base.write_text(json.dumps([
        {"rule": "lock-order", "file": "gone.py", "message": "fixed ages ago"},
    ]))
    rc = _cli(["--baseline", str(base), "--prune", str(mod)])
    capsys.readouterr()
    assert rc == 1  # the new finding still fails the run
    assert json.loads(base.read_text()) == []


def test_cli_write_baseline_then_green(tmp_path, capsys):
    mod = tmp_path / "locky.py"
    mod.write_text(CYCLE_SRC)
    base = tmp_path / "baseline.json"
    rc = _cli(["--baseline", str(base), "--write-baseline", str(mod)])
    capsys.readouterr()
    assert rc == 0
    rc = _cli(["--baseline", str(base), str(mod)])
    capsys.readouterr()
    assert rc == 0


# ---------------------------------------------------------------------------
# rpc-telemetry-discipline: RPC traffic must go through the instrumented
# choke points (register / RPCClient.call), or it is invisible to the
# per-method stats table and the cross-process trace
# ---------------------------------------------------------------------------


def test_rpc_telemetry_flags_raw_handler_insert():
    src = dedent("""
        def wire(rpc):
            rpc.handlers["Sneaky.call"] = lambda: 1
    """)
    fs = run_source(src, "server/extra.py")
    assert any(f.rule == "rpc-telemetry-discipline"
               and "register" in f.message for f in fs)


def test_rpc_telemetry_flags_private_frame_import_and_call():
    src = dedent("""
        from nomad_tpu.rpc.transport import _send_frame

        def leak(sock, payload):
            _send_frame(sock, payload)
    """)
    fs = run_source(src, "server/extra.py")
    assert any("side channel" in f.message for f in fs)

    src2 = dedent("""
        from nomad_tpu.rpc import transport

        def leak(sock):
            return transport._recv_frame(sock)
    """)
    fs2 = run_source(src2, "server/extra.py")
    assert any(f.rule == "rpc-telemetry-discipline"
               and "instrumented RPC path" in f.message for f in fs2)


def test_rpc_telemetry_flags_handbuilt_envelope():
    src = dedent("""
        def craft(seq):
            return {"seq": seq, "method": "Node.Register", "body": ()}
    """)
    fs = run_source(src, "server/extra.py")
    assert any(f.rule == "rpc-telemetry-discipline"
               and "TraceContext" in f.message for f in fs)


def test_rpc_telemetry_accepts_register_and_local_helpers():
    # the blessed shapes: register(), RPCClient.call, and a module's OWN
    # _read_exact helper (the websocket framer) stay clean
    src = dedent("""
        def wire(rpc, client):
            rpc.register("Status.ping", lambda: "pong")
            return client.call("Status.ping")

        def _read_exact(rfile, n):
            return rfile.read(n)

        def use(rfile):
            return _read_exact(rfile, 4)
    """)
    assert run_source(src, "server/extra.py") == []


def test_rpc_telemetry_exempts_transport_itself():
    src = dedent("""
        def handler_loop(self, method, fn):
            self.handlers[method] = fn
            return {"seq": 1, "method": method}
    """)
    assert run_source(src, "rpc/transport.py") == []
    assert run_source(src, "plugins/transport.py") == []


# ---------------------------------------------------------------------------
# blocking-read-discipline
# ---------------------------------------------------------------------------


def test_blocking_read_flags_unrouted_read_endpoint():
    # a read-shaped endpoint that answers straight from the store: no
    # QueryMeta, no min_query_index — the bug shape the funnel removed
    src = dedent("""
        def bind(rpc, server):
            rpc.register("Job.List", lambda: server.fsm.state.jobs())
            rpc.register("Eval.GetEval", lambda i: server.fsm.state.eval_by_id(i))
    """)
    fs = run_source(src, "rpc/endpoints.py")
    flagged = [f for f in fs if f.rule == "blocking-read-discipline"]
    assert len(flagged) == 2
    assert any("Job.List" in f.message for f in flagged)
    assert any("Eval.GetEval" in f.message for f in flagged)


def test_blocking_read_accepts_funnel_and_waiver():
    src = dedent("""
        def bind(rpc, server):
            def serve_read(table, run, query_opts, key=None):
                return run(server.fsm.state)

            rpc.register(
                "Job.List",
                lambda query_opts=None: serve_read(
                    "jobs", lambda s: s.jobs(), query_opts),
            )

            def get_client_allocs(node_id, min_index, timeout):
                return server.fsm.state.allocs_by_node(node_id)

            # blocking-read-waiver: pre-watch long-poll with its own
            # min_index protocol
            rpc.register("Node.GetClientAllocs", get_client_allocs)

            # write endpoints are out of scope for the funnel entirely
            rpc.register("Job.Register", server.register_job)
    """)
    assert [f for f in run_source(src, "rpc/endpoints.py")
            if f.rule == "blocking-read-discipline"] == []


def test_blocking_read_scopes_endpoint_rule_to_endpoint_modules():
    # the same unrouted register outside an endpoints.py module is some
    # other registry's business (test harnesses, plugin tables)
    src = dedent("""
        def wire(rpc, server):
            rpc.register("Job.List", lambda: server.fsm.state.jobs())
    """)
    assert [f for f in run_source(src, "server/harness.py")
            if f.rule == "blocking-read-discipline"] == []


def test_blocking_read_flags_state_writing_hub_callback():
    src = dedent("""
        def wire(hub, server):
            hub.add_callback(
                lambda tables, index: server.fsm.state.upsert_evals(index, []))
    """)
    fs = run_source(src, "server/wiring.py")
    assert any(f.rule == "blocking-read-discipline"
               and "upsert_evals" in f.message for f in fs)


def test_blocking_read_flags_lock_taking_hub_callback():
    src = dedent("""
        def wire(watch_hub, store):
            def observer(tables, index):
                with store._lock:
                    return len(store.evals)

            watch_hub.add_callback(observer)
    """)
    fs = run_source(src, "server/wiring.py")
    assert any(f.rule == "blocking-read-discipline"
               and "store._lock" in f.message for f in fs)


def test_blocking_read_accepts_observer_callback():
    # pure observation — counters, appends — is the blessed callback
    # shape; non-hub add_callback receivers are out of scope entirely
    src = dedent("""
        def wire(hub, rec, metrics, seen, server):
            hub.add_callback(lambda tables, index: seen.append(index))

            def observer(tables, index):
                metrics.incr_counter("nomad.watch.observed", len(tables))

            hub.add_callback(observer)
            rec.add_callback(lambda: server.fsm.state.upsert_evals(0, []))
    """)
    assert [f for f in run_source(src, "server/wiring.py")
            if f.rule == "blocking-read-discipline"] == []
