"""Mesh construction and sharding specs for the placement scan.

The scale axes of this domain map onto a 2-D ``jax.sharding.Mesh``:

  "evals" — data parallelism over independent evaluations (each eval's scan
            is independent; the broker dequeues many at once). The analog of
            DP in an ML workload.
  "nodes" — model/sequence parallelism over the cluster's node axis: every
            [N]-shaped array (capacity, masks, scores) is sharded across
            chips, and XLA inserts the all-gather/all-reduce/argmax
            collectives the ring-ordered selection needs. The analog of
            TP/SP: the "long context" here is the 5K-node (and beyond)
            cluster state.

We use GSPMD via jit + NamedSharding rather than hand-written shard_map:
the scan body is dominated by elementwise ops, cumsums and reductions over
the node axis, all of which XLA partitions well.
"""
from __future__ import annotations

from typing import Optional, Tuple


def make_mesh(n_devices: Optional[int] = None, eval_parallel: int = 1):
    """Build a ("evals", "nodes") mesh over the available devices."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    ep = max(1, min(eval_parallel, n))
    while n % ep != 0:
        ep -= 1
    grid = np.asarray(devices).reshape(ep, n // ep)
    return Mesh(grid, ("evals", "nodes"))


def batched_scan_shardings(mesh):
    """(static, carry, xs, p_real) NamedShardings for the FULLY-batched scan
    (engine._build_batched_scan): every array carries a leading eval axis
    (concurrent evals see different snapshots/node sets/jobs, so node
    tables batch too). Eval axis shards over "evals"; node dims over
    "nodes"; small per-TG/spread tables replicate within an eval shard;
    the evals' step counts replicate everywhere, since the loop's bound is
    their maximum.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    e = "evals"
    static = (
        ns(e, "nodes", None),        # totals [B, N, D]
        ns(e, "nodes", None),        # reserved [B, N, D]
        ns(e, None, None),           # asks [B, G, D]
        ns(e, None, "nodes"),        # feat_packed [B, G, N] (uint8 lanes)
        ns(e, None, "nodes"),        # aff_score [B, G, N]
        ns(e, None),                 # desired_counts [B, G]
        ns(e, None),                 # dh_job [B, G]
        ns(e, None),                 # dh_tg [B, G]
        ns(e, None),                 # limits [B, G]
        ns(e, None, None, "nodes"),  # spread_vids [B, G, S, N]
        ns(e, None, None, None),     # spread_desired [B, G, S, V]
        ns(e, None, None),           # spread_weights [B, G, S]
        ns(e, None, None),           # spread_has_targets [B, G, S]
        ns(e, None, None),           # spread_active [B, G, S]
        ns(e, None),                 # sum_spread_weights [B, G]
        ns(e),                       # n_real [B]
        ns(e, None, "nodes", None),  # e_ask [B, G, N, 2]
        ns(e, None, "nodes"),        # dp_vids [B, D, N]
        ns(e, None),                 # dp_limit [B, D]
        ns(e, None, None),           # dp_applies [B, G, D]
        ns(e, "nodes", None, None),  # pre_res [B, N, C, 4]
        ns(e, "nodes", None),        # pre_prio [B, N, C]
        ns(e, "nodes", None),        # pre_elig [B, N, C]
        ns(e, "nodes", None),        # pre_mp [B, N, C]
        ns(e, "nodes", None),        # pre_gid [B, N, C]
        ns(e, "nodes", None, None),  # pre_evf [B, N, C, 2]
    )
    carry = (
        ns(e, "nodes", None),        # used [B, N, D]
        ns(e, None, "nodes"),        # tg_counts [B, G, N]
        ns(e, "nodes"),              # job_counts [B, N]
        ns(e, None, None, None),     # spread_counts [B, G, S, V]
        ns(e, None, None, None),     # spread_entry [B, G, S, V]
        ns(e),                       # offset [B]
        ns(e, None),                 # failed [B, G]
        ns(e, "nodes", None),        # e_base [B, N, 2]
        ns(e, None, None),           # dp_counts [B, D, V]
        ns(e, "nodes", None),        # pre_alive [B, N, C]
        ns(e, "nodes", None),        # pre_remaining [B, N, 3]
        ns(e, None),                 # pre_counts [B, GP]
    )
    xs = (
        ns(e, None),                 # tg_idx [B, P]
        ns(e, None, None),           # penalty_idx [B, P, K]
        ns(e, None),                 # evict_node [B, P]
        ns(e, None, None),           # evict_res [B, P, D]
        ns(e, None),                 # evict_tg [B, P]
        ns(e, None),                 # limit_p [B, P]
        ns(e, None),                 # sum_sw_p [B, P]
        ns(e, None, None),           # ev_factor [B, P, 2]
        ns(e, None, None),           # rev_factor [B, P, 2]
        ns(e, None, None),           # forced_node [B, P, W]
    )
    return static, carry, xs, ns()   # p_real [B]


def batched_place_scan(mesh):
    """The mesh-sharded, eval-batched placement scan over FULLY batched
    inputs (node tables included — see batched_scan_shardings). Thin
    wrapper over the ONE builder (engine._build_batched_scan); the
    production path is tpu.batcher.DeviceBatcher, which pads/stacks real
    EncodedEvals and uses these same shardings.
    """
    from ..tpu.engine import _build_batched_scan

    return _build_batched_scan(in_shardings=batched_scan_shardings(mesh))
