"""The least work of a placement scan, from shapes alone.

What is counted is the work the scoring spec asks for, whatever implements
it: one placement step of one evaluation has to look at every node of the
(padded) fleet once, because every node's score can have changed and the
highest wins (a job with spread or affinity stanzas scores every feasible
node by the spec; a plain job's candidate ring is walked in order over the
same planes). It counts no pass a particular implementation makes over its
own intermediates, and no padding of the batch or of the step count.

Per node and step the state that has to be read:

    4 B  running binpack exponential, cpu         (Q27 int32)
    4 B  running binpack exponential, memory      (Q27 int32)
    4 B  free cpu                                 (fit check, int32)
    4 B  free memory                              (fit check, int32)
    4 B  free disk                                (fit check, int32)
    1 B  feasibility / affinity-presence bits     (packed uint8)
    2 B  this job's placements on the node        (anti-affinity, int16)
   ----
   23 B  a plain evaluation
  + 1 B  the node's spread-attribute value id     (stanza evaluations)

written per step: the chosen node's five words and its count, and the
step's outputs (node 4 B, score 8 B, pulls 4 B, skipped 4 B, evict 4 B):
46 B, against N x 23 read.

Operations per node and step, counted as the spec's integer arithmetic:
2 multiplies and 2 shifts (the two selection exponentials), 3 compares and
2 ands (fit), 4 adds and 2 clips (BestFit), 1 multiply-shift (anti-
affinity), 1 add and 1 multiply (mean of terms), 2 compare-selects (ring
order, running maximum): 23; a stanza evaluation adds the spread boost
(1 gather, 2 adds, 1 multiply, 1 divide) and the affinity term (1 add): 29.

On a v5e the bytes bound is the larger by far (23 B at 819 GB/s is 28 ps a
node; 23 operations at the table's 197 T/s is 0.12 ps), so the roofline
shares read from this file are shares of the HBM roofline. That is a
generous floor: a fleet's planes (N=5,120 nodes x 23 B = 118 KB an
evaluation) fit on-chip memory, and a scan that kept them there between
steps would beat it. The share says how far the time is from streaming the
planes once a step, no more.
"""
from __future__ import annotations

import json
import os

PLAIN_BYTES_PER_NODE_STEP = 23
STANZA_BYTES_PER_NODE_STEP = 24
WRITTEN_BYTES_PER_STEP = 46
PLAIN_OPS_PER_NODE_STEP = 23
STANZA_OPS_PER_NODE_STEP = 29
NODE_PAD = 128     # the fleet's planes are laid out in whole lanes


def padded_nodes(n_nodes: int) -> int:
    return -(-int(n_nodes) // NODE_PAD) * NODE_PAD


def scan_bytes(n_nodes: int, placements: int, stanzas: bool) -> int:
    """Least bytes moved by one evaluation's scan of ``placements`` steps
    over ``n_nodes`` nodes."""
    per = STANZA_BYTES_PER_NODE_STEP if stanzas else PLAIN_BYTES_PER_NODE_STEP
    return int(placements) * (padded_nodes(n_nodes) * per + WRITTEN_BYTES_PER_STEP)


def scan_ops(n_nodes: int, placements: int, stanzas: bool) -> int:
    per = STANZA_OPS_PER_NODE_STEP if stanzas else PLAIN_OPS_PER_NODE_STEP
    return int(placements) * padded_nodes(n_nodes) * per


def load_peaks(device_kind: str, path: str = "") -> dict:
    """The peaks of ``device_kind``; an unknown device is an error."""
    path = path or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {path}: "
                       f"known kinds are {sorted(table)}")
    return table[device_kind]


def least_seconds(evals: list, peaks: dict) -> tuple:
    """(least seconds, 'bytes' or 'operations') for a list of evaluations,
    each (n_nodes, placements, stanzas): the larger of bytes over the HBM
    peak and operations over the arithmetic peak."""
    b = sum(scan_bytes(*e) for e in evals) / float(peaks["hbm_bytes_per_s"])
    o = sum(scan_ops(*e) for e in evals) / float(peaks["bf16_flops_per_s"])
    return (b, "bytes") if b >= o else (o, "operations")
