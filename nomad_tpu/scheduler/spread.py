"""Spread scoring (reference ``scheduler/spread.go``)."""
from __future__ import annotations

from typing import Dict, List, Optional

from ..structs.structs import Job, Spread, TaskGroup
from .context import EvalContext
from .propertyset import PropertySet, get_property
from .rank import RankedNode

IMPLICIT_TARGET = "*"


def _godiv(a: float, b: float) -> float:
    """Float division with Go semantics: x/0 = ±Inf, 0/0 = NaN."""
    if b == 0.0:
        import math

        return math.nan if a == 0.0 else math.copysign(math.inf, a)
    return a / b


class SpreadInfo:
    def __init__(self, weight: int) -> None:
        self.weight = weight
        self.desired_counts: Dict[str, float] = {}


class SpreadIterator:
    def __init__(self, ctx: EvalContext, source) -> None:
        self.ctx = ctx
        self.source = source
        self.job: Optional[Job] = None
        self.tg: Optional[TaskGroup] = None
        self.job_spreads: List[Spread] = []
        self.tg_spread_info: Dict[str, Dict[str, SpreadInfo]] = {}
        self.sum_spread_weights = 0
        self.has_spread = False
        self.group_property_sets: Dict[str, List[PropertySet]] = {}

    def reset(self) -> None:
        self.source.reset()
        for sets in self.group_property_sets.values():
            for ps in sets:
                ps.populate_proposed()

    def set_job(self, job: Job) -> None:
        self.job = job
        if job.spreads:
            self.job_spreads = list(job.spreads)

    def set_task_group(self, tg: TaskGroup) -> None:
        self.tg = tg
        if tg.name not in self.group_property_sets:
            sets: List[PropertySet] = []
            for spread in self.job_spreads:
                pset = PropertySet(self.ctx, self.job)
                pset.set_target_attribute(spread.attribute, tg.name)
                sets.append(pset)
            for spread in tg.spreads:
                pset = PropertySet(self.ctx, self.job)
                pset.set_target_attribute(spread.attribute, tg.name)
                sets.append(pset)
            self.group_property_sets[tg.name] = sets
        self.has_spread = bool(self.group_property_sets[tg.name])
        if tg.name not in self.tg_spread_info:
            self._compute_spread_info(tg)

    def has_spreads(self) -> bool:
        return self.has_spread

    def next(self) -> Optional[RankedNode]:
        while True:
            option = self.source.next()
            if option is None or not self.has_spreads():
                return option

            tg_name = self.tg.name
            total_spread_score = 0.0
            for pset in self.group_property_sets[tg_name]:
                nvalue, error_msg, used_count = pset.used_count(option.node, tg_name)
                # Include this placement itself in the count.
                used_count += 1
                if error_msg:
                    total_spread_score -= 1.0
                    continue
                spread_details = self.tg_spread_info[tg_name][pset.target_attribute]
                if not spread_details.desired_counts:
                    # No targets: even-spread scoring.
                    total_spread_score += even_spread_score_boost(pset, option.node)
                else:
                    total_spread_score += targeted_spread_boost(
                        spread_details.desired_counts, nvalue, used_count,
                        spread_details.weight, self.sum_spread_weights)

            if total_spread_score != 0.0:
                option.scores.append(total_spread_score)
                self.ctx.metrics.score_node(option.node, "allocation-spread", total_spread_score)
            return option

    def _compute_spread_info(self, tg: TaskGroup) -> None:
        spread_infos: Dict[str, SpreadInfo] = {}
        total_count = tg.count
        combined = list(tg.spreads) + list(self.job_spreads)
        for spread in combined:
            si = SpreadInfo(spread.weight)
            si.desired_counts = desired_counts(spread, total_count)
            spread_infos[spread.attribute] = si
            self.sum_spread_weights += spread.weight
        self.tg_spread_info[tg.name] = spread_infos


def desired_counts(spread, total_count: int) -> Dict[str, float]:
    """A spread's targets as placements of ``total_count``; what the targets
    leave over goes to the implicit target."""
    desired: Dict[str, float] = {}
    sum_desired = 0.0
    for st in spread.spread_target:
        desired[st.value] = (float(st.percent) / 100.0) * float(total_count)
        sum_desired += desired[st.value]
    if 0 < sum_desired < float(total_count):
        desired[IMPLICIT_TARGET] = float(total_count) - sum_desired
    return desired


def targeted_spread_boost(desired: Dict[str, float], nvalue, used_count: int,
                          weight, sum_weights) -> float:
    """One targeted spread's term for a node whose attribute reads
    ``nvalue``; ``used_count`` includes the placement being scored."""
    desired_count = desired.get(nvalue)
    if desired_count is None:
        desired_count = desired.get(IMPLICIT_TARGET)
        if desired_count is None:
            return -1.0
    # Go float division semantics: x/0 = ±Inf, 0/0 = NaN — a
    # percent-0 target yields -Inf, steering allocs away.
    spread_weight = _godiv(float(weight), float(sum_weights))
    return _godiv(desired_count - float(used_count), desired_count) * spread_weight


def even_spread_score_boost(pset: PropertySet, node) -> float:
    """Score when no targets are set: prefer under-used attribute values."""
    combined_use = pset.get_combined_use_map()
    if not combined_use:
        return 0.0
    nvalue, ok = get_property(node, pset.target_attribute)
    if not ok:
        return -1.0
    return even_spread_boost(combined_use.get(nvalue, 0),
                             min(combined_use.values()),
                             max(combined_use.values()))


def even_spread_boost(current: int, min_count: int, max_count: int) -> float:
    """The even-spread term from the node's value's count and the least and
    the most any value holds."""
    if min_count == 0:
        delta_boost = -1.0
    else:
        delta = min_count - current
        delta_boost = float(delta) / float(min_count)
    if current != min_count:
        return delta_boost
    elif min_count == max_count:
        return -1.0
    elif min_count == 0:
        return 1.0
    delta = max_count - min_count
    return float(delta) / float(min_count)
