"""Core data model for the TPU-native orchestrator.

This is a fresh design with the same semantics as the reference's
``nomad/structs/structs.go`` data model (Node structs.go:1508, Job :3285,
TaskGroup :4687, Task :5263, Allocation :7466, Evaluation :8352, Plan :8645).
Unlike the reference, resources are modelled with a single flattened
``ComparableResources`` representation from the start (the reference carries
legacy 0.8-era shapes alongside; we only implement the 0.9+ semantics), and
every struct is designed so the scheduler can *densify* it into device tensors
(see nomad_tpu/tpu/encode.py).
"""
from __future__ import annotations

import time as _time
import uuid as _uuid
from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, Optional, Tuple

# ---------------------------------------------------------------------------
# Constants (reference: nomad/structs/structs.go)
# ---------------------------------------------------------------------------

JOB_TYPE_CORE = "_core"
JOB_TYPE_SERVICE = "service"
JOB_TYPE_BATCH = "batch"
JOB_TYPE_SYSTEM = "system"

JOB_STATUS_PENDING = "pending"
JOB_STATUS_RUNNING = "running"
JOB_STATUS_DEAD = "dead"

JOB_MIN_PRIORITY = 1
JOB_DEFAULT_PRIORITY = 50
JOB_MAX_PRIORITY = 100

NODE_STATUS_INIT = "initializing"
NODE_STATUS_READY = "ready"
NODE_STATUS_DOWN = "down"

NODE_SCHED_ELIGIBLE = "eligible"
NODE_SCHED_INELIGIBLE = "ineligible"

ALLOC_DESIRED_RUN = "run"
ALLOC_DESIRED_STOP = "stop"
ALLOC_DESIRED_EVICT = "evict"

ALLOC_CLIENT_PENDING = "pending"
ALLOC_CLIENT_RUNNING = "running"
ALLOC_CLIENT_COMPLETE = "complete"
ALLOC_CLIENT_FAILED = "failed"
ALLOC_CLIENT_LOST = "lost"

EVAL_STATUS_BLOCKED = "blocked"
EVAL_STATUS_PENDING = "pending"
EVAL_STATUS_COMPLETE = "complete"
EVAL_STATUS_FAILED = "failed"
EVAL_STATUS_CANCELLED = "canceled"

EVAL_TRIGGER_JOB_REGISTER = "job-register"
EVAL_TRIGGER_JOB_DEREGISTER = "job-deregister"
EVAL_TRIGGER_PERIODIC_JOB = "periodic-job"
EVAL_TRIGGER_NODE_DRAIN = "node-drain"
EVAL_TRIGGER_NODE_UPDATE = "node-update"
EVAL_TRIGGER_ALLOC_STOP = "alloc-stop"
EVAL_TRIGGER_SCHEDULED = "scheduled"
EVAL_TRIGGER_ROLLING_UPDATE = "rolling-update"
EVAL_TRIGGER_DEPLOYMENT_WATCHER = "deployment-watcher"
EVAL_TRIGGER_FAILED_FOLLOW_UP = "failed-follow-up"
EVAL_TRIGGER_MAX_PLANS = "max-plan-attempts"
EVAL_TRIGGER_RETRY_FAILED_ALLOC = "alloc-failure"
EVAL_TRIGGER_QUEUED_ALLOCS = "queued-allocs"
EVAL_TRIGGER_PREEMPTION = "preemption"

CORE_JOB_EVAL_GC = "eval-gc"
CORE_JOB_NODE_GC = "node-gc"
CORE_JOB_JOB_GC = "job-gc"
CORE_JOB_DEPLOYMENT_GC = "deployment-gc"
CORE_JOB_FORCE_GC = "force-gc"

# Constraint operands (reference structs.go:6619-6631)
CONSTRAINT_DISTINCT_PROPERTY = "distinct_property"
CONSTRAINT_DISTINCT_HOSTS = "distinct_hosts"
CONSTRAINT_REGEX = "regexp"
CONSTRAINT_VERSION = "version"
CONSTRAINT_SEMVER = "semver"
CONSTRAINT_SET_CONTAINS = "set_contains"
CONSTRAINT_SET_CONTAINS_ALL = "set_contains_all"
CONSTRAINT_SET_CONTAINS_ANY = "set_contains_any"
CONSTRAINT_ATTRIBUTE_IS_SET = "is_set"
CONSTRAINT_ATTRIBUTE_IS_NOT_SET = "is_not_set"

DEPLOYMENT_STATUS_RUNNING = "running"
DEPLOYMENT_STATUS_PAUSED = "paused"
DEPLOYMENT_STATUS_FAILED = "failed"
DEPLOYMENT_STATUS_SUCCESSFUL = "successful"
DEPLOYMENT_STATUS_CANCELLED = "cancelled"

DEPLOYMENT_ACTIVE_STATUSES = (DEPLOYMENT_STATUS_RUNNING, DEPLOYMENT_STATUS_PAUSED)

# Dynamic port range (reference structs/network.go:11-15)
MIN_DYNAMIC_PORT = 20000
MAX_DYNAMIC_PORT = 32000
MAX_VALID_PORT = 65536


def generate_uuid() -> str:
    return str(_uuid.uuid4())


def generate_uuids(n: int) -> List[str]:
    """Batch-mint n v4-format UUID strings from one entropy read — the
    dense placement path mints one id per placement, and per-call
    ``uuid.uuid4()`` object construction is measurable at that volume."""
    import os as _os

    raw = _os.urandom(16 * n).hex()
    out = []
    for k in range(n):
        h = raw[32 * k : 32 * (k + 1)]
        # stamp version (4) and variant (10xx) nibbles like uuid4
        out.append(
            f"{h[0:8]}-{h[8:12]}-4{h[13:16]}-"
            f"{'89ab'[int(h[16], 16) & 3]}{h[17:20]}-{h[20:32]}"
        )
    return out


def now_ns() -> int:
    return _time.time_ns()


# ---------------------------------------------------------------------------
# Resources
# ---------------------------------------------------------------------------


@dataclass
class Port:
    label: str = ""
    value: int = 0
    to: int = 0


@dataclass
class NetworkResource:
    """A network ask or offer (reference structs.go NetworkResource)."""

    mode: str = ""
    device: str = ""
    cidr: str = ""
    ip: str = ""
    mbits: int = 0
    reserved_ports: List[Port] = field(default_factory=list)
    dynamic_ports: List[Port] = field(default_factory=list)

    def copy(self) -> "NetworkResource":
        return NetworkResource(
            mode=self.mode,
            device=self.device,
            cidr=self.cidr,
            ip=self.ip,
            mbits=self.mbits,
            reserved_ports=[replace(p) for p in self.reserved_ports],
            dynamic_ports=[replace(p) for p in self.dynamic_ports],
        )


@dataclass
class RequestedDevice:
    """A device ask on a task (reference structs.go RequestedDevice).

    ``name`` may be "<vendor>/<type>/<name>", "<type>/<name>" or "<type>".
    """

    name: str = ""
    count: int = 1
    constraints: List["Constraint"] = field(default_factory=list)
    affinities: List["Affinity"] = field(default_factory=list)

    def id(self) -> "DeviceIdTuple":
        parts = self.name.split("/")
        if len(parts) >= 3:
            return DeviceIdTuple(parts[0], parts[1], "/".join(parts[2:]))
        if len(parts) == 2:
            return DeviceIdTuple("", parts[0], parts[1])
        return DeviceIdTuple("", self.name, "")


@dataclass(frozen=True)
class DeviceIdTuple:
    vendor: str = ""
    type: str = ""
    name: str = ""

    def matches(self, ask: "DeviceIdTuple") -> bool:
        """Whether this concrete device group satisfies the (possibly
        partially-specified) ask id (reference structs/devices semantics)."""
        if ask.name and ask.name != self.name:
            return False
        if ask.type and ask.type != self.type:
            return False
        if ask.vendor and ask.vendor != self.vendor:
            return False
        return True


@dataclass
class Resources:
    """Per-task resource ask (reference structs.go Resources)."""

    cpu: int = 0  # MHz
    memory_mb: int = 0
    disk_mb: int = 0
    networks: List[NetworkResource] = field(default_factory=list)
    devices: List[RequestedDevice] = field(default_factory=list)


@dataclass
class AllocatedDeviceResource:
    vendor: str = ""
    type: str = ""
    name: str = ""
    device_ids: List[str] = field(default_factory=list)

    def id(self) -> DeviceIdTuple:
        return DeviceIdTuple(self.vendor, self.type, self.name)

    def copy(self) -> "AllocatedDeviceResource":
        return AllocatedDeviceResource(
            self.vendor, self.type, self.name, list(self.device_ids)
        )


@dataclass
class AllocatedTaskResources:
    cpu_shares: int = 0
    memory_mb: int = 0
    networks: List[NetworkResource] = field(default_factory=list)
    devices: List[AllocatedDeviceResource] = field(default_factory=list)

    def copy(self) -> "AllocatedTaskResources":
        return AllocatedTaskResources(
            self.cpu_shares, self.memory_mb,
            [n.copy() for n in self.networks],
            [d.copy() for d in self.devices],
        )

    def add(self, other: "AllocatedTaskResources") -> None:
        self.cpu_shares += other.cpu_shares
        self.memory_mb += other.memory_mb

    def add_networks(self, networks: List[NetworkResource]) -> None:
        """Merge networks BY DEVICE (reference structs.go:2981
        AllocatedTaskResources.Add + Networks.NetIndex): an alloc with a
        task net and a group net on the same NIC flattens to ONE entry
        whose mbits/ports accumulate — preemption reads Networks[0]."""
        for n in networks:
            for mine in self.networks:
                if mine.device == n.device:
                    mine.mbits += n.mbits
                    mine.reserved_ports = list(mine.reserved_ports) + list(n.reserved_ports)
                    mine.dynamic_ports = list(mine.dynamic_ports) + list(n.dynamic_ports)
                    break
            else:
                self.networks.append(n.copy())

    def subtract(self, other: "AllocatedTaskResources") -> None:
        self.cpu_shares -= other.cpu_shares
        self.memory_mb -= other.memory_mb


@dataclass
class AllocatedSharedResources:
    disk_mb: int = 0
    networks: List[NetworkResource] = field(default_factory=list)

    def copy(self) -> "AllocatedSharedResources":
        return AllocatedSharedResources(
            self.disk_mb, [n.copy() for n in self.networks]
        )


@dataclass
class AllocatedResources:
    tasks: Dict[str, AllocatedTaskResources] = field(default_factory=dict)
    shared: AllocatedSharedResources = field(default_factory=AllocatedSharedResources)

    def copy(self) -> "AllocatedResources":
        return AllocatedResources(
            {k: v.copy() for k, v in self.tasks.items()}, self.shared.copy()
        )

    def comparable(self) -> "ComparableResources":
        c = ComparableResources()
        for tr in self.tasks.values():
            c.flattened.add(tr)
            c.flattened.add_networks(tr.networks)
        c.shared.disk_mb = self.shared.disk_mb
        c.flattened.add_networks(self.shared.networks)
        return c


@dataclass
class ComparableResources:
    """Flattened task-group resources (reference structs.go:3192)."""

    flattened: AllocatedTaskResources = field(default_factory=AllocatedTaskResources)
    shared: AllocatedSharedResources = field(default_factory=AllocatedSharedResources)

    def add(self, other: Optional["ComparableResources"]) -> None:
        if other is None:
            return
        self.flattened.add(other.flattened)
        self.shared.disk_mb += other.shared.disk_mb

    def subtract(self, other: Optional["ComparableResources"]) -> None:
        if other is None:
            return
        self.flattened.subtract(other.flattened)
        self.shared.disk_mb -= other.shared.disk_mb

    def superset(self, other: "ComparableResources") -> Tuple[bool, str]:
        """Reference structs.go:3227 — ignores networks."""
        if self.flattened.cpu_shares < other.flattened.cpu_shares:
            return False, "cpu"
        if self.flattened.memory_mb < other.flattened.memory_mb:
            return False, "memory"
        if self.shared.disk_mb < other.shared.disk_mb:
            return False, "disk"
        return True, ""

    def copy(self) -> "ComparableResources":
        c = ComparableResources()
        c.add(self)
        return c


# ---------------------------------------------------------------------------
# Node
# ---------------------------------------------------------------------------


@dataclass
class NodeDeviceInstance:
    id: str = ""
    healthy: bool = True
    locality: str = ""


@dataclass
class NodeDeviceResource:
    vendor: str = ""
    type: str = ""
    name: str = ""
    instances: List[NodeDeviceInstance] = field(default_factory=list)
    attributes: Dict[str, Any] = field(default_factory=dict)

    def id(self) -> DeviceIdTuple:
        return DeviceIdTuple(self.vendor, self.type, self.name)


@dataclass
class NodeResources:
    cpu_shares: int = 0
    memory_mb: int = 0
    disk_mb: int = 0
    networks: List[NetworkResource] = field(default_factory=list)
    devices: List[NodeDeviceResource] = field(default_factory=list)

    def comparable(self) -> ComparableResources:
        c = ComparableResources()
        c.flattened.cpu_shares = self.cpu_shares
        c.flattened.memory_mb = self.memory_mb
        c.shared.disk_mb = self.disk_mb
        c.flattened.networks = list(self.networks)
        return c


@dataclass
class NodeReservedResources:
    cpu_shares: int = 0
    memory_mb: int = 0
    disk_mb: int = 0
    reserved_host_ports: str = ""

    def comparable(self) -> ComparableResources:
        c = ComparableResources()
        c.flattened.cpu_shares = self.cpu_shares
        c.flattened.memory_mb = self.memory_mb
        c.shared.disk_mb = self.disk_mb
        return c


@dataclass
class DriverInfo:
    name: str = ""
    detected: bool = False
    healthy: bool = False
    health_description: str = ""


@dataclass
class HostVolume:
    name: str = ""
    path: str = ""
    read_only: bool = False


@dataclass
class DrainStrategy:
    """How a node drain proceeds (reference structs.go DrainStrategy /
    DrainSpec): ``deadline_ns`` is the grace duration (-1 forces an
    immediate drain, 0 means no deadline); ``force_deadline_ns`` is the
    wall-clock instant the drainer force-migrates everything, stamped by
    the endpoint before the raft apply so replicas agree."""

    deadline_ns: int = 60 * 60 * 10**9
    ignore_system_jobs: bool = False
    force_deadline_ns: int = 0

    def deadline_passed(self, now_ns: int) -> bool:
        if self.deadline_ns < 0:
            return True
        return self.force_deadline_ns > 0 and now_ns >= self.force_deadline_ns


@dataclass
class Node:
    """A client node (reference structs.go:1508)."""

    id: str = field(default_factory=generate_uuid)
    # shared secret minted by the client at first boot; authenticates
    # node-scoped RPCs like Node.DeriveVaultToken (structs.go Node.SecretID)
    # — scrubbed from read endpoints, never returned to other callers
    secret_id: str = field(default_factory=generate_uuid)
    name: str = ""
    datacenter: str = "dc1"
    node_class: str = ""
    attributes: Dict[str, str] = field(default_factory=dict)
    meta: Dict[str, str] = field(default_factory=dict)
    node_resources: NodeResources = field(default_factory=NodeResources)
    reserved_resources: Optional[NodeReservedResources] = None
    drivers: Dict[str, DriverInfo] = field(default_factory=dict)
    host_volumes: Dict[str, HostVolume] = field(default_factory=dict)
    status: str = NODE_STATUS_READY
    status_description: str = ""
    scheduling_eligibility: str = NODE_SCHED_ELIGIBLE
    drain: bool = False
    drain_strategy: Optional[DrainStrategy] = None
    computed_class: str = ""
    http_addr: str = ""
    create_index: int = 0
    modify_index: int = 0

    def comparable_resources(self) -> ComparableResources:
        return self.node_resources.comparable()

    def comparable_reserved_resources(self) -> Optional[ComparableResources]:
        if self.reserved_resources is None:
            return None
        return self.reserved_resources.comparable()

    def terminal_status(self) -> bool:
        return self.status == NODE_STATUS_DOWN

    def ready(self) -> bool:
        return (
            self.status == NODE_STATUS_READY
            and not self.drain
            and self.scheduling_eligibility == NODE_SCHED_ELIGIBLE
        )

    def compute_class(self) -> None:
        from .node_class import compute_node_class

        self.computed_class = compute_node_class(self)

    def copy(self) -> "Node":
        import copy as _copy

        c = _copy.deepcopy(self)
        # derived caches (funcs.node_capacity_vecs) must not survive into
        # a copy whose resources the caller may go on to mutate
        c.__dict__.pop("_cap_vecs", None)
        return c

    def without_secret(self) -> "Node":
        """Shallow copy with secret_id cleared — what read endpoints
        return (node_endpoint.go GetNode clears SecretID before replying).
        Shallow is safe: stored nodes are treated as immutable."""
        if not self.secret_id:
            return self
        import dataclasses as _dc

        return _dc.replace(self, secret_id="")


# ---------------------------------------------------------------------------
# Job spec
# ---------------------------------------------------------------------------


@dataclass
class Constraint:
    ltarget: str = ""
    rtarget: str = ""
    operand: str = "="

    def __str__(self) -> str:
        return f"{self.ltarget} {self.operand} {self.rtarget}"


@dataclass
class Affinity:
    ltarget: str = ""
    rtarget: str = ""
    operand: str = "="
    weight: int = 0  # [-100, 100]


@dataclass
class SpreadTarget:
    value: str = ""
    percent: int = 0


@dataclass
class Spread:
    attribute: str = ""
    weight: int = 0
    spread_target: List[SpreadTarget] = field(default_factory=list)


@dataclass
class EphemeralDisk:
    sticky: bool = False
    size_mb: int = 150
    migrate: bool = False


@dataclass
class ReschedulePolicy:
    attempts: int = 0
    interval_ns: int = 0
    delay_ns: int = 0
    delay_function: str = "constant"  # constant | exponential | fibonacci
    max_delay_ns: int = 0
    unlimited: bool = False


@dataclass
class RestartPolicy:
    attempts: int = 2
    interval_ns: int = 30 * 60 * 10**9
    delay_ns: int = 15 * 10**9
    mode: str = "fail"


@dataclass
class UpdateStrategy:
    """Task-group update strategy (reference structs.go UpdateStrategy)."""

    stagger_ns: int = 30 * 10**9
    max_parallel: int = 1
    health_check: str = "checks"
    min_healthy_time_ns: int = 10 * 10**9
    healthy_deadline_ns: int = 5 * 60 * 10**9
    progress_deadline_ns: int = 10 * 60 * 10**9
    auto_revert: bool = False
    auto_promote: bool = False
    canary: int = 0

    def rolling(self) -> bool:
        return self.max_parallel > 0


@dataclass
class MigrateStrategy:
    max_parallel: int = 1
    health_check: str = "checks"
    min_healthy_time_ns: int = 10 * 10**9
    healthy_deadline_ns: int = 5 * 60 * 10**9


@dataclass
class VolumeRequest:
    name: str = ""
    type: str = "host"
    source: str = ""
    read_only: bool = False


@dataclass
class VolumeMount:
    """A task's mount of a group volume (reference structs.go VolumeMount)."""

    volume: str = ""
    destination: str = ""
    read_only: bool = False


VOLUME_TYPE_HOST = "host"


@dataclass
class Service:
    name: str = ""
    port_label: str = ""
    tags: List[str] = field(default_factory=list)
    # check stanzas as plain dicts: {"name", "type", "ttl", "http",
    # "interval", ...} (reference structs.go ServiceCheck)
    checks: List[Dict[str, Any]] = field(default_factory=list)
    # Consul Connect stanza as a plain dict (reference structs.go
    # ConsulConnect): {"sidecar_service": {"port": ..., "proxy": {...}},
    # "sidecar_task": {"driver": ..., "config": {...}, ...}}
    connect: Optional[Dict[str, Any]] = None

    def has_sidecar(self) -> bool:
        return bool(self.connect and "sidecar_service" in self.connect)


#: Connect sidecar naming (reference structs.go ConnectProxyPrefix) —
#: shared by the server's injection hook and the client's Consul
#: registration (proxy port label / task kind).
CONNECT_PROXY_PREFIX = "connect-proxy"


@dataclass
class LogConfig:
    """Per-task log rotation policy (reference structs.go LogConfig:
    MaxFiles × MaxFileSizeMB, defaults 10 × 10)."""

    max_files: int = 10
    max_file_size_mb: int = 10


@dataclass
class Task:
    name: str = ""
    driver: str = ""
    user: str = ""
    config: Dict[str, Any] = field(default_factory=dict)
    env: Dict[str, str] = field(default_factory=dict)
    meta: Dict[str, str] = field(default_factory=dict)
    resources: Resources = field(default_factory=Resources)
    constraints: List[Constraint] = field(default_factory=list)
    affinities: List[Affinity] = field(default_factory=list)
    services: List[Service] = field(default_factory=list)
    artifacts: List[Dict[str, Any]] = field(default_factory=list)
    templates: List[Dict[str, Any]] = field(default_factory=list)
    vault: Optional[Dict[str, Any]] = None
    leader: bool = False
    # task role marker (reference structs.go TaskKind), e.g.
    # "connect-proxy:<service>" for injected sidecars
    kind: str = ""
    kill_timeout_ns: int = 5 * 10**9
    kill_signal: str = "SIGTERM"
    restart_policy: Optional[RestartPolicy] = None
    dispatch_payload_file: str = ""
    # volume_mount stanzas (reference structs.go VolumeMount)
    volume_mounts: List["VolumeMount"] = field(default_factory=list)
    log_config: LogConfig = field(default_factory=LogConfig)


@dataclass
class TaskGroup:
    name: str = ""
    count: int = 1
    constraints: List[Constraint] = field(default_factory=list)
    affinities: List[Affinity] = field(default_factory=list)
    spreads: List[Spread] = field(default_factory=list)
    tasks: List[Task] = field(default_factory=list)
    restart_policy: RestartPolicy = field(default_factory=RestartPolicy)
    reschedule_policy: Optional[ReschedulePolicy] = None
    ephemeral_disk: EphemeralDisk = field(default_factory=EphemeralDisk)
    update: Optional[UpdateStrategy] = None
    migrate: Optional[MigrateStrategy] = None
    networks: List[NetworkResource] = field(default_factory=list)
    volumes: Dict[str, VolumeRequest] = field(default_factory=dict)
    meta: Dict[str, str] = field(default_factory=dict)
    # GROUP-level services (reference structs.go TaskGroup.Services) —
    # where Consul Connect stanzas live
    services: List[Service] = field(default_factory=list)

    def lookup_task(self, name: str) -> Optional[Task]:
        for t in self.tasks:
            if t.name == name:
                return t
        return None


@dataclass
class PeriodicConfig:
    enabled: bool = False
    spec: str = ""
    spec_type: str = "cron"
    prohibit_overlap: bool = False
    timezone: str = "UTC"


@dataclass
class ParameterizedJobConfig:
    payload: str = "optional"
    meta_required: List[str] = field(default_factory=list)
    meta_optional: List[str] = field(default_factory=list)


@dataclass
class Job:
    """A job specification (reference structs.go:3285)."""

    id: str = ""
    name: str = ""
    namespace: str = "default"
    region: str = "global"
    type: str = JOB_TYPE_SERVICE
    priority: int = JOB_DEFAULT_PRIORITY
    all_at_once: bool = False
    datacenters: List[str] = field(default_factory=lambda: ["dc1"])
    constraints: List[Constraint] = field(default_factory=list)
    affinities: List[Affinity] = field(default_factory=list)
    spreads: List[Spread] = field(default_factory=list)
    task_groups: List[TaskGroup] = field(default_factory=list)
    update: Optional[UpdateStrategy] = None
    periodic: Optional[PeriodicConfig] = None
    parameterized: Optional[ParameterizedJobConfig] = None
    payload: bytes = b""
    meta: Dict[str, str] = field(default_factory=dict)
    stop: bool = False
    parent_id: str = ""
    status: str = JOB_STATUS_PENDING
    status_description: str = ""
    stable: bool = False
    version: int = 0
    create_index: int = 0
    modify_index: int = 0
    job_modify_index: int = 0

    def stopped(self) -> bool:
        return self.stop

    def namespaced_id(self) -> Tuple[str, str]:
        return (self.namespace, self.id)

    def lookup_task_group(self, name: str) -> Optional[TaskGroup]:
        for tg in self.task_groups:
            if tg.name == name:
                return tg
        return None

    def combined_task_meta(self, tg_name: str, task_name: str) -> Dict[str, str]:
        """Job -> group -> task meta, task wins (reference Job.CombinedTaskMeta)."""
        out = dict(self.meta)
        tg = self.lookup_task_group(tg_name)
        if tg is not None:
            out.update(tg.meta)
            task = tg.lookup_task(task_name)
            if task is not None:
                out.update(task.meta)
        return out

    def is_periodic(self) -> bool:
        return self.periodic is not None and self.periodic.enabled

    def is_parameterized(self) -> bool:
        return self.parameterized is not None

    def copy(self) -> "Job":
        import copy as _copy

        return _copy.deepcopy(self)

    def derive_child(self, child_id: str) -> "Job":
        """Copy for a periodic/dispatch child: fresh indexes, runnable, not
        stable (reference periodic.go deriveJob / job_endpoint.go Dispatch)."""
        child = self.copy()
        child.id = child_id
        child.name = child_id
        child.parent_id = self.id
        child.periodic = None
        child.stop = False
        child.stable = False
        child.version = 0
        child.status = ""
        child.status_description = ""
        child.create_index = child.modify_index = child.job_modify_index = 0
        return child


# ---------------------------------------------------------------------------
# Deployment
# ---------------------------------------------------------------------------


@dataclass
class DeploymentState:
    auto_revert: bool = False
    auto_promote: bool = False
    promoted: bool = False
    placed_canaries: List[str] = field(default_factory=list)
    desired_canaries: int = 0
    desired_total: int = 0
    placed_allocs: int = 0
    healthy_allocs: int = 0
    unhealthy_allocs: int = 0
    progress_deadline_ns: int = 0
    require_progress_by_ns: int = 0


@dataclass
class Deployment:
    id: str = field(default_factory=generate_uuid)
    namespace: str = "default"
    job_id: str = ""
    job_version: int = 0
    job_modify_index: int = 0
    job_create_index: int = 0
    task_groups: Dict[str, DeploymentState] = field(default_factory=dict)
    status: str = DEPLOYMENT_STATUS_RUNNING
    status_description: str = ""
    create_index: int = 0
    modify_index: int = 0

    def active(self) -> bool:
        return self.status in DEPLOYMENT_ACTIVE_STATUSES

    def get_id(self) -> str:
        return self.id

    def has_placed_canaries(self) -> bool:
        return any(len(s.placed_canaries) > 0 for s in self.task_groups.values())

    def requires_promotion(self) -> bool:
        return any(
            s.desired_canaries > 0 and not s.promoted for s in self.task_groups.values()
        )

    def copy(self) -> "Deployment":
        import copy as _copy

        return _copy.deepcopy(self)


@dataclass
class DeploymentStatusUpdate:
    deployment_id: str = ""
    status: str = ""
    status_description: str = ""


def deployment_get_id(d: Optional[Deployment]) -> str:
    return d.id if d is not None else ""


# ---------------------------------------------------------------------------
# Allocation
# ---------------------------------------------------------------------------


@dataclass
class RescheduleEvent:
    reschedule_time_ns: int = 0
    prev_alloc_id: str = ""
    prev_node_id: str = ""
    delay_ns: int = 0


@dataclass
class RescheduleTracker:
    events: List[RescheduleEvent] = field(default_factory=list)


@dataclass
class DesiredTransition:
    migrate: Optional[bool] = None
    reschedule: Optional[bool] = None
    force_reschedule: Optional[bool] = None

    def should_migrate(self) -> bool:
        return self.migrate is True

    def should_force_reschedule(self) -> bool:
        return self.force_reschedule is True


@dataclass
class AllocDeploymentStatus:
    healthy: Optional[bool] = None
    timestamp_ns: int = 0
    canary: bool = False
    modify_index: int = 0

    def is_unhealthy(self) -> bool:
        return self.healthy is False

    def is_healthy(self) -> bool:
        return self.healthy is True


@dataclass
class TaskState:
    state: str = "pending"  # pending | running | dead
    failed: bool = False
    restarts: int = 0
    started_at_ns: int = 0
    finished_at_ns: int = 0
    # event trail synced to the server (reference structs.go TaskState
    # .Events → `nomad alloc status` / UI); entries are
    # {"Type", "Message", "DisplayMessage", "Time"} dicts
    events: List[Dict[str, Any]] = field(default_factory=list)

    def successful(self) -> bool:
        return self.state == "dead" and not self.failed


@dataclass
class Allocation:
    """A placement of a task group on a node (reference structs.go:7466)."""

    id: str = field(default_factory=generate_uuid)
    namespace: str = "default"
    eval_id: str = ""
    name: str = ""
    node_id: str = ""
    node_name: str = ""
    job_id: str = ""
    job: Optional[Job] = None
    task_group: str = ""
    allocated_resources: Optional[AllocatedResources] = None
    desired_status: str = ALLOC_DESIRED_RUN
    desired_description: str = ""
    desired_transition: DesiredTransition = field(default_factory=DesiredTransition)
    client_status: str = ALLOC_CLIENT_PENDING
    client_description: str = ""
    task_states: Dict[str, TaskState] = field(default_factory=dict)
    deployment_id: str = ""
    deployment_status: Optional[AllocDeploymentStatus] = None
    reschedule_tracker: Optional[RescheduleTracker] = None
    previous_allocation: str = ""
    next_allocation: str = ""
    preempted_allocations: List[str] = field(default_factory=list)
    preempted_by_allocation: str = ""
    followup_eval_id: str = ""
    metrics: Optional["AllocMetric"] = None
    create_index: int = 0
    modify_index: int = 0
    alloc_modify_index: int = 0
    create_time_ns: int = 0
    modify_time_ns: int = 0

    def index(self) -> int:
        """The trailing ``[N]`` of the alloc name (reference structs.go
        AllocIndex / AllocName)."""
        l, r = self.name.rfind("["), self.name.rfind("]")
        if l == -1 or r == -1 or l >= r:
            return -1
        try:
            return int(self.name[l + 1 : r])
        except ValueError:
            return -1

    # -- status ------------------------------------------------------------

    def server_terminal_status(self) -> bool:
        return self.desired_status in (ALLOC_DESIRED_STOP, ALLOC_DESIRED_EVICT)

    def client_terminal_status(self) -> bool:
        return self.client_status in (
            ALLOC_CLIENT_COMPLETE,
            ALLOC_CLIENT_FAILED,
            ALLOC_CLIENT_LOST,
        )

    def terminal_status(self) -> bool:
        return self.server_terminal_status() or self.client_terminal_status()

    def ran_successfully(self) -> bool:
        if not self.task_states:
            return False
        return all(s.successful() for s in self.task_states.values())

    # -- resources ---------------------------------------------------------

    def comparable_resources(self) -> ComparableResources:
        if self.allocated_resources is not None:
            return self.allocated_resources.comparable()
        return ComparableResources()

    # -- rescheduling ------------------------------------------------------

    def reschedule_policy(self) -> Optional[ReschedulePolicy]:
        if self.job is None:
            return None
        tg = self.job.lookup_task_group(self.task_group)
        if tg is None:
            return None
        return tg.reschedule_policy

    def last_event_time_ns(self) -> int:
        """Latest task finished/started timestamp (reference :7725)."""
        last = 0
        for s in self.task_states.values():
            if s.finished_at_ns > last:
                last = s.finished_at_ns
        if last == 0:
            last = self.modify_time_ns
        return last

    def next_delay_ns(self) -> int:
        """Delay before this alloc may be rescheduled (reference :7779)."""
        policy = self.reschedule_policy()
        if policy is None:
            return 0
        delay = policy.delay_ns
        tracker = self.reschedule_tracker
        if tracker is None or not tracker.events:
            return delay
        events = tracker.events
        if policy.delay_function == "exponential":
            delay = events[-1].delay_ns * 2
        elif policy.delay_function == "fibonacci":
            if len(events) >= 2:
                fib_n1 = events[-1].delay_ns
                fib_n2 = events[-2].delay_ns
                if fib_n2 == policy.max_delay_ns and fib_n1 == policy.delay_ns:
                    delay = fib_n1
                else:
                    delay = fib_n1 + fib_n2
        else:
            return delay
        if policy.max_delay_ns > 0 and delay > policy.max_delay_ns:
            delay = policy.max_delay_ns
            time_diff = self.last_event_time_ns() - events[-1].reschedule_time_ns
            if time_diff > delay:
                delay = policy.delay_ns
        return delay

    def next_reschedule_time(self) -> Tuple[int, bool]:
        """(reschedule_time_ns, eligible) — reference :7752."""
        fail_time = self.last_event_time_ns()
        policy = self.reschedule_policy()
        if (
            self.desired_status == ALLOC_DESIRED_STOP
            or self.client_status != ALLOC_CLIENT_FAILED
            or fail_time == 0
            or policy is None
        ):
            return 0, False
        next_delay = self.next_delay_ns()
        next_time = fail_time + next_delay
        eligible = policy.unlimited or (
            policy.attempts > 0 and self.reschedule_tracker is None
        )
        if policy.attempts > 0 and self.reschedule_tracker and self.reschedule_tracker.events:
            attempted = 0
            for ev in reversed(self.reschedule_tracker.events):
                if fail_time - ev.reschedule_time_ns < policy.interval_ns:
                    attempted += 1
            eligible = attempted < policy.attempts and next_delay < policy.interval_ns
        return next_time, eligible

    def should_reschedule(self, policy: Optional[ReschedulePolicy], fail_time_ns: int) -> bool:
        if self.desired_status in (ALLOC_DESIRED_STOP, ALLOC_DESIRED_EVICT):
            return False
        if self.client_status != ALLOC_CLIENT_FAILED:
            return False
        return self.reschedule_eligible(policy, fail_time_ns)

    def reschedule_eligible(self, policy: Optional[ReschedulePolicy], fail_time_ns: int) -> bool:
        if policy is None:
            return False
        enabled = policy.attempts > 0 or policy.unlimited
        if not enabled:
            return False
        if policy.unlimited:
            return True
        if self.reschedule_tracker is None or not self.reschedule_tracker.events:
            return True
        attempted = 0
        for ev in reversed(self.reschedule_tracker.events):
            if fail_time_ns - ev.reschedule_time_ns < policy.interval_ns:
                attempted += 1
        return attempted < policy.attempts

    def copy(self) -> "Allocation":
        import copy as _copy

        return _copy.deepcopy(self)

    def copy_skip_job(self) -> "Allocation":
        """Copy sharing the (immutable) job. Must not mutate self —
        concurrent snapshot readers share this object.

        Field-wise rather than ``deepcopy``: this is the hottest copy in
        the scheduling pipeline (every alloc is copied on state-store
        insert and on the client sync path), and generic deepcopy's
        reflection over the whole object graph costs ~0.6ms per alloc —
        the dominant per-placement cost at C1M scale. Scalars/strings
        share; every mutable container is copied."""
        import copy as _copy

        c = _copy.copy(self)
        # memoized derived state must not leak onto a copy whose caller
        # may replace resources (e.g. in-place updates)
        c.__dict__.pop("_usage_vec", None)
        if self.allocated_resources is not None:
            c.allocated_resources = self.allocated_resources.copy()
        c.desired_transition = _copy.copy(self.desired_transition)
        c.task_states = (
            {k: _copy.deepcopy(v) for k, v in self.task_states.items()}
            if self.task_states else {}
        )
        if self.deployment_status is not None:
            c.deployment_status = _copy.copy(self.deployment_status)
        if self.reschedule_tracker is not None:
            c.reschedule_tracker = _copy.deepcopy(self.reschedule_tracker)
        c.preempted_allocations = list(self.preempted_allocations)
        if self.metrics is not None:
            c.metrics = self.metrics.copy()
        return c


# ---------------------------------------------------------------------------
# Alloc metrics
# ---------------------------------------------------------------------------


@dataclass
class NodeScoreMeta:
    node_id: str = ""
    scores: Dict[str, float] = field(default_factory=dict)
    norm_score: float = 0.0


@dataclass
class AllocMetric:
    """Scheduling diagnostics carried on each alloc (reference structs.go:8035)."""

    nodes_evaluated: int = 0
    nodes_filtered: int = 0
    nodes_available: Dict[str, int] = field(default_factory=dict)
    class_filtered: Dict[str, int] = field(default_factory=dict)
    constraint_filtered: Dict[str, int] = field(default_factory=dict)
    nodes_exhausted: int = 0
    class_exhausted: Dict[str, int] = field(default_factory=dict)
    dimension_exhausted: Dict[str, int] = field(default_factory=dict)
    quota_exhausted: List[str] = field(default_factory=list)
    score_meta: List[NodeScoreMeta] = field(default_factory=list)
    allocation_time_ns: int = 0
    coalesced_failures: int = 0
    # transient scratch, not serialized
    _topk: int = 5

    def evaluate_node(self) -> None:
        self.nodes_evaluated += 1

    def filter_node(self, node: Optional[Node], reason: str) -> None:
        self.nodes_filtered += 1
        if node is not None and node.node_class:
            self.class_filtered[node.node_class] = self.class_filtered.get(node.node_class, 0) + 1
        if reason:
            self.constraint_filtered[reason] = self.constraint_filtered.get(reason, 0) + 1

    def exhausted_node(self, node: Optional[Node], dimension: str) -> None:
        self.nodes_exhausted += 1
        if node is not None and node.node_class:
            self.class_exhausted[node.node_class] = self.class_exhausted.get(node.node_class, 0) + 1
        if dimension:
            self.dimension_exhausted[dimension] = self.dimension_exhausted.get(dimension, 0) + 1

    def score_node(self, node: Optional[Node], name: str, score: float) -> None:
        if node is None:
            return
        for m in self.score_meta:
            if m.node_id == node.id:
                m.scores[name] = score
                if name == "normalized-score":
                    m.norm_score = score
                return
        m = NodeScoreMeta(node_id=node.id, scores={name: score})
        if name == "normalized-score":
            m.norm_score = score
        self.score_meta.append(m)

    def populate_score_meta_data(self) -> None:
        """Keep only the top-K scored nodes (reference uses a kheap of 5)."""
        self.score_meta.sort(key=lambda m: m.norm_score, reverse=True)
        del self.score_meta[self._topk :]

    def copy(self) -> "AllocMetric":
        import copy as _copy

        c = _copy.copy(self)
        c.nodes_available = dict(self.nodes_available)
        c.class_filtered = dict(self.class_filtered)
        c.constraint_filtered = dict(self.constraint_filtered)
        c.class_exhausted = dict(self.class_exhausted)
        c.dimension_exhausted = dict(self.dimension_exhausted)
        c.quota_exhausted = list(self.quota_exhausted)
        c.score_meta = [
            NodeScoreMeta(m.node_id, dict(m.scores), m.norm_score)
            for m in self.score_meta
        ]
        return c


# ---------------------------------------------------------------------------
# Evaluation
# ---------------------------------------------------------------------------


@dataclass
class Evaluation:
    """A scheduling trigger (reference structs.go:8352)."""

    id: str = field(default_factory=generate_uuid)
    namespace: str = "default"
    priority: int = JOB_DEFAULT_PRIORITY
    type: str = JOB_TYPE_SERVICE
    triggered_by: str = EVAL_TRIGGER_JOB_REGISTER
    job_id: str = ""
    job_modify_index: int = 0
    node_id: str = ""
    node_modify_index: int = 0
    deployment_id: str = ""
    status: str = EVAL_STATUS_PENDING
    status_description: str = ""
    wait_ns: int = 0
    wait_until_ns: int = 0
    next_eval: str = ""
    previous_eval: str = ""
    blocked_eval: str = ""
    failed_tg_allocs: Dict[str, AllocMetric] = field(default_factory=dict)
    class_eligibility: Dict[str, bool] = field(default_factory=dict)
    escaped_computed_class: bool = False
    quota_limit_reached: str = ""
    annotate_plan: bool = False
    queued_allocations: Dict[str, int] = field(default_factory=dict)
    leader_ack: str = ""
    snapshot_index: int = 0
    create_index: int = 0
    modify_index: int = 0
    create_time_ns: int = 0
    modify_time_ns: int = 0
    # distributed-trace context ({"trace_id", "span_id"}) carried with
    # the eval through raft and RPC so one trace_id follows submit ->
    # broker -> (possibly remote) worker -> plan apply -> ack
    trace_ctx: Optional[Dict[str, str]] = None

    def __post_init__(self) -> None:
        if self.trace_ctx is None:
            # stamp the ambient trace at CREATION: an eval minted inside
            # an RPC handler span (Job.Register) or by a scheduler
            # processing a traced eval (follow-up/blocked evals) inherits
            # that trace. Deterministic across replicas — the stamp rides
            # the raft log; FSM-side decode passes trace_ctx explicitly.
            # Deferred import: structs is the data layer, loaded long
            # before the trace package.
            from ..trace import context as _trace_context

            self.trace_ctx = _trace_context.inject()

    def terminal_status(self) -> bool:
        return self.status in (EVAL_STATUS_COMPLETE, EVAL_STATUS_FAILED, EVAL_STATUS_CANCELLED)

    def should_enqueue(self) -> bool:
        if self.status == EVAL_STATUS_PENDING:
            return True
        if self.status in (
            EVAL_STATUS_COMPLETE,
            EVAL_STATUS_FAILED,
            EVAL_STATUS_BLOCKED,
            EVAL_STATUS_CANCELLED,
        ):
            return False
        raise ValueError(f"unhandled evaluation ({self.id}) status {self.status}")

    def should_block(self) -> bool:
        if self.status == EVAL_STATUS_BLOCKED:
            return True
        if self.status in (
            EVAL_STATUS_COMPLETE,
            EVAL_STATUS_FAILED,
            EVAL_STATUS_PENDING,
            EVAL_STATUS_CANCELLED,
        ):
            return False
        raise ValueError(f"unhandled evaluation ({self.id}) status {self.status}")

    def make_plan(self, job: Optional[Job]) -> "Plan":
        p = Plan(
            eval_id=self.id,
            priority=self.priority,
            job=job,
        )
        if job is not None:
            p.all_at_once = job.all_at_once
        return p

    def next_rolling_eval(self, wait_ns: int) -> "Evaluation":
        now = now_ns()
        return Evaluation(
            namespace=self.namespace,
            priority=self.priority,
            type=self.type,
            triggered_by=EVAL_TRIGGER_ROLLING_UPDATE,
            job_id=self.job_id,
            job_modify_index=self.job_modify_index,
            status=EVAL_STATUS_PENDING,
            wait_ns=wait_ns,
            previous_eval=self.id,
            create_time_ns=now,
            modify_time_ns=now,
        )

    def create_blocked_eval(
        self,
        class_eligibility: Optional[Dict[str, bool]],
        escaped: bool,
        quota_reached: str,
    ) -> "Evaluation":
        now = now_ns()
        return Evaluation(
            namespace=self.namespace,
            priority=self.priority,
            type=self.type,
            triggered_by=EVAL_TRIGGER_QUEUED_ALLOCS,
            job_id=self.job_id,
            job_modify_index=self.job_modify_index,
            status=EVAL_STATUS_BLOCKED,
            previous_eval=self.id,
            class_eligibility=class_eligibility or {},
            escaped_computed_class=escaped,
            quota_limit_reached=quota_reached,
            create_time_ns=now,
            modify_time_ns=now,
        )

    def create_failed_follow_up_eval(self, wait_ns: int) -> "Evaluation":
        now = now_ns()
        return Evaluation(
            namespace=self.namespace,
            priority=self.priority,
            type=self.type,
            triggered_by=EVAL_TRIGGER_FAILED_FOLLOW_UP,
            job_id=self.job_id,
            job_modify_index=self.job_modify_index,
            status=EVAL_STATUS_PENDING,
            wait_ns=wait_ns,
            previous_eval=self.id,
            create_time_ns=now,
            modify_time_ns=now,
        )

    def update_modify_time(self) -> None:
        now = now_ns()
        self.modify_time_ns = max(now, self.create_time_ns + 1)

    def copy(self) -> "Evaluation":
        import copy as _copy

        return _copy.deepcopy(self)


# ---------------------------------------------------------------------------
# Plan
# ---------------------------------------------------------------------------


@dataclass
class DesiredUpdates:
    ignore: int = 0
    place: int = 0
    migrate: int = 0
    stop: int = 0
    in_place_update: int = 0
    destructive_update: int = 0
    canary: int = 0
    preemptions: int = 0


@dataclass
class PlanAnnotations:
    desired_tg_updates: Dict[str, DesiredUpdates] = field(default_factory=dict)
    preempted_allocs: List[Allocation] = field(default_factory=list)


@dataclass
class DenseTGPlacements:
    """A block of fresh placements of ONE task group kept as parallel
    arrays end to end: device scan -> plan submit -> plan apply -> FSM
    upsert. The TPU-native answer to the reference's per-alloc object
    flow (generic_sched.go:497-518 builds one Allocation per placement;
    plan_apply.go:324-336 already normalizes alloc DIFFS on the wire —
    this design goes further and defers materializing Allocation objects
    entirely until something reads them).

    Every placement in a block shares the job, task group, eval,
    deployment and — because the dense path only engages for task groups
    with no network or device asks — the exact AllocatedResources shape
    (``resources_proto``). Per-placement state is just the parallel
    lists: id, name, node, score, nodes-evaluated. ``materialize(i)``
    builds (and caches) the classic Allocation object on read; the cache
    lives outside the dataclass fields so wire/raft codecs never ship it.
    """

    namespace: str = "default"
    job_id: str = ""
    task_group: str = ""
    eval_id: str = ""
    deployment_id: str = ""
    job: Optional[Job] = None
    resources_proto: Optional[AllocatedResources] = None
    # capacity ask of ONE placement: (cpu, mem_mb, disk_mb, mbits) — the
    # plan applier's vectorized re-check and the state store's usage
    # mirror consume this instead of per-alloc comparable_resources()
    ask_vec: Tuple[float, float, float, float] = (0.0, 0.0, 0.0, 0.0)
    ids: List[str] = field(default_factory=list)
    names: List[str] = field(default_factory=list)
    node_ids: List[str] = field(default_factory=list)
    node_names: List[str] = field(default_factory=list)
    scores: List[float] = field(default_factory=list)
    nodes_evaluated: List[int] = field(default_factory=list)
    nodes_available: Dict[str, int] = field(default_factory=dict)
    # per-placement preempted alloc ids (device-side preemption engine,
    # tpu/preempt.py); empty when the block preempts nothing — the
    # common case, so the wire cost is one empty list
    preempted: List[List[str]] = field(default_factory=list)
    create_index: int = 0
    modify_index: int = 0
    create_time_ns: int = 0

    def __len__(self) -> int:
        return len(self.ids)

    def __getstate__(self):
        # lazy caches never ship (pickle path; the wire codec already
        # serializes declared fields only)
        d = self.__dict__.copy()
        d.pop("_mat", None)
        d.pop("_by_node", None)
        d.pop("_by_id", None)
        return d

    def id_index_map(self) -> Dict[str, int]:
        """alloc id -> slot (cached; blocks are immutable once committed)."""
        m = self.__dict__.get("_by_id")
        if m is None:
            m = {aid: i for i, aid in enumerate(self.ids)}
            self.__dict__["_by_id"] = m
        return m

    def key(self) -> str:
        """Store-level block key (ids are unique, blocks are non-empty)."""
        return self.ids[0] if self.ids else ""

    def stamp(self, index: int, timestamp_ns: int) -> None:
        """Index-stamp at FSM apply; invalidates any materialization made
        against a provisional (optimistic-snapshot) stamp."""
        self.create_index = index
        self.modify_index = index
        if timestamp_ns:
            self.create_time_ns = timestamp_ns
        self.__dict__.pop("_mat", None)

    def clone_for_snapshot(self) -> "DenseTGPlacements":
        """Shallow copy sharing the (immutable-once-built) parallel
        arrays but NOT the lazy ``_mat`` cache. The optimistic plan
        applier folds the COPY into its snapshot while the original
        rides the raft payload into the live FSM store: the FSM's
        commit stamp would otherwise mutate index fields and pop the
        cache on an object that concurrent snapshot readers are
        materializing against."""
        c = object.__new__(DenseTGPlacements)
        c.__dict__.update(self.__dict__)
        c.__dict__.pop("_mat", None)
        return c

    def node_index_map(self) -> Dict[str, List[int]]:
        """node_id -> placement indices (cached; blocks are immutable
        once committed)."""
        m = self.__dict__.get("_by_node")
        if m is None:
            m = {}
            for i, nid in enumerate(self.node_ids):
                m.setdefault(nid, []).append(i)
            self.__dict__["_by_node"] = m
        return m

    def materialize(self, i: int) -> Allocation:
        cache = self.__dict__.get("_mat")
        if cache is None:
            cache = self.__dict__["_mat"] = [None] * len(self.ids)
        a = cache[i]
        if a is None:
            score = self.scores[i] if i < len(self.scores) else 0.0
            metrics = AllocMetric(
                nodes_evaluated=(
                    self.nodes_evaluated[i] if i < len(self.nodes_evaluated) else 0
                ),
                nodes_available=self.nodes_available,
                score_meta=[
                    NodeScoreMeta(
                        node_id=self.node_ids[i],
                        scores={"binpack": score, "normalized-score": score},
                        norm_score=score,
                    )
                ],
            )
            a = Allocation(
                id=self.ids[i],
                namespace=self.namespace,
                eval_id=self.eval_id,
                name=self.names[i],
                node_id=self.node_ids[i],
                node_name=self.node_names[i],
                job_id=self.job_id,
                job=self.job,
                task_group=self.task_group,
                allocated_resources=self.resources_proto,
                desired_status=ALLOC_DESIRED_RUN,
                client_status=ALLOC_CLIENT_PENDING,
                deployment_id=self.deployment_id,
                metrics=metrics,
                create_index=self.create_index,
                modify_index=self.modify_index,
                create_time_ns=self.create_time_ns,
                modify_time_ns=self.create_time_ns,
            )
            # every placement in the block shares ask_vec by construction
            a.__dict__["_usage_vec"] = self.ask_vec
            if self.preempted and i < len(self.preempted) and self.preempted[i]:
                a.preempted_allocations = list(self.preempted[i])
            cache[i] = a
        return a

    def select(self, keep: List[int]) -> "DenseTGPlacements":
        """Sub-block of the given placement indices (plan applier partial
        commit)."""
        return DenseTGPlacements(
            namespace=self.namespace,
            job_id=self.job_id,
            task_group=self.task_group,
            eval_id=self.eval_id,
            deployment_id=self.deployment_id,
            job=self.job,
            resources_proto=self.resources_proto,
            ask_vec=self.ask_vec,
            ids=[self.ids[i] for i in keep],
            names=[self.names[i] for i in keep],
            node_ids=[self.node_ids[i] for i in keep],
            node_names=[self.node_names[i] for i in keep],
            scores=[self.scores[i] for i in keep] if self.scores else [],
            nodes_evaluated=(
                [self.nodes_evaluated[i] for i in keep] if self.nodes_evaluated else []
            ),
            nodes_available=self.nodes_available,
            preempted=(
                [self.preempted[i] for i in keep] if self.preempted else []
            ),
        )


@dataclass
class Plan:
    """A proposed set of mutations, submitted to the leader (reference structs.go:8645)."""

    eval_id: str = ""
    eval_token: str = ""
    priority: int = JOB_DEFAULT_PRIORITY
    all_at_once: bool = False
    job: Optional[Job] = None
    node_update: Dict[str, List[Allocation]] = field(default_factory=dict)
    node_allocation: Dict[str, List[Allocation]] = field(default_factory=dict)
    annotations: Optional[PlanAnnotations] = None
    deployment: Optional[Deployment] = None
    deployment_updates: List[DeploymentStatusUpdate] = field(default_factory=list)
    node_preemptions: Dict[str, List[Allocation]] = field(default_factory=dict)
    # dense placement blocks (DenseTGPlacements): fresh placements that
    # never materialize per-alloc objects on the commit path
    dense_placements: List[DenseTGPlacements] = field(default_factory=list)
    snapshot_index: int = 0
    # Scheduler opt-in to the asynchronous eval-lifecycle pipeline
    # (nomad_tpu/pipeline): the submitting worker may hand commit + ack
    # to the async applier instead of blocking on the plan future. Only
    # set on device-built plans whose success the scheduler does not
    # need to inspect before completing the eval.
    async_ok: bool = False

    def dense_count(self) -> int:
        return sum(len(b.ids) for b in self.dense_placements)

    def append_stopped_alloc(
        self, alloc: Allocation, desired_desc: str, client_status: str = ""
    ) -> None:
        """Reference Plan.AppendStoppedAlloc (structs.go:8707)."""
        new_alloc = alloc.copy_skip_job()
        if self.job is None and alloc.job is not None:
            self.job = alloc.job
        new_alloc.job = None
        new_alloc.desired_status = ALLOC_DESIRED_STOP
        new_alloc.desired_description = desired_desc
        if client_status:
            new_alloc.client_status = client_status
        self.node_update.setdefault(alloc.node_id, []).append(new_alloc)

    def append_preempted_alloc(self, alloc: Allocation, preempting_alloc_id: str) -> None:
        new_alloc = Allocation(
            id=alloc.id,
            job_id=alloc.job_id,
            namespace=alloc.namespace,
            node_id=alloc.node_id,
            desired_status=ALLOC_DESIRED_EVICT,
            preempted_by_allocation=preempting_alloc_id,
            desired_description=f"Preempted by alloc ID {preempting_alloc_id}",
            allocated_resources=alloc.allocated_resources,
            task_group=alloc.task_group,
        )
        self.node_preemptions.setdefault(alloc.node_id, []).append(new_alloc)

    def pop_update(self, alloc: Allocation) -> None:
        existing = self.node_update.get(alloc.node_id, [])
        if existing and existing[-1].id == alloc.id:
            existing.pop()
            if not existing:
                self.node_update.pop(alloc.node_id, None)

    def append_alloc(self, alloc: Allocation) -> None:
        alloc.job = None
        self.node_allocation.setdefault(alloc.node_id, []).append(alloc)

    def is_noop(self) -> bool:
        return (
            not self.node_update
            and not self.node_allocation
            and not self.dense_placements
            and self.deployment is None
            and not self.deployment_updates
        )

    def inflate_dense(self) -> None:
        """Materialize dense blocks into ``node_allocation`` (test
        harness / compatibility consumers; the production plan applier
        keeps blocks dense end to end)."""
        for block in self.dense_placements:
            for i in range(len(block.ids)):
                alloc = block.materialize(i)
                self.node_allocation.setdefault(alloc.node_id, []).append(alloc)
        self.dense_placements = []


@dataclass
class PlanResult:
    """What the leader committed (reference structs.go:8819)."""

    node_update: Dict[str, List[Allocation]] = field(default_factory=dict)
    node_allocation: Dict[str, List[Allocation]] = field(default_factory=dict)
    deployment: Optional[Deployment] = None
    deployment_updates: List[DeploymentStatusUpdate] = field(default_factory=list)
    node_preemptions: Dict[str, List[Allocation]] = field(default_factory=dict)
    dense_placements: List[DenseTGPlacements] = field(default_factory=list)
    refresh_index: int = 0
    alloc_index: int = 0

    def is_noop(self) -> bool:
        return (
            not self.node_update
            and not self.node_allocation
            and not self.dense_placements
            and not self.deployment_updates
            and self.deployment is None
        )

    def full_commit(self, plan: Plan) -> Tuple[bool, int, int]:
        expected = 0
        actual = 0
        for node, alloc_list in plan.node_allocation.items():
            expected += len(alloc_list)
            actual += len(self.node_allocation.get(node, []))
        expected += plan.dense_count()
        actual += sum(len(b.ids) for b in self.dense_placements)
        return actual == expected, expected, actual


# ---------------------------------------------------------------------------
# Operator / scheduler configuration
# ---------------------------------------------------------------------------


SCHED_ALG_BINPACK = "binpack"
SCHED_ALG_TPU_BINPACK = "tpu_binpack"
SCHED_ALGORITHMS = (SCHED_ALG_BINPACK, SCHED_ALG_TPU_BINPACK)


@dataclass
class PreemptionConfig:
    system_scheduler_enabled: bool = True
    batch_scheduler_enabled: bool = False
    service_scheduler_enabled: bool = False


@dataclass
class SchedulerConfiguration:
    """Runtime-mutable scheduler config (reference structs/operator.go:124).

    ``scheduler_algorithm`` selects the placement backend:
    ``binpack`` = host iterator pipeline (parity oracle),
    ``tpu_binpack`` = batched JAX engine (the default, bit-identical
    to the host oracle). Every boundary a configuration enters by (the
    agent's config file, ``Operator.SchedulerSetConfiguration``, the HTTP
    ``PUT``, ``Server.__init__``) calls ``validate()``: any other name
    would send every eval to the host stack in silence.
    """

    scheduler_algorithm: str = SCHED_ALG_TPU_BINPACK
    preemption_config: PreemptionConfig = field(default_factory=PreemptionConfig)
    create_index: int = 0
    modify_index: int = 0

    def validate(self) -> None:
        if self.scheduler_algorithm not in SCHED_ALGORITHMS:
            raise ValueError(
                f"scheduler_algorithm {self.scheduler_algorithm!r}: "
                f"expected one of {', '.join(SCHED_ALGORITHMS)}"
            )


@dataclass
class QueryOptions:
    """Read-RPC options (reference structs/structs.go QueryOptions).

    ``min_query_index`` > 0 turns the read into a blocking query: the
    server parks the request until the target table moves past that
    index or ``max_query_time`` elapses. ``allow_stale`` lets any
    server — leader or follower — answer from its local FSM instead of
    forwarding to the leader.
    """

    min_query_index: int = 0
    max_query_time: float = 0.0
    allow_stale: bool = False


@dataclass
class QueryMeta:
    """Response metadata stamped on every read served with QueryOptions
    (reference structs/structs.go QueryMeta).

    ``index`` is the state-store index the result is consistent with —
    clients chain it back as the next ``min_query_index``.
    ``follower_lag_ms`` is only meaningful on stale reads: how far
    behind the leader's heartbeat stream this replica was when it
    answered.
    """

    index: int = 0
    known_leader: bool = False
    last_contact_ms: float = 0.0
    follower_lag_ms: float = 0.0
