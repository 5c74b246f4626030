"""Canonical test fixtures (reference ``nomad/mock/mock.go``)."""
from __future__ import annotations

from .structs.structs import (
    ALLOC_CLIENT_PENDING,
    ALLOC_DESIRED_RUN,
    EVAL_STATUS_PENDING,
    EVAL_TRIGGER_JOB_REGISTER,
    EVAL_TRIGGER_NODE_UPDATE,
    JOB_STATUS_PENDING,
    JOB_TYPE_BATCH,
    JOB_TYPE_SERVICE,
    JOB_TYPE_SYSTEM,
    AllocatedResources,
    AllocatedSharedResources,
    AllocatedTaskResources,
    Affinity,
    Allocation,
    Constraint,
    DriverInfo,
    EphemeralDisk,
    Evaluation,
    Job,
    MigrateStrategy,
    NetworkResource,
    Node,
    NodeDeviceInstance,
    NodeDeviceResource,
    NodeReservedResources,
    NodeResources,
    Port,
    ReschedulePolicy,
    Resources,
    RestartPolicy,
    Spread,
    SpreadTarget,
    Task,
    TaskGroup,
    generate_uuid,
)

MINUTE_NS = 60 * 10**9
SECOND_NS = 10**9


def node() -> Node:
    n = Node(
        id=generate_uuid(),
        datacenter="dc1",
        name="foobar",
        drivers={
            "exec": DriverInfo(detected=True, healthy=True),
            "mock_driver": DriverInfo(detected=True, healthy=True),
        },
        attributes={
            "kernel.name": "linux",
            "arch": "x86",
            "nomad.version": "0.5.0",
            "driver.exec": "1",
            "driver.mock_driver": "1",
        },
        node_resources=NodeResources(
            cpu_shares=4000,
            memory_mb=8192,
            disk_mb=100 * 1024,
            networks=[
                NetworkResource(device="eth0", cidr="192.168.0.100/32", ip="192.168.0.100", mbits=1000)
            ],
        ),
        reserved_resources=NodeReservedResources(
            cpu_shares=100,
            memory_mb=256,
            disk_mb=4 * 1024,
            reserved_host_ports="22",
        ),
        meta={"pci-dss": "true", "database": "mysql", "version": "5.6"},
        node_class="linux-medium-pci",
    )
    n.compute_class()
    return n


def nvidia_node() -> Node:
    n = node()
    n.node_resources.devices = [
        NodeDeviceResource(
            type="gpu",
            vendor="nvidia",
            name="1080ti",
            attributes={
                "memory_mb": 11264,
                "cuda_cores": 3584,
                "graphics_clock_mhz": 1480,
                "memory_bandwidth_gbps": 11,
            },
            instances=[
                NodeDeviceInstance(id=generate_uuid(), healthy=True),
                NodeDeviceInstance(id=generate_uuid(), healthy=True),
            ],
        )
    ]
    n.compute_class()
    return n


def job() -> Job:
    j = Job(
        region="global",
        id=f"mock-service-{generate_uuid()}",
        name="my-job",
        type=JOB_TYPE_SERVICE,
        priority=50,
        datacenters=["dc1"],
        constraints=[Constraint(ltarget="${attr.kernel.name}", rtarget="linux", operand="=")],
        task_groups=[
            TaskGroup(
                name="web",
                count=10,
                ephemeral_disk=EphemeralDisk(size_mb=150),
                restart_policy=RestartPolicy(
                    attempts=3, interval_ns=10 * MINUTE_NS, delay_ns=MINUTE_NS, mode="delay"
                ),
                reschedule_policy=ReschedulePolicy(
                    attempts=2,
                    interval_ns=10 * MINUTE_NS,
                    delay_ns=5 * SECOND_NS,
                    delay_function="constant",
                ),
                migrate=MigrateStrategy(),
                tasks=[
                    Task(
                        name="web",
                        driver="exec",
                        config={"command": "/bin/date"},
                        env={"FOO": "bar"},
                        resources=Resources(
                            cpu=500,
                            memory_mb=256,
                            networks=[
                                NetworkResource(
                                    mbits=50,
                                    dynamic_ports=[Port(label="http"), Port(label="admin")],
                                )
                            ],
                        ),
                        meta={"foo": "bar"},
                    )
                ],
                meta={"elb_check_type": "http", "elb_check_interval": "30s", "elb_check_min": "3"},
            )
        ],
        meta={"owner": "armon"},
        status=JOB_STATUS_PENDING,
        version=0,
        create_index=42,
        modify_index=99,
        job_modify_index=99,
    )
    return j


def batch_job() -> Job:
    j = Job(
        region="global",
        id=f"mock-batch-{generate_uuid()}",
        name="batch-job",
        type=JOB_TYPE_BATCH,
        priority=50,
        datacenters=["dc1"],
        task_groups=[
            TaskGroup(
                name="worker",
                count=10,
                ephemeral_disk=EphemeralDisk(size_mb=150),
                restart_policy=RestartPolicy(
                    attempts=3, interval_ns=10 * MINUTE_NS, delay_ns=5 * SECOND_NS, mode="delay"
                ),
                reschedule_policy=ReschedulePolicy(
                    attempts=2,
                    interval_ns=10 * MINUTE_NS,
                    delay_ns=5 * SECOND_NS,
                    delay_function="constant",
                ),
                tasks=[
                    Task(
                        name="worker",
                        driver="mock_driver",
                        config={"run_for": "500ms"},
                        env={"FOO": "bar"},
                        resources=Resources(cpu=100, memory_mb=100),
                        meta={"foo": "bar"},
                    )
                ],
            )
        ],
        status=JOB_STATUS_PENDING,
        create_index=43,
        modify_index=99,
        job_modify_index=99,
    )
    return j


def c1m_job(tpl: dict, job_id: str, count: int = 0) -> Job:
    """One job of the C1M mix from a template row of
    ``benchmark/configs/c1m-5k.json`` (``jobs.templates``: the one table
    of the mix, in the benchmark harness's template keys), with ``count``
    tasks or the row's own."""
    j = job() if tpl["kind"] == "service" else batch_job()
    j.id = job_id
    j.datacenters = list(tpl["datacenters"])
    j.constraints = [
        Constraint(ltarget="${attr.kernel.name}", rtarget="linux", operand="=")
    ] if tpl["linux_only"] else []
    tg = j.task_groups[0]
    tg.count = count or tpl["count"]
    tg.ephemeral_disk.size_mb = tpl["disk"]
    tg.tasks[0].resources = Resources(cpu=tpl["cpu"], memory_mb=tpl["mem"])
    spread, affinity = tpl.get("spread"), tpl.get("affinity")
    if spread:
        tg.spreads = [Spread(
            attribute=spread["attribute"], weight=spread["weight"],
            spread_target=[SpreadTarget(value=v, percent=p)
                           for v, p in spread["targets"].items()],
        )]
    if affinity:
        tg.affinities = [Affinity(
            ltarget="${attr.kernel.name}",
            rtarget="linux" if affinity["linux"] else "windows",
            operand="=", weight=affinity["weight"],
        )]
    return j


def system_job() -> Job:
    j = Job(
        region="global",
        id=f"mock-system-{generate_uuid()}",
        name="my-job",
        type=JOB_TYPE_SYSTEM,
        priority=100,
        datacenters=["dc1"],
        constraints=[Constraint(ltarget="${attr.kernel.name}", rtarget="linux", operand="=")],
        task_groups=[
            TaskGroup(
                name="web",
                count=1,
                ephemeral_disk=EphemeralDisk(size_mb=50),
                restart_policy=RestartPolicy(
                    attempts=3, interval_ns=10 * MINUTE_NS, delay_ns=MINUTE_NS, mode="delay"
                ),
                tasks=[
                    Task(
                        name="web",
                        driver="exec",
                        config={"command": "/bin/date"},
                        env={},
                        resources=Resources(cpu=500, memory_mb=256),
                    )
                ],
            )
        ],
        meta={"owner": "armon"},
        status=JOB_STATUS_PENDING,
        create_index=42,
        modify_index=99,
        job_modify_index=99,
    )
    return j


def eval() -> Evaluation:
    return Evaluation(
        id=generate_uuid(),
        priority=50,
        type=JOB_TYPE_SERVICE,
        job_id=generate_uuid(),
        status=EVAL_STATUS_PENDING,
        triggered_by=EVAL_TRIGGER_JOB_REGISTER,
    )


def alloc() -> Allocation:
    j = job()
    a = Allocation(
        id=generate_uuid(),
        eval_id=generate_uuid(),
        node_id="12345678-abcd-efab-cdef-123456789abc",
        namespace="default",
        task_group="web",
        allocated_resources=AllocatedResources(
            tasks={
                "web": AllocatedTaskResources(
                    cpu_shares=500,
                    memory_mb=256,
                    networks=[
                        NetworkResource(
                            device="eth0",
                            ip="192.168.0.100",
                            mbits=50,
                            reserved_ports=[Port(label="admin", value=5000)],
                            dynamic_ports=[Port(label="http", value=9876)],
                        )
                    ],
                )
            },
            shared=AllocatedSharedResources(disk_mb=150),
        ),
        job=j,
        job_id=j.id,
        desired_status=ALLOC_DESIRED_RUN,
        client_status=ALLOC_CLIENT_PENDING,
    )
    a.name = f"{j.id}.web[0]"
    return a
