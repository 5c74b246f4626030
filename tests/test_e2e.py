"""E2E suites over real agent processes (reference e2e/: rescheduling/,
spread/, deployment/, clientstate/) — black-box through the SDK only.
"""
import os
import time

import pytest

from e2e_framework import (
    AgentProc,
    allocs_of,
    running_allocs,
    service_job,
    wait_until,
)


@pytest.fixture(scope="module")
def dev():
    agent = AgentProc("-dev", "-no-gossip", name="dev")
    yield agent
    agent.stop()


class TestJobLifecycle:
    def test_run_update_stop(self, dev):
        api = dev.api
        job = service_job("e2e-life", count=2, command="sleep 300")
        api.jobs.register(job)
        wait_until(lambda: len(running_allocs(api, "e2e-life")) == 2,
                   msg="2 allocs running")
        # scale down via re-register
        job["TaskGroups"][0]["Count"] = 1
        api.jobs.register(job)
        wait_until(lambda: len(running_allocs(api, "e2e-life")) == 1,
                   msg="scaled to 1")
        api.jobs.deregister("e2e-life")
        wait_until(lambda: not running_allocs(api, "e2e-life"),
                   msg="all stopped")


class TestRescheduling:
    def test_failed_alloc_rescheduled(self, dev):
        """reference e2e/rescheduling: a dying task is replaced on a new
        alloc rather than restarted forever in place."""
        api = dev.api
        job = service_job("e2e-resched", count=1, command="exit 1")
        job["TaskGroups"][0]["Tasks"][0]["RestartPolicy"] = {
            "Attempts": 0, "Mode": "fail", "IntervalNs": 5_000_000_000,
            "DelayNs": 100_000_000,
        }
        job["TaskGroups"][0]["ReschedulePolicy"] = {
            "Attempts": 2, "IntervalNs": 60_000_000_000,
            "DelayNs": 500_000_000, "DelayFunction": "constant",
            "Unlimited": False,
        }
        api.jobs.register(job)
        wait_until(
            lambda: len([a for a in allocs_of(api, "e2e-resched")
                         if a["ClientStatus"] == "failed"]) >= 1
            and len(allocs_of(api, "e2e-resched")) >= 2,
            msg="failed alloc replaced by reschedule",
        )
        # replacements chain via PreviousAllocation/NextAllocation
        allocs = allocs_of(api, "e2e-resched")
        infos = [api.allocations.info(a["ID"])[0] for a in allocs]
        assert any(i.get("PreviousAllocation") for i in infos), \
            "reschedule links predecessor"


class TestSpreadAcrossNodes:
    def test_allocs_spread_on_two_clients(self):
        """reference e2e/spread: a spread stanza distributes allocs
        across client nodes (real server + 2 real client processes)."""
        server = AgentProc("-server", "-no-gossip", name="spread-srv")
        # discover the server's RPC address through its API
        raft, _ = server.api.get("/v1/operator/raft/configuration")
        rpc_addr = raft["Servers"][0]["Address"]
        clients = [
            AgentProc("-client", "-servers", rpc_addr, "-no-gossip",
                      "-node-class", f"rack{i}", name=f"spread-c{i}")
            for i in range(2)
        ]
        try:
            api = server.api
            wait_until(lambda: len((api.nodes.list()[0]) or []) == 2,
                       timeout=180, msg="2 nodes registered")
            job = service_job("e2e-spread", count=4, command="sleep 300")
            job["TaskGroups"][0]["Spreads"] = [
                {"Attribute": "${node.class}", "Weight": 100}
            ]
            api.jobs.register(job)
            wait_until(lambda: len(running_allocs(api, "e2e-spread")) == 4,
                       timeout=180, msg="4 allocs running")
            nodes_used = {a["NodeID"] for a in running_allocs(api, "e2e-spread")}
            assert len(nodes_used) == 2, "spread across both nodes"
            per_node = [sum(1 for a in running_allocs(api, "e2e-spread")
                            if a["NodeID"] == n) for n in nodes_used]
            assert sorted(per_node) == [2, 2], f"even spread, got {per_node}"
        finally:
            for c in clients:
                c.stop()
            server.stop()


class TestDeployment:
    def test_rolling_update_completes(self, dev):
        """reference e2e/deployment: an update stanza drives a rolling
        deployment to 'successful'."""
        api = dev.api
        job = service_job("e2e-deploy", count=2, command="sleep 300")
        job["TaskGroups"][0]["Update"] = {
            "MaxParallel": 1, "MinHealthyTimeNs": 100_000_000,
            "HealthyDeadlineNs": 30_000_000_000,
        }
        api.jobs.register(job)
        wait_until(lambda: len(running_allocs(api, "e2e-deploy")) == 2,
                   msg="initial rollout")
        # destructive update → new deployment
        job["TaskGroups"][0]["Tasks"][0]["Config"]["args"] = ["-c", "sleep 301"]
        api.jobs.register(job)

        def deployment_successful():
            deps, _ = api.jobs.deployments("e2e-deploy")
            return any(d["Status"] == "successful" and d["JobVersion"] >= 1
                       for d in deps or [])

        wait_until(deployment_successful, timeout=90,
                   msg="rolling deployment successful")


class TestClientState:
    def test_hard_kill_recovery(self, tmp_path_factory):
        """reference e2e/clientstate: kill -9 the agent; a restarted agent
        with the same data dir re-attaches to the live task instead of
        starting a second copy."""
        data_dir = str(tmp_path_factory.mktemp("e2e-state"))
        marker = os.path.join(data_dir, "counter")
        agent = AgentProc("-dev", "-no-gossip", "-data-dir", data_dir,
                          name="state-1")
        try:
            api = agent.api
            # the task appends its pid once at start: a restarted task
            # would append again
            job = service_job(
                "e2e-state", count=1,
                command=f"echo $$ >> {marker}; sleep 600",
            )
            api.jobs.register(job)
            wait_until(lambda: len(running_allocs(api, "e2e-state")) == 1,
                       timeout=150, msg="alloc running")
            wait_until(lambda: os.path.exists(marker), msg="task marker")
            pid_before = open(marker).read().strip()

            agent.kill_hard()
            # the task itself survives the agent's death (detached)
            assert open(marker).read().strip() == pid_before

            agent2 = AgentProc("-dev", "-no-gossip", "-data-dir", data_dir,
                               name="state-2")
            try:
                api2 = agent2.api
                wait_until(lambda: len(running_allocs(api2, "e2e-state")) == 1,
                           timeout=150, msg="alloc recovered after restart")
                time.sleep(1.0)
                assert open(marker).read().strip() == pid_before, \
                    "task re-attached, not restarted"
            finally:
                agent2.stop()
        finally:
            agent.stop()


class TestAffinities:
    """reference e2e/affinities: placements follow affinity weights."""

    def test_affinity_steers_placements(self):
        server = AgentProc("-server", "-no-gossip", name="aff-srv")
        raft, _ = server.api.get("/v1/operator/raft/configuration")
        rpc_addr = raft["Servers"][0]["Address"]
        clients = [
            AgentProc("-client", "-servers", rpc_addr, "-no-gossip",
                      "-node-class", f"aff-r{i}", name=f"aff-c{i}")
            for i in range(2)
        ]
        try:
            api = server.api
            wait_until(lambda: len(api.nodes.list()[0] or []) == 2,
                       timeout=180, msg="both nodes registered")
            # placements 1..count-1 strictly favor the affinity node
            # (anti = -(c+1)/count > -1 while c+1 < count); the FINAL
            # placement's +1 affinity and -1 anti-affinity cancel exactly
            # and the winner is capacity-dependent — assert count-1
            job = service_job("e2e-aff", count=4, command="sleep 300")
            job["Affinities"] = [{
                "LTarget": "${node.class}", "RTarget": "aff-r1",
                "Operand": "=", "Weight": 100,
            }]
            api.jobs.register(job)
            wait_until(lambda: len(running_allocs(api, "e2e-aff")) == 4,
                       timeout=120, msg="4 allocs running")
            nodes, _ = api.nodes.list()
            class_of = {n["ID"]: n.get("NodeClass", "") for n in nodes}
            placements = [class_of[a["NodeID"]]
                          for a in running_allocs(api, "e2e-aff")]
            # strong positive affinity: all but (possibly) the tying
            # final placement land on the affinity node
            assert placements.count("aff-r1") >= 3, placements
        finally:
            for c in clients:
                c.stop()
            server.stop()


class TestNomadExec:
    """reference e2e/nomadexec: command execution inside a live task."""

    def test_exec_and_fs_roundtrip(self, dev):
        api = dev.api
        job = service_job("e2e-exec",
                          command="echo bootmark > $NOMAD_TASK_DIR/mark; sleep 300")
        api.jobs.register(job)
        wait_until(lambda: running_allocs(api, "e2e-exec"), msg="alloc running")
        alloc = running_allocs(api, "e2e-exec")[0]

        # one-shot exec runs INSIDE the task env
        res, _ = api.allocations.exec_task(
            alloc["ID"], "t", ["/bin/sh", "-c", "echo from-exec; exit 7"])
        assert "from-exec" in res["Output"] and res["ExitCode"] == 7

        # fs API sees the file the task wrote
        data = api.alloc_fs.cat(alloc["ID"], "t/local/mark")
        assert data.strip() == b"bootmark"
        entries, _ = api.alloc_fs.ls(alloc["ID"], "t/local")
        assert any(e["Name"] == "mark" for e in entries)

        # task logs captured
        logs = api.alloc_fs.logs(alloc["ID"], "t", "stdout")
        assert isinstance(logs, (bytes, str))
        api.jobs.deregister("e2e-exec")


class TestMetricsE2E:
    """reference e2e/metrics: telemetry visible after scheduling load."""

    def test_scheduler_counters_present(self, dev):
        api = dev.api
        job = service_job("e2e-metrics", count=2, command="sleep 300")
        api.jobs.register(job)
        wait_until(lambda: len(running_allocs(api, "e2e-metrics")) == 2,
                   msg="allocs running")
        # the inmem sink aggregates in 10s intervals: poll until the
        # scheduling counters from this job's eval surface
        def counter_names():
            m = api.agent.metrics()
            names = {c["Name"] for c in m.get("Counters", [])}
            names |= {s["Name"] for s in m.get("Samples", [])}
            return names

        # BOTH names inside ONE polled predicate: asserting "plan" on a
        # separate fresh fetch can land in a new 10s inmem aggregation
        # interval that hasn't seen a plan sample yet (r3 suite-load race)
        def scheduler_and_plan_counters():
            names = counter_names()
            return (
                any("invoke_scheduler" in n for n in names)
                and any("plan" in n for n in names)
            )

        wait_until(scheduler_and_plan_counters, timeout=30,
                   msg="scheduler+plan counters visible in one interval")
        # prometheus format serves too
        import urllib.request

        with urllib.request.urlopen(
            dev.http_addr + "/v1/metrics?format=prometheus", timeout=10
        ) as resp:
            text = resp.read().decode()
        assert "nomad_" in text and "# TYPE" in text
        api.jobs.deregister("e2e-metrics")


class TestParameterizedDispatch:
    """reference e2e (dispatch/periodic slot): parameterized job dispatch
    creates child jobs with payloads."""

    def test_dispatch_with_payload(self, dev):
        api = dev.api
        job = service_job("e2e-batch-param", count=1,
                          command='cat $NOMAD_TASK_DIR/input.txt > $NOMAD_TASK_DIR/out; sleep 300')
        job["Type"] = "batch"
        job["ParameterizedJob"] = {"Payload": "required"}
        job["TaskGroups"][0]["Tasks"][0]["DispatchPayloadFile"] = "input.txt"
        api.jobs.register(job)

        out, _ = api.jobs.dispatch("e2e-batch-param", payload=b"dispatched-data")
        child_id = out["DispatchedJobID"]
        wait_until(lambda: running_allocs(api, child_id), msg="child running")
        alloc = running_allocs(api, child_id)[0]
        wait_until(lambda: api.alloc_fs.cat(alloc["ID"], "t/local/out").strip()
                   == b"dispatched-data", msg="payload delivered")


class TestHostVolumes:
    """reference e2e/hostvolumes: a client-declared host volume is
    scheduled against (HostVolumeChecker) and mounted into the task."""

    def test_volume_scheduling_and_mount(self, tmp_path_factory):
        host_dir = tmp_path_factory.mktemp("hostvol")
        (host_dir / "seed.txt").write_text("from-the-host")
        agent = AgentProc("-dev", "-no-gossip",
                          "-host-volume", f"shared={host_dir}",
                          name="hv-agent")
        try:
            api = agent.api
            # the node advertises the volume
            nodes, _ = api.nodes.list()
            info, _ = api.nodes.info(nodes[0]["ID"])
            assert "shared" in (info.get("HostVolumes") or {})

            job = service_job(
                "e2e-hv", count=1,
                command="cat data/seed.txt > $NOMAD_TASK_DIR/copied; "
                        "echo task-was-here > data/written.txt; sleep 300",
            )
            job["TaskGroups"][0]["Volumes"] = {
                "data": {"Name": "data", "Type": "host", "Source": "shared"},
            }
            job["TaskGroups"][0]["Tasks"][0]["VolumeMounts"] = [
                {"Volume": "data", "Destination": "data"},
            ]
            api.jobs.register(job)
            # generous: suite-context CPU contention (jax compiles on all
            # cores) can starve the agent for a while
            wait_until(lambda: running_allocs(api, "e2e-hv"), timeout=180,
                       msg="alloc running")
            alloc = running_allocs(api, "e2e-hv")[0]
            # the task read host data through the mount...
            wait_until(lambda: api.alloc_fs.cat(
                alloc["ID"], "t/local/copied").strip() == b"from-the-host",
                msg="host file visible through mount")
            # ...and wrote back to the HOST through it
            wait_until(lambda: (host_dir / "written.txt").exists(),
                       msg="task write landed on the host volume")
            assert (host_dir / "written.txt").read_text().strip() == "task-was-here"

            # a job demanding a MISSING volume doesn't place
            bad = service_job("e2e-hv-missing", count=1, command="sleep 30")
            bad["TaskGroups"][0]["Volumes"] = {
                "data": {"Name": "data", "Type": "host", "Source": "no-such"},
            }
            api.jobs.register(bad)
            evals_seen = []
            def blocked():
                evs, _ = api.jobs.evaluations("e2e-hv-missing")
                evals_seen[:] = evs or []
                return any(e.get("Status") == "complete"
                           and e.get("FailedTGAllocs") for e in evals_seen)
            wait_until(blocked, timeout=120, msg="missing volume fails placement")
            assert not running_allocs(api, "e2e-hv-missing")
        finally:
            agent.stop()


class TestClusterOpsE2E:
    """Config-file boot + runtime join + key rotation + force-leave +
    client GC, over REAL forked agent processes (e2e
    criteria; reference e2e slots for agent config and cluster ops)."""

    def test_config_boot_join_rotate_forceleave_gc(self, tmp_path):
        import base64
        import secrets as _secrets
        import socket

        def free_port(k):
            # OUTSIDE the kernel's ephemeral range (and pid-scattered), so
            # the agents' own ephemeral http/rpc binds can't steal a
            # reserved port in the boot window (bind TOCTOU)
            for attempt in range(50):
                p = 21000 + (os.getpid() * 13 + k * 7919 + attempt) % 9000
                s = socket.socket()
                try:
                    s.bind(("127.0.0.1", p))
                    return p
                except OSError:
                    continue
                finally:
                    s.close()
            raise RuntimeError("no free fixed port found")

        key_a = base64.b64encode(_secrets.token_bytes(32)).decode()
        key_b = base64.b64encode(_secrets.token_bytes(32)).decode()
        serf1, serf2 = free_port(1), free_port(2)

        def write_cfg(name, serf_port, client=False):
            p = tmp_path / f"{name}.hcl"
            p.write_text(f'''
name       = "{name}"
datacenter = "dc1"
ports {{
  http = 0
  serf = {serf_port}
}}
server {{
  enabled          = true
  bootstrap_expect = 1
  encrypt          = "{key_a}"
}}
client {{
  enabled = {"true" if client else "false"}
}}
''')
            return str(p)

        # both agents boot from CONFIG FILES; no retry_join — they meet
        # via the runtime /v1/agent/join endpoint
        a1 = AgentProc("-config", write_cfg("ops1", serf1, client=True),
                       "-dev", name="ops1")
        a2 = AgentProc("-config", write_cfg("ops2", serf2), name="ops2")
        try:
            api1, api2 = a1.api, a2.api
            # config file took effect (name flows into gossip identity)
            wait_until(lambda: api1.agent.members()["Members"][0]["Name"]
                       .startswith("ops1"), msg="config-file name visible")

            # runtime join
            out = api1.agent.join([f"127.0.0.1:{serf2}"])
            assert out["num_joined"] == 1
            wait_until(lambda: len(api1.agent.members()["Members"]) == 2,
                       msg="runtime join converged on 1")
            wait_until(lambda: len(api2.agent.members()["Members"]) == 2,
                       msg="runtime join converged on 2")

            # cluster-wide key rotation from ONE node's endpoint
            api1.agent.keyring_op("install", key_b)
            wait_until(lambda: key_b in api2.agent.keyring_list()["Keys"],
                       msg="install propagated to 2")
            api1.agent.keyring_op("use", key_b)
            wait_until(lambda: key_b in api2.agent.keyring_list()["PrimaryKeys"],
                       msg="use propagated to 2")
            api1.agent.keyring_op("remove", key_a)
            wait_until(lambda: list(api2.agent.keyring_list()["Keys"])
                       == [key_b], msg="remove propagated to 2")
            # gossip still alive post-rotation
            time.sleep(1.0)
            assert len(api1.agent.members()["Members"]) == 2

            # run a short batch task on the dev agent's client, then GC it
            job = service_job("e2e-gc", count=1, command="true")
            job["Type"] = "batch"
            api1.jobs.register(job)
            wait_until(lambda: any(
                a["ClientStatus"] == "complete"
                for a in allocs_of(api1, "e2e-gc")), timeout=180,
                msg="batch task complete")
            out = api1.agent.client_gc()
            assert out["Collected"] >= 1

            # kill 2's gossip hard, then evict it from 1's view
            a2.kill_hard()
            api1.agent.force_leave("ops2.global")
            wait_until(lambda: any(
                m["Name"] == "ops2.global" and m["Status"] in ("left", "failed")
                for m in api1.agent.members()["Members"]),
                msg="forced member marked left/failed")
        finally:
            a1.stop()
            a2.stop()


class TestServerFailoverE2E:
    """Multi-server black-box failover (reference
    nomad/testing.go:41 multi-server clusters + testutil/wait.go:85
    WaitForLeader): 3 fork-exec wire-raft server agents + a client
    agent; SIGKILL the leader mid-workload and assert a new leader
    commits the remaining placements with no alloc lost or doubled;
    then `operator raft remove-peer` the corpse and rotate the gossip
    keyring under load."""

    def _free_port(self, k):
        import socket

        for attempt in range(50):
            p = 22000 + (os.getpid() * 17 + k * 6211 + attempt) % 9000
            s = socket.socket()
            try:
                s.bind(("127.0.0.1", p))
                return p
            except OSError:
                continue
            finally:
                s.close()
        raise RuntimeError("no free fixed port found")

    def test_leader_sigkill_failover(self, tmp_path):
        import base64
        import secrets as _secrets

        key_a = base64.b64encode(_secrets.token_bytes(32)).decode()
        key_b = base64.b64encode(_secrets.token_bytes(32)).decode()
        serf = [self._free_port(i) for i in (1, 2, 3)]
        rpc = [self._free_port(i) for i in (4, 5, 6)]

        servers = []
        for i in range(3):
            servers.append(AgentProc(
                "-server", "-wire-raft",
                "-name", f"fo{i}",
                "-bootstrap-expect", "3",
                "-data-dir", str(tmp_path / f"s{i}"),
                "-rpc-port", str(rpc[i]),
                "-serf-port", str(serf[i]),
                "-encrypt", key_a,
                "-retry-join", f"127.0.0.1:{serf[0]}",
                name=f"fo{i}",
            ))
        client = AgentProc(
            "-client", "-no-gossip",
            "-data-dir", str(tmp_path / "c0"),
            "-servers", ",".join(f"127.0.0.1:{p}" for p in rpc),
            name="fo-client",
        )
        try:
            apis = [s.api for s in servers]

            def leader_index():
                for i, api in enumerate(apis):
                    if servers[i].proc.poll() is not None:
                        continue
                    try:
                        if api.status.leader() not in ("", "unknown", None):
                            return i
                    except Exception:  # noqa: BLE001 — mid-election
                        continue
                return None

            wait_until(lambda: leader_index() is not None, timeout=180,
                       msg="initial leader elected")
            li = leader_index()
            follower = apis[(li + 1) % 3]

            # manual-ops mode: autopilot's dead-server cleanup would race
            # the explicit `operator raft remove-peer` exercised below
            apis[li].operator.autopilot_set_configuration(
                {"CleanupDeadServers": False})

            # the client node registers (through any server's HTTP -> RPC
            # forward to the leader)
            wait_until(lambda: any(
                n["Status"] == "ready"
                for n in (follower.nodes.list()[0] or [])),
                timeout=180, msg="client node ready")

            # workload phase 1: committed and placed before the kill
            follower.jobs.register(service_job("fo-pre", count=2,
                                               command="sleep 600"))
            wait_until(lambda: len(running_allocs(follower, "fo-pre")) == 2,
                       timeout=180, msg="pre-failover job running")

            # workload phase 2: registered through the DOOMED leader just
            # before SIGKILL — its evals are committed in raft but may be
            # un-processed; the NEW leader must restore and place them
            leader_api = apis[li]
            for k in range(4):
                leader_api.jobs.register(service_job(
                    f"fo-mid-{k}", count=2, command="sleep 600"))
            servers[li].kill_hard()

            wait_until(lambda: leader_index() is not None and
                       leader_index() != li,
                       timeout=180, msg="new leader elected after SIGKILL")
            survivor = apis[leader_index()]

            try:
                for k in range(4):
                    wait_until(
                        lambda k=k: len(running_allocs(survivor, f"fo-mid-{k}")) == 2,
                        timeout=240, msg=f"fo-mid-{k} placed by the new leader")
            except AssertionError:
                for k in range(4):
                    for a in allocs_of(survivor, f"fo-mid-{k}"):
                        print(f"fo-mid-{k}:", a["Name"], a["DesiredStatus"],
                              a["ClientStatus"])
                        if a["ClientStatus"] == "failed":
                            info, _ = survivor.allocations.info(a["ID"])
                            for task, st in (info.get("TaskStates") or {}).items():
                                for ev in st.get("Events") or []:
                                    print("   event:", task, ev.get("Type"),
                                          ev.get("DisplayMessage"),
                                          ev.get("DriverError", ""))
                evs, _ = survivor.evaluations.list()
                print("evals:", [(e["JobID"], e["Status"]) for e in evs or []])
                nodes, _ = survivor.nodes.list()
                print("nodes:", [(n["Name"], n["Status"]) for n in nodes or []])
                print("client log tail:", "".join(client.lines[-15:]))
                for i, s in enumerate(servers):
                    print(f"server fo{i} log tail:", "".join(s.lines[-10:]))
                raise

            # no alloc lost or doubled: each job holds EXACTLY its count of
            # run-desired allocs, with unique names
            for jid in ["fo-pre"] + [f"fo-mid-{k}" for k in range(4)]:
                allocs = [a for a in allocs_of(survivor, jid)
                          if a["DesiredStatus"] == "run"]
                names = [a["Name"] for a in allocs]
                assert len(names) == 2, (jid, names)
                assert len(set(names)) == 2, f"duplicate alloc names: {names}"

            # pre-failover allocs survived untouched (no reschedule storm)
            assert len(running_allocs(survivor, "fo-pre")) == 2

            # operator raft remove-peer evicts the corpse from the config
            # (autopilot cleanup disabled above, so it's still there)
            cfg, _ = survivor.operator.raft_get_configuration()
            dead = [s for s in cfg["Servers"] if s["ID"].startswith(f"fo{li}")]
            assert dead, cfg
            survivor.operator.raft_remove_peer(dead[0]["ID"])
            def peer_gone():
                c, _ = survivor.operator.raft_get_configuration()
                return all(not s["ID"].startswith(f"fo{li}")
                           for s in c["Servers"])
            wait_until(peer_gone, timeout=60, msg="dead peer removed")

            # keyring rotation UNDER LOAD: rotate while a job registers
            survivor.agent.keyring_op("install", key_b)
            survivor.jobs.register(service_job("fo-rotate", count=2,
                                               command="sleep 600"))
            survivor.agent.keyring_op("use", key_b)
            other = apis[(leader_index() + 1) % 3]
            if servers[(leader_index() + 1) % 3].proc.poll() is not None:
                other = apis[(leader_index() + 2) % 3]
            wait_until(lambda: key_b in other.agent.keyring_list()
                       ["PrimaryKeys"], timeout=60,
                       msg="rotation converged on the other survivor")
            survivor.agent.keyring_op("remove", key_a)
            wait_until(lambda: len(running_allocs(survivor, "fo-rotate")) == 2,
                       timeout=240, msg="job placed during rotation")
            # gossip still healthy across survivors after remove
            wait_until(lambda: sum(
                1 for m in survivor.agent.members()["Members"]
                if m["Status"] == "alive") >= 2, timeout=60,
                msg="survivors alive after rotation")
        finally:
            client.stop()
            for s in servers:
                s.stop()
