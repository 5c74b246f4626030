"""Wire raft tests: election, replication, recovery, snapshot install.

Covers the consensus slot (reference vendored hashicorp/raft,
nomad/server.go:1079): multi-node clusters over real loopback TCP — the
reference's in-process multi-server strategy (nomad/testing.go joining N
TestServers, SURVEY §4.2).
"""
import dataclasses
import shutil
import tempfile
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.rpc.transport import RPCServer
from nomad_tpu.server.fsm import JOB_REGISTER, NODE_REGISTER, NomadFSM
from nomad_tpu.server.raft import NotLeaderError
from nomad_tpu.server.wire_raft import LEADER, WireRaft, WireRaftConfig


def fast_config(node_id: str) -> WireRaftConfig:
    return WireRaftConfig(
        node_id=node_id,
        election_timeout_min=0.15,
        election_timeout_max=0.3,
        heartbeat_interval=0.03,
        rpc_timeout=0.5,
        apply_timeout=5.0,
    )


def wait_until(fn, timeout=8.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fn():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


class Node:
    """One raft participant with its own RPC endpoint and FSM."""

    def __init__(self, node_id: str, data_dir=None):
        self.node_id = node_id
        self.rpc = RPCServer()
        self.fsm = NomadFSM()
        self.data_dir = data_dir
        self.raft = None

    def wire(self, all_nodes, start=True):
        peers = {
            n.node_id: n.rpc.addr for n in all_nodes if n.node_id != self.node_id
        }
        self.raft = WireRaft(
            self.rpc, peers, fast_config(self.node_id), data_dir=self.data_dir
        )
        self.raft.join(self.fsm)
        self.rpc.start()
        if start:
            self.raft.start()
        return self

    def stop(self):
        if self.raft is not None:
            self.raft.close()
        self.rpc.stop()


@pytest.fixture
def cluster():
    nodes = []

    def make(n, data_dirs=None, defer=()):
        for i in range(n):
            nodes.append(Node(f"n{i}", data_dirs[i] if data_dirs else None))
        for node in nodes:
            node.wire(nodes, start=node.node_id not in defer)
        return nodes

    yield make
    for node in nodes:
        node.stop()


def leader_of(nodes):
    leaders = [n for n in nodes if n.raft.state == LEADER]
    return leaders[0] if len(leaders) == 1 else None


class TestWireRaft:
    def test_single_leader_elected(self, cluster):
        nodes = cluster(3)
        wait_until(lambda: leader_of(nodes) is not None, msg="leader election")
        leader = leader_of(nodes)
        # followers agree on who leads
        wait_until(
            lambda: all(
                n.raft.leader_id == leader.node_id for n in nodes
            ),
            msg="leader agreement",
        )

    def test_replication_to_all_fsms(self, cluster):
        nodes = cluster(3)
        wait_until(lambda: leader_of(nodes) is not None)
        leader = leader_of(nodes)
        node = mock.node()
        index, _ = leader.raft.apply(0, NODE_REGISTER, node)
        assert index > 0
        wait_until(
            lambda: all(
                n.fsm.state.node_by_id(node.id) is not None for n in nodes
            ),
            msg="replication to all FSMs",
        )

    def test_follower_rejects_apply(self, cluster):
        nodes = cluster(3)
        wait_until(lambda: leader_of(nodes) is not None)
        follower = next(n for n in nodes if n.raft.state != LEADER)
        with pytest.raises(NotLeaderError):
            follower.raft.apply(0, NODE_REGISTER, mock.node())

    def test_leader_failover(self, cluster):
        nodes = cluster(3)
        wait_until(lambda: leader_of(nodes) is not None)
        leader = leader_of(nodes)
        n1 = mock.node()
        leader.raft.apply(0, NODE_REGISTER, n1)

        leader.stop()
        rest = [n for n in nodes if n is not leader]
        wait_until(lambda: leader_of(rest) is not None, msg="re-election")
        new_leader = leader_of(rest)
        assert new_leader is not leader
        # old entry survived, new applies work
        assert new_leader.fsm.state.node_by_id(n1.id) is not None
        n2 = mock.node()
        new_leader.raft.apply(0, NODE_REGISTER, n2)
        wait_until(
            lambda: all(
                n.fsm.state.node_by_id(n2.id) is not None for n in rest
            ),
            msg="post-failover replication",
        )

    def test_late_follower_catches_up(self, cluster):
        nodes = cluster(3, defer=("n2",))
        active = nodes[:2]
        late = nodes[2]
        wait_until(lambda: leader_of(active) is not None)
        leader = leader_of(active)
        registered = [mock.node() for _ in range(5)]
        for n in registered:
            leader.raft.apply(0, NODE_REGISTER, n)
        # now the laggard starts participating
        late.raft.start()
        wait_until(
            lambda: all(
                late.fsm.state.node_by_id(n.id) is not None for n in registered
            ),
            msg="late follower catch-up",
        )

    def test_snapshot_install_for_compacted_follower(self, cluster):
        nodes = cluster(3, defer=("n2",))
        active = nodes[:2]
        late = nodes[2]
        wait_until(lambda: leader_of(active) is not None)
        leader = leader_of(active)
        registered = [mock.node() for _ in range(5)]
        for n in registered:
            leader.raft.apply(0, NODE_REGISTER, n)
        job = mock.job()
        leader.raft.apply(0, JOB_REGISTER, job)
        # compact the leader's log so the laggard can't be served entries
        snap_index = leader.raft.snapshot(0)
        assert snap_index > 0
        assert leader.raft._entries_from(1) is None
        late.raft.start()
        wait_until(
            lambda: late.fsm.state.job_by_id("default", job.id) is not None
            and all(late.fsm.state.node_by_id(n.id) is not None for n in registered),
            msg="snapshot install",
        )

    def test_snapshot_blob_is_codec_not_pickle(self):
        """InstallSnapshot ships msgpack through the typed codec — never
        pickle, which would hand code execution to any peer reaching the
        RPC port (ADVICE r1). Round-trips every state table including ACL
        and autopilot entries."""
        import pickle

        from nomad_tpu.server import wire_raft as wr
        from nomad_tpu.state import StateStore
        from nomad_tpu.structs.acl import ACLPolicy, ACLToken

        store = StateStore()
        n = mock.node()
        store.upsert_node(1, n)
        j = mock.job()
        store.upsert_job(2, j)
        store.upsert_acl_policies(3, [ACLPolicy(
            name="readonly", rules='namespace "default" { policy = "read" }'
        )])
        tok = ACLToken(name="t", type="client", policies=["readonly"])
        store.upsert_acl_tokens(4, [tok])

        blob = wr._encode_fsm_state(store.snapshot())
        # a pickle payload must NOT be interpretable by the decode path
        with pytest.raises(Exception):
            wr._decode_fsm_state(pickle.dumps({"__reduce__": "nope"}))

        restored = wr._decode_fsm_state(blob)
        assert restored.node_by_id(n.id).name == n.name
        assert restored.job_by_id("default", j.id).id == j.id
        assert restored.acl_policies_table["readonly"].rules
        assert restored.acl_token_by_accessor(tok.accessor_id).name == "t"
        assert restored.latest_index == store.latest_index
        # pickle survives only in the legacy local-disk fallback — never
        # on any path that touches wire bytes
        import inspect

        for fn in (wr._encode_fsm_state, wr._decode_fsm_state,
                   wr.WireRaft._handle_install_snapshot,
                   wr.WireRaft._handle_append_entries,
                   wr.WireRaft._append_locked,
                   wr.WireRaft.snapshot):
            src = inspect.getsource(fn)
            for needle in ("import pickle", "pickle.loads", "pickle.dumps"):
                assert needle not in src, f"{fn.__name__}: {needle}"

    def test_restart_recovers_from_disk(self):
        tmp = tempfile.mkdtemp(prefix="wire-raft-")
        try:
            node = Node("solo", data_dir=tmp).wire([])
            wait_until(lambda: node.raft.state == LEADER, msg="solo leader")
            registered = [mock.node() for _ in range(3)]
            for n in registered:
                node.raft.apply(0, NODE_REGISTER, n)
            term_before = node.raft.current_term
            node.stop()

            node2 = Node("solo", data_dir=tmp).wire([])
            wait_until(lambda: node2.raft.state == LEADER, msg="solo re-leader")
            assert node2.raft.current_term >= term_before
            for n in registered:
                assert node2.fsm.state.node_by_id(n.id) is not None, "log replay"
            node2.stop()
        finally:
            shutil.rmtree(tmp, ignore_errors=True)


@pytest.mark.parametrize("path", ["jsonapi", "fsm_snapshot"])
def test_stored_scheduler_config_drops_the_removed_fields(path):
    """A SchedulerConfiguration written before the chunked tier went still
    carries chunk_k and parity_sample_rate: both decoders drop them and
    keep the rest."""
    from nomad_tpu.structs.structs import (
        PreemptionConfig,
        SchedulerConfiguration,
    )

    if path == "jsonapi":
        from nomad_tpu.agent import jsonapi

        cfg = jsonapi.from_json_obj(SchedulerConfiguration, {
            "SchedulerAlgorithm": "binpack", "ChunkK": 256,
            "ParitySampleRate": 0.25,
            "PreemptionConfig": {"BatchSchedulerEnabled": True},
        })
    else:
        import msgpack

        from nomad_tpu.server import wire_raft as wr
        from nomad_tpu.state import StateStore

        store = StateStore()
        store.scheduler_set_config(5, SchedulerConfiguration(
            scheduler_algorithm="binpack",
            preemption_config=PreemptionConfig(batch_scheduler_enabled=True),
        ))
        wire = msgpack.unpackb(wr._encode_fsm_state(store.snapshot()),
                               raw=False, strict_map_key=False)
        wire["scheduler_config_entry"].update(
            chunk_k=256, parity_sample_rate=0.25)
        fsm = NomadFSM()
        fsm.restore(wr._decode_fsm_state(
            msgpack.packb(wire, use_bin_type=True)))
        index, cfg = fsm.state.scheduler_config()
        assert index == 5
    assert cfg.scheduler_algorithm == "binpack"
    assert cfg.preemption_config.batch_scheduler_enabled
    assert not hasattr(cfg, "chunk_k")
    assert not hasattr(cfg, "parity_sample_rate")
    cfg.validate()


class TestServerOnWireRaft:
    def test_three_servers_schedule_and_replicate(self):
        """Three Server processes-worth of runtime on wire raft: writes on
        the leader replicate; the leader's scheduler places allocs; the
        follower FSMs see them (reference: FSM on every server,
        fsm.go:173)."""
        from nomad_tpu.server.server import Server, ServerConfig

        rpcs = [RPCServer() for _ in range(3)]
        rafts = []
        for i, rpc in enumerate(rpcs):
            peers = {
                f"s{j}": rpcs[j].addr for j in range(3) if j != i
            }
            # three Servers' threads share one GIL (and, under xdist, the
            # cores): a follower that hears no heartbeat for 0.15-0.3 s
            # there is starved, not partitioned, and its election unseats
            # the leader mid-apply. Elections here wait 1-2 s.
            rafts.append(WireRaft(rpc, peers, dataclasses.replace(
                fast_config(f"s{i}"),
                election_timeout_min=1.0, election_timeout_max=2.0,
            )))
        servers = [
            Server(ServerConfig(num_schedulers=1, deterministic=True),
                   raft=rafts[i], name=f"s{i}")
            for i in range(3)
        ]
        try:
            for rpc in rpcs:
                rpc.start()
            for s in servers:
                s.start()
            for r in rafts:
                r.start()
            # a leader that stands: its first write (the seeded scheduler
            # configuration) is committed and on every replica
            wait_until(
                lambda: sum(1 for r in rafts if r.state == LEADER) == 1
                and all(s.fsm.state.scheduler_config()[1] is not None
                        for s in servers),
                timeout=30,
                msg="a leader whose first write reached every replica",
            )
            leader = next(s for s, r in zip(servers, rafts) if r.state == LEADER)
            followers = [s for s in servers if s is not leader]

            leader.register_node(mock.node())
            leader.register_node(mock.node())
            job = mock.job()
            leader.register_job(job)
            wait_until(
                lambda: len(leader.fsm.state.allocs_by_job("default", job.id, True)) == 10,
                timeout=30,
                msg="placement on leader",
            )
            wait_until(
                lambda: all(
                    len(f.fsm.state.allocs_by_job("default", job.id, True)) == 10
                    for f in followers
                ),
                timeout=30,
                msg="alloc replication to followers",
            )
        finally:
            for s in servers:
                s.stop()
            for r in rafts:
                r.close()
            for rpc in rpcs:
                rpc.stop()


class TestAgentsOnWireRaft:
    def test_three_agent_cluster_bootstrap_and_write(self):
        """Three full agents with gossip + wire raft: membership converges,
        raft bootstraps at expect=3, exactly one leader emerges, and a
        write through any agent's RPC lands on every FSM."""
        from nomad_tpu.agent.agent import Agent, AgentConfig
        from nomad_tpu.rpc.transport import RPCClient
        from nomad_tpu.server.wire_raft import WireRaftConfig

        agents = []
        try:
            for i in range(3):
                cfg = AgentConfig(
                    name=f"a{i}", server_enabled=True, wire_raft=True,
                    bootstrap_expect=3, num_schedulers=0,
                )
                a = Agent(cfg)
                # speed up elections for the test
                a.wire_raft.config = WireRaftConfig(
                    node_id=a.wire_raft.node_id,
                    election_timeout_min=0.15, election_timeout_max=0.3,
                    heartbeat_interval=0.03, rpc_timeout=0.5,
                )
                agents.append(a)
            agents[0].start()
            seed = "{}:{}".format(*agents[0].membership.gossip_addr)
            for a in agents[1:]:
                a.config.retry_join = [seed]
                a.start()
            wait_until(
                lambda: all(a._raft_started for a in agents),
                msg="raft bootstrap at expect=3",
            )
            wait_until(
                lambda: sum(1 for a in agents if a.server.is_leader) == 1,
                msg="single leader among agents",
            )
            # gossip leader tag → follower forwarding works
            leader = next(a for a in agents if a.server.is_leader)
            follower = next(a for a in agents if not a.server.is_leader)
            wait_until(
                lambda: follower.rpc.leader_addr == leader.rpc.addr,
                msg="leader tag propagated",
            )
            node = mock.node()
            cli = RPCClient(*follower.rpc.addr)
            cli.call("Node.Register", node)
            wait_until(
                lambda: all(
                    a.server.fsm.state.node_by_id(node.id) is not None
                    for a in agents
                ),
                msg="write replicated to every agent FSM",
            )
            cli.close()
        finally:
            for a in agents:
                a.shutdown()


class TestReplicatedPeerRemoval:
    def test_remove_peer_replicated_shrinks_all_views(self, cluster):
        """Autopilot-style removal goes through the log: every replica's
        peer set shrinks, not just the leader's."""
        nodes = cluster(3)
        wait_until(lambda: leader_of(nodes) is not None)
        leader = leader_of(nodes)
        followers = [n for n in nodes if n is not leader]
        victim = followers[0]
        victim.stop()
        leader.raft.remove_peer_replicated(victim.node_id)
        survivor = followers[1]
        wait_until(
            lambda: victim.node_id not in leader.raft.peers
            and victim.node_id not in survivor.raft.peers,
            msg="peer removed on every replica",
        )
        # the shrunken cluster still commits
        n = mock.node()
        leader.raft.apply(0, NODE_REGISTER, n)
        wait_until(lambda: survivor.fsm.state.node_by_id(n.id) is not None,
                   msg="post-removal commit")


class TestStagedMembership:
    """Log-replicated peer ADDITION (the reference gets staged
    nonvoter->voter configuration changes from vendored hashicorp/raft,
    used at leader.go:859): adds commit through the log, so every
    replica grows its configuration at the same position and a minority
    partition can never grow its own voter set."""

    @staticmethod
    def _sever(node, peer_id):
        """Cut node's OUTBOUND RPC to peer_id; returns a restore fn."""
        from nomad_tpu.rpc.transport import RPCError

        orig = node.raft._client

        def gated(pid, _orig=orig):
            if pid == peer_id:
                raise RPCError("partitioned")
            return _orig(pid)

        node.raft._client = gated
        return lambda: setattr(node.raft, "_client", orig)

    def test_staged_add_promotes_to_voter(self, cluster):
        nodes = cluster(3)
        wait_until(lambda: leader_of(nodes) is not None, msg="leader")
        leader = leader_of(nodes)
        leader.raft.apply(0, NODE_REGISTER, mock.node())

        # a fourth server appears (gossip handed it the current peer map)
        d = Node("n3")
        nodes.append(d)  # fixture cleanup
        d.wire(nodes[:3] + [d])
        assert leader.raft.add_peer_staged("n3", d.rpc.addr)

        # every replica (the new one included) converges on a 4-server
        # VOTER configuration
        wait_until(
            lambda: all(
                len(n.raft.peers) == 3
                and not n.raft.nonvoters
                and not n.raft._self_nonvoter
                for n in nodes
            ),
            timeout=12, msg="staged add promoted everywhere",
        )
        # the new voter has the replicated state
        wait_until(lambda: len(d.fsm.state.nodes()) == 1, msg="catch-up")

    def test_add_during_partition_heals_to_single_config(self, cluster):
        nodes = cluster(3)
        wait_until(lambda: leader_of(nodes) is not None, msg="leader")
        leader = leader_of(nodes)
        victim = next(n for n in nodes if n.raft.state != LEADER)
        others = [n for n in nodes if n is not victim]

        # full partition: victim <-/-> {others}
        restores = []
        for other in others:
            restores.append(self._sever(other, victim.node_id))
            restores.append(self._sever(victim, other.node_id))

        # add a fourth server while partitioned: commits on the majority
        d = Node("n3")
        nodes.append(d)
        d.wire(nodes[:3] + [d])
        restores.append(self._sever(victim, "n3"))
        restores.append(self._sever(d, victim.node_id))
        assert leader.raft.add_peer_staged("n3", d.rpc.addr)
        majority = others + [d]
        wait_until(
            lambda: all(
                "n3" in (set(n.raft.peers) | {n.node_id})
                and not n.raft.nonvoters
                for n in majority
            ),
            timeout=12, msg="add committed on the majority side",
        )
        # the minority never learned the add, and CANNOT stage one itself
        assert "n3" not in victim.raft.peers
        assert victim.raft.add_peer_staged("n3", d.rpc.addr) is False
        assert "n3" not in victim.raft.peers

        # heal: the victim converges onto the SAME single configuration
        for restore in restores:
            restore()
        wait_until(
            lambda: set(victim.raft.peers) | {victim.node_id}
            == {"n0", "n1", "n2", "n3"}
            and not victim.raft.nonvoters,
            timeout=12, msg="healed minority adopts the replicated config",
        )
        # exactly one leader across the healed 4-voter cluster, and writes
        # replicate everywhere (no split quorum)
        wait_until(lambda: leader_of(nodes) is not None, timeout=12,
                   msg="single leader after heal")
        final_leader = leader_of(nodes)
        marker = mock.node()
        final_leader.raft.apply(0, NODE_REGISTER, marker)
        wait_until(
            lambda: all(
                n.fsm.state.node_by_id(marker.id) is not None for n in nodes
            ),
            timeout=12, msg="post-heal replication to all four",
        )

    def test_snapshot_carries_membership_config(self, cluster):
        """A follower caught up via InstallSnapshot past compacted
        PEER_ADD entries must still learn the added peer — membership
        rides the snapshot (hashicorp/raft keeps config in snapshot
        meta)."""
        nodes = cluster(3)
        wait_until(lambda: leader_of(nodes) is not None, msg="leader")
        leader = leader_of(nodes)
        victim = next(n for n in nodes if n.raft.state != LEADER)
        others = [n for n in nodes if n is not victim]

        restores = []
        for other in others:
            restores.append(self._sever(other, victim.node_id))
            restores.append(self._sever(victim, other.node_id))

        # add + promote a fourth server while the victim is partitioned
        d = Node("n3")
        nodes.append(d)
        d.wire(nodes[:3] + [d])
        restores.append(self._sever(victim, "n3"))
        restores.append(self._sever(d, victim.node_id))
        assert leader.raft.add_peer_staged("n3", d.rpc.addr)
        majority = others + [d]
        wait_until(
            lambda: all(not n.raft.nonvoters and "n3" in
                        (set(n.raft.peers) | {n.node_id}) for n in majority),
            timeout=12, msg="staged add committed+promoted",
        )
        for _ in range(3):
            leader2 = leader_of(majority)
            leader2.raft.apply(0, NODE_REGISTER, mock.node())
        # compact: the PEER_ADD entries disappear from the log
        leader2 = leader_of(majority)
        assert leader2.raft.snapshot() > 0
        leader2.raft.apply(0, NODE_REGISTER, mock.node())

        for restore in restores:
            restore()
        # the victim catches up via InstallSnapshot and STILL learns n3
        wait_until(
            lambda: "n3" in victim.raft.peers and not victim.raft.nonvoters,
            timeout=12, msg="snapshot-installed config includes the add",
        )
        wait_until(
            lambda: len(victim.fsm.state.nodes()) == len(leader2.fsm.state.nodes()),
            timeout=12, msg="victim state caught up",
        )
