"""Agent config files: HCL/JSON load, merge, precedence, agent boot
(reference command/agent/config.go + config_parse.go + their tests)."""

import json

import pytest

from nomad_tpu.agent import Agent, AgentConfig
from nomad_tpu.agent.config_file import (
    ConfigError,
    apply_file_config,
    load_agent_config,
    load_config_sources,
    merge_config,
)

HCL = """
region     = "euw"
datacenter = "dc7"
name       = "cfg-agent"
bind_addr  = "127.0.0.1"

ports {
  http = 0
  rpc  = 0
}

server {
  enabled          = true
  bootstrap_expect = 1
  num_schedulers   = 3
  default_scheduler_config {
    scheduler_algorithm = "binpack"
  }
}

client {
  enabled    = true
  node_class = "compute"
  meta {
    team = "infra"
  }
  host_volume "data" {
    path = "/srv/data"
  }
}

telemetry {
  statsd_address = "127.0.0.1:8125"
  prefix         = "np"
}
"""


def test_hcl_file_maps_reference_keys(tmp_path):
    f = tmp_path / "agent.hcl"
    f.write_text(HCL)
    cfg = load_agent_config([str(f)])
    assert cfg.region == "euw"
    assert cfg.datacenter == "dc7"
    assert cfg.name == "cfg-agent"
    assert cfg.server_enabled and cfg.client_enabled
    assert cfg.num_schedulers == 3
    assert cfg.scheduler_algorithm == "binpack"
    assert cfg.node_class == "compute"
    assert cfg.meta == {"team": "infra"}
    assert cfg.host_volumes == {"data": "/srv/data"}
    assert cfg.telemetry_statsd_address == "127.0.0.1:8125"
    assert cfg.telemetry_prefix == "np"


ALG_FILE = """
server {
  enabled = true
  default_scheduler_config {
    scheduler_algorithm = "%s"
  }
}
"""


@pytest.fixture(scope="module")
def alg_agent():
    a = Agent(AgentConfig(dev_mode=True, num_schedulers=0,
                          scheduler_algorithm="binpack", name="alg-dev"))
    a.start()
    yield a
    a.shutdown()


def _set_algorithm(agent, boundary, alg):
    """Write ``alg`` through one of the two run-time boundaries."""
    if boundary == "rpc":
        from nomad_tpu.rpc.transport import RPCClient
        from nomad_tpu.structs.structs import SchedulerConfiguration

        c = RPCClient(*agent.rpc.addr)
        try:
            return c.call("Operator.SchedulerSetConfiguration",
                          SchedulerConfiguration(scheduler_algorithm=alg))
        finally:
            c.close()
    import urllib.request

    req = urllib.request.Request(
        agent.http_addr + "/v1/operator/scheduler/configuration",
        data=json.dumps({"SchedulerAlgorithm": alg}).encode(), method="PUT")
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read().decode())


@pytest.mark.parametrize("alg", ["binpack", "tpu_binpack"])
def test_scheduler_algorithm_accepted(alg, tmp_path, alg_agent):
    """Both algorithms pass every boundary and reach the stored
    configuration: file -> AgentConfig, ServerConfig -> the leader-seeded
    SchedulerConfiguration, and the RPC and HTTP writes."""
    from nomad_tpu.server.server import Server, ServerConfig

    f = tmp_path / "agent.hcl"
    f.write_text(ALG_FILE % alg)
    cfg = load_agent_config([str(f)])
    assert cfg.scheduler_algorithm == alg

    srv = Server(ServerConfig(scheduler_algorithm=cfg.scheduler_algorithm,
                              num_schedulers=0))
    try:
        srv.start()
        assert srv.fsm.state.scheduler_config()[1].scheduler_algorithm == alg
    finally:
        srv.stop()

    state = alg_agent.server.fsm.state
    for boundary in ("rpc", "http"):
        other = "binpack" if alg == "tpu_binpack" else "tpu_binpack"
        _set_algorithm(alg_agent, boundary, other)
        _set_algorithm(alg_agent, boundary, alg)
        assert state.scheduler_config()[1].scheduler_algorithm == alg


@pytest.mark.parametrize("alg", ["tpu_binpack_chunked", "", "spread"])
@pytest.mark.parametrize("boundary", ["file", "rpc", "http"])
def test_scheduler_algorithm_refused(boundary, alg, tmp_path, alg_agent):
    """A removed or unknown algorithm is refused where it enters, with an
    error that names the accepted values, and the stored configuration
    stays as it was (any other string would send every eval to the host
    stack in silence)."""
    accepted = "binpack, tpu_binpack"
    if boundary == "file":
        from nomad_tpu.server.server import Server, ServerConfig

        f = tmp_path / "agent.hcl"
        f.write_text(ALG_FILE % alg)
        with pytest.raises(ConfigError, match=accepted):
            load_agent_config([str(f)])
        with pytest.raises(ValueError, match=accepted):
            Server(ServerConfig(scheduler_algorithm=alg, num_schedulers=0))
        return
    state = alg_agent.server.fsm.state
    before_index, before = state.scheduler_config()
    before_alg = before.scheduler_algorithm
    if boundary == "rpc":
        from nomad_tpu.rpc.transport import RPCError

        with pytest.raises(RPCError, match=accepted):
            _set_algorithm(alg_agent, "rpc", alg)
    else:
        import urllib.error

        with pytest.raises(urllib.error.HTTPError) as exc:
            _set_algorithm(alg_agent, "http", alg)
        assert exc.value.code == 400
        assert accepted in exc.value.read().decode()
    index, after = state.scheduler_config()
    assert (index, after.scheduler_algorithm) == (before_index, before_alg)


def test_removed_tier_knob_is_an_unknown_key(tmp_path):
    """default_scheduler_config is checked like every other block: the
    removed tier's knob fails as a misspelt key does."""
    f = tmp_path / "agent.hcl"
    f.write_text(
        'server {\n  default_scheduler_config {\n    chunk_k = 256\n  }\n}\n')
    with pytest.raises(ConfigError, match="chunk_k"):
        load_agent_config([str(f)])


def test_json_file_and_directory_merge_order(tmp_path):
    d = tmp_path / "conf.d"
    d.mkdir()
    (d / "00-base.json").write_text(json.dumps({
        "region": "us", "ports": {"http": 1111, "rpc": 2222},
        "server": {"enabled": True, "num_schedulers": 1},
    }))
    (d / "10-override.hcl").write_text(
        'ports { http = 3333 }\nserver { num_schedulers = 5 }\n'
    )
    data = load_config_sources([str(d)])
    # later files merge over earlier, key-by-key (objects deep-merge)
    assert data["ports"] == {"http": 3333, "rpc": 2222}
    assert data["server"] == {"enabled": True, "num_schedulers": 5}
    assert data["region"] == "us"

    cfg = load_agent_config([str(d)])
    assert cfg.http_port == 3333 and cfg.rpc_port == 2222
    assert cfg.num_schedulers == 5


def test_unknown_keys_fail_loudly(tmp_path):
    f = tmp_path / "bad.hcl"
    f.write_text('regon = "typo"\n')
    with pytest.raises(ConfigError, match="regon"):
        load_agent_config([str(f)])
    f2 = tmp_path / "bad2.hcl"
    f2.write_text('server { bootstrap_expct = 3 }\n')
    with pytest.raises(ConfigError, match="bootstrap_expct"):
        load_agent_config([str(f2)])


def test_missing_path_and_bad_volume(tmp_path):
    with pytest.raises(ConfigError, match="does not exist"):
        load_config_sources([str(tmp_path / "nope.hcl")])
    f = tmp_path / "vol.hcl"
    f.write_text('client { host_volume "x" { } }\n')
    with pytest.raises(ConfigError, match="path"):
        load_agent_config([str(f)])


def test_merge_scalars_replace_objects_merge():
    out = merge_config(
        {"a": 1, "o": {"x": 1, "y": 2}, "l": [1, 2]},
        {"a": 9, "o": {"y": 3}, "l": [7]},
    )
    assert out == {"a": 9, "o": {"x": 1, "y": 3}, "l": [7]}


def test_apply_does_not_mutate_base():
    base = AgentConfig()
    cfg = apply_file_config(base, {"region": "apac"})
    assert cfg.region == "apac" and base.region == "global"


def test_agent_boots_from_config_file(tmp_path):
    """The e2e shape: write a file, boot a real agent from it, observe
    the configured identity through the HTTP API."""
    vol = tmp_path / "data"
    vol.mkdir()
    f = tmp_path / "boot.hcl"
    f.write_text(HCL.replace("/srv/data", str(vol)))
    cfg = load_agent_config([str(f)])
    cfg.dev_mode = True  # in-proc raft; ephemeral ports already set
    a = Agent(cfg)
    a.start()
    try:
        from nomad_tpu.api import Client, Config

        api = Client(Config(address=a.http_addr))
        info = api.agent.self()
        assert info["config"]["Region"] == "euw"
        assert info["config"]["Datacenter"] == "dc7"
        assert info["member"]["Name"].startswith("cfg-agent")
        # client node registered with file-configured class + meta
        nodes, _ = api.nodes.list()
        assert nodes and nodes[0]["NodeClass"] == "compute"
    finally:
        a.shutdown()


def test_cli_flags_override_file(tmp_path):
    """defaults < files < flags, via the real CLI path."""
    from nomad_tpu.cli.main import main as cli_main

    f = tmp_path / "agent.hcl"
    f.write_text('region = "filereg"\ndatacenter = "filedc"\n')
    # exercise only the config-assembly path: patch Agent.start via a
    # sentinel agent that records its config and exits immediately
    captured = {}

    class FakeAgent:
        def __init__(self, cfg):
            captured["cfg"] = cfg
            self.http_addr = "http://x"
            self.client = None
            self.server = None

        def start(self):
            raise KeyboardInterrupt  # unwind out of the serve loop

        def shutdown(self):
            pass

    import nomad_tpu.agent as agent_pkg

    orig = agent_pkg.Agent
    agent_pkg.Agent = FakeAgent
    try:
        try:
            cli_main([
                "agent", "-config", str(f), "-dc", "flagdc", "-dev",
            ], out=lambda s: None)
        except KeyboardInterrupt:
            pass
    finally:
        agent_pkg.Agent = orig
    cfg = captured["cfg"]
    assert cfg.region == "filereg"  # from file
    assert cfg.datacenter == "flagdc"  # flag wins over file
    assert cfg.dev_mode
