"""Config subsystem: KVS registry, env precedence, persistence, admin
API, dynamic apply.

Reference: internal/config/config.go:188-668,
cmd/admin-handlers-config-kv.go.
"""

import json
import os

import pytest

from minio_tpu.config import ConfigError, ServerConfig
from tests.s3_harness import S3TestServer

ADMIN = "/minio/admin/v3"


class TestResolution:
    def test_defaults(self):
        cfg = ServerConfig(environ={})
        assert cfg.get("scanner", "interval") == "60"
        assert cfg.get_int("heal", "interval", 0) == 3600
        assert cfg.get_bool("compression", "enable") is False

    def test_env_wins_over_stored(self):
        cfg = ServerConfig(environ={"MINIO_SCANNER_INTERVAL": "7"})
        cfg.set_kv("scanner", {"interval": "99"})
        assert cfg.get_int("scanner", "interval", 0) == 7
        assert cfg.merged()["scanner"]["interval"] == "7"

    def test_stored_wins_over_default(self):
        cfg = ServerConfig(environ={})
        cfg.set_kv("scanner", {"interval": "99"})
        assert cfg.get_int("scanner", "interval", 0) == 99

    def test_unknown_subsys_and_key(self):
        cfg = ServerConfig(environ={})
        with pytest.raises(ConfigError):
            cfg.set_kv("nope", {"a": "1"})
        with pytest.raises(ConfigError):
            cfg.set_kv("scanner", {"bogus_key": "1"})

    def test_del_resets_to_default(self):
        cfg = ServerConfig(environ={})
        cfg.set_kv("scanner", {"interval": "99"})
        cfg.del_kv("scanner", ["interval"])
        assert cfg.get("scanner", "interval") == "60"

    def test_dynamic_apply_callback(self):
        cfg = ServerConfig(environ={})
        seen = []
        cfg.on_change("scanner", lambda c: seen.append(
            c.get_int("scanner", "interval", 0)))
        cfg.set_kv("scanner", {"interval": "30"})
        assert seen == [30]

    def test_help(self):
        h = ServerConfig.help("scanner")
        assert any(kv["key"] == "interval" for kv in h["scanner"])
        assert "compression" in ServerConfig.help()


class TestPersistence:
    def test_round_trip_via_drives(self, tmp_path):
        from minio_tpu.erasure.sets import ErasureSets, ErasureServerPools
        from minio_tpu.storage.local import LocalStorage

        os.environ["MINIO_TPU_FSYNC"] = "0"
        disks = [LocalStorage(str(tmp_path / f"d{i}")) for i in range(4)]
        pools = ErasureServerPools([ErasureSets(disks)])
        cfg = ServerConfig(pools, environ={})
        cfg.set_kv("heal", {"interval": "123"})
        # a fresh instance over the same drives reads it back
        cfg2 = ServerConfig(pools, environ={})
        assert cfg2.get_int("heal", "interval", 0) == 123


@pytest.fixture(scope="module")
def srv(tmp_path_factory):
    os.environ["MINIO_TPU_FSYNC"] = "0"
    s = S3TestServer(str(tmp_path_factory.mktemp("cfg")),
                     start_services=True, scan_interval=3600.0)
    yield s
    s.close()


class TestAdminConfigAPI:
    def test_get_config(self, srv):
        r = srv.request("GET", f"{ADMIN}/get-config")
        assert r.status == 200
        cfg = json.loads(r.text())
        assert cfg["scanner"]["interval"]
        assert "compression" in cfg

    def test_set_and_del_kv(self, srv):
        r = srv.request("PUT", f"{ADMIN}/set-config-kv", data=json.dumps(
            {"subsys": "scanner", "kv": {"interval": "42"}}).encode())
        assert r.status == 200
        assert json.loads(r.text())["restart"] is False
        cfg = json.loads(srv.request("GET", f"{ADMIN}/get-config").text())
        assert cfg["scanner"]["interval"] == "42"
        # dynamic apply reached the running scanner
        assert srv.server.services.scanner.interval == 42
        r = srv.request("DELETE", f"{ADMIN}/del-config-kv",
                        query=[("subsys", "scanner"),
                               ("keys", "interval")])
        assert r.status == 200
        cfg = json.loads(srv.request("GET", f"{ADMIN}/get-config").text())
        assert cfg["scanner"]["interval"] == "60"

    def test_secret_redaction(self, srv):
        srv.request("PUT", f"{ADMIN}/set-config-kv", data=json.dumps(
            {"subsys": "audit_webhook",
             "kv": {"auth_token": "supersecret"}}).encode())
        cfg = json.loads(srv.request("GET", f"{ADMIN}/get-config").text())
        assert cfg["audit_webhook"]["auth_token"] == "*REDACTED*"

    def test_bad_input(self, srv):
        assert srv.request("PUT", f"{ADMIN}/set-config-kv",
                           data=b"not json").status == 400
        r = srv.request("PUT", f"{ADMIN}/set-config-kv", data=json.dumps(
            {"subsys": "scanner", "kv": {"nope": "1"}}).encode())
        assert r.status == 400

    def test_help_endpoint(self, srv):
        r = srv.request("GET", f"{ADMIN}/help-config-kv",
                        query=[("subsys", "heal")])
        assert r.status == 200
        assert any(kv["key"] == "interval"
                   for kv in json.loads(r.text())["heal"])

    def test_requires_admin(self, srv):
        assert srv.raw_request("GET", f"{ADMIN}/get-config").status == 403


class TestStartupApply:
    def test_cli_interval_not_stomped_by_defaults(self, tmp_path):
        """A server started with an explicit scan interval keeps it: the
        config registry's default must not override CLI/env choices at
        startup (regression: live scanner silently ran at 60s)."""
        os.environ["MINIO_TPU_FSYNC"] = "0"
        s = S3TestServer(str(tmp_path / "ia"), start_services=True,
                         scan_interval=1.5)
        try:
            assert s.server.services.scanner.interval == 1.5
        finally:
            s.close()

    def test_persisted_interval_applies_at_startup(self, tmp_path):
        from minio_tpu.erasure.sets import ErasureSets, ErasureServerPools
        from minio_tpu.storage.local import LocalStorage

        os.environ["MINIO_TPU_FSYNC"] = "0"
        root = str(tmp_path / "pa")
        s = S3TestServer(root, start_services=True, scan_interval=1.5)
        r = s.request("PUT", f"{ADMIN}/set-config-kv", data=json.dumps(
            {"subsys": "scanner", "kv": {"interval": "7"}}).encode())
        assert r.status == 200
        assert s.server.services.scanner.interval == 7
        s.close()
        # restart over the same drives: stored value is explicit -> applies
        s2 = S3TestServer(root, start_services=True, scan_interval=1.5)
        try:
            assert s2.server.services.scanner.interval == 7
        finally:
            s2.close()


def test_every_gate_is_listed():
    """The `MINIO_TPU_*` names the package reads are the names of the
    README's table, both ways: a gate cannot arrive unlisted, a row
    cannot outlive its gate, and the count (ROADMAP D1) only goes down."""
    import re

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    name = re.compile(r"MINIO_TPU_[A-Z0-9_]+")
    read = set()
    for dirpath, _dirs, files in os.walk(os.path.join(repo, "minio_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(dirpath, f), encoding="utf-8") as fh:
                    read |= set(name.findall(fh.read()))
    with open(os.path.join(repo, "README.md"), encoding="utf-8") as fh:
        readme = fh.read()
    start = readme.index("## Environment variables (`MINIO_TPU_*`)")
    table = readme[start:readme.index("\n## ", start + 1)]
    listed = [name.search(line).group(0) for line in table.splitlines()
              if line.startswith("| `")]
    assert len(listed) == len(set(listed)), "a row is there twice"
    assert set(listed) == read, (
        f"read and not listed: {sorted(read - set(listed))}; "
        f"listed and not read: {sorted(set(listed) - read)}")
    assert len(read) <= 104, f"{len(read)} gates: ROADMAP D1 counts down"
