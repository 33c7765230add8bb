"""SQLite backend specifics: migrations, durability, network isolation.
(The full Manager contract suite in test_store.py already runs against this
backend via the parametrized `store` fixture.)"""

import pytest

from keto_tpu.namespace import MemoryNamespaceManager
from keto_tpu.persistence import SQLiteTupleStore
from keto_tpu.relationtuple import RelationQuery, RelationTuple, SubjectID


@pytest.fixture
def nsmgr():
    m = MemoryNamespaceManager()
    m.add("n")
    return m


def t(s):
    return RelationTuple.from_string(s)


class TestMigrations:
    def test_fresh_db_migrates_up(self, tmp_path, nsmgr):
        s = SQLiteTupleStore(str(tmp_path / "m.db"), namespace_manager=nsmgr)
        status = s.migrator.status()
        assert len(status) >= 2
        assert all(m.applied for m in status)
        assert not s.migrator.has_pending()
        s.close()

    def test_status_before_migrate(self, tmp_path, nsmgr):
        s = SQLiteTupleStore(
            str(tmp_path / "m.db"), namespace_manager=nsmgr, auto_migrate=False
        )
        assert s.migrator.has_pending()
        assert all(not m.applied for m in s.migrator.status())
        ran = s.migrator.up()
        assert len(ran) >= 2
        assert not s.migrator.has_pending()
        s.close()

    def test_failing_migration_rolls_back_completely(self, tmp_path):
        """A failing multi-statement migration must leave no partial DDL and
        no version row (executescript would have committed implicitly)."""
        import sqlite3

        from keto_tpu.persistence.migrator import Migrator

        mdir = tmp_path / "migrations"
        mdir.mkdir()
        (mdir / "001_bad.up.sql").write_text(
            "CREATE TABLE good_one (id INTEGER PRIMARY KEY);\n"
            "CREATE TABLE bad one (syntax error here;\n"
        )
        (mdir / "001_bad.down.sql").write_text("DROP TABLE good_one;\n")
        conn = sqlite3.connect(str(tmp_path / "rb.db"))
        m = Migrator(conn, str(mdir))
        with pytest.raises(sqlite3.OperationalError):
            m.up()
        # the first statement's table must have been rolled back
        tables = {
            r[0]
            for r in conn.execute(
                "SELECT name FROM sqlite_master WHERE type='table'"
            )
        }
        assert "good_one" not in tables
        assert m.applied_versions() == set()
        conn.close()

    def test_down_then_up_roundtrip(self, tmp_path, nsmgr):
        s = SQLiteTupleStore(str(tmp_path / "m.db"), namespace_manager=nsmgr)
        n_all = len(s.migrator.status())
        assert len(s.migrator.down(steps=n_all)) == n_all
        assert s.migrator.has_pending()
        s.migrator.up()
        s.write_relation_tuples(t("n:o#r@alice"))
        assert len(s) == 1
        s.close()


class TestDurability:
    def test_tuples_and_version_survive_reopen(self, tmp_path, nsmgr):
        path = str(tmp_path / "d.db")
        s = SQLiteTupleStore(path, namespace_manager=nsmgr, network_id="net")
        s.write_relation_tuples(t("n:o#r@alice"), t("n:o#r@bob"))
        s.delete_relation_tuples(t("n:o#r@bob"))
        v = s.version
        assert v == 2
        s.close()

        s2 = SQLiteTupleStore(path, namespace_manager=nsmgr, network_id="net")
        assert s2.version == v  # durable snaptoken
        tuples, version = s2.snapshot()
        assert tuples == [t("n:o#r@alice")]
        assert version == v
        s2.close()


class TestDefaultNetworkDurability:
    def test_server_path_without_explicit_nid_survives_restart(self, tmp_path, nsmgr):
        # the serve path passes no network_id; the store must adopt the
        # database's network on reopen (reference determineNetwork,
        # registry_default.go:207-225)
        path = str(tmp_path / "srv.db")
        s = SQLiteTupleStore(path, namespace_manager=nsmgr)
        s.write_relation_tuples(t("n:o#r@alice"))
        nid = s.network_id
        s.close()
        s2 = SQLiteTupleStore(path, namespace_manager=nsmgr)
        assert s2.network_id == nid
        assert s2.all_tuples() == [t("n:o#r@alice")]
        assert s2.version == 1
        s2.close()


class TestIsolation:
    def test_two_networks_one_database(self, tmp_path, nsmgr):
        # reference manager_isolation.go:44-138: two persisters with
        # different nids over one database must not see each other
        path = str(tmp_path / "iso.db")
        s1 = SQLiteTupleStore(path, namespace_manager=nsmgr, network_id="n1")
        s2 = SQLiteTupleStore(path, namespace_manager=nsmgr, network_id="n2")
        s1.write_relation_tuples(t("n:o#r@alice"))
        s2.write_relation_tuples(t("n:o#r@bob"))
        assert s1.get_relation_tuples(RelationQuery(namespace="n"))[0] == [
            t("n:o#r@alice")
        ]
        assert s2.get_relation_tuples(RelationQuery(namespace="n"))[0] == [
            t("n:o#r@bob")
        ]
        # independent version counters per network
        assert s1.version == 1
        assert s2.version == 1
        s1.close()
        s2.close()


class TestDeviceIntegration:
    def test_snapshot_manager_over_sqlite(self, tmp_path, nsmgr):
        from keto_tpu.engine.closure import ClosureCheckEngine
        from keto_tpu.graph import SnapshotManager

        s = SQLiteTupleStore(str(tmp_path / "g.db"), namespace_manager=nsmgr)
        s.write_relation_tuples(
            t("n:obj#access@(n:org#member)"), t("n:org#member@alice")
        )
        mgr = SnapshotManager(s)
        dev = ClosureCheckEngine(mgr, query_mode="device")
        assert dev.subject_is_allowed(t("n:obj#access@alice"))
        assert not dev.subject_is_allowed(t("n:obj#access@bob"))
        # incremental write-through
        s.write_relation_tuples(t("n:org#member@carol"))
        assert dev.subject_is_allowed(t("n:obj#access@carol"))
        s.close()
