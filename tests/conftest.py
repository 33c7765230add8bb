"""Test configuration.

The suite runs on the CPU backend: JAX_PLATFORMS=cpu with an 8-device
virtual CPU mesh for the multi-device sharding tests, set here before
anything imports jax. The persistent compilation cache stays off so test
runs never write into <repo>/.jax-cache. What runs on the chip is
chip_smoke.py; what compiles for it is tests/test_tpu_compile.py.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402

from keto_tpu.namespace import MemoryNamespaceManager  # noqa: E402
from keto_tpu.relationtuple import RelationTuple  # noqa: E402
from keto_tpu.store import InMemoryTupleStore  # noqa: E402


def random_store(rng, n_objects, n_users, n_edges, n_rel=3):
    """Random tuple graph with a healthy share of subject-set indirections
    (shared by the engine parity suites: ``from conftest import
    random_store``)."""
    store = InMemoryTupleStore()
    tuples = set()
    for _ in range(n_edges):
        obj = f"o{rng.integers(n_objects)}"
        rel = f"r{rng.integers(n_rel)}"
        if rng.random() < 0.45:
            sub = f"n:o{rng.integers(n_objects)}#r{rng.integers(n_rel)}"
        else:
            sub = f"u{rng.integers(n_users)}"
        tuples.add(f"n:{obj}#{rel}@({sub})")
    store.write_relation_tuples(
        *(RelationTuple.from_string(s) for s in tuples)
    )
    return store


@pytest.fixture
def nsmgr():
    return MemoryNamespaceManager()


@pytest.fixture(scope="session")
def pgfake_server():
    """One in-tree fake postgres (wire protocol over sqlite) per session;
    each test leg opens its own logical database on it."""
    from keto_tpu.persistence.pgfake import start_server

    srv = start_server()
    yield srv
    srv.stop()


@pytest.fixture(
    params=["memory", "sqlite", "columnar", "postgres", "mysql", "cockroach"]
)
def store(request, nsmgr, tmp_path):
    """Every contract/engine test runs against all persistence backends —
    the reference's one-suite-many-DSNs matrix across 4 SQL engines
    (SURVEY.md §4; internal/persistence/sql/persister.go:50-51). The
    postgres/cockroach legs speak the real v3 wire protocol through the
    in-tree driver (pgwire.py) — against a live server when
    KETO_TEST_PG_DSN is set, else against the in-tree fake (pgfake.py),
    the same role the reference's dockertest containers play
    (internal/x/dbx/dsn_testutils.go:45-61). The mysql leg runs the MySQL
    dialect SQL through the DB-API translation shim (mysqlfake.py) unless
    KETO_TEST_MYSQL_DSN points at a real server."""
    import uuid as _uuid

    if request.param == "memory":
        yield InMemoryTupleStore(namespace_manager=nsmgr)
        return
    if request.param == "columnar":
        from keto_tpu.store import ColumnarTupleStore

        yield ColumnarTupleStore(namespace_manager=nsmgr)
        return
    if request.param == "postgres":
        from keto_tpu.persistence.postgres import PostgresTupleStore

        dsn = os.environ.get("KETO_TEST_PG_DSN")
        fresh = dsn is None
        if fresh:
            srv = request.getfixturevalue("pgfake_server")
            dsn = (
                f"postgres://keto@127.0.0.1:{srv.port}"
                f"/pg_{_uuid.uuid4().hex[:12]}"
            )
            s = PostgresTupleStore(dsn, namespace_manager=nsmgr)
        else:
            try:
                s = PostgresTupleStore(dsn, namespace_manager=nsmgr)
            except Exception as e:
                # an unreachable EXTERNAL server is a visible skip, not a
                # matrix-wide error (the in-tree fake leg always runs)
                pytest.skip(f"external postgres unavailable: {e}")
        yield s
        if not fresh:  # shared external database: leave it clean
            from keto_tpu.relationtuple import RelationQuery

            s.delete_all_relation_tuples(RelationQuery())
        s.close()
        return
    if request.param == "cockroach":
        from keto_tpu.persistence.dialect import CockroachDialect
        from keto_tpu.persistence.sqlstore import SQLTupleStore

        srv = request.getfixturevalue("pgfake_server")
        s = SQLTupleStore(
            CockroachDialect(),
            f"postgres://keto@127.0.0.1:{srv.port}"
            f"/crdb_{_uuid.uuid4().hex[:12]}",
            namespace_manager=nsmgr,
        )
        yield s
        s.close()
        return
    if request.param == "mysql":
        from keto_tpu.persistence.dialect import MySQLDialect
        from keto_tpu.persistence.sqlstore import SQLTupleStore

        external = os.environ.get("KETO_TEST_MYSQL_DSN")
        dsn = external or f"mysql+fake:///my_{_uuid.uuid4().hex[:12]}"
        try:
            s = SQLTupleStore(MySQLDialect(), dsn, namespace_manager=nsmgr)
        except Exception as e:
            if external:
                pytest.skip(f"external mysql unavailable: {e}")
            raise
        yield s
        if external:  # shared external database: leave it clean
            from keto_tpu.relationtuple import RelationQuery

            s.delete_all_relation_tuples(RelationQuery())
        s.close()
        return
    from keto_tpu.persistence import SQLiteTupleStore

    s = SQLiteTupleStore(
        str(tmp_path / "keto.db"), namespace_manager=nsmgr
    )
    yield s
    s.close()
