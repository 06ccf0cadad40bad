"""Statement-shape tests for the mysql/postgres dialects.

No mysql/postgres server exists in this environment, so the dialect SQL is
exercised through a recording fake DB-API connection: every statement the
store core executes is captured and checked for (a) placeholder/arg-count
agreement, (b) no un-rewritten '?' markers in %s dialects, (c) the exact
statement text (golden), so a typo in dialect SQL fails here instead of at
a customer's database.
"""

import re

import pytest

from cerbos_tpu.storage.db import DBStore, MySQLDialect, PostgresDialect, Sqlite3Dialect

POLICY_DOC = """
apiVersion: api.cerbos.dev/v1
resourcePolicy:
  resource: doc
  version: default
  rules:
    - actions: ["view"]
      effect: EFFECT_ALLOW
      roles: [user]
"""


class FakeCursor:
    def __init__(self, log):
        self.log = log
        self.rowcount = 0

    def execute(self, sql, args=()):
        self.log.append((sql, tuple(args)))

    def executemany(self, sql, seq):
        for args in seq:
            self.log.append((sql, tuple(args)))

    def fetchall(self):
        return []

    def fetchone(self):
        return None


class FakeConn:
    def __init__(self):
        self.statements = []

    def cursor(self):
        return FakeCursor(self.statements)

    def commit(self):
        pass

    def rollback(self):
        pass

    def close(self):
        pass


def _drive(dialect):
    """Run every store operation through a recording connection."""
    conn = FakeConn()
    dialect.connect = lambda conf: conn  # bypass the missing client library
    store = DBStore(dialect, {})
    store.get_all()
    store.get("cerbos.resource.doc.vdefault")
    store.get_schema("doc.json")
    store.list_schema_ids()
    store.add_or_update([POLICY_DOC])
    store.set_disabled(["cerbos.resource.doc.vdefault"], True)
    store.delete(["cerbos.resource.doc.vdefault"])
    store.list_policy_ids()
    store.list_policy_ids(include_disabled=True)
    store.get_raw("cerbos.resource.doc.vdefault")
    store.add_schema("doc.json", b"{}")
    store.delete_schema("doc.json")
    return conn.statements


@pytest.mark.parametrize("dialect_cls", [Sqlite3Dialect, MySQLDialect, PostgresDialect])
def test_placeholders_match_args(dialect_cls):
    dialect = dialect_cls()
    marker = dialect.placeholder
    for sql, args in _drive(dialect):
        if sql.strip().startswith("CREATE"):
            continue
        n = sql.count(marker)
        assert n == len(args), f"{dialect.name}: {n} markers vs {len(args)} args in: {sql}"
        if marker == "%s":
            assert "?" not in sql, f"{dialect.name}: un-rewritten '?' marker in: {sql}"


def _norm(sql: str) -> str:
    return re.sub(r"\s+", " ", sql).strip()


def test_mysql_statement_goldens():
    stmts = {_norm(s) for s, _ in _drive(MySQLDialect())}
    assert (
        "INSERT INTO policy (fqn, kind, definition, disabled) VALUES (%s, %s, %s, %s) "
        "ON DUPLICATE KEY UPDATE definition = VALUES(definition), kind = VALUES(kind), "
        "disabled = VALUES(disabled), updated_at = NOW()"
    ) in stmts
    assert (
        "INSERT INTO schema_defs (id, definition) VALUES (%s, %s) "
        "ON DUPLICATE KEY UPDATE definition = VALUES(definition)"
    ) in stmts
    assert "SELECT definition FROM policy WHERE disabled = %s" in stmts
    assert "DELETE FROM policy WHERE fqn = %s" in stmts
    # DDL uses MySQL column types
    ddl = " ".join(s for s, _ in _drive(MySQLDialect()) if s.strip().startswith("CREATE"))
    assert "MEDIUMTEXT" in ddl and "TINYINT" in ddl and "MEDIUMBLOB" in ddl


def test_postgres_statement_goldens():
    stmts = {_norm(s) for s, _ in _drive(PostgresDialect())}
    assert (
        "INSERT INTO policy (fqn, kind, definition, disabled) VALUES (%s, %s, %s, %s) "
        "ON CONFLICT(fqn) DO UPDATE SET definition = excluded.definition, "
        "kind = excluded.kind, disabled = excluded.disabled, updated_at = NOW()"
    ) in stmts
    assert (
        "INSERT INTO schema_defs (id, definition) VALUES (%s, %s) "
        "ON CONFLICT(id) DO UPDATE SET definition = excluded.definition"
    ) in stmts
    ddl = " ".join(s for s, _ in _drive(PostgresDialect()) if s.strip().startswith("CREATE"))
    assert "BOOLEAN" in ddl and "TIMESTAMPTZ" in ddl and "BYTEA" in ddl


def test_bool_column_representations():
    # postgres BOOLEAN must bind bool; mysql/sqlite TINYINT/INTEGER bind int
    assert PostgresDialect().bool_value(True) is True
    assert PostgresDialect().bool_value(False) is False
    assert MySQLDialect().bool_value(True) == 1
    assert Sqlite3Dialect().bool_value(False) == 0
