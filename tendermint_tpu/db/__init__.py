"""Key-value store backends (role of tmlibs/db in the reference).

The reference uses goleveldb for blockstore/state/txindex/addrbook
(`tmlibs/db`); here the persistent backend is SQLite (stdlib, ACID,
single-file) and MemDB backs tests/replay.
"""

from tendermint_tpu.db.kv import DB, Batch, MemDB, SQLiteDB, db_provider

__all__ = ["DB", "Batch", "MemDB", "SQLiteDB", "db_provider"]
