"""serve_rows_per_flush: rows per flush of the serving batcher
(``serving/server.py``): the change of ``Server.stats()`` rows over the
change of its flushes across the window."""
from __future__ import annotations


def read(records):
    serve = records.serve
    if serve is None or not serve["flushes"]:
        return None
    return serve["rows"] / serve["flushes"]
