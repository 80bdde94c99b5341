"""Way in: a prepared statement, EXECUTE <name> USING <bindings>, over the
same protocol as served_text.  The statements are client-held (replayed in
the X-Trino-Prepared-Statement header), as a connection pool holds them, so
the coordinator's prepared fast path (runtime/fastpath.py) takes them: plan
cache, bindings as jit arguments, a coordinator-local executor over resident
pages.
"""

from __future__ import annotations

import loader

_text = loader.load_module("entries", "served_text")


class Entry(_text.Entry):
    def statement(self, name: str, binding) -> str:
        return f"EXECUTE {name} USING " + ", ".join(binding.literals)

    def new_client(self):
        client = super().new_client()
        for name, t in self.templates.items():
            client.prepared[name] = loader.sql_text(t, "prepared_text")
        return client
