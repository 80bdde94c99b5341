"""Way in: SQL text from a client over /v1/statement + nextUri.

One coordinator and `layout.workers` workers in this process
(testing/runner.DistributedQueryRunner), one client.StatementClient per
stream.  The session properties come from the configuration's file.
"""

from __future__ import annotations

import loader


class Entry:
    def __init__(self, config: dict, templates: dict, scale: float):
        from trino_tpu.connectors.tpch import TpchConnector
        from trino_tpu.testing.runner import DistributedQueryRunner
        from trino_tpu.utils.tracing import InMemorySpanExporter

        self.templates = templates
        self.runner = DistributedQueryRunner(
            num_workers=int(config["layout"]["workers"])
        )
        self.runner.register_catalog("tpch", TpchConnector(scale))
        self.runner.start()
        coord = self.runner.coordinator
        for prop, value in config["session"].items():
            coord.session.set(prop, str(value))
        self._exporter = InMemorySpanExporter()
        coord.tracer.add_exporter(self._exporter)
        for w in self.runner.workers:
            w.tracer.add_exporter(self._exporter)

    def statement(self, name: str, binding) -> str:
        return loader.sql_text(self.templates[name])

    def new_client(self):
        from trino_tpu.client.client import StatementClient

        return StatementClient(self.runner.client_url)

    def client(self, stream: int):
        """-> request(template name, binding) -> (rows, query id), for one stream."""
        client = self.new_client()

        def request(name: str, binding):
            _cols, rows = client.execute(self.statement(name, binding), timeout=3000.0)
            return rows, client.last_query_id

        return request

    def spans(self) -> list:
        return self._exporter.snapshot()

    def close(self) -> None:
        self.runner.stop()
