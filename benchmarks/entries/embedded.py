"""Way in: the library entry of the README quick start.  Engine() with the
TPC-H connector, tables resident on the device, one process; a request is
execute_page(sql), block_until_ready on its columns, to_pylist().  A result
column that does not live on the accelerator fails the request.
"""

from __future__ import annotations

import loader


class Entry:
    def __init__(self, config: dict, templates: dict, scale: float):
        import jax

        from trino_tpu.connectors.tpch import TpchConnector
        from trino_tpu.runtime.engine import Engine
        from trino_tpu.utils.tracing import InMemorySpanExporter

        self.templates = templates
        self.platform = jax.devices()[0].platform
        self.engine = Engine()
        self.engine.register_catalog("tpch", TpchConnector(scale))
        for prop, value in config["session"].items():
            self.engine.session.set(prop, str(value))
        self._exporter = InMemorySpanExporter()
        self.engine.tracer.add_exporter(self._exporter)

    def client(self, stream: int):
        import jax

        def request(name: str, binding):
            page = self.engine.execute_page(loader.sql_text(self.templates[name]))
            arrays = [a for c in page.columns
                      for a in (c.data, c.valid, c.data2) if a is not None]
            jax.block_until_ready(arrays)
            for a in arrays:
                where = {d.platform for d in a.devices()}
                if where != {self.platform}:
                    raise RuntimeError(f"{name}: a result array lives on {where}")
            return page.to_pylist(), None

        return request

    def spans(self) -> list:
        return self._exporter.snapshot()

    def close(self) -> None:
        self.engine = None
