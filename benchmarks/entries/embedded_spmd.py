"""Way in: the library entry over every chip of the host.
Engine(distributed=True, devices=<the layout's chips>) with the TPC-H
connector: one SPMD program per statement under shard_map, scan columns
sharded by row range, stages joined by collectives.  A request is
execute_page(sql), block_until_ready on its columns, to_pylist().  A result
column that does not live on the accelerator fails the request, and so does
a scan page that is not laid in equal row ranges, one per chip: every page
the executor keeps is looked at once, in the request that first used it.
"""

from __future__ import annotations

import loader


class Entry:
    def __init__(self, config: dict, templates: dict, scale: float):
        import jax

        from trino_tpu.connectors.tpch import TpchConnector
        from trino_tpu.runtime.engine import Engine
        from trino_tpu.utils.tracing import InMemorySpanExporter

        chips = int(config["layout"]["chips"])
        devices = jax.devices()[:chips]
        if len(devices) != chips:
            raise RuntimeError(f"the layout wants {chips} devices, JAX has {len(devices)}")
        self.templates = templates
        self.chips = chips
        self.platform = devices[0].platform
        self.engine = Engine(distributed=True, devices=devices)
        self.engine.register_catalog("tpch", TpchConnector(scale))
        for prop, value in config["session"].items():
            self.engine.session.set(prop, str(value))
        self._exporter = InMemorySpanExporter()
        self.engine.tracer.add_exporter(self._exporter)
        self._seen: list = []  # the scan pages already looked at

    def check_shards(self) -> None:
        """Every array of every scan page the executor keeps: one shard per
        chip, each a quarter of the rows.  Raises on the first that is not."""
        for page in self.engine.executor._sharded_pages.values():
            if any(page is p for p in self._seen):
                continue
            arrays = [a for c in page.columns
                      for a in (c.data, c.valid, c.data2) if a is not None]
            for a in arrays + [page.live]:
                shards = a.addressable_shards
                rows = {s.data.shape[0] for s in shards}
                if (len({s.device.id for s in shards}) != self.chips
                        or rows != {a.shape[0] // self.chips}):
                    raise RuntimeError(
                        f"a scan column of {a.shape[0]} rows lies in shards of "
                        f"{sorted(rows)} rows on {len(shards)} device(s): {a.sharding}")
            self._seen.append(page)

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
            self.check_shards()
            return page.to_pylist(), None

        return request

    def spans(self) -> list:
        return self._exporter.snapshot()

    def close(self) -> None:
        self.engine = None
