"""Start the system under test: load a configuration's tables into a
`Domain` and serve it with `tidb_tpu.server.MySQLServer` on a loop thread,
port 0, with the defaults `python -m tidb_tpu` has.  No `TIDB_TPU_*`
variable and no `tidb_tpu_*` sysvar is set here.
"""

from __future__ import annotations

import asyncio
import threading
import time

import numpy as np

from . import datagen
from .wire import WireClient


def load(config: dict, seed: int, sf: float, log) -> tuple:
    """(domain, tables, seconds by phase).  `tables` holds the benchmark's
    own narrow copy of every column loaded, for the reference."""
    from tidb_tpu.session import Domain

    t0 = time.perf_counter()
    domain = Domain()
    sess = domain.new_session()
    want = config["tables"]
    kept = {name: {c: [] for c in spec["columns"]}
            for name, spec in want.items()}
    stores = {}
    for name, spec in want.items():
        sess.execute(spec["ddl"])
        info = domain.catalog.info_schema().table("test", name)
        stores[name] = (info, domain.storage.table(info.id))

    def put(name: str, block: dict):
        info, store = stores[name]
        cols = want[name]["columns"]
        dicts = {i: datagen.DICTIONARIES[c] for i, c in enumerate(cols)
                 if c in datagen.DICTIONARIES}
        store.bulk_load_arrays([block[c] for c in cols],
                               ts=domain.storage.current_ts(),
                               dictionaries=dicts or None)
        for c in cols:
            kept[name][c].append(block[c])

    if "customer" in want:
        put("customer", datagen.customer(sf, seed))
    for orders, lineitem in datagen.order_blocks(sf, seed):
        if "orders" in want:
            put("orders", orders)
        if "lineitem" in want:
            put("lineitem", lineitem)
    t_gen = time.perf_counter()
    for name, (info, store) in stores.items():
        domain.storage.regions.split_even(info.id, config["regions"],
                                          store.base_rows)
    for name in config.get("analyze", ()):
        sess.execute(f"analyze table {name}")
    t_done = time.perf_counter()
    tables = {name: {c: np.concatenate(parts) for c, parts in cols.items()}
              for name, cols in kept.items()}
    for name, cols in tables.items():
        log({"loaded": name, "rows": int(len(next(iter(cols.values())))),
             "columns": list(cols)})
    return domain, tables, {"generate_load_s": t_gen - t0,
                            "split_analyze_s": t_done - t_gen}


class Served:
    """One MySQLServer for one Domain on an event loop in a thread, as
    `serve_forever` runs it."""

    def __init__(self, domain):
        from tidb_tpu.server import MySQLServer

        self.srv = MySQLServer(domain, port=0)
        self.loop = asyncio.new_event_loop()
        started = threading.Event()

        def run():
            asyncio.set_event_loop(self.loop)
            self.loop.run_until_complete(self.srv.start())
            started.set()
            self.loop.run_forever()

        self.thread = threading.Thread(target=run, daemon=True,
                                       name="bench-server")
        self.thread.start()
        if not started.wait(30):
            raise RuntimeError("server failed to start")

    def client(self) -> WireClient:
        return WireClient(self.srv.host, self.srv.port)

    def stop(self):
        asyncio.run_coroutine_threadsafe(
            self.srv.shutdown(drain_s=2.0), self.loop).result(30)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(10)
