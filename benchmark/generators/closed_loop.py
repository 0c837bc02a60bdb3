"""Closed loop: `clients` callers, each sending its next request when the
last one ended. Callers that wait for a reply (agents, batch jobs) make this
load; a slow server receives less of it.

Parameters (traffic file): `clients` (a number, or "slots" for one client a
slot of the configuration), `prompt_tokens`, `max_tokens` (distribution
blocks), `pool` (how many shapes to prepare), `ramp_seconds` (the loop runs
that long before the window opens), `ramp_prompt_tokens` (each client's
FIRST request, sent in the ramp, has its prompt cut to this and its budget
staggered over (0, max]: every slot is decoding within seconds and the
clients end at spread-out times, so the window opens on the steady state
and not on a queue of admissions), optional `tenants`, `shape_seed`.
`at_open` / `at_close` are called as the window opens and closes (before
what is in flight is cut): the harness reads the program's counters there.
"""

from __future__ import annotations

import threading
import time

from benchmark import loadlib


def run(*, host: str, port: int, params: dict, seed: int, seconds: float,
        config: dict, at_open=None, at_close=None) -> dict:
    clients = params["clients"]
    n_clients = int(config["serve"]["slots"] if clients == "slots" else clients)
    shapes = loadlib.build_shapes(params, seed, int(params["pool"]))
    ramp = float(params.get("ramp_seconds", 0.0))
    short = int(params.get("ramp_prompt_tokens", 0))
    for i, sh in enumerate(shapes[:n_clients]):  # the ramp's round
        sh.max_tokens = max(8, sh.max_tokens * (i + 1) // n_clients)
        if short and sh.prompt_tokens > short:
            sh.prompt, sh.prompt_tokens = sh.prompt[:short - 1], short
    records: list = []
    lock = threading.Lock()
    stop = threading.Event()
    cursor = [0]
    t_open = time.monotonic() + ramp
    t_close = t_open + seconds

    def client():
        while not stop.is_set() and time.monotonic() < t_close:
            with lock:
                if cursor[0] >= len(shapes):
                    return
                shape = shapes[cursor[0]]
                cursor[0] += 1
                rec = loadlib.Record(shape=shape, t_due=time.monotonic())
                records.append(rec)
            loadlib.stream_completion(host, port, rec, stop)

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(n_clients)]
    for th in threads:
        th.start()
    time.sleep(max(0.0, t_open - time.monotonic()))
    if at_open is not None:
        at_open()
    time.sleep(max(0.0, t_close - time.monotonic()))
    if at_close is not None:
        at_close()
    loadlib.cut(records, stop, threads)
    return {"t0": t_open, "t1": t_close, "records": records}
