"""What every traffic generator shares: seeded shapes, prompt text, and one
streamed completion over HTTP timed at the client's seat.

Extended copy of `experiments/loadgen.py`'s HTTP mode (its gaps: uniform
lengths only, no closed loop, no report of how late the generator ran). The
original stays in the program for a later PR to delete.

Steadiness rule (the contract's): the *shapes* of a run — prompt lengths,
output budgets, which requests share a prefix — are drawn from the traffic
file's own `shape_seed`, in a fixed order; `--seed` chooses the prompt text
(and, through the model file, the weights). Every seed thus offers the same work. (The sizes
were first permuted by the seed too: a closed-loop window holds about ten
admissions, and which ones fell into it moved `out_tok_s` by 6% from seed
to seed against 1% between two runs of one seed; my chip runs, PR 25.)
"""

from __future__ import annotations

import http.client
import json
import math
import threading
import time
from dataclasses import dataclass, field

import numpy as np

LETTERS = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", np.uint8)


def draw(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """n integer sizes from a distribution block of a traffic file."""
    kind = spec["dist"]
    if kind == "uniform":
        x = rng.integers(int(spec["lo"]), int(spec["hi"]) + 1, n)
    elif kind == "lognormal":
        x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
        x = np.clip(np.rint(x), int(spec["lo"]), int(spec["hi"]))
    elif kind == "fixed":
        x = np.full(n, int(spec["value"]))
    else:
        raise ValueError(f"unknown distribution {kind!r} in the traffic file")
    return x.astype(np.int64)


def text_of(rng: np.random.Generator, n_tokens: int) -> str:
    """ASCII letters: the byte-level tokenizer gives one token a byte."""
    return LETTERS[rng.integers(0, len(LETTERS), int(n_tokens))].tobytes().decode()


@dataclass
class Shape:
    """One request as the generator will send it."""
    prompt: str
    prompt_tokens: int  # BOS included
    max_tokens: int
    tenant: int = -1  # index of the shared prefix, -1 = shares nothing


def build_shapes(params: dict, seed: int, n: int) -> list:
    """n request shapes: sizes, in order, from `shape_seed` (the same for
    every run), text from `seed`. `tenants`, when present, makes a share of
    the requests open with one of a few long prefixes."""
    fixed = np.random.default_rng([int(params.get("shape_seed", 0)), 1])
    rng = np.random.default_rng([int(seed), 2])
    prompt_n = draw(params["prompt_tokens"], fixed, n)
    out_n = draw(params["max_tokens"], fixed, n)
    tenants = params.get("tenants")
    tenant_of = np.full(n, -1)
    prefixes = []
    if tenants:
        count = int(tenants["count"])
        tenant_of = np.where(fixed.random(n) < float(tenants["share"]),
                             fixed.integers(0, count, n), -1)
        prefixes = [text_of(rng, k)
                    for k in draw(tenants["prefix_tokens"], fixed, count)]
    shapes = []
    for i in range(n):
        pre = prefixes[tenant_of[i]] if tenant_of[i] >= 0 else ""
        body = text_of(rng, max(1, int(prompt_n[i]) - 1))  # BOS is the +1
        shapes.append(Shape(prompt=pre + body,
                            prompt_tokens=1 + len(pre) + len(body),
                            max_tokens=int(out_n[i]), tenant=int(tenant_of[i])))
    return shapes


@dataclass
class Record:
    """What the client saw of one request. Times are time.monotonic()."""
    shape: Shape
    t_due: float
    t_sent: float = 0.0
    t_end: float = 0.0  # when it ended: the finish frame, or the failure
    events: list = field(default_factory=list)  # (t, tokens in this frame)
    finish: str | None = None
    timings: dict | None = None
    status: int | None = None
    error: str | None = None
    done: bool = False  # the stream reached [DONE]
    cut: bool = False  # abandoned by the generator at the window's end
    conn: object = None  # the live connection, so that `cut` can close it


def stream_completion(host: str, port: int, rec: Record,
                      stop: threading.Event, timeout_s: float = 120.0) -> None:
    """One streamed greedy completion on the legacy endpoint (raw prompt:
    exact token counts); fills `rec`. Returns early, marking `rec.cut`,
    once `stop` is set: closing the socket makes the server cancel."""
    conn = rec.conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
    try:
        rec.t_sent = time.monotonic()
        conn.request("POST", "/v1/completions", json.dumps({
            "prompt": rec.shape.prompt, "max_tokens": rec.shape.max_tokens,
            "temperature": 0.0, "stream": True, "include_token_ids": True,
        }), {"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec.status = resp.status
        if resp.status != 200:
            rec.error = f"http_{resp.status}"
            resp.read()
            return
        buf = b""
        while True:
            if stop.is_set():
                rec.cut = True
                return
            # read1: read(n) would wait for n bytes and clock the first
            # token at a buffer boundary
            chunk = resp.read1(65536)
            if not chunk:
                break
            now = time.monotonic()
            buf += chunk
            while b"\n\n" in buf:
                frame, _, buf = buf.partition(b"\n\n")
                if not frame.startswith(b"data: "):
                    continue  # keep-alive comments
                payload = frame[6:]
                if payload == b"[DONE]":
                    rec.done = True
                    return
                ev = json.loads(payload)
                if "error" in ev:
                    rec.error = str(ev["error"].get("message", "error"))[:200]
                    continue
                choice = (ev.get("choices") or [{}])[0]
                n = len(ev.get("token_ids") or ())
                if n and (choice.get("text") or choice.get("delta")):
                    rec.events.append((now, n))
                if choice.get("finish_reason"):
                    rec.finish, rec.t_end = choice["finish_reason"], now
                    rec.timings = ev.get("timings")
        if stop.is_set():
            rec.cut = True
        else:
            rec.error = rec.error or "stream ended without [DONE]"
    except (OSError, http.client.HTTPException, ValueError) as e:
        if stop.is_set():
            rec.cut = True
        else:
            rec.error = f"{type(e).__name__}: {e}"[:200]
    finally:
        rec.t_end = rec.t_end or time.monotonic()
        conn.close()


def cut(records: list, stop: threading.Event, threads: list) -> None:
    """End of the window: abandon what is still in flight. Closing the
    socket wakes a reader blocked on a quiet stream, and the server cancels
    the request on the disconnect."""
    import socket

    stop.set()
    for rec in records:
        sock = getattr(rec.conn, "sock", None)
        if sock is not None and not rec.done:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
    for th in threads:
        th.join(timeout=30)


def http_json(host: str, port: int, method: str, path: str, body=None,
              timeout: float = 60.0):
    """-> (status, parsed JSON or text)."""
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request(method, path,
                     None if body is None else json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        data = resp.read()
    finally:
        conn.close()
    try:
        return resp.status, json.loads(data)
    except ValueError:
        return resp.status, data.decode(errors="replace")


def prometheus(text: str) -> dict:
    """{family: sum of its samples} and {family{labels}: value} of a
    Prometheus text exposition."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        name, _, value = line.rpartition(" ")
        try:
            v = float(value)
        except ValueError:
            continue
        out[name] = v
        family = name.split("{", 1)[0]
        if family != name:
            out[family] = out.get(family, 0.0) + v
    return out
