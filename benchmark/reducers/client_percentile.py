"""A percentile of what the clients saw, over requests DUE inside the window.

params: `quantity` (ttft_ms | itl_ms | late_ms | queue_wait_ms), `q`.

- ttft_ms: first content event minus the time the request was due (closed
  loop: the moment its client was free). A failed request counts as +inf; a
  request cut at the window's end before its first token is left out.
- itl_ms: the gap per output token, pooled over every content event inside
  the window of every request (ramp requests too): an event carrying k
  tokens dt after the previous one counts k gaps of dt/k.
- late_ms: sent minus due, the generator's own lateness.
- queue_wait_ms: the server's `timings.queue_wait_ms` of the requests that
  FINISHED inside the window (the number arrives with the finish frame;
  ramp requests too).
"""

from benchmark.reducers import weighted_quantile


def failed(rec) -> bool:
    """error / timeout / refused / ended without [DONE] while not cut."""
    if rec.cut:
        return False
    return bool(rec.error or rec.status != 200 or not rec.done
                or rec.finish not in ("stop", "length"))


def reduce(params: dict, run: dict):
    t0, t1 = run["t0"], run["t1"]
    quantity, q = params["quantity"], float(params["q"])
    mine = [r for r in run["records"] if t0 <= r.t_due < t1]
    pairs = []
    if quantity == "ttft_ms":
        for r in mine:
            if r.events:
                pairs.append(((r.events[0][0] - r.t_due) * 1e3, 1))
            elif failed(r):
                pairs.append((float("inf"), 1))
    elif quantity == "itl_ms":
        for r in run["records"]:
            for (ta, _), (tb, k) in zip(r.events, r.events[1:]):
                if t0 <= tb < t1:
                    pairs.append(((tb - ta) * 1e3 / k, k))
    elif quantity == "late_ms":
        pairs = [((r.t_sent - r.t_due) * 1e3, 1) for r in mine if r.t_sent]
    elif quantity == "queue_wait_ms":
        pairs = [(float(r.timings["queue_wait_ms"]), 1) for r in run["records"]
                 if t0 <= r.t_end < t1 and r.timings
                 and r.timings.get("queue_wait_ms") is not None]
    else:
        raise ValueError(f"client_percentile: unknown quantity {quantity!r}")
    return weighted_quantile(pairs, q)
