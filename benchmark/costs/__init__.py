"""What a kernel's call must move and compute, from shapes alone.

Kept with the benchmark so that a PR which changes a kernel cannot change
what the benchmark reckons for it. Each module gives `cost(...) -> (flops,
bytes)` for one call and `calls(config, trace_op) -> (flops, bytes) | None`,
which tells from a traced device op which call it was, or None when the
trace does not say (then no roofline share is reported).
"""
