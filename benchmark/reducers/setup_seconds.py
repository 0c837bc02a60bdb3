"""Process start to the opening of the window's generator: files, load,
check, warm-up, compile, ramp. The harness's own clock. params: none."""


def reduce(params: dict, run: dict):
    return run["setup_s"]
