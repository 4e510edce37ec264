"""Process start to the start of the measured window: imports, weights
made on the device from the seed, compile or cache read, warm-up, ramp."""
NAME, UNIT = "setup_s", "s"


def read(run):
    return run.records["setup_s"]
