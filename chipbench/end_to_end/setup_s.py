"""Process start to window open: chip open, imports, every compile, prefill,
the first training burst with its readings, the warm-up bursts."""


def read(run):
    return run["window"]["setup_s"]
