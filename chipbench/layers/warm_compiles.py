"""Programs the backend compiled during set-up that the persistent cache did
not serve (`compile_stats`, read at window open)."""


def read(run):
    programs, _seconds, hits, _writes = run["window"]["compile_open"]
    return float(programs - hits)
