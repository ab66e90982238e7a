"""Device time of the burst program (its XLA-module events in the traced
stretch) over the gradient steps those bursts ran."""


def read(run):
    t = run["trace"]
    if not t or not t["grants"]:
        return None
    want = run["traffic"]["burst_program"]
    total = sum(sum(durs) for name, durs in t["devices"][0]["modules"].items() if want in name)
    if total <= 0:
        return None
    return 1e3 * total / t["grants"]
