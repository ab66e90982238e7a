"""Backend compile requests between window open and close (`compile_stats`);
0 when every shape was warmed up."""


def read(run):
    return float(run["window"]["compiles"])
