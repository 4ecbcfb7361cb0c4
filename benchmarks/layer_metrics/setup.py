"""Entry and loader: the two parts of set-up the program owns.
`healthy_s` is spawn to the first healthy answer (imports, weights from
the seed, engine build); `warmup_s` is the first warm request sent to
the last returned (every program the window uses, from the compile
cache after a checkout's first run)."""

METRICS = [
    {"name": "healthy_s", "unit": "s", "layer": "entry and loader",
     "moves": "setup_s", "source": "host_clock"},
    {"name": "warmup_s", "unit": "s", "layer": "entry and loader",
     "moves": "setup_s", "source": "host_clock"},
]


def read(run):
    return {"healthy_s": run["healthy_s"], "warmup_s": run["warmup_s"]}
