"""End-to-end reader: what the agent costs its host.  CPU seconds of the
server process (time.process_time(): all threads) over the window, per
statsd line the sink's aggregates account for.  Nothing where the traffic
has no statsd lines.
"""


def read(ctx: dict):
    if ctx["lines_accounted"] <= 0:
        return None
    return ctx["metrics_mod"].cpu_us_per_line(ctx["cpu_seconds"],
                                              ctx["lines_accounted"])
