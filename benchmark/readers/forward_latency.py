"""End-to-end reader: what a local feels.  Per local and interval, from
the moment its forward was due to the ack of its last chunk
(metrics.forward_latencies_ms), over all locals x intervals of the window.
Nothing where the generator forwards nothing.
"""


def read(ctx: dict, q: float):
    xs = ctx["forward_ms"]
    if not xs:
        return None
    return ctx["metrics_mod"].percentile(xs, q)
