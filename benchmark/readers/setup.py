"""End-to-end reader: set-up time, from the start of the process to the
first tick of the measured window (JAX and the native engine, server boot,
the seed's payloads, the wait for a tick, the warm intervals)."""


def read(ctx: dict):
    return ctx["setup_s"]
