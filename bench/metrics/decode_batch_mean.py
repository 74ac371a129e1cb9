"""Active slots per batched decode step in the window."""


def read(run):
    c = run.counters
    return c.decoded_slots / c.decode_steps if c.decode_steps else None
