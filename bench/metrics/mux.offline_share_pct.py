"""Share of the window the multiplexer gave to offline steps: the sum of
their durations on its clock (the last clipped at the window's end) over
the window."""


def read(rec):
    if rec["offline_arch"] is None:
        return None
    w = rec["window_s"]
    busy = sum(max(0.0, min(end, w) - start)
               for kind, start, end, *_ in rec["timeline"] if kind == "offline")
    return 100.0 * busy / w
