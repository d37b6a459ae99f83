"""Share of the window the throttle held offline work back while no
request waited: the multiplexer's idle quanta on its clock (the last
clipped at the window's end) over the window.  This is the time the PID's
windup costs the offline job."""


def read(rec):
    if rec["offline_arch"] is None:
        return None
    w = rec["window_s"]
    idle = sum(max(0.0, min(end, w) - start)
               for kind, start, end, *_ in rec["timeline"] if kind == "idle")
    return 100.0 * idle / w
