"""Mean host-clock time of one ServingEngine.step() to block_until_ready,
over the loop's online steps."""


def read(rec):
    dts = [s["dt"] for s in rec["online_steps"]]
    return 1e3 * sum(dts) / len(dts) if dts else None
