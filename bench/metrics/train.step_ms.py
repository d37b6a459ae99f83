"""Mean host-clock time of one offline train step (batch made, step run,
loss read back), over the loop's offline steps."""


def read(rec):
    dts = [s["dt"] for s in rec["offline_steps"]]
    return 1e3 * sum(dts) / len(dts) if dts else None
