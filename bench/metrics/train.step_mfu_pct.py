"""Model FLOP utilisation of the offline step: forward and backward FLOPs
from shapes (bench/flops.py; recomputation not counted) over the device's
busy time inside traced train-step spans and the chip's bf16 peak."""
from bench import flops, trace_reduce


def read(rec):
    if rec["trace"] is None or rec["peak"] is None or not rec["offline_arch"]:
        return None
    pairs = trace_reduce.traced_steps(rec["trace"], "bench.train_step",
                                      rec["offline_steps"])
    f = flops.train_step(rec["offline_arch"], rec["offline_batch"],
                         rec["offline_seq"])
    need = len(pairs) * f / rec["peak"]["bf16_flops"]
    return flops.roofline_share(need, sum(busy for *_, busy in pairs))
