"""A cell at a size the CPU runs in seconds, for the harness's own tests:
the published configurations' shapes of layers, cut in width and depth."""
from __future__ import annotations

import copy

from bench import spec

SMALL_ARCH = {
    "danube-xlstm": dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                         head_dim=16, d_ff=128, vocab_size=256, window=16,
                         vocab_pad_multiple=16),
}
SMALL_OFFLINE = dict(num_layers=2, d_model=64, num_heads=4, head_dim=16,
                     vocab_size=256, vocab_pad_multiple=16, ssm_chunk=8)


def small_cell(config: str = "danube-xlstm", traffic: str = "steady",
               rate: float = 40.0) -> dict:
    """Configuration `config` under traffic `traffic` with its models at a
    CPU size, prompts of 4-12 tokens, 2-6 new tokens, and `rate` arrivals
    per second, as a cell of one chip that reports the steady cell's
    metrics."""
    base = copy.deepcopy(spec.cell("danube-xlstm.steady"))
    conf = spec.load_json(spec.BENCH / "configs" / f"{config}.json")
    mix = spec.load_json(spec.BENCH / "traffic" / f"{traffic}.json")
    cell = dict(base, name=f"{config}.{traffic}", config=config,
                traffic=traffic, config_data=conf, traffic_data=mix)
    conf["online"]["arch"].update(SMALL_ARCH[config])
    conf["offline"]["arch"].update(SMALL_OFFLINE)
    conf["offline"].update(batch=4, seq=16)
    mix.update(prompt_tokens=[4, 12], new_tokens=[2, 6])
    conf["online"]["engine"]["kv_capacity"] = 32
    conf["online"]["capacity_rps"] = rate / mix["rate_share_of_capacity"]
    conf["mux"].update(base_step_s=0.005, quantum_s=0.005,
                       offline_step_s=0.02, latency_budget_s=0.5,
                       quota_frac=0.9)
    conf["check"]["served_requests"] = 6
    conf["limits"] = dict(SMALL_LIMITS)
    return cell


# Limits at this size, on the numbers the configurations compare, set from
# its readings on the CPU (seeds 1-8): the program's served gap read
# 0-0.016 and the float8 control's 0.066-0.65; the head's first gradient
# read 0.0010-0.0045 on the program and 0.29-0.45 with half of the batch;
# the median leaf's change read 0.0004-0.026 on the program and 1 for a
# step that keeps its state.
SMALL_LIMITS = {"mux_accounting_faults": 0, "served_logit_gap": 0.04,
                "train_head_grad_gap": 0.05, "train_change_median": 0.3}
SMALL_SEED = 3
