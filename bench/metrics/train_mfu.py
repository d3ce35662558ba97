"""Model FLOP utilization of the training window: the operations the
model needs per token (``bench/cost.py``) times the tokens trained per
second over the traced window, over the chip's bf16 peak."""


def read(obs: dict):
    c = obs["counters"]
    peaks = obs.get("peaks")
    if not peaks or not c.get("tokens") or not obs.get("window_s"):
        return None
    return 100.0 * c["flops_per_token"] * c["tokens"] / obs["window_s"] \
        / peaks["bf16_flops_per_s"]
